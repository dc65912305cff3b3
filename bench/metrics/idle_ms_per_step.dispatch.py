"""Device idle time per traced engine step while the host prepares and
dispatches the decode program, in ms: idle intervals inside ``bench.step``
whose innermost engine span is ``decode.dispatch`` (``_spans``)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.idle_ms_per_step(ctx, _spans.DISPATCH)
