"""Device idle time per traced engine step while the host schedules, in ms:
idle intervals inside the benchmark's ``bench.step`` whose innermost engine
span is a scheduling stage (chunked prefill, admission, CLUSTER
transitions, relay formation, retirement, KV accounting, the invariant
audit; ``_spans.SCHEDULE``), on the trace's clock (``_spans``)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.idle_ms_per_step(ctx, _spans.SCHEDULE)
