"""Device idle time per traced engine step while the host samples, in ms:
idle intervals inside ``bench.step`` whose innermost engine span is
``sample`` (the non-finite-row and token read-backs and the sampler's
dispatch; ``_spans``)."""
from bench.metrics import _spans


def read(ctx):
    return _spans.idle_ms_per_step(ctx, _spans.SAMPLE)
