"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device-op intervals / window)."""
from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    return 100.0 * trace_reduce.idle_share(tr)
