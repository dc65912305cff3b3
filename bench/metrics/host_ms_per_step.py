"""Host time per engine step, in ms: the span of the benchmark's
annotation around ``eng.step()`` minus the device busy time inside it,
averaged over the steps of the traced window."""
import statistics

from bench import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.ops:
        return None
    own = trace_reduce.host_self_ms(tr, "bench.step")
    return statistics.fmean(own) if own else None
