"""Device time per batched decode-step program, in ms: the mean duration
of the decode programs (XLA modules named after the jitted steps) in the
traced window."""
from bench import trace_reduce

DECODE_PROGRAMS = ("jit_mixed_step", "jit_serve_step", "jit_relay_step")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.modules:
        return None
    n = trace_reduce.count_of(tr, DECODE_PROGRAMS)
    if n == 0:
        return None
    return trace_reduce.time_of(tr, DECODE_PROGRAMS, "modules") * 1e3 / n
