"""Device time of the prefill programs in the traced window, in ms per
thousand prompt tokens prefilled there."""
from bench import trace_reduce

PREFILL_PROGRAMS = ("jit_slot_prefill", "jit_suffix_prefill",
                    "jit_chunk_prefill")


def read(ctx):
    tr, drv = ctx["trace"], ctx["driver"]
    if tr is None or not tr.modules or drv.trace_t0 is None:
        return None
    t_start = drv.trace_t0
    toks = sum(max(rec.req.prefill_tokens, 0)
               for rec in drv.recs.values()
               if not rec.warm and rec.times and rec.times[0] >= t_start)
    secs = trace_reduce.time_of(tr, PREFILL_PROGRAMS, "modules")
    if toks <= 0 or secs <= 0:
        return None
    return secs * 1e3 / (toks / 1000.0)

