"""Model FLOP/s utilisation of the decode steps, in %: the model flops of
the traced window's batched decode steps (``_cost.decode_step_flops``:
matmuls at the active expert count and the output head for every
decoding slot, attention at each slot's length) over (the device time of
the decode programs x peak bf16 FLOP/s)."""
from bench import trace_reduce
from bench.metrics import _cost
from bench.metrics.decode_step_ms import DECODE_PROGRAMS


def read(ctx):
    tr, drv, peaks = ctx["trace"], ctx["driver"], ctx["peaks"]
    if tr is None or not tr.modules or not drv.step_log or peaks is None:
        return None
    secs = trace_reduce.time_of(tr, DECODE_PROGRAMS, "modules")
    flops = sum(_cost.decode_step_flops(ctx["cfg"], rows)
                for _, rows in drv.step_log if rows)
    if secs <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (secs * peaks["bf16_flops"])
