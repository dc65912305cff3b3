"""Share of its roofline that clustered decode attention reaches, in %.

The least time is max(FLOPs / peak FLOP/s, bytes / HBM bytes/s) of the
decode attention the STEADY slots of each traced step need (``_cost``:
the representatives' K rows, every head's V rows, q and out, at each
slot's cached length; shared prefix pages once). The kernel time is the
device time of the decode attention kernels' calls inside the decode
programs: a trace names each call after the jitted function that holds it
(``kernels/ops.py``), listed below. Which bound binds is printed on
stderr."""
import sys

from bench import trace_reduce
from bench.metrics import _cost
from bench.metrics.decode_step_ms import DECODE_PROGRAMS

KERNELS = ("paged_chai_decode_attention", "relay_prefix_attention")


def read(ctx):
    tr, drv, peaks = ctx["trace"], ctx["driver"], ctx["peaks"]
    if tr is None or not tr.ops or not drv.step_log or peaks is None:
        return None
    ksec = trace_reduce.ops_within(tr, KERNELS, DECODE_PROGRAMS)
    if ksec <= 0:
        return None
    flops = byts = 0.0
    for _, rows in drv.step_log:
        f, b = _cost.decode_attn(ctx["cfg"], rows)
        flops += f
        byts += b
    t_flop = flops / peaks["bf16_flops"]
    t_byte = byts / peaks["hbm_bytes_per_s"]
    bound = "HBM bandwidth" if t_byte >= t_flop else "bf16 FLOP/s"
    print(f"[bench] decode_attn_roofline: {flops:.4g} FLOP, {byts:.4g} B, "
          f"kernel {ksec:.6f}s, bound by {bound}", file=sys.stderr)
    if max(t_flop, t_byte) <= 0:
        return None
    return 100.0 * max(t_flop, t_byte) / ksec
