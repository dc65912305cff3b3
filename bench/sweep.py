#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, by a sweep on
the chip. Run by hand, once, when a cell is defined; the cell then runs at
a fixed rate (about 0.8 x the highest sustained one) written into its
traffic file.

    python3 bench/sweep.py --workload nemotron15b.chat-poisson --seed 7 \\
        --seconds 30 --rates 1,2,3,4

One process and one engine serve every rate in turn, lowest first. For
each rate it prints the requests sent and finished, output tokens/s, TTFT
p50/p95 in the first and second half of the window, and the backlog left
at the end. A rate is sustained when the backlog stays within the batch
and TTFT does not grow from the first half to the second.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import run as bench_run
    from bench import spec as spec_mod
    from bench import traffic_gen
    from bench.driver import Driver

    spec = spec_mod.resolve(args.workload, spec_mod.load_benchmark(ROOT))
    if spec["traffic"]["loop"] != "open":
        bench_run.fail("a rate sweep needs an open-loop cell")
    _, dev = bench_run.device_info(False, int(spec["cell"]["chips"]))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg, ecfg, params, traffic, Engine = bench_run.build(spec, args.seed,
                                                         False)
    eng = Engine(cfg, params, ecfg)
    drv = Driver(eng, traffic)
    drv.warm_shapes(traffic.prompt_lengths())
    drv.warm_decode()
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(spec["traffic"], rate_rps=rate)
        drv.traffic = traffic_gen.Traffic(mix, args.seed, cfg.vocab_size,
                                          ecfg.batch_slots,
                                          scale=traffic.scale)
        t0 = time.perf_counter()
        t1 = t0 + args.seconds
        drv.open_schedule(t0, args.seconds)
        drv.run(t1)
        half = (t0 + t1) / 2
        first = drv.window_stats(t0, half)["ttft"]
        second = drv.window_stats(half, t1)["ttft"]
        ws = drv.window_stats(t0, t1)
        sent = [r for r in drv.recs.values() if t0 <= r.t0 < t1]
        done = [r for r in sent if r.done is not None]
        row = {"rate_rps": rate, "sent": len(sent), "finished": len(done),
               "out_tok_s": ws["tokens"] / args.seconds,
               "ttft_p50_ms_1st": _q(first, 50), "ttft_p95_ms_1st":
               _q(first, 95), "ttft_p50_ms_2nd": _q(second, 50),
               "ttft_p95_ms_2nd": _q(second, 95),
               "queued_at_end": len(eng.queue)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # Clear what is left before the next rate.
        drv._pending = []
        for r in sent:
            eng.abort(r.uid)
        while eng.has_work():
            drv.step()
    print(json.dumps({"sweep": rows, "device": dev.device_kind}))
    return 0


def _q(xs, pct):
    if len(xs) < 2:
        return None
    return statistics.quantiles(xs, n=100, method="inclusive")[pct - 1] * 1e3


if __name__ == "__main__":
    sys.exit(main())
