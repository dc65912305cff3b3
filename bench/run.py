#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix. The
run draws the weights on the device from the seed, builds the program's
serving engine, warms every shape the traffic uses (prefill buckets, the
decode programs, CLUSTER), fills the slots, and then measures for
``--seconds``. With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` it takes a profiler trace of the last part of the
window and reports the per-layer metrics and a breakdown. Both then check
the served tokens against the plain reference (``bench/check.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with
the numbers compared and their limits under ``check``, which comes last.
A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.

``--rehearse`` runs the same control flow on the CPU at tiny widths and
lengths (interpret-mode kernels) to check it end to end. Its numbers are
not measurements of anything.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_S = 3.0           # traced part at the end of a --trace 1 window
OPEN_WARM_S = 10.0      # open loop: arrivals served before the window
REHEARSE_STEPS_PER_S = 40


def fail(msg, code=2):
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def p95(xs):
    """95th percentile (linear interpolation between order statistics)."""
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    return statistics.quantiles(xs, n=100, method="inclusive")[94]


def parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; not a measurement")
    ap.add_argument("--control", action="store_true",
                    help="compare the control (the reference one precision "
                         "step lower) in the program's place")
    return ap.parse_args(argv)


def device_info(rehearse, chips):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as err:
        fail(f"JAX found no device: {err}")
    dev = devs[0]
    if not rehearse and dev.platform != "tpu":
        fail(f"no TPU found (first device is {dev.platform!r}); the "
             f"benchmark runs on the chip only")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs, dev


def build(spec, seed, rehearse):
    from bench import model_config as mc
    from bench import traffic_gen, weights
    from repro.models import transformer as tfm
    from repro.serving.engine import ServingEngine
    conf = spec["config"]
    cfg = mc.model_config(conf)
    slots = conf["engine"]["batch_slots"]
    scale = 1.0
    override = {}
    if rehearse:
        cfg = mc.rehearsal_sizes(cfg)
        slots, scale = 4, 1.0 / 32
    traffic = traffic_gen.Traffic(spec["traffic"], seed, cfg.vocab_size,
                                  slots, scale=scale)
    if rehearse:
        p, o = traffic.max_lengths()
        override = {"batch_slots": slots, "num_pages": 0,
                    "num_chai_pages": 0,
                    "max_seq": -(-(p + o) // 16) * 16}
    ecfg = mc.engine_config(conf, **override)
    t0 = time.perf_counter()
    params = weights.draw(tfm.param_structs(cfg)[0], seed)
    import jax
    jax.block_until_ready(params)
    print(f"[bench] weights drawn in {time.perf_counter() - t0:.2f}s",
          file=sys.stderr)
    return cfg, ecfg, params, traffic, ServingEngine


def sizes_of(cfg, conf, max_seq):
    """The plain sizes and CHAI settings the reference reads: the sizes
    from the model the configuration builds, the CHAI settings from the
    configuration file."""
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "n_experts", "n_shared_experts", "top_k",
            "moe_d_ff", "activation", "gated_mlp", "norm_eps", "rope_theta")
    out = {k: getattr(cfg, k) for k in keys}
    out["ffn_types"] = list(cfg.ffn_types)
    chai = conf["model"].get("chai", {})
    on = chai.get("enabled", False)
    out["warmup"] = chai["warmup_tokens"] if on else 10 ** 9
    out["feature_window"] = min(chai.get("feature_window", 1), max_seq)
    out["kmeans_iters"] = chai.get("kmeans_iters", 0)
    out["k_max"] = cfg.k_max if on else 0
    return out


def main(argv=None):
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not (ROOT / "src" / "repro").is_dir():
        fail("the program (src/repro) is not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import spec as spec_mod
    try:
        spec = spec_mod.resolve(args.workload, spec_mod.load_benchmark(ROOT))
    except spec_mod.SpecError as err:
        fail(str(err))
    cell = spec["cell"]
    devs, dev = device_info(args.rehearse, int(cell["chips"]))

    import jax
    from bench import check, peaks
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.__setitem__(
            0, compiles[0] + (ev == "/jax/core/compile/backend_compile_duration")))
    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        print(f"[bench] compile cache {cache_dir}", file=sys.stderr)
        peak = peaks.peaks(dev.device_kind)
    else:
        peak = None

    from bench.driver import Driver
    cfg, ecfg, params, traffic, Engine = build(spec, args.seed,
                                               args.rehearse)
    tel = "basic" if args.trace else "off"
    eng = Engine(cfg, params, dataclasses.replace(ecfg, telemetry=tel))
    drv = Driver(eng, traffic, annotate=bool(args.trace))
    print(f"[bench] {args.workload}: {cfg.name} slots={ecfg.batch_slots} "
          f"max_seq={ecfg.max_seq} pages={eng.dense_pool.capacity}/"
          f"{eng.chai_pool.capacity if eng.chai_pool else 0} "
          f"loop={traffic.loop}", file=sys.stderr)

    # -- set-up: compile and warm every shape, fill the slots --------------
    drv.warm_shapes(traffic.prompt_lengths())
    drv.warm_decode()
    drv.warm_relay()
    seconds = float(args.seconds)
    if traffic.loop == "closed":
        drv.closed_fill()
        drv.until_steady()
    else:
        warm = OPEN_WARM_S if not args.rehearse else 1.0
        drv.open_schedule(time.perf_counter(), warm + seconds)
        drv.run(time.perf_counter() + warm)
    stats0 = _counters(eng)
    compiles0 = compiles[0]
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    t1 = t0 + seconds
    print(f"[bench] set-up {setup_s:.2f}s, window {seconds:g}s",
          file=sys.stderr)

    # -- the measured window -----------------------------------------------
    trace = None
    if args.trace:
        trace_s = min(TRACE_S, seconds)
        drv.run(t1 - trace_s)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        drv.step_log = []
        jax.profiler.start_trace(trace_dir)
        drv.trace_t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            drv.run(t1)
        jax.profiler.stop_trace()
    elif args.rehearse and traffic.loop == "closed":
        # A rehearsal's window is a fixed number of steps, so its sample of
        # finished requests does not depend on how busy the CPU is.
        drv.run_steps(int(seconds * REHEARSE_STEPS_PER_S))
        t1 = time.perf_counter()
    else:
        drv.run(t1)
    stats1 = _counters(eng)
    print(f"[bench] programs compiled in the window: "
          f"{compiles[0] - compiles0} (before it: {compiles0})",
          file=sys.stderr)
    ws = drv.window_stats(t0, t1)
    mem = dev.memory_stats() or {}
    mem_peak = int(mem.get("peak_bytes_in_use", 0))
    if args.trace:
        from bench import trace_reduce
        path = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.load_xplane(path)
        shutil.rmtree(trace_dir, ignore_errors=True)

    sent = [r for r in drv.recs.values()
            if not r.warm and t0 <= r.t0 < t1]
    failed = sum(1 for r in drv.recs.values() if not r.warm
                 and r.req.finish_reason == "error")
    if drv.late_s:
        late = sorted(drv.late_s)
        print(f"[bench] generator lateness: median "
              f"{statistics.median(late) * 1e3:.3f} ms, max "
              f"{late[-1] * 1e3:.3f} ms over {len(late)} sends",
              file=sys.stderr)
    steps_in = [k for t, k in drv.step_kind.items() if t0 <= t < t1]
    for kind in sorted(set(steps_in)):
        gaps = [g for g, k in zip(ws["itl"], ws["itl_kind"]) if k == kind]
        print(f"[bench] steps {kind}: {steps_in.count(kind)} steps, "
              f"{len(gaps)} gaps, gap p50 {_ms(_q(gaps, 50))} ms, p95 "
              f"{_ms(p95(gaps))} ms", file=sys.stderr)
    print(f"[bench] window: {ws['tokens']} tokens, {ws['first_tokens']} "
          f"first tokens, {len(ws['itl'])} gaps, {len(sent)} requests sent,"
          f" {drv.steps} steps in all", file=sys.stderr)

    ctx = {"trace": trace, "driver": drv, "eng": eng, "cfg": cfg,
           "ecfg": ecfg, "peaks": peak, "window": (t0, t1),
           "stats": (stats0, stats1), "window_stats": ws}
    metrics = {}
    if args.trace:
        for m in spec["per_layer"]:
            val = spec["readers"][m["name"]].read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "ttft_p95_ms": _ms(p95(ws["ttft"])),
               "itl_p95_ms": _ms(p95(ws["itl"])),
               "out_tok_s": ws["tokens"] / seconds,
               "hbm_peak_gib": mem_peak / 2 ** 30}
        for m in spec["end_to_end"]:
            val = e2e.get(m["name"])
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace is not None:
        from bench import trace_reduce
        device["busy_s"] = trace_reduce.device_busy_s(trace)
        device["window_s"] = trace.window_s
        breakdown = {"device_ops": trace_reduce.top_ops(trace),
                     "idle_gaps": trace_reduce.idle_by_host(trace)}
        for name, secs in trace_reduce.top_ops(trace, kind="modules"):
            n = trace_reduce.count_of(trace, [name])
            print(f"[bench] program {name}: {secs:.6f} s over {n} calls",
                  file=sys.stderr)

    # -- correct: served tokens against the plain reference ----------------
    conf = spec["config"]
    finished = [{"uid": r.uid, "prompt": r.req.prompt,
                 "generated": list(r.req.generated),
                 "membership": r.membership}
                for r in drv.finished_in(t0, t1)
                if r.req.finish_reason in ("length", "stop")]
    picked = check.sample(finished, args.seed,
                          int(conf["check"]["sample_requests"]))
    for r in picked:
        if r["membership"] is not None:
            r["membership"] = {k: np.asarray(v)
                               for k, v in r["membership"].items()}
    sizes = sizes_of(cfg, conf, ecfg.max_seq)
    del ctx, drv, eng, trace
    gc.collect()
    reference = spec_mod.load_module("reference", conf["reference"])
    tr0 = time.perf_counter()
    results = check.compare(reference, params, sizes, picked,
                            control=args.control)
    n_cmp = sum(len(r["gap"]) for r in results)
    lim = conf["check"]
    gap_key, cost_key = "gap", "cost"
    if args.control:
        gap_key, cost_key = "gap_control", "cost_control"
    for label, key in (("served", "gap"), ("control", "gap_control")):
        allg = [r[key] for r in results if r.get(key) is not None]
        if allg:
            g = np.concatenate(allg)
            print(f"[bench] {label} gaps over {g.size} tokens: max "
                  f"{g.max():.6g}, mean {g.mean():.6g}, p99 "
                  f"{np.quantile(g, 0.99):.6g}, share not the reference's "
                  f"best {np.mean(g > 0):.6g}", file=sys.stderr)
    for label, key in (("run", "cost"), ("control", "cost_control")):
        allc = [r[key] for r in results if r.get(key) is not None]
        if allc:
            c = np.stack(allc)
            print(f"[bench] {label} membership cost ratio per request x "
                  f"layer: {np.round(c.astype(float), 4).tolist()}, largest "
                  f"{c.max():.6g}", file=sys.stderr)
    agree = [r["agree"] for r in results if "agree" in r]
    if agree:
        print(f"[bench] run's representatives equal to the reference's: "
              f"{np.round(agree, 4).tolist()}", file=sys.stderr)
    gaps = [r[gap_key] for r in results if len(r[gap_key])]
    gap_mean = (float(np.concatenate(gaps).mean()) if gaps
                else float("inf"))
    costs = [r[cost_key] for r in results if r.get(cost_key) is not None]
    excess = (float(np.concatenate([np.ravel(c) for c in costs]).mean())
              - 1.0 if costs else 0.0)
    bad_mem = sum(check.membership_faults(r["membership"]) for r in picked)
    limit = float(lim["rehearse_gap_mean_limit" if args.rehearse
                      else "gap_mean_limit"])
    numbers = {"gap_mean": {"value": gap_mean, "limit": limit},
               "membership_excess": {"value": excess,
                                     "limit": float(lim["excess_limit"])},
               "membership_faults": {"value": bad_mem, "limit": 0}}
    correct = (all(n["value"] <= n["limit"] for n in numbers.values())
               and n_cmp >= 1 and failed == 0)
    print(f"[bench] reference over {len(picked)} requests "
          f"({n_cmp} served tokens) in {time.perf_counter() - tr0:.2f}s",
          file=sys.stderr)

    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.rehearse:
        out["rehearsal"] = "CPU rehearsal at tiny sizes: not a measurement"
    out["check"] = numbers
    for name, n in numbers.items():
        print(f"check {name}: {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def _q(xs, pct):
    if len(xs) < 2:
        return xs[0] if xs else None
    return statistics.quantiles(xs, n=100, method="inclusive")[pct - 1]


def _ms(x):
    return None if x is None else x * 1e3


def _counters(eng):
    """Program counters read around the window."""
    out = {"steps_executed": eng.steps_executed,
           "cluster_transitions": eng.cluster_transitions}
    if eng.prefix_cache is not None:
        out.update({f"prefix.{k}": v for k, v in
                    eng.prefix_cache.stats.items()})
    return out


if __name__ == "__main__":
    sys.exit(main())
