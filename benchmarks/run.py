"""Benchmark harness: one bench per paper table/figure.

  python -m benchmarks.run            # all benches
  python -m benchmarks.run --only bench_kv_memory,bench_flops
  python -m benchmarks.run --list     # available bench names

Each bench saves JSON under benchmarks/results/ and returns a dict with a
``claim_check`` section verifying the paper's claims (or their CPU-proxy
analogues — labeled). The end-of-run summary is printed AND written to
benchmarks/results/summary.json (CI uploads results/*.json as artifacts).
Exit code is non-zero if any claim check fails.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "bench_accuracy_proxy": "Tables 1-3 (greedy agreement, logit fidelity)",
    "bench_qkv_ablation": "Table 4 (CHAI-QKV share_values ablation)",
    "bench_flops": "Figs 1/14 (attention FLOP ratios)",
    "bench_elbow": "Fig 8 (per-layer elbow cluster counts)",
    "bench_membership": "Fig 9 (membership churn)",
    "bench_kv_memory": "Fig 11 + paged-allocator lane",
    "bench_latency": "Fig 12 + scheduler / fused-kernel / prefix_reuse "
                     "lanes",
    "bench_cluster_dist": "Fig 13 (cluster size distribution)",
    "bench_fault_soak": "robustness lane (seeded fault soak, deep audit)",
    "bench_telemetry_overhead": "observability lane (tier cost contract, "
                                "trace/metrics export round-trips)",
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print available bench names and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, desc in BENCHES.items():
            print(f"{name:24s} {desc}")
        return 0
    names = args.only.split(",") if args.only else list(BENCHES)
    enable_compile_cache()

    failures, summaries = [], {}
    for name in names:
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            result = mod.run()
            checks = result.get("claim_check", {})
            bad = {k: v for k, v in checks.items()
                   if isinstance(v, bool) and not v}
            status = "ok" if not bad else f"CLAIM-FAIL {sorted(bad)}"
            if bad:
                failures.append(name)
            summaries[name] = {"status": status, "checks": checks,
                               "seconds": round(time.time() - t0, 1)}
            print(f"  {status} ({summaries[name]['seconds']}s)")
            for k, v in checks.items():
                print(f"    {k}: {v}")
        except Exception as e:
            failures.append(name)
            summaries[name] = {"status": f"ERROR {e}"}
            traceback.print_exc()
    print("\n=== summary ===")
    print(json.dumps(summaries, indent=1, default=str))
    results_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(results_dir, exist_ok=True)
    summary_path = os.path.join(results_dir, "summary.json")
    # A partial (--only) run MERGES into the existing summary so it
    # cannot silently drop the other benches' recorded claim checks;
    # a full run replaces it. Exit code reflects THIS run only.
    if args.only and os.path.exists(summary_path):
        try:
            with open(summary_path) as f:
                merged = json.load(f).get("benches", {})
        except (json.JSONDecodeError, OSError):
            merged = {}
        merged.update(summaries)
        summaries = merged
    # The artifact's failures field must describe EVERY recorded entry
    # (merged ones included), not just this invocation's.
    all_failures = sorted(n for n, s in summaries.items()
                          if not str(s.get("status", "")).startswith("ok"))
    with open(summary_path, "w") as f:
        json.dump({"benches": summaries, "failures": all_failures},
                  f, indent=1, default=str)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
