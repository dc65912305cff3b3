"""End-to-end driver: continuous-batching CHAI serving under Poisson load.

Trains a small model on the synthetic corpus (so generations are
meaningful), then serves the SAME Poisson-arrival workload (exponential
inter-arrival gaps, mixed output lengths) through the slot-level
continuous-batching engine (per-slot CHAI phase machine, slots
admitted/retired independently) with CHAI on and off, reporting
per-request TTFT and request throughput for each, plus the CHAI-vs-MHA
comparison (KV bytes, greedy-token agreement).

  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import numpy as np

from repro.configs.base import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.workload import poisson_workload
from repro.train.trainer import Trainer, TrainerConfig


def make_workload(pipe, *, n_req=12, prompt_len=24, mean_gap_s=0.02,
                  new_tokens=(8, 128), seed=0):
    """(arrival_delay, prompt, max_new) tuples — the shared mixed-length
    Poisson distribution (repro.serving.workload) with prompts from the
    synthetic corpus."""
    rng = np.random.default_rng(seed)
    arrivals, lens = poisson_workload(rng, n_req, mean_gap_s=mean_gap_s,
                                      new_tokens=new_tokens)
    return [(float(arrivals[i]),
             pipe.batch(2000 + i)["tokens"][0, :prompt_len],
             int(lens[i]))
            for i in range(n_req)]


def serve(cfg, params, workload, *, use_chai, slots=6, max_seq=192):
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=max_seq,
                                     use_chai=use_chai))
    # Two identical passes: the first warms every jit so the reported
    # numbers reflect steady-state serving, not XLA compile time.
    for _ in (0, 1):
        t0 = time.time()
        batch = [eng.submit(prompt, max_new_tokens=max_new, uid=i,
                            arrival_delay=delay)
                 for i, (delay, prompt, max_new) in enumerate(workload)]
        steps0 = eng.steps_executed
        eng.run()
        wall = time.time() - t0
    ttfts = np.array([r.ttft for r in batch])
    n_tok = sum(len(r.generated) for r in batch)
    span = max(r.t_done for r in batch) - min(r.t_arrival for r in batch)
    return {
        "gen": {r.uid: r.generated for r in batch},
        "wall_s": wall,
        "req_per_s": len(batch) / span,
        "tok_per_s": n_tok / wall,
        "ttft_ms_mean": 1e3 * float(ttfts.mean()),
        "ttft_ms_p95": 1e3 * float(np.percentile(ttfts, 95)),
        # the engine drains its pools on retire, so the footprint is the
        # run's high-water allocated-page bytes
        "kv_bytes": int(eng.kv_bytes_peak()),
        "kv_steady": int(eng.kv_bytes(chai=eng.chai_on)),   # analytic
        "decode_steps": eng.steps_executed - steps0,
    }


def main():
    cfg = reduced(get_config("chai-llama-7b"), n_layers=2, d_model=64,
                  n_heads=8, d_ff=128, vocab=256).replace(dtype="float32")
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
    print("training a small LM on the synthetic corpus ...")
    tr = Trainer(cfg, data, TrainerConfig(
        total_steps=80, ckpt_every=10**9, log_every=40,
        ckpt_dir="/tmp/serve_batched_ckpt",
        lr_kw=dict(peak=3e-3, warmup=8, total=80)))
    state, metrics = tr.run()
    params = state["params"]
    cfg_chai = cfg.with_chai(enabled=True,
                             cluster_counts=(5,) * cfg.n_attn_layers)
    workload = make_workload(tr.pipe)

    print("\nserving the Poisson workload: CHAI on ...")
    cont = serve(cfg_chai, params, workload, use_chai=True)
    print("serving the Poisson workload: CHAI off ...")
    mha = serve(cfg, params, workload, use_chai=False)

    keys = ("wall_s", "req_per_s", "tok_per_s", "ttft_ms_mean",
            "ttft_ms_p95", "kv_bytes")
    print(f"\n{'':14}{'CHAI':>12}{'MHA':>12}")
    for key in keys:
        print(f"{key:14}{cont[key]:>12.2f}{mha[key]:>12.2f}")

    agree_chai = np.mean([np.mean(np.asarray(cont["gen"][u]) ==
                                  np.asarray(mha["gen"][u]))
                          for u in cont["gen"]])
    print(f"\ngreedy-token agreement CHAI vs MHA:  {agree_chai:.1%}")
    # steady-state analytic saving; the engine's paged pools realize it
    # at the allocator level too (kv_bytes row = peak allocated-page
    # bytes, which drops as dense pages free at compaction)
    print(f"KV saving (CHAI vs MHA, steady):     "
          f"{1 - cont['kv_steady'] / mha['kv_steady']:.1%}")


if __name__ == "__main__":
    main()
