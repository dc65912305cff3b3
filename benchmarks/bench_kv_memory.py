"""Paper Fig 11: K,V-cache memory, MHA vs CHAI, across sequence lengths.

Three lanes:
  1. **Analytic** — exact steady-state bytes for the full LLaMA-7B config
     (the paper's model) and every assigned MHA-regime arch. The paper's
     21.4% saving comes from dropping non-representative K rows; V is
     kept (Table 4).
  2. **Paged allocator** — the continuous-batching engine on a tiny MHA
     model: resident (allocated-page) bytes sampled across PREFILL ->
     WARMUP -> CLUSTER -> STEADY. The claim check asserts the saving is
     *realized by the allocator*: steady-state paged-CHAI bytes fall
     below the dense-MHA rectangle (every slot's K and V for all of
     ``max_seq``).
  3. **Tier transitions** — a prefix-family workload past device
     capacity on three engines: A (pressured + host offload), B
     (pressured, HBM-only), C (unpressured reference). Claims: (a)
     demoted-then-promoted requests in A emit bitwise-identical greedy
     tokens vs the all-HBM run C, with at least one host->hot
     promotion; (b) A's effective prefix-cache hit tokens exceed the
     HBM-only baseline B under the same pressure (the host tier turns
     evictions into reuse). The hot/host/compressed byte trajectory
     comes from ``kv_bytes_history``; a tiny-host variant exercises the
     int4 compressed rung."""
from __future__ import annotations

import numpy as np

import jax

from benchmarks.common import save_result
from repro.configs.base import get_config, list_configs, reduced
from repro.core.cache import kv_cache_bytes
from repro.models import transformer as tfm
from repro.serving import invariants
from repro.serving.engine import EngineConfig, EngineCore, ServingEngine
from repro.serving.sampling import SamplingParams


def _paged_allocator_lane(slots=2, max_seq=64, page_size=16, n_req=4):
    """PREFILL->STEADY allocated-bytes trajectory of the paged engine."""
    cfg = reduced(get_config("chai-llama-7b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    cfg = cfg.with_chai(enabled=True, warmup_tokens=3)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=max_seq,
                                     page_size=page_size))
    rng = np.random.default_rng(0)
    for i in range(n_req):
        eng.submit(rng.integers(0, cfg.vocab_size, size=8),
                   max_new_tokens=24, uid=i)
    eng.run()
    hist = eng.kv_bytes_history
    dense_mha = kv_cache_bytes(cfg, slots, max_seq, chai=False)
    # steady state = every occupied slot past CLUSTER (no warmup slot is
    # holding dense K pages); churn steps with a fresh WARMUP admission
    # are transient and excluded. No steady sample means the workload
    # never exercised the saving — fail loudly rather than report a
    # vacuous (drained-engine) number.
    steady = [h for h in hist
              if h.get("n_warmup") == 0 and h.get("n_steady", 0) > 0]
    if not steady:
        raise RuntimeError(
            "paged allocator lane produced no steady-state sample "
            f"(warmup_tokens={cfg.chai.warmup_tokens}, history={hist}); "
            "the claim check would be vacuous")
    steady_bytes = max(h["kv_bytes"] for h in steady)
    return {
        "note": "allocated-page bytes from the serving engine's PagePool "
                "accounting (tiny model; layout-level numbers, not "
                "hardware-level)",
        "workload": {"slots": slots, "max_seq": max_seq,
                     "page_size": page_size, "n_req": n_req,
                     "prompt_len": 8, "max_new": 24},
        "timeline": hist,
        "peak_bytes": eng.kv_bytes_peak(),
        "steady_chai_bytes": steady_bytes,
        "dense_mha_bytes": dense_mha,
        "paged_steady_saving_vs_dense_mha":
            1 - steady_bytes / dense_mha,
    }


def _tier_lane(page_size=8, num_pages=12):
    """Hierarchical KV tiers under device pressure: demote / promote
    round trips, reuse uplift vs an HBM-only pool, byte trajectory."""
    cfg = reduced(get_config("chai-llama-7b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    cfg = cfg.with_chai(enabled=True, warmup_tokens=3)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    # Prefix family past device capacity + extensions that route later
    # matches through the (by then demoted/evicted) suffix leaves.
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, 64, size=2 * page_size).tolist()
    base = [prefix + rng.integers(1, 64, size=page_size).tolist()
            for _ in range(4)]
    ext = [p + rng.integers(1, 64, size=page_size).tolist()
           for p in base[:2]]
    workload = base + ext

    def run_engine(**kw):
        core = EngineCore(cfg, params,
                          EngineConfig(batch_slots=1, max_seq=64,
                                       page_size=page_size,
                                       prefix_cache=True, **kw))
        toks = {}
        for p in workload:
            r = core.add_request(list(p), SamplingParams(max_new_tokens=8))
            while core.has_work():   # serialize: maximal reuse per prompt
                core.step()
            assert r.finish_reason == "length", r.finish_reason
            toks[r.uid] = list(r.generated)
        return core, toks

    pressured = dict(num_pages=num_pages)
    a, a_toks = run_engine(kv_offload=True, host_pages=64,
                           tier_prefetch=False, **pressured)
    b, b_toks = run_engine(**pressured)            # HBM-only baseline
    c, c_toks = run_engine()                       # unpressured reference
    # Tiny host pool: demotions overflow onto the int4 compressed rung.
    comp, _ = run_engine(kv_offload=True, host_pages=2,
                         compressed_pages=32, tier_prefetch=False,
                         **pressured)

    a_stats, b_stats = a.prefix_stats(), b.prefix_stats()
    transitions = a.tier_stats()["transitions"]
    trajectory = [{k: h.get(k, 0) for k in
                   ("step", "kv_bytes", "host_bytes", "compressed_bytes")}
                  for h in a.kv_bytes_history]
    comp_traj = [h.get("compressed_bytes", 0)
                 for h in comp.kv_bytes_history]
    return {
        "note": "tiny-model tier ladder; byte numbers are layout-level "
                "(PagePool accounting), not hardware-level",
        "workload": {"prompts": len(workload), "page_size": page_size,
                     "device_pages": num_pages, "prompt_blocks": "3-4"},
        "trajectory": trajectory,
        "transitions": transitions,
        "offload": {"demoted_blocks": a_stats["demoted_blocks"],
                    "promoted_blocks": a_stats["promoted_blocks"],
                    "demoted_snapshots": a_stats["demoted_snapshots"],
                    "tokens_reused": a_stats["tokens_reused"]},
        "hbm_only": {"evicted_blocks": b_stats["evicted_blocks"],
                     "tokens_reused": b_stats["tokens_reused"]},
        "compressed_peak_bytes": max(comp_traj, default=0),
        "claims": {
            # (a) demoted-then-promoted requests replay bitwise
            "promoted_bitwise_vs_all_hbm":
                a_toks == c_toks
                and transitions.get("host->hot/dense", 0) > 0,
            # (b) the host tier turns evictions into cache hits
            "reuse_tokens_above_hbm_only":
                a_stats["tokens_reused"] > b_stats["tokens_reused"],
            "compressed_tier_exercised": max(comp_traj, default=0) > 0,
            "leak_free_after_drain":
                invariants.audit_leaks(a) == []
                and invariants.audit_leaks(comp) == [],
        },
    }


def run():
    seqs = [256, 512, 1024, 2048, 4096]
    per_arch = {}
    for arch in list_configs():
        cfg = get_config(arch)
        if cfg.n_attn_layers == 0 or not cfg.is_mha:
            continue                      # GQA/SSM: no K-cache saving
        rows = {}
        for s in seqs:
            full = kv_cache_bytes(cfg, 1, s, chai=False)
            ch = kv_cache_bytes(cfg, 1, s, chai=True)
            rows[str(s)] = {"mha_bytes": full, "chai_bytes": ch,
                            "saving_frac": 1 - ch / full}
        per_arch[arch] = rows

    paged = _paged_allocator_lane()
    tiers = _tier_lane()
    llama = per_arch["chai-llama-7b"]["2048"]
    result = {
        "note": "exact analytic bytes; MHA-regime archs only (GQA archs "
                "get compute-only wins, DESIGN.md §4)",
        "per_arch": per_arch,
        "paged_allocator": paged,
        "kv_tiers": tiers,
        "paper_claim": "LLaMA-7B seq 2048: ~1.2 GB KV cache, up to 21.4% "
                       "saving",
        "claim_check": {
            "llama_kv_GB_at_2048": llama["mha_bytes"] / 2**30,
            "llama_saving_frac": llama["saving_frac"],
            "saving_in_paper_range": 0.10 <= llama["saving_frac"] <= 0.30,
            "kv_close_to_1.2GB": 0.8 <= llama["mha_bytes"] / 2**30 <= 1.6,
            # the tentpole: the allocator (not just the formula) realizes
            # the saving — steady paged-CHAI below the dense-MHA rectangle
            "paged_steady_below_dense_mha":
                paged["steady_chai_bytes"] < paged["dense_mha_bytes"],
            "compaction_frees_pages":
                paged["steady_chai_bytes"] < paged["peak_bytes"],
            # the tier ladder: bitwise promotion, reuse uplift vs an
            # HBM-only pool, the int4 rung exercised, zero leaks
            **{f"tier_{k}": v for k, v in tiers["claims"].items()},
        },
    }
    save_result("bench_kv_memory", result)
    return result


if __name__ == "__main__":
    print(run())
