"""Shared-prefix KV reuse: radix cache, COW paged pool, cached-aware
prefill.

Correctness contract: the prefix cache is a pure *work-skipping* layer —
every greedy token must be identical to a cold run of the same prompt,
whether the request misses, partially hits (suffix-only prefill over
aliased pages), fully hits a CHAI snapshot (STEADY entry, zero prefill
attention FLOPs, zero WARMUP/CLUSTER steps), or is replayed entirely
host-side. Refcounts must drop to zero after eviction + slot churn.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.core import cache as chai_cache
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.prefix_cache import PrefixCache

MHA_ARCH = "chai-llama-7b"
GQA_ARCH = "nemotron-4-15b"
PS = 16


def _cfg(arch, **chai_kw):
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32")
    return cfg.with_chai(enabled=True, warmup_tokens=3, **chai_kw)


def _engine(cfg, params, *, prefix_cache=True, slots=2, max_seq=64,
            **ecfg_kw):
    return ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=max_seq,
                                     page_size=PS,
                                     prefix_cache=prefix_cache, **ecfg_kw))


def _cold_tokens(cfg, params, prompt, max_new, **ecfg_kw):
    eng = _engine(cfg, params, prefix_cache=False, **ecfg_kw)
    eng.submit(prompt, max_new_tokens=max_new, uid=0)
    return eng.run()[0].generated


def _by_uid(done):
    return {r.uid: r for r in done}


# ------------------------------------------------------- PagePool refcount
def test_page_pool_refcount_shared_pages_freed_at_zero():
    pool = chai_cache.PagePool(8, PS)
    pages = pool.alloc(2)
    pool.incref(pages)                      # a second holder
    assert pool.refcount(pages[0]) == 2
    pool.free(pages)                        # first holder drops
    assert pool.pages_in_use == 2           # still held
    pool.free(pages)                        # second holder drops -> freed
    assert pool.pages_in_use == 0
    with pytest.raises(AssertionError):     # rc 0: double free
        pool.free(pages[:1])
    with pytest.raises(AssertionError):     # incref of a free page
        pool.incref(pages[:1])


# --------------------------------------------------------- radix tree unit
def _mk_cache(dense=32, chai=16):
    dense_pool = chai_cache.PagePool(dense, PS)
    chai_pool = chai_cache.PagePool(chai, PS)
    return PrefixCache(dense_pool, chai_pool, PS), dense_pool, chai_pool


def test_radix_match_insert_and_divergence():
    cache, pool, _ = _mk_cache()
    rng = np.random.default_rng(0)
    a = rng.integers(0, 64, size=3 * PS)
    kg, vg = pool.alloc(3), pool.alloc(3)
    assert cache.insert(a, kg, vg) == 3
    # full match is capped at (len-1)//PS so one suffix token remains
    assert len(cache.match(a)) == 2
    assert len(cache.match(np.concatenate([a, [1]]))) == 3
    # diverging INSIDE block 2 shares only the first block's node
    b = a.copy()
    b[PS + 3] ^= 1
    m = cache.match(np.concatenate([b, [1]]))
    assert len(m) == 1 and m[0].kg_page == kg[0]
    # re-inserting the same prompt creates nothing new
    assert cache.insert(a, kg, vg) == 0
    # each cached block holds one reference on each of its pages
    assert all(pool.refcount(p) == 2 for p in kg + vg)


def test_radix_lru_eviction_pins_locked_and_frees_pages():
    cache, pool, _ = _mk_cache()
    rng = np.random.default_rng(1)
    a = rng.integers(0, 64, size=2 * PS)
    b = rng.integers(0, 64, size=2 * PS)
    ka, va = pool.alloc(2), pool.alloc(2)
    kb, vb = pool.alloc(2), pool.alloc(2)
    cache.insert(a, ka, va)
    cache.insert(b, kb, vb)
    pool.free(ka + va + kb + vb)            # slots retired; cache holds
    assert pool.pages_in_use == 8
    nodes_b = cache.match(np.concatenate([b, [0]]))
    cache.lock(nodes_b)                     # an active slot pins b's chain
    assert cache.evict_until(dense_free=pool.free_pages + 4)
    # a's chain went (LRU, unlocked); b's leaf is pinned transitively? No:
    # only unlocked leaves are evictable — b's chain survives.
    assert cache.match(np.concatenate([a, [0]])) == []
    assert len(cache.match(np.concatenate([b, [0]]))) == 2
    cache.unlock(nodes_b)
    cache.clear()
    assert pool.pages_in_use == 0           # freed-at-zero: nothing leaks


# ------------------------------------------------ engine parity: the matrix
@pytest.mark.parametrize("arch,chai_kw,cfg_kw", [
    (MHA_ARCH, {}, {}),
    (MHA_ARCH, {}, {"kv_cache_dtype": "int8"}),
    (MHA_ARCH, {"share_values": True}, {}),
    (MHA_ARCH, {"share_values": True}, {"kv_cache_dtype": "int8"}),
    (GQA_ARCH, {}, {}),
    (GQA_ARCH, {}, {"kv_cache_dtype": "int8"}),
])
@pytest.mark.slow
def test_hit_miss_partial_parity_vs_cold(arch, chai_kw, cfg_kw):
    """miss -> snapshot hit -> partial hit, every flavour: greedy tokens
    identical to a cold engine without the cache."""
    cfg = _cfg(arch, **chai_kw).replace(**cfg_kw)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, size=24)   # 1 block + tail
    part = np.concatenate([prompt[:PS],
                           rng.integers(0, cfg.vocab_size, size=8)])
    cold = _cold_tokens(cfg, params, prompt, 12)
    cold_part = _cold_tokens(cfg, params, part, 12)

    eng = _engine(cfg, params)
    eng.submit(prompt, max_new_tokens=12, uid=0)        # miss
    miss = _by_uid(eng.run())[0]
    assert miss.cache_hit == "" and miss.generated == cold

    eng.submit(prompt, max_new_tokens=12, uid=1)        # warm
    warm = _by_uid(eng.run())[1]
    assert warm.generated == cold
    if eng.chai_clustered:      # MHA: full-prompt CHAI snapshot
        assert warm.cache_hit == "snapshot"
        assert warm.prefill_tokens == 0
    else:                       # GQA: dense block reuse only
        assert warm.cache_hit == "prefix"
        assert warm.prefill_tokens == len(prompt) - PS

    eng.submit(part, max_new_tokens=12, uid=2)          # partial
    partial = _by_uid(eng.run())[2]
    assert partial.cache_hit == "prefix"
    assert partial.cached_tokens == PS
    assert partial.prefill_tokens == 8
    assert partial.generated == cold_part

    # drain + drop the cache: every page refcount reaches zero
    eng.prefix_cache.clear()
    assert eng.dense_pool.pages_in_use == 0
    if eng.chai_pool is not None:
        assert eng.chai_pool.pages_in_use == 0


def test_snapshot_skips_warmup_and_cluster_entirely():
    """Acceptance: a warm full-prompt request performs zero prefill
    attention FLOPs and zero WARMUP/CLUSTER transitions, yet emits greedy
    tokens bit-identical to the cold path (replayed warmup tokens + the
    same STEADY state)."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=24)
    eng = _engine(cfg, params)
    eng.submit(prompt, max_new_tokens=12, uid=0)
    cold = _by_uid(eng.run())[0]
    clusters_after_cold = eng.cluster_transitions
    assert clusters_after_cold == 1

    eng.submit(prompt, max_new_tokens=12, uid=1)
    warm = _by_uid(eng.run())[1]
    assert warm.cache_hit == "snapshot"
    assert warm.prefill_tokens == 0                  # no prefill forward
    assert eng.cluster_transitions == clusters_after_cold   # no CLUSTER
    assert warm.generated == cold.generated          # bit-identical

    # replay-only: snapshot covers max_new -> no slot, no device work
    steps_before = eng.steps_executed
    eng.submit(prompt, max_new_tokens=3, uid=2)
    replay = _by_uid(eng.run())[2]
    assert replay.cache_hit == "replay" and replay.slot == -1
    assert eng.steps_executed == steps_before
    assert replay.generated == cold.generated[:3]


def test_cached_membership_equals_cold_membership():
    """The snapshot's per-layer cluster membership is the exact ctx the
    cold path computed (identical membership => identical CHAI math)."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=24)

    eng = _engine(cfg, params)
    eng.submit(prompt, max_new_tokens=12, uid=0)
    cold = _by_uid(eng.run())[0]
    snap = eng.prefix_cache.snapshot_for(prompt)
    assert snap is not None
    # the cold slot's membership survives in the engine's persistent ctx
    for key in ("h2c", "reps"):
        np.testing.assert_array_equal(
            snap.ctx[key], np.asarray(eng._dev_ctx[key][:, cold.slot]))


@pytest.mark.slow
def test_cow_divergence_after_shared_prefix():
    """Two concurrent requests share a cached block then diverge: each
    writes only its own pages (the shared page is read-only; the
    snapshot's partial tail was copied), so both match their cold runs
    and the shared pages survive both retirements."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    base = rng.integers(0, cfg.vocab_size, size=PS)
    p1 = np.concatenate([base, rng.integers(0, cfg.vocab_size, size=6)])
    p2 = np.concatenate([base, rng.integers(0, cfg.vocab_size, size=6)])
    cold1 = _cold_tokens(cfg, params, p1, 14)
    cold2 = _cold_tokens(cfg, params, p2, 14)

    eng = _engine(cfg, params)
    eng.submit(p1, max_new_tokens=14, uid=0)            # seeds the block
    eng.run()
    # both diverging requests in ONE wave: slot 2 aliases the block slot 1
    # seeded, while slot 1 (same wave) still holds it — shared, read-only
    eng.submit(p1, max_new_tokens=14, uid=1)
    eng.submit(p2, max_new_tokens=14, uid=2)
    done = _by_uid(eng.run())
    assert done[1].generated == cold1
    assert done[2].generated == cold2
    assert done[2].cache_hit in ("prefix", "snapshot")
    eng.prefix_cache.clear()
    assert eng.dense_pool.pages_in_use == 0
    assert eng.chai_pool.pages_in_use == 0


@pytest.mark.slow
def test_concurrent_snapshot_hits_share_pages():
    """Acceptance: >= 2 concurrent warm requests over one shared prompt
    allocate strictly fewer pages than the no-sharing baseline (full
    pages aliased; only partial tails + headroom are per-slot)."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, size=32)   # 2 full blocks

    eng = _engine(cfg, params)
    eng.submit(prompt, max_new_tokens=16, uid=0)
    eng.run()
    base_stats = eng.prefix_stats()
    assert base_stats["snapshots"] == 1

    # no-sharing baseline: peak pages of 2 cold requests side by side
    engb = _engine(cfg, params, prefix_cache=False)
    engb.submit(prompt, max_new_tokens=16, uid=0)
    engb.submit(prompt, max_new_tokens=16, uid=1)
    engb.run()
    cold_peak = max(h["dense_pages"] + h["chai_pages"]
                    for h in engb.kv_bytes_history)

    for uid in (1, 2):
        eng.submit(prompt, max_new_tokens=16, uid=uid)
    hist0 = len(eng.kv_bytes_history)
    done = _by_uid(eng.run())
    assert done[1].cache_hit == done[2].cache_hit == "snapshot"
    assert done[1].generated == done[2].generated
    warm_peak = max(h["dense_pages"] + h["chai_pages"]
                    for h in eng.kv_bytes_history[hist0:])
    assert warm_peak < cold_peak    # shared pages counted once
    eng.prefix_cache.clear()
    assert eng.dense_pool.pages_in_use == 0
    assert eng.chai_pool.pages_in_use == 0


@pytest.mark.slow
def test_eviction_under_pressure_then_no_leaks():
    """A pool too small to keep cache + new work evicts LRU entries to
    admit fresh requests; everything still completes with cold-parity
    tokens and zero pages leak after the final clear."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=24) for _ in range(4)]
    colds = [_cold_tokens(cfg, params, p, 8) for p in prompts]

    need = chai_cache.pages_needed(24 + 8, PS)
    eng = _engine(cfg, params, slots=1,
                  num_pages=2 * need + 3, num_chai_pages=need + 2)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=8, uid=i)
    done = _by_uid(eng.run())
    for i in range(4):
        assert done[i].generated == colds[i], i
    stats = eng.prefix_stats()
    assert stats["evicted_blocks"] + stats["evicted_snapshots"] > 0
    eng.prefix_cache.clear()
    assert eng.dense_pool.pages_in_use == 0
    assert eng.chai_pool.pages_in_use == 0


def test_prefix_cache_config_validation():
    dan = reduced(get_config("h2o-danube-1.8b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    assert dan.n_global_layers == 0     # no pages to share
    dp = tfm.init_params(dan, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        _engine(dan, dp, use_chai=False)
    gem = reduced(get_config("gemma2-9b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    if gem.n_local_layers:              # local rings are not paged
        gp = tfm.init_params(gem, jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            _engine(gem, gp)
