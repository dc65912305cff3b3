"""Paged KV cache: allocator behaviour + batched-vs-alone engine parity.

Sharing the page pools must not change a token: requests served together
decode exactly as the same requests served alone (one at a time on the
same slots) for every arch/flag combination, while the page allocator
realizes CHAI's memory saving — dense K pages return to the pool at
compaction, admission is page-budget gated, and nothing leaks across
slot churn.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.core import cache as chai_cache
from repro.core.clustering import chai_widths
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine

MHA_ARCH = "chai-llama-7b"
GQA_ARCH = "nemotron-4-15b"


def _cfg(arch, **chai_kw):
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32")
    return cfg.with_chai(enabled=True, warmup_tokens=3, **chai_kw)


def _run(cfg, submissions, *, use_chai=True, slots=2, max_seq=64,
         alone=False, **ecfg_kw):
    """Serve ``submissions`` together, or with ``alone`` each by itself:
    one ``run()`` per request, the engine's other slots FREE."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=max_seq,
                                     use_chai=use_chai, page_size=16,
                                     **ecfg_kw))
    subs = list(enumerate(submissions))
    for wave in ([[s] for s in subs] if alone else [subs]):
        for i, (prompt, max_new) in wave:
            eng.submit(prompt, max_new_tokens=max_new, uid=i)
        done = eng.run()
    assert len(done) == len(submissions)
    return {r.uid: r for r in done}, eng


def _submissions(cfg, lens=(12, 5, 9, 7), prompt_len=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, size=prompt_len), m)
            for m in lens]


# ---------------------------------------------------------- PagePool -------
def test_page_pool_alloc_free_exhaustion():
    pool = chai_cache.PagePool(8, 16)       # 7 usable (page 0 = null)
    assert pool.capacity == 7 and pool.free_pages == 7
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert pool.free_pages == 0 and pool.pages_in_use == 7
    assert chai_cache.NULL_PAGE not in a + b
    assert len(set(a + b)) == 7             # all distinct
    with pytest.raises(MemoryError):
        pool.alloc(1)
    pool.free(a)
    assert pool.free_pages == 3
    c = pool.alloc(3)                       # freed pages are reusable
    assert sorted(c) == sorted(a)
    pool.free(b)
    pool.free(c)
    assert pool.pages_in_use == 0


def test_page_pool_guards():
    pool = chai_cache.PagePool(4, 16)
    pages = pool.alloc(2)
    pool.free(pages[:1])
    with pytest.raises(AssertionError):     # double free
        pool.free(pages[:1])
    with pytest.raises(AssertionError):     # null page is never freeable
        pool.free([chai_cache.NULL_PAGE])


def test_pages_needed_ceil():
    assert chai_cache.pages_needed(1, 16) == 1
    assert chai_cache.pages_needed(16, 16) == 1
    assert chai_cache.pages_needed(17, 16) == 2
    assert chai_cache.pages_needed(64, 16) == 4


# ------------------------------------------------- structs + accounting ----
def test_paged_state_structs_layout():
    """Paged structs: dense rectangles replaced by pool + block tables;
    clustered pool only for MHA+CHAI; scale pools only under int8."""
    cfg = _cfg(MHA_ARCH)
    shapes, _ = chai_cache.paged_state_structs(cfg, 2, 64, page_size=16,
                                               dense_pages=9, chai_pages=5)
    assert "kg" not in shapes and "vg" not in shapes
    assert shapes["kvp"].shape == (2, 9, cfg.n_kv_heads, 16, cfg.head_dim)
    k_max, _ = chai_widths(cfg)
    assert shapes["cp"].shape == (2, 5, k_max, 16, cfg.head_dim)
    assert shapes["bt_kg"].shape == shapes["bt_vg"].shape == (2, 4)
    assert shapes["bt_kc"].shape == (2, 4)
    assert "bt_vc" not in shapes            # share_values off
    assert "kvp_scale" not in shapes        # fp32 cache

    gqa = _cfg(GQA_ARCH)
    shapes, _ = chai_cache.paged_state_structs(gqa, 2, 64, page_size=16,
                                               dense_pages=9)
    assert "cp" not in shapes and "bt_kc" not in shapes
    assert "chai_scores" in shapes          # compute-only saving remains

    i8 = _cfg(MHA_ARCH).replace(kv_cache_dtype="int8")
    shapes, _ = chai_cache.paged_state_structs(i8, 2, 64, page_size=16,
                                               dense_pages=9, chai_pages=5)
    assert shapes["kvp"].dtype == jnp.int8
    assert shapes["kvp_scale"].shape == (2, 9, i8.n_kv_heads, 16)
    assert shapes["cp_scale"].shape == (2, 5, k_max, 16)


def test_paged_kv_bytes_accounting():
    """Allocated bytes = pages-in-use x page bytes; a steady CHAI slot
    (k_max clustered rows, dense K freed) costs less than its dense
    residency (KV rows for K AND V)."""
    cfg = _cfg(MHA_ARCH)
    dense_pb = chai_cache.paged_page_bytes(cfg, 16, kind="dense")
    chai_pb = chai_cache.paged_page_bytes(cfg, 16, kind="chai")
    k_max, _ = chai_widths(cfg)
    assert dense_pb == 2 * cfg.n_kv_heads * 16 * cfg.head_dim * 4
    assert chai_pb == 2 * k_max * 16 * cfg.head_dim * 4
    assert chai_pb < dense_pb               # k_max < n_heads
    assert chai_cache.paged_kv_bytes(cfg, 16, 3, 2) == \
        3 * dense_pb + 2 * chai_pb
    # WARMUP residency (K+V dense + reserved-nothing) vs STEADY residency
    # (V dense + K clustered): steady strictly cheaper.
    warm = chai_cache.paged_kv_bytes(cfg, 16, 2, 0)     # K + V pages
    steady = chai_cache.paged_kv_bytes(cfg, 16, 1, 1)   # V + clustered K
    assert steady < warm


# ------------------------------------------------------------ parity -------
@pytest.mark.slow
@pytest.mark.parametrize("arch", [MHA_ARCH, GQA_ARCH])
def test_paged_greedy_parity(arch):
    """Token-for-token parity: requests sharing the pools == the same
    requests served alone, through PREFILL/WARMUP/CLUSTER/STEADY phase
    mixes."""
    cfg = _cfg(arch)
    subs = _submissions(cfg, lens=(12, 5, 9, 12, 7))
    paged, engp = _run(cfg, subs)
    alone, _ = _run(cfg, subs, alone=True)
    for uid in alone:
        assert paged[uid].generated == alone[uid].generated, uid
    # every page went home
    assert engp.dense_pool.pages_in_use == 0
    if engp.chai_pool is not None:
        assert engp.chai_pool.pages_in_use == 0


@pytest.mark.slow
@pytest.mark.parametrize("share_values", [False, True])
def test_paged_parity_int8_and_shared_values(share_values):
    """The int8 scale pools and the clustered-V pages keep each
    request's numerics whatever else shares the pools: batched ==
    alone."""
    cfg = _cfg(MHA_ARCH, share_values=share_values).replace(
        kv_cache_dtype="int8")
    subs = _submissions(cfg, lens=(10, 6, 8))
    paged, engp = _run(cfg, subs)
    alone, _ = _run(cfg, subs, alone=True)
    for uid in alone:
        assert paged[uid].generated == alone[uid].generated, uid
    assert engp.dense_pool.pages_in_use == 0
    assert engp.chai_pool.pages_in_use == 0


@pytest.mark.slow
def test_paged_parity_int8_gqa_dense_layout_carries_scales():
    """GQA int8: the dense pool's per-row scales travel with each
    slot's pages, so greedy tokens batched == alone."""
    cfg = _cfg(GQA_ARCH).replace(kv_cache_dtype="int8")
    subs = _submissions(cfg, lens=(10, 6, 8))
    paged, engp = _run(cfg, subs)
    alone, _ = _run(cfg, subs, alone=True)
    for uid in alone:
        assert paged[uid].generated == alone[uid].generated, uid
    assert engp.dense_pool.pages_in_use == 0


# ------------------------------------------------- allocator behaviour -----
@pytest.mark.slow
def test_exhausted_pool_queues_admission_then_reuses_pages():
    """A pool sized for ONE request serializes admission: later requests
    wait in the queue (page-budget gate), are admitted as pages free at
    retire, and all complete with tokens identical to an unconstrained
    run. After N churn cycles, zero pages leak."""
    cfg = _cfg(MHA_ARCH)
    subs = _submissions(cfg, lens=(8, 8, 8, 8, 8), prompt_len=8)
    need = chai_cache.pages_needed(8 + 8, 16)
    tight, engt = _run(cfg, subs, num_pages=2 * need + 1,
                       num_chai_pages=need + 1)
    roomy, engr = _run(cfg, subs)
    for uid in roomy:
        assert tight[uid].generated == roomy[uid].generated, uid
    # page-budget admission actually serialized the tight run: with pages
    # for only one in-flight request, later requests started strictly
    # after earlier ones retired, despite 2 batch slots being free.
    admits = sorted((tight[u].admit_step, tight[u].retire_step)
                    for u in tight)
    for (a1, _), (_, r0) in zip(admits[1:], admits[:-1]):
        assert a1 >= r0
    # the roomy run interleaved (continuous batching baseline behaviour)
    assert engr.steps_executed < engt.steps_executed
    # churn left nothing behind
    assert engt.dense_pool.pages_in_use == 0
    assert engt.chai_pool.pages_in_use == 0


@pytest.mark.slow
def test_oversized_request_raises_memory_error():
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=2, max_seq=64,
                                     page_size=16, num_pages=3))
    # beyond the KV capacity entirely: rejected at submit
    with pytest.raises(ValueError):
        eng.submit(np.zeros(40, np.int32), max_new_tokens=40)
    # fits max_seq but not this (deliberately tiny) pool: page-budget
    # admission raises once the engine is idle and it still cannot fit
    eng.submit(np.zeros(40, np.int32), max_new_tokens=20)
    with pytest.raises(MemoryError):
        eng.run()


# ------------------------------------------------ fused decode kernel ------
@pytest.mark.slow
@pytest.mark.parametrize("arch,chai_kw,cfg_kw", [
    (MHA_ARCH, {}, {}),
    (MHA_ARCH, {"share_values": True}, {"kv_cache_dtype": "int8"}),
    (GQA_ARCH, {}, {}),
])
def test_fused_decode_greedy_parity_with_jnp_reference(monkeypatch, arch,
                                                       chai_kw, cfg_kw):
    """End-to-end acceptance: the fused one-launch decode produces
    token-for-token greedy parity with the pre-fusion jnp math across
    phase mixes, int8 and share_values."""
    from repro.core import chai_attention as chai_core
    cfg = _cfg(arch, **chai_kw).replace(**cfg_kw)
    subs = _submissions(cfg, lens=(10, 6, 8))
    fused_p, _ = _run(cfg, subs)
    monkeypatch.setattr(chai_core, "USE_FUSED_DECODE", False)
    reference, _ = _run(cfg, subs)
    for uid in reference:
        assert fused_p[uid].generated == reference[uid].generated, uid


# ------------------------------------------- the memory win, realized ------
@pytest.mark.slow
def test_steady_state_paged_chai_below_dense_mha():
    """The acceptance criterion: the allocator's steady-state CHAI
    footprint is BELOW the dense-MHA rectangle (every slot's K and V for
    all of ``max_seq``) — and the trajectory shows the drop at
    compaction."""
    cfg = _cfg(MHA_ARCH)
    subs = _submissions(cfg, lens=(24, 24), prompt_len=8)
    _, eng = _run(cfg, subs, max_seq=64)
    hist = eng.kv_bytes_history
    assert hist, "the engine records its allocated-bytes trajectory"
    dense_mha = chai_cache.kv_cache_bytes(cfg, 2, 64, chai=False)
    warm_peak = max(h["kv_bytes"] for h in hist)
    # steady state: every slot past CLUSTER (dense K pages freed)
    steady = [h for h in hist if h["step"] > cfg.chai.warmup_tokens + 1]
    assert steady, hist
    steady_bytes = steady[-1]["kv_bytes"]
    assert steady_bytes < warm_peak          # compaction freed pages
    assert steady_bytes < dense_mha          # CHAI saving, allocator-level
