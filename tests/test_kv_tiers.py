"""Hierarchical KV tiering: host-offload pool, async prefetch, int4 tier.

Contract under test: under device page pressure unlocked prefix-cache
entries DEMOTE to host pages instead of dropping (hot -> host ->
compressed int4 -> gone); a hit on a demoted entry PROMOTES it back into
fresh device pages and the request's greedy tokens are bitwise identical
to an all-HBM run; preemption swap-out routes its payload through the
same host pool; and every path is leak-free across device AND host pools
(the autouse conftest gate audits both).
"""
import numpy as np
import pytest

import jax

from repro.configs.base import get_config, reduced
from repro.core.cache import (dequant_rows_int4, pack_int4, quant_rows_int4,
                              unpack_int4)
from repro.models import transformer as tfm
from repro.serving import invariants
from repro.serving import kv_tiers
from repro.serving.engine import EngineConfig, EngineCore
from repro.serving.sampling import SamplingParams

ARCH = "chai-llama-7b"          # MHA+CHAI: snapshots + kc/vc pages
GREEDY = SamplingParams(max_new_tokens=8)

_params_cache = {}


def _model():
    if ARCH not in _params_cache:
        cfg = reduced(get_config(ARCH), n_layers=2, d_model=32, d_ff=64,
                      vocab=64).replace(dtype="float32")
        cfg = cfg.with_chai(enabled=True, warmup_tokens=3)
        _params_cache[ARCH] = (cfg,
                               tfm.init_params(cfg, jax.random.PRNGKey(0)))
    return _params_cache[ARCH]


def _ecfg(**kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("audit_level", "deep")
    return EngineConfig(**kw)


def _drain(core, max_steps=600):
    outs = []
    for _ in range(max_steps):
        if not core.has_work():
            return outs
        outs.extend(core.step())
    raise AssertionError(f"engine did not drain in {max_steps} steps")


def _family_prompts(n, *, prefix_blocks=2, ps=8, seed=0, vocab=64):
    """Prompts sharing a whole-block prefix (radix reuse) with distinct
    suffixes — the tier workload: families overflow the device pool."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=prefix_blocks * ps).tolist()
    return [prefix + rng.integers(1, vocab, size=ps).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# int4 pack/quant units (core/cache.py)
# ---------------------------------------------------------------------------
def test_int4_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for n in (1, 7, 8, 33):                     # odd lengths pad a nibble
        codes = rng.integers(-7, 8, size=(3, n)).astype(np.int8)
        packed = pack_int4(codes)
        assert packed.dtype == np.uint8
        assert packed.shape[-1] == (n + 1) // 2
        out = unpack_int4(packed, n)
        np.testing.assert_array_equal(out, codes)


def test_int4_quant_error_bounded_per_row():
    """Symmetric per-row int4: |x - dq(q(x))| <= scale/2 = amax/14."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((4, 6, 32)).astype(np.float32) * 3.0
    q, scale = quant_rows_int4(x)
    assert q.dtype == np.int8 and np.abs(q).max() <= 7
    back = dequant_rows_int4(q, scale)
    err = np.abs(back - x)
    bound = np.abs(x).max(axis=-1, keepdims=True) / 14.0 + 1e-6
    assert (err <= bound + 1e-5).all()


def test_compress_payload_roundtrip_shapes_and_dtype():
    rng = np.random.default_rng(2)
    for dt in (np.float32, np.int8):
        data = (rng.standard_normal((2, 4, 8, 16)) * 5).astype(dt)
        payload = {"data": data, "scale": rng.standard_normal(
            (2, 4, 8, 1)).astype(np.float32)}
        cp = kv_tiers.compress_payload(payload)
        assert cp["packed"].nbytes < data.nbytes or dt == np.int8
        out = kv_tiers.decompress_payload(cp)
        assert out["data"].shape == data.shape
        assert out["data"].dtype == data.dtype
        np.testing.assert_array_equal(out["scale"], payload["scale"])
        # int4 resolution: error bounded by half a quantization step
        err = np.abs(out["data"].astype(np.float64)
                     - data.astype(np.float64))
        bound = np.abs(data).max(axis=-1, keepdims=True) / 14.0 + 1.0
        assert (err <= bound).all()


# ---------------------------------------------------------------------------
# HostPagePool / TierManager units
# ---------------------------------------------------------------------------
def test_host_page_pool_semantics():
    pool = kv_tiers.HostPagePool(5, 8)          # 4 usable pages
    payloads = [{"data": np.full((2, 2), i, np.float32)} for i in range(3)]
    pages = [pool.store(p) for p in payloads]
    assert pool.pages_in_use == 3 and pool.bytes_stored() == 3 * 16
    for pg, p in zip(pages, payloads):
        assert pool.fetch(pg) is p
    # aliasing: freed-at-zero keeps the payload until the last ref dies
    pool.incref([pages[0]])
    pool.free([pages[0]])
    assert pool.fetch(pages[0]) is payloads[0]
    pool.free([pages[0]])
    assert pages[0] not in pool._data
    out = []
    invariants._audit_pool("host", pool, out)
    assert out == []
    with pytest.raises(MemoryError):
        pool.alloc(4)


def _entry(tier="hot", compressible=True):
    from repro.serving.prefix_cache import BlockNode
    e = BlockNode(key=(1,), kg_page=1, vg_page=2, parent=None)
    e.tier = tier
    e.compressible = compressible
    return e


def _payloads(rng, n=1):
    return {"kg": [{"data": rng.standard_normal(
                        (2, 3, 8, 4)).astype(np.float32)}
                   for _ in range(n)],
            "vg": [{"data": rng.standard_normal(
                        (2, 3, 8, 4)).astype(np.float32)}
                   for _ in range(n)]}


def test_tier_manager_store_verify_fetch_release():
    rng = np.random.default_rng(3)
    tm = kv_tiers.TierManager(8, host_pages={"dense": 8, "chai": 0},
                              comp_pages={"dense": 8, "chai": 0})
    e = _entry()
    pl = _payloads(rng)
    tm.store_entry(e, pl)
    assert e.tier == kv_tiers.TIER_HOST and e.tier_crc != 0
    assert tm.verify_entry(e)
    got = tm.fetch_entry(e)
    np.testing.assert_array_equal(got["kg"][0]["data"],
                                  pl["kg"][0]["data"])
    # corruption is caught by the CRC
    stored = tm.host["dense"].fetch(e.tier_pages["kg"][0])
    stored["data"] = stored["data"] + 1.0
    assert not tm.verify_entry(e)
    tm.release_entry(e)
    assert tm.host["dense"].pages_in_use == 0
    assert e.tier_pages == {}


def test_tier_manager_ladder_compress_then_drop():
    """make_room walks host->compressed->gone: a compressible victim is
    re-coded to int4, an uncompressible one is structurally dropped."""
    rng = np.random.default_rng(4)
    dropped = []
    tm = kv_tiers.TierManager(8, host_pages={"dense": 2, "chai": 0},
                              comp_pages={"dense": 2, "chai": 0})
    tm.drop_hook = lambda e: (dropped.append(e), tm.discard_entry(e))
    tm.droppable_hook = lambda e: True
    comp = _entry(compressible=True)
    tm.store_entry(comp, _payloads(rng))        # host full (2 pages)
    assert tm.make_room({"dense": 2})           # compresses `comp`
    assert comp.tier == kv_tiers.TIER_COMP
    assert tm.verify_entry(comp)                # restamped over int4
    assert tm.host["dense"].pages_in_use == 0
    assert tm.comp["dense"].pages_in_use == 2
    assert tm.transitions[("host", "compressed", "dense")] == 2
    # an uncompressible entry under the same pressure is dropped
    snap_like = _entry(compressible=False)
    tm.store_entry(snap_like, _payloads(rng))
    assert tm.make_room({"dense": 2})
    assert dropped == [snap_like]
    assert snap_like.tier_pages == {}
    # and a compressed-tier resident sheds when ITS pool overflows
    tm.droppable_hook = lambda e: True
    another = _entry(compressible=True)
    tm.store_entry(another, _payloads(rng))
    assert tm.make_room({"dense": 2})           # comp pool full: drops LRU
    assert comp in dropped
    # impossible requests fail fast
    assert not tm.make_room({"dense": 99})


# ---------------------------------------------------------------------------
# engine integration: demote -> promote, bitwise parity
# ---------------------------------------------------------------------------
def _run_family(ecfg_kw, prompts, max_new=8):
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(**ecfg_kw))
    tokens = {}
    for p in prompts:
        r = core.add_request(list(p), SamplingParams(max_new_tokens=max_new))
        _drain(core)                  # serialize: maximal reuse per prompt
        tokens[r.uid] = list(r.generated)
        assert r.finish_reason == "length"
    return core, tokens


def test_demoted_radix_blocks_promote_bitwise():
    """Prefix-family workload past device capacity: evictions demote to
    host, later family members hit the demoted blocks, promotion yields
    tokens bitwise identical to an unpressured all-HBM run."""
    rng = np.random.default_rng(55)
    base = _family_prompts(4, seed=5)
    # Extending a base prompt by one fresh block routes the match
    # THROUGH its (by then demoted) suffix leaf — snapshots only serve
    # exact-prompt repeats, so this is the block-promotion path.
    extended = [p + rng.integers(1, 64, size=8).tolist() for p in base[:2]]
    workload = base + extended
    # 9 usable dense pages: one 24-token request needs 8 pages of
    # headroom, so cached family suffixes demote between requests.
    tight = dict(batch_slots=1, prefix_cache=True, kv_offload=True,
                 num_pages=12, host_pages=64, tier_prefetch=False)
    core, toks = _run_family(tight, workload)
    st = core.prefix_stats()
    assert st["demoted_blocks"] > 0, "workload never demoted — resize"
    assert st["promoted_blocks"] > 0, "no demoted entry was ever hit"
    ts = core.tier_stats()
    assert ts["transitions"].get("hot->host/dense", 0) > 0
    assert ts["transitions"].get("host->hot/dense", 0) > 0
    # all-HBM reference: same workload, no pressure, no offload
    _, ref = _run_family(dict(batch_slots=1, prefix_cache=True), workload)
    assert toks == ref


def test_demoted_snapshot_promotes_bitwise():
    """A CHAI snapshot demoted under pressure is promoted on the next
    full-prompt hit; the resumed decode matches the unpressured run."""
    cfg, params = _model()
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 64, size=16).tolist()
    filler = _family_prompts(3, prefix_blocks=2, seed=7)

    def run(**kw):
        core = EngineCore(cfg, params, _ecfg(batch_slots=1,
                                             prefix_cache=True, **kw))
        first = core.add_request(list(prompt),
                                 SamplingParams(max_new_tokens=10))
        _drain(core)
        assert core.prefix_stats()["snapshots"] == 1
        for f in filler:              # pressure: evict/demote the snapshot
            core.add_request(list(f), SamplingParams(max_new_tokens=10))
            _drain(core)
        dup = core.add_request(list(prompt),
                               SamplingParams(max_new_tokens=10))
        _drain(core)
        assert dup.finish_reason == "length"
        return core, list(first.generated), list(dup.generated)

    core, first, dup = run(kv_offload=True, num_pages=12,
                           num_chai_pages=12, tier_prefetch=False)
    st = core.prefix_stats()
    assert st["demoted_snapshots"] > 0, "snapshot never demoted — resize"
    assert st["promoted_snapshots"] > 0
    _, first_ref, dup_ref = run()
    assert first == first_ref and dup == dup_ref


def test_compressed_hit_replans_cold_with_parity():
    """Default (lossy_promote=False): a hit on an int4-compressed block
    drops it and re-plans cold — tokens still match the clean run."""
    prompts = _family_prompts(6, seed=8)
    tight = dict(batch_slots=1, prefix_cache=True, kv_offload=True,
                 num_pages=10, host_pages=2, compressed_pages=16,
                 tier_prefetch=False)
    core, toks = _run_family(tight, prompts)
    ts = core.tier_stats()
    assert ts["transitions"].get("host->compressed/dense", 0) > 0, \
        "host pool never spilled to int4 — resize"
    _, ref = _run_family(dict(batch_slots=1, prefix_cache=True), prompts)
    assert toks == ref


def test_prefetch_promotes_ahead_of_admission():
    """add_request queues demoted-entry promotion; step() drains it so
    the planner finds the entry hot (prefetch_hits counts the save)."""
    rng = np.random.default_rng(9)
    base = _family_prompts(4, seed=9)
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(
        batch_slots=1, prefix_cache=True, kv_offload=True, num_pages=12,
        host_pages=64, telemetry="basic"))
    for p in base:
        core.add_request(list(p), GREEDY)
        _drain(core)
    assert core.prefix_stats()["demoted_blocks"] > 0
    # extending the first prompt routes through its demoted suffix leaf
    core.add_request(base[0] + rng.integers(1, 64, size=8).tolist(),
                     GREEDY)
    _drain(core)
    ts = core.tier_stats()
    assert ts["prefetch_hits"] + ts["prefetch_misses"] > 0
    snap = core.metrics()
    assert "tier_transitions_total" in snap["counters"]
    assert "kv_tier_pages" in snap["gauges"]


def test_preemption_swaps_through_host_pool():
    """The preemption resume payload lives in host-tier pages (no
    bespoke host dict), is freed at swap-in, and the victim resumes."""
    cfg, params = _model()
    rng = np.random.default_rng(10)
    core = EngineCore(cfg, params, _ecfg(batch_slots=1, prefix_cache=True))
    victim = core.add_request(rng.integers(1, 64, size=12).tolist(),
                              SamplingParams(max_new_tokens=12))
    for _ in range(4):
        core.step()
    preemptor = core.add_request(rng.integers(1, 64, size=6).tolist(),
                                 SamplingParams(max_new_tokens=4),
                                 priority=1)
    assert core.step() is not None
    rs = victim.resume_state
    assert rs is not None and rs["tier_pages"], "victim not swapped out"
    assert "pools" not in rs            # the bespoke host dict is gone
    held = sum(p.pages_in_use for p in core.tiers.host.values()
               if p is not None)
    assert held == sum(len(v) for v in rs["tier_pages"].values()) > 0
    assert invariants.audit(core) == []     # cross-tier refs balance
    _drain(core)
    assert preemptor.finish_reason == "length"
    assert victim.finish_reason == "length"
    assert len(victim.generated) == 12
    assert core.preemptions == 1
    ts = core.tier_stats()
    assert ts["transitions"].get("host->hot/chai",
                                 ts["transitions"].get("host->hot/dense",
                                                       0)) > 0
    assert all(p.pages_in_use == 0 for pools in
               (core.tiers.host, core.tiers.comp)
               for p in pools.values() if p is not None)


def test_over_capacity_workload_is_leak_free():
    """A prefix-family workload several times the device pool completes;
    the autouse conftest gate + this explicit audit check device AND
    host pools conserve and hold zero orphans afterwards."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(
        batch_slots=2, prefix_cache=True, kv_offload=True, num_pages=14))
    for i in range(3):
        for p in _family_prompts(4, seed=20 + i):
            core.add_request(list(p), GREEDY)
        _drain(core)
    assert core.tier_stats()["transitions"]    # the ladder actually ran
    assert invariants.audit_leaks(core) == []


@pytest.mark.no_leak_gate
def test_orphaned_host_page_fails_the_audit():
    """A host page with no owning entry (simulated leak) is flagged by
    the cross-tier reference audit."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(prefix_cache=True,
                                         kv_offload=True))
    core.add_request(_family_prompts(1, seed=30)[0], GREEDY)
    _drain(core)
    core.tiers.store_pages(
        "dense", [{"data": np.zeros((2, 3, 8, 4), np.float32)}])
    problems = invariants.audit_leaks(core)
    assert any("host_pool[dense]" in v for v in problems)


def test_kv_offload_requires_paged_layout():
    """A model with no global attention layer has no page pools, so no
    tier to offload into: kv_offload is refused."""
    cfg = reduced(get_config("h2o-danube-1.8b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    assert cfg.n_global_layers == 0
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_offload"):
        EngineCore(cfg, params, _ecfg(use_chai=False, kv_offload=True))
