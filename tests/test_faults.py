"""Robustness layer: typed failure taxonomy, deterministic fault
injection, invariant auditing, per-request quarantine, and the
supervised AsyncLLM driver.

Contract under test: a request-isolatable failure (injected or real)
ends exactly ONE request with a typed ``finish_reason="error"`` while
every untouched greedy request generates bitwise-identical tokens to a
fault-free run; engine-level corruption raises ``EngineFault`` instead
of silently continuing; and all fault paths return pages
refcount-exactly (the autouse conftest leak gate audits every engine
built here).
"""
import asyncio

import numpy as np
import pytest

import jax

from repro.configs.base import get_config, reduced
from repro.models import transformer as tfm
from repro.serving import invariants
from repro.serving.async_api import AsyncLLM
from repro.serving.engine import EngineConfig, EngineCore
from repro.serving.faults import (
    CapacityError,
    EngineFault,
    FaultInjector,
    FaultSpec,
    QuarantineError,
    RequestError,
    SnapshotRestoreError,
    ValidationError,
    checksum_arrays,
    corrupt_arrays,
)
from repro.serving.sampling import FINISH_ERROR, SamplingParams

ARCH = "chai-llama-7b"          # MHA+CHAI: exercises snapshots + kc/vc
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


def _drain(core, max_steps=400):
    outs = []
    for _ in range(max_steps):
        if not core.has_work():
            return outs
        outs.extend(core.step())
    raise AssertionError(f"engine did not drain in {max_steps} steps")


def _prompts(n, length=(6, 14), seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(*length))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# taxonomy + injector + integrity helpers (pure units)
# ---------------------------------------------------------------------------
def test_fault_taxonomy_backcompat_bases():
    """New typed errors must still be catchable as the historical types
    (MemoryError for the page budget, ValueError for add_request)."""
    cap = CapacityError("full", uid=7)
    assert isinstance(cap, MemoryError) and isinstance(cap, RequestError)
    assert cap.uid == 7
    val = ValidationError("bad", uid=3)
    assert isinstance(val, ValueError) and isinstance(val, RequestError)
    assert isinstance(QuarantineError("q"), RequestError)
    assert isinstance(SnapshotRestoreError("s"), RequestError)
    ef = EngineFault("broken", violations=["a", "b"])
    assert isinstance(ef, RuntimeError)
    assert not isinstance(ef, RequestError)
    assert "a" in str(ef) and "b" in str(ef)


def test_faultspec_validates_site_mode_p():
    with pytest.raises(ValueError):
        FaultSpec("no.such.site")
    with pytest.raises(ValueError):
        FaultSpec("pool.alloc", mode="explode")
    with pytest.raises(ValueError):
        FaultSpec("pool.alloc", p=0.0)
    with pytest.raises(ValueError):
        FaultSpec("pool.alloc", p=1.5)


def test_fault_injector_is_deterministic_and_replayable():
    """Same (seed, plan, call sequence) => byte-identical firing log;
    gating on step/uid/count behaves exactly as specified."""
    specs = [FaultSpec("pool.alloc", mode="transient", step=4),
             FaultSpec("swap.in", uid=9, count=2),
             FaultSpec("step.logits", mode="nan", p=0.4, count=-1)]
    calls = ([("pool.alloc", s, u) for s in range(6) for u in (1, 9)]
             + [("swap.in", 5, u) for u in (1, 9, 9, 9)]
             + [("step.logits", s, 2) for s in range(30)])

    def run():
        # fresh specs per run so count bookkeeping never crosses runs
        inj = FaultInjector(
            [FaultSpec(s.site, s.mode, s.step, s.uid, s.count, s.p)
             for s in specs], seed=11)
        log = []
        for site, step, uid in calls:
            spec = inj.fire(site, step=step, uid=uid)
            log.append(None if spec is None else spec.mode)
        return log, inj.report()

    log_a, rep_a = run()
    log_b, rep_b = run()
    assert log_a == log_b
    assert rep_a == rep_b
    # step gate: pool.alloc fired exactly once, at step 4
    pool = [f for f in rep_a["fired"] if f["site"] == "pool.alloc"]
    assert [f["step"] for f in pool] == [4]
    # uid + count gate: swap.in fired twice, only for uid 9
    swap = [f for f in rep_a["fired"] if f["site"] == "swap.in"]
    assert len(swap) == 2 and all(f["uid"] == 9 for f in swap)
    # probabilistic arm fired some-but-not-all of 30 eligible calls
    nan = [f for f in rep_a["fired"] if f["site"] == "step.logits"]
    assert 0 < len(nan) < 30


def test_fault_payload_checksum_detects_corruption():
    """The swap-out integrity stamp: corrupting any leaf of the resume
    payload changes the CRC; corruption is deterministic in the seed and
    works on read-only (device_get-style) leaves."""
    def payload():
        a = np.arange(24, dtype=np.float32).reshape(4, 6)
        a.setflags(write=False)
        return {"cols": {"k": a},
                "pools": {"kg": np.ones((2, 3), np.float32)}}

    base = checksum_arrays(payload())
    assert base == checksum_arrays(payload())        # order/shape stable
    t1, t2 = payload(), payload()
    assert corrupt_arrays(t1, seed=5) and corrupt_arrays(t2, seed=5)
    assert checksum_arrays(t1) != base
    assert checksum_arrays(t1) == checksum_arrays(t2)  # seeded => identical


# ---------------------------------------------------------------------------
# engine quarantine paths
# ---------------------------------------------------------------------------
def test_validation_error_is_typed_and_catchable_as_valueerror():
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(max_seq=32))
    with pytest.raises(ValidationError):
        core.add_request(list(range(1, 30)), GREEDY, max_new_tokens=20)
    with pytest.raises(ValueError):                   # legacy catch
        core.add_request(list(range(1, 30)), GREEDY, max_new_tokens=20)
    assert not core.has_work()


def test_nan_logits_quarantine_isolates_one_request():
    """A poisoned logits row typed-fails ITS slot; the other slots keep
    decoding and produce the exact fault-free tokens."""
    cfg, params = _model()
    prompts = _prompts(3, seed=1)

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(batch_slots=3), faults=faults)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return core, reqs

    clean_core, clean = run(None)
    inj = FaultInjector([FaultSpec("step.logits", mode="nan",
                                   uid=clean[1].uid)], seed=0)
    core, reqs = run(inj)
    assert reqs[1].finish_reason == FINISH_ERROR
    assert "non-finite logits" in reqs[1].error
    for k in (0, 2):
        assert reqs[k].finish_reason == clean[k].finish_reason
        assert list(reqs[k].generated) == list(clean[k].generated)
    fs = core.fault_stats()
    assert fs["quarantined"] == 1
    assert fs["injector"]["fired"][0]["site"] == "step.logits"
    assert clean_core.fault_stats()["quarantined"] == 0


def test_pool_alloc_fault_quarantines_queued_request():
    """mode="error" at the admission planner typed-fails the queued
    request before it touches any device state."""
    cfg, params = _model()
    prompts = _prompts(3, seed=2)
    inj = FaultInjector([FaultSpec("pool.alloc", mode="error", uid=1)],
                        seed=0)
    core = EngineCore(cfg, params, _ecfg(batch_slots=3), faults=inj)
    reqs = [core.add_request(p, GREEDY) for p in prompts]
    outs = _drain(core)
    assert reqs[1].finish_reason == FINISH_ERROR and reqs[1].error
    assert all(r.finish_reason == "length" for r in (reqs[0], reqs[2]))
    terminal = [o for o in outs if o.uid == reqs[1].uid and o.finished]
    assert terminal and terminal[0].finish_reason == FINISH_ERROR


def test_pool_alloc_transient_fault_only_delays_admission():
    """mode="transient" blocks the plan for one step; the request is
    retried, completes, and (being untouched otherwise) matches the
    fault-free tokens. It must NOT trigger preemption or the impossible-
    head CapacityError."""
    cfg, params = _model()
    prompts = _prompts(2, seed=3)

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(), faults=faults)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return reqs

    clean = run(None)
    inj = FaultInjector([FaultSpec("pool.alloc", mode="transient",
                                   count=2)], seed=0)
    faulted = run(inj)
    for c, f in zip(clean, faulted):
        assert f.finish_reason == "length" == c.finish_reason
        assert list(f.generated) == list(c.generated)


def test_swap_corruption_fault_is_quarantined_at_swap_in():
    """Preemption swap-out stamps a CRC; an injected payload corruption
    is caught at swap-in BEFORE any device mutation and the victim is
    quarantined — the preemptor and the pool are untouched."""
    cfg, params = _model()
    rng = np.random.default_rng(4)
    inj = FaultInjector([FaultSpec("swap.corrupt", mode="corrupt")],
                        seed=0)
    core = EngineCore(cfg, params,
                      _ecfg(batch_slots=1, prefix_cache=True),
                      faults=inj)
    victim = core.add_request(rng.integers(1, 64, size=12).tolist(),
                              SamplingParams(max_new_tokens=12))
    for _ in range(4):
        core.step()
    preemptor = core.add_request(rng.integers(1, 64, size=6).tolist(),
                                 SamplingParams(max_new_tokens=4),
                                 priority=1)
    _drain(core)
    assert preemptor.finish_reason == "length"
    assert victim.finish_reason == FINISH_ERROR
    assert "checksum mismatch" in victim.error
    fs = core.fault_stats()
    assert fs["swap_checksum_failures"] == 1
    assert fs["quarantined"] == 1
    assert core.preemptions == 1


def test_snapshot_restore_fault_recovers_by_replanning_cold():
    """An injected CHAI-snapshot restore failure drops the snapshot and
    re-plans the admission cold — the duplicate request still completes
    with the exact tokens a fault-free duplicate run produces."""
    cfg, params = _model()
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 64, size=16).tolist()

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(prefix_cache=True),
                          faults=faults)
        first = core.add_request(list(prompt), GREEDY)
        _drain(core)
        assert core.prefix_stats()["snapshots"] >= 1
        dup = core.add_request(list(prompt), GREEDY)
        _drain(core)
        return core, first, dup

    _, _, dup_clean = run(None)
    inj = FaultInjector([FaultSpec("snapshot.restore", count=1)], seed=0)
    core, first, dup = run(inj)
    assert [f["site"] for f in inj.fired] == ["snapshot.restore"]
    assert dup.finish_reason == "length"
    assert list(dup.generated) == list(dup_clean.generated)
    assert core.fault_stats()["quarantined"] == 0    # recovered, not failed


def test_kernel_fault_degrades_to_reference_decode_with_parity():
    """An injected fused-decode failure flips the engine into the jnp
    reference path for the rest of its life; greedy tokens are identical
    (the reference path IS the parity oracle)."""
    cfg, params = _model()
    prompts = _prompts(2, seed=6)

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(), faults=faults)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return core, reqs

    _, clean = run(None)
    inj = FaultInjector([FaultSpec("kernel.decode", count=1)], seed=0)
    core, reqs = run(inj)
    fs = core.fault_stats()
    assert fs["degraded_decode"] is True
    assert fs["decode_fallbacks"] == 1
    assert fs["quarantined"] == 0
    for c, f in zip(clean, reqs):
        assert list(f.generated) == list(c.generated)


@pytest.mark.no_leak_gate
def test_real_decode_failure_raises_engine_fault():
    """A real (non-injected) decode failure is not masked by the jnp
    reference fallback: step() raises EngineFault, and the engine never
    reports a degraded decode it did not run."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg())
    core.add_request(_prompts(1, seed=6)[0], GREEDY)

    def broken(*args):
        raise RuntimeError("kernel failed to lower")

    core._mha_step = core._chai_step = core._mixed_step = broken
    with pytest.raises(EngineFault, match="fused kernel path"):
        _drain(core)
    fs = core.fault_stats()
    assert fs["degraded_decode"] is False
    assert fs["decode_fallbacks"] == 0


def test_relay_residency_fault_dissolves_groups_not_requests():
    """A relay-formation fault falls back to per-request decode for that
    step; nobody fails and tokens match the relay-free run."""
    cfg, params = _model()
    rng = np.random.default_rng(7)
    shared = rng.integers(1, 64, size=16).tolist()
    prompts = [shared + rng.integers(1, 64, size=3).tolist()
               for _ in range(2)]

    def run(faults, relay):
        core = EngineCore(cfg, params,
                          _ecfg(prefix_cache=True, relay_decode=relay),
                          faults=faults)
        # seed the radix tree so the family members below admit through
        # the SAME cached chain (relay groups form on shared radix nodes)
        core.add_request(shared + [1, 2], GREEDY)
        _drain(core)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return core, reqs

    _, clean = run(None, relay=False)
    inj = FaultInjector([FaultSpec("relay.residency", count=-1)], seed=0)
    core, reqs = run(inj, relay=True)
    assert core.fault_stats()["relay_dissolved"] >= 1
    for c, f in zip(clean, reqs):
        assert f.finish_reason == "length"
        assert list(f.generated) == list(c.generated)


# ---------------------------------------------------------------------------
# invariant auditor
# ---------------------------------------------------------------------------
def test_invariant_audit_clean_on_live_and_idle_engine():
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(prefix_cache=True))
    for p in _prompts(2, seed=8):
        core.add_request(p, GREEDY)
    core.step()
    assert invariants.audit(core, deep=True) == []
    _drain(core)
    assert invariants.audit_leaks(core) == []
    # every step() call was audited (prefill-only steps included, so the
    # audit count dominates the batched-decode step count)
    assert core.fault_stats()["audit_steps"] >= core.steps_executed > 0


@pytest.mark.no_leak_gate
def test_invariant_audit_detects_pool_corruption():
    """Deliberately break pool conservation mid-flight: the next step()
    must raise EngineFault naming the violation instead of decoding on
    corrupt state."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg())
    core.add_request(_prompts(1, seed=9)[0], GREEDY)
    core.step()
    # a page that is both free and referenced: conservation + overlap
    page = next(iter(core.dense_pool._rc))
    core.dense_pool._free.append(page)
    with pytest.raises(EngineFault) as ei:
        core.step()
    assert ei.value.violations
    assert any("dense_pool" in v for v in ei.value.violations)


@pytest.mark.no_leak_gate
def test_invariant_audit_detects_leaked_reference():
    """A page reference nothing accounts for (the classic quarantine-
    path bug) is caught by the refcount audit."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg())
    core.add_request(_prompts(1, seed=10)[0], GREEDY)
    core.step()
    [page] = core.dense_pool.alloc(1)          # held by nobody
    vio = invariants.audit(core)
    assert any("outstanding references" in v for v in vio)
    with pytest.raises(EngineFault):
        core.step()


# ---------------------------------------------------------------------------
# AsyncLLM supervision
# ---------------------------------------------------------------------------
def test_async_capacity_fault_fails_only_its_stream():
    """A request that can NEVER fit typed-fails its own stream
    (CapacityError, still catchable as MemoryError); a concurrent small
    request on the same engine completes normally."""
    cfg, params = _model()
    rng = np.random.default_rng(11)
    big = rng.integers(1, 64, size=40).tolist()   # needs 12 dense pages
    small = rng.integers(1, 64, size=6).tolist()  # needs 4 dense pages
    ecfg = _ecfg(batch_slots=2, num_pages=8, num_chai_pages=16)

    async def main():
        async with AsyncLLM(cfg, params, ecfg) as llm:
            async def run(p, n):
                try:
                    return await llm.generate(p, max_new_tokens=n)
                except MemoryError as err:
                    return err
            return await asyncio.gather(run(big, 8), run(small, 4))

    r_big, r_small = asyncio.run(main())
    assert isinstance(r_big, CapacityError)
    assert r_small.finish_reason == "length"
    assert len(r_small.token_ids) == 4


def test_async_supervised_restart_recovers_from_transient_faults():
    """Non-typed step() failures are retried with backoff; the driver
    keeps the stream alive and the request completes."""
    cfg, params = _model()
    prompt = _prompts(1, seed=12)[0]

    async def main():
        async with AsyncLLM(cfg, params, _ecfg(),
                            restart_backoff=0.001) as llm:
            real = llm.core.step
            calls = {"n": 0}

            def flaky():
                calls["n"] += 1
                if calls["n"] <= 2:
                    raise RuntimeError("transient executor glitch")
                return real()

            llm.core.step = flaky
            out = await llm.generate(prompt, max_new_tokens=5)
            return out, calls["n"], llm.restarts

    out, n_calls, restarts = asyncio.run(main())
    assert out.finish_reason == "length" and len(out.token_ids) == 5
    assert n_calls >= 3
    assert restarts == 2


def test_async_exhausted_retries_broadcast_engine_fault():
    cfg, params = _model()
    prompt = _prompts(1, seed=13)[0]

    async def main():
        llm = AsyncLLM(cfg, params, _ecfg(), max_restarts=1,
                       restart_backoff=0.001)
        try:
            def dead():
                raise RuntimeError("persistent engine failure")
            llm.core.step = dead
            with pytest.raises(EngineFault, match="exhausted"):
                await llm.generate(prompt, max_new_tokens=4)
            assert llm.restarts == 2        # 1 retry + the fatal attempt
        finally:
            await llm.close()

    asyncio.run(main())


def test_async_unattributable_memoryerror_is_engine_fault():
    """A bare MemoryError with NO queue head cannot be pinned on a
    request — the old code crashed the driver on queue[0]; now it
    escalates to a typed EngineFault broadcast."""
    cfg, params = _model()
    prompt = _prompts(1, seed=14)[0]

    async def main():
        llm = AsyncLLM(cfg, params, _ecfg())
        try:
            real = llm.core.step
            state = {"fired": False}

            def spurious():
                if (not state["fired"] and not llm.core.queue
                        and llm.core.has_active):
                    state["fired"] = True
                    raise MemoryError("spurious allocator failure")
                return real()

            llm.core.step = spurious
            with pytest.raises(EngineFault, match="no queue head"):
                await llm.generate(prompt, max_new_tokens=6)
            assert state["fired"]
        finally:
            await llm.close()

    asyncio.run(main())


def test_async_quarantine_stream_gets_typed_terminal_output():
    """An in-flight quarantine (NaN logits) is NOT a driver failure: the
    stream receives a terminal chunk with finish_reason="error" and the
    driver keeps serving the other stream."""
    cfg, params = _model()
    prompts = _prompts(2, seed=15)
    inj = FaultInjector([FaultSpec("step.logits", mode="nan", uid=0)],
                        seed=0)

    async def main():
        async with AsyncLLM(cfg, params, _ecfg(), faults=inj) as llm:
            outs = await asyncio.gather(
                llm.generate(prompts[0], max_new_tokens=6),
                llm.generate(prompts[1], max_new_tokens=6))
            return outs, llm.core.fault_stats()

    (o0, o1), fs = asyncio.run(main())
    assert o0.finish_reason == FINISH_ERROR
    assert o1.finish_reason == "length" and len(o1.token_ids) == 6
    assert fs["quarantined"] == 1


# -- KV-tier offload faults (serving/kv_tiers.py wiring) --------------------

def _family_workload(rng):
    """Prefix-family prompts + extensions that route later matches
    through demoted suffix leaves (the tier promotion path)."""
    prefix = rng.integers(1, 64, size=16).tolist()
    base = [prefix + rng.integers(1, 64, size=8).tolist()
            for _ in range(4)]
    ext = [p + rng.integers(1, 64, size=8).tolist() for p in base[:2]]
    return base + ext


def _run_offload_workload(cfg, params, workload, faults, **kw):
    core = EngineCore(cfg, params,
                      _ecfg(batch_slots=1, prefix_cache=True, **kw),
                      faults=faults)
    toks = []
    for p in workload:
        r = core.add_request(list(p), GREEDY)
        _drain(core)
        assert r.finish_reason == "length"
        toks.append(list(r.generated))
    return core, toks


def test_offload_corruption_is_caught_at_promotion_and_replanned():
    """``offload.out`` mode="corrupt" damages the host-tier copy AFTER
    its CRC stamp. The promotion path catches the mismatch, drops ONLY
    the damaged entry, and re-plans the request cold — greedy tokens
    are unchanged and nothing is quarantined (losing a cache entry is
    recovery, not failure)."""
    cfg, params = _model()
    workload = _family_workload(np.random.default_rng(20))
    _, clean = _run_offload_workload(cfg, params, workload, None)
    inj = FaultInjector([FaultSpec("offload.out", mode="corrupt",
                                   count=-1)], seed=0)
    core, toks = _run_offload_workload(
        cfg, params, workload, inj, kv_offload=True, num_pages=12,
        host_pages=64, tier_prefetch=False)
    assert any(f["site"] == "offload.out" for f in inj.fired)
    assert core.tier_stats()["offload_checksum_failures"] > 0
    st = core.prefix_stats()
    assert st["promoted_blocks"] == 0 and st["promoted_snapshots"] == 0
    assert core.fault_stats()["quarantined"] == 0
    assert toks == clean


def test_offload_out_noncorrupt_mode_declines_demotion():
    """Any non-corrupt ``offload.out`` mode makes the engine decline the
    demotion: the victim drops outright (always safe) and the workload
    completes with fault-free tokens and zero host-tier residency."""
    cfg, params = _model()
    workload = _family_workload(np.random.default_rng(21))
    _, clean = _run_offload_workload(cfg, params, workload, None)
    inj = FaultInjector([FaultSpec("offload.out", mode="error",
                                   count=-1)], seed=0)
    core, toks = _run_offload_workload(
        cfg, params, workload, inj, kv_offload=True, num_pages=12,
        host_pages=64, tier_prefetch=False)
    st = core.prefix_stats()
    assert st["demoted_blocks"] == 0 and st["demoted_snapshots"] == 0
    assert st["evicted_blocks"] + st["evicted_snapshots"] > 0
    assert core.tiers.tier_pages() == {
        k: 0 for k in core.tiers.tier_pages()}
    assert toks == clean


def test_offload_in_fault_at_promotion_replans_cold():
    """An injected ``offload.in`` failure at cache-entry promotion
    drops the entry and re-plans cold — same recovery contract as the
    snapshot-restore fault: tokens unchanged, nothing quarantined."""
    cfg, params = _model()
    workload = _family_workload(np.random.default_rng(22))
    _, clean = _run_offload_workload(cfg, params, workload, None)
    inj = FaultInjector([FaultSpec("offload.in", count=1)], seed=0)
    core, toks = _run_offload_workload(
        cfg, params, workload, inj, kv_offload=True, num_pages=12,
        host_pages=64, tier_prefetch=False)
    assert [f["site"] for f in inj.fired] == ["offload.in"]
    assert core.fault_stats()["quarantined"] == 0
    assert toks == clean


def test_offload_in_fault_at_swap_in_quarantines_victim():
    """The same ``offload.in`` site at preemption swap-in is NOT
    recoverable per-entry (the payload is a live request's KV): the
    victim alone is quarantined — parity with the ``swap.in`` arm —
    and its host-tier pages are released refcount-exactly."""
    cfg, params = _model()
    rng = np.random.default_rng(23)
    inj = FaultInjector([FaultSpec("offload.in", uid=0)], seed=0)
    core = EngineCore(cfg, params, _ecfg(batch_slots=1,
                                         prefix_cache=True), faults=inj)
    victim = core.add_request(rng.integers(1, 64, size=12).tolist(),
                              SamplingParams(max_new_tokens=12))
    for _ in range(4):
        core.step()
    preemptor = core.add_request(rng.integers(1, 64, size=6).tolist(),
                                 SamplingParams(max_new_tokens=4),
                                 priority=1)
    _drain(core)
    assert preemptor.finish_reason == "length"
    assert victim.finish_reason == FINISH_ERROR
    assert "host-tier fetch failure" in victim.error
    assert core.fault_stats()["quarantined"] == 1
    assert all(p.pages_in_use == 0 for p in core.tiers.host.values()
               if p is not None)
