"""Continuous-batching engine: batched-vs-solo parity + slot lifecycle.

(a) Token-for-token greedy parity between requests served together on
    several slots and the same requests served alone, one at a time on
    an engine of the same slot count, across an MHA arch (clustered K
    cache) and a GQA arch (compute-only saving) — neither the phase mix
    of the other slots nor a slot's placement may change a token. (The
    slot count stays: a batch of another size compiles other CPU matmul
    kernels, whose last-bit differences can flip CHAI's choice between
    two heads equidistant from their cluster's centroid.)
(b) A short request admitted beside a long one retires early and its slot
    is reused by a queued request while the long one is still running —
    the head-of-line-blocking fix the scheduler exists for.
"""
import numpy as np
import pytest

import jax

from repro.configs.base import get_config, reduced
from repro.core import cache as chai_cache
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine

MHA_ARCH = "chai-llama-7b"      # n_heads == n_kv_heads
GQA_ARCH = "nemotron-4-15b"     # grouped KV heads


def _cfg(arch, **chai_kw):
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32")
    return cfg.with_chai(enabled=True, warmup_tokens=3, **chai_kw)


def _run(cfg, submissions, *, use_chai=True, slots=2, max_seq=64,
         alone=False):
    """Serve ``submissions`` together, or with ``alone`` each by itself:
    one ``run()`` per request, the engine's other slots FREE."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=max_seq,
                                     use_chai=use_chai))
    subs = list(enumerate(submissions))
    for wave in ([[s] for s in subs] if alone else [subs]):
        for i, (prompt, max_new) in wave:
            eng.submit(prompt, max_new_tokens=max_new, uid=i)
        done = eng.run()
    assert len(done) == len(submissions)
    return {r.uid: r for r in done}, eng


def _submissions(cfg, n_req=5, prompt_len=8, seed=0):
    rng = np.random.default_rng(seed)
    lens = [12, 5, 9, 12, 7, 4, 11][:n_req]
    return [(rng.integers(0, cfg.vocab_size, size=prompt_len), m)
            for m in lens]


@pytest.mark.slow
@pytest.mark.parametrize("arch", [MHA_ARCH, GQA_ARCH])
def test_greedy_parity_continuous_vs_cohort(arch):
    """Identical greedy tokens per request served batched (phases
    interleaved across the slots) and alone (no other request in the
    batch), through all phases (warmup_tokens=3 < several max_new):
    PREFILL/WARMUP/CLUSTER/STEADY all exercised."""
    cfg = _cfg(arch)
    subs = _submissions(cfg)
    cont, eng = _run(cfg, subs)
    solo, _ = _run(cfg, subs, alone=True)
    for uid in solo:
        assert cont[uid].generated == solo[uid].generated, uid
        assert len(cont[uid].generated) == subs[uid][1]
    # slot scheduling actually interleaved phases (not one-at-a-time)
    assert eng.steps_executed < sum(m for _, m in subs)


@pytest.mark.slow
def test_greedy_parity_without_chai():
    """use_chai=False: the engine reduces to plain MHA continuous decode
    and batched requests still match the same requests served alone."""
    cfg = _cfg(MHA_ARCH)
    subs = _submissions(cfg, n_req=4)
    cont, _ = _run(cfg, subs, use_chai=False)
    solo, _ = _run(cfg, subs, use_chai=False, alone=True)
    for uid in solo:
        assert cont[uid].generated == solo[uid].generated, uid


def test_short_request_retires_early_and_slot_is_reused():
    cfg = _cfg(MHA_ARCH)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(3)]
    subs = [(prompts[0], 24),   # long: holds its slot for 24 tokens
            (prompts[1], 4),    # short: retires after 4
            (prompts[2], 4)]    # queued: must reuse the short one's slot
    done, eng = _run(cfg, subs, slots=2)
    long_req, short_req, queued = done[0], done[1], done[2]
    assert short_req.retire_step < long_req.retire_step
    assert queued.slot == short_req.slot
    assert queued.admit_step >= short_req.retire_step
    # the queued request ran while the long one was still decoding —
    # no cohort barrier
    assert queued.admit_step < long_req.retire_step
    assert queued.retire_step < long_req.retire_step
    # per-request timing is recorded
    for r in done.values():
        assert r.ttft >= 0 and r.latency >= r.ttft > 0


def test_phase_vector_tracks_slot_lifecycle():
    """The paged state's per-slot phase vector drives the machine:
    zero-init state is all FREE; constants are ordered FREE < PREFILL <
    WARMUP < CLUSTER < STEADY (the mixed step's mask relies on it)."""
    assert (chai_cache.PHASE_FREE < chai_cache.PHASE_PREFILL
            < chai_cache.PHASE_WARMUP < chai_cache.PHASE_CLUSTER
            < chai_cache.PHASE_STEADY)
    cfg = _cfg(MHA_ARCH)
    state = chai_cache.init_paged_state(cfg, 2, 16, page_size=8,
                                        dense_pages=9, chai_pages=5)
    assert state["phase"].shape == (2,)
    assert (np.asarray(state["phase"]) == chai_cache.PHASE_FREE).all()
    # dense and clustered K pools side by side, no dense rectangles
    assert "kvp" in state and "cp" in state and "chai_scores" in state
    assert "kg" not in state and "kg_chai" not in state


def test_prompt_bucket_rounding():
    from repro.serving.engine import ServingEngine
    assert ServingEngine._prompt_bucket(1, 64) == 1
    assert ServingEngine._prompt_bucket(3, 64) == 4
    assert ServingEngine._prompt_bucket(8, 64) == 8
    assert ServingEngine._prompt_bucket(9, 64) == 16
    assert ServingEngine._prompt_bucket(33, 64) == 64
    assert ServingEngine._prompt_bucket(60, 64) == 64   # capped at max_seq


@pytest.mark.slow
def test_prefill_jit_bucketing_compiles_per_bucket_not_per_length():
    """Regression: the engine keys one prefill jit per power-of-two
    prompt BUCKET (tail masked), not per exact/padded length — served
    batched or one request at a time — and the padded prefill does not
    change a single greedy token of the batched run against the same
    requests served alone."""
    cfg = _cfg(MHA_ARCH)
    rng = np.random.default_rng(3)
    lengths = [3, 5, 6, 7, 9, 12]          # buckets: {4, 8, 8, 8, 16, 16}
    subs = [(rng.integers(0, cfg.vocab_size, size=t), 8) for t in lengths]
    cont, eng = _run(cfg, subs)
    assert set(eng._slot_prefills) == {4, 8, 16}
    assert len(eng._slot_prefills) == 3    # O(log max_seq), not 6
    solo, eng_solo = _run(cfg, subs, alone=True)
    for uid in solo:
        assert cont[uid].generated == solo[uid].generated, uid
    # each bucket's jit compiled once (3 compiles, not one per distinct
    # prompt length)
    assert set(eng_solo._slot_prefills) == {4, 8, 16}
    assert all(fn._cache_size() == 1
               for fn in eng_solo._slot_prefills.values())


@pytest.mark.slow
def test_mixed_workload_throughput_beats_cohort():
    """Mixed-length workload: continuous batching needs strictly fewer
    batched decode steps than lockstep cohorts of the same size would
    (the step count is the hardware-independent throughput proxy;
    bench_latency measures wall time)."""
    cfg = _cfg(MHA_ARCH)
    rng = np.random.default_rng(2)
    subs = [(rng.integers(0, cfg.vocab_size, size=8), int(m))
            for m in rng.integers(4, 25, size=6)]
    _, eng_cont = _run(cfg, subs, slots=2, max_seq=64)
    # cohort lower bound on decode steps: each cohort runs max(max_new)
    sizes = [m for _, m in subs]
    cohort_steps = sum(max(sizes[i:i + 2]) for i in range(0, len(sizes), 2))
    assert eng_cont.steps_executed < cohort_steps
