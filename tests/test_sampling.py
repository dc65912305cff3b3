"""Per-request sampling: SamplingParams validation, the batched device
sampler, and batch-independence of seeded runs.

The load-bearing guarantees:

* ``temperature=0`` is BITWISE the raw-logits argmax — the engine's
  historical greedy path — regardless of top_k/top_p/seed, so every
  greedy parity/snapshot-replay guarantee survives the sampler.
* Token n of a request draws from ``fold_in(PRNGKey(seed), n)``: seeded
  temperature/top-k/top-p runs are reproducible run-to-run AND between
  requests served together and alone (different slot placements and
  batch compositions — same tokens).
* Stop token ids and max_new_tokens finish requests identically served
  together or alone.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.launch import steps as steps_mod
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.sampling import SamplingParams, finish_reason, scan_finish

MHA_ARCH = "chai-llama-7b"


def _cfg():
    cfg = reduced(get_config(MHA_ARCH), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32")
    return cfg.with_chai(enabled=True, warmup_tokens=3)


def _run(cfg, subs, *, slots=2, alone=False, **ecfg_kw):
    """Serve ``subs`` together, or with ``alone`` each by itself: one
    ``run()`` per request, the engine's other slots FREE."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=64,
                                     **ecfg_kw))
    subs = list(enumerate(subs))
    for wave in ([[s] for s in subs] if alone else [subs]):
        for i, (prompt, sp) in wave:
            eng.submit(prompt, max_new_tokens=sp.max_new_tokens, uid=i,
                       sampling=sp)
        done = eng.run()
    assert len(done) == len(subs)
    return {r.uid: r for r in done}


# ------------------------------------------------------------ unit ---------
def test_sampling_params_validation():
    SamplingParams(temperature=0.7, top_k=5, top_p=0.9)     # ok
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.5).greedy


def test_finish_reason_stop_and_length():
    sp = SamplingParams(stop_token_ids=(9,))
    assert finish_reason([1, 2], sp, 8) == ""
    assert finish_reason([1, 9], sp, 8) == "stop"
    assert finish_reason([1, 2], sp, 2) == "length"
    # stop wins when both trigger on the same token
    assert finish_reason([1, 9], sp, 2) == "stop"
    toks, reason = scan_finish([1, 9, 3, 4], sp, 8)
    assert toks == [1, 9] and reason == "stop"
    # stop strings via a detokenizer
    detok = lambda ids: " ".join(map(str, ids))
    sps = SamplingParams(stop=("2 3",))
    toks, reason = scan_finish([1, 2, 3, 4], sps, 8, detok)
    assert toks == [1, 2, 3] and reason == "stop"


def test_sampler_temperature_zero_is_bitwise_argmax():
    """The device sampler's greedy lane == raw-logits argmax, bit for
    bit, independent of the other knobs (the old engine ``_sample``)."""
    sampler = jax.jit(steps_mod.make_sampler())
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    old_greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for k, p, seed in ((0, 1.0, 0), (3, 0.5, 7), (64, 0.01, 123)):
        out = sampler(logits,
                      jnp.zeros((8,), jnp.float32),
                      jnp.full((8,), k, jnp.int32),
                      jnp.full((8,), p, jnp.float32),
                      jnp.full((8,), seed, jnp.uint32),
                      jnp.arange(8, dtype=jnp.int32))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(old_greedy))


def test_sampler_top_k_top_p_restrict_support():
    """top_k=1 == argmax even at high temperature; top-k/top-p masks
    keep draws inside the allowed support; draws are seed-deterministic
    and vary with the count."""
    sampler = jax.jit(steps_mod.make_sampler())
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    ones = jnp.ones((4,), jnp.float32)

    def draw(temp, k, p, seed, count):
        return np.asarray(sampler(
            logits, ones * temp, jnp.full((4,), k, jnp.int32),
            ones * p, jnp.full((4,), seed, jnp.uint32),
            jnp.full((4,), count, jnp.int32)))

    np.testing.assert_array_equal(
        draw(5.0, 1, 1.0, 0, 0), np.asarray(jnp.argmax(logits, -1)))
    top8 = np.argsort(-np.asarray(logits), axis=-1)[:, :8]
    for seed in range(5):
        toks = draw(1.0, 8, 1.0, seed, 0)
        assert all(toks[i] in top8[i] for i in range(4))
    # deterministic per (seed, count); different counts decorrelate
    np.testing.assert_array_equal(draw(1.0, 0, 0.9, 3, 5),
                                  draw(1.0, 0, 0.9, 3, 5))
    samples = {tuple(draw(1.5, 0, 1.0, 3, c)) for c in range(8)}
    assert len(samples) > 1


# ------------------------------------------------- engine-level parity -----
@pytest.mark.slow
def test_seeded_sampling_reproducible_across_schedulers():
    """Same prompts + per-request (temperature, top_k, top_p, seed):
    token-for-token identical across repeat runs and between the
    requests served together and each served alone."""
    cfg = _cfg()
    rng = np.random.default_rng(2)
    sps = [SamplingParams(temperature=0.8, top_k=16, top_p=0.95,
                          seed=100 + i, max_new_tokens=m)
           for i, m in enumerate((12, 5, 9, 7))]
    subs = [(rng.integers(0, cfg.vocab_size, size=8), sp) for sp in sps]
    cont = _run(cfg, subs)
    cont2 = _run(cfg, subs)
    alone = _run(cfg, subs, alone=True)
    for uid in cont:
        assert cont[uid].generated == cont2[uid].generated, uid   # rerun
        assert cont[uid].generated == alone[uid].generated, uid   # batch
        assert len(cont[uid].generated) == sps[uid].max_new_tokens
        assert cont[uid].finish_reason == "length"
    # different seeds actually diverge (the sampler is not greedy)
    alt = [(p, SamplingParams(temperature=0.8, top_k=16, top_p=0.95,
                              seed=sp.seed + 1000,
                              max_new_tokens=sp.max_new_tokens))
           for p, sp in subs]
    cont_alt = _run(cfg, alt)
    assert any(cont_alt[u].generated != cont[u].generated for u in cont)


@pytest.mark.slow
def test_temperature_zero_engine_matches_legacy_greedy():
    """An explicit temperature=0 SamplingParams (whatever the other
    knobs say) generates exactly the tokens the default greedy submit()
    path does — the bit-identical guarantee snapshot replay rests on."""
    cfg = _cfg()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(3)]
    legacy = _run(cfg, [(p, SamplingParams(max_new_tokens=10))
                        for p in prompts])
    explicit = _run(cfg,
                    [(p, SamplingParams(temperature=0.0, top_k=5,
                                        top_p=0.5, seed=42,
                                        max_new_tokens=10))
                     for p in prompts])
    for uid in legacy:
        assert legacy[uid].generated == explicit[uid].generated, uid


@pytest.mark.slow
def test_stop_tokens_finish_identically_across_schedulers():
    """A stop token retires the request early (reason "stop", stop token
    kept) with identical truncation served together and alone."""
    cfg = _cfg()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(3)]
    # pick stop ids from an unconstrained greedy run so they actually hit
    probe = _run(cfg, [(p, SamplingParams(max_new_tokens=12))
                       for p in prompts])
    stops = tuple(int(probe[u].generated[5]) for u in probe)
    sps = [SamplingParams(stop_token_ids=stops, max_new_tokens=12)
           for _ in prompts]
    cont = _run(cfg, list(zip(prompts, sps)))
    alone = _run(cfg, list(zip(prompts, sps)), alone=True)
    hit_early = 0
    for uid in cont:
        assert cont[uid].generated == alone[uid].generated, uid
        assert cont[uid].finish_reason == alone[uid].finish_reason, uid
        if cont[uid].finish_reason == "stop":
            hit_early += 1
            assert cont[uid].generated[-1] in stops
            assert len(cont[uid].generated) < 12
    assert hit_early > 0        # the stop ids were chosen to trigger
