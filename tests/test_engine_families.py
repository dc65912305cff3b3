"""Every model family on the engine's one paged layout.

The engine builds ``paged_state_structs`` for every model: page pools
exist exactly when the model has a global attention layer, and a model
with only local attention or recurrent layers keeps its rings and
recurrent state per slot with no pools at all. Each family must serve
several requests on fewer slots with the greedy tokens of a plain
batch-1 loop over the model's own prefill and ``decode_step`` (local
windows below ``max_seq``, so the rings wrap) — and, with CHAI on, the
same tokens batched as alone (one request at a time on the same slots)
through WARMUP, CLUSTER and STEADY.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine

MAX_SEQ = 64
FAMILIES = ["deepseek-moe-16b", "gemma2-9b", "gemma3-4b",
            "h2o-danube-1.8b", "recurrentgemma-9b", "rwkv6-1.6b"]


def _cfg(arch):
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32")
    assert cfg.window_size < MAX_SEQ
    return cfg


def _submissions(cfg, seed=0):
    """Three requests whose prompts fall in three prefill buckets, two
    of them past the local window."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, size=t), m)
            for t, m in ((11, 14), (5, 6), (20, 9))]


def _serve(cfg, params, subs, *, use_chai, alone=False):
    """Three requests on two slots: together, or with ``alone`` one
    ``run()`` each."""
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=2, max_seq=MAX_SEQ,
                                     use_chai=use_chai, page_size=16,
                                     audit_level="deep"))
    subs = list(enumerate(subs))
    for wave in ([[s] for s in subs] if alone else [subs]):
        for i, (prompt, max_new) in wave:
            eng.submit(prompt, max_new_tokens=max_new, uid=i)
        eng.run()
    done = {r.uid: r for r in eng.done}
    assert len(done) == len(subs)
    return done, eng


def _greedy_reference(cfg, params, prompt, max_new):
    """Batch-1 greedy loop over the model's own unpadded prefill and
    ``decode_step`` on its dense decode state."""
    state = tfm.init_decode_state(cfg, 1, MAX_SEQ)
    logits, state, _ = tfm.forward_fullseq(
        params, cfg, jnp.asarray(prompt, jnp.int32)[None], state=state,
        logits_slice="last")
    step = jax.jit(lambda p, t, s: tfm.decode_step(p, cfg, t, s))
    toks = [int(jnp.argmax(logits[0, -1]))]
    while len(toks) < max_new:
        logits, state = step(params, jnp.asarray(toks[-1:], jnp.int32),
                             state)
        toks.append(int(jnp.argmax(logits[0])))
    return toks


@pytest.mark.slow
@pytest.mark.parametrize("arch", FAMILIES)
def test_engine_serves_family(arch):
    cfg = _cfg(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    subs = _submissions(cfg)
    done, eng = _serve(cfg, params, subs, use_chai=False)
    has_pools = cfg.n_global_layers > 0
    assert ("kvp" in eng._dev_state) == has_pools
    assert (eng.dense_pool is not None) == has_pools
    assert (eng.tiers is not None) == has_pools
    for uid, (prompt, max_new) in enumerate(subs):
        assert done[uid].generated == _greedy_reference(
            cfg, params, prompt, max_new), uid


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma2-9b", "h2o-danube-1.8b",
                                  "recurrentgemma-9b"])
def test_engine_serves_family_chai(arch):
    cfg = _cfg(arch).with_chai(enabled=True, warmup_tokens=3)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    subs = _submissions(cfg, seed=1)
    batched, eng = _serve(cfg, params, subs, use_chai=True)
    solo, _ = _serve(cfg, params, subs, use_chai=True, alone=True)
    assert eng.chai_on and eng.cluster_transitions == len(subs)
    for uid in solo:
        assert batched[uid].generated == solo[uid].generated, uid
        assert len(batched[uid].generated) == subs[uid][1]


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "rwkv6-1.6b"])
def test_kv_bytes_without_pools(arch):
    """A model with no pools allocates no pages: ``kv_bytes()`` reads its
    local rings alone (0 without them), and the reservation is the
    same."""
    cfg = _cfg(arch)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=2, max_seq=MAX_SEQ,
                                     use_chai=False))
    rings = (2 * cfg.n_local_layers * 2 * cfg.n_kv_heads
             * cfg.window_size * cfg.head_dim * 4)
    assert eng.dense_pool is None and eng.chai_pool is None
    assert eng.kv_bytes() == eng.kv_bytes_capacity() == rings
    assert (rings > 0) == (arch == "h2o-danube-1.8b")


def test_priority_arrival_waits_without_pools():
    """With no pools there is no host tier to swap a victim through: a
    higher-priority arrival waits for a free slot instead of preempting,
    and both requests finish."""
    cfg = _cfg("h2o-danube-1.8b")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=1, max_seq=MAX_SEQ,
                                     use_chai=False))
    (p_low, _), (p_high, _), _ = _submissions(cfg)
    low = eng.add_request(p_low, max_new_tokens=8, priority=0)
    eng.step()
    high = eng.add_request(p_high, max_new_tokens=4, priority=5)
    eng.run()
    assert eng.preemptions == 0 and low.preemptions == 0
    assert low.finish_reason == high.finish_reason == "length"
    assert high.admit_step >= low.retire_step
