"""Integration: fault-tolerant trainer + CHAI serving engine end-to-end."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return reduced(get_config("chai-llama-7b"), n_layers=2, d_model=32,
                   n_heads=4, d_ff=64, vocab=128).replace(dtype="float32")


def _data_cfg(vocab):
    return DataConfig(vocab_size=vocab, seq_len=32, global_batch=4)


# ---------------------------------------------------------------- train ----
def test_trainer_loss_decreases(tmp_path):
    cfg = _tiny_cfg()
    tcfg = TrainerConfig(total_steps=30, ckpt_every=100, log_every=100,
                         ckpt_dir=str(tmp_path))
    tr = Trainer(cfg, _data_cfg(cfg.vocab_size), tcfg)
    state = tr.init_state()
    batch0 = tr.pipe.global_batch_array(0)
    _, m0 = tr._one_step(state, batch0)
    state, metrics = tr.run()
    assert float(metrics["loss"]) < float(m0["loss"]) - 0.3


def test_trainer_restart_resumes_exactly(tmp_path):
    """Train 20 straight vs 10 + restart + 10: identical final loss
    (stateless-seeded data + checkpointed optimizer => bitwise resume)."""
    cfg = _tiny_cfg()
    d = _data_cfg(cfg.vocab_size)

    t1 = Trainer(cfg, d, TrainerConfig(total_steps=20, ckpt_every=100,
                                       log_every=100,
                                       ckpt_dir=str(tmp_path / "a")))
    _, m_straight = t1.run()

    kw = dict(total_steps=20, ckpt_every=10, log_every=100,
              ckpt_dir=str(tmp_path / "b"))
    t2 = Trainer(cfg, d, TrainerConfig(**kw))
    t2.run(max_steps=10)                      # "crash" after step 10
    t3 = Trainer(cfg, d, TrainerConfig(**kw))  # fresh process restarts
    _, m_resumed = t3.run()
    np.testing.assert_allclose(float(m_straight["loss"]),
                               float(m_resumed["loss"]), rtol=1e-5)


def test_trainer_straggler_hook_fires(tmp_path):
    cfg = _tiny_cfg()
    seen = []
    tr = Trainer(cfg, _data_cfg(cfg.vocab_size),
                 TrainerConfig(total_steps=8, ckpt_every=100, log_every=100,
                               ckpt_dir=str(tmp_path), straggler_factor=2.0),
                 step_delay_hook=lambda s: 0.5 if s == 5 else 0.0,
                 on_straggler=lambda s, dt: seen.append(s))
    tr.run()
    assert 5 in seen


def test_microbatched_matches_fused(tmp_path):
    """Gradient accumulation (2 microbatches) == fused step (same batch)."""
    from repro.launch import steps as steps_mod
    from repro.optim import adamw
    from repro.train import train_step as ts_mod
    cfg = _tiny_cfg()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                                   jnp.int32)}
    fused = steps_mod.make_train_step(cfg, remat=False)
    micro = ts_mod.make_microbatched_train_step(cfg, n_micro=2, remat=False)
    p1, _, m1 = fused(params, adamw.init(params), batch)
    p2, _, m2 = micro(params, adamw.init(params), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------- serve ----
def _engine(cfg, **kw):
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(batch_slots=2, max_seq=64, **kw)
    return ServingEngine(cfg, params, ecfg)


def test_engine_generates_all_requests(rng):
    cfg = _tiny_cfg().with_chai(enabled=True)
    eng = _engine(cfg)
    for i in range(4):
        eng.submit(rng.integers(0, cfg.vocab_size, size=8),
                   max_new_tokens=10, uid=i)
    done = eng.run()
    assert len(done) == 4
    for r in done:
        assert len(r.generated) == 10
        assert r.ttft >= 0 and r.latency >= r.ttft


def test_engine_warmup_matches_mha(rng):
    """Tokens generated during the MHA warmup phase are identical with
    CHAI on and off (CHAI only kicks in after warmup_tokens)."""
    cfg = _tiny_cfg().with_chai(enabled=True, warmup_tokens=5)
    prompts = [rng.integers(0, cfg.vocab_size, size=8) for _ in range(2)]

    outs = {}
    for use_chai in (True, False):
        eng = _engine(cfg, use_chai=use_chai)
        for i, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=8, uid=i)
        done = sorted(eng.run(), key=lambda r: r.uid)
        outs[use_chai] = [r.generated for r in done]
    warm = cfg.chai.warmup_tokens
    for g_chai, g_mha in zip(outs[True], outs[False]):
        assert g_chai[:warm + 1] == g_mha[:warm + 1]


def test_engine_kv_bytes_reports_saving():
    cfg = reduced(get_config("chai-llama-7b")).with_chai(enabled=True)
    eng = _engine(cfg.replace(dtype="float32"))
    assert eng.kv_bytes(chai=True) < eng.kv_bytes(chai=False)
