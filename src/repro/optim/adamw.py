"""AdamW with global-norm clipping and cosine schedule (optax-free).

Optimizer state shards exactly like the parameters (the moment pytrees reuse
the param logical axes), so the dry-run's in_shardings cover it for free.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp



class AdamWState(NamedTuple):
    step: jnp.ndarray          # () int32
    m: dict
    v: dict


def init(params) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      m=jax.tree.map(zeros, params),
                      v=jax.tree.map(zeros, params))


def state_structs(param_structs, param_logical):
    """ShapeDtypeStructs + logical axes matching ``init`` (for the dry-run)."""
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    shapes = AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        m=jax.tree.map(f32, param_structs),
        v=jax.tree.map(f32, param_structs))
    from repro.sharding.rules import Ax
    logical = AdamWState(step=Ax(), m=param_logical, v=param_logical)
    return shapes, logical


def cosine_lr(step, *, peak=3e-4, warmup=100, total=10000, floor=0.1):
    warm = peak * (step + 1) / warmup
    frac = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0,
                    1.0)
    cos = peak * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warmup, warm, cos)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def update(grads, state: AdamWState, params, *, lr=None, b1=0.9, b2=0.95,
           eps=1e-8, weight_decay=0.1, clip_norm=1.0):
    step = state.step + 1
    if lr is None:
        lr = cosine_lr(state.step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g.astype(jnp.float32) * scale, grads)

    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state.m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g),
                     state.v, grads)
    t = step.astype(jnp.float32)
    mhat_c = 1.0 / (1 - b1 ** t)
    vhat_c = 1.0 / (1 - b2 ** t)

    def upd(p, m_, v_):
        u = (m_ * mhat_c) / (jnp.sqrt(v_ * vhat_c) + eps)
        u = u + weight_decay * p.astype(jnp.float32)
        out = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        # barrier: keep the f32->bf16 convert BEFORE the ZeRO all-gather
        # (XLA otherwise hoists the convert past it and gathers f32 —
        # 2x wire bytes; EXPERIMENTS.md §Perf iteration 4).
        return jax.lax.optimization_barrier(out)

    new_params = jax.tree.map(upd, params, m, v)
    return new_params, AdamWState(step=step, m=m, v=v), {
        "grad_norm": gnorm, "lr": lr}
