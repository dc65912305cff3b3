"""Shared low-level layers: norms, rotary embeddings, softcap, activations."""
from __future__ import annotations

import jax
import jax.numpy as jnp



def rms_norm(x, scale, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    out = (y * (1.0 + scale.astype(jnp.float32))).astype(dt)
    # barrier: pin the f32->model-dtype cast so SPMD reshardings after
    # the norm move 2-byte values, not the hoisted f32 intermediates
    # (halves activation all-gathers; EXPERIMENTS.md §Perf cell 2).
    return jax.lax.optimization_barrier(out)


def softcap(x, cap):
    """Gemma-2 style tanh softcap; identity when cap <= 0."""
    if cap and cap > 0:
        return cap * jnp.tanh(x / cap)
    return x


def activation_fn(name):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if name == "relu2":  # squared ReLU (nemotron)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------- rotary ----
def rope_freqs(head_dim, theta):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x, positions, theta):
    """LLaMA-style half-rotation RoPE.

    x: (..., T, n_heads, head_dim); positions: broadcastable to (..., T).
    """
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(x, w, softcap_value=0.0):
    logits = jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)
    return softcap(logits, softcap_value)
