"""RWKV-6 "Finch" block: data-dependent-decay linear attention + channel mix.

Full-sequence path is **chunkwise-parallel** (flash-linear-attention style):
sequential ``lax.scan`` over chunks carrying the (B, H, dk, dv) state, with
MXU-friendly matmuls inside each chunk. Intra-chunk relative decays use the
factored form R~ = r * exp(logW_{i-1}), K~ = k * exp(-logW_j); per-step
log-decay is clamped to >= -LOG_CLAMP so the factored exponentials stay in
fp32 range for the chunk length used (documented in DESIGN.md §3).
Decode is the plain one-step recurrence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


CHUNK = 32
LOG_CLAMP = 1.5          # per-step |log w| cap; CHUNK*LOG_CLAMP = 48 < 88


def _token_shift_full(x, last):
    """x: (B, T, d); last: (B, d) previous token (state) -> shifted x."""
    prev = jnp.concatenate([last[:, None], x[:, :-1]], axis=1)
    return prev


def _decays(xw, p, cfg):
    """Data-dependent per-channel log decay, clamped. xw: (..., d)."""
    lora = jnp.einsum("...d,dr->...r", xw, p["w_decay_a"])
    lora = jnp.einsum("...r,rd->...d", jnp.tanh(lora), p["w_decay_b"])
    logw = -jnp.exp(jnp.clip(p["decay_base"] + lora, -8.0, 1.0))
    return jnp.maximum(logw.astype(jnp.float32), -LOG_CLAMP)


def _pin_replicated_d(t):
    """Keep (B, T, d) activations replicated on d (batch stays sharded).

    Without this GSPMD computes the lerp d-sharded and ALL-GATHERS it in
    f32 before each projection matmul — 6 x 512 MB/layer of pure wire
    waste on the prefill cells (EXPERIMENTS.md §Perf cell 2). Only active
    under a sharding ctx (production meshes); no-op on CPU tests.
    """
    from repro.sharding.context import current_ctx
    ctx = current_ctx()
    if ctx is None or t.ndim != 3:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    ba = ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(ctx.mesh, P(ba, None, None)))


def _project(x, xs, p, cfg):
    """Lerped projections. x: (..., d); xs: shifted x."""
    mu = p["mu"]  # (5, d): r, k, v, w, g

    def lerp(i):
        # NOTE: a with_sharding_constraint pin here was measured WORSE
        # (+10% collective bytes — it adds a resharding instead of
        # changing the producer's layout; EXPERIMENTS.md §Perf cell 2 it1)
        return x + (xs - x) * mu[i]

    r = jnp.einsum("...d,de->...e", lerp(0), p["w_r"])
    k = jnp.einsum("...d,de->...e", lerp(1), p["w_k"])
    v = jnp.einsum("...d,de->...e", lerp(2), p["w_v"])
    logw = _decays(lerp(3), p, cfg)
    g = jax.nn.silu(jnp.einsum("...d,de->...e", lerp(4), p["w_g"]))
    return r, k, v, logw, g


def _heads(x, n_heads, hd):
    return x.reshape(*x.shape[:-1], n_heads, hd)


def _group_norm(o, scale, eps):
    """Per-head group norm on (..., H, hd)."""
    mean = o.mean(axis=-1, keepdims=True)
    var = o.var(axis=-1, keepdims=True)
    o = (o - mean) * jax.lax.rsqrt(var + eps)
    return o * (1.0 + scale)


def rwkv_time_mix_fullseq(x, p, cfg, state):
    """x: (B, T, d); state: dict(shift=(B, d), wkv=(B, H, dk, dv))."""
    bsz, t, d = x.shape
    nh, hd = cfg.n_rwkv_heads, cfg.rwkv_head_dim
    xs = _token_shift_full(x, state["shift"])
    r, k, v, logw, g = _project(x, xs, p, cfg)
    r, k, v = (_heads(a, nh, hd).astype(jnp.float32) for a in (r, k, v))
    logw = _heads(logw, nh, hd)                              # (B, T, H, hd)
    u = p["u"].astype(jnp.float32)                           # (H, hd)

    c = min(CHUNK, t)
    assert t % c == 0, (t, c)
    nc = t // c

    def chunked(a):  # (B, T, H, X) -> (nc, B, H, c, X)
        return a.reshape(bsz, nc, c, nh, -1).transpose(1, 0, 3, 2, 4)

    rc, kc, vc, wc = map(chunked, (r, k, v, logw))

    def step(s, xs_):
        r_i, k_i, v_i, lw_i = xs_                            # (B, H, c, hd)
        cum = jnp.cumsum(lw_i, axis=2)                       # logW_i (inclusive)
        cum_prev = cum - lw_i                                # logW_{i-1}
        r_t = r_i * jnp.exp(cum_prev)
        k_t = k_i * jnp.exp(-cum)
        att = jnp.einsum("bhid,bhjd->bhij", r_t, k_t)
        mask = jnp.tril(jnp.ones((c, c), bool), k=-1)
        att = jnp.where(mask, att, 0.0)
        bonus = jnp.einsum("bhid,bhid->bhi", r_i * u[None, :, None, :], k_i)
        o = jnp.einsum("bhij,bhjv->bhiv", att, v_i)          # intra
        o += jnp.einsum("bhid,bhdv->bhiv", r_t, s)           # cross-chunk
        o += bonus[..., None] * v_i                          # current token
        decay_all = jnp.exp(cum[:, :, -1:, :])               # exp(logW_c)
        k_rem = k_i * jnp.exp(cum[:, :, -1:, :] - cum)       # W_c / W_j
        s_new = s * jnp.swapaxes(decay_all, -1, -2) \
            + jnp.einsum("bhjd,bhjv->bhdv", k_rem, v_i)
        return s_new, o

    # state stores S with shape (B, H, dk, dv); decay applies on dk axis.
    s0 = state["wkv"].astype(jnp.float32)
    s_fin, o = jax.lax.scan(step, s0, (rc, kc, vc, wc))
    o = o.transpose(1, 0, 3, 2, 4).reshape(bsz, t, nh, hd)   # (B, T, H, hd)
    o = _group_norm(o, p["ln_x"].reshape(nh, hd), cfg.norm_eps)
    o = (o.reshape(bsz, t, d) * g.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("btd,de->bte", o, p["w_o"])
    # barrier: down-proj output must all-reduce in bf16; XLA otherwise
    # hoists the residual/norm f32 convert before the AR (2x wire bytes).
    y = jax.lax.optimization_barrier(y)
    return y, {"shift": x[:, -1], "wkv": s_fin.astype(x.dtype)}


def rwkv_time_mix_decode(x, p, cfg, state):
    """x: (B, d); one-step recurrence."""
    bsz, d = x.shape
    nh, hd = cfg.n_rwkv_heads, cfg.rwkv_head_dim
    xs = state["shift"]
    r, k, v, logw, g = _project(x, xs, p, cfg)
    r, k, v = (_heads(a, nh, hd).astype(jnp.float32) for a in (r, k, v))
    w = jnp.exp(_heads(logw, nh, hd))                        # (B, H, hd)
    u = p["u"].astype(jnp.float32)
    s = state["wkv"].astype(jnp.float32)                     # (B, H, dk, dv)
    kv = jnp.einsum("bhd,bhv->bhdv", k, v)
    o = jnp.einsum("bhd,bhdv->bhv", r, s + u[None, :, :, None] * kv)
    s_new = s * w[..., None] + kv
    o = _group_norm(o, p["ln_x"].reshape(nh, hd), cfg.norm_eps)
    o = (o.reshape(bsz, d) * g.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bd,de->be", o, p["w_o"])
    return y, {"shift": x, "wkv": s_new.astype(x.dtype)}


def rwkv_channel_mix_fullseq(x, p, last):
    xs = _token_shift_full(x, last)
    mu = p["cmu"]
    xk = x + (xs - x) * mu[0]
    xr = x + (xs - x) * mu[1]
    k = jnp.square(jax.nn.relu(jnp.einsum("...d,df->...f", xk, p["c_k"])))
    kv = jnp.einsum("...f,fd->...d", k, p["c_v"])
    kv = jax.lax.optimization_barrier(kv)    # bf16 AR (see time-mix)
    r = jax.nn.sigmoid(jnp.einsum("...d,de->...e", xr, p["c_r"]))
    return r * kv, x[:, -1]


def rwkv_channel_mix_decode(x, p, last):
    mu = p["cmu"]
    xk = x + (last - x) * mu[0]
    xr = x + (last - x) * mu[1]
    k = jnp.square(jax.nn.relu(jnp.einsum("bd,df->bf", xk, p["c_k"])))
    kv = jnp.einsum("bf,fd->bd", k, p["c_v"])
    r = jax.nn.sigmoid(jnp.einsum("bd,de->be", xr, p["c_r"]))
    return r * kv, x
