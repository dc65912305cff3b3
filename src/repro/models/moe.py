"""Mixture-of-Experts FFN: top-k routing, capacity-based scatter dispatch.

TPU-idiomatic "dropping" MoE (GShard/MaxText style): tokens are scattered
into an (E, C, d) buffer (C = capacity), expert FFNs run as a single batched
einsum over the expert dim (shardable on the "model"/expert-parallel axis),
and results gather back with combine weights. Tokens over capacity drop to
the residual path. Includes shared experts (DeepSeekMoE) and the standard
load-balance + router-z auxiliary losses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import activation_fn


def expert_capacity(n_tokens, cfg):
    cap = int(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to multiple of 8, floor 8


def moe_ffn_ragged(x, p, cfg):
    """Dropless MoE via sort-by-expert + ``jax.lax.ragged_dot``.

    Exact (no capacity drops) and sequence-length independent — the serving
    engine's path, so prefill / decode / full-forward agree bitwise on
    routing. x: (B, T, d) -> (B, T, d).
    """
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n, d)
    act = activation_fn(cfg.activation)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_ids.reshape(-1)                          # (N*k,)
    order = jnp.argsort(flat_e)                              # stable
    tok_of = order // k                                      # source token
    xs = xf[tok_of]                                          # (N*k, d)
    group_sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, p["w_gate"], group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], group_sizes)
    yb = jax.lax.ragged_dot((act(g) * u).astype(xs.dtype), p["w_down"],
                            group_sizes)

    # Unsort and combine.
    unsorted = jnp.zeros((n * k, d), yb.dtype).at[order].set(yb)
    y = (unsorted.reshape(n, k, d).astype(jnp.float32)
         * gate_vals[..., None]).sum(axis=1).astype(x.dtype)

    if cfg.n_shared_experts > 0:
        sg = jnp.einsum("nd,df->nf", xf, p["shared_gate"])
        su = jnp.einsum("nd,df->nf", xf, p["shared_up"])
        y = y + jnp.einsum("nf,fd->nd", act(sg) * su, p["shared_down"])
    return y.reshape(b, t, d)


def moe_ffn_ep(x, p, cfg, ctx, *, return_aux=False):
    """Expert-parallel MoE: shard_map + all-to-all dispatch (GShard-style).

    Why: GSPMD cannot partition the capacity-buffer scatter (data-dependent
    indices crossing shards) and falls back to replicating tokens to every
    expert shard — measured 100–140 GiB/device all-gathers on the MoE train
    cells (EXPERIMENTS.md §Dry-run). This path makes the dispatch explicit:

      tokens sharded (batch over data axes, seq over the model axis)
      -> local top-k routing (router weights replicated)
      -> per-expert capacity buffer (E, C_loc, d), C_loc ~ k*n_loc*cf/E
      -> all_to_all over the model axis: (E, C_loc, d) -> (E_loc, M*C_loc, d)
      -> batched expert FFN with the *local* expert weights (E_loc, d, f)
      -> reverse all_to_all -> local unscatter + combine weights
      shared experts: tensor-parallel over the model axis (psum of partials)

    x: (B, T, d) with B divisible by prod(batch_axes) and T by the model
    axis. Falls back to ``moe_ffn`` when no mesh context / not divisible.
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    m = ctx.axis_size(ctx.model_axis)
    dp = 1
    for a in ctx.batch_axes:
        dp *= ctx.axis_size(a)
    b, t, d = x.shape
    e, k, f = cfg.n_experts, cfg.top_k, cfg.moe_d_ff
    if b % dp or t % m or e % m:
        return moe_ffn(x, p, cfg, return_aux=return_aux)
    act = activation_fn(cfg.activation)
    batch_spec = ctx.batch_axes if len(ctx.batch_axes) > 1 \
        else ctx.batch_axes[0]
    maxis = ctx.model_axis
    n_loc = (b // dp) * (t // m)
    cap = expert_capacity(n_loc, cfg)

    def body(xb, router, w_gate, w_up, w_down, shared):
        # xb: (B/dp, T/m, d); experts: (E/m, d, f); router: (d, E)
        bl, tl, _ = xb.shape
        n = bl * tl
        xf = xb.reshape(n, d)
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # local capacity scatter (identical math to moe_ffn)
        onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.int32)
        flat_oh = onehot.reshape(n * k, e)
        pos_in_expert = (jnp.cumsum(flat_oh, axis=0) - flat_oh)
        pos = (pos_in_expert * flat_oh).sum(-1).reshape(n, k)
        keep = pos < cap
        flat_idx = jnp.where(keep, expert_ids * cap + pos, e * cap)
        buf = jnp.zeros((e * cap + 1, d), dtype=xb.dtype)
        src = jnp.repeat(xf[:, None, :], k, axis=1).reshape(n * k, d)
        buf = buf.at[flat_idx.reshape(-1)].set(src, mode="drop")
        buf = buf[: e * cap].reshape(e, cap, d)

        # DISPATCH: (E, C, d) -> (E/m, m*C, d) across the model axis
        recv = jax.lax.all_to_all(buf, maxis, split_axis=0, concat_axis=1,
                                  tiled=True)
        g = jnp.einsum("ecd,edf->ecf", recv, w_gate)
        u = jnp.einsum("ecd,edf->ecf", recv, w_up)
        yb = jnp.einsum("ecf,efd->ecd", act(g) * u, w_down)
        # COMBINE: reverse all-to-all back to the owning token shard
        yb = jax.lax.all_to_all(yb, maxis, split_axis=1, concat_axis=0,
                                tiled=True)

        ybf = jnp.concatenate(
            [yb.reshape(e * cap, d), jnp.zeros((1, d), yb.dtype)], axis=0)
        gathered = ybf[flat_idx.reshape(-1)].reshape(n, k, d)
        w = (gate_vals * keep.astype(gate_vals.dtype))[..., None]
        y = (gathered.astype(jnp.float32) * w).sum(1).astype(xb.dtype)

        # shared experts: dense + small -> weights replicated, computed
        # per token shard. (TP partials would psum across the model axis,
        # but that axis shards *tokens* here — partials would mix shards.)
        if shared is not None:
            sg, su, sd = shared
            hs = act(jnp.einsum("nd,df->nf", xf, sg)) \
                * jnp.einsum("nd,df->nf", xf, su)
            y = y + jnp.einsum("nf,fd->nd", hs, sd).astype(xb.dtype)

        me = probs.mean(axis=0)
        ce = jax.nn.one_hot(expert_ids[:, 0], e).mean(axis=0)
        lb = e * jnp.sum(me * ce)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        # aux losses averaged over every shard
        all_axes = (*ctx.batch_axes, maxis)
        lb = jax.lax.pmean(lb, all_axes)
        z = jax.lax.pmean(z, all_axes)
        return y.reshape(bl, tl, d), lb, z

    shared_specs = None
    shared_args = None
    if cfg.n_shared_experts > 0:
        shared_specs = (P(None, None), P(None, None), P(None, None))
        shared_args = (p["shared_gate"], p["shared_up"], p["shared_down"])

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_spec, maxis, None),        # x: batch + seq shard
                  P(None, None),                      # router replicated
                  P(maxis, None, None),               # experts sharded on E
                  P(maxis, None, None),
                  P(maxis, None, None),
                  shared_specs),
        out_specs=(P(batch_spec, maxis, None), P(), P()),
        check_vma=False)
    y, lb, z = fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                  shared_args)
    if return_aux:
        return y, {"load_balance": lb, "router_z": z}
    return y


def moe_ffn(x, p, cfg, *, return_aux=False):
    """x: (B, T, d). p: layer-indexed MoE params.

    Returns y (B, T, d) and (optionally) aux loss dict.
    """
    b, t, d = x.shape
    n = b * t
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n, d)
    act = activation_fn(cfg.activation)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)          # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)              # renormalize

    cap = expert_capacity(n, cfg)
    # Position of each (token, choice) within its expert, by priority order.
    onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.int32)  # (N, k, E)
    flat_oh = onehot.reshape(n * k, e)
    pos_in_expert = (jnp.cumsum(flat_oh, axis=0) - flat_oh)  # (N*k, E)
    pos = (pos_in_expert * flat_oh).sum(-1).reshape(n, k)    # (N, k)
    keep = pos < cap                                         # (N, k)

    flat_idx = expert_ids * cap + pos                        # (N, k)
    flat_idx = jnp.where(keep, flat_idx, e * cap)            # overflow slot

    # Scatter tokens into the expert buffer (E*C+1, d); last row = dropped.
    buf = jnp.zeros((e * cap + 1, d), dtype=x.dtype)
    src = jnp.repeat(xf[:, None, :], k, axis=1).reshape(n * k, d)
    buf = buf.at[flat_idx.reshape(-1)].set(src, mode="drop")
    buf = buf[: e * cap].reshape(e, cap, d)

    # Batched expert FFN: (E, C, d) x (E, d, f) -> (E, C, f)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    yb = jnp.einsum("ecf,efd->ecd", act(g) * u, p["w_down"])  # (E, C, d)

    # Gather back with combine weights.
    ybf = jnp.concatenate(
        [yb.reshape(e * cap, d), jnp.zeros((1, d), yb.dtype)], axis=0)
    gathered = ybf[flat_idx.reshape(-1)].reshape(n, k, d)
    w = (gate_vals * keep.astype(gate_vals.dtype))[..., None]
    y = (gathered.astype(jnp.float32) * w).sum(axis=1).astype(x.dtype)

    # Shared experts (DeepSeekMoE): dense FFN over all tokens, added.
    if cfg.n_shared_experts > 0:
        sg = jnp.einsum("nd,df->nf", xf, p["shared_gate"])
        su = jnp.einsum("nd,df->nf", xf, p["shared_up"])
        y = y + jnp.einsum("nf,fd->nd", act(sg) * su, p["shared_down"])

    y = y.reshape(b, t, d)
    if not return_aux:
        return y
    # Load-balance loss (Switch): E * sum_e f_e * P_e; and router z-loss.
    me = probs.mean(axis=0)                                   # (E,)
    ce = jax.nn.one_hot(expert_ids[:, 0], e).mean(axis=0)     # top-1 fraction
    lb = e * jnp.sum(me * ce)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, {"load_balance": lb, "router_z": z}
