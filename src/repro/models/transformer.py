"""Heterogeneous scan-over-layers decoder supporting all assigned archs.

Design (DESIGN.md §3.5):
  * Parameters live in **stacked groups** — one stack per layer family
    (attention / dense-FFN / MoE / RG-LRU / RWKV), stacked over the layers
    that use that family. HLO size is therefore layer-count independent.
  * A single ``lax.scan`` walks layers; per-layer int32 arrays carry the
    mixer/FFN kind and the index into each group stack; ``lax.switch``
    dispatches (only kinds present in the config are lowered).
  * A declarative **param table** generates params, ShapeDtypeStructs (for
    the allocation-free dry-run) and logical sharding axes from one source.

Three entry points: ``forward_fullseq`` (train & prefill), ``decode_step``
(one token against a mutable state), and ``init_decode_state``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN_GLOBAL, ATTN_LOCAL, FFN_DENSE, FFN_MOE,
                                RGLRU, RWKV, ModelConfig)
from repro.models import attention as attn_mod
from repro.models import frontends, mlp, moe, rglru, rwkv
from repro.models.layers import embed_lookup, rms_norm, softcap, unembed
from repro.sharding.rules import Ax

# ---------------------------------------------------------------------------
# Param table
# ---------------------------------------------------------------------------

MIXER_KINDS = {ATTN_GLOBAL: 0, ATTN_LOCAL: 1, RGLRU: 2, RWKV: 3}
FFN_KIND_DENSE, FFN_KIND_MOE, FFN_KIND_CMIX = 0, 1, 2


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def param_table(cfg: ModelConfig):
    """Returns {group: {name: (shape, Ax(logical...), init_scale)}}."""
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    nA, nG, nL = cfg.n_attn_layers, cfg.n_global_layers, cfg.n_local_layers
    nR, nW = cfg.n_rec_layers, cfg.n_rwkv_layers
    nD = sum(1 for lt, ft in zip(cfg.layer_types, cfg.ffn_types)
             if ft == FFN_DENSE and lt != RWKV)
    nM = cfg.n_moe_ffn
    t: Dict[str, Dict[str, tuple]] = {}

    t["embed"] = {"tok": ((cfg.vocab_size, d), Ax("vocab", "embed"), 0.02)}
    if cfg.frontend != "none":
        t["frontend"] = {"adapter": ((d, d), Ax("embed", "embed_tp"),
                                     d ** -0.5)}
    if not cfg.tie_embeddings:
        t["unembed"] = {"w": ((d, cfg.vocab_size), Ax("embed", "vocab"),
                              d ** -0.5)}
    t["final_norm"] = {"scale": ((d,), Ax("embed"), 0.0)}

    if nA:
        g = {
            "ln": ((nA, d), Ax("layers", "embed"), 0.0),
            "wq": ((nA, d, h, hd), Ax("layers", "embed", "heads", "head_dim"),
                   d ** -0.5),
            "wk": ((nA, d, kv, hd),
                   Ax("layers", "embed", "kv_heads", "head_dim"), d ** -0.5),
            "wv": ((nA, d, kv, hd),
                   Ax("layers", "embed", "kv_heads", "head_dim"), d ** -0.5),
            "wo": ((nA, h, hd, d), Ax("layers", "heads", "head_dim", "embed"),
                   (h * hd) ** -0.5),
        }
        if cfg.qk_norm:
            g["q_norm"] = ((nA, hd), Ax("layers", "head_dim"), 0.0)
            g["k_norm"] = ((nA, hd), Ax("layers", "head_dim"), 0.0)
        t["attn"] = g

    if nD:
        g = {
            "ln": ((nD, d), Ax("layers", "embed"), 0.0),
            "w_up": ((nD, d, cfg.d_ff), Ax("layers", "embed", "mlp"),
                     d ** -0.5),
            "w_down": ((nD, cfg.d_ff, d), Ax("layers", "mlp", "embed"),
                       cfg.d_ff ** -0.5),
        }
        if cfg.gated_mlp:
            g["w_gate"] = ((nD, d, cfg.d_ff), Ax("layers", "embed", "mlp"),
                           d ** -0.5)
        t["ffn"] = g

    if nM:
        fe, e = cfg.moe_d_ff, cfg.n_experts
        g = {
            "ln": ((nM, d), Ax("layers", "embed"), 0.0),
            "router": ((nM, d, e), Ax("layers", "embed", "experts"),
                       d ** -0.5),
            "w_gate": ((nM, e, d, fe),
                       Ax("layers", "experts", "embed", "expert_mlp"),
                       d ** -0.5),
            "w_up": ((nM, e, d, fe),
                     Ax("layers", "experts", "embed", "expert_mlp"),
                     d ** -0.5),
            "w_down": ((nM, e, fe, d),
                       Ax("layers", "experts", "expert_mlp", "embed"),
                       fe ** -0.5),
        }
        if cfg.n_shared_experts:
            sf = cfg.n_shared_experts * fe
            g["shared_gate"] = ((nM, d, sf), Ax("layers", "embed", "mlp"),
                                d ** -0.5)
            g["shared_up"] = ((nM, d, sf), Ax("layers", "embed", "mlp"),
                              d ** -0.5)
            g["shared_down"] = ((nM, sf, d), Ax("layers", "mlp", "embed"),
                                sf ** -0.5)
        t["moe"] = g

    if nR:
        rw_, cw = cfg.rnn_width, cfg.conv_width
        t["rglru"] = {
            "ln": ((nR, d), Ax("layers", "embed"), 0.0),
            "w_x": ((nR, d, rw_), Ax("layers", "embed", "rnn"), d ** -0.5),
            "w_gate": ((nR, d, rw_), Ax("layers", "embed", "rnn"), d ** -0.5),
            "conv_w": ((nR, cw, rw_), Ax("layers", "conv", "rnn"),
                       cw ** -0.5),
            "conv_b": ((nR, rw_), Ax("layers", "rnn"), 0.0),
            "w_a": ((nR, rw_, rw_), Ax("layers", "rnn", "embed_tp"),
                    rw_ ** -0.5),
            "w_i": ((nR, rw_, rw_), Ax("layers", "rnn", "embed_tp"),
                    rw_ ** -0.5),
            "log_lambda": ((nR, rw_), Ax("layers", "rnn"), 0.5),
            "w_out": ((nR, rw_, d), Ax("layers", "rnn", "embed"),
                      rw_ ** -0.5),
        }

    if nW:
        lora = 64
        t["rwkv"] = {
            "ln1": ((nW, d), Ax("layers", "embed"), 0.0),
            "ln2": ((nW, d), Ax("layers", "embed"), 0.0),
            "mu": ((nW, 5, d), Ax("layers", None, "embed"), 0.3),
            "w_r": ((nW, d, d), Ax("layers", "embed", "embed_tp"), d ** -0.5),
            "w_k": ((nW, d, d), Ax("layers", "embed", "embed_tp"), d ** -0.5),
            "w_v": ((nW, d, d), Ax("layers", "embed", "embed_tp"), d ** -0.5),
            "w_g": ((nW, d, d), Ax("layers", "embed", "embed_tp"), d ** -0.5),
            "w_decay_a": ((nW, d, lora), Ax("layers", "embed", "lora"),
                          d ** -0.5),
            "w_decay_b": ((nW, lora, d), Ax("layers", "lora", "embed"), 0.01),
            "decay_base": ((nW, d), Ax("layers", "embed"), 0.5),
            "u": ((nW, cfg.n_rwkv_heads, cfg.rwkv_head_dim),
                  Ax("layers", "heads", "head_dim"), 0.5),
            "w_o": ((nW, d, d), Ax("layers", "embed_tp", "embed"), d ** -0.5),
            "ln_x": ((nW, d), Ax("layers", "embed"), 0.0),
            "cmu": ((nW, 2, d), Ax("layers", None, "embed"), 0.3),
            "c_k": ((nW, d, cfg.d_ff), Ax("layers", "embed", "mlp"),
                    d ** -0.5),
            "c_v": ((nW, cfg.d_ff, d), Ax("layers", "mlp", "embed"),
                    cfg.d_ff ** -0.5),
            "c_r": ((nW, d, d), Ax("layers", "embed", "embed_tp"), d ** -0.5),
        }
    return t


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    table = param_table(cfg)
    dt = _dtype(cfg)
    params: Dict[str, Any] = {}
    leaves = [(g, n) for g, grp in table.items() for n in grp]
    keys = jax.random.split(key, len(leaves))
    for (g, n), k in zip(leaves, keys):
        shape, _, scale = table[g][n]
        params.setdefault(g, {})
        if scale == 0.0:
            params[g][n] = jnp.zeros(shape, dt)
        else:
            # Drawn in the target dtype: a full-width bf16 leaf never
            # passes through an fp32 temporary twice its size.
            params[g][n] = (jax.random.normal(k, shape, dt)
                            * scale).astype(dt)
    return params


def param_structs(cfg: ModelConfig):
    """(ShapeDtypeStruct pytree, Ax pytree) — no allocation (dry-run)."""
    table = param_table(cfg)
    dt = _dtype(cfg)
    shapes = {g: {n: jax.ShapeDtypeStruct(s, dt)
                  for n, (s, _, _) in grp.items()}
              for g, grp in table.items()}
    logical = {g: {n: ax for n, (_, ax, _) in grp.items()}
               for g, grp in table.items()}
    return shapes, logical


# ---------------------------------------------------------------------------
# Per-layer routing arrays
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig):
    """Static per-layer routing: kinds + per-group indices (numpy int32)."""
    L = cfg.n_layers
    mixer = np.zeros(L, np.int32)
    ffn = np.zeros(L, np.int32)
    idx: Dict[str, np.ndarray] = {k: np.zeros(L, np.int32) for k in
                                  ("attn", "global", "local", "dense", "moe",
                                   "rec", "rwkv")}
    counters = dict(attn=0, glob=0, loc=0, dense=0, moe=0, rec=0, rwkv=0)
    for i, (lt, ft) in enumerate(zip(cfg.layer_types, cfg.ffn_types)):
        mixer[i] = MIXER_KINDS[lt]
        if lt in (ATTN_GLOBAL, ATTN_LOCAL):
            idx["attn"][i] = counters["attn"]
            counters["attn"] += 1
            if lt == ATTN_GLOBAL:
                idx["global"][i] = counters["glob"]
                counters["glob"] += 1
            else:
                idx["local"][i] = counters["loc"]
                counters["loc"] += 1
        elif lt == RGLRU:
            idx["rec"][i] = counters["rec"]
            counters["rec"] += 1
        elif lt == RWKV:
            idx["rwkv"][i] = counters["rwkv"]
            counters["rwkv"] += 1
        if lt == RWKV:
            ffn[i] = FFN_KIND_CMIX
        elif ft == FFN_MOE:
            ffn[i] = FFN_KIND_MOE
            idx["moe"][i] = counters["moe"]
            counters["moe"] += 1
        else:
            ffn[i] = FFN_KIND_DENSE
            idx["dense"][i] = counters["dense"]
            counters["dense"] += 1
    present_mixers = sorted(set(mixer.tolist()))
    present_ffns = sorted(set(ffn.tolist()))
    mixer_compact = np.array([present_mixers.index(m) for m in mixer],
                             np.int32)
    ffn_compact = np.array([present_ffns.index(f) for f in ffn], np.int32)
    return dict(mixer=mixer, ffn=ffn, mixer_compact=mixer_compact,
                ffn_compact=ffn_compact, present_mixers=present_mixers,
                present_ffns=present_ffns, **idx)


def tree_index(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def tree_update(tree, i, new):
    return jax.tree.map(
        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype),
                                                         i, 0), tree, new)


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

def decode_state_structs(cfg: ModelConfig, batch: int, max_seq: int):
    """(ShapeDtypeStruct pytree, Ax pytree) for the decode state."""
    dt = _dtype(cfg)
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    w = min(cfg.window_size, max_seq)
    shapes: Dict[str, Any] = {"pos": jax.ShapeDtypeStruct((batch,),
                                                          jnp.int32)}
    logical: Dict[str, Any] = {"pos": Ax("batch")}
    cache_ax = Ax("layers", "batch", "kv_heads", "seq", "head_dim")
    cache_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dt
    if cfg.n_global_layers:
        s = (cfg.n_global_layers, batch, kv, max_seq, hd)
        shapes["kg"] = jax.ShapeDtypeStruct(s, cache_dt)
        shapes["vg"] = jax.ShapeDtypeStruct(s, cache_dt)
        logical["kg"] = cache_ax
        logical["vg"] = cache_ax
        if cfg.kv_cache_dtype == "int8":
            ss = (cfg.n_global_layers, batch, kv, max_seq)
            sax = Ax("layers", "batch", "kv_heads", "seq")
            shapes["kg_scale"] = jax.ShapeDtypeStruct(ss, jnp.float32)
            shapes["vg_scale"] = jax.ShapeDtypeStruct(ss, jnp.float32)
            logical["kg_scale"] = sax
            logical["vg_scale"] = sax
    if cfg.n_local_layers:
        s = (cfg.n_local_layers, batch, kv, w, hd)
        shapes["kl"] = jax.ShapeDtypeStruct(s, dt)
        shapes["vl"] = jax.ShapeDtypeStruct(s, dt)
        logical["kl"] = Ax("layers", "batch", "kv_heads", "seq_nosplit",
                           "head_dim")
        logical["vl"] = Ax("layers", "batch", "kv_heads", "seq_nosplit",
                           "head_dim")
    if cfg.n_rec_layers:
        shapes["rg_h"] = jax.ShapeDtypeStruct(
            (cfg.n_rec_layers, batch, cfg.rnn_width), dt)
        shapes["rg_conv"] = jax.ShapeDtypeStruct(
            (cfg.n_rec_layers, batch, cfg.conv_width - 1, cfg.rnn_width), dt)
        logical["rg_h"] = Ax("layers", "batch", "rnn")
        logical["rg_conv"] = Ax("layers", "batch", None, "rnn")
    if cfg.n_rwkv_layers:
        nh, rhd = cfg.n_rwkv_heads, cfg.rwkv_head_dim
        shapes["rwkv_wkv"] = jax.ShapeDtypeStruct(
            (cfg.n_rwkv_layers, batch, nh, rhd, rhd), dt)
        shapes["rwkv_shift"] = jax.ShapeDtypeStruct(
            (cfg.n_rwkv_layers, batch, cfg.d_model), dt)
        shapes["rwkv_cshift"] = jax.ShapeDtypeStruct(
            (cfg.n_rwkv_layers, batch, cfg.d_model), dt)
        logical["rwkv_wkv"] = Ax("layers", "batch", "heads", None, None)
        logical["rwkv_shift"] = Ax("layers", "batch", "embed")
        logical["rwkv_cshift"] = Ax("layers", "batch", "embed")
    return shapes, logical


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int):
    shapes, _ = decode_state_structs(cfg, batch, max_seq)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


# ---------------------------------------------------------------------------
# Full-sequence forward (train + prefill)
# ---------------------------------------------------------------------------

def _tile_size(n, cap):
    """Largest divisor of ``n`` not exceeding ``cap`` (kernel tiling)."""
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _mixer_fullseq_branch(kind, cfg, params, plan_arrays, positions,
                          write_cache, valid_len=None, prefix_len=None,
                          prefix_kv=None):
    """Returns branch fn(operand) -> (y, state) for lax.switch.

    ``valid_len`` (traced, bucketed prefill; scalar or per-example (B,)):
    tokens at positions >= valid_len are padding. Global-cache writes of
    padding rows are harmless (masked by ``pos`` validity at decode and
    overwritten as the sequence advances), but the LOCAL ring cache wraps
    modulo the window — the real tail [valid_len - w, valid_len) must
    land in the ring, not the padded tail — so the ring is rebuilt
    functionally: slot s takes the LATEST real position ≡ s (mod w),
    exactly the invariant the unpadded write path establishes. (The
    valid_len path assumes ``positions == prefix + arange(T)``, which is
    how the engine prefills.)

    ``prefix_kv`` (+ traced ``prefix_len``): cached-prefix suffix
    prefill. ``prefix_kv`` is {"pool": (nG, nP, KV, page, hd) paged KV
    pool, "scale": matching int8 scale pool or None, "bt_k"/"bt_v":
    (B, P) block tables} addressing the pages that already hold
    positions [0, prefix_len). The new tokens' queries (at absolute
    positions ``prefix_len + t``) take a non-causal paged pass over the
    real prefix pages plus a causal flash pass over the fresh suffix,
    merged by online-softmax state (logit softcap applied in both
    kernels). Only global layers support a prefix — the engine gates
    the prefix cache to local-free archs."""

    def attn_branch(op, *, local):
        x, state, idxs = op
        p = tree_index(params["attn"], idxs["attn"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        q, k, v = attn_mod.project_qkv(xn, p, cfg, positions)
        window = cfg.window_size if local else 0
        if prefix_kv is not None and not local:
            # Suffix prefill, two passes merged via online-softmax state:
            # (1) a paged prefix pass streams ONLY the real cached pages
            # through scalar-prefetched block tables (non-causal — every
            # suffix query sits past the whole prefix), (2) a causal
            # flash pass over the fresh suffix at relative offset 0.
            # Each emits unfinalized (m, l, acc); the merge rescales by
            # exp(m_i - m) and the finalize normalizes once. plen == 0
            # (cold first chunk) leaves the prefix state at the exact
            # merge identity (m = -inf, l = acc = 0).
            from repro.kernels import flash_attention as fk
            from repro.kernels import ops as kops
            pool = tree_index(prefix_kv["pool"], idxs["global"])
            spool = (tree_index(prefix_kv["scale"], idxs["global"])
                     if prefix_kv.get("scale") is not None else None)
            t_q = q.shape[1]
            cap = float(cfg.attn_logit_softcap or 0.0)
            plen_vec = jnp.broadcast_to(
                jnp.asarray(prefix_len, jnp.int32), (q.shape[0],))
            st_p = fk.paged_prefix_attend(
                q, pool, prefix_kv["bt_k"], prefix_kv["bt_v"], plen_vec,
                k_scale_pool=spool, v_scale_pool=spool, softcap=cap,
                tq=_tile_size(t_q, 256))
            st_s = fk.flash_prefill(q, k, v, offset=0,
                                    tq=_tile_size(t_q, 256),
                                    ts=_tile_size(t_q, 512),
                                    softcap=cap, emit_state=True)
            y = kops.finalize_prefill_state(
                kops.merge_prefill_states(st_s, st_p), dtype=q.dtype)
        else:
            y = attn_mod.attention_fullseq(
                q, k, v, positions, positions, window=window,
                attn_softcap=cfg.attn_logit_softcap)
        y = attn_mod.output_proj(y, p)
        if write_cache and state:
            t = x.shape[1]
            if local and "kl" in state:
                w = state["kl"].shape[3]
                kc = state["kl"]
                kn = tree_index(kc, idxs["local"])
                vn = tree_index(state["vl"], idxs["local"])
                if valid_len is None:
                    n = min(t, w)
                    slots = jnp.mod(positions[-n:], w)
                    kn = kn.at[:, :, slots, :].set(
                        k[:, -n:].transpose(0, 2, 1, 3).astype(kn.dtype))
                    vn = vn.at[:, :, slots, :].set(
                        v[:, -n:].transpose(0, 2, 1, 3).astype(vn.dtype))
                else:
                    # Latest real position per ring slot: p(s) is the
                    # largest p < valid_len with p ≡ s (mod w); slots
                    # with no such p (valid_len < w tail) keep old rows.
                    # valid_len may be per-example (B,) — the cohort
                    # scheduler right-pads ragged prompts to one bucket.
                    vl = jnp.asarray(valid_len, jnp.int32)
                    bN = kn.shape[0]
                    vl_b = (jnp.broadcast_to(vl, (bN,)) if vl.ndim == 0
                            else vl)
                    s_arr = jnp.arange(w, dtype=jnp.int32)
                    p_s = s_arr[None, :] + w * (
                        (vl_b[:, None] - 1 - s_arr[None, :]) // w)
                    keep = (p_s >= 0)[:, None, :, None]
                    p_c = jnp.clip(p_s, 0, t - 1)         # (B, w)
                    k_rows = jnp.take_along_axis(
                        k, p_c[:, :, None, None], axis=1)  # (B, w, KV, hd)
                    v_rows = jnp.take_along_axis(
                        v, p_c[:, :, None, None], axis=1)
                    k_rows = k_rows.transpose(0, 2, 1, 3)
                    v_rows = v_rows.transpose(0, 2, 1, 3)
                    kn = jnp.where(keep, k_rows.astype(kn.dtype), kn)
                    vn = jnp.where(keep, v_rows.astype(vn.dtype), vn)
                state = dict(state)
                state["kl"] = tree_update(kc, idxs["local"], kn)
                state["vl"] = tree_update(state["vl"], idxs["local"], vn)
            elif not local and "kg" in state:
                kn = tree_index(state["kg"], idxs["global"])
                vn = tree_index(state["vg"], idxs["global"])
                state = dict(state)
                kt = k.transpose(0, 2, 1, 3)          # (B, KV, T, hd)
                vt = v.transpose(0, 2, 1, 3)
                if cfg.kv_cache_dtype == "int8":
                    from repro.core.cache import quant_rows
                    kq, ks = quant_rows(kt)
                    vq, vs = quant_rows(vt)
                    kn = kn.at[:, :, positions, :].set(kq)
                    vn = vn.at[:, :, positions, :].set(vq)
                    ksn = tree_index(state["kg_scale"], idxs["global"])
                    vsn = tree_index(state["vg_scale"], idxs["global"])
                    ksn = ksn.at[:, :, positions].set(ks)
                    vsn = vsn.at[:, :, positions].set(vs)
                    state["kg_scale"] = tree_update(
                        state["kg_scale"], idxs["global"], ksn)
                    state["vg_scale"] = tree_update(
                        state["vg_scale"], idxs["global"], vsn)
                else:
                    kn = kn.at[:, :, positions, :].set(kt.astype(kn.dtype))
                    vn = vn.at[:, :, positions, :].set(vt.astype(vn.dtype))
                state["kg"] = tree_update(state["kg"], idxs["global"], kn)
                state["vg"] = tree_update(state["vg"], idxs["global"], vn)
        return x + y, state

    def rglru_branch(op):
        x, state, idxs = op
        p = tree_index(params["rglru"], idxs["rec"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        if state and "rg_h" in state:
            h0 = tree_index(state["rg_h"], idxs["rec"])
            tail = tree_index(state["rg_conv"], idxs["rec"])
            y, (h1, tail1) = rglru.rglru_fullseq(xn, p, cfg, h0=h0,
                                                 conv_tail=tail)
            state = dict(state)
            state["rg_h"] = tree_update(state["rg_h"], idxs["rec"], h1)
            state["rg_conv"] = tree_update(state["rg_conv"], idxs["rec"],
                                           tail1)
        else:
            y, _ = rglru.rglru_fullseq(xn, p, cfg)
        return x + y, state

    def rwkv_branch(op):
        x, state, idxs = op
        p = tree_index(params["rwkv"], idxs["rwkv"])
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        if state and "rwkv_wkv" in state:
            st = {"shift": tree_index(state["rwkv_shift"], idxs["rwkv"]),
                  "wkv": tree_index(state["rwkv_wkv"], idxs["rwkv"])}
        else:
            b = x.shape[0]
            st = {"shift": jnp.zeros((b, cfg.d_model), x.dtype),
                  "wkv": jnp.zeros((b, cfg.n_rwkv_heads, cfg.rwkv_head_dim,
                                    cfg.rwkv_head_dim), x.dtype)}
        y, st1 = rwkv.rwkv_time_mix_fullseq(xn, p, cfg, st)
        if state and "rwkv_wkv" in state:
            state = dict(state)
            state["rwkv_shift"] = tree_update(state["rwkv_shift"],
                                              idxs["rwkv"], st1["shift"])
            state["rwkv_wkv"] = tree_update(state["rwkv_wkv"], idxs["rwkv"],
                                            st1["wkv"])
        return x + y, state

    if kind == MIXER_KINDS[ATTN_GLOBAL]:
        return functools.partial(attn_branch, local=False)
    if kind == MIXER_KINDS[ATTN_LOCAL]:
        return functools.partial(attn_branch, local=True)
    if kind == MIXER_KINDS[RGLRU]:
        return rglru_branch
    return rwkv_branch


def _ffn_fullseq_branch(kind, cfg, params, moe_impl="capacity"):
    def dense_branch(op):
        x, state, idxs, aux = op
        p = tree_index(params["ffn"], idxs["dense"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        return x + mlp.dense_ffn(xn, p, cfg), state, aux

    def moe_branch(op):
        x, state, idxs, aux = op
        p = tree_index(params["moe"], idxs["moe"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        if moe_impl == "ragged":
            y = moe.moe_ffn_ragged(xn, p, cfg)
        elif moe_impl == "ep":
            from repro.sharding.context import current_ctx
            ctx = current_ctx()
            if ctx is None:
                y, a = moe.moe_ffn(xn, p, cfg, return_aux=True)
            else:
                y, a = moe.moe_ffn_ep(xn, p, cfg, ctx, return_aux=True)
            aux = {"load_balance": aux["load_balance"] + a["load_balance"],
                   "router_z": aux["router_z"] + a["router_z"]}
        else:
            y, a = moe.moe_ffn(xn, p, cfg, return_aux=True)
            aux = {"load_balance": aux["load_balance"] + a["load_balance"],
                   "router_z": aux["router_z"] + a["router_z"]}
        return x + y, state, aux

    def cmix_branch(op):
        x, state, idxs, aux = op
        p = tree_index(params["rwkv"], idxs["rwkv"])
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        if state and "rwkv_cshift" in state:
            last = tree_index(state["rwkv_cshift"], idxs["rwkv"])
        else:
            last = jnp.zeros((x.shape[0], cfg.d_model), x.dtype)
        y, last1 = rwkv.rwkv_channel_mix_fullseq(xn, p, last)
        if state and "rwkv_cshift" in state:
            state = dict(state)
            state["rwkv_cshift"] = tree_update(state["rwkv_cshift"],
                                               idxs["rwkv"], last1)
        return x + y, state, aux

    return {FFN_KIND_DENSE: dense_branch, FFN_KIND_MOE: moe_branch,
            FFN_KIND_CMIX: cmix_branch}[kind]


def forward_fullseq(params, cfg: ModelConfig, inputs, *, state=None,
                    positions=None, remat=False, logits_slice=None,
                    moe_impl=None, unroll=False, valid_len=None,
                    prefix_len=None, prefix_kv=None):
    """inputs: tokens (B, T) int32, or embeddings (B, T, d) for stub
    frontends. state: decode-state pytree to fill (prefill) or None (train).

    Returns (logits, state, aux). ``logits_slice``: if "last", only the final
    position's logits are computed (prefill saves the unembed matmul).
    ``unroll``: unroll the layer scan — identical math, layer-count-sized
    HLO; used by the dry-run so cost_analysis counts every layer (XLA
    counts a while body ONCE — measured in EXPERIMENTS.md §Roofline).
    ``valid_len`` (traced int32, scalar or per-example (B,)): bucketed
    prefill — tokens at index >= valid_len within this call are
    right-padding. "last" logits then come from index valid_len - 1, the
    decode state's ``pos`` starts at valid_len, and local ring-cache
    writes mask the padding tail (the engine's power-of-two prompt
    buckets reuse one jit per bucket; the cohort scheduler passes a
    per-example vector for ragged cohorts).
    ``prefix_len``/``prefix_kv`` (traced scalar + paged pool/block-table
    dict, see ``_mixer_fullseq_branch``): cached-prefix suffix prefill —
    this call's tokens sit at absolute positions ``prefix_len +
    arange(T)`` and attend over the cached prefix pages; global-cache
    writes land at those absolute positions, and ``pos`` starts at
    ``prefix_len + valid_len``.
    """
    plan = layer_plan(cfg)
    if inputs.dtype in (jnp.int32, jnp.int64):
        h = embed_lookup(params["embed"]["tok"], inputs).astype(_dtype(cfg))
    else:
        h = frontends.adapt(inputs.astype(_dtype(cfg)), params["frontend"])
    b, t = h.shape[0], h.shape[1]
    if positions is None:
        positions = jnp.arange(t, dtype=jnp.int32)
        if prefix_len is not None:
            positions = jnp.asarray(prefix_len, jnp.int32) + positions

    xs = {
        "mixer_compact": jnp.asarray(plan["mixer_compact"]),
        "ffn_compact": jnp.asarray(plan["ffn_compact"]),
        "idxs": {k: jnp.asarray(plan[k]) for k in
                 ("attn", "global", "local", "dense", "moe", "rec", "rwkv")},
    }
    mixer_branches = [
        _mixer_fullseq_branch(k, cfg, params, plan, positions,
                              write_cache=state is not None,
                              valid_len=valid_len, prefix_len=prefix_len,
                              prefix_kv=prefix_kv)
        for k in plan["present_mixers"]]
    if moe_impl is None:
        # inference paths (prefill) default to the exact dropless MoE
        moe_impl = "capacity" if state is None else "ragged"
    ffn_branches = [_ffn_fullseq_branch(k, cfg, params, moe_impl)
                    for k in plan["present_ffns"]]

    empty_state = state if state is not None else {}

    from repro.sharding.context import pin_activations

    def body(carry, x_i):
        hh, st, aux = carry
        hh, st = jax.lax.switch(x_i["mixer_compact"], mixer_branches,
                                (hh, st, x_i["idxs"]))
        hh, st, aux = jax.lax.switch(x_i["ffn_compact"], ffn_branches,
                                     (hh, st, x_i["idxs"], aux))
        return (pin_activations(hh), st, aux), None

    if remat:
        body = jax.checkpoint(body)

    aux0 = {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32)}
    (h, out_state, aux), _ = jax.lax.scan(body, (h, empty_state, aux0), xs,
                                          unroll=cfg.n_layers if unroll
                                          else 1)

    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    if logits_slice == "last":
        if valid_len is None:
            h = h[:, -1:]
        else:   # bucketed prefill: last REAL token, not last padded one
            vl = jnp.asarray(valid_len, jnp.int32)
            if vl.ndim == 0:
                h = jax.lax.dynamic_slice_in_dim(h, vl - 1, 1, axis=1)
            else:   # ragged cohort: per-example last real token
                h = jnp.take_along_axis(h, (vl - 1)[:, None, None], axis=1)
    w_un = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["unembed"]["w"])
    logits = unembed(h, w_un, cfg.final_logit_softcap)
    if state is not None and "pos" in out_state:
        out_state = dict(out_state)
        fill = t if valid_len is None else jnp.asarray(valid_len, jnp.int32)
        if prefix_len is not None:
            fill = fill + jnp.asarray(prefix_len, jnp.int32)
        out_state["pos"] = jnp.broadcast_to(
            jnp.asarray(fill, jnp.int32), (b,))
    return logits, (out_state if state is not None else None), aux


# ---------------------------------------------------------------------------
# Decode step (one token). CHAI hooks: see repro/core/chai_attention.py
# ---------------------------------------------------------------------------

def _mixer_decode_branch(kind, cfg, params, chai_ctx, mixed_phase=False,
                         decode_ts=0, relay=None):
    from repro.core import chai_attention as chai_mod

    @jax.named_scope("decode_attn")
    def attn_branch(op, *, local):
        x, state, idxs = op     # x: (B, d)
        p = tree_index(params["attn"], idxs["attn"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        pos = state["pos"]      # (B,)
        if chai_ctx is not None and mixed_phase:
            # Continuous batching: warmup and steady slots share the batch.
            # Run both attention paths in one jit and mask-and-select per
            # slot (static shapes). Each path commits its cache writes only
            # for its own slots (write_mask), so every buffer keeps a
            # single linear update chain — donation aliases in place, no
            # whole-buffer merge copies.
            from repro.core import cache as chai_cache
            steady = state["phase"] >= chai_cache.PHASE_STEADY   # (B,)
            y_m, state = _plain_decode_attention(xn, p, cfg, state, idxs,
                                                 local=local,
                                                 write_mask=~steady)
            y_c, state = chai_mod.chai_decode_attention(
                xn, p, cfg, state, idxs, chai_ctx, local=local,
                write_mask=steady, decode_ts=decode_ts, relay=relay)
            y = jnp.where(steady[:, None, None], y_c, y_m)
        elif chai_ctx is not None:
            y, state = chai_mod.chai_decode_attention(
                xn, p, cfg, state, idxs, chai_ctx, local=local,
                decode_ts=decode_ts, relay=relay)
        else:
            y, state = _plain_decode_attention(xn, p, cfg, state, idxs,
                                               local=local)
        y = jnp.einsum("bhe,hed->bd", y, p["wo"])
        return x + y, state

    def rglru_branch(op):
        x, state, idxs = op
        p = tree_index(params["rglru"], idxs["rec"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        h0 = tree_index(state["rg_h"], idxs["rec"])
        tail = tree_index(state["rg_conv"], idxs["rec"])
        y, (h1, tail1) = rglru.rglru_decode(xn, p, cfg, h0, tail)
        state = dict(state)
        state["rg_h"] = tree_update(state["rg_h"], idxs["rec"], h1)
        state["rg_conv"] = tree_update(state["rg_conv"], idxs["rec"], tail1)
        return x + y, state

    def rwkv_branch(op):
        x, state, idxs = op
        p = tree_index(params["rwkv"], idxs["rwkv"])
        xn = rms_norm(x, p["ln1"], cfg.norm_eps)
        st = {"shift": tree_index(state["rwkv_shift"], idxs["rwkv"]),
              "wkv": tree_index(state["rwkv_wkv"], idxs["rwkv"])}
        y, st1 = rwkv.rwkv_time_mix_decode(xn, p, cfg, st)
        state = dict(state)
        state["rwkv_shift"] = tree_update(state["rwkv_shift"], idxs["rwkv"],
                                          st1["shift"])
        state["rwkv_wkv"] = tree_update(state["rwkv_wkv"], idxs["rwkv"],
                                        st1["wkv"])
        return x + y, state

    if kind == MIXER_KINDS[ATTN_GLOBAL]:
        return functools.partial(attn_branch, local=False)
    if kind == MIXER_KINDS[ATTN_LOCAL]:
        return functools.partial(attn_branch, local=True)
    if kind == MIXER_KINDS[RGLRU]:
        return rglru_branch
    return rwkv_branch


def _masked_rows(write_mask, new, old):
    """Commit ``new`` only for slots in ``write_mask`` (mixed-phase step);
    identity when no mask. new/old: (B, ...)."""
    if write_mask is None:
        return new
    m = write_mask.reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def paged_token_coords(bt, pos, page):
    """(physical page, in-page row) for each slot's current write position.
    bt: (B, P) block table; pos: (B,). Unallocated logical pages map to
    the null sink page 0 — writes there are harmless by construction."""
    b = pos.shape[0]
    return bt[jnp.arange(b), pos // page], pos % page


def paged_write(state, key, layer, page_idx, row, new, write_mask=None):
    """Commit one token's rows into layer ``layer`` of the stacked pool
    ``state[key]`` — (L, nP, rows, page, hd) K/V pages or (L, nP, rows,
    page) int8 scales — at (page_idx[b], row[b]) for each slot b, in place
    (``ops.paged_kv_write_rows``: no layer of the pool is sliced out,
    relaid or written back). Slots outside ``write_mask`` (B,) write into
    the null sink page 0 instead of their own page. Returns a new state
    dict."""
    from repro.kernels import ops as kops
    if write_mask is not None:
        page_idx = jnp.where(write_mask, page_idx, 0)
    return {**state, key: kops.paged_kv_write_rows(state[key], layer,
                                                   page_idx, row, new)}


def _paged_global_write(state, idxs, k, v, pos, write_mask, cfg):
    """Paged-layout global-cache decode write: commit one token's K/V
    rows into each slot's current page of the shared dense pool ``kvp``
    (and its int8 scale pool), in place in the layer stack. Returns the
    new state; the fused decode kernel streams the stacked pool through
    its block tables directly."""
    from repro.core.cache import quant_rows
    lay = idxs["global"]
    page = state["kvp"].shape[3]
    pk, row = paged_token_coords(state["bt_kg"], pos, page)
    pv, _ = paged_token_coords(state["bt_vg"], pos, page)
    if cfg.kv_cache_dtype == "int8":
        (k, ks), (v, vs) = quant_rows(k), quant_rows(v)
        state = paged_write(state, "kvp_scale", lay, pk, row, ks, write_mask)
        state = paged_write(state, "kvp_scale", lay, pv, row, vs, write_mask)
    state = paged_write(state, "kvp", lay, pk, row, k, write_mask)
    return paged_write(state, "kvp", lay, pv, row, v, write_mask)


def _paged_global_update(state, idxs, k, v, pos, write_mask, cfg):
    """``_paged_global_write`` + dense logical views (B, KV, S, hd)
    gathered through the block tables straight out of the layer stack —
    the jnp fallback's interface (the attention math downstream is
    identical to the dense layout's).
    """
    from repro.core.cache import dequant_rows, gather_pages
    state = _paged_global_write(state, idxs, k, v, pos, write_mask, cfg)
    lay = idxs["global"]
    kc_f = gather_pages(state["kvp"], state["bt_kg"], lay)
    vc_f = gather_pages(state["kvp"], state["bt_vg"], lay)
    if "kvp_scale" in state:
        spool = state["kvp_scale"]
        kc_f = dequant_rows(kc_f, gather_pages(spool, state["bt_kg"], lay))
        vc_f = dequant_rows(vc_f, gather_pages(spool, state["bt_vg"], lay))
    return state, kc_f, vc_f


def _plain_decode_attention(xn, p, cfg, state, idxs, *, local,
                            write_mask=None):
    """MHA/GQA decode for one token. xn: (B, d). Returns ((B, H, hd), state).

    ``write_mask`` (B,) bool: cache rows are committed only for masked
    slots (the mixed-phase step runs this path alongside the CHAI path)."""
    b = xn.shape[0]
    pos = state["pos"]
    ar = jnp.arange(b)
    # positions (B, 1): per-example rotary phase for the new token
    q, k, v = attn_mod.project_qkv(xn[:, None], p, cfg, pos[:, None])
    q = q[:, 0]      # (B, H, hd)
    k = k[:, 0]      # (B, KV, hd)
    v = v[:, 0]
    if local:
        w = state["kl"].shape[3]
        kc = tree_index(state["kl"], idxs["local"])
        vc = tree_index(state["vl"], idxs["local"])
        slot = jnp.mod(pos, w)
        kc = kc.at[ar, :, slot, :].set(
            _masked_rows(write_mask, k.astype(kc.dtype), kc[ar, :, slot, :]))
        vc = vc.at[ar, :, slot, :].set(
            _masked_rows(write_mask, v.astype(vc.dtype), vc[ar, :, slot, :]))
        kv_pos = jax.vmap(lambda pp: attn_mod.ring_positions(pp + 1, w))(pos)
        state = dict(state)
        state["kl"] = tree_update(state["kl"], idxs["local"], kc)
        state["vl"] = tree_update(state["vl"], idxs["local"], vc)
        window = cfg.window_size
    elif "kvp" in state:
        # Paged layout: same math over block-table-gathered views.
        state, kc_f, vc_f = _paged_global_update(state, idxs, k, v, pos,
                                                 write_mask, cfg)
        s = kc_f.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        window = 0
        kc, vc = kc_f, vc_f
    else:
        s = state["kg"].shape[3]
        kc = tree_index(state["kg"], idxs["global"])
        vc = tree_index(state["vg"], idxs["global"])
        state = dict(state)
        if cfg.kv_cache_dtype == "int8":
            from repro.core.cache import dequant_rows, quant_rows
            kq, ks = quant_rows(k)              # (B, KV, hd), (B, KV)
            vq, vs = quant_rows(v)
            kc = kc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, kq, kc[ar, :, pos, :]))
            vc = vc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, vq, vc[ar, :, pos, :]))
            ksc = tree_index(state["kg_scale"], idxs["global"])
            vsc = tree_index(state["vg_scale"], idxs["global"])
            ksc = ksc.at[ar, :, pos].set(
                _masked_rows(write_mask, ks, ksc[ar, :, pos]))
            vsc = vsc.at[ar, :, pos].set(
                _masked_rows(write_mask, vs, vsc[ar, :, pos]))
            state["kg_scale"] = tree_update(state["kg_scale"],
                                            idxs["global"], ksc)
            state["vg_scale"] = tree_update(state["vg_scale"],
                                            idxs["global"], vsc)
            kc_f, vc_f = dequant_rows(kc, ksc), dequant_rows(vc, vsc)
        else:
            kc = kc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, k.astype(kc.dtype),
                             kc[ar, :, pos, :]))
            vc = vc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, v.astype(vc.dtype),
                             vc[ar, :, pos, :]))
            kc_f, vc_f = kc, vc
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        state["kg"] = tree_update(state["kg"], idxs["global"], kc)
        state["vg"] = tree_update(state["vg"], idxs["global"], vc)
        window = 0
        kc, vc = kc_f, vc_f
    y, probs = _decode_attention_batched(q, kc, vc, kv_pos, pos, window,
                                         cfg.attn_logit_softcap)
    if "chai_scores" in state:
        # CHAI warmup: accumulate attention over the first feature_window
        # prefix positions as clustering features (paper §3.3).
        wf = state["chai_scores"].shape[-1]
        pw = probs.reshape(b, -1, probs.shape[-1])[:, :, :wf]  # (B, H, Wf)
        if pw.shape[-1] < wf:   # local ring narrower than feature window
            pw = jnp.pad(pw, ((0, 0), (0, 0), (0, wf - pw.shape[-1])))
        if write_mask is not None:   # steady slots: features stay frozen
            pw = pw * write_mask[:, None, None]
        buf = tree_index(state["chai_scores"], idxs["attn"])
        state["chai_scores"] = tree_update(state["chai_scores"],
                                           idxs["attn"], buf + pw)
    return y, state


def _decode_attention_batched(q, kc, vc, kv_pos, pos, window, cap):
    """Per-example-position decode attention. q: (B,H,hd); kc/vc: (B,KV,S,hd);
    kv_pos: (B,S); pos: (B,)."""
    b, h, hd = q.shape
    n_kv, s = kc.shape[1], kc.shape[2]
    qs = q.reshape(b, n_kv, h // n_kv, hd).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    sc = jnp.einsum("bkgd,bksd->bkgs", qs, kc.astype(jnp.float32)) * scale
    sc = softcap(sc, cap)
    valid = (kv_pos >= 0) & (kv_pos <= pos[:, None])
    if window:
        valid &= (pos[:, None] - kv_pos) < window
    sc = jnp.where(valid[:, None, None, :], sc, attn_mod.NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vc.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype), p


def _ffn_decode_branch(kind, cfg, params, moe_impl="ragged"):
    def dense_branch(op):
        x, state, idxs = op
        p = tree_index(params["ffn"], idxs["dense"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        return x + mlp.dense_ffn(xn[:, None], p, cfg)[:, 0], state

    @jax.named_scope("moe_ffn")
    def moe_branch(op):
        x, state, idxs = op
        p = tree_index(params["moe"], idxs["moe"])
        xn = rms_norm(x, p["ln"], cfg.norm_eps)
        if moe_impl == "ragged":
            y = moe.moe_ffn_ragged(xn[:, None], p, cfg)[:, 0]
        else:
            y = moe.moe_ffn(xn[:, None], p, cfg)[:, 0]
        return x + y, state

    def cmix_branch(op):
        x, state, idxs = op
        p = tree_index(params["rwkv"], idxs["rwkv"])
        xn = rms_norm(x, p["ln2"], cfg.norm_eps)
        last = tree_index(state["rwkv_cshift"], idxs["rwkv"])
        y, last1 = rwkv.rwkv_channel_mix_decode(xn, p, last)
        state = dict(state)
        state["rwkv_cshift"] = tree_update(state["rwkv_cshift"],
                                           idxs["rwkv"], last1)
        return x + y, state

    return {FFN_KIND_DENSE: dense_branch, FFN_KIND_MOE: moe_branch,
            FFN_KIND_CMIX: cmix_branch}[kind]


def decode_step(params, cfg: ModelConfig, tokens, state, *, chai_ctx=None,
                mixed_phase=False, embeddings=None, moe_impl="ragged",
                unroll=False, decode_ts=0, relay=None):
    """One decode step. tokens: (B,) int32 (or embeddings (B, d) for stub
    frontends). Returns (logits (B, V), new_state).

    Logits are float32 regardless of the model dtype (``unembed``
    promotes before the optional softcap) — the contract the batched
    sampler (``repro.launch.steps.make_sampler``) relies on: its
    ``temperature=0`` lane takes ``argmax`` over these exact values, so
    greedy serving is bitwise-stable across engine versions, and its
    sampling lanes get full-precision softmax/cumsum mass.

    ``mixed_phase``: with a ``chai_ctx``, route each batch slot through the
    MHA or CHAI attention path according to ``state["phase"]`` (unified
    per-slot layout — continuous batching). ``decode_ts``: the page size
    the fused CHAI decode kernel's dense-layout tile derives from (the
    engine passes its page size so every KV layout tiles — and therefore
    rounds — identically).

    ``relay`` (shared-prefix relay decode, pytree of group-batched
    arrays built by the engine): STEADY slots grouped by their deepest
    shared radix node skip their prefix pages in the fused decode and
    instead share ONE group-batched prefix-attention pass per layer over
    a contiguous resident copy of the shared pages. Both passes run on
    the online-softmax side-output contract: ``emit_state=True`` makes
    the fused decode kernels return the unfinalized triple
    (m (B, R), l (B, R), acc (B, A, hd)) — running row-max, running
    exp-sum, and UNNORMALIZED weighted-V accumulator, one row per rep
    (m, l) / per accumulator row (acc) — instead of finalized outputs.
    Triples combine associatively: m' = max(m1, m2), each side rescaled
    by exp(m_i - m'), and a single finalize divides acc by the gathered
    l and applies the head->cluster broadcast. The empty state
    (m = NEG_INF, l = 0, acc = 0) is the exact (bitwise) merge identity
    because in-kernel m is clamped >= -1e30 whenever any tile computed,
    so non-grouped slots ride through the same merge unchanged. See
    ``repro.kernels.ops.merge_decode_states`` / ``finalize_decode_state``
    and ``repro.core.chai_attention`` for the relay dict layout.
    """
    plan = layer_plan(cfg)
    if embeddings is not None:
        h = frontends.adapt(embeddings[:, None].astype(_dtype(cfg)),
                            params["frontend"])[:, 0]
    else:
        h = embed_lookup(params["embed"]["tok"], tokens).astype(_dtype(cfg))

    xs = {
        "mixer_compact": jnp.asarray(plan["mixer_compact"]),
        "ffn_compact": jnp.asarray(plan["ffn_compact"]),
        "idxs": {k: jnp.asarray(plan[k]) for k in
                 ("attn", "global", "local", "dense", "moe", "rec", "rwkv")},
    }
    mixer_branches = [_mixer_decode_branch(k, cfg, params, chai_ctx,
                                           mixed_phase, decode_ts, relay)
                      for k in plan["present_mixers"]]
    ffn_branches = [_ffn_decode_branch(k, cfg, params, moe_impl)
                    for k in plan["present_ffns"]]

    def body(carry, x_i):
        hh, st = carry
        hh, st = jax.lax.switch(x_i["mixer_compact"], mixer_branches,
                                (hh, st, x_i["idxs"]))
        hh, st = jax.lax.switch(x_i["ffn_compact"], ffn_branches,
                                (hh, st, x_i["idxs"]))
        return (hh, st), None

    (h, state), _ = jax.lax.scan(body, (h, state), xs,
                                 unroll=cfg.n_layers if unroll else 1)
    h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
    w_un = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["unembed"]["w"])
    logits = unembed(h, w_un, cfg.final_logit_softcap)
    state = dict(state)
    state["pos"] = state["pos"] + 1
    return logits, state
