"""Clustered Head Attention — the paper's core op (decode path).

Score computation + softmax run only for representative heads; attention
weights broadcast to member heads via a gather; V stays per-head
(paper Table 4: pruning V loses accuracy; ``share_values`` implements the
CHAI-QKV ablation).

MHA archs additionally store a *clustered K cache* (k_max rows instead of
H) — the paper's 21.4% KV-memory saving. GQA archs keep the per-group K
cache (DESIGN.md §4) and get the compute-only saving.

The attention math itself runs as ONE fused Pallas launch per decode step
(``repro.kernels.ops.chai_decode_attention`` /
``paged_chai_decode_attention``): online-softmax clustered scores +
h2c-broadcast AV, streaming dense tiles or block-table pages through VMEM
with in-kernel int8 dequant — no (B, R, S) score tensor and, on the paged
layout, no densifying page gather. ``decode_ts`` (the engine passes its
page size) pins the dense tile size to the paged page size so every KV
layout performs bit-identical arithmetic (cross-layout greedy parity).
The pure-jnp math is kept as the fallback for shapes the kernel does not
cover (attention logit softcap, local ring caches) and as the reference
path (``USE_FUSED_DECODE = False``). Every int8 layout — dense and paged,
MHA and GQA — carries a real per-(row, position) scale gather; the fused
dispatch passes scales unconditionally (the historical dense-GQA
code-reinterpret corner is gone).

ctx arrays may be shared across the batch (ndim without B) or per-request
(batched) — see repro.core.clustering.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.layers import apply_rope, rms_norm, softcap

# Module switch: tests flip this to pin fused-vs-jnp token parity; the
# engine honors it at trace time (each ServingEngine builds fresh jits).
USE_FUSED_DECODE = True


def unfused(fn):
    """``fn`` with ``USE_FUSED_DECODE`` held False while it runs: wrap a
    step maker's function before ``jax.jit`` and every decode attention
    it traces takes the jnp reference path (the flag is read at trace
    time)."""
    @functools.wraps(fn)
    def wrapped(*args):
        global USE_FUSED_DECODE
        prev = USE_FUSED_DECODE
        USE_FUSED_DECODE = False
        try:
            return fn(*args)
        finally:
            USE_FUSED_DECODE = prev
    return wrapped


def _rope1(x, pos, theta):
    """x: (B, n, hd) single-token heads; pos: (B,)."""
    return apply_rope(x[:, None], pos[:, None], theta)[:, 0]


def _qk_norm(x, scale, cfg):
    return rms_norm(x, scale, cfg.norm_eps) if cfg.qk_norm else x


def chai_decode_attention(xn, p, cfg, state, idxs, chai_ctx, *, local,
                          write_mask=None, decode_ts=0, relay=None):
    """xn: (B, d) normed hidden. Returns (out (B, H, hd), new_state).

    ``write_mask`` (B,) bool: cache rows are committed only for masked
    slots (the mixed-phase continuous step runs this path alongside the
    plain MHA path on one batch). ``decode_ts``: S-tile size for the
    fused dense kernel (0 = whole sequence; the engine passes its page
    size so dense and paged layouts tile identically).

    ``relay`` (shared-prefix relay decode, paged+fused layouts): pytree
    of group-batched arrays — see ``_relay_prefix_state`` for the
    layout. Grouped slots' fused decode runs SUFFIX-ONLY (rolled block
    tables + shifted ``pos``) with ``emit_state=True``, one
    group-batched prefix pass runs per layer over the resident copy of
    the shared pages, and the two (m, l, acc) triples merge by
    online-softmax combine before the finalize. Non-grouped slots carry
    the empty prefix state — the exact merge identity. The jnp fallback
    ignores ``relay`` harmlessly: block tables still hold the prefix
    pages, so the densified full-attention math is already complete."""
    if cfg.is_mha and not local:
        return _chai_mha_decode(xn, p, cfg, state, idxs, chai_ctx,
                                write_mask, decode_ts=decode_ts,
                                relay=relay)
    if not cfg.is_mha:
        return _chai_gqa_decode(xn, p, cfg, state, idxs, chai_ctx,
                                local=local, write_mask=write_mask,
                                decode_ts=decode_ts, relay=relay)
    # MHA arch with a local layer (none of the assigned archs hit this):
    from repro.models.transformer import _plain_decode_attention
    return _plain_decode_attention(xn, p, cfg, state, idxs, local=local,
                                   write_mask=write_mask)


def _fused_ok(cfg):
    """The fused kernel covers everything the engine serves — the
    gemma2-style attention-logit softcap is applied in-kernel between
    QK-scale and the online-softmax update (static ``softcap`` flag)."""
    return USE_FUSED_DECODE


def _dense_ts(decode_ts, s):
    return decode_ts if decode_ts and s % decode_ts == 0 else s


def _layer_ctx(chai_ctx, attn_idx):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, attn_idx, 0,
                                               keepdims=False), chai_ctx)


# ------------------------------------------------- shared-prefix relay -----
def _roll_bt(bt, shift):
    """Rotate each slot's block-table row left by ``shift`` pages, so the
    kernel's logical page 0 is the slot's first PRIVATE (post-prefix)
    page. With ``pos`` shifted down by the prefix length, the wrapped
    prefix entries reappearing at the tail sit at token indices
    > pos - plen and are masked by the kernel's validity test."""
    p = bt.shape[1]
    idx = (jnp.arange(p, dtype=jnp.int32)[None, :] + shift[:, None]) % p
    return jnp.take_along_axis(bt, idx, axis=1)


def _relay_prefix_state(relay, idxs, q_rep, *, acc_rows, use_v_scale,
                        softcap, ts):
    """One group-batched prefix-attention pass for this layer, scattered
    back to batch rows as an unfinalized (m, l, acc) online-softmax
    state.

    ``relay`` layout (engine-built, host-side):
      k / v            (nG, G, KV/VR, Sp, hd)  resident copies of the
                       shared dense pages (one contiguous view per group)
      k_scale/v_scale  (nG, G, rows, Sp)       int8 scales (optional)
      plen             (G,)                    shared prefix length
      members          (G, Nmax)               slot index per member
      k_row/a_row/v_row (nA, G, NR / A)        per-layer row-routing maps
                       (rep -> K row, acc row -> score row, acc row ->
                       V row) — this is where the h2c broadcast lives,
                       deferred out of the prefix compute
      gid/midx/len     (B,)                    slot -> (group, member)
      in_group         (B,) bool               grouped-slot mask

    Non-members scatter the empty state (m = NEG_INF, l = acc = 0) —
    the exact bitwise merge identity, so ungrouped slots pass through
    the merge unchanged. ``use_v_scale=False`` rides share_values int8
    V codes scale-less, mirroring the clustered-pool reinterpret."""
    from repro.kernels import ops as kops
    from repro.kernels.chai_attention import NEG_INF
    from repro.models.transformer import tree_index
    kp = tree_index(relay["k"], idxs["global"])
    vp = tree_index(relay["v"], idxs["global"])
    ks = (tree_index(relay["k_scale"], idxs["global"])
          if "k_scale" in relay else None)
    vs = (tree_index(relay["v_scale"], idxs["global"])
          if use_v_scale and "v_scale" in relay else None)
    k_row = tree_index(relay["k_row"], idxs["attn"])
    a_row = tree_index(relay["a_row"], idxs["attn"])
    v_row = tree_index(relay["v_row"], idxs["attn"])
    g, nmax = relay["members"].shape
    _, r, hd = q_rep.shape
    qg = q_rep[relay["members"]].reshape(g, nmax * r, hd)
    m, l, acc = kops.relay_prefix_attention(
        qg, kp, vp, k_row, a_row, v_row, relay["plen"],
        k_scale=ks, v_scale=vs, ts=ts, softcap=softcap)
    gid, midx, ing = relay["gid"], relay["midx"], relay["in_group"]
    m_pb = jnp.where(ing[:, None], m.reshape(g, nmax, r)[gid, midx],
                     NEG_INF)
    l_pb = jnp.where(ing[:, None], l.reshape(g, nmax, r)[gid, midx], 0.0)
    acc_pb = jnp.where(ing[:, None, None],
                       acc.reshape(g, nmax, acc_rows, hd)[gid, midx], 0.0)
    return m_pb, l_pb, acc_pb


# ---------------------------------------------------------------- MHA ------
def _chai_mha_decode(xn, p, cfg, state, idxs, chai_ctx, write_mask=None, *,
                     decode_ts=0, relay=None):
    from repro.models.transformer import _masked_rows, tree_index, \
        tree_update
    b, d = xn.shape
    ar = jnp.arange(b)
    hd, h = cfg.head_dim, cfg.n_heads
    pos = state["pos"]
    ctx = _layer_ctx(chai_ctx, idxs["attn"])
    reps, h2c = ctx["reps"], ctx["h2c"]
    batched = reps.ndim == 2                      # (B, k) vs (k,)
    share_v = cfg.chai.share_values

    if batched:
        # Per-request membership: project all heads, gather activations.
        q = jnp.einsum("bd,dhe->bhe", xn, p["wq"])
        k = jnp.einsum("bd,dhe->bhe", xn, p["wk"])
        if cfg.qk_norm:
            q = _qk_norm(q, p["q_norm"], cfg)
            k = _qk_norm(k, p["k_norm"], cfg)
        q_rep = jnp.take_along_axis(q, reps[..., None], axis=1)
        k_rep = jnp.take_along_axis(k, reps[..., None], axis=1)
    else:
        # Shared membership: gather weight rows (skips pruned projections —
        # the paper's full compute saving).
        wq_r = jnp.take(p["wq"], reps, axis=1)    # (d, k, hd)
        wk_r = jnp.take(p["wk"], reps, axis=1)
        q_rep = jnp.einsum("bd,dke->bke", xn, wq_r)
        k_rep = jnp.einsum("bd,dke->bke", xn, wk_r)
        if cfg.qk_norm:
            q_rep = _qk_norm(q_rep, p["q_norm"], cfg)
            k_rep = _qk_norm(k_rep, p["k_norm"], cfg)
    q_rep = _rope1(q_rep, pos, cfg.rope_theta)
    k_rep = _rope1(k_rep, pos, cfg.rope_theta)

    int8 = cfg.kv_cache_dtype == "int8"
    if int8:
        from repro.core.cache import dequant_rows, quant_rows
    paged = "cp" in state
    if paged:
        from repro.core.cache import gather_pages
        from repro.models.transformer import (_paged_write_rows,
                                              paged_token_coords)
        mask = functools.partial(_masked_rows, write_mask)

    # Clustered K cache update (k rows, not H). The fused kernel reads
    # the raw (possibly int8) buffers directly, so the dequantized /
    # page-gathered dense views are only built on the jnp fallback path.
    ksc = csc = None
    if paged:
        cp = tree_index(state["cp"], idxs["global"])      # (nP, k, page, hd)
        page = cp.shape[2]
        pk, row = paged_token_coords(state["bt_kc"], pos, page)
        if int8:
            kq, ks = quant_rows(k_rep)
            cp = _paged_write_rows(cp, pk, row, kq, mask)
            csc = tree_index(state["cp_scale"], idxs["global"])
            csc = _paged_write_rows(csc, pk, row, ks, mask)
        else:
            cp = _paged_write_rows(cp, pk, row, k_rep, mask)
    else:
        kc = tree_index(state["kg_chai"], idxs["global"])   # (B, k, S, hd)
        if int8:
            kq, ks = quant_rows(k_rep)
            kc = kc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, kq, kc[ar, :, pos, :]))
            ksc = tree_index(state["kg_chai_scale"], idxs["global"])
            ksc = ksc.at[ar, :, pos].set(
                _masked_rows(write_mask, ks, ksc[ar, :, pos]))
        else:
            kc = kc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, k_rep.astype(kc.dtype),
                             kc[ar, :, pos, :]))

    # V: full per-head (or clustered for the CHAI-QKV ablation).
    vsc = vsp = None
    if share_v:
        if batched:
            v = jnp.einsum("bd,dhe->bhe", xn, p["wv"])
            v_new = jnp.take_along_axis(v, reps[..., None], axis=1)
        else:
            wv_r = jnp.take(p["wv"], reps, axis=1)
            v_new = jnp.einsum("bd,dke->bke", xn, wv_r)
        if paged:
            # Clustered V pages live in the same cp pool (scale-less,
            # mirroring the unified vg_chai gather).
            pv, vrow = paged_token_coords(state["bt_vc"], pos, page)
            cp = _paged_write_rows(cp, pv, vrow, v_new, mask)
        else:
            vc = tree_index(state["vg_chai"], idxs["global"])
            vc = vc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, v_new.astype(vc.dtype),
                             vc[ar, :, pos, :]))
    else:
        v_new = jnp.einsum("bd,dhe->bhe", xn, p["wv"])
        if paged:
            vp = tree_index(state["kvp"], idxs["global"])
            pv, vrow = paged_token_coords(state["bt_vg"], pos, page)
            if int8:
                vq, vs = quant_rows(v_new)
                vp = _paged_write_rows(vp, pv, vrow, vq, mask)
                vsp = tree_index(state["kvp_scale"], idxs["global"])
                vsp = _paged_write_rows(vsp, pv, vrow, vs, mask)
            else:
                vp = _paged_write_rows(vp, pv, vrow, v_new, mask)
        else:
            vc = tree_index(state["vg"], idxs["global"])
            if int8:
                vq, vs = quant_rows(v_new)
                vc = vc.at[ar, :, pos, :].set(
                    _masked_rows(write_mask, vq, vc[ar, :, pos, :]))
                vsc = tree_index(state["vg_scale"], idxs["global"])
                vsc = vsc.at[ar, :, pos].set(
                    _masked_rows(write_mask, vs, vsc[ar, :, pos]))
            else:
                vc = vc.at[ar, :, pos, :].set(
                    _masked_rows(write_mask, v_new.astype(vc.dtype),
                                 vc[ar, :, pos, :]))

    gather_idx = h2c if batched else jnp.broadcast_to(h2c, (b, h))
    if _fused_ok(cfg):
        # One fused Pallas launch: scores + online softmax + h2c AV.
        from repro.kernels import ops as kops
        cap = float(cfg.attn_logit_softcap or 0.0)
        if paged:
            relay_on = relay is not None
            bt_kc = state["bt_kc"]
            bt_v = state["bt_vc"] if share_v else state["bt_vg"]
            pos_k = pos
            if relay_on:
                # Suffix-only fused decode: rolled tables + shifted pos
                # drop the prefix pages from this launch; the group-
                # batched prefix pass below covers them once per group.
                shift = relay["len"] // page
                bt_kc = _roll_bt(bt_kc, shift)
                bt_v = _roll_bt(bt_v, shift)
                pos_k = pos - relay["len"]
            if share_v:
                out = kops.paged_chai_decode_attention(
                    q_rep, cp, bt_kc, cp, bt_v,
                    gather_idx, pos_k, k_scale_pool=csc, share_values=True,
                    softcap=cap, emit_state=relay_on)
            else:
                out = kops.paged_chai_decode_attention(
                    q_rep, cp, bt_kc, vp, bt_v,
                    gather_idx, pos_k, k_scale_pool=csc, v_scale_pool=vsp,
                    softcap=cap, emit_state=relay_on)
            if relay_on:
                pref = _relay_prefix_state(
                    relay, idxs, q_rep,
                    acc_rows=q_rep.shape[1] if share_v else h,
                    use_v_scale=not share_v, softcap=cap, ts=decode_ts)
                out = kops.finalize_decode_state(
                    kops.merge_decode_states(out, pref, gather_idx,
                                             share_values=share_v),
                    gather_idx, share_values=share_v)
        else:
            out = kops.chai_decode_attention(
                q_rep, kc, vc, gather_idx, pos, k_scale=ksc, v_scale=vsc,
                share_values=share_v,
                ts=_dense_ts(decode_ts, kc.shape[2]), softcap=cap)
    else:
        # jnp fallback (softcap configs / reference path): densify and
        # dequantize, then the pre-fusion three-step math.
        if paged:
            kc_f = gather_pages(cp, state["bt_kc"])
            if int8:
                kc_f = dequant_rows(kc_f, gather_pages(csc,
                                                       state["bt_kc"]))
            if share_v:
                vc_f = gather_pages(cp, state["bt_vc"])
            else:
                vc_f = gather_pages(vp, state["bt_vg"])
                if int8:
                    vc_f = dequant_rows(vc_f, gather_pages(
                        vsp, state["bt_vg"]))
        else:
            kc_f = dequant_rows(kc, ksc) if int8 else kc
            if share_v:
                vc_f = vc
            else:
                vc_f = dequant_rows(vc, vsc) if int8 else vc
        s = kc_f.shape[2]
        scale = 1.0 / math.sqrt(hd)
        sc = jnp.einsum("bke,bkse->bks", q_rep.astype(jnp.float32),
                        kc_f.astype(jnp.float32)) * scale
        sc = softcap(sc, cfg.attn_logit_softcap)
        kv_pos = jnp.arange(s, dtype=jnp.int32)
        valid = kv_pos[None, :] <= pos[:, None]
        sc = jnp.where(valid[:, None, :], sc, attn_mod.NEG_INF)
        a = jax.nn.softmax(sc, axis=-1)                     # (B, k, S)

        if share_v:
            out_rep = jnp.einsum("bks,bksd->bkd", a,
                                 vc_f.astype(jnp.float32))
            out = jnp.take_along_axis(out_rep, gather_idx[..., None],
                                      axis=1)
        else:
            a_full = jnp.take_along_axis(a, gather_idx[..., None], axis=1)
            out = jnp.einsum("bhs,bhsd->bhd", a_full,
                             vc_f.astype(jnp.float32))

    state = dict(state)
    if paged:
        state["cp"] = tree_update(state["cp"], idxs["global"], cp)
        if int8:
            state["cp_scale"] = tree_update(state["cp_scale"],
                                            idxs["global"], csc)
        if not share_v:
            state["kvp"] = tree_update(state["kvp"], idxs["global"], vp)
            if int8:
                state["kvp_scale"] = tree_update(state["kvp_scale"],
                                                 idxs["global"], vsp)
    else:
        state["kg_chai"] = tree_update(state["kg_chai"], idxs["global"], kc)
        if int8:
            state["kg_chai_scale"] = tree_update(state["kg_chai_scale"],
                                                 idxs["global"], ksc)
            if not share_v:
                state["vg_scale"] = tree_update(state["vg_scale"],
                                                idxs["global"], vsc)
        if share_v:
            state["vg_chai"] = tree_update(state["vg_chai"], idxs["global"],
                                           vc)
        else:
            state["vg"] = tree_update(state["vg"], idxs["global"], vc)
    return out.astype(xn.dtype), state


# ---------------------------------------------------------------- GQA ------
def _chai_gqa_decode(xn, p, cfg, state, idxs, chai_ctx, *, local,
                     write_mask=None, decode_ts=0, relay=None):
    from repro.models.transformer import _masked_rows, tree_index, \
        tree_update
    b, d = xn.shape
    ar = jnp.arange(b)
    hd, h, n_kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    qpk = cfg.q_per_kv
    pos = state["pos"]
    ctx = _layer_ctx(chai_ctx, idxs["attn"])
    reps, cluster_of = ctx["reps"], ctx["cluster_of"]   # (.., KV, r/qpk)
    batched = reps.ndim == 3
    r = reps.shape[-1]

    if batched:
        q = jnp.einsum("bd,dhe->bhe", xn, p["wq"]).reshape(b, n_kv, qpk, hd)
        if cfg.qk_norm:
            q = _qk_norm(q, p["q_norm"], cfg)
        q_rep = jnp.take_along_axis(q, reps[..., None], axis=2)
    else:
        wq_g = p["wq"].reshape(d, n_kv, qpk, hd)
        idx = jnp.broadcast_to(reps[None, ..., None], (d, n_kv, r, hd))
        wq_r = jnp.take_along_axis(wq_g, idx, axis=2)   # (d, KV, r, hd)
        q_rep = jnp.einsum("bd,dkre->bkre", xn, wq_r)
        if cfg.qk_norm:
            q_rep = _qk_norm(q_rep, p["q_norm"], cfg)
    q_rep = apply_rope(q_rep.reshape(b, 1, n_kv * r, hd),
                       pos[:, None], cfg.rope_theta).reshape(b, n_kv, r, hd)

    # K/V: per-group projections unchanged (no K saving for GQA).
    k_new = jnp.einsum("bd,dke->bke", xn, p["wk"])
    if cfg.qk_norm:
        k_new = _qk_norm(k_new, p["k_norm"], cfg)
    k_new = _rope1(k_new, pos, cfg.rope_theta)
    v_new = jnp.einsum("bd,dke->bke", xn, p["wv"])

    paged = not local and "kvp" in state
    # Fused one-launch decode covers the global paths; the local ring
    # cache keeps the jnp math (ring-ordered kv positions). The dense
    # GQA int8 layout carries a real per-row scale gather exactly like
    # the paged path (the historical no-scales code-reinterpret corner
    # is gone), so the fused dispatch passes scales everywhere.
    fused = _fused_ok(cfg) and not local
    int8 = cfg.kv_cache_dtype == "int8"

    def _flat_qrep_h2c():
        gather_idx = (cluster_of if batched
                      else jnp.broadcast_to(cluster_of, (b, n_kv, qpk)))
        q_flat = q_rep.reshape(b, n_kv * r, hd)
        h2c_flat = (jnp.arange(n_kv, dtype=jnp.int32)[None, :, None] * r
                    + gather_idx).reshape(b, h)
        return q_flat, h2c_flat

    if local:
        w = state["kl"].shape[3]
        kc = tree_index(state["kl"], idxs["local"])
        vc = tree_index(state["vl"], idxs["local"])
        slot = jnp.mod(pos, w)
        kc = kc.at[ar, :, slot, :].set(
            _masked_rows(write_mask, k_new.astype(kc.dtype),
                         kc[ar, :, slot, :]))
        vc = vc.at[ar, :, slot, :].set(
            _masked_rows(write_mask, v_new.astype(vc.dtype),
                         vc[ar, :, slot, :]))
        kv_pos = jax.vmap(lambda pp: attn_mod.ring_positions(pp + 1, w))(pos)
        window = cfg.window_size
        kc_f, vc_f = kc, vc     # local rings are never quantized
    elif paged:
        # GQA paged: K and V stay page-resident in the dense pool for the
        # whole request (no clustered cache — compute-only saving).
        from repro.models.transformer import (_paged_global_write,
                                              _paged_global_update)
        if fused:
            state, pool, spool = _paged_global_write(
                state, idxs, k_new, v_new, pos, write_mask, cfg)
            q_flat, h2c_flat = _flat_qrep_h2c()
            from repro.kernels import ops as kops
            cap = float(cfg.attn_logit_softcap or 0.0)
            relay_on = relay is not None
            bt_kg, bt_vg, pos_k = state["bt_kg"], state["bt_vg"], pos
            if relay_on:
                shift = relay["len"] // pool.shape[2]
                bt_kg = _roll_bt(bt_kg, shift)
                bt_vg = _roll_bt(bt_vg, shift)
                pos_k = pos - relay["len"]
            out = kops.paged_chai_decode_attention(
                q_flat, pool, bt_kg, pool, bt_vg,
                h2c_flat, pos_k, k_scale_pool=spool, v_scale_pool=spool,
                reps_per_group=r, softcap=cap, emit_state=relay_on)
            if relay_on:
                pref = _relay_prefix_state(relay, idxs, q_flat,
                                           acc_rows=h, use_v_scale=True,
                                           softcap=cap, ts=decode_ts)
                out = kops.finalize_decode_state(
                    kops.merge_decode_states(out, pref, h2c_flat),
                    h2c_flat)
            return out.astype(xn.dtype), state
        state, kc, vc = _paged_global_update(state, idxs, k_new, v_new,
                                             pos, write_mask, cfg)
        s = kc.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        window = 0
        kc_f, vc_f = kc, vc     # already dequantized dense views
    else:
        s = state["kg"].shape[3]
        kc = tree_index(state["kg"], idxs["global"])
        vc = tree_index(state["vg"], idxs["global"])
        ksc = vsc = None
        if int8:
            from repro.core.cache import dequant_rows, quant_rows
            kq, ks = quant_rows(k_new)
            vq, vs = quant_rows(v_new)
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
        else:
            kc = kc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, k_new.astype(kc.dtype),
                             kc[ar, :, pos, :]))
            vc = vc.at[ar, :, pos, :].set(
                _masked_rows(write_mask, v_new.astype(vc.dtype),
                             vc[ar, :, pos, :]))
        kv_pos = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32), (b, s))
        window = 0

        def _commit_dense(state):
            state = dict(state)
            state["kg"] = tree_update(state["kg"], idxs["global"], kc)
            state["vg"] = tree_update(state["vg"], idxs["global"], vc)
            if int8:
                state["kg_scale"] = tree_update(state["kg_scale"],
                                                idxs["global"], ksc)
                state["vg_scale"] = tree_update(state["vg_scale"],
                                                idxs["global"], vsc)
            return state

        if fused:
            q_flat, h2c_flat = _flat_qrep_h2c()
            from repro.kernels import ops as kops
            out = kops.chai_decode_attention(
                q_flat, kc, vc, h2c_flat, pos, k_scale=ksc, v_scale=vsc,
                reps_per_group=r, ts=_dense_ts(decode_ts, s),
                softcap=float(cfg.attn_logit_softcap or 0.0))
            return out.astype(xn.dtype), _commit_dense(state)
        if int8:
            kc_f, vc_f = dequant_rows(kc, ksc), dequant_rows(vc, vsc)
        else:
            kc_f, vc_f = kc, vc

    scale = 1.0 / math.sqrt(hd)
    sc = jnp.einsum("bkre,bkse->bkrs", q_rep.astype(jnp.float32),
                    kc_f.astype(jnp.float32)) * scale
    sc = softcap(sc, cfg.attn_logit_softcap)
    valid = (kv_pos >= 0) & (kv_pos <= pos[:, None])
    if window:
        valid &= (pos[:, None] - kv_pos) < window
    sc = jnp.where(valid[:, None, None, :], sc, attn_mod.NEG_INF)
    a = jax.nn.softmax(sc, axis=-1)                     # (B, KV, r, S)

    gather_idx = (cluster_of if batched
                  else jnp.broadcast_to(cluster_of, (b, n_kv, qpk)))
    a_full = jnp.take_along_axis(a, gather_idx[..., None], axis=2)
    out = jnp.einsum("bkgs,bksd->bkgd", a_full, vc_f.astype(jnp.float32))
    out = out.reshape(b, h, hd)

    if local:
        state = dict(state)
        state["kl"] = tree_update(state["kl"], idxs["local"], kc)
        state["vl"] = tree_update(state["vl"], idxs["local"], vc)
    elif not paged:     # paged: _paged_global_update already committed
        state = _commit_dense(state)
    return out.astype(xn.dtype), state
