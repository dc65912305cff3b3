"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def _softcap(sc, cap):
    """Gemma2-style tanh logit softcap (identity when cap <= 0), applied
    after QK-scale and before the validity mask — the exact insertion
    point of the fused kernels' static ``softcap`` flag."""
    if cap:
        return cap * jnp.tanh(sc / cap)
    return sc


def flash_decode_ref(q, k_cache, v_cache, pos, *, window=0):
    """q: (B, H, hd); k/v_cache: (B, KV, S, hd); pos: (B,) int32 (number of
    valid tokens - 1 == current position). Returns (B, H, hd) fp32."""
    b, h, hd = q.shape
    n_kv, s = k_cache.shape[1], k_cache.shape[2]
    qpk = h // n_kv
    qs = q.reshape(b, n_kv, qpk, hd).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bksd->bkgs", qs,
                    k_cache.astype(jnp.float32)) / jnp.sqrt(
                        jnp.float32(hd))
    kv_pos = jnp.arange(s, dtype=jnp.int32)
    valid = kv_pos[None, :] <= pos[:, None]
    if window:
        valid &= (pos[:, None] - kv_pos[None, :]) < window
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    a = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", a, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd)


def flash_prefill_ref(q, k, v, *, offset=0, window=0, softcap=0.0):
    """q: (B, T, H, hd); k/v: (B, S, KV, hd); causal with query positions
    offset..offset+T-1 against key positions 0..S-1."""
    b, t, h, hd = q.shape
    s, n_kv = k.shape[1], k.shape[2]
    qpk = h // n_kv
    qs = q.reshape(b, t, n_kv, qpk, hd).astype(jnp.float32)
    sc = jnp.einsum("btkgd,bskd->btkgs", qs,
                    k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    sc = _softcap(sc, softcap)
    qp = offset + jnp.arange(t)[:, None]
    kp = jnp.arange(s)[None, :]
    valid = kp <= qp
    if window:
        valid &= (qp - kp) < window
    sc = jnp.where(valid[None, :, None, None, :], sc, NEG_INF)
    a = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", a, v.astype(jnp.float32))
    return out.reshape(b, t, h, hd)


def chai_scores_ref(q_rep, k_cache, pos, *, reps_per_group=0, window=0,
                    softcap=0.0):
    """Clustered scores. q_rep: (B, R, hd) representative-head queries;
    k_cache: (B, KV, S, hd). reps_per_group r maps rep j -> KV group j//r
    (MHA clustered cache: KV == R, r == 1). Returns normalized A (B, R, S)."""
    b, r_total, hd = q_rep.shape
    n_kv, s = k_cache.shape[1], k_cache.shape[2]
    r = reps_per_group or 1
    kg = k_cache[:, jnp.arange(r_total) // r]            # (B, R, S, hd)
    sc = jnp.einsum("bre,brse->brs", q_rep.astype(jnp.float32),
                    kg.astype(jnp.float32)) / jnp.sqrt(jnp.float32(hd))
    sc = _softcap(sc, softcap)
    kv_pos = jnp.arange(s, dtype=jnp.int32)
    valid = kv_pos[None, :] <= pos[:, None]
    if window:
        valid &= (pos[:, None] - kv_pos[None, :]) < window
    sc = jnp.where(valid[:, None, :], sc, NEG_INF)
    return jax.nn.softmax(sc, axis=-1)


def chai_scores_i8_ref(q_rep, k_cache_i8, k_scale, pos, *,
                       reps_per_group=0):
    """Oracle for the fused int8-dequant clustered scores."""
    kf = k_cache_i8.astype(jnp.float32) * k_scale[..., None]
    return chai_scores_ref(q_rep, kf, pos, reps_per_group=reps_per_group)


def chai_av_ref(a, v_cache, h2c):
    """a: (B, R, S) normalized clustered scores; v_cache: (B, H, S, hd);
    h2c: (B, H) or (H,) flat head->row map. Returns (B, H, hd) fp32."""
    b, h = v_cache.shape[0], v_cache.shape[1]
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h))
    a_full = jnp.take_along_axis(a, h2c[..., None], axis=1)   # (B, H, S)
    return jnp.einsum("bhs,bhsd->bhd", a_full.astype(jnp.float32),
                      v_cache.astype(jnp.float32))


def chai_decode_ref(q_rep, k_cache, v_cache, h2c, pos, *, reps_per_group=0):
    a = chai_scores_ref(q_rep, k_cache, pos, reps_per_group=reps_per_group)
    return chai_av_ref(a, v_cache, h2c)


# -------------------------------------------------------------- paged ------
def gather_pages_ref(pool, bt):
    """Densify a page pool through block tables. pool: (nP, rows, page,
    hd); bt: (B, P) int32 -> (B, rows, P*page, hd). Null-page entries
    yield garbage rows that the ``pos`` masks of the oracles below hide —
    the same contract the paged kernels rely on. Reuses the production
    gather (its correctness is pinned independently by the
    scatter-then-compare kernel tests); the oracle value here is the
    dense attention math it feeds."""
    from repro.core.cache import gather_pages
    return gather_pages(pool, bt)


def paged_decode_ref(q, kv_pool, bt_k, bt_v, pos, *, window=0):
    """Oracle for ``paged_decode``: densify then flash-decode."""
    return flash_decode_ref(q, gather_pages_ref(kv_pool, bt_k),
                            gather_pages_ref(kv_pool, bt_v), pos,
                            window=window)


def paged_chai_scores_ref(q_rep, k_pool, bt, pos, *, reps_per_group=0):
    """Oracle for ``paged_chai_qk`` + ``row_softmax``."""
    return chai_scores_ref(q_rep, gather_pages_ref(k_pool, bt), pos,
                           reps_per_group=reps_per_group)


def paged_chai_av_ref(a, v_pool, bt_v, h2c):
    """Oracle for ``paged_chai_av``."""
    return chai_av_ref(a, gather_pages_ref(v_pool, bt_v), h2c)


def paged_chai_decode_ref(q_rep, k_pool, bt_k, v_pool, bt_v, h2c, pos, *,
                          reps_per_group=0):
    a = paged_chai_scores_ref(q_rep, k_pool, bt_k, pos,
                              reps_per_group=reps_per_group)
    return paged_chai_av_ref(a, v_pool, bt_v, h2c)


def paged_write_rows_ref(pool, layer, page_idx, row, new, write_mask=None):
    """Oracle for ``paged_write_rows``: the jnp scatter of each slot's
    token rows into one layer of a stacked pool (L, nP, rows, page[, hd]);
    slots outside ``write_mask`` rewrite their old rows."""
    lp = pool[layer]
    old = lp[page_idx, :, row]                     # (B, rows[, hd])
    new = new.astype(pool.dtype)
    if write_mask is not None:
        m = write_mask.reshape((-1,) + (1,) * (new.ndim - 1))
        new = jnp.where(m, new, old)
    return pool.at[layer].set(lp.at[page_idx, :, row].set(new))


# ------------------------------------------------------ fused decode -------
def chai_fused_decode_ref(q_rep, k_cache, v_cache, h2c, pos, *,
                          k_scale=None, v_scale=None, reps_per_group=0,
                          share_values=False, window=0, softcap=0.0):
    """Oracle for ``chai_fused_decode`` across the full dispatch matrix:
    {MHA, GQA} x {fp32, int8 scale rows} x {share_values} x {window}.

    v_cache rows: H (per-head), a divisor of H (GQA per-group), or R
    (share_values clustered). int8 inputs pass per-row ``k_scale`` /
    ``v_scale`` (B, rows, S); share_values V codes are reinterpreted
    scale-less, matching the engine's clustered-V semantics."""
    b = q_rep.shape[0]
    kf = k_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
    a = chai_scores_ref(q_rep, kf, pos, reps_per_group=reps_per_group,
                        window=window, softcap=softcap)      # (B, R, S)
    vf = v_cache.astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h2c.shape[0]))
    h = h2c.shape[1]
    if share_values:
        out_rep = jnp.einsum("brs,brsd->brd", a, vf)
        return jnp.take_along_axis(out_rep, h2c[..., None], axis=1)
    if vf.shape[1] != h:         # GQA: head h reads V of group h // qpk
        vf = jnp.repeat(vf, h // vf.shape[1], axis=1)
    return chai_av_ref(a, vf, h2c)


def paged_chai_fused_decode_ref(q_rep, k_pool, bt_k, v_pool, bt_v, h2c,
                                pos, *, k_scale_pool=None,
                                v_scale_pool=None, reps_per_group=0,
                                share_values=False, window=0, softcap=0.0):
    """Oracle for ``paged_chai_fused_decode``: densify then dense-ref."""
    return chai_fused_decode_ref(
        q_rep, gather_pages_ref(k_pool, bt_k),
        gather_pages_ref(v_pool, bt_v), h2c, pos,
        k_scale=(None if k_scale_pool is None
                 else gather_pages_ref(k_scale_pool, bt_k)),
        v_scale=(None if v_scale_pool is None
                 else gather_pages_ref(v_scale_pool, bt_v)),
        reps_per_group=reps_per_group, share_values=share_values,
        window=window, softcap=softcap)


# ------------------------------------- three-kernel pipeline (oracle) ------
def chai_three_kernel_decode(q_rep, k_cache, v_cache, h2c, pos, *,
                             k_scale=None, v_scale=None, reps_per_group=1,
                             share_values=False, window=0, ts=512,
                             interpret=True):
    """The pre-fusion production path — QK kernel -> row softmax kernel ->
    AV kernel, materializing the (B, R, S) score tensor between launches.
    Kept ONLY as the oracle / baseline for the fused kernel (3 launches +
    one HBM round-trip of the scores; see ``ops.decode_launch_count``)."""
    from repro.kernels import chai_attention as ck
    if k_scale is not None:
        sc = ck.chai_qk_i8(q_rep, k_cache, k_scale, pos,
                           reps_per_group=reps_per_group, window=window,
                           ts=ts, interpret=interpret)
    else:
        sc = ck.chai_qk(q_rep, k_cache, pos, reps_per_group=reps_per_group,
                        window=window, ts=ts, interpret=interpret)
    a = ck.row_softmax(sc, interpret=interpret)
    vf = v_cache
    if v_scale is not None:    # no int8 AV kernel existed; dequant outside
        vf = v_cache.astype(jnp.float32) * v_scale[..., None]
    b = q_rep.shape[0]
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h2c.shape[0]))
    h = h2c.shape[1]
    if share_values:
        # Clustered V: AV per rep row, gather members after.
        r = a.shape[1]
        out_rep = ck.chai_av(a, vf, jnp.arange(r, dtype=jnp.int32), ts=ts,
                             interpret=interpret)
        return jnp.take_along_axis(out_rep, h2c[..., None], axis=1)
    if vf.shape[1] != h:       # GQA: expand per-group V to per-head rows
        vf = jnp.repeat(vf, h // vf.shape[1], axis=1)
    return ck.chai_av(a, vf, h2c, ts=ts, interpret=interpret)


def paged_chai_three_kernel_decode(q_rep, k_pool, bt_k, v_pool, bt_v, h2c,
                                   pos, *, reps_per_group=1,
                                   share_values=False, window=0,
                                   interpret=True):
    """Paged three-kernel pipeline (fp32 pools), kept as the fused paged
    kernel's launch-count / parity baseline."""
    from repro.kernels import chai_attention as ck
    sc = ck.paged_chai_qk(q_rep, k_pool, bt_k, pos,
                          reps_per_group=reps_per_group, window=window,
                          interpret=interpret)
    a = ck.row_softmax(sc, interpret=interpret)
    b = q_rep.shape[0]
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h2c.shape[0]))
    if share_values:
        r = a.shape[1]
        out_rep = ck.paged_chai_av(a, v_pool, bt_v,
                                   jnp.arange(r, dtype=jnp.int32),
                                   interpret=interpret)
        return jnp.take_along_axis(out_rep, h2c[..., None], axis=1)
    h = h2c.shape[1]
    if v_pool.shape[1] != h:   # GQA: expand per-group V pool rows
        v_pool = jnp.repeat(v_pool, h // v_pool.shape[1], axis=1)
    return ck.paged_chai_av(a, v_pool, bt_v, h2c, interpret=interpret)


# ------------------------------------------- relay shared-prefix decode ----
def relay_prefix_decode_ref(q, k, v, k_row, a_row, v_row, plen, *,
                            k_scale=None, v_scale=None, softcap=0.0):
    """Oracle for ``relay_prefix_decode``: dense row gathers + the masked
    softmax state computed in one shot. q: (G, NR, hd); k: (G, KV, Sp,
    hd); v: (G, VR, Sp, hd); k_row: (G, NR); a_row/v_row: (G, A); plen:
    (G,). Returns (m (G, NR), l (G, NR), acc (G, A, hd)) f32."""
    g, nr, hd = q.shape
    sp = k.shape[2]
    kf = k.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
    kg = jnp.take_along_axis(
        kf, k_row[:, :, None, None].astype(jnp.int32), axis=1)
    sc = jnp.einsum("gre,grse->grs", q.astype(jnp.float32),
                    kg) / jnp.sqrt(jnp.float32(hd))
    sc = _softcap(sc, softcap)
    idx = jnp.arange(sp, dtype=jnp.int32)
    sc = jnp.where(idx[None, None, :] < plen[:, None, None], sc, NEG_INF)
    m = jnp.maximum(jnp.max(sc, axis=-1), -1e30)          # (G, NR)
    p = jnp.exp(sc - m[..., None])                        # (G, NR, Sp)
    l = jnp.sum(p, axis=-1)                               # (G, NR)
    vf = v.astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    vg = jnp.take_along_axis(
        vf, v_row[:, :, None, None].astype(jnp.int32), axis=1)
    p_a = jnp.take_along_axis(p, a_row[:, :, None].astype(jnp.int32),
                              axis=1)                     # (G, A, Sp)
    acc = jnp.einsum("gas,gasd->gad", p_a, vg)            # (G, A, hd)
    return m, l, acc


def paged_prefix_attend_ref(q, kv_pool, bt_k, bt_v, plen, *,
                            k_scale_pool=None, v_scale_pool=None,
                            softcap=0.0):
    """Oracle for ``paged_prefix_attend``: densify the pool through the
    block tables, then the non-causal masked softmax state. Returns the
    head-major triple (m (B, H, T), l (B, H, T), acc (B, H, T, hd))."""
    b, t, h, hd = q.shape
    kf = gather_pages_ref(kv_pool, bt_k).astype(jnp.float32)
    if k_scale_pool is not None:
        kf = kf * gather_pages_ref(k_scale_pool, bt_k)[..., None]
    vf = gather_pages_ref(kv_pool, bt_v).astype(jnp.float32)
    if v_scale_pool is not None:
        vf = vf * gather_pages_ref(v_scale_pool, bt_v)[..., None]
    qpk = h // kf.shape[1]
    kf = jnp.repeat(kf, qpk, axis=1)                      # (B, H, S, hd)
    vf = jnp.repeat(vf, qpk, axis=1)
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)      # (B, H, T, hd)
    sc = jnp.einsum("bhtd,bhsd->bhts", qh, kf) / jnp.sqrt(
        jnp.float32(hd))
    sc = _softcap(sc, softcap)
    s = kf.shape[2]
    idx = jnp.arange(s, dtype=jnp.int32)
    sc = jnp.where(idx[None, None, None, :] < plen[:, None, None, None],
                   sc, NEG_INF)
    # plen == 0 rows never run a tile in-kernel, so their m stays NEG_INF
    # (the merge identity); computed rows clamp at -1e30 like every kernel.
    m = jnp.where(plen[:, None, None] > 0,
                  jnp.maximum(jnp.max(sc, axis=-1), -1e30),
                  NEG_INF)                                # (B, H, T)
    p = jnp.where(plen[:, None, None, None] > 0,
                  jnp.exp(sc - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhts,bhsd->bhtd", p, vf)
    return m, l, acc
