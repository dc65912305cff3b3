"""Pallas TPU kernels for Clustered Head Attention (the paper's core op).

Decomposition (DESIGN.md §3.2):
  1. ``chai_qk``      — raw scores for the R representative heads only
                        (R <= H: the compute CHAI removes). GQA: rep j reads
                        the K tile of its group j // reps_per_group via a
                        static index_map; MHA reads the clustered K cache.
  2. ``row_softmax``  — masked softmax over each (b, rep) row (row fits
                        VMEM; one pass).
  3. ``chai_av``      — the broadcast-and-accumulate: head h gathers the A
                        tile of its cluster via a **scalar-prefetched**
                        ``h2c`` index map (TPU-idiomatic dynamic gather, as
                        in paged-attention kernels) and multiplies with its
                        own V tile. Per-head V is preserved (Table 4).

The three-kernel split above survives only as the *oracle* (see
``repro.kernels.ref``): the production decode path is the **one-pass fused
kernel** below (``chai_fused_decode`` / ``paged_chai_fused_decode``). The
old "why not fused" argument (the rep row max/denominator is only known
after the last S tile) is answered the same way flash decode answers it:
carry online-softmax state — running max ``m`` and normalizer ``l`` per
rep row, plus per-member-head output accumulators — in VMEM scratch across
the sequentially-iterated S-tile grid axis, rescaling the accumulators by
``exp(m_prev - m_new)`` at every tile. One launch per decode step; no
``(B, R, S)`` logits ever touch HBM.

Fused dataflow per (batch, S-tile) grid step:

  K tile (R rep rows)  --QK+mask-->  scores (R, Ts)   [int8: dequant here]
  scores --online softmax update-->  m, l (R,)  +  p = exp(sc - m) (R, Ts)
  p --h2c one-hot broadcast------->  p_full (H, Ts)
  V tile (H rows)  --AV----------->  acc (H, hd) accumulators
                                     [share_values: acc stays (R, hd) and
                                      the h2c gather moves to finalize]

Paged variants (``paged_chai_fused_decode``): K/V live in page pools
addressed through scalar-prefetched int32 block tables (one S-tile == one
page) driving the BlockSpec index maps, so the serving engine's clustered
pages stream HBM->VMEM straight from the ``PagePool`` layout without
densification. int8 pools dequantize in-VMEM from the mirror-shaped scale
pools — the HBM byte saving happens on the stream, where it counts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import _squeeze_state

NEG_INF = -2.0e38


def _interpret_default():
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------------ QK ----
def _qk_kernel(pos_ref, q_ref, k_ref, o_ref, *, scale, ts, window):
    b = pl.program_id(0)
    s = pl.program_id(2)
    q = q_ref[0, 0, :].astype(jnp.float32)[None, :]        # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (Ts, hd)
    sc = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
    idx = s * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    sc = jnp.where(valid, sc, NEG_INF)
    o_ref[0, 0, :] = sc[:, 0]


def chai_qk(q_rep, k_cache, pos, *, reps_per_group=1, window=0, ts=512,
            interpret=None):
    """q_rep: (B, R, hd); k_cache: (B, KV, S, hd) with KV*reps_per_group==R
    (MHA clustered cache: KV==R, reps_per_group==1). -> raw scores (B,R,S)."""
    if interpret is None:
        interpret = _interpret_default()
    b, r_total, hd = q_rep.shape
    s = k_cache.shape[2]
    ts = min(ts, s)
    assert s % ts == 0
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_qk_kernel, scale=scale, ts=ts, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, r_total, s // ts),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda bb, rr, ss, pos_r:
                             (bb, rr, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, rr, ss, pos_r:
                             (bb, rr // reps_per_group, ss, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, ts), lambda bb, rr, ss, pos_r:
                                   (bb, rr, ss)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, r_total, s), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), q_rep, k_cache)


# ------------------------------------------------------------- softmax ----
def _softmax_kernel(x_ref, o_ref):
    x = x_ref[0, 0, :]
    m = jnp.maximum(jnp.max(x), -1e30)
    p = jnp.exp(x - m)
    o_ref[0, 0, :] = p / jnp.maximum(jnp.sum(p), 1e-37)


def row_softmax(scores, *, interpret=None):
    """scores: (B, R, S) raw (already masked) -> normalized A (B, R, S)."""
    if interpret is None:
        interpret = _interpret_default()
    b, r, s = scores.shape
    return pl.pallas_call(
        _softmax_kernel,
        grid=(b, r),
        in_specs=[pl.BlockSpec((1, 1, s), lambda bb, rr: (bb, rr, 0))],
        out_specs=pl.BlockSpec((1, 1, s), lambda bb, rr: (bb, rr, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, s), jnp.float32),
        interpret=interpret,
    )(scores)


# ------------------------------------------------------- paged QK ---------
def _paged_qk_kernel(pos_ref, bt_ref, q_ref, k_ref, o_ref, *, scale, page,
                     window):
    b = pl.program_id(0)
    s = pl.program_id(2)               # logical page index
    q = q_ref[0, 0, :].astype(jnp.float32)[None, :]        # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (page, hd)
    sc = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
    idx = s * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    o_ref[0, 0, :] = jnp.where(valid, sc, NEG_INF)[:, 0]


def paged_chai_qk(q_rep, k_pool, bt, pos, *, reps_per_group=1, window=0,
                  interpret=None):
    """Paged clustered scores. q_rep: (B, R, hd); k_pool: (nP, KV, page,
    hd) page pool with KV * reps_per_group == R (MHA clustered pool:
    KV == k_max, reps_per_group == 1); bt: (B, P) int32 block table;
    pos: (B,). Returns raw scores (B, R, P*page) — feed ``row_softmax``."""
    if interpret is None:
        interpret = _interpret_default()
    b, r_total, hd = q_rep.shape
    page = k_pool.shape[2]
    n_pages = bt.shape[1]
    s = n_pages * page
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_paged_qk_kernel, scale=scale, page=page,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, r_total, n_pages),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda bb, rr, ss, pos_r, bt_r:
                             (bb, rr, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda bb, rr, ss, pos_r, bt_r:
                             (bt_r[bb, ss], rr // reps_per_group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, page),
                                   lambda bb, rr, ss, pos_r, bt_r:
                                   (bb, rr, ss)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, r_total, s), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), bt.astype(jnp.int32), q_rep, k_pool)


# ------------------------------------------------------- int8 QK ----------
def _qk_i8_kernel(pos_ref, q_ref, k_ref, ks_ref, o_ref, *, scale, ts,
                  window):
    """Fused int8-dequant scores: K tile loads 1 byte/elem from HBM and
    dequantizes in VMEM (the memory-bound decode's byte saving happens on
    the HBM->VMEM stream, which is exactly what BlockSpec tiles)."""
    b = pl.program_id(0)
    s = pl.program_id(2)
    q = q_ref[0, 0, :].astype(jnp.float32)[None, :]        # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (Ts, hd) int8
    krow = ks_ref[0, 0].astype(jnp.float32)[:, None]       # (Ts, 1) scales
    sc = jnp.dot(k, q.T, preferred_element_type=jnp.float32)
    sc = sc * krow * scale
    idx = s * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    o_ref[0, 0, :] = jnp.where(valid, sc, NEG_INF)[:, 0]


def chai_qk_i8(q_rep, k_cache_i8, k_scale, pos, *, reps_per_group=1,
               window=0, ts=512, interpret=None):
    """int8 variant of ``chai_qk``. k_cache_i8: (B, KV, S, hd) int8;
    k_scale: (B, KV, S) f32 per-row scales. Returns raw scores (B, R, S).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, r_total, hd = q_rep.shape
    s = k_cache_i8.shape[2]
    ts = min(ts, s)
    assert s % ts == 0
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_qk_i8_kernel, scale=scale, ts=ts,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, r_total, s // ts),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda bb, rr, ss, pos_r:
                             (bb, rr, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, rr, ss, pos_r:
                             (bb, rr // reps_per_group, ss, 0)),
                pl.BlockSpec((1, 1, ts), lambda bb, rr, ss, pos_r:
                             (bb, rr // reps_per_group, ss)),
            ],
            out_specs=pl.BlockSpec((1, 1, ts), lambda bb, rr, ss, pos_r:
                                   (bb, rr, ss)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, r_total, s), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), q_rep, k_cache_i8, k_scale)


# ------------------------------------------------------------------ AV ----
def _av_kernel(h2c_ref, a_ref, v_ref, o_ref, acc_scr, *, n_tiles):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a = a_ref[0, 0, :].astype(jnp.float32)[None, :]        # (1, Ts)
    v = v_ref[0, 0].astype(jnp.float32)                    # (Ts, hd)
    acc_scr[...] += jnp.dot(a, v, preferred_element_type=jnp.float32)

    @pl.when(s == n_tiles - 1)
    def _fin():
        o_ref[0, 0, :] = acc_scr[0, :].astype(o_ref.dtype)


def chai_av(a, v_cache, h2c, *, ts=512, interpret=None):
    """a: (B, R, S) normalized clustered scores; v_cache: (B, H, S, hd);
    h2c: (B, H) int32 head -> A-row map (scalar-prefetched: drives the A
    BlockSpec index_map). Returns (B, H, hd) fp32."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, s, hd = v_cache.shape
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h))
    ts = min(ts, s)
    assert s % ts == 0
    n_tiles = s // ts
    kernel = functools.partial(_av_kernel, n_tiles=n_tiles)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, n_tiles),
            in_specs=[
                pl.BlockSpec((1, 1, ts), lambda bb, hh, ss, h2c_r:
                             (bb, h2c_r[bb, hh], ss)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, hh, ss, h2c_r:
                             (bb, hh, ss, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, hd), lambda bb, hh, ss, h2c_r:
                                   (bb, hh, 0)),
            scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
    )(h2c.astype(jnp.int32), a, v_cache)


# ------------------------------------------------------- paged AV ---------
def _paged_av_kernel(h2c_ref, bt_ref, a_ref, v_ref, o_ref, acc_scr, *,
                     n_tiles):
    # Same accumulate as _av_kernel; both scalar refs are consumed by the
    # index_maps (A row via h2c, V page via the block table).
    _av_kernel(h2c_ref, a_ref, v_ref, o_ref, acc_scr, n_tiles=n_tiles)


def paged_chai_av(a, v_pool, bt_v, h2c, *, interpret=None):
    """Paged broadcast-and-accumulate: head h reads the A row of its
    cluster (scalar-prefetched ``h2c``) and its own V rows from the page
    pool (scalar-prefetched block table) — the two gathers compose in
    one index_map pair. a: (B, R, S) normalized clustered scores with
    S == P * page; v_pool: (nP, H, page, hd); bt_v: (B, P) int32;
    h2c: (B, H) or (H,) int32. Returns (B, H, hd) fp32."""
    if interpret is None:
        interpret = _interpret_default()
    _, h, page, hd = v_pool.shape
    b = a.shape[0]
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h))
    n_pages = bt_v.shape[1]
    assert a.shape[2] == n_pages * page, (a.shape, n_pages, page)
    kernel = functools.partial(_paged_av_kernel, n_tiles=n_pages)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, n_pages),
            in_specs=[
                pl.BlockSpec((1, 1, page),
                             lambda bb, hh, ss, h2c_r, bt_r:
                             (bb, h2c_r[bb, hh], ss)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda bb, hh, ss, h2c_r, bt_r:
                             (bt_r[bb, ss], hh, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, hd),
                                   lambda bb, hh, ss, h2c_r, bt_r:
                                   (bb, hh, 0)),
            scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
    )(h2c.astype(jnp.int32), bt_v.astype(jnp.int32), a, v_pool)


# ------------------------------------------------- fused one-pass decode ---
def _fused_tile(pos_ref, q_ref, h2c_ref, k_ref, ks_ref, v_ref, vs_ref,
                out_refs, m_scr, l_scr, acc_scr, *, scale, ts, window,
                n_tiles, reps_per_group, v_rep, share_values, softcap=0.0,
                emit_state=False):
    """One (batch, S-tile) step of the fused clustered decode.

    Shared by the dense and paged variants — the paged caller only differs
    in how the K/V BlockSpecs locate the tile (block tables vs contiguous
    cache), so dense and paged produce bit-identical arithmetic for equal
    tile sizes (the engine's layout-parity guarantee).

    Scratch: ``m_scr``/``l_scr`` (R, 1) running max / normalizer per rep
    row; ``acc_scr`` (H, hd) per-member-head output accumulators (under
    ``share_values``: (R, hd) per-cluster — the h2c gather then happens at
    finalize, after normalization).

    ``emit_state``: instead of the finalized (H, hd) output, write the raw
    online-softmax triple — m (R,), l (R,), acc (rows_acc, hd) — so a
    caller can merge this pass with another (relay shared-prefix decode)
    before normalizing. The deferred jnp finalize (h2c gather + divide) is
    bitwise-identical to the in-kernel one-hot finalize."""
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (R, hd)
    k = k_ref[0].astype(jnp.float32)                     # (KVk, Ts, hd)
    r_total, hd = q.shape
    kv_k = k.shape[0]
    # Per-group rep scores: rep j reads the K rows of group j // rpg
    # (MHA clustered cache: KVk == R, rpg == 1 — plain batched matvec).
    q3 = q.reshape(kv_k, reps_per_group, hd)
    sc = jax.lax.dot_general(q3, k, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    if ks_ref is not None:   # int8: scores scaled by the per-row K scales
        sc = sc * ks_ref[0].astype(jnp.float32)[:, None, :]
    sc = sc.reshape(r_total, ts) * scale
    if softcap:
        # tanh logit softcap (gemma2): between QK-scale and the validity
        # mask, matching the jnp oracle's insertion point exactly.
        sc = softcap * jnp.tanh(sc / softcap)
    idx = s * ts + jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    sc = jnp.where(valid, sc, NEG_INF)                   # (R, Ts)

    m_prev = m_scr[...]                                  # (R, 1)
    m_new = jnp.maximum(
        jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True)), -1e30)
    alpha = jnp.exp(m_prev - m_new)                      # (R, 1)
    p = jnp.exp(sc - m_new)                              # (R, Ts)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)

    v = v_ref[0].astype(jnp.float32)                     # (KVv, Ts, hd)
    if vs_ref is not None:   # int8: dequant V rows before the AV dot
        v = v * vs_ref[0].astype(jnp.float32)[..., None]

    h2c = h2c_ref[0]                                     # (H, 1) int32
    h_total = h2c.shape[0]
    oneh = (h2c == jax.lax.broadcasted_iota(
        jnp.int32, (h_total, r_total), 1)).astype(jnp.float32)   # (H, R)

    if share_values:
        # Clustered V (KVv == R): accumulate per cluster; broadcast to
        # member heads at finalize (after normalization).
        pv = jax.lax.dot_general(p[:, None, :], v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)[:, 0]
        acc_scr[...] = acc_scr[...] * alpha + pv         # (R, hd)
    else:
        # Broadcast the cluster rows to member heads (one-hot matmul: the
        # MXU-friendly spelling of the h2c gather), then per-head AV.
        p_full = jnp.dot(oneh, p,
                         preferred_element_type=jnp.float32)     # (H, Ts)
        alpha_full = jnp.dot(oneh, alpha,
                             preferred_element_type=jnp.float32)  # (H, 1)
        if v_rep > 1:        # GQA: head h reads the V rows of group h//qpk
            v = jnp.repeat(v, v_rep, axis=0)
        pv = jax.lax.dot_general(p_full[:, None, :], v,
                                 (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)[:, 0]
        acc_scr[...] = acc_scr[...] * alpha_full + pv    # (H, hd)

    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(s == n_tiles - 1)
    def _fin():
        if emit_state:
            m_ref, l_ref, acc_ref = out_refs
            m_ref[0] = m_scr[...]
            l_ref[0] = l_scr[...]
            acc_ref[0] = acc_scr[...]
        else:
            (o_ref,) = out_refs
            if share_values:
                out_r = acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)
                out = jnp.dot(oneh, out_r,
                              preferred_element_type=jnp.float32)  # (H, hd)
            else:
                l_full = jnp.dot(oneh, l_scr[...],
                                 preferred_element_type=jnp.float32)
                out = acc_scr[...] / jnp.maximum(l_full, 1e-37)
            o_ref[0] = out.astype(o_ref.dtype)


def _fused_arg_router(n_prefetch, has_ks, has_vs, *, n_out=1, **flags):
    """Positional-ref unpacking for the optional int8 scale inputs.

    Kernel signature: [scalar-prefetch refs] q, h2c, k, [ks], v, [vs],
    <n_out output refs>, m, l, acc — the first prefetch ref is always
    ``pos``; paged adds the two block tables (consumed by the index maps
    only). ``n_out`` is 1 (finalized output) or 3 (emit_state m/l/acc)."""
    def kernel(*refs):
        pos_ref = refs[0]
        rest = list(refs[n_prefetch:])
        q_ref = rest.pop(0)
        h2c_ref = rest.pop(0)
        k_ref = rest.pop(0)
        ks_ref = rest.pop(0) if has_ks else None
        v_ref = rest.pop(0)
        vs_ref = rest.pop(0) if has_vs else None
        out_refs = tuple(rest[:n_out])
        m_scr, l_scr, acc_scr = rest[n_out:]
        _fused_tile(pos_ref, q_ref, h2c_ref, k_ref, ks_ref, v_ref, vs_ref,
                    out_refs, m_scr, l_scr, acc_scr, **flags)
    return kernel


def _fused_shapes(q_rep, v_rows, h2c, share_values):
    b, r_total, hd = q_rep.shape
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h2c.shape[0]))
    h_total = h2c.shape[1]
    if share_values:
        assert v_rows == r_total, (v_rows, r_total)
        v_rep = 1
    else:
        assert h_total % v_rows == 0, (h_total, v_rows)
        v_rep = h_total // v_rows
    rows_acc = r_total if share_values else h_total
    # h2c rides as an (H, 1) column per slot: a (1, H) row block of a
    # (B, H) array is not (8, 128)-tileable, and the column compares
    # against the (H, R) iota without an in-kernel transpose.
    h2c = h2c.astype(jnp.int32)[..., None]
    return b, r_total, hd, h2c, h_total, v_rep, rows_acc


def _tile_major(scales, ts):
    """(N, rows, S) per-row scales -> (N * S/ts, rows, ts), one block per
    (slot, S-tile). A (1, rows, ts) block of the (N, rows, S) array is not
    (8, 128)-tileable when ts < S and ts % 128 != 0; in tile-major order
    each block spans its array's last two dims (as a paged scale pool's
    pages already do)."""
    n, rows, s = scales.shape
    return (scales.reshape(n, rows, s // ts, ts).transpose(0, 2, 1, 3)
            .reshape(n * (s // ts), rows, ts))


def _fused_outputs(b, r_total, h_total, rows_acc, hd, emit_state):
    """Output BlockSpecs/shapes for the fused decode (grid-rank agnostic:
    every output block is slot ``bb``'s whole slab). ``emit_state`` m/l
    are written as (R, 1) columns — the scratch layout — and squeezed by
    ``_squeeze_state`` outside the kernel."""
    def slot(bb, *_):
        return (bb, 0, 0)

    if not emit_state:
        return (pl.BlockSpec((1, h_total, hd), slot),
                jax.ShapeDtypeStruct((b, h_total, hd), jnp.float32))
    specs = [pl.BlockSpec((1, r_total, 1), slot),
             pl.BlockSpec((1, r_total, 1), slot),
             pl.BlockSpec((1, rows_acc, hd), slot)]
    shapes = [jax.ShapeDtypeStruct((b, r_total, 1), jnp.float32),
              jax.ShapeDtypeStruct((b, r_total, 1), jnp.float32),
              jax.ShapeDtypeStruct((b, rows_acc, hd), jnp.float32)]
    return specs, shapes


def chai_fused_decode(q_rep, k_cache, v_cache, h2c, pos, *, k_scale=None,
                      v_scale=None, reps_per_group=1, share_values=False,
                      window=0, ts=512, softcap=0.0, emit_state=False,
                      interpret=None):
    """One-pass fused clustered decode over a dense cache.

    q_rep: (B, R, hd) rep-head queries; k_cache: (B, KVk, S, hd) with
    KVk * reps_per_group == R (MHA clustered cache: KVk == R); v_cache:
    (B, KVv, S, hd) — per-head V (KVv == H), per-group V (GQA: H % KVv
    == 0) or clustered V (share_values: KVv == R); h2c: (B, H) or (H,)
    int32 flat head -> rep-row map; pos: (B,) int32. int8 caches pass
    per-row scales via ``k_scale``/``v_scale`` (B, rows, S) and are
    dequantized in VMEM. Returns (B, H, hd) fp32 in ONE kernel launch —
    no (B, R, S) score tensor is ever materialized.

    ``emit_state``: return the raw mergeable online-softmax triple
    (m (B, R), l (B, R), acc (B, rows_acc, hd)) instead of the finalized
    output — the relay shared-prefix merge contract."""
    if interpret is None:
        interpret = _interpret_default()
    assert not (share_values and reps_per_group > 1), \
        "clustered V is an MHA-only ablation"
    s = k_cache.shape[2]
    kv_k, kv_v = k_cache.shape[1], v_cache.shape[1]
    b, r_total, hd, h2c, h_total, v_rep, rows_acc = _fused_shapes(
        q_rep, kv_v, h2c, share_values)
    assert kv_k * reps_per_group == r_total, (kv_k, reps_per_group, r_total)
    ts = min(ts, s)
    if s % ts:
        ts = s
    n_tiles = s // ts
    scale = 1.0 / math.sqrt(hd)

    in_specs = [
        pl.BlockSpec((1, r_total, hd), lambda bb, ss, pos_r: (bb, 0, 0)),
        pl.BlockSpec((1, h_total, 1), lambda bb, ss, pos_r: (bb, 0, 0)),
        pl.BlockSpec((1, kv_k, ts, hd), lambda bb, ss, pos_r:
                     (bb, 0, ss, 0)),
    ]
    inputs = [q_rep, h2c, k_cache]
    if k_scale is not None:
        in_specs.append(pl.BlockSpec((1, kv_k, ts), lambda bb, ss, pos_r:
                                     (bb * n_tiles + ss, 0, 0)))
        inputs.append(_tile_major(k_scale, ts))
    in_specs.append(pl.BlockSpec((1, kv_v, ts, hd), lambda bb, ss, pos_r:
                                 (bb, 0, ss, 0)))
    inputs.append(v_cache)
    if v_scale is not None:
        in_specs.append(pl.BlockSpec((1, kv_v, ts), lambda bb, ss, pos_r:
                                     (bb * n_tiles + ss, 0, 0)))
        inputs.append(_tile_major(v_scale, ts))

    out_specs, out_shape = _fused_outputs(b, r_total, h_total, rows_acc,
                                          hd, emit_state)
    kernel = _fused_arg_router(
        1, k_scale is not None, v_scale is not None,
        n_out=3 if emit_state else 1, scale=scale, ts=ts,
        window=window, n_tiles=n_tiles, reps_per_group=reps_per_group,
        v_rep=v_rep, share_values=share_values, softcap=softcap,
        emit_state=emit_state)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_tiles),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((r_total, 1), jnp.float32),
                pltpu.VMEM((r_total, 1), jnp.float32),
                pltpu.VMEM((rows_acc, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(pos.astype(jnp.int32), *inputs)
    return _squeeze_state(out) if emit_state else out


def paged_chai_fused_decode(q_rep, k_pool, bt_k, v_pool, bt_v, h2c, pos, *,
                            k_scale_pool=None, v_scale_pool=None,
                            reps_per_group=1, share_values=False, window=0,
                            softcap=0.0, emit_state=False, interpret=None):
    """One-pass fused clustered decode over block-table page pools.

    q_rep: (B, R, hd); k_pool: (nP, KVk, page, hd) clustered pages (MHA:
    KVk == k_max) or the dense pool (GQA: KVk == n_kv_heads); v_pool:
    (nP, KVv, page, hd) — the dense per-head pool, or the clustered pool
    itself under ``share_values``; bt_k/bt_v: (B, P) int32 block tables
    (scalar-prefetched: they drive the K/V BlockSpec index maps, so pool
    pages stream HBM->VMEM exactly like dense tiles); h2c: (B, H) or
    (H,); pos: (B,). int8 pools pass ``k_scale_pool``/``v_scale_pool``
    (nP, rows, page) mirrors. Returns (B, H, hd) fp32 — one launch, no
    (B, R, S) scores, no densified pool gather.

    ``emit_state``: return (m (B, R), l (B, R), acc (B, rows_acc, hd))
    unfinalized — the relay suffix pass runs this over the private pages
    only and merges with the shared-prefix state before normalizing."""
    if interpret is None:
        interpret = _interpret_default()
    assert not (share_values and reps_per_group > 1), \
        "clustered V is an MHA-only ablation"
    kv_k, page = k_pool.shape[1], k_pool.shape[2]
    kv_v = v_pool.shape[1]
    b, r_total, hd, h2c, h_total, v_rep, rows_acc = _fused_shapes(
        q_rep, kv_v, h2c, share_values)
    assert kv_k * reps_per_group == r_total, (kv_k, reps_per_group, r_total)
    n_pages = bt_k.shape[1]
    assert bt_v.shape == bt_k.shape == (b, n_pages)
    scale = 1.0 / math.sqrt(hd)

    in_specs = [
        pl.BlockSpec((1, r_total, hd),
                     lambda bb, ss, pos_r, btk_r, btv_r: (bb, 0, 0)),
        pl.BlockSpec((1, h_total, 1),
                     lambda bb, ss, pos_r, btk_r, btv_r: (bb, 0, 0)),
        pl.BlockSpec((1, kv_k, page, hd),
                     lambda bb, ss, pos_r, btk_r, btv_r:
                     (btk_r[bb, ss], 0, 0, 0)),
    ]
    inputs = [q_rep, h2c, k_pool]
    if k_scale_pool is not None:
        in_specs.append(pl.BlockSpec((1, kv_k, page),
                                     lambda bb, ss, pos_r, btk_r, btv_r:
                                     (btk_r[bb, ss], 0, 0)))
        inputs.append(k_scale_pool)
    in_specs.append(pl.BlockSpec((1, kv_v, page, hd),
                                 lambda bb, ss, pos_r, btk_r, btv_r:
                                 (btv_r[bb, ss], 0, 0, 0)))
    inputs.append(v_pool)
    if v_scale_pool is not None:
        in_specs.append(pl.BlockSpec((1, kv_v, page),
                                     lambda bb, ss, pos_r, btk_r, btv_r:
                                     (btv_r[bb, ss], 0, 0)))
        inputs.append(v_scale_pool)

    out_specs, out_shape = _fused_outputs(b, r_total, h_total, rows_acc,
                                          hd, emit_state)
    kernel = _fused_arg_router(
        3, k_scale_pool is not None, v_scale_pool is not None,
        n_out=3 if emit_state else 1, scale=scale,
        ts=page, window=window, n_tiles=n_pages,
        reps_per_group=reps_per_group, v_rep=v_rep,
        share_values=share_values, softcap=softcap, emit_state=emit_state)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_pages),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((r_total, 1), jnp.float32),
                pltpu.VMEM((r_total, 1), jnp.float32),
                pltpu.VMEM((rows_acc, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(pos.astype(jnp.int32), bt_k.astype(jnp.int32),
      bt_v.astype(jnp.int32), *inputs)
    return _squeeze_state(out) if emit_state else out


# --------------------------------------- relay shared-prefix decode --------
def _relay_prefix_kernel(plen_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                         krow_ref, arow_ref, vrow_ref, m_ref, l_ref,
                         acc_ref, m_scr, l_scr, acc_scr, *, scale, ts,
                         n_tiles, softcap=0.0):
    """One (group, S-tile) step of the relay shared-prefix pass.

    All member slots of a relay group attend the SAME packed resident
    prefix K/V — the kernel batches their rep queries along one row axis
    (NR = Nmax * R) so the prefix streams HBM->VMEM once per group, not
    once per slot. Per-member cluster assignments differ, so three int32
    row maps route the gathers (spelled as one-hot matmuls, the MXU
    idiom): ``k_row`` query-row -> prefix K row, ``a_row`` accumulator
    row -> query row (the h2c broadcast, deferred from the suffix merge),
    ``v_row`` accumulator row -> prefix V row.

    Masking is ``idx < plen`` only — every prefix position precedes every
    decode query, so there is no causal constraint inside the prefix; the
    same mask hides the zero-padded tail of shorter groups."""
    g = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (NR, hd)
    k = k_ref[0].astype(jnp.float32)                     # (KV, Ts, hd)
    nr, hd = q.shape
    kv = k.shape[0]
    k_row = krow_ref[0]                                  # (NR, 1) int32
    oneh_k = (k_row == jax.lax.broadcasted_iota(
        jnp.int32, (nr, kv), 1)).astype(jnp.float32)     # (NR, KV)
    kg = jnp.dot(oneh_k, k.reshape(kv, ts * hd),
                 preferred_element_type=jnp.float32).reshape(nr, ts, hd)
    sc = jax.lax.dot_general(q[:, None, :], kg,
                             (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)[:, 0]
    if ks_ref is not None:   # int8: scores scaled by per-(row, pos) scales
        ksg = jnp.dot(oneh_k, ks_ref[0].astype(jnp.float32),
                      preferred_element_type=jnp.float32)  # (NR, Ts)
        sc = sc * ksg
    sc = sc * scale
    if softcap:
        sc = softcap * jnp.tanh(sc / softcap)
    idx = s * ts + jax.lax.broadcasted_iota(jnp.int32, (1, ts), 1)
    sc = jnp.where(idx < plen_ref[g], sc, NEG_INF)       # (NR, Ts)

    m_prev = m_scr[...]                                  # (NR, 1)
    m_new = jnp.maximum(
        jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True)), -1e30)
    alpha = jnp.exp(m_prev - m_new)                      # (NR, 1)
    p = jnp.exp(sc - m_new)                              # (NR, Ts)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new

    v = v_ref[0].astype(jnp.float32)                     # (VR, Ts, hd)
    if vs_ref is not None:
        v = v * vs_ref[0].astype(jnp.float32)[..., None]
    a_row = arow_ref[0]                                  # (A, 1) int32
    v_row = vrow_ref[0]                                  # (A, 1) int32
    a_total = a_row.shape[0]
    vr = v.shape[0]
    oneh_a = (a_row == jax.lax.broadcasted_iota(
        jnp.int32, (a_total, nr), 1)).astype(jnp.float32)  # (A, NR)
    p_a = jnp.dot(oneh_a, p, preferred_element_type=jnp.float32)
    alpha_a = jnp.dot(oneh_a, alpha, preferred_element_type=jnp.float32)
    oneh_v = (v_row == jax.lax.broadcasted_iota(
        jnp.int32, (a_total, vr), 1)).astype(jnp.float32)  # (A, VR)
    vg = jnp.dot(oneh_v, v.reshape(vr, ts * hd),
                 preferred_element_type=jnp.float32).reshape(
                     a_total, ts, hd)
    pv = jax.lax.dot_general(p_a[:, None, :], vg,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)[:, 0]
    acc_scr[...] = acc_scr[...] * alpha_a + pv           # (A, hd)

    @pl.when(s == n_tiles - 1)
    def _fin():
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]
        acc_ref[0] = acc_scr[...]


def relay_prefix_decode(q, k, v, k_row, a_row, v_row, plen, *,
                        k_scale=None, v_scale=None, ts=0, softcap=0.0,
                        interpret=None):
    """One batched shared-prefix attention pass per relay group.

    q: (G, NR, hd) member rep queries stacked per group (NR = Nmax * R,
    zero-padded members compute garbage rows that the engine's scatter
    discards); k: (G, KV, Sp, hd) packed resident prefix K (the radix
    chain's dense rows); v: (G, VR, Sp, hd) packed resident prefix V;
    k_row/a_row/v_row: (G, NR)/(G, A)/(G, A) int32 routing maps (see
    ``_relay_prefix_kernel``); plen: (G,) int32 valid prefix lengths
    (scalar-prefetched; masks the zero-padded tail — Sp is the page-
    aligned max over groups). int8 prefixes pass ``k_scale``/``v_scale``
    (G, rows, Sp) mirrors (share_values V codes ride scale-less, matching
    the clustered-pool reinterpret semantics). Returns the mergeable
    triple (m (G, NR), l (G, NR), acc (G, A, hd)) f32 — combine with the
    suffix ``emit_state`` triple via ``ops.merge_decode_states``."""
    if interpret is None:
        interpret = _interpret_default()
    g, nr, hd = q.shape
    kv, sp = k.shape[1], k.shape[2]
    vr = v.shape[1]
    a_total = a_row.shape[1]
    assert k_row.shape == (g, nr) and v_row.shape == (g, a_total)
    assert v.shape[2] == sp
    ts = ts or sp
    ts = min(ts, sp)
    if sp % ts:
        ts = sp
    n_tiles = sp // ts
    scale = 1.0 / math.sqrt(hd)

    in_specs = [
        pl.BlockSpec((1, nr, hd), lambda gg, ss, plen_r: (gg, 0, 0)),
        pl.BlockSpec((1, kv, ts, hd), lambda gg, ss, plen_r:
                     (gg, 0, ss, 0)),
    ]
    inputs = [q, k]
    if k_scale is not None:
        in_specs.append(pl.BlockSpec((1, kv, ts), lambda gg, ss, plen_r:
                                     (gg * n_tiles + ss, 0, 0)))
        inputs.append(_tile_major(k_scale, ts))
    in_specs.append(pl.BlockSpec((1, vr, ts, hd), lambda gg, ss, plen_r:
                                 (gg, 0, ss, 0)))
    inputs.append(v)
    if v_scale is not None:
        in_specs.append(pl.BlockSpec((1, vr, ts), lambda gg, ss, plen_r:
                                     (gg * n_tiles + ss, 0, 0)))
        inputs.append(_tile_major(v_scale, ts))
    # Row maps ride as (rows, 1) columns and m/l come back as (NR, 1)
    # columns: (1, rows) blocks of (G, rows) arrays are not
    # (8, 128)-tileable, and columns match the scratch layout.
    in_specs += [
        pl.BlockSpec((1, nr, 1), lambda gg, ss, plen_r: (gg, 0, 0)),
        pl.BlockSpec((1, a_total, 1), lambda gg, ss, plen_r: (gg, 0, 0)),
        pl.BlockSpec((1, a_total, 1), lambda gg, ss, plen_r: (gg, 0, 0)),
    ]
    inputs += [x.astype(jnp.int32)[..., None] for x in (k_row, a_row, v_row)]

    has_ks, has_vs = k_scale is not None, v_scale is not None

    def kernel(*refs):
        plen_ref = refs[0]
        rest = list(refs[1:])
        q_ref = rest.pop(0)
        k_ref = rest.pop(0)
        ks_ref = rest.pop(0) if has_ks else None
        v_ref = rest.pop(0)
        vs_ref = rest.pop(0) if has_vs else None
        (krow_ref, arow_ref, vrow_ref, m_ref, l_ref, acc_ref,
         m_scr, l_scr, acc_scr) = rest
        _relay_prefix_kernel(plen_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                             krow_ref, arow_ref, vrow_ref, m_ref, l_ref,
                             acc_ref, m_scr, l_scr, acc_scr, scale=scale,
                             ts=ts, n_tiles=n_tiles, softcap=softcap)

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, n_tiles),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, nr, 1), lambda gg, ss, plen_r: (gg, 0, 0)),
                pl.BlockSpec((1, nr, 1), lambda gg, ss, plen_r: (gg, 0, 0)),
                pl.BlockSpec((1, a_total, hd), lambda gg, ss, plen_r:
                             (gg, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((nr, 1), jnp.float32),
                pltpu.VMEM((nr, 1), jnp.float32),
                pltpu.VMEM((a_total, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((g, nr, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, nr, 1), jnp.float32),
            jax.ShapeDtypeStruct((g, a_total, hd), jnp.float32),
        ],
        interpret=interpret,
    )(plen.astype(jnp.int32), *inputs)
    return m[..., 0], l[..., 0], acc
