"""Pallas TPU flash attention: decode (one query token), paged decode, and
prefill.

Decode: grid (B, H, S/Ts), online-softmax carried in VMEM scratch across the
sequentially-iterated S-tile axis; K/V stream HBM->VMEM via BlockSpecs; the
GQA group map (h -> h // q_per_kv) is a static index_map. Valid-length
masking uses a scalar-prefetched per-example ``pos`` vector.

Paged decode: same online softmax, but K/V live in a shared page pool
(nP, KV, page, hd) and each slot's pages are located through
scalar-prefetched int32 block tables — the tables drive the K/V BlockSpec
index_maps, so the pool pages stream HBM->VMEM exactly like dense tiles
(the paged-attention idiom; one S-tile == one page).

Prefill: grid (B, H, Tq/Tb, S/Ts) with causal block skipping.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _interpret_default():
    return jax.default_backend() == "cpu"


# ------------------------------------------------------------------ decode
def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale, window, ts, n_tiles):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                      # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (Ts, hd)
    sc = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
    idx = s * ts + jax.lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    sc = jnp.where(valid, sc, NEG_INF)                       # (Ts, 1)

    m_prev = m_scr[...]                                      # (1, 1)
    m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(sc)), -1e30)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)                                  # (Ts, 1)
    l_new = l_scr[...] * alpha + jnp.sum(p)
    v = v_ref[0, 0].astype(jnp.float32)                      # (Ts, hd)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.T, v, preferred_element_type=jnp.float32)          # (1, hd)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(s == n_tiles - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-37)).astype(
                           o_ref.dtype)


def flash_decode(q, k_cache, v_cache, pos, *, window=0, ts=512,
                 interpret=None):
    """q: (B, H, hd); k/v_cache: (B, KV, S, hd); pos: (B,) int32.
    Returns (B, H, hd) fp32."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, hd = q.shape
    n_kv, s = k_cache.shape[1], k_cache.shape[2]
    qpk = h // n_kv
    ts = min(ts, s)
    assert s % ts == 0, (s, ts)
    n_tiles = s // ts
    scale = 1.0 / math.sqrt(hd)

    grid = (b, h, n_tiles)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               ts=ts, n_tiles=n_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, 1, hd), lambda bb, hh, ss, pos_r:
                             (bb, hh, 0, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, hh, ss, pos_r:
                             (bb, hh // qpk, ss, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, hh, ss, pos_r:
                             (bb, hh // qpk, ss, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, hd), lambda bb, hh, ss, pos_r:
                                   (bb, hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), q[:, :, None], k_cache, v_cache)
    return out[:, :, 0]


# ------------------------------------------------------------ paged decode
def _paged_decode_kernel(pos_ref, btk_ref, btv_ref, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, *, scale, window,
                         page, n_pages):
    b = pl.program_id(0)
    s = pl.program_id(2)               # logical page index

    @pl.when(s == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                      # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (page, hd)
    sc = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
    idx = s * page + jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    pos = pos_ref[b]
    valid = idx <= pos
    if window:
        valid &= (pos - idx) < window
    sc = jnp.where(valid, sc, NEG_INF)                       # (page, 1)

    m_prev = m_scr[...]                                      # (1, 1)
    m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(sc)), -1e30)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p)
    v = v_ref[0, 0].astype(jnp.float32)                      # (page, hd)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p.T, v, preferred_element_type=jnp.float32)          # (1, hd)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(s == n_pages - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-37)).astype(
                           o_ref.dtype)


def paged_decode(q, kv_pool, bt_k, bt_v, pos, *, window=0, interpret=None):
    """Paged flash decode. q: (B, H, hd); kv_pool: (nP, KV, page, hd)
    shared K/V page pool; bt_k/bt_v: (B, P) int32 block tables (a slot's
    logical page j lives in physical page bt[b, j]; null entries point at
    the reserved page 0 and are masked by ``pos``); pos: (B,) int32.
    Logical sequence length is P * page. Returns (B, H, hd) fp32."""
    if interpret is None:
        interpret = _interpret_default()
    b, h, hd = q.shape
    n_kv, page = kv_pool.shape[1], kv_pool.shape[2]
    n_pages = bt_k.shape[1]
    assert bt_v.shape == bt_k.shape == (b, n_pages)
    qpk = h // n_kv
    scale = 1.0 / math.sqrt(hd)

    grid = (b, h, n_pages)
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               window=window, page=page, n_pages=n_pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, 1, hd),
                             lambda bb, hh, ss, pos_r, btk_r, btv_r:
                             (bb, hh, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda bb, hh, ss, pos_r, btk_r, btv_r:
                             (btk_r[bb, ss], hh // qpk, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda bb, hh, ss, pos_r, btk_r, btv_r:
                             (btv_r[bb, ss], hh // qpk, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, hd),
                                   lambda bb, hh, ss, pos_r, btk_r, btv_r:
                                   (bb, hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), bt_k.astype(jnp.int32), bt_v.astype(jnp.int32),
      q[:, :, None], kv_pool, kv_pool)
    return out[:, :, 0]


# ------------------------------------------------------------------ prefill
def _prefill_kernel(off_ref, q_ref, k_ref, v_ref, out_refs, m_scr, l_scr,
                    acc_scr, *, scale, window, tq, ts, n_tiles,
                    softcap=0.0, emit_state=False):
    i = pl.program_id(2)           # q tile
    j = pl.program_id(3)           # kv tile

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Query offset arrives as a scalar-prefetched value so it may be
    # TRACED — the prefix cache's suffix prefill runs one jit per suffix
    # bucket with the cached-prefix length varying per request.
    q_start = off_ref[0] + i * tq
    kv_start = j * ts
    # causal block skip: this kv tile intersects the causal triangle iff
    # kv_start <= q_end; window skip iff kv_end > q_start - window
    q_end = q_start + tq - 1
    relevant = kv_start <= q_end
    if window:
        relevant &= (kv_start + ts - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (Tq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (Ts, hd)
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if softcap:
            # tanh logit softcap (gemma2): after QK-scale, before the
            # causal mask — the jnp oracle's exact insertion point.
            sc = softcap * jnp.tanh(sc / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 0)
        ki = kv_start + jax.lax.broadcasted_iota(jnp.int32, (tq, ts), 1)
        valid = ki <= qi
        if window:
            valid &= (qi - ki) < window
        sc = jnp.where(valid, sc, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(sc, -1)), -1e30)
        alpha = jnp.exp(m_prev - m_new)                      # (Tq,)
        p = jnp.exp(sc - m_new[:, None])                     # (Tq, Ts)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, -1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    @pl.when(j == n_tiles - 1)
    def _fin():
        if emit_state:
            m_ref, l_ref, acc_ref = out_refs
            m_ref[0, 0] = m_scr[...]
            l_ref[0, 0] = l_scr[...]
            acc_ref[0, 0] = acc_scr[...]
        else:
            (o_ref,) = out_refs
            o_ref[0, 0] = (acc_scr[...]
                           / jnp.maximum(l_scr[:, 0],
                                         1e-37)[:, None]).astype(
                               o_ref.dtype)


def flash_prefill(q, k, v, *, offset=0, window=0, tq=256, ts=512,
                  softcap=0.0, emit_state=False, interpret=None):
    """q: (B, T, H, hd); k/v: (B, S, KV, hd) (time-major KV, as projected).
    Causal: query t at absolute position offset+t. ``offset`` may be a
    python int OR a traced int32 scalar (it rides in via scalar prefetch)
    — the prefix-cache suffix prefill attends new tokens over cached
    prefix KV with a per-request offset under one jit per suffix bucket.
    Returns (B, T, H, hd).

    ``emit_state``: return the raw head-major online-softmax triple
    (m (B, H, T), l (B, H, T), acc (B, H, T, hd)) f32 instead of the
    finalized output — the paged suffix prefill merges this causal
    self-attention pass with a ``paged_prefix_attend`` pass over the
    cached prefix pages via ``ops.merge_prefill_states``."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, hd = q.shape
    s, n_kv = k.shape[1], k.shape[2]
    qpk = h // n_kv
    tq = min(tq, t)
    ts = min(ts, s)
    assert t % tq == 0 and s % ts == 0, (t, tq, s, ts)
    n_tiles = s // ts
    scale = 1.0 / math.sqrt(hd)
    # kernels want head-major layouts: (B, H, T, hd)
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    off = jnp.asarray(offset, jnp.int32).reshape((1,))

    grid = (b, h, t // tq, n_tiles)
    base = functools.partial(_prefill_kernel, scale=scale, window=window,
                             tq=tq, ts=ts, n_tiles=n_tiles,
                             softcap=softcap, emit_state=emit_state)
    n_out = 3 if emit_state else 1

    def kernel(off_ref, q_ref, k_ref, v_ref, *rest):
        base(off_ref, q_ref, k_ref, v_ref, tuple(rest[:n_out]),
             *rest[n_out:])

    if emit_state:
        out_specs, out_shape = _state_outputs(b, h, t, tq, hd)
    else:
        out_specs = pl.BlockSpec((1, 1, tq, hd),
                                 lambda bb, hh, ii, jj, off_r:
                                 (bb, hh, ii, 0))
        out_shape = jax.ShapeDtypeStruct((b, h, t, hd), q.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, tq, hd), lambda bb, hh, ii, jj, off_r:
                             (bb, hh, ii, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, hh, ii, jj, off_r:
                             (bb, hh // qpk, jj, 0)),
                pl.BlockSpec((1, 1, ts, hd), lambda bb, hh, ii, jj, off_r:
                             (bb, hh // qpk, jj, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(off, qh, kh, vh)
    if emit_state:
        return _squeeze_state(out)
    return out.transpose(0, 2, 1, 3)


def _state_outputs(b, h, t, tq, hd):
    """Head-major (m, l, acc) output specs for the prefill-state kernels.
    m/l are written as (Tq, 1) columns, the scratch layout: a (1, Tq) row
    block of a (B, H, T) array is not (8, 128)-tileable."""
    def tile(bb, hh, ii, *_):
        return (bb, hh, ii, 0)

    specs = [pl.BlockSpec((1, 1, tq, 1), tile),
             pl.BlockSpec((1, 1, tq, 1), tile),
             pl.BlockSpec((1, 1, tq, hd), tile)]
    shapes = [jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
              jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32),
              jax.ShapeDtypeStruct((b, h, t, hd), jnp.float32)]
    return specs, shapes


def _squeeze_state(out):
    """(m, l, acc) with m/l written as (.., rows, 1) columns -> (.., rows)."""
    m, l, acc = out
    return m[..., 0], l[..., 0], acc


# --------------------------------------------------- paged prefix attend
def _scale_row(s_ref, kvh):
    """KV head ``kvh``'s (1, page) scale row out of the page's whole
    (KV, page) scale block: a (1, 1, page) block of the (nP, KV, page)
    scale pool is not (8, 128)-tileable, so the row is picked in-kernel."""
    return s_ref[0, pl.ds(kvh, 1), :].astype(jnp.float32)


def _paged_prefix_kernel(plen_ref, btk_ref, btv_ref, q_ref, k_ref, ks_ref,
                         v_ref, vs_ref, m_ref, l_ref, acc_ref, m_scr,
                         l_scr, acc_scr, *, scale, page, n_pages, qpk,
                         softcap=0.0):
    """Suffix-prefill prefix pass: every suffix query attends every cached
    prefix position (< plen) — no causal constraint inside the prefix.
    Pages beyond the prefix are redirected to the null sink page by the
    index map and skipped here; emits the mergeable m/l/acc triple."""
    b = pl.program_id(0)
    kvh = pl.program_id(1) // qpk      # KV head of this query head
    j = pl.program_id(3)               # logical page index

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    plen = plen_ref[b]

    @pl.when(j * page < plen)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (Tq, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (page, hd)
        sc = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if ks_ref is not None:   # int8: per-(row, pos) K scales
            sc = sc * _scale_row(ks_ref, kvh)                # (1, page)
        sc = sc * scale
        if softcap:
            sc = softcap * jnp.tanh(sc / softcap)
        tq = q.shape[0]
        ki = j * page + jax.lax.broadcasted_iota(jnp.int32, (tq, page), 1)
        sc = jnp.where(ki < plen, sc, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(jnp.maximum(m_prev, jnp.max(sc, -1)), -1e30)
        alpha = jnp.exp(m_prev - m_new)                  # (Tq,)
        p = jnp.exp(sc - m_new[:, None])                 # (Tq, page)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, -1)
        v = v_ref[0, 0].astype(jnp.float32)              # (page, hd)
        if vs_ref is not None:
            v = v * _scale_row(vs_ref, kvh).T                # (page, 1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    @pl.when(j == n_pages - 1)
    def _fin():
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        acc_ref[0, 0] = acc_scr[...]


def paged_prefix_attend(q, kv_pool, bt_k, bt_v, plen, *, k_scale_pool=None,
                        v_scale_pool=None, softcap=0.0, tq=256,
                        interpret=None):
    """Attend suffix queries over cached prefix pages, streamed via
    scalar-prefetched block tables (no densifying slot-capacity gather).

    q: (B, T, H, hd) suffix queries; kv_pool: (nP, KV, page, hd) dense
    page pool; bt_k/bt_v: (B, P) int32 block tables; plen: (B,) int32
    cached-prefix token counts (entries past the prefix are redirected to
    the null sink page and masked). int8 pools pass the mirror-shaped
    scale pools. Returns the HEAD-MAJOR mergeable triple (m (B, H, T),
    l (B, H, T), acc (B, H, T, hd)) f32 — combine with the suffix
    ``flash_prefill(..., emit_state=True)`` pass via
    ``ops.merge_prefill_states``."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, hd = q.shape
    n_kv, page = kv_pool.shape[1], kv_pool.shape[2]
    n_pages = bt_k.shape[1]
    assert bt_v.shape == bt_k.shape == (b, n_pages)
    qpk = h // n_kv
    tq = min(tq, t)
    assert t % tq == 0, (t, tq)
    scale = 1.0 / math.sqrt(hd)
    qh = q.transpose(0, 2, 1, 3)       # (B, H, T, hd)

    def _k_page(bb, ss, plen_r, btk_r, btv_r):
        # Null-sink redirect past the prefix: the fetch is cheap (page 0)
        # and the compute is skipped in-kernel.
        return jnp.where(ss * page < plen_r[bb], btk_r[bb, ss], 0)

    def _v_page(bb, ss, plen_r, btk_r, btv_r):
        return jnp.where(ss * page < plen_r[bb], btv_r[bb, ss], 0)

    in_specs = [
        pl.BlockSpec((1, 1, tq, hd),
                     lambda bb, hh, ii, jj, plen_r, btk_r, btv_r:
                     (bb, hh, ii, 0)),
        pl.BlockSpec((1, 1, page, hd),
                     lambda bb, hh, ii, jj, plen_r, btk_r, btv_r:
                     (_k_page(bb, jj, plen_r, btk_r, btv_r),
                      hh // qpk, 0, 0)),
    ]
    inputs = [qh, kv_pool]
    if k_scale_pool is not None:
        in_specs.append(pl.BlockSpec(
            (1, n_kv, page), lambda bb, hh, ii, jj, plen_r, btk_r, btv_r:
            (_k_page(bb, jj, plen_r, btk_r, btv_r), 0, 0)))
        inputs.append(k_scale_pool)
    in_specs.append(pl.BlockSpec(
        (1, 1, page, hd), lambda bb, hh, ii, jj, plen_r, btk_r, btv_r:
        (_v_page(bb, jj, plen_r, btk_r, btv_r), hh // qpk, 0, 0)))
    inputs.append(kv_pool)
    if v_scale_pool is not None:
        in_specs.append(pl.BlockSpec(
            (1, n_kv, page), lambda bb, hh, ii, jj, plen_r, btk_r, btv_r:
            (_v_page(bb, jj, plen_r, btk_r, btv_r), 0, 0)))
        inputs.append(v_scale_pool)

    has_ks = k_scale_pool is not None
    has_vs = v_scale_pool is not None

    def kernel(plen_ref, btk_ref, btv_ref, *refs):
        rest = list(refs)
        q_ref = rest.pop(0)
        k_ref = rest.pop(0)
        ks_ref = rest.pop(0) if has_ks else None
        v_ref = rest.pop(0)
        vs_ref = rest.pop(0) if has_vs else None
        m_ref, l_ref, acc_ref, m_scr, l_scr, acc_scr = rest
        _paged_prefix_kernel(plen_ref, btk_ref, btv_ref, q_ref, k_ref,
                             ks_ref, v_ref, vs_ref, m_ref, l_ref, acc_ref,
                             m_scr, l_scr, acc_scr, scale=scale, page=page,
                             n_pages=n_pages, qpk=qpk, softcap=softcap)

    out_specs, out_shape = _state_outputs(b, h, t, tq, hd)
    return _squeeze_state(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, t // tq, n_pages),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, hd), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(plen.astype(jnp.int32), bt_k.astype(jnp.int32),
      bt_v.astype(jnp.int32), *inputs))
