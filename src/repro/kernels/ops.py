"""Jit'd dispatch wrappers over the Pallas kernels.

On CPU (this container) kernels run with interpret=True; on TPU they lower
to Mosaic. ``chai_decode_attention`` / ``paged_chai_decode_attention`` are
the public decode ops: ONE fused Pallas launch per decode step (online
softmax over rep-head scores + h2c-broadcast AV, int8 dequant in VMEM) —
the pre-fusion three-kernel pipeline survives only as the oracle in
``repro.kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import chai_attention as ck
from repro.kernels import flash_attention as fk


@functools.partial(jax.jit, static_argnames=("window", "ts", "interpret"))
def flash_decode_attention(q, k_cache, v_cache, pos, *, window=0, ts=512,
                           interpret=None):
    return fk.flash_decode(q, k_cache, v_cache, pos, window=window, ts=ts,
                           interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "tq", "ts", "softcap",
                                    "emit_state", "interpret"))
def flash_prefill_attention(q, k, v, offset=0, *, window=0, tq=256, ts=512,
                            softcap=0.0, emit_state=False, interpret=None):
    """``offset`` is a regular (traceable) argument: the prefix-cache
    suffix prefill varies it per request without retracing. ``softcap``
    is static — a python float baked into the kernel (0 = off).
    ``emit_state`` returns the head-major mergeable (m, l, acc) triple
    instead of the finalized output (see ``merge_prefill_states``)."""
    return fk.flash_prefill(q, k, v, offset=offset, window=window, tq=tq,
                            ts=ts, softcap=softcap, emit_state=emit_state,
                            interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "tq", "interpret"))
def paged_prefix_attention(q, kv_pool, bt_k, bt_v, plen, *,
                           k_scale_pool=None, v_scale_pool=None,
                           softcap=0.0, tq=256, interpret=None):
    """Suffix-prefill prefix pass over block-table pages: q (B, T, H, hd)
    suffix queries attend every cached prefix position (< plen, (B,)
    int32) streaming only real pages — no slot-capacity densify. Returns
    the head-major mergeable (m, l, acc) triple; combine with the
    ``flash_prefill_attention(..., emit_state=True)`` suffix pass via
    ``merge_prefill_states`` and normalize with
    ``finalize_prefill_state``."""
    return fk.paged_prefix_attend(q, kv_pool, bt_k, bt_v, plen,
                                  k_scale_pool=k_scale_pool,
                                  v_scale_pool=v_scale_pool,
                                  softcap=softcap, tq=tq,
                                  interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("reps_per_group", "share_values",
                                    "window", "ts", "softcap", "emit_state",
                                    "interpret"))
def chai_decode_attention(q_rep, k_cache, v_cache, h2c, pos, *,
                          k_scale=None, v_scale=None, reps_per_group=1,
                          share_values=False, window=0, ts=512, softcap=0.0,
                          emit_state=False, interpret=None):
    """The paper's decode op — ONE fused Pallas launch. q_rep: (B, R, hd)
    rep-head queries; k_cache: (B, KVk, S, hd) (clustered for MHA:
    KVk==R); v_cache: (B, KVv, S, hd) per-head / per-group / clustered
    (share_values) V; h2c: (B, H) or (H,) flat head->rep-row map; pos:
    (B,). int8 caches pass per-row ``k_scale``/``v_scale`` (B, rows, S).
    Returns (B, H, hd) fp32; no (B, R, S) scores touch HBM."""
    return ck.chai_fused_decode(q_rep, k_cache, v_cache, h2c, pos,
                                k_scale=k_scale, v_scale=v_scale,
                                reps_per_group=reps_per_group,
                                share_values=share_values, window=window,
                                ts=ts, softcap=softcap,
                                emit_state=emit_state, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, kv_pool, bt_k, bt_v, pos, *, window=0,
                           interpret=None):
    """Paged flash decode over a block-table page pool. q: (B, H, hd);
    kv_pool: (nP, KV, page, hd); bt_k/bt_v: (B, P) int32; pos: (B,).
    Returns (B, H, hd) fp32."""
    return fk.paged_decode(q, kv_pool, bt_k, bt_v, pos, window=window,
                           interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("reps_per_group", "share_values",
                                    "window", "softcap", "emit_state",
                                    "interpret"))
def paged_chai_decode_attention(q_rep, k_pool, bt_k, v_pool, bt_v, h2c,
                                pos, *, layer=0, k_scale_pool=None,
                                v_scale_pool=None, reps_per_group=1,
                                share_values=False, window=0, softcap=0.0,
                                emit_state=False, interpret=None):
    """The paper's decode op over the serving engine's paged layout — ONE
    fused Pallas launch streaming tiles of several pages through VMEM (no
    densifying gather), none past a slot's ``pos``. q_rep: (B, R, hd);
    k_pool: (L, nP, KVk, page, hd) clustered pages (MHA: KVk == k_max)
    or the dense pool (GQA), stacked by layer; v_pool: (L, nP, KVv,
    page, hd) per-head V pages, or the clustered
    pool under ``share_values``; ``layer``: int32 scalar (traced), the
    layer the pages are read from — the stack is never sliced; bt_k/bt_v:
    (B, P) int32 block tables; h2c: (B, H) or (H,). int8 pools pass the
    mirror-shaped (L, nP, rows, page) scale pools. Returns (B, H, hd)
    fp32."""
    return ck.paged_chai_fused_decode(
        q_rep, k_pool, bt_k, v_pool, bt_v, h2c, pos, layer=layer,
        k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool,
        reps_per_group=reps_per_group, share_values=share_values,
        window=window, softcap=softcap, emit_state=emit_state,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write_rows(pool, layer, page_idx, row, new, *, interpret=None):
    """Decode-step token write into a stacked page pool, in place: slot
    b's rows ``new[b]`` land at ``pool[layer, page_idx[b], :, row[b]]``.
    pool: (L, nP, rows, page, hd) or (L, nP, rows, page) int8 scales;
    new: (B, rows, hd) or (B, rows). Pages must be distinct apart from the
    null sink page 0. Returns the pool (aliased to the input buffer)."""
    return ck.paged_write_rows(pool, layer, page_idx, row, new,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("ts", "softcap", "interpret"))
def relay_prefix_attention(q, k, v, k_row, a_row, v_row, plen, *,
                           k_scale=None, v_scale=None, ts=0, softcap=0.0,
                           interpret=None):
    """ONE batched shared-prefix attention pass per relay group (the
    RelayAttention idea keyed by radix node): member rep queries stack
    along one row axis so the packed resident prefix streams HBM->VMEM
    once per GROUP, not once per slot — decode cost for N slots sharing a
    system prompt drops from O(N * prefix) to O(prefix) per step. Returns
    the mergeable (m, l, acc) triple; combine with the suffix
    ``emit_state`` triple via ``merge_decode_states`` and normalize with
    ``finalize_decode_state``."""
    return ck.relay_prefix_decode(q, k, v, k_row, a_row, v_row, plen,
                                  k_scale=k_scale, v_scale=v_scale, ts=ts,
                                  softcap=softcap, interpret=interpret)


# ----------------------------------------- online-softmax state merging ----
def _bcast_h2c(h2c, b):
    if h2c.ndim == 1:
        h2c = jnp.broadcast_to(h2c, (b, h2c.shape[0]))
    return h2c


def merge_decode_states(s1, s2, h2c, *, share_values=False):
    """Online-softmax combine of two mergeable decode-state triples.

    Each state is (m (B, R), l (B, R), acc (B, rows_acc, hd)) as emitted
    by the fused decode kernels under ``emit_state`` (rows_acc == H, or R
    under ``share_values``). The combine is the flash-attention identity:
    m = max(m1, m2); l = l1*e^(m1-m) + l2*e^(m2-m); acc likewise, with
    the per-rep rescale broadcast to member-head acc rows through
    ``h2c``. An empty state (m = NEG_INF, l = 0, acc = 0) is the EXACT
    identity: the other side's m is kernel-clamped >= -1e30, so its
    rescale is e^0 == 1.0 bitwise and the empty side contributes 0."""
    m1, l1, acc1 = s1
    m2, l2, acc2 = s2
    b = m1.shape[0]
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = l1 * c1 + l2 * c2
    if share_values:
        a1, a2 = c1, c2            # acc rows are the rep rows themselves
    else:
        h2c = _bcast_h2c(h2c, b)
        a1 = jnp.take_along_axis(c1, h2c, axis=1)     # (B, H)
        a2 = jnp.take_along_axis(c2, h2c, axis=1)
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    return m, l, acc


def finalize_decode_state(state, h2c, *, share_values=False):
    """Normalize a (possibly merged) decode-state triple to (B, H, hd)
    fp32. Bitwise-identical to the fused kernels' in-kernel one-hot
    finalize: the one-hot matmul there sums exactly one nonzero term per
    row, which is this gather."""
    m, l, acc = state
    b = m.shape[0]
    h2c = _bcast_h2c(h2c, b)
    if share_values:
        out_r = acc / jnp.maximum(l, 1e-37)[..., None]
        return jnp.take_along_axis(out_r, h2c[..., None], axis=1)
    l_full = jnp.take_along_axis(l, h2c, axis=1)
    return acc / jnp.maximum(l_full, 1e-37)[..., None]


def merge_prefill_states(s1, s2):
    """Online-softmax combine of two head-major prefill-state triples
    (m (B, H, T), l (B, H, T), acc (B, H, T, hd)) — the prefix pass
    (``paged_prefix_attention``) and the causal suffix self-attention
    pass (``flash_prefill_attention(emit_state=True)``). An all-masked
    prefix (plen == 0, the cold first chunk) merges as the exact
    identity."""
    m1, l1, acc1 = s1
    m2, l2, acc2 = s2
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = l1 * c1 + l2 * c2
    acc = acc1 * c1[..., None] + acc2 * c2[..., None]
    return m, l, acc


def finalize_prefill_state(state, dtype=jnp.float32):
    """Normalize a head-major prefill-state triple to (B, T, H, hd)."""
    m, l, acc = state
    out = acc / jnp.maximum(l, 1e-37)[..., None]
    return out.transpose(0, 2, 1, 3).astype(dtype)


def decode_flop_estimate(b, h, r, s, hd, *, share_values=False, window=0):
    """Analytic decode-attention FLOPs: clustered scores + AV.

    ``share_values``: the CHAI-QKV ablation prunes V rows too, so AV is
    R·S·hd, not H·S·hd. ``window``: sliding-window attention touches at
    most ``window`` positions, so effective S = min(S, window)."""
    s_eff = min(s, window) if window else s
    av_rows = r if share_values else h
    scores = 2.0 * b * r * s_eff * hd
    av = 2.0 * b * av_rows * s_eff * hd
    return scores + av


# --- fused-vs-pipeline analytic lane (benchmarks/bench_latency.py) ---------
def decode_launch_count(fused=True):
    """Kernel launches per CHAI decode step: the fused path is ONE
    ``pallas_call``; the retired pipeline was QK -> row softmax -> AV."""
    return 1 if fused else 3


def decode_hbm_bytes_estimate(b, h, r, s, hd, *, cache_bytes=4,
                              share_values=False, window=0, fused=True):
    """Analytic HBM bytes moved by one CHAI decode-attention step.

    Both paths stream the same cache tiles (K: R rep rows; V: H per-head
    rows, or R under ``share_values``) plus the (negligible) q/out
    vectors. The three-kernel pipeline additionally round-trips the
    (B, R, S) fp32 score tensor through HBM three times (QK write,
    softmax read+write) and re-reads the normalized rows per member head
    (B, H, S) in AV — exactly the traffic fusion deletes."""
    s_eff = min(s, window) if window else s
    v_rows = r if share_values else h
    cache = b * (r + v_rows) * s_eff * hd * cache_bytes
    qout = b * (r + h) * hd * 4
    total = cache + qout
    if not fused:
        total += b * r * s_eff * 4 * 3        # scores: write, read, write
        total += b * h * s_eff * 4            # AV reads A row per head
    return float(total)


# --- relay shared-prefix analytic lane (benchmarks/bench_latency.py) -------
def relay_prefix_hbm_bytes_estimate(k_rows, v_rows, prefix_len, hd, *,
                                    cache_bytes=4, int8_scales=False):
    """HBM bytes one relay group streams for its shared-prefix pass per
    decode step — independent of the member count N by construction: the
    packed resident prefix (k_rows + v_rows KV rows x prefix_len x hd)
    is read ONCE per group. Per-member q/acc traffic is O(N * R * hd),
    negligible against O(prefix) and excluded here exactly as
    ``decode_hbm_bytes_estimate`` treats its q/out vectors. Contrast with
    the non-relay cost: each of the N slots re-streams the same prefix
    through its own block table, N x this figure."""
    total = (k_rows + v_rows) * prefix_len * hd * cache_bytes
    if int8_scales:
        total += (k_rows + v_rows) * prefix_len * 4
    return float(total)


def relay_prefix_mxu_pass_estimate(n_members, r, prefix_len, *, ts,
                                   lanes=128):
    """Systolic-array passes over the prefix for one relay group's QK.

    The member rep rows batch along the MXU row axis, so the pass count
    is flat in N until N * R exceeds one ``lanes``-row tile — the
    hardware-cost spelling of "prefix attention is O(prefix), not
    O(N * prefix)". The per-request baseline is N launches of
    ceil(R / lanes) * ceil(prefix / ts) passes each."""
    import math
    return (math.ceil(max(n_members, 1) * r / lanes)
            * math.ceil(max(prefix_len, 1) / ts))
