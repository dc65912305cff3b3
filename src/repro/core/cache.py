"""CHAI KV-cache layouts: the cohort flow (dense -> clustered) and the
*paged* layout the serving engine runs on.

Two layouts, one phase machine (PREFILL -> WARMUP -> CLUSTER -> STEADY):

1. **Cohort** (``chai_state_structs`` / ``compact_kv``) — the paper's
   batch-lockstep flow, a library flow for the dry-run and the examples
   (``launch/dryrun.py``, ``examples/``). ``compact_kv`` is §3.5's
   "remove the Key tokens associated [with pruned heads]": after
   membership identification the dense K cache is gathered down to
   representative rows. Run it as a donated jit so the full cache's
   buffer is released on device.

2. **Paged** (``paged_state_structs``, the serving engine's layout) —
   fixed-size pages of ``page_size`` tokens spanning all global layers,
   drawn from two device pools (``kvp``: dense K/V rows, ``n_kv_heads``
   wide; ``cp``: clustered rows, ``k_max`` wide), addressed through
   per-slot int32 block tables (``bt_kg``/``bt_vg`` -> ``kvp``,
   ``bt_kc``/``bt_vc`` -> ``cp``), beside a per-slot ``phase`` vector and
   the ``chai_scores`` warmup-feature buffer. A model with no global
   attention layer has nothing to page: its state holds no pools and no
   block tables, only its local rings or recurrent state, ``pos``,
   ``phase`` and ``chai_scores``, and every per-slot function below skips
   the pools it lacks. Page 0 of every pool is a reserved *null sink*:
   unallocated block-table entries point at it, so masked/oob writes land
   harmlessly and reads from it are always masked by ``pos`` validity.
   ``PagePool`` is the host-side allocator (free list, page 0 excluded).
   ``insert_slot_paged`` scatters a prefilled request into its pages,
   ``compact_kv_slot_paged`` gathers the representative rows into
   clustered pages and *nulls the dense block-table row* — the engine
   then returns the dense pages to the pool, realizing the paper's KV
   saving at the allocator level (``paged_kv_bytes``) instead of only
   analytically. int8 caches keep per-row scales in mirror-shaped scale
   pools (``kvp_scale``/``cp_scale``) indexed by the same block tables.
   Every pool is one array stacked by global layer, (L, nP, rows,
   page[, hd]); decode steps read and write it in place at (layer, page):
   the fused decode kernel and the token-row write kernel
   (``ops.paged_kv_write_rows``) take the layer as a scalar-prefetched
   index, and the jnp paths gather ``pool[layer, bt]`` — no layer is ever
   sliced out of the stack and written back.

``quant_rows``/``dequant_rows`` implement the per-(head, position)
symmetric int8 cache quantization shared by all layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.clustering import chai_widths
from repro.models.transformer import decode_state_structs
from repro.sharding.rules import Ax

# Per-slot lifecycle phases (paper Fig 10). PREFILL and CLUSTER are
# transient (they happen synchronously inside a host-driven jit call); the
# device-resident ``phase`` vector only ever holds FREE / WARMUP / STEADY.
PHASE_FREE = 0
PHASE_PREFILL = 1
PHASE_WARMUP = 2
PHASE_CLUSTER = 3
PHASE_STEADY = 4


def quant_rows(x):
    """Symmetric int8 over the last axis. x: (..., hd) ->
    (int8 same-shape, f32 scale (...))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequant_rows(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


# -- int4 row quantization (host-side: the compressed KV tier) --------------
#
# The serving tiers (serving/kv_tiers.py) store cold KV pages in host
# memory; under host pressure radix-cached pages drop to an int4 packed
# representation — the same symmetric per-row scheme as ``quant_rows``
# with the int4 extreme ±7 and two codes packed per byte. These run on
# demoted (host-resident) payloads, so they are numpy, not jnp.

def quant_rows_int4(x):
    """Symmetric int4 over the last axis. x: (..., hd) ->
    (int8 codes in [-7, 7] same-shape, f32 scale (...))."""
    x = np.asarray(x, np.float32)
    amax = np.max(np.abs(x), axis=-1)
    scale = np.maximum(amax, 1e-6) / 7.0
    q = np.clip(np.rint(x / scale[..., None]), -7, 7).astype(np.int8)
    return q, scale.astype(np.float32)


def dequant_rows_int4(q, scale):
    return (np.asarray(q, np.int8).astype(np.float32)
            * np.asarray(scale, np.float32)[..., None])


def pack_int4(q):
    """Pack int4 codes (int8 values in [-8, 7]) two per byte along the
    last axis; odd lengths zero-pad. (..., n) int8 -> (..., ceil(n/2))
    uint8, low nibble = even index."""
    q = np.asarray(q, np.int8)
    if q.shape[-1] % 2:
        q = np.concatenate(
            [q, np.zeros(q.shape[:-1] + (1,), np.int8)], axis=-1)
    lo = (q[..., 0::2] & 0x0F).astype(np.uint8)
    hi = (q[..., 1::2] & 0x0F).astype(np.uint8)
    return lo | (hi << 4)


def unpack_int4(packed, n):
    """Inverse of ``pack_int4``: (..., ceil(n/2)) uint8 -> (..., n) int8
    codes, sign-extending each nibble."""
    p = np.asarray(packed, np.uint8)
    lo = (p & 0x0F).astype(np.int8)
    hi = ((p >> 4) & 0x0F).astype(np.int8)
    lo = np.where(lo >= 8, lo - 16, lo).astype(np.int8)
    hi = np.where(hi >= 8, hi - 16, hi).astype(np.int8)
    out = np.empty(p.shape[:-1] + (2 * p.shape[-1],), np.int8)
    out[..., 0::2] = lo
    out[..., 1::2] = hi
    return out[..., :n]


def chai_state_structs(cfg: ModelConfig, batch: int, max_seq: int):
    """Decode-state structs with the clustered K cache (MHA archs only --
    GQA archs keep the plain state)."""
    shapes, logical = decode_state_structs(cfg, batch, max_seq)
    if not (cfg.is_mha and cfg.chai.enabled):
        return shapes, logical
    k_max, _ = chai_widths(cfg)
    dt = shapes["kg"].dtype
    ng, b, _, s, hd = shapes["kg"].shape
    shapes = dict(shapes)
    logical = dict(logical)
    shapes.pop("kg")
    kg_ax = logical.pop("kg")
    shapes["kg_chai"] = jax.ShapeDtypeStruct((ng, b, k_max, s, hd), dt)
    logical["kg_chai"] = Ax("layers", "batch", "clusters", "seq", "head_dim")
    if cfg.kv_cache_dtype == "int8":
        shapes.pop("kg_scale")
        logical.pop("kg_scale")
        shapes["kg_chai_scale"] = jax.ShapeDtypeStruct((ng, b, k_max, s),
                                                       jnp.float32)
        logical["kg_chai_scale"] = Ax("layers", "batch", "clusters", "seq")
    if cfg.chai.share_values:
        shapes.pop("vg")
        logical.pop("vg")
        shapes["vg_chai"] = jax.ShapeDtypeStruct((ng, b, k_max, s, hd), dt)
        logical["vg_chai"] = Ax("layers", "batch", "clusters", "seq",
                                "head_dim")
    return shapes, logical


def init_chai_state(cfg: ModelConfig, batch: int, max_seq: int):
    shapes, _ = chai_state_structs(cfg, batch, max_seq)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def add_score_buffer(state, cfg: ModelConfig, batch: int):
    """Attach the warmup score-accumulation buffer (nA, B, H, Wf)."""
    s = state["kg"].shape[3] if "kg" in state else state["kl"].shape[3]
    wf = min(cfg.chai.feature_window, int(s))
    state = dict(state)
    state["chai_scores"] = jnp.zeros(
        (cfg.n_attn_layers, batch, cfg.n_heads, wf), jnp.float32)
    return state


def pop_score_buffer(state):
    state = dict(state)
    scores = state.pop("chai_scores")
    return state, scores


def compact_kv(state, chai_ctx, cfg: ModelConfig):
    """Convert a full MHA decode state into the clustered layout.

    state["kg"]: (nG, B, H, S, hd); ctx reps: (nA, B, k) or (nA, k).
    Returns a new state with kg_chai (and vg_chai under share_values).
    Donate ``state`` when jitting to free the dense K cache in place.
    """
    if not (cfg.is_mha and cfg.chai.enabled):
        return state
    reps = chai_ctx["reps"]
    batched = reps.ndim == 3
    kg = state["kg"]                                  # (nG, B, H, S, hd)
    ng, b, h, s, hd = kg.shape
    k_max = reps.shape[-1]
    # All-global MHA archs: attention layer i == global layer i.
    r = reps if batched else jnp.broadcast_to(reps[:, None, :], (ng, b, k_max))
    idx = r[..., None, None]                          # (nG, B, k, 1, 1)
    kg_chai = jnp.take_along_axis(kg, idx, axis=2)
    new_state = {k: v for k, v in state.items()
                 if k not in ("kg", "kg_scale")}
    new_state["kg_chai"] = kg_chai
    if cfg.kv_cache_dtype == "int8" and "kg_scale" in state:
        new_state["kg_chai_scale"] = jnp.take_along_axis(
            state["kg_scale"], r[..., None], axis=2)
    if cfg.chai.share_values:
        vg_chai = jnp.take_along_axis(state["vg"], idx, axis=2)
        new_state.pop("vg")
        new_state["vg_chai"] = vg_chai
    return new_state


# ---------------------------------------------------------------------------
# Paged layout (the serving engine's state)
# ---------------------------------------------------------------------------

NULL_PAGE = 0   # reserved per-pool sink; never allocated, never read valid


class PagePool:
    """Host-side page allocator for one device pool.

    ``num_pages`` is the pool array's page dimension; page ``NULL_PAGE``
    is reserved as the sink for unallocated block-table entries, so the
    usable capacity is ``num_pages - 1``. Allocation state lives on the
    host (the device only ever sees block tables); ``alloc``/``free``
    are O(n) list ops on the free list.

    Pages are **reference counted** so the prefix cache can alias one
    physical page into many block tables (and its own radix index):
    ``alloc`` hands out pages at refcount 1, ``incref`` adds a sharer,
    ``free`` drops one reference and returns the page to the free list
    only when the count reaches zero (freed-at-zero semantics). Shared
    pages are read-only by convention — a writer must copy first
    (copy-on-write, ``copy_pool_page``).
    """

    def __init__(self, num_pages: int, page_size: int):
        assert num_pages >= 2, "pool needs the null page plus capacity"
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free list (reuse-hot pages first); page 0 excluded.
        self._free = list(range(self.num_pages - 1, NULL_PAGE, -1))
        self._rc: dict = {}            # page id -> reference count

    @property
    def capacity(self):
        return self.num_pages - 1

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.capacity - len(self._free)

    def refcount(self, page: int) -> int:
        return self._rc.get(int(page), 0)

    def counters(self) -> dict:
        """Refcount-exact allocator snapshot: free pages, pages in use,
        and total outstanding references. The abort path's no-leak
        guarantee is checked against this — after a mid-flight abort the
        counters must return to their pre-admission values."""
        return {"free": len(self._free),
                "in_use": self.pages_in_use,
                "refs": int(sum(self._rc.values()))}

    def alloc(self, n: int):
        """Pop ``n`` pages at refcount 1; raises if the pool cannot
        cover them."""
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"of {self.capacity}")
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._rc[p] = 1
        return pages

    def incref(self, pages):
        """Add one reference per page (aliasing an allocated page)."""
        for p in pages:
            p = int(p)
            assert self._rc.get(p, 0) > 0, f"incref of free page {p}"
            self._rc[p] += 1

    def free(self, pages):
        """Drop one reference per page; a page returns to the free list
        when its count reaches zero (double-free / null-free guarded)."""
        for p in pages:
            p = int(p)
            assert p != NULL_PAGE, "freeing the null page"
            assert 0 < p < self.num_pages, p
            rc = self._rc.get(p, 0)
            assert rc > 0, f"double free of page {p}"
            if rc == 1:
                del self._rc[p]
                self._free.append(p)
            else:
                self._rc[p] = rc - 1


def pages_needed(tokens: int, page_size: int):
    return -(-int(tokens) // int(page_size))


def gather_pages(pool, bt, layer=None):
    """Dense logical view of one pool through block tables.

    pool: (nP, rows, page[, hd]), or the decode state's stack of such
    pools (L, nP, ...) read at ``layer`` in the same indexing op (never a
    sliced-out layer); bt: (B, P) int32 -> (B, rows, P*page[, hd]).
    Entries pointing at the null page yield garbage rows — callers mask by
    ``pos`` validity, exactly as the dense rectangles mask their zero
    tail."""
    g = pool[bt] if layer is None else pool[layer, bt]   # (B, P, rows, ...)
    m = jnp.moveaxis(g, 2, 1)                     # (B, rows, P, page[, hd])
    b, rows, p, ps = m.shape[:4]
    return m.reshape((b, rows, p * ps) + m.shape[4:])


def paged_state_structs(cfg: ModelConfig, batch: int, max_seq: int, *,
                        page_size: int, dense_pages: int,
                        chai_pages: int = 0, chai: bool = True):
    """Decode-state structs for the paged continuous-batching layout.

    The dense per-slot ``kg``/``vg`` rectangles are replaced by one
    shared pool ``kvp`` of ``dense_pages`` pages (page = ``page_size``
    tokens x all global layers x ``n_kv_heads`` rows) plus per-slot
    block tables ``bt_kg``/``bt_vg``; MHA+CHAI archs add the clustered
    pool ``cp`` (``k_max`` rows) with tables ``bt_kc`` (and ``bt_vc``
    under ``share_values``). Everything else (local ring caches,
    recurrent state, ``pos``) is the model's own decode state, plus the
    per-slot ``phase`` and, with CHAI on, ``chai_scores``."""
    from repro.models.transformer import decode_state_structs as _structs
    assert max_seq % page_size == 0, (max_seq, page_size)
    n_slot_pages = max_seq // page_size
    shapes, logical = _structs(cfg, batch, max_seq)
    shapes, logical = dict(shapes), dict(logical)
    shapes["phase"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    logical["phase"] = Ax("batch")
    chai_on = chai and cfg.chai.enabled and cfg.k_max > 0
    int8 = cfg.kv_cache_dtype == "int8"
    bt_sds = jax.ShapeDtypeStruct((batch, n_slot_pages), jnp.int32)
    if cfg.n_global_layers:
        ng, kv, hd = cfg.n_global_layers, cfg.n_kv_heads, cfg.head_dim
        cache_dt = shapes["kg"].dtype
        for k in ("kg", "vg", "kg_scale", "vg_scale"):
            shapes.pop(k, None)
            logical.pop(k, None)
        shapes["kvp"] = jax.ShapeDtypeStruct(
            (ng, dense_pages, kv, page_size, hd), cache_dt)
        logical["kvp"] = Ax("layers", None, "kv_heads", None, "head_dim")
        if int8:
            shapes["kvp_scale"] = jax.ShapeDtypeStruct(
                (ng, dense_pages, kv, page_size), jnp.float32)
            logical["kvp_scale"] = Ax("layers", None, "kv_heads", None)
        shapes["bt_kg"] = bt_sds
        shapes["bt_vg"] = bt_sds
        logical["bt_kg"] = Ax("batch", None)
        logical["bt_vg"] = Ax("batch", None)
    if not chai_on:
        return shapes, logical
    wf = min(cfg.chai.feature_window, max_seq)
    shapes["chai_scores"] = jax.ShapeDtypeStruct(
        (cfg.n_attn_layers, batch, cfg.n_heads, wf), jnp.float32)
    logical["chai_scores"] = Ax("layers", "batch", "heads", None)
    if cfg.is_mha and "kvp" in shapes:
        k_max, _ = chai_widths(cfg)
        ng, hd = cfg.n_global_layers, cfg.head_dim
        cache_dt = shapes["kvp"].dtype
        shapes["cp"] = jax.ShapeDtypeStruct(
            (ng, chai_pages, k_max, page_size, hd), cache_dt)
        logical["cp"] = Ax("layers", None, "clusters", None, "head_dim")
        if int8:
            shapes["cp_scale"] = jax.ShapeDtypeStruct(
                (ng, chai_pages, k_max, page_size), jnp.float32)
            logical["cp_scale"] = Ax("layers", None, "clusters", None)
        shapes["bt_kc"] = bt_sds
        logical["bt_kc"] = Ax("batch", None)
        if cfg.chai.share_values:
            shapes["bt_vc"] = bt_sds
            logical["bt_vc"] = Ax("batch", None)
    return shapes, logical


def init_paged_state(cfg: ModelConfig, batch: int, max_seq: int, *,
                     page_size: int, dense_pages: int, chai_pages: int = 0,
                     chai: bool = True):
    shapes, _ = paged_state_structs(cfg, batch, max_seq,
                                    page_size=page_size,
                                    dense_pages=dense_pages,
                                    chai_pages=chai_pages, chai=chai)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _scatter_pages(pool, x, pages):
    """Scatter a dense batch-1 rectangle into pool pages.

    pool: (nG, nP, rows, page[, hd]); x: (nG, 1, rows, S[, hd]);
    pages: (P,) int32 with null-padding (trailing writes land in the
    null sink). S == P * page."""
    ng, _, rows, s = x.shape[:4]
    page = pool.shape[3]
    p = s // page
    m = x.reshape((ng, rows, p, page) + x.shape[4:])
    m = jnp.moveaxis(m, 2, 1)                    # (nG, P, rows, page[, hd])
    return pool.at[:, pages].set(m.astype(pool.dtype))


def insert_slot_paged(state, mini, slot, kg_pages, vg_pages, *,
                      bt_kg_row=None, bt_vg_row=None):
    """Write a prefilled batch=1 dense decode state into slot ``slot``
    and reset its CHAI bookkeeping, scattering its global K/V rows into the slot's
    freshly allocated pages and recording the block tables. Donate
    ``state`` when jitting.

    Prefix-cache admissions alias shared pages: ``kg_pages``/``vg_pages``
    are then the SCATTER vectors (``NULL_PAGE`` for the cached-prefix
    logical pages, so the mini state's zero rows land in the null sink)
    while ``bt_kg_row``/``bt_vg_row`` carry the full logical->physical
    mapping including the aliased pages. Default (cold path): block
    tables == scatter vectors."""
    state = dict(state)
    paged_keys = ("kg", "vg", "kg_scale", "vg_scale")
    for k, v in mini.items():
        if k in paged_keys:
            continue
        axis = 0 if state[k].ndim == 1 else 1
        state[k] = jax.lax.dynamic_update_index_in_dim(
            state[k], v.astype(state[k].dtype), slot, axis)
    if "kvp" in state and "kg" in mini:
        state["kvp"] = _scatter_pages(state["kvp"], mini["kg"], kg_pages)
        state["kvp"] = _scatter_pages(state["kvp"], mini["vg"], vg_pages)
        if "kvp_scale" in state:
            state["kvp_scale"] = _scatter_pages(
                state["kvp_scale"], mini["kg_scale"], kg_pages)
            state["kvp_scale"] = _scatter_pages(
                state["kvp_scale"], mini["vg_scale"], vg_pages)
        state["bt_kg"] = state["bt_kg"].at[slot].set(
            kg_pages if bt_kg_row is None else bt_kg_row)
        state["bt_vg"] = state["bt_vg"].at[slot].set(
            vg_pages if bt_vg_row is None else bt_vg_row)
    if "chai_scores" in state:
        nA, _, h, wf = state["chai_scores"].shape
        state["chai_scores"] = jax.lax.dynamic_update_index_in_dim(
            state["chai_scores"], jnp.zeros((nA, 1, h, wf), jnp.float32),
            slot, 1)
    state["phase"] = state["phase"].at[slot].set(PHASE_WARMUP)
    return state


def compact_kv_slot_paged(state, slot_ctx, cfg: ModelConfig, slot,
                          kc_pages, vc_pages=None):
    """Paged per-slot compaction: gather slot ``slot``'s representative K
    rows out of its dense pages into the clustered pages ``kc_pages``,
    then *null the dense block-table row* — after this jit returns, the
    engine hands the dense pages back to the ``PagePool`` (the
    allocator-level realization of the paper's §3.5 KV saving).

    Under ``share_values`` the dense V pages are compacted into
    ``vc_pages`` and freed the same way; otherwise V stays page-resident
    in the dense pool until retire. Donate ``state`` when jitting."""
    state = dict(state)
    if cfg.is_mha and cfg.chai.enabled and "cp" in state:
        reps = slot_ctx["reps"]                              # (nA, k)
        null_row = jnp.zeros_like(kc_pages)

        def gather(pool_key, scale_key, bt_key, dst_pages):
            bt_row = jax.lax.dynamic_index_in_dim(
                state[bt_key], slot, 0, keepdims=False)      # (P,)
            rows = state["kvp"][:, bt_row]       # (nG, P, KV, page, hd)
            idx = reps[:, None, :, None, None]
            g = jnp.take_along_axis(rows, idx, axis=2)
            state[pool_key] = state[pool_key].at[:, dst_pages].set(
                g.astype(state[pool_key].dtype))
            if scale_key in state:
                srows = state["kvp_scale"][:, bt_row]
                sg = jnp.take_along_axis(srows, reps[:, None, :, None],
                                         axis=2)
                state[scale_key] = state[scale_key].at[:, dst_pages].set(sg)
            state[bt_key] = state[bt_key].at[slot].set(null_row)

        gather("cp", "cp_scale", "bt_kg", kc_pages)
        state["bt_kc"] = state["bt_kc"].at[slot].set(kc_pages)
        if cfg.chai.share_values:
            # V codes move scale-less, mirroring the cohort layout's
            # vg -> vg_chai gather (int8 codes are reinterpreted).
            vd_pages = kc_pages if vc_pages is None else vc_pages
            bt_row = jax.lax.dynamic_index_in_dim(
                state["bt_vg"], slot, 0, keepdims=False)
            rows = state["kvp"][:, bt_row]
            g = jnp.take_along_axis(rows, reps[:, None, :, None, None],
                                    axis=2)
            state["cp"] = state["cp"].at[:, vd_pages].set(
                g.astype(state["cp"].dtype))
            state["bt_vc"] = state["bt_vc"].at[slot].set(vd_pages)
            state["bt_vg"] = state["bt_vg"].at[slot].set(null_row)
    state["phase"] = state["phase"].at[slot].set(PHASE_STEADY)
    return state


def copy_pool_page(state, src, dst, *, kind):
    """Copy ONE physical page (all global layers) inside a pool — the
    copy-on-write primitive for the prefix cache. ``kind="dense"`` copies
    ``kvp`` (+ ``kvp_scale``), ``kind="chai"`` copies ``cp`` (+
    ``cp_scale``). ``src``/``dst`` are traced int32 scalars; donate
    ``state`` when jitting."""
    keys = (("kvp", "kvp_scale") if kind == "dense"
            else ("cp", "cp_scale"))
    state = dict(state)
    for k in keys:
        if k in state:
            row = jax.lax.dynamic_index_in_dim(state[k], src, 1,
                                               keepdims=False)
            state[k] = jax.lax.dynamic_update_index_in_dim(state[k], row,
                                                           dst, 1)
    return state


def restore_slot_snapshot(state, slot, bt_kg_row, bt_vg_row, bt_kc_row,
                          bt_vc_row, pos):
    """Prefix-cache snapshot resume: point slot ``slot``'s block tables at
    the (shared / copied) snapshot pages, rewind ``pos`` to the snapshot's
    STEADY-entry position, and enter STEADY directly — the warm request
    skips PREFILL, WARMUP and CLUSTER entirely. Donate ``state`` when
    jitting."""
    state = dict(state)
    for key, row in (("bt_kg", bt_kg_row), ("bt_vg", bt_vg_row),
                     ("bt_kc", bt_kc_row), ("bt_vc", bt_vc_row)):
        if key in state:
            state[key] = state[key].at[slot].set(row)
    state["pos"] = state["pos"].at[slot].set(pos)
    if "chai_scores" in state:
        nA, _, h, wf = state["chai_scores"].shape
        state["chai_scores"] = jax.lax.dynamic_update_index_in_dim(
            state["chai_scores"], jnp.zeros((nA, 1, h, wf), jnp.float32),
            slot, 1)
    state["phase"] = state["phase"].at[slot].set(PHASE_STEADY)
    return state


_PAGED_POOL_KEYS = ("kvp", "kvp_scale", "cp", "cp_scale")


def save_slot_paged(state, slot, kg_pages, vg_pages, kc_pages, vc_pages):
    """Preemption swap-out: gather slot ``slot``'s entire per-slot state
    — every per-slot column (``pos``, ``phase``, ``chai_scores``, local
    rings, …) plus the CONTENTS of its pool pages — so the engine can
    free the physical pages and later restore the slot bitwise
    (``load_slot_paged``). Recompute-based resume cannot be exact here:
    CHAI decode is an approximation of full attention, so the K/V rows a
    re-prefill would produce for generated tokens differ from the rows
    the original decode wrote.

    Page vectors are the null-padded ``(P,)`` logical->physical maps; a
    pool kind the slot does not hold (e.g. dense K after compaction)
    passes an all-null vector and round-trips null-sink garbage, keeping
    one trace per arch. Returns ``(cols, pools)`` pytrees."""
    cols = {}
    for k, v in state.items():
        if k in _PAGED_POOL_KEYS or k.startswith("bt_"):
            continue
        axis = 0 if v.ndim == 1 else 1
        cols[k] = jax.lax.dynamic_index_in_dim(v, slot, axis,
                                               keepdims=True)
    pools = {}
    if "kvp" in state:
        pools["kg"] = state["kvp"][:, kg_pages]
        pools["vg"] = state["kvp"][:, vg_pages]
        if "kvp_scale" in state:
            pools["kg_scale"] = state["kvp_scale"][:, kg_pages]
            pools["vg_scale"] = state["kvp_scale"][:, vg_pages]
    if "cp" in state:
        pools["kc"] = state["cp"][:, kc_pages]
        if "cp_scale" in state:
            pools["kc_scale"] = state["cp_scale"][:, kc_pages]
        if "bt_vc" in state:
            pools["vc"] = state["cp"][:, vc_pages]
            if "cp_scale" in state:
                pools["vc_scale"] = state["cp_scale"][:, vc_pages]
    return cols, pools


def load_slot_paged(state, slot, cols, pools, kg_pages, vg_pages,
                    kc_pages, vc_pages, bt_kg_row, bt_vg_row, bt_kc_row,
                    bt_vc_row):
    """Preemption swap-in: the inverse of ``save_slot_paged`` against
    freshly allocated pages. Per-slot columns are written back verbatim,
    saved page contents are scattered at the new physical ids, and the
    block tables are rebuilt from the new logical->physical maps
    (null-padded vectors land their tails in the null sink, as every
    paged write does). Donate ``state`` when jitting."""
    state = dict(state)
    for k, v in cols.items():
        axis = 0 if state[k].ndim == 1 else 1
        state[k] = jax.lax.dynamic_update_index_in_dim(
            state[k], v.astype(state[k].dtype), slot, axis)
    if "kvp" in state:
        state["kvp"] = state["kvp"].at[:, kg_pages].set(pools["kg"])
        state["kvp"] = state["kvp"].at[:, vg_pages].set(pools["vg"])
        if "kvp_scale" in state:
            state["kvp_scale"] = state["kvp_scale"].at[:, kg_pages].set(
                pools["kg_scale"])
            state["kvp_scale"] = state["kvp_scale"].at[:, vg_pages].set(
                pools["vg_scale"])
        state["bt_kg"] = state["bt_kg"].at[slot].set(bt_kg_row)
        state["bt_vg"] = state["bt_vg"].at[slot].set(bt_vg_row)
    if "cp" in state:
        state["cp"] = state["cp"].at[:, kc_pages].set(pools["kc"])
        if "cp_scale" in state:
            state["cp_scale"] = state["cp_scale"].at[:, kc_pages].set(
                pools["kc_scale"])
        state["bt_kc"] = state["bt_kc"].at[slot].set(bt_kc_row)
        if "bt_vc" in state:
            state["cp"] = state["cp"].at[:, vc_pages].set(pools["vc"])
            if "cp_scale" in state:
                state["cp_scale"] = state["cp_scale"].at[:, vc_pages].set(
                    pools["vc_scale"])
            state["bt_vc"] = state["bt_vc"].at[slot].set(bt_vc_row)
    return state


def reset_slot_paged(state, slot):
    """Paged retire: phase -> FREE, rewind ``pos``, null every block-table
    row (the engine frees the physical pages host-side)."""
    state = dict(state)
    state["phase"] = state["phase"].at[slot].set(PHASE_FREE)
    state["pos"] = state["pos"].at[slot].set(0)
    for key in ("bt_kg", "bt_vg", "bt_kc", "bt_vc"):
        if key in state:
            state[key] = state[key].at[slot].set(
                jnp.zeros((state[key].shape[1],), jnp.int32))
    return state


def paged_page_bytes(cfg: ModelConfig, page_size: int, *, kind: str):
    """Bytes of ONE page (``page_size`` tokens x all global layers).

    kind="dense": ``n_kv_heads`` rows (+ f32 scales under int8);
    kind="chai": ``k_max`` clustered rows (+ scales)."""
    if cfg.n_global_layers == 0:
        return 0
    if kind == "dense":
        rows = cfg.n_kv_heads
    else:
        rows, _ = chai_widths(cfg)
    int8 = cfg.kv_cache_dtype == "int8"
    esize = 1 if int8 else jnp.dtype(cfg.dtype).itemsize
    n = cfg.n_global_layers * rows * page_size * cfg.head_dim * esize
    if int8:
        n += cfg.n_global_layers * rows * page_size * 4      # f32 scales
    return int(n)


def paged_kv_bytes(cfg: ModelConfig, page_size: int, dense_in_use: int,
                   chai_in_use: int = 0, *, batch: int = 0,
                   max_seq: int = 0):
    """ACTUAL allocated KV bytes of the paged layout: pages in use times
    page bytes, plus the (non-paged) local ring caches. This is the
    number the serving engine reports — it falls when dense pages are
    freed at compaction."""
    total = (dense_in_use * paged_page_bytes(cfg, page_size, kind="dense")
             + chai_in_use * paged_page_bytes(cfg, page_size, kind="chai"))
    if batch and cfg.n_local_layers:
        w = min(cfg.window_size, max_seq)
        dt = jnp.dtype(cfg.dtype).itemsize
        total += int(2 * cfg.n_local_layers * batch * cfg.n_kv_heads
                     * w * cfg.head_dim * dt)
    return int(total)


def kv_cache_bytes(cfg: ModelConfig, batch: int, seq: int, *,
                   chai: bool = False):
    """Analytic steady-state KV-cache size in bytes (paper Fig 11)."""
    if cfg.n_attn_layers == 0:
        return 0
    if cfg.kv_cache_dtype == "int8":
        esize = 1 + 4 / cfg.head_dim      # int8 row + f32 scale per row
    else:
        esize = jnp.dtype(cfg.dtype).itemsize
    hd = cfg.head_dim
    k_max, _ = chai_widths(cfg)
    total = 0
    for lt in cfg.layer_types:
        if lt == "attn_global":
            k_rows = k_max if (chai and cfg.is_mha and cfg.chai.enabled) \
                else cfg.n_kv_heads
            v_rows = (k_max if (chai and cfg.is_mha and
                                cfg.chai.share_values) else cfg.n_kv_heads)
            total += int(batch * (k_rows + v_rows) * seq * hd * esize)
        elif lt == "attn_local":
            w = min(cfg.window_size, seq)
            total += int(batch * 2 * cfg.n_kv_heads * w * hd * esize)
    return total
