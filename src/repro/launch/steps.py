"""Jit-able step functions: train_step, serve_prefill, serve_step (+CHAI).

These are the exact functions the dry-run lowers and the drivers execute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import cache as chai_cache
from repro.models import transformer as tfm
from repro.optim import adamw

LB_COEF = 0.01
Z_COEF = 1e-3


def cross_entropy(logits, labels):
    """logits (B, T, V) fp32; labels (B, T) int32 -> mean loss."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def make_loss_fn(cfg: ModelConfig, *, remat=True, moe_impl="capacity",
                 unroll=False):
    def loss_fn(params, batch):
        inputs = batch.get("tokens", batch.get("embeddings"))
        logits, _, aux = tfm.forward_fullseq(params, cfg, inputs,
                                             remat=remat, moe_impl=moe_impl,
                                             unroll=unroll)
        ce = cross_entropy(logits, batch["labels"])
        loss = ce + LB_COEF * aux["load_balance"] + Z_COEF * aux["router_z"]
        return loss, {"ce": ce, **aux}
    return loss_fn


def make_train_step(cfg: ModelConfig, *, remat=True, moe_impl="capacity",
                    lr_kw: Optional[dict] = None, unroll=False,
                    grad_dtype=None, grad_shardings=None):
    """``grad_dtype='bfloat16'`` casts gradients before the optimizer.
    ``grad_shardings``: pin gradients to the ZeRO (data-sharded) layout —
    without it XLA lowers the data-axis grad all-reduce as
    reduce-scatter + ALL-GATHER of the full f32 gradients, then re-slices
    for the sharded moments; the constraint deletes the gather
    (EXPERIMENTS.md §Perf iteration 4)."""
    loss_fn = make_loss_fn(cfg, remat=remat, moe_impl=moe_impl,
                           unroll=unroll)
    lr_kw = lr_kw or {}

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        if grad_dtype is not None:
            grads = jax.tree.map(
                lambda g: g.astype(jnp.dtype(grad_dtype)), grads)
        if grad_shardings is not None:
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        lr = adamw.cosine_lr(opt_state.step, **lr_kw) if lr_kw else None
        params, opt_state, om = adamw.update(grads, opt_state, params, lr=lr)
        return params, opt_state, {"loss": loss, **metrics, **om}

    return train_step


def make_serve_prefill(cfg: ModelConfig, batch: int, max_seq: int, *,
                       moe_impl="capacity", unroll=False):
    """Whole-cohort prefill. ``batch_inputs`` may carry ``true_lens``
    ((B,) int32): prompts are then right-padded to one power-of-two
    bucket and each row's padding tail is masked per example (last-real
    logits, per-example ``pos``), so a caller compiles one prefill per
    BUCKET instead of one per padded cohort length."""
    def serve_prefill(params, batch_inputs):
        inputs = batch_inputs.get("tokens", batch_inputs.get("embeddings"))
        state = tfm.init_decode_state(cfg, batch, max_seq)
        logits, state, _ = tfm.forward_fullseq(
            params, cfg, inputs, state=state, logits_slice="last",
            moe_impl=moe_impl, unroll=unroll,
            valid_len=batch_inputs.get("true_lens"))
        return logits[:, 0], state

    return serve_prefill


def make_serve_step(cfg: ModelConfig, *, chai=False, moe_impl="capacity",
                    unroll=False, decode_ts=0):
    """``decode_ts``: the page size the fused CHAI decode kernel's S-tile
    derives from (0 = whole sequence on the cohort layout) — the engine
    passes its page size."""
    def serve_step(params, batch_inputs, state, chai_ctx=None):
        kw = {}
        if "embeddings" in batch_inputs:
            kw["embeddings"] = batch_inputs["embeddings"]
            tokens = None
        else:
            tokens = batch_inputs["tokens"]
        logits, state = tfm.decode_step(params, cfg, tokens, state,
                                        chai_ctx=chai_ctx if chai else None,
                                        moe_impl=moe_impl, unroll=unroll,
                                        decode_ts=decode_ts, **kw)
        return logits, state

    return serve_step


def make_sampler(top_k_cap: int = 256):
    """Batched per-slot token sampler — the single device-side sampling
    path of the serving engine.

    ``sample(logits, temperature, top_k, top_p, seed, count)``:

    * ``logits`` (B, V); per-slot vectors ``temperature`` (B,) f32,
      ``top_k`` (B,) i32 (0 = widest support), ``top_p`` (B,) f32,
      ``seed`` (B,) u32, ``count`` (B,) i32 — tokens the slot's request
      has sampled so far.
    * Slots with ``temperature == 0`` take ``argmax(logits)`` — computed
      on the raw logits exactly as the engine's historical greedy path,
      so greedy decode stays BITWISE identical (CHAI snapshot replay and
      every cross-layout parity test rest on this). The whole sampling
      lane sits behind one batch-level ``lax.cond``: an all-greedy batch
      never pays for it, and greedy rows inside a mixed batch feed the
      lane a zeroed row instead of their (discarded) logits.
    * Sampling slots draw from ``fold_in(PRNGKey(seed), count)``: token
      n of a request depends only on (seed, n, logits) — never the slot
      id or engine step — so seeded runs reproduce across slot
      placements and batch sizes.
    * The candidate set is ``lax.top_k(scaled, min(top_k_cap, V))`` — an
      O(V·cap) selection instead of the old full-vocab argsort. top-k /
      top-p masks apply in descending order within the candidates (top-p
      after top-k, rank 0 always kept); probabilities are normalized
      against the FULL vocab (logsumexp over the row), so the nucleus
      mass matches the unsorted distribution exactly. ``top_k == 0`` and
      any nucleus extending past ``top_k_cap`` truncate to the cap.
    """
    @jax.named_scope("sample")
    def sample(logits, temperature, top_k, top_p, seed, count):
        lg = logits.astype(jnp.float32)
        greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        v = lg.shape[-1]
        cap = min(top_k_cap, v)

        def one(row, t, k, p, s, c):
            key = jax.random.fold_in(jax.random.PRNGKey(s), c)
            scaled = row / jnp.maximum(t, 1e-6)
            sl, idx = jax.lax.top_k(scaled, cap)       # descending, stable
            probs = jnp.exp(sl - jax.nn.logsumexp(scaled))
            cum = jnp.cumsum(probs)
            ranks = jnp.arange(cap)
            keep = ranks < jnp.where(k > 0, k, cap)    # top-k
            keep &= (cum - probs) < p                  # top-p (nucleus)
            keep = keep.at[0].set(True)                # never mask rank 0
            masked = jnp.where(keep, sl, -jnp.inf)
            pick = jax.random.categorical(key, masked)
            return jnp.take(idx, pick).astype(jnp.int32)

        def sampling_lane(_):
            # Greedy rows contribute a dead zero row — their draw is
            # discarded by the final select, so don't feed it real work.
            live = temperature > 0.0
            rows = jnp.where(live[:, None], lg, 0.0)
            return jax.vmap(one)(rows, temperature, top_k, top_p, seed,
                                 count)

        sampled = jax.lax.cond(jnp.any(temperature > 0.0), sampling_lane,
                               lambda _: greedy_tok, None)
        return jnp.where(temperature > 0.0, sampled, greedy_tok)

    return sample


def make_compact_step(cfg: ModelConfig):
    def compact(state, chai_ctx):
        return chai_cache.compact_kv(state, chai_ctx, cfg)
    return compact


# ---------------------------------------------------------------------------
# Continuous batching (slot-level) steps
# ---------------------------------------------------------------------------

def make_mixed_step(cfg: ModelConfig, *, moe_impl="ragged", unroll=False,
                    decode_ts=0):
    """Mixed-phase decode step: each batch slot is routed to the MHA path
    (WARMUP) or the CHAI path (STEADY) by ``state["phase"]`` — one jit,
    static shapes, mask-and-select inside the attention branch. The CHAI
    side runs the fused one-launch decode kernel (``decode_ts`` as in
    ``make_serve_step``)."""
    def mixed_step(params, batch_inputs, state, chai_ctx):
        kw = {}
        if "embeddings" in batch_inputs:
            kw["embeddings"] = batch_inputs["embeddings"]
            tokens = None
        else:
            tokens = batch_inputs["tokens"]
        logits, state = tfm.decode_step(params, cfg, tokens, state,
                                        chai_ctx=chai_ctx, mixed_phase=True,
                                        moe_impl=moe_impl, unroll=unroll,
                                        decode_ts=decode_ts, **kw)
        return logits, state

    return mixed_step


def make_relay_step(cfg: ModelConfig, *, moe_impl="ragged", unroll=False,
                    decode_ts=0):
    """Shared-prefix relay decode step: ``make_mixed_step`` plus a
    ``relay`` pytree of group-batched arrays (resident prefix K/V copies,
    row-routing maps, membership) built host-side by the engine. Grouped
    STEADY slots run ONE prefix-attention pass per group per layer and a
    suffix-only fused decode, merged by online-softmax state inside the
    attention branch; non-grouped slots ride through unchanged (their
    prefix state is the exact merge identity). Always mixed-phase — a
    relay batch may carry WARMUP slots, which are never grouped.
    Shape-specialized per (groups, max members, max prefix) signature."""
    def relay_step(params, batch_inputs, state, chai_ctx, relay):
        kw = {}
        if "embeddings" in batch_inputs:
            kw["embeddings"] = batch_inputs["embeddings"]
            tokens = None
        else:
            tokens = batch_inputs["tokens"]
        logits, state = tfm.decode_step(params, cfg, tokens, state,
                                        chai_ctx=chai_ctx, mixed_phase=True,
                                        moe_impl=moe_impl, unroll=unroll,
                                        decode_ts=decode_ts, relay=relay,
                                        **kw)
        return logits, state

    return relay_step


# ---------------------------------------------------------------------------
# Paged KV layout (continuous batching over a block-table page pool)
# ---------------------------------------------------------------------------

def make_paged_slot_prefill(cfg: ModelConfig, max_seq: int, *,
                            moe_impl="capacity", unroll=False):
    """Prefill ONE request (batch=1 forward) and insert it into batch slot
    ``slot``: the forward fills a dense mini state, which is then
    scattered into the slot's freshly allocated pages (``kg_pages``/
    ``vg_pages``: (P,) int32, null-padded; all null for a model with no
    pools). Donate the state when jitting.

    The returned callable is shape-specialized to the PADDED length of
    ``tokens`` — the engine right-pads prompts to power-of-two buckets
    and passes the real length as the traced ``true_len``, so retraces
    are O(log max_seq) instead of O(distinct prompt lengths). Padding
    rows beyond ``true_len`` are masked out of the logits, the decode
    ``pos`` and the local ring caches (``forward_fullseq`` valid_len),
    and their cache rows land either inside the slot's own pages —
    masked by ``pos`` — or in the null sink page."""
    def slot_prefill(params, tokens, true_len, state, slot, kg_pages,
                     vg_pages):
        mini = tfm.init_decode_state(cfg, 1, max_seq)
        logits, mini, _ = tfm.forward_fullseq(
            params, cfg, tokens, state=mini, logits_slice="last",
            moe_impl=moe_impl, unroll=unroll, valid_len=true_len)
        state = chai_cache.insert_slot_paged(state, mini, slot, kg_pages,
                                             vg_pages)
        return logits[:, 0], state

    return slot_prefill


def _paged_prefix_kv(state, bt_kg_row, bt_vg_row):
    """Paged prefix_kv dict for a suffix/chunk prefill: the pool and
    block tables go to the kernel as-is — the paged prefix pass streams
    only the real pages through scalar-prefetched tables instead of
    gathering the whole slot-capacity view per layer."""
    return {"pool": state["kvp"],
            "scale": state.get("kvp_scale"),
            "bt_k": bt_kg_row[None],             # (1, P)
            "bt_v": bt_vg_row[None]}


def make_paged_suffix_prefill(cfg: ModelConfig, max_seq: int, *,
                              moe_impl="capacity", unroll=False):
    """Cached-aware prefill: forward ONLY the uncached suffix of a
    prompt whose first ``prefix_len`` tokens (a whole number of pages)
    already live in shared pages aliased into the slot's block tables.

    ``tokens`` (1, Tb) is the right-padded suffix bucket; ``true_len``
    its real length; ``bt_kg_row``/``bt_vg_row`` the FULL logical page
    mapping (aliased prefix + fresh suffix pages); ``kg_scatter``/
    ``vg_scatter`` the same rows with the aliased entries nulled so the
    mini state's scatter cannot touch shared pages (copy-on-write: the
    suffix writes only into the slot's own pages). Suffix queries take a
    paged non-causal pass over the cached prefix pages plus a causal
    flash pass over the suffix, merged by online-softmax state; shape-
    specialized per suffix bucket only. Donate the state when jitting."""
    def suffix_prefill(params, tokens, true_len, prefix_len, state, slot,
                       kg_scatter, vg_scatter, bt_kg_row, bt_vg_row):
        prefix_kv = _paged_prefix_kv(state, bt_kg_row, bt_vg_row)
        mini = tfm.init_decode_state(cfg, 1, max_seq)
        logits, mini, _ = tfm.forward_fullseq(
            params, cfg, tokens, state=mini, logits_slice="last",
            moe_impl=moe_impl, unroll=unroll, valid_len=true_len,
            prefix_len=prefix_len, prefix_kv=prefix_kv)
        state = chai_cache.insert_slot_paged(
            state, mini, slot, kg_scatter, vg_scatter,
            bt_kg_row=bt_kg_row, bt_vg_row=bt_vg_row)
        return logits[:, 0], state

    return suffix_prefill


def make_paged_chunk_prefill(cfg: ModelConfig, max_seq: int, *,
                             moe_impl="capacity", unroll=False):
    """Chunked (Sarathi-style) prefill: forward ONE page-aligned chunk of
    a long prompt, treating everything the slot has already prefilled —
    radix-aliased prefix pages AND earlier chunks — as the cached prefix
    of a suffix prefill. ``prefix_len`` is the chunk's start position;
    ``kg_scatter``/``vg_scatter`` null every page outside the chunk's
    range, so the mini state touches only the pages this chunk fills.

    ``phase`` distinguishes the final chunk (``PHASE_WARMUP``: the slot
    joins the decode batch next step) from intermediate ones
    (``PHASE_FREE``: the interleaved batched decode treats the slot as
    empty — its stray write at ``pos`` lands in a page the NEXT chunk's
    whole-page scatter overwrites, and ``insert_slot_paged`` re-anchors
    ``pos`` and zeroes the clustering features every chunk). Donate the
    state when jitting; shape-specialized per chunk bucket."""
    def chunk_prefill(params, tokens, true_len, prefix_len, state, slot,
                      kg_scatter, vg_scatter, bt_kg_row, bt_vg_row, phase):
        prefix_kv = _paged_prefix_kv(state, bt_kg_row, bt_vg_row)
        mini = tfm.init_decode_state(cfg, 1, max_seq)
        logits, mini, _ = tfm.forward_fullseq(
            params, cfg, tokens, state=mini, logits_slice="last",
            moe_impl=moe_impl, unroll=unroll, valid_len=true_len,
            prefix_len=prefix_len, prefix_kv=prefix_kv)
        state = chai_cache.insert_slot_paged(
            state, mini, slot, kg_scatter, vg_scatter,
            bt_kg_row=bt_kg_row, bt_vg_row=bt_vg_row)
        state["phase"] = state["phase"].at[slot].set(phase)
        return logits[:, 0], state

    return chunk_prefill


def make_slot_swap(cfg: ModelConfig):
    """Preemption KV swap (out, in): a preempted slot's per-slot state
    and page CONTENTS move to the host so its physical pages can be
    reclaimed, and move back verbatim into fresh pages at resume.
    Resume-by-recompute cannot be output-identical here: CHAI decode is
    an approximation of full attention, so a re-prefill would produce
    different K/V rows for the generated tokens than the original decode
    wrote (and re-running identify could change membership outright).
    Swapping the actual rows makes resume bitwise."""
    def swap_out(state, slot, kg_pages, vg_pages, kc_pages, vc_pages):
        return chai_cache.save_slot_paged(state, slot, kg_pages, vg_pages,
                                          kc_pages, vc_pages)

    def swap_in(state, slot, cols, pools, kg_pages, vg_pages, kc_pages,
                vc_pages, bt_kg_row, bt_vg_row, bt_kc_row, bt_vc_row):
        return chai_cache.load_slot_paged(
            state, slot, cols, pools, kg_pages, vg_pages, kc_pages,
            vc_pages, bt_kg_row, bt_vg_row, bt_kc_row, bt_vc_row)

    return swap_out, swap_in


def make_snapshot_restore(cfg: ModelConfig):
    """CHAI snapshot resume: alias the snapshot's clustered + dense-V
    pages into the slot's block tables and enter STEADY directly."""
    def restore(state, slot, bt_kg_row, bt_vg_row, bt_kc_row, bt_vc_row,
                pos):
        return chai_cache.restore_slot_snapshot(
            state, slot, bt_kg_row, bt_vg_row, bt_kc_row, bt_vc_row, pos)

    return restore


def make_page_copy(cfg: ModelConfig, kind: str):
    """Copy-on-write page copy inside one pool (``kind``: dense|chai)."""
    def copy(state, src, dst):
        return chai_cache.copy_pool_page(state, src, dst, kind=kind)

    return copy


def make_page_fetch(cfg: ModelConfig, kind: str):
    """Gather ONE physical page's contents (all global layers) out of a
    pool — the host-offload demotion read (``kind``: dense|chai).
    Returns a payload dict ``{"data": (nG, rows, ps, hd)}`` plus a
    ``"scale"`` plane under int8 KV. One trace per kind: the page id is
    a traced scalar."""
    key, skey = (("kvp", "kvp_scale") if kind == "dense"
                 else ("cp", "cp_scale"))

    def fetch(state, page):
        out = {"data": jax.lax.dynamic_index_in_dim(state[key], page, 1,
                                                    keepdims=False)}
        if skey in state:
            out["scale"] = jax.lax.dynamic_index_in_dim(
                state[skey], page, 1, keepdims=False)
        return out

    return fetch


def make_page_put(cfg: ModelConfig, kind: str):
    """Scatter a host payload back into ONE physical page — the tier
    promotion write, the exact inverse of ``make_page_fetch``. Donate
    ``state`` when jitting."""
    key, skey = (("kvp", "kvp_scale") if kind == "dense"
                 else ("cp", "cp_scale"))

    def put(state, page, payload):
        state = dict(state)
        state[key] = jax.lax.dynamic_update_index_in_dim(
            state[key], payload["data"].astype(state[key].dtype), page, 1)
        if skey in state:
            state[skey] = jax.lax.dynamic_update_index_in_dim(
                state[skey], payload["scale"].astype(state[skey].dtype),
                page, 1)
        return state

    return put


def make_paged_slot_cluster(cfg: ModelConfig, identify_fn):
    """Paged CLUSTER transition: identify membership, scatter it into the
    batched ctx, gather the slot's representative K rows from its dense
    pages into the clustered pages, and null the dense block-table row —
    the engine frees those dense pages host-side right after this jit."""
    def cluster_slot(state, ctx, slot, kc_pages, vc_pages):
        from repro.core import clustering
        scores = jax.lax.dynamic_slice_in_dim(state["chai_scores"], slot, 1,
                                              axis=1)[:, 0]
        slot_ctx = clustering.identify_membership_slot(scores, cfg,
                                                       identify_fn)
        ctx = clustering.update_ctx_slot(ctx, slot_ctx, slot)
        state = chai_cache.compact_kv_slot_paged(state, slot_ctx, cfg, slot,
                                                 kc_pages, vc_pages)
        return state, ctx

    return cluster_slot


def make_paged_slot_reset(cfg: ModelConfig):
    def reset(state, slot):
        return chai_cache.reset_slot_paged(state, slot)
    return reset


def jaxpr_text(fn, *example_args):
    """Canonical jaxpr text of ``fn`` traced at ``example_args``.

    Introspection only (telemetry overhead claims, kernel-coverage
    tests): proves two callables lower to the same computation without
    executing either. ``fn`` may be a ``jax.jit`` wrapper — tracing goes
    through it; compare jit-wrapped against jit-wrapped (the pjit
    equation wraps the inner jaxpr either way). Memory addresses of
    embedded thunks (``custom_jvp`` prints ``<function ... at 0x...>``)
    are scrubbed so two independently built but identical programs
    compare equal.
    """
    import re
    txt = str(jax.make_jaxpr(fn)(*example_args))
    return re.sub(r"<(function|bound method) .+? at 0x[0-9a-f]+>",
                  r"<\1>", txt)
