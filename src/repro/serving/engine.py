"""Step-driven serving core with a per-slot CHAI phase machine.

Request lifecycle (paper Fig 10), tracked PER BATCH SLOT:

    PREFILL  --(batch=1 full forward; KV rows written into the slot)-->
    WARMUP   --(MHA decode steps; per-head attention scores accumulate
                into the slot's clustering-feature buffer)-->
    CLUSTER  --(per-slot K-Means membership identification; the slot's
                dense K rows are compacted to representative rows — the
                paper's 21.4% KV saving — via a donated slot-indexed
                gather)-->
    STEADY   --(Clustered Head Attention decode until a finish condition)

plus three out-of-band edges:

* ABORT — ``abort(uid)`` cancels a request at any phase (or still
  queued), returning every page it held to the pools.
* chunked PREFILL self-loop — with ``EngineConfig.prefill_chunk_tokens``
  set, a long prompt forwards one page-aligned chunk per ``step()``
  instead of monolithically, so a prompt storm cannot stall the decoding
  slots for its whole length (greedy tokens are unchanged;
  global-attention archs only).
* PREEMPT / RESUME — with ``EngineConfig.preemption`` (default on), a
  strictly-higher-``priority`` arrival that cannot be admitted for page
  budget evicts the lowest-priority running slot: the victim's pages and
  per-slot state are swapped to the host, its pages freed, and the
  request requeued at the front; re-admission swaps everything back into
  fresh pages and continues the SAME decode chain bitwise. (The swap is
  correctness, not just speed: CHAI decode approximates full attention,
  so recomputing the victim's generated tokens by prefill would diverge
  from the decode-written KV.) A mid-PREFILL victim restarts instead.

The engine is layered:

* ``EngineCore`` — owns the device state, page pools, prefix cache, and
  ONE public scheduling primitive: ``step()`` runs exactly one scheduler
  iteration (admit arrived requests into free slots -> cluster/compact
  slots whose warmup completed -> one mixed-phase batched decode ->
  retire finished slots) and returns a ``StepOutput`` per request that
  produced tokens. ``add_request`` enqueues with per-request
  ``SamplingParams`` (temperature / top-k / top-p / seed / stops);
  ``abort`` cancels mid-flight, refcount-exactly. Callers drive the loop
  themselves — streaming frontends yield between steps.
* ``repro.serving.api`` — the user-facing ``LLM.generate`` /
  ``LLM.stream`` / ``Session`` frontend over ``step()``.
* ``ServingEngine`` — the historical ``submit()`` / ``run()`` batch
  surface, now a thin compatibility wrapper that loops ``step()``.

Sampling is one batched device jit (``repro.launch.steps.make_sampler``);
``temperature=0`` slots take the raw-logits argmax, so greedy decode is
bitwise-identical to the historical greedy path (CHAI snapshot
capture/replay stays gated to greedy requests). Seeded draws key on
(request seed, tokens sampled so far) — reproducible across slot
placements and batch sizes.

Scheduling is slot-level continuous batching: a fixed pool of batch
slots (static shapes for XLA) holds requests at *different* phases
simultaneously; the decode step is one jit that routes each slot to the
MHA or CHAI attention path according to the per-slot ``phase`` vector
(mask-and-select), host-dispatching to the cheaper all-MHA / all-CHAI
jits when no slot is mid-transition.

The device state is block-table paged KV
(``repro.core.cache.paged_state_structs``). Admission is page-budget
based, and the CLUSTER transition frees the slot's dense K pages back to
the ``PagePool`` the moment the representative rows are gathered into
clustered pages — steady-state CHAI occupies less allocator memory than
dense MHA (the paper's 21.4%-class saving in ``kv_bytes()``). A model
with no global attention layer has nothing to page: its state holds no
pools, and it runs without page-budget admission, KV tiers, preemption,
a prefix cache or chunked prefill.

Every Request records arrival, admission (slot id + engine step), first
token, and completion, so per-request TTFT / ITL / latency and engine
throughput fall out directly.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import cache as chai_cache
from repro.core import chai_attention as chai_mod
from repro.core import clustering
from repro.launch import steps as steps_mod
from repro.serving import exporters as exporters_mod
from repro.serving import faults as faults_mod
from repro.serving import invariants as invariants_mod
from repro.serving import kv_tiers as kv_tiers_mod
from repro.serving import sampling as sampling_mod
from repro.serving import telemetry as telemetry_mod
from repro.serving.faults import (CapacityError, EngineFault, FaultInjector,
                                  InjectedFault, QuarantineError,
                                  RequestError, SnapshotRestoreError,
                                  ValidationError)
from repro.serving.sampling import SamplingParams

#: phase id -> timeline-event name (serving/telemetry.py lifecycle)
_PHASE_NAMES = {
    chai_cache.PHASE_FREE: "FREE",
    chai_cache.PHASE_PREFILL: "PREFILL",
    chai_cache.PHASE_WARMUP: "WARMUP",
    chai_cache.PHASE_CLUSTER: "CLUSTER",
    chai_cache.PHASE_STEADY: "STEADY",
}


@dataclasses.dataclass(eq=False)       # identity semantics: the queue and
class Request:                         # abort() membership-test Requests
    uid: int
    prompt: np.ndarray                 # (T,) int32
    max_new_tokens: int = 32
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    priority: int = 0                  # preemption class: higher outranks
    # -- filled by the engine --
    generated: Optional[List[int]] = None
    finish_reason: str = ""            # "" while in flight; "length" |
    #                                    "stop" | "aborted" when done
    t_enqueue: float = 0.0
    t_arrival: float = 0.0             # Poisson workloads: earliest admit
    t_first_token: float = 0.0
    t_done: float = 0.0
    slot: int = -1                     # slot the request ran in
    admit_step: int = -1               # engine step at admission
    retire_step: int = -1              # engine step at retire
    # -- prefix cache --
    cache_hit: str = ""                # "" | "prefix" | "snapshot" | "replay"
    cached_tokens: int = 0             # prompt tokens served from cache
    prefill_tokens: int = -1           # tokens actually forwarded (prefill)
    # -- failure taxonomy --
    error: str = ""                    # quarantine message when
    #                                    finish_reason == "error"
    # -- preemption --
    preemptions: int = 0               # times this request lost its slot
    # Host-swapped slot state (phase/count, per-slot columns, page
    # contents, CHAI membership) captured at eviction; consumed by the
    # swap-in admission. None for fresh and mid-PREFILL-evicted requests.
    resume_state: Optional[dict] = dataclasses.field(default=None,
                                                     repr=False)

    @property
    def finished(self) -> bool:
        return bool(self.finish_reason)

    @property
    def ttft(self):
        return self.t_first_token - self.t_arrival

    @property
    def latency(self):
        return self.t_done - self.t_arrival


@dataclasses.dataclass
class StepOutput:
    """Per-request result of one ``EngineCore.step()``: the token ids
    emitted for this request THIS step (one decode token; several at a
    snapshot/replay admission), and whether the request just finished."""
    uid: int
    token_ids: List[int]
    finished: bool = False
    finish_reason: str = ""


@dataclasses.dataclass
class EngineConfig:
    batch_slots: int = 4               # slot-pool size (static)
    max_seq: int = 256                 # KV capacity per slot (static)
    # Default SamplingParams for requests submitted without one:
    # greedy=True -> temperature 0 (the historical behaviour);
    # greedy=False -> temperature 1.0. Requests carrying explicit
    # SamplingParams ignore this flag entirely.
    greedy: bool = True
    use_chai: bool = True
    # -- paged KV ---------------------------------------------------------
    # Block-table page pools; a slot's dense K pages are FREED at
    # compaction, so steady-state CHAI occupies less allocator memory
    # than dense MHA (the paper's saving, realized).
    page_size: int = 16                # tokens per page (divides max_seq)
    # Pool capacities in pages, INCLUDING the reserved null page 0.
    # 0 = auto: worst case for batch_slots requests of max_seq tokens
    # (admission is then never page-limited — shrink to exercise the
    # page-budget admission path).
    num_pages: int = 0                 # dense K/V pool
    num_chai_pages: int = 0            # clustered pool (MHA+CHAI archs)
    # -- shared-prefix KV reuse (global-attention archs only) -----------
    # Radix-tree prefix cache over token blocks: admission aliases the
    # longest cached block-prefix into the slot's block tables and
    # prefills only the uncached suffix; for MHA+CHAI archs a GREEDY
    # request whose FULL prompt was served before resumes from a CHAI
    # snapshot (membership + clustered pages) and enters STEADY directly.
    # Retiring slots that still hold their dense pages (GQA /
    # use_chai=False) index their FULL sequence (prompt + generated), so
    # a multi-turn Session's next turn prefills only the new user
    # message. Cached pages are refcounted, copy-on-write, LRU-evicted
    # under pressure.
    prefix_cache: bool = False
    # -- SLO-aware scheduling ---------------------------------------------
    # Chunked prefill (Sarathi-style): a prompt longer than this
    # forwards at most ``prefill_chunk_tokens`` per ``step()`` (rounded
    # up to a page multiple), interleaved with running decodes — a long
    # prompt no longer stalls every concurrent stream for its whole
    # monolithic prefill, bounding inter-token latency. 0 = monolithic.
    # Global-attention-only archs (same constraint as prefix_cache:
    # local rings / recurrent state cannot be rebuilt suffix-only).
    prefill_chunk_tokens: int = 0
    # Priority preemption: when the arrived queue head outranks a
    # running request and the pools cannot cover it, the lowest-priority
    # running slot is preempted — its pages return refcount-exactly via
    # the abort path's free, and the request re-queues right behind the
    # preemptor carrying its progress cursor (generated tokens, CHAI
    # membership / warmup scores), so resumed decoding continues where
    # it stopped instead of failing. Equal priorities never preempt.
    preemption: bool = True
    # -- shared-prefix relay decode (prefix_cache + CHAI) ---------------
    # Compute system-prompt attention once per batch: STEADY slots
    # admitted through the same radix chain group on their deepest
    # shared node with >= relay_min_group members; each decode step runs
    # ONE group-batched prefix-attention pass per layer over a resident
    # contiguous copy of the shared pages (rep rows only — the
    # head->cluster broadcast is deferred to the merge), while each
    # slot's fused decode covers only its private suffix pages; the two
    # online-softmax states merge before the finalize. Per-step prefix
    # attention cost is O(prefix), independent of the group size.
    # Grouped tokens match the per-request decode path token-for-token
    # (the two-phase merge reorders float accumulation); ungrouped slots
    # stay BITWISE identical to relay_decode=False.
    relay_decode: bool = False
    relay_min_group: int = 2       # smallest group worth a prefix pass
    # -- runtime self-checks (serving/invariants.py) --------------------
    # "basic" (default): cheap host-side checks after every step() —
    # pool conservation, refcount accounting, phase legality, cache
    # lock/residency consistency. "deep": additionally pull the device
    # block tables + phase vector and verify them against the host
    # bookkeeping. "off": no auditing (benchmark hot loops). A failed
    # audit raises EngineFault (the engine state itself is suspect).
    audit_level: str = "basic"     # "off" | "basic" | "deep"
    # -- telemetry (serving/telemetry.py) -------------------------------
    # "off" (default): NullTelemetry — every hook is a no-op behind an
    # ``enabled`` guard, and the decode step stays jaxpr-identical to an
    # uninstrumented engine (claim-checked by bench_telemetry_overhead).
    # "basic": MetricsRegistry counters/gauges/histograms + per-request
    # lifecycle timelines (TTFT / ITL / queue time) + a span for every
    # step() stage, also written into a jax.profiler trace when one runs
    # and exportable as a Chrome trace (``step_trace()``).
    telemetry: str = "off"         # "off" | "basic"
    # -- degraded-decode healing ----------------------------------------
    # After an injected kernel.decode fault flips ``degraded_decode`` the
    # engine stays on the jnp reference jits (a real fused-path failure
    # raises EngineFault instead). With decode_heal_steps = N > 0 it
    # reverts to the fused path after N consecutive clean decode steps
    # (no kernel.decode fault observed); each revert counts in
    # ``decode_heals``. 0 (default) = never heal (the historical
    # permanently-degraded behaviour).
    decode_heal_steps: int = 0
    # -- hierarchical KV tiers (serving/kv_tiers.py) ---------------------
    # Every engine with page pools owns a TierManager: preemption
    # swap-out always routes victim payloads through its host page pool;
    # a model with no global attention layer has neither. kv_offload
    # additionally turns prefix-cache eviction into DEMOTION — under
    # pool pressure unlocked radix leaves / CHAI snapshots move to host
    # pages instead of dropping (the LRU ladder walks hot -> host ->
    # compressed int4 -> gone), and a hit on a demoted entry promotes it
    # back into fresh device pages (bitwise-identical greedy replay).
    kv_offload: bool = False
    # Host / compressed pool sizes in usable pages PER KIND (dense and
    # clustered pools each get this many). 0 = auto: host covers 2x the
    # device pool; the int4 pool matches the host pool. Only radix
    # nodes ride the compressed rung (snapshots replay bitwise).
    host_pages: int = 0
    compressed_pages: int = 0
    # Admission-time prefetch: add_request queues the promotion of the
    # demoted entries the request will hit; step() drains a bounded
    # number per iteration ahead of the admission (synchronous
    # promotion remains the fallback on a miss).
    tier_prefetch: bool = True
    # A hit on an int4-compressed entry: False (default) drops the
    # entry and re-plans cold (still bitwise — prefill recomputes);
    # True promotes the dequantized approximation (bench arm).
    lossy_promote: bool = False


#: planner sentinel: a demoted entry was dropped mid-plan (failed
#: promotion / compressed-tier hit) — the tree changed, plan again.
_REPLAN = object()


class EngineCore:
    """Device-state owner + one-iteration scheduler (``step()``).

    ``detokenizer``: optional ``List[int] -> str`` used to match
    ``SamplingParams.stop`` strings against the generated tokens.
    """

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig, *,
                 detokenizer: Optional[Callable] = None,
                 faults: Optional[FaultInjector] = None):
        assert cfg.n_attn_layers > 0 or not ecfg.use_chai, \
            "CHAI needs attention layers"
        if ecfg.audit_level not in ("off", "basic", "deep"):
            raise ValueError(f"audit_level must be off|basic|deep, "
                             f"got {ecfg.audit_level!r}")
        if ecfg.decode_heal_steps < 0:
            raise ValueError("decode_heal_steps must be >= 0, got "
                             f"{ecfg.decode_heal_steps}")
        # telemetry tier validation happens inside make_telemetry
        self.tel = telemetry_mod.make_telemetry(ecfg.telemetry)
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.detokenizer = detokenizer
        # -- fault containment / robustness --------------------------------
        self.faults = faults           # None = no injection sites active
        self.quarantined = 0           # requests typed-failed ("error")
        self.audit_steps = 0           # step()s that ran the auditor
        self.degraded_decode = False   # injected kernel fault: jnp now
        self.decode_fallbacks = 0      # injected kernel faults survived
        self.decode_heals = 0          # degraded->fused reverts (healing)
        self._heal_clean = 0           # consecutive clean degraded steps
        self._decode_fault_hit = False  # kernel.decode fired this step
        self.relay_dissolved = 0       # relay groups dissolved by fault
        self.swap_checksum_failures = 0
        self.offload_checksum_failures = 0   # corrupted promotions caught
        self.prefetch_hits = 0         # demoted hit found already promoted
        self.prefetch_misses = 0       # demoted hit promoted synchronously
        self._jnp_steps = None         # lazily-built degraded decode jits
        self._fault_blocked = False    # last plan blocked by injection
        self.queue: deque = deque()
        self.done: List[Request] = []
        self.steps_executed = 0        # batched decode steps
        self._step_calls = 0           # every _step_inner entry (spans)
        self._span_step = -1           # step ordinal current spans carry
        b, s = ecfg.batch_slots, ecfg.max_seq

        chai_on = ecfg.use_chai and cfg.chai.enabled and cfg.k_max > 0
        self.chai_on = chai_on
        # Page pools hold global-attention KV: archs without global
        # layers have nothing to page. MHA+CHAI archs add the clustered
        # pool.
        has_pools = cfg.n_global_layers > 0
        self.chai_clustered = has_pools and chai_on and cfg.is_mha
        self.dense_pool = None
        self.chai_pool = None
        # Allocated-bytes trajectory (benchmarks/tests). Bounded:
        # recording stops at _HISTORY_MAX entries (the PREFILL->STEADY
        # head is what the benches read); the peak is a running int.
        self.kv_bytes_history: List[dict] = []
        self._kv_peak = 0
        assert s % ecfg.page_size == 0, (s, ecfg.page_size)
        p_slot = s // ecfg.page_size
        self._slot_pages_max = p_slot
        if has_pools:
            n_dense = ecfg.num_pages or (2 * b * p_slot + 1)
            self.dense_pool = chai_cache.PagePool(n_dense, ecfg.page_size)
            if self.chai_clustered:
                share = 2 if cfg.chai.share_values else 1
                n_chai = ecfg.num_chai_pages or (share * b * p_slot + 1)
                self.chai_pool = chai_cache.PagePool(n_chai, ecfg.page_size)
        # -- shared-prefix KV reuse ---------------------------------------
        self.prefix_cache = None
        if ecfg.prefix_cache:
            if (cfg.n_local_layers or cfg.n_rec_layers
                    or cfg.n_rwkv_layers):
                # Local rings / recurrent state depend on the whole
                # prefix but are not paged — a suffix-only prefill
                # cannot rebuild them.
                raise ValueError(
                    "prefix_cache supports global-attention-only archs "
                    f"(got {cfg.name!r} with local/recurrent layers)")
            from repro.serving.prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(self.dense_pool,
                                            self.chai_pool, ecfg.page_size)
        # -- hierarchical KV tiers (serving/kv_tiers.py) ------------------
        # Built for EVERY engine with page pools: preemption swap-out
        # always routes its victim payloads through the host pool.
        # Prefix-cache demotion (eviction -> host instead of drop)
        # additionally needs ecfg.kv_offload.
        self.tiers = None
        self._prefetch_q: deque = deque()
        self._prefetch_ids: set = set()
        if ecfg.kv_offload and not has_pools:
            raise ValueError(
                "kv_offload needs page pools: "
                f"{cfg.name!r} has no global attention layer")
        if has_pools:
            host_d = ecfg.host_pages or 2 * self.dense_pool.capacity
            host_c = 0
            if self.chai_pool is not None:
                host_c = ecfg.host_pages or 2 * self.chai_pool.capacity
            self.tiers = kv_tiers_mod.TierManager(
                ecfg.page_size,
                host_pages={"dense": host_d, "chai": host_c},
                # Only radix nodes compress, and nodes hold dense pages
                # only — the clustered kind never rides the int4 rung.
                comp_pages={"dense": ecfg.compressed_pages or host_d,
                            "chai": 0},
                on_transition=self._tel_tier_transition)
            if self.prefix_cache is not None:
                self.prefix_cache.tiers = self.tiers
                self.tiers.drop_hook = self.prefix_cache.drop_demoted
                self.tiers.droppable_hook = self.prefix_cache._droppable
                if ecfg.kv_offload:
                    self.prefix_cache.demote_hook = self._demote_entry
        # -- chunked prefill (page-aligned chunks) ------------------------
        self._chunk = 0
        if ecfg.prefill_chunk_tokens:
            if (cfg.n_local_layers or cfg.n_rec_layers
                    or cfg.n_rwkv_layers):
                raise ValueError(
                    "prefill_chunk_tokens supports global-attention-only "
                    f"archs (got {cfg.name!r} with local/recurrent "
                    "layers): chunk forwards cannot rebuild local rings "
                    "or recurrent state from earlier chunks")
            ps = ecfg.page_size
            self._chunk = -(-ecfg.prefill_chunk_tokens // ps) * ps
        # Device state persists across step()/run() calls, so cached
        # pages keep their contents between request waves (retired slots
        # rewind pos — stale rows are masked exactly like the zero tail).
        # None until the first step.
        self._dev_state = None
        self._dev_ctx = None
        self.cluster_transitions = 0   # CLUSTER phase transitions executed
        # -- step-driven scheduler state ----------------------------------
        self._uid_counter = 0          # monotonic: uids never collide
        self._requests: dict = {}      # uid -> Request (abort lookup)
        self._slot_req: List[Optional[Request]] = [None] * b
        self._slot_count = [0] * b          # tokens generated this admission
        self._slot_pages: List[dict] = [{} for _ in range(b)]  # page ids
        self._slot_locked: List[list] = [[] for _ in range(b)]  # cache pins
        # chunked prefill cursors: {"req", "tokens", "cursor"} per slot
        self._slot_prefill_state: List[Optional[dict]] = [None] * b
        self.preemptions = 0           # slots reclaimed for priority
        self._next_tok = np.zeros((b,), np.int32)   # host mirror
        self._next_tok_dev = jnp.zeros((b,), jnp.int32)
        self._tok_dirty = False
        self._phases = np.full((b,), chai_cache.PHASE_FREE, np.int32)
        # Per-slot SamplingParams device vectors (FREE slots sample
        # greedily — their tokens are never recorded). Host mirrors are
        # re-uploaded only after an admission/retire edited them.
        self._samp_host = {"temperature": np.zeros((b,), np.float32),
                           "top_k": np.zeros((b,), np.int32),
                           "top_p": np.ones((b,), np.float32),
                           "seed": np.zeros((b,), np.uint32)}
        self._samp_dev = None
        self._samp_dirty = True
        # jax.jit wrappers are lazy (no tracing until the first call), so
        # every step is declared here unconditionally. decode_ts =
        # page_size: the page size the fused CHAI decode kernel's S-tile
        # derives from (kernels.chai_attention.decode_tile_pages).
        self._sampler = jax.jit(steps_mod.make_sampler())
        # All-greedy fast path: the full sampler computes its sampling
        # lane (argsort + softmax + PRNG) for every slot and discards it
        # via jnp.where on greedy rows — host-dispatch a bare argmax when
        # NO slot is sampling (the engine default), exactly like the
        # phase-mix step dispatch. Bitwise-identical to the sampler's
        # greedy lane (both argmax the raw f32 logits).
        self._argmax = jax.jit(
            lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))
        # Always-on NaN/Inf logits guard: one reduction per row — a
        # non-finite row quarantines ITS slot; decode rows are
        # independent, so the others are bitwise-untouched.
        self._finite_rows = jax.jit(
            lambda lg: jnp.isfinite(jnp.max(lg, axis=-1)))
        self._mha_step = jax.jit(
            steps_mod.make_serve_step(cfg, chai=False,
                                      decode_ts=ecfg.page_size),
            donate_argnums=(2,))
        self._reset_slot = jax.jit(steps_mod.make_paged_slot_reset(cfg),
                                   donate_argnums=(0,))
        self._slot_prefills: dict = {}       # pow2 length bucket -> jit
        self._suffix_prefills: dict = {}     # suffix bucket -> jit
        self._chunk_prefills: dict = {}      # chunk bucket -> jit
        self._cluster_slot = None            # built lazily (identify hook)
        self._swap_fns = None                # preemption KV swap (out, in)
        self._restore_snapshot = jax.jit(
            steps_mod.make_snapshot_restore(cfg), donate_argnums=(0,))
        self._copy_page = {
            kind: jax.jit(steps_mod.make_page_copy(cfg, kind),
                          donate_argnums=(0,))
            for kind in ("dense", "chai")}
        # Tier demote/promote: one-page gather / scatter jits (the page id
        # is traced — one trace per kind).
        self._fetch_page = {"dense": jax.jit(
            steps_mod.make_page_fetch(cfg, "dense"))}
        self._put_page = {"dense": jax.jit(
            steps_mod.make_page_put(cfg, "dense"), donate_argnums=(0,))}
        if self.chai_clustered:
            self._fetch_page["chai"] = jax.jit(
                steps_mod.make_page_fetch(cfg, "chai"))
            self._put_page["chai"] = jax.jit(
                steps_mod.make_page_put(cfg, "chai"), donate_argnums=(0,))
        self._set_ctx = jax.jit(clustering.update_ctx_slot,
                                donate_argnums=(0,))
        if chai_on:
            self._chai_step = jax.jit(
                steps_mod.make_serve_step(cfg, chai=True,
                                          decode_ts=ecfg.page_size),
                donate_argnums=(2,))
            self._mixed_step = jax.jit(
                steps_mod.make_mixed_step(cfg, decode_ts=ecfg.page_size),
                donate_argnums=(2,))
            self._identify = jax.jit(
                lambda sc: clustering.identify_membership(sc, cfg))
        # -- shared-prefix relay decode -----------------------------------
        # Host caches keyed by a clustering-context version: the per-slot
        # head->cluster maps feeding the relay row maps change only at
        # CLUSTER transitions, snapshot restores and preemption swap-ins,
        # so row maps / host ctx mirrors are rebuilt only when the
        # version moves (not every step).
        self._ctx_version = 0
        self._ctx_host_cache = None    # (version, {name: np.ndarray})
        self._relay_rows_cache = None  # (key, {k_row, a_row, v_row})
        self._pack_prefix = {}         # chain length -> resident-pack jit
        self.relay_steps = 0           # decode steps that ran the relay
        self.relay_grouped_slots = 0   # cumulative grouped-slot count
        # Mixed-batch sampling sub-batch (greedy slots skip the sampling
        # lane): row gather / scatter-over-argmax helpers.
        self._take_rows = jax.jit(lambda a, idx: a[idx])
        self._put_rows = jax.jit(lambda a, idx, v: a.at[idx].set(v))
        self.relay_decode = False
        if ecfg.relay_decode:
            if not (chai_on and ecfg.prefix_cache):
                raise ValueError(
                    "relay_decode requires prefix_cache with CHAI "
                    "enabled (the relay groups STEADY slots by their "
                    "locked radix chain)")
            self.relay_decode = True
            # One jit; jax retraces per relay signature (G, Nmax, Sp) —
            # group shapes recur across steps so the trace cache holds.
            self._relay_step = jax.jit(
                steps_mod.make_relay_step(cfg, decode_ts=ecfg.page_size),
                donate_argnums=(2,))

    # -- public API --------------------------------------------------------
    def default_sampling(self) -> SamplingParams:
        return (SamplingParams() if self.ecfg.greedy
                else SamplingParams(temperature=1.0))

    def add_request(self, prompt, sampling: Optional[SamplingParams] = None,
                    *, max_new_tokens: Optional[int] = None, uid=None,
                    arrival_delay: float = 0.0,
                    priority: int = 0) -> Request:
        """Enqueue a request with per-request ``SamplingParams``.

        ``max_new_tokens`` (when given) overrides
        ``sampling.max_new_tokens``. ``arrival_delay`` (seconds from now)
        models open-loop arrivals: the scheduler will not admit the
        request before its arrival time. ``priority``: preemption class —
        under page pressure a strictly-higher-priority arrival may
        reclaim a running lower-priority slot (``EngineConfig.preemption``).
        Default uids come from a monotonic engine counter (explicit uids
        bump it past themselves, so later defaults can never collide with
        retired requests)."""
        sp = sampling if sampling is not None else self.default_sampling()
        max_new = (max_new_tokens if max_new_tokens is not None
                   else sp.max_new_tokens)
        if len(prompt) + max_new > self.ecfg.max_seq:
            raise ValidationError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new}) exceeds max_seq "
                f"({self.ecfg.max_seq}): the KV capacity (dense slot or "
                f"page budget) cannot hold the request", uid=uid)
        if sp.stop and self.detokenizer is None:
            raise ValidationError(
                "SamplingParams.stop strings need an engine "
                "detokenizer (EngineCore(detokenizer=...))", uid=uid)
        if uid is None:
            uid = self._uid_counter
        self._uid_counter = max(self._uid_counter, int(uid) + 1)
        req = Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new, sampling=sp,
                      priority=int(priority))
        req.t_enqueue = time.time()
        req.t_arrival = req.t_enqueue + arrival_delay
        req.generated = []
        self.queue.append(req)
        self._requests[uid] = req
        if (self.ecfg.kv_offload and self.ecfg.tier_prefetch
                and self.prefix_cache is not None):
            # Admission-time prefetch: queue the demoted entries this
            # prompt will hit for promotion ahead of the planning step.
            self._queue_prefetch(req)
        if self.tel.enabled:
            self.tel.counter("requests_submitted_total",
                             help="Requests enqueued via add_request")
            self.tel.gauge("engine_queue_depth", len(self.queue),
                           help="Requests waiting in the arrival queue")
            self.tel.event(req.uid, "enqueue", t=req.t_enqueue,
                           prompt_tokens=int(len(req.prompt)),
                           max_new_tokens=int(max_new),
                           priority=int(priority))
        return req

    def _done(self, req: Request):
        """Finalize a request: move it to ``done`` and drop the abort
        lookup entry (unless a newer request reused the uid), so a
        long-lived core does not grow per request served. ``done`` itself
        accumulates for the batch ``run()`` surface; step-driven
        frontends keep it bounded via ``reap_done()``."""
        self.done.append(req)
        if self._requests.get(req.uid) is req:
            del self._requests[req.uid]
        if self.tel.enabled:
            self._tel_finish(req)

    def reap_done(self) -> List[Request]:
        """Return AND clear the finished-request list. Long-lived
        frontends (``LLM``) call this after collecting their outputs;
        the legacy ``ServingEngine.run()`` surface leaves ``done``
        accumulating across calls instead."""
        out, self.done = self.done, []
        return out

    def abort(self, uid) -> bool:
        """Cancel a request: a queued request is dropped before touching
        the device; a running one retires immediately — its pages (and
        prefix-cache locks) return refcount-exactly, its slot resets, and
        concurrent slots are untouched. Tokens generated so far stay on
        the Request (``finish_reason="aborted"``). Returns False for
        unknown / already-finished uids."""
        req = self._requests.get(uid)
        if req is None or req.finished:
            return False
        if req in self.queue:
            self.queue.remove(req)
            self._free_resume(req)      # swapped-out victims hold host pages
            req.finish_reason = sampling_mod.FINISH_ABORT
            req.t_done = time.time()
            req.retire_step = self.steps_executed
            self._done(req)
            return True
        for i, r in enumerate(self._slot_req):
            if r is req:
                self._retire_slot(i, sampling_mod.FINISH_ABORT)
                return True
        return False

    @property
    def has_active(self) -> bool:
        return any(r is not None for r in self._slot_req)

    def has_work(self) -> bool:
        return bool(self.queue) or self.has_active

    def next_arrival(self) -> Optional[float]:
        """Earliest queued arrival time (callers sleep until it when
        ``step()`` makes no progress), or None when the queue is empty."""
        return self.queue[0].t_arrival if self.queue else None

    def step(self) -> List[StepOutput]:
        """Run exactly ONE scheduler iteration: advance one prefill chunk
        for every mid-prefill slot, admit arrived requests into free
        slots (prefix-cache planning and priority preemption included),
        run CLUSTER transitions for slots whose warmup just completed,
        execute one mixed-phase batched decode + sample, and retire slots
        that hit a finish condition. Returns one ``StepOutput`` per
        request that emitted tokens. Non-blocking: with no admissible
        work it returns ``[]`` (use ``next_arrival()`` to wait); with the
        engine idle and the queue head unserviceable even after draining
        the prefix cache, raises ``CapacityError`` (a ``MemoryError``,
        exactly like the page-budget gate always has, now carrying the
        uid). Request-isolatable failures (injected faults, swap-in
        corruption, non-finite logits) never raise: the offending
        request is QUARANTINED — typed ``StepOutput`` with
        ``finish_reason="error"``, pages released refcount-exactly — and
        the batch keeps running. ``EngineConfig.audit_level`` gates an
        invariant audit after the iteration; a violation raises
        ``EngineFault``."""
        tel = self.tel
        if tel.enabled:
            t0 = time.perf_counter()
        with tel.span("step", step=self._step_calls):
            outs = self._step_inner()
            if self.ecfg.audit_level != "off":
                self.audit_steps += 1
                with tel.span("audit", step=self._span_step):
                    vio = invariants_mod.audit(
                        self, deep=self.ecfg.audit_level == "deep")
                if vio:
                    raise EngineFault(
                        f"invariant audit failed at step "
                        f"{self.steps_executed}", violations=vio)
        if tel.enabled:
            tel.observe("engine_step_seconds", time.perf_counter() - t0,
                        help="Wall time of one step() iteration")
            tel.counter("engine_steps_total",
                        help="step() iterations executed")
            tel.gauge("engine_queue_depth", len(self.queue),
                      help="Requests waiting in the arrival queue")
            tel.gauge("engine_active_slots",
                      sum(1 for r in self._slot_req if r is not None),
                      help="Batch slots holding a live request")
        return outs

    def _step_inner(self) -> List[StepOutput]:
        outs: List[StepOutput] = []
        self._ensure_dev_state()
        if self._prefetch_q:
            self._drain_prefetch()
        b = self.ecfg.batch_slots
        drained = False
        self._fault_blocked = False
        self._span_step = self._step_calls
        self._step_calls += 1
        self._advance_prefills(outs)
        while True:
            blocked = self._admit(outs)
            active = [i for i in range(b)
                      if self._slot_req[i] is not None
                      and self._phases[i] != chai_cache.PHASE_PREFILL]
            if active:
                break
            if self.has_active:
                return outs        # only mid-prefill slots: progress made
            if not self.queue or not blocked:
                return outs        # idle, or waiting on future arrivals
            if self._fault_blocked:
                return outs        # injected transient: retry next step
            # The failed plan ran with the engine idle (no retire can
            # intervene between the attempt and here). Drain the prefix
            # cache and retry once — only if even an empty cache cannot
            # cover the request is it impossible.
            if not drained and self.prefix_cache is not None and (
                    self.prefix_cache.num_blocks
                    or self.prefix_cache.num_snapshots):
                self.prefix_cache.clear()
                drained = True
                continue
            head = self.queue[0]
            n = self._pages_for(head)
            if self.dense_pool.free_pages < 2 * n:
                raise CapacityError(
                    f"request uid={head.uid} needs {2 * n} "
                    f"dense pages; pool capacity "
                    f"{self.dense_pool.capacity}", uid=head.uid)
            share = 2 if self.cfg.chai.share_values else 1
            raise CapacityError(
                f"request uid={head.uid} needs {n * share} "
                f"clustered pages; pool capacity "
                f"{self.chai_pool.capacity}", uid=head.uid)
        self._cluster_transitions(active, outs)
        # A kernel.cluster quarantine may have retired slots mid-list.
        active = [i for i in active if self._slot_req[i] is not None]
        if active:
            outs.extend(self._decode(active))
        return outs

    # -- fault injection / quarantine --------------------------------------
    def _fault(self, site: str, uid: int = -1):
        """Consult the fault injector at a named site; None when no
        injector is armed or nothing fires."""
        if self.faults is None:
            return None
        spec = self.faults.fire(site, step=self.steps_executed, uid=uid)
        if spec is not None and self.tel.enabled:
            self.tel.counter("faults_injected_total", site=site,
                             mode=spec.mode,
                             help="Injected faults that fired, by site")
        return spec

    def _quarantine_queued(self, req: Request, err: RequestError,
                           outs: List[StepOutput]):
        """Typed-fail a request that is still queued (or was just popped):
        no device state to unwind — record the error and finish it."""
        if req in self.queue:
            self.queue.remove(req)
        self._free_resume(req)  # swapped-out victims hold host pages
        req.finish_reason = sampling_mod.FINISH_ERROR
        req.error = str(err)
        req.t_done = time.time()
        req.retire_step = self.steps_executed
        self.quarantined += 1
        if self.tel.enabled:
            self.tel.event(req.uid, "quarantine", reason=str(err))
        self._done(req)
        outs.append(StepOutput(req.uid, [], True,
                               sampling_mod.FINISH_ERROR))

    def _abort_admission(self, i: int, req: Request, gen0: int,
                         hit0: tuple):
        """Unwind a failed ``_admit_to_slot``: free the plan's pages and
        locks refcount-exactly, reset the slot on device, and rewind the
        request's progress records to their pre-admission values."""
        self._slot_prefill_state[i] = None
        self._phases[i] = chai_cache.PHASE_FREE
        self._slot_count[i] = 0
        self._dev_state = self._reset_slot(self._dev_state, jnp.int32(i))
        self._free_pages(self._slot_pages[i])
        if self._slot_locked[i]:
            self.prefix_cache.unlock(self._slot_locked[i])
            self._slot_locked[i] = []
        req.generated = req.generated[:gen0]
        req.cache_hit, req.cached_tokens, req.prefill_tokens = hit0

    # -- telemetry hooks (all callers guard on self.tel.enabled) -----------
    def _tel_admit(self, i: int, req: Request, plan: dict, resumed: bool):
        """Admission succeeded: labeled admit counter, queue-wait
        histogram, CHAI cache-hit token counters, timeline event."""
        tel = self.tel
        kind = "swap" if resumed else plan["kind"]
        tel.counter("requests_admitted_total", kind=kind,
                    help="Slot admissions by plan kind")
        tel.observe("request_queue_seconds",
                    max(0.0, time.time() - req.t_enqueue),
                    help="Enqueue-to-admission wait")
        tel.event(req.uid, "resume" if resumed else "admit", slot=i,
                  kind=kind, step=self.steps_executed,
                  cached_tokens=int(req.cached_tokens))
        if req.cache_hit == "prefix":
            tel.counter("prefix_hit_tokens_total", req.cached_tokens,
                        help="Prompt tokens served from the radix cache")
        elif req.cache_hit == "snapshot":
            tel.counter("snapshot_hit_tokens_total", req.cached_tokens,
                        help="Prompt tokens served from CHAI snapshots")
            # Snapshot admissions land in STEADY with warmup tokens
            # already emitted: their first token happened here.
            if req.generated and req.t_first_token:
                tel.event(req.uid, "first_token", t=req.t_first_token)
                tel.observe("request_ttft_seconds",
                            max(0.0, req.t_first_token - req.t_enqueue),
                            help="Enqueue-to-first-token latency")
                tel.counter("tokens_generated_total", len(req.generated),
                            help="Generated tokens emitted")
                tel.token(req.uid, n=len(req.generated),
                          t=req.t_first_token)

    def _tel_finish(self, req: Request):
        """Request reached a terminal state (retire, abort, quarantine,
        replay): reason-labeled counter, latency histogram, timeline
        seal."""
        tel = self.tel
        reason = req.finish_reason or "unknown"
        tel.counter("requests_finished_total", reason=reason,
                    help="Requests finished, by finish_reason")
        if req.error:
            tel.counter("requests_quarantined_total",
                        help="Requests typed-failed and quarantined")
        if req.t_done and req.t_enqueue:
            tel.observe("request_latency_seconds",
                        max(0.0, req.t_done - req.t_enqueue),
                        help="Enqueue-to-completion latency")
        data = {"reason": reason,
                "tokens": len(req.generated or ()),
                "preemptions": int(req.preemptions)}
        if req.error:
            data["error"] = req.error
        if req.cache_hit:
            data["cache_hit"] = req.cache_hit
        tel.event(req.uid, "finish", t=req.t_done or None, **data)
        tel.finish(req.uid)

    def _tel_clusters(self, i: int):
        """Per-layer cluster-count gauges from slot ``i``'s freshly
        written clustering context (one small device fetch per CLUSTER
        transition — never on the per-step path)."""
        ctx = {k: np.asarray(v[:, i]) for k, v in self._dev_ctx.items()}
        if "h2c" in ctx:                      # MHA: (nA, H) head->cluster
            h2c = ctx["h2c"]
            for layer in range(h2c.shape[0]):
                self.tel.gauge("chai_clusters", len(np.unique(h2c[layer])),
                               layer=layer,
                               help="Clusters per attention layer at the "
                                    "latest CLUSTER transition")
        elif "cluster_of" in ctx:             # GQA: (nA, KV, qpk)
            co = ctx["cluster_of"]
            for layer in range(co.shape[0]):
                n = sum(int(len(np.unique(co[layer, g])))
                        for g in range(co.shape[1]))
                self.tel.gauge("chai_clusters", n, layer=layer,
                               help="Clusters per attention layer at the "
                                    "latest CLUSTER transition")

    def _refresh_gauges(self):
        """Point-in-time gauges recomputed at scrape time."""
        tel = self.tel
        tel.gauge("engine_queue_depth", len(self.queue),
                  help="Requests waiting in the arrival queue")
        tel.gauge("engine_active_slots",
                  sum(1 for r in self._slot_req if r is not None),
                  help="Batch slots holding a live request")
        tel.gauge("engine_degraded_decode", int(self.degraded_decode),
                  help="1 while decode runs the jnp reference fallback")
        self._tel_kv_gauges(self.kv_bytes())
        if self.tiers is not None:
            help_tier = "KV pages resident per tier and pool kind"
            tel.gauge("kv_tier_pages", self.dense_pool.pages_in_use,
                      tier="hot", kind="dense", help=help_tier)
            if self.chai_pool is not None:
                tel.gauge("kv_tier_pages", self.chai_pool.pages_in_use,
                          tier="hot", kind="chai", help=help_tier)
            for (tier, kind), n in self.tiers.tier_pages().items():
                tel.gauge("kv_tier_pages", n, tier=tier, kind=kind,
                          help=help_tier)

    def _tel_kv_gauges(self, bytes_now: int):
        """Allocated-KV gauges: bytes, and pages in use per pool."""
        tel = self.tel
        tel.gauge("kv_bytes_allocated", bytes_now,
                  help="Allocated KV bytes right now")
        if self.dense_pool is not None:
            tel.gauge("dense_pages_in_use", self.dense_pool.pages_in_use,
                      help="Dense-pool pages in use")
        if self.chai_pool is not None:
            tel.gauge("chai_pages_in_use", self.chai_pool.pages_in_use,
                      help="Clustered-pool pages in use")

    def metrics(self):
        """JSON-ready metrics snapshot (refreshes point-in-time gauges
        first). None when ``EngineConfig.telemetry == "off"``."""
        if not self.tel.enabled:
            return None
        self._refresh_gauges()
        return self.tel.snapshot()

    def metrics_text(self):
        """Prometheus text exposition of ``metrics()`` (None when
        telemetry is off)."""
        snap = self.metrics()
        return None if snap is None else exporters_mod.to_prometheus(snap)

    def request_timeline(self, uid):
        """Lifecycle timeline (events + derived TTFT/ITL/queue summary)
        for one request uid; None when unknown or telemetry is off."""
        return self.tel.timeline(uid)

    def step_trace(self):
        """Chrome-trace JSON object of the recorded step spans (empty
        when telemetry is off)."""
        return exporters_mod.to_chrome_trace(self.tel.spans)

    # -- scheduler ---------------------------------------------------------
    @staticmethod
    def _prompt_bucket(t: int, cap: int) -> int:
        """Next power of two >= t, capped at max_seq."""
        b = 1
        while b < t:
            b <<= 1
        return min(b, cap)

    def _slot_prefill_fn(self, bucket: int):
        """One compiled prefill per power-of-two prompt-length BUCKET
        (prompts are right-padded to the bucket; the tail is masked via
        the traced ``true_len``), so prefill retraces are O(log max_seq)
        instead of O(distinct prompt lengths)."""
        fn = self._slot_prefills.get(bucket)
        if fn is None:
            fn = jax.jit(steps_mod.make_paged_slot_prefill(
                self.cfg, self.ecfg.max_seq), donate_argnums=(3,))
            self._slot_prefills[bucket] = fn
        return fn

    def _padded_prompt(self, prompt):
        """Right-pad a prompt to its bucket; returns (tokens (1, bucket),
        true_len scalar). The jit cache key is the padded array's shape,
        so the bucket is computed in exactly one place."""
        t = len(prompt)
        bucket = self._prompt_bucket(t, self.ecfg.max_seq)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :t] = prompt
        return jnp.asarray(toks), jnp.int32(t)

    def _suffix_prefill_fn(self, bucket: int):
        """One compiled suffix prefill per suffix-length bucket (the
        cached-prefix length rides in as a traced scalar)."""
        fn = self._suffix_prefills.get(bucket)
        if fn is None:
            fn = jax.jit(steps_mod.make_paged_suffix_prefill(
                self.cfg, self.ecfg.max_seq), donate_argnums=(4,))
            self._suffix_prefills[bucket] = fn
        return fn

    def _padded_suffix(self, suffix, prefix_len: int):
        """Right-pad an uncached suffix to its bucket. The bucket must
        keep ``prefix_len + bucket`` within max_seq (padded cache writes
        must stay inside the slot's logical pages); when the power-of-two
        bucket would overflow, fall back to the suffix's page-multiple —
        a key that depends only on the suffix length, NOT on prefix_len,
        so the jit-key set stays O(log max_seq + max_seq/page_size)
        instead of one compile per distinct cached-prefix length."""
        t = len(suffix)
        ps = self.ecfg.page_size
        bucket = self._prompt_bucket(t, self.ecfg.max_seq)
        if bucket > self.ecfg.max_seq - prefix_len:
            bucket = chai_cache.pages_needed(t, ps) * ps
        assert t <= bucket <= self.ecfg.max_seq - prefix_len, \
            (bucket, t, prefix_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :t] = suffix
        return jnp.asarray(toks), jnp.int32(t)

    def _chunk_prefill_fn(self, bucket: int):
        """One compiled chunk prefill per chunk-length bucket (start
        position and final-chunk phase ride in as traced scalars)."""
        fn = self._chunk_prefills.get(bucket)
        if fn is None:
            fn = jax.jit(steps_mod.make_paged_chunk_prefill(
                self.cfg, self.ecfg.max_seq), donate_argnums=(4,))
            self._chunk_prefills[bucket] = fn
        return fn

    def _cluster_fn(self):
        # Built on first use so a monkeypatched ``_identify`` hook (tests,
        # CHAI-static ablations) is honored.
        if self._cluster_slot is None:
            self._cluster_slot = jax.jit(
                steps_mod.make_paged_slot_cluster(self.cfg, self._identify),
                donate_argnums=(0, 1))
        return self._cluster_slot

    def _swap_fns_get(self):
        """(swap_out, swap_in) jits for preemption KV swap — one trace
        per arch (page vectors are fixed-length, null-padded)."""
        if self._swap_fns is None:
            out, inn = steps_mod.make_slot_swap(self.cfg)
            self._swap_fns = (jax.jit(out),
                              jax.jit(inn, donate_argnums=(0,)))
        return self._swap_fns

    # -- sampling (host <-> device) ----------------------------------------
    def _set_slot_sampling(self, slot: int, sp: SamplingParams):
        h = self._samp_host
        h["temperature"][slot] = sp.temperature
        h["top_k"][slot] = sp.top_k
        h["top_p"][slot] = sp.top_p
        h["seed"][slot] = np.uint32(sp.seed)
        self._samp_dirty = True

    def _sample_first(self, logits, req: Request) -> int:
        """Sample a request's FIRST token from its prefill logits (count
        0 — the same draw a batched decode step makes for its row)."""
        sp = req.sampling
        if sp.greedy:
            return int(np.asarray(self._argmax(logits))[0])
        out = self._sampler(
            logits,
            jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            jnp.asarray([sp.top_p], jnp.float32),
            jnp.asarray(np.asarray([sp.seed], np.uint32)),
            jnp.zeros((1,), jnp.int32))
        return int(np.asarray(out)[0])

    def _finish_of(self, req: Request) -> str:
        return sampling_mod.finish_reason(req.generated, req.sampling,
                                          req.max_new_tokens,
                                          self.detokenizer)

    # -- paged-pool bookkeeping (host side) --------------------------------
    def _pages_for(self, req) -> int:
        """Logical pages a request can touch over its lifetime."""
        n = chai_cache.pages_needed(
            len(req.prompt) + req.max_new_tokens, self.ecfg.page_size)
        return min(n, self._slot_pages_max)

    def _try_alloc(self, req):
        """Page-budget admission: allocate the request's dense K + V pages
        (and reserve its clustered pages, so the CLUSTER transition can
        never deadlock mid-flight). Returns a page dict or None if the
        pools cannot cover it yet."""
        n = self._pages_for(req)
        chai_n = self._chai_pages_per(n)
        if self.dense_pool.free_pages < 2 * n:
            return None
        if chai_n and self.chai_pool.free_pages < chai_n:
            return None
        pages = {"kg": self.dense_pool.alloc(n),
                 "vg": self.dense_pool.alloc(n)}
        if self.chai_clustered:
            pages["kc"] = self.chai_pool.alloc(n)
            if self.cfg.chai.share_values:
                pages["vc"] = self.chai_pool.alloc(n)
        return pages

    def _free_pages(self, pages: dict):
        for key, pool in (("kg", self.dense_pool), ("vg", self.dense_pool),
                          ("kc", self.chai_pool), ("vc", self.chai_pool)):
            if key in pages:
                pool.free(pages.pop(key))

    def _page_vec(self, pages):
        """Null-padded (P,) int32 device vector of a page list."""
        vec = np.zeros((self._slot_pages_max,), np.int32)
        vec[:len(pages)] = pages
        return jnp.asarray(vec)

    # -- prefix-cache admission planning (host side) -----------------------
    def _chai_pages_per(self, n: int) -> int:
        if not self.chai_clustered:
            return 0
        return n * (2 if self.cfg.chai.share_values else 1)

    def _eligible_snapshot(self, req):
        """The single gate for the CHAI snapshot fast path (used by the
        admit loop's replay check AND the planner — one definition, no
        divergence): cache on + clustered CHAI + a GREEDY request
        (replay correctness rests on greedy determinism; sampling
        requests take the block-prefix path instead). Preempted requests
        (generated tokens already emitted) never replay — their tokens
        must continue, not repeat."""
        if req.generated:
            return None
        if (self.prefix_cache is not None and self.chai_clustered
                and req.sampling.greedy):
            return self.prefix_cache.snapshot_for(req.prompt)
        return None

    def _pool_space(self, dense_need: int, chai_need: int) -> bool:
        """True when the pools can cover the request, evicting unlocked
        prefix-cache entries (LRU) if that is what it takes."""
        ok = (self.dense_pool.free_pages >= dense_need
              and (not chai_need
                   or self.chai_pool.free_pages >= chai_need))
        if ok or self.prefix_cache is None:
            return ok
        return self.prefix_cache.evict_until(dense_free=dense_need,
                                             chai_free=chai_need)

    def _plan_admission(self, req):
        """Build an admission plan for the queue head, mutating the pools
        (alloc + incref) and locking the cache entries it aliases.
        Returns None when the pools cannot cover the request yet.

        kinds: "cold" (no reuse), "prefix" (longest cached block-prefix
        aliased, suffix prefilled), "snapshot" (full prompt cached with a
        CHAI snapshot: enter STEADY directly). The replay fast path
        (snapshot covers max_new_tokens entirely — host-side, no slot)
        is handled by the admit loop before planning. Preempted requests
        (``resume_state`` set) take the swap-in plan instead: fresh pages
        matching what the slot held, restored bitwise — no prefill. A
        model with no page pools is always admitted cold, with no pages."""
        if self.dense_pool is None:
            return {"kind": "cold", "pages": {}, "locked": []}
        spec = self._fault("pool.alloc", uid=req.uid)
        if spec is not None:
            if spec.mode == "error":
                raise QuarantineError(
                    f"injected allocator failure for uid={req.uid}",
                    uid=req.uid)
            self._fault_blocked = True
            return None     # transient: the plan retries next step
        cache = self.prefix_cache
        if req.resume_state is not None:
            return self._plan_swap_in(req)
        snap = self._eligible_snapshot(req)
        if snap is not None:
            plan = self._plan_snapshot(req, snap)
            if plan is _REPLAN:
                # A demoted snapshot failed promotion and was dropped:
                # plan again from scratch (bounded — each replan removed
                # at least one cache entry).
                return self._plan_admission(req)
            if plan is not None:
                return plan
            return None         # a cold plan needs strictly more pages
        matched = cache.match(req.prompt) if cache is not None else []
        if matched:
            plan = self._plan_prefix(req, matched)
            if plan is _REPLAN:
                return self._plan_admission(req)
            if plan is not None:
                return plan
            return None
        n = self._pages_for(req)
        if not self._pool_space(2 * n, self._chai_pages_per(n)):
            return None     # even LRU eviction cannot cover it yet
        pages = self._try_alloc(req)
        if pages is None:
            return None
        return {"kind": "cold", "pages": pages, "locked": []}

    def _plan_swap_in(self, req):
        """Allocate fresh pages matching exactly what the preempted slot
        held per pool kind (a clustered STEADY victim, e.g., holds no
        dense K pages); the swap-in restores the saved contents into
        them. Never more pages than the original admission, so a request
        that was admitted once can always be planned again."""
        want = req.resume_state["npages"]
        dense_need = want.get("kg", 0) + want.get("vg", 0)
        chai_need = want.get("kc", 0) + want.get("vc", 0)
        if not self._pool_space(dense_need, chai_need):
            return None
        pages = {}
        for kind, pool in (("kg", self.dense_pool), ("vg", self.dense_pool),
                           ("kc", self.chai_pool), ("vc", self.chai_pool)):
            if want.get(kind):
                pages[kind] = pool.alloc(want[kind])
        return {"kind": "swap", "pages": pages, "locked": []}

    def _plan_prefix(self, req, matched):
        """Alias ``matched`` block pages; allocate fresh pages for the
        suffix + generation headroom (and the full clustered reservation,
        as on the cold path)."""
        cache = self.prefix_cache
        n = self._pages_for(req)
        n_m = min(len(matched), n)
        matched = matched[:n_m]
        chai_n = self._chai_pages_per(n)
        cache.lock(matched)     # pin before eviction can run
        demoted = [m for m in matched
                   if m.tier != kv_tiers_mod.TIER_HOT]
        for m in matched:
            if m.prefetched:
                m.prefetched = False
                self.prefetch_hits += 1
                if self.tel.enabled:
                    self.tel.counter(
                        "prefetch_hits_total",
                        help="Demoted entries promoted before the "
                             "planner needed them")
        # Promoted nodes each need 2 fresh dense pages on top of the
        # suffix allocation.
        if not self._pool_space(2 * (n - n_m) + 2 * len(demoted), chai_n):
            cache.unlock(matched)
            return None
        for m in demoted:
            if self.ecfg.tier_prefetch and self.ecfg.kv_offload:
                self.prefetch_misses += 1
                if self.tel.enabled:
                    self.tel.counter(
                        "prefetch_misses_total",
                        help="Demoted entries promoted synchronously "
                             "at plan time")
            if not self._promote_entry(m, uid=req.uid):
                cache.unlock(matched)   # dropped entries stay evicted
                return _REPLAN
        kg_fresh = self.dense_pool.alloc(n - n_m)
        vg_fresh = self.dense_pool.alloc(n - n_m)
        kg_alias = [m.kg_page for m in matched]
        vg_alias = [m.vg_page for m in matched]
        self.dense_pool.incref(kg_alias)
        self.dense_pool.incref(vg_alias)
        pages = {"kg": kg_alias + kg_fresh, "vg": vg_alias + vg_fresh}
        if self.chai_clustered:
            pages["kc"] = self.chai_pool.alloc(n)
            if self.cfg.chai.share_values:
                pages["vc"] = self.chai_pool.alloc(n)
        null = [chai_cache.NULL_PAGE] * n_m
        return {"kind": "prefix", "pages": pages, "locked": matched,
                "prefix_len": n_m * self.ecfg.page_size,
                "scatter_kg": null + kg_fresh,
                "scatter_vg": null + vg_fresh}

    def _plan_snapshot(self, req, snap):
        """Resume from a CHAI snapshot: share its full pages, copy its
        partial tail page(s) (copy-on-write), allocate headroom for the
        remaining generation, and enter STEADY directly."""
        cache = self.prefix_cache
        share = self.cfg.chai.share_values
        ps = self.ecfg.page_size
        n = self._pages_for(req)
        p_full, rem = divmod(snap.pos, ps)
        dense_need = 0 if share else (n - p_full)
        chai_need = (n - p_full) * (2 if share else 1)
        cache.lock([snap])
        if snap.prefetched:
            snap.prefetched = False
            self.prefetch_hits += 1
            if self.tel.enabled:
                self.tel.counter(
                    "prefetch_hits_total",
                    help="Demoted entries promoted before the planner "
                         "needed them")
        extra_d = extra_c = 0
        if snap.tier != kv_tiers_mod.TIER_HOT:
            extra_d = len(snap.tier_pages.get("vg", ()))
            extra_c = (len(snap.tier_pages.get("kc", ()))
                       + len(snap.tier_pages.get("vc", ())))
        if not self._pool_space(dense_need + extra_d,
                                chai_need + extra_c):
            cache.unlock([snap])
            return None
        if snap.tier != kv_tiers_mod.TIER_HOT:
            if self.ecfg.tier_prefetch and self.ecfg.kv_offload:
                self.prefetch_misses += 1
                if self.tel.enabled:
                    self.tel.counter(
                        "prefetch_misses_total",
                        help="Demoted entries promoted synchronously "
                             "at plan time")
            if not self._promote_entry(snap, uid=req.uid):
                cache.unlock([snap])    # dropped — re-plan cold
                return _REPLAN
        copies = []     # (pool kind, src physical page, dst physical page)
        pages = {}
        if not share:
            vg_fresh = self.dense_pool.alloc(n - p_full)
            self.dense_pool.incref(snap.vg_pages[:p_full])
            pages["vg"] = snap.vg_pages[:p_full] + vg_fresh
            if rem:
                copies.append(("dense", snap.vg_pages[p_full], vg_fresh[0]))
        kc_fresh = self.chai_pool.alloc(n - p_full)
        self.chai_pool.incref(snap.kc_pages[:p_full])
        pages["kc"] = snap.kc_pages[:p_full] + kc_fresh
        if rem:
            copies.append(("chai", snap.kc_pages[p_full], kc_fresh[0]))
        if share:
            vc_fresh = self.chai_pool.alloc(n - p_full)
            self.chai_pool.incref(snap.vc_pages[:p_full])
            pages["vc"] = snap.vc_pages[:p_full] + vc_fresh
            if rem:
                copies.append(("chai", snap.vc_pages[p_full], vc_fresh[0]))
        return {"kind": "snapshot", "snapshot": snap, "pages": pages,
                "locked": [snap], "copies": copies}

    _HISTORY_MAX = 1 << 16

    def _record_kv_bytes(self, phases=None):
        bytes_now = self.kv_bytes()
        self._kv_peak = max(self._kv_peak, bytes_now)
        if self.tel.enabled:
            self._tel_kv_gauges(bytes_now)
        if len(self.kv_bytes_history) >= self._HISTORY_MAX:
            return
        dense_pages, chai_pages = self._pool_pages("pages_in_use")
        rec = {
            "step": self.steps_executed,
            "kv_bytes": bytes_now,
            "dense_pages": dense_pages,
            "chai_pages": chai_pages,
        }
        if self.tiers is not None:
            tb = self.tiers.tier_bytes()
            rec["host_bytes"] = tb.get(kv_tiers_mod.TIER_HOST, 0)
            rec["compressed_bytes"] = tb.get(kv_tiers_mod.TIER_COMP, 0)
        if phases is not None:
            rec["n_warmup"] = int((phases == chai_cache.PHASE_WARMUP).sum())
            rec["n_steady"] = int((phases == chai_cache.PHASE_STEADY).sum())
        self.kv_bytes_history.append(rec)

    def _pool_pages(self, attr: str):
        """(dense, clustered) pools' ``PagePool`` attribute ``attr``; 0
        for an absent pool."""
        return tuple(0 if pool is None else getattr(pool, attr)
                     for pool in (self.dense_pool, self.chai_pool))

    def _ensure_dev_state(self):
        """Device state, built once and kept across ``step()``/``run()``
        calls: prefix-cache pages survive between request waves."""
        cfg, ecfg = self.cfg, self.ecfg
        b = ecfg.batch_slots
        if self._dev_state is None:
            dense_pages, chai_pages = self._pool_pages("num_pages")
            self._dev_state = chai_cache.init_paged_state(
                cfg, b, ecfg.max_seq, page_size=ecfg.page_size,
                dense_pages=dense_pages, chai_pages=chai_pages,
                chai=self.chai_on)
            self._dev_ctx = (clustering.init_batched_ctx(cfg, b)
                             if self.chai_on else None)
        return self._dev_state, self._dev_ctx

    def _replay_request(self, req, snap):
        """Serve a request entirely from a CHAI snapshot's replayed warmup
        tokens: no slot, no pages, no device work at all."""
        now = time.time()
        toks, reason = sampling_mod.scan_finish(
            snap.tokens[:req.max_new_tokens], req.sampling,
            req.max_new_tokens, self.detokenizer)
        req.generated = toks
        req.finish_reason = reason or sampling_mod.FINISH_LENGTH
        req.cache_hit = "replay"
        req.cached_tokens = len(req.prompt)
        req.prefill_tokens = 0
        req.t_first_token = now
        req.t_done = time.time()
        req.admit_step = req.retire_step = self.steps_executed
        self.prefix_cache.stats["snapshot_hits"] += 1
        self.prefix_cache.stats["tokens_reused"] += len(req.prompt)
        if self.tel.enabled:
            tel = self.tel
            tel.counter("requests_admitted_total", kind="replay",
                        help="Slot admissions by plan kind")
            tel.counter("snapshot_hit_tokens_total", len(req.prompt),
                        help="Prompt tokens served from CHAI snapshots")
            tel.event(req.uid, "admit", kind="replay", slot=-1,
                      step=self.steps_executed,
                      cached_tokens=int(req.cached_tokens))
            tel.event(req.uid, "first_token", t=req.t_first_token)
            tel.observe("request_ttft_seconds",
                        max(0.0, req.t_first_token - req.t_enqueue),
                        help="Enqueue-to-first-token latency")
            tel.counter("tokens_generated_total", len(toks),
                        help="Generated tokens emitted")
            tel.token(req.uid, n=len(toks), t=req.t_first_token)
        self._done(req)

    def _capture_snapshot(self, slot, req, pages):
        """Capture the slot's STEADY-entry state (membership, clustered K
        pages, dense V pages, warmup tokens) keyed by its full prompt.
        Full pages are shared (incref); the partial tail page — which the
        still-running slot keeps writing — is copied, copy-on-write.
        Skipped (not an error) when the pools cannot spare the copies."""
        from repro.serving.prefix_cache import ChaiSnapshot
        cache = self.prefix_cache
        key = tuple(int(t) for t in req.prompt)
        if cache.snapshot_for(key) is not None:
            return
        cfg, ps = self.cfg, self.ecfg.page_size
        share = cfg.chai.share_values
        warm = cfg.chai.warmup_tokens
        pos_steady = len(req.prompt) + warm
        p_full, rem = divmod(pos_steady, ps)
        dense_copies = 1 if (rem and not share) else 0
        chai_copies = (2 if share else 1) if rem else 0
        if not self._pool_space(dense_copies, chai_copies):
            return
        vg_pages, vc_pages = [], []
        if not share:
            vg_pages = list(pages["vg"][:p_full])
            self.dense_pool.incref(vg_pages)
        kc_pages = list(pages["kc"][:p_full])
        self.chai_pool.incref(kc_pages)
        if share:
            vc_pages = list(pages["vc"][:p_full])
            self.chai_pool.incref(vc_pages)
        if rem:
            if not share:
                [dst] = self.dense_pool.alloc(1)
                self._dev_state = self._copy_page["dense"](
                    self._dev_state, jnp.int32(pages["vg"][p_full]),
                    jnp.int32(dst))
                vg_pages.append(dst)
            [dst] = self.chai_pool.alloc(1)
            self._dev_state = self._copy_page["chai"](
                self._dev_state, jnp.int32(pages["kc"][p_full]),
                jnp.int32(dst))
            kc_pages.append(dst)
            if share:
                [dst] = self.chai_pool.alloc(1)
                self._dev_state = self._copy_page["chai"](
                    self._dev_state, jnp.int32(pages["vc"][p_full]),
                    jnp.int32(dst))
                vc_pages.append(dst)
        slot_ctx = {k: np.asarray(v[:, slot])
                    for k, v in self._dev_ctx.items()}
        cache.add_snapshot(ChaiSnapshot(
            prompt=key, pos=pos_steady,
            tokens=list(req.generated[:warm + 1]), ctx=slot_ctx,
            vg_pages=vg_pages, kc_pages=kc_pages, vc_pages=vc_pages))

    # -- KV tier ops (demote / promote / prefetch) -------------------------
    def _tel_tier_transition(self, frm: str, to: str, kind: str, n: int):
        """TierManager transition callback -> Prometheus counter."""
        if self.tel.enabled:
            self.tel.counter("tier_transitions_total", n,
                             help="KV page transitions between tiers",
                             **{"from": frm, "to": to})

    @staticmethod
    def _entry_device_pages(entry) -> dict:
        """Device pages an entry owns, keyed by pool key (kg/vg/kc/vc)."""
        if hasattr(entry, "kg_page"):  # radix node
            return {"kg": [entry.kg_page], "vg": [entry.vg_page]}
        out = {}
        if entry.vg_pages:
            out["vg"] = list(entry.vg_pages)
        if entry.kc_pages:
            out["kc"] = list(entry.kc_pages)
        if entry.vc_pages:
            out["vc"] = list(entry.vc_pages)
        return out

    def _demote_entry(self, entry) -> bool:
        """Move an unlocked prefix-cache entry's device pages to the host
        tier. Called by PrefixCache._evict_one under device pool pressure
        (the victim is already off the LRU). Returns False to fall back
        to a plain drop. Pages are gathered to host BEFORE the device
        refs are released, so a False return never loses data."""
        if self.tiers is None or self._dev_state is None:
            return False
        spec = self._fault("offload.out")
        if spec is not None and spec.mode != "corrupt":
            return False  # demotion declined -> plain drop
        refs = self._entry_device_pages(entry)
        need = {}
        for pk, pages in refs.items():
            kind = kv_tiers_mod.POOL_OF[pk]
            need[kind] = need.get(kind, 0) + len(pages)
        if not self.tiers.make_room(need):
            return False
        payloads = {}
        for pk, pages in refs.items():
            kind = kv_tiers_mod.POOL_OF[pk]
            fetch = self._fetch_page.get(kind)
            if fetch is None:
                return False
            payloads[pk] = [jax.device_get(
                fetch(self._dev_state, jnp.int32(p))) for p in pages]
        self.tiers.store_entry(entry, payloads)
        if spec is not None and spec.mode == "corrupt":
            # Damage the stored host copy AFTER the CRC stamp, so the
            # promotion path detects it (corrupt_arrays mutates the
            # payload dicts the host pool holds).
            tree = {pk: {str(j): p for j, p in enumerate(pl)}
                    for pk, pl in payloads.items()}
            faults_mod.corrupt_arrays(tree, seed=self.faults.seed)
        # Host copy is safe: release the device references.
        for pk, pages in refs.items():
            kind = kv_tiers_mod.POOL_OF[pk]
            pool = self.dense_pool if kind == "dense" else self.chai_pool
            pool.free(pages)
            self.tiers.record("hot", "host", kind, len(pages))
        if hasattr(entry, "vg_pages"):  # snapshot: page ids now live in
            entry.vg_pages = []         # entry.tier_pages
            entry.kc_pages = []
            entry.vc_pages = []
        return True

    def _promote_entry(self, entry, *, uid: int = -1) -> bool:
        """Bring a demoted entry back into fresh device pages. The caller
        must have verified device pool headroom (``_pool_space``) first.
        Returns False — with the entry DROPPED — on checksum mismatch, an
        injected ``offload.in`` fault, or a compressed entry when lossy
        promotion is off; the caller re-plans the request cold."""
        cache = self.prefix_cache
        t0 = time.perf_counter()
        frm = entry.tier
        if frm == kv_tiers_mod.TIER_COMP and not self.ecfg.lossy_promote:
            cache.drop_demoted(entry)
            return False
        failed = self._fault("offload.in", uid) is not None
        if not failed and not self.tiers.verify_entry(entry):
            self.offload_checksum_failures += 1
            failed = True
        if failed:
            cache.drop_demoted(entry)
            return False
        payloads = self.tiers.fetch_entry(entry)
        new_pages = {}
        for pk, pl in payloads.items():
            kind = kv_tiers_mod.POOL_OF[pk]
            pool = self.dense_pool if kind == "dense" else self.chai_pool
            pages = pool.alloc(len(pl))
            put = self._put_page[kind]
            for p, payload in zip(pages, pl):
                dev = {k: jnp.asarray(v) for k, v in payload.items()
                       if k in ("data", "scale")}
                self._dev_state = put(self._dev_state, jnp.int32(p), dev)
            new_pages[pk] = pages
        self.tiers.release_entry(entry)
        if hasattr(entry, "kg_page"):
            entry.kg_page = new_pages["kg"][0]
            entry.vg_page = new_pages["vg"][0]
            cache.stats["promoted_blocks"] += 1
        else:
            entry.vg_pages = new_pages.get("vg", [])
            entry.kc_pages = new_pages.get("kc", [])
            entry.vc_pages = new_pages.get("vc", [])
            cache.stats["promoted_snapshots"] += 1
        entry.tier = kv_tiers_mod.TIER_HOT
        entry.tier_crc = 0
        for pk, pages in new_pages.items():
            kind = kv_tiers_mod.POOL_OF[pk]
            self.tiers.record(frm, "hot", kind, len(pages))
        cache._lru_file(entry)  # no-op while the entry is locked
        if self.tel.enabled:
            self.tel.observe("promote_wait_seconds",
                             time.perf_counter() - t0,
                             help="Host->device promotion latency")
        return True

    def _queue_prefetch(self, req: Request):
        """At admission time, look up which demoted prefix-cache entries
        this prompt will hit and queue them for promotion ahead of the
        planning step (drained by ``_step_inner``)."""
        cache = self.prefix_cache
        targets = []
        snap = self._eligible_snapshot(req)
        if snap is not None and snap.tier != kv_tiers_mod.TIER_HOT:
            targets = [snap]
        else:
            matched = cache.match(req.prompt)
            targets = [m for m in matched
                       if m.tier != kv_tiers_mod.TIER_HOT]
        for e in targets:
            if id(e) in self._prefetch_ids or e.prefetched:
                continue
            self._prefetch_ids.add(id(e))
            self._prefetch_q.append(e)

    def _drain_prefetch(self, budget: int = 4):
        """Promote up to ``budget`` queued entries into free device pages.
        Never evicts to make room — if the pools are full the queue waits
        (the synchronous fallback in the planners still covers the hit)."""
        while self._prefetch_q and budget > 0:
            e = self._prefetch_q.popleft()
            self._prefetch_ids.discard(id(e))
            if (e.tier == kv_tiers_mod.TIER_HOT
                    or getattr(e, "evicted", False) or e.locks):
                continue
            if (e.tier == kv_tiers_mod.TIER_COMP
                    and not self.ecfg.lossy_promote):
                continue
            counts = self.tiers._entry_page_counts(e)
            dense_need = counts.get("dense", 0)
            chai_need = counts.get("chai", 0)
            if (self.dense_pool.counters()["free"] < dense_need
                    or (chai_need and self.chai_pool.counters()["free"]
                        < chai_need)):
                self._prefetch_q.appendleft(e)
                self._prefetch_ids.add(id(e))
                return
            if self._promote_entry(e):
                e.prefetched = True
            budget -= 1

    def _free_resume(self, req: Request):
        """Release the host-tier pages backing a preempted request's
        resume payload (quarantine / abort while swapped out)."""
        rs = req.resume_state
        if not rs or "tier_pages" not in rs or self.tiers is None:
            return
        for pk, pages in rs["tier_pages"].items():
            kind = kv_tiers_mod.POOL_OF[pk]
            self.tiers.free_pages(kind, pages)
            self.tiers.record("host", "gone", kind, len(pages))
        rs["tier_pages"] = {}

    # -- step internals ----------------------------------------------------
    def _admit(self, outs: List[StepOutput]) -> bool:
        """Fill free slots from the arrived FIFO prefix while the page
        budget covers prompt + generation headroom (prefix-cache hits
        alias shared pages and need fewer). When the head outranks a
        running request and the pools cannot cover it, preempt the
        lowest-priority slot and retry the plan. Returns True when the
        queue head had arrived but could not be planned (page-blocked)."""
        now = time.time()
        blocked = False
        while self.queue and self.queue[0].t_arrival <= now:
            head = self.queue[0]
            with self.tel.span("admit", step=self._span_step,
                               uid=head.uid):
                snap = self._eligible_snapshot(head)
                if snap is not None and \
                        head.max_new_tokens <= len(snap.tokens):
                    # Snapshot covers the whole request: serve it host-side
                    # without occupying a slot.
                    req = self.queue.popleft()
                    self._replay_request(req, snap)
                    outs.append(StepOutput(req.uid, list(req.generated), True,
                                           req.finish_reason))
                    continue
                free_slots = [i for i in range(self.ecfg.batch_slots)
                              if self._slot_req[i] is None]
                if not free_slots and not self._try_preempt(head):
                    break
                if not free_slots:      # preemption just freed a slot
                    continue
                try:
                    plan = self._plan_admission(head)
                except RequestError as err:
                    self._quarantine_queued(head, err, outs)
                    continue
                if plan is None:        # FIFO holds until pages free up
                    if not self._fault_blocked and self._try_preempt(head):
                        continue        # pages reclaimed — retry the plan
                    blocked = True
                    break
                i = free_slots[0]
                req = self.queue.popleft()
                resumed = bool(req.generated)
                gen0 = len(req.generated)
                hit0 = (req.cache_hit, req.cached_tokens, req.prefill_tokens)
                try:
                    self._admit_to_slot(i, req, plan)
                except SnapshotRestoreError:
                    # Recoverable: unwind the admission, drop the damaged
                    # snapshot, and re-plan the request cold next iteration
                    # (greedy tokens are unchanged — snapshot replay is a
                    # parity guarantee, not a correctness dependency).
                    self._abort_admission(i, req, gen0, hit0)
                    self.prefix_cache.drop_snapshot(plan["snapshot"])
                    self.queue.appendleft(req)
                    continue
                except RequestError as err:
                    self._abort_admission(i, req, gen0, hit0)
                    self._quarantine_queued(req, err, outs)
                    continue
                if req.generated and not req.t_first_token:
                    req.t_first_token = time.time()
                req.slot, req.admit_step = i, self.steps_executed
                self._slot_req[i] = req
                self._set_slot_sampling(i, req.sampling)
                if self.tel.enabled:
                    self._tel_admit(i, req, plan, resumed)
                if resumed:
                    continue    # tokens so far were already emitted/checked
                trunc, reason = sampling_mod.scan_finish(
                    req.generated, req.sampling, req.max_new_tokens,
                    self.detokenizer)
                if reason:
                    req.generated = trunc
                    self._retire_slot(i, reason)
                if req.generated or reason:
                    # Chunked admissions have no first token yet — their
                    # StepOutput comes from the final chunk.
                    outs.append(StepOutput(req.uid, list(req.generated),
                                           bool(reason), reason))
        return blocked

    def _admit_to_slot(self, i: int, req: Request, plan: dict):
        """Place ``req`` into free slot ``i`` according to ``plan``,
        mutating the device state and the slot bookkeeping."""
        self._slot_pages[i] = plan.get("pages", {})
        self._slot_locked[i] = plan.get("locked", [])
        if plan["kind"] == "snapshot":
            snap = plan["snapshot"]
            if self._fault("snapshot.restore", uid=req.uid) is not None:
                raise SnapshotRestoreError(
                    f"injected snapshot-restore failure for "
                    f"uid={req.uid}", uid=req.uid)
            st = self._dev_state
            for kind, src, dst in plan["copies"]:
                st = self._copy_page[kind](st, jnp.int32(src),
                                           jnp.int32(dst))
            null = self._page_vec([])
            st = self._restore_snapshot(
                st, jnp.int32(i), null,
                self._page_vec(self._slot_pages[i].get("vg", [])),
                self._page_vec(self._slot_pages[i].get("kc", [])),
                self._page_vec(self._slot_pages[i].get("vc", [])),
                jnp.int32(snap.pos))
            self._dev_state = st
            dev_ctx = {k: jnp.asarray(v) for k, v in snap.ctx.items()}
            self._dev_ctx = self._set_ctx(self._dev_ctx, dev_ctx,
                                          jnp.int32(i))
            self._ctx_version += 1
            req.generated.extend(snap.tokens)
            req.cache_hit = "snapshot"
            req.cached_tokens = len(req.prompt)
            req.prefill_tokens = 0
            self._phases[i] = chai_cache.PHASE_STEADY
            self._slot_count[i] = len(snap.tokens)
            self.prefix_cache.stats["snapshot_hits"] += 1
            self.prefix_cache.stats["tokens_reused"] += len(req.prompt)
            self._next_tok[i] = snap.tokens[-1]
            self._tok_dirty = True
            if self.tel.enabled:
                self.tel.event(req.uid, "phase", phase="STEADY", slot=i)
            return
        if plan["kind"] == "swap":
            self._swap_in_slot(i, req)
            return
        self._phases[i] = chai_cache.PHASE_PREFILL
        if self._fault("kernel.prefill", uid=req.uid) is not None:
            raise QuarantineError(
                f"injected prefill-kernel failure for uid={req.uid}",
                uid=req.uid)
        if self.tel.enabled:
            self.tel.event(req.uid, "phase", phase="PREFILL", slot=i)
        prompt = req.prompt
        if plan["kind"] == "prefix":
            pre = plan["prefix_len"]
            req.cache_hit = "prefix"
            req.cached_tokens = pre
            req.prefill_tokens = len(prompt) - pre
            self.prefix_cache.stats["partial_hits"] += 1
            self.prefix_cache.stats["tokens_reused"] += pre
            self.prefix_cache.stats["tokens_prefilled"] += \
                req.prefill_tokens
        else:
            pre = 0
            req.prefill_tokens = len(prompt)
            if self.prefix_cache is not None:
                self.prefix_cache.stats["misses"] += 1
                self.prefix_cache.stats["tokens_prefilled"] += len(prompt)
        if self._chunk and len(prompt) - pre > self._chunk:
            # Chunked prefill: run the first chunk now; step() advances
            # one chunk per iteration until _finish_prefill fires.
            self._slot_prefill_state[i] = {"req": req, "tokens": prompt,
                                           "cursor": pre}
            self._advance_chunk(i)
            return
        if plan["kind"] == "prefix":
            toks, true_len = self._padded_suffix(prompt[pre:], pre)
            fn = self._suffix_prefill_fn(toks.shape[1])
            logits, st = fn(
                self.params, toks, true_len, jnp.int32(pre),
                self._dev_state, jnp.int32(i),
                self._page_vec(plan["scatter_kg"]),
                self._page_vec(plan["scatter_vg"]),
                self._page_vec(self._slot_pages[i]["kg"]),
                self._page_vec(self._slot_pages[i]["vg"]))
        else:
            toks, true_len = self._padded_prompt(prompt)
            prefill = self._slot_prefill_fn(toks.shape[1])
            # A model with no pools holds no pages: null page vectors.
            logits, st = prefill(
                self.params, toks, true_len, self._dev_state, jnp.int32(i),
                self._page_vec(self._slot_pages[i].get("kg", [])),
                self._page_vec(self._slot_pages[i].get("vg", [])))
        self._dev_state = st
        self._finish_prefill(i, req, logits)

    def _advance_prefills(self, outs: List[StepOutput]):
        """Forward ONE page-aligned chunk for every mid-prefill slot —
        chunked prefill's per-step progress, interleaved with the batched
        decode of the other slots. A slot whose final chunk completes
        enters WARMUP and emits its first token here."""
        for i in range(self.ecfg.batch_slots):
            st = self._slot_prefill_state[i]
            if st is None:
                continue
            req = st["req"]
            with self.tel.span("prefill.advance", step=self._span_step,
                               uid=req.uid):
                self._advance_chunk(i)
                if self._slot_prefill_state[i] is not None:
                    continue                # more chunks to go
                # final chunk fired: the first token was just sampled
                reason = self._finish_of(req)
                if reason:
                    self._retire_slot(i, reason)
                outs.append(StepOutput(req.uid, [req.generated[-1]],
                                       bool(reason), reason))

    def _advance_chunk(self, i: int):
        """Prefill the next chunk of slot ``i``'s pending tokens. Chunk
        starts are page-aligned (the chunk size is a page multiple and
        radix-aliased prefixes are whole pages), so each chunk's scatter
        touches exactly its own page range; intermediate chunks park the
        device phase at FREE so the interleaved decode skips the slot."""
        st = self._slot_prefill_state[i]
        eff, cur = st["tokens"], st["cursor"]
        end = min(cur + self._chunk, len(eff))
        final = end == len(eff)
        toks, true_len = self._padded_suffix(eff[cur:end], cur)
        ps = self.ecfg.page_size
        lo, hi = cur // ps, chai_cache.pages_needed(end, ps)
        pages = self._slot_pages[i]

        def scatter(page_list):
            return [p if lo <= j < hi else chai_cache.NULL_PAGE
                    for j, p in enumerate(page_list)]

        fn = self._chunk_prefill_fn(toks.shape[1])
        phase = (chai_cache.PHASE_WARMUP if final
                 else chai_cache.PHASE_FREE)
        logits, self._dev_state = fn(
            self.params, toks, true_len, jnp.int32(cur),
            self._dev_state, jnp.int32(i),
            self._page_vec(scatter(pages["kg"])),
            self._page_vec(scatter(pages["vg"])),
            self._page_vec(pages["kg"]),
            self._page_vec(pages["vg"]),
            jnp.int32(phase))
        st["cursor"] = end
        if final:
            self._slot_prefill_state[i] = None
            self._finish_prefill(i, st["req"], logits)

    def _finish_prefill(self, i: int, req: Request, logits):
        """Prefill completed (monolithic, or a chunked prefill's final
        chunk): index the prompt into the prefix cache, enter WARMUP, and
        sample the request's first token."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.prompt,
                                     self._slot_pages[i]["kg"],
                                     self._slot_pages[i]["vg"])
        self._phases[i] = chai_cache.PHASE_WARMUP
        self._slot_count[i] = 1
        tok = self._sample_first(logits, req)
        req.generated.append(tok)
        first = not req.t_first_token
        if first:
            req.t_first_token = time.time()
        self._next_tok[i] = tok
        self._tok_dirty = True
        if self.tel.enabled:
            tel = self.tel
            tel.event(req.uid, "phase", phase="WARMUP", slot=i)
            if req.prefill_tokens > 0:
                tel.counter("prefill_tokens_total", req.prefill_tokens,
                            help="Prompt tokens actually forwarded")
            if first:
                tel.event(req.uid, "first_token", t=req.t_first_token)
                tel.observe("request_ttft_seconds",
                            max(0.0, req.t_first_token - req.t_enqueue),
                            help="Enqueue-to-first-token latency")
            tel.counter("tokens_generated_total",
                        help="Generated tokens emitted")
            tel.token(req.uid, t=req.t_first_token if first else None)

    # -- priority preemption -----------------------------------------------
    def _swap_in_slot(self, i: int, req: Request):
        """Resume a preempted request: upload its saved per-slot columns
        and page contents into the freshly allocated pages, rebuild the
        block tables, and restore its CHAI membership — the slot decodes
        on bitwise the state it was evicted with. Integrity: the payload
        carries the CRC32 stamped at swap-out; a mismatch (host-side
        corruption) quarantines the request BEFORE any device mutation."""
        resume = req.resume_state
        if self._fault("swap.in", uid=req.uid) is not None:
            raise QuarantineError(
                f"injected swap-in failure for uid={req.uid}", uid=req.uid)
        if self._fault("offload.in", uid=req.uid) is not None:
            raise QuarantineError(
                f"injected host-tier fetch failure for uid={req.uid}",
                uid=req.uid)
        tier_payloads = {
            k: self.tiers.fetch_pages(kv_tiers_mod.POOL_OF[k], pg)
            for k, pg in resume["tier_pages"].items()}
        tree = {k: {str(j): p for j, p in enumerate(pl)}
                for k, pl in tier_payloads.items()}
        crc = resume.get("crc")
        if crc is not None and faults_mod.checksum_arrays(
                {"cols": resume["cols"], "pools": tree}) != crc:
            self.swap_checksum_failures += 1
            raise QuarantineError(
                f"swap-in checksum mismatch for uid={req.uid}: the "
                "host-side resume payload was corrupted while swapped "
                "out", uid=req.uid)
        # Rebuild the padded pool upload from the per-page host copies.
        pools_np = {k: np.zeros(shape, dtype)
                    for k, (shape, dtype) in resume["pool_meta"].items()}
        for k, pl in tier_payloads.items():
            sk = k + "_scale"
            for j, p in enumerate(pl):
                pools_np[k][:, j] = p["data"]
                if "scale" in p and sk in pools_np:
                    pools_np[sk][:, j] = p["scale"]
        for k, pg in resume["tier_pages"].items():
            kind = kv_tiers_mod.POOL_OF[k]
            self.tiers.free_pages(kind, pg)
            self.tiers.record("host", "hot", kind, len(pg))
        resume["tier_pages"] = {}
        req.resume_state = None
        pages = self._slot_pages[i]
        vecs = [self._page_vec(pages.get(k, []))
                for k in ("kg", "vg", "kc", "vc")]
        _, swap_in = self._swap_fns_get()
        cols = {k: jnp.asarray(v) for k, v in resume["cols"].items()}
        pools = {k: jnp.asarray(v) for k, v in pools_np.items()}
        self._dev_state = swap_in(self._dev_state, jnp.int32(i), cols,
                                  pools, *vecs, *vecs)
        if self.chai_on:
            dev_ctx = {k: jnp.asarray(v) for k, v in resume["ctx"].items()}
            self._dev_ctx = self._set_ctx(self._dev_ctx, dev_ctx,
                                          jnp.int32(i))
            self._ctx_version += 1
        self._phases[i] = resume["phase"]
        self._slot_count[i] = resume["count"]
        self._next_tok[i] = req.generated[-1]
        self._tok_dirty = True
        self._record_kv_bytes(self._phases)

    def _try_preempt(self, head: Request) -> bool:
        """Reclaim the lowest-priority running slot that ``head``
        strictly outranks (ties never preempt). Returns True when a slot
        was preempted — the caller retries its admission plan."""
        if not self.ecfg.preemption or self.tiers is None:
            return False    # no pools: nothing to swap a victim through
        victims = [i for i in range(self.ecfg.batch_slots)
                   if self._slot_req[i] is not None
                   and self._slot_req[i].priority < head.priority]
        if not victims:
            return False
        # Lowest priority first; among equals the most recent admission
        # loses (least progress thrown away).
        i = min(victims, key=lambda j: (self._slot_req[j].priority,
                                        -self._slot_req[j].admit_step))
        if int(self._phases[i]) != chai_cache.PHASE_PREFILL:
            # The victim's payload lands in the host tier: make room
            # there first (compressing / dropping demoted cache entries
            # LRU-first). No room -> no preemption this step.
            pages = self._slot_pages[i]
            need = {}
            d = len(pages.get("kg", ())) + len(pages.get("vg", ()))
            c = len(pages.get("kc", ())) + len(pages.get("vc", ()))
            if d:
                need["dense"] = d
            if c:
                need["chai"] = c
            if need and not self.tiers.make_room(need):
                return False
        self._preempt_slot(i)
        return True

    def _preempt_slot(self, i: int):
        """Evict slot ``i`` WITHOUT finishing its request: swap its KV to
        the host (per-slot state columns + page contents — generated
        tokens stay on the Request), free every page refcount-exactly via
        the abort path's mechanics, and re-queue the request right behind
        the current queue head. A mid-PREFILL victim has no decode state
        worth saving and simply restarts its prefill. The victim's pages
        are indexed into the prefix cache first, so — if they survive the
        preemptor's own allocation — OTHER requests sharing the prompt
        prefix can still alias them."""
        r = self._slot_req[i]
        phase = int(self._phases[i])
        if phase != chai_cache.PHASE_PREFILL:
            pages = self._slot_pages[i]
            vecs = [self._page_vec(pages.get(k, []))
                    for k in ("kg", "vg", "kc", "vc")]
            swap_out, _ = self._swap_fns_get()
            with self.tel.span("preempt.swap", step=self._span_step,
                               uid=r.uid, slot=i):
                cols, pools = swap_out(self._dev_state, jnp.int32(i),
                                       *vecs)
            pools_host = jax.device_get(pools)
            npages = {k: len(pages.get(k, ()))
                      for k in ("kg", "vg", "kc", "vc")}
            # Split the padded pool gathers into per-real-page payloads
            # (copies, so the big padded arrays are released) — these go
            # into the SAME host page pool prefix-cache demotion uses.
            payloads = {}
            for k in ("kg", "vg", "kc", "vc"):
                if k not in pools_host or not npages[k]:
                    continue
                sk = k + "_scale"
                pl = []
                for j in range(npages[k]):
                    p = {"data": np.array(pools_host[k][:, j])}
                    if sk in pools_host:
                        p["scale"] = np.array(pools_host[sk][:, j])
                    pl.append(p)
                payloads[k] = pl
            resume = {
                "phase": phase, "count": self._slot_count[i],
                "cols": jax.device_get(cols),
                "npages": npages,
                # Padded shapes/dtypes to rebuild the swap-in upload.
                "pool_meta": {k: (v.shape, v.dtype)
                              for k, v in pools_host.items()},
            }
            if self.chai_on:
                resume["ctx"] = {k: np.asarray(v[:, i])
                                 for k, v in self._dev_ctx.items()}
            # Integrity stamp: swap-in verifies this before touching the
            # device, so host-side damage to the payload quarantines the
            # request instead of restoring corrupted KV.
            tree = {k: {str(j): p for j, p in enumerate(pl)}
                    for k, pl in payloads.items()}
            resume["crc"] = faults_mod.checksum_arrays(
                {"cols": resume["cols"], "pools": tree})
            if self._fault("swap.corrupt", uid=r.uid) is not None:
                faults_mod.corrupt_arrays(tree, seed=self.faults.seed)
            tier_pages = {}
            for k, pl in payloads.items():
                kind = kv_tiers_mod.POOL_OF[k]
                tier_pages[k] = self.tiers.store_pages(kind, pl)
                self.tiers.record("hot", "host", kind, len(pl))
            resume["tier_pages"] = tier_pages
            r.resume_state = resume
            if self.prefix_cache is not None:
                self._index_retired(r, self._slot_pages[i])
        r.preemptions += 1
        self.preemptions += 1
        self._slot_prefill_state[i] = None
        self._slot_req[i] = None
        self._phases[i] = chai_cache.PHASE_FREE
        self._slot_count[i] = 0
        self._dev_state = self._reset_slot(self._dev_state, jnp.int32(i))
        self._free_pages(self._slot_pages[i])
        if self._slot_locked[i]:
            self.prefix_cache.unlock(self._slot_locked[i])
            self._slot_locked[i] = []
        self._samp_host["temperature"][i] = 0.0
        self._samp_dirty = True
        self.queue.insert(min(1, len(self.queue)), r)
        if self.tel.enabled:
            self.tel.counter("preemptions_total",
                             help="Slots reclaimed for a higher-priority "
                                  "arrival")
            self.tel.event(r.uid, "preempt", slot=i,
                           phase=_PHASE_NAMES.get(phase, str(phase)),
                           step=self.steps_executed)

    def _cluster_transitions(self, active, outs: List[StepOutput]):
        """CLUSTER + compact slots whose warmup just completed; the
        slot's dense K pages return to the pool here. An injected
        ``kernel.cluster`` fault quarantines the transitioning request
        BEFORE clustering mutates the pools (``outs`` receives its typed
        StepOutput); other slots keep decoding."""
        if not self.chai_on:
            return
        cfg = self.cfg
        warm = cfg.chai.warmup_tokens
        for i in active:
            if not (self._slot_count[i] == warm + 1
                    and self._phases[i] == chai_cache.PHASE_WARMUP):
                continue
            req = self._slot_req[i]
            with self.tel.span("cluster", step=self._span_step,
                               uid=req.uid):
                if self._fault("kernel.cluster", uid=req.uid) is not None:
                    self._retire_slot(
                        i, sampling_mod.FINISH_ERROR, index=False,
                        error=f"injected cluster-transition failure for "
                              f"uid={req.uid}")
                    outs.append(StepOutput(req.uid, [], True,
                                           sampling_mod.FINISH_ERROR))
                    continue
                self._phases[i] = chai_cache.PHASE_CLUSTER
                self.cluster_transitions += 1
                if self.tel.enabled:
                    self.tel.counter("cluster_transitions_total",
                                     help="WARMUP->CLUSTER->STEADY "
                                          "transitions executed")
                    self.tel.event(req.uid, "phase", phase="CLUSTER", slot=i)
                kc_vec = self._page_vec(self._slot_pages[i].get("kc", []))
                vc_vec = self._page_vec(self._slot_pages[i].get("vc", []))
                self._dev_state, self._dev_ctx = self._cluster_fn()(
                    self._dev_state, self._dev_ctx, jnp.int32(i),
                    kc_vec, vc_vec)
                self._ctx_version += 1
                if (self.prefix_cache is not None
                        and self.chai_clustered
                        and self._slot_req[i].sampling.greedy):
                    self._capture_snapshot(i, self._slot_req[i],
                                           self._slot_pages[i])
                if self.chai_clustered:
                    freed = len(self._slot_pages[i]["kg"])
                    self.dense_pool.free(self._slot_pages[i].pop("kg"))
                    if cfg.chai.share_values:
                        freed += len(self._slot_pages[i]["vg"])
                        self.dense_pool.free(self._slot_pages[i].pop("vg"))
                    if self.tel.enabled:
                        self.tel.counter(
                            "chai_dense_pages_freed_total", freed,
                            help="Dense pages freed at compaction (the "
                                 "paper's KV saving, realized)")
                self._record_kv_bytes(self._phases)
                self._phases[i] = chai_cache.PHASE_STEADY
                if self.tel.enabled:
                    self.tel.event(req.uid, "phase", phase="STEADY", slot=i)
                    self._tel_clusters(i)

    # -- shared-prefix relay decode ----------------------------------------
    def _ctx_host(self):
        """np mirror of the clustering context, rebuilt only when the
        ctx version moved (CLUSTER transition / snapshot restore /
        preemption swap-in)."""
        if (self._ctx_host_cache is None
                or self._ctx_host_cache[0] != self._ctx_version):
            self._ctx_host_cache = (
                self._ctx_version,
                {k: np.asarray(v) for k, v in self._dev_ctx.items()})
        return self._ctx_host_cache[1]

    def _pack_prefix_fn(self, n_pages):
        """Jit that copies ``n_pages`` dense-pool prefix pages into a
        contiguous ``(nG, rows, n_pages*page, hd)`` resident view (+ int8
        scale planes). A copy, not an alias: relay steps donate the
        state, and cached views must survive the buffer reuse."""
        fn = self._pack_prefix.get(n_pages)
        if fn is None:
            def pack(state, kg, vg):
                def view(bt):
                    g = state["kvp"][:, bt]     # (nG, p0, rows, page, hd)
                    ng, p, rows, page, hd = g.shape
                    return (g.transpose(0, 2, 1, 3, 4)
                            .reshape(ng, rows, p * page, hd))
                out = {"k": view(kg), "v": view(vg)}
                if state.get("kvp_scale") is not None:
                    def sview(bt):
                        sg = state["kvp_scale"][:, bt]
                        ng, p, rows, page = sg.shape
                        return (sg.transpose(0, 2, 1, 3)
                                .reshape(ng, rows, p * page))
                    out["k_scale"] = sview(kg)
                    out["v_scale"] = sview(vg)
                return out
            fn = jax.jit(pack)
            self._pack_prefix[n_pages] = fn
        return fn

    def _resident_view(self, chain):
        """Packed resident copy of a radix chain's shared pages, cached
        on the deepest node and keyed by the chain's page identity.
        Prefix pages are immutable while cached (COW re-plans divergent
        writers onto fresh pages; eviction flips ``node.evicted`` and
        drops ``node.resident``), so the cache survives across steps."""
        node = chain[-1]
        key = (tuple(n.kg_page for n in chain),
               tuple(n.vg_page for n in chain))
        if node.resident is None or node.resident[0] != key:
            fn = self._pack_prefix_fn(len(chain))
            node.resident = (key, fn(self._dev_state,
                                     jnp.asarray(key[0], jnp.int32),
                                     jnp.asarray(key[1], jnp.int32)))
        return node.resident[1]

    def _relay_row_maps(self, groups, nmax):
        """Per-layer kernel row maps for the grouped prefix pass (see
        ``repro.core.chai_attention._relay_prefix_state`` for the layout
        contract). Host numpy, cached per (ctx version, membership):
        padded member entries keep index 0 — their rows compute garbage
        the per-slot scatter discards."""
        key = (self._ctx_version,
               tuple((id(g["node"]), tuple(g["members"])) for g in groups))
        if (self._relay_rows_cache is not None
                and self._relay_rows_cache[0] == key):
            return self._relay_rows_cache[1]
        ctx = self._ctx_host()
        cfg = self.cfg
        G = len(groups)
        if cfg.is_mha:
            reps, h2c = ctx["reps"], ctx["h2c"]   # (nA,B,R), (nA,B,H)
            nA, _, R = reps.shape
            H = h2c.shape[-1]
            share = cfg.chai.share_values
            A = nmax * (R if share else H)
            k_row = np.zeros((nA, G, nmax * R), np.int32)
            a_row = np.zeros((nA, G, A), np.int32)
            v_row = np.zeros((nA, G, A), np.int32)
            for g, grp in enumerate(groups):
                for j, slot in enumerate(grp["members"]):
                    # Prefix K = the slot's rep rows gathered from the
                    # chain's DENSE pages (bitwise == the clustered rows
                    # the suffix pass reads: compaction is a gather).
                    k_row[:, g, j * R:(j + 1) * R] = reps[:, slot]
                    if share:
                        # share_values: acc stays per-rep; V gathers the
                        # rep's dense row (scale-less under int8 — the
                        # codes were moved into cp, not requantized).
                        a_row[:, g, j * R:(j + 1) * R] = \
                            j * R + np.arange(R, dtype=np.int32)
                        v_row[:, g, j * R:(j + 1) * R] = reps[:, slot]
                    else:
                        a_row[:, g, j * H:(j + 1) * H] = \
                            j * R + h2c[:, slot]
                        v_row[:, g, j * H:(j + 1) * H] = \
                            np.arange(H, dtype=np.int32)
        else:
            reps = ctx["reps"]                  # (nA, B, KV, r)
            cluster_of = ctx["cluster_of"]      # (nA, B, KV, qpk)
            nA, _, n_kv, r = reps.shape
            qpk = cluster_of.shape[-1]
            H = n_kv * qpk
            rt = n_kv * r
            k_row = np.zeros((nA, G, nmax * rt), np.int32)
            a_row = np.zeros((nA, G, nmax * H), np.int32)
            v_row = np.zeros((nA, G, nmax * H), np.int32)
            kv_of_rep = np.repeat(np.arange(n_kv, dtype=np.int32), r)
            kv_of_head = np.repeat(np.arange(n_kv, dtype=np.int32), qpk)
            for g, grp in enumerate(groups):
                for j, slot in enumerate(grp["members"]):
                    k_row[:, g, j * rt:(j + 1) * rt] = kv_of_rep
                    h2c_flat = (np.arange(n_kv, dtype=np.int32)
                                [None, :, None] * r
                                + cluster_of[:, slot]).reshape(nA, H)
                    a_row[:, g, j * H:(j + 1) * H] = j * rt + h2c_flat
                    v_row[:, g, j * H:(j + 1) * H] = kv_of_head
        maps = {"k_row": jnp.asarray(k_row), "a_row": jnp.asarray(a_row),
                "v_row": jnp.asarray(v_row)}
        self._relay_rows_cache = (key, maps)
        return maps

    def _build_relay(self, active):
        """Form shared-prefix relay groups over the STEADY slots.

        Slots admitted through the radix prefix cache keep their matched
        chain pinned in ``_slot_locked``; each slot picks the DEEPEST
        chain node shared by >= ``relay_min_group`` eligible slots, and
        slots that picked the same node form one group. Returns the
        relay dict consumed by ``make_relay_step`` (``None`` -> plain
        phase-mix dispatch): group-batched resident prefix views + row
        maps + per-slot scatter coords. Non-grouped slots ride along
        with ``in_group=False`` / ``len=0`` — the merge identity keeps
        them bitwise-identical to the non-relay path."""
        from repro.serving.prefix_cache import BlockNode
        if not chai_mod.USE_FUSED_DECODE or self.degraded_decode:
            return None       # jnp fallback attends full tables already
        min_g = max(1, self.ecfg.relay_min_group)
        chains = {}
        for i in active:
            if self._phases[i] != chai_cache.PHASE_STEADY:
                continue
            locked = self._slot_locked[i]
            if not locked or not all(isinstance(e, BlockNode)
                                     for e in locked):
                continue      # snapshot pins / no radix plan
            if any(e.evicted for e in locked):
                continue      # chain lost pages since admission
            chains[i] = locked
        if len(chains) < min_g:
            return None
        counts: dict = {}
        for chain in chains.values():
            for node in chain:
                counts[id(node)] = counts.get(id(node), 0) + 1
        by_node: dict = {}
        for i, chain in sorted(chains.items()):
            pick = None
            for depth, node in enumerate(chain, start=1):
                if counts[id(node)] >= min_g:
                    pick = (node, depth)        # deepest wins
            if pick is None:
                continue
            node, depth = pick
            grp = by_node.setdefault(
                id(node), {"node": node, "depth": depth, "members": []})
            grp["members"].append(i)
        groups = [g for g in by_node.values()
                  if len(g["members"]) >= min_g]
        if not groups:
            return None
        if self._fault("relay.residency") is not None:
            # Dissolve the groups formed this step to the per-request
            # decode path — grouped-vs-ungrouped is token-identical, so
            # dissolving is always safe.
            self.relay_dissolved += 1
            if self.tel.enabled:
                self.tel.counter("relay_dissolved_total",
                                 help="Relay groups dissolved by an "
                                      "injected residency fault")
            return None
        ps = self.ecfg.page_size
        b = self.ecfg.batch_slots
        nmax = max(len(g["members"]) for g in groups)
        packs = [self._resident_view(chains[g["members"][0]][:g["depth"]])
                 for g in groups]
        sp_max = max(p["k"].shape[2] for p in packs)

        def stack(name):
            arrs = []
            for p in packs:
                a = p[name]
                pad = sp_max - a.shape[2]
                if pad:     # zero tail; plen masks it in the kernel
                    widths = [(0, 0)] * a.ndim
                    widths[2] = (0, pad)
                    a = jnp.pad(a, widths)
                arrs.append(a)
            return jnp.stack(arrs, axis=1)

        relay = {"k": stack("k"), "v": stack("v")}
        if "k_scale" in packs[0]:
            relay["k_scale"] = stack("k_scale")
            relay["v_scale"] = stack("v_scale")
        members = np.zeros((len(groups), nmax), np.int32)
        plen_g = np.zeros((len(groups),), np.int32)
        gid = np.zeros((b,), np.int32)
        midx = np.zeros((b,), np.int32)
        plen_b = np.zeros((b,), np.int32)
        ing = np.zeros((b,), bool)
        for g, grp in enumerate(groups):
            plen_g[g] = grp["depth"] * ps
            for j, slot in enumerate(grp["members"]):
                members[g, j] = slot
                gid[slot] = g
                midx[slot] = j
                plen_b[slot] = plen_g[g]
                ing[slot] = True
        relay.update(self._relay_row_maps(groups, nmax))
        relay.update({
            "plen": jnp.asarray(plen_g), "members": jnp.asarray(members),
            "gid": jnp.asarray(gid), "midx": jnp.asarray(midx),
            "len": jnp.asarray(plen_b), "in_group": jnp.asarray(ing)})
        self.relay_grouped_slots += int(ing.sum())
        if self.tel.enabled:
            self.tel.counter("relay_groups_formed_total", len(groups),
                             help="Shared-prefix relay groups formed")
            self.tel.counter("relay_grouped_slots_total", int(ing.sum()),
                             help="Slot-steps decoded through a relay "
                                  "group")
        return relay

    def _decode(self, active) -> List[StepOutput]:
        """One batched decode step; host-dispatch the cheapest jit that
        covers the current phase mix, then one batched sample. The token
        and SamplingParams vectors live on device between steps; host
        mirrors are re-uploaded only after an admission/retire edited
        them."""
        outs: List[StepOutput] = []
        tel = self.tel
        step_no = self._span_step
        b = self.ecfg.batch_slots
        occupied = self._phases[self._phases != chai_cache.PHASE_FREE]
        with tel.span("relay.form", step=step_no):
            relay = self._build_relay(active) if self.relay_decode \
                else None
        self._decode_fault_hit = False
        program = self._decode_program(relay, occupied)
        try:
            with tel.span("decode.dispatch", step=step_no, program=program,
                          degraded=self.degraded_decode):
                if self._tok_dirty:
                    self._next_tok_dev = jnp.asarray(self._next_tok)
                    self._tok_dirty = False
                inputs = {"tokens": self._next_tok_dev}
                logits, state = self._dispatch_decode(program, inputs,
                                                      relay)
        except EngineFault:
            raise
        except InjectedFault:
            # Injected kernel.decode fault (raised before any jit runs, so
            # the donated device state is intact): fall back to the jnp
            # reference jits and retry the step (``decode_heal_steps`` can
            # revert later). A real failure of the fused path is NOT
            # masked: it raises EngineFault below, so no run ever reports
            # numbers for a decode path other than the kernel's.
            self.degraded_decode = True
            self.decode_fallbacks += 1
            self._heal_clean = 0
            if tel.enabled:
                tel.counter("decode_fallbacks_total",
                            help="Fused-decode failures survived via the "
                                 "jnp reference fallback")
                tel.gauge("engine_degraded_decode", 1,
                          help="1 while decode runs the jnp reference "
                               "fallback")
            program = self._decode_program(None, occupied)
            try:
                with tel.span("decode.dispatch", step=step_no,
                              program=program, degraded=True, retry=True):
                    logits, state = self._dispatch_decode(program, inputs,
                                                          None)
            except Exception as err2:
                raise EngineFault(
                    "decode failed on the jnp reference fallback after "
                    f"an injected kernel.decode fault: {err2!r}") from err2
        except Exception as err:
            raise EngineFault(
                f"decode failed on the fused kernel path: {err!r}") from err
        else:
            if tel.enabled and not self.degraded_decode:
                self._count_decode_tiles(program, active)
            if self.degraded_decode and self.ecfg.decode_heal_steps > 0:
                self._maybe_heal()
        if self.faults is not None:
            for i in active:
                if self._fault("step.logits",
                               uid=self._slot_req[i].uid) is not None:
                    logits = logits.at[i].set(jnp.nan)
        with tel.span("sample", step=step_no):
            finite = np.asarray(self._finite_rows(logits))
            self._dev_state = state
            temps = self._samp_host["temperature"]
            if not temps.any():
                tok_dev = self._argmax(logits)      # all-greedy fast path
            else:
                counts = np.zeros((b,), np.int32)
                for i in active:
                    counts[i] = len(self._slot_req[i].generated)
                rows = np.nonzero(temps > 0.0)[0]
                nb = 1 << (len(rows) - 1).bit_length()
                if nb < b:
                    # Mixed batch: greedy slots skip the sampling lane
                    # (argsort + softmax + PRNG) entirely — the sampler runs
                    # on a gathered power-of-two sub-batch of the sampling
                    # rows, scattered over the batched argmax. Bitwise-
                    # identical to the full sampler: each row's draw depends
                    # only on that row's (logits, params, seed, count), and
                    # greedy rows argmax the same raw f32 logits either way.
                    idx = np.full((nb,), rows[0], np.int32)   # pad: dup row0
                    idx[:len(rows)] = rows
                    idx_dev = jnp.asarray(idx)
                    drawn = self._sampler(
                        self._take_rows(logits, idx_dev),
                        jnp.asarray(temps[idx]),
                        jnp.asarray(self._samp_host["top_k"][idx]),
                        jnp.asarray(self._samp_host["top_p"][idx]),
                        jnp.asarray(self._samp_host["seed"][idx]),
                        jnp.asarray(counts[idx]))
                    tok_dev = self._put_rows(self._argmax(logits), idx_dev,
                                             drawn)
                else:
                    if self._samp_dirty:
                        self._samp_dev = {k: jnp.asarray(v)
                                          for k, v in self._samp_host.items()}
                        self._samp_dirty = False
                    tok_dev = self._sampler(logits,
                                            self._samp_dev["temperature"],
                                            self._samp_dev["top_k"],
                                            self._samp_dev["top_p"],
                                            self._samp_dev["seed"],
                                            jnp.asarray(counts))
            self._next_tok_dev = tok_dev
            toks = np.asarray(tok_dev)
            self._next_tok[:] = toks
        self.steps_executed += 1
        with tel.span("retire", step=step_no):
            for i in active:
                r = self._slot_req[i]
                if not finite[i]:
                    # NaN/Inf logits: the slot's sampled token is garbage —
                    # quarantine this request; rows are independent, so the
                    # other slots' draws are exactly what they would have
                    # been.
                    self._retire_slot(
                        i, sampling_mod.FINISH_ERROR, index=False,
                        error=f"non-finite logits for uid={r.uid} at step "
                              f"{self.steps_executed - 1}")
                    outs.append(StepOutput(r.uid, [], True,
                                           sampling_mod.FINISH_ERROR))
                    continue
                r.generated.append(int(toks[i]))
                self._slot_count[i] += 1
                if tel.enabled:
                    tel.counter("tokens_generated_total",
                                help="Generated tokens emitted")
                    tel.token(r.uid)
                reason = self._finish_of(r)
                if reason:
                    self._retire_slot(i, reason)
                outs.append(StepOutput(r.uid, [int(toks[i])], bool(reason),
                                       reason))
        with tel.span("kv.account", step=step_no):
            self._record_kv_bytes(self._phases)
        return outs

    def _count_decode_tiles(self, program, active):
        """Registry counters ``decode_attn_tiles_walked`` / ``_live``: the
        S-tiles of one launch of the fused paged CHAI decode kernel this
        step (every attention layer walks the same tiles). Walked is the
        kernel's grid, every slot times the tiles of ``max_seq``; live are
        the tiles it copies and computes, those up to each slot's
        position. A decoding slot's position is its request's length less
        one; any other slot counts its first tile only. Relay steps count
        the whole sequence, though their suffix pass starts past the
        shared prefix."""
        if self.dense_pool is None or not self.chai_on or program == "mha":
            return      # no fused paged decode kernel ran
        ts, n_tiles = chai_mod.paged_decode_tiling(self.cfg,
                                                   self._dev_state)
        pos = np.zeros((self.ecfg.batch_slots,), np.int64)
        for i in active:
            r = self._slot_req[i]
            pos[i] = len(r.prompt) + len(r.generated) - 1
        live = (pos // ts + 1).sum()
        self.tel.counter("decode_attn_tiles_walked", pos.size * n_tiles,
                         help="S-tiles in the fused paged decode kernel's "
                              "grid, per step")
        self.tel.counter("decode_attn_tiles_live", int(live),
                         help="S-tiles the fused paged decode kernel "
                              "copies and computes, per step")

    def _decode_program(self, relay, occupied) -> str:
        """The cheapest decode program covering the phase mix of the
        ``occupied`` slots: ``relay``, ``chai`` (all STEADY), ``mha`` (all
        WARMUP, or CHAI off) or ``mixed``."""
        if relay is not None:
            return "relay"
        if not self.chai_on:
            return "mha"
        if (occupied == chai_cache.PHASE_STEADY).all():
            return "chai"
        if (occupied == chai_cache.PHASE_WARMUP).all():
            return "mha"
        return "mixed"

    def _dispatch_decode(self, program, inputs, relay):
        """Host-dispatch the step jit ``program`` names
        (``_decode_program``). ``degraded_decode`` swaps in the jnp
        reference jits (``_jnp_decode_steps``) — same makers, traced with
        the fused kernels disabled."""
        state = self._dev_state
        if self._fault("kernel.decode") is not None:
            self._decode_fault_hit = True
            if not self.degraded_decode:
                raise InjectedFault("kernel.decode")
        if program == "relay":
            self.relay_steps += 1
            return self._relay_step(self.params, inputs, state,
                                    self._dev_ctx, relay)
        if self.degraded_decode:
            step = self._jnp_decode_steps()[program]
        else:
            step = {"mha": self._mha_step,
                    "chai": self._chai_step if self.chai_on else None,
                    "mixed": self._mixed_step if self.chai_on else None,
                    }[program]
        if program == "mha":
            return step(self.params, inputs, state)
        return step(self.params, inputs, state, self._dev_ctx)

    def _maybe_heal(self):
        """Degraded-decode healing: after ``decode_heal_steps``
        consecutive clean decode steps (dispatch succeeded and the
        kernel.decode injector stayed quiet), revert to the fused jits.
        A firing arm — even one masked by the degraded path — resets the
        clean-step count."""
        if self._decode_fault_hit:
            self._heal_clean = 0
            return
        self._heal_clean += 1
        if self._heal_clean < self.ecfg.decode_heal_steps:
            return
        self.degraded_decode = False
        self.decode_heals += 1
        self._heal_clean = 0
        if self.tel.enabled:
            self.tel.counter("decode_heals_total",
                             help="Degraded decode reverted to the fused "
                                  "kernel path")
            self.tel.gauge("engine_degraded_decode", 0,
                           help="1 while decode runs the jnp reference "
                                "fallback")

    def _jnp_decode_steps(self):
        """Degraded decode jits, built lazily on the first injected
        kernel.decode fault: the SAME step makers, but the module flag
        that routes decode attention to the fused Pallas kernels is held
        False while each jit traces, so the whole phase mix runs on the jnp
        reference path (token-parity with the fused path; the relay is
        skipped — ``_build_relay`` returns None while degraded)."""
        if self._jnp_steps is None:
            from repro.core.chai_attention import unfused

            cfg, ts = self.cfg, self.ecfg.page_size
            steps = {"mha": jax.jit(
                unfused(steps_mod.make_serve_step(cfg, chai=False,
                                                  decode_ts=ts)),
                donate_argnums=(2,))}
            if self.chai_on:
                steps["chai"] = jax.jit(
                    unfused(steps_mod.make_serve_step(cfg, chai=True,
                                                      decode_ts=ts)),
                    donate_argnums=(2,))
                steps["mixed"] = jax.jit(
                    unfused(steps_mod.make_mixed_step(cfg, decode_ts=ts)),
                    donate_argnums=(2,))
            self._jnp_steps = steps
        return self._jnp_steps

    def _retire_slot(self, i: int, reason: str, *, error: str = "",
                     index: bool = True):
        """Retire/abort slot ``i``: finalize the request, index its full
        sequence into the prefix cache (when the slot still holds its
        dense pages), reset the slot on device, and return every page it
        held to the pools (refcount-exact; shared pages survive while the
        cache or concurrent slots reference them). Quarantine retires
        pass ``error`` (recorded on the Request) and ``index=False`` —
        a damaged sequence must never seed the prefix cache."""
        r = self._slot_req[i]
        r.generated = r.generated[:r.max_new_tokens]
        r.finish_reason = reason
        r.error = error
        if error:
            self.quarantined += 1
            if self.tel.enabled:
                self.tel.event(r.uid, "quarantine", reason=error)
        r.t_done = time.time()
        r.retire_step = self.steps_executed
        self._done(r)
        self._slot_req[i] = None
        self._phases[i] = chai_cache.PHASE_FREE
        self._slot_count[i] = 0
        if index and self.prefix_cache is not None:
            self._index_retired(r, self._slot_pages[i])
        self._dev_state = self._reset_slot(self._dev_state, jnp.int32(i))
        # block tables are nulled; pages go back
        self._free_pages(self._slot_pages[i])
        if self._slot_locked[i]:
            self.prefix_cache.unlock(self._slot_locked[i])
            self._slot_locked[i] = []
        self._samp_host["temperature"][i] = 0.0     # FREE slots: greedy
        self._samp_dirty = True

    def _index_retired(self, req: Request, pages: dict):
        """Retire-time radix insertion: index the slot's FULL sequence
        (prompt + generated) so a follow-up turn — ``Session`` chat over
        the same history — prefills only its new suffix. Decode wrote K/V
        for every token except the last sampled one, and only slots that
        still hold their dense K AND V pages have a complete paged record
        (clustered-CHAI slots freed dense K at compaction; their reuse
        path is the prompt-keyed snapshot instead)."""
        if "kg" not in pages or "vg" not in pages:
            return
        seq = list(map(int, req.prompt)) + list(req.generated[:-1])
        self.prefix_cache.insert(seq, pages["kg"], pages["vg"])

    # -- metrics ------------------------------------------------------------
    def fault_stats(self):
        """Robustness counters + the injector's replayable plan/firing
        log (None when no injector is armed)."""
        return {"quarantined": self.quarantined,
                "audit_steps": self.audit_steps,
                "degraded_decode": self.degraded_decode,
                "decode_fallbacks": self.decode_fallbacks,
                "decode_heals": self.decode_heals,
                "relay_dissolved": self.relay_dissolved,
                "swap_checksum_failures": self.swap_checksum_failures,
                "offload_checksum_failures": self.offload_checksum_failures,
                "injector": (self.faults.report()
                             if self.faults is not None else None)}

    def prefix_stats(self):
        """Prefix-cache counters + current residency (empty when the
        cache is off)."""
        if self.prefix_cache is None:
            return {}
        dense_held, chai_held = self.prefix_cache.held_pages()
        return {**self.prefix_cache.stats,
                "blocks": self.prefix_cache.num_blocks,
                "snapshots": self.prefix_cache.num_snapshots,
                "dense_page_refs": dense_held,
                "chai_page_refs": chai_held}

    def tier_stats(self):
        """Hierarchical KV tier counters: per-tier residency, transition
        totals, and prefetch hit/miss counts (None when the engine has
        no tiers — a model with no global attention layer)."""
        if self.tiers is None:
            return None
        out = self.tiers.stats()
        out["prefetch_hits"] = self.prefetch_hits
        out["prefetch_misses"] = self.prefetch_misses
        out["offload_checksum_failures"] = self.offload_checksum_failures
        return out

    def kv_bytes(self, *, chai: Optional[bool] = None):
        """KV-cache bytes. With explicit ``chai=``: the paper's ANALYTIC
        steady-state size (Fig 11 A/B comparisons) — hardware-independent,
        unchanged by the engine's layout. With no argument: this engine's
        allocated bytes right now (pages in use x page bytes + the
        non-paged local rings). This falls when a slot's dense pages are
        freed at compaction, so steady-state CHAI reports LESS than the
        dense-MHA rectangle — the paper's saving realized by the
        allocator. ``kv_bytes_history`` records the trajectory;
        ``kv_bytes_capacity()`` gives the pools' total reservation. A
        model with no pools reads its local rings only (0 without
        them)."""
        if chai is None:
            return chai_cache.paged_kv_bytes(
                self.cfg, self.ecfg.page_size,
                *self._pool_pages("pages_in_use"),
                batch=self.ecfg.batch_slots, max_seq=self.ecfg.max_seq)
        return chai_cache.kv_cache_bytes(
            self.cfg, self.ecfg.batch_slots, self.ecfg.max_seq, chai=chai)

    def kv_bytes_peak(self):
        """High-water allocated bytes over the run (O(1): a running
        maximum, not a history scan)."""
        return max(self._kv_peak, self.kv_bytes())

    def kv_bytes_capacity(self):
        """Bytes if every pool page were in use (the device-side
        reservation)."""
        return chai_cache.paged_kv_bytes(
            self.cfg, self.ecfg.page_size, *self._pool_pages("capacity"),
            batch=self.ecfg.batch_slots, max_seq=self.ecfg.max_seq)

    def throughput(self):
        """Completed requests per second of engine wall time."""
        if not self.done:
            return 0.0
        t0 = min(r.t_arrival for r in self.done)
        t1 = max(r.t_done for r in self.done)
        return len(self.done) / max(t1 - t0, 1e-9)


class ServingEngine(EngineCore):
    """Historical batch surface — a thin compatibility wrapper over the
    step-driven ``EngineCore``: ``submit()`` enqueues (optionally with
    ``sampling=SamplingParams(...)``), ``run()`` loops ``step()`` until
    the queue drains. New code should prefer ``repro.serving.api.LLM``
    (generate / stream / abort / Session) or drive ``step()`` directly.
    """

    def submit(self, prompt, max_new_tokens=32, uid=None, *,
               arrival_delay: float = 0.0,
               sampling: Optional[SamplingParams] = None):
        """Enqueue a request (see ``EngineCore.add_request``)."""
        return self.add_request(prompt, sampling,
                                max_new_tokens=max_new_tokens, uid=uid,
                                arrival_delay=arrival_delay)

    def run(self):
        """Drain the queue; returns completed requests."""
        while self.has_work():
            outs = self.step()
            if not outs and not self.has_active and self.queue:
                # open-loop idle: wait for the next arrival
                time.sleep(max(1e-4,
                               self.queue[0].t_arrival - time.time()))
        return self.done
