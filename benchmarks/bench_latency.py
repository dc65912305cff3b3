"""Paper Fig 12: time-to-first-token and time-to-next-token, MHA vs CHAI.

Five measurements:
  1. **CPU wall time** on the trained tiny model through the serving
     engine (real phase machine, real clustering overhead in TTFT).
  2. **Analytic TPU v5e model** for the full LLaMA-7B config: decode
     attention is HBM-bandwidth-bound, so TTNT speedup ≈ KV-bytes-read
     ratio; prefill is compute-bound, so TTFT speedup ≈ score-FLOP ratio.
  3. **Poisson lane**: a mixed-length (8–128 new tokens) Poisson-arrival
     workload through the continuous-batching engine — per-request TTFT,
     request throughput, decode steps, and the paged pools' peak
     allocation against their reservation.
  4. **Fused kernel lane**: one decode-attention step through the fused
     one-launch kernel vs the retired three-kernel pipeline — kernel
     launches per step (counted by intercepting ``pallas_call``),
     analytic HBM bytes moved, output parity, and measured step latency.
     ``python -m benchmarks.bench_latency --check-fused`` runs only the
     deterministic claims (parity + 3→1 launch count) and exits non-zero
     on regression — CI gates on it.
  5. **Prefix-reuse lane**: Poisson arrivals over a shared system prompt
     through the radix prefix cache — TTFT cold vs warm (CHAI snapshot
     hits enter STEADY directly), allocator pages saved vs a no-sharing
     engine, and zero-leak refcount checks after the pools drain. Its
     ``relay`` sub-lane gates the shared-prefix relay decode: grouped
     token parity with the per-request path, kernel-launch flatness in
     the group size, and the O(prefix) per-step HBM/MXU cost structure.
     ``python -m benchmarks.bench_latency --check`` runs ALL
     deterministic claim checks (fused + relay) and exits non-zero on
     regression — CI gates on it.
  6. **Streaming lane**: one request through ``LLM.stream()`` (greedy
     and seeded sampling) — TTFT plus inter-token latency (ITL) p50/p99
     from per-chunk arrival stamps, and the deterministic claim that the
     first token arrives strictly before the request completes.
  7. **SLO storm lane**: steady Poisson decode traffic interrupted by a
     long-prompt arrival. Monolithic prefill forwards the storm prompt
     inside ONE ``step()`` — every decoding slot's ITL absorbs it;
     ``prefill_chunk_tokens`` page-slices the prompt across steps so
     decode tokens keep flowing. Gated step-domain claims (monolithic
     stalls decode for the whole prompt, chunked interleaves every
     intermediate step) plus the advisory wall-clock reading: chunked
     ITL p99 within 2x the no-storm baseline where monolithic prefill
     violates it (on paper-scale hardware; see the lane docstring for
     why the CPU proxy inverts the ratio).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmarks.common import save_result, tiny_trained
from repro.configs.base import get_config
from repro.core.cache import kv_cache_bytes
from repro.kernels.ops import decode_flop_estimate
from repro.launch.roofline import HBM_BW, PEAK_FLOPS
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.workload import poisson_workload


def _engine_times(cfg, params, pipe, use_chai, n_req=4, max_new=12):
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=2, max_seq=128,
                                     use_chai=use_chai))
    for i in range(n_req):
        eng.submit(pipe.batch(900 + i)["tokens"][0, :24],
                   max_new_tokens=max_new, uid=i)
    t0 = time.time()
    done = eng.run()
    wall = time.time() - t0
    ttft = float(np.mean([r.ttft for r in done]))
    per_tok = (wall - ttft * (n_req / eng.ecfg.batch_slots)) / (
        n_req * max_new)
    return {"wall_s": wall, "ttft_s": ttft, "per_token_s": per_tok}


def _poisson_lane(cfg, params, pipe, *, n_req=18, slots=6, prompt_len=16,
                  new_tokens=(8, 128), mean_gap_s=0.01, seed=0):
    """A Poisson workload (exponential inter-arrival gaps, mixed 8-128
    new tokens) through the engine.

    Output lengths are long-tailed (most requests short, a minority
    near the cap — the regime continuous batching exists for). On a CPU
    the decode step runs the fused kernel in interpret mode, so the
    wall-clock numbers are advisory."""
    rng = np.random.default_rng(seed)
    arrivals, lens = poisson_workload(rng, n_req, mean_gap_s=mean_gap_s,
                                      new_tokens=new_tokens)
    prompts = [pipe.batch(3000 + i)["tokens"][0, :prompt_len]
               for i in range(n_req)]
    eng = ServingEngine(cfg, params,
                        EngineConfig(batch_slots=slots, max_seq=192))
    # Two identical passes; the first warms every jit (prefill per prompt
    # length, all phase-mix step variants) so the measured pass reflects
    # steady-state serving, not compile time.
    for timed in (False, True):
        t0 = time.time()
        batch = [eng.submit(prompts[i], max_new_tokens=int(lens[i]),
                            uid=i, arrival_delay=float(arrivals[i]))
                 for i in range(n_req)]
        steps0 = eng.steps_executed
        eng.run()
        wall = time.time() - t0
    ttfts = np.array([r.ttft for r in batch])
    span = max(r.t_done for r in batch) - min(r.t_arrival for r in batch)
    return {
        "wall_s": wall,
        "req_per_s": n_req / span,
        "ttft_s_mean": float(ttfts.mean()),
        "ttft_s_p95": float(np.percentile(ttfts, 95)),
        "decode_steps": eng.steps_executed - steps0,
        "kv_bytes_peak": int(eng.kv_bytes_peak()),
        "kv_bytes_capacity": int(eng.kv_bytes_capacity()),
        "workload": {"n_req": n_req, "slots": slots,
                     "new_tokens": list(map(int, lens)),
                     "arrival_span_s": float(arrivals[-1])},
    }


@contextlib.contextmanager
def _count_pallas_launches():
    """Count ``pl.pallas_call`` invocations (== kernel launches per
    un-jitted call) by intercepting the module attribute every kernel
    wrapper resolves at call time."""
    from jax.experimental import pallas as pl
    counter = {"n": 0}
    orig = pl.pallas_call

    def counted(*a, **kw):
        counter["n"] += 1
        return orig(*a, **kw)

    pl.pallas_call = counted
    try:
        yield counter
    finally:
        pl.pallas_call = orig


def _time_best(fn, *args, reps=5):
    import jax
    out = fn(*args)                       # compile
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _fused_kernel_lane(seed=0, timing=True):
    """Fused one-launch decode vs the retired three-kernel pipeline on a
    representative MHA decode shape: launch count, analytic HBM bytes per
    step, allclose parity, and measured per-step wall time (CPU interpret
    mode — the launch/byte counts are the hardware-independent claims;
    the timing is the advisory proxy, skipped when ``timing=False``,
    e.g. by the deterministic ``--check-fused`` CI gate)."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.kernels import chai_attention as ck
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    b, h, r, s, hd, ts = 4, 8, 5, 256, 32, 64
    q = jnp.asarray(rng.normal(size=(b, r, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, r, s, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.float32)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    pos = jnp.asarray(rng.integers(s // 2, s, size=b), jnp.int32)

    with _count_pallas_launches() as fused_n:
        out_fused = ck.chai_fused_decode(q, kc, vc, h2c, pos, ts=ts,
                                         interpret=True)
    with _count_pallas_launches() as pipe_n:
        out_pipe = ref.chai_three_kernel_decode(q, kc, vc, h2c, pos, ts=ts)
    parity = bool(np.allclose(np.asarray(out_fused), np.asarray(out_pipe),
                              rtol=2e-3, atol=2e-3))

    result = {
        "shape": {"b": b, "h": h, "r": r, "s": s, "hd": hd, "ts": ts},
        "launches_per_step": {"fused": fused_n["n"],
                              "three_kernel": pipe_n["n"]},
        "hbm_bytes_per_step_est": {
            "fused": ops.decode_hbm_bytes_estimate(b, h, r, s, hd,
                                                   fused=True),
            "three_kernel": ops.decode_hbm_bytes_estimate(b, h, r, s, hd,
                                                          fused=False),
        },
        "parity_allclose": parity,
        "claims": {
            # deterministic, EMPIRICAL (CI gates on these via
            # --check-fused): launch counts are observed by interception,
            # parity by execution. The HBM-bytes numbers above are
            # analytic model outputs — reported for the roofline story,
            # never gated (both sides come from one formula, so a
            # boolean on them could not fail).
            "fused_single_launch":
                fused_n["n"] == ops.decode_launch_count(fused=True)
                and pipe_n["n"] == ops.decode_launch_count(fused=False),
            "fused_parity": parity,
        },
    }
    if timing:
        fused_jit = jax.jit(functools.partial(ck.chai_fused_decode, ts=ts,
                                              interpret=True))
        pipe_jit = jax.jit(functools.partial(ref.chai_three_kernel_decode,
                                             ts=ts))
        t_fused = _time_best(fused_jit, q, kc, vc, h2c, pos)
        t_pipe = _time_best(pipe_jit, q, kc, vc, h2c, pos)
        result["step_latency_s"] = {"fused": t_fused,
                                    "three_kernel": t_pipe}
        # advisory (wall clock on shared CPU runners is noisy)
        result["claims"]["fused_latency_no_worse"] = \
            t_fused <= t_pipe * 1.25
    return result


def _prefix_reuse_lane(cfg, params, pipe, *, n_warm=4, prompt_len=96,
                       max_new=16, slots=4, mean_gap_s=0.005, seed=0):
    """Shared-prefix KV reuse (radix prefix cache + CHAI snapshots):
    Poisson arrivals over ONE shared system prompt. Wave 1 is cold (it
    seeds the cache); wave 2 mixes exact repeats (CHAI snapshot hits —
    STEADY entry, zero prefill) and shared-prefix-different-suffix
    requests (partial hits — suffix-only prefill). Reports TTFT cold vs
    warm, allocator pages saved vs a no-sharing engine, and leak-freedom
    after the pools drain."""
    from repro.serving.prefix_cache import PrefixCache  # noqa: F401
    rng = np.random.default_rng(seed)
    sys_prompt = np.asarray(pipe.batch(7000)["tokens"][0, :prompt_len])
    other = np.asarray(pipe.batch(7001)["tokens"][0, :prompt_len])

    def tails(base):
        return [np.concatenate([sys_prompt[:prompt_len - 8],
                                np.asarray(pipe.batch(base + i)["tokens"]
                                           [0, :8])])
                for i in range(n_warm // 2)]

    arrivals = np.cumsum(rng.exponential(mean_gap_s, size=n_warm))

    def fresh(prefix_cache):
        return ServingEngine(cfg, params, EngineConfig(
            batch_slots=slots, max_seq=128, page_size=16,
            prefix_cache=prefix_cache))

    out = {}
    for lane, cached in (("prefix_cache", True), ("no_sharing", False)):
        eng = fresh(cached)
        # wave 0: compiles the cold-prefill jits and seeds the cache with
        # an unrelated prompt, so the measured cold request below is
        # jit-warm but cache-cold (a true miss).
        eng.submit(other, max_new_tokens=max_new, uid=0)
        eng.run()
        cold = eng.submit(sys_prompt, max_new_tokens=max_new, uid=1)
        eng.run()                               # measured miss: seeds cache
        # wave 1 (unmeasured): compiles the suffix-prefill / snapshot
        # restore jits on one warm mix
        for i, p in enumerate([sys_prompt] + tails(7200)):
            eng.submit(p, max_new_tokens=max_new, uid=100 + i)
        eng.run()
        # wave 2 (measured): same mix shape — exact repeats hit the CHAI
        # snapshot, fresh tails partially hit the shared prefix. Pages
        # are compared as the wave's allocation DELTA (pages_in_use also
        # counts the cache's own residency, which is the reservation
        # being traded for the sharing).
        uniq = tails(7300)
        in_use0 = (eng.dense_pool.pages_in_use
                   + (eng.chai_pool.pages_in_use if eng.chai_pool else 0))
        hist0 = len(eng.kv_bytes_history)
        warm_reqs = []
        for i in range(n_warm):
            prompt = sys_prompt if i % 2 == 0 else uniq[i // 2]
            warm_reqs.append(eng.submit(
                prompt, max_new_tokens=max_new, uid=200 + i,
                arrival_delay=float(arrivals[i])))
        eng.run()
        warm_hist = eng.kv_bytes_history[hist0:]
        # single warm request on an idle engine: the TTFT comparison is
        # cold-prefill-alone vs snapshot-resume-alone (both jit-warm);
        # the concurrent wave above measures pages/hits, where TTFT
        # would mostly measure admission queueing behind decode steps.
        warm_alone = eng.submit(sys_prompt, max_new_tokens=max_new,
                                uid=300)
        eng.run()
        out[lane] = {
            "ttft_cold_s": cold.ttft,
            "ttft_warm_s": warm_alone.ttft,
            "ttft_warm_wave_s_mean": float(np.mean([r.ttft
                                                    for r in warm_reqs])),
            "warm_wave_pages_allocated": max(
                h["dense_pages"] + h["chai_pages"] for h in warm_hist)
                - in_use0,
            "hits": {r.uid: r.cache_hit for r in warm_reqs},
            "prefill_tokens": sum(max(r.prefill_tokens, 0)
                                  for r in warm_reqs),
        }
        if cached:
            out[lane]["stats"] = eng.prefix_stats()
            eng.prefix_cache.clear()
        out[lane]["pages_leaked"] = (eng.dense_pool.pages_in_use
                                     + (eng.chai_pool.pages_in_use
                                        if eng.chai_pool else 0))
    cachedl, basel = out["prefix_cache"], out["no_sharing"]
    out["pages_saved"] = (basel["warm_wave_pages_allocated"]
                          - cachedl["warm_wave_pages_allocated"])
    out["claims"] = {
        # a fully-cached warm request skips prefill AND warmup/cluster:
        # TTFT must beat the cold request's (deterministic work skipped,
        # but still a wall-clock measure — advisory in CI)
        "warm_ttft_below_cold":
            cachedl["ttft_warm_s"] < cachedl["ttft_cold_s"],
        # >= 2 concurrent shared-prefix requests allocate strictly fewer
        # pages than the no-sharing baseline (deterministic)
        "pages_saved_vs_no_sharing": out["pages_saved"] > 0,
        # refcounts drain to zero after eviction + slot reset
        "no_page_leaks": cachedl["pages_leaked"] == 0
                         and basel["pages_leaked"] == 0,
        # snapshot fast path actually exercised
        "snapshot_hit_observed":
            "snapshot" in cachedl["hits"].values()
            or "replay" in cachedl["hits"].values(),
    }
    return out


def _relay_lane(cfg, params, pipe, *, prefix_blocks=4, max_new=8, seed=0):
    """Shared-prefix relay decode: the system prompt's attention is
    computed ONCE per group of STEADY slots and merged into each slot's
    suffix-only fused decode via online-softmax state.

    Deterministic gated claims (``--check`` runs these in CI):

    * token parity — grouped greedy tokens match the per-request decode
      path exactly;
    * launch flatness — tracing the relay step for a 1-member and a
      2-member group constructs the SAME number of kernel launches (the
      prefix pass is grid-batched over groups, never per slot);
    * O(prefix) cost — per-step prefix HBM bytes take no member count at
      all and double when the prefix doubles, and the MXU pass estimate
      stays flat across group sizes 1/2/8 (member rep rows batch along
      the systolic row axis) while the per-request baseline pays
      N x the single-slot cost.
    """
    import jax
    from repro.kernels import ops
    from repro.launch import steps as steps_mod

    ps = 16
    plen = prefix_blocks * ps
    prefix = np.asarray(pipe.batch(8100)["tokens"][0, :plen])
    tails = [np.concatenate([prefix,
                             np.asarray(pipe.batch(8200 + i)["tokens"]
                                        [0, :4 + i])])
             for i in range(2)]

    def serve(relay, prompts, min_group):
        eng = ServingEngine(cfg, params, EngineConfig(
            batch_slots=2, max_seq=128, page_size=ps, prefix_cache=True,
            relay_decode=relay, relay_min_group=min_group))
        captured = {}
        if relay:
            orig = eng._relay_step

            def spy(p, inputs, state, ctx, rel):
                if "sds" not in captured:   # shapes only, no host copy
                    captured["sds"] = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        (inputs, state, ctx, rel))
                return orig(p, inputs, state, ctx, rel)

            eng._relay_step = spy
        eng.submit(prefix, max_new_tokens=max_new, uid=0)   # seed cache
        eng.run()
        for j, p in enumerate(prompts):
            eng.submit(p, max_new_tokens=max_new, uid=j + 1)
        done = {r.uid: r for r in eng.run()}
        toks = [done[j + 1].generated for j in range(len(prompts))]
        return toks, eng, captured.get("sds")

    base, _, _ = serve(False, tails, 2)
    toks, eng2, sds2 = serve(True, tails, 2)          # 2-member group
    _, eng1, sds1 = serve(True, tails[:1], 1)         # 1-member group

    # Launch flatness: trace the relay step over the captured shapes and
    # count ``pallas_call`` equations in the (recursively walked) jaxpr —
    # the compiled step launches exactly what the trace contains. Eqn
    # counting (not ``pallas_call`` interception) because the engine runs
    # above already populated the nested-jit trace caches.
    step_fn = steps_mod.make_relay_step(cfg, decode_ts=ps)
    p_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)

    def trace_launches(sds):
        inputs, state, ctx, rel = sds
        jaxpr = jax.make_jaxpr(step_fn)(p_sds, inputs, state, ctx, rel)
        n, todo = 0, [jaxpr.jaxpr]
        while todo:
            j = todo.pop()
            for eqn in j.eqns:
                n += eqn.primitive.name == "pallas_call"
                for p in eqn.params.values():
                    for sub in (p if isinstance(p, (list, tuple))
                                else [p]):
                        inner = getattr(sub, "jaxpr", None)
                        if inner is not None:
                            todo.append(inner)
                        elif hasattr(sub, "eqns"):
                            todo.append(sub)
        return n

    launches = {"group_of_1": trace_launches(sds1),
                "group_of_2": trace_launches(sds2)}

    # O(prefix) cost model over the ACTUAL resident-view geometry
    k_rows, v_rows = sds2[3]["k"].shape[2], sds2[3]["v"].shape[2]
    hd = sds2[3]["k"].shape[-1]
    r = sds2[3]["k_row"].shape[-1] // sds2[3]["members"].shape[-1]
    int8 = "k_scale" in sds2[3]
    hbm = {s: ops.relay_prefix_hbm_bytes_estimate(
               k_rows, v_rows, s, hd, cache_bytes=1 if int8 else 4,
               int8_scales=int8) for s in (plen, 2 * plen)}
    mxu = {n: ops.relay_prefix_mxu_pass_estimate(n, r, plen, ts=ps)
           for n in (1, 2, 8)}

    out = {
        "prefix_len": plen,
        "relay_steps": eng2.relay_steps,
        "grouped_slots": eng2.relay_grouped_slots,
        "launches_per_trace": launches,
        "prefix_hbm_bytes_per_step": hbm[plen],
        "prefix_hbm_bytes_per_step_2x_prefix": hbm[2 * plen],
        "per_request_baseline_hbm_bytes_n2": 2 * hbm[plen],
        "mxu_passes_by_group_size": mxu,
        "per_request_baseline_mxu_passes_n8": 8 * mxu[1],
        "claims": {
            # grouped greedy tokens == per-request decode path
            "relay_tokens_match_per_request":
                toks == base and eng2.relay_steps > 0
                and eng1.relay_steps > 0,
            # one grid-batched prefix pass per layer, not one per slot
            "relay_launches_flat_in_group_size":
                0 < launches["group_of_1"] == launches["group_of_2"],
            # per-step prefix HBM bytes: member-count-free by
            # construction, linear in the prefix length
            "relay_prefix_hbm_o_prefix":
                hbm[2 * plen] == 2 * hbm[plen],
            # QK passes flat while N*R fits one MXU tile; the
            # per-request baseline pays N x the single-slot passes
            "relay_mxu_passes_flat_in_n":
                mxu[1] == mxu[2] == mxu[8]
                and 8 * mxu[1] > mxu[8],
        },
    }
    return out


def _streaming_lane(cfg, params, pipe, *, prompt_len=16, max_new=24,
                    slots=2):
    """Per-token streaming latency through the ``LLM.stream`` frontend:
    TTFT (enqueue -> first token) and inter-token latency (ITL) p50/p99
    for greedy and seeded sampling, read from the ENGINE's lifecycle
    telemetry (``telemetry="basic"`` events summarized by
    ``common.lifecycle_metrics``) instead of client-side stamps around
    the streaming loop — the engine stamps first-token and per-token
    times at the step that produced them, so the numbers exclude
    frontend queue hand-off. The incremental-delivery claim (first
    token strictly before the last, more than one chunk) is
    deterministic; the latency numbers are wall-clock and advisory on
    shared runners."""
    from benchmarks.common import lifecycle_metrics
    from repro.serving.api import LLM
    from repro.serving.engine import EngineConfig
    from repro.serving.sampling import SamplingParams

    llm = LLM(cfg, params, EngineConfig(batch_slots=slots, max_seq=128,
                                        telemetry="basic"))
    prompt = pipe.batch(8000)["tokens"][0, :prompt_len]
    out = {}
    lanes = {
        "greedy": SamplingParams(max_new_tokens=max_new),
        "sampled": SamplingParams(temperature=0.8, top_k=16, top_p=0.95,
                                  seed=7, max_new_tokens=max_new),
    }
    for sp in lanes.values():       # warm BOTH samplers' jits (the
        llm.generate(prompt, sp)    # batched sampler traces separately)
    uids = {}
    for lane, sp in lanes.items():
        n_chunks, finished, uid = 0, False, None
        for chunk in llm.stream(prompt, sp):
            n_chunks += 1
            finished = chunk.finished
            uid = chunk.uid
        uids[lane] = uid
        out[lane] = {"n_chunks": n_chunks, "finished": finished}
    summaries = lifecycle_metrics(llm.core)
    for lane, uid in uids.items():
        s = summaries[uid]
        itl = np.asarray(s["itl_s"]) if s["itl_s"] else np.zeros(1)
        out[lane].update({
            "n_tokens": s["n_tokens"],
            "ttft_s": s["ttft_s"],
            "queue_s": s.get("queue_s"),
            "itl_s_p50": float(np.percentile(itl, 50)),
            "itl_s_p99": float(np.percentile(itl, 99)),
            "total_s": s["latency_s"],
            "finish_reason": s["finish_reason"],
        })
    out["claims"] = {
        # deterministic: streaming delivered the first token in its own
        # chunk, strictly before the request completed (engine-stamped)
        "stream_first_token_before_completion": all(
            v["n_chunks"] > 1 and v["ttft_s"] < v["total_s"]
            and v["finished"] and v["n_tokens"] == max_new
            for v in (out["greedy"], out["sampled"])),
    }
    return out


def _slo_storm_lane(cfg, params, pipe, *, n_decode=3, decode_prompt=16,
                    storm_len=96, max_new=24, storm_new=8, chunk=16,
                    slots=4, mean_gap_s=0.002, seed=0):
    """SLO under a long-prompt storm: steady Poisson decode traffic is
    interrupted by a long-prompt arrival once every decoder has produced
    a few tokens. Three lanes on identical jit-warm engines:

    * ``baseline``   — decode traffic alone (no storm): the ITL floor.
    * ``monolithic`` — storm admitted with ``prefill_chunk_tokens=0``:
      the whole storm prompt forwards inside ONE ``step()``, so every
      decoding slot's next token waits for the full prefill.
    * ``chunked``    — ``prefill_chunk_tokens=chunk``: the storm prefill
      page-slices across steps, decode tokens flow between chunks.

    The GATED claims are step-domain and deterministic: monolithic
    prefill emits the storm's first token in its admission step (zero
    intermediate steps — the whole prompt's work lands inside one decode
    interval), while chunked prefill spans ``storm_pages`` steps with
    every decoder emitting a token in each intermediate step.

    The wall-clock reading — chunked ITL p99 within 2x the no-storm
    baseline where monolithic violates it — is what those facts mean on
    paper-scale hardware (prefill FLOPs dwarf one decode step). It is
    reported here but NOT gated: on this CPU container the cost ratio
    INVERTS (the monolithic prefill is compiled jnp, a few ms, while
    every decode step pays the interpret-mode Pallas kernel), so the
    tiny-model wall clock measures the interpreter, not the storm."""
    from repro.serving.api import LLM
    from repro.serving.sampling import SamplingParams

    rng = np.random.default_rng(seed)
    gaps = np.cumsum(rng.exponential(mean_gap_s, size=n_decode))
    prompts = [pipe.batch(9000 + i)["tokens"][0, :decode_prompt]
               for i in range(n_decode)]
    # pipe rows are shorter than the storm prompt — concatenate two
    storm = np.concatenate([np.asarray(pipe.batch(9100)["tokens"][0]),
                            np.asarray(pipe.batch(9101)["tokens"][0])
                            ])[:storm_len]
    assert len(storm) == storm_len, len(storm)
    sp = SamplingParams(max_new_tokens=max_new)
    storm_sp = SamplingParams(max_new_tokens=storm_new)

    def run_lane(chunk_tokens, with_storm):
        llm = LLM(cfg, params, EngineConfig(
            batch_slots=slots, max_seq=128, page_size=16,
            prefill_chunk_tokens=chunk_tokens))
        core = llm.core
        # Warm every jit variant the measured pass can hit. Arrival
        # staggering is wall-clock nondeterministic, so the decode step
        # must be warm for EVERY phase mix: the all-MHA and all-CHAI
        # fast paths warm on a plain generate; the general mixed jit
        # needs a STEADY slot coexisting with a WARMUP one — force that
        # by admitting a second request (and the storm prompt, warming
        # its monolithic bucket / chunk bucket) after the first request
        # reaches STEADY.
        ra = core.add_request(prompts[0], sp, uid=900)
        for _ in range(cfg.chai.warmup_tokens + 2):
            core.step()
        rb = core.add_request(prompts[1], sp, uid=901)
        rs = core.add_request(storm, storm_sp, uid=902)
        while not (ra.finished and rb.finished and rs.finished):
            core.step()
        core.reap_done()

        reqs = [core.add_request(p, sp, uid=i,
                                 arrival_delay=float(gaps[i]))
                for i, p in enumerate(prompts)]
        stamps = {r.uid: [] for r in reqs}   # per decode uid: (step, t)
        storm_req, storm_submit_step, storm_first_step = None, None, None
        n_steps = 0
        while (not all(r.finished for r in reqs)
               or (storm_req is not None and not storm_req.finished)):
            outs = core.step()
            n_steps += 1
            now = time.time()
            for o in outs:
                if o.uid in stamps:
                    stamps[o.uid].extend([(n_steps, now)]
                                         * len(o.token_ids))
                elif storm_req is not None and o.uid == storm_req.uid \
                        and storm_first_step is None:
                    storm_first_step = n_steps
            if (with_storm and storm_req is None
                    and all(len(r.generated) >= 4 for r in reqs)):
                storm_req = core.add_request(storm, storm_sp, uid=99)
                storm_submit_step = n_steps
            if not outs and not core.has_active:
                time.sleep(1e-4)    # waiting on a Poisson arrival
        core.reap_done()

        itl = np.concatenate([np.diff([t for _, t in s])
                              for s in stamps.values() if len(s) > 1])
        out = {
            "n_itl_samples": int(itl.size),
            "itl_s_p50": float(np.percentile(itl, 50)),
            "itl_s_p99": float(np.percentile(itl, 99)),
            "itl_s_max": float(itl.max()),
        }
        if with_storm:
            # steps strictly between the storm's admission step and the
            # step that emitted its first token — the prefill window a
            # decoder could starve in
            window = range(storm_submit_step + 2, storm_first_step)
            out["storm_prefill_intermediate_steps"] = len(window)
            out["decode_tokens_during_storm_prefill"] = sum(
                1 for s in stamps.values() for step, _ in s
                if step in window)
        return out

    storm_pages = -(-storm_len // 16)
    out = {
        "workload": {"n_decode": n_decode, "decode_prompt": decode_prompt,
                     "storm_len": storm_len, "storm_pages": storm_pages,
                     "max_new": max_new, "chunk": chunk, "slots": slots},
        "baseline": run_lane(0, with_storm=False),
        "monolithic": run_lane(0, with_storm=True),
        "chunked": run_lane(chunk, with_storm=True),
    }
    bound = 2.0 * out["baseline"]["itl_s_p99"]
    mono, chnk = out["monolithic"], out["chunked"]
    out["itl_p99_2x_baseline_bound_s"] = bound
    out["claims"] = {
        # -- deterministic, gated ------------------------------------
        # one-shot prefill has NO intermediate steps: the storm's first
        # token arrives in its admission step, so every decoder's next
        # token absorbed the whole prompt's forward
        "monolithic_prefill_stalls_decode":
            mono["storm_prefill_intermediate_steps"] == 0,
        # chunked prefill spans the page-sliced window and every
        # decoder emits a token in every intermediate step
        "chunked_decode_flows_during_prefill":
            chnk["storm_prefill_intermediate_steps"] >= storm_pages - 2
            and chnk["decode_tokens_during_storm_prefill"]
                >= n_decode * (storm_pages - 2),
        # -- wall-clock, advisory (see docstring: the CPU proxy
        # inverts the prefill/decode cost ratio) ---------------------
        "chunked_itl_p99_within_2x_baseline":
            chnk["itl_s_p99"] <= bound,
        "monolithic_violates_2x_baseline":
            mono["itl_s_p99"] > bound,
    }
    return out


def _analytic_full(seqs=(256, 512, 1024, 2048)):
    cfg = get_config("chai-llama-7b")
    h, hd = cfg.n_heads, cfg.head_dim
    counts = cfg.chai_cluster_counts()
    out = {}
    for s in seqs:
        # TTNT: decode is memory-bound -> bytes of KV read per token
        mha_bytes = kv_cache_bytes(cfg, 1, s, chai=False)
        chai_bytes = kv_cache_bytes(cfg, 1, s, chai=True)
        # TTFT: prefill is compute-bound -> attention score flops
        mha_fl = sum(decode_flop_estimate(1, h, h, s, hd)
                     for _ in counts) * s
        chai_fl = sum(decode_flop_estimate(1, h, k, s, hd)
                      for k in counts) * s
        out[str(s)] = {
            "ttnt_speedup_bound": mha_bytes / chai_bytes,
            "ttft_attention_speedup_bound": mha_fl / chai_fl,
            "ttnt_mha_s_v5e": mha_bytes / HBM_BW,
            "ttnt_chai_s_v5e": chai_bytes / HBM_BW,
        }
    return out


def run():
    cfg, params, pipe, _ = tiny_trained()
    cfg_chai = cfg.with_chai(enabled=True,
                             cluster_counts=(5,) * cfg.n_attn_layers)
    cpu_mha = _engine_times(cfg, params, pipe, use_chai=False)
    cpu_chai = _engine_times(cfg_chai, params, pipe, use_chai=True)
    poisson = _poisson_lane(cfg_chai, params, pipe)
    fused = _fused_kernel_lane()
    prefix = _prefix_reuse_lane(cfg_chai, params, pipe)
    prefix["relay"] = _relay_lane(cfg_chai, params, pipe)
    streaming = _streaming_lane(cfg_chai, params, pipe)
    slo = _slo_storm_lane(cfg_chai, params, pipe)

    result = {
        "proxy_note": "CPU wall time on tiny model (engine incl. "
                      "clustering overhead) + analytic v5e model for "
                      "LLaMA-7B (paper Fig 12 ran V100s)",
        "cpu_tiny": {"mha": cpu_mha, "chai": cpu_chai,
                     "per_token_speedup":
                         cpu_mha["per_token_s"] / cpu_chai["per_token_s"]},
        "poisson_continuous": poisson,
        "fused_kernel_lane": fused,
        "prefix_reuse": prefix,
        "streaming": streaming,
        "slo_storm": slo,
        "analytic_llama7b_v5e": _analytic_full(),
        "paper_claim": "TTFT up to 1.73x, TTNT up to 5x at seq 2048",
        "claim_check": {
            # fused decode: 3 launches -> 1 (observed), same outputs
            "fused_single_launch": fused["claims"]["fused_single_launch"],
            "fused_parity": fused["claims"]["fused_parity"],
            "ttnt_bound_exceeds_1": _analytic_full()["2048"]
                ["ttnt_speedup_bound"] > 1.0,
            "ttft_attn_bound_exceeds_1": _analytic_full()["2048"]
                ["ttft_attention_speedup_bound"] > 1.0,
            # page-budget admission never exceeds the pool reservation
            "paged_peak_within_capacity":
                poisson["kv_bytes_peak"] <= poisson["kv_bytes_capacity"],
            # prefix-reuse lane: deterministic allocator claims + the
            # (advisory-in-CI) cold-vs-warm TTFT ordering
            "prefix_warm_ttft_below_cold":
                prefix["claims"]["warm_ttft_below_cold"],
            "prefix_pages_saved_vs_no_sharing":
                prefix["claims"]["pages_saved_vs_no_sharing"],
            "prefix_no_page_leaks": prefix["claims"]["no_page_leaks"],
            "prefix_snapshot_hit_observed":
                prefix["claims"]["snapshot_hit_observed"],
            # relay decode lane: deterministic (token parity is executed,
            # launch flatness is trace-counted, the cost-model booleans
            # encode the O(prefix) / flat-in-N structure CI must keep)
            "relay_tokens_match_per_request":
                prefix["relay"]["claims"]["relay_tokens_match_per_request"],
            "relay_launches_flat_in_group_size":
                prefix["relay"]["claims"]
                    ["relay_launches_flat_in_group_size"],
            "relay_prefix_hbm_o_prefix":
                prefix["relay"]["claims"]["relay_prefix_hbm_o_prefix"],
            "relay_mxu_passes_flat_in_n":
                prefix["relay"]["claims"]["relay_mxu_passes_flat_in_n"],
            # streaming frontend: tokens arrive incrementally
            # (deterministic; the ITL percentiles above are advisory)
            "stream_first_token_before_completion":
                streaming["claims"]["stream_first_token_before_completion"],
            # SLO storm lane, deterministic step-domain claims (the
            # wall-clock ITL booleans stay advisory inside the lane —
            # the CPU proxy inverts the prefill/decode cost ratio):
            # one-shot prefill absorbs the whole storm prompt inside a
            # single decode interval; chunked prefill keeps every
            # decoder emitting through the storm's prefill window
            "slo_storm_monolithic_prefill_stalls_decode":
                slo["claims"]["monolithic_prefill_stalls_decode"],
            "slo_storm_chunked_decode_flows_during_prefill":
                slo["claims"]["chunked_decode_flows_during_prefill"],
        },
    }
    save_result("bench_latency", result)
    return result


def check_fused():
    """Deterministic fused-decode gate (CI): parity with the three-kernel
    pipeline, the 3 -> 1 launch-count drop, and the HBM-bytes ordering.
    Exits non-zero on any regression; never times anything."""
    lane = _fused_kernel_lane(timing=False)
    gated = {k: lane["claims"][k] for k in
             ("fused_single_launch", "fused_parity")}
    print({"fused_kernel_lane": lane, "gated": gated})
    return 0 if all(gated.values()) else 1


def check():
    """Full deterministic claim gate (CI): the fused-decode checks plus
    the relay-decode lane (token parity, launch flatness, O(prefix) cost
    structure). Exits non-zero on any regression; never times anything."""
    rc = check_fused()
    cfg, params, pipe, _ = tiny_trained()
    cfg_chai = cfg.with_chai(enabled=True,
                             cluster_counts=(5,) * cfg.n_attn_layers)
    lane = _relay_lane(cfg_chai, params, pipe)
    print({"relay_lane": lane, "gated": lane["claims"]})
    return 1 if (rc or not all(lane["claims"].values())) else 0


if __name__ == "__main__":
    import argparse
    import sys
    ap = argparse.ArgumentParser()
    ap.add_argument("--check-fused", action="store_true",
                    help="run only the deterministic fused-decode claim "
                         "checks (CI gate); exit 1 on regression")
    ap.add_argument("--check", action="store_true",
                    help="run every deterministic claim check (fused "
                         "decode + relay lane); exit 1 on regression")
    args = ap.parse_args()
    if args.check:
        sys.exit(check())
    if args.check_fused:
        sys.exit(check_fused())
    print(run())
