#!/usr/bin/env python3
"""Serve a few requests through the paged CHAI serving path on one TPU chip.

    python3 chip_smoke.py

The model is chai-llama-7b at its published widths (d_model 4096, 32 MHA
heads of 128, d_ff 11008, vocab 32000) in bf16 with random weights from a
fixed seed. Only depth is cut, to the first 16 of 32 layers: the full bf16
model has 13.5 GB of weights and does not fit 16 GB of HBM beside its KV
cache. The requests go through the code ``repro.launch.serve`` runs: the
continuous scheduler over paged KV with CHAI on, so every request passes
PREFILL -> WARMUP -> CLUSTER -> STEADY. Two waves of the same shapes are
served: the first compiles (or reads the compile cache), the second is warm.

Checks (any failure exits 1 and prints no result line):
  * JAX's first device is a TPU (there is no CPU fallback);
  * every request finished, none with finish_reason "error";
  * no decode fallback, no degraded decode, no quarantined request;
  * CLUSTER ran and STEADY decode steps happened;
  * the compiled STEADY decode program holds a Mosaic kernel
    (``tpu_custom_call``), not the Pallas interpreter;
  * one STEADY step's logits from the fused kernel path agree with the jnp
    reference decode path on the same inputs within ``LOGIT_TOL``;
  * the paged fused decode kernel agrees with its ``ref.py`` oracle on
    random pages at the model's head widths within ``KERNEL_TOL``.

The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH, LAYERS, SEED = "chai-llama-7b", 16, 0
SLOTS, MAX_SEQ, MAX_NEW = 8, 2048, 32
# One prompt per slot, in both of the pow2 prefill buckets (512, 1024).
LENGTHS = (512, 576, 640, 704, 768, 832, 960, 1024)
# Fused kernel vs jnp reference: both read the same bf16 weights and KV and
# differ only in how the attention sums are ordered and rounded, which
# compounds over 16 bf16 layers. Bound: max |fused - ref| over the logits
# of the batch, relative to max |ref|. On a v5e the sound kernel reads
# 1.1e-2 to 1.5e-2 and a kernel fed a head -> rep map rolled by one head
# reads 0.72 (PERF.md); the limit sits between them.
LOGIT_TOL = 5e-2
# Paged fused decode kernel (bf16 inputs) vs its f32 oracle, max |diff|
# relative to max |oracle| (see ``kernel_check``). On a v5e the sound
# kernel reads 2.9e-3 and one fed a head -> rep map rolled by one or two
# heads reads 1.0 to 1.2 (PERF.md).
KERNEL_TOL = 5e-2


def pages_needed(slots, lengths, max_new, page=16):
    """Pool pages for the workload: every slot holds its longest request at
    once, plus the reserved null page (the engine's auto size doubles this
    for prefix-cache headroom, which this run does not use)."""
    return slots * math.ceil((max(lengths) + max_new) / page) + 1


def run(cfg, *, slots=SLOTS, max_seq=MAX_SEQ, lengths=LENGTHS,
        max_new=MAX_NEW, seed=SEED):
    """Serve two waves of ``lengths`` prompts through the default engine
    and return (engine, per-wave results, STEADY-step observations)."""
    import jax
    import numpy as np

    from repro.core.chai_attention import unfused
    from repro.launch import steps as steps_mod
    from repro.launch.serve import build_engine, serve, synthetic_prompts
    from repro.serving.engine import EngineConfig

    compile_s = {"backend": 0.0, "programs": 0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s["backend"] += secs
            compile_s["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compile_s["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    page = EngineConfig.page_size
    t0 = time.time()
    eng = build_engine(cfg, slots=slots, max_seq=max_seq, seed=seed,
                       num_pages=pages_needed(slots, lengths, max_new, page))
    jax.block_until_ready(eng.params)
    print(f"[smoke] init: {time.time() - t0:.2f}s "
          f"(weights {sum(a.nbytes for a in jax.tree.leaves(eng.params)):,}"
          f" B)")

    # Watch the STEADY (all-CHAI) decode jit: count its calls, and at the
    # first one check its program and compare its logits with the jnp
    # reference step on the same inputs (taken before the state is donated).
    fused_step = eng._chai_step
    ref_logits = jax.jit(unfused(
        lambda p, i, s, c: steps_mod.make_serve_step(
            cfg, chai=True, decode_ts=page)(p, i, s, c)[0]))
    seen = {"steps": 0}

    def chai_step(params, inputs, state, ctx):
        first = seen["steps"] == 0
        if first:
            hlo = fused_step.lower(params, inputs, state, ctx).compile()
            seen["kernel"] = "tpu_custom_call" in hlo.as_text()
            seen["ref"] = np.asarray(ref_logits(params, inputs, state, ctx),
                                     np.float32)
        logits, state = fused_step(params, inputs, state, ctx)
        if first:
            seen["fused"] = np.asarray(logits, np.float32)
        seen["steps"] += 1
        return logits, state

    eng._chai_step = chai_step

    waves = []
    for wave, name in enumerate(("cold", "warm")):
        before = dict(compile_s)
        prompts = synthetic_prompts(cfg, lengths, seed=seed + wave)
        done, wall = serve(eng, prompts, max_new)
        toks = sum(len(r.generated) for r in done)
        waves.append({"wall": wall, "tokens": toks, "done": done,
                      "compile": compile_s["backend"] - before["backend"],
                      "programs": compile_s["programs"] - before["programs"],
                      "hits": compile_s["cache_hits"] - before["cache_hits"]})
        print(f"[smoke] wave {name}: {len(done)} requests, {toks} tokens, "
              f"wall {wall:.2f}s, {toks / wall:.1f} tok/s, compile "
              f"{waves[-1]['compile']:.2f}s over {waves[-1]['programs']} "
              f"programs ({waves[-1]['hits']} from the cache)")
    return eng, waves, seen


def kernel_check(cfg, *, slots=SLOTS, lengths=LENGTHS, page=16, seed=SEED):
    """Run the paged fused CHAI decode kernel once against its ``ref.py``
    oracle (f32, highest matmul precision) on random bf16 pages at the
    model's head widths: H heads of head_dim over k_max representative
    rows, one slot per prompt length, pages scattered through the pool.
    Unit-normal queries and keys give each rep row's attention a spread
    of about e^±2, so a wrong head -> rep map moves the output by about
    its own size. Returns max |kernel - oracle| / max |oracle|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import chai_attention as ck
    from repro.kernels import ref

    h, r, hd = cfg.n_heads, cfg.k_max, cfg.head_dim
    n_pages = math.ceil(max(lengths) / page)
    rng = np.random.default_rng(seed)
    # Page 0 is the pool's null page; every slot gets scattered pages.
    bt = jnp.asarray((1 + rng.permutation(slots * n_pages))
                     .reshape(slots, n_pages), jnp.int32)
    h2c = np.stack([rng.permutation(np.concatenate(
        [np.arange(r), rng.integers(0, r, h - r)])) for _ in range(slots)])
    pos = jnp.asarray(np.asarray(lengths) - 1, jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_pool = 1 + slots * n_pages
    q = jax.random.normal(kq, (slots, r, hd), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (n_pool, r, page, hd), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (n_pool, h, page, hd), jnp.bfloat16)

    h2c = jnp.asarray(h2c, jnp.int32)
    got = ck.paged_chai_fused_decode(q, k_pool, bt, v_pool, bt, h2c, pos)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.paged_chai_fused_decode_ref)(
            q, k_pool, bt, v_pool, bt, h2c, pos)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return math.inf
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check(eng, waves, seen, n_sent):
    """Every check of the module docstring but the device's; returns the
    list of failures (empty when all passed)."""
    import numpy as np

    bad = []
    if not eng.chai_clustered:
        bad.append("engine is not on the clustered CHAI pool")
    n_req = sum(len(w["done"]) for w in waves)
    reasons = sorted({r.finish_reason for w in waves for r in w["done"]})
    print(f"[smoke] requests finished: {n_req} of {n_sent}, finish reasons "
          f"{reasons}")
    if n_req != n_sent or not set(reasons) <= {"length", "stop"}:
        bad.append(f"requests: {n_req} finished, reasons {reasons}")
    fs = eng.fault_stats()
    print(f"[smoke] decode_fallbacks={fs['decode_fallbacks']} "
          f"degraded_decode={fs['degraded_decode']} "
          f"quarantined={fs['quarantined']}")
    if fs["decode_fallbacks"] or fs["degraded_decode"] or fs["quarantined"]:
        bad.append(f"fault stats {fs}")
    print(f"[smoke] cluster_transitions={eng.cluster_transitions} "
          f"steady_decode_steps={seen['steps']} "
          f"decode_steps={eng.steps_executed}")
    if eng.cluster_transitions == 0 or seen["steps"] == 0:
        bad.append("CLUSTER or STEADY never ran")
    if seen.get("steps"):
        print(f"[smoke] tpu_custom_call in STEADY decode program: "
              f"{seen['kernel']}")
        if not seen["kernel"]:
            bad.append("STEADY decode program has no Mosaic kernel")
        diff = float(np.max(np.abs(seen["fused"] - seen["ref"])))
        scale = float(np.max(np.abs(seen["ref"])))
        rel = diff / max(scale, 1e-30)
        agree = float(np.mean(seen["fused"].argmax(-1)
                              == seen["ref"].argmax(-1)))
        print(f"[smoke] fused vs jnp STEADY logits: max|diff|={diff:.6g} "
              f"max|ref|={scale:.6g} rel={rel:.6g} (tol {LOGIT_TOL}) "
              f"argmax agreement {agree:.3f}")
        if not (np.isfinite(seen["fused"]).all() and rel <= LOGIT_TOL):
            bad.append(f"fused vs jnp logits rel diff {rel:.4g}")
    return bad


def main():
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package (src/repro) is not beside "
              "this script", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        print(f"chip_smoke: JAX found no device: {err}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 2

    from repro.core import cache as chai_cache
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import model_config

    print(f"[smoke] device: {dev.platform} {dev.device_kind} "
          f"x{len(devices)}; compile cache: {enable_compile_cache()}")
    cfg = model_config(ARCH, full=True, layers=LAYERS)
    print(f"[smoke] {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads} "
          f"kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"layers={cfg.n_layers} (cut from 32) k_max={cfg.k_max}")
    print(f"[smoke] workload: {SLOTS} slots, max_seq {MAX_SEQ}, prompts "
          f"{min(LENGTHS)}-{max(LENGTHS)} tokens, max_new {MAX_NEW}, "
          f"page 16, {pages_needed(SLOTS, LENGTHS, MAX_NEW)} pages per "
          f"pool")
    t0 = time.time()
    try:
        eng, waves, seen = run(cfg)
    except Exception as err:           # noqa: BLE001 — reported, exit 1
        print(f"chip_smoke: FAIL: serving raised {err!r}", file=sys.stderr)
        raise
    bad = check(eng, waves, seen, 2 * len(LENGTHS))
    stats = dev.memory_stats() or {}
    print(f"[smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")
    rel_k = kernel_check(cfg)
    print(f"[smoke] paged fused kernel vs ref.py oracle ({cfg.n_heads} "
          f"heads x {cfg.head_dim}, R={cfg.k_max}, {SLOTS} slots of "
          f"{min(LENGTHS)}-{max(LENGTHS)} tokens): rel={rel_k:.6g} "
          f"(tol {KERNEL_TOL})")
    if not rel_k <= KERNEL_TOL:
        bad.append(f"paged fused kernel vs oracle rel diff {rel_k:.4g}")
    kv_chai = chai_cache.kv_cache_bytes(cfg, SLOTS, MAX_SEQ, chai=True)
    kv_mha = chai_cache.kv_cache_bytes(cfg, SLOTS, MAX_SEQ, chai=False)
    print(f"[smoke] kv_bytes at {SLOTS}x{MAX_SEQ}: chai={kv_chai:,} "
          f"mha={kv_mha:,} ({100 * (1 - kv_chai / kv_mha):.1f}% saved); "
          f"allocator peak {eng.kv_bytes_peak():,} of "
          f"{eng.kv_bytes_capacity():,}")
    print(f"[smoke] total wall {time.time() - t0:.2f}s")
    if bad:
        for b in bad:
            print(f"chip_smoke: FAIL: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
