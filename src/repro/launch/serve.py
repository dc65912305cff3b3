"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Runs the serving engine — continuous batching over paged KV, CHAI on
(PREFILL -> WARMUP -> CLUSTER -> STEADY) — with random weights from seed 0
and synthetic prompts, and reports TTFT / latency / KV bytes
for CHAI vs MHA.

By default the config is ``reduced`` (d_model 64, fp32) so it runs on a
CPU. ``--full`` serves the registered config at its published widths in
its own dtype, with only the layer count cut by ``--layers``:

    python -m repro.launch.serve --arch chai-llama-7b --full --layers 16 \\
        --slots 8 --max-seq 2048 --prompt-len 1024 --max-new 32

``chip_smoke.py`` drives the same functions on a TPU.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import get_config, reduced, with_depth
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine


def model_config(arch, *, full=False, layers=0, chai=True):
    """``full``: published widths and dtype, depth cut to ``layers`` (0 =
    all). Otherwise the reduced CPU-scale config (``layers`` 0 = its
    default depth)."""
    cfg = get_config(arch)
    if full:
        cfg = with_depth(cfg, layers) if layers else cfg
    else:
        cfg = reduced(cfg, n_layers=layers or None)
    return cfg.with_chai(enabled=True) if chai else cfg


def build_engine(cfg, *, slots, max_seq, seed=0, num_pages=0, chai=True):
    """Random weights from ``seed`` and an engine with default settings.
    ``num_pages`` sizes both page pools (0 = the engine's worst-case auto
    size)."""
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    ecfg = EngineConfig(batch_slots=slots, max_seq=max_seq, use_chai=chai,
                        num_pages=num_pages, num_chai_pages=num_pages)
    return ServingEngine(cfg, params, ecfg)


def synthetic_prompts(cfg, lengths, seed=0):
    """One random-token prompt per entry of ``lengths``."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n) for n in lengths]


def serve(eng, prompts, max_new):
    """Submit ``prompts`` and run the engine until they all finish.
    Returns (this wave's finished requests, wall seconds). Every step
    reads its sampled tokens back to the host, so the wall time ends
    with the device's last step."""
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    t0 = time.time()
    eng.run()
    wall = time.time() - t0
    return eng.reap_done(), wall


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chai-llama-7b")
    ap.add_argument("--full", action="store_true",
                    help="published widths in the config's own dtype")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (0 = default)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--no-chai", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = model_config(args.arch, full=args.full, layers=args.layers,
                       chai=not args.no_chai)
    eng = build_engine(cfg, slots=args.slots, max_seq=args.max_seq,
                       chai=not args.no_chai)
    prompts = synthetic_prompts(cfg, [args.prompt_len] * args.requests)
    done, wall = serve(eng, prompts, args.max_new)

    ttfts = [r.ttft for r in done]
    lats = [r.latency for r in done]
    n_tok = sum(len(r.generated) for r in done)
    print(f"[serve] arch={cfg.name} chai={eng.chai_on} "
          f"requests={len(done)} tokens={n_tok}")
    print(f"[serve] wall={wall:.2f}s tok/s={n_tok / wall:.1f} "
          f"ttft_mean={np.mean(ttfts)*1e3:.0f}ms "
          f"lat_mean={np.mean(lats)*1e3:.0f}ms")
    print(f"[serve] kv_bytes chai={eng.kv_bytes(chai=True):,} "
          f"mha={eng.kv_bytes(chai=False):,} "
          f"saving={100*(1-eng.kv_bytes(chai=True)/max(eng.kv_bytes(chai=False),1)):.1f}%")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
