"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains *reduced* configs end-to-end (the full
configs are exercised allocation-free by the dry-run). On a TPU fleet the
same driver runs the full config: the mesh comes from ``jax.device_count()``
(elastic), shardings from the logical-axis rules, and the XLA flags below
enable the latency-hiding scheduler for compute/comm overlap.

TPU launch (documented for real runs; harmless here):
  LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_permute=true"
  XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true \
             --xla_tpu_megacore_fusion_allow_ags=true"
"""
from __future__ import annotations

import argparse

import jax

from repro.configs.base import get_config, reduced
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chai-llama-7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="train the full (not reduced) config — TPU fleets")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-parallel width for the elastic mesh")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    cfg = cfg.replace(dtype="float32") if not args.full else cfg

    mesh = None
    if jax.device_count() > 1:
        from repro.launch.mesh import elastic_mesh
        mesh = elastic_mesh(model_parallel=args.mesh_model)
        print(f"[train] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir, n_micro=args.n_micro)
    trainer = Trainer(cfg, data_cfg, tcfg, mesh=mesh)
    state, metrics = trainer.run()
    print(f"[train] done: loss={float(metrics['loss']):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
