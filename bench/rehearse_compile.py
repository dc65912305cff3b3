#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e and
print what each needs of the chip's memory. Run by hand, without a chip,
before spending chip time on a new configuration or pool size:

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py deepseek-moe-16b-l6

Programs: the weight draw, the decode steps (all-WARMUP, all-STEADY and
mixed), the CLUSTER transition, and the prefill buckets the cells' traffic
uses (whole-prompt, and suffix prefill where the engine keeps a prefix
cache). For each it prints ``memory_analysis()``: argument, output and
temporary bytes. Nothing runs, so it says nothing about time.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

GIB = 2 ** 30


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model_config as mc
    from bench import spec as spec_mod
    from bench import traffic_gen, weights
    from repro.core import cache as chai_cache
    from repro.core import clustering
    from repro.launch import steps as steps_mod
    from repro.models import transformer as tfm

    # Compile the Pallas kernels as the chip runs them: the kernels pick
    # the interpreter whenever JAX's default backend is the CPU.
    from repro.kernels import chai_attention, flash_attention
    chai_attention._interpret_default = lambda: False
    flash_attention._interpret_default = lambda: False

    name = argv[0]
    only = set(argv[1:])
    conf = spec_mod.load_config(name)
    cfg = mc.model_config(conf)
    ecfg = mc.engine_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    b, s, ps = ecfg.batch_slots, ecfg.max_seq, ecfg.page_size
    chai_on = cfg.chai.enabled and cfg.k_max > 0
    clustered = chai_on and cfg.is_mha
    p_structs = sds(tfm.param_structs(cfg)[0])
    st = sds(chai_cache.paged_state_structs(
        cfg, b, s, page_size=ps, dense_pages=ecfg.num_pages,
        chai_pages=ecfg.num_chai_pages if clustered else 0,
        chai=chai_on)[0])
    ctx = sds(clustering.ctx_structs(cfg, b)[0]) if chai_on else None
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    toks = {"tokens": i32(b)}
    n_slot_pages = s // ps

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    print(f"{cfg.name}: slots={b} max_seq={s} dense_pages={ecfg.num_pages} "
          f"chai_pages={ecfg.num_chai_pages if clustered else 0}")
    print(f"  weights {nbytes(p_structs) / GIB:.3f} GiB, decode state "
          f"{nbytes(st) / GIB:.3f} GiB")

    def show(label, fn, *args, donate=()):
        if only and label.split("[")[0] not in only:
            return
        t0 = time.perf_counter()
        try:
            c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        except Exception as err:            # noqa: BLE001 — reported
            print(f"  {label}: DOES NOT COMPILE: {err}")
            return
        m = c.memory_analysis()
        print(f"  {label}: args {m.argument_size_in_bytes / GIB:.3f} GiB, "
              f"out {m.output_size_in_bytes / GIB:.3f} GiB, temp "
              f"{m.temp_size_in_bytes / GIB:.3f} GiB, alias "
              f"{m.alias_size_in_bytes / GIB:.3f} GiB, kernel "
              f"{'tpu_custom_call' in c.as_text()}, compiled in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    show("weights", weights.make_fn(tfm.param_structs(cfg)[0]), key)
    show("mha_step", steps_mod.make_serve_step(cfg, chai=False,
                                               decode_ts=ps),
         p_structs, toks, st, donate=(2,))
    if chai_on:
        show("chai_step", steps_mod.make_serve_step(cfg, chai=True,
                                                    decode_ts=ps),
             p_structs, toks, st, ctx, donate=(2,))
        show("mixed_step", steps_mod.make_mixed_step(cfg, decode_ts=ps),
             p_structs, toks, st, ctx, donate=(2,))
        ident = lambda sc: clustering.identify_membership(sc, cfg)
        show("cluster", steps_mod.make_paged_slot_cluster(cfg, ident),
             st, ctx, i32(), i32(n_slot_pages), i32(n_slot_pages),
             donate=(0, 1))
    buckets, suffix = set(), set()
    for w in spec_mod.load_benchmark(ROOT)["workloads"]:
        if w["config"] != name:
            continue
        tr = traffic_gen.Traffic(spec_mod.load_traffic(w["traffic"]), 0,
                                 cfg.vocab_size, b)
        pre = max((len(p) for p in tr.prefixes), default=0)
        for n in tr.prompt_lengths():
            if pre:
                suffix.add(_bucket(n - pre, s))
                buckets.add(_bucket(pre + 1, s))
            else:
                buckets.add(_bucket(n, s))
    for t in sorted(buckets):
        show(f"prefill[{t}]", steps_mod.make_paged_slot_prefill(cfg, s),
             p_structs, i32(1, t), i32(), st, i32(), i32(n_slot_pages),
             i32(n_slot_pages), donate=(3,))
    if suffix and clustered and ecfg.relay_decode:
        from bench.driver import relay_structs
        for g, nmax in ((1, 2), (2, b // 2)):
            show(f"relay[{g},{nmax}]", steps_mod.make_relay_step(
                cfg, decode_ts=ps), p_structs, toks, st, ctx,
                sds(relay_structs(cfg, b, g, nmax, max(tr.prefixes[0].shape)
                                  if tr.prefixes else ps)), donate=(2,))
    for t in sorted(suffix):
        show(f"suffix_prefill[{t}]",
             steps_mod.make_paged_suffix_prefill(cfg, s),
             p_structs, i32(1, t), i32(), i32(), st, i32(),
             i32(n_slot_pages), i32(n_slot_pages), i32(n_slot_pages),
             i32(n_slot_pages), donate=(4,))
    return 0


def _bucket(t, cap):
    b = 1
    while b < t:
        b <<= 1
    return min(b, cap)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
