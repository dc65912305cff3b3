"""Compile the main-path Pallas kernels for a TPU v5e chip, without a chip.

Each test lowers one kernel at chai-llama-7b widths (32 heads of 128,
R = 19 representative heads, pages of 16 tokens) for one device of a
described ``v5e:2x2`` topology and asserts the compiled program holds a
Mosaic kernel (``tpu_custom_call``). Interpret-mode tests cannot see what
these catch: BlockSpecs whose last two dims are not (8, 128)-tileable,
unsupported in-kernel ops, and kernels that outgrow VMEM. The last test
compiles the engine's own decode step programs and reads their optimized
HLO for copies of the KV pools.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chai_attention as ck
from repro.kernels import flash_attention as fk

B, H, HD, R, PAGE, P, NP, L = 4, 32, 128, 19, 16, 16, 64, 6
BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("variant", ["bf16", "f32", "int8", "emit_state",
                                     "share_values", "gqa"])
def test_paged_chai_fused_decode_lowers(one_chip, variant):
    kv_dt = {"f32": F32, "int8": I8}.get(variant, BF16)
    r, kv_k, rpg, h = R, R, 1, H
    if variant == "gqa":                      # 48 query heads over 8 KV
        r, kv_k, rpg, h = 48, 8, 6, 48
    kv_v = r if variant == "share_values" else (kv_k if rpg > 1 else h)
    scaled = variant == "int8"

    def fn(q, kp, vp, bt, h2c, pos, *scales):
        ks, vs = scales if scaled else (None, None)
        return ck.paged_chai_fused_decode(
            q, kp, bt, vp, bt, h2c, pos, k_scale_pool=ks, v_scale_pool=vs,
            reps_per_group=rpg, share_values=variant == "share_values",
            emit_state=variant == "emit_state", interpret=False)

    shapes = [((B, r, HD), BF16), ((NP, kv_k, PAGE, HD), kv_dt),
              ((NP, kv_v, PAGE, HD), kv_dt), ((B, P), I32), ((B, h), I32),
              ((B,), I32)]
    if scaled:
        shapes += [((NP, kv_k, PAGE), F32), ((NP, kv_v, PAGE), F32)]
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("int8", [False, True])
def test_paged_chai_fused_decode_stacked_lowers(one_chip, int8):
    """The decode state's layer-stacked pools, read at a traced layer."""
    kv_dt = I8 if int8 else BF16

    def fn(q, kp, vp, bt, h2c, pos, layer, *scales):
        ks, vs = scales if int8 else (None, None)
        return ck.paged_chai_fused_decode(
            q, kp, bt, vp, bt, h2c, pos, layer=layer, k_scale_pool=ks,
            v_scale_pool=vs, interpret=False)

    shapes = [((B, R, HD), BF16), ((L, NP, R, PAGE, HD), kv_dt),
              ((L, NP, H, PAGE, HD), kv_dt), ((B, P), I32), ((B, H), I32),
              ((B,), I32), ((), I32)]
    if int8:
        shapes += [((L, NP, R, PAGE), F32), ((L, NP, H, PAGE), F32)]
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("cell", ["dsmoe16b", "nemotron15b"])
def test_paged_chai_fused_decode_cell_shapes_lower(one_chip, cell):
    """The multi-page kernel at a benchmark configuration's own shapes,
    the layer stacks read at a traced layer. DeepSeekMoE
    (``dsmoe16b.decode-long``): 24 slots of 256 pages of 16, 13 clustered
    K rows and 16 V heads of 128, 4800 clustered and 6000 dense pages in a
    6-layer stack. Nemotron-4 15B on the GQA path: 32 slots of 288
    pages, 48 query heads over 8 KV heads, 5 representatives per group,
    10000 pages in an 8-layer stack."""
    if cell == "dsmoe16b":
        b, p, n_layers, h, kv_k, kv_v, rpg, nk, nv = (24, 256, 6, 16, 13,
                                                     16, 1, 4800, 6000)
    else:
        b, p, n_layers, h, kv_k, kv_v, rpg, nk, nv = (32, 288, 8, 48, 8, 8,
                                                     5, 10000, 10000)
    assert ck.decode_tile_pages(PAGE, kv_k + kv_v, HD, BF16, p) == 8

    def fn(q, kp, vp, bt_k, bt_v, h2c, pos, layer):
        return ck.paged_chai_fused_decode(
            q, kp, bt_k, vp, bt_v, h2c, pos, layer=layer,
            reps_per_group=rpg, interpret=False)

    _compile(one_chip, fn, ((b, kv_k * rpg, HD), BF16),
             ((n_layers, nk, kv_k, PAGE, HD), BF16),
             ((n_layers, nv, kv_v, PAGE, HD), BF16), ((b, p), I32),
             ((b, p), I32), ((b, h), I32), ((b,), I32), ((), I32))


@pytest.mark.parametrize("kind", ["bf16", "int8", "scale"])
def test_paged_kv_write_rows_lowers(one_chip, kind):
    """The token-row write lowers and aliases the donated pool to its
    output. A K/V pool is not copied; an f32 scale pool is relaid at entry
    and exit (the TPU keeps it with the page axis minor)."""
    dt = {"bf16": BF16, "int8": I8}.get(kind, F32)
    blk = (H, PAGE) if kind == "scale" else (H, PAGE, HD)
    args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip) for sh, d in
            [((L, NP) + blk, dt), ((), I32), ((B,), I32), ((B,), I32),
             ((B, H) + blk[2:], dt)]]
    fn = lambda pool, layer, page, row, new: ck.paged_write_rows(
        pool, layer, page, row, new, interpret=False)
    text = jax.jit(fn, donate_argnums=0).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "output_to_operand_aliasing" in text
    pool_shape = re.escape(f"[{','.join(map(str, (L, NP) + blk))}]")
    assert (re.search(pool_shape + r"\S* copy", text) is None) == (
        kind != "scale")


@pytest.mark.parametrize("int8", [False, True])
def test_chai_fused_decode_lowers(one_chip, int8):
    s, kv_dt = P * PAGE, I8 if int8 else BF16

    def fn(q, k, v, h2c, pos, *scales):
        ks, vs = scales if int8 else (None, None)
        return ck.chai_fused_decode(q, k, v, h2c, pos, k_scale=ks,
                                    v_scale=vs, ts=PAGE, interpret=False)

    shapes = [((B, R, HD), BF16), ((B, R, s, HD), kv_dt),
              ((B, H, s, HD), kv_dt), ((B, H), I32), ((B,), I32)]
    if int8:
        shapes += [((B, R, s), F32), ((B, H, s), F32)]
    _compile(one_chip, fn, *shapes)


def test_paged_decode_lowers(one_chip):
    _compile(one_chip,
             lambda q, kv, bt, pos: fk.paged_decode(q, kv, bt, bt, pos,
                                                    interpret=False),
             ((B, H, HD), BF16), ((NP, H, PAGE, HD), BF16), ((B, P), I32),
             ((B,), I32))


def test_flash_decode_lowers(one_chip):
    s = P * PAGE
    _compile(one_chip,
             lambda q, k, v, pos: fk.flash_decode(q, k, v, pos, ts=PAGE,
                                                  interpret=False),
             ((B, H, HD), BF16), ((B, H, s, HD), BF16),
             ((B, H, s, HD), BF16), ((B,), I32))


@pytest.mark.parametrize("emit_state", [False, True])
def test_flash_prefill_lowers(one_chip, emit_state):
    t = 512
    _compile(one_chip,
             lambda q, k, v: fk.flash_prefill(q, k, v, emit_state=emit_state,
                                              interpret=False),
             ((1, t, H, HD), BF16), ((1, t, H, HD), BF16),
             ((1, t, H, HD), BF16))


@pytest.mark.parametrize("int8", [False, True])
def test_paged_prefix_attend_lowers(one_chip, int8):
    kv_dt = I8 if int8 else BF16

    def fn(q, kv, bt, plen, *scales):
        ks, vs = scales if int8 else (None, None)
        return fk.paged_prefix_attend(q, kv, bt, bt, plen, k_scale_pool=ks,
                                      v_scale_pool=vs, interpret=False)

    shapes = [((1, 256, H, HD), BF16), ((NP, H, PAGE, HD), kv_dt),
              ((1, P), I32), ((1,), I32)]
    if int8:
        shapes += [((NP, H, PAGE), F32), ((NP, H, PAGE), F32)]
    _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("int8", [False, True])
def test_relay_prefix_decode_lowers(one_chip, int8):
    g, n, sp = 2, 4, P * PAGE
    kv_dt = I8 if int8 else BF16

    def fn(q, k, v, k_row, a_row, v_row, plen, *scales):
        ks, vs = scales if int8 else (None, None)
        return ck.relay_prefix_decode(q, k, v, k_row, a_row, v_row, plen,
                                      k_scale=ks, v_scale=vs, ts=PAGE,
                                      interpret=False)

    shapes = [((g, n * R, HD), BF16), ((g, R, sp, HD), kv_dt),
              ((g, H, sp, HD), kv_dt), ((g, n * R), I32), ((g, n * H), I32),
              ((g, n * H), I32), ((g,), I32)]
    if int8:
        shapes += [((g, R, sp), F32), ((g, H, sp), F32)]
    _compile(one_chip, fn, *shapes)


# HLO instructions that may carry a pool's shape without moving it.
_PLUMBING = {"parameter", "tuple", "get-tuple-element", "bitcast",
             "custom-call"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("program", ["chai_step", "mixed_step"])
def test_decode_steps_copy_no_whole_pool(one_chip, monkeypatch, program, kv):
    """The engine's all-STEADY (``make_serve_step(chai=True)``) and mixed
    decode programs at a small paged MHA CHAI configuration: no instruction
    of their optimized HLO may have the shape of one layer's pool or of a
    whole stack of pools (dense ``kvp``, clustered ``cp``), apart from
    parameters, tuple plumbing, bitcasts and the kernels' aliased outputs:
    every decode access goes to the stack at (layer, page). A per-layer
    slice, relayout or write-back of a pool would show here. The pools are
    large enough (2048 + 1536 pages) that the compiler does not prefetch a
    whole pool into VMEM. int8 scale pools: the same holds for one layer's
    shape; their stacks are still relaid at the program's entry and exit
    (the TPU keeps an f32 (..., rows, 16) array with the page axis minor,
    and the Pallas kernels take it row-major)."""
    from repro.configs.base import get_config, reduced
    from repro.core import cache as chai_cache
    from repro.core import clustering
    from repro.launch import steps
    from repro.models import transformer as tfm
    monkeypatch.setattr(ck, "_interpret_default", lambda: False)
    monkeypatch.setattr(fk, "_interpret_default", lambda: False)
    cfg = reduced(get_config("chai-llama-7b"), n_layers=2, d_model=1024,
                  n_heads=8, d_ff=256, vocab=256, dtype="bfloat16")
    cfg = cfg.with_chai(enabled=True, warmup_tokens=3)
    if kv == "int8":
        cfg = cfg.replace(kv_cache_dtype="int8")
    b, max_seq = 2, 128
    sds = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), tree)
    state = sds(chai_cache.paged_state_structs(
        cfg, b, max_seq, page_size=PAGE, dense_pages=2048,
        chai_pages=1536)[0])
    args = (sds(tfm.param_structs(cfg)[0]),
            {"tokens": jax.ShapeDtypeStruct((b,), I32, sharding=one_chip)},
            state, sds(clustering.ctx_structs(cfg, b)[0]))
    fn = (steps.make_serve_step(cfg, chai=True, decode_ts=PAGE)
          if program == "chai_step"
          else steps.make_mixed_step(cfg, decode_ts=PAGE))
    text = jax.jit(fn, donate_argnums=2).lower(*args).compile().as_text()
    forbidden = {}
    for key in ("kvp", "cp", "kvp_scale", "cp_scale"):
        if key in state:
            shape = state[key].shape
            forbidden[shape[1:]] = key + " layer"
            if not key.endswith("_scale"):
                forbidden[shape] = key + " stack"
    assert len(forbidden) == (4 if kv == "bf16" else 6)
    moved = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(3) in _PLUMBING:
            continue
        shape = tuple(int(d) for d in m.group(2).split(",") if d)
        if shape in forbidden:
            moved.append(f"{forbidden[shape]}: {m.group(1)} {m.group(3)}")
    assert not moved, moved
