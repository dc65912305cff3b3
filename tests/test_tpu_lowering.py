"""Compile the main-path Pallas kernels for a TPU v5e chip, without a chip.

Each test lowers one kernel at chai-llama-7b widths (32 heads of 128,
R = 19 representative heads, pages of 16 tokens) for one device of a
described ``v5e:2x2`` topology and asserts the compiled program holds a
Mosaic kernel (``tpu_custom_call``). Interpret-mode tests cannot see what
these catch: BlockSpecs whose last two dims are not (8, 128)-tileable,
unsupported in-kernel ops, and kernels that outgrow VMEM.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import chai_attention as ck
from repro.kernels import flash_attention as fk

B, H, HD, R, PAGE, P, NP = 4, 32, 128, 19, 16, 16, 64
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
