"""Per-slot paged KV state: quantization round-trips, per-slot
compaction parity with the cohort flow's whole-batch gather, and the
insert/reset round trip of one slot's state."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.core import cache as chai_cache
from repro.core import clustering
from repro.models.transformer import init_decode_state


# ------------------------------------------------------------- quant -------
@pytest.mark.parametrize("shape,seed,scale", [
    ((4, 16), 0, 1.0), ((2, 3, 8), 1, 100.0), ((7,  64), 2, 1e-3),
    ((1, 1, 1, 4), 3, 1.0), ((5, 32), 4, 1e4),
])
def test_quant_rows_roundtrip_bound(shape, seed, scale):
    """Property: per-row symmetric int8 reconstructs within half a grid
    step of the row scale, for any shape/magnitude."""
    x = jnp.asarray(np.random.default_rng(seed).normal(size=shape) * scale,
                    jnp.float32)
    q, s = chai_cache.quant_rows(x)
    assert q.dtype == jnp.int8 and s.shape == shape[:-1]
    err = np.abs(np.asarray(chai_cache.dequant_rows(q, s)) - np.asarray(x))
    bound = 0.5 * np.asarray(s)[..., None] + 1e-7
    assert (err <= bound).all()
    # int8 range respected, scale strictly positive
    assert np.asarray(q).min() >= -127 and np.asarray(q).max() <= 127
    assert (np.asarray(s) > 0).all()


def test_quant_rows_zero_row_stable():
    q, s = chai_cache.quant_rows(jnp.zeros((3, 8)))
    assert (np.asarray(q) == 0).all() and np.isfinite(np.asarray(s)).all()
    assert (np.asarray(chai_cache.dequant_rows(q, s)) == 0).all()


# ----------------------------------------------------- per-slot compact ----
def _mha_cfg(share_values, int8):
    cfg = reduced(get_config("chai-llama-7b"), n_layers=2, d_model=32,
                  d_ff=64, vocab=64).replace(dtype="float32")
    if int8:
        cfg = cfg.replace(kv_cache_dtype="int8")
    return cfg.with_chai(enabled=True, share_values=share_values,
                         cluster_counts=(3,) * cfg.n_attn_layers)


@pytest.mark.parametrize("share_values", [False, True])
@pytest.mark.parametrize("int8", [False, True])
def test_compact_kv_slot_paged_matches_whole_batch(rng, share_values, int8):
    """Paged per-slot compaction == the cohort path's whole-batch
    ``compact_kv``: inserting each slot's dense rows into pages,
    compacting, and densifying the clustered pages reproduces
    ``kg_chai`` (and scales / ``vg_chai``) bit-for-bit — while the dense
    block-table rows are nulled (the pages become freeable)."""
    cfg = _mha_cfg(share_values, int8)
    b, s, page = 3, 16, 8
    n_slot = s // page
    dense = init_decode_state(cfg, b, s)
    for k in dense:
        if k == "pos":
            dense[k] = jnp.full((b,), s - 1, jnp.int32)
        elif dense[k].dtype == jnp.int8:
            dense[k] = jnp.asarray(
                rng.integers(-127, 128, size=dense[k].shape), jnp.int8)
        else:
            dense[k] = jnp.asarray(rng.normal(size=dense[k].shape),
                                   dense[k].dtype)
    k_max, _ = clustering.chai_widths(cfg)
    reps = jnp.asarray(
        rng.integers(0, cfg.n_heads, size=(cfg.n_attn_layers, b, k_max)),
        jnp.int32)

    whole = chai_cache.compact_kv(dict(dense), {"reps": reps}, cfg)

    n_chai = (2 if share_values else 1) * b * n_slot + 1
    paged = chai_cache.init_paged_state(
        cfg, b, s, page_size=page, dense_pages=2 * b * n_slot + 1,
        chai_pages=n_chai)
    dense_pool = chai_cache.PagePool(2 * b * n_slot + 1, page)
    chai_pool = chai_cache.PagePool(n_chai, page)
    pages = []
    for i in range(b):
        mini = {k: v[:, i:i + 1] if v.ndim > 1 else v[i:i + 1]
                for k, v in dense.items()}
        pg = {"kg": dense_pool.alloc(n_slot), "vg": dense_pool.alloc(n_slot),
              "kc": chai_pool.alloc(n_slot)}
        if share_values:
            pg["vc"] = chai_pool.alloc(n_slot)
        pages.append(pg)
        paged = chai_cache.insert_slot_paged(
            paged, mini, i, jnp.asarray(pg["kg"], jnp.int32),
            jnp.asarray(pg["vg"], jnp.int32))
    compact = jax.jit(chai_cache.compact_kv_slot_paged,
                      static_argnames=("cfg",), donate_argnums=(0,))
    for i in range(b):
        paged = compact(paged, {"reps": reps[:, i]}, cfg, jnp.int32(i),
                        jnp.asarray(pages[i]["kc"], jnp.int32),
                        jnp.asarray(pages[i].get("vc", pages[i]["kc"]),
                                    jnp.int32))

    def densify(pool, bt):     # (nG, nP, rows, page[,hd]), (b, P) -> slot i
        return np.concatenate(
            [np.asarray(pool[:, bt[i]]).swapaxes(1, 2).reshape(
                pool.shape[0], pool.shape[2], -1, *pool.shape[4:])
             [:, None] for i in range(b)], axis=1)

    bt_kc = np.asarray(paged["bt_kc"])
    np.testing.assert_array_equal(np.asarray(whole["kg_chai"]),
                                  densify(np.asarray(paged["cp"]), bt_kc))
    if int8:
        np.testing.assert_array_equal(
            np.asarray(whole["kg_chai_scale"]),
            densify(np.asarray(paged["cp_scale"]), bt_kc))
    if share_values:
        np.testing.assert_array_equal(
            np.asarray(whole["vg_chai"]),
            densify(np.asarray(paged["cp"]), np.asarray(paged["bt_vc"])))
    # dense K tables nulled (pages freeable); V tables nulled only under
    # share_values; every slot advanced to STEADY
    assert (np.asarray(paged["bt_kg"]) == chai_cache.NULL_PAGE).all()
    assert ((np.asarray(paged["bt_vg"]) == chai_cache.NULL_PAGE).all()
            == share_values)
    assert (np.asarray(paged["phase"]) == chai_cache.PHASE_STEADY).all()


@pytest.mark.parametrize("arch", ["gemma2-9b", "recurrentgemma-9b",
                                  "rwkv6-1.6b"])
def test_insert_and_reset_slot_paged_roundtrip(rng, arch):
    """``insert_slot_paged`` lands one prefilled request's ring or
    recurrent state (and, with a global layer, its K/V pages and block
    tables) in its slot — phase WARMUP, warmup scores cleared — and
    leaves every other slot and page bit for bit; ``reset_slot_paged``
    frees the slot (phase FREE, pos 0, block tables null)."""
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32").with_chai(enabled=True)
    b, s, page = 3, 32, 8
    n_slot = s // page
    state = chai_cache.init_paged_state(cfg, b, s, page_size=page,
                                        dense_pages=2 * b * n_slot + 1)
    assert ("kvp" in state) == (cfg.n_global_layers > 0)
    for k, v in state.items():
        if k.startswith("bt_") or k in ("pos", "phase"):
            continue
        state[k] = jnp.asarray(rng.normal(size=v.shape), v.dtype)
    state["pos"] = jnp.asarray([3, 5, 9], jnp.int32)
    state["phase"] = jnp.full((b,), chai_cache.PHASE_STEADY, jnp.int32)
    mini = init_decode_state(cfg, 1, s)
    for k, v in mini.items():
        mini[k] = jnp.asarray(rng.normal(size=v.shape), v.dtype)
    mini["pos"] = jnp.full((1,), 7, jnp.int32)
    pages = np.zeros((n_slot,), np.int32)
    if "kvp" in state:
        pages[:] = np.arange(1, n_slot + 1)
    vpages = np.where(pages > 0, pages + n_slot, 0).astype(np.int32)

    out = chai_cache.insert_slot_paged(state, mini, 1, jnp.asarray(pages),
                                       jnp.asarray(vpages))
    for k, v in out.items():
        new, old = np.asarray(v), np.asarray(state[k])
        if k in ("kvp", "kvp_scale"):
            continue
        axis = 0 if new.ndim == 1 or k.startswith("bt_") else 1
        for i in (0, 2):                 # other slots: bit for bit
            np.testing.assert_array_equal(np.take(new, i, axis),
                                          np.take(old, i, axis), k)
        if k in mini:                    # the request's slot
            np.testing.assert_array_equal(
                np.take(new, 1, axis), np.take(np.asarray(mini[k]), 0, axis),
                k)
    assert int(out["phase"][1]) == chai_cache.PHASE_WARMUP
    if "chai_scores" in out:            # rwkv6: no attention to cluster
        assert (np.asarray(out["chai_scores"][:, 1]) == 0).all()
    if "kvp" in out:
        pool, old = np.asarray(out["kvp"]), np.asarray(state["kvp"])
        ng, _, kv, _, hd = pool.shape
        for key, pg in (("kg", pages), ("vg", vpages)):
            rows = np.asarray(mini[key])[:, 0].reshape(ng, kv, n_slot, page,
                                                       hd)
            np.testing.assert_array_equal(pool[:, pg],
                                          rows.transpose(0, 2, 1, 3, 4))
        untouched = np.setdiff1d(np.arange(pool.shape[1]),
                                 np.concatenate([pages, vpages]))
        np.testing.assert_array_equal(pool[:, untouched], old[:, untouched])
        np.testing.assert_array_equal(np.asarray(out["bt_kg"][1]), pages)
        np.testing.assert_array_equal(np.asarray(out["bt_vg"][1]), vpages)

    out2 = chai_cache.reset_slot_paged(out, 1)
    assert int(out2["phase"][1]) == chai_cache.PHASE_FREE
    assert int(out2["pos"][1]) == 0
    for k in [k for k in out2 if k.startswith("bt_")] + ["pos", "phase"]:
        if k.startswith("bt_"):
            assert (np.asarray(out2[k][1]) == chai_cache.NULL_PAGE).all(), k
        for i in (0, 2):
            np.testing.assert_array_equal(np.asarray(out2[k][i]),
                                          np.asarray(out[k][i]), k)
