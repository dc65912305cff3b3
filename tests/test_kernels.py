"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Every Pallas kernel is exercised across sequence lengths, head counts,
GQA ratios, windows, tile sizes, and dtypes, asserting allclose against
ref.py. interpret=True executes the kernel body in Python on CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import chai_attention as ck
from repro.kernels import flash_attention as fk
from repro.kernels import ops, ref

TOL = dict(rtol=2e-3, atol=2e-3)
# bf16-valued outputs carry ~2^-8 quantization; oracles compute in f32.
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)


def _tol(dtype):
    return TOL_BF16 if dtype == jnp.bfloat16 else TOL


def _mk(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# --------------------------------------------------------------- decode ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,hd,ts,window", [
    (1, 4, 4, 32, 16, 8, 0),
    (2, 8, 2, 64, 32, 16, 0),       # GQA 4:1
    (3, 6, 1, 48, 8, 16, 0),        # MQA
    (2, 4, 4, 64, 32, 64, 0),       # single tile
    (2, 8, 4, 64, 16, 16, 24),      # sliding window
])
def test_flash_decode_sweep(rng, dtype, b, h, kv, s, hd, ts, window):
    q = _mk(rng, (b, h, hd), dtype)
    kc = _mk(rng, (b, kv, s, hd), dtype)
    vc = _mk(rng, (b, kv, s, hd), dtype)
    pos = jnp.asarray(rng.integers(1, s, size=b), jnp.int32)
    out = fk.flash_decode(q, kc, vc, pos, window=window, ts=ts,
                          interpret=True)
    want = ref.flash_decode_ref(q, kc, vc, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,t,h,kv,hd,tq,ts,window,offset", [
    (1, 16, 4, 4, 16, 8, 8, 0, 0),
    (2, 32, 8, 2, 32, 8, 16, 0, 0),
    (1, 16, 4, 1, 16, 16, 16, 0, 0),
    (2, 16, 4, 4, 16, 8, 8, 12, 0),    # windowed
    (1, 8, 4, 4, 16, 8, 8, 0, 8),      # offset continuation (prefill chunk)
])
def test_flash_prefill_sweep(rng, dtype, b, t, h, kv, hd, tq, ts, window,
                             offset):
    q = _mk(rng, (b, t, h, hd), dtype)
    s = t + offset
    k = _mk(rng, (b, s, kv, hd), dtype)
    v = _mk(rng, (b, s, kv, hd), dtype)
    out = fk.flash_prefill(q, k, v, offset=offset, window=window, tq=tq,
                           ts=ts, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, offset=offset, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ----------------------------------------------------------------- CHAI ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,r,s,hd,ts", [
    (1, 8, 3, 32, 16, 8),
    (2, 16, 5, 64, 32, 16),
    (2, 4, 4, 32, 16, 32),    # k == H (degenerate: no clustering)
    (3, 8, 1, 24, 8, 8),      # single cluster
])
def test_chai_decode_mha_sweep(rng, dtype, b, h, r, s, hd, ts):
    """MHA regime: clustered K cache has R rows; V cache has all H rows."""
    q_rep = _mk(rng, (b, r, hd), dtype)
    kc = _mk(rng, (b, r, s, hd), dtype)
    vc = _mk(rng, (b, h, s, hd), dtype)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    pos = jnp.asarray(rng.integers(1, s, size=b), jnp.int32)
    sc = ck.chai_qk(q_rep, kc, pos, ts=ts, interpret=True)
    a = ck.row_softmax(sc, interpret=True)
    out = ck.chai_av(a, vc, h2c, ts=ts, interpret=True)
    want = ref.chai_decode_ref(q_rep, kc, vc, h2c, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,kv,rpg,s,hd,ts", [
    (2, 4, 2, 32, 16, 8),     # GQA: 4 groups x 2 reps each
    (1, 2, 3, 64, 32, 16),
])
def test_chai_qk_gqa_groups(rng, b, kv, rpg, s, hd, ts):
    """GQA regime: rep j reads K of group j // reps_per_group."""
    r_total = kv * rpg
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    kc = _mk(rng, (b, kv, s, hd), jnp.float32)
    pos = jnp.asarray(rng.integers(1, s, size=b), jnp.int32)
    sc = ck.chai_qk(q_rep, kc, pos, reps_per_group=rpg, ts=ts,
                    interpret=True)
    a = ck.row_softmax(sc, interpret=True)
    want = ref.chai_scores_ref(q_rep, kc, pos, reps_per_group=rpg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), **TOL)


def test_chai_av_shared_membership(rng):
    """h2c may be (H,) — broadcast across batch."""
    b, h, r, s, hd = 2, 8, 3, 32, 16
    a = jnp.asarray(rng.random((b, r, s)), jnp.float32)
    vc = _mk(rng, (b, h, s, hd), jnp.float32)
    h2c = jnp.asarray(rng.integers(0, r, size=h), jnp.int32)
    out = ops.chai_decode_attention  # noqa: F841  (public API import check)
    got = ck.chai_av(a, vc, h2c, ts=8, interpret=True)
    want = ref.chai_av_ref(a, vc, h2c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_fused_op_matches_ref(rng):
    b, h, r, s, hd = 2, 8, 4, 64, 32
    q_rep = _mk(rng, (b, r, hd), jnp.float32)
    kc = _mk(rng, (b, r, s, hd), jnp.float32)
    vc = _mk(rng, (b, h, s, hd), jnp.float32)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    pos = jnp.asarray([13, 60], jnp.int32)
    got = ops.chai_decode_attention(q_rep, kc, vc, h2c, pos, ts=16,
                                    interpret=True)
    want = ref.chai_decode_ref(q_rep, kc, vc, h2c, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_decode_masks_future_positions(rng):
    """pos masking: entries beyond pos must not affect the output."""
    b, h, s, hd = 1, 4, 32, 16
    q = _mk(rng, (b, h, hd), jnp.float32)
    kc = _mk(rng, (b, h, s, hd), jnp.float32)
    vc = _mk(rng, (b, h, s, hd), jnp.float32)
    pos = jnp.asarray([10], jnp.int32)
    out1 = fk.flash_decode(q, kc, vc, pos, ts=8, interpret=True)
    kc2 = kc.at[:, :, 11:].set(999.0)
    vc2 = vc.at[:, :, 11:].set(-999.0)
    out2 = fk.flash_decode(q, kc2, vc2, pos, ts=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------- paged ----
def _mk_tables(rng, b, n_pages, n_pool, n=1):
    """n block tables of distinct physical pages (page 0 = null sink,
    never allocated; no two slots/tables share a page)."""
    assert n_pool - 1 >= n * b * n_pages
    perm = rng.permutation(np.arange(1, n_pool))[:n * b * n_pages]
    tables = perm.reshape(n, b, n_pages)
    out = tuple(jnp.asarray(t, jnp.int32) for t in tables)
    return out[0] if n == 1 else out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,n_pages,page,hd,window", [
    (1, 4, 4, 4, 8, 16, 0),
    (2, 8, 2, 4, 16, 32, 0),      # GQA 4:1
    (3, 6, 1, 6, 8, 8, 0),        # MQA
    (2, 4, 4, 1, 64, 32, 0),      # single page
    (2, 8, 4, 4, 16, 16, 24),     # sliding window
])
def test_paged_decode_sweep(rng, dtype, b, h, kv, n_pages, page, hd,
                            window):
    n_pool = 2 * b * n_pages + 1
    pool = _mk(rng, (n_pool, kv, page, hd), dtype)
    bt_k, bt_v = _mk_tables(rng, b, n_pages, n_pool, n=2)
    q = _mk(rng, (b, h, hd), dtype)
    pos = jnp.asarray(rng.integers(1, n_pages * page, size=b), jnp.int32)
    out = fk.paged_decode(q, pool, bt_k, bt_v, pos, window=window,
                          interpret=True)
    want = ref.paged_decode_ref(q, pool, bt_k, bt_v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_paged_decode_matches_dense_flash_decode(rng):
    """Scatter a dense cache into pool pages: the paged kernel must
    reproduce the dense kernel on the same logical contents."""
    b, h, kv, n_pages, page, hd = 2, 4, 4, 4, 8, 16
    s = n_pages * page
    kc = _mk(rng, (b, kv, s, hd), jnp.float32)
    vc = _mk(rng, (b, kv, s, hd), jnp.float32)
    n_pool = 2 * b * n_pages + 1
    bt_k, bt_v = _mk_tables(rng, b, n_pages, n_pool, n=2)
    pool = jnp.asarray(rng.normal(size=(n_pool, kv, page, hd)), jnp.float32)
    kp = kc.reshape(b, kv, n_pages, page, hd).transpose(2, 0, 1, 3, 4)
    vp = vc.reshape(b, kv, n_pages, page, hd).transpose(2, 0, 1, 3, 4)
    for i in range(b):
        for j in range(n_pages):
            pool = pool.at[bt_k[i, j]].set(kp[j, i])
            pool = pool.at[bt_v[i, j]].set(vp[j, i])
    q = _mk(rng, (b, h, hd), jnp.float32)
    pos = jnp.asarray([s - 1, 13], jnp.int32)
    got = fk.paged_decode(q, pool, bt_k, bt_v, pos, interpret=True)
    want = fk.flash_decode(q, kc, vc, pos, ts=page, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_paged_decode_null_pages_masked(rng):
    """Unallocated block-table entries point at the null sink page 0;
    whatever garbage lives there must not affect the output."""
    b, h, n_pages, page, hd = 1, 4, 4, 8, 16
    n_pool = 2 * n_pages + 1
    pool = _mk(rng, (n_pool, h, page, hd), jnp.float32)
    bt = _mk_tables(rng, b, n_pages, n_pool)
    # only the first 2 logical pages are allocated; pos stays inside them
    bt_trunc = bt.at[:, 2:].set(0)
    pos = jnp.asarray([2 * page - 1], jnp.int32)
    q = _mk(rng, (b, h, hd), jnp.float32)
    out1 = fk.paged_decode(q, pool, bt_trunc, bt_trunc, pos, interpret=True)
    poisoned = pool.at[0].set(999.0)
    out2 = fk.paged_decode(q, poisoned, bt_trunc, bt_trunc, pos,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b,kv,rpg,n_pages,page,hd", [
    (2, 3, 1, 4, 8, 16),       # MHA clustered pool (KV == R == k_max)
    (1, 2, 3, 4, 16, 32),      # GQA groups
])
def test_paged_chai_qk_sweep(rng, b, kv, rpg, n_pages, page, hd):
    r_total = kv * rpg
    n_pool = b * n_pages + 1
    k_pool = _mk(rng, (n_pool, kv, page, hd), jnp.float32)
    bt = _mk_tables(rng, b, n_pages, n_pool)
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    pos = jnp.asarray(rng.integers(1, n_pages * page, size=b), jnp.int32)
    sc = ck.paged_chai_qk(q_rep, k_pool, bt, pos, reps_per_group=rpg,
                          interpret=True)
    a = ck.row_softmax(sc, interpret=True)
    want = ref.paged_chai_scores_ref(q_rep, k_pool, bt, pos,
                                     reps_per_group=rpg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), **TOL)


@pytest.mark.parametrize("b,h,r,n_pages,page,hd", [
    (2, 8, 3, 4, 8, 16),
    (1, 4, 4, 2, 16, 32),      # k == H (degenerate)
])
def test_paged_chai_av_sweep(rng, b, h, r, n_pages, page, hd):
    s = n_pages * page
    n_pool = b * n_pages + 1
    a = jnp.asarray(rng.random((b, r, s)), jnp.float32)
    v_pool = _mk(rng, (n_pool, h, page, hd), jnp.float32)
    bt_v = _mk_tables(rng, b, n_pages, n_pool)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    got = ck.paged_chai_av(a, v_pool, bt_v, h2c, interpret=True)
    want = ref.paged_chai_av_ref(a, v_pool, bt_v, h2c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_paged_chai_pipeline_matches_ref(rng):
    """Full paged CHAI decode: paged QK -> row softmax -> paged AV vs the
    densify-then-reference oracle (clustered K pool + per-head V pool)."""
    b, h, r, n_pages, page, hd = 2, 8, 4, 4, 8, 16
    nk, nv = b * n_pages + 1, b * n_pages + 1
    k_pool = _mk(rng, (nk, r, page, hd), jnp.float32)
    v_pool = _mk(rng, (nv, h, page, hd), jnp.float32)
    bt_k = _mk_tables(rng, b, n_pages, nk)
    bt_v = _mk_tables(rng, b, n_pages, nv)
    q_rep = _mk(rng, (b, r, hd), jnp.float32)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    pos = jnp.asarray([n_pages * page - 1, 11], jnp.int32)
    sc = ck.paged_chai_qk(q_rep, k_pool, bt_k, pos, interpret=True)
    a = ck.row_softmax(sc, interpret=True)
    got = ck.paged_chai_av(a, v_pool, bt_v, h2c, interpret=True)
    want = ref.paged_chai_decode_ref(q_rep, k_pool, bt_k, v_pool, bt_v,
                                     h2c, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("b,kv,rpg,s,hd,ts", [
    (2, 4, 1, 32, 16, 8),      # MHA clustered cache (KV == R)
    (1, 2, 3, 64, 32, 16),     # GQA groups
])
def test_chai_qk_i8_fused_dequant(rng, b, kv, rpg, s, hd, ts):
    """Fused int8-dequant scores kernel vs dequant-then-ref oracle."""
    from repro.core.cache import quant_rows
    r_total = kv * rpg
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    kf = _mk(rng, (b, kv, s, hd), jnp.float32)
    kq, ks = quant_rows(kf)
    pos = jnp.asarray(rng.integers(1, s, size=b), jnp.int32)
    sc = ck.chai_qk_i8(q_rep, kq, ks, pos, reps_per_group=rpg, ts=ts,
                       interpret=True)
    a = ck.row_softmax(sc, interpret=True)
    want = ref.chai_scores_i8_ref(q_rep, kq, ks, pos, reps_per_group=rpg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), **TOL)


# ------------------------------------------------- fused one-pass decode ---
def _fused_case(rng, *, b=2, kv=3, rpg=1, s=128, hd=16, int8=False,
                share_values=False, qpk=None):
    """Build one fused-decode problem. MHA: rpg == 1, H chosen freely;
    GQA: H = kv * qpk, R = kv * rpg, h2c flat = group*rpg + within-group
    cluster. pos is ragged (one slot near the end, the rest random)."""
    from repro.core.cache import quant_rows
    r_total = kv * rpg
    if rpg == 1 and qpk is None:        # MHA: clustered cache, k_max rows
        h = 8
        h2c = rng.integers(0, r_total, size=(b, h))
    else:                               # GQA: within-group membership
        qpk = qpk or 4
        h = kv * qpk
        cluster_of = rng.integers(0, rpg, size=(b, kv, qpk))
        h2c = (np.arange(kv)[None, :, None] * rpg + cluster_of).reshape(b, h)
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    kc = _mk(rng, (b, kv, s, hd), jnp.float32)
    v_rows = r_total if share_values else (h if rpg == 1 else kv)
    vc = _mk(rng, (b, v_rows, s, hd), jnp.float32)
    pos = np.asarray(rng.integers(1, s, size=b))
    pos[0] = s - 1                      # ragged: one slot at full length
    kw = dict(reps_per_group=rpg, share_values=share_values)
    if int8:
        kq, ks = quant_rows(kc)
        kc, kw["k_scale"] = kq, ks
        if not share_values:            # clustered V codes stay scale-less
            vq, vs = quant_rows(vc)
            vc, kw["v_scale"] = vq, vs
    return (q_rep, kc, vc, jnp.asarray(h2c, jnp.int32),
            jnp.asarray(pos, jnp.int32)), kw


@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("mode", ["mha", "mha_share", "gqa"])
def test_chai_fused_decode_matrix(rng, mode, int8, window):
    """The full dispatch matrix the engine serves: {MHA, GQA} x
    {fp32, int8} x {share_values} x {window} x ragged pos — fused kernel
    vs the pure-jnp oracle AND the retired three-kernel pipeline."""
    kw_case = dict(share_values=(mode == "mha_share"))
    if mode == "gqa":
        kw_case.update(rpg=3, qpk=4)
    args, kw = _fused_case(rng, int8=int8, **kw_case)
    got = ck.chai_fused_decode(*args, ts=32, window=window, interpret=True,
                               **kw)
    want = ref.chai_fused_decode_ref(*args, window=window, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # the three-kernel pipeline survives as the second, independent oracle
    pipe = ref.chai_three_kernel_decode(*args, ts=32, window=window, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(pipe), **TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chai_fused_decode_cache_dtypes(rng, dtype):
    """bf16 caches stream through the fused kernel (f32 accumulation)."""
    (q, kc, vc, h2c, pos), kw = _fused_case(rng)
    kc, vc = kc.astype(dtype), vc.astype(dtype)
    got = ck.chai_fused_decode(q, kc, vc, h2c, pos, ts=32, interpret=True,
                               **kw)
    want = ref.chai_fused_decode_ref(q, kc, vc, h2c, pos, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **_tol(dtype))


def _paged_case(rng, mode, *, b, n_pages, page, hd=16, int8=False):
    """One paged fused-decode problem: (q_rep, k_pool, bt_k, v_pool, bt_v,
    h2c) and the kernel kwargs. MHA: clustered K (k_max rows) and per-head
    V; mha_share: clustered V; GQA: rpg 3 reps over 2 KV groups of 4
    heads. int8 quantizes the pools and passes the scale pools (clustered
    V codes stay scale-less)."""
    from repro.core.cache import quant_rows
    kv, rpg = (2, 3) if mode == "gqa" else (3, 1)
    share = mode == "mha_share"
    r_total = kv * rpg
    if mode == "gqa":
        h = kv * 4
        cluster_of = rng.integers(0, rpg, size=(b, kv, 4))
        h2c = (np.arange(kv)[None, :, None] * rpg
               + cluster_of).reshape(b, h)
        v_rows = kv
    else:
        h = 8
        h2c = rng.integers(0, r_total, size=(b, h))
        v_rows = r_total if share else h
    n_pool = 2 * b * n_pages + 1
    k_pool = _mk(rng, (n_pool, kv, page, hd), jnp.float32)
    v_pool = _mk(rng, (n_pool, v_rows, page, hd), jnp.float32)
    bt_k, bt_v = _mk_tables(rng, b, n_pages, n_pool, n=2)
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    kw = dict(reps_per_group=rpg, share_values=share)
    if int8:
        k_pool, kw["k_scale_pool"] = quant_rows(k_pool)
        if not share:
            v_pool, kw["v_scale_pool"] = quant_rows(v_pool)
    return (q_rep, k_pool, bt_k, v_pool, bt_v,
            jnp.asarray(h2c, jnp.int32)), kw


def _paged_ts(k_pool, v_pool, n_pages, int8):
    """The paged kernel's S-tile for these pools (tokens)."""
    page = k_pool.shape[-2]
    return page * ck.decode_tile_pages(
        page, k_pool.shape[-3] + v_pool.shape[-3], k_pool.shape[-1],
        k_pool.dtype, n_pages, scaled=int8)


@pytest.mark.parametrize("variant", ["f32", "int8", "softcap", "emit_state",
                                     "relay"])
@pytest.mark.parametrize("mode", ["mha", "mha_share", "gqa"])
def test_paged_chai_fused_decode_matrix(rng, mode, variant):
    """Paged fused decode across the dispatch matrix (pools + block tables
    + scale pools) vs the densify-then-reference oracle. 20 pages of 8
    tokens walk as a 16-page tile and a ragged 4-page one (int8: one page
    per tile), and the four slots sit at pos 0, the last token of the
    first tile, the first token of the second, and the last of max_seq.
    emit_state finalizes to the kernel's own output bit for bit; relay
    runs it over tables rolled past a 3-page prefix with pos shifted
    down by it (the suffix-only pass)."""
    from repro.core.chai_attention import _roll_bt
    b, n_pages, page = 4, 20, 8
    int8 = variant == "int8"
    (q, k_pool, bt_k, v_pool, bt_v, h2c), kw = _paged_case(
        rng, mode, b=b, n_pages=n_pages, page=page, int8=int8)
    ts = _paged_ts(k_pool, v_pool, n_pages, int8)
    assert ts == (page if int8 else 16 * page)
    pos = np.asarray([0, ts - 1, ts, n_pages * page - 1])
    if variant == "softcap":
        kw["softcap"] = 30.0
    if variant == "relay":
        shift = 3
        pos[:3] += shift * page
        bt_k = _roll_bt(bt_k, jnp.full((b,), shift, jnp.int32))
        bt_v = _roll_bt(bt_v, jnp.full((b,), shift, jnp.int32))
        pos = pos - shift * page
    args = (q, k_pool, bt_k, v_pool, bt_v, h2c, jnp.asarray(pos, jnp.int32))
    want = ref.paged_chai_fused_decode_ref(*args, **kw)
    got = ck.paged_chai_fused_decode(*args, interpret=True, **kw)
    if variant in ("emit_state", "relay"):
        state = ck.paged_chai_fused_decode(*args, emit_state=True,
                                           interpret=True, **kw)
        fin = ops.finalize_decode_state(state, h2c,
                                        share_values=kw["share_values"])
        assert (np.asarray(fin) == np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    if variant == "softcap":   # the flag is live
        kw.pop("softcap")
        uncapped = ck.paged_chai_fused_decode(*args, interpret=True, **kw)
        assert not np.allclose(np.asarray(got), np.asarray(uncapped))
    if variant == "f32":       # second oracle: the paged three-kernel path
        pipe = ref.paged_chai_three_kernel_decode(*args, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(pipe),
                                   **TOL)


def test_paged_fused_matches_dense_fused_bitwise(rng):
    """Same logical cache contents, equal tile size: the paged fused
    kernel must reproduce the dense fused kernel BIT-FOR-BIT (this is
    what pins cross-KV-layout greedy token parity in the engine). 16
    pages of 16 walk as two 8-page tiles; slot 1 ends in the first, so
    the paged kernel skips its second tile, which the dense one computes
    fully masked."""
    b, h, r, n_pages, page, hd = 2, 8, 4, 16, 16, 16
    s = n_pages * page
    nk = 2 * b * n_pages + 1
    k_pool = _mk(rng, (nk, r, page, hd), jnp.float32)
    v_pool = _mk(rng, (nk, h, page, hd), jnp.float32)
    bt_k, bt_v = _mk_tables(rng, b, n_pages, nk, n=2)
    kc = np.zeros((b, r, s, hd), np.float32)
    vc = np.zeros((b, h, s, hd), np.float32)
    for i in range(b):
        for j in range(n_pages):
            kc[i, :, j * page:(j + 1) * page] = np.asarray(
                k_pool)[np.asarray(bt_k)[i, j]]
            vc[i, :, j * page:(j + 1) * page] = np.asarray(
                v_pool)[np.asarray(bt_v)[i, j]]
    q = _mk(rng, (b, r, hd), jnp.float32)
    h2c = jnp.asarray(rng.integers(0, r, size=(b, h)), jnp.int32)
    pos = jnp.asarray([s - 1, 23], jnp.int32)
    ts = _paged_ts(k_pool, v_pool, n_pages, False)
    assert ts == s // 2
    dense = ck.chai_fused_decode(q, jnp.asarray(kc), jnp.asarray(vc), h2c,
                                 pos, ts=ts, interpret=True)
    paged = ck.paged_chai_fused_decode(q, k_pool, bt_k, v_pool, bt_v, h2c,
                                       pos, interpret=True)
    assert (np.asarray(dense) == np.asarray(paged)).all()


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("fill", ["null_page", "nan_pages"])
def test_paged_fused_null_pages_masked(rng, fill, int8):
    """Pages past a slot's position are never read. null_page: their
    block-table entries point at the null sink page 0, filled with +-999;
    nan_pages: they keep real pages, filled with NaN (int8: NaN scales).
    Slot 0 ends in the second page of its first 16-page tile (int8: one
    page per tile); slot 1 two pages into its second. The output equals,
    bit for bit, the run on clean pools."""
    b, n_pages, page, mode = 2, 20, 8, "mha"
    (q, k_pool, bt_k, v_pool, bt_v, h2c), kw = _paged_case(
        rng, mode, b=b, n_pages=n_pages, page=page, int8=int8)
    ts = _paged_ts(k_pool, v_pool, n_pages, int8)
    pos = np.asarray([2 * page - 1, ts + page + 3])
    past = np.arange(n_pages)[None, :] * page > pos[:, None]
    clean = ck.paged_chai_fused_decode(
        q, k_pool, bt_k, v_pool, bt_v, h2c, jnp.asarray(pos, jnp.int32),
        interpret=True, **kw)
    if fill == "null_page":
        bt_k = jnp.where(past, 0, bt_k)
        bt_v = jnp.where(past, 0, bt_v)
        k_pages, v_pages = np.asarray([0]), np.asarray([0])
        bad_k, bad_v = 999.0, -999.0
    else:
        k_pages, v_pages = np.asarray(bt_k)[past], np.asarray(bt_v)[past]
        bad_k = bad_v = np.nan
    if int8:
        kw["k_scale_pool"] = kw["k_scale_pool"].at[k_pages].set(bad_k)
        kw["v_scale_pool"] = kw["v_scale_pool"].at[v_pages].set(bad_v)
    else:
        k_pool = k_pool.at[k_pages].set(bad_k)
        v_pool = v_pool.at[v_pages].set(bad_v)
    out = ck.paged_chai_fused_decode(
        q, k_pool, bt_k, v_pool, bt_v, h2c, jnp.asarray(pos, jnp.int32),
        interpret=True, **kw)
    assert np.isfinite(np.asarray(out)).all()
    assert (np.asarray(out) == np.asarray(clean)).all()


@pytest.mark.parametrize("page,rows,hd,dtype,n_pages,scaled,want", [
    (16, 13 + 16, 128, jnp.bfloat16, 256, False, 8),   # DeepSeekMoE cell
    (16, 8 + 8, 128, jnp.bfloat16, 256, False, 8),     # GQA 48/8 heads
    (16, 13 + 16, 128, jnp.int8, 256, True, 1),        # int8: one page
    (8, 3 + 8, 16, jnp.float32, 20, False, 16),        # ragged 16 + 4
    (16, 13 + 16, 128, jnp.bfloat16, 4, False, 4),     # short max_seq
    (16, 32 + 32, 128, jnp.float32, 256, False, 4),    # VMEM-bound
    (256, 64 + 64, 256, jnp.float32, 64, False, 1),    # one page over it
])
def test_decode_tile_pages(page, rows, hd, dtype, n_pages, scaled, want):
    """The tile function: at least DECODE_TILE_MIN_TOKENS tokens unless
    the double-buffered K + V tiles would outgrow the VMEM budget or the
    slot is shorter; never more pages than a slot has; int8 walks one
    page. A tile that does not divide n_pages leaves a ragged last tile,
    which the kernel masks."""
    ppt = ck.decode_tile_pages(page, rows, hd, dtype, n_pages,
                               scaled=scaled)
    assert ppt == want
    assert 1 <= ppt <= n_pages
    tile_bytes = 2 * ppt * page * rows * hd * jnp.dtype(dtype).itemsize
    assert ppt == 1 or tile_bytes <= ck.DECODE_TILE_VMEM_BYTES
    if not scaled and ppt < n_pages and 2 * tile_bytes <= \
            ck.DECODE_TILE_VMEM_BYTES:
        assert ppt * page >= ck.DECODE_TILE_MIN_TOKENS
    n_tiles = -(-n_pages // ppt)
    assert (n_tiles - 1) * ppt < n_pages <= n_tiles * ppt


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("variant", ["bf16", "int8", "emit_state"])
@pytest.mark.parametrize("mode", ["mha", "mha_share", "gqa"])
def test_paged_fused_decode_stacked_pool_matches_layer_bitwise(rng, mode,
                                                               variant,
                                                               layer):
    """The decode state holds each pool stacked by layer (L, nP, ...). The
    fused kernel reads the stack at a scalar-prefetched layer index; it
    must return, bit for bit, what the call on that layer's own pool
    returns — first and last layer of a 3-layer stack, through the kernel
    and through the jitted op with a traced layer."""
    from repro.core.cache import quant_rows
    n_layers, b, n_pages, page, hd = 3, 2, 4, 16, 16
    kv, rpg = (2, 3) if mode == "gqa" else (3, 1)
    share = mode == "mha_share"
    r_total = kv * rpg
    if mode == "gqa":
        h = kv * 4
        cluster_of = rng.integers(0, rpg, size=(b, kv, 4))
        h2c = (np.arange(kv)[None, :, None] * rpg
               + cluster_of).reshape(b, h)
        v_rows = kv
    else:
        h = 8
        h2c = rng.integers(0, r_total, size=(b, h))
        v_rows = r_total if share else h
    n_pool = 2 * b * n_pages + 1
    dt = jnp.bfloat16 if variant == "bf16" else jnp.float32
    k_pool = _mk(rng, (n_layers, n_pool, kv, page, hd), dt)
    v_pool = (k_pool if share
              else _mk(rng, (n_layers, n_pool, v_rows, page, hd), dt))
    bt_k, bt_v = _mk_tables(rng, b, n_pages, n_pool, n=2)
    q_rep = _mk(rng, (b, r_total, hd), jnp.float32)
    pos = jnp.asarray([n_pages * page - 1, 37], jnp.int32)
    kw = dict(reps_per_group=rpg, share_values=share,
              emit_state=variant == "emit_state")
    if variant == "int8":
        k_pool, kw["k_scale_pool"] = quant_rows(k_pool)
        if share:
            v_pool = k_pool
        else:
            v_pool, kw["v_scale_pool"] = quant_rows(v_pool)
    args = (q_rep, k_pool, bt_k, v_pool, bt_v, jnp.asarray(h2c, jnp.int32),
            pos)
    one = {k: (v[layer] if k.endswith("_pool") else v)
           for k, v in kw.items()}
    want = ck.paged_chai_fused_decode(q_rep, k_pool[layer], bt_k,
                                      v_pool[layer], bt_v, args[5], pos,
                                      interpret=True, **one)
    got = ck.paged_chai_fused_decode(*args, layer=layer, interpret=True,
                                     **kw)
    got_op = ops.paged_chai_decode_attention(
        *args, layer=jnp.int32(layer), interpret=True, **kw)
    for g in (got, got_op):
        for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
            assert (np.asarray(x) == np.asarray(y)).all()


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kind", ["bf16", "int8", "scale"])
def test_paged_kv_write_rows_matches_scatter_bitwise(rng, kind, layer):
    """The decode step's in-place token write (``paged_write`` ->
    ``ops.paged_kv_write_rows``) against the jnp scatter into the sliced
    layer it replaces: K/V pages (bf16, int8 codes) and int8 scale pages,
    with masked-out slots (their own pages untouched) and two slots on
    the null sink page 0. Every page but the sink equals the scatter's
    bit for bit, and every other layer is untouched; the sink's contents
    are unspecified (reads of it are always masked by ``pos``)."""
    from repro.kernels.ref import paged_write_rows_ref
    from repro.models.transformer import paged_write
    n_layers, n_pool, rows, page, hd = 3, 12, 4, 16, 128
    shape = (n_layers, n_pool, rows, page) + (() if kind == "scale"
                                              else (hd,))
    dt = {"bf16": jnp.bfloat16, "int8": jnp.int8}.get(kind, jnp.float32)
    pool = jnp.asarray(rng.integers(-100, 100, size=shape), dt)
    # slots: 3 real pages written, 2 masked out on real pages, 2 on the
    # sink page 0 (one of them masked out)
    pages = jnp.asarray([5, 0, 9, 2, 0, 7, 11], jnp.int32)
    rows_ = jnp.asarray([0, 3, 15, 7, 9, 8, 1], jnp.int32)
    mask = jnp.asarray([True, True, True, False, False, False, True])
    new = jnp.asarray(rng.integers(-100, 100, size=(7,) + shape[2:3]
                                   + shape[4:]), dt)
    got = paged_write({"pool": pool}, "pool", jnp.int32(layer), pages,
                      rows_, new, mask)["pool"]
    want = paged_write_rows_ref(pool, layer, pages, rows_, new, mask)
    got, want, pool = np.asarray(got), np.asarray(want), np.asarray(pool)
    assert (got[:, 1:] == want[:, 1:]).all()
    others = [i for i in range(n_layers) if i != layer]
    assert (got[others] == pool[others]).all()
    assert (got[layer, [2, 7]] == pool[layer, [2, 7]]).all()   # masked
    assert not (got[layer, [5, 9, 11]] == pool[layer, [5, 9, 11]]).all()


# --------------------------------------------------- tanh logit softcap ----
@pytest.mark.parametrize("softcap", [5.0, 50.0])
@pytest.mark.parametrize("mode", ["mha", "mha_share", "gqa"])
def test_chai_fused_decode_softcap_matches_oracle(rng, mode, softcap):
    """gemma2-style softcap inside the fused kernel (between QK-scale and
    the online-softmax update) vs the jnp oracle, across the dispatch
    matrix — this is what lets softcap archs stay on the fused path."""
    kw_case = dict(share_values=(mode == "mha_share"))
    if mode == "gqa":
        kw_case.update(rpg=3, qpk=4)
    args, kw = _fused_case(rng, **kw_case)
    got = ck.chai_fused_decode(*args, ts=32, softcap=softcap,
                               interpret=True, **kw)
    want = ref.chai_fused_decode_ref(*args, softcap=softcap, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # the flag is live: capping must actually change the output
    uncapped = ck.chai_fused_decode(*args, ts=32, interpret=True, **kw)
    assert not np.allclose(np.asarray(got), np.asarray(uncapped))


@pytest.mark.parametrize("offset", [0, 8])
def test_flash_prefill_softcap_matches_oracle(rng, offset):
    """The prefix cache's flash suffix path under a logit softcap (the
    gemma2 suffix prefill no longer falls back to jnp)."""
    b, t, h, kv, hd, cap = 2, 16, 4, 4, 16, 20.0
    s = t + offset
    q = _mk(rng, (b, t, h, hd), jnp.float32)
    k = _mk(rng, (b, s, kv, hd), jnp.float32)
    v = _mk(rng, (b, s, kv, hd), jnp.float32)
    got = fk.flash_prefill(q, k, v, offset=offset, tq=8, ts=8, softcap=cap,
                           interpret=True)
    want = ref.flash_prefill_ref(q, k, v, offset=offset, softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    uncapped = fk.flash_prefill(q, k, v, offset=offset, tq=8, ts=8,
                                interpret=True)
    assert not np.allclose(np.asarray(got), np.asarray(uncapped))


def _all_avals(jaxpr):
    """Every aval in a (recursively closed) jaxpr."""
    seen = []
    todo = [jaxpr]
    while todo:
        j = todo.pop()
        for eqn in j.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                if hasattr(v, "aval"):
                    seen.append(v.aval)
            for p in eqn.params.values():
                vals = p if isinstance(p, (list, tuple)) else [p]
                for sub in vals:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        todo.append(inner)
                    elif hasattr(sub, "eqns"):
                        todo.append(sub)
    return seen


def test_fused_decode_materializes_no_brs_scores(rng):
    """Acceptance criterion: the fused path allocates NO (B, R, S) score
    tensor anywhere in its jaxpr — while the three-kernel pipeline
    provably does (the check has teeth)."""
    from repro.kernels import ops
    (q, kc, vc, h2c, pos), kw = _fused_case(rng, b=2, kv=3, s=128)
    b, r, s = 2, 3, 128

    def fused(q, kc, vc, h2c, pos):
        return ops.chai_decode_attention(q, kc, vc, h2c, pos, ts=32,
                                         interpret=True)

    def pipeline(q, kc, vc, h2c, pos):
        return ref.chai_three_kernel_decode(q, kc, vc, h2c, pos, ts=32)

    fused_avals = _all_avals(jax.make_jaxpr(fused)(q, kc, vc, h2c, pos))
    pipe_avals = _all_avals(jax.make_jaxpr(pipeline)(q, kc, vc, h2c, pos))
    assert not any(getattr(a, "shape", None) == (b, r, s)
                   for a in fused_avals)
    assert any(getattr(a, "shape", None) == (b, r, s) for a in pipe_avals)
