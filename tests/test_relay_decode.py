"""Shared-prefix relay decode: one prefix-attention pass per group.

Correctness contract: relaying is a pure *work-restructuring* layer —
the prefix half of every grouped slot's attention is computed ONCE per
group (batched over members, rep rows only) and merged into the slot's
suffix-only fused decode via online-softmax state. Grouped greedy tokens
must match the per-request decode path token-for-token across
{MHA, GQA} x {fp32, int8} x share_values x group sizes; slots that never
group (no shared chain, evicted node, snapshot entry) must stay
BITWISE on the non-relay path (the empty prefix state is the exact merge
identity). The kernel-level sweeps pin the merge algebra; the engine
sweeps pin group formation, resident-view caching and fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.kernels import chai_attention as ck
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.models import transformer as tfm
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.prefix_cache import BlockNode
from repro.serving.sampling import SamplingParams

MHA_ARCH = "chai-llama-7b"
GQA_ARCH = "nemotron-4-15b"
PS = 16
TOL = dict(rtol=2e-3, atol=2e-3)

rng = np.random.default_rng(0)


def _mk(shape, dtype=np.float32):
    if dtype == np.int8:
        return jnp.asarray(rng.integers(-127, 127, shape, dtype=np.int8))
    return jnp.asarray(rng.normal(size=shape).astype(dtype))


# ------------------------------------------------ kernel-level merge parity
def _mha_relay_case(n, *, share=False, int8=False, r=3, h=6, hd=16,
                    sp=32, ssuf=32):
    """One group of ``n`` members sharing clustered prefix rows [0, sp).

    Returns (full fused output, relay-composed output): the full pass
    attends the whole cache; the relay path runs the group-batched
    prefix kernel over the shared rows + the suffix-only fused decode,
    then merges the (m, l, acc) states.
    """
    s = sp + ssuf
    kdt = np.int8 if int8 else np.float32
    kc = np.asarray(rng.integers(-127, 127, (n, r, s, hd))
                    if int8 else rng.normal(size=(n, r, s, hd)), kdt)
    kc[:, :, :sp] = kc[0, :, :sp]           # shared prefix (clustered rows)
    v_rows = r if share else h
    vc = np.asarray(rng.integers(-127, 127, (n, v_rows, s, hd))
                    if int8 else rng.normal(size=(n, v_rows, s, hd)), kdt)
    vc[:, :, :sp] = vc[0, :, :sp]
    ks = vs = None
    if int8:
        ks = np.asarray(rng.normal(size=(n, r, s)), np.float32)
        ks[:, :, :sp] = ks[0, :, :sp]
        ks = jnp.asarray(ks)
        if not share:                       # share_values: codes move
            vs = np.asarray(rng.normal(size=(n, v_rows, s)), np.float32)
            vs[:, :, :sp] = vs[0, :, :sp]
            vs = jnp.asarray(vs)
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    q = _mk((n, r, hd))
    h2c = jnp.asarray(rng.integers(0, r, (n, h)), jnp.int32)
    pos = jnp.asarray(rng.integers(sp + 1, s, (n,)), jnp.int32)

    full = ck.chai_fused_decode(q, kc, vc, h2c, pos, k_scale=ks,
                                v_scale=vs, share_values=share, ts=16)

    # group-batched prefix pass over the shared rows
    qg = q.reshape(1, n * r, hd)
    k_row = jnp.asarray(np.tile(np.arange(r), n)[None], jnp.int32)
    if share:
        a_row = jnp.asarray(np.arange(n * r)[None], jnp.int32)
        v_row = k_row
    else:
        a_row = jnp.asarray((np.arange(n)[:, None] * r
                             + np.asarray(h2c)).reshape(1, n * h),
                            jnp.int32)
        v_row = jnp.asarray(np.tile(np.arange(h), n)[None], jnp.int32)
    mp, lp, accp = ck.relay_prefix_decode(
        qg, kc[0:1, :, :sp], vc[0:1, :v_rows, :sp], k_row, a_row, v_row,
        jnp.asarray([sp], jnp.int32),
        k_scale=None if ks is None else ks[0:1, :, :sp],
        v_scale=None if vs is None else vs[0:1, :, :sp], ts=16)
    a_rows = r if share else h
    pref = (mp.reshape(n, r), lp.reshape(n, r),
            accp.reshape(n, a_rows, hd))
    suf = ck.chai_fused_decode(q, kc[:, :, sp:], vc[:, :, sp:], h2c,
                               pos - sp,
                               k_scale=None if ks is None else ks[:, :, sp:],
                               v_scale=None if vs is None else vs[:, :, sp:],
                               share_values=share, ts=16, emit_state=True)
    out = kops.finalize_decode_state(
        kops.merge_decode_states(suf, pref, h2c, share_values=share),
        h2c, share_values=share)
    return np.asarray(full), np.asarray(out)


def _gqa_relay_case(n, *, int8=False, kv=2, rpg=2, qpk=2, hd=16,
                    sp=32, ssuf=32):
    s = sp + ssuf
    h = kv * qpk
    rt = kv * rpg
    kdt = np.int8 if int8 else np.float32
    kc = np.asarray(rng.integers(-127, 127, (n, kv, s, hd))
                    if int8 else rng.normal(size=(n, kv, s, hd)), kdt)
    kc[:, :, :sp] = kc[0, :, :sp]
    vc = np.asarray(rng.integers(-127, 127, (n, kv, s, hd))
                    if int8 else rng.normal(size=(n, kv, s, hd)), kdt)
    vc[:, :, :sp] = vc[0, :, :sp]
    ks = vs = None
    if int8:
        sc = np.asarray(rng.normal(size=(2, n, kv, s)), np.float32)
        sc[:, :, :, :sp] = sc[:, 0:1, :, :sp]
        ks, vs = jnp.asarray(sc[0]), jnp.asarray(sc[1])
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    q = _mk((n, rt, hd))
    cl = rng.integers(0, rpg, (n, kv, qpk))
    h2c = jnp.asarray((np.arange(kv)[None, :, None] * rpg
                       + cl).reshape(n, h), jnp.int32)
    pos = jnp.asarray(rng.integers(sp + 1, s, (n,)), jnp.int32)

    full = ck.chai_fused_decode(q, kc, vc, h2c, pos, k_scale=ks,
                                v_scale=vs, reps_per_group=rpg, ts=16)

    qg = q.reshape(1, n * rt, hd)
    k_row = jnp.asarray(
        np.tile(np.repeat(np.arange(kv), rpg), n)[None], jnp.int32)
    a_row = jnp.asarray((np.arange(n)[:, None] * rt
                         + np.asarray(h2c)).reshape(1, n * h), jnp.int32)
    v_row = jnp.asarray(
        np.tile(np.repeat(np.arange(kv), qpk), n)[None], jnp.int32)
    mp, lp, accp = ck.relay_prefix_decode(
        qg, kc[0:1, :, :sp], vc[0:1, :, :sp], k_row, a_row, v_row,
        jnp.asarray([sp], jnp.int32),
        k_scale=None if ks is None else ks[0:1, :, :sp],
        v_scale=None if vs is None else vs[0:1, :, :sp], ts=16)
    pref = (mp.reshape(n, rt), lp.reshape(n, rt), accp.reshape(n, h, hd))
    suf = ck.chai_fused_decode(q, kc[:, :, sp:], vc[:, :, sp:], h2c,
                               pos - sp,
                               k_scale=None if ks is None else ks[:, :, sp:],
                               v_scale=None if vs is None else vs[:, :, sp:],
                               reps_per_group=rpg, ts=16, emit_state=True)
    out = kops.finalize_decode_state(
        kops.merge_decode_states(suf, pref, h2c), h2c)
    return np.asarray(full), np.asarray(out)


@pytest.mark.parametrize("n", [1, 2, 8])
@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("int8", [False, True])
def test_relay_merge_matches_full_fused_mha(n, share, int8):
    full, out = _mha_relay_case(n, share=share, int8=int8)
    np.testing.assert_allclose(out, full, **TOL)


@pytest.mark.parametrize("n", [1, 2, 8])
@pytest.mark.parametrize("int8", [False, True])
def test_relay_merge_matches_full_fused_gqa(n, int8):
    full, out = _gqa_relay_case(n, int8=int8)
    np.testing.assert_allclose(out, full, **TOL)


@pytest.mark.parametrize("int8", [False, True])
def test_relay_prefix_kernel_vs_oracle(int8):
    g, nmax, kv, r, hd, sp = 2, 3, 8, 3, 16, 64
    nr, h = nmax * r, 8
    a = nmax * h
    q = _mk((g, nr, hd))
    kdt = np.int8 if int8 else np.float32
    k, v = _mk((g, kv, sp, hd), kdt), _mk((g, kv, sp, hd), kdt)
    ks = _mk((g, kv, sp)) if int8 else None
    vs = _mk((g, kv, sp)) if int8 else None
    k_row = jnp.asarray(rng.integers(0, kv, (g, nr)), jnp.int32)
    a_row = jnp.asarray(rng.integers(0, nr, (g, a)), jnp.int32)
    v_row = jnp.asarray(rng.integers(0, kv, (g, a)), jnp.int32)
    plen = jnp.asarray([48, 16], jnp.int32)
    got = ck.relay_prefix_decode(q, k, v, k_row, a_row, v_row, plen,
                                 k_scale=ks, v_scale=vs, ts=16)
    want = ref.relay_prefix_decode_ref(q, k, v, k_row, a_row, v_row, plen,
                                       k_scale=ks, v_scale=vs)
    for a_, b_ in zip(got, want):
        np.testing.assert_allclose(a_, b_, **TOL)


def test_empty_prefix_state_is_bitwise_merge_identity():
    n, r, h, hd, s = 2, 3, 6, 16, 64
    q = _mk((n, r, hd))
    kc, vc = _mk((n, r, s, hd)), _mk((n, h, s, hd))
    h2c = jnp.asarray(rng.integers(0, r, (n, h)), jnp.int32)
    pos = jnp.asarray([40, 63], jnp.int32)
    st = ck.chai_fused_decode(q, kc, vc, h2c, pos, ts=16, emit_state=True)
    empty = (jnp.full((n, r), ck.NEG_INF), jnp.zeros((n, r)),
             jnp.zeros((n, h, hd)))
    merged = kops.finalize_decode_state(
        kops.merge_decode_states(st, empty, h2c), h2c)
    direct = kops.finalize_decode_state(st, h2c)
    assert (np.asarray(merged) == np.asarray(direct)).all()


# ----------------------------------------------------- engine-level parity
def _cfg(arch, chai_kw=(), cfg_kw=()):
    cfg = reduced(get_config(arch), n_layers=2, d_model=32, d_ff=64,
                  vocab=64).replace(dtype="float32", **dict(cfg_kw))
    return cfg.with_chai(enabled=True, warmup_tokens=3, **dict(chai_kw))


def _engine(cfg, params, *, slots=2, relay=True, min_group=2, **kw):
    return ServingEngine(cfg, params,
                         EngineConfig(batch_slots=slots, max_seq=64,
                                      page_size=PS, prefix_cache=True,
                                      relay_decode=relay,
                                      relay_min_group=min_group, **kw))


def _shared_prompts(n, prefix_blocks=2, seed=7):
    r = np.random.default_rng(seed)
    prefix = r.integers(0, 64, size=prefix_blocks * PS).tolist()
    return prefix, [prefix + r.integers(0, 64, size=4 + j).tolist()
                    for j in range(n)]


def _serve(cfg, params, prompts, *, warm, max_new=8, **kw):
    eng = _engine(cfg, params, **kw)
    eng.submit(warm, max_new_tokens=4, uid=0)
    eng.run()
    for j, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=max_new, uid=j + 1)
    done = {r.uid: r for r in eng.run()}
    return [done[j + 1].generated for j in range(len(prompts))], eng


@pytest.mark.slow
@pytest.mark.parametrize("arch,chai_kw,cfg_kw", [
    (MHA_ARCH, {}, {}),
    (MHA_ARCH, {}, {"kv_cache_dtype": "int8"}),
    (MHA_ARCH, {"share_values": True}, {}),
    (MHA_ARCH, {"share_values": True}, {"kv_cache_dtype": "int8"}),
    (GQA_ARCH, {}, {}),
    (GQA_ARCH, {}, {"kv_cache_dtype": "int8"}),
])
def test_relay_engine_token_parity(arch, chai_kw, cfg_kw):
    cfg = _cfg(arch, chai_kw, cfg_kw)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(2)
    base, _ = _serve(cfg, params, prompts, warm=prefix + [1], relay=False)
    got, eng = _serve(cfg, params, prompts, warm=prefix + [1], relay=True)
    assert eng.relay_steps > 0
    assert got == base


@pytest.mark.slow
@pytest.mark.parametrize("n", [1, 2])
def test_relay_group_sizes_small(n):
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(n)
    base, _ = _serve(cfg, params, prompts, warm=prefix + [1], relay=False,
                     min_group=1)
    got, eng = _serve(cfg, params, prompts, warm=prefix + [1], relay=True,
                      min_group=1)
    assert eng.relay_steps > 0
    assert got == base


@pytest.mark.slow
def test_relay_group_size_eight():
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(8)
    base, _ = _serve(cfg, params, prompts, warm=prefix + [1], relay=False,
                     slots=8, max_new=6)
    got, eng = _serve(cfg, params, prompts, warm=prefix + [1], relay=True,
                      slots=8, max_new=6)
    assert eng.relay_steps > 0
    # at least one step grouped every slot at once
    assert eng.relay_grouped_slots >= 8
    assert got == base


@pytest.mark.slow
def test_relay_midstream_eviction_dissolves_group():
    """Forced eviction of the grouped node mid-stream: the group stops
    forming (``node.evicted`` guards formation; the resident view is
    dropped) and the remaining tokens still match the per-request path —
    the slots' own block tables never depended on the resident copy."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(2)
    base, _ = _serve(cfg, params, prompts, warm=prefix + [1], relay=False)
    eng = _engine(cfg, params, relay=True)
    eng.submit(prefix + [1], max_new_tokens=4, uid=0)
    eng.run()
    for j, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=8, uid=j + 1)
    while eng.relay_steps == 0 and eng.has_work():
        eng.step()
    assert eng.relay_steps > 0
    for locked in eng._slot_locked:         # evict the chain mid-group
        for node in locked:
            if isinstance(node, BlockNode):
                node.evicted = True
                node.resident = None
    frozen = eng.relay_steps
    eng.run()
    assert eng.relay_steps == frozen        # no group ever reformed
    done = {r.uid: r for r in eng.done}
    assert [done[j + 1].generated
            for j in range(len(prompts))] == base


@pytest.mark.slow
def test_relay_divergent_slot_left_out_of_group():
    """COW-style divergence: a third request shares only the first block
    (it diverged inside block 2, so admission gave it fresh pages). The
    deepest-shared-node rule groups the two full-chain slots; the
    divergent slot decodes ungrouped. All tokens match the per-request
    path."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(2)
    div = list(prefix)
    div[PS + 3] ^= 1                        # diverge inside block 2
    prompts = prompts + [div + [9, 9]]
    base, _ = _serve(cfg, params, prompts, warm=prefix + [1], relay=False,
                     slots=3)
    got, eng = _serve(cfg, params, prompts, warm=prefix + [1], relay=True,
                      slots=3)
    assert eng.relay_steps > 0
    # every relay step grouped exactly the two full-chain slots
    assert eng.relay_grouped_slots == 2 * eng.relay_steps
    assert got == base


# ------------------------------------------------------------ jaxpr shape
def _iter_eqns(jaxpr):
    todo = [jaxpr]
    while todo:
        j = todo.pop()
        for eqn in j.eqns:
            yield eqn
            for p in eqn.params.values():
                vals = p if isinstance(p, (list, tuple)) else [p]
                for sub in vals:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        todo.append(inner)
                    elif hasattr(sub, "eqns"):
                        todo.append(sub)


@pytest.mark.slow
def test_relay_jaxpr_prefix_pass_once_per_group():
    """The traced relay step launches the prefix kernel ONCE per layer
    over the group batch — its (G, Nmax*R) state output appears exactly
    n_layers times, independent of how many slots the group holds."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    prefix, prompts = _shared_prompts(2)
    eng = _engine(cfg, params, relay=True)
    eng.submit(prefix + [1], max_new_tokens=4, uid=0)
    eng.run()
    captured = {}
    orig = eng._relay_step

    def spy(p, inputs, state, ctx, relay):
        captured.setdefault("a", (inputs, ctx, relay))
        return orig(p, inputs, state, ctx, relay)

    eng._relay_step = spy
    for j, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=6, uid=j + 1)
    eng.run()
    inputs, ctx, relay = captured["a"]
    g, nr = relay["k_row"].shape[1:]
    nmax = int(relay["members"].shape[1])
    assert nmax == 2                        # both slots grouped
    jaxpr = jax.make_jaxpr(orig)(eng.params, inputs, eng._dev_state,
                                 ctx, relay)
    eqns = [e for e in _iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]
    # the GROUP-batched prefix state (G, Nmax*R) is produced once in the
    # layer scan body (or n_layers times if unrolled) — never scaled by
    # the member count (the kernel writes m/l as (rows, 1) columns)
    hits = [e for e in eqns
            if any(tuple(v.aval.shape) == (g, nr, 1) for v in e.outvars)]
    assert 1 <= len(hits) <= cfg.n_layers
    # a per-slot formulation would emit (G, R) prefix states per member;
    # no such kernel exists in the trace
    assert not any(tuple(v.aval.shape) == (g, nr // nmax, 1)
                   for e in eqns for v in e.outvars)


# ------------------------------------- mixed-batch sampling lane skipping
@pytest.mark.slow
def test_mixed_batch_greedy_skips_sampling_lane():
    """Satellite: with greedy slots in the batch, the sampler runs on a
    gathered sub-batch of only the sampling rows; the sampling request's
    tokens are identical to the full-lane run (per-row draws depend only
    on that row's logits/params/seed/count)."""
    cfg = _cfg(MHA_ARCH)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    r = np.random.default_rng(3)
    p_s = r.integers(0, 64, size=8).tolist()
    p_g = r.integers(0, 64, size=9).tolist()
    samp = SamplingParams(temperature=0.8, top_k=8, seed=5)

    def serve(second_sampling):
        eng = ServingEngine(cfg, params,
                            EngineConfig(batch_slots=2, max_seq=64,
                                         page_size=PS))
        sizes = []
        orig = eng._sampler
        eng._sampler = lambda lg, *a: (sizes.append(int(lg.shape[0]))
                                       or orig(lg, *a))
        eng.submit(p_s, max_new_tokens=6, uid=0, sampling=samp)
        kw = ({"sampling": SamplingParams(temperature=1.2, seed=11)}
              if second_sampling else {})
        eng.submit(p_g, max_new_tokens=6, uid=1, **kw)
        done = {q.uid: q for q in eng.run()}
        return done[0].generated, sizes

    mixed_toks, mixed_sizes = serve(False)
    full_toks, full_sizes = serve(True)
    assert mixed_toks == full_toks          # sub-batch is draw-preserving
    assert mixed_sizes and set(mixed_sizes) == {1}   # greedy row skipped
    # the full lane must at some step batch BOTH sampling rows through the
    # sampler; size-1 steps around it are legitimate (staggered admission,
    # early retirement sub-batches the survivor)
    assert max(full_sizes) == 2
