"""The benchmark's yardstick at small sizes: trace reduction, operation
and byte counts, name resolution, traffic determinism, the peak table."""
import json

import numpy as np
import pytest

from bench import check, peaks, spec, trace_reduce, traffic_gen
from bench.metrics import _cost


# -- trace reduction ----------------------------------------------------------
def _trace():
    # Device ops on one chip over a 100 ns window; host annotations.
    ops = {"/device:TPU:0": [("fusion.1", 10, 20), ("paged_chai_fused_decode",
                                                    25, 15),
                             ("fusion.2", 60, 10), ("copy", 95, 20)]}
    modules = {"/device:TPU:0": [("jit_mixed_step(1)", 10, 30),
                                 ("jit_slot_prefill(2)", 60, 10)]}
    host = [("bench.window", 0, 100), ("bench.step", 5, 40),
            ("bench.record", 45, 10), ("bench.step", 55, 20),
            ("bench.wait", 75, 20)]
    return trace_reduce.Trace(ops, modules, host, (0.0, 100.0))


def test_busy_union_and_idle_share():
    tr = _trace()
    # [10, 40) merged from the two overlapping ops, [60, 70), [95, 100).
    assert trace_reduce.union(tr.ops["/device:TPU:0"])[0] == (10, 40)
    assert trace_reduce.device_busy_s(tr) == pytest.approx(45e-9)
    assert trace_reduce.idle_share(tr) == pytest.approx(0.55)


def test_kernel_and_program_time_by_name():
    tr = _trace()
    assert trace_reduce.time_of(tr, ["paged_chai_fused_decode"]) == \
        pytest.approx(15e-9)
    assert trace_reduce.time_of(tr, ["jit_mixed_step"], "modules") == \
        pytest.approx(30e-9)
    assert trace_reduce.count_of(tr, ["jit_slot_prefill"]) == 1
    # Clipped to the window: the last op runs past its end.
    assert trace_reduce.time_of(tr, ["copy"]) == pytest.approx(5e-9)
    top = trace_reduce.top_ops(tr, k=2)
    assert [n for n, _ in top] == ["fusion.1", "paged_chai_fused_decode"]


def test_idle_gaps_attributed_to_host_annotations():
    tr = _trace()
    gaps = trace_reduce.gaps(tr)
    assert gaps == [(0.0, 10), (40, 60), (70, 95)]
    by = dict(trace_reduce.idle_by_host(tr))
    # (0,10) mid 5 -> bench.step; (40,60) mid 50 -> bench.record;
    # (70,95) mid 82.5 -> bench.wait.
    assert by["bench.step"] == pytest.approx(10e-9)
    assert by["bench.record"] == pytest.approx(20e-9)
    assert by["bench.wait"] == pytest.approx(25e-9)
    # Host self time per step: span minus device busy inside it.
    assert trace_reduce.host_self_ms(tr, "bench.step") == \
        pytest.approx([(40 - 30) * 1e-6, (20 - 10) * 1e-6])


def test_kernel_ops_counted_inside_decode_programs_only():
    tr = _trace()
    tr.ops["/device:TPU:0"].append(("paged_chai_fused_decode", 62, 4))
    # Inside jit_mixed_step [10, 40) only the first event counts; the one
    # at 62 lies in the prefill program.
    assert trace_reduce.ops_within(tr, ["paged_chai_fused_decode"],
                                   ["jit_mixed_step"]) == pytest.approx(15e-9)
    # A TPU trace names each operation by its HLO text; a kernel call is
    # named after the jitted function that holds it, with an instance
    # number. A Mosaic call of another name does not count.
    call = ('%paged_chai_decode_attention.9 = f32[2,4]{1,0} custom-call(%a),'
            ' custom_call_target="tpu_custom_call"')
    other = ('%ragged-dot-none.2 = bf16[4,8]{1,0} custom-call(%b), '
             'custom_call_target="tpu_custom_call"')
    tr.ops["/device:TPU:0"] = [(call, 12, 5), (other, 20, 3), (call, 64, 2)]
    assert trace_reduce.op_name(call) == "paged_chai_decode_attention.9"
    assert trace_reduce.op_base(call) == "paged_chai_decode_attention"
    assert trace_reduce.op_base("copy") == "copy"
    assert trace_reduce.ops_within(tr, ["paged_chai_decode_attention"],
                                   ["jit_mixed_step"]) == pytest.approx(5e-9)


def test_breakdown_leaves_out_loops_and_cuts_labels():
    tr = _trace()
    loop = "%while.6 = (s32[], bf16[4]{0}) while((s32[], bf16[4]{0}) %t)"
    cond = "%cond.2 = (bf16[4]{0}) conditional(s32[] %i, (bf16[4]{0}) %a)"
    long_op = "%fusion.9 = bf16[4]{0} fusion(" + "x" * 400 + ")"
    tr.ops["/device:TPU:0"] = [(loop, 0, 90), (cond, 5, 80),
                               (long_op, 10, 30), ("copy", 50, 5)]
    top = trace_reduce.top_ops(tr)
    assert [s for _, s in top] == pytest.approx([30e-9, 5e-9])
    assert top[0][0] == long_op[:trace_reduce.LABEL_CHARS]
    assert top[1][0] == "copy"


# -- operation and byte counts ---------------------------------------------------
class _Cfg:
    """A tiny MHA configuration: 2 layers, 4 heads of 8, k_max 2."""
    head_dim, n_heads, n_kv_heads, k_max = 8, 4, 4, 2
    n_attn_layers, d_model, d_ff, vocab_size = 2, 32, 64, 100
    kv_cache_dtype, is_mha, gated_mlp = "", True, True
    n_experts, top_k, n_shared_experts, moe_d_ff = 4, 2, 1, 16
    ffn_types = ("dense", "moe")


def test_decode_attention_counts_by_hand():
    c = _Cfg()
    steady = _cost.STEADY
    # One STEADY slot at 10 positions: QK over 2 rep rows, AV over 4
    # heads: 2 * 10 * 8 * (2 + 4) per layer, 2 layers.
    flops, byts = _cost.decode_attn(c, [(steady, 10, 0, -1)])
    assert flops == 2 * 10 * 8 * 6 * 2
    # K: 2 rep rows, V: 4 rows, 10 positions, 8 wide, bf16; q/out 6 rows.
    assert byts == (10 * 8 * (2 + 4) * 2 + 6 * 8 * 2) * 2
    # WARMUP slots are not the clustered kernel's work.
    assert _cost.decode_attn(c, [(_cost.WARMUP, 10, 0, -1)]) == (0.0, 0.0)


def test_shared_prefix_bytes_counted_once():
    c = _Cfg()
    s = _cost.STEADY
    one = _cost.decode_attn(c, [(s, 10, 6, 0)])[1]
    two = _cost.decode_attn(c, [(s, 10, 6, 0), (s, 10, 6, 0)])[1]
    prefix = 6 * 8 * (2 + 4) * 2 * 2
    assert two - one == one - prefix


def test_matmul_flops_by_hand():
    c = _Cfg()
    attn = 32 * 8 * (2 * 4 + 2 * 4)
    dense = 3 * 32 * 64
    moe = 3 * 32 * 16 * (2 + 1) + 32 * 4
    assert _cost.matmul_flops_per_token(c) == \
        2 * (attn + dense + attn + moe + 32 * 100)


# -- names ---------------------------------------------------------------------------
def test_resolves_config_traffic_and_metrics_by_name():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        got = spec.resolve(cell["name"], bench)
        assert got["config"]["name"] == cell["config"]
        assert got["traffic"]["loop"] in ("open", "closed")
        for m in got["per_layer"]:
            assert callable(got["readers"][m["name"]].read)


def test_unknown_names_fail():
    bench = spec.load_benchmark()
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such-cell", bench)
    with pytest.raises(spec.SpecError):
        spec.load_config("no-such-config")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix")
    with pytest.raises(spec.SpecError):
        spec.load_module("metrics", "no_such_metric")


def test_added_files_are_found_by_name(tmp_path, monkeypatch):
    """A new cell needs only new files and an entry: resolution finds a
    metric reader and a traffic mix that did not exist before."""
    monkeypatch.setattr(spec, "BENCH_DIR", tmp_path)
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "c1.json").write_text(json.dumps({"model": {}}))
    (tmp_path / "traffic" / "t1.json").write_text(json.dumps(
        {"loop": "closed"}))
    (tmp_path / "metrics" / "m.one.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = {"configs": [{"name": "c1"}],
             "workloads": [{"name": "w1", "config": "c1", "traffic": "t1",
                            "chips": 1}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "m.one", "workloads": ["w1"]}]}
    got = spec.resolve("w1", bench)
    assert got["readers"]["m.one"].read({}) == 42.0


# -- traffic -------------------------------------------------------------------------
@pytest.mark.parametrize("mix", ["decode-long", "chat-poisson",
                                 "agent-prefix"])
def test_traffic_is_deterministic_in_the_seed(mix):
    m = spec.load_traffic(mix)

    def draw(seed):
        t = traffic_gen.Traffic(m, seed, 1000, 8)
        if t.loop == "open":
            return [(d, p.tolist(), o) for d, p, o, _ in t.schedule(20.0)]
        return [(p.tolist(), o) for p, o, _ in
                (t.next_request(c) for c in range(12))]

    seed = 2 ** 31 + 12345           # seeds wider than 32 signed bits
    assert draw(seed) == draw(seed)
    assert draw(seed) != draw(seed + 1)


def test_every_seed_serves_the_same_sizes():
    m = spec.load_traffic("decode-long")
    a = traffic_gen.Traffic(m, 1, 1000, 8)
    b = traffic_gen.Traffic(m, 2, 1000, 8)
    assert sorted(a.sizes) == sorted(b.sizes)
    n = len(a.sizes)
    # The same sizes in the same order for every seed, the pool cycled;
    # only the token ids differ.
    ra = [a.next_request(0) for _ in range(n + 3)]
    rb = [b.next_request(0) for _ in range(n + 3)]
    assert [(len(p), o) for p, o, _ in ra] == [(len(p), o) for p, o, _ in rb]
    assert sorted(len(p) for p, _, _ in ra[:n]) == sorted(p for p, _ in
                                                         a.sizes)
    assert ra[n][1] == ra[0][1]
    assert ra[0][0].tolist() != rb[0][0].tolist()


def test_quantile_lengths_follow_the_distribution():
    x = traffic_gen.quantile_lengths(
        {"dist": "lognormal", "median": 1024, "sigma": 0.7, "lo": 256,
         "hi": 4096}, 501)
    assert x.min() >= 256 and x.max() <= 4096
    assert abs(int(np.median(x)) - 1024) <= 1
    g = traffic_gen.exponential_gaps(2.0, 1000)
    assert abs(g.mean() - 0.5) < 0.02


def test_membership_cost_blind_to_pair_ties_not_to_permutation():
    """The cost ratio reads 1 for either representative of a two-head
    cluster and more than 1 for a valid partition of the wrong heads; the
    reference's own K-Means finds the planted clusters."""
    from bench.reference import decoder
    rng = np.random.default_rng(5)
    base = rng.normal(size=(4, 32))
    # Heads 0-1 and 2-3 are near copies; heads 4-5 stand alone.
    z = np.stack([base[0], base[0] + 0.01 * rng.normal(size=32),
                  base[1], base[1] + 0.01 * rng.normal(size=32),
                  base[2], base[3]])
    z = np.asarray(decoder.standardise(z))
    own = np.asarray(decoder.kmeans_reps(z, 4, 12))
    assert own[0] == own[1] and own[2] == own[3]
    assert own[4] == 4 and own[5] == 5
    feats = z[None]
    ref = check.rep_cost(feats, own[None])
    swapped = own.copy()
    swapped[[0, 1]] = 1 if own[0] == 0 else 0
    assert np.allclose(check.rep_cost(feats, swapped[None]), ref)
    wrong = np.array([[0, 0, 2, 3, 3, 5]])       # valid, wrong heads paired
    assert (check.rep_cost(feats, wrong) / ref)[0] > 10


# -- peaks ---------------------------------------------------------------------------
def test_peaks_known_and_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
