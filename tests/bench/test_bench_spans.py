"""The engine's step-stage spans put onto the device trace's clock, and the
three ``idle_ms_per_step`` readers, on a hand-built trace: two traced steps
with hand-placed device operations and engine spans on a clock 1000 s
apart from the trace's."""
from types import SimpleNamespace

import pytest

from bench import spec, trace_reduce
from bench.metrics import _spans

US = 1000.0                         # trace times below are in µs
CLOCK = 1000.0                      # engine clock - trace clock, seconds


def _engine(name, a, b, step, uid=None, lag=0.0):
    """An engine span at trace µs [a, b), on the engine's clock, started
    ``lag`` µs later than the trace puts it."""
    sp = {"name": name, "t0": (a + lag) * US * 1e-9 + CLOCK,
          "t1": (b + lag) * US * 1e-9 + CLOCK, "step": step}
    if uid is not None:
        sp["uid"] = uid
    return sp


def _ctx(lag_b=0.0, device=0.0):
    # Device: the decode programs and sampling reads of two steps, a CLUSTER
    # program inside step 2's cluster span, placed ``device`` µs later than
    # below. Window [0, 250) µs.
    ops = {"/device:TPU:0": [(n, (a + device) * US, (b - a) * US)
                             for n, a, b in (
        ("decode", 26, 88), ("argmax", 89, 90), ("cluster", 123, 124),
        ("decode", 130, 190), ("argmax", 192, 193))]}
    host = [("bench.window", 0, 250 * US)] + [
        (n, a * US, (b - a) * US) for n, a, b in (
            ("bench.step", 10, 110), ("bench.record", 110, 120),
            ("bench.step", 120, 220), ("bench.record", 220, 230))]
    modules = {"/device:TPU:0": [
        ("jit_serve_step(1)", (26 + device) * US, 62 * US),
        ("jit_mixed_step(2)", (130 + device) * US, 60 * US)]}
    tr = trace_reduce.Trace(ops, modules, host, (0.0, 250 * US))
    spans = [
        # a step before the trace started: not paired
        _engine("step", -60, -20, 6), _engine("sample", -50, -30, 6),
        _engine("step", 10, 106, 7), _engine("admit", 12, 20, 7, uid=1),
        _engine("relay.form", 20, 22, 7),
        _engine("decode.dispatch", 22, 30, 7),
        _engine("sample", 30, 92, 7), _engine("retire", 92, 100, 7),
        _engine("kv.account", 100, 103, 7), _engine("audit", 103, 105, 7)]
    spans += [_engine(n, a, b, 8, uid=uid, lag=lag_b) for n, a, b, uid in (
        ("step", 120, 216, None), ("cluster", 121, 125, 2),
        ("decode.dispatch", 125, 131, None), ("sample", 131, 195, None),
        ("retire", 195, 205, None), ("kv.account", 205, 208, None),
        ("audit", 208, 214, None))]
    drv = SimpleNamespace(trace_t0=CLOCK + 5 * US * 1e-9)
    eng = SimpleNamespace(tel=SimpleNamespace(spans=spans))
    return {"trace": tr, "driver": drv, "eng": eng}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


# Idle inside the two bench.step spans, by hand:
#   step 7 [10, 110): [10, 26) [88, 89) [90, 110) = 37 µs: step's own 2
#     ([10, 12)) and 1 ([105, 106)), admit 8, relay.form 2, dispatch 4
#     ([22, 26)), sample 1 + 2, retire 8, kv.account 3, audit 2, and 4
#     after the engine's step span closed ([106, 110)).
#   step 8 [120, 220): [120, 123) [124, 130) [190, 192) [193, 220) = 38
#     µs: step's own 1 + 2, cluster 2 + 1, dispatch 5, sample 2 + 2,
#     retire 10, kv.account 3, audit 6, and 4 after the step span.
# The idle [110, 120) of bench.record is not inside a bench.step.
SCHEDULE_US = 8 + 2 + 8 + 3 + 2 + 3 + 10 + 3 + 6           # 45
DISPATCH_US = 4 + 5
SAMPLE_US = 1 + 2 + 2 + 2


@pytest.mark.parametrize("lag_b", [0.0, 0.3])
def test_idle_split_by_engine_stage(lag_b):
    ctx = _ctx(lag_b)
    res = _spans.attribute(ctx)
    assert res["steps"] == 2
    idle = {k: v / US for k, v in res["idle_ns"].items()}
    assert idle["step"] == pytest.approx(6)
    assert idle["none"] == pytest.approx(8)
    assert sum(idle.values()) == pytest.approx(37 + 38)
    # Anchored per step: a lag of the engine's clock in one step moves
    # nothing, and shows as the alignment residual.
    assert res["residual_ns"] == pytest.approx(lag_b * US)
    per_step_ms = {"schedule": SCHEDULE_US, "dispatch": DISPATCH_US,
                   "sample": SAMPLE_US}
    for part, us in per_step_ms.items():
        assert _read(f"idle_ms_per_step.{part}", ctx) == \
            pytest.approx(us / 2 * 1e-3)


def test_pairing_skips_steps_before_the_trace():
    ctx = _ctx()
    tr = ctx["trace"]
    marks = [e for e in tr.host if e[0] == "bench.step"]
    pairs = _spans.align(ctx["eng"].tel.spans, marks, ctx["driver"].trace_t0)
    assert [sorted(sp["step"] for sp in sps)[0] for _, _, sps in pairs] == \
        [7, 8]
    assert [off for _, off, _ in pairs] == \
        pytest.approx([-CLOCK * 1e9] * 2)


def test_no_engine_spans_reads_nothing():
    """An engine that records no step spans at the traced run's tier (a
    program older than them), or a run without a trace: no number and no
    error."""
    ctx = _ctx()
    ctx["eng"].tel.spans = []
    for part in ("schedule", "dispatch", "sample"):
        assert _read(f"idle_ms_per_step.{part}", ctx) is None
    ctx = _ctx()
    ctx["trace"] = None
    assert _read("idle_ms_per_step.sample", ctx) is None


def test_clock_check_reads_program_start_against_dispatch():
    """Each decode program starts after its ``decode.dispatch`` span opens
    (4 and 5 µs here); a device timeline placed early reads positive."""
    ctx = _ctx()
    marks = [e for e in ctx["trace"].host if e[0] == "bench.step"]
    pairs = _spans.align(ctx["eng"].tel.spans, marks, ctx["driver"].trace_t0)
    assert _spans.device_leads(ctx["trace"], pairs) == \
        pytest.approx([-4 * US, -5 * US])
    early = _ctx(device=-10.0)
    pairs = _spans.align(early["eng"].tel.spans, marks,
                         early["driver"].trace_t0)
    assert _spans.device_leads(early["trace"], pairs) == \
        pytest.approx([6 * US, 5 * US])


def test_device_placed_early_is_moved_after_its_dispatch():
    """A device timeline placed 10 µs early is moved later by the largest
    lead (6 µs): the split then reads as if the trace had placed it 4 µs
    early, where the first step's program starts as its dispatch opens."""
    res = _spans.attribute(_ctx(device=-10.0))
    assert res["shift_ns"] == pytest.approx(6 * US)
    ref = _spans.attribute(_ctx(device=-4.0))
    assert ref["shift_ns"] == 0.0
    assert res["idle_ns"].keys() == ref["idle_ns"].keys()
    for k, v in ref["idle_ns"].items():
        assert res["idle_ns"][k] == pytest.approx(v), k
