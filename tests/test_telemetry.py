"""Engine telemetry: registry semantics, exporters, tiers, spans,
lifecycle timelines, decode healing, and the new kernel fault sites.

The cost contract (off-tier jaxpr identity, basic-tier overhead bound)
is gated end-to-end by ``benchmarks/bench_telemetry_overhead.py``; here
the jaxpr-identity claim gets a fast unit check and everything else is
exercised at the Python level.
"""
import asyncio
import json

import numpy as np
import pytest

import jax

from repro.configs.base import get_config, reduced
from repro.launch.steps import jaxpr_text
from repro.models import transformer as tfm
from repro.serving import exporters
from repro.serving.async_api import AsyncLLM
from repro.serving.engine import EngineConfig, EngineCore
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.sampling import SamplingParams
from repro.serving.telemetry import (MetricsRegistry, NullTelemetry,
                                     Telemetry, make_telemetry,
                                     summarize_timeline)

ARCH = "chai-llama-7b"
GREEDY = SamplingParams(max_new_tokens=8)

_params_cache = {}


def _model():
    if ARCH not in _params_cache:
        cfg = reduced(get_config(ARCH), n_layers=2, d_model=32, d_ff=64,
                      vocab=64).replace(dtype="float32")
        cfg = cfg.with_chai(enabled=True, warmup_tokens=3)
        _params_cache[ARCH] = (cfg,
                               tfm.init_params(cfg, jax.random.PRNGKey(0)))
    return _params_cache[ARCH]


def _ecfg(**kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("audit_level", "deep")
    kw.setdefault("telemetry", "basic")
    return EngineConfig(**kw)


def _drain(core, max_steps=400):
    outs = []
    for _ in range(max_steps):
        if not core.has_work():
            return outs
        outs.extend(core.step())
    raise AssertionError(f"engine did not drain in {max_steps} steps")


def _prompts(n, length=(6, 14), seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=int(rng.integers(*length))).tolist()
            for _ in range(n)]


def _counter_value(snap, name, **labels):
    total = 0.0
    for s in snap["counters"].get(name, {"series": []})["series"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            total += s["value"]
    return total


# ---------------------------------------------------------------------------
# registry semantics (pure units)
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram_series():
    reg = MetricsRegistry()
    reg.counter("req_total", 2, labels={"kind": "cold"}, help="h")
    reg.counter("req_total", labels={"kind": "cold"})
    reg.counter("req_total", labels={"kind": "warm"})
    reg.gauge("depth", 4)
    reg.gauge("depth", 7)                     # gauges overwrite
    reg.observe("lat_seconds", 0.003, buckets=(0.001, 0.01, 0.1))
    reg.observe("lat_seconds", 5.0, buckets=(0.001, 0.01, 0.1))
    reg.observe("lat_seconds", float("nan"), buckets=(0.001, 0.01, 0.1))
    snap = reg.snapshot()
    assert _counter_value(snap, "req_total", kind="cold") == 3
    assert _counter_value(snap, "req_total", kind="warm") == 1
    assert snap["gauges"]["depth"]["series"][0]["value"] == 7
    h = snap["histograms"]["lat_seconds"]["series"][0]
    assert h["count"] == 2 and h["sum"] == pytest.approx(5.003)
    assert h["counts"] == [0, 1, 0, 1]        # NaN dropped, 5.0 -> +Inf
    json.dumps(snap)                          # snapshot is JSON-ready
    with pytest.raises(ValueError):
        reg.counter("neg_total", -1)


def test_summarize_timeline_derivations():
    evs = [
        {"uid": 1, "ev": "enqueue", "t": 10.0},
        {"uid": 1, "ev": "admit", "t": 10.5},
        {"uid": 1, "ev": "phase", "t": 10.5, "phase": "PREFILL"},
        {"uid": 1, "ev": "first_token", "t": 11.0},
        {"uid": 1, "ev": "tokens", "t": 11.0, "n": 1},
        {"uid": 1, "ev": "tokens", "t": 11.2, "n": 1},
        {"uid": 1, "ev": "preempt", "t": 11.3},
        {"uid": 1, "ev": "tokens", "t": 11.6, "n": 1},
        {"uid": 1, "ev": "finish", "t": 11.7, "reason": "length"},
    ]
    s = summarize_timeline(evs)
    assert s["queue_s"] == pytest.approx(0.5)
    assert s["ttft_s"] == pytest.approx(1.0)
    assert s["latency_s"] == pytest.approx(1.7)
    assert s["n_tokens"] == 3 and s["preemptions"] == 1
    assert s["itl_s"] == [pytest.approx(0.2), pytest.approx(0.4)]
    assert s["phases"] == ["PREFILL"] and s["finish_reason"] == "length"


def test_make_telemetry_tiers():
    assert isinstance(make_telemetry("off"), NullTelemetry)
    assert not make_telemetry("off").enabled
    assert isinstance(make_telemetry("basic"), Telemetry)
    assert make_telemetry("basic").enabled
    # The tiers are off | basic: spans are recorded at basic.
    for tier in ("trace", "verbose"):
        with pytest.raises(ValueError):
            make_telemetry(tier)
    cfg, params = _model()
    with pytest.raises(ValueError):
        EngineCore(cfg, params, _ecfg(telemetry="loud"))
    with pytest.raises(ValueError):
        EngineCore(cfg, params, _ecfg(decode_heal_steps=-1))


# ---------------------------------------------------------------------------
# exporters (pure units)
# ---------------------------------------------------------------------------
def test_prometheus_text_roundtrip():
    reg = MetricsRegistry()
    reg.counter("req_total", 3, labels={"kind": "cold"}, help="requests")
    reg.gauge("depth", 2, help="queue depth")
    reg.observe("lat_seconds", 0.002, buckets=(0.001, 0.01), help="lat")
    text = exporters.to_prometheus(reg.snapshot())
    parsed = exporters.parse_prometheus(text)
    samples = {(n, tuple(sorted(l.items()))): v
               for n, l, v in parsed["samples"]}
    assert samples[("req_total", (("kind", "cold"),))] == 3
    assert samples[("depth", ())] == 2
    assert parsed["types"]["req_total"] == "counter"
    assert parsed["types"]["lat_seconds"] == "histogram"
    # histogram buckets are cumulative and end at +Inf == _count
    assert samples[("lat_seconds_bucket", (("le", "0.001"),))] == 0
    assert samples[("lat_seconds_bucket", (("le", "0.01"),))] == 1
    assert samples[("lat_seconds_bucket", (("le", "+Inf"),))] == 1
    assert samples[("lat_seconds_count", ())] == 1
    with pytest.raises(ValueError):
        exporters.parse_prometheus("not a metric line at all{")


def test_chrome_trace_roundtrip_and_validation():
    spans = [{"name": "step", "step": 3, "t0": 1.0, "t1": 1.5,
              "args": {"slots": 2}, "error": False},
             {"name": "sample", "step": 3, "t0": 1.1, "t1": 1.2,
              "args": {}, "error": True}]
    obj = exporters.to_chrome_trace(spans)
    evs = exporters.from_chrome_trace(json.dumps(obj))
    assert [e["name"] for e in evs] == ["step", "sample"]
    assert evs[0]["ph"] == "X" and evs[0]["dur"] == pytest.approx(5e5)
    assert evs[0]["args"]["step"] == 3 and evs[0]["args"]["slots"] == 2
    assert evs[1]["args"]["error"] is True
    with pytest.raises(ValueError):
        exporters.from_chrome_trace('{"no": "traceEvents"}')
    with pytest.raises(ValueError):
        exporters.from_chrome_trace(
            {"traceEvents": [{"ph": "X", "ts": 0, "pid": 0, "tid": 0}]})


def test_jsonl_events_roundtrip():
    evs = [{"uid": 2, "ev": "enqueue", "t": 5.0},
           {"uid": 1, "ev": "enqueue", "t": 4.0}]
    text = exporters.events_jsonl(evs)
    back = exporters.read_jsonl(text)
    assert [e["uid"] for e in back] == [1, 2]     # globally time-ordered


# ---------------------------------------------------------------------------
# engine integration: tiers, spans, timelines
# ---------------------------------------------------------------------------
def test_off_tier_is_noop():
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(telemetry="off"))
    reqs = [core.add_request(p, GREEDY) for p in _prompts(2)]
    _drain(core)
    assert all(r.finish_reason == "length" for r in reqs)
    assert core.metrics() is None and core.metrics_text() is None
    assert core.request_timeline(reqs[0].uid) is None
    assert core.step_trace()["traceEvents"] == []
    assert isinstance(core.tel, NullTelemetry)


def test_off_tier_decode_step_jaxpr_identical():
    """The telemetry tier never reaches the device program (fast unit
    variant of the bench gate): identical decode-step jaxpr text for an
    off engine and a basic engine, which records spans."""
    cfg, params = _model()
    off = EngineCore(cfg, params, _ecfg(telemetry="off"))
    trc = EngineCore(cfg, params, _ecfg(telemetry="basic"))
    off.add_request(_prompts(1)[0], GREEDY)
    _drain(off)
    ex = (off.params, {"tokens": off._next_tok_dev}, off._dev_state)
    assert jaxpr_text(off._mha_step, *ex) == jaxpr_text(trc._mha_step, *ex)
    cex = ex + (off._dev_ctx,)
    assert (jaxpr_text(off._chai_step, *cex)
            == jaxpr_text(trc._chai_step, *cex))


def test_trace_tier_step_spans_cover_stages():
    """At basic every decode step records one step span holding one
    decode.dispatch, sample, retire, kv.account and audit; admissions and
    CLUSTER transitions record one span per request."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg())
    [core.add_request(p, GREEDY) for p in _prompts(2)]
    _drain(core)
    by_step = {}
    for sp in core.tel.spans:
        by_step.setdefault(sp["step"], []).append(sp["name"])
    decode_steps = {s: n for s, n in by_step.items()
                    if "decode.dispatch" in n}
    assert decode_steps, by_step
    for names in decode_steps.values():
        for stage in ("decode.dispatch", "sample", "retire", "kv.account",
                      "step", "audit"):
            assert names.count(stage) == 1, (stage, names)
    names = [sp["name"] for sp in core.tel.spans]
    assert names.count("admit") == 2 and names.count("cluster") == 2
    # step ordinals are unique per step() call, monotone
    steps = sorted(by_step)
    assert steps == list(range(steps[0], steps[0] + len(steps)))
    # off records no spans at all
    core2 = EngineCore(cfg, params, _ecfg(telemetry="off"))
    core2.add_request(_prompts(1)[0], GREEDY)
    _drain(core2)
    assert list(core2.tel.spans) == []


def test_step_spans_nest_with_parent_ids_and_uids():
    """Each stage span's parent is the span open around it: the decode
    stages and the per-request stages sit under their step's ``step``
    span, and the per-request ones (chunked prefill, admission, CLUSTER)
    carry the request's uid. ``decode.dispatch`` names the program."""
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(prefill_chunk_tokens=8))
    reqs = [core.add_request(p, GREEDY)
            for p in _prompts(2, length=(12, 20), seed=11)]
    _drain(core)
    uids = {r.uid for r in reqs}
    spans = list(core.tel.spans)
    by_id = {sp["id"]: sp for sp in spans}
    assert len(by_id) == len(spans)
    steps = {sp["step"]: sp for sp in spans if sp["name"] == "step"}
    for sp in spans:
        if sp["name"] == "step":
            assert sp["parent"] is None
            continue
        parent = by_id[sp["parent"]]
        assert parent["t0"] <= sp["t0"] <= sp["t1"] <= parent["t1"]
        if sp["name"] in ("prefill.advance", "admit", "cluster",
                          "decode.dispatch", "sample", "retire",
                          "kv.account", "audit"):
            assert parent is steps[sp["step"]], sp
        if sp["name"] in ("prefill.advance", "admit", "cluster"):
            assert sp["uid"] in uids, sp
    names = [sp["name"] for sp in spans]
    for stage in ("prefill.advance", "admit", "cluster"):
        assert names.count(stage) >= len(reqs), (stage, names)
    programs = {sp["args"]["program"] for sp in spans
                if sp["name"] == "decode.dispatch"}
    assert "chai" in programs and programs <= {"chai", "mha", "mixed"}
    # The Chrome export carries the ids, parents and uids.
    evs = core.step_trace()["traceEvents"]
    assert {e["args"]["uid"] for e in evs if e["name"] == "admit"} == uids
    assert all("parent" in e["args"] for e in evs if e["name"] == "sample")


def test_off_tier_creates_no_profiler_annotation(monkeypatch):
    """``off`` hands out the shared null context manager: no span and no
    ``jax.profiler.TraceAnnotation``. ``basic`` makes one annotation per
    span, named ``engine.<stage>``."""
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    cfg, params = _model()
    off = EngineCore(cfg, params, _ecfg(telemetry="off"))
    off.add_request(_prompts(1)[0], GREEDY)
    _drain(off)
    assert made == [] and list(off.tel.spans) == []
    on = EngineCore(cfg, params, _ecfg(telemetry="basic"))
    on.add_request(_prompts(1)[0], GREEDY)
    _drain(on)
    assert made == ["engine." + sp["name"] for sp in
                    sorted(on.tel.spans, key=lambda sp: sp["id"])]


def test_span_ring_keeps_newest_and_counts_evicted():
    tel = Telemetry(max_spans=4)
    for i in range(10):
        with tel.span(f"s{i}", step=i):
            pass
    assert [sp["name"] for sp in tel.spans] == ["s6", "s7", "s8", "s9"]
    assert [sp["id"] for sp in tel.spans] == [6, 7, 8, 9]
    assert tel.spans_evicted == 6
    assert tel.snapshot()["meta"]["spans_evicted"] == 6
    # Nesting gives parent ids; a raising span is recorded as an error
    # and closes, so the next span's parent is again the outer one.
    with tel.span("outer") as outer:
        with pytest.raises(RuntimeError):
            with tel.span("inner", uid=7):
                raise RuntimeError("boom")
        with tel.span("next"):
            pass
    inner, nxt, out = list(tel.spans)[-3:]
    assert inner["parent"] == outer.id and inner["error"] is True
    assert inner["uid"] == 7
    assert nxt["parent"] == outer.id and out["parent"] is None


def test_engine_spans_land_in_profiler_trace(tmp_path):
    """Under ``jax.profiler.trace`` the engine's spans are host events of
    the trace (``engine.<stage>``), on the profiler's clock, each child
    inside its step; the program writes no ``bench.*`` event."""
    from jax.profiler import ProfileData
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg())
    core.add_request(_prompts(1)[0], GREEDY)
    core.step()                               # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            core.step()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events]
    assert not [n for n, _, _ in host if n.startswith("bench.")]
    ev = {}
    for n, a, b in host:
        ev.setdefault(n, []).append((a, b))
    steps = ev["engine.step"]
    assert len(steps) == 3
    for child in ("engine.decode.dispatch", "engine.sample",
                  "engine.retire"):
        assert len(ev[child]) == 3, child
        for a, b in ev[child]:
            assert any(s <= a and b <= e for s, e in steps), child


def test_request_timeline_lifecycle_and_metrics():
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(telemetry="basic",
                                         prefix_cache=True))
    reqs = [core.add_request(p, GREEDY) for p in _prompts(3, seed=2)]
    _drain(core)
    for r in reqs:
        tl = core.request_timeline(r.uid)
        names = [e["ev"] for e in tl["events"]]
        assert names[0] == "enqueue" and names[-1] == "finish"
        assert "admit" in names and "first_token" in names
        s = tl["summary"]
        assert s["n_tokens"] == len(r.generated) == 8
        assert s["finish_reason"] == "length"
        assert 0 <= s["queue_s"] and 0 <= s["ttft_s"] <= s["latency_s"]
        # CHAI phase walk appears on the timeline in engine order
        phases = [p for p in s["phases"]
                  if p in ("PREFILL", "WARMUP", "CLUSTER", "STEADY")]
        assert phases == ["PREFILL", "WARMUP", "CLUSTER", "STEADY"], s
    snap = core.metrics()
    assert _counter_value(snap, "requests_finished_total",
                          reason="length") == 3
    assert _counter_value(snap, "tokens_generated_total") == 24
    assert _counter_value(snap, "cluster_transitions_total") == 3
    assert snap["gauges"]["engine_active_slots"]["series"][0]["value"] == 0
    hist = snap["histograms"]["request_ttft_seconds"]["series"][0]
    assert hist["count"] == 3
    parsed = exporters.parse_prometheus(core.metrics_text())
    assert ("engine_steps_total" in parsed["types"]
            and parsed["types"]["request_ttft_seconds"] == "histogram")
    assert core.request_timeline(10**9) is None


def test_timeline_preempt_and_resume_events():
    cfg, params = _model()
    core = EngineCore(cfg, params, _ecfg(batch_slots=2, telemetry="basic",
                                         preemption=True))
    low = [core.add_request(p, SamplingParams(max_new_tokens=10))
           for p in _prompts(2, seed=4)]
    core.step()
    core.step()
    hi = core.add_request(_prompts(1, seed=5)[0],
                          SamplingParams(max_new_tokens=4), priority=1)
    _drain(core)
    assert hi.finish_reason == "length"
    victim = next(r for r in low if r.preemptions > 0)
    tl = core.request_timeline(victim.uid)
    names = [e["ev"] for e in tl["events"]]
    assert "preempt" in names and "resume" in names
    assert tl["summary"]["preemptions"] == victim.preemptions
    snap = core.metrics()
    assert _counter_value(snap, "preemptions_total") >= 1


# ---------------------------------------------------------------------------
# decode healing (satellite 1)
# ---------------------------------------------------------------------------
def test_decode_heals_after_clean_steps_with_parity():
    """``decode_heal_steps=N``: a transient fused-decode failure degrades
    to the jnp reference path, then N consecutive clean decode steps
    flip the engine back to the fused jits — tokens bitwise match the
    fault-free run across the degrade AND the heal."""
    cfg, params = _model()
    prompts = _prompts(2, seed=6)

    def run(faults, heal):
        core = EngineCore(cfg, params,
                          _ecfg(telemetry="basic",
                                decode_heal_steps=heal), faults=faults)
        reqs = [core.add_request(p, SamplingParams(max_new_tokens=12))
                for p in prompts]
        _drain(core)
        return core, reqs

    _, clean = run(None, 3)
    inj = FaultInjector([FaultSpec("kernel.decode", count=1)], seed=0)
    core, reqs = run(inj, 3)
    fs = core.fault_stats()
    assert fs["decode_fallbacks"] == 1
    assert fs["decode_heals"] == 1
    assert fs["degraded_decode"] is False       # healed before drain
    for c, f in zip(clean, reqs):
        assert list(f.generated) == list(c.generated)
    snap = core.metrics()
    assert _counter_value(snap, "decode_heals_total") == 1
    assert _counter_value(snap, "decode_fallbacks_total") == 1
    assert (snap["gauges"]["engine_degraded_decode"]["series"][0]["value"]
            == 0)


def test_decode_heal_resets_on_refire():
    """A kernel arm that keeps firing while degraded pins the engine on
    the reference path: every firing resets the clean-step count, so
    with an unlimited arm the engine must NOT heal."""
    cfg, params = _model()
    inj = FaultInjector([FaultSpec("kernel.decode", count=-1)], seed=0)
    core = EngineCore(cfg, params, _ecfg(decode_heal_steps=2),
                      faults=inj)
    core.add_request(_prompts(1)[0], GREEDY)
    _drain(core)
    fs = core.fault_stats()
    assert fs["degraded_decode"] is True and fs["decode_heals"] == 0


def test_decode_heal_disabled_by_default():
    cfg, params = _model()
    assert EngineConfig().decode_heal_steps == 0
    inj = FaultInjector([FaultSpec("kernel.decode", count=1)], seed=0)
    core = EngineCore(cfg, params, _ecfg(), faults=inj)
    core.add_request(_prompts(1)[0], GREEDY)
    _drain(core)
    fs = core.fault_stats()
    assert fs["degraded_decode"] is True and fs["decode_heals"] == 0


# ---------------------------------------------------------------------------
# new kernel fault sites (satellite 2)
# ---------------------------------------------------------------------------
def test_kernel_prefill_fault_quarantines_request():
    """An injected prefill-kernel failure quarantines THAT request at
    admission; the other request decodes to parity with a clean run."""
    cfg, params = _model()
    prompts = _prompts(2, seed=8)

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(telemetry="basic"),
                          faults=faults)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return core, reqs

    _, clean = run(None)
    inj = FaultInjector([FaultSpec("kernel.prefill", uid=0, count=1)],
                        seed=0)
    core, reqs = run(inj)
    assert [f["site"] for f in inj.fired] == ["kernel.prefill"]
    assert reqs[0].finish_reason == "error"
    assert "prefill" in reqs[0].error
    assert reqs[1].finish_reason == "length"
    assert list(reqs[1].generated) == list(clean[1].generated)
    assert core.fault_stats()["quarantined"] == 1
    snap = core.metrics()
    assert _counter_value(snap, "faults_injected_total",
                          site="kernel.prefill") == 1
    assert _counter_value(snap, "requests_quarantined_total") == 1


def test_kernel_cluster_fault_quarantines_transitioning_request():
    """An injected clustering-kernel failure at the WARMUP->CLUSTER edge
    quarantines the transitioning request BEFORE the pools mutate; the
    other slot keeps decoding to parity."""
    cfg, params = _model()
    prompts = _prompts(2, seed=9)

    def run(faults):
        core = EngineCore(cfg, params, _ecfg(telemetry="basic"),
                          faults=faults)
        reqs = [core.add_request(p, GREEDY) for p in prompts]
        _drain(core)
        return core, reqs

    _, clean = run(None)
    inj = FaultInjector([FaultSpec("kernel.cluster", uid=1, count=1)],
                        seed=0)
    core, reqs = run(inj)
    assert [f["site"] for f in inj.fired] == ["kernel.cluster"]
    assert reqs[1].finish_reason == "error"
    assert "cluster" in reqs[1].error
    assert reqs[0].finish_reason == "length"
    assert list(reqs[0].generated) == list(clean[0].generated)
    assert core.fault_stats()["quarantined"] == 1
    snap = core.metrics()
    assert _counter_value(snap, "faults_injected_total",
                          site="kernel.cluster") == 1
    # the quarantine landed on the victim's timeline
    tl = core.request_timeline(reqs[1].uid)
    assert "quarantine" in [e["ev"] for e in tl["events"]]


def test_soak_report_carries_telemetry_section():
    from repro.serving.soak import run_soak
    cfg, params = _model()
    ecfg = _ecfg(batch_slots=3, prefix_cache=True, telemetry="basic")
    report = run_soak(cfg, params, ecfg, seed=3, n_requests=8)
    tel = report["telemetry"]
    assert tel["metrics"]["counters"]["engine_steps_total"]
    assert tel["chrome_trace"]["traceEvents"]
    assert tel["timelines"]
    json.dumps(report, default=float)           # report stays JSON-ready
    off = run_soak(cfg, params, _ecfg(batch_slots=3, prefix_cache=True,
                                      telemetry="off"),
                   seed=3, n_requests=8)
    assert "telemetry" not in off
    # telemetry never perturbs the deterministic sections
    assert off["requests"] == report["requests"]


# ---------------------------------------------------------------------------
# async front door (satellite 3's engine-side accessors)
# ---------------------------------------------------------------------------
def test_async_metrics_and_timeline_accessors():
    cfg, params = _model()
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, size=8).tolist()

    async def main():
        kw = dict(batch_slots=2, max_seq=64, page_size=8,
                  telemetry="basic")
        async with AsyncLLM(cfg, params, EngineConfig(**kw)) as llm:
            out = await llm.generate(prompt, GREEDY)
            assert len(out.token_ids) == 8
            text = await llm.metrics_text()
            parsed = exporters.parse_prometheus(text)
            names = {s[0] for s in parsed["samples"]}
            assert {"requests_finished_total", "driver_restarts",
                    "tokens_generated_total"} <= names
            tl = await llm.timeline(out.uid)
            assert tl["summary"]["n_tokens"] == 8
            assert await llm.timeline(10**9) is None
            trace = await llm.step_trace()
            assert any(e["name"] == "decode.dispatch"
                       for e in trace["traceEvents"])
        async with AsyncLLM(cfg, params, EngineConfig(
                batch_slots=2, max_seq=64, page_size=8,
                telemetry="off")) as llm_off:
            await llm_off.generate(prompt, GREEDY)
            assert await llm_off.metrics() is None
            assert await llm_off.metrics_text() is None

    asyncio.run(main())


def test_decode_tile_counters_count_live_tiles():
    """``decode_attn_tiles_walked`` / ``_live`` count one launch of the
    fused paged decode kernel per step that runs it: the grid (slots x
    tiles of max_seq) and the tiles up to each decoding slot's position,
    read here from the device state just before each dispatch. A
    126-token prompt crosses the 128-token tile boundary while it
    decodes; the short one stays in the first tile, so tiles are
    skipped."""
    from repro.core import cache as chai_cache
    from repro.core.chai_attention import paged_decode_tiling
    cfg, params = _model()
    b = 2
    core = EngineCore(cfg, params, _ecfg(batch_slots=b, max_seq=256))
    prompts = _prompts(b, seed=5)
    prompts[0] = (prompts[0] * 20)[:126]
    for p in prompts:
        core.add_request(p, GREEDY)
    expect = {"walked": 0, "live": 0, "steps": 0}
    dispatch = core._dispatch_decode

    def spy(program, inputs, relay):
        if program != "mha":
            ts, n_tiles = paged_decode_tiling(cfg, core._dev_state)
            pos = np.asarray(core._dev_state["pos"])
            decoding = [core._slot_req[i] is not None
                        and core._phases[i] != chai_cache.PHASE_PREFILL
                        for i in range(b)]
            expect["steps"] += 1
            expect["walked"] += b * n_tiles
            expect["live"] += sum(int(pos[i]) // ts + 1 if decoding[i]
                                  else 1 for i in range(b))
        return dispatch(program, inputs, relay)

    core._dispatch_decode = spy
    _drain(core)
    assert paged_decode_tiling(cfg, core._dev_state) == (128, 2)
    snap = core.metrics()
    walked = _counter_value(snap, "decode_attn_tiles_walked")
    live = _counter_value(snap, "decode_attn_tiles_live")
    assert expect["steps"] > 0
    assert walked == expect["walked"] == 4 * expect["steps"]
    assert live == expect["live"]
    assert expect["steps"] * 2 < live < walked
