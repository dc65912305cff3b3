"""Engine-wide telemetry: metrics registry, step spans, request timelines.

Zero-dependency observability substrate for the serving engine, tiered by
``EngineConfig.telemetry``:

  off    null object; every hook is a no-op and the decode hot path is
         provably untouched (jaxpr-identical step — see
         benchmarks/bench_telemetry_overhead.py).
  basic  MetricsRegistry counters/gauges/histograms, per-request
         lifecycle timelines (enqueue → admit → phase transitions →
         first token → finish), and a span for every stage of
         ``EngineCore.step()``.

A span records its name, start and end (``perf_counter`` seconds), the
step ordinal, its own id and its parent's (the span open around it), and
the request uid where it acts on one request. Spans live in a ring of
the newest ``max_spans``; the evicted are counted. Every span is also a
``jax.profiler.TraceAnnotation("engine.<name>")``: under a profiler
session it lands in the trace beside the device's operations, on the
profiler's clock; without one it costs a no-op in C++.

The registry is snapshot-able (JSON-ready dict). Export formats
(Prometheus text, Chrome trace JSON, JSONL event logs) live in
``serving/exporters.py``.

All instrumentation hooks that allocate or format are guarded engine-side
by ``tel.enabled`` / handed a shared null context manager, so the "off"
tier costs at most a handful of attribute reads per step.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

TIERS = ("off", "basic")

# Default histogram buckets (seconds scale — covers sub-ms CPU decode
# steps through multi-second prefill/queue waits).  +Inf is implicit.
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


def _label_key(labels: Optional[Dict[str, Any]]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class MetricsRegistry:
    """Counters, gauges and bounded-bucket histograms with label sets.

    Names follow Prometheus conventions (``snake_case``, counters end in
    ``_total``, timings in ``_seconds``).  A (name, label-set) pair is one
    series.  ``snapshot()`` returns a plain JSON-ready dict.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # name -> {"help": str, "series": {lkey: float}}
        self._counters: Dict[str, Dict[str, Any]] = {}
        self._gauges: Dict[str, Dict[str, Any]] = {}
        # name -> {"help", "buckets": tuple, "series":
        #          {lkey: {"counts": [int]*(nb+1), "sum": f, "count": n}}}
        self._histograms: Dict[str, Dict[str, Any]] = {}

    # -- write side ------------------------------------------------------
    def counter(self, name: str, value: float = 1.0,
                labels: Optional[Dict[str, Any]] = None, help: str = ""):
        if value < 0:
            raise ValueError(f"counter {name} increment must be >= 0")
        key = _label_key(labels)
        with self._lock:
            m = self._counters.setdefault(name, {"help": help, "series": {}})
            m["series"][key] = m["series"].get(key, 0.0) + value

    def gauge(self, name: str, value: float,
              labels: Optional[Dict[str, Any]] = None, help: str = ""):
        key = _label_key(labels)
        with self._lock:
            m = self._gauges.setdefault(name, {"help": help, "series": {}})
            m["series"][key] = float(value)

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, Any]] = None,
                buckets: Optional[Tuple[float, ...]] = None, help: str = ""):
        key = _label_key(labels)
        with self._lock:
            m = self._histograms.get(name)
            if m is None:
                bks = tuple(buckets) if buckets else DEFAULT_BUCKETS
                if list(bks) != sorted(bks):
                    raise ValueError(f"histogram {name} buckets not sorted")
                m = self._histograms[name] = {
                    "help": help, "buckets": bks, "series": {}}
            s = m["series"].get(key)
            if s is None:
                s = m["series"][key] = {
                    "counts": [0] * (len(m["buckets"]) + 1),
                    "sum": 0.0, "count": 0}
            v = float(value)
            if math.isnan(v):
                return
            # First bucket whose upper bound >= v; last slot is +Inf.
            idx = len(m["buckets"])
            for i, ub in enumerate(m["buckets"]):
                if v <= ub:
                    idx = i
                    break
            s["counts"][idx] += 1
            s["sum"] += v
            s["count"] += 1

    # -- read side -------------------------------------------------------
    @staticmethod
    def _series_list(series, render):
        return [{"labels": dict(k), **render(v)} for k, v in
                sorted(series.items())]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "counters": {
                    n: {"help": m["help"],
                        "series": self._series_list(
                            m["series"], lambda v: {"value": v})}
                    for n, m in sorted(self._counters.items())},
                "gauges": {
                    n: {"help": m["help"],
                        "series": self._series_list(
                            m["series"], lambda v: {"value": v})}
                    for n, m in sorted(self._gauges.items())},
                "histograms": {
                    n: {"help": m["help"], "buckets": list(m["buckets"]),
                        "series": self._series_list(
                            m["series"],
                            lambda s: {"counts": list(s["counts"]),
                                       "sum": s["sum"],
                                       "count": s["count"]})}
                    for n, m in sorted(self._histograms.items())},
            }


class _Span:
    """Context manager recording one closed span into the telemetry sink,
    inside a profiler annotation of the same name."""

    __slots__ = ("_tel", "name", "step", "uid", "args", "id", "parent",
                 "t0", "_ann")

    def __init__(self, tel, name, step, uid, args):
        self._tel, self.name, self.step = tel, name, step
        self.uid, self.args = uid, args

    def __enter__(self):
        tel = self._tel
        self.id = tel._next_id
        tel._next_id += 1
        self.parent = tel._open[-1] if tel._open else None
        tel._open.append(self.id)
        meta = dict(self.args) if self.args else {}
        if self.uid is not None:
            meta["uid"] = self.uid
        self._ann = tel._profiler.TraceAnnotation("engine." + self.name,
                                                  **meta)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        tel = self._tel
        tel._open.pop()
        sp = {"name": self.name, "step": self.step, "t0": self.t0,
              "t1": t1, "id": self.id, "parent": self.parent}
        if self.uid is not None:
            sp["uid"] = self.uid
        if self.args:
            sp["args"] = self.args
        if exc_type is not None:
            sp["error"] = True
        if len(tel.spans) == tel.max_spans:
            tel.spans_evicted += 1
        tel.spans.append(sp)
        return False


class _NullCM:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CM = _NullCM()


def summarize_timeline(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Derive TTFT / queue time / ITL / counts from a raw event list."""
    first = {}
    for ev in events:
        first.setdefault(ev["ev"], ev["t"])
    t_enq = first.get("enqueue")
    t_admit = first.get("admit")
    t_first = first.get("first_token")
    t_fin = first.get("finish")
    tok_ts = [ev["t"] for ev in events if ev["ev"] == "tokens"]
    itl = [b - a for a, b in zip(tok_ts, tok_ts[1:])]
    out: Dict[str, Any] = {
        "n_events": len(events),
        "n_tokens": sum(int(ev.get("n", 1)) for ev in events
                        if ev["ev"] == "tokens"),
        "preemptions": sum(1 for ev in events if ev["ev"] == "preempt"),
        "phases": [ev["phase"] for ev in events if ev["ev"] == "phase"],
        "itl_s": itl,
    }
    if t_enq is not None and t_admit is not None:
        out["queue_s"] = t_admit - t_enq
    if t_enq is not None and t_first is not None:
        out["ttft_s"] = t_first - t_enq
    if t_enq is not None and t_fin is not None:
        out["latency_s"] = t_fin - t_enq
    fin = [ev for ev in events if ev["ev"] == "finish"]
    if fin:
        out["finish_reason"] = fin[-1].get("reason")
    return out


class Telemetry:
    """Live telemetry sink for one EngineCore (the basic tier).

    Spans are bounded: a ring keeps the newest ``max_spans`` and counts
    the older ones it evicts in ``spans_evicted``. Finished request
    timelines are kept in an LRU of ``max_timelines``; in-flight
    timelines are unbounded but naturally small (≤ queue + slots).
    """

    tier = "basic"
    enabled = True

    def __init__(self, *, max_spans: int = 1 << 16,
                 max_timelines: int = 1024):
        import jax.profiler
        self._profiler = jax.profiler
        self.registry = MetricsRegistry()
        self.max_spans = max_spans
        self.max_timelines = max_timelines
        self.spans: "collections.deque[Dict[str, Any]]" = \
            collections.deque(maxlen=max_spans)
        self.spans_evicted = 0
        self._next_id = 0
        self._open: List[int] = []      # open span ids, outermost first
        self._active: Dict[str, List[Dict[str, Any]]] = {}
        self._finished: "collections.OrderedDict[str, List[Dict[str, Any]]]" \
            = collections.OrderedDict()
        self._last_token_t: Dict[str, float] = {}

    # -- metrics passthrough --------------------------------------------
    def counter(self, name, value=1.0, help="", **labels):
        self.registry.counter(name, value, labels=labels or None, help=help)

    def gauge(self, name, value, help="", **labels):
        self.registry.gauge(name, value, labels=labels or None, help=help)

    def observe(self, name, value, help="", buckets=None, **labels):
        self.registry.observe(name, value, labels=labels or None,
                              buckets=buckets, help=help)

    # -- spans -----------------------------------------------------------
    def span(self, name: str, step: int = -1, uid=None, **args):
        """A span of one engine stage; ``uid`` names the request it acts
        on, ``args`` are kept with it (and in the profiler's trace)."""
        return _Span(self, name, step, uid, args or None)

    # -- request timelines ----------------------------------------------
    def event(self, uid: str, name: str, t: Optional[float] = None, **data):
        ev = {"uid": uid, "ev": name,
              "t": time.time() if t is None else t}
        if data:
            ev.update(data)
        tl = self._active.get(uid)
        if tl is None:
            if name == "enqueue":
                # Re-submitted uid: restart its timeline rather than
                # append to a sealed one.
                self._finished.pop(uid, None)
            tl = self._active[uid] = []
        tl.append(ev)

    def token(self, uid: str, n: int = 1, t: Optional[float] = None):
        """Record n tokens emitted for uid; feeds the ITL histogram."""
        now = time.time() if t is None else t
        last = self._last_token_t.get(uid)
        if last is not None and n == 1:
            self.observe("request_itl_seconds", now - last,
                         help="Inter-token latency (per decode token)")
        self._last_token_t[uid] = now
        self.event(uid, "tokens", t=now, n=n)

    def finish(self, uid: str):
        """Seal uid's timeline (moves it to the bounded finished LRU)."""
        self._last_token_t.pop(uid, None)
        tl = self._active.pop(uid, None)
        if tl is None:
            return
        self._finished[uid] = tl
        self._finished.move_to_end(uid)
        while len(self._finished) > self.max_timelines:
            self._finished.popitem(last=False)

    def timeline(self, uid: str) -> Optional[Dict[str, Any]]:
        tl = self._active.get(uid) or self._finished.get(uid)
        if tl is None:
            return None
        return {"uid": uid, "events": list(tl),
                "summary": summarize_timeline(tl)}

    def timelines(self) -> List[Dict[str, Any]]:
        out = [self.timeline(uid) for uid in
               list(self._finished) + list(self._active)]
        return [t for t in out if t is not None]

    def iter_events(self) -> Iterable[Dict[str, Any]]:
        for tl in list(self._finished.values()) + list(self._active.values()):
            for ev in tl:
                yield ev

    # -- snapshots -------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        snap = self.registry.snapshot()
        snap["meta"] = {"tier": self.tier, "spans": len(self.spans),
                        "spans_evicted": self.spans_evicted,
                        "timelines": len(self._active) + len(self._finished)}
        return snap


class NullTelemetry:
    """The "off" tier: every hook is a no-op; ``enabled`` gates all
    engine-side formatting/allocation so the hot path is untouched."""

    tier = "off"
    enabled = False
    registry = None
    spans: Tuple[Dict[str, Any], ...] = ()
    spans_evicted = 0

    def counter(self, *a, **k):
        pass

    def gauge(self, *a, **k):
        pass

    def observe(self, *a, **k):
        pass

    def span(self, *a, **k):
        return _NULL_CM

    def event(self, *a, **k):
        pass

    def token(self, *a, **k):
        pass

    def finish(self, *a, **k):
        pass

    def timeline(self, uid):
        return None

    def timelines(self):
        return []

    def iter_events(self):
        return iter(())

    def snapshot(self):
        return None


def make_telemetry(tier: str):
    """Factory: ``off`` → shared-shape NullTelemetry, else a live sink."""
    if tier not in TIERS:
        raise ValueError(f"telemetry tier must be one of {TIERS}, got {tier!r}")
    if tier == "off":
        return NullTelemetry()
    return Telemetry()
