"""The engine's step-stage spans on the device trace's clock.

At the ``basic`` telemetry tier, which the traced run uses, the engine keeps
a span for every stage of ``EngineCore.step()`` in memory
(``serving/telemetry.py``): its name, start and end on ``perf_counter``,
the step ordinal, and its parent's id. The trace holds the benchmark's own
``bench.step`` annotation around each ``eng.step()`` call, on the
profiler's clock. The k-th traced ``bench.step`` encloses the k-th engine
``step`` span that started after the trace did, so the spans of that step
are moved onto the trace's clock by the difference of the two starts. The
spread of that difference over the traced steps (the alignment residual)
is printed on stderr.

Each idle interval of the device (``trace_reduce.gaps``, the intervals
``idle_share`` counts) that falls inside a ``bench.step`` is then put down,
instant by instant, to the innermost engine span covering it. Idle time in
the ``step`` span's own time, outside every stage, and idle time in a
``bench.step`` outside the engine's ``step`` span, stay unattributed; the
covered share is printed beside the split.

The split is as good as the profiler's placing of the device's timeline
against the host's, which on a TPU v5e has read up to about 1 ms early. A
decode program cannot start before the host opened its ``decode.dispatch``
span, so where the trace shows one earlier, the device's idle intervals are
moved later by the largest such lead: the least shift that puts every
decode program after its dispatch. The shift is printed with the split.
"""
from __future__ import annotations

import bisect
import statistics
import sys
from collections import defaultdict

from bench import trace_reduce
from bench.metrics.decode_step_ms import DECODE_PROGRAMS

# Engine spans per metric. ``audit`` is the scheduler's per-step check of
# its own bookkeeping and ``kv.account`` its KV accounting; ``preempt.swap``
# runs inside an admission.
SCHEDULE = ("prefill.advance", "admit", "cluster", "relay.form", "retire",
            "kv.account", "audit", "preempt.swap")
DISPATCH = ("decode.dispatch",)
SAMPLE = ("sample",)

_KEY = "engine_idle"          # the attribution, computed once per ctx


def align(spans, bench_steps, t_start):
    """Pair each traced ``bench.step`` (``(name, start_ns, dur_ns)``) with
    the engine ``step`` span of the same rank among those that started at
    or after ``t_start`` (``perf_counter`` seconds). Returns
    ``[(bench_step, offset_ns, [spans of that step])]``; ``offset_ns``
    moves an engine time (seconds x 1e9) onto the trace's clock."""
    steps = sorted((sp for sp in spans
                    if sp["name"] == "step" and sp["t0"] >= t_start),
                   key=lambda sp: sp["t0"])
    marks = sorted(bench_steps, key=lambda e: e[1])
    by_step = defaultdict(list)
    for sp in spans:
        by_step[sp["step"]].append(sp)
    return [(mark, mark[1] - sp["t0"] * 1e9, by_step[sp["step"]])
            for mark, sp in zip(marks, steps)]


def _idle_ns(gaps, starts, lo, hi):
    """Device idle time in [lo, hi) from sorted disjoint ``gaps``."""
    total = 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(gaps) and gaps[i][0] < hi:
        a, b = gaps[i]
        total += max(0.0, min(b, hi) - max(a, lo))
        i += 1
    return total


def split_step(gaps, starts, mark, offset_ns, spans):
    """Idle ns inside one ``bench.step`` by the innermost engine span at
    each instant (``"none"`` where no engine span covers it)."""
    lo, hi = mark[1], mark[1] + mark[2]
    placed = [(sp["name"], sp["t0"] * 1e9 + offset_ns,
               sp["t1"] * 1e9 + offset_ns) for sp in spans]
    cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                              for _, a, b in placed for t in (a, b)})
    out = defaultdict(float)
    for x, y in zip(cuts, cuts[1:]):
        idle = _idle_ns(gaps, starts, x, y)
        if idle <= 0:
            continue
        cover = [(b - a, name) for name, a, b in placed if a <= x and y <= b]
        out[min(cover)[1] if cover else "none"] += idle
    return out


def device_leads(tr, pairs, within_ns=5e6):
    """For each ``decode.dispatch`` span of the traced steps: ns by which
    the decode program that starts nearest to it (within ``within_ns``)
    starts before the span opens (positive: the trace places the device
    early against the host)."""
    if not tr.modules:
        return []
    mods = sorted(s for _, s, _ in trace_reduce.matching(
        tr.modules[sorted(tr.modules)[0]], DECODE_PROGRAMS))
    out = []
    for _, off, sps in pairs:
        for sp in sps:
            if sp["name"] != "decode.dispatch":
                continue
            t0 = sp["t0"] * 1e9 + off
            i = bisect.bisect_left(mods, t0)
            near = min(mods[max(i - 1, 0):i + 1], key=lambda m: abs(m - t0),
                       default=None)
            if near is not None and abs(near - t0) < within_ns:
                out.append(t0 - near)
    return out


def attribute(ctx):
    """``{"steps", "idle_ns": {span name: ns}, "residual_ns"}`` over the
    traced steps, or None where the trace or the engine's spans are
    missing (an engine that records no spans at this tier)."""
    if _KEY in ctx:
        return ctx[_KEY]
    ctx[_KEY] = None
    tr, drv, eng = ctx["trace"], ctx["driver"], ctx["eng"]
    spans = list(getattr(eng.tel, "spans", ()))
    if tr is None or not tr.ops or drv.trace_t0 is None or not spans:
        return None
    marks = [e for e in tr.host if e[0] == "bench.step"
             and e[1] >= tr.window[0] and e[1] + e[2] <= tr.window[1]]
    pairs = align(spans, marks, drv.trace_t0)
    if not pairs:
        return None
    leads = device_leads(tr, pairs)
    shift = max(max(leads, default=0.0), 0.0)
    gaps = [(a + shift, b + shift) for a, b in trace_reduce.gaps(tr)]
    starts = [a for a, _ in gaps]
    idle = defaultdict(float)
    for mark, off, sps in pairs:
        for name, ns in split_step(gaps, starts, mark, off, sps).items():
            idle[name] += ns
    offs = [off for _, off, _ in pairs]
    res = {"steps": len(pairs), "idle_ns": dict(idle),
           "residual_ns": max(offs) - min(offs), "shift_ns": shift}
    ctx[_KEY] = res
    _report(res, len(marks), offs, leads)
    return res


def _report(res, n_marks, offs, leads):
    n, idle = res["steps"], res["idle_ns"]
    inside = sum(idle.values())
    covered = sum(idle.get(k, 0.0) for k in SCHEDULE + DISPATCH + SAMPLE)
    per = {k: round(v * 1e-6 / n, 4) for k, v in
           sorted(idle.items(), key=lambda kv: -kv[1])}
    q = (statistics.quantiles(offs, n=4) if len(offs) > 1
         else [offs[0]] * 3)
    print(f"[bench] engine spans: {n} traced steps paired of {n_marks} "
          f"bench.step; alignment residual (max - min of bench.step start "
          f"- engine step start) {res['residual_ns'] * 1e-6:.6f} ms, "
          f"quartile spread {(q[2] - q[0]) * 1e-6:.6f} ms",
          file=sys.stderr)
    print(f"[bench] device idle inside bench.step: "
          f"{inside * 1e-6 / n:.4f} ms per step, by innermost engine span "
          f"(ms per step) {per}; covered by the three idle_ms_per_step "
          f"metrics {100.0 * covered / max(inside, 1e-12):.2f}%",
          file=sys.stderr)
    if leads:
        early = [x for x in leads if x > 0]
        print(f"[bench] clock check: as traced, the decode program starts "
              f"before its decode.dispatch span in {len(early)} of "
              f"{len(leads)} steps, {-max(leads) * 1e-6:.4f} to "
              f"{-min(leads) * 1e-6:.4f} ms after the span opens; device "
              f"idle moved {res['shift_ns'] * 1e-6:.4f} ms later",
              file=sys.stderr)


def idle_ms_per_step(ctx, names):
    """Device idle ms per traced engine step under the spans ``names``."""
    res = attribute(ctx)
    if res is None:
        return None
    return sum(res["idle_ns"].get(k, 0.0) for k in names) * 1e-6 \
        / res["steps"]
