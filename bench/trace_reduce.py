"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a ``Trace``: the device's operations and programs (XLA modules) per chip,
and the benchmark's own host annotations, all on one clock in
nanoseconds. The functions below work on that plain structure, so a test
can hand them a synthetic one.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]           # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]            # chip plane -> device operations
    modules: Dict[str, List[Event]]        # chip plane -> XLA programs
    host: List[Event]                      # benchmark host annotations
    window: Tuple[float, float]            # traced window [t0, t1) in ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, window: Tuple[float, float] = None,
                host_prefix: str = "bench.") -> Trace:
    """Device planes are ``/device:<chip>``; their "XLA Ops" line holds the
    operations (a loop or a conditional beside the operations it runs) and
    their "XLA Modules" line the programs. The "Async XLA Ops" line, which
    spans each asynchronous copy from its start to its done, is left out:
    a copy in flight is not an operation running. Host events whose name
    starts with ``host_prefix`` are the benchmark's annotations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:")
        lines = list(plane.lines)
        # A device plane without an "XLA Ops" line: every line is ops.
        has_ops = any(ln.name == "XLA Ops" for ln in lines)
        for line in lines:
            lname = line.name or ""
            for ev in line.events:
                e = (ev.name, float(ev.start_ns), float(ev.duration_ns))
                if dev and "Modules" in lname:
                    modules[plane.name].append(e)
                elif dev and (lname == "XLA Ops" or not has_ops):
                    ops[plane.name].append(e)
                elif not dev and ev.name.startswith(host_prefix):
                    host.append(e)
    if window is None:
        spans = [e for e in host if e[0] == host_prefix + "window"]
        if spans:
            window = (spans[0][1], spans[0][1] + spans[0][2])
        else:
            every = [e for evs in ops.values() for e in evs] + host
            window = (min((e[1] for e in every), default=0.0),
                      max((e[1] + e[2] for e in every), default=0.0))
    return Trace(dict(ops), dict(modules), host, window)


# -- interval arithmetic ----------------------------------------------------
def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by ``events``."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def device_busy_s(tr: Trace) -> float:
    """Seconds in which any operation ran, averaged over the chips."""
    if not tr.ops:
        return 0.0
    lo, hi = tr.window
    return sum(busy_ns(evs, lo, hi) for evs in tr.ops.values()) \
        / len(tr.ops) * 1e-9


def idle_share(tr: Trace) -> float:
    return 1.0 - device_busy_s(tr) / max(tr.window_s, 1e-12)


def matching(events: Sequence[Event], names: Sequence[str]) -> List[Event]:
    return [e for e in events if any(n in e[0] for n in names)]


def time_of(tr: Trace, names: Sequence[str], kind: str = "ops") -> float:
    """Seconds of device events whose name contains any of ``names``,
    summed over chips and clipped to the window."""
    lo, hi = tr.window
    src = tr.ops if kind == "ops" else tr.modules
    return sum(d for evs in src.values()
               for _, _, d in clip(matching(evs, names), lo, hi)) * 1e-9


def op_name(name: str) -> str:
    """The HLO instruction name of a device operation. A TPU trace names
    each operation by its HLO text, ``%<name> = <shape> <opcode>(...)``;
    a name without that form is returned as it is."""
    if name.startswith("%") and " = " in name:
        return name[1:].split(" = ", 1)[0]
    return name


def op_base(name: str) -> str:
    """``op_name`` without the ``.<n>`` that tells instances apart: a
    kernel call's base is the name of the jitted function that holds it."""
    base, _, n = op_name(name).rpartition(".")
    return base if base and n.isdigit() else op_name(name)


def ops_within(tr: Trace, names: Sequence[str],
               programs: Sequence[str]) -> float:
    """Seconds of device operations whose base name (``op_base``) is one of
    ``names`` that start inside a program (XLA module) whose name contains
    any of ``programs``, summed over chips and clipped to the window."""
    lo, hi = tr.window
    want = set(names)
    total = 0.0
    for plane, evs in tr.ops.items():
        spans = union(matching(tr.modules.get(plane, []), programs))
        starts = [a for a, _ in spans]
        for name, s, d in clip(evs, lo, hi):
            if op_base(name) not in want:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                total += d
    return total * 1e-9


def count_of(tr: Trace, names: Sequence[str], kind: str = "modules") -> int:
    lo, hi = tr.window
    src = tr.ops if kind == "ops" else tr.modules
    return sum(len(clip(matching(evs, names), lo, hi))
               for evs in src.values())


CONTAINER = re.compile(r"[)}\]] (while|conditional|call)\(")
LABEL_CHARS = 160


def top_ops(tr: Trace, k: int = 10, kind: str = "ops") -> List[list]:
    """The ``k`` operation (or, with ``kind="modules"``, program) names
    that took most device time (seconds, summed over chips). Loops and
    conditionals are left out, as the operations they run are listed; an
    operation's HLO text is cut to its first ``LABEL_CHARS`` characters."""
    lo, hi = tr.window
    tot: Dict[str, float] = defaultdict(float)
    for evs in getattr(tr, kind).values():
        for name, _, d in clip(evs, lo, hi):
            if kind == "ops" and CONTAINER.search(name):
                continue
            tot[name] += d * 1e-9
    top = sorted(tot.items(), key=lambda x: -x[1])[:k]
    return [[n[:LABEL_CHARS], s] for n, s in top]


def gaps(tr: Trace) -> List[Tuple[float, float]]:
    """Idle [start, end) intervals of the first chip inside the window."""
    if not tr.ops:
        return [tr.window]
    lo, hi = tr.window
    evs = tr.ops[sorted(tr.ops)[0]]
    out, t = [], lo
    for a, b in union(clip(evs, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_at(host: Sequence[Event], t: float, skip=("bench.window",)) -> str:
    """The innermost (shortest) annotation covering time ``t``."""
    best, best_d = "none", float("inf")
    for name, s, d in host:
        if name in skip:
            continue
        if s <= t < s + d and d < best_d:
            best, best_d = name, d
    return best


def idle_by_host(tr: Trace, k: int = 10) -> List[list]:
    """Idle device time, attributed to what the host was doing at the
    middle of each gap; the ``k`` largest totals as [name, seconds]."""
    tot: Dict[str, float] = defaultdict(float)
    for a, b in gaps(tr):
        tot[host_at(tr.host, (a + b) / 2)] += (b - a) * 1e-9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def host_self_ms(tr: Trace, span: str) -> List[float]:
    """For each host annotation named ``span``: its length minus the device
    busy time inside it, in ms (the host's own share of the span)."""
    if not tr.ops:
        return []
    evs = tr.ops[sorted(tr.ops)[0]]
    lo, hi = tr.window
    out = []
    for name, s, d in tr.host:
        if name != span or s < lo or s + d > hi:
            continue
        out.append((d - busy_ns(evs, s, s + d)) * 1e-6)
    return out
