"""Resolve a benchmark cell by name.

``BENCHMARK.json`` at the repository root lists the cells (``workloads``),
their configurations and metrics. Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own,
found by name:

    bench/configs/<config>.json       sizes, engine settings, limits
    bench/traffic/<traffic>.json      parameters for ``traffic_gen``
    bench/metrics/<metric>.py         ``read(ctx)`` for one per-layer metric
    bench/reference/<reference>.py    the plain reference a config names

A later cell is added by adding such files and an entry; no file here
changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(KeyError):
    """An unknown or malformed name in the benchmark's data."""

    def __str__(self):
        return str(self.args[0]) if self.args else "spec error"


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    have = sorted(e["name"] for e in entries)
    raise SpecError(f"unknown {what} {name!r}; have {have}")


def _load_json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no {kind} file for {name!r} ({path.name})")
    data = json.loads(path.read_text())
    data.setdefault("name", name)
    return data


def load_config(name: str) -> dict:
    return _load_json("configs", name)


def load_traffic(name: str) -> dict:
    return _load_json("traffic", name)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} module for {name!r} ({path.name})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, bench: dict) -> dict:
    """The cell named ``workload``: its entry, configuration, traffic, and
    the metric entries it reports (end-to-end and per-layer)."""
    cell = _by_name(bench["workloads"], workload, "workload")
    _by_name(bench["configs"], cell["config"], "config")
    cfg = load_config(cell["config"])
    traffic = load_traffic(cell["traffic"])

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    per_layer = [m for m in bench["per_layer"] if reports(m)]
    readers = {m["name"]: load_module("metrics", m["name"])
               for m in per_layer}
    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer, "readers": readers}
