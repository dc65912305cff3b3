"""Shared fixtures. NOTE: no XLA device-count flag here on purpose —
smoke tests and benches must see the real single CPU device; only
launch/dryrun.py (its own process) forces 512 placeholder devices."""
import numpy as np
import pytest

import jax


@pytest.fixture(scope="session", autouse=True)
def _x64_off():
    jax.config.update("jax_enable_x64", False)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _page_leak_gate(request):
    """Universal serving-tier leak gate: every ``EngineCore`` built
    during a test is audited afterwards — pool conservation (device AND
    host/compressed tier pools) always, and (for cores left IDLE) zero
    leaked page references / dangling prefix-cache locks / orphaned
    host-tier pages. Replaces the old ad-hoc per-test counter checks.
    Opt out with ``@pytest.mark.no_leak_gate`` (tests that corrupt
    engine state on purpose)."""
    from repro.serving.engine import EngineCore

    cores = []
    orig = EngineCore.__init__

    def patched(self, *args, **kw):
        orig(self, *args, **kw)
        cores.append(self)

    EngineCore.__init__ = patched
    try:
        yield
    finally:
        EngineCore.__init__ = orig
    if request.node.get_closest_marker("no_leak_gate"):
        return
    from repro.serving import invariants
    problems = []
    for core in cores:
        for v in invariants.audit_leaks(core):
            problems.append(v)
    assert not problems, (
        "page leak gate: engine(s) left damaged state behind:\n  "
        + "\n  ".join(problems))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "no_leak_gate: skip the autouse EngineCore page-leak audit")
