"""A whole benchmark run on the CPU at tiny sizes (``--rehearse``), and the
same run with the timed path broken underneath: each fault must turn
``correct`` false, and so must the control (the reference one precision
step lower, in the program's place)."""
import json
import subprocess
import sys

import pytest

from bench import faults
from bench import run as bench_run

CELL = "dsmoe16b.decode-long"
ARGS = ["--workload", CELL, "--seed", "2147483659", "--seconds", "1.5",
        "--trace", "0", "--rehearse"]


def _run(capsys, extra=()):
    assert bench_run.main(ARGS + list(extra)) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_rehearsal_line_shape_and_correct(capsys):
    res = _run(capsys)
    assert list(res)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True, res["check"]
    assert res["device"]["platform"] == "cpu"
    assert set(res["metrics"]) == {"itl_p95_ms", "out_tok_s", "hbm_peak_gib",
                                   "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_rehearsal_traced_reports_per_layer(capsys):
    res = _run(capsys, ["--trace", "1"])
    assert res["correct"] is True, res["check"]
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    # Counters read on any platform; device-trace metrics need a chip.
    assert "batch_occupancy" in res["metrics"]
    assert "decode_attn_roofline" not in res["metrics"]


def test_control_is_not_correct(capsys):
    res = _run(capsys, ["--control"])
    assert res["correct"] is False, res["check"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(capsys, monkeypatch, fault):
    faults.plant(fault, monkeypatch.setattr)
    res = _run(capsys)
    assert res["correct"] is False, res["check"]


def test_no_chip_exits_nonzero_without_result():
    """Without --rehearse the run refuses the CPU and prints no result."""
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=str(bench_run.ROOT), capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
