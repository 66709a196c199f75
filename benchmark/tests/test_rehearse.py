"""run.py end to end on the CPU at tiny sizes, for every cell of
BENCHMARK.json: a well-formed last line, never "platform": "tpu"; the
lower-precision control and a timed path broken underneath both come
out `correct: false`.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
Each case starts a daemon child and takes 15-40 s."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(cell, *extra, seconds="3"):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 5), "--seconds", seconds,
         "--rehearse", *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def last(lines):
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_line(cell):
    p, lines = run(cell)
    assert p.returncode == 0, p.stderr[-2000:]
    assert '"platform": "tpu"' not in p.stdout
    rec = last(lines)
    assert set(rec) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert rec["correct"] is True and rec["failed"] == 0
    assert rec["device"]["platform"] != "tpu"
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(rec["metrics"]) == want
    for m in rec["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert any(ln.startswith("compared: ") for ln in lines)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_per_layer_metrics(cell):
    p, lines = run(cell, "--trace", "1", seconds="4")
    assert p.returncode == 0, p.stderr[-2000:]
    rec = last(lines)
    allowed = {m["name"] for m in BENCH["per_layer"]
               if cell in m.get("workloads", [cell])}
    assert rec["metrics"] and set(rec["metrics"]) <= allowed
    assert {"busy_s", "window_s"} <= set(rec["device"])
    assert set(rec["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_is_not_correct(cell):
    p, lines = run(cell, "--control")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last(lines)["correct"] is False
    assert any("FAILED" in ln for ln in lines if ln.startswith("compared"))


def lane_config(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    c = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    return json.load(open(os.path.join(REPO, c["file"])))


@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell):
    p, lines = run(cell, "--sabotage", lane_config(cell)["sabotage"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert last(lines)["correct"] is False


def test_no_chip_no_result():
    """Without --rehearse the run needs a TPU: on this CPU it exits
    non-zero and prints no result line."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
