"""The reduction from a capture to busy/idle and per-pattern time:
its arithmetic on a made-up capture, and on a small recorded one
(capture_small.json: 300 ms of a v5e traced under embed-ingest-sat in
PR 23, cut by record_capture.py, operation names cut to their left-hand
side).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q"""
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import tracereduce as R  # noqa: E402


def test_union_merges_overlaps_and_nesting():
    total, merged = R.union_ns([(0, 10), (5, 12), (20, 30), (22, 25)])
    assert total == 22
    assert merged == [[0, 12], [20, 30]]
    assert R.gaps_ns(merged, 0, 40) == [(12, 20), (30, 40)]
    assert R.gaps_ns(merged, -5, 30) == [(-5, 0), (12, 20)]


def test_op_name_is_the_left_hand_side():
    raw = "%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(f32[8] %p), kind=kLoop"
    assert R.op_name(raw) == "fusion.12"
    assert R.op_name("plain") == "plain"


def made_up():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%while.1 = () while()", 0, 40 * ms],     # encloses
                ["%fusion.1 = f32[] fusion()", 0, 10 * ms],
                ["%_flash_pallas.2 = f32[] custom-call()", 10 * ms, 30 * ms],
                ["%fusion.1 = f32[] fusion()", 60 * ms, 10 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_run(1)", 0, 40 * ms], ["jit_fwd(2)", 60 * ms, 10 * ms]]},
            {"name": "Async XLA Ops", "events": [["copy-start", 0, 90 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["np.asarray(jax.Array)", 0, 41 * ms],
                ["tokenize", 42 * ms, 17 * ms],
                ["commit", 71 * ms, 29 * ms]]}]}]}


def test_busy_idle_and_patterns_on_a_made_up_capture():
    red = R.reduce(made_up())
    assert red["devices"] == 1
    assert abs(red["window_s"] - 0.100) < 1e-12
    assert abs(red["busy_s"] - 0.050) < 1e-12       # 0-40 and 60-70
    assert abs(1 - red["busy_s"] / red["window_s"] - 0.5) < 1e-12
    # the enclosing while is in the union, not in the per-op sums
    assert "while.1" not in red["ops"]
    assert abs(red["ops"]["fusion.1"] - 0.020) < 1e-12
    assert abs(red["ops"]["_flash_pallas.2"] - 0.030) < 1e-12
    assert red["modules"]["jit_run(1)"] == [1, 0.040]
    flash = sum(v for k, v in red["ops"].items() if re.search("flash", k))
    assert abs(100 * flash / red["busy_s"] - 60.0) < 1e-9
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert abs(gaps["tokenize"] - 0.020) < 1e-12    # 40-60
    assert abs(gaps["commit"] - 0.030) < 1e-12      # 70-100
    assert red["breakdown"]["device_ops"][0][0] == "_flash_pallas.2"


def test_chips_that_ran_nothing_are_not_averaged_in():
    """A cell that holds four chips and serves from one: busy is that
    of the chip used."""
    cap = made_up()
    cap["planes"] += [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": []},
        {"name": "Steps", "events": [["0", 0, 5_000_000]]}]}
        for i in (1, 2, 3)]
    red = R.reduce(cap)
    assert red["devices"] == 1
    assert abs(red["busy_s"] - 0.050) < 1e-12
    assert dict(red["breakdown"]["idle_gaps"]).keys() == {"tokenize",
                                                          "commit"}


def test_no_device_plane_is_zero_busy():
    cap = {"planes": [p for p in made_up()["planes"]
                      if not p["name"].startswith(R.DEVICE_PREFIX)]}
    red = R.reduce(cap)
    assert red["busy_s"] == 0.0 and red["devices"] == 0
    assert red["window_s"] > 0


def test_recorded_capture():
    """300 ms of a real capture (one device plane, the host's threads):
    the numbers below were read from it in PR 23 and must not move."""
    cap = json.load(open(os.path.join(HERE, "capture_small.json")))
    red = R.reduce(cap)
    assert red["devices"] == 1
    assert abs(red["window_s"] - 0.299478673) < 1e-9
    assert abs(red["busy_s"] - 0.060366003) < 1e-9
    idle = 1 - red["busy_s"] / red["window_s"]
    assert abs(idle - 0.79843) < 1e-4
    # per-pattern time: the projections' matmul fusions, and a program
    # (this slice holds short buckets only: no flash kernel in it)
    conv = sum(v for k, v in red["ops"].items()
               if re.search("^convolution", k))
    assert abs(conv - 0.01172452) < 1e-9
    assert not any(re.search("flash", k) for k in red["ops"])
    assert red["modules"]["jit_fwd(6733930089933920135)"][0] == 1
    # nested operations make the sum exceed the union, never fall short
    assert sum(red["ops"].values()) >= red["busy_s"] * 0.5
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert abs(gaps["PjitFunction(true_divide)"] - 0.203599987) < 1e-9
    assert len(red["breakdown"]["device_ops"]) == 10
    assert all(len(n) < 80 for n, _ in red["breakdown"]["device_ops"])
