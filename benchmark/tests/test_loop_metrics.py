"""The search daemon's loop accounting as the benchmark reads it (PR
24): the traced CPU rehearsal prints every per-layer metric that reads
the loop's spans, the dispatch windows and the start-up phases, and the
two heartbeats around the window account for the time between them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
One daemon child, ~30 s."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "search-coalesced"
NEW = ["housekeeping_pct.search", "sweep_ms.search",
       "loop_wait_pct.search", "lane_sync_ms.search", "score_ms.search",
       "select_ms.search", "topk_window_ms.search",
       "daemon_boot_s.search"]


@pytest.fixture(scope="module")
def traced():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "7",
         "--rehearse", "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    hbs = json.load(open(os.path.join(
        REPO, ".bench_work", CELL, "heartbeats.json")))
    return json.loads(lines[-1]), hbs


def test_the_eight_metrics_are_declared_for_the_cell():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["source"] == "program_span"
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert spec["reader"] == "heartbeat"


def test_traced_rehearsal_prints_the_eight_metrics(traced):
    """All eight: the rehearsal's top-k program is `topk`, which the
    window metric reads beside the chip's `fused_topk`."""
    rec, _ = traced
    assert rec["correct"] is True and rec["failed"] == 0
    for name in NEW:
        assert name in rec["metrics"], name
        assert rec["metrics"][name]["value"] > 0, name
    pct = [rec["metrics"][n]["value"]
           for n in ("housekeeping_pct.search", "loop_wait_pct.search")]
    assert all(0 < v < 100 for v in pct) and sum(pct) < 100
    # the six the benchmark had print as before (the roofline needs
    # a device)
    for name in ("drain_ms.search", "commit_ms.search",
                 "queries_per_dispatch.search", "submit_us.search",
                 "query_p95_ms.search"):
        assert name in rec["metrics"], name


def test_the_heartbeats_account_for_the_time_between_them(traced):
    _, hbs = traced
    a, b = hbs["start"], hbs["end"]
    for hb in (a, b):
        assert {"spans", "devtime", "startup_ms"} <= set(hb)

    def d(path):
        x, y = a, b
        for part in path.split("/"):
            x, y = x.get(part, {}), y[part]
        return y - (x or 0)

    loop = d("spans/search.loop/total_ms")
    wall = (b["ts"] - a["ts"]) * 1e3
    assert abs(loop - wall) <= 0.02 * wall, (loop, wall)
    kids = sum(d(f"spans/search.{p}/total_ms") for p in (
        "idle", "drain_cycle", "sweep_results", "sweep_stages", "publish"))
    assert 0 <= loop - kids <= 0.02 * loop, (loop, kids)
    cycle = d("spans/search.drain_cycle/total_ms")
    stages = sum(d(f"spans/search.{p}/total_ms") for p in (
        "drain", "score", "select", "commit"))
    assert 0 <= cycle - stages <= 0.05 * cycle, (cycle, stages)
    # every dispatch of the window closed its window
    progs = [k for k in b["devtime"] if "topk" in k]
    assert sum(d(f"devtime/{k}/n") for k in progs) == d("dispatches") > 0
