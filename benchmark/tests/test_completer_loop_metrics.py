"""The continuous lane's loop accounting as the benchmark reads it (PR
34): a traced CPU rehearsal of a decoder cell prints the six metrics
that read the `infer.*` loop spans, the heartbeat pair the run recorded
gives the same numbers through the metric files, and the leaves account
for the loop; the search cell's line carries the share of answered
clients that were back in the next drain.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_completer_loop_metrics.py -q
Two daemon children, ~60 s.  Run benchmark/tests without xdist (a
cell's cases share .bench_work/<cell>)."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELL = "pangu-docqa-shared-prefix"
DECODERS = [CELL, "kimi-sessions-growing-context",
            "trinity-mixed-short-long"]
SIX = ["loop_join_pct.complete", "admit_host_ms.complete",
       "loop_collect_pct.complete", "loop_emit_pct.complete",
       "loop_idle_pct.complete", "decode_rows_per_s.complete"]
SEARCH_CELL, SEARCH_METRIC = "search-coalesced", \
    "next_drain_return_pct.search"
LEAVES = ("idle", "beat", "gather", "prepare", "emit", "rebid",
          "prefix_hit", "state_restore", "state_snapshot", "join",
          "sample", "decode", "collect", "handoff", "adopt")


def _traced(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 34), "--seconds", "5",
         "--rehearse", "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    hbs = json.load(open(os.path.join(
        REPO, ".bench_work", cell, "heartbeats.json")))
    return json.loads(lines[-1]), hbs


@pytest.fixture(scope="module")
def traced():
    return _traced(CELL)


@pytest.fixture(scope="module")
def traced_search():
    return _traced(SEARCH_CELL)


def _read(name, hbs):
    """The metric file's own reader over a recorded heartbeat pair."""
    spec = json.load(open(os.path.join(
        REPO, "benchmark", "metrics", name + ".json")))
    mod_spec = importlib.util.spec_from_file_location(
        "reader_" + spec["reader"], os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py"))
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read({"hb_start": hbs["start"], "hb_end": hbs["end"]},
                    **spec["args"])


def test_the_seven_metrics_are_declared():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in SIX + [SEARCH_METRIC]}
    assert sorted(mine) == sorted(SIX + [SEARCH_METRIC])
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in mine}
    for name, m in mine.items():
        assert m["moves"] == "queries_per_s" and m["layer"] in layers
        assert m["workloads"] == (
            [SEARCH_CELL] if name == SEARCH_METRIC else DECODERS)
        counter = name in ("decode_rows_per_s.complete", SEARCH_METRIC)
        assert m["source"] == (
            "program_counter" if counter else "program_span")
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", name + ".json")))
        assert spec["reader"] == "heartbeat" and spec["what"]
    # the seven are the newest entries: nothing before them moved
    assert [m["name"] for m in bench["per_layer"][-7:]] == \
        SIX + [SEARCH_METRIC]


def test_traced_rehearsal_prints_the_six(traced):
    rec, hbs = traced
    assert rec["correct"] is True and rec["failed"] == 0
    for name in SIX:
        assert name in rec["metrics"], name
        # the printed value is the metric file's reading of the pair
        assert rec["metrics"][name]["value"] == pytest.approx(
            _read(name, hbs)), name
    v = {n: rec["metrics"][n]["value"] for n in SIX}
    shares = [v[n] for n in SIX if n.startswith("loop_")]
    assert all(0 <= s < 100 for s in shares), v
    assert v["loop_join_pct.complete"] > 0
    assert v["loop_collect_pct.complete"] > 0
    assert v["admit_host_ms.complete"] > 0
    assert v["decode_rows_per_s.complete"] > 0
    # the ones the cell printed before are still on the line
    for name in ("decode_step_ms.complete", "rows_per_step.complete",
                 "join_ms.complete", "prefix_hit_pct.complete",
                 "daemon_boot_s.complete"):
        assert name in rec["metrics"], name


def test_the_leaves_account_for_the_loop(traced):
    _, hbs = traced
    a, b = hbs["start"], hbs["end"]
    for hb in (a, b):
        assert {"spans", "devtime", "startup_ms"} <= set(hb)

    def d(name, field="total_ms"):
        return b["spans"].get(name, {}).get(field, 0) \
            - a["spans"].get(name, {}).get(field, 0)

    loop = d("infer.loop")
    wall = (b["ts"] - a["ts"]) * 1e3
    assert abs(loop - wall) <= 0.03 * wall, (loop, wall)
    leaves = sum(d(f"infer.{p}") for p in LEAVES)
    assert 0 <= loop - leaves <= 0.05 * loop, (loop, leaves)
    # an admission round holds its leaves
    inside = sum(d(f"infer.{p}") for p in (
        "gather", "prepare", "prefix_hit", "join", "sample"))
    assert inside <= d("infer.admit") <= inside + d("infer.emit") \
        + 0.02 * loop
    # a join a request, a collect and an emit a chunk (+ an emit a join)
    assert d("infer.join", "n") == d("infer.sample", "n") > 0
    assert d("infer.collect", "n") == d("infer.decode", "n") > 0
    assert d("infer.emit", "n") == \
        d("infer.collect", "n") + d("infer.join", "n")
    # the shares and the rate stand on the loop's BUSY time (its
    # admission rounds, its chunk rounds, the beat): what the later
    # heartbeat waited for after the window is idle, and must not
    # dilute them
    busy = d("infer.admit") + d("infer.chunk") + d("infer.beat")
    assert abs(loop - d("infer.idle") - busy) <= 0.02 * loop
    rows = b["decode_rows"] - a["decode_rows"]
    assert _read("decode_rows_per_s.complete", hbs) == \
        pytest.approx(1000.0 * rows / busy)
    assert _read("loop_join_pct.complete", hbs) == \
        pytest.approx(100.0 * d("infer.admit") / busy)
    assert _read("loop_collect_pct.complete", hbs) == \
        pytest.approx(100.0 * d("infer.collect") / busy)
    assert _read("loop_idle_pct.complete", hbs) == \
        pytest.approx(100.0 * d("infer.idle") / loop)
    later = {"start": a, "end": json.loads(json.dumps(b))}
    for name in ("infer.idle", "infer.loop"):     # 30 s more of idle
        later["end"]["spans"][name]["total_ms"] += 30000.0
    for name in SIX:
        if name != "loop_idle_pct.complete":
            assert _read(name, later) == pytest.approx(_read(name, hbs))


def test_a_heartbeat_without_the_spans_reads_nothing(traced):
    """The parent commit's heartbeat has no infer.loop: every one of
    the six is left out of its line, none raises."""
    _, hbs = traced
    old = {}
    for side in ("start", "end"):
        hb = dict(hbs[side])
        hb["spans"] = {k: v for k, v in hb["spans"].items()
                       if k in ("infer.join", "infer.sample",
                                "infer.decode", "infer.collect",
                                "infer.flush", "infer.prefix_hit")}
        old[side] = hb
    for name in SIX:
        assert _read(name, old) is None, name
    untraced = {side: {k: v for k, v in hbs[side].items()
                       if k != "spans"} for side in ("start", "end")}
    for name in SIX:
        assert _read(name, untraced) is None, name


def test_the_search_line_says_who_came_back(traced_search):
    rec, hbs = traced_search
    assert rec["correct"] is True and rec["failed"] == 0
    assert SEARCH_METRIC in rec["metrics"]
    v = rec["metrics"][SEARCH_METRIC]["value"]
    assert v == pytest.approx(_read(SEARCH_METRIC, hbs))
    assert 0 <= v <= 100
    a, b = hbs["start"], hbs["end"]
    back = b["returned_next_drain"] - a["returned_next_drain"]
    assert 0 <= back <= b["served"] - a["served"]
    # a heartbeat without the counter (the parent's) reads nothing
    old = {side: {k: x for k, x in hbs[side].items()
                  if k != "returned_next_drain"}
           for side in ("start", "end")}
    assert _read(SEARCH_METRIC, old) is None
