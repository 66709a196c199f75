"""The continuous lane's loop accounting (protocol.CONT_LOOP_PHASES):
every pass of Completer.run_continuous is one `infer.loop` span, every
second of it belongs to one leaf, and every leaf rides the profiler's
clock — what tests/test_searcher.py holds the search daemon's loop to.

One traced run of the tiny model serves all of the traced cases
(~20 s: two programs to compile, then a few idle beats).
"""
from __future__ import annotations

import json
import threading
import time

import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as cmod
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig
from libsplinter_tpu.utils import trace as tmod

# the CONT_INFER_STAGES that are disjoint in time on the loop's thread
# (flush is a sum inside emit, window_release inside join and decode)
STAGE_LEAVES = ("prefix_hit", "state_restore", "state_zero",
                "state_snapshot", "join", "sample", "decode", "collect",
                "handoff", "adopt")
ENCLOSING = ("loop", "admit", "chunk")
LEAVES = tuple(p for p in P.CONT_LOOP_PHASES if p not in ENCLOSING) \
    + STAGE_LEAVES


def _mkstore(tmp_path, tag):
    name = f"/spt-{tag}-{tmp_path.name}"
    Store.unlink(name)
    return name, Store.create(name, nslots=128, max_val=16384, vec_dim=8)


def _submit(st, key, prompt):
    st.set(key, prompt)
    st.label_or(key, P.LBL_INFER_REQ)
    st.bump(key)


def _await_ready(st, keys, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(st.labels(k) & P.LBL_READY for k in keys):
            return True
        time.sleep(0.05)
    return False


class _AnnLog:
    """Stands in for utils.trace._annotation: a context manager that
    logs (thread, name, "enter" | "exit")."""

    def __init__(self):
        self.events: list[tuple[int, str, str]] = []

    def __call__(self, name):
        log = self.events

        class _Ctx:
            def __enter__(self):
                log.append((threading.get_ident(), name, "enter"))
                return self

            def __exit__(self, *exc):
                log.append((threading.get_ident(), name, "exit"))
                return False

        return _Ctx()


def _serve(st, comp, n_requests: int, idle_s: float):
    """Run the lane in a thread, serve n_requests in two bursts (the
    second joins a live batch), idle on, stop.  Returns the loop's
    thread id."""
    box = {}

    def run():
        box["tid"] = threading.get_ident()
        comp.run_continuous(idle_timeout_ms=20, stop_after=180.0)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    keys = [f"q/{i}" for i in range(n_requests)]
    for k in keys[:2]:
        _submit(st, k, f"tell me {k} and then some more words")
    assert _await_ready(st, keys[:2]), comp.stats
    for k in keys[2:]:
        _submit(st, k, f"tell me {k} and then some more words")
    assert _await_ready(st, keys), comp.stats
    time.sleep(idle_s)
    comp.stop()
    th.join(timeout=10)
    assert not th.is_alive()
    return box["tid"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run: (heartbeat snapshot, annotation log, loop
    thread id).  The heartbeat is the LAST beat's, so it holds whole
    passes only."""
    tmp = tmp_path_factory.mktemp("contloop")
    mp = pytest.MonkeyPatch()
    ann = _AnnLog()
    mp.setattr(cmod.tracer, "enabled", True)
    mp.setattr(tmod, "_annotation", ann)
    cmod.tracer.reset()
    name, st = _mkstore(tmp, "cloop")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=12,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16, rebid_tokens=8)
        comp.attach()
        comp._bid = 0                 # a held shard bid: rebid runs
        tid = _serve(st, comp, 5, idle_s=4.6)   # two beats after work
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        yield snap, ann.events, tid
    finally:
        mp.undo()
        cmod.tracer.reset()
        st.close()
        Store.unlink(name)


def test_traced_run_publishes_every_loop_phase(traced):
    """(a) every infer.<phase> of CONT_LOOP_PHASES is in the
    heartbeat's `spans`, and the leaves add up to the loop."""
    snap, _, _ = traced
    # nothing was dropped for size (the completer's own `truncated`
    # counts truncated completions: True would be the heartbeat's)
    assert snap["truncated"] == 0 and "quantiles" in snap
    spans = snap["spans"]
    want = {f"infer.{p}" for p in P.CONT_LOOP_PHASES}
    assert want <= set(spans), want - set(spans)
    for stage in ("join", "sample", "decode", "collect", "prefix_hit"):
        assert f"infer.{stage}" in spans, stage
    assert "infer.e2e" not in spans

    def total(name):
        return spans.get(f"infer.{name}", {}).get("total_ms", 0.0)

    loop = total("loop")
    leaves = sum(total(p) for p in LEAVES)
    assert loop > 4000                  # the idle beats alone
    assert abs(loop - leaves) <= 0.05 * loop, (loop, leaves, spans)
    # admit encloses its leaves and nothing else of the loop's
    inside = sum(total(p) for p in ("gather", "prepare", "prefix_hit",
                                    "join", "sample"))
    assert inside <= total("admit") * 1.001 + 0.5
    assert total("admit") <= inside + total("emit") + 0.05 * loop
    # the loop's busy time is its admission rounds, its chunk rounds
    # and the beat: idle is the rest
    busy = total("admit") + total("chunk") + total("beat")
    assert abs(loop - total("idle") - busy) <= 0.02 * loop
    in_chunk = sum(total(p) for p in ("decode", "collect", "rebid"))
    assert in_chunk <= total("chunk") * 1.001 + 0.5
    # one join, one sample and one prepare pair a request; one emit a
    # chunk and one a join
    assert spans["infer.join"]["n"] == 5 == spans["infer.sample"]["n"]
    assert spans["infer.prepare"]["n"] == 10
    assert spans["infer.emit"]["n"] == \
        spans["infer.collect"]["n"] + spans["infer.join"]["n"]
    assert spans["infer.collect"]["n"] == spans["infer.decode"]["n"] > 0
    assert spans["infer.beat"]["n"] >= 2
    assert spans["infer.rebid"]["n"] >= 1
    assert snap["decode_rows"] >= snap["decode_steps"] > 0


def test_no_leaf_opens_inside_another(traced):
    """(b) on the loop's thread the annotations never nest, every
    leaf that ran opened one, and `loop` / `admit` never do."""
    _, events, tid = traced
    mine = [(n, what) for t, n, what in events if t == tid]
    assert mine, "the loop opened no annotation"
    open_now = None
    for name, what in mine:
        if what == "enter":
            assert open_now is None, (open_now, name)
            open_now = name
        else:
            assert open_now == name, (open_now, name)
            open_now = None
    assert open_now is None
    seen = {n for n, _ in mine}
    assert seen <= {f"infer.{p}" for p in LEAVES}, seen
    for p in ("idle", "beat", "gather", "prepare", "prefix_hit", "join",
              "sample", "emit", "decode", "collect", "rebid"):
        assert f"infer.{p}" in seen, p
    for p in ENCLOSING:
        assert f"infer.{p}" not in seen


def test_annotated_time_matches_the_histograms(traced):
    """One clock pair a phase: every leaf's histogram counts what its
    annotations enclosed (prefix_hit opens three a hit, two a miss;
    gather one a round and two a request; prepare two a request)."""
    snap, events, tid = traced
    opened: dict[str, int] = {}
    for t, n, what in events:
        if t == tid and what == "enter":
            opened[n] = opened.get(n, 0) + 1
    spans = snap["spans"]
    # the heartbeat is the last beat's; the log runs to the stop
    for p in ("join", "sample", "decode", "collect", "emit", "rebid"):
        n = spans[f"infer.{p}"]["n"]
        assert n <= opened[f"infer.{p}"] <= n + 1, p
    assert opened["infer.prepare"] == 10
    assert opened["infer.prefix_hit"] >= 2 * 5


def test_tracing_off_opens_nothing(tmp_path, monkeypatch):
    """(c) tracing off: no `spans` section, no histogram, and
    utils.trace._annotation is never called."""
    ann = _AnnLog()
    monkeypatch.setattr(cmod.tracer, "enabled", False)
    monkeypatch.setattr(tmod, "_annotation", ann)
    cmod.tracer.reset()
    name, st = _mkstore(tmp_path, "cloopoff")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=12,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16, rebid_tokens=8)
        comp.attach()
        comp._bid = 0
        _serve(st, comp, 3, idle_s=2.2)
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert snap["completions"] == 3
        for sec in ("spans", "quantiles", "recorder"):
            assert sec not in snap, sec
        assert ann.events == []
        assert cmod.tracer.snapshot() == {}
    finally:
        st.close()
        Store.unlink(name)
