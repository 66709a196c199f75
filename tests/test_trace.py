"""Span tracer (utils/trace.py): aggregation, thread safety, no-op
cost path, and heartbeat integration."""
from __future__ import annotations

import json
import threading
import time

import numpy as np

from libsplinter_tpu.utils.trace import Tracer


def test_disabled_tracer_is_noop():
    t = Tracer(enabled=False)
    with t.span("x"):
        pass
    assert t.snapshot() == {}
    # disabled spans share one context object (no per-call allocation)
    assert t.span("a") is t.span("b")


def test_span_aggregation():
    t = Tracer(enabled=True)
    for _ in range(3):
        with t.span("work"):
            time.sleep(0.002)
    snap = t.snapshot()
    assert snap["work"]["n"] == 3
    assert snap["work"]["total_ms"] >= 5
    assert snap["work"]["max_ms"] >= snap["work"]["total_ms"] / 3 - 1e-6
    t.reset()
    assert t.snapshot() == {}


def test_span_thread_safety():
    t = Tracer(enabled=True)

    def worker():
        for _ in range(200):
            with t.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t.snapshot()["w"]["n"] == 1600


def test_embedder_heartbeat_carries_spans(tmp_path, monkeypatch):
    from libsplinter_tpu import Store, T_VARTEXT
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine import embedder as emod

    monkeypatch.setattr(emod.tracer, "enabled", True)
    emod.tracer.reset()
    name = f"/spt-trace-{tmp_path.name}"
    Store.unlink(name)
    # max_val must hold the full heartbeat: counters (incl. the commit
    # pipeline's) + the span table + the quantiles section
    st = Store.create(name, nslots=64, max_val=4096, vec_dim=8)
    try:
        emb = emod.Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("k", "text")
        st.set_type("k", T_VARTEXT)
        st.label_or("k", P.LBL_EMBED_REQ)
        emb.run_once()
        emb.publish_stats()
        snap = json.loads(st.get(P.KEY_EMBED_STATS).rstrip(b"\0"))
        assert "spans" in snap
        assert snap["spans"]["embed.drain"]["n"] >= 1
        assert snap["spans"]["embed.commit"]["n"] >= 1
        # histogram-sourced quantiles ride the same heartbeat under
        # the PIPELINE_STAGES names (prefix stripped)
        assert "quantiles" in snap
        assert snap["quantiles"]["commit"]["n"] >= 1
        for k in ("p50_ms", "p90_ms", "p99_ms", "max_ms"):
            assert k in snap["quantiles"]["commit"], k
    finally:
        st.close()
        Store.unlink(name)


# ------------------------------------------- one span, two clocks

class _AnnotationLog:
    """Stands in for jax.profiler.TraceAnnotation: records which names
    were opened and that each was closed."""

    def __init__(self):
        self.opened: list[str] = []
        self.closed: list[str] = []

    def __call__(self, name):
        log = self

        class _Ann:
            def __enter__(self):
                log.opened.append(name)
                return self

            def __exit__(self, *exc):
                log.closed.append(name)
                return False

        return _Ann()


def test_leaf_span_opens_a_trace_annotation(monkeypatch):
    import jax.profiler

    log = _AnnotationLog()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", log)
    t = Tracer(enabled=True)
    with t.span("search.idle", leaf=True):
        time.sleep(0.002)
    assert log.opened == log.closed == ["search.idle"]
    snap = t.snapshot()["search.idle"]
    assert snap["n"] == 1 and snap["total_ms"] >= 1.5


def test_enclosing_span_stays_off_the_profilers_clock(monkeypatch):
    import jax.profiler

    log = _AnnotationLog()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", log)
    t = Tracer(enabled=True)
    with t.span("search.loop"):
        with t.span("search.drain_cycle"):
            with t.annotation("search.drain"):
                pass
    # only the leaf rides the profiler's clock; the annotation alone
    # records no histogram (its call site sums the stage and records)
    assert log.opened == log.closed == ["search.drain"]
    assert set(t.snapshot()) == {"search.loop", "search.drain_cycle"}


def test_disabled_tracer_imports_no_jax():
    """With SPTPU_TRACE unset every span form is the shared no-op, no
    TraceAnnotation is constructed and utils/trace.py pulls in no jax
    (a fresh interpreter: this one has jax loaded already)."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from libsplinter_tpu.utils.trace import Tracer, tracer\n"
        "assert tracer.enabled is False\n"
        "noop = Tracer._NOOP\n"
        "assert tracer.span('search.idle', leaf=True) is noop\n"
        "assert tracer.span('search.loop') is noop\n"
        "assert tracer.annotation('search.drain') is noop\n"
        "with tracer.span('search.idle', leaf=True):\n"
        "    pass\n"
        "assert tracer.snapshot() == {}\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = {k: v for k, v in os.environ.items() if k != "SPTPU_TRACE"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
