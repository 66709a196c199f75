"""Search daemon: request protocol, query coalescing, result commit,
stage quantiles, and the CLI dispatch path.  `make search-check` runs
this file (the coalescing smoke test is the acceptance gate: N
concurrent clients must cost << N device dispatches)."""
from __future__ import annotations

import contextlib
import io
import json
import threading
import time

import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.searcher import (Searcher, daemon_live,
                                             qb_buckets, submit_search)
from libsplinter_tpu.utils.trace import tracer


@pytest.fixture
def traced():
    """Enable the process tracer for one test, restoring cleanly."""
    prev = tracer.enabled
    tracer.enabled = True
    yield tracer
    tracer.enabled = prev
    tracer.reset()


def _fill_docs(store, n, rng, dim=None):
    dim = dim or store.vec_dim
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    for i in range(n):
        store.set(f"doc/{i}", f"text {i}")
        store.vec_set(f"doc/{i}", vecs[i])
    return vecs


def _request(store, key, qvec, k=5, bloom=0):
    store.set(key, json.dumps({"k": k, "bloom": bloom}))
    store.vec_set(key, qvec)
    store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
    store.bump(key)


def _result(store, key):
    return json.loads(
        store.get(P.search_result_key(store.find_index(key)))
        .rstrip(b"\0"))


def _dense_ref(lane, q, exclude=()):
    norms = np.linalg.norm(lane, axis=1) * np.linalg.norm(q)
    with np.errstate(invalid="ignore"):
        s = np.where(norms > 0, lane @ q / np.maximum(norms, 1e-12),
                     -np.inf)
    s[list(exclude)] = -np.inf
    return s


@pytest.mark.parametrize("n", [32, 40])
def test_coalesces_concurrent_requests(store, n):
    """Acceptance: n in-flight queries that fit one program of the
    kernel's lane width -> ONE device dispatch (a second, narrower
    one would read the whole lane again), with every per-request
    result equal to the dense scan with the request rows masked out."""
    rng = np.random.default_rng(1)
    _fill_docs(store, 64, rng)
    sr = Searcher(store)
    sr.attach()
    qs = rng.normal(size=(n, store.vec_dim)).astype(np.float32)
    keys = [f"__sqtmp_{1000 + i}" for i in range(n)]
    for key, q in zip(keys, qs):
        _request(store, key, q)
    req_slots = {store.find_index(k) for k in keys}

    served = sr.run_once()
    assert served == n
    assert sr.stats.dispatches == 1
    assert sr.stats.coalesced_max == n
    assert sr.stats.coalesce_ratio() == float(n)

    lane = np.array(store.vectors)
    for key, q in zip(keys, qs):
        rec = _result(store, key)
        ref = _dense_ref(lane, q, exclude=req_slots)
        order = np.argsort(-ref)[:5]
        assert rec["i"] == list(order)
        np.testing.assert_allclose(rec["s"], ref[order], rtol=1e-4)
        assert rec["keys"] == [store.key_at(int(i)) for i in order]
        assert not store.labels(key) & (P.LBL_SEARCH_REQ | P.LBL_WAITING)


@pytest.mark.parametrize("nq,plan", [
    (0, []), (1, [8]), (8, [8]), (9, [128]), (32, [128]), (40, [128]),
    (64, [128]), (128, [128]), (129, [256]), (200, [256]),
    (256, [256]), (257, [256, 8]), (300, [256, 128]),
    (600, [256, 256, 128]), (700, [256, 256, 256])])
def test_qb_chunk_plan(nq, plan):
    """The plan follows what a dispatch costs — one scan of the lane
    whatever its width: the largest bucket while more than it
    remains, then ONE cover bucket for the tail."""
    from libsplinter_tpu.engine.searcher import _qb_chunks
    assert _qb_chunks(nq) == plan


def test_qb_chunk_plan_holds_for_every_count():
    from libsplinter_tpu.engine.searcher import _qb_chunks
    from libsplinter_tpu.ops.similarity import FUSED_Q_LANE
    buckets = qb_buckets()
    assert len(buckets) == 3 and buckets[1] == FUSED_Q_LANE
    for nq in range(1, 701):
        plan = _qb_chunks(nq)
        assert sum(plan) >= nq, (nq, plan)
        assert set(plan) <= set(buckets), (nq, plan)
        # at most one bucket below the largest, and it comes last
        assert all(b == buckets[-1] for b in plan[:-1]), (nq, plan)
        assert sum(plan[:-1]) < nq, (nq, plan)
        if nq <= FUSED_Q_LANE:
            assert len(plan) == 1, (nq, plan)


def test_system_rows_never_surface(store):
    """Request slots hold query vectors and heartbeat rows hold JSON;
    none may appear in results even for a query identical to another
    pending query."""
    rng = np.random.default_rng(2)
    _fill_docs(store, 16, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_a", q)
    _request(store, "__sqtmp_b", q)            # identical query
    assert sr.run_once() == 2
    for key in ("__sqtmp_a", "__sqtmp_b"):
        rec = _result(store, key)
        assert all(k.startswith("doc/") for k in rec["keys"])


def test_bloom_groups_and_masks(store):
    """Requests with different bloom prefilters group into separate
    dispatches, each honoring its own mask."""
    rng = np.random.default_rng(3)
    _fill_docs(store, 24, rng)
    marked = [f"doc/{i}" for i in (3, 7, 11)]
    for key in marked:
        store.label_or(key, P.LBL_CHUNK)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_all", q, k=20, bloom=0)
    _request(store, "__sqtmp_chunk", q, k=20, bloom=P.LBL_CHUNK)
    assert sr.run_once() == 2
    assert sr.stats.dispatches == 2            # one per mask group
    rec = _result(store, "__sqtmp_chunk")
    assert sorted(rec["keys"]) == sorted(marked)
    assert len(_result(store, "__sqtmp_all")["keys"]) > 3


def test_fast_flag_rides_the_request(store):
    """--fast requests bf16 scoring server-side: fast and exact
    requests group into separate dispatches (matmul precision is a
    per-program property), and both come back correct."""
    rng = np.random.default_rng(14)
    _fill_docs(store, 16, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    store.set("__sqtmp_f", json.dumps({"k": 3, "fast": True}))
    store.vec_set("__sqtmp_f", q)
    store.label_or("__sqtmp_f", P.LBL_SEARCH_REQ)
    store.bump("__sqtmp_f")
    _request(store, "__sqtmp_x", q, k=3)
    assert sr.run_once() == 2
    assert sr.stats.dispatches == 2            # one per precision group
    assert (_result(store, "__sqtmp_f")["i"]
            == _result(store, "__sqtmp_x")["i"])   # cpu: same math


def test_bad_request_params_fail_fast(store):
    """Malformed params can never succeed: the daemon answers with an
    error result and clears the label instead of spinning."""
    rng = np.random.default_rng(4)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    key = "__sqtmp_bad"
    store.set(key, "not json at all")
    store.vec_set(key, rng.normal(size=store.vec_dim)
                  .astype(np.float32))
    store.label_or(key, P.LBL_SEARCH_REQ)
    store.bump(key)
    assert sr.run_once() == 0
    assert sr.stats.parse_errors == 1
    assert "err" in _result(store, key)
    assert not store.labels(key) & P.LBL_SEARCH_REQ


def test_vectorless_request_fails_fast(store):
    rng = np.random.default_rng(5)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    key = "__sqtmp_novec"
    store.set(key, json.dumps({"k": 3}))       # no vec_set
    store.label_or(key, P.LBL_SEARCH_REQ)
    store.bump(key)
    assert sr.run_once() == 0
    assert "err" in _result(store, key)
    assert not store.labels(key) & P.LBL_SEARCH_REQ


def test_oversized_k_clamped_to_lane(store):
    """A request k beyond nslots (or the CLI's x8 growth crossing the
    lane) must clamp the fetch, never trace top_k(k > rows) and
    poison-pill the drain loop."""
    rng = np.random.default_rng(13)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_huge", q, k=store.nslots * 20)
    assert sr.run_once() == 1                  # serviced, not crashed
    rec = _result(store, "__sqtmp_huge")
    assert len(rec["keys"]) == 8
    assert rec["fetched"] <= store.nslots


def test_k_larger_than_candidates(store):
    rng = np.random.default_rng(6)
    _fill_docs(store, 4, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_big", q, k=50)
    assert sr.run_once() == 1
    rec = _result(store, "__sqtmp_big")
    assert len(rec["keys"]) == 4               # every doc, nothing more
    assert rec["n"] == 4                       # candidates exhausted
    assert rec["n"] < rec["fetched"]           # client growth stops


@pytest.mark.obs
def test_heartbeat_quantiles_and_liveness(traced):
    """With tracing on, the heartbeat carries SEARCH_STAGES quantile
    summaries (what `spt metrics` renders) and its ts drives
    daemon_live.  Own store: the traced heartbeat needs max_val
    headroom beyond the small fixture's 1 KiB (publish_heartbeat would
    degrade the quantiles section away, which is exactly what the
    fixture-sized store SHOULD do — but not what this test checks)."""
    import os
    import uuid

    name = f"/spt-srhb-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    store = Store.create(name, nslots=256, max_val=4096, vec_dim=32)
    try:
        rng = np.random.default_rng(7)
        _fill_docs(store, 16, rng)
        sr = Searcher(store)
        sr.attach()
        assert not daemon_live(store)          # no heartbeat yet
        _request(store, "__sqtmp_q", rng.normal(size=store.vec_dim)
                 .astype(np.float32))
        assert sr.run_once() == 1
        sr.publish_stats()
        assert daemon_live(store)
        snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert snap["served"] == 1
        for stage in P.SEARCH_STAGES:
            assert stage in snap["quantiles"], snap["quantiles"].keys()
            assert "p50_ms" in snap["quantiles"][stage]
        assert snap["lane"]["full_uploads"] == 1

        # and the same quantiles render through `spt metrics`
        from libsplinter_tpu.cli.main import COMMANDS, Session
        ses = Session(name)
        try:
            fn, _, _ = COMMANDS["metrics"]
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                fn(ses, [])
            out = buf.getvalue()
            assert "sptpu_searcher_served 1" in out
            assert "sptpu_searcher_lane_full_uploads 1" in out
            for stage in P.SEARCH_STAGES:
                assert (f'daemon="searcher",stage="{stage}"' in out
                        ), f"{stage} quantiles missing from exposition"
        finally:
            ses.close()
    finally:
        store.close()
        Store.unlink(name)


@pytest.mark.obs
def test_traced_request_hits_flight_recorder(store, traced):
    """A stamped request's wake->commit journey lands in the searcher's
    ring under the SEARCH_STAGES event names."""
    rng = np.random.default_rng(8)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    key = "__sqtmp_tr"
    store.set(key, json.dumps({"k": 2}))
    store.vec_set(key, rng.normal(size=store.vec_dim)
                  .astype(np.float32))
    store.label_or(key, P.LBL_SEARCH_REQ)
    tid = P.stamp_trace(store, key)
    store.bump(key)
    assert sr.run_once() == 1
    recs = sr.recorder.tail(4)
    assert [r["id"] for r in recs] == [tid]
    assert [e[0] for e in recs[0]["events"]] == list(P.SEARCH_STAGES)
    # stamp consumed: companion key + TRACED bit gone
    assert not store.labels(key) & P.LBL_TRACED


def test_raced_rewrite_not_committed(store):
    """A request slot rewritten between gather and commit must NOT get
    the stale result: the commit is epoch-gated like the embedder's."""
    rng = np.random.default_rng(9)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    key = "__sqtmp_race"
    _request(store, key,
             rng.normal(size=store.vec_dim).astype(np.float32))

    real_service = sr._service

    def racing_service(reqs):
        store.set(key, json.dumps({"k": 3}))   # epoch moves mid-flight
        return real_service(reqs)

    sr._service = racing_service
    assert sr.run_once() == 0
    assert sr.stats.raced == 1
    assert store.labels(key) & P.LBL_SEARCH_REQ   # still pending
    sr._service = real_service
    assert sr.run_once() == 1                  # retried clean


def _wait_heartbeat(store, timeout_s: float = 10.0) -> None:
    """Block until the daemon thread has published once: it is in
    its loop, so the next request reaches it by signal and the CLI
    finds it alive — not by whichever thread the scheduler ran first."""
    deadline = time.monotonic() + timeout_s
    while P.KEY_SEARCH_STATS not in store:
        assert time.monotonic() < deadline, "no heartbeat"
        time.sleep(0.005)


def _wait_served(sr, n: int, timeout_s: float = 10.0) -> None:
    """Block until the daemon thread has COUNTED `n` answers: a client
    wakes at its own row of the commit loop, before the drain that
    served it has been added to the stats."""
    deadline = time.monotonic() + timeout_s
    while sr.stats.served < n:
        assert time.monotonic() < deadline, sr.stats
        time.sleep(0.005)


def test_submit_search_round_trip(store):
    """Client helper against a live daemon thread: label, wait, read."""
    rng = np.random.default_rng(10)
    vecs = _fill_docs(store, 12, rng)
    sr = Searcher(store)
    sr.attach()
    t = threading.Thread(target=sr.run,
                         kwargs={"stop_after": 10.0,
                                 "idle_timeout_ms": 20})
    t.start()
    try:
        _wait_heartbeat(store)
        key = "__sqtmp_cli"
        store.set(key, "placeholder")
        store.vec_set(key, vecs[3])
        rec = submit_search(store, key, 3, timeout_ms=8000)
        assert rec is not None and rec["keys"][0] == "doc/3"
    finally:
        sr.stop()
        t.join()
    assert sr.stats.wakes >= 1                 # signal path, not sweep


def test_cli_search_dispatches_to_daemon(store, monkeypatch):
    """cmd_search routes through a live daemon (heartbeat fresh) and
    renders its rows; the daemon's served counter proves the dispatch
    took the server-side path."""
    from libsplinter_tpu.cli.main import COMMANDS, Session

    rng = np.random.default_rng(11)
    vecs = _fill_docs(store, 20, rng)
    sr = Searcher(store)
    sr.attach()

    # an embedding daemon stand-in: answers the scratch-key embed with
    # a vector aimed at doc/7
    from libsplinter_tpu.engine.embedder import Embedder
    emb = Embedder(store, encoder_fn=lambda texts: np.tile(
        vecs[7], (len(texts), 1)))
    emb.attach()

    stop = threading.Event()

    def daemons():
        while not stop.is_set():
            emb.run_once()
            sr.run_once()
            sr.publish_stats()
            time.sleep(0.005)

    t = threading.Thread(target=daemons)
    t.start()
    try:
        _wait_heartbeat(store)
        ses = Session(store.name)
        fn, _, _ = COMMANDS["search"]
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            fn(ses, ["--json", "--limit", "2", "find doc seven"])
        rows = json.loads(buf.getvalue())
    finally:
        stop.set()
        t.join()
        ses.close()
    assert rows and rows[0]["key"] == "doc/7"
    assert rows[0]["similarity"] == pytest.approx(1.0, abs=1e-5)
    assert sr.stats.served >= 1                # daemon path was used
    # the CLI never staged a client-side lane for this query
    assert ses._lane is None


def test_daemon_live_dead_pid_reads_dead_instantly(store):
    """The staleness fix: a fresh heartbeat ts whose publisher pid is
    gone must NOT hold daemon_live true for max_age_s — the CLI's
    fallback to local scoring should be instant after a crash."""
    import os
    import subprocess
    import sys

    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    snap = {"ts": time.time(), "pid": proc.pid, "served": 0}
    store.set(P.KEY_SEARCH_STATS, json.dumps(snap))
    assert not daemon_live(store)
    # same snapshot with a live pid (ours) is live
    snap["pid"] = os.getpid()
    store.set(P.KEY_SEARCH_STATS, json.dumps(snap))
    assert daemon_live(store)
    # pre-pid-format heartbeats fall back to age-only (compat)
    store.set(P.KEY_SEARCH_STATS, json.dumps({"ts": time.time()}))
    assert daemon_live(store)
    store.set(P.KEY_SEARCH_STATS,
              json.dumps({"ts": time.time() - 3600}))
    assert not daemon_live(store)


def test_submit_search_repulses_once_at_half_deadline(store):
    """A pulse that races the daemon's signal_wait re-arm used to cost
    the whole timeout; submit_search now re-bumps exactly once when
    half the deadline is gone with the label still set."""
    bumps = []
    orig = store.bump
    store.bump = lambda key: (bumps.append(key), orig(key))[1]
    try:
        store.set("__sqtmp_rp", "x")
        store.vec_set("__sqtmp_rp", np.ones(store.vec_dim, np.float32))
        rec = submit_search(store, "__sqtmp_rp", 3, timeout_ms=250)
    finally:
        store.bump = orig
    assert rec is None                 # no daemon: times out
    assert bumps.count("__sqtmp_rp") == 2   # initial + ONE re-pulse


def test_sweep_fault_site_contained(store):
    """`searcher.sweep` chaos reachability (splint SPL104): an
    injected raise fires out of sweep_results itself; in production
    the run loop's cycle firewall absorbs it (drain_faults) and the
    next heartbeat cadence retries — here we pin that the site is
    live and that the sweep runs clean once the hit window passes."""
    from libsplinter_tpu.utils import faults

    rng = np.random.default_rng(23)
    _fill_docs(store, 4, rng)
    sr = Searcher(store)
    sr.attach()
    faults.arm("searcher.sweep:raise@1")
    try:
        assert faults.registered_sites() == ("searcher.sweep",)
        with pytest.raises(faults.FaultInjected):
            sr.sweep_results()
        assert sr.sweep_results() == 0   # window passed: clean sweep
        assert faults.stats()["searcher.sweep"]["fired"] == 1
    finally:
        faults.disarm()


def _no_list(monkeypatch):
    monkeypatch.setattr(
        Store, "list",
        lambda self: pytest.fail("a sweep walked every key: list()"))


def test_sweeps_find_their_rows_by_prefix_scan(store, monkeypatch):
    """Both heartbeat sweeps name their candidate rows with the
    native prefix scan and never call Store.list(); they retire the
    rows the predicate names and no others.  `sweep_keys` grows by
    the live keys each scan went over, `sweep_rows` by the rows the
    sweeps opened."""
    from libsplinter_tpu.obs import spans as S

    rng = np.random.default_rng(25)
    _fill_docs(store, 12, rng)
    sr = Searcher(store)
    sr.attach()
    names = ("__sqtmp_a", "__sqtmp_b", "__sqtmp_c")
    for name in names:
        _request(store, name, rng.normal(size=store.vec_dim)
                 .astype(np.float32))
    assert sr.run_once() == 3
    result_row = {n: P.search_result_key(store.find_index(n))
                  for n in names}
    stage_row = {}
    for key in ("st_a", "st_b"):
        store.set(key, "req")
        P.stamp_trace(store, key)
        idx = store.find_index(key)
        w = S.SpanWriter(store, "searcher", staged=True)
        assert w.begin(idx, store.epoch_at(idx)) is not None
        stage_row[key] = P.span_stage_key(idx)
    assert all(r in store for r in (*result_row.values(),
                                    *stage_row.values()))
    # one request slot and one staged slot are rewritten: epoch moved
    store.set("__sqtmp_b", "a new request owns this slot")
    store.set("st_b", "rewritten")
    live = store.header().used_slots
    _no_list(monkeypatch)

    assert sr.sweep_results() == 1
    assert sr.stats.results_reaped == 1
    assert (sr.stats.sweep_keys, sr.stats.sweep_rows) == (live, 3)
    assert result_row["__sqtmp_b"] not in store
    assert result_row["__sqtmp_a"] in store
    assert result_row["__sqtmp_c"] in store

    assert sr.sweep_stages() == 1
    assert (sr.stats.sweep_keys, sr.stats.sweep_rows) == (2 * live - 1, 5)
    assert stage_row["st_b"] not in store and stage_row["st_a"] in store

    # TTL: ten minutes on, every leftover of both kinds goes
    later = time.time() + 600
    assert sr.sweep_results(now=later) == 2
    assert sr.sweep_stages(now=later) == 1
    assert not any(r in store for r in (*result_row.values(),
                                        *stage_row.values()))
    assert sr.stats.sweep_rows == 5 + 2 + 1


def test_first_sweep_reclaims_a_predecessors_rows(store, monkeypatch):
    """A restarted daemon's first sweep retires what the previous
    generation left: rows whose request slot is gone, and rows in the
    pre-TTL format nobody owns."""
    store.set("req", "placeholder")
    idx = store.find_index("req")
    store.set(P.search_result_key(idx), json.dumps({"keys": []}))
    store.set(P.search_result_key(store.nslots + 5), json.dumps(
        {"e": 2, "ts": time.time()}))
    store.set("__sr_notanindex", "{}")
    _no_list(monkeypatch)
    sr = Searcher(store)
    sr.attach()
    assert sr.sweep_results() == 2
    assert sr.stats.sweep_rows == 3
    assert P.search_result_key(idx) not in store
    assert "__sr_notanindex" in store      # not a result row: left


def test_result_ttl_sweep_reaps_orphans(store):
    """A client that times out never consumes its __sr_ row; the
    periodic sweep retires rows past the TTL and rows whose request
    slot epoch moved on — and leaves live rows alone."""
    rng = np.random.default_rng(21)
    _fill_docs(store, 12, rng)
    sr = Searcher(store)
    sr.attach()
    for name in ("__sqtmp_o1", "__sqtmp_o2", "__sqtmp_keep"):
        _request(store, name, rng.normal(size=store.vec_dim)
                 .astype(np.float32))
    assert sr.run_once() == 3
    # all three rows exist; nobody consumed them
    rows = [k for k in store.list()
            if k.startswith(P.SEARCH_RESULT_PREFIX)]
    assert len(rows) == 3

    # o2's slot is rewritten (a NEW request will own it): epoch moved
    store.set("__sqtmp_o2", "brand new content")
    assert sr.sweep_results() == 1     # only the epoch-moved row
    assert sr.stats.results_reaped == 1

    # TTL expiry: pretend 10 minutes pass — both leftovers reap
    assert sr.sweep_results(now=time.time() + 600) == 2
    assert not [k for k in store.list()
                if k.startswith(P.SEARCH_RESULT_PREFIX)]

    # a fresh result row within TTL with an unmoved slot survives
    _request(store, "__sqtmp_keep",
             rng.normal(size=store.vec_dim).astype(np.float32))
    assert sr.run_once() == 1
    assert sr.sweep_results() == 0


def test_per_batch_failure_fails_only_that_batch(store, monkeypatch):
    """Acceptance: a device failure injected mid-_service fails only
    the faulted batch's requests with error records; the sibling batch
    commits normally and the daemon's loop never unwinds."""
    from libsplinter_tpu.engine import resident
    from libsplinter_tpu.utils import faults

    rng = np.random.default_rng(22)
    _fill_docs(store, 16, rng)
    marked = [f"doc/{i}" for i in (2, 5)]
    for key in marked:
        store.label_or(key, P.LBL_CHUNK)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    # two bloom groups -> two batches, dispatched [poison, fine].
    # Site hit order: dispatch(b1)=1, dispatch(b2)=2, then b1's
    # degradation ladder re-hits dispatch at 3 (unfused) and 4
    # (per-request) — so select@1 fails b1's fetch and dispatch@3-4
    # defeats exactly b1's ladder, leaving b2 untouched.
    # That hit order is only guaranteed when batches resolve at
    # flush(): the window's ready-probe (drain_ready) resolves an
    # already-COMPLETED batch at the next push, so on a fast or
    # lightly-loaded host b1's select + ladder can fire before b2's
    # dispatch and the armed 3-4 window lands on the wrong hits.
    # Forcing every entry not-ready defers resolution to flush()
    # (dispatch order) — same per-batch domains, deterministic counts.
    monkeypatch.setattr(resident.CallbackWindow, "_entry_ready",
                        lambda self, entry: False)
    _request(store, "__sqtmp_poison", q, k=3, bloom=0)
    _request(store, "__sqtmp_fine", q, k=3, bloom=P.LBL_CHUNK)
    faults.arm("searcher.select:raise@1,searcher.dispatch:raise@3-4")
    try:
        served = sr.run_once()
    finally:
        faults.disarm()
    assert served == 1                 # the healthy batch committed
    assert sr.stats.batch_faults == 1
    assert sr.stats.req_failures == 1
    rec_bad = _result(store, "__sqtmp_poison")
    assert "err" in rec_bad            # failed WITH an error record
    rec_ok = _result(store, "__sqtmp_fine")
    assert sorted(rec_ok["keys"]) == sorted(marked)
    for key in ("__sqtmp_poison", "__sqtmp_fine"):
        assert not store.labels(key) & P.LBL_SEARCH_REQ


def test_batch_failure_recovers_unfused(store):
    """One transient device failure: the unfused retry serves the
    batch's requests correctly — no client ever sees it."""
    from libsplinter_tpu.utils import faults

    rng = np.random.default_rng(23)
    _fill_docs(store, 16, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_tr1", q, k=4)
    faults.arm("searcher.select:raise@1")
    try:
        served = sr.run_once()
    finally:
        faults.disarm()
    assert served == 1
    assert sr.stats.retried_unfused == 1
    lane = np.array(store.vectors)
    ref = _dense_ref(lane, q,
                     exclude={store.find_index("__sqtmp_tr1")})
    rec = _result(store, "__sqtmp_tr1")
    assert rec["i"] == list(np.argsort(-ref)[:4])


def test_cli_search_local_flag_bypasses_daemon(store):
    """--local forces client-side scoring even with a fresh daemon
    heartbeat."""
    from libsplinter_tpu.cli.main import COMMANDS, Session

    rng = np.random.default_rng(12)
    vecs = _fill_docs(store, 10, rng)
    sr = Searcher(store)
    sr.attach()
    sr.publish_stats()                         # heartbeat says "live"

    from libsplinter_tpu.engine.embedder import Embedder
    emb = Embedder(store, encoder_fn=lambda texts: np.tile(
        vecs[2], (len(texts), 1)))
    emb.attach()
    stop = threading.Event()

    def embed_only():
        while not stop.is_set():
            emb.run_once()
            time.sleep(0.005)

    t = threading.Thread(target=embed_only)
    t.start()
    try:
        ses = Session(store.name)
        fn, _, _ = COMMANDS["search"]
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            fn(ses, ["--json", "--local", "--limit", "1", "query"])
        rows = json.loads(buf.getvalue())
    finally:
        stop.set()
        t.join()
        ses.close()
    assert rows and rows[0]["key"] == "doc/2"
    assert sr.stats.served == 0                # daemon untouched
    assert rows[0]["distance"] is not None     # local path scores both


# ------------------------------------------- the run loop, accounted

@pytest.mark.obs
def test_run_loop_is_fully_accounted(traced):
    """With tracing on, every pass of run() is one search.loop span
    and every second of it belongs to one child: the heartbeat names
    every phase, the children's totals add up to the loop's, and the
    stage spans still count serviced drains only."""
    import os
    import uuid

    tracer.reset()
    name = f"/spt-srloop-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    store = Store.create(name, nslots=256, max_val=4096, vec_dim=32)
    try:
        rng = np.random.default_rng(21)
        vecs = _fill_docs(store, 16, rng)
        sr = Searcher(store)
        sr.attach()
        t = threading.Thread(target=sr.run, kwargs={
            "stop_after": 2.0, "idle_timeout_ms": 20,
            "heartbeat_interval_s": 0.4})       # several beats
        t.start()
        try:
            for i in range(6):
                key = f"__sqtmp_loop{i}"
                store.set(key, "placeholder")
                store.vec_set(key, vecs[i])
                rec = submit_search(store, key, 3, timeout_ms=8000)
                assert rec is not None and rec["keys"][0] == f"doc/{i}"
        finally:
            t.join()
        snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert "truncated" not in snap
        spans = snap["spans"]
        want = {f"search.{p}" for p in (*P.SEARCH_LOOP_PHASES,
                                        *P.SEARCH_STAGES, "drain_cycle")}
        assert want <= set(spans), want - set(spans)
        assert "search.e2e" not in spans        # quantiles has it
        assert "e2e" in snap["quantiles"]

        def total(phase):
            return spans[f"search.{phase}"]["total_ms"]

        loop = total("loop")
        children = sum(total(p) for p in (
            "idle", "drain_cycle", "sweep_results", "sweep_stages",
            "publish"))
        assert loop > 1500                       # ~2 s of passes
        assert abs(loop - children) <= 0.05 * loop, (loop, children)
        # two beats at least, each with both scans: sweep_keys counts
        # the live keys both went over, sweep_rows only the __sr_ /
        # __sp_ rows they opened (six answers, each consumed at once)
        beats = spans["search.sweep_results"]["n"]
        assert beats >= 2
        assert spans["search.sweep_stages"]["n"] == beats
        assert spans["search.publish"]["n"] >= beats - 1
        assert snap["sweep_keys"] >= 2 * beats * 16
        assert snap["sweep_rows"] <= 2 * beats * 6 < snap["sweep_keys"]
        # stage spans count serviced drains, as before: one record per
        # drain that had requests, idle drains only in drain_cycle
        served = spans["search.score"]["n"]
        assert 1 <= served <= 6
        for stage in ("wake", "drain", "select", "commit", "refresh",
                      "mask"):
            assert spans[f"search.{stage}"]["n"] == served, stage
        assert spans["search.drain_cycle"]["n"] == snap["drains"] > served
        assert snap["served"] == 6
        # the first full upload is the start-up phase a bare Searcher
        # records itself
        assert snap["startup_ms"]["first_refresh"] > 0
        assert snap["startup_ms"]["total"] >= \
            snap["startup_ms"]["first_refresh"]
    finally:
        store.close()
        Store.unlink(name)


def test_returned_next_drain_counts_who_came_back(store):
    """`returned_next_drain`: at each gather, the requests whose slot
    the PREVIOUS serviced drain answered (a client keeps its key).  An
    idle gather in between changes nothing; a client that skips a
    serviced drain is not counted; the heartbeat carries the count
    beside `served`."""
    rng = np.random.default_rng(23)
    vecs = _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    a, b, c = "__sqtmp_a", "__sqtmp_b", "__sqtmp_c"
    _request(store, a, vecs[0], k=2)
    _request(store, b, vecs[1], k=2)
    assert sr.run_once() == 2
    assert sr.stats.returned_next_drain == 0     # nobody before them
    _request(store, a, vecs[2], k=2)             # a is back at once,
    _request(store, c, vecs[3], k=2)             # c is new
    assert sr.run_once() == 2
    assert sr.stats.returned_next_drain == 1
    assert sr.run_once() == 0                    # an idle gather
    _request(store, b, vecs[4], k=2)             # b sat a drain out
    assert sr.run_once() == 1
    assert sr.stats.returned_next_drain == 1
    _request(store, b, vecs[5], k=2)             # and is back at once
    _request(store, a, vecs[6], k=2)             # a sat b's drain out
    assert sr.run_once() == 2
    assert sr.stats.returned_next_drain == 2
    assert sr.stats.served == 7
    sr.publish_stats()
    snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
    assert snap["returned_next_drain"] == 2 and snap["served"] == 7


# ---------------------------------- the journal-fed lane and its mask

def _mask_spy(sr):
    """Record a copy of the mask each dispatch was handed (and the
    array's identity), through the daemon's own program lookup."""
    seen = []
    real = sr._program

    def program(k_fetch, mxu_bf16=False):
        fn = real(k_fetch, mxu_bf16=mxu_bf16)

        def spy(arr, q, mask, norms):
            seen.append((mask, mask.copy()))
            return fn(arr, q, mask, norms)

        spy._devtime_name = getattr(fn, "_devtime_name", None)
        return spy

    sr._program = program
    return seen


@pytest.mark.parametrize("seed", range(4))
def test_patched_mask_equals_candidate_mask(store, seed):
    """The liveness mask is state the daemon patches, never rebuilds:
    after any seeded run of writes, unsets, re-sets and serviced
    drains it equals candidate_mask() of the store row for row, it
    is the SAME float32 array drain after drain, and what a dispatch
    was handed is that array with exactly the pending rows zeroed."""
    rng = np.random.default_rng(100 + seed)
    dim = store.vec_dim
    _fill_docs(store, 24, rng)
    sr = Searcher(store)
    sr.attach()
    seen = _mask_spy(sr)
    keys = [f"doc/{i}" for i in range(40)]
    handed = set()
    for rnd in range(5):
        for _ in range(int(rng.integers(1, 12))):
            key = keys[rng.integers(0, len(keys))]
            op = rng.integers(0, 4)
            if op == 0 and key in store:
                store.unset(key)
            elif op == 1 and key in store:
                store.set(key, "rewritten")
            else:
                if key not in store:
                    store.set(key, "new")
                store.vec_set(key, rng.normal(size=dim).astype(np.float32))
        reqs = [f"__sqtmp_m{seed}_{i}"
                for i in rng.choice(6, size=int(rng.integers(1, 5)),
                                    replace=False)]
        for key in reqs:
            _request(store, key,
                     rng.normal(size=dim).astype(np.float32))
        want = P.candidate_mask(store)
        want[[store.find_index(k) for k in reqs]] = 0.0
        before = len(seen)
        assert sr.run_once() == len(reqs)
        assert len(seen) == before + 1
        mask, at_dispatch = seen[-1]
        handed.add(id(mask))
        assert at_dispatch.dtype == np.float32
        assert at_dispatch.shape == (store.nslots,)
        np.testing.assert_array_equal(at_dispatch, want)
        # after the drain the request rows are candidates again, and
        # once the lane has read the drain's own commits the mask is
        # candidate_mask() of the store
        sr.lane.refresh()
        np.testing.assert_array_equal(sr._sync_live(),
                                      P.candidate_mask(store))
    assert len(handed) == 1 and handed == {id(sr._live)}
    assert sr.lane.full_uploads == 1
    assert sr.lane.journal_fallbacks == 1        # the first attach
    assert sr.lane.audit() == 0


@pytest.mark.parametrize("whose", ["own", "peer_stripe"])
def test_pending_rows_are_masked_for_the_drain_and_live_after(
        store, whose):
    """Request rows hold query vectors: every pending one — a peer
    replica's stripe too — is out of the candidate set while a drain
    scores, and live again when its last dispatch has been fetched."""
    rng = np.random.default_rng(7)
    dim = store.vec_dim
    _fill_docs(store, 16, rng)
    sr = Searcher(store)
    sr.attach()
    seen = _mask_spy(sr)
    q = rng.normal(size=dim).astype(np.float32)
    _request(store, "__sqtmp_mine", q)
    _request(store, "__sqtmp_other", q)
    mine = store.find_index("__sqtmp_mine")
    other = store.find_index("__sqtmp_other")
    if whose == "peer_stripe":
        sr.stripes.owns = lambda idx: idx != other
    want = P.candidate_mask(store)
    assert want[mine] == 1.0 and want[other] == 1.0
    want[[mine, other]] = 0.0
    served = sr.run_once()
    assert served == (2 if whose == "own" else 1)
    _, at_dispatch = seen[-1]
    np.testing.assert_array_equal(at_dispatch, want)
    assert sr._live[mine] == 1.0 and sr._live[other] == 1.0
    rec = _result(store, "__sqtmp_mine")
    assert mine not in rec["i"] and other not in rec["i"]
    if whose == "peer_stripe":
        assert store.labels("__sqtmp_other") & P.LBL_SEARCH_REQ


def test_a_failed_drain_leaves_its_request_rows_live(store):
    rng = np.random.default_rng(8)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_a", q)
    assert sr.run_once() == 1
    _request(store, "__sqtmp_a", q)
    row = store.find_index("__sqtmp_a")

    def boom(*a, **k):
        raise RuntimeError("dispatch plumbing broke")

    sr._dispatch_groups = boom
    assert sr.run_once() == 0
    assert sr.stats.drain_faults == 1
    assert sr._live[row] == 1.0


def test_64_dirty_drain_of_100k_slots_scans_under_1000():
    """A drain that follows 64 rewritten request rows on a 100,000-
    slot store looks at a few hundred epochs (refresh + mask patch +
    the hidden rows' restore), not twice 100,000; nothing falls back
    and the audit finds nothing the journal missed."""
    import os
    import uuid

    name = f"/spt-sr100k-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    st = Store.create(name, nslots=100_000, max_val=2048, vec_dim=8)
    try:
        rng = np.random.default_rng(5)
        _fill_docs(st, 200, rng)
        sr = Searcher(st)
        sr.attach()
        keys = [f"__sqtmp_k{i}" for i in range(64)]

        def ask():
            for key in keys:
                _request(st, key, rng.normal(size=8).astype(np.float32))

        ask()
        assert sr.run_once() == 64
        first = sr.stats.lane_slots_scanned
        assert first >= 3 * st.nslots            # upload + first mask
        ask()
        assert sr.run_once() == 64
        per_drain = sr.stats.lane_slots_scanned - first
        assert 64 <= per_drain < 1000, per_drain
        assert sr.lane.journal_fallbacks == 1    # the first attach
        assert sr.lane.journal_rows >= 128
        sr._publish_beat()
        snap = json.loads(st.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert snap["lane"]["lane_audit_rows"] == 0
        assert snap["lane"]["journal_fallbacks"] == 1
        assert snap["lane_slots_scanned"] == sr.stats.lane_slots_scanned
        assert snap["lane_slots_scanned"] - first < 1000   # no audit in it
        for k in ("lane_slots_scanned", "journal_rows",
                  "journal_fallbacks", "lane_audit_rows"):
            assert k in snap["lane"], k
        rec = _result(st, keys[0])
        assert len(rec["i"]) == 5
        assert all(st.key_at(i).startswith("doc/") for i in rec["i"])
    finally:
        st.close()
        Store.unlink(name)


class _LosesARecord:
    """A store whose journal drops the records of one slot."""
    lost = -1

    def __init__(self, st):
        self._st = st

    def __getattr__(self, name):
        return getattr(self._st, name)

    def changed_since(self, cursor):
        rows, cur, complete = self._st.changed_since(cursor)
        return rows[rows != self.lost], cur, complete


def test_the_beat_audits_the_lane_and_publishes_what_it_found(store_2k):
    """Once a heartbeat the lane runs the full comparison: a row the
    journal did not deliver is staged, counted and published, and the
    mask is patched for it like for any row the lane re-examined."""
    from libsplinter_tpu.ops import StagedLane

    store = store_2k
    rng = np.random.default_rng(9)
    _fill_docs(store, 8, rng)

    view = _LosesARecord(store)
    sr = Searcher(store, lane=StagedLane(view))
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_a", q)
    assert sr.run_once() == 1
    store.set("doc/late", "a document the journal loses")
    store.vec_set("doc/late", q)                 # the best hit there is
    view.lost = row = store.find_index("doc/late")
    _request(store, "__sqtmp_a", q)
    assert sr.run_once() == 1
    assert sr._live[row] == 0.0                  # stale: not a candidate
    assert row not in _result(store, "__sqtmp_a")["i"]
    sr._publish_beat()
    snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
    assert snap["lane"]["lane_audit_rows"] == 1
    # once in the record: the lane's counters are not copied beside
    # `served` (the 2 KB heartbeat has no room for both)
    assert "lane_audit_rows" not in snap and "journal_rows" not in snap
    _request(store, "__sqtmp_a", q)
    assert sr.run_once() == 1
    assert sr._live[row] == 1.0                  # the audit's rows too
    assert _result(store, "__sqtmp_a")["i"][0] == row


def test_a_serviced_drain_counts_the_kernels_passes(store_2k):
    """The fused program returns the selection passes it ran and the
    tiles it scanned; the drain's fetch adds both to the stats, and
    the heartbeat publishes them (the unfused program counts none)."""
    store = store_2k
    rng = np.random.default_rng(46)
    _fill_docs(store, 40, rng)
    sr = Searcher(store, fused=True, interpret=True, use_pallas=True,
                  block_n=64)
    sr.attach()
    qs = rng.normal(size=(3, store.vec_dim)).astype(np.float32)
    for i, q in enumerate(qs):
        _request(store, f"__sqtmp_p{i}", q)
    assert sr.run_once() == 3
    assert sr.stats.dispatches == 1
    tiles = store.nslots // 64
    assert sr.stats.select_tiles == tiles
    # at least the first tile with rows fills k_pad = 16 places, and a
    # tile never runs more than k_pad passes
    assert 16 <= sr.stats.select_passes <= 16 * tiles
    lane = np.array(store.vectors)
    hidden = {store.find_index(f"__sqtmp_p{i}") for i in range(3)}
    for i, q in enumerate(qs):
        ref = _dense_ref(lane, q, exclude=hidden)
        assert _result(store, f"__sqtmp_p{i}")["i"] == \
            list(np.argsort(-ref)[:5])
    sr._publish_beat()
    snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
    assert snap["select_passes"] == sr.stats.select_passes
    assert snap["select_tiles"] == tiles
    _request(store, "__sqtmp_p0", qs[0])
    assert sr.run_once() == 1
    assert sr.stats.select_tiles == 2 * tiles


# ------------------------- the gather reads the journal (LabelCursor)

def _count_calls(obj, *names):
    """Wrap the named methods of one object to count their calls."""
    calls = dict.fromkeys(names, 0)
    for name in names:
        def counted(*a, _real=getattr(obj, name), _name=name, **k):
            calls[_name] += 1
            return _real(*a, **k)

        setattr(obj, name, counted)
    return calls


def _asking_now(store):
    """The oracle's own walk (past any call counter on the object)."""
    return Store.enumerate_indices(store, P.LBL_SEARCH_REQ)


def test_steady_drains_walk_no_slots():
    """Acceptance: on a store of 100,000 slots, with requests raised
    by submit_search's own sequence against the daemon's run loop, a
    steady drain calls no all-slot function — enumerate_indices and
    epochs() are reached only from the first attach — and the gathers
    read the labels of a few rows a request, not of every slot."""
    import os
    import uuid

    name = f"/spt-srgather-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    st = Store.create(name, nslots=100_000, max_val=2048, vec_dim=8)
    sr = Searcher(st)
    sr.attach()
    t = threading.Thread(target=sr.run, kwargs={
        "stop_after": 120.0, "idle_timeout_ms": 20,
        "heartbeat_interval_s": 3600.0})     # one beat: the first
    try:
        rng = np.random.default_rng(21)
        vecs = _fill_docs(st, 64, rng, dim=8)
        t.start()
        _wait_heartbeat(st)
        n_clients, rounds = 8, 6
        failed = []

        def client(c, n):
            key = f"__sqtmp_g{c}"
            for r in range(n):
                st.set(key, "placeholder")
                st.vec_set(key, vecs[(c * 7 + r) % 64])
                rec = submit_search(st, key, 3, timeout_ms=60_000)
                if rec is None or len(rec["i"]) != 3:
                    failed.append((c, r))

        def burst(n):
            ts = [threading.Thread(target=client, args=(c, n))
                  for c in range(n_clients)]
            for x in ts:
                x.start()
            for x in ts:
                x.join()

        burst(1)                     # the lane's upload, the first mask
        assert not failed
        _wait_served(sr, n_clients)
        assert sr.stats.gather_fallbacks == 1    # the first attach
        scanned0 = sr.stats.gather_slots_scanned
        assert scanned0 >= st.nslots
        calls = _count_calls(st, "enumerate_indices", "epochs")
        served0 = sr.stats.served
        burst(rounds)
        assert not failed
        asked = n_clients * rounds
        _wait_served(sr, served0 + asked)
        assert sr.stats.served - served0 == asked
        assert calls == {"enumerate_indices": 0, "epochs": 0}
        assert 0 < sr.stats.gather_slots_scanned - scanned0 < 8 * asked
        assert sr.stats.gather_fallbacks == 1
        assert sr.lane.journal_fallbacks == 1
    finally:
        sr.stop()
        if t.is_alive():
            t.join()
        st.close()
        Store.unlink(name)


def test_a_request_labelled_after_the_gather_read_its_set(store):
    """The race of a gather that reads the ring between a client's
    `set` and its `label_or`: the row is looked at, found unlabelled
    and dropped — and comes back from the label_or's own record,
    which is behind no cursor yet."""
    rng = np.random.default_rng(22)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    assert sr.run_once() == 0                    # the first walk
    calls = _count_calls(store, "enumerate_indices")
    key = "__sqtmp_race"
    store.set(key, json.dumps({"k": 3}))
    store.vec_set(key, rng.normal(size=store.vec_dim).astype(np.float32))
    scanned = sr.stats.gather_slots_scanned
    assert sr.run_once() == 0                    # sees the set alone
    assert sr.stats.gather_slots_scanned == scanned + 1
    assert sr._asking.pending.size == 0
    assert sr._asking._cursor == store.journal_head()
    store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
    store.bump(key)
    assert sr.run_once() == 1
    assert len(_result(store, key)["i"]) == 3
    assert calls["enumerate_indices"] == 0


@pytest.mark.parametrize("why", ["torn", "deferred", "peer_stripe"])
def test_a_row_left_labelled_stays_pending_with_no_new_record(store, why):
    """A request the drain does not serve — its vector torn under the
    gather, deferred by admission, a peer replica's stripe — stays in
    the pending set and is looked at again by the next gather, though
    the journal names it no second time."""
    rng = np.random.default_rng(23)
    _fill_docs(store, 8, rng)
    sr = Searcher(store, admit_cap=2 if why == "deferred" else None)
    sr.attach()
    assert sr.run_once() == 0                    # the first walk
    keys = [f"__sqtmp_p{i}" for i in range(5)]
    for key in keys:
        _request(store, key,
                 rng.normal(size=store.vec_dim).astype(np.float32))
    rows = sorted(store.find_index(k) for k in keys)
    held = rows[2]
    if why == "torn":
        real = store.vec_gather

        def torn_once(rows_a):
            vecs, eps = real(rows_a)
            eps[list(rows_a).index(held)] = Store.GATHER_TORN
            store.vec_gather = real
            return vecs, eps

        store.vec_gather = torn_once
    elif why == "peer_stripe":
        sr.stripes.owns = lambda idx: idx != held
    calls = _count_calls(store, "enumerate_indices")
    first = sr.run_once()
    assert first == (2 if why == "deferred" else 4)
    head = store.journal_head()
    left = _asking_now(store)
    assert held in left and len(left) == 5 - first
    # the set as the gather left it: the served rows leave it at the
    # next look, the held ones stay
    assert sr._asking.pending.tolist() == rows
    if why == "deferred":
        assert sr._had_deferred
    sr.stripes.owns = lambda idx: True
    served = first
    while served < 5:
        before = _asking_now(store)
        got = sr.run_once()
        assert got > 0
        served += got
        assert sr._asking.pending.tolist() == before
    assert served == 5 and not _asking_now(store)
    for key in keys:
        assert len(_result(store, key)["i"]) == 5
    assert sr.run_once() == 0 and sr._asking.pending.size == 0
    assert calls["enumerate_indices"] == 0
    # nobody raised a label again: the records since are the drains'
    # own commits
    assert sr.stats.gather_fallbacks == 1
    assert head <= store.journal_head()


def test_a_lapped_cursor_walks_once_and_misses_nothing(store):
    """Writers that lap the gather's cursor: one fallback walk, after
    the journal read, finds the request whose record was overwritten;
    the next gather reads the journal again."""
    from libsplinter_tpu import _native as N

    rng = np.random.default_rng(24)
    _fill_docs(store, 8, rng)
    sr = Searcher(store)
    sr.attach()
    assert sr.run_once() == 0
    assert sr.stats.gather_fallbacks == 1
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_lap", q)
    for i in range(N.JOURNAL_CAP + 1):           # its record is gone
        store.set("churn", "x")
    calls = _count_calls(store, "enumerate_indices")
    scanned = sr.stats.gather_slots_scanned
    assert sr.run_once() == 1
    assert sr.stats.gather_fallbacks == 2
    assert sr.stats.gather_slots_scanned == scanned + store.nslots
    assert calls["enumerate_indices"] == 1
    _request(store, "__sqtmp_lap", q)
    assert sr.run_once() == 1                    # by the journal again
    assert sr.stats.gather_fallbacks == 2
    assert calls["enumerate_indices"] == 1
    assert sr.stats.gather_slots_scanned < scanned + store.nslots + 16


@pytest.mark.parametrize("predecessor", ["none", "crashed_mid_drain"])
def test_a_fresh_daemon_reclaims_requests_raised_before_it(
        store, predecessor):
    """The first gather after attach walks every slot (cursor taken
    first): a request raised before the daemon existed — or gathered
    by a predecessor that died before serving it — is served."""
    rng = np.random.default_rng(25)
    _fill_docs(store, 8, rng)
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    if predecessor == "crashed_mid_drain":
        old = Searcher(store)
        old.attach()
        assert old.run_once() == 0
        _request(store, "__sqtmp_early", q)
        assert len(old._gather_requests()) == 1  # read; never served
    else:
        _request(store, "__sqtmp_early", q)
    sr = Searcher(store)
    sr.attach()
    assert sr.run_once() == 1
    assert sr.stats.gather_fallbacks == 1
    assert sr.stats.gather_slots_scanned == store.nslots
    assert len(_result(store, "__sqtmp_early")["i"]) == 5


def test_the_beat_audits_the_labels_and_adopts_what_it_found(store_2k):
    """A label raised behind the journal's back is invisible to the
    gathers; the beat's walk finds it, adopts it, counts it in the
    heartbeat, and the next drain serves it."""
    from libsplinter_tpu.store import LabelCursor

    store = store_2k
    rng = np.random.default_rng(26)
    _fill_docs(store, 8, rng)
    view = _LosesARecord(store)
    sr = Searcher(store)
    sr._asking = LabelCursor(view, P.LBL_SEARCH_REQ)
    sr.attach()
    q = rng.normal(size=store.vec_dim).astype(np.float32)
    _request(store, "__sqtmp_seen", q)
    assert sr.run_once() == 1
    sr._publish_beat()
    assert sr.stats.gather_audit_rows == 0 and not sr._audit_adopted
    store.set("__sqtmp_lost", "placeholder")
    view.lost = row = store.find_index("__sqtmp_lost")
    _request(store, "__sqtmp_lost", q)
    _request(store, "__sqtmp_seen", q)
    assert sr.run_once() == 1                    # the other is unseen
    assert store.labels("__sqtmp_lost") & P.LBL_SEARCH_REQ
    scanned = sr.stats.gather_slots_scanned
    sr._publish_beat()
    assert sr.stats.gather_slots_scanned == scanned   # no audit in it
    snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
    assert snap["gather_audit_rows"] == 1
    assert snap["gather_fallbacks"] == 1
    assert snap["gather_slots_scanned"] == scanned
    assert sr._audit_adopted and row in sr._asking.pending
    assert sr.run_once() == 1 and not sr._audit_adopted
    assert len(_result(store, "__sqtmp_lost")["i"]) == 5
    sr._publish_beat()                           # counted once
    assert sr.stats.gather_audit_rows == 1


def test_a_label_with_no_record_and_no_pulse_is_served_within_a_beat(
        store_2k):
    """The run loop: an idle daemon whose beat adopts a row drains in
    the same pass — no wake is coming for it."""
    from libsplinter_tpu.store import LabelCursor

    store = store_2k
    rng = np.random.default_rng(27)
    _fill_docs(store, 8, rng)
    view = _LosesARecord(store)
    sr = Searcher(store)
    sr._asking = LabelCursor(view, P.LBL_SEARCH_REQ)
    sr.attach()
    t = threading.Thread(target=sr.run, kwargs={
        "stop_after": 60.0, "idle_timeout_ms": 20,
        "heartbeat_interval_s": 0.25})
    t.start()
    try:
        _wait_heartbeat(store)
        key = "__sqtmp_quiet"
        store.set(key, json.dumps({"k": 3}))
        store.vec_set(key,
                      rng.normal(size=store.vec_dim).astype(np.float32))
        view.lost = store.find_index(key)
        time.sleep(0.05)                         # the set's wake drains
        store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)   # no bump
        deadline = time.monotonic() + 30.0
        while store.labels(key) & P.LBL_SEARCH_REQ:
            assert time.monotonic() < deadline, "never served"
            time.sleep(0.01)
        assert len(_result(store, key)["i"]) == 3
    finally:
        sr.stop()
        t.join()
    assert sr.stats.gather_audit_rows == 1
    assert sr.stats.gather_fallbacks == 1


@pytest.mark.parametrize("seed", range(4))
def test_pending_set_is_the_enumeration_at_every_gather(store, seed):
    """Oracle: over a seeded interleaving of submits, rewrites,
    cancels, unsets, commits, deferrals and stripe changes, what a
    gather returns is enumerate_indices(LBL_SEARCH_REQ) at that
    moment, `_hidden_rows` is what it was built from before (the
    drain's own rows + that whole set), and the beat's audit never
    finds a row the journal did not name."""
    rng = np.random.default_rng(300 + seed)
    dim = store.vec_dim
    _fill_docs(store, 16, rng)
    sr = Searcher(store, admit_cap=3)
    sr.attach()
    gathers = []
    real_rows = sr._asking.rows

    def rows():
        got = real_rows()
        want = _asking_now(store)
        assert got.tolist() == want
        gathers.append(want)
        return got

    sr._asking.rows = rows
    real_hidden = sr._hidden_rows
    hidden_seen = []

    def hidden(reqs):
        got = real_hidden(reqs)
        want = np.unique(np.asarray(
            [r.idx for r in reqs] + gathers[-1], np.int64))
        np.testing.assert_array_equal(got, want)
        hidden_seen.append(got.size)
        return got

    sr._hidden_rows = hidden
    pool = [f"__sqtmp_o{i}" for i in range(10)]
    blob = json.dumps({"k": 3})
    for rnd in range(24):
        for _ in range(int(rng.integers(0, 7))):
            key = pool[rng.integers(0, len(pool))]
            op = rng.integers(0, 8)
            if op <= 3 or key not in store:      # submit (or again)
                _request(store, key,
                         rng.normal(size=dim).astype(np.float32), k=3)
            elif op == 4:                        # rewrite under way
                store.set(key, blob)
            elif op == 5:                        # the client gives up
                store.label_clear(key,
                                  P.LBL_SEARCH_REQ | P.LBL_WAITING)
            elif op == 6:                        # the key goes
                store.unset(key)
            else:                                # a raise with no pulse
                store.label_or(key, P.LBL_SEARCH_REQ)
        mode = rng.integers(0, 3)
        sr.stripes.owns = (
            (lambda idx: True) if mode else
            (lambda idx, p=int(rng.integers(0, 2)): idx % 2 == p))
        sr.run_once()
        if rnd % 5 == 4:
            sr._publish_beat()
            assert sr._asking.pending.tolist() == _asking_now(store)
    sr.stripes.owns = lambda idx: True
    for _ in range(8):
        sr.run_once()
    assert not _asking_now(store) and sr._asking.pending.size == 0
    assert len(gathers) >= 32 and max(map(len, gathers)) >= 3
    assert hidden_seen and max(hidden_seen) >= 3
    assert sr.stats.gather_audit_rows == 0
    assert sr.stats.gather_fallbacks == 1
    assert sr.stats.served >= 10
