"""Daemon stats heartbeats: structured JSON snapshots in debug-labeled
store keys (__embedder_stats / __completer_stats) — the observability
counterpart of the reference's append-only __debug channel
(/root/reference/splainference.cpp:94-100), consumable by the sidecar's
group-63 debug watch."""
from __future__ import annotations

import json

import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.engine.embedder import Embedder


def _mkstore(tag):
    name = f"/spt-stats-{tag}"
    Store.unlink(name)
    return name, Store.create(name, nslots=64, max_val=1024, vec_dim=8)


def test_embedder_stats_heartbeat(tmp_path):
    name, st = _mkstore(tmp_path.name)
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("k", "text")
        st.set_type("k", 0x80)        # T_VARTEXT
        st.label_or("k", P.LBL_EMBED_REQ)
        emb.run_once()
        emb.publish_stats()
        snap = json.loads(st.get(P.KEY_EMBED_STATS).rstrip(b"\0"))
        assert snap["embedded"] == 1
        assert snap["pending"] == 0
        assert "ts" in snap
        assert st.labels(P.KEY_EMBED_STATS) & P.LBL_DEBUG
    finally:
        st.close()
        Store.unlink(name)


def test_heartbeat_degrades_on_overflow(tmp_path):
    """A snapshot too big for max_val must degrade to the scalar
    counters (truncated flag set), not silently vanish — enabling
    tracing must never remove the heartbeat."""
    name, st = _mkstore(f"ovf-{tmp_path.name}")
    try:
        big = {"completions": 7, "spans": {f"s{i}": {"n": i,
               "total_ms": 1.0, "max_ms": 1.0} for i in range(200)}}
        P.publish_heartbeat(st, "__hb", big)
        snap = json.loads(st.get("__hb").rstrip(b"\0"))
        assert snap["completions"] == 7
        assert snap.get("truncated") is True
        assert "spans" not in snap
    finally:
        st.close()
        Store.unlink(name)


class _SetSpy:
    """Store facade recording every publish attempt's section set —
    the degradation ORDER is observable, not just the survivors."""

    def __init__(self, st):
        self._st = st
        self.attempts: list[list[str]] = []

    def set(self, key, val):
        self.attempts.append(sorted(json.loads(val).keys()))
        self._st.set(key, val)

    def label_or(self, key, mask):
        self._st.label_or(key, mask)


def _traced_payload():
    """A realistic SPTPU_TRACE=1 embedder heartbeat: scalar counters +
    a slow log (largest), a quantiles section (medium), and recorder
    accounting (small)."""
    slow = [{"id": (1 << 24) | i, "key": f"bench/{i}",
             "wall_ms": 123.456, "ts": 1e9,
             "slow_threshold_ms": 10.0,
             "events": [[s, 1.234] for s in P.PIPELINE_STAGES]}
            for i in range(12)]
    quantiles = {s: {"n": 30, "total_ms": 99.9, "max_ms": 9.9,
                     "p50_ms": 1.11, "p90_ms": 2.22, "p95_ms": 2.88,
                     "p99_ms": 3.33} for s in P.PIPELINE_STAGES}
    return {"wakes": 9, "embedded": 8, "pending": 0,
            "overlap_ratio": 0.5,
            "recorder": {"recorded": 12, "dropped": 0,
                         "slow_promoted": 12},
            "quantiles": quantiles, "slow_log": slow}


@pytest.mark.obs
def test_heartbeat_drop_order_quantiles_then_slow_log(tmp_path):
    """Section-by-section degradation follows a FIXED order, not the
    sections' sizes: quantiles first (smaller than the slow log here),
    then the slow log — and the scalar core counters always land
    last-resort."""
    # max_val sized so BOTH optional sections must go (core counters
    # + recorder accounting still fit)
    name = f"/spt-stats-order-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=320, vec_dim=8)
    try:
        spy = _SetSpy(st)
        payload = _traced_payload()
        assert len(json.dumps(payload["quantiles"])) \
            < len(json.dumps(payload["slow_log"]))
        P.publish_heartbeat(spy, "__hb", payload)
        # attempt 0 carried everything; quantiles went first, the slow
        # log only after it; core counters never dropped
        assert "slow_log" in spy.attempts[0]
        assert "quantiles" in spy.attempts[0]
        dropped_slow = next(i for i, a in enumerate(spy.attempts)
                            if "slow_log" not in a)
        dropped_q = next(i for i, a in enumerate(spy.attempts)
                         if "quantiles" not in a)
        assert dropped_q < dropped_slow, spy.attempts
        assert all("embedded" in a and "wakes" in a
                   for a in spy.attempts)
        snap = json.loads(st.get("__hb").rstrip(b"\0"))
        assert snap.get("truncated") is True
        assert "slow_log" not in snap and "quantiles" not in snap
        assert snap["embedded"] == 8
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.obs
def test_heartbeat_spans_survive_the_bulky_sections(tmp_path):
    """The sections deltas are read from (`spans`, `devtime`) go LAST:
    with quantiles and a slow log that cannot fit, both of those go
    and `spans`/`devtime` stay, though `spans` is the largest section
    left."""
    name = f"/spt-stats-q-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=2048, vec_dim=8)
    try:
        payload = _traced_payload()
        payload["spans"] = {
            f"embed.{s}": {"n": 30, "total_ms": 99.9, "max_ms": 9.9}
            for s in (*P.PIPELINE_STAGES, "drain_cycle", "e2e")}
        payload["devtime"] = {"ring": {"n": 30, "compiles": 1,
                                       "runtime_compiles": 0}}
        payload["lane"] = {"full_uploads": 1}
        P.publish_heartbeat(st, "__hb", payload)
        snap = json.loads(st.get("__hb").rstrip(b"\0"))
        assert snap.get("truncated") is True
        assert "slow_log" not in snap and "quantiles" not in snap
        assert set(snap["spans"]) == set(payload["spans"])
        assert snap["devtime"] == payload["devtime"]
        assert snap["lane"] == payload["lane"]      # never reached
        assert snap["embedded"] == 8
    finally:
        st.close()
        Store.unlink(name)


def _searcher_payload():
    """The search daemon's traced heartbeat with every section filled
    at the magnitudes of a long-running 1.5M-slot deployment: counts
    to 1e5, totals to 1e5 ms (the shape of searcher.publish_stats +
    attach_trace_sections)."""
    from libsplinter_tpu.engine.searcher import SearcherStats
    import dataclasses

    stats = {f.name: 98765 for f in dataclasses.fields(SearcherStats)}
    stats["sweep_keys"] = 98765 * 1_572_864
    # every dispatch scans the lane's 1,536 tiles, about a pass a tile
    stats["select_tiles"] = stats["select_passes"] = 98765 * 1_536
    names = [f"search.{p}" for p in (*P.SEARCH_LOOP_PHASES,
                                     *P.SEARCH_STAGES, "drain_cycle")]
    spans = {n: {"n": 98765, "total_ms": 98765.4, "max_ms": 17232.9}
             for n in names}
    quant = {n[len("search."):]: {
        "n": 98765, "total_ms": 98765.432, "max_ms": 17232.912,
        "p50_ms": 21.2471, "p90_ms": 33.5127, "p95_ms": 40.1234,
        "p99_ms": 71.4682} for n in (*names, "search.e2e")}
    prog = {"n": 98765, "compiles": 2, "runtime_compiles": 2,
            "total_ms": 98765.4, "p50_ms": 21.247, "p99_ms": 71.468}
    idle = {"n": 0, "compiles": 2, "runtime_compiles": 2}
    return {
        **stats,
        "spans_obs": {"committed": 98765, "recovered": 0, "dropped": 0,
                      "pending": 0},
        "coalesce_ratio": 19.888, "generation": 1, "inflight_depth": 2,
        "lane": {"full_uploads": 1, "refreshes": 98765,
                 "rows_staged": 9876543, "rows_padded": 9876543,
                 "scatter_chunks": 98765, "ring_dispatches": 98765,
                 "ring_chunks": 98765, "chunks_bucket_64": 98765},
        "startup_ms": {"process": 13185.3, "jax": 0.0, "store_open": 3.7,
                       "attach": 2984.4, "warmup": 98765.4,
                       "first_refresh": 16244.2, "total": 131183.0},
        "compile_events": 6,
        "devtime": {"stage_update": idle, "fused_topk": prog,
                    "scatter_ring": idle, "scatter": idle},
        "spans": spans, "quantiles": quant,
        "recorder": {"recorded": 98765, "dropped": 98765,
                     "slow_promoted": 98765,
                     "slow_threshold_ms": 98765.432},
        "slow_log": [{"id": (1 << 24) | i, "key": "<drain>",
                      "wall_ms": 2793.429, "ts": 1790550501.957,
                      "slow_threshold_ms": 98765.432,
                      "events": [[s, 98765.432]
                                 for s in P.SEARCH_STAGES]}
                     for i in range(4)]}


@pytest.mark.obs
def test_searcher_heartbeat_keeps_what_is_read_at_2k(tmp_path):
    """At the benchmark cell's max_val (2,048) the full search
    heartbeat cannot fit.  `quantiles` goes first, then the slow log
    and the recorder's accounting; `spans`, `devtime`, `startup_ms`
    and every scalar counter survive, and the record parses."""
    from libsplinter_tpu.engine.searcher import SearcherStats
    import dataclasses

    name = f"/spt-stats-sr-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=2048, vec_dim=8)
    try:
        spy = _SetSpy(st)
        payload = _searcher_payload()
        P.publish_heartbeat(spy, "__hb", payload)
        gone = [next(i for i, a in enumerate(spy.attempts)
                     if sec not in a)
                for sec in ("quantiles", "slow_log", "recorder")]
        assert gone == [1, 2, 3], spy.attempts   # the fixed order
        raw = st.get("__hb").rstrip(b"\0")
        assert len(raw) <= 2048
        snap = json.loads(raw)
        assert snap.get("truncated") is True
        for sec in ("spans", "devtime", "startup_ms"):
            assert snap[sec] == payload[sec], sec
        for f in dataclasses.fields(SearcherStats):
            assert snap[f.name] == payload[f.name], f.name
        for k in ("coalesce_ratio", "generation", "inflight_depth",
                  "compile_events", "ts", "pid"):
            assert k in snap
        # what the benchmark's metrics read
        assert snap["spans"]["search.drain"]["n"] == 98765
        assert snap["spans"]["search.commit"]["total_ms"] == 98765.4
        assert snap["devtime"]["fused_topk"]["total_ms"] == 98765.4
        assert snap["startup_ms"]["total"] == 131183.0
        assert snap["select_passes"] == snap["select_tiles"] == 151703040
    finally:
        st.close()
        Store.unlink(name)


def _completer_payload():
    """The continuous lane's traced heartbeat with every section filled
    at the magnitudes of a long-running pangu deployment (the shape of
    completer.publish_stats + attach_trace_sections): counts to 1e5,
    totals to 1e7 ms, every loop phase and every stage in `spans`."""
    import dataclasses

    from libsplinter_tpu.engine.completer import CompleterStats

    stats = {f.name: 98765 for f in dataclasses.fields(CompleterStats)}
    names = [f"infer.{p}" for p in (*P.CONT_LOOP_PHASES,
                                    *P.CONT_INFER_STAGES)]
    spans = {n: {"n": 9876543, "total_ms": 98765432.1, "max_ms": 17232.9}
             for n in names}
    quant = {n[len("infer."):]: {
        "n": 9876543, "total_ms": 98765432.123, "max_ms": 17232.912,
        "p50_ms": 21.2471, "p90_ms": 33.5127, "p95_ms": 40.1234,
        "p99_ms": 71.4682} for n in (*names, "infer.e2e")}
    prog = {"n": 9876543, "compiles": 2, "runtime_compiles": 0,
            "total_ms": 98765432.1, "p50_ms": 21.247, "p99_ms": 71.468}
    gauges = ("inflight_depth", "bp_memo", "pages_free", "pages_used",
              "live_tokens", "pages_used_peak", "prefix_hits",
              "prefix_misses", "prefix_hit_tokens", "prefix_evictions",
              "prefix_shared_pages", "prefix_evictable",
              "prefix_cow_copies", "prefix_bytes_saved",
              "audit_records", "compile_events", "generation")
    return {
        **stats, **{g: 9876543210 for g in gauges},
        "spans_obs": {"committed": 98765, "recovered": 0, "dropped": 0,
                      "pending": 0},
        "kv_dtype": "bfloat16", "pool_mb": 1887.437,
        "pool_mb_peak": 1887.437,
        "expert_totals": [9876543210] * 16,
        "startup_ms": {"process": 13185.3, "jax": 9876.5,
                       "weights": 98765.4, "warmup": 98765.4,
                       "total": 220592.6},
        "devtime": {k: prog for k in (
            "paged_chunk", "suffix_prefill", "bucket_prefill",
            "cow_copy", "sample", "state_copy", "state_zero")},
        "spans": spans, "quantiles": quant,
        "recorder": {"recorded": 98765, "dropped": 98765,
                     "slow_promoted": 98765,
                     "slow_threshold_ms": 98765.432},
        "slow_log": [{"id": (1 << 24) | i, "key": f"__cq_{i:06d}",
                      "wall_ms": 27934.429, "ts": 1790550501.957,
                      "slow_threshold_ms": 98765.432,
                      "events": [[s, 98765.432]
                                 for s in P.CONT_INFER_STAGES]}
                     for i in range(4)]}


@pytest.mark.obs
def test_a_dropped_section_never_overwrites_a_counter(tmp_path):
    """The rehearsals' stores hold 4,096 B: the traced completer
    heartbeat sheds sections there, and its `truncated` counter (which
    the benchmark reads as a fault counter) must stay the count."""
    name = f"/spt-stats-cp4-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=4096, vec_dim=8)
    try:
        payload = _completer_payload()
        payload["truncated"] = 0
        P.publish_heartbeat(st, "__hb", payload)
        snap = json.loads(st.get("__hb").rstrip(b"\0"))
        assert "quantiles" not in snap and "slow_log" not in snap
        assert snap["truncated"] == 0 and snap["truncated"] is not True
        assert snap["spans"] == payload["spans"]
        # a payload with no such counter still gets the mark
        del payload["truncated"]
        P.publish_heartbeat(st, "__hb", payload)
        snap = json.loads(st.get("__hb").rstrip(b"\0"))
        assert snap["truncated"] is True
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.obs
def test_completer_heartbeat_keeps_what_is_read_at_16k(tmp_path):
    """At pangu's cell's max_val (16,384) the traced continuous-lane
    heartbeat, with the loop's phases beside the stages, still lands
    with `spans`, `devtime`, `startup_ms` and every scalar intact;
    whatever has to go goes from HEARTBEAT_DROP_FIRST, in its order."""
    import dataclasses

    from libsplinter_tpu.engine.completer import CompleterStats

    name = f"/spt-stats-cp-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=16384, vec_dim=8)
    try:
        spy = _SetSpy(st)
        payload = _completer_payload()
        P.publish_heartbeat(spy, "__hb", payload)
        raw = st.get("__hb").rstrip(b"\0")
        assert len(raw) <= 16384
        snap = json.loads(raw)
        dropped = set(payload) - set(snap)
        assert dropped <= set(P.HEARTBEAT_DROP_FIRST), dropped
        assert sorted(dropped) == sorted(
            P.HEARTBEAT_DROP_FIRST[:len(dropped)])
        for sec in ("spans", "devtime", "startup_ms", "expert_totals",
                    "spans_obs"):
            assert snap[sec] == payload[sec], sec
        # every scalar, the completer's own `truncated` (completions
        # cut at the slot's size) with them: the heartbeat's mark
        # never takes a counter's place
        for f in dataclasses.fields(CompleterStats):
            assert snap[f.name] == payload[f.name], f.name
        for k, v in payload.items():
            if not isinstance(v, (dict, list)):
                assert snap[k] == v, k
        # what the benchmark's metrics read
        want = {f"infer.{p}" for p in (*P.CONT_LOOP_PHASES,
                                       *P.CONT_INFER_STAGES)}
        assert set(snap["spans"]) == want
        assert snap["spans"]["infer.loop"]["total_ms"] == 98765432.1
        assert snap["spans"]["infer.join"]["n"] == 9876543
        assert snap["devtime"]["paged_chunk"]["total_ms"] == 98765432.1
        assert snap["startup_ms"]["total"] == 220592.6
    finally:
        st.close()
        Store.unlink(name)


def test_completer_stats_heartbeat(tmp_path):
    name, st = _mkstore(tmp_path.name)
    try:
        comp = Completer(st, generate_fn=lambda p: iter([b"ok "]),
                         template="none")
        comp.attach()
        st.set("q", "hi")
        st.label_or("q", P.LBL_INFER_REQ)
        comp.run_once()
        comp.publish_stats()
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert snap["completions"] == 1
        assert snap["vanished"] == 0
        assert st.labels(P.KEY_COMPLETE_STATS) & P.LBL_DEBUG
    finally:
        st.close()
        Store.unlink(name)
