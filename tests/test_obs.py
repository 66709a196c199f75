"""The obs subsystem: log-bucketed histograms, flight recorder, and
Prometheus exposition — plus their threading through the daemons
(trace-id stamps, heartbeat quantiles, slow log, `spt metrics` /
`spt trace tail`).

Grouped under `pytest -m obs` (the `make obs-check` tier)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from libsplinter_tpu import Store, T_VARTEXT
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.obs.hist import (
    LogHistogram, bucket_index, bucket_upper_ms,
)
from libsplinter_tpu.obs.prom import PromWriter
from libsplinter_tpu.obs.recorder import FlightRecorder
from libsplinter_tpu.utils.trace import Tracer

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------- histogram

class TestLogHistogram:
    def test_quantiles_within_bucket_resolution(self):
        h = LogHistogram()
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=1.0, sigma=1.2, size=5000)
        for s in samples:
            h.record(float(s))
        for q in (0.5, 0.9, 0.99):
            exact = float(np.quantile(samples, q))
            got = h.quantile(q)
            # log-bucket resolution: ~19% relative at 4 buckets/octave
            assert abs(got - exact) / exact < 0.25, (q, got, exact)
        assert h.n == 5000
        assert h.max_ms == pytest.approx(float(samples.max()))

    def test_quantiles_clamped_to_observed_range(self):
        h = LogHistogram()
        h.record(3.0)
        assert h.quantile(0.5) == 3.0
        assert h.quantile(0.99) == 3.0

    def test_bucket_edges_monotonic_and_owning(self):
        prev = 0.0
        for ms in (0.0005, 0.001, 0.01, 1.0, 50.0, 7000.0, 1e8):
            i = bucket_index(ms)
            assert ms <= bucket_upper_ms(i)
            assert bucket_upper_ms(i) >= prev
            prev = bucket_upper_ms(i)
        assert bucket_index(0.0) == 0

    def test_merge_equals_union(self):
        a, b, u = LogHistogram(), LogHistogram(), LogHistogram()
        for v in (0.1, 0.5, 2.0, 2.1):
            a.record(v)
            u.record(v)
        for v in (10.0, 80.0):
            b.record(v)
            u.record(v)
        a.merge(b)
        assert a.counts == u.counts
        assert a.n == u.n and a.max_ms == u.max_ms
        assert a.quantile(0.5) == u.quantile(0.5)

    def test_state_roundtrip_merges_cross_process(self):
        h = LogHistogram()
        for v in (0.2, 5.0, 5.0, 300.0):
            h.record(v)
        h2 = LogHistogram.from_state(
            json.loads(json.dumps(h.state())))
        assert h2.counts == h.counts
        assert h2.quantile(0.9) == h.quantile(0.9)
        # version mismatch -> empty, never silently wrong edges
        bad = h.state()
        bad["v"] = 999
        assert LogHistogram.from_state(bad).n == 0

    def test_snapshot_shape(self):
        h = LogHistogram()
        h.record(1.5)
        snap = h.snapshot()
        for k in ("n", "total_ms", "max_ms", "p50_ms", "p90_ms",
                  "p95_ms", "p99_ms"):
            assert k in snap, k
        assert LogHistogram().snapshot() == {
            "n": 0, "total_ms": 0.0, "max_ms": 0.0}


# ------------------------------------------------------------ flight recorder

class TestFlightRecorder:
    def test_ring_bounds_and_tail_order(self):
        r = FlightRecorder(capacity=4, slow_ms=1e9)
        for i in range(10):
            r.record(i, f"k{i}", 1.0, [["drain", 1.0]])
        assert len(r) == 4
        assert [rec["id"] for rec in r.tail()] == [6, 7, 8, 9]
        assert [rec["id"] for rec in r.tail(2)] == [8, 9]
        assert r.recorded == 10
        assert r.dropped == 6

    def test_explicit_slow_threshold_promotes(self):
        r = FlightRecorder(capacity=8, slow_ms=5.0)
        r.record(1, "fast", 2.0, [])
        r.record(2, "slow", 50.0, [])
        slow = r.slow_log()
        assert [s["id"] for s in slow] == [2]
        assert slow[0]["slow_threshold_ms"] == 5.0
        assert r.slow_promoted == 1

    def test_auto_threshold_arms_at_5x_live_p50(self):
        r = FlightRecorder(capacity=64)
        r.slow_ms = None               # force auto mode (ignore env)
        assert r.slow_threshold_ms() is None    # unarmed: no samples
        for _ in range(30):
            r.record(1, "k", 2.0, [])
        thr = r.slow_threshold_ms()
        assert thr == pytest.approx(5 * r.e2e.quantile(0.5))
        r.record(2, "outlier", thr * 3, [])
        assert [s["id"] for s in r.slow_log()] == [2]

    def test_slow_log_survives_ring_wrap(self):
        r = FlightRecorder(capacity=2, slow_ms=5.0)
        r.record(1, "slow", 99.0, [])
        for i in range(10, 20):
            r.record(i, "fast", 1.0, [])
        assert 1 not in [rec["id"] for rec in r.tail()]
        assert [s["id"] for s in r.slow_log()] == [1]


# ---------------------------------------------------------------- exposition

class TestPromExposition:
    def test_histogram_cumulative_buckets(self):
        h = LogHistogram()
        for v in (0.5, 0.5, 100.0):
            h.record(v)
        out = PromWriter()
        out.histogram("x_ms", h, {"span": "s"})
        text = out.render()
        assert "# TYPE x_ms histogram" in text
        lines = [ln for ln in text.splitlines() if "_bucket" in ln]
        counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
        assert counts == sorted(counts)          # cumulative
        assert counts[-1] == 3
        assert 'x_ms_count{span="s"} 3' in text
        assert '+Inf' in lines[-1]

    def test_summary_from_heartbeat_quantiles(self):
        snap = {"n": 7, "total_ms": 14.0, "p50_ms": 1.0,
                "p90_ms": 2.0, "p95_ms": 2.5, "p99_ms": 3.0,
                "max_ms": 3.3}
        w = PromWriter()
        w.summary("stage_ms", snap, {"stage": "commit"})
        text = w.render()
        assert '{stage="commit",quantile="0.5"} 1.0' in text
        assert '{stage="commit",quantile="0.99"} 3.0' in text
        assert 'stage_ms_count{stage="commit"} 7' in text

    def test_families_grouped_across_interleaved_emits(self):
        """Exposition format: every line of one metric family must be
        contiguous under a single TYPE header even when callers
        interleave families (per-daemon loops over shared names)."""
        w = PromWriter()
        w.metric("age_s", 1.0, {"daemon": "embedder"})
        w.summary("stage_ms", {"n": 1, "total_ms": 1.0, "p50_ms": 1.0},
                  {"daemon": "embedder"})
        w.metric("age_s", 2.0, {"daemon": "completer"})
        w.summary("stage_ms", {"n": 2, "total_ms": 2.0, "p50_ms": 1.0},
                  {"daemon": "completer"})
        lines = w.render().splitlines()
        fams = []
        for ln in lines:
            if ln.startswith("# TYPE"):
                fams.append(ln.split()[2])
        assert fams == ["age_s", "stage_ms"]      # one header each
        # no family's sample appears after another family started
        owner = [("age_s" if ln.startswith("age_s") else "stage_ms")
                 for ln in lines if not ln.startswith("#")]
        assert owner == sorted(owner, key=["age_s",
                                           "stage_ms"].index)

    def test_scalars_skip_non_numeric(self):
        w = PromWriter()
        w.scalars("lane", {"rows": 5, "note": "text",
                           "truncated": True})
        text = w.render()
        assert "lane_rows 5" in text
        assert "note" not in text and "truncated" not in text

    def test_tracer_render_prom(self):
        t = Tracer(enabled=True)
        with t.span("embed.commit"):
            pass
        text = t.render_prom(counters={"staged_lane": {
            "scatter_chunks": 3, "rows_padded": 128}})
        assert 'sptpu_span_ms_bucket{span="embed.commit"' in text
        assert "sptpu_staged_lane_scatter_chunks 3" in text
        assert "sptpu_staged_lane_rows_padded 128" in text

    def test_staged_lane_counters_shape(self):
        from libsplinter_tpu.ops.staged_lane import StagedLane

        lane = StagedLane.__new__(StagedLane)   # no device needed
        lane.full_uploads = 1
        lane.refreshes = 4
        lane.rows_staged = 100
        lane.rows_padded = 128
        lane.scatter_chunks = 2
        lane.ring_dispatches = 1
        lane.ring_chunks = 2
        lane.chunk_hist = {64: 2}
        lane.lane_slots_scanned = 130
        lane.journal_rows = 66
        lane.journal_fallbacks = 1
        lane.lane_audit_rows = 0
        c = lane.counters()
        assert c["chunks_bucket_64"] == 2
        assert c["ring_dispatches"] == 1
        assert c["lane_slots_scanned"] == 130 and c["journal_rows"] == 66
        assert c["journal_fallbacks"] == 1 and c["lane_audit_rows"] == 0
        assert all(isinstance(v, (int, float)) for v in c.values())


# --------------------------------------------------------- tracer quantiles

class TestTracerQuantiles:
    def test_prefix_filter_strips_names(self):
        t = Tracer(enabled=True)
        t.record("embed.drain", 1.0)
        t.record("embed.commit", 2.0)
        t.record("infer.render", 3.0)
        q = t.quantiles("embed.")
        assert set(q) == {"drain", "commit"}
        assert set(t.quantiles()) == {"embed.drain", "embed.commit",
                                      "infer.render"}

    def test_snapshot_keeps_legacy_keys(self):
        t = Tracer(enabled=True)
        with t.span("w"):
            pass
        s = t.snapshot()["w"]
        assert s["n"] == 1
        assert "total_ms" in s and "max_ms" in s and "p50_ms" in s


# ------------------------------------------------------- daemon integration

def _mkstore(tag, nslots=128, max_val=4096):
    name = f"/spt-obs-{tag}"
    Store.unlink(name)
    return name, Store.create(name, nslots=nslots, max_val=max_val,
                              vec_dim=8)


@pytest.fixture
def traced(monkeypatch):
    from libsplinter_tpu.utils.trace import tracer

    monkeypatch.setattr(tracer, "enabled", True)
    tracer.reset()
    yield tracer
    tracer.reset()


def test_embedder_flight_record_reconstructs_request(tmp_path, traced):
    """A client-stamped embed request yields one recorder entry whose
    event sequence is exactly PIPELINE_STAGES, the stamp is consumed,
    and the ring rides KEY_EMBED_TRACE after a heartbeat."""
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"fr-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("req", "trace me")
        st.set_type("req", T_VARTEXT)
        st.label_or("req", P.LBL_EMBED_REQ)
        st.bump("req")
        tid = P.stamp_trace(st, "req")
        assert tid is not None and (tid >> 24) > 0
        assert emb.run_once() == 1

        assert emb.recorder.recorded == 1
        rec = emb.recorder.tail(1)[0]
        assert rec["id"] == tid
        assert rec["key"] == "req"
        assert [e[0] for e in rec["events"]] == list(P.PIPELINE_STAGES)
        assert all(e[1] >= 0.0 for e in rec["events"])
        assert rec["wall_ms"] > 0
        # the stamp was consumed: a second drain records nothing new
        idx = st.find_index("req")
        with pytest.raises(KeyError):
            st.get(P.trace_stamp_key(idx))

        emb.publish_stats()
        ring = json.loads(st.get(P.KEY_EMBED_TRACE).rstrip(b"\0"))
        assert ring["trace"][0]["id"] == tid
        hb = json.loads(st.get(P.KEY_EMBED_STATS).rstrip(b"\0"))
        assert "quantiles" in hb and "recorder" in hb
        assert hb["recorder"]["recorded"] == 1
    finally:
        st.close()
        Store.unlink(name)


def test_embedder_slow_log_promotion(tmp_path, traced):
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"slow-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.recorder.slow_ms = 1e-4      # everything is "slow"
        emb.attach()
        st.set("s", "slow one")
        st.set_type("s", T_VARTEXT)
        st.label_or("s", P.LBL_EMBED_REQ)
        st.bump("s")
        P.stamp_trace(st, "s")
        emb.run_once()
        assert emb.recorder.slow_promoted == 1
        emb.publish_stats()
        hb = json.loads(st.get(P.KEY_EMBED_STATS).rstrip(b"\0"))
        assert hb["slow_log"][0]["key"] == "s"
        assert hb["slow_log"][0]["slow_threshold_ms"] == 1e-4
    finally:
        st.close()
        Store.unlink(name)


def test_untraced_requests_cost_no_records(tmp_path):
    """Tracing disabled: no stamps read, no records, stage acc off."""
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"off-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("k", "plain")
        st.set_type("k", T_VARTEXT)
        st.label_or("k", P.LBL_EMBED_REQ)
        st.bump("k")
        assert emb.run_once() == 1
        assert emb.recorder.recorded == 0
        assert emb._stage_acc is None
    finally:
        st.close()
        Store.unlink(name)


def test_stale_stamp_never_attributed_to_next_request(tmp_path,
                                                      traced):
    """A stamp that lands AFTER its request was serviced (the client
    lost the race) must not corrupt the NEXT request's flight record:
    the embedded epoch marks it stale and the daemon consumes it."""
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"stale-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("r", "first request")
        st.set_type("r", T_VARTEXT)
        st.label_or("r", P.LBL_EMBED_REQ)
        st.bump("r")
        assert emb.run_once() == 1    # serviced BEFORE any stamp
        stale_tid = P.stamp_trace(st, "r")   # client lost the race

        # next request on the same key, NOT stamped by anyone
        st.set("r", "second request")
        st.label_or("r", P.LBL_EMBED_REQ)
        st.bump("r")
        assert emb.run_once() == 1
        assert emb.recorder.recorded == 0, emb.recorder.tail()
        assert stale_tid not in [rec["id"] for rec in
                                 emb.recorder.tail()]
        # the stale stamp AND its discovery label were consumed, not
        # left to rot (a phantom LBL_TRACED would cost a dead lookup
        # on every future drain of this row)
        idx = st.find_index("r")
        with pytest.raises(KeyError):
            st.get(P.trace_stamp_key(idx))
        assert not st.labels("r") & P.LBL_TRACED
    finally:
        st.close()
        Store.unlink(name)


def test_completer_batched_drain_consumes_stamp(tmp_path, traced):
    """process_batch claims stamped requests through _prepare, which
    consumes the stamp — a later serial request on the same key must
    not inherit it as a phantom flight record."""
    import jax.numpy as jnp

    from libsplinter_tpu.engine.completer import Completer
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)

    name, st = _mkstore(f"bstamp-{tmp_path.name}")
    try:
        model = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                                buckets=(32,), temp=0.0, seed=1)
        comp = Completer(st, model=model, max_new_tokens=4,
                         flush_tokens=2, template="none", batch_cap=4)
        comp.attach()
        st.set("b", "batched prompt")
        st.label_or("b", P.LBL_INFER_REQ)
        P.stamp_trace(st, "b")
        st.bump("b")
        assert comp.run_once() == 1   # batched path: stamp consumed
        idx = st.find_index("b")
        with pytest.raises(KeyError):
            st.get(P.trace_stamp_key(idx))
        assert not st.labels("b") & P.LBL_TRACED
        assert comp.recorder.recorded == 0   # aggregated via spans only
    finally:
        st.close()
        Store.unlink(name)


def test_completer_flight_record_serial_path(tmp_path, traced):
    from libsplinter_tpu.engine.completer import Completer

    name, st = _mkstore(f"comp-{tmp_path.name}")
    try:
        comp = Completer(st, generate_fn=lambda p: iter([b"ok "]),
                         template="none")
        comp.attach()
        st.set("q", "hi")
        st.label_or("q", P.LBL_INFER_REQ)
        st.bump("q")
        tid = P.stamp_trace(st, "q")
        assert comp.run_once() == 1
        rec = comp.recorder.tail(1)[0]
        assert rec["id"] == tid
        assert [e[0] for e in rec["events"]] == list(P.INFER_STAGES)
        comp.publish_stats()
        hb = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert set(P.INFER_STAGES) <= set(hb["quantiles"])
        ring = json.loads(st.get(P.KEY_COMPLETE_TRACE).rstrip(b"\0"))
        assert ring["trace"][0]["id"] == tid
    finally:
        st.close()
        Store.unlink(name)


def test_orphan_stamp_shed_without_followup_request(tmp_path,
                                                    traced):
    """A stamp that lands AFTER its request was serviced, with no
    second request ever arriving on the key, must still be retired:
    the stamp slot's own write surfaces through the dirty mask and
    the daemon's discard path sheds it (no leaked __tr_<idx> slot,
    no permanent LBL_TRACED)."""
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"orph-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("o", "serviced before stamp")
        st.set_type("o", T_VARTEXT)
        st.label_or("o", P.LBL_EMBED_REQ)
        st.bump("o")
        assert emb.run_once() == 1
        P.stamp_trace(st, "o")        # too late: request already done
        emb.run_once()                # stamp slot in the dirty mask
        idx = st.find_index("o")
        with pytest.raises(KeyError):
            st.get(P.trace_stamp_key(idx))
        assert not st.labels("o") & P.LBL_TRACED
        assert emb.recorder.recorded == 0
    finally:
        st.close()
        Store.unlink(name)


def test_orphan_shed_leaves_pending_infer_stamp(tmp_path, traced):
    """The embedder's orphan shed must NOT retire a stamp whose
    request is still pending for the OTHER daemon (LBL_INFER_REQ)."""
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"xd-{tmp_path.name}")
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("q", "a completion request")
        st.label_or("q", P.LBL_INFER_REQ)
        P.stamp_trace(st, "q")
        st.bump("q")
        emb.run_once()                # embedder drains the dirty bits
        idx = st.find_index("q")
        assert st.get(P.trace_stamp_key(idx))   # stamp survives
        assert st.labels("q") & P.LBL_TRACED
    finally:
        st.close()
        Store.unlink(name)


def test_trace_ring_publish_shrinks_to_fit(tmp_path):
    """An oversized flight-recorder ring publishes a SHORTER tail
    (halving until it fits max_val), never an empty key: `spt trace
    tail` must keep working exactly when there is the most data."""
    name = f"/spt-obs-ring-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=1024, vec_dim=8)
    try:
        r = FlightRecorder(capacity=64, slow_ms=1e9)
        for i in range(40):
            r.record((7 << 24) | i, f"key/{i}", 12.345,
                     [[s, 1.234] for s in P.PIPELINE_STAGES])
        P.publish_trace_ring(st, "__ring", r)
        snap = json.loads(st.get("__ring").rstrip(b"\0"))
        got = snap["trace"]
        assert 1 <= len(got) < 32
        assert got[-1]["id"] == (7 << 24) | 39   # newest survive
    finally:
        st.close()
        Store.unlink(name)


# ------------------------------------------------------------------- CLI

def test_cli_metrics_and_trace_tail(tmp_path, traced, monkeypatch,
                                    capsys):
    from libsplinter_tpu.cli.main import main
    from libsplinter_tpu.engine.embedder import Embedder

    name, st = _mkstore(f"cli-{tmp_path.name}")
    monkeypatch.setenv("SPTPU_DEFAULT_STORE", name)
    monkeypatch.delenv("SPTPU_NS_PREFIX", raising=False)
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 8), np.float32), max_ctx=64)
        emb.attach()
        st.set("k", "metric me")
        st.set_type("k", T_VARTEXT)
        st.label_or("k", P.LBL_EMBED_REQ)
        st.bump("k")
        tid = P.stamp_trace(st, "k")
        emb.run_once()
        emb.publish_stats()

        assert main(["metrics"]) == 0
        out = capsys.readouterr().out
        assert "# TYPE sptpu_store_parse_failures counter" in out
        assert "sptpu_embedder_embedded 1" in out
        assert 'sptpu_stage_ms{daemon="embedder",stage="commit"' in out
        assert "sptpu_heartbeat_age_seconds" in out

        assert main(["trace", "tail", "4"]) == 0
        out = capsys.readouterr().out
        assert f"id={tid:#x}" in out
        assert "drain=" in out and "commit=" in out

        # empty-store UX: no recorder ring is a message, not an error
        st2_name, st2 = _mkstore(f"cli2-{tmp_path.name}")
        st2.close()
        monkeypatch.setenv("SPTPU_DEFAULT_STORE", st2_name)
        assert main(["trace", "tail"]) == 0
        assert "no traced requests" in capsys.readouterr().out
        Store.unlink(st2_name)
    finally:
        st.close()
        Store.unlink(name)
