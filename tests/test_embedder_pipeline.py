"""The commit pipeline: wake->commit must never park on a device
round-trip it could overlap (the old fused commit waited on the device
synchronously inside the wake handler).

Three tiers:
  - CommitPipeline unit tests with hand-rolled futures (completion-order
    resolution, back-pressure, blocking accounting);
  - Embedder integration with the stub encoder (probe lane routing,
    pipeline counters on real drains, heartbeat surface);
  - a slow-marked CPU micro-bench running the event-driven daemon loop
    and asserting the wake handler performed ZERO blocking device
    fetches across a multi-wave load (the regression guard that needs
    no TPU hardware).
"""
from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

import libsplinter_tpu as sp
from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.embedder import (
    CommitPipeline, Embedder, EmbedderStats,
)


def fake_encoder(texts):
    out = np.zeros((len(texts), 32), np.float32)
    for i, t in enumerate(texts):
        out[i, 0] = len(t)
        out[i, 2] = 1.0
    return out


def _request(store, key, text):
    store.set(key, text)
    store.set_type(key, sp.T_VARTEXT)
    store.label_or(key, P.LBL_EMBED_REQ)
    store.bump(key)


class FakePending:
    """A controllable encode future: flips ready on command."""

    def __init__(self, tag, *, ready):
        self.tag = tag
        self.ready = ready
        self.n = 1

    def is_ready(self):
        return self.ready

    def materialize(self):
        return np.full((1, 4), float(self.tag), np.float32)


class TestCommitPipeline:
    def _pipe(self, depth=4):
        committed = []
        stats = EmbedderStats()

        def commit(rows, epochs, vecs):
            committed.append(rows)
            return len(rows)

        return CommitPipeline(commit, stats, depth), committed, stats

    def test_completion_order_beats_dispatch_order(self):
        pipe, committed, stats = self._pipe()
        slow = FakePending(1, ready=False)
        fast = FakePending(2, ready=True)
        pipe.push([1], [2], slow)
        pipe.push([2], [2], fast)     # finished first: commits first
        assert committed == [[2]]
        slow.ready = True
        assert pipe.drain_ready() == 1
        assert committed == [[2], [1]]
        assert stats.ready_commits == 2
        assert stats.blocking_waits == 0
        assert stats.futures_resolved == 2

    def test_backpressure_blocks_only_past_depth(self):
        pipe, committed, stats = self._pipe(depth=1)
        a = FakePending(1, ready=False)
        b = FakePending(2, ready=False)
        c = FakePending(3, ready=False)
        pipe.push([1], [2], a)
        assert committed == []        # within depth: nothing forced
        pipe.push([2], [2], b)        # depth exceeded: oldest forced
        assert committed == [[1]]
        assert stats.blocking_waits == 1
        pipe.push([3], [2], c)
        assert committed == [[1], [2]]
        pipe.flush()
        assert committed == [[1], [2], [3]]
        assert stats.futures_resolved == 3
        assert stats.inflight_peak == 2

    def test_flush_takes_ready_futures_first(self):
        pipe, committed, _ = self._pipe()
        a = FakePending(1, ready=False)
        b = FakePending(2, ready=True)
        pipe._q.append((["a"], [0], a, time.perf_counter(), 0.0))
        pipe._q.append((["b"], [0], b, time.perf_counter(), 0.0))
        pipe.flush()
        assert committed == [["b"], ["a"]]

    def test_overlap_accounting(self):
        pipe, _, stats = self._pipe()
        p = FakePending(1, ready=True)
        pipe.push([1], [2], p)
        pipe.flush()
        # the future dwelled in flight (however briefly) and the host
        # never blocked: all device time was overlapped
        assert stats.overlap_ms > 0
        assert stats.overlap_ratio() > 0.0


class TestEmbedderPipeline:
    def test_multi_batch_drain_counters(self, store):
        emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64,
                       batch_cap=4)
        emb.attach()
        for i in range(32):
            _request(store, f"k{i}", f"text number {i}")
        assert emb.run_once() == 32
        # 32 rows / batch_cap 4 = 8 dispatched futures, all resolved
        assert emb.stats.futures_dispatched == 8
        assert emb.stats.futures_resolved == 8
        # stub futures are host memory: the wake handler must have
        # done ZERO blocking device fetches
        assert emb.stats.blocking_waits == 0
        assert emb.stats.ready_commits == 8
        assert emb.stats.overlap_ratio() > 0.0
        assert emb.stats.device_wait_ms >= 0.0

    def test_probe_lane_routes_small_drains(self, store):
        emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64)
        emb.attach()
        _request(store, "probe", "one hot key")
        assert emb.run_once() == 1
        assert emb.stats.probe_lane_hits == 1
        for i in range(20):            # > probe_batch_max: windowed lane
            _request(store, f"bulk{i}", f"bulk text {i}")
        assert emb.run_once() == 20
        assert emb.stats.probe_lane_hits == 1

    def test_probe_lane_threshold_configurable(self, store):
        emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64,
                       probe_batch_max=0)
        emb.attach()
        _request(store, "probe", "never short-circuited")
        assert emb.run_once() == 1
        assert emb.stats.probe_lane_hits == 0

    def test_probe_lane_still_guards_context(self, store):
        emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64)
        emb.attach()
        _request(store, "huge", "word " * 100)
        assert emb.run_once() == 0
        assert emb.stats.ctx_exceeded == 1
        assert store.labels("huge") & P.LBL_CTX_EXCEEDED

    def test_heartbeat_carries_pipeline_stats(self, store):
        emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64)
        emb.attach()
        for i in range(12):
            _request(store, f"h{i}", f"heartbeat text {i}")
        emb.run_once()
        emb.publish_stats()
        payload = json.loads(store.get(P.KEY_EMBED_STATS))
        for field in ("futures_dispatched", "futures_resolved",
                      "blocking_waits", "inflight_peak",
                      "overlap_ratio", "device_wait_ms", "overlap_ms",
                      "commit_host_ms", "probe_lane_hits"):
            assert field in payload, field
        assert payload["overlap_ratio"] > 0.0
        assert payload["blocking_waits"] == 0


@pytest.mark.slow
def test_pipeline_microbench_no_blocking_fetch_in_wake_handler(store):
    """CPU micro-bench regression guard: the event-driven daemon under
    a multi-wave load (bulk drains + single-key latency probes) must
    resolve every commit without one blocking device fetch inside the
    wake handler, and must report real overlap — catches a reintroduced
    inline device_get without TPU hardware."""
    emb = Embedder(store, encoder_fn=fake_encoder, max_ctx=64,
                   batch_cap=8)
    emb.attach()
    t = threading.Thread(
        target=emb.run,
        kwargs=dict(idle_timeout_ms=20, stop_after=15.0,
                    sweep_interval_s=3600.0),
        daemon=True)
    t.start()
    try:
        time.sleep(0.05)
        client = Store.open(store.name)
        lat = []
        try:
            # three bulk waves with latency probes in between — the
            # shape of a set->vector p50 loop, shrunk for CI
            for wave in range(3):
                for i in range(40):
                    _request(client, f"w{wave}/k{i}",
                             f"wave {wave} text {i}")
                key = f"probe/{wave}"
                t1 = time.perf_counter()
                _request(client, key, "latency probe text")
                idx = client.find_index(key)
                deadline = t1 + 10.0
                while client.labels_at(idx) & P.LBL_EMBED_REQ:
                    assert time.perf_counter() < deadline, \
                        "probe starved: wake path wedged"
                    time.sleep(0.0005)
                lat.append((time.perf_counter() - t1) * 1e3)
        finally:
            client.close()
    finally:
        emb.stop()
        t.join(timeout=5.0)
    assert emb.stats.embedded >= 123          # 3 x (40 + 1)
    assert emb.stats.futures_resolved == emb.stats.futures_dispatched
    # THE invariant: stub futures are always ready, so any blocking
    # wait means someone re-introduced a synchronous device fetch on
    # the wake->commit path
    assert emb.stats.blocking_waits == 0
    assert emb.stats.overlap_ratio() > 0.0
    assert emb.stats.probe_lane_hits >= 1     # probes short-circuited
    assert len(lat) == 3
