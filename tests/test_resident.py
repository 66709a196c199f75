"""Resident device loop + K-deep dispatch overlap (engine/resident.py;
`make dispatch-check` runs this file).

The PR-7 contract: the hot lanes stop paying one runtime dispatch per
drain, and BOTH mechanisms are byte-exact against the per-call paths —
  - embed vectors: resident ring vs per-call encode (fixed seed);
  - search results: K-deep select/commit vs fetch-in-dispatch-order;
  - decode tokens: K-deep chunk window vs the sync chunk cadence;
  - staged-lane refreshes: ring scatter vs per-chunk scatter —
plus compile-count pinning (ring occupancy is an OPERAND: no drain
geometry may recompile the resident program), the heartbeat gauges
(`ring_occupancy`, `inflight_depth`, `resident_iterations`), and the
SPTPU_FAULT sites for a ring stalled or crashed mid-dispatch.
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
from libsplinter_tpu.engine.resident import (CallbackWindow,
                                             InflightWindow, RingResult,
                                             pending_ready)
from libsplinter_tpu.models import default_tokenizer
from libsplinter_tpu.models.encoder import EmbeddingModel, EncoderConfig


class FakeFuture:
    def __init__(self, tag, *, ready):
        self.tag = tag
        self.ready = ready

    def is_ready(self):
        return self.ready


# --------------------------------------------------- InflightWindow

class TestInflightWindow:
    def test_pending_ready_contract(self):
        assert pending_ready(None)
        assert pending_ready(np.zeros(3))
        assert pending_ready(b"host bytes")
        assert pending_ready((np.zeros(2), None))
        assert pending_ready(FakeFuture(0, ready=True))
        assert not pending_ready(FakeFuture(0, ready=False))
        assert not pending_ready((FakeFuture(0, ready=True),
                                  FakeFuture(1, ready=False)))

    def test_completion_order_beats_dispatch_order(self):
        done = []
        win = CallbackWindow(4, lambda p, pend, ready: done.append(p))
        slow = FakeFuture(1, ready=False)
        fast = FakeFuture(2, ready=True)
        win.push(1, slow)
        win.push(2, fast)              # finished first: resolves first
        assert done == [2]
        slow.ready = True
        assert win.drain_ready() == 1
        assert done == [2, 1]
        assert win.ready_resolves == 2
        assert win.blocking_resolves == 0

    def test_depth_bound_forces_oldest(self):
        done = []
        win = CallbackWindow(1, lambda p, pend, ready: done.append(
            (p, ready)))
        a, b, c = (FakeFuture(i, ready=False) for i in range(3))
        win.push("a", a)
        assert done == []              # within depth: nothing forced
        win.push("b", b)               # depth exceeded: oldest forced
        assert done == [("a", False)]
        win.push("c", c)
        assert done == [("a", False), ("b", False)]
        win.flush()
        assert [p for p, _ in done] == ["a", "b", "c"]
        assert win.inflight_peak == 2
        assert win.blocking_resolves == 3

    def test_flush_takes_ready_first(self):
        done = []
        win = CallbackWindow(4, lambda p, pend, ready: done.append(p))
        win.push_entry(("a", FakeFuture(0, ready=False)))
        win.push_entry(("b", FakeFuture(1, ready=True)))
        win.flush()
        assert done == ["b", "a"]

    def test_base_class_is_abstract(self):
        win = InflightWindow(2)
        with pytest.raises(NotImplementedError):
            win.push_entry(("x", None))


# ------------------------------------------------ resident ring (model)

@pytest.fixture(scope="module")
def ring_model():
    cfg = EncoderConfig.tiny(out_dim=32)
    return EmbeddingModel(cfg, buckets=(16, 32))


class TestEncoderRing:
    def test_ring_matches_per_call_byte_exact(self, ring_model):
        m = ring_model
        rng = np.random.default_rng(3)
        depth, cap, b = 4, 8, 16
        ids = rng.integers(0, m.cfg.vocab_size,
                           (depth, cap, b)).astype(np.int32)
        lens = rng.integers(1, b + 1, (depth, cap)).astype(np.int32)
        per = [m.encode_ids_async(ids[i], lens[i]).materialize()
               for i in range(depth)]
        ring = m.encode_ring_async(ids, lens, depth)
        for i in range(depth):
            got = ring.slot(i, cap).materialize()
            np.testing.assert_array_equal(got, per[i])

    def test_occupancy_is_an_operand_not_a_shape(self, ring_model):
        """Every occupancy 1..depth reuses ONE compiled program — a
        drain's ring fill level must never jit on the wake path."""
        m = ring_model
        depth, cap, b = 4, 8, 16
        ids = np.ones((depth, cap, b), np.int32)
        lens = np.full((depth, cap), b, np.int32)
        m.encode_ring_async(ids, lens, depth).materialize_host()
        c0 = m.compile_count()
        for occ in (1, 2, 3, 4):
            m.encode_ring_async(ids, lens, occ).materialize_host()
        assert m.compile_count() == c0

    def test_out_buffer_pool_recycles(self, ring_model):
        m = ring_model
        depth, cap, b = 4, 8, 16
        ids = np.ones((depth, cap, b), np.int32)
        lens = np.full((depth, cap), b, np.int32)
        r1 = m.encode_ring_async(ids, lens, 2)
        pool = m._ring_pool[(depth, cap)]
        held = len(pool)
        r1.materialize_host()          # host copy landed: buffer back
        assert len(pool) == held + 1
        r2 = m.encode_ring_async(ids, lens, 2)   # consumes (donates) it
        assert len(pool) == held
        r2.materialize_host()

    def test_ring_slot_wire_upcast_matches_per_call(self):
        """int8-wire rings must convert slot views exactly like
        PendingEmbeddings (the shared _wire_to_f32)."""
        cfg = EncoderConfig.tiny(out_dim=32)
        m8 = EmbeddingModel(cfg, buckets=(16,), fetch_dtype="int8")
        rng = np.random.default_rng(5)
        ids = rng.integers(0, cfg.vocab_size, (2, 4, 16)).astype(np.int32)
        lens = rng.integers(1, 17, (2, 4)).astype(np.int32)
        per = [m8.encode_ids_async(ids[i], lens[i]).materialize()
               for i in range(2)]
        ring = m8.encode_ring_async(ids, lens, 2)
        for i in range(2):
            np.testing.assert_array_equal(
                ring.slot(i, 4).materialize(), per[i])

    def test_failed_fetch_caches_error_and_skips_pool(self):
        """A ring whose device fetch fails must poison NEITHER the
        sibling slots' error reporting (the real error re-raises, no
        None deref) NOR the donation pool (the buffer is dropped)."""
        class BoomArray:
            def is_ready(self):
                return True

            def __array__(self, *a, **kw):
                raise RuntimeError("device fell over")

        pool: list = []
        ring = RingResult(BoomArray(), 2, release=pool.append)
        with pytest.raises(RuntimeError, match="device fell over"):
            ring.slot(0, 1).materialize()
        with pytest.raises(RuntimeError, match="device fell over"):
            ring.slot(1, 1).materialize()     # cached, not a None deref
        assert ring.is_ready()                # forcing will not block
        assert pool == []                     # poisoned buffer dropped

        fell_back = []
        ring2 = RingResult(BoomArray(), 2, release=pool.append,
                           retry=lambda i, n: fell_back.append(i)
                           or np.zeros((n, 4), np.float32))
        out = ring2.slot(1, 3).materialize()
        assert out.shape == (3, 4)
        assert fell_back == [1]               # per-slot fallback armed

    def test_n_valid_bounds_checked(self, ring_model):
        ids = np.ones((2, 4, 16), np.int32)
        lens = np.full((2, 4), 16, np.int32)
        with pytest.raises(ValueError):
            ring_model.encode_ring_async(ids, lens, 0)
        with pytest.raises(ValueError):
            ring_model.encode_ring_async(ids, lens, 3)


# -------------------------------------------------- embedder lane

def _arm_embed(store, n, word="text"):
    for i in range(n):
        store.set(f"k{i}", f"{word} number {i} " * (1 + i % 4))
        store.set_type(f"k{i}", sp.T_VARTEXT)
        store.label_or(f"k{i}", P.LBL_EMBED_REQ)
        store.bump(f"k{i}")


def _embed_run(tmp_path, tag, n=30, **emb_kw):
    from libsplinter_tpu.engine.embedder import Embedder

    name = f"/spt-res-{tag}-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=256, max_val=1024, vec_dim=32)
    try:
        cfg = EncoderConfig.tiny(out_dim=32)
        model = EmbeddingModel(cfg, buckets=(16, 32))
        emb = Embedder(st, model=model,
                       tokenizer=default_tokenizer(cfg.vocab_size),
                       max_ctx=128, **emb_kw)
        emb.attach()
        _arm_embed(st, n)
        served = emb.run_once()
        vecs = np.stack([st.vec_get(f"k{i}") for i in range(n)])
        return served, vecs, emb
    finally:
        st.close()
        Store.unlink(name)


class TestEmbedderRing:
    def test_ring_vectors_byte_identical_to_per_call(self, tmp_path):
        """THE parity bar: resident-ring drains commit byte-identical
        vectors to per-call drains at a fixed weight seed."""
        n0, v0, e0 = _embed_run(tmp_path, "percall", batch_cap=4,
                                ring_depth=0)
        n1, v1, e1 = _embed_run(tmp_path, "ring", batch_cap=4,
                                ring_depth=4)
        assert n0 == n1 == 30
        assert e0.stats.ring_dispatches == 0
        assert e1.stats.ring_dispatches >= 1
        assert e1.stats.resident_iterations >= 2
        assert e1.stats.ring_occupancy_peak >= 2
        np.testing.assert_array_equal(v0, v1)

    def test_ring_disengages_below_two_full_batches(self, tmp_path):
        """Tiny drains (the latency-probe lane) must never pay ring
        assembly: one batch -> the per-call path."""
        n, _, emb = _embed_run(tmp_path, "small", n=3, batch_cap=4,
                               ring_depth=4)
        assert n == 3
        assert emb.stats.ring_dispatches == 0

    def test_warmup_ring_pins_compile_count(self, tmp_path):
        """After warmup_ring, drains at ANY ring occupancy (different
        drain sizes across join/finish cycles) never recompile."""
        from libsplinter_tpu.engine.embedder import Embedder

        name = f"/spt-res-warm-{tmp_path.name}"
        Store.unlink(name)
        st = Store.create(name, nslots=256, max_val=1024, vec_dim=32)
        try:
            cfg = EncoderConfig.tiny(out_dim=32)
            model = EmbeddingModel(cfg, buckets=(16, 32))
            emb = Embedder(st, model=model,
                           tokenizer=default_tokenizer(cfg.vocab_size),
                           max_ctx=128, batch_cap=4, ring_depth=4)
            emb.attach()
            model.warmup(batch_sizes=(1, 2, 4))
            model.warmup_ring(emb.ring_depth, emb.batch_cap)
            c0 = model.compile_count()
            assert c0 > 0
            for n in (9, 17, 30):      # different ring occupancies
                _arm_embed(st, n)
                assert emb.run_once() == n
                # finish cycle: re-arm the same keys next round
            assert model.compile_count() == c0, \
                "resident program recompiled across drain cycles"
            assert emb.stats.ring_dispatches >= 2
        finally:
            st.close()
            Store.unlink(name)

    def test_heartbeat_carries_ring_gauges(self, store):
        from libsplinter_tpu.engine.embedder import Embedder

        emb = Embedder(store, encoder_fn=lambda ts: np.zeros(
            (len(ts), store.vec_dim), np.float32), max_ctx=64,
            ring_depth=4, inflight_depth=3)
        emb.attach()
        emb.publish_stats()
        snap = json.loads(store.get(P.KEY_EMBED_STATS).rstrip(b"\0"))
        disp = snap["dispatch"]
        for field in ("ring_dispatches", "resident_iterations",
                      "ring_occupancy", "ring_occupancy_peak",
                      "ring_faults", "ring_depth", "inflight_depth"):
            assert field in disp, field
        assert disp["ring_depth"] == 4
        assert disp["inflight_depth"] == 3


# -------------------------------------------------- searcher lane

def _search_round(store, sr, keys, qs):
    for key, q in zip(keys, qs):
        store.set(key, json.dumps({"k": 5}))
        store.vec_set(key, q)
        store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
        store.bump(key)
    served = sr.run_once()
    out = {}
    for key in keys:
        out[key] = json.loads(store.get(
            P.search_result_key(store.find_index(key))).rstrip(b"\0"))
    return served, out


class TestSearcherOverlap:
    def _fill(self, store, n=64, seed=11):
        rng = np.random.default_rng(seed)
        vecs = rng.normal(size=(n, store.vec_dim)).astype(np.float32)
        for i in range(n):
            store.set(f"doc/{i}", f"text {i}")
            store.vec_set(f"doc/{i}", vecs[i])
        return rng

    def test_overlap_results_identical_to_in_order(self, store):
        """Search results must not depend on inflight_depth — the
        window only reorders HOST work, never device math."""
        from libsplinter_tpu.engine.searcher import Searcher

        rng = self._fill(store)
        qs = rng.normal(size=(24, store.vec_dim)).astype(np.float32)
        keys = [f"__sqtmp_{1000 + i}" for i in range(24)]
        results = {}
        for depth in (1, 4):
            sr = Searcher(store, inflight_depth=depth)
            sr.attach()
            served, out = _search_round(store, sr, keys, qs)
            assert served == 24
            results[depth] = out
            if depth > 1:
                assert sr.stats.inflight_peak >= 1
            for key in keys:
                store.unset(P.search_result_key(store.find_index(key)))
        # strip per-commit wall timestamps + the round's slot epochs
        # (each round rewrites the request slots) before comparing
        for out in results.values():
            for rec in out.values():
                rec.pop("ts", None)
                rec.pop("e", None)
        assert results[1] == results[4]

    def test_window_bounds_inflight(self, store):
        """Many QB chunks in one drain: the window never holds more
        than inflight_depth un-awaited batch dispatches."""
        from libsplinter_tpu.engine.searcher import Searcher

        rng = self._fill(store)
        # 3 bloom groups x 1 chunk each -> 3 dispatches in one drain
        sr = Searcher(store, inflight_depth=2)
        sr.attach()
        keys, qs = [], []
        for g, bloom in enumerate((0, P.LBL_CHUNK, P.LBL_META)):
            for i in range(4):
                key = f"__sqtmp_{2000 + g * 8 + i}"
                store.set(key, json.dumps({"k": 3, "bloom": bloom}))
                store.vec_set(key, rng.normal(
                    size=store.vec_dim).astype(np.float32))
                store.label_or(key, P.LBL_SEARCH_REQ)
                store.bump(key)
                keys.append(key)
        for i in range(8):             # give the bloom groups members
            store.label_or(f"doc/{i}", P.LBL_CHUNK)
            store.label_or(f"doc/{i + 8}", P.LBL_META)
        served = sr.run_once()
        assert served == len(keys)
        assert sr.stats.dispatches >= 3
        # peak counts the moment AFTER a push, before the overflow
        # resolve — depth+1 max (CommitPipeline's pinned semantics)
        assert 1 <= sr.stats.inflight_peak <= 3
        # a dispatch is ready at its select or blocks there: the
        # heartbeat carries the blocked ones beside `dispatches`
        assert 0 <= sr.stats.blocking_selects <= sr.stats.dispatches

    def test_heartbeat_carries_inflight_gauge(self, store_2k):
        from libsplinter_tpu.engine.searcher import Searcher

        store = store_2k          # room for the whole heartbeat
        sr = Searcher(store, inflight_depth=3)
        sr.attach()
        sr.publish_stats()
        snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert snap["inflight_depth"] == 3
        assert "inflight_peak" in snap
        # the staged-lane ring counters ride the lane section
        assert "ring_dispatches" in snap["lane"]


# -------------------------------------------------- completer lane

class TestCompleterOverlap:
    def _serve(self, tmp_path, tag, depth, n_req=3):
        import jax.numpy as jnp

        from libsplinter_tpu.engine.completer import Completer
        from libsplinter_tpu.models.decoder import (CompletionModel,
                                                    DecoderConfig)

        name = f"/spt-res-dec-{tag}-{tmp_path.name}"
        Store.unlink(name)
        st = Store.create(name, nslots=128, max_val=4096, vec_dim=8)
        try:
            model = CompletionModel(
                DecoderConfig.tiny(dtype=jnp.float32), buckets=(32,),
                temp=0.0, seed=1)
            comp = Completer(st, model=model, max_new_tokens=10,
                             flush_tokens=4, template="none",
                             batch_cap=4, page_size=16,
                             inflight_depth=depth)
            comp.attach()
            for i in range(n_req):
                st.set(f"q/{i}", f"say {i} things")
                st.label_or(f"q/{i}", P.LBL_INFER_REQ)
                st.bump(f"q/{i}")
            th = threading.Thread(
                target=comp.run_continuous,
                kwargs=dict(idle_timeout_ms=20, stop_after=60.0),
                daemon=True)
            th.start()
            deadline = time.time() + 50
            keys = [f"q/{i}" for i in range(n_req)]
            while time.time() < deadline:
                if all(st.labels(k) & P.LBL_READY for k in keys):
                    break
                time.sleep(0.05)
            comp.stop()
            th.join(timeout=10)
            assert all(st.labels(k) & P.LBL_READY for k in keys), \
                comp.stats
            out = b"|".join(st.get(k).rstrip(b"\0") for k in keys)
            assert comp._paged_cache.used_pages == 0, "pages leaked"
            return out, comp
        finally:
            st.close()
            Store.unlink(name)

    def test_k_deep_decode_byte_identical_to_sync(self, tmp_path):
        """THE decode parity bar: greedy completions through the
        K-deep chunk window == the collect-every-chunk cadence."""
        sync_out, sync_comp = self._serve(tmp_path, "sync", depth=1)
        deep_out, deep_comp = self._serve(tmp_path, "deep", depth=3)
        assert sync_out == deep_out
        assert deep_comp.stats.inflight_peak >= 2
        assert sync_comp.stats.inflight_peak <= 1

    def test_heartbeat_carries_inflight_gauge(self, store):
        from libsplinter_tpu.engine.completer import Completer

        comp = Completer(store, generate_fn=lambda p: iter([b"x"]),
                         template="none", inflight_depth=4)
        comp.attach()
        comp.publish_stats()
        snap = json.loads(store.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert snap["inflight_depth"] == 4
        assert "inflight_peak" in snap


# -------------------------------------------------- metrics surface

@pytest.mark.obs
def test_metrics_exposition_renders_overlap_gauges(tmp_path):
    """The ISSUE-7 obs satellite: `spt metrics` renders the ring /
    in-flight gauges as sptpu_<lane>_* so saturation of the overlap
    window is scrapeable in production."""
    import contextlib
    import io
    import os
    import uuid

    from libsplinter_tpu.engine.embedder import Embedder
    from libsplinter_tpu.engine.searcher import Searcher

    name = f"/spt-res-prom-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    st = Store.create(name, nslots=256, max_val=4096, vec_dim=32)
    try:
        emb = Embedder(st, encoder_fn=lambda ts: np.zeros(
            (len(ts), 32), np.float32), max_ctx=64, ring_depth=8)
        emb.attach()
        emb.publish_stats()
        sr = Searcher(st, inflight_depth=2)
        sr.attach()
        sr.publish_stats()

        from libsplinter_tpu.cli.main import COMMANDS, Session
        ses = Session(name)
        try:
            fn, _, _ = COMMANDS["metrics"]
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                fn(ses, [])
            out = buf.getvalue()
            for needle in ("sptpu_embedder_ring_depth 8",
                           "sptpu_embedder_ring_dispatches",
                           "sptpu_embedder_resident_iterations",
                           "sptpu_embedder_ring_occupancy",
                           "sptpu_embedder_inflight_depth",
                           "sptpu_searcher_inflight_depth 2",
                           "sptpu_searcher_inflight_peak",
                           "sptpu_searcher_lane_ring_dispatches"):
                assert needle in out, f"{needle} missing:\n{out[:2000]}"
        finally:
            ses.close()
    finally:
        st.close()
        Store.unlink(name)


# -------------------------------------------------- staged-lane ring

class TestStagedLaneRing:
    def test_ring_scatter_refresh_exact(self, store):
        """A refresh whose plan repeats buckets goes through the ring
        scatter and must land the exact same lane as per-chunk."""
        from libsplinter_tpu.ops.staged_lane import StagedLane

        rng = np.random.default_rng(9)
        n = 200
        v0 = rng.normal(size=(n, store.vec_dim)).astype(np.float32)
        for i in range(n):
            store.set(f"d/{i}", "x")
            store.vec_set(f"d/{i}", v0[i])
        idxs = np.array([store.find_index(f"d/{i}") for i in range(n)])

        lane = StagedLane(store)
        lane.refresh()
        v1 = v0 + 1.0
        for i in range(n):
            store.vec_set(f"d/{i}", v1[i])
        arr = np.asarray(lane.refresh())
        # 200 dirty -> plan [64, 64, 64, 64(tail)]: same-bucket chunks
        # coalesce into ring dispatches
        assert lane.ring_dispatches >= 1
        assert lane.ring_chunks >= 2
        for i in range(n):
            np.testing.assert_array_equal(arr[idxs[i]], v1[i])
        norms = np.asarray(lane.norms)[idxs]
        np.testing.assert_allclose(norms, np.linalg.norm(v1, axis=1),
                                   rtol=1e-6)

    def test_buffered_chunks_lost_mid_refresh_stay_dirty(
            self, store, monkeypatch):
        """A refresh that dies with chunks still buffered (or whose
        scatter raises) must NOT have marked those rows staged — the
        next refresh re-stages them instead of serving stale rows
        forever."""
        from libsplinter_tpu.ops import staged_lane as sl_mod
        from libsplinter_tpu.ops.staged_lane import StagedLane

        rng = np.random.default_rng(13)
        n = 200
        v0 = rng.normal(size=(n, store.vec_dim)).astype(np.float32)
        for i in range(n):
            store.set(f"d/{i}", "x")
            store.vec_set(f"d/{i}", v0[i])
        lane = StagedLane(store)
        lane.refresh()
        v1 = v0 + 1.0
        for i in range(n):
            store.vec_set(f"d/{i}", v1[i])

        import libsplinter_tpu.ops.similarity as sim
        real = sim.scatter_rows_with_norms_ring
        calls = {"n": 0}

        def boom(*a, **kw):
            calls["n"] += 1
            raise RuntimeError("scatter died")

        monkeypatch.setattr(sim, "scatter_rows_with_norms_ring", boom)
        with pytest.raises(RuntimeError):
            lane.refresh()
        assert calls["n"] == 1
        monkeypatch.setattr(sim, "scatter_rows_with_norms_ring", real)
        arr = np.asarray(lane.refresh())      # everything re-staged
        idxs = np.array([store.find_index(f"d/{i}") for i in range(n)])
        for i in range(n):
            np.testing.assert_array_equal(arr[idxs[i]], v1[i])

    def test_ring_disabled_matches(self, store):
        from libsplinter_tpu.ops.staged_lane import StagedLane

        rng = np.random.default_rng(10)
        n = 200
        for i in range(n):
            store.set(f"d/{i}", "x")
            store.vec_set(
                f"d/{i}",
                rng.normal(size=store.vec_dim).astype(np.float32))
        lane = StagedLane(store)
        lane.ring_depth = 1
        lane.refresh()
        v1 = rng.normal(size=(n, store.vec_dim)).astype(np.float32)
        for i in range(n):
            store.vec_set(f"d/{i}", v1[i])
        arr = np.asarray(lane.refresh())
        assert lane.ring_dispatches == 0
        idxs = np.array([store.find_index(f"d/{i}") for i in range(n)])
        for i in range(0, n, 17):
            np.testing.assert_array_equal(arr[idxs[i]], v1[i])


# -------------------------------------------------- fault sites

class TestRingFaults:
    def test_ring_dispatch_raise_degrades_to_per_call(self, tmp_path):
        """An injected failure at resident.ring_dispatch costs only
        the ring: its chunks fall back to the per-call programs and
        every request still embeds, byte-identically."""
        from libsplinter_tpu.utils import faults

        n0, v0, _ = _embed_run(tmp_path, "flt-ref", batch_cap=4,
                               ring_depth=0)
        faults.arm("resident.ring_dispatch:raise@1")
        try:
            n, vecs, emb = _embed_run(tmp_path, "flt", batch_cap=4,
                                      ring_depth=4)
        finally:
            faults.disarm()
        assert n == n0 == 30
        assert emb.stats.ring_faults >= 1
        assert emb.stats.drain_faults == 0
        np.testing.assert_array_equal(vecs, v0)

    def test_ring_collect_raise_falls_back_per_slot(self, tmp_path):
        """A collect-time failure (where async dispatch surfaces
        device errors) re-encodes the affected slot on the per-call
        programs: no batch fails, no cap degrades, vectors stay
        byte-identical."""
        from libsplinter_tpu.utils import faults

        n0, v0, _ = _embed_run(tmp_path, "col-ref", batch_cap=4,
                               ring_depth=0)
        faults.arm("resident.ring_collect:raise@1")
        try:
            n, vecs, emb = _embed_run(tmp_path, "col", batch_cap=4,
                                      ring_depth=4)
        finally:
            faults.disarm()
        assert n == n0 == 30
        assert emb.stats.ring_faults >= 1
        assert emb.stats.batch_faults == 0    # no cap degradation
        np.testing.assert_array_equal(vecs, v0)

    def test_ring_collect_stall_absorbed(self, tmp_path):
        """A stall mid-collect (device hiccup) slows the drain but
        loses nothing."""
        from libsplinter_tpu.utils import faults

        faults.arm("resident.ring_collect:stall50@1")
        try:
            n, vecs, emb = _embed_run(tmp_path, "stall", batch_cap=4,
                                      ring_depth=4)
        finally:
            faults.disarm()
        assert n == 30
        assert emb.stats.ring_dispatches >= 1

    @pytest.mark.chaos
    def test_ring_dispatch_crash_recovers(self, tmp_path):
        """Chaos: a child daemon crashed INSIDE a resident-ring drain
        (os._exit mid-dispatch) strands nothing — a restarted daemon
        converges every request."""
        import os
        import subprocess
        import sys

        from libsplinter_tpu.utils.faults import CRASH_EXIT_CODE

        name = f"/spt-res-crash-{tmp_path.name}"
        Store.unlink(name)
        st = Store.create(name, nslots=256, max_val=1024, vec_dim=32)
        try:
            _arm_embed(st, 20)
            child = os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "chaos_child.py")
            env = dict(os.environ)
            env["SPTPU_FAULT"] = "resident.ring_dispatch:crash@1"
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable, child, "embedder_ring", name],
                env=env, capture_output=True, text=True, timeout=300)
            assert out.returncode == CRASH_EXIT_CODE, out.stderr[-800:]

            from libsplinter_tpu.engine.embedder import Embedder
            cfg = EncoderConfig.tiny(out_dim=32)
            model = EmbeddingModel(cfg, buckets=(16, 32))
            emb = Embedder(st, model=model,
                           tokenizer=default_tokenizer(cfg.vocab_size),
                           max_ctx=128, batch_cap=4, ring_depth=4)
            emb.attach()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                emb.run_once()
                if not st.enumerate_indices(P.LBL_EMBED_REQ):
                    break
            assert not st.enumerate_indices(P.LBL_EMBED_REQ)
            for i in range(20):
                assert np.abs(st.vec_get(f"k{i}")).max() > 0, i
            assert emb.stats.ring_dispatches >= 1
        finally:
            st.close()
            Store.unlink(name)

    @pytest.mark.chaos
    @pytest.mark.slow
    def test_supervisor_restarts_lane_wedged_in_ring(self, tmp_path,
                                                     monkeypatch):
        """PR-4 supervisor acceptance for PR 7: an embedder lane
        WEDGED inside a resident program (45 s stall at the ring
        collect — a hung device, not a crash) goes heartbeat-stale,
        the supervisor SIGKILLs + restarts it (fault stripped from
        generation 2), and every pending request still embeds — no
        stranded rows."""
        import os
        import uuid

        from libsplinter_tpu.engine.supervisor import Supervisor

        name = f"/spt-res-sup-{os.getpid()}-{uuid.uuid4().hex[:8]}"
        Store.unlink(name)
        st = Store.create(name, nslots=128, max_val=2048, vec_dim=16)
        try:
            monkeypatch.setenv("SPTPU_FAULT",
                               "resident.ring_collect:stall45000@1")
            monkeypatch.setenv("SPTPU_FORCE_CPU", "1")
            sup = Supervisor(
                name, lanes=("embedder",), store=st,
                lane_args={"embedder": ["--batch-cap", "2",
                                        "--ring-depth", "2",
                                        "--max-ctx", "64"]},
                backoff_base_ms=100, backoff_max_ms=2000,
                breaker_threshold=8, breaker_window_s=300,
                heartbeat_timeout_s=20, startup_grace_s=300,
                healthy_after_s=5)
            t = threading.Thread(target=sup.run,
                                 kwargs={"poll_interval_s": 0.2,
                                         "stop_after": 600.0})
            t.start()
            try:
                # wait for the lane's FIRST heartbeat so the hang
                # detector has a baseline, then submit the work the
                # armed stall will wedge
                deadline = time.monotonic() + 400
                while time.monotonic() < deadline:
                    if P.heartbeat_live(st, P.KEY_EMBED_STATS,
                                        max_age_s=30):
                        break
                    time.sleep(0.5)
                assert P.heartbeat_live(st, P.KEY_EMBED_STATS,
                                        max_age_s=30), "lane never up"
                _arm_embed(st, 8)
                deadline = time.monotonic() + 400
                while time.monotonic() < deadline:
                    if not st.enumerate_indices(P.LBL_EMBED_REQ):
                        break
                    time.sleep(0.5)
                assert not st.enumerate_indices(P.LBL_EMBED_REQ), \
                    sup.lanes["embedder"].snapshot()
                for i in range(8):
                    assert np.abs(st.vec_get(f"k{i}")).max() > 0, i
                ln = sup.lanes["embedder"]
                assert ln.restarts >= 1, \
                    "wedged lane was never restarted"
            finally:
                sup.stop()
                t.join()
                sup.shutdown()
        finally:
            st.close()
            Store.unlink(name)
