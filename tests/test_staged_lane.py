"""StagedLane: device-resident vector-lane cache with O(dirty) re-staging.

A second search after k dirty writes must
transfer O(k) rows, not the whole lane (the round-1 CLI re-uploaded the
full (nslots, dim) matrix per query) — and the r05 dirty-refresh cliff:
large dirty sets chunk through the fixed bucket set (padding waste <=
2x, no fresh jit compiles), instead of padding to one giant scatter."""
from __future__ import annotations

import os
import uuid

import numpy as np
import pytest

from libsplinter_tpu.ops import StagedLane
from libsplinter_tpu.ops.staged_lane import _UPDATE_BUCKETS, _chunk_plan


def _fill(store, n, dim, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, dim)).astype(np.float32)
    for i in range(n):
        store.set(f"doc/{i}", f"text {i}")
        store.vec_set(f"doc/{i}", vecs[i])
    return vecs


class TestNativePrimitives:
    def test_epochs_snapshot(self, store):
        e0 = store.epochs()
        assert e0.shape == (store.nslots,)
        assert e0.dtype == np.uint64
        store.set("k", b"v")
        e1 = store.epochs()
        idx = store.find_index("k")
        assert e1[idx] > e0[idx]
        assert (np.delete(e1, idx) == np.delete(e0, idx)).all()

    def test_vec_gather(self, store):
        dim = store.vec_dim
        v = np.arange(dim, dtype=np.float32)
        store.set("k", b"v")
        store.vec_set("k", v)
        idx = store.find_index("k")
        empty = next(i for i in range(store.nslots)
                     if store.epoch_at(i) == 0)
        vecs, eps = store.vec_gather(np.array([idx, empty]))
        assert eps[0] == store.epoch_at(idx) and eps[0] % 2 == 0
        np.testing.assert_array_equal(vecs[0], v)
        # a stable never-written slot reports epoch 0 (NOT the torn
        # sentinel) and a zeros row
        assert eps[1] == 0 and eps[1] != store.GATHER_TORN
        assert (vecs[1] == 0).all()

    def test_vec_gather_out_of_range(self, store):
        vecs, eps = store.vec_gather(np.array([store.nslots + 5]))
        assert eps[0] == store.GATHER_TORN
        assert (vecs[0] == 0).all()


class TestStagedLane:
    def test_full_upload_then_incremental(self, store):
        dim = store.vec_dim
        vecs = _fill(store, 20, dim)
        lane = StagedLane(store)
        arr = np.asarray(lane.refresh())
        assert lane.full_uploads == 1 and lane.rows_staged == 0
        for i in range(20):
            np.testing.assert_array_equal(
                arr[store.find_index(f"doc/{i}")], vecs[i])

        # no writes -> zero transfer
        lane.refresh()
        assert lane.full_uploads == 1 and lane.rows_staged == 0

        # k dirty writes -> exactly k rows re-staged
        k = 3
        new = np.ones((k, dim), np.float32) * 7.5
        for i in range(k):
            store.vec_set(f"doc/{i}", new[i])
        arr = np.asarray(lane.refresh())
        assert lane.full_uploads == 1
        assert lane.rows_staged == k
        for i in range(k):
            np.testing.assert_array_equal(
                arr[store.find_index(f"doc/{i}")], new[i])
        # untouched rows still correct
        np.testing.assert_array_equal(
            arr[store.find_index("doc/10")], vecs[10])

    def test_text_write_restages_row(self, store):
        _fill(store, 4, store.vec_dim)
        lane = StagedLane(store)
        lane.refresh()
        store.set("doc/2", "new text bumps the epoch")
        np.asarray(lane.refresh())
        assert lane.rows_staged == 1

    def test_unset_zeroes_staged_row(self, store):
        _fill(store, 4, store.vec_dim)
        lane = StagedLane(store)
        idx = store.find_index("doc/1")
        lane.refresh()
        store.unset("doc/1")
        arr = np.asarray(lane.refresh())
        assert (arr[idx] == 0).all()

    def test_large_update_bucket_padding(self, store):
        n = 150  # > first bucket (64), exercises padding with dup rows
        vecs = _fill(store, n, store.vec_dim)
        lane = StagedLane(store)
        lane.refresh()
        for i in range(n):
            store.vec_set(f"doc/{i}", vecs[i] + 1.0)
        arr = np.asarray(lane.refresh())
        assert lane.rows_staged == n
        for i in (0, 77, n - 1):
            np.testing.assert_array_equal(
                arr[store.find_index(f"doc/{i}")], vecs[i] + 1.0)

    def test_topk_reads_cache(self, store):
        dim = store.vec_dim
        _fill(store, 16, dim, seed=3)
        target = np.zeros(dim, np.float32)
        target[0] = 1.0
        store.set("hit", "the needle")
        store.vec_set("hit", target)
        lane = StagedLane(store)
        scores, idxs = lane.topk(target, k=1)
        assert idxs[0] == store.find_index("hit")
        assert scores[0] == pytest.approx(1.0, abs=1e-5)

    def test_invalidate_forces_full_upload(self, store):
        _fill(store, 4, store.vec_dim)
        lane = StagedLane(store)
        lane.refresh()
        lane.invalidate()
        lane.refresh()
        assert lane.full_uploads == 2


class TestNorms:
    def test_norms_track_incremental_updates(self, store):
        """Row norms are lane-static data maintained at stage time
        (full pass on upload, O(dirty) on refresh) — they must match a
        fresh host computation after incremental writes."""
        dim = store.vec_dim
        _fill(store, 12, dim)
        lane = StagedLane(store)
        lane.refresh()
        want = np.linalg.norm(np.array(store.vectors), axis=1)
        np.testing.assert_allclose(np.asarray(lane.norms), want,
                                   rtol=1e-6)
        store.vec_set("doc/4", np.full(dim, 3.0, np.float32))
        lane.refresh()
        assert lane.full_uploads == 1          # incremental, not re-upload
        want = np.linalg.norm(np.array(store.vectors), axis=1)
        np.testing.assert_allclose(np.asarray(lane.norms), want,
                                   rtol=1e-6)

    def test_topk_uses_staged_norms(self, store):
        dim = store.vec_dim
        _fill(store, 8, dim)
        lane = StagedLane(store)
        slot = store.find_index("doc/3")
        q = np.array(store.vectors)[slot]
        s, i = lane.topk(q, k=1)
        assert int(i[0]) == slot
        assert s[0] == pytest.approx(1.0, abs=1e-5)


class TestChunkPlan:
    """The refresh chunking policy is pure math — pin it exactly."""

    def test_headline_decompositions(self):
        assert _chunk_plan(128) == [64, 64]
        assert _chunk_plan(8192) == [4096, 4096]
        assert _chunk_plan(40000) == [32768, 4096, 4096]

    def test_small_counts_take_one_bucket(self):
        assert _chunk_plan(1) == [64]
        assert _chunk_plan(64) == [64]
        assert _chunk_plan(500) == [512]

    @pytest.mark.parametrize("n", [1, 63, 64, 65, 100, 128, 511, 513,
                                   4095, 4097, 8192, 32768, 32769,
                                   40000, 100000])
    def test_invariants(self, n):
        plan = _chunk_plan(n)
        # every chunk is a precompiled bucket shape
        assert all(b in _UPDATE_BUCKETS for b in plan)
        total = sum(plan)
        assert total >= n                     # covers every dirty row
        # padding waste bounded at 2x (floor of one smallest bucket)
        assert total <= max(2 * n, _UPDATE_BUCKETS[0])


class TestLargeDirtyRefresh:
    """The r05 cliff regression guard: refresh cost must be
    piecewise-linear in the dirty count (chunk count x bucket size),
    with full_uploads pinned at 1 and zero jit compiles beyond the
    fixed bucket set."""

    DIM = 8

    def _big_store(self, k):
        from libsplinter_tpu import Store

        nslots = 1
        while nslots < k * 2:
            nslots *= 2
        nslots = max(nslots, 256)
        name = f"/spt-biglane-{os.getpid()}-{uuid.uuid4().hex[:8]}"
        return Store.create(name, nslots=nslots, max_val=64,
                            vec_dim=self.DIM), name

    @pytest.mark.parametrize("k", [128, 8192, 40000])
    def test_accounting_and_correctness(self, k):
        from libsplinter_tpu import Store
        from libsplinter_tpu.ops.similarity import _scatter_rows_norms_fn

        st, name = self._big_store(k)
        try:
            rng = np.random.default_rng(7)
            v0 = rng.normal(size=(k, self.DIM)).astype(np.float32)
            for i in range(k):
                st.set(f"d/{i}", "x")
            idxs = np.array([st.find_index(f"d/{i}") for i in range(k)])
            for i in range(k):
                st.vec_set_at(int(idxs[i]), v0[i])

            lane = StagedLane(st)
            lane.refresh()
            assert lane.full_uploads == 1 and lane.rows_staged == 0

            fn = _scatter_rows_norms_fn()
            compiles_before = (fn._cache_size()
                               if hasattr(fn, "_cache_size") else None)

            # dirty every row, refresh, and audit the chunk accounting
            v1 = v0 + 1.0
            for i in range(k):
                st.vec_set(f"d/{i}", v1[i])
            arr = np.asarray(lane.refresh())

            assert lane.full_uploads == 1          # never a re-upload
            assert lane.rows_staged == k           # every real row moved
            plan = _chunk_plan(k)
            assert lane.scatter_chunks == len(plan)
            assert lane.rows_padded == sum(plan)
            # piecewise-linear: chunk count x bucket size never pads
            # past 2x the dirty count (the old single-scatter path
            # padded 8,192 -> 32,768: the 53x wall-time cliff)
            assert lane.rows_padded <= max(2 * k, 64)
            assert all(b in _UPDATE_BUCKETS
                       for b in lane.chunk_hist)

            # value correctness on a sample (full compare at small k)
            sample = (np.arange(k) if k <= 1024
                      else rng.choice(k, size=512, replace=False))
            for i in sample:
                np.testing.assert_array_equal(arr[idxs[i]], v1[i])
            # norms maintained O(dirty), exact
            want = np.linalg.norm(v1[sample], axis=1)
            got = np.asarray(lane.norms)[idxs[sample]]
            np.testing.assert_allclose(got, want, rtol=1e-6)

            # no fresh compile beyond the fixed bucket set: a second
            # same-size refresh reuses every program (compile-count
            # hook = the jitted scatter's signature cache)
            if compiles_before is not None:
                # the big refresh compiled exactly one program per
                # DISTINCT bucket in its plan (the jit cache is global
                # across stores/dtypes, so assert the delta) ...
                delta = fn._cache_size() - compiles_before
                assert delta <= len(set(plan))
                # ... and a same-size re-refresh compiles NOTHING: no
                # dirty count ever costs a fresh program at steady state
                steady = fn._cache_size()
                for i in range(k):
                    st.vec_set(f"d/{i}", v0[i])
                lane.refresh()
                assert fn._cache_size() == steady
                assert lane.rows_staged == 2 * k
        finally:
            st.close()
            Store.unlink(name)


class TestWireDtype:
    """f16 staging wire: half the staged bytes, device lane still f32,
    quantization bounded and ranking-preserved; norms stay exact (they
    come from the f32 data, not the wire copy)."""

    def test_f16_upload_and_refresh(self, store):
        dim = store.vec_dim
        vecs = _fill(store, 20, dim)
        lane = StagedLane(store, wire="f16")
        arr = np.asarray(lane.refresh())
        assert arr.dtype == np.float32        # device lane stays f32
        for i in range(20):
            np.testing.assert_allclose(
                arr[store.find_index(f"doc/{i}")], vecs[i],
                atol=2e-3, rtol=2e-3)         # f16 quantization bound
        # incremental path quantizes the same way
        new = np.full(dim, 0.123456, np.float32)
        store.vec_set("doc/0", new)
        arr = np.asarray(lane.refresh())
        assert lane.full_uploads == 1 and lane.rows_staged == 1
        np.testing.assert_allclose(
            arr[store.find_index("doc/0")], new, atol=2e-3, rtol=2e-3)
        # norms are computed from the exact f32 gather, not the wire
        want = np.linalg.norm(np.array(store.vectors), axis=1)
        np.testing.assert_allclose(np.asarray(lane.norms), want,
                                   rtol=1e-6)

    def test_f16_ranking_matches_f32(self, store):
        dim = store.vec_dim
        _fill(store, 32, dim, seed=5)
        f32 = StagedLane(store)
        f16 = StagedLane(store, wire="f16")
        q = np.array(store.vectors)[store.find_index("doc/7")]
        _, i32 = f32.topk(q, k=5)
        _, i16 = f16.topk(q, k=5)
        assert int(i16[0]) == int(i32[0]) == store.find_index("doc/7")
        assert set(map(int, i16)) == set(map(int, i32))

    def test_wire_rejects_unknown(self, store):
        with pytest.raises(ValueError):
            StagedLane(store, wire="int8")

    def test_wire_env_default(self, store, monkeypatch):
        monkeypatch.setenv("SPTPU_LANE_WIRE", "f16")
        lane = StagedLane(store)
        assert lane.wire == "f16"
        monkeypatch.delenv("SPTPU_LANE_WIRE")
        assert StagedLane(store).wire == "f32"


# ---------------------------------------------------- the change journal

def _mutate(store, rng, keys, dim):
    """One seeded write of the kinds a deployment makes: new rows,
    rewritten vectors and texts, unsets, re-sets of an unset key,
    typed and appended values."""
    op = rng.integers(0, 6)
    key = keys[rng.integers(0, len(keys))]
    try:
        if op == 0 or key not in store:
            store.set(key, f"text {rng.integers(1 << 30)}")
            store.vec_set(key, rng.normal(size=dim).astype(np.float32))
        elif op == 1:
            store.vec_set(key, rng.normal(size=dim).astype(np.float32))
        elif op == 2:
            store.unset(key)
        elif op == 3:
            store.append(key, "+")
        elif op == 4:
            store.set(key, "rewritten")
        else:
            store.stamp(key)
    except OSError:
        pass                       # value full: the lock still moved


def _assert_lane_is_the_store(store, lane):
    """The journal-fed lane against the full scan it replaced: device
    rows, staged epochs and the liveness rule, row for row."""
    from libsplinter_tpu.engine import protocol as P

    np.testing.assert_array_equal(np.asarray(lane.array),
                                  np.array(store.vectors))
    np.testing.assert_array_equal(lane.staged_epochs(), store.epochs())
    np.testing.assert_array_equal(
        P.live_epochs(lane.staged_epochs()).astype(np.float32),
        P.candidate_mask(store))
    np.testing.assert_allclose(
        np.asarray(lane.norms),
        np.linalg.norm(np.array(store.vectors), axis=1), rtol=1e-6)


class TestChangeJournal:
    def test_store_names_the_rows_that_moved(self, store):
        c0 = store.journal_head()
        rows, c1, complete = store.changed_since(c0)
        assert complete and rows.size == 0 and c1 == c0
        store.set("a", "1")
        store.set("b", "2")
        store.vec_set("a", np.ones(store.vec_dim, np.float32))
        store.label_or("a", 1)        # moves no epoch; journaled too
        store.label_clear("a", 1)              # a clear is not
        rows, c2, complete = store.changed_since(c1)
        assert complete and c2 == c1 + 4
        assert sorted(rows) == sorted({store.find_index("a"),
                                       store.find_index("b")})
        np.testing.assert_array_equal(
            store.epochs_at(rows), store.epochs()[rows])
        # every consumer has its own cursor: the first still reads all
        assert store.changed_since(c0)[0].size == 2

    @pytest.mark.parametrize("seed", range(6))
    def test_journal_fed_lane_equals_full_scan(self, store, seed):
        """After any seeded sequence of writes, unsets and re-sets
        the journal-fed lane equals the store row for row, without a
        scan: every refresh after the upload reads the journal."""
        rng = np.random.default_rng(seed)
        dim = store.vec_dim
        keys = [f"doc/{i}" for i in range(40)]
        _fill(store, 24, dim, seed=seed)
        lane = StagedLane(store)
        lane.refresh()
        scanned0 = lane.lane_slots_scanned
        for _ in range(8):
            for _ in range(int(rng.integers(1, 30))):
                _mutate(store, rng, keys, dim)
            lane.refresh()
            _assert_lane_is_the_store(store, lane)
        assert lane.full_uploads == 1
        assert lane.journal_fallbacks == 1       # the first attach
        assert lane.journal_rows > 0
        # it looked at the rows the journal named, nothing like a scan
        assert lane.lane_slots_scanned - scanned0 <= 8 * 40
        assert lane.audit() == 0 and lane.lane_audit_rows == 0
        _assert_lane_is_the_store(store, lane)

    def test_retrain_moves_the_epoch_backward_and_is_restaged(self, store):
        dim = store.vec_dim
        _fill(store, 4, dim)
        lane = StagedLane(store)
        lane.refresh()
        for _ in range(3):
            store.vec_set("doc/1", np.full(dim, 2.0, np.float32))
        lane.refresh()
        row = store.find_index("doc/1")
        assert lane.staged_epochs([row])[0] > 4
        store.retrain("doc/1")                 # epoch 4, vector zeroed
        assert store.epoch_at(row) == 4
        arr = np.asarray(lane.refresh())
        assert (arr[row] == 0).all()
        assert lane.journal_fallbacks == 1 and lane.audit() == 0
        _assert_lane_is_the_store(store, lane)

    def test_unchanged_store_reads_nothing(self, store):
        _fill(store, 8, store.vec_dim)
        lane = StagedLane(store)
        lane.refresh()
        before = lane.counters()
        lane.refresh()
        after = lane.counters()
        for k in ("lane_slots_scanned", "journal_rows",
                  "journal_fallbacks", "rows_staged"):
            assert after[k] == before[k], k

    @pytest.mark.parametrize("how", ["first_attach", "overflow",
                                     "invalidate"])
    def test_fallback_scans_and_counts(self, store, how):
        """No cursor yet, or a cursor the writers lapped: the refresh
        is the full comparison, counted, and the lane is right."""
        from libsplinter_tpu import _native as N

        dim = store.vec_dim
        _fill(store, 12, dim)
        lane = StagedLane(store)
        lane.refresh()
        assert lane.journal_fallbacks == 1 and lane.full_uploads == 1
        assert lane.lane_slots_scanned == 2 * store.nslots
        if how == "overflow":
            store.vec_set("doc/3", np.full(dim, 2.0, np.float32))
            for i in range(N.JOURNAL_CAP + 1):
                store.stamp("doc/5")
            lane.refresh()
            assert lane.journal_fallbacks == 2
            assert lane.full_uploads == 1        # a scan, not an upload
            assert lane.lane_slots_scanned == 3 * store.nslots
            assert lane.rows_staged == 2
        elif how == "invalidate":
            store.vec_set("doc/3", np.full(dim, 2.0, np.float32))
            lane.invalidate()
            lane.refresh()
            assert lane.journal_fallbacks == 2 and lane.full_uploads == 2
        _assert_lane_is_the_store(store, lane)
        # and the journal is the way again
        store.vec_set("doc/4", np.full(dim, 3.0, np.float32))
        n = lane.journal_fallbacks
        lane.refresh()
        assert lane.journal_fallbacks == n
        _assert_lane_is_the_store(store, lane)
        assert lane.audit() == 0

    @pytest.mark.parametrize("where", ["odd_at_compare",
                                       "torn_at_gather"])
    def test_row_seen_mid_write_is_staged_when_its_writer_is_done(
            self, store, where):
        """A writer's record is appended while its slot is still odd.
        A lane that looks then finds the row mid-write; the record is
        behind its cursor for good, so the row has to be REMEMBERED:
        marked not live, and staged by the next refresh although the
        journal has nothing new to say."""
        from libsplinter_tpu import Store
        from libsplinter_tpu.engine import protocol as P

        dim = store.vec_dim
        _fill(store, 6, dim)
        row = store.find_index("doc/2")

        class MidWrite:
            """The store as a reader sees it while doc/2's writer
            holds the seqlock (once)."""

            def __init__(self, st):
                self._st, self.armed = st, False

            def __getattr__(self, name):
                return getattr(self._st, name)

            def epochs_at(self, rows):
                eps = self._st.epochs_at(rows)
                if self.armed and where == "odd_at_compare":
                    self.armed = False
                    eps[np.asarray(rows) == row] -= np.uint64(1)
                return eps

            def vec_gather_iter(self, rows, chunks):
                for off, vecs, eps in self._st.vec_gather_iter(
                        rows, chunks):
                    if self.armed and where == "torn_at_gather":
                        self.armed = False
                        eps[np.asarray(rows)[off: off + eps.size]
                            == row] = Store.GATHER_TORN
                    yield off, vecs, eps

        view = MidWrite(store)
        lane = StagedLane(view)
        lane.refresh()
        new = np.full(dim, 4.5, np.float32)
        store.vec_set("doc/2", new)
        store.vec_set("doc/4", new)
        view.armed = True
        arr = np.asarray(lane.refresh())
        # the sibling landed; the row mid-write did not, and reads as
        # not live (the candidate_mask rule on an odd staged epoch)
        np.testing.assert_array_equal(arr[store.find_index("doc/4")], new)
        assert not (arr[row] == new).all()
        assert lane.staged_epochs([row])[0] % 2 == 1
        assert not P.live_epochs(lane.staged_epochs([row]))[0]
        assert lane.rows_staged == 1
        # nothing new in the journal, and still it is staged now
        head = store.journal_head()
        arr = np.asarray(lane.refresh())
        assert store.journal_head() == head
        np.testing.assert_array_equal(arr[row], new)
        assert lane.rows_staged == 2
        assert lane.journal_fallbacks == 1       # never a scan
        assert lane.audit() == 0
        _assert_lane_is_the_store(store, lane)

    @pytest.mark.parametrize("after_snapshot", [1, 2])
    def test_write_during_the_upload_is_found_afterwards(
            self, store, after_snapshot):
        """The cursor is taken BEFORE the upload's first epoch
        snapshot: a row written while the lane streams up (after the
        first snapshot: the copy is suspect; after the second: only
        the journal knows) is found by the next refresh."""
        dim = store.vec_dim
        _fill(store, 6, dim)
        new = np.full(dim, 9.0, np.float32)

        class WritesDuringUpload:
            def __init__(self, st):
                self._st, self.snapshots = st, 0

            def __getattr__(self, name):
                return getattr(self._st, name)

            def epochs(self):
                eps = self._st.epochs()
                self.snapshots += 1
                if self.snapshots == after_snapshot:
                    self._st.vec_set("doc/1", new)
                return eps

        lane = StagedLane(WritesDuringUpload(store))
        lane.refresh()
        assert lane.full_uploads == 1
        arr = np.asarray(lane.refresh())
        np.testing.assert_array_equal(arr[store.find_index("doc/1")], new)
        assert lane.journal_fallbacks == 1       # the upload alone
        _assert_lane_is_the_store(store, lane)

    def test_audit_finds_what_the_journal_dropped(self, store):
        """The audit is the one thing that can tell a missed record
        from a right answer: drop a row from the journal's answer and
        the next audit finds it, stages it and counts it."""
        dim = store.vec_dim
        _fill(store, 6, dim)
        row = store.find_index("doc/3")

        class LosesARecord:
            def __init__(self, st):
                self._st = st

            def __getattr__(self, name):
                return getattr(self._st, name)

            def changed_since(self, cursor):
                rows, cur, complete = self._st.changed_since(cursor)
                return rows[rows != row], cur, complete

        lane = StagedLane(LosesARecord(store))
        lane.refresh()
        new = np.full(dim, 6.0, np.float32)
        store.vec_set("doc/3", new)
        store.vec_set("doc/5", new)
        arr = np.asarray(lane.refresh())
        assert not (arr[row] == new).all()       # the stale device row
        scanned = lane.lane_slots_scanned
        assert lane.audit() == 1
        assert lane.lane_audit_rows == 1
        assert lane.lane_slots_scanned == scanned     # a drain's, not its
        np.testing.assert_array_equal(np.asarray(lane.array)[row], new)
        assert lane.audit() == 0 and lane.lane_audit_rows == 1

    def test_64_dirty_rows_of_100k_slots(self):
        """What the journal buys: a refresh after 64 writes on a
        100,000-slot store looks at 64 epochs, not 100,000, and the
        audit finds nothing it missed."""
        from libsplinter_tpu import Store

        name = f"/spt-j100k-{os.getpid()}-{uuid.uuid4().hex[:8]}"
        st = Store.create(name, nslots=100_000, max_val=64, vec_dim=8)
        try:
            rng = np.random.default_rng(3)
            for i in range(256):
                st.set(f"d/{i}", "x")
                st.vec_set(f"d/{i}", rng.normal(size=8).astype(np.float32))
            lane = StagedLane(st)
            lane.refresh()
            base = lane.lane_slots_scanned
            for i in range(64):
                st.vec_set(f"d/{i}", rng.normal(size=8).astype(np.float32))
            lane.refresh()
            assert lane.rows_staged == 64
            assert lane.lane_slots_scanned - base == 64 < 1000
            assert lane.journal_rows == 64
            assert lane.audit() == 0
            assert lane.lane_slots_scanned - base == 64
            np.testing.assert_array_equal(np.asarray(lane.array),
                                          np.array(st.vectors))
        finally:
            st.close()
            Store.unlink(name)

    def test_take_examined_names_the_rows_to_patch(self, store):
        _fill(store, 6, store.vec_dim)
        lane = StagedLane(store)
        lane.refresh()
        assert lane.take_examined() is None      # an upload: every row
        assert lane.take_examined().size == 0
        store.vec_set("doc/1", np.ones(store.vec_dim, np.float32))
        store.unset("doc/2")
        i1 = store.find_index("doc/1")
        lane.refresh()
        got = lane.take_examined()
        assert i1 in got and got.size == 2
        assert lane.take_examined().size == 0
        # nobody takes: the rows stay distinct, so it cannot outgrow
        # the lane
        for _ in range(5):
            store.stamp("doc/1")
            store.stamp("doc/3")
            lane.refresh()
        assert lane.take_examined().size == 2
