"""Behavioral spec of the core store — parity with the reference TAP suite
(splinter_test.c:85-533, SURVEY.md §4): CRUD, size query, list, mop modes,
snapshots, named types + BIGUINT promotion, timestamps, embedding
round-trip, integer ops (carry/borrow, EPROTOTYPE), tandem keys, purge,
system keys, append, persistence."""
import os
import uuid

import numpy as np
import pytest

import libsplinter_tpu as sp
from libsplinter_tpu import Eagain, Store


def test_create_open_close(tmp_path):
    name = f"/spt-lc-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    st = Store.create(name, nslots=32, max_val=128, vec_dim=0)
    st.set("a", b"1")
    st2 = Store.open(name)
    assert st2.get("a") == b"1"
    st2.close()
    st.close()
    Store.unlink(name)


def test_create_is_exclusive(tmp_path):
    """Re-creating a live store must fail (it would corrupt peers);
    overwrite=True unlinks first."""
    name = f"/spt-excl-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    st = Store.create(name, nslots=32, max_val=128, vec_dim=0)
    with pytest.raises(OSError):
        Store.create(name, nslots=32, max_val=128, vec_dim=0)
    st.close()
    st2 = Store.create(name, nslots=32, max_val=128, vec_dim=0,
                       overwrite=True)
    st2.close()
    Store.unlink(name)


def test_open_missing_fails():
    with pytest.raises(OSError):
        Store.open(f"/spt-missing-{uuid.uuid4().hex}")


def test_persistent_file_backed(tmp_path):
    path = str(tmp_path / "store.spt")
    st = Store.create(path, nslots=32, max_val=128, vec_dim=8,
                      persistent=True)
    st.set("persist", b"across-restart")
    st.vec_set("persist", np.arange(8, dtype=np.float32))
    st.close()
    # the store IS the checkpoint: a fresh open sees everything
    st2 = Store.open(path, persistent=True)
    assert st2.get("persist") == b"across-restart"
    assert st2.vec_get("persist")[7] == 7.0
    st2.close()
    Store.unlink(path, persistent=True)


def test_set_get_roundtrip(store):
    store.set("k", b"hello world")
    assert store.get("k") == b"hello world"
    store.set("k", b"overwrite")
    assert store.get("k") == b"overwrite"


def test_get_missing_raises(store):
    with pytest.raises(KeyError):
        store.get("nope")


def test_size_query(store):
    store.set("k", b"12345")
    assert store.value_len("k") == 5


def test_value_too_large(store):
    with pytest.raises(OSError):
        store.set("big", b"x" * (store.max_val + 1))


def test_key_too_long(store):
    with pytest.raises(OSError):
        store.set("k" * 200, b"x")


def test_unset(store):
    store.set("gone", b"x")
    store.unset("gone")
    with pytest.raises(KeyError):
        store.get("gone")
    with pytest.raises(KeyError):
        store.unset("gone")


def test_unset_then_reuse_slot(store):
    """Tombstones keep probe chains intact and get reused."""
    for i in range(50):
        store.set(f"k{i}", b"v")
    for i in range(0, 50, 2):
        store.unset(f"k{i}")
    for i in range(0, 50, 2):  # re-insert over tombstones
        store.set(f"k{i}", b"w")
    for i in range(50):
        assert store.get(f"k{i}") in (b"v", b"w")


def test_list(store):
    keys = {f"key-{i}" for i in range(10)}
    for k in keys:
        store.set(k, b"x")
    assert set(store.list()) >= keys
    assert set(iter(store)) >= keys


def _fill_tombstoned(st):
    for i in range(40):
        st.set(f"__sr_{i}", b"r")
        st.set(f"doc/{i}", b"d")
    for i in range(0, 40, 3):
        st.unset(f"__sr_{i}")          # tombstones inside the runs
    for i in range(0, 40, 6):
        st.set(f"__sr_{i}", b"again")  # re-set: a slot reused


def _fill_whole_key(st):
    for k in ("__sp_", "__sp_7", "__sp", "__sq_7", "_", "x__sp_7"):
        st.set(k, b"v")


def _fill_long_key(st):
    st.set("k" * 127, b"longest")
    st.set("k" * 126 + "j", b"differs in the last byte")
    st.set("k" * 64, b"half")


def _fill_loaded(st):
    n = int(st.nslots * 0.91)
    for i in range(n):
        st.set(f"vec/{i}", b"")
    for i in range(0, n, 4099):
        st.set(f"__sr_{i}", b"r")
    for i in range(0, n, 8191):
        st.unset(f"vec/{i}")


_PREFIX_CASES = {
    # case: (nslots, fill, prefixes)
    "tombstoned_and_reset": (256, _fill_tombstoned,
                             ("__sr_", "__sr_1", "doc/", "doc/3")),
    "empty_prefix": (256, _fill_tombstoned, ("",)),
    "no_key_that_long": (256, _fill_tombstoned,
                         ("__sr_" + "9" * 40, "q" * 127, "q" * 300)),
    "prefix_is_a_whole_key": (64, _fill_whole_key,
                              ("__sp_", "__sp_7", "__sp", "_", "x")),
    "key_of_127_bytes": (64, _fill_long_key,
                         ("k" * 127, "k" * 126, "k" * 65, "k")),
    "empty_store": (64, lambda st: None, ("", "__sr_")),
    "load_over_90pct_262144_slots": (262_144, _fill_loaded,
                                     ("__sr_", "vec/26214", "__sp_")),
}


@pytest.mark.parametrize("case", _PREFIX_CASES)
def test_keys_with_prefix_matches_list_filter(case):
    """The native prefix scan names exactly the keys a list() walk
    filtered with startswith would, in the same (slot) order, and
    counts every live key it went over."""
    nslots, fill, prefixes = _PREFIX_CASES[case]
    name = f"/spt-pfx-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    st = Store.create(name, nslots=nslots, max_val=16, vec_dim=0)
    try:
        fill(st)
        every = st.list()
        if nslots > 100_000:
            assert len(every) > 0.9 * nslots
        for pfx in prefixes:
            want = [k for k in every if k.startswith(pfx)]
            assert st.keys_with_prefix(pfx) == want, pfx
            keys, scanned = st.scan_prefix(pfx)
            assert keys == want and scanned == len(every), pfx
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.parametrize("then", ["gone", "replaced"])
def test_keys_with_prefix_drops_a_slot_that_changed_under_the_scan(
        store, monkeypatch, then):
    """The scan compares keys in place, unvalidated; each index is
    resolved through key_at and the prefix checked again, so a slot
    whose key went or was replaced between the two is dropped, as the
    get() -> KeyError path dropped it after a list() walk."""
    store.set("__sr_1", b"r")
    store.set("__sr_2", b"r")
    raced = store.find_index("__sr_1")
    real = store.key_at

    def key_at(idx):
        if idx != raced:
            return real(idx)
        store.unset("__sr_1")         # the writer got there first
        return None if then == "gone" else "doc/7"

    monkeypatch.setattr(store, "key_at", key_at)
    assert store.keys_with_prefix("__sr_") == ["__sr_2"]


def test_contains(store):
    store.set("here", b"x")
    assert "here" in store
    assert "not-here" not in store


def test_append(store):
    store.set("log", b"hello")
    store.append("log", b" world")
    assert store.get("log") == b"hello world"


def test_append_to_missing_creates(store):
    store.append("fresh", b"start")
    assert store.get("fresh") == b"start"


def test_append_overflow(store):
    store.set("full", b"x" * (store.max_val - 4))
    with pytest.raises(OSError):
        store.append("full", b"12345678")


def test_epoch_advances_by_two_per_write(store):
    store.set("e", b"1")
    e1 = store.epoch("e")
    assert e1 % 2 == 0 and e1 >= 2
    store.set("e", b"2")
    assert store.epoch("e") == e1 + 2


def test_global_epoch_advances(store):
    g0 = store.header().global_epoch
    store.set("a", b"x")
    store.set("b", b"y")
    assert store.header().global_epoch >= g0 + 2


def test_header_snapshot(store):
    h = store.header()
    assert h.magic == 0x53505455
    assert h.version == 2
    assert h.nslots == 256
    assert h.vec_dim == 32
    assert h.mop_mode == sp.MOP_HYBRID  # default for new stores
    store.set("one", b"x")
    assert store.header().used_slots == 1


def test_slot_snapshot(store):
    store.set("snap", b"abcd")
    store.label_or("snap", 0x5)
    s = store.slot("snap")
    assert s.key == "snap"
    assert s.val_len == 4
    assert s.labels == 0x5
    assert s.epoch % 2 == 0
    assert s.ctime > 0 and s.atime > 0
    assert store.slot_at(s.index).key == "snap"


def test_named_types(store):
    store.set("t", b"x")
    assert store.get_type("t") == sp.T_VOID
    store.set_type("t", sp.T_JSON)
    assert store.get_type("t") == sp.T_JSON
    store.set_type("t", sp.T_VARTEXT)
    assert store.get_type("t") == sp.T_VARTEXT


def test_biguint_promotion(store):
    store.set("n", b"12345")
    store.set_type("n", sp.T_BIGUINT)
    assert store.get_type("n") == sp.T_BIGUINT
    assert store.get_uint("n") == 12345
    assert store.value_len("n") == 8


def test_biguint_promotion_rejects_garbage(store):
    store.set("g", b"not-a-number")
    with pytest.raises(OSError):
        store.set_type("g", sp.T_BIGUINT)


def test_integer_ops(store):
    store.set_uint("c", 10)
    assert store.integer_op("c", sp.IOP_INC) == 11
    assert store.integer_op("c", sp.IOP_DEC) == 10
    assert store.integer_op("c", sp.IOP_ADD, 32) == 42
    assert store.integer_op("c", sp.IOP_SUB, 2) == 40
    assert store.integer_op("c", sp.IOP_AND, 0xF) == 8
    assert store.integer_op("c", sp.IOP_OR, 0x30) == 0x38
    assert store.integer_op("c", sp.IOP_XOR, 0xFF) == 0xC7
    assert store.integer_op("c", sp.IOP_NOT) == (~0xC7) & (2**64 - 1)


def test_integer_carry_borrow(store):
    store.set_uint("w", 2**64 - 1)
    assert store.integer_op("w", sp.IOP_INC) == 0  # wraps
    assert store.integer_op("w", sp.IOP_DEC) == 2**64 - 1  # borrows back


def test_integer_op_wrong_type_eprototype(store):
    store.set("s", b"text")
    with pytest.raises(OSError) as exc:
        store.integer_op("s", sp.IOP_INC)
    import errno
    assert exc.value.errno == errno.EPROTOTYPE


def test_tandem_keys(store):
    n = store.tandem_set("doc", [b"chunk0", b"chunk1", b"chunk2"])
    assert n == 3
    assert store.tandem_count("doc") == 3
    assert store.tandem_get("doc", 0) == b"chunk0"
    assert store.tandem_get("doc", 2) == b"chunk2"
    assert store.get("doc.1") == b"chunk1"  # plain keys underneath
    removed = store.tandem_unset("doc", 16)
    assert removed == 3
    assert store.tandem_count("doc") == 0


def test_embedding_roundtrip(store):
    store.set("vec", b"text")
    v = np.random.default_rng(0).normal(size=32).astype(np.float32)
    store.vec_set("vec", v)
    np.testing.assert_array_equal(store.vec_get("vec"), v)


def test_embedding_zeroed_on_unset_and_new_key(store):
    store.set("z", b"a")
    store.vec_set("z", np.ones(32, dtype=np.float32))
    store.unset("z")
    store.set("z", b"b")  # may or may not reuse the slot
    np.testing.assert_array_equal(store.vec_get("z"),
                                  np.zeros(32, dtype=np.float32))


def test_vector_lane_is_zero_copy(store):
    """The SoA lane view reflects vec_set without copies."""
    store.set("lane", b"x")
    idx = store.find_index("lane")
    v = np.full(32, 7.5, dtype=np.float32)
    store.vec_set("lane", v)
    np.testing.assert_array_equal(store.vectors[idx], v)
    assert store.vectors.shape == (256, 32)


def test_vec_on_novec_store(store_novec):
    store_novec.set("k", b"x")
    with pytest.raises(OSError):
        store_novec.vec_set("k", np.zeros(8, dtype=np.float32))


def test_vec_commit_batch_epoch_gate(store):
    store.set("a", b"one")
    store.set("b", b"two")
    ia, ib = store.find_index("a"), store.find_index("b")
    ea, eb = store.epoch_at(ia), store.epoch_at(ib)
    store.set("b", b"changed")  # invalidates eb
    rows = np.array([ia, ib], dtype=np.uint32)
    epochs = np.array([ea, eb], dtype=np.uint64)
    vecs = np.ones((2, 32), dtype=np.float32)
    res = store.vec_commit_batch(rows, epochs, vecs)
    assert res[0] == 0          # committed
    assert res[1] != 0          # -ESTALE: raced
    assert store.vec_get("a")[0] == 1.0
    assert store.vec_get("b")[0] == 0.0


def test_vec_commit_batch_write_once(store):
    store.set("w1", b"x")
    idx = store.find_index("w1")
    store.vec_set("w1", np.full(32, 2.0, dtype=np.float32))
    rows = np.array([idx], dtype=np.uint32)
    epochs = np.array([store.epoch_at(idx)], dtype=np.uint64)
    res = store.vec_commit_batch(rows, epochs,
                                 np.ones((1, 32), dtype=np.float32),
                                 write_once=True)
    assert res[0] != 0  # -EEXIST
    assert store.vec_get("w1")[0] == 2.0


def test_mop_modes(store):
    assert store.get_mop() == sp.MOP_HYBRID
    store.set_mop(sp.MOP_OFF)
    assert store.get_mop() == sp.MOP_OFF
    store.set_mop(sp.MOP_FULL)
    assert store.get_mop() == sp.MOP_FULL
    # full-boil: shrinking a value leaves no stale tail
    store.set("m", b"A" * 512)
    store.set("m", b"B")
    assert store.get("m") == b"B"
    store.set_mop(sp.MOP_HYBRID)


def test_purge_survival(store):
    for i in range(20):
        store.set(f"p{i}", f"value-{i}".encode())
    store.unset("p3")
    swept = store.purge()
    assert swept > 0
    for i in range(20):
        if i == 3:
            continue
        assert store.get(f"p{i}") == f"value-{i}".encode()


def test_system_key(store):
    store.set_system("__scratch")
    s = store.slot("__scratch")
    assert s.val_len == store.max_val
    assert s.flags & sp.native_abi.F_SYSTEM
    assert store.get_type("__scratch") == sp.T_BINARY


def test_user_flags(store):
    store.set("u", b"x")
    store.slot_usr_set("u", 0xA5)
    assert store.slot_usr_get("u") == 0xA5
    store.config_set_user(0xB)
    assert store.config_get_user() == 0xB
    assert store.config_get_user() <= 0xF  # only 4 store-level bits


def test_retrain_backward_epoch(store):
    store.set("r", b"x")
    store.set("r", b"y")
    store.vec_set("r", np.ones(32, dtype=np.float32))
    before = store.epoch("r")
    assert before > 4
    store.retrain("r")
    after = store.epoch("r")
    assert after == 4            # backward epoch = "revalidate me"
    assert after < before
    np.testing.assert_array_equal(store.vec_get("r"),
                                  np.zeros(32, dtype=np.float32))
    assert store.get("r") == b"y"  # value survives retrain


def test_timestamps_backfill(store):
    store.set("t", b"x")
    before = store.slot("t").ctime
    delta = Store.ticks_per_us() * 1000  # 1 ms ago
    store.stamp("t", which=0, ticks_ago=delta)
    after = store.slot("t").ctime
    assert after != before
    assert after < Store.now()


def test_now_monotonic():
    a = Store.now()
    b = Store.now()
    assert b >= a
    assert Store.ticks_per_us() >= 1


def test_poll_timeout(store):
    store.set("pp", b"x")
    assert store.poll("pp", timeout_ms=30) is False


def test_poll_wakes_on_write(store):
    import threading
    store.set("pw", b"x")

    def writer():
        import time
        time.sleep(0.05)
        w = Store.open(store.name)
        w.set("pw", b"y")
        w.close()

    t = threading.Thread(target=writer)
    t.start()
    assert store.poll("pw", timeout_ms=2000) is True
    t.join()


def test_slot_exhaustion(store_novec):
    st = store_novec
    filled = 0
    try:
        for i in range(st.nslots + 8):
            st.set(f"fill-{i}", b"x")
            filled += 1
    except OSError:
        pass
    assert filled == st.nslots


def test_parse_failure_diag(store):
    assert store.header().parse_failures == 0
    store.report_parse_failure()
    h = store.header()
    assert h.parse_failures == 1


def test_open_numa(store):
    """NUMA-bound open maps the store; bind result is advisory
    (reference parity: splinter_open_numa, splinter.c:250-264)."""
    import errno

    store.set("numa-k", b"v")
    st2, bind_rc = type(store).open_numa(store.name, 0)
    try:
        assert bind_rc in (0, -errno.ENOSYS, -errno.EPERM, -errno.EINVAL)
        assert st2.get("numa-k") == b"v"
    finally:
        st2.close()
    st3, bad_rc = type(store).open_numa(store.name, -1)
    st3.close()
    assert bad_rc == -errno.EINVAL


# -- a label raise is journaled; LabelCursor follows a mask through it --

def test_changed_since_names_a_row_after_label_or_alone(store):
    """label_or moves no epoch and still appends its slot — after the
    bits are readable; label_clear and a raise on a missing key
    append nothing."""
    store.set("asks", b"v")
    row = store.find_index("asks")
    cur = store.journal_head()
    e0 = store.epoch_at(row)
    store.label_or("asks", 0x5)
    rows, cur2, complete = store.changed_since(cur)
    assert complete and rows.tolist() == [row] and cur2 == cur + 1
    assert store.labels_at(row) & 0x5 == 0x5
    assert store.epoch_at(row) == e0
    store.label_clear("asks", 0x5)
    with pytest.raises(KeyError):
        store.label_or("no-such-key", 1)
    rows, cur3, complete = store.changed_since(cur2)
    assert complete and rows.size == 0 and cur3 == cur2


def test_label_cursor_follows_raises_without_walking(store):
    from libsplinter_tpu.store import LabelCursor

    BIT, OTHER = 1 << 57, 1 << 3
    for i in range(6):
        store.set(f"k{i}", b"v")
    store.label_or("k0", BIT)                    # before the cursor
    lc = LabelCursor(store, BIT)
    assert lc.rows().tolist() == [store.find_index("k0")]
    assert lc.fallbacks == 1 and lc.slots_scanned == store.nslots
    walks = []
    real = store.enumerate_indices
    store.enumerate_indices = lambda m: walks.append(m) or real(m)
    store.label_or("k3", BIT)
    store.label_or("k4", OTHER)                  # named, not asking
    store.set("k5", b"w")                        # an epoch record
    want = sorted(store.find_index(k) for k in ("k0", "k3"))
    assert lc.rows().tolist() == want
    assert lc.slots_scanned == store.nslots + 4
    assert lc.rows().tolist() == want            # held, no new record
    assert lc.slots_scanned == store.nslots + 6
    store.label_clear("k0", BIT)                 # a clear: no record
    assert lc.rows().tolist() == [store.find_index("k3")]
    store.unset("k3")                            # labels zeroed
    assert lc.rows().size == 0 and lc.pending.size == 0
    assert walks == [] and lc.fallbacks == 1


def test_label_cursor_wants_every_bit_of_its_mask(store):
    from libsplinter_tpu.store import LabelCursor

    store.set("half", b"v")
    store.set("whole", b"v")
    lc = LabelCursor(store, 0x6)
    assert lc.rows().size == 0
    store.label_or("half", 0x2)
    store.label_or("whole", 0x6)
    assert lc.rows().tolist() == [store.find_index("whole")]
    assert lc.rows().tolist() == store.enumerate_indices(0x6)
    store.label_or("half", 0x4)                  # the other bit, later
    assert lc.rows().tolist() == store.enumerate_indices(0x6)
    assert len(lc.pending) == 2


@pytest.mark.parametrize("how", ["lapped", "claimed_never_written"])
def test_label_cursor_walks_when_the_journal_cannot_answer(store, how):
    from libsplinter_tpu import _native as N
    from libsplinter_tpu.store import LabelCursor

    BIT = 1 << 57
    store.set("a", b"v")
    store.set("b", b"v")
    lc = LabelCursor(store, BIT)
    assert lc.rows().size == 0
    store.label_or("a", BIT)
    if how == "lapped":
        for _ in range(N.JOURNAL_CAP + 1):
            store.set("b", b"w")
    else:
        real = store.changed_since
        store.changed_since = lambda c: (
            np.empty(0, np.uint32), store.journal_head(), False)
    assert lc.rows().tolist() == [store.find_index("a")]
    assert lc.fallbacks == 2
    assert lc.slots_scanned == 2 * store.nslots
    if how != "lapped":
        store.changed_since = real
    store.label_or("b", BIT)
    assert lc.rows().tolist() == sorted(
        store.find_index(k) for k in "ab")
    assert lc.fallbacks == 2                     # by the journal again


def test_label_cursor_audit_counts_only_what_no_record_named(store):
    from libsplinter_tpu.store import LabelCursor

    BIT = 1 << 57
    for k in "abc":
        store.set(k, b"v")
    lc = LabelCursor(store, BIT)
    assert lc.audit() == 0 and lc.fallbacks == 0     # never walked yet
    lc.rows()
    store.label_or("a", BIT)          # named by a record not yet read
    assert lc.audit() == 0 and lc.audit_rows == 0
    assert lc.pending.tolist() == [store.find_index("a")]
    scanned = lc.slots_scanned
    real = store.changed_since
    lost = store.find_index("b")

    def loses(cursor):
        rows, cur, complete = real(cursor)
        return rows[rows != lost], cur, complete

    store.changed_since = loses
    store.label_or("b", BIT)
    assert lc.rows().tolist() == [store.find_index("a")]   # unseen
    assert lc.audit() == 1 and lc.audit_rows == 1
    assert lc.pending.tolist() == sorted(
        store.find_index(k) for k in "ab")
    assert lc.audit() == 0 and lc.audit_rows == 1    # adopted: known
    assert lc.slots_scanned == scanned + 1       # audits are not in it
    assert lc.rows().tolist() == lc.pending.tolist()
