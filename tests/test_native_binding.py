"""Which native calls keep the interpreter lock (`_native.KEEPS_LOCK`,
bound through `ctypes.PyDLL`) and which drop it (`ctypes.CDLL`), held
in BOTH directions over the whole `_native._sigs()` table — and that
many client threads under the lock-keeping binding lose nothing.
Counts and read-backs only: no test here reads a clock."""
from __future__ import annotations

import ctypes
import sys
import threading

import numpy as np
import pytest

from libsplinter_tpu import Eagain
from libsplinter_tpu import _native as N
from libsplinter_tpu import store as S

JOIN_S = 60.0

# The request protocol's constant-time calls: bounded work, no sleep, no
# syscall that can block, no callback into Python (`_native.KEEPS_LOCK`
# has the rule and the worst case).
KEEPS = {"spt_vec_set", "spt_find_index", "spt_set", "spt_get",
         "spt_label_or", "spt_get_labels", "spt_bump"}

# Held across one of these the lock would stop every other thread.
WAITS = {"spt_poll", "spt_poll_labels", "spt_signal_wait", "spt_bus_wait"}
LINEAR = {                      # in the slots, in a batch, or in a text
    "spt_enumerate", "spt_enumerate_prefix", "spt_list",
    "spt_changed_since", "spt_epochs", "spt_epochs_at", "spt_vec_gather",
    "spt_vec_commit_batch", "spt_purge", "spt_header_snapshot",
    "spt_tandem_unset", "spt_tandem_count", "spt_shard_election",
    "spt_wptok_create", "spt_wptok_create_hashed", "spt_wptok_destroy",
    "spt_wptok_encode", "spt_wptok_encode_batch",
}
SYSCALLS = {                    # map, unmap, open, advise: may block
    "spt_create", "spt_open", "spt_open_numa", "spt_close", "spt_unlink",
    "spt_bus_init", "spt_bus_open", "spt_bus_close", "spt_madvise",
}
# Bounded calls that no client thread makes between an answer and its
# next request: they stay as they were until a measurement asks for
# them (PR 33's run showed no gain on the daemon's side).
AS_BEFORE = {
    "spt_nslots", "spt_max_val", "spt_vec_dim", "spt_vec_lane",
    "spt_values_base", "spt_last_error", "spt_unset", "spt_append",
    "spt_get_raw", "spt_key_at", "spt_epoch_at", "spt_get_at",
    "spt_labels_at", "spt_flags_at", "spt_slot_snapshot",
    "spt_slot_snapshot_at", "spt_set_type", "spt_get_type",
    "spt_integer_op", "spt_tandem_set", "spt_tandem_get",
    "spt_label_andnot", "spt_watch_register", "spt_watch_unregister",
    "spt_watch_label_register", "spt_watch_label_unregister",
    "spt_signal_count", "spt_signal_pulse", "spt_bus_drain",
    "spt_bus_peek", "spt_shard_claim", "spt_shard_claim_ex",
    "spt_shard_rebid", "spt_shard_release", "spt_bid_info", "spt_set_mop",
    "spt_get_mop", "spt_retrain", "spt_set_system", "spt_slot_usr_set",
    "spt_slot_usr_get", "spt_config_set_user", "spt_config_get_user",
    "spt_now", "spt_ticks_per_us", "spt_stamp", "spt_vec_get",
    "spt_vec_set_at", "spt_vec_get_at", "spt_journal_head",
    "spt_report_parse_failure",
}
DROPS = WAITS | LINEAR | SYSCALLS | AS_BEFORE


def test_the_table_is_the_list():
    assert N.KEEPS_LOCK == KEEPS
    assert not KEEPS & DROPS
    assert sum(map(len, (WAITS, LINEAR, SYSCALLS, AS_BEFORE))) == len(DROPS)
    # nothing classified here that the binding no longer has
    assert KEEPS | DROPS <= set(N._sigs())


@pytest.mark.parametrize("name", sorted(N._sigs()))
def test_symbol_is_bound_as_classified(name):
    """A symbol added to `_sigs()` fails here until it is put in one of
    the sets above by the rule; a listed symbol must keep the lock and
    every other must drop it."""
    assert (name in KEEPS) != (name in DROPS), \
        f"{name}: classify it (bounded, no sleep, no blocking syscall, " \
        f"no callback -> KEEPS; else one of the DROPS sets)"
    fn = getattr(N.get_lib(), name)
    keeps = bool(fn._flags_ & ctypes._FUNCFLAG_PYTHONAPI)
    assert keeps == (name in KEEPS), name
    assert fn._flags_ & ctypes._FUNCFLAG_USE_ERRNO, name


# -- many client threads, every call of a request ---------------------------

N_THREADS, N_REQUESTS = 16, 500
LBL_A, LBL_B = 1 << 3, 1 << 9


def _fast_switching(fn):
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        return fn()
    finally:
        sys.setswitchinterval(old)


def _run_threads(targets):
    errors: list[Exception] = []

    def guarded(t):
        def run():
            try:
                t()
            except Exception as e:         # surfaced by the caller
                errors.append(e)
        return run

    ths = [threading.Thread(target=guarded(t), daemon=True)
           for t in targets]
    for t in ths:
        t.start()
    for t in ths:
        t.join(JOIN_S)
        assert not t.is_alive()
    assert not errors, errors


def test_request_sequences_under_threads_read_back(store):
    """16 threads, a 1 us switch interval, 500 requests each on a key
    of their own — `vec_set`, `find_index`, `set`, `label_or`, `bump`,
    `labels`, `get` — and every value, vector and label reads back."""
    done = [0] * N_THREADS
    idxs = []

    def client(t):
        key = f"req-{t}"
        store.set(key, b"placeholder")
        idx0 = store.find_index(key)
        idxs.append(idx0)

        def run():
            for i in range(N_REQUESTS):
                vec = np.full(store.vec_dim, t * 1000 + i, np.float32)
                store.vec_set(key, vec)
                assert store.find_index(key) == idx0
                val = f"{t}:{i}".encode()
                store.set(key, val)
                want = LBL_A if i % 2 else LBL_B
                store.label_clear(key, LBL_A | LBL_B)
                store.label_or(key, want)
                store.bump(key)
                assert store.labels(key) & (LBL_A | LBL_B) == want
                assert store.get(key) == val
                assert (store.vec_get(key) == vec).all()
                done[t] += 1
        return run

    targets = [client(t) for t in range(N_THREADS)]
    e0 = store.header().global_epoch
    _fast_switching(lambda: _run_threads(targets))
    assert done == [N_REQUESTS] * N_THREADS
    assert len(set(idxs)) == N_THREADS
    # vec_set, set and bump each fan out once a request: none was lost
    assert store.header().global_epoch - e0 == 3 * N_THREADS * N_REQUESTS
    for t in range(N_THREADS):
        assert store.get(f"req-{t}") == f"{t}:{N_REQUESTS - 1}".encode()
        assert store.labels(f"req-{t}") & (LBL_A | LBL_B) == LBL_A


def test_a_parked_wait_does_not_stop_the_setters(store):
    """A thread parked in `poll_labels(..., 500 ms)` holds no lock: the
    other thread's 1,000 `set`s are all done before it times out."""
    n_sets = 1_000
    store.set("parked", b"x")
    entered, finished = threading.Event(), threading.Event()
    seen: dict = {}

    def waiter():
        entered.set()
        seen["woken"] = store.poll_labels("parked", LBL_A, LBL_A, 500)
        seen["sets_done"] = finished.is_set()

    def setter():
        assert entered.wait(JOIN_S)
        for i in range(n_sets):
            store.set(f"other-{i % 8}", b"v%d" % i)
        finished.set()

    _run_threads([waiter, setter])
    assert seen == {"woken": False, "sets_done": True}
    assert store.get("other-7") == b"v%d" % (n_sets - 1)


# -- what a lock-keeping call returns has not changed -----------------------

def _hold(store, key):
    """Take `key`'s seqlock and keep it, as a writer that died mid-write
    leaves it (`spt__lock`, the try-lock every writer goes through)."""
    lock = N.get_lib().spt__lock
    lock.restype = ctypes.c_int32
    lock.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                     ctypes.POINTER(ctypes.c_uint64)]
    assert lock(store._h, store.find_index(key),
                ctypes.byref(ctypes.c_uint64())) == 0


@pytest.mark.parametrize("call", ["set", "vec_set"])
def test_held_slot_still_surfaces_eagain_after_retries(store, call,
                                                       monkeypatch):
    store.set("held", b"before")
    turns = []
    real_sleep = S.time.sleep
    monkeypatch.setattr(S.time, "sleep",
                        lambda s: (turns.append(s), real_sleep(s)))
    _hold(store, "held")
    try:
        with pytest.raises(Eagain):
            if call == "set":
                store.set("held", b"after")
            else:
                store.vec_set("held", np.ones(store.vec_dim, np.float32))
        # every -EAGAIN yielded the interpreter once, in Python
        assert turns == [0] * S._RETRIES
        with pytest.raises(Eagain):      # a reader sees the odd epoch too
            store.get("held")
    finally:
        store.retrain("held")            # the sanctioned recovery
    assert store.get("held") == b"before"
    assert not store.vec_get("held").any()   # the refused write left none
    store.set("held", b"after")
    assert store.get("held") == b"after"


def test_errno_is_private_to_a_thread_across_lock_keeping_calls(store):
    """`use_errno` on the second handle too: ctypes swaps its private,
    per-thread errno around every call, and a call on one thread leaves
    another thread's untouched."""
    store.set("k", b"x")
    n, out = 8, {}

    def thread(t):
        def run():
            ctypes.set_errno(100 + t)
            for _ in range(200):
                store.find_index("k")
                store.labels("k")
                with pytest.raises(KeyError):
                    store.find_index(f"never-set-{t}")
            out[t] = ctypes.get_errno()
        return run

    _fast_switching(lambda: _run_threads([thread(t) for t in range(n)]))
    assert out == {t: 100 + t for t in range(n)}
