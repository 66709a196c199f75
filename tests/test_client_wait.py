"""The client's bounded wait (engine/client.py `wait_with_repulse`
over `Store.poll_labels` / native `spt_poll_labels`), judged by its
own counters and not by the wall clock: a commit that flips a label
and bumps — which moves no epoch — has to END a wait slice (`woken`),
not be slept through until the 50 ms slice runs out
(`slice_timeouts`)."""
from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np
import pytest

from libsplinter_tpu.engine import client as C
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.pipeliner import submit_script
from libsplinter_tpu.engine.searcher import submit_search

JOIN_S = 30.0


def _delta(before: dict) -> dict:
    after = C.wait_counters()
    return {k: after[k] - before[k] for k in after}


class _Spy:
    """The store as the client sees it, with `bump` timed and the entry
    into the native wait flagged — so a stand-in daemon can commit
    INSIDE the client's wait whichever thread the scheduler favours."""

    def __init__(self, store):
        self._st, self.bumps = store, []
        self.waiting = threading.Event()

    def __getattr__(self, name):
        return getattr(self._st, name)

    def bump(self, key):
        self.bumps.append(time.monotonic())
        return self._st.bump(key)

    def poll_labels(self, *a, **kw):
        self.waiting.set()
        return self._st.poll_labels(*a, **kw)


def _when_waiting(spy: _Spy, key: str, mask: int,
                  commit) -> threading.Thread:
    """A stand-in daemon: once `key` carries `mask` and the client has
    gone into its wait, run `commit()` — what that lane's commit does
    to the store."""
    def run():
        if not spy.waiting.wait(JOIN_S):
            return
        stop = time.monotonic() + JOIN_S
        while time.monotonic() < stop:
            try:
                if spy.labels(key) & mask:
                    commit()
                    return
            except KeyError:
                pass
            time.sleep(0.0005)
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _joined(t: threading.Thread) -> None:
    t.join(JOIN_S)
    assert not t.is_alive()


# -- (a) each lane's commit ends the wait by a wake -------------------------

def _search(store, key):
    store.set(key, "placeholder")
    store.vec_set(key, np.ones(32, np.float32))
    idx = store.find_index(key)
    rkey = P.search_result_key(idx)

    def commit():                     # Searcher._commit_result
        store.set(rkey, json.dumps({"s": [1.0], "i": [idx], "keys": [key]}))
        store.label_or(rkey, P.LBL_READY)
        store.label_clear(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
        store.bump(key)

    t = _when_waiting(store, key, P.LBL_SEARCH_REQ, commit)
    out = submit_search(store, key, 1, timeout_ms=20_000, retry=False)
    return t, out, lambda o: isinstance(o, dict) and o["i"] == [idx]


def _script(store, key):
    store.set(key, "placeholder")
    rkey = P.script_result_key(store.find_index(key))

    def commit():                     # Pipeliner's result commit
        store.set(rkey, json.dumps({"ok": True, "ret": [7]}))
        store.label_clear(key, P.LBL_SCRIPT_REQ | P.LBL_WAITING)
        store.bump(key)

    t = _when_waiting(store, key, P.LBL_SCRIPT_REQ, commit)
    out = submit_script(store, key, script="return 7", timeout_ms=20_000,
                        retry=False)
    return t, out, lambda o: o == {"ok": True, "ret": [7]}


def _embed(store, key):
    def commit():                     # the embedder's shed: a label only
        store.label_clear(key, P.LBL_EMBED_REQ | P.LBL_WAITING)

    t = _when_waiting(store, key, P.LBL_EMBED_REQ, commit)
    out = C.submit_embed(store, key, "some text", timeout_ms=20_000,
                         retry=False)
    return t, out, lambda o: isinstance(o, dict) \
        and o["err"] == P.ERR_OVERLOADED


def _completion(store, key):
    def commit():                     # the completer's finish
        store.set(key, b"hello world")
        store.label_clear(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        store.label_or(key, P.LBL_READY)
        store.bump(key)

    t = _when_waiting(store, key, P.LBL_INFER_REQ, commit)
    out = C.submit_completion(store, key, "hello", timeout_ms=20_000,
                              retry=False)
    return t, out, lambda o: o == b"hello world"


PROTOCOLS = {"search": _search, "script": _script, "embed": _embed,
             "completion": _completion}


@pytest.mark.parametrize("bus", [False, True], ids=["unarmed", "armed"])
@pytest.mark.parametrize("lane", sorted(PROTOCOLS))
def test_commit_wakes_the_wait(store, lane, bus):
    """What each daemon's commit does ends the client's wait in the
    slice it lands in — on the event bus where a daemon has armed
    it, on the 1 ms sleep where none has."""
    if bus:
        store.bus_init()
    before = C.wait_counters()
    t, out, ok = PROTOCOLS[lane](_Spy(store), f"req-{lane}")
    _joined(t)
    assert ok(out), out
    d = _delta(before)
    assert d["waits"] == 1
    assert d["woken"] >= 1
    assert d["slice_timeouts"] == 0
    assert d["repulses"] == 0


# -- (b) level-triggered: nothing is lost between check() and the wait ------

def test_poll_labels_true_on_entry_returns_at_once(store):
    store.set("k", b"x")
    store.label_or("k", P.LBL_READY)
    # a wait without end, so only the condition can return it
    assert store.poll_labels("k", P.LBL_READY, P.LBL_READY, -1) is True
    assert store.poll_labels("k", P.LBL_SEARCH_REQ, 0, -1) is True


def test_flip_between_check_and_wait_is_not_lost(store):
    store.set("k", b"x")
    store.label_or("k", P.LBL_SEARCH_REQ)
    looks = []

    def check():
        looks.append(1)
        if len(looks) == 1:
            # the commit lands right after this look said PENDING
            store.label_clear("k", P.LBL_SEARCH_REQ)
            return C.PENDING
        return "done"

    before = C.wait_counters()
    out = C.wait_with_repulse(store, "k", 20_000, check,
                              mask=P.LBL_SEARCH_REQ, want=0)
    assert out == "done" and len(looks) == 2
    assert _delta(before) == {"waits": 1, "woken": 1, "slice_timeouts": 0,
                              "repulses": 0}


# -- (c) the key unset mid-wait ---------------------------------------------

def test_unset_mid_wait_returns_what_check_returns(store):
    key, spy = "gone", _Spy(store)
    t = _when_waiting(spy, key, P.LBL_INFER_REQ, lambda: store.unset(key))
    before = C.wait_counters()
    out = C.submit_completion(spy, key, "hello", timeout_ms=20_000,
                              retry=False)
    _joined(t)
    assert out is None
    d = _delta(before)
    assert d["woken"] >= 1 and d["slice_timeouts"] == 0


def test_poll_labels_unknown_key_raises(store):
    with pytest.raises(KeyError):
        store.poll_labels("never-set", P.LBL_READY, P.LBL_READY, 5)


# -- (d) no flip: the budget runs out ---------------------------------------

def test_no_flip_times_out_with_one_repulse_at_half_budget(store):
    store.set("k", b"x")
    store.label_or("k", P.LBL_SEARCH_REQ)
    st, budget_ms = _Spy(store), 160.0
    before = C.wait_counters()
    t0 = time.monotonic()
    out = C.wait_with_repulse(st, "k", budget_ms, lambda: C.PENDING,
                              mask=P.LBL_SEARCH_REQ, want=0)
    assert out is None
    assert (time.monotonic() - t0) * 1e3 >= budget_ms
    assert len(st.bumps) == 1
    assert (st.bumps[0] - t0) * 1e3 >= budget_ms / 2 - 1.0
    d = _delta(before)
    assert d["waits"] == 1 and d["repulses"] == 1 and d["woken"] == 0
    # every slice ended by time (four of them on an idle machine; a
    # loaded one overshoots its slices and fits fewer into the budget)
    assert d["slice_timeouts"] >= 1


def test_poll_labels_times_out(store):
    store.set("k", b"x")
    store.label_or("k", P.LBL_SEARCH_REQ)
    assert store.poll_labels("k", P.LBL_SEARCH_REQ, 0, 20) is False
    # every bit of `want` has to hold
    store.label_or("k", P.LBL_READY)
    assert store.poll_labels("k", P.LBL_READY | P.LBL_WAITING,
                             P.LBL_READY | P.LBL_WAITING, 20) is False


# -- (e) a label flip that moves no epoch -----------------------------------

@pytest.mark.parametrize("mask,want,flip", [
    (P.LBL_SEARCH_REQ, 0, "clear"),
    (P.LBL_READY, P.LBL_READY, "raise"),
], ids=["cleared", "raised"])
def test_label_flip_without_epoch_move_is_seen(store, mask, want, flip):
    """The case the epoch wait slept through: no write, no bump."""
    store.set("k", b"x")
    store.label_or("k", P.LBL_WAITING | (mask if flip == "clear" else 0))
    e0 = store.epoch_at(store.find_index("k"))

    def commit():
        if flip == "clear":
            store.label_clear("k", mask)
        else:
            store.label_or("k", mask)

    spy = _Spy(store)
    t = _when_waiting(spy, "k", P.LBL_WAITING, commit)

    def check():
        return "done" if store.labels("k") & mask == want else C.PENDING

    before = C.wait_counters()
    out = C.wait_with_repulse(spy, "k", 20_000, check,
                              mask=mask, want=want)
    _joined(t)
    assert out == "done"
    assert store.epoch_at(store.find_index("k")) == e0
    # the epoch wait cannot see what just happened
    assert store.poll("k", timeout_ms=5) is False
    d = _delta(before)
    assert d["woken"] >= 1 and d["slice_timeouts"] == 0


def test_epoch_move_wakes_the_label_wait(store):
    """A rewrite with the label still standing (a streamed chunk, a
    client's new request) returns the wait: the caller looks again."""
    store.set("k", b"x")
    done = threading.Event()

    def stream():                     # a write a millisecond: one of
        while not done.is_set():      # them lands inside the wait
            store.set("k", b"chunk")
            time.sleep(0.001)

    t = threading.Thread(target=stream, daemon=True)
    t.start()
    try:
        assert store.poll_labels("k", P.LBL_READY, P.LBL_READY,
                                 20_000) is True
    finally:
        done.set()
    _joined(t)
    assert not store.labels("k") & P.LBL_READY


# -- the counters under many client threads ---------------------------------

def test_counters_lose_no_update_under_threads(store):
    """The submit paths run on many client threads: every wait is
    counted once, none lost to a torn read-modify-write."""
    n_threads, n_each = 16, 200
    store.set("k", b"x")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = C.wait_counters()

        def work():
            for _ in range(n_each):
                C.wait_with_repulse(store, "k", 1_000, lambda: "done",
                                    mask=P.LBL_READY, want=P.LBL_READY)

        ths = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            _joined(t)
    finally:
        sys.setswitchinterval(old)
    assert _delta(before) == {"waits": n_threads * n_each, "woken": 0,
                              "slice_timeouts": 0, "repulses": 0}
