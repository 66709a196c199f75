"""An answer's budget by the request (protocol.stamp_max_new, ROADMAP
B1.14): the stamp's discipline beside the deadline stamp's, and the
continuous lane seating a row with min(stamp, --max-new-tokens) and
reserving pages for the request's own budget."""
from __future__ import annotations

import threading
import time

import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig

PROMPT = "tell me a thing and then some more words"


@pytest.fixture()
def store(tmp_path):
    name = f"/spt-mn-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=16384, vec_dim=8)
    yield st
    st.close()
    Store.unlink(name)


@pytest.fixture(scope="module")
def tiny_model():
    return CompletionModel(DecoderConfig.tiny(max_len=128),
                           buckets=(16, 32), temp=0.0, seed=1)


def test_stamp_round_trip_and_stale_epoch(store):
    store.set("r", "x")
    idx = store.find_index("r")
    assert P.read_max_new(store, idx) is None
    assert P.stamp_max_new(store, "r", 17)
    assert store.labels("r") & P.LBL_MAX_NEW
    assert P.read_max_new(store, idx, epoch=store.epoch_at(idx)) == 17
    # a stamp of another epoch binds nobody: consumed, None
    store.set("r", "rewritten")
    assert P.read_max_new(store, idx, epoch=store.epoch_at(idx)) is None
    assert not store.labels("r") & P.LBL_MAX_NEW
    # budgets under one are one; a missing key never raises
    assert P.stamp_max_new(store, "r", 0)
    assert P.read_max_new(store, idx) == 1
    P.clear_max_new(store, idx)
    assert P.read_max_new(store, idx) is None
    assert not P.stamp_max_new(store, "no-such-key", 4)
    assert P.LBL_MAX_NEW & (P.LBL_DEADLINE | P.LBL_DECODE_READY
                            | P.TENANT_MASK | P.LBL_SCRIPT_REQ) == 0


def test_orphan_budget_stamp_is_shed(store):
    """A stamp whose request is no longer pending is retired by the
    daemons' discard path, like a deadline stamp."""
    store.set("r", "x")
    idx = store.find_index("r")
    P.stamp_max_new(store, "r", 9)
    assert P.shed_orphan_stamp(store, idx, store.labels("r"))
    assert P.read_max_new(store, idx) is None
    assert not store.labels("r") & P.LBL_MAX_NEW


@pytest.fixture()
def lane(store, tiny_model):
    comp = Completer(store, model=tiny_model, max_new_tokens=12,
                     flush_tokens=4, template="none", batch_cap=2,
                     page_size=16)
    comp.attach()
    th = threading.Thread(target=comp.run_continuous, daemon=True,
                          kwargs={"idle_timeout_ms": 20,
                                  "stop_after": 240.0})
    th.start()
    yield comp
    comp.stop()
    th.join(timeout=30)


def _ask(store, key, n=None, prompt=PROMPT):
    out = submit_completion(store, key, prompt, timeout_ms=240_000,
                            **({} if n is None else {"max_new_tokens": n}))
    assert isinstance(out, bytes) and out.startswith(prompt.encode())
    return out


def _answered(comp, n):
    for _ in range(400):
        if comp.stats.answers_finished >= n:
            return
        time.sleep(0.02)
    raise AssertionError(comp.stats)


def test_budgets_no_stamp_under_and_over(store, lane):
    """No stamp: the daemon's 12.  A stamp under it ends the answer
    there; a stamp over it is clamped to the daemon's."""
    s = lane.stats
    _ask(store, "a")
    _answered(lane, 1)
    assert (s.answer_tokens, s.budgeted_requests) == (12, 0)
    _ask(store, "b", 5)
    _answered(lane, 2)
    assert (s.answer_tokens, s.budgeted_requests) == (17, 1)
    _ask(store, "c", 500)
    _answered(lane, 3)
    assert (s.answer_tokens, s.budgeted_requests) == (29, 2)
    _ask(store, "d", 1)
    _answered(lane, 4)
    assert s.answer_tokens == 30
    # every stamp was consumed at its claim
    for k in "abcd":
        idx = store.find_index(k)
        assert not store.labels(k) & P.LBL_MAX_NEW
        assert P.read_max_new(store, idx) is None
    lane.publish_stats()
    import json
    hb = json.loads(store.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
    assert (hb["budgeted_requests"], hb["answer_tokens"],
            hb["answers_finished"]) == (3, 30, 4)


def test_no_stamp_leaves_the_heartbeat_as_it_was(store, lane):
    _ask(store, "a")
    _answered(lane, 1)
    lane.publish_stats()
    import json
    hb = json.loads(store.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
    assert not {"budgeted_requests", "answer_tokens",
                "answers_finished"} & set(hb)


def test_two_rows_finish_apart_and_the_freed_row_is_reseated(store, lane):
    """Rows of ONE batch with budgets 2 and 12: the short one returns
    while the long one decodes, and a third request takes its row
    before the long one ends."""
    done = {}

    def one(key, n):
        _ask(store, key, n)
        done[key] = time.perf_counter()
    # compile first, so that the order below is the lane's own
    _ask(store, "warm", 2)
    long_, short = (threading.Thread(target=one, args=a)
                    for a in (("long", 12), ("short", 2)))
    long_.start()
    short.start()
    short.join(timeout=120)
    third = threading.Thread(target=one, args=("third", 2))
    third.start()
    for t in (long_, third):
        t.join(timeout=120)
    assert set(done) == {"long", "short", "third"}
    assert done["short"] < done["long"]
    s = lane.stats
    assert s.answers_finished == 4 and s.answer_tokens == 2 + 12 + 2 + 2
    # the two rows were live together: steps with two rows happened
    assert s.decode_rows > s.decode_steps


def test_worst_len_reserves_by_the_request(store, tiny_model,
                                            monkeypatch):
    """Admission plans its pages for the request's OWN budget: a
    prompt (clipped to the 28 tokens the daemon's 100 leave of a
    128-token window) with 4 new tokens asks the seat for 32 tokens (28
    + one chunk of 4 behind the join's own token; 36 should it replay
    its last), the same prompt without a stamp for the whole window the
    daemon's 100 would fill."""
    from libsplinter_tpu.engine.prefix_cache import Seat
    asked, plan = [], Seat.plan

    def spy(self, need, need_replay):
        asked.append((need, need_replay))
        return plan(self, need, need_replay)
    monkeypatch.setattr(Seat, "plan", spy)
    comp = Completer(store, model=tiny_model, max_new_tokens=100,
                     flush_tokens=4, template="none", batch_cap=2,
                     page_size=16, pool_pages=16)
    comp.attach()
    th = threading.Thread(target=comp.run_continuous, daemon=True,
                          kwargs={"idle_timeout_ms": 20,
                                  "stop_after": 240.0})
    th.start()
    try:
        _ask(store, "modest", 4)
        _answered(comp, 1)
        assert comp.stats.answer_tokens == 4 and asked == [(32, 36)]
        _ask(store, "greedy", prompt=PROMPT[::-1])
        _answered(comp, 2)
        assert asked[1:] == [(128, 128)]
    finally:
        comp.stop()
        th.join(timeout=30)
