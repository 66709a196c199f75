"""The generator's parts: seeding, the arrival loops and their lateness
arithmetic, driven against a stand-in call (no daemon, no JAX).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
(the benchmark's own tests, run by hand: not part of the repo's tier-1)."""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import traffic as T  # noqa: E402

queries = T.part("payloads", "queries")
poisson = T.part("loops", "open_poisson")
closed = T.part("loops", "closed_clients")


class StandIn:
    """A call that takes `ms` and fails every `fail_every`-th request."""

    def __init__(self, ms=2.0, fail_every=0):
        self.ms, self.fail_every, self.seen = ms, fail_every, []

    def request(self, i, client, rec):
        time.sleep(self.ms / 1e3)
        self.seen.append((i, client))
        return not (self.fail_every and i % self.fail_every == 0)


def test_parts_are_found_by_name_and_unknown_ones_refused():
    assert hasattr(T.part("calls", "submit_search"), "Call")
    assert hasattr(T.part("prepare", "unit_rows"), "prepare")
    with pytest.raises(ValueError):
        T.part("loops", "no_such_loop")


def test_poisson_same_gaps_other_order():
    a = poisson.poisson_due_times(200.0, 10.0, 0, 1)
    b = poisson.poisson_due_times(200.0, 10.0, 0, 2**31 + 2)
    assert len(a) == len(b) == 2000
    assert a[0] == 0.0 and a[-1] < 10.0
    ga, gb = np.diff(a), np.diff(b)
    assert not np.allclose(ga, gb)
    # the same multiset of gaps but for the one the permutation put first
    assert abs(np.sort(ga).sum() - np.sort(gb).sum()) < 0.1
    assert (np.diff(a) >= 0).all()
    assert np.allclose(a, poisson.poisson_due_times(200.0, 10.0, 0, 1))


def test_lateness_arithmetic():
    due = np.asarray([0.0, 0.1, 0.2, 0.3])
    sent = due + np.asarray([0.001, 0.002, 0.003, 0.010])
    late = poisson.lateness_ms(due, sent)
    assert late["n"] == 4
    assert abs(late["max"] - 10.0) < 1e-9
    assert abs(late["p50"] - 2.5) < 1e-9


def test_open_loop_sends_every_due_request_and_times_from_due():
    call = StandIn(ms=3.0, fail_every=50)
    mix = {"rate_per_s": 100.0, "threads": 8, "shape_seed": 0}
    res = poisson.run(call, mix, 1.0, seed=7, start_at=1000)
    assert res["attempted"] == 100 == len(res["records"])
    assert sorted(i for i, _ in call.seen) == list(range(1000, 1100))
    assert res["failed"] == 2 and res["completed"] == 98   # 1000, 1050
    assert all(r["ms"] >= 3.0 for r in res["records"])
    assert res["lateness_ms"]["n"] == 100 and res["lateness_ms"]["p50"] < 50


def test_closed_loop_counts_what_finished_inside_the_window():
    call = StandIn(ms=5.0)
    ticks = []
    res = closed.run(call, {"clients": 4}, 0.5, seed=0, start_at=0,
                     on_tick=ticks.append)
    assert res["failed"] == 0 and res["attempted"] == len(res["records"])
    # a request that began inside the window and ended after it is
    # attempted, not completed
    assert res["attempted"] - 4 <= res["completed"] <= res["attempted"]
    assert 4 * 40 <= res["attempted"] <= 4 * 100
    assert {c for _, c in call.seen} == {0, 1, 2, 3}
    # client c walks c, c+4, c+8, ... of the pool
    assert all(i % 4 == c for i, c in call.seen)
    assert ticks and ticks[-1] >= 0.45


def test_queries_are_unit_and_seeded():
    rng = np.random.default_rng(0)
    v = rng.standard_normal((50, 16)).astype(np.float32)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    spec = {"pool": 10, "noise": 0.05}
    q1 = queries.make_queries(spec, 2**31 + 9, v, np.arange(50))
    q2 = queries.make_queries(spec, 2**31 + 9, v, np.arange(50))
    assert np.allclose(q1, q2)
    assert not np.allclose(q1, queries.make_queries(spec, 8, v,
                                                    np.arange(50)))
    assert np.allclose(np.linalg.norm(q1, axis=1), 1.0, atol=1e-5)
    # even queries sit on a stored row: a clear top-1
    assert ((v @ q1[0::2].T).max(axis=0) > 0.95).all()


def test_roofline_credits_stored_rows_only():
    import work
    _, b1 = work.topk_scan(1000, 768, 32)
    _, b2 = work.topk_scan(2000, 768, 32)
    assert b1 == 4.0 * 1000 * 768 + 4.0 * 1000 + 4.0 * 32 * 768
    assert b2 > 1.9 * b1
