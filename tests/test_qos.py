"""Multi-tenant QoS tier: admission policy units, deadline fast-fail
on all three lanes, weighted fairness under 10:1 offered-load skew,
typed shedding (overloaded + retry_after_ms) and shed-then-admit
recovery, the bounded join-backpressure memo, the slow:<ms>:<p> fault
action, the shared client retry wrapper, the open-loop loadgen, and
the chaos-under-load scenario (supervised full stack + SPTPU_FAULT
lane kill mid-run, zero admitted-request loss) — `make qos-check`
runs the fast tier."""
import json
import threading
import time

import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.client import (call_with_retries,
                                           submit_completion)
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.engine.embedder import Embedder
from libsplinter_tpu.engine.qos import (AdmissionController,
                                        TenantLedger, WaitingRow,
                                        parse_tenant_weights)
from libsplinter_tpu.engine.searcher import Searcher, submit_search
from libsplinter_tpu.utils import faults


# ---------------------------------------------------------------- policy

class TestAdmissionController:
    def test_expired_partition(self):
        c = AdmissionController()
        plan = c.plan([WaitingRow("a", 1, deadline=10.0),
                       WaitingRow("b", 1, deadline=2000.0),
                       WaitingRow("c", 1)], 8, now=1000.0)
        assert [r.item for r in plan.expired] == ["a"]
        assert [r.item for r in plan.admit] == ["b", "c"]
        assert not plan.shed and not plan.deferred

    def test_shed_beyond_high_water(self):
        c = AdmissionController(high_water=3)
        rows = [WaitingRow(i, 0) for i in range(10)]
        plan = c.plan(rows, 2)
        assert len(plan.admit) == 2
        assert len(plan.deferred) == 3
        assert len(plan.shed) == 5

    def test_no_high_water_never_sheds(self):
        c = AdmissionController()
        plan = c.plan([WaitingRow(i, 0) for i in range(10)], 2)
        assert len(plan.deferred) == 8 and not plan.shed

    def test_fair_interleave_two_tenants(self):
        c = AdmissionController()
        rows = [WaitingRow(f"a{i}", 1) for i in range(20)] \
            + [WaitingRow(f"b{i}", 2) for i in range(2)]
        plan = c.plan(rows, 6)
        # the minority tenant's two requests both make the admit set
        assert sum(1 for r in plan.admit if r.tenant == 2) == 2

    def test_weighted_share_converges(self):
        # tenant 1 weighted 3x tenant 2; both saturate.  Across many
        # drains the admitted ratio lands within 2x of 3:1.
        c = AdmissionController(weights={1: 3.0, 2: 1.0})
        served = {1: 0, 2: 0}
        for _ in range(40):
            rows = [WaitingRow(("t1", i), 1) for i in range(20)] \
                + [WaitingRow(("t2", i), 2) for i in range(20)]
            plan = c.plan(rows, 8)
            for r in plan.admit:
                served[r.tenant] += 1
        ratio = served[1] / served[2]
        assert 1.5 <= ratio <= 6.0, served

    def test_starved_tenant_leads_next_drain(self):
        # stride state persists: a tenant present-but-denied in one
        # drain keeps its low pass and leads the next one
        c = AdmissionController()
        rows = [WaitingRow(f"a{i}", 1) for i in range(4)] \
            + [WaitingRow("b0", 2)]
        plan = c.plan(rows, 1)
        assert plan.admit[0].tenant == 1      # tie broke to tenant 1
        rows = [WaitingRow(f"a{i}", 1) for i in range(1, 4)] \
            + [WaitingRow("b0", 2)]
        plan = c.plan(rows, 1)
        assert plan.admit[0].item == "b0"     # denied tenant leads

    def test_idle_tenant_banks_no_priority(self):
        c = AdmissionController()
        for _ in range(10):
            c.plan([WaitingRow("a", 1)], 1)
        # tenant 2 was idle throughout; when it arrives it may lead
        # one admission but must not monopolize a saturated drain
        rows = [WaitingRow(f"a{i}", 1) for i in range(10)] \
            + [WaitingRow(f"b{i}", 2) for i in range(10)]
        plan = c.plan(rows, 10)
        t1 = sum(1 for r in plan.admit if r.tenant == 1)
        assert 3 <= t1 <= 7, plan.admit

    def test_idle_after_heavy_service_no_monopoly(self):
        # the review repro: tenant 2 served once, goes idle; tenant 1
        # then serves heavily ALONE.  When tenant 2 returns under
        # saturation it must compete equally — neither monopolizing
        # (banked priority) nor being punished for tenant 1's
        # uncontended service
        c = AdmissionController()
        c.plan([WaitingRow("b0", 2)], 1)      # t2 served, goes idle
        for r in range(100):
            c.plan([WaitingRow(f"a{r}-{i}", 1) for i in range(10)], 4)
        rows = [WaitingRow(f"a{i}", 1) for i in range(20)] \
            + [WaitingRow(f"b{i}", 2) for i in range(20)]
        plan = c.plan(rows, 10)
        t1 = sum(1 for r in plan.admit if r.tenant == 1)
        assert 3 <= t1 <= 7, plan.admit

    def test_zero_capacity_still_expires_and_sheds(self):
        c = AdmissionController(high_water=1)
        plan = c.plan([WaitingRow("a", 1, deadline=1.0),
                       WaitingRow("b", 1), WaitingRow("c", 1)],
                      0, now=5.0)
        assert [r.item for r in plan.expired] == ["a"]
        assert not plan.admit
        assert len(plan.deferred) == 1 and len(plan.shed) == 1

    def test_parse_tenant_weights(self):
        assert parse_tenant_weights("1:3,2:1.5") == {1: 3.0, 2: 1.5}
        assert parse_tenant_weights(None) is None
        assert parse_tenant_weights("") is None
        with pytest.raises(ValueError):
            parse_tenant_weights("1=3")
        with pytest.raises(ValueError):
            parse_tenant_weights("1:0")

    def test_ledger(self):
        led = TenantLedger()
        led.bump(1, "admitted")
        led.bump(1, "served_tokens", 12)
        led.bump(2, "shed")
        snap = led.snapshot()
        assert snap["1"]["admitted"] == 1
        assert snap["1"]["served_tokens"] == 12
        assert snap["2"]["shed"] == 1
        assert snap["2"]["deadline_expired"] == 0


# ---------------------------------------------------------------- wire

class TestProtocolQoS:
    def test_tenant_label_round_trip(self, store):
        store.set("r", "x")
        P.stamp_tenant(store, "r", 7)
        assert P.read_tenant(store.labels("r")) == 7
        P.stamp_tenant(store, "r", 3)        # replaces, not ORs
        assert P.read_tenant(store.labels("r")) == 3
        with pytest.raises(ValueError):
            P.tenant_label(16)

    def test_deadline_stamp_round_trip(self, store):
        store.set("r", "x")
        idx = store.find_index("r")
        assert P.stamp_deadline(store, "r", 123.5)
        assert store.labels("r") & P.LBL_DEADLINE
        assert P.read_deadline(store, idx,
                               epoch=store.epoch_at(idx)) == 123.5
        # a rewrite invalidates the stamp (epoch moved)
        store.set("r", "y")
        assert P.read_deadline(store, idx,
                               epoch=store.epoch_at(idx)) is None
        # the stale stamp was consumed
        assert P.read_deadline(store, idx) is None

    def test_error_payloads(self):
        rec = P.parse_error_payload(P.overloaded_payload(350))
        assert rec == {"err": "overloaded", "retry_after_ms": 350}
        assert P.parse_error_payload(
            P.DEADLINE_EXPIRED_DIAGNOSTIC)["err"] == "deadline_expired"
        assert P.parse_error_payload(b"a normal completion") is None
        assert P.parse_error_payload(b"{not json") is None
        assert P.parse_error_payload(b'{"no_err": 1}') is None


# ---------------------------------------------------------------- faults

class TestSlowFaultAction:
    def test_slow_fires_probabilistically_with_jitter(self, monkeypatch):
        monkeypatch.setenv("SPTPU_FAULT_SEED", "11")
        faults.arm("x.s:slow:30:0.5")
        try:
            t0 = time.perf_counter()
            for _ in range(20):
                faults.fault("x.s")
            wall_ms = (time.perf_counter() - t0) * 1e3
            st = faults.stats()["x.s"]
            assert st["hits"] == 20
            assert 0 < st["fired"] < 20       # p gates inside the hits
            # each firing sleeps 15-30 ms
            assert wall_ms >= st["fired"] * 15 * 0.9
            assert st["spec"] == "x.s:slow:30:0.5"
            faults.arm(st["spec"])            # spec round-trips
        finally:
            faults.disarm()

    def test_slow_composes_with_hit_window(self, monkeypatch):
        monkeypatch.setenv("SPTPU_FAULT_SEED", "3")
        faults.arm("x.s:slow:5:1@2-3")
        try:
            for _ in range(6):
                faults.fault("x.s")
            assert faults.stats()["x.s"]["fired"] == 2
        finally:
            faults.disarm()

    def test_bad_slow_specs_fail_loudly(self):
        for bad in ("x:slow", "x:slow:abc:0.5", "x:slow:10:0",
                    "x:slow:10:2", "x:slow:0:0.5"):
            with pytest.raises(faults.FaultSpecError):
                faults.arm(bad)
        faults.disarm()


# ---------------------------------------------------------------- client

class TestRetryWrapper:
    def test_honors_retry_after_and_succeeds(self):
        calls = []

        def attempt(left_ms):
            calls.append(left_ms)
            if len(calls) < 3:
                return P.overloaded_record(20)
            return {"ok": True}

        t0 = time.monotonic()
        out = call_with_retries(attempt, timeout_ms=5000)
        assert out == {"ok": True} and len(calls) == 3
        # two waits of >= ~10ms (jitter floor 0.5x) happened
        assert (time.monotonic() - t0) >= 0.02

    def test_returns_overloaded_at_deadline(self):
        out = call_with_retries(
            lambda left: P.overloaded_record(10_000),
            timeout_ms=80)
        assert out["err"] == "overloaded"

    def test_terminal_results_not_retried(self):
        calls = []

        def attempt(left_ms):
            calls.append(1)
            return {"err": "deadline_expired"}

        out = call_with_retries(attempt, timeout_ms=500)
        assert out["err"] == "deadline_expired" and len(calls) == 1

    def test_lane_down_fails_fast(self, store):
        # a fresh supervisor heartbeat marking the lane down vetoes
        # the attempt entirely
        P.publish_heartbeat(store, P.KEY_SUPERVISOR_STATS, {
            "lanes": {"searcher": {"state": "down"}}})
        calls = []
        out = call_with_retries(lambda left: calls.append(1),
                                timeout_ms=500, store=store,
                                lane="searcher")
        assert out is None and not calls


# ---------------------------------------------------------------- searcher

def _seed_docs(store, n=8):
    rng = np.random.default_rng(0)
    for i in range(n):
        v = rng.standard_normal(store.vec_dim).astype(np.float32)
        store.set(f"doc{i}", f"doc {i}")
        store.vec_set(f"doc{i}", v / np.linalg.norm(v))


def _search_req(store, key, k=3, tenant=0, deadline=None):
    params = {"k": k}
    if deadline is not None:
        params["deadline"] = deadline
    store.set(key, json.dumps(params))
    qv = np.zeros(store.vec_dim, np.float32)
    qv[0] = 1.0
    store.vec_set(key, qv)
    if tenant:
        P.stamp_tenant(store, key, tenant)
    store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
    store.bump(key)


def _search_result(store, key):
    return json.loads(store.get(
        P.search_result_key(store.find_index(key))).rstrip(b"\0"))


class TestSearcherQoS:
    def test_deadline_expired_fast_fail(self, store):
        _seed_docs(store)
        sr = Searcher(store)
        sr.attach()
        _search_req(store, "q1", deadline=time.time() - 1.0)
        _search_req(store, "q2", deadline=time.time() + 60.0)
        sr.run_once()
        assert _search_result(store, "q1")["err"] == "deadline_expired"
        assert not store.labels("q1") & P.LBL_SEARCH_REQ
        assert "err" not in _search_result(store, "q2")
        assert sr.stats.deadline_expired == 1

    def test_deadline_via_companion_stamp(self, store):
        _seed_docs(store)
        sr = Searcher(store)
        sr.attach()
        _search_req(store, "q1")
        P.stamp_deadline(store, "q1", time.time() - 1.0)
        sr.run_once()
        assert _search_result(store, "q1")["err"] == "deadline_expired"

    def test_shed_then_admit_after_drain(self, store):
        _seed_docs(store)
        sr = Searcher(store, admit_cap=2, queue_high_water=1,
                      retry_after_ms=123)
        sr.attach()
        for i in range(6):
            _search_req(store, f"q{i}", tenant=1)
        served = sr.run_once()
        assert served == 2
        shed = [i for i in range(6)
                if (store.labels(f"q{i}") & P.LBL_SEARCH_REQ) == 0
                and _search_result(store, f"q{i}").get("err")
                == "overloaded"]
        assert len(shed) == 3 and sr.stats.shed == 3
        for i in shed:
            assert _search_result(store,
                                  f"q{i}")["retry_after_ms"] == 123
        # one deferred request still waits; the next drain admits it
        waiting = [i for i in range(6)
                   if store.labels(f"q{i}") & P.LBL_SEARCH_REQ]
        assert len(waiting) == 1 and sr._had_deferred
        assert sr.run_once() == 1
        assert "err" not in _search_result(store, f"q{waiting[0]}")
        # drained: a fresh request admits cleanly (shed-then-admit)
        _search_req(store, "fresh", tenant=2)
        assert sr.run_once() == 1
        assert "err" not in _search_result(store, "fresh")
        assert sr.tenants.get(1, "shed") == 3

    def test_fairness_10_to_1(self, store):
        """The acceptance property: a 10:1 offered-load tenant pair
        under equal weights both make progress, the starved tenant
        within 2x of its fair (half) share."""
        _seed_docs(store)
        sr = Searcher(store, admit_cap=4)
        sr.attach()
        n_heavy, n_light = 0, 0
        for round_ in range(6):
            for j in range(10):
                _search_req(store, f"h{round_}-{j}", tenant=1)
            _search_req(store, f"l{round_}", tenant=2)
            sr.run_once()
        heavy = sr.tenants.get(1, "admitted")
        light = sr.tenants.get(2, "admitted")
        assert light + heavy > 0
        # all 6 light requests served despite 10x heavy pressure;
        # fair share at equal weights is half the admitted capacity,
        # and the light tenant's whole offered load fits under it
        assert light == 6, (heavy, light)
        assert heavy >= light            # unused share flowed onward

    def test_heartbeat_carries_tenants_and_qos(self, store_2k):
        store = store_2k          # the whole heartbeat has to fit
        _seed_docs(store)
        sr = Searcher(store, admit_cap=2, queue_high_water=0)
        sr.attach()
        for i in range(4):
            _search_req(store, f"q{i}", tenant=3)
        sr.run_once()
        sr.publish_stats()
        snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert snap["qos"]["admit_cap"] == 2
        assert snap["qos"]["queue_high_water"] == 0
        assert snap["tenants"]["3"]["admitted"] == 2
        assert snap["tenants"]["3"]["shed"] == 2
        assert snap["shed"] == 2

    def test_submit_search_retries_through_shed(self, store):
        """Client integration: a shed submit retries after the hint
        and lands once the queue drains."""
        _seed_docs(store)
        sr = Searcher(store, admit_cap=1, queue_high_water=0,
                      retry_after_ms=30)
        sr.attach()
        t = threading.Thread(
            target=sr.run,
            kwargs=dict(idle_timeout_ms=10, stop_after=30.0))
        t.start()
        try:
            results = {}
            qv = np.zeros(store.vec_dim, np.float32)
            qv[0] = 1.0
            for i in range(4):
                # submit_search's contract: the key's vector lane
                # already holds the embedded query
                store.set(f"c{i}", "query")
                store.vec_set(f"c{i}", qv)

            def client(name, tenant):
                results[name] = submit_search(
                    store, name, 3, timeout_ms=8000, tenant=tenant)

            ths = [threading.Thread(target=client,
                                    args=(f"c{i}", 1 + i % 2))
                   for i in range(4)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(timeout=20)
            ok = [r for r in results.values()
                  if r is not None and "err" not in r]
            assert len(ok) == 4, results
        finally:
            sr.stop()
            t.join(timeout=10)


# ---------------------------------------------------------------- embedder

def _embed_req(store, key, text, tenant=0, deadline=None):
    store.set(key, text)
    if tenant:
        P.stamp_tenant(store, key, tenant)
    if deadline is not None:
        P.stamp_deadline(store, key, deadline)
    store.label_or(key, P.LBL_EMBED_REQ | P.LBL_WAITING)
    store.bump(key)


def _fake_encoder(store):
    def enc(texts):
        out = np.zeros((len(texts), store.vec_dim), np.float32)
        for i in range(len(texts)):
            out[i, 0] = 1.0
        return out
    return enc


class TestEmbedderQoS:
    def test_deadline_expired_fast_fail(self, store):
        emb = Embedder(store, encoder_fn=_fake_encoder(store),
                       max_ctx=64)
        emb.attach()
        _embed_req(store, "e1", "expired", tenant=1,
                   deadline=time.time() - 1.0)
        _embed_req(store, "e2", "live", tenant=1,
                   deadline=time.time() + 60.0)
        emb.run_once()
        assert not store.labels("e1") & P.LBL_EMBED_REQ
        assert np.abs(store.vec_get("e1")).max() == 0   # no vector
        assert np.abs(store.vec_get("e2")).max() > 0
        assert emb.stats.deadline_expired == 1
        assert emb.tenants.get(1, "deadline_expired") == 1
        # the deadline stamp was consumed, not leaked
        assert P.deadline_key(store.find_index("e1")) not in store

    def test_shed_then_admit(self, store):
        emb = Embedder(store, encoder_fn=_fake_encoder(store),
                       max_ctx=64, admit_cap=2, queue_high_water=1)
        emb.attach()
        for i in range(6):
            _embed_req(store, f"e{i}", f"text {i}", tenant=1)
        emb.run_once()
        assert emb.stats.shed == 3 and emb.stats.deferred == 1
        done = sum(1 for i in range(6)
                   if np.abs(store.vec_get(f"e{i}")).max() > 0)
        assert done == 2
        # deferred row still pending; the next drain embeds it
        emb.run_once()
        done = sum(1 for i in range(6)
                   if np.abs(store.vec_get(f"e{i}")).max() > 0)
        assert done == 3
        # drained lane admits fresh work (shed-then-admit)
        _embed_req(store, "fresh", "fresh text", tenant=2)
        emb.run_once()
        assert np.abs(store.vec_get("fresh")).max() > 0

    def test_fairness_10_to_1(self, store):
        emb = Embedder(store, encoder_fn=_fake_encoder(store),
                       max_ctx=64, admit_cap=4)
        emb.attach()
        for round_ in range(5):
            for j in range(10):
                _embed_req(store, f"h{round_}-{j}", f"heavy {j}",
                           tenant=1)
            _embed_req(store, f"l{round_}", "light", tenant=2)
            emb.run_once()
        light = sum(1 for r in range(5)
                    if np.abs(store.vec_get(f"l{r}")).max() > 0)
        assert light == 5                # every light round served
        assert emb.tenants.get(1, "admitted") >= 5

    def test_rejected_reembed_zeroes_stale_vector(self, store):
        """The review repro: a RE-embed request shed (or expired)
        must scrub the slot's PREVIOUS vector — otherwise the cleared
        label + surviving stale vector is indistinguishable from a
        successful embed of the new text."""
        emb = Embedder(store, encoder_fn=_fake_encoder(store),
                       max_ctx=64)
        emb.attach()
        _embed_req(store, "doc", "version one")
        emb.run_once()
        assert np.abs(store.vec_get("doc")).max() > 0
        # re-embed with an already-expired deadline: rejected
        _embed_req(store, "doc", "version two", tenant=1,
                   deadline=time.time() - 1.0)
        emb.run_once()
        assert not store.labels("doc") & P.LBL_EMBED_REQ
        assert np.abs(store.vec_get("doc")).max() == 0
        # and the shed path scrubs too
        emb2 = Embedder(store, encoder_fn=_fake_encoder(store),
                        max_ctx=64, admit_cap=1, queue_high_water=0)
        emb2.attach()
        _embed_req(store, "doc", "version three", tenant=1)
        _embed_req(store, "other", "filler a", tenant=1)
        _embed_req(store, "other2", "filler b", tenant=1)
        emb2.run_once()
        shed_keys = [k for k in ("doc", "other", "other2")
                     if not store.labels(k) & P.LBL_EMBED_REQ
                     and np.abs(store.vec_get(k)).max() == 0]
        assert len(shed_keys) == emb2.stats.shed == 2

    def test_deferred_request_keeps_trace_stamp(self, store):
        """A request deferred by admission keeps its trace stamp (and
        LBL_TRACED) for the drain that actually serves it — consuming
        at gather lost the flight record of every waiting request."""
        _seed_docs(store)
        sr = Searcher(store, admit_cap=1)
        sr.attach()
        _search_req(store, "q0", tenant=1)
        _search_req(store, "q1", tenant=1)
        tid = P.stamp_trace(store, "q1")
        assert tid is not None
        sr.run_once()                  # q0 admitted, q1 deferred
        waiting = [k for k in ("q0", "q1")
                   if store.labels(k) & P.LBL_SEARCH_REQ]
        assert len(waiting) == 1
        w = waiting[0]
        assert store.labels(w) & P.LBL_TRACED or w != "q1"
        if w == "q1":
            idx = store.find_index("q1")
            assert P.trace_stamp_key(idx) in store
        sr.run_once()                  # now served: stamp consumed
        idx = store.find_index("q1")
        assert P.trace_stamp_key(idx) not in store
        assert not store.labels("q1") & P.LBL_TRACED

    def test_untagged_traffic_is_pass_through(self, store):
        # no QoS config, no tenant/deadline stamps: the admission hook
        # must not change behavior or touch the planner
        emb = Embedder(store, encoder_fn=_fake_encoder(store),
                       max_ctx=64)
        emb.attach()
        for i in range(5):
            _embed_req(store, f"e{i}", f"text {i}")
        n = emb.run_once()
        assert n == 5
        assert emb.stats.deferred == 0 and emb.stats.shed == 0
        assert not emb.tenants.snapshot()


# ---------------------------------------------------------------- completer

def _infer_req(store, key, prompt, tenant=0, deadline=None):
    store.set(key, prompt)
    if tenant:
        P.stamp_tenant(store, key, tenant)
    if deadline is not None:
        P.stamp_deadline(store, key, deadline)
    store.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
    store.bump(key)


def _gen(prompt):
    yield b"pong"


class TestCompleterQoS:
    def test_deadline_expired_fast_fail(self, store):
        comp = Completer(store, generate_fn=_gen, template="none")
        comp.attach()
        _infer_req(store, "c1", "expired", tenant=2,
                   deadline=time.time() - 1.0)
        _infer_req(store, "c2", "live", tenant=2,
                   deadline=time.time() + 60.0)
        comp.run_once()
        labels = store.labels("c1")
        assert labels & P.LBL_READY
        assert not labels & (P.LBL_INFER_REQ | P.LBL_SERVICING)
        rec = P.parse_error_payload(store.get("c1"))
        assert rec["err"] == "deadline_expired"
        assert store.get_str("c2").endswith("pong")
        assert comp.stats.deadline_expired == 1
        assert comp.tenants.get(2, "deadline_expired") == 1
        assert comp.tenants.get(2, "served_tokens") >= 1

    def test_shed_with_typed_overloaded(self, store):
        comp = Completer(store, generate_fn=_gen, template="none",
                         queue_high_water=2, retry_after_ms=77)
        comp.attach()
        for i in range(6):
            _infer_req(store, f"c{i}", f"prompt {i}", tenant=1)
        comp.run_once()
        shed = []
        for i in range(6):
            rec = P.parse_error_payload(store.get(f"c{i}"))
            if rec and rec["err"] == "overloaded":
                assert rec["retry_after_ms"] == 77
                assert store.labels(f"c{i}") & P.LBL_READY
                shed.append(i)
        assert len(shed) == 2 and comp.stats.shed == 2
        # two admitted now, two deferred for the next drain
        assert comp.stats.deferred == 2
        comp.run_once()
        done = sum(1 for i in range(6)
                   if store.get_str(f"c{i}").endswith("pong"))
        assert done == 4
        # drained: fresh work admits cleanly
        _infer_req(store, "fresh", "hello", tenant=3)
        comp.run_once()
        assert store.get_str("fresh").endswith("pong")

    def test_fair_order_across_tenants(self, store):
        served = []

        def recording_gen(prompt):
            served.append(prompt)
            yield b"."

        comp = Completer(store, generate_fn=recording_gen,
                         template="none")
        comp.attach()
        for i in range(6):
            _infer_req(store, f"h{i}", f"heavy{i}", tenant=1)
        _infer_req(store, "lite", "light0", tenant=2)
        comp.run_once()
        # the single light request is served before the heavy tail
        assert "light0" in served[0] or "light0" in served[1], served

    def test_bp_memo_bounded(self, store):
        """The satellite: memo entries whose slot epoch moved or whose
        request label is gone are evicted by the sweep."""
        comp = Completer(store, generate_fn=_gen, template="none")
        comp.attach()
        for i in range(4):
            _infer_req(store, f"m{i}", f"prompt {i}")
            comp._bp_memo[store.find_index(f"m{i}")] = (
                store.epoch_at(store.find_index(f"m{i}")), 999)
        assert len(comp._bp_memo) == 4
        # m0: rewritten (epoch moves); m1: served (label cleared)
        store.set("m0", "rewritten")
        store.label_clear("m1", P.LBL_INFER_REQ | P.LBL_WAITING)
        dropped = comp._sweep_bp_memo()
        assert dropped == 2 and len(comp._bp_memo) == 2
        # hard cap backstop
        for i in range(5000):
            comp._bp_memo[10_000 + i] = (0, 1)
        comp._sweep_bp_memo()
        assert len(comp._bp_memo) <= 4096

    def test_submit_completion_client(self, store):
        comp = Completer(store, generate_fn=_gen, template="none")
        comp.attach()
        t = threading.Thread(
            target=comp.run,
            kwargs=dict(idle_timeout_ms=10, stop_after=20.0))
        t.start()
        try:
            out = submit_completion(store, "cq", "hello",
                                    timeout_ms=8000, tenant=4)
            assert isinstance(out, bytes) and out.endswith(b"pong")
        finally:
            comp.stop()
            t.join(timeout=10)

    def test_submit_completion_clears_stale_ready(self, store):
        """A recycled key (or a retry after a shed) may still carry
        READY from its previous terminal state — the submit must clear
        it or the wait loop returns the raw prompt instantly."""
        comp = Completer(store, generate_fn=_gen, template="none")
        comp.attach()
        store.set("cq", "old result")
        store.label_or("cq", P.LBL_READY)
        t = threading.Thread(
            target=comp.run,
            kwargs=dict(idle_timeout_ms=10, stop_after=20.0))
        t.start()
        try:
            out = submit_completion(store, "cq", "hello",
                                    timeout_ms=8000)
            assert isinstance(out, bytes) and out.endswith(b"pong")
        finally:
            comp.stop()
            t.join(timeout=10)

    def test_submit_completion_surfaces_typed_errors(self, store):
        comp = Completer(store, generate_fn=_gen, template="none",
                         queue_high_water=0, retry_after_ms=40)
        comp.attach()
        # saturate: high_water=0 sheds everything beyond the drain cap
        for i in range(3):
            _infer_req(store, f"bg{i}", "filler")
        out = submit_completion(store, "cq", "hello",
                                timeout_ms=250, retry=True)
        # nobody drains: timeout (None) — now drain once; the client's
        # record (if shed) is typed
        assert out is None
        comp.run_once()
        rec = P.parse_error_payload(store.get("cq"))
        if rec is not None:
            assert rec["err"] == "overloaded"


# ---------------------------------------------------------------- heartbeat

def test_metrics_renders_tenant_series(store_2k, capsys):
    store = store_2k              # the whole heartbeat has to fit
    from libsplinter_tpu.cli.main import Session
    from libsplinter_tpu.cli.metrics import cmd_metrics

    _seed_docs(store)
    sr = Searcher(store, admit_cap=1, queue_high_water=0)
    sr.attach()
    for i in range(3):
        _search_req(store, f"q{i}", tenant=5)
    sr.run_once()
    sr.publish_stats()
    ses = Session(store.name)
    ses._store = store
    cmd_metrics(ses, [])
    out = capsys.readouterr().out
    assert 'sptpu_searcher_tenant_admitted{' in out
    assert 'tenant="5"' in out
    assert "sptpu_searcher_shed" in out
    assert "sptpu_searcher_qos_retry_after_ms" in out
    ses._store = None                 # fixture owns the handle


# ---------------------------------------------------------------- loadgen

def _lane_threads(store, stop_after=60.0, **searcher_kw):
    def enc(texts):
        out = np.zeros((len(texts), store.vec_dim), np.float32)
        for i, t in enumerate(texts):
            out[i, hash(t) % store.vec_dim] = 1.0
        return out

    emb = Embedder(store, encoder_fn=enc, max_ctx=64)
    emb.attach()
    sr = Searcher(store, **searcher_kw)
    sr.attach()
    comp = Completer(store, generate_fn=lambda p: iter([b"answer"]),
                     template="none")
    comp.attach()
    daemons = (emb, sr, comp)
    ths = [threading.Thread(
        target=d.run, kwargs=dict(idle_timeout_ms=10,
                                  stop_after=stop_after), daemon=True)
        for d in daemons]
    for t in ths:
        t.start()
    return daemons, ths


class TestLoadgen:
    def test_open_loop_mixed_run(self, store):
        from libsplinter_tpu.cli.loadgen import (LoadGenerator,
                                                 TenantSpec,
                                                 evaluate_slo)

        daemons, ths = _lane_threads(store)
        try:
            gen = LoadGenerator(
                store,
                [TenantSpec(1, 12.0, deadline_ms=5000),
                 TenantSpec(2, 4.0, deadline_ms=5000)],
                duration_s=1.5, corpus=8, seed=3)
            rep = gen.run()
            assert rep["issued"] > 5
            assert rep["lost"] == 0
            assert rep["ok"] >= rep["issued"] * 0.8, rep
            # per-tenant per-lane quantiles sourced from the log
            # histograms
            t1 = rep["per_tenant"]["1"]
            assert any("p99_ms" in row for row in t1.values())
            assert evaluate_slo(rep, goodput=0.5) == []
            assert evaluate_slo(rep, p99_ms=0.0001) != []
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)

    def test_rag_churn_scenario(self, store):
        from libsplinter_tpu.cli.loadgen import (LoadGenerator,
                                                 TenantSpec)

        daemons, ths = _lane_threads(store)
        try:
            gen = LoadGenerator(
                store, [TenantSpec(1, 6.0, deadline_ms=6000)],
                duration_s=1.5, corpus=8, seed=5,
                scenario="rag-churn")
            rep = gen.run()
            assert rep["scenario"] == "rag-churn"
            assert rep["lost"] == 0
            assert rep["ok"] >= max(1, rep["issued"] - 1), rep
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)

    def test_tenants_flag_validated_at_parse(self, store):
        from libsplinter_tpu.cli.loadgen import cmd_loadgen
        from libsplinter_tpu.cli.main import CliError, Session

        ses = Session(store.name)
        ses._store = store
        with pytest.raises(CliError):
            cmd_loadgen(ses, ["--tenants", "16", "--duration", "0.1"])
        ses._store = None             # fixture owns the handle

    def test_fixed_arrivals_deterministic_schedule(self, store):
        from libsplinter_tpu.cli.loadgen import (LoadGenerator,
                                                 TenantSpec)

        gen = LoadGenerator(store, [TenantSpec(1, 10.0)],
                            duration_s=1.0, arrivals="fixed", seed=1)
        sched = gen._schedule()
        # 0.1s stride inside 1s (float accumulation may land the last
        # arrival a hair under the cutoff)
        assert len(sched) in (9, 10)
        assert all(b[0] > a[0] for a, b in zip(sched, sched[1:]))


# ---------------------------------------------------------------- chaos

@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_under_load_rag_churn(store, monkeypatch):
    """The acceptance scenario: a `spt supervise`d full stack serves
    mixed 3-tenant open-loop rag-churn traffic while SPTPU_FAULT
    kills the searcher lane mid-run; the supervisor restarts it
    (fault stripped from the respawn), no admitted request is lost,
    and the post-restart SLOs hold."""
    from libsplinter_tpu.cli.loadgen import (LoadGenerator,
                                             TenantSpec, evaluate_slo)
    from libsplinter_tpu.engine.supervisor import Supervisor

    # the searcher's 3rd drain dies mid-gather — under rag-churn load
    # that is a crash with requests in every lane's queue
    monkeypatch.setenv("SPTPU_FAULT", "searcher.gather:crash@3")
    monkeypatch.setenv("SPTPU_FORCE_CPU", "1")
    sup = Supervisor(store.name,
                     lanes=("embedder", "searcher", "completer"),
                     store=store,
                     lane_args={
                         "completer": ["--max-new-tokens", "4"],
                     },
                     backoff_base_ms=100, backoff_max_ms=1500,
                     breaker_threshold=8, breaker_window_s=120,
                     startup_grace_s=300)
    t = threading.Thread(target=sup.run,
                         kwargs={"poll_interval_s": 0.1,
                                 "stop_after": 600.0})
    t.start()
    try:
        # wait for all three lanes to heartbeat before offering load
        deadline = time.monotonic() + 240
        keys = (P.KEY_EMBED_STATS, P.KEY_SEARCH_STATS,
                P.KEY_COMPLETE_STATS)
        while time.monotonic() < deadline:
            if all(P.heartbeat_live(store, k, max_age_s=30)
                   for k in keys):
                break
            time.sleep(0.5)
        else:
            pytest.fail("lanes never came up under supervision")

        tenants = [TenantSpec(1, 3.0, deadline_ms=60_000),
                   TenantSpec(2, 1.5, deadline_ms=60_000),
                   TenantSpec(3, 0.8, deadline_ms=60_000)]
        gen = LoadGenerator(store, tenants, duration_s=8.0,
                            corpus=8, seed=7, scenario="rag-churn",
                            drain_s=120.0)
        rep = gen.run()
        # the kill actually happened and the lane came back
        assert sup.lanes["searcher"].restarts >= 1, rep
        # zero admitted-request loss through the crash
        assert rep["lost"] == 0, rep
        # post-restart SLO: the run completes with real goodput
        violations = evaluate_slo(rep, goodput=0.9)
        assert not violations, (violations, rep)
        assert rep["ok"] >= 1
    finally:
        sup.stop()
        t.join(timeout=30)
        sup.shutdown()
