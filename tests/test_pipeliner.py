"""Pipeline-lane tier (`make pipeline-check`): sandbox containment
(hostile scripts die with typed records while sibling in-flight
scripts complete unharmed), the yielding-verb chain end-to-end against
a live in-process stack, per-tenant deadline enforcement observable in
`spt metrics`, the stored-script library + loadgen script scenarios,
the `pipeliner.exec` / `pipeliner.verb` fault sites (in-process
containment AND the supervised crash-recovery drill: stranded scripts
reclaimed + re-run, zero admitted loss), and the script-vs-client
chaining latency bar (rag-churn p50 >= 30% down)."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.client import submit_embed
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.engine.embedder import Embedder
from libsplinter_tpu.engine.pipeliner import (Pipeliner,
                                              consume_script_result,
                                              store_script,
                                              submit_script)
from libsplinter_tpu.engine.searcher import Searcher
from libsplinter_tpu.scripting.library import (SCRIPT_LIBRARY,
                                               seed_library)
from libsplinter_tpu.scripting.sandbox import (ScriptBudget,
                                               ScriptKilled,
                                               SandboxedRuntime)
from libsplinter_tpu.utils import faults

CHILD = os.path.join(os.path.dirname(__file__), "chaos_child.py")


@pytest.fixture(autouse=True)
def _no_faults():
    faults.disarm()
    yield
    faults.disarm()


def _pump_until(pl, pred, timeout_s=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        pl.pump()
        if pred():
            return True
        time.sleep(0.002)
    return False


def _submit(store, key, *, script=None, name=None, args=None,
            tenant=0, deadline_ts=None):
    """Non-blocking submit (the loadgen wire form) for tests that
    drive the pipeliner synchronously via pump()."""
    req: dict = {"args": list(args or [])}
    if script is not None:
        req["script"] = script
    else:
        req["name"] = name
    if deadline_ts is not None:
        req["deadline"] = round(deadline_ts, 6)
    store.set(key, json.dumps(req))
    if tenant:
        P.stamp_tenant(store, key, tenant)
    store.label_or(key, P.LBL_SCRIPT_REQ | P.LBL_WAITING)
    store.bump(key)
    return store.find_index(key)


def _result(store, key):
    try:
        raw = store.get(P.script_result_key(store.find_index(key)))
        return json.loads(raw.rstrip(b"\0"))
    except (KeyError, OSError, ValueError):
        return None


def _done(store, key):
    try:
        return not store.labels(key) & P.LBL_SCRIPT_REQ
    except KeyError:
        return True


# ------------------------------------------------------- sandbox units

class TestSandbox:
    def test_step_budget_kills_infinite_loop(self):
        rt = SandboxedRuntime(ScriptBudget(max_steps=20_000))
        with pytest.raises(ScriptKilled) as ei:
            rt.run("while true do end")
        assert ei.value.reason == "budget_exceeded"
        assert rt.kill_reason == "budget_exceeded"

    def test_pcall_cannot_swallow_the_kill(self):
        rt = SandboxedRuntime(ScriptBudget(max_steps=20_000))
        with pytest.raises(ScriptKilled):
            rt.run("while true do "
                   "pcall(function() while true do end end) end")

    def test_deadline_kills_mid_compute(self):
        rt = SandboxedRuntime(ScriptBudget(
            max_steps=100_000_000, deadline_ts=time.time() + 0.15))
        t0 = time.monotonic()
        with pytest.raises(ScriptKilled) as ei:
            rt.run("while true do end")
        assert ei.value.reason == "deadline_expired"
        assert time.monotonic() - t0 < 5.0

    def test_huge_allocation_guarded(self):
        from libsplinter_tpu.scripting.microlua import LuaError
        rt = SandboxedRuntime(ScriptBudget(max_str_len=4096))
        with pytest.raises(LuaError, match="string budget"):
            rt.run("return string.rep('x', 1000000)")

    def test_os_removed_io_absent(self):
        rt = SandboxedRuntime(ScriptBudget())
        assert rt.run("return type(os), type(io)") == ("nil", "nil")

    def test_coroutine_cap(self):
        rt = SandboxedRuntime(ScriptBudget(max_coroutines=4))
        out = rt.run("""
            local cos = {}
            for i = 1, 8 do
              local co = coroutine.create(function()
                coroutine.yield()
              end)
              local ok = pcall(coroutine.resume, co)
              cos[#cos + 1] = ok
            end
            local n = 0
            for i = 1, #cos do if cos[i] then n = n + 1 end end
            return n
        """)
        rt.close()
        assert out[0] <= 4


class TestSleepClamp:
    def test_lua_host_sleep_clamped(self, store):
        # satellite: scripting/lua_host.py _sleep used to honor any
        # float — with a budget it is clamped to max_sleep_s and the
        # remaining deadline
        from libsplinter_tpu.scripting.sandbox import \
            make_sandboxed_runtime
        rt = make_sandboxed_runtime(
            store, ScriptBudget(max_sleep_s=0.05))
        t0 = time.monotonic()
        rt.run("splinter.sleep(1e9)")
        assert time.monotonic() - t0 < 2.0

    def test_cli_lua_budget_knobs(self, store, capsys):
        from libsplinter_tpu.cli.main import CliError, Session
        from libsplinter_tpu.cli.script import cmd_lua

        ses = Session(store.name)
        ses._store = store
        # the CLI host accepts the lane's budget knobs and reports a
        # typed kill — CLI and lane sandbox semantics cannot drift
        with pytest.raises(CliError, match="budget_exceeded"):
            cmd_lua(ses, ["--max-steps", "20000", "-e",
                          "while true do end"])
        # sleep clamp rides the same flags
        t0 = time.monotonic()
        cmd_lua(ses, ["--max-sleep-s", "0.05", "-e",
                      "splinter.sleep(1e9) print('ok')"])
        assert time.monotonic() - t0 < 2.0
        assert "ok" in capsys.readouterr().out
        ses._store = None             # fixture owns the handle


# -------------------------------------------------- lane containment

class TestContainment:
    """Hostile scripts die typed; a sibling in-flight script is
    unharmed.  Each hostile case runs CONCURRENTLY with a friendly
    script awaiting a verb the test resolves afterward."""

    def _friendly(self, store, pl, key="friendly"):
        _submit(store, key,
                script="local ok = splinter.submit_embed("
                       "'fdoc', 'hello') return ok and 1 or 0")
        assert _pump_until(
            pl, lambda: any(r.await_ is not None
                            for r in pl.runs.values()), 5.0)
        return key

    def _resolve_embed(self, store, doc="fdoc"):
        # play the embedder: commit a vector and clear the label
        v = np.zeros(store.vec_dim, np.float32)
        v[0] = 1.0
        store.vec_set(doc, v)
        store.label_clear(doc, P.LBL_EMBED_REQ | P.LBL_WAITING)
        store.bump(doc)

    def test_infinite_loop_dies_sibling_completes(self, store):
        pl = Pipeliner(store, max_steps=30_000)
        pl.attach()
        fk = self._friendly(store, pl)
        _submit(store, "hostile", script="while true do end")
        assert _pump_until(pl, lambda: _done(store, "hostile"), 20.0)
        rec = _result(store, "hostile")
        assert rec["err"] == "budget_exceeded"
        assert pl.stats.killed_budget == 1
        self._resolve_embed(store)
        assert _pump_until(pl, lambda: _done(store, fk), 5.0)
        assert _result(store, fk)["ok"] is True

    def test_deep_recursion_dies_typed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "rec",
                script="local function f() return f() end f()")
        assert _pump_until(pl, lambda: _done(store, "rec"), 20.0)
        rec = _result(store, "rec")
        assert rec["err"] in ("script_error", "budget_exceeded")
        assert "overflow" in rec.get("detail", "") \
            or rec["err"] == "budget_exceeded"

    def test_huge_allocation_dies_typed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "alloc",
                script="return string.rep('x', 100000000)")
        assert _pump_until(pl, lambda: _done(store, "alloc"), 10.0)
        rec = _result(store, "alloc")
        assert rec["err"] == "script_error"
        assert "string budget" in rec["detail"]

    def test_giant_sleep_clamped_by_deadline(self, store):
        pl = Pipeliner(store, max_sleep_s=0.1)
        pl.attach()
        fk = self._friendly(store, pl)
        _submit(store, "sleeper",
                script="splinter.sleep(1e9) return 1")
        assert _pump_until(pl, lambda: _done(store, "sleeper"), 10.0)
        assert _result(store, "sleeper")["ok"] is True  # woke clamped
        self._resolve_embed(store)
        assert _pump_until(pl, lambda: _done(store, fk), 5.0)

    def test_verb_storm_dies_typed(self, store):
        pl = Pipeliner(store, max_verbs=8)
        pl.attach()
        _submit(store, "storm", script="""
            for i = 1, 100 do
              splinter.submit_embed("st" .. i, "x")
            end
            return 1
        """)

        def drive():
            # resolve each embed instantly so the storm keeps going
            for key in store.list():
                if key.startswith("st"):
                    labels = store.labels(key)
                    if labels & P.LBL_EMBED_REQ:
                        v = np.zeros(store.vec_dim, np.float32)
                        v[0] = 1.0
                        store.vec_set(key, v)
                        store.label_clear(
                            key, P.LBL_EMBED_REQ | P.LBL_WAITING)
            return _done(store, "storm")

        assert _pump_until(pl, drive, 20.0)
        rec = _result(store, "storm")
        assert rec["err"] == "budget_exceeded"
        assert "verb budget" in rec["detail"]
        assert pl.stats.killed_budget == 1

    def test_parse_error_typed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "bad", script="this is (( not lua")
        assert _pump_until(pl, lambda: _done(store, "bad"), 5.0)
        assert _result(store, "bad")["err"] == "script_error"
        assert pl.stats.parse_errors == 1

    def test_unknown_stored_script_typed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "ghost", name="no-such-script")
        assert _pump_until(pl, lambda: _done(store, "ghost"), 5.0)
        assert "unknown stored script" in \
            _result(store, "ghost")["detail"]

    def test_yield_outside_verb_typed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "yielder", script="coroutine.yield(42)")
        assert _pump_until(pl, lambda: _done(store, "yielder"), 5.0)
        rec = _result(store, "yielder")
        assert rec["err"] == "script_error"
        assert "yield outside" in rec["detail"]

    def test_exec_fault_raise_contained(self, store):
        # pipeliner.exec raise: ONE script fails typed, the sibling
        # admitted in the same drain completes
        faults.arm("pipeliner.exec:raise@1")
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "victim", script="return 1")
        _submit(store, "survivor", script="return 2")
        assert _pump_until(
            pl, lambda: _done(store, "victim")
            and _done(store, "survivor"), 10.0)
        recs = {_result(store, "victim")["err"] if
                _result(store, "victim").get("err") else "ok",
                "ok" if _result(store, "survivor").get("ok")
                else _result(store, "survivor")["err"]}
        # exactly one died on the injected exec fault
        assert "script_error" in recs or "ok" in recs
        both = [_result(store, "victim"), _result(store, "survivor")]
        assert sum(1 for r in both if r.get("ok")) == 1
        assert sum(1 for r in both
                   if r.get("err") == "script_error") == 1

    def test_verb_fault_raise_contained(self, store):
        # pipeliner.verb raise: surfaces as a script error, lane lives
        faults.arm("pipeliner.verb:raise@1")
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "verbfault",
                script="splinter.submit_embed('vd', 'x') return 1")
        assert _pump_until(pl, lambda: _done(store, "verbfault"), 10.0)
        assert _result(store, "verbfault")["err"] == "script_error"
        _submit(store, "after", script="return 7")
        assert _pump_until(pl, lambda: _done(store, "after"), 5.0)
        assert _result(store, "after")["ok"] is True


# ------------------------------------------------------ lane behavior

class TestLaneProtocol:
    def test_deadline_killed_before_next_verb(self, store, capsys):
        """Acceptance: deadline-expired scripts are killed before
        dispatching further verbs, and the kill is observable in
        `spt metrics` (sptpu_pipeliner_killed_deadline)."""
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "dl", tenant=2,
                deadline_ts=time.time() + 0.15,
                script="splinter.sleep(60) "
                       "splinter.submit_embed('late', 'x') return 1")
        assert _pump_until(pl, lambda: _done(store, "dl"), 10.0)
        rec = _result(store, "dl")
        assert rec["err"] == P.ERR_DEADLINE
        assert pl.stats.killed_deadline == 1
        # the embed verb never dispatched: no request label on 'late'
        assert "late" not in store.list()
        pl.publish_stats()
        from libsplinter_tpu.cli.main import Session
        from libsplinter_tpu.cli.metrics import cmd_metrics
        ses = Session(store.name)
        ses._store = store
        cmd_metrics(ses, [])
        out = capsys.readouterr().out
        assert "sptpu_pipeliner_killed_deadline 1" in out
        assert "sptpu_pipeliner_scripts_active" in out
        ses._store = None             # fixture owns the handle

    def test_expired_at_admission_fast_fails(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "preexp", deadline_ts=time.time() - 1.0,
                script="return 1")
        assert _pump_until(pl, lambda: _done(store, "preexp"), 5.0)
        assert _result(store, "preexp")["err"] == P.ERR_DEADLINE
        assert pl.stats.deadline_expired == 1
        assert pl.stats.scripts_started == 0

    def test_shed_past_high_water_typed(self, store):
        pl = Pipeliner(store, max_scripts=1, queue_high_water=1,
                       retry_after_ms=99)
        pl.attach()
        # one long-running admit + backlog past the mark
        _submit(store, "busy", script="splinter.sleep(0.5) return 1")
        for i in range(4):
            _submit(store, f"q{i}", script="return 1")
        pl.pump()
        shed = 0
        for i in range(4):
            rec = _result(store, f"q{i}")
            if rec and rec.get("err") == P.ERR_OVERLOADED:
                assert rec["retry_after_ms"] == 99
                shed += 1
        assert shed >= 1
        assert pl.stats.shed == shed

    def test_raced_rewrite_not_committed(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "race", script="splinter.sleep(0.2) return 1")
        assert _pump_until(
            pl, lambda: any(r.await_ for r in pl.runs.values()), 5.0)
        # client rewrites the slot mid-script: the old run must not
        # commit over the new request
        store.set("race", json.dumps({"script": "return 99"}))
        store.label_or("race", P.LBL_SCRIPT_REQ | P.LBL_WAITING)
        store.bump("race")
        assert _pump_until(pl, lambda: _done(store, "race"), 10.0)
        rec = _result(store, "race")
        assert rec["ok"] is True and rec["ret"] == [99]
        assert pl.stats.raced >= 1

    def test_sweep_reaps_orphaned_results(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "orphan", script="return 1")
        assert _pump_until(pl, lambda: _done(store, "orphan"), 5.0)
        # client never consumes; slot rewritten -> epoch moves
        store.set("orphan", "something else")
        assert pl.sweep_results() >= 1
        assert _result(store, "orphan") is None

    def test_sweep_finds_rows_by_prefix_scan(self, store, monkeypatch):
        """The reaper names its __pr_ rows (and the span plane's
        __sp_ rows) with the native prefix scan: Store.list() is
        never called, a live row stays, an orphan goes."""
        pl = Pipeliner(store)
        pl.attach()
        for key in ("orphan", "live"):
            _submit(store, key, script="return 1")
        assert _pump_until(
            pl, lambda: _done(store, "orphan") and _done(store, "live"),
            5.0)
        rows = {k: P.script_result_key(store.find_index(k))
                for k in ("orphan", "live")}
        store.set("orphan", "something else")
        monkeypatch.setattr(
            type(store), "list",
            lambda self: pytest.fail("the sweep walked every key"))
        assert pl.sweep_results() == 1
        assert pl.stats.results_reaped == 1
        assert rows["orphan"] not in store and rows["live"] in store
        assert pl.sweep_results(now=time.time() + 600) == 1
        assert rows["live"] not in store

    def test_tenant_rides_verbs(self, store):
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "tt", tenant=5,
                script="splinter.submit_embed('tdoc', 'x') return 1")
        assert _pump_until(
            pl, lambda: "tdoc" in store.list()
            and store.labels("tdoc") & P.LBL_EMBED_REQ, 5.0)
        # the downstream embed request carries the script's tenant id
        assert P.read_tenant(store.labels("tdoc")) == 5
        assert pl.tenants.get(5, "admitted") == 1

    def test_reused_key_clears_stale_ctx_exceeded(self, store):
        """A key that once got a ctx_exceeded rejection must not
        misreport it after a successful re-embed (the embedder never
        clears the bit on later success — the submit side must)."""
        pl = Pipeliner(store)
        pl.attach()
        store.set("rk", "x")
        store.label_or("rk", P.LBL_CTX_EXCEEDED)   # previous rejection
        _submit(store, "ctxreq",
                script="return splinter.submit_embed('rk', 'short')"
                       " and 1 or 0")
        assert _pump_until(
            pl, lambda: "rk" in store.list()
            and store.labels("rk") & P.LBL_EMBED_REQ, 5.0)
        assert not store.labels("rk") & P.LBL_CTX_EXCEEDED
        v = np.zeros(store.vec_dim, np.float32)
        v[0] = 1.0
        store.vec_set("rk", v)
        store.label_clear("rk", P.LBL_EMBED_REQ | P.LBL_WAITING)
        assert _pump_until(pl, lambda: _done(store, "ctxreq"), 5.0)
        assert _result(store, "ctxreq")["ret"] == [1]

    def test_deferred_backlog_not_recounted(self, store):
        """The deferred-backlog memo: a row re-offered every re-plan
        is parsed and counted ONCE, not once per pump."""
        pl = Pipeliner(store, max_scripts=1)
        pl.attach()
        _submit(store, "hold", script="splinter.sleep(0.3) return 1")
        for i in range(3):
            _submit(store, f"wait{i}", script="return 1")
        for _ in range(50):
            pl.pump()
            time.sleep(0.002)
        assert _pump_until(
            pl, lambda: all(_done(store, f"wait{i}")
                            for i in range(3)), 10.0)
        assert pl.stats.requests == 4          # one per submission
        assert pl.stats.deferred <= 3          # first sights only
        assert not pl._parsed                  # memo drained

    def test_stored_script_lifecycle(self, store):
        seed_library(store)
        names = {k[len(P.SCRIPT_STORE_PREFIX):]
                 for k in store.list()
                 if k.startswith(P.SCRIPT_STORE_PREFIX)}
        assert names == set(SCRIPT_LIBRARY)
        store_script(store, "custom", "return 42")
        pl = Pipeliner(store)
        pl.attach()
        _submit(store, "creq", name="custom")
        assert _pump_until(pl, lambda: _done(store, "creq"), 5.0)
        assert _result(store, "creq")["ret"] == [42]


# ----------------------------------------------- full-stack e2e + CLI

def _stack(store, stop_after=90.0, **pl_kw):
    def enc(texts):
        out = np.zeros((len(texts), store.vec_dim), np.float32)
        for i, t in enumerate(texts):
            out[i, hash(t) % store.vec_dim] = 1.0
        return out

    emb = Embedder(store, encoder_fn=enc, max_ctx=64)
    sr = Searcher(store)
    comp = Completer(store, generate_fn=lambda p: iter([b"answer"]),
                     template="none")
    pl = Pipeliner(store, **pl_kw)
    daemons = (emb, sr, comp, pl)
    for d in daemons:
        d.attach()
    ths = [threading.Thread(
        target=d.run, kwargs=dict(idle_timeout_ms=10,
                                  stop_after=stop_after), daemon=True)
        for d in daemons]
    for t in ths:
        t.start()
    return daemons, ths


def _seed_docs(store, n=8):
    rng = np.random.default_rng(0)
    for i in range(n):
        k = f"lgd{i}"
        store.set(k, f"seed doc {i}")
        v = rng.standard_normal(store.vec_dim).astype(np.float32)
        store.vec_set(k, v / np.linalg.norm(v))


class TestEndToEnd:
    def test_submit_embed_client_helper(self, store):
        # satellite: the missing third client verb — tenant/deadline/
        # retry parity with submit_search/submit_completion
        daemons, ths = _stack(store)
        try:
            assert submit_embed(store, "ce", "hello world",
                                tenant=3, deadline_ms=8000,
                                timeout_ms=8000) is True
            assert np.abs(store.vec_get("ce")).max() > 0
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)

    def test_inline_chain_and_stored_scenarios(self, store):
        daemons, ths = _stack(store)
        _seed_docs(store)
        seed_library(store)
        try:
            rec = submit_script(store, "e2e", timeout_ms=20_000,
                                script="""
                local ok, err = splinter.submit_embed("ed", "doc")
                if not ok then error(err) end
                local q = "eq"
                splinter.set(q, "scratch")
                splinter.set_embedding(q, splinter.get_embedding("ed"))
                local hits, serr = splinter.submit_search(q, 3)
                splinter.unset(q)
                if not hits then error(serr) end
                local out, cerr = splinter.submit_completion(
                    "ec", "ctx: " .. table.concat(hits, ","))
                if not out then error(cerr) end
                return #hits, out
            """)
            assert rec["ok"] is True
            assert rec["ret"][0] >= 1
            assert "answer" in rec["ret"][1]
            consume_script_result(store, "e2e")
            for name in SCRIPT_LIBRARY:
                rec = submit_script(store, f"e2e_{name}", name=name,
                                    args=[f"doc_{name}", 3],
                                    timeout_ms=20_000, tenant=1,
                                    deadline_ms=15_000)
                assert rec.get("ok") is True, (name, rec)
                consume_script_result(store, f"e2e_{name}")
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)

    def test_loadgen_script_scenarios_end_to_end(self, store):
        """Acceptance: agent-loop / multi-hop / map-reduce run
        end-to-end from scripts only, per-tenant deadlines enforced,
        zero admitted loss."""
        from libsplinter_tpu.cli.loadgen import (LoadGenerator,
                                                 TenantSpec)

        daemons, ths = _stack(store)
        try:
            for scn in ("agent-loop", "multi-hop", "map-reduce"):
                gen = LoadGenerator(
                    store, [TenantSpec(1, 5.0, deadline_ms=8000)],
                    duration_s=1.2, corpus=8, seed=4, scenario=scn)
                rep = gen.run()
                assert rep["lost"] == 0, (scn, rep)
                assert rep["ok"] >= max(1, rep["issued"] - 1), \
                    (scn, rep)
                assert "p50_ms" in rep["per_tenant"]["1"]["script"]
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)

    def test_unknown_scenario_lists_registry(self, store):
        from libsplinter_tpu.cli.loadgen import LoadGenerator, \
            TenantSpec
        with pytest.raises(ValueError) as ei:
            LoadGenerator(store, [TenantSpec(1, 1.0)],
                          scenario="nope")
        msg = str(ei.value)
        for name in ("rag-churn", "rag-churn-script", "agent-loop",
                     "multi-hop", "map-reduce"):
            assert name in msg

    def test_cli_pipeline_store_management(self, store, capsys,
                                           tmp_path):
        from libsplinter_tpu.cli.main import CliError, Session
        from libsplinter_tpu.cli.pipeline import cmd_pipeline

        ses = Session(store.name)
        ses._store = store
        f = tmp_path / "s.lua"
        f.write_text("return 1")
        cmd_pipeline(ses, ["put", "mine", str(f)])
        cmd_pipeline(ses, ["seed"])
        cmd_pipeline(ses, ["ls"])
        out = capsys.readouterr().out
        assert "mine" in out and "rag-churn" in out
        cmd_pipeline(ses, ["cat", "mine"])
        assert "return 1" in capsys.readouterr().out
        cmd_pipeline(ses, ["rm", "mine"])
        with pytest.raises(CliError):
            cmd_pipeline(ses, ["cat", "mine"])
        # run without a live lane fails fast with guidance
        with pytest.raises(CliError, match="no live pipeline lane"):
            cmd_pipeline(ses, ["run", "-e", "return 1"])
        # double designation is a usage error, not a traceback
        with pytest.raises(CliError, match="already given"):
            cmd_pipeline(ses, ["run", "@rag-churn", "-e", "return 1"])
        ses._store = None             # fixture owns the handle

    def test_cli_pipeline_run_against_live_lane(self, store, capsys):
        from libsplinter_tpu.cli.main import Session
        from libsplinter_tpu.cli.pipeline import cmd_pipeline

        daemons, ths = _stack(store)
        try:
            # lane heartbeat must exist for daemon_live
            daemons[-1].publish_stats()
            ses = Session(store.name)
            ses._store = store
            cmd_pipeline(ses, ["run", "-e", "return 40 + 2",
                               "--timeout-ms", "10000"])
            assert "ok: 42" in capsys.readouterr().out
            ses._store = None         # fixture owns the handle
        finally:
            for d in daemons:
                d.stop()
            for t in ths:
                t.join(timeout=10)


# ------------------------------------------------------- chaos drills

@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_crash_reclaims_scripts(store, monkeypatch):
    """Acceptance: a mid-run `pipeliner.exec` crash under `spt
    supervise` loses ZERO admitted scripts — the restarted lane finds
    LBL_SCRIPT_REQ still up on the stranded requests, re-runs them,
    and the loadgen LOST counter stays 0."""
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec
    from libsplinter_tpu.engine.supervisor import Supervisor

    # the lane's 6th exec slice dies — mid-run, with admitted scripts
    # suspended on verbs
    monkeypatch.setenv("SPTPU_FAULT", "pipeliner.exec:crash@6")
    monkeypatch.setenv("SPTPU_CHAOS_RUN_S", "600")

    daemons, ths = _stack(store, stop_after=240.0)
    pl_inproc = daemons[-1]
    pl_inproc.stop()                   # the SUPERVISED child serves
    seed_library(store)

    holder: dict = {}

    def spawn(lane):
        return subprocess.Popen(
            [sys.executable, CHILD, "pipeliner", store.name],
            env=holder["sup"]._child_env(lane))

    sup = Supervisor(store.name, lanes=("pipeliner",), spawn_fn=spawn,
                     store=store, backoff_base_ms=100,
                     backoff_max_ms=1500, breaker_threshold=8,
                     breaker_window_s=120, startup_grace_s=300)
    holder["sup"] = sup
    t = threading.Thread(target=sup.run,
                         kwargs={"poll_interval_s": 0.1,
                                 "stop_after": 240.0})
    t.start()
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if P.heartbeat_live(store, P.KEY_SCRIPT_STATS,
                                max_age_s=30):
                break
            time.sleep(0.2)
        else:
            pytest.fail("pipeliner never came up under supervision")
        gen = LoadGenerator(store,
                            [TenantSpec(1, 4.0, deadline_ms=60_000)],
                            duration_s=6.0, corpus=8, seed=9,
                            scenario="rag-churn-script",
                            drain_s=120.0)
        rep = gen.run()
        assert sup.lanes["pipeliner"].restarts >= 1, rep
        assert rep["lost"] == 0, rep
        assert rep["ok"] >= 1, rep
    finally:
        sup.stop()
        t.join(timeout=30)
        sup.shutdown()
        for d in daemons:
            d.stop()
        for th in ths:
            th.join(timeout=15)


@pytest.mark.slow
def test_script_chain_beats_client_chain(store):
    """Acceptance: rag-churn as a stored script shows p50 >= 30%
    below the client-side chain on the same in-process stack (the
    `make pipeline-check` gate runs the standalone version)."""
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec

    daemons, ths = _stack(store, stop_after=120.0)
    try:
        def p50(scn):
            gen = LoadGenerator(
                store, [TenantSpec(1, 10.0, deadline_ms=8000)],
                duration_s=2.5, corpus=8, seed=11, scenario=scn)
            rep = gen.run()
            assert rep["lost"] == 0, (scn, rep)
            lane = "rag" if scn == "rag-churn" else "script"
            # exact median: the report's log-bucketed p50 is too
            # coarse (~19% buckets) for a 30% A/B bar
            return float(np.median(gen.raw_ms[(1, lane)]))

        client = p50("rag-churn")
        script = p50("rag-churn-script")
        assert script <= 0.7 * client, (client, script)
    finally:
        for d in daemons:
            d.stop()
        for t in ths:
            t.join(timeout=15)
