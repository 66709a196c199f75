"""Device-time & compile attribution tier (`make compile-check`): the
named-program registry (obs/devtime.py) — compile-event ledgering
with warmup/runtime cause split, dispatch marks and the warmup
exclusion, the `__compile_<i>` store ring and its cross-restart
generation visibility, span schema v3 (device_ms / dispatch_queue),
tail-based span retention, the Perfetto export's device + compile
tracks, replica-suffixed devtime heartbeat discovery (SPL105
discipline), and the seeded-recompile drill that proves the gate
script can actually fail."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.obs import spans as S
from libsplinter_tpu.obs.devtime import (DevtimeRegistry, close_mark,
                                         collect_compile_events)

GATE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "compile_gate_check.py")


class FakeJit:
    """A callable with the jit private cache API: `grow` scripts when
    a call 'compiles' (cache size bump)."""

    def __init__(self, result=None):
        self.cache = 0
        self.grow_next = False
        self.result = result if result is not None \
            else np.zeros((2,), np.float32)
        self.calls = 0

    def _cache_size(self):
        return self.cache

    def __call__(self, *args, **kwargs):
        self.calls += 1
        if self.grow_next:
            self.cache += 1
            self.grow_next = False
        return self.result


@pytest.fixture
def reg():
    return DevtimeRegistry()


# --------------------------------------------- ledger + cause split

class TestCompileLedger:
    def test_warmup_vs_runtime_cause(self, reg):
        fn = FakeJit()
        w = reg.register("completer.chunk", fn)
        with reg.warmup_phase():
            fn.grow_next = True
            w(np.ones((4, 8), np.int32))
        assert reg.compile_events() == 0          # warmup is free
        fn.grow_next = True
        w(np.ones((4, 16), np.int32))
        assert reg.compile_events() == 1
        assert reg.compile_events("completer") == 1
        assert reg.compile_events("embedder") == 0
        evs = reg.pending_events()
        assert [e["cause"] for e in evs] == ["warmup", "runtime"]
        rt = evs[1]
        assert rt["program"] == "completer.chunk"
        assert rt["lane"] == "completer"
        assert "int32[4, 16]" in rt["shapes_key"]
        assert rt["duration_ms"] >= 0
        assert rt["generation"] == reg.generation

    def test_no_growth_no_event(self, reg):
        fn = FakeJit()
        w = reg.register("searcher.topk", fn)
        for _ in range(5):
            w(np.ones((8,), np.float32))
        assert reg.pending_events() == []
        assert reg.compile_events() == 0

    def test_non_jit_callable_never_ledgers(self, reg):
        calls = []
        w = reg.register("embedder.encode",
                         lambda x: calls.append(x) or
                         np.zeros((1,), np.float32))
        w("text")
        assert calls == ["text"] and reg.pending_events() == []

    def test_reregister_same_name_reuses_program(self, reg):
        a, b = FakeJit(), FakeJit()
        reg.register("completer.trunk", a)
        reg.register("completer.trunk", b)  # lru_cache factory rerun
        assert list(reg._progs) == ["completer.trunk"]

    def test_kill_switch_returns_fn_untouched(self, monkeypatch):
        monkeypatch.setenv("SPTPU_DEVTIME", "0")
        off = DevtimeRegistry()
        fn = FakeJit()
        assert off.register("completer.chunk", fn) is fn
        assert fn.__wrapped__ is fn        # unwrap stays unconditional


# ------------------------------------------ marks + warmup exclusion

class TestDispatchMarks:
    def test_warmup_opens_no_device_window(self, reg):
        fn = FakeJit(result=object())      # async-ish: not ndarray
        w = reg.register("completer.chunk", fn)
        with reg.warmup_phase():
            w()
        assert reg.take_mark("completer.chunk") is None
        assert reg.take_lane_ms("completer") == 0.0

    def test_async_result_leaves_mark_for_collect_point(self, reg):
        fn = FakeJit(result=object())
        w = reg.register("completer.paged_chunk", fn)
        w()
        mark = reg.take_mark("completer.paged_chunk")
        assert mark is not None
        assert reg.take_mark("completer.paged_chunk") is None  # popped
        time.sleep(0.002)
        ms = mark.close()
        assert ms >= 2.0
        assert mark.close() == 0.0                 # idempotent
        assert reg.take_lane_ms("completer") >= 2.0
        assert reg.take_lane_ms("completer") == 0.0  # popped
        close_mark(None)                           # None-safe helper

    def test_sync_ndarray_result_closes_inline(self, reg):
        w = reg.register("searcher.topk",
                         FakeJit(result=np.zeros((4,), np.float32)))
        w()
        assert reg.take_mark("searcher.topk") is None
        assert reg.take_lane_ms("searcher") > 0.0

    def test_heartbeat_section_and_share(self, reg):
        fn = FakeJit(result=np.zeros((2,), np.float32))
        w = reg.register("completer.chunk", fn)
        fn.grow_next = True
        w()
        w()
        sec = reg.heartbeat_section("completer")
        assert sec["chunk"]["n"] == 2
        assert sec["chunk"]["compiles"] == 1
        assert sec["chunk"]["runtime_compiles"] == 1
        assert sec["chunk"]["p99_ms"] >= sec["chunk"]["p50_ms"] >= 0
        assert reg.heartbeat_section("embedder") == {}
        assert 0.0 <= reg.device_ms_share() <= 1.0


# --------------------------------------------------- the store ring

class TestCompileRing:
    def _seed(self, reg, name, shapes=((4,),)):
        fn = FakeJit()
        w = reg.register(name, fn)
        for shp in shapes:
            fn.grow_next = True
            w(np.ones(shp, np.int32))

    def test_flush_and_collect(self, reg, store):
        self._seed(reg, "completer.chunk", ((4,), (8,)))
        assert reg.flush(store) == 2
        assert reg.pending_events() == []          # drained
        assert reg.flush(store) == 0
        evs = collect_compile_events(store)
        assert len(evs) == 2
        assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
        assert {e["program"] for e in evs} == {"completer.chunk"}
        assert store.get_uint(P.KEY_COMPILE_HEAD) == 2

    def test_ring_bounded_oldest_overwritten(self, reg, store):
        n = S.span_ring_size(store)
        for i in range(n + 3):
            self._seed(reg, "completer.chunk", ((i + 1,),))
        reg.flush(store)
        evs = collect_compile_events(store)
        assert len(evs) == n                      # bounded ring
        assert int(store.get_uint(P.KEY_COMPILE_HEAD)) == n + 3

    def test_generation_bump_survives_restart(self, reg, store):
        """The crash/restart drill: generation 0's events stay in the
        ring; the restarted process (fresh registry state, generation
        synced from the lane's bumped supervision counter) lands its
        under the new generation — the ring tells the two lives
        apart."""
        self._seed(reg, "completer.chunk")
        reg.flush(store)
        # supervised restart: attach() syncs the registry generation
        # from bump_generation, and the re-exec resets in-process state
        reg.reset()
        g = P.bump_generation(store, P.KEY_COMPLETE_STATS)
        reg.generation = max(reg.generation, g)
        assert reg.generation >= 1
        self._seed(reg, "completer.chunk")        # factory re-runs
        reg.flush(store)
        gens = [e["generation"] for e in
                collect_compile_events(store)]
        assert len(gens) == 2 and gens[0] == 0 and gens[1] >= 1

    def test_flush_full_store_degrades_quietly(self, reg):
        self._seed(reg, "completer.chunk")
        class Dead:
            def __contains__(self, k):
                raise OSError("full")
        assert reg.flush(Dead()) == 0             # never raises
        assert reg.compile_events() == 1          # counters keep truth


# ------------------------------------- span schema v3 + tail spans

class TestSpanV3:
    def test_device_ms_split(self, store):
        w = S.SpanWriter(store, "completer", eager=True)
        store.set("req", "x")
        tid = P.stamp_trace(store, "req")
        idx = store.find_index("req")
        pend = w.begin(idx, store.epoch_at(idx))
        time.sleep(0.005)
        assert w.commit(pend, device_ms=2.0)
        rec = S.collect_spans(store, tid)[0]
        assert rec["device_ms"] == 2.0
        assert rec["dispatch_queue"] == pytest.approx(
            rec["service_ms"] - 2.0, abs=0.01)
        assert rec["dispatch_queue"] >= 0

    def test_no_device_window_no_v3_fields(self, store):
        w = S.SpanWriter(store, "completer", eager=True)
        store.set("req", "x")
        tid = P.stamp_trace(store, "req")
        idx = store.find_index("req")
        assert w.commit(w.begin(idx, store.epoch_at(idx)),
                        device_ms=0.0)
        rec = S.collect_spans(store, tid)[0]
        assert "device_ms" not in rec
        assert "dispatch_queue" not in rec

    def test_tail_span_resolves_by_trace_id(self, store):
        w = S.SpanWriter(store, "completer", eager=True)
        tid = w.tail_span("slow/key", 120.0,
                          stages={"decode": 100.0, "flush": 20.0},
                          extra={"tokens": 7}, device_ms=80.0)
        assert tid is not None
        recs = S.collect_spans(store, tid)
        assert len(recs) == 1
        rec = recs[0]
        assert rec["tail"] is True
        assert rec["key"] == "slow/key"
        assert rec["service_ms"] == pytest.approx(120.0, abs=15.0)
        assert rec["stages"] == {"decode": 100.0, "flush": 20.0}
        assert rec["tokens"] == 7 and rec["device_ms"] == 80.0
        # the tree renders standalone (slow-log `spt trace show` path)
        tree = S.assemble_tree(recs)
        assert tree["tid"] == tid
        assert tree["root"]["span"]["lane"] == "completer"

    def test_chrome_trace_device_and_compile_tracks(self):
        now = time.time()
        spans = [{"tid": 7, "span": 7, "parent": 0,
                  "lane": "completer", "key": "k", "status": "ok",
                  "t_queue": now - 0.02, "t_admit": now - 0.01,
                  "queue_ms": 10.0, "service_ms": 10.0,
                  "device_ms": 6.0, "dispatch_queue": 4.0}]
        compiles = [{"program": "completer.chunk",
                     "lane": "completer", "shapes_key": "(int32[4])",
                     "duration_ms": 12.5, "generation": 1,
                     "cause": "runtime", "ts": now}]
        doc = S.to_chrome_trace(spans, compile_events=compiles)
        evs = doc["traceEvents"]
        host = [e for e in evs if e.get("cat") == "span"]
        dev = [e for e in evs if e.get("cat") == "device"]
        comp = [e for e in evs if e.get("cat") == "compile"]
        assert len(host) == len(dev) == len(comp) == 1
        # three DISTINCT tracks: host lane, device lane, compile
        assert len({host[0]["pid"], dev[0]["pid"], comp[0]["pid"]}) \
            == 3
        assert comp[0]["ph"] == "i"
        assert comp[0]["args"]["shapes_key"] == "(int32[4])"
        # the device slice sits at the TAIL of the service window
        assert dev[0]["ts"] == pytest.approx(
            host[0]["ts"] + 4.0 * 1e3, abs=1.0)
        names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert {"lane:completer", "device:completer",
                "compiles"} <= names
        assert doc["otherData"]["compile_events"] == 1


# ------------------------------- replica-suffixed devtime discovery

class TestReplicaDevtimeKeys:
    def test_devtime_sections_discovered_per_replica(self, store):
        """SPL105 discipline: a reader that hardcodes the base
        heartbeat key misses replica N's devtime/compile counters —
        discovery must go through replica_heartbeat_keys."""
        base = P.KEY_COMPLETE_STATS
        for r in (0, 1):
            snap = {"pid": os.getpid(), "ts": time.time(),
                    "replica": r,
                    "compile_events": r,       # distinct per replica
                    "devtime": {"chunk": {"n": 5 + r, "compiles": 1,
                                          "runtime_compiles": r}}}
            key = P.replica_stats_key(base, r)
            store.set(key, json.dumps(snap))
            # heartbeats are debug-labeled: the bloom prefilter IS
            # the discovery path (replica_heartbeat_map enumerates
            # LBL_DEBUG, never walks per-base key guesses)
            store.label_or(key, P.LBL_DEBUG)
        found = {}
        for r, key in P.replica_heartbeat_keys(store, base):
            snap = json.loads(store.get(key).rstrip(b"\0"))
            found[r] = (snap["compile_events"],
                        snap["devtime"]["chunk"]["n"])
        assert found == {0: (0, 5), 1: (1, 6)}


# ------------------------------------------- the gate's own drills

@pytest.mark.slow
class TestGateScript:
    def _run(self, *args):
        env = dict(os.environ)
        env.pop("SPTPU_SEED_RECOMPILE", None)
        env.pop("SPTPU_DEVTIME", None)
        return subprocess.run(
            [sys.executable, GATE, *args], env=env,
            capture_output=True, text=True, timeout=900)

    def test_clean_gate_passes(self):
        p = self._run()
        assert p.returncode == 0, p.stderr
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["value"] == 0 and rec["warmup_events"] > 0

    def test_seeded_recompile_is_caught_by_name(self):
        p = self._run("--seed-recompile")
        assert p.returncode == 0, p.stderr
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["value"] > 0 and rec["ok"]
        progs = {g["program"] for g in rec["guilty"]}
        assert any(pr.startswith("completer.") for pr in progs)
        assert all(g["shapes_key"] for g in rec["guilty"])


# ------------------------------------- the search daemon's windows

class TestSearcherWindows:
    """The daemon path takes the program's mark right after the
    dispatch and closes it at the fetch (searcher._service /
    _resolve_batch): windows count dispatches, span records carry a
    real device_ms, and no failure leaves a mark dangling."""

    @staticmethod
    def _searcher(store, n_docs=12):
        from libsplinter_tpu.engine.searcher import Searcher
        rng = np.random.default_rng(5)
        vecs = rng.normal(size=(n_docs, store.vec_dim)).astype(np.float32)
        for i in range(n_docs):
            store.set(f"doc/{i}", f"text {i}")
            store.vec_set(f"doc/{i}", vecs[i])
        sr = Searcher(store)
        sr.attach()
        sr.run_once()                 # stage the lane, no request yet
        return sr, vecs

    @staticmethod
    def _ask(store, key, qvec, *, stamp=False):
        store.set(key, json.dumps({"k": 3}))
        store.vec_set(key, qvec)
        store.label_or(key, P.LBL_SEARCH_REQ | P.LBL_WAITING)
        tid = P.stamp_trace(store, key) if stamp else None
        store.bump(key)
        return tid

    @staticmethod
    def _program(sr):
        """(registry entry, short name) of the program this searcher
        dispatches for a k=3 request."""
        from libsplinter_tpu.engine import searcher as M
        from libsplinter_tpu.obs.devtime import DEVTIME
        fn = sr._program(M._k_bucket(3 + M.K_CUSHION))
        name = fn._devtime_name
        return DEVTIME._progs[name], name.split(".", 1)[1]

    def test_windows_count_daemon_dispatches(self, store_2k):
        from libsplinter_tpu.obs.devtime import DEVTIME
        store = store_2k
        sr, vecs = self._searcher(store)
        prog, short = self._program(sr)
        n0, total0 = prog.hist.n, prog.hist.total_ms
        d0 = sr.stats.dispatches
        for i in range(5):
            self._ask(store, f"__sqtmp_w{i}", vecs[i])
            assert sr.run_once() == 1
        n = sr.stats.dispatches - d0
        assert n == 5
        assert prog.hist.n - n0 == n
        assert prog.hist.total_ms > total0
        assert prog.last_mark is None            # every mark taken
        sec = DEVTIME.heartbeat_section("searcher")[short]
        assert sec["n"] == prog.hist.n
        assert sec["total_ms"] == pytest.approx(prog.hist.total_ms,
                                                abs=0.06)
        # and the heartbeat carries it: a window's mean is a
        # difference of two heartbeats
        sr.publish_stats()
        snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
        assert snap["devtime"][short]["total_ms"] == sec["total_ms"]
        assert snap["dispatches"] == sr.stats.dispatches

    def test_committed_span_carries_device_ms(self, store_2k):
        store = store_2k
        sr, vecs = self._searcher(store)
        tid = self._ask(store, "__sqtmp_sp", vecs[2], stamp=True)
        assert sr.run_once() == 1                # flushes the span
        rec = S.collect_spans(store, tid)[0]
        assert rec["device_ms"] > 0
        assert rec["dispatch_queue"] >= 0

    def test_failed_dispatch_leaves_no_mark(self, store_2k):
        """A dispatch that raises leaves no mark; a fetch that raises
        closes the batch's mark after the degraded retry, whose own
        dispatch closes its own: nothing dangles, every request is
        served, and the windows still add up."""
        from libsplinter_tpu.obs.devtime import DEVTIME
        from libsplinter_tpu.utils import faults
        store = store_2k
        sr, vecs = self._searcher(store)
        prog, _ = self._program(sr)

        def topk_progs():
            return [p for p in DEVTIME._progs.values()
                    if p.lane == "searcher" and "topk" in p.short]
        # the registry is the PROCESS's: a file that calls a top-k
        # program directly (tests/test_fused_topk.py: no collect point
        # takes the mark) leaves one open on this worker, and on the
        # CPU this searcher never dispatches the fused program that
        # would take it over.  Those are not this searcher's.
        stale = [p.last_mark for p in topk_progs()]
        for site in ("searcher.dispatch", "searcher.select"):
            n0 = prog.hist.n
            faults.arm(f"{site}:raise@1")
            try:
                self._ask(store, f"__sqtmp_f_{site[-6:]}", vecs[1])
                assert sr.run_once() == 1        # degraded, served
            finally:
                faults.disarm()
            assert sr.stats.retried_unfused >= 1
            assert all(p.last_mark is None
                       or any(p.last_mark is m for m in stale)
                       for p in topk_progs())
            # dispatch failed: the retry's window only; fetch failed:
            # the batch's own window and the retry's
            assert prog.hist.n - n0 == (1 if "dispatch" in site else 2)
