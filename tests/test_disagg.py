"""Disaggregated prefill/decode lanes (ISSUE 18).

The serving contract under test: splitting the continuous completer
into a PrefillLane (dense bucket prefill + page handoff) and a
DecodeLane (adoption + ragged paged decode) must be INVISIBLE to
clients — greedy bytes identical to the unified lane (including a
joiner that lands mid-burst), zero admitted-request loss through a
crash on either side of the handoff, and phase-aware deadlines that
die typed BEFORE paying the phase they cannot finish in.

The crash drills spawn jax-importing children under `spt supervise`
(tests/chaos_child.py prefill_lane / decode_lane) and are marked
slow + chaos; `make disagg-check` runs the fast tier plus the
scripts/disagg_check.py isolation gate.
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time
import uuid

import pytest

jnp = pytest.importorskip("jax.numpy")

from libsplinter_tpu import Store  # noqa: E402
from libsplinter_tpu.engine import protocol as P  # noqa: E402
from libsplinter_tpu.engine.completer import Completer  # noqa: E402
from libsplinter_tpu.engine.disagg import (DecodeLane,  # noqa: E402
                                           PrefillLane)
from libsplinter_tpu.models.decoder import (CompletionModel,  # noqa: E402
                                            DecoderConfig)

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(HERE, "chaos_child.py")

KW = dict(max_new_tokens=8, flush_tokens=4, template="none",
          batch_cap=4, page_size=8)


@pytest.fixture(scope="module")
def model():
    """One tiny model for the whole module: the jit caches live on
    the model object, so every lane after the first test runs warm."""
    return CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                           buckets=(32,), temp=0.0, seed=1,
                           suffix_buckets=(8,))


def _mkstore(tag: str, max_val: int = 16384):
    # max_val 16384 > page_wire_bytes(tiny f32, page=8) = 4096: wire
    # export/import is the default path; 4096 forces the re-prefill
    # fallback (the record's token ids) instead
    name = f"/spt-disagg-{tag}-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    Store.unlink(name)
    return name, Store.create(name, nslots=128, max_val=max_val,
                              vec_dim=8)


def _submit(st, key, prompt, deadline=None):
    st.set(key, prompt)
    if deadline is not None:
        P.stamp_deadline(st, key, deadline)
    st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
    st.bump(key)


def _await(st, keys, bit=P.LBL_READY, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(st.labels(k) & bit for k in keys):
            return True
        time.sleep(0.02)
    return False


def _settles(read, want, timeout=5.0):
    """A lane counts AFTER its record is readable (READY is raised,
    then the wire keys and stamps go, then the counter moves): a
    client that has just seen the record waits for the count."""
    deadline = time.monotonic() + timeout
    while read() != want and time.monotonic() < deadline:
        time.sleep(0.005)
    return read() == want


def _run_bg(daemon, stop_after=180.0):
    th = threading.Thread(
        target=daemon.run_continuous,
        kwargs=dict(idle_timeout_ms=20, stop_after=stop_after),
        daemon=True)
    th.start()
    return th


def _no_handoff_keys(st):
    """No `__ho_` record/page/scale key survives a finished request —
    the wire keys ride LBL_DEBUG, so enumerate that label."""
    for idx in st.enumerate_indices(P.LBL_DEBUG):
        key = st.key_at(idx)
        if key is not None and key.startswith(P.HANDOFF_PREFIX):
            return False
    return True


def _serve(tag, daemons_fn, model, prompts, joiner=None,
           max_val=16384):
    """Run `prompts` (plus an optional mid-burst `joiner` submitted
    after the first completion) to READY and return {key: bytes}."""
    name, st = _mkstore(tag, max_val=max_val)
    daemons = daemons_fn(st, model)
    ths = []
    try:
        for d in daemons:
            d.attach()
        ths = [_run_bg(d) for d in daemons]
        keys = []
        for i, prompt in enumerate(prompts):
            keys.append(f"q/{i}")
            _submit(st, keys[-1], prompt)
        if joiner is not None:
            # mid-burst joiner: lands after the first completion while
            # the rest of the burst is still in flight
            assert _await(st, keys[:1]), "first completion never READY"
            keys.append("q/join")
            _submit(st, "q/join", joiner)
        assert _await(st, keys), [
            (k, hex(st.labels(k))) for k in keys]
        out = {k: st.get(k).rstrip(b"\0") for k in keys}
        for d in daemons:
            d.stop()
        for th in ths:
            th.join(timeout=30)
        assert _no_handoff_keys(st)
        return out, [dict(getattr(d, "_lane_stats", {}))
                     for d in daemons]
    finally:
        for d in daemons:
            d.stop()
        for th in ths:
            th.join(timeout=30)
        st.close()
        Store.unlink(name)


def _unified(st, model):
    return [Completer(st, model=model, **KW)]


def _split(st, model):
    return [PrefillLane(st, model=model, **KW),
            DecodeLane(st, model=model, **KW)]


PROMPTS = ["say one thing", "list two colors ok", "count to three"]
JOINER = "and a late joiner arrives"


@pytest.fixture(scope="module")
def sharded_model():
    """tp=2 over the conftest's virtual 8-device CPU mesh: the wire
    handoff must round-trip kv-head-SHARDED pools byte-exactly."""
    from libsplinter_tpu.parallel import (ShardedCompletionModel,
                                          make_mesh)
    return ShardedCompletionModel(
        DecoderConfig.tiny(dtype=jnp.float32), make_mesh(dp=4, tp=2),
        buckets=(32,), temp=0.0, seed=1, suffix_buckets=(8,))


class TestByteExactness:
    def test_split_matches_unified_with_midburst_joiner(self, model):
        """Greedy bytes through the handoff — wire-page export/import
        path — are identical to the unified lane's, including a
        joiner admitted while the burst is mid-flight."""
        uni, _ = _serve("uni", _unified, model, PROMPTS, joiner=JOINER)
        spl, stats = _serve("spl", _split, model, PROMPTS,
                            joiner=JOINER)
        assert spl == uni
        pf, dl = stats
        assert pf["handoffs"] >= 4 and pf["handoff_failed"] == 0
        assert dl["adopted"] == pf["handoffs"]
        # the real wire path, not the fallback
        assert dl["handoff_refill"] == 0
        assert pf["handoff_wire_mb"] > 0

    def test_split_matches_unified_tp2_cpu_mesh(self, sharded_model):
        """The page handoff across a tp=2 mesh: exported wire pages
        gather the kv-head-sharded pool, adoption scatters it back
        under the same sharding, and greedy bytes through the split
        match the unified sharded lane (`make disagg-check` runs
        this — the multichip dry-run contract from conftest)."""
        uni, _ = _serve("uni-tp2", _unified, sharded_model, PROMPTS)
        spl, stats = _serve("spl-tp2", _split, sharded_model, PROMPTS)
        assert spl == uni
        pf, dl = stats
        assert pf["handoffs"] >= 3 and pf["handoff_failed"] == 0
        assert dl["adopted"] == pf["handoffs"]
        # the real wire path on the mesh, not the refill fallback
        assert dl["handoff_refill"] == 0
        assert pf["handoff_wire_mb"] > 0

    def test_split_matches_unified_int4_packed_wire(self, model):
        """PR 20: the handoff wire carries int4 pools in their NATIVE
        packed dtype — uint8 nibble pages plus f32 scale rows, half
        the int8 wire and an eighth of f32 — and split greedy bytes
        still match the unified int4 lane exactly (the wire is the
        pool's own bytes, so packed handoff is structurally exact,
        not tolerance-bounded)."""
        kw4 = dict(KW, kv_dtype="int4")

        def uni4(st, m):
            return [Completer(st, model=m, **kw4)]

        def spl4(st, m):
            return [PrefillLane(st, model=m, **kw4),
                    DecodeLane(st, model=m, **kw4)]

        uni, _ = _serve("uni-i4", uni4, model, PROMPTS, joiner=JOINER)
        spl, stats = _serve("spl-i4", spl4, model, PROMPTS,
                            joiner=JOINER)
        assert spl == uni
        pf, dl = stats
        assert pf["handoffs"] >= 4 and pf["handoff_failed"] == 0
        assert dl["adopted"] == pf["handoffs"]
        assert dl["handoff_refill"] == 0      # real wire, no fallback
        # the wire itself halves vs int8 at the same page count
        c4 = model.init_paged(2, page=8, kv_dtype="int4")
        c8 = model.init_paged(2, page=8, kv_dtype="int8")
        assert str(model._page_wire_dtype(c4)) == "uint8"
        assert model.page_wire_bytes(c4) * 2 == model.page_wire_bytes(c8)

    @pytest.mark.slow
    def test_refill_fallback_matches_unified_int4(self, model):
        """A store too small for even the PACKED wire page degrades
        the int4 handoff to re-prefill-from-record, byte-identically
        to the unified int4 lane — the fallback replays tokens, so it
        is layout-blind and must survive the packed geometry."""
        kw4 = dict(KW, kv_dtype="int4")
        wire = model.page_wire_bytes(
            model.init_paged(2, page=8, kv_dtype="int4"))

        def uni4(st, m):
            return [Completer(st, model=m, **kw4)]

        def spl4(st, m):
            return [PrefillLane(st, model=m, **kw4),
                    DecodeLane(st, model=m, **kw4)]

        uni, _ = _serve("uni-i4s", uni4, model, PROMPTS, max_val=wire)
        spl, stats = _serve("spl-i4s", spl4, model, PROMPTS,
                            max_val=wire)
        assert spl == uni
        pf, dl = stats
        assert pf["handoffs"] >= 3
        assert dl["handoff_refill"] == pf["handoffs"]
        assert pf["handoff_wire_mb"] == 0

    @pytest.mark.slow
    def test_refill_fallback_matches_unified(self, model):
        """A store too small for wire pages (max_val 4096 ==
        page_wire_bytes) degrades to re-prefill-from-record — and the
        bytes still match the unified lane exactly."""
        uni, _ = _serve("uni4k", _unified, model, PROMPTS,
                        max_val=4096)
        spl, stats = _serve("spl4k", _split, model, PROMPTS,
                            max_val=4096)
        assert spl == uni
        pf, dl = stats
        assert pf["handoffs"] >= 3
        assert dl["handoff_refill"] == pf["handoffs"]
        assert pf["handoff_wire_mb"] == 0


class TestLoopAccounting:
    def test_lanes_open_their_phases_and_handoff_adopt_land(
            self, model, monkeypatch):
        """Both lanes run the loop accounting of the unified lane
        (protocol.CONT_LOOP_PHASES): on each lane's thread the leaves
        never nest and `loop` / `admit` open no annotation; the
        prefill lane's round is gather, prepare, join, handoff, the
        decode lane's gather and adopt; one `infer.handoff` and one
        `infer.adopt` a handed-off request."""
        from libsplinter_tpu.engine import completer as cmod
        from libsplinter_tpu.utils import trace as tmod

        events: list[tuple[int, str, str]] = []

        class _Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append((threading.get_ident(), self.name, "in"))

            def __exit__(self, *exc):
                events.append((threading.get_ident(), self.name, "out"))
                return False

        monkeypatch.setattr(cmod.tracer, "enabled", True)
        monkeypatch.setattr(tmod, "_annotation", _Ann)
        cmod.tracer.reset()
        try:
            _, stats = _serve("acct", _split, model, PROMPTS,
                              joiner=JOINER)
            snap = cmod.tracer.snapshot()
        finally:
            cmod.tracer.reset()
        pf, dl = stats
        assert pf["handoffs"] >= 4 and dl["adopted"] == pf["handoffs"]
        assert snap["infer.handoff"]["n"] == pf["handoffs"]
        assert snap["infer.adopt"]["n"] == dl["adopted"]
        assert snap["infer.join"]["n"] >= pf["handoffs"]
        for p in ("loop", "admit", "chunk", "idle", "gather", "prepare",
                  "emit", "decode", "collect"):
            assert snap[f"infer.{p}"]["n"] > 0, p
        by_thread: dict[int, list] = {}
        for tid, name, what in events:
            by_thread.setdefault(tid, []).append((name, what))
        lanes = {}
        for tid, evs in by_thread.items():
            open_now = None
            for name, what in evs:
                if what == "in":
                    assert open_now is None, (open_now, name)
                    open_now = name
                else:
                    assert open_now == name, (open_now, name)
                    open_now = None
            names = {n for n, _ in evs}
            assert not names & {"infer.loop", "infer.admit",
                                "infer.chunk"}
            lanes["prefill" if "infer.handoff" in names
                  else "decode"] = names
        assert {"infer.gather", "infer.prepare", "infer.join",
                "infer.handoff", "infer.idle"} <= lanes["prefill"]
        assert not lanes["prefill"] & {"infer.adopt", "infer.decode"}
        assert {"infer.gather", "infer.adopt", "infer.decode",
                "infer.collect", "infer.emit",
                "infer.idle"} <= lanes["decode"]
        assert not lanes["decode"] & {"infer.handoff", "infer.prepare"}


class TestPhaseAwareQoS:
    def test_prefill_fast_fails_deadline_inside_prefill_wall(
            self, model):
        """A deadline that lands inside the rolling prefill-wall EMA
        dies typed at admission — BEFORE paying prefill.  The
        no-deadline sibling sails through to DECODE_READY."""
        name, st = _mkstore("ff")
        pf = PrefillLane(st, model=model, **KW)
        th = None
        try:
            pf.attach()
            # a lane that has learned prefill costs ~10 s must reject
            # a deadline 2 s out without serving it
            pf.qos_slack_s = 10.0
            _submit(st, "doomed", "expires in prefill",
                    deadline=time.time() + 2.0)
            _submit(st, "live", "no deadline here")
            th = _run_bg(pf)
            assert _await(st, ["doomed"], timeout=60)
            rec = P.parse_error_payload(st.get("doomed"))
            assert rec["err"] == "deadline_expired"
            assert _settles(lambda: pf.stats.deadline_expired, 1)
            # the live request got the full prefill + handoff
            assert _await(st, ["live"], bit=P.LBL_DECODE_READY,
                          timeout=60)
            assert _settles(lambda: pf._lane_stats["handoffs"], 1)
        finally:
            pf.stop()
            if th:
                th.join(timeout=30)
            st.close()
            Store.unlink(name)

    def test_decode_rejects_expired_handoff_before_adoption(
            self, model):
        """An expired DECODE_READY handoff dies typed at the adopt
        edge — before consuming pool pages or a batch slot — and its
        wire keys leave the store with it."""
        name, st = _mkstore("exp")
        pf = PrefillLane(st, model=model, **KW)
        dl = DecodeLane(st, model=model, **KW)
        tp = td = None
        try:
            pf.attach()
            dl.attach()
            _submit(st, "q", "soon to expire",
                    deadline=time.time() + 1.5)
            tp = _run_bg(pf)
            assert _await(st, ["q"], bit=P.LBL_DECODE_READY,
                          timeout=60)
            pf.stop()
            tp.join(timeout=30)
            time.sleep(1.6)           # let the deadline lapse
            td = _run_bg(dl)
            assert _await(st, ["q"], timeout=60)
            rec = P.parse_error_payload(st.get("q"))
            assert rec["err"] == "deadline_expired"
            assert _settles(lambda: dl.stats.deadline_expired, 1)
            assert dl._lane_stats["adopted"] == 0
            assert _no_handoff_keys(st)
        finally:
            pf.stop()
            dl.stop()
            for th in (tp, td):
                if th:
                    th.join(timeout=30)
            st.close()
            Store.unlink(name)

    def test_adopt_backpressure_keeps_row_decode_ready(self, model):
        """A decode pool that cannot cover the worst-case reservation
        leaves the handoff DECODE_READY (counted, never stranded
        mid-decode) — the autoscaler's pool_occ signal is what turns
        this into capacity."""
        name, st = _mkstore("bp")
        pf = PrefillLane(st, model=model, **KW)
        kw = dict(KW)
        kw["pool_pages"] = 16         # the one-window floor
        dl = DecodeLane(st, model=model, **kw)
        tp = td = None
        try:
            pf.attach()
            dl.attach()
            # squat 15 of the 16 pool pages on a row the lane thinks
            # is free: the worst-case reservation (>= 2 pages) cannot
            # fit in the 1 remaining
            cache = dl._ensure_paged_cache()
            assert cache.ensure(KW["batch_cap"] - 1, 15 * KW["page_size"])
            _submit(st, "q", "too big for that pool")
            tp = _run_bg(pf)
            td = _run_bg(dl)
            assert _await(st, ["q"], bit=P.LBL_DECODE_READY,
                          timeout=60)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if dl._lane_stats["adopt_backpressure"] >= 2:
                    break
                time.sleep(0.05)
            assert dl._lane_stats["adopt_backpressure"] >= 2
            labels = st.labels("q")
            assert labels & P.LBL_DECODE_READY
            assert not labels & (P.LBL_SERVICING | P.LBL_READY)
            assert dl._lane_stats["adopted"] == 0
            # capacity returns -> the parked handoff is adopted and
            # finishes; nothing was stranded by the wait
            cache.free_row(KW["batch_cap"] - 1)
            assert _await(st, ["q"], timeout=60)
            assert dl._lane_stats["adopted"] == 1
        finally:
            pf.stop()
            dl.stop()
            for th in (tp, td):
                if th:
                    th.join(timeout=30)
            st.close()
            Store.unlink(name)


def _seed_handoff(st, key, *, servicing):
    """A handed-off row as the prefill lane leaves it: value bytes,
    DECODE_READY (plus SERVICING when a decode replica has adopted
    it), a v1 record, and one wire page."""
    st.set(key, "prompt bytes")
    st.label_or(key, P.LBL_DECODE_READY
                | (P.LBL_SERVICING if servicing else 0))
    idx = st.find_index(key)
    assert P.write_handoff_record(st, idx, {
        "len": 3, "ids": [1, 2, 3], "carry": 5, "n_tok": 1,
        "remaining": 7, "disp_left": 7, "plen": st.value_len(key),
        "t0": 0, "tenant": 0, "deadline": None, "wire_pages": 1,
        "quant": False})
    st.set(P.handoff_page_key(idx, 0), b"\x01" * 64)
    st.label_or(P.handoff_page_key(idx, 0), P.LBL_DEBUG)
    return idx


class TestCrossLaneReclaim:
    """The two lanes' stripe maps are independent over the SAME slot
    space, so each lane's restart-time reclaim must only touch rows
    on ITS side of the handoff flip: SERVICING-only rows belong to
    prefill, anything carrying DECODE_READY belongs to decode.  A
    sweep that crosses the line deletes a live replica's in-flight
    state and double-services the request."""

    def test_prefill_reclaim_skips_decode_owned_rows(self, model):
        """A restarted prefill replica must not clobber a row a live
        decode replica is mid-decode on (SERVICING|DECODE_READY):
        record and wire pages survive, labels untouched.  Its own
        died-mid-prefill SERVICING-only row is still re-queued."""
        name, st = _mkstore("pfskip")
        pf = PrefillLane(st, model=model, **KW)
        try:
            pf.attach()
            adopted = _seed_handoff(st, "adopted", servicing=True)
            st.set("mine", "died mid prefill")
            st.label_or("mine", P.LBL_SERVICING)
            assert pf._reclaim_stranded() == 1
            labels = st.labels("adopted")
            assert labels & P.LBL_DECODE_READY
            assert labels & P.LBL_SERVICING
            assert P.read_handoff_record(st, adopted) is not None
            assert P.handoff_page_key(adopted, 0) in st
            labels = st.labels("mine")
            assert labels & P.LBL_WAITING and labels & P.LBL_INFER_REQ
            assert not labels & P.LBL_SERVICING
        finally:
            st.close()
            Store.unlink(name)

    def test_decode_reclaim_skips_prefill_claims(self, model):
        """A decode replica attach/restart while prefill work is in
        flight must not touch SERVICING-only rows (a live prefill
        replica's claims).  Its own dead adopter's row rolls back to
        bare DECODE_READY with the slot truncated to plen."""
        name, st = _mkstore("dlskip")
        dl = DecodeLane(st, model=model, **KW)
        try:
            dl.attach()
            st.set("claim", "being prefilled right now")
            st.label_or("claim", P.LBL_SERVICING)
            mine = _seed_handoff(st, "mine", servicing=True)
            plen = P.read_handoff_record(st, mine)["plen"]
            st.set("mine", "prompt bytes plus a dead adopter tail")
            st.label_or("mine", P.LBL_SERVICING | P.LBL_DECODE_READY)
            assert dl._reclaim_stranded() == 1
            labels = st.labels("claim")
            assert labels & P.LBL_SERVICING
            assert not labels & P.LBL_WAITING
            labels = st.labels("mine")
            assert labels & P.LBL_DECODE_READY
            assert not labels & P.LBL_SERVICING
            assert st.value_len("mine") == plen
        finally:
            st.close()
            Store.unlink(name)

    def test_decode_reclaim_record_vanished_requeues(self, model):
        """The WAITING fallback applies ONLY to rows still carrying
        DECODE_READY whose record is gone — nothing to resume from,
        full re-prefill."""
        name, st = _mkstore("dlvan")
        dl = DecodeLane(st, model=model, **KW)
        try:
            dl.attach()
            idx = _seed_handoff(st, "mine", servicing=True)
            P.clear_handoff(st, idx, pages=1)
            assert dl._reclaim_stranded() == 1
            labels = st.labels("mine")
            assert labels & P.LBL_WAITING and labels & P.LBL_INFER_REQ
            assert not labels & (P.LBL_SERVICING | P.LBL_DECODE_READY)
        finally:
            st.close()
            Store.unlink(name)

    def test_handoff_survives_post_flip_bookkeeping_failure(
            self, model, monkeypatch):
        """An error AFTER the DECODE_READY flip (spans.commit here)
        must not reach run_continuous's failure handler — that would
        re-queue a row the decode lane already owns, leaving
        WAITING|DECODE_READY with no record and streaming the first
        token twice."""
        name, st = _mkstore("postflip")
        pf = PrefillLane(st, model=model, **KW)
        th = None

        def boom(*a, **k):
            raise OSError("spans ring full")

        try:
            pf.attach()
            monkeypatch.setattr(pf.spans, "commit", boom)
            _submit(st, "q", "post flip failure")
            th = _run_bg(pf)
            assert _await(st, ["q"], bit=P.LBL_DECODE_READY,
                          timeout=60)
            idx = st.find_index("q")
            assert P.read_handoff_record(st, idx) is not None
            labels = st.labels("q")
            assert not labels & (P.LBL_WAITING | P.LBL_SERVICING)
            assert pf._lane_stats["handoffs"] == 1
            assert pf._lane_stats["handoff_failed"] == 0
        finally:
            pf.stop()
            if th:
                th.join(timeout=30)
            st.close()
            Store.unlink(name)


# ------------------------------------------------------- crash drills

@pytest.fixture
def cstore():
    name = f"/spt-disagg-chaos-{os.getpid()}-{uuid.uuid4().hex[:6]}"
    st = Store.create(name, nslots=128, max_val=16384, vec_dim=8)
    yield st
    st.close()
    Store.unlink(name)


def _supervised_pair_recovers(cstore, fault_spec, crashed_lane,
                              monkeypatch):
    """Both disaggregated lanes as restartable children under `spt
    supervise`, one of them armed to crash mid-handoff: every
    admitted request must still converge to READY with the prompt
    intact, the crashed lane must have been restarted, and no wire
    key may outlive its request (zero admitted loss, nothing
    stranded)."""
    from libsplinter_tpu.engine.supervisor import Supervisor

    monkeypatch.setenv("SPTPU_FAULT", fault_spec)
    monkeypatch.setenv("SPTPU_CHAOS_RUN_S", "600")
    cstore.set("q", "hello disaggregated")
    cstore.label_or("q", P.LBL_INFER_REQ | P.LBL_WAITING)
    cstore.bump("q")

    holder: dict = {}

    def spawn(lane):
        role = ("prefill_lane" if lane.name == "prefill"
                else "decode_lane")
        return subprocess.Popen(
            [sys.executable, CHILD, role, cstore.name],
            env=holder["sup"]._child_env(lane))

    sup = Supervisor(cstore.name, lanes=("prefill", "decode"),
                     spawn_fn=spawn, store=cstore,
                     backoff_base_ms=100, backoff_max_ms=2000,
                     breaker_threshold=8, breaker_window_s=240,
                     startup_grace_s=300)
    holder["sup"] = sup
    t = threading.Thread(target=sup.run,
                         kwargs={"poll_interval_s": 0.1,
                                 "stop_after": 420.0})
    t.start()
    try:
        deadline = time.monotonic() + 360
        while time.monotonic() < deadline:
            if cstore.labels("q") & P.LBL_READY:
                break
            time.sleep(0.25)
        assert cstore.labels("q") & P.LBL_READY, sup.lanes
        assert sup.lanes[crashed_lane].restarts >= 1
        assert sup.lanes[crashed_lane].state != "down"
        assert cstore.get("q").rstrip(b"\0").startswith(
            b"hello disaggregated")
        # a request submitted AFTER the crash round-trips too (the
        # generation-2 child serves with the fault stripped)
        cstore.set("q2", "again, disaggregated")
        cstore.label_or("q2", P.LBL_INFER_REQ | P.LBL_WAITING)
        cstore.bump("q2")
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if cstore.labels("q2") & P.LBL_READY:
                break
            time.sleep(0.25)
        assert cstore.labels("q2") & P.LBL_READY
        assert cstore.get("q2").rstrip(b"\0").startswith(
            b"again, disaggregated")
        for k in ("q", "q2"):
            assert not cstore.labels(k) & (
                P.LBL_INFER_REQ | P.LBL_SERVICING
                | P.LBL_DECODE_READY)
        assert _no_handoff_keys(cstore)
    finally:
        sup.stop()
        t.join()
        sup.shutdown()


@pytest.mark.chaos
@pytest.mark.slow
def test_supervise_recovers_prefill_handoff_crash(cstore, monkeypatch):
    """The prefill lane crashes at prefill.handoff — wire pages
    written, NO record, row still SERVICING.  The restarted lane's
    stripe-scoped reclaim sweeps the orphan wire keys, re-queues the
    row WAITING, and the second pass hands it off cleanly."""
    _supervised_pair_recovers(cstore, "prefill.handoff:crash@1",
                              "prefill", monkeypatch)


@pytest.mark.chaos
@pytest.mark.slow
def test_supervise_recovers_decode_adopt_crash(cstore, monkeypatch):
    """The decode lane crashes at decode.adopt — the handoff claimed
    (SERVICING|DECODE_READY), nothing imported.  Recovery re-opens
    the row to bare DECODE_READY (slot truncated to the record's
    plen) and the restarted lane re-adopts from the surviving wire
    pages."""
    _supervised_pair_recovers(cstore, "decode.adopt:crash@1",
                              "decode", monkeypatch)
