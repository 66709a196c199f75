"""Cross-lane distributed tracing: span records, the shared span
ring, and span-tree assembly.

PR 2's flight recorder reconstructs one request's journey through ONE
daemon; a request has not been a lane-local event since the pipeline
lane (PR 12) started chaining ingest -> embed -> top-k -> complete
server-side.  This module is the cross-lane layer: every lane commits
one SPAN RECORD per traced request into a shared bounded ring in the
store, each span carrying the trace context (trace id + parent span
id, propagated through the `__tr_<idx>` stamp — engine/protocol.py),
the request's queue-enter / admit / commit wall clocks, and the
queue-wait vs service-time split the CPU-inference paper (PAPERS.md,
arxiv 2406.07553) argues is THE decomposition that matters under
open-loop load.  `spt trace show <id>` assembles the tree;
`spt trace export` emits Chrome/Perfetto trace-event JSON.

Wire protocol (all keys in engine/protocol.py):

  - ``__sp_<idx>``   pending-span STAGING row, written at admission.
    This is the crash-surviving half: a lane that dies mid-service
    leaves the staging row (and the un-consumed trace stamp) behind,
    so the restarted lane's re-drain recovers the chain identity, the
    ORIGINAL queue-enter clock, and the attempt count — the committed
    span then shows the restart gap instead of silently restarting
    the clock.  Orphans (slot epoch moved under a raced rewrite, or
    TTL) are swept by `sweep_span_stages` on the lanes' heartbeat
    cadence and by `protocol.shed_orphan_stamp`'s discard path —
    the `__sr_` reaper discipline, so the staging rows cannot leak.
  - ``__span_<i>``   the bounded ring of COMMITTED spans: the slot is
    claimed by atomically incrementing the ``__span_head`` BIGUINT,
    so concurrent lanes never fight over a slot and the ring is
    bounded by construction (old spans overwrite).

Span capture is ALWAYS ON — its cost is bounded by head sampling
(only stamped requests pay anything; `spt loadgen --trace-sample p`
seeds the decision) and gated under the obs-check <3% overhead
budget.  Tail capture of slow requests rides the recorder's existing
slow-log machinery; lanes may additionally stamp `tail: true` spans
for SLO violators.

Everything here is host-side stdlib + store calls — no jax — so the
pipeline lane and the telemetry sampler import it freely.
"""
from __future__ import annotations

import json
import time

from .. import _native as N
from ..engine import protocol as P

# staging rows older than this are orphans even when their slot never
# moved (a client that stamped and gave up); generous vs any sane
# request deadline, the __sr_ reaper's value
STAGE_TTL_S = 120.0

# span-record statuses (the typed-error vocabulary, plus ok)
OK = "ok"


def span_ring_size(store) -> int:
    """The ring length for a store — derived from geometry so every
    writer agrees without coordination: an eighth of the slots,
    clamped to [16, 128] (a tiny test store must not drown in ring
    keys; a big one keeps useful history)."""
    return max(16, min(128, store.nslots // 8))


# staging wire form (compact, JSON-free — this is wake-path work):
# "tid:span:parent:epoch:attempts:t_queue:gap_ms:ts"
def _encode_stage(pend: "PendingSpan", now: float) -> str:
    return (f"{pend.tid}:{pend.span}:{pend.parent}:{pend.epoch}:"
            f"{pend.attempts}:{pend.t_queue:.6f}:{pend.gap_ms:.3f}:"
            f"{now:.6f}")


def decode_stage(raw: bytes) -> dict | None:
    """Parse a staging row; None when unreadable (retire it)."""
    try:
        parts = raw.rstrip(b"\0").decode().split(":")
        return {"tid": int(parts[0]), "sp": int(parts[1]),
                "pa": int(parts[2]), "e": int(parts[3]),
                "a": int(parts[4]), "tq": float(parts[5]),
                "gap": float(parts[6]), "ts": float(parts[7])}
    except (ValueError, IndexError, UnicodeDecodeError):
        return None


class PendingSpan:
    """One in-service traced request's span state, held by the lane
    between admission and commit."""

    __slots__ = ("idx", "epoch", "key", "tid", "parent", "span",
                 "t_queue", "t_admit", "attempts", "gap_ms", "tenant")

    def __init__(self, idx, epoch, key, tid, parent, span, t_queue,
                 t_admit, attempts=1, gap_ms=0.0, tenant=0):
        self.idx = idx
        self.epoch = epoch
        self.key = key
        self.tid = tid
        self.parent = parent
        self.span = span
        self.t_queue = t_queue       # client stamp wall ts (0 unknown)
        self.t_admit = t_admit       # this lane's admit wall ts
        self.attempts = attempts     # 1 = first service attempt
        self.gap_ms = gap_ms         # wall lost to restarts (attempt>1)
        self.tenant = tenant

    @property
    def stamp(self) -> tuple[int, float]:
        """The legacy (trace_id, client_wall_ts) pair the flight
        recorders consume — one accessor so the two obs layers can't
        disagree about what the stamp said."""
        return self.tid, self.t_queue


class SpanWriter:
    """Per-lane span capture.  `begin` at admission, `commit` at the
    result commit; both never raise — tracing must never fail a
    request.

    Wake-path discipline: a store WRITE costs tens of microseconds in
    a live daemon (dirty-mask + event-bus signalling), so the hot
    path pays as few as possible.  Committed records BUFFER in memory
    and `flush()` lands them in the shared ring on the heartbeat
    cadence (publish_stats / run_once call it) — the obs-check <3%
    budget gates exactly this split.  `staged=True` additionally
    writes the per-request `__sp_<idx>` staging row at begin (one
    write), buying crash recovery with attempt counts and restart-gap
    attribution — the pipeline lane opts in (its requests live whole
    chains); the one-drain lanes rely on the stamp itself surviving
    until commit, so a crashed drain still re-services with the chain
    identity intact (the restart shows up as queue wait).  `eager`
    flushes every commit immediately (the pipeline lane again — its
    pump is not a device wake path)."""

    def __init__(self, store, lane: str, *, staged: bool = False,
                 eager: bool = False, max_buffer: int = 128):
        self.store = store
        self.lane = lane
        self.staged = staged
        self.eager = eager
        self.max_buffer = max(1, max_buffer)
        self.committed = 0           # spans landed in the ring
        self.recovered = 0           # crash-recovered staging rows
        self.dropped = 0             # ring/staging writes that failed
        self._buf: list[dict] = []   # committed, awaiting flush
        self._head_ready = False     # __span_head known to exist

    # -- admission ---------------------------------------------------------

    def begin(self, idx: int, epoch: int,
              tenant: int = 0) -> PendingSpan | None:
        """Open a span for the traced request in slot idx: read the
        trace context (stamp left IN PLACE — it must survive a crash),
        recover a previous attempt's staging row if one exists, and
        (re)write the staging row.  Returns None when the row carries
        no usable context (stale stamp: consumed, exactly the legacy
        discipline)."""
        st = self.store
        ctx = P.read_trace_ctx(st, idx, epoch=epoch)
        stage = self._read_stage(idx) if self.staged else None
        now = time.time()
        if stage is not None and stage["e"] == epoch and (
                ctx is None or stage["tid"] == ctx[0]):
            # a previous attempt staged this request and never
            # committed: a lane crash mid-service.  Keep the original
            # queue-enter clock and span id; the committed span will
            # carry the attempt count and the restart gap.
            attempts = stage["a"] + 1
            gap_ms = max(now - stage["ts"], 0.0) * 1e3 + stage["gap"]
            tid, parent, span = stage["tid"], stage["pa"], stage["sp"]
            t_queue = stage["tq"]
            self.recovered += 1
        elif ctx is not None:
            tid, t_queue, parent, span = ctx
            attempts, gap_ms = 1, 0.0
            if stage is not None:     # stale staging from another life
                P.clear_span_stage(st, idx)
        else:
            if stage is not None:
                P.clear_span_stage(st, idx)
            return None
        pend = PendingSpan(idx, epoch, None, tid, parent, span,
                           t_queue, now, attempts, gap_ms, tenant)
        if self.staged:
            # consume-late: the stamp must survive a crash so the
            # restarted lane recovers the chain identity; the staging
            # row carries the attempt count + restart gap
            self._write_stage(pend, now)
        else:
            # consume-early (the pre-span discipline): one-drain
            # lanes retire the stamp here, while the slot is still
            # this request's — commit() then touches no stamp at all
            # on the wake path
            P.clear_trace_stamp(st, idx)
            try:
                pend.key = st.key_at(idx)
                if pend.key is not None:
                    st.label_clear(pend.key, P.LBL_TRACED)
            except (KeyError, OSError):
                pass
        return pend

    def _read_stage(self, idx: int) -> dict | None:
        # contains-check first: the no-crash common case must not pay
        # a full buffered get + KeyError for a row that isn't there
        sk = P.span_stage_key(idx)
        if sk not in self.store:
            return None
        try:
            return decode_stage(self.store.get(sk))
        except (KeyError, OSError):
            return None

    def _write_stage(self, pend: PendingSpan, now: float) -> None:
        try:
            self.store.set(P.span_stage_key(pend.idx),
                           _encode_stage(pend, now))
        except (KeyError, OSError):
            self.dropped += 1        # full store: the span loses its
            # crash survival, the request loses nothing

    # -- commit ------------------------------------------------------------

    def commit(self, pend: PendingSpan | None, *, status: str = OK,
               stages: dict | None = None,
               extra: dict | None = None,
               device_ms: float | None = None) -> bool:
        """Finalize one span: build the record (buffered for flush),
        retire the staging row, and retire the trace stamp +
        LBL_TRACED on the request key while the stamp is still OURS.
        `stages` is the lane's per-stage ms map (the pinned *_STAGES
        vocabulary) when stage tracing was on.  `device_ms` is the
        drain's device window (DEVTIME.take_lane_ms) — drain-scoped:
        the whole batch's dispatch->collect wall is attributed to the
        traced span(s) that rode it, so it may exceed this one span's
        service slice under heavy batching (a ceiling, never an
        undercount)."""
        if pend is None:
            return False
        st = self.store
        now = time.time()
        if pend.key is None:
            try:
                pend.key = st.key_at(pend.idx)
            except (KeyError, OSError):
                pass
        # the record itself is BUILT at flush time — the wake path
        # pays only this append and (staged lanes only) the cleanup
        self._buf.append((pend, status, stages, extra, now, device_ms))
        if self.staged:
            # consume-late cleanup: the staging row retires; the
            # stamp + label only while the stamp is still OURS
            # (content-gated, not epoch-gated — a client that
            # re-stamped mid-service owns the slot's NEW stamp and
            # keeps it)
            P.clear_span_stage(st, pend.idx)
            try:
                ctx = P.read_trace_ctx(st, pend.idx)
                if ctx is not None and ctx[3] == pend.span:
                    P.clear_trace_stamp(st, pend.idx)
                    if pend.key is not None:
                        st.label_clear(pend.key, P.LBL_TRACED)
            except (KeyError, OSError):
                pass
        if self.eager or len(self._buf) >= self.max_buffer:
            self.flush()
        return True

    def tail_span(self, key, wall_ms: float, *, status: str = OK,
                  stages: dict | None = None,
                  extra: dict | None = None,
                  device_ms: float | None = None,
                  tenant: int = 0) -> int | None:
        """Tail-based retention: synthesize a span for a SLOW request
        that carried no trace stamp — the slow log keeps full stage
        detail for SLO violators even when head sampling skipped them.
        Allocates a fresh trace id (returned so the recorder's slow
        entry resolves via `spt trace show <id>`); the record carries
        `tail: true` and a service window covering the measured wall.
        Never raises (tracing must never fail a request)."""
        try:
            tid = P.next_trace_id()
        except Exception:
            return None
        now = time.time()
        pend = PendingSpan(-1, 0, key, tid, 0, tid, 0.0,
                           now - max(wall_ms, 0.0) / 1e3,
                           tenant=tenant)
        ex = {"tail": True}
        if extra:
            ex.update(extra)
        self._buf.append((pend, status, stages, ex, now, device_ms))
        if self.eager or len(self._buf) >= self.max_buffer:
            self.flush()
        return tid

    @staticmethod
    def _build(lane: str, pend: PendingSpan, status: str,
               stages: dict | None, extra: dict | None,
               now: float, device_ms: float | None = None) -> dict:
        queue_ms = max(now - pend.t_queue, 0.0) * 1e3 \
            if pend.t_queue > 0 else 0.0
        service_ms = max(now - pend.t_admit, 0.0) * 1e3
        # queue-wait vs service-time split: everything before this
        # lane admitted the request is queue (client submit -> admit,
        # including any restart gap), everything after is service
        queue_ms = max(queue_ms - service_ms, 0.0)
        rec = {"tid": pend.tid, "span": pend.span,
               "parent": pend.parent, "lane": lane,
               "key": pend.key, "idx": pend.idx, "e": pend.epoch,
               "status": status,
               "t_queue": round(pend.t_queue, 6),
               "t_admit": round(pend.t_admit, 6),
               "t_commit": round(now, 6),
               "queue_ms": round(queue_ms, 3),
               "service_ms": round(service_ms, 3),
               "ts": round(now, 3)}
        if device_ms is not None and device_ms > 0:
            # schema v3: host service decomposes into dispatch_queue
            # (host-side work before/around the device window) and
            # device_ms (dispatch->collect wall, drain-scoped)
            rec["device_ms"] = round(device_ms, 3)
            rec["dispatch_queue"] = round(
                max(service_ms - device_ms, 0.0), 3)
        if pend.tenant:
            rec["tenant"] = pend.tenant
        if pend.attempts > 1:
            rec["attempts"] = pend.attempts
            rec["gap_ms"] = round(pend.gap_ms, 3)
        if stages:
            rec["stages"] = {k: round(float(v), 3)
                             for k, v in stages.items()}
        if extra:
            rec.update(extra)
        return rec

    def flush(self) -> int:
        """Build and land the buffered records in the shared ring —
        heartbeat-cadence work (publish_stats / run_once), NOT the
        wake path: each ring write signals the store's event bus,
        which is exactly the cost the <3% obs budget keeps off
        serving drains.  Returns records landed."""
        if not self._buf:
            return 0
        buf, self._buf = self._buf, []
        st = self.store
        landed = 0
        for pend, status, stages, extra, now, device_ms in buf:
            rec = self._build(self.lane, pend, status, stages, extra,
                              now, device_ms)
            slot = self._claim_ring_slot()
            ok = False
            if slot is not None:
                try:
                    st.set(P.span_ring_key(slot), json.dumps(rec))
                    ok = True
                except OSError:
                    rec.pop("stages", None)  # too big: drop the
                    try:                     # optional section,
                        st.set(P.span_ring_key(slot),  # keep the span
                               json.dumps(rec))
                        ok = True
                    except (KeyError, OSError):
                        pass
                except KeyError:
                    pass
            if ok:
                landed += 1
            else:
                self.dropped += 1
        self.committed += landed
        return landed

    def _claim_ring_slot(self) -> int | None:
        """Atomically claim the next ring slot index (multi-writer
        safe — the BIGUINT head increments across processes).  None
        when the store cannot host the counter (full store: spans
        degrade to nothing, serving is untouched)."""
        st = self.store
        try:
            if not self._head_ready:
                if P.KEY_SPAN_HEAD not in st:
                    st.set_uint(P.KEY_SPAN_HEAD, 0)
                self._head_ready = True
            head = int(st.integer_op(P.KEY_SPAN_HEAD, N.IOP_INC))
        except (KeyError, OSError, ValueError):
            self._head_ready = False
            return None
        return (head - 1) % span_ring_size(st)

    def counters(self) -> dict:
        """The heartbeat `spans_obs` section (droppable under a tiny
        store's max_val, like every optional section; `spt metrics`
        renders it flat as sptpu_<lane>_spans_*)."""
        return {"committed": self.committed,
                "recovered": self.recovered,
                "dropped": self.dropped,
                "pending": len(self._buf)}


# -- sweeps ----------------------------------------------------------------

def sweep_span_stages(store, *, ttl_s: float = STAGE_TTL_S,
                      now: float | None = None, stats=None) -> int:
    """Retire orphaned pending-span staging rows: slot gone, slot
    epoch moved past the staged one (raced rewrite — the new occupant
    stages its own span), or TTL expired (a crashed chain nobody ever
    re-drained).  Heartbeat-cadence work, mirroring the `__sr_`
    reaper; returns the reaped count.  `stats`, where given, has the
    searcher's counters: `sweep_keys` grows by the live keys the
    native prefix scan passed, `sweep_rows` by the rows it matched."""
    now = time.time() if now is None else now
    pfx = P.SPAN_STAGE_PREFIX
    reaped = 0
    keys, scanned = store.scan_prefix(pfx)
    if stats is not None:
        stats.sweep_keys += scanned
        stats.sweep_rows += len(keys)
    for key in keys:
        try:
            idx = int(key[len(pfx):])
        except ValueError:
            continue
        try:
            rec = decode_stage(store.get(key))
        except (KeyError, OSError):
            continue
        if rec is None:
            retire = True             # unreadable/legacy: retire
        elif idx >= store.nslots or store.key_at(idx) is None:
            retire = True
        elif store.epoch_at(idx) != rec["e"]:
            retire = True
        else:
            retire = (now - rec["ts"]) > ttl_s
        if retire:
            try:
                store.unset(key)
                reaped += 1
            except (KeyError, OSError):
                pass
    return reaped


# -- assembly / export -----------------------------------------------------

def collect_spans(store, trace_id: int | None = None) -> list[dict]:
    """Every committed span in the ring (optionally one trace's),
    oldest commit first."""
    out: list[dict] = []
    for i in range(span_ring_size(store)):
        try:
            raw = store.get(P.span_ring_key(i)).rstrip(b"\0")
            rec = json.loads(raw)
        except (KeyError, OSError, ValueError):
            continue
        if not isinstance(rec, dict) or "tid" not in rec:
            continue
        if trace_id is not None and rec.get("tid") != trace_id:
            continue
        out.append(rec)
    out.sort(key=lambda r: (r.get("t_admit", 0.0), r.get("span", 0)))
    return out


def assemble_tree(spans: list[dict]) -> dict:
    """One trace's spans -> a tree: {"tid", "root": node, ...} where
    each node is {"span": record | None, "children": [node...]}.
    Spans whose parent is not in the set hang under a synthesized
    root (the client-side chain case: hops are siblings under the
    originating client, which never commits a span of its own)."""
    if not spans:
        return {"tid": None, "root": {"span": None, "children": []}}
    tid = spans[0].get("tid")
    by_span = {s.get("span"): {"span": s, "children": []}
               for s in spans}
    root = {"span": None, "children": []}
    for s in spans:
        node = by_span[s.get("span")]
        parent = s.get("parent", 0)
        if parent and parent in by_span and parent != s.get("span"):
            by_span[parent]["children"].append(node)
        else:
            root["children"].append(node)
    # a single top-level span IS the root (the stored-script case:
    # the pipeliner's script span, verbs underneath)
    if len(root["children"]) == 1:
        root = root["children"][0]
    return {"tid": tid, "root": root}


def render_tree(tree: dict) -> list[str]:
    """ASCII rendering with the per-hop queue/service breakdown —
    what `spt trace show` prints."""
    out: list[str] = []
    tid = tree.get("tid")
    out.append(f"trace {tid:#x} (pid {tid >> 24})" if tid
               else "trace <empty>")

    def fmt(node, depth):
        s = node.get("span")
        pad = "  " * depth
        if s is not None:
            line = (f"{pad}└─ [{s.get('lane')}] key={s.get('key')!r} "
                    f"span={s.get('span', 0):#x} "
                    f"queue={s.get('queue_ms', 0)}ms "
                    f"service={s.get('service_ms', 0)}ms "
                    f"status={s.get('status')}")
            if s.get("device_ms") is not None:
                line += (f" device={s['device_ms']}ms "
                         f"dispatch_queue="
                         f"{s.get('dispatch_queue', 0)}ms")
            if s.get("tail"):
                line += " tail"
            if s.get("attempts", 1) > 1:
                line += (f" attempts={s['attempts']} "
                         f"restart_gap={s.get('gap_ms', 0)}ms")
            if s.get("tenant"):
                line += f" tenant={s['tenant']}"
            out.append(line)
            stages = s.get("stages")
            if stages:
                out.append(pad + "     stages: " + " ".join(
                    f"{k}={v}ms" for k, v in stages.items()))
        kids = sorted(node.get("children", ()),
                      key=lambda n: (n["span"] or {}).get("t_admit", 0))
        for child in kids:
            fmt(child, depth + (0 if s is None else 1))

    fmt(tree.get("root", {}), 0)
    if len(out) == 1:
        out.append("  (no spans committed for this trace)")
    return out


_LANE_PIDS = {"client": 1, "embedder": 2, "searcher": 3,
              "completer": 4, "pipeliner": 5, "telemetry": 6}
# device tracks render as their own "processes" beside the host lanes
# (pid = lane pid + _DEVICE_PID_OFFSET, named "device:<lane>"); the
# compile-event instants get one dedicated track of their own
_DEVICE_PID_OFFSET = 10
_COMPILE_PID = 90


def to_chrome_trace(spans: list[dict],
                    compile_events: list[dict] | None = None) -> dict:
    """Chrome/Perfetto trace-event JSON for a set of spans (one trace
    or the whole ring): per span one `X` (complete) slice for the
    service window plus one for the queue wait, grouped into one
    "process" per lane with `M` metadata naming it — load the output
    straight into ui.perfetto.dev or chrome://tracing.  Spans carrying
    the v3 `device_ms` split additionally emit a device slice on the
    lane's `device:<lane>` track (placed at the tail of the service
    window — dispatch_queue first, then the device window); compile
    ledger records (obs/devtime.py) land as `i` instants on the
    dedicated compile track."""
    events: list[dict] = []
    lanes_seen: set[str] = set()
    device_lanes: set[str] = set()
    for s in spans:
        lane = str(s.get("lane", "?"))
        pid = _LANE_PIDS.get(lane, 99)
        tid = int(s.get("tid", 0))
        lanes_seen.add(lane)
        t_admit = float(s.get("t_admit", 0.0))
        t_queue = float(s.get("t_queue", 0.0)) or t_admit
        queue_ms = float(s.get("queue_ms", 0.0))
        service_ms = float(s.get("service_ms", 0.0))
        args = {"trace": f"{tid:#x}",
                "span": f"{int(s.get('span', 0)):#x}",
                "parent": f"{int(s.get('parent', 0)):#x}",
                "status": str(s.get("status", "?")),
                "attempts": int(s.get("attempts", 1))}
        if s.get("stages"):
            args["stages"] = s["stages"]
        if queue_ms > 0:
            events.append({
                "name": f"queue {s.get('key')}", "cat": "queue",
                "ph": "X", "ts": round(t_queue * 1e6, 1),
                "dur": round(queue_ms * 1e3, 1),
                "pid": pid, "tid": tid & 0xFFFFFF, "args": args})
        events.append({
            "name": f"{lane} {s.get('key')}", "cat": "span",
            "ph": "X", "ts": round(t_admit * 1e6, 1),
            "dur": round(max(service_ms, 0.001) * 1e3, 1),
            "pid": pid, "tid": tid & 0xFFFFFF, "args": args})
        device_ms = float(s.get("device_ms", 0.0))
        if device_ms > 0:
            device_lanes.add(lane)
            # the device window closes the service slice: host-side
            # dispatch_queue first, then dispatch->collect
            t_dev = t_admit + max(service_ms - device_ms, 0.0) / 1e3
            events.append({
                "name": f"device {s.get('key')}", "cat": "device",
                "ph": "X", "ts": round(t_dev * 1e6, 1),
                "dur": round(max(device_ms, 0.001) * 1e3, 1),
                "pid": pid + _DEVICE_PID_OFFSET,
                "tid": tid & 0xFFFFFF, "args": args})
    for ev in compile_events or ():
        events.append({
            "name": f"compile {ev.get('program', '?')}",
            "cat": "compile", "ph": "i", "s": "p",
            "ts": round(float(ev.get("ts", 0.0)) * 1e6, 1),
            "pid": _COMPILE_PID, "tid": 0,
            "args": {"program": str(ev.get("program", "?")),
                     "lane": str(ev.get("lane", "?")),
                     "shapes_key": str(ev.get("shapes_key", "?")),
                     "duration_ms": float(ev.get("duration_ms", 0.0)),
                     "generation": int(ev.get("generation", 0)),
                     "cause": str(ev.get("cause", "?"))}})
    for lane in sorted(lanes_seen):
        events.append({"name": "process_name", "ph": "M",
                       "pid": _LANE_PIDS.get(lane, 99), "tid": 0,
                       "args": {"name": f"lane:{lane}"}})
    for lane in sorted(device_lanes):
        events.append({"name": "process_name", "ph": "M",
                       "pid": (_LANE_PIDS.get(lane, 99)
                               + _DEVICE_PID_OFFSET), "tid": 0,
                       "args": {"name": f"device:{lane}"}})
    if compile_events:
        events.append({"name": "process_name", "ph": "M",
                       "pid": _COMPILE_PID, "tid": 0,
                       "args": {"name": "compiles"}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"generator": "spt trace export",
                          "spans": len(spans),
                          "compile_events": len(compile_events or ())}}
