"""The coordination contract between clients and the inference daemons.

Mirrors the reference's label state machine and well-known keys
(splinterrc_example:83-85, splinter.h:477-491, splinference.cpp:50-89,
splainference.cpp:51-109; SURVEY.md §2.2) so a client written against the
reference's conventions finds identical behavior here.
"""
import itertools
import json
import os
import time
from collections.abc import Sequence

from .. import _native as N

# --- bloom labels (bit masks) -------------------------------------------
LBL_EMBED_REQ = 0x1            # "embed me" — wakes the embedding daemon
LBL_WAITING = 0x40             # client is blocked on this key
LBL_CTX_EXCEEDED = 0x80        # input exceeded the model context window
LBL_CHUNK = 0x200              # ingest: document chunk
LBL_META = 0x400               # ingest: metadata slot
LBL_SCRIPT_REQ = 0x1 << 56     # "run my script" — wakes the pipeline lane
LBL_SEARCH_REQ = 0x1 << 57     # "search me" — wakes the search daemon
LBL_TRACED = 0x1 << 58         # request carries a trace stamp (obs)
LBL_DEADLINE = 0x1 << 52       # request carries a deadline stamp (QoS)
LBL_DECODE_READY = 0x1 << 53   # prefill committed; awaiting decode adoption
LBL_MAX_NEW = 0x1 << 54        # request carries its own answer budget
LBL_DEBUG = 0x1 << 59          # debug channel (sidecar watches this)
LBL_INFER_REQ = 0x1 << 60      # "complete me" — wakes the completion daemon
LBL_SERVICING = 0x1 << 61      # completion in progress
LBL_READY = 0x1 << 62          # completion finished

# --- bloom bit indices (for watch_label_register) -----------------------
BIT_EMBED_REQ = 0
BIT_WAITING = 6
BIT_CTX_EXCEEDED = 7
BIT_SCRIPT_REQ = 56
BIT_SEARCH_REQ = 57
BIT_DEADLINE = 52
BIT_DECODE_READY = 53
BIT_MAX_NEW = 54
BIT_DEBUG = 59
BIT_INFER_REQ = 60

# --- multi-tenant QoS label field ----------------------------------------
# The tenant id rides the request's own bloom label word, bits 48..51
# (ids 1..15; 0 = the untagged default tenant), the way LBL_TRACED
# rides bit 58: daemons read every candidate's label word anyway, so
# tenant discovery costs nothing, and one tenant's waiting rows can be
# enumerated cheaply with a bloom prefilter
# (enumerate_indices(tenant_label(t) | LBL_SEARCH_REQ)).  Daemons
# never clear the tenant field — it survives the WAITING->SERVICING->
# READY trifecta so post-hoc accounting can still attribute the slot.
TENANT_SHIFT = 48
TENANT_BITS = 4
TENANT_MASK = ((1 << TENANT_BITS) - 1) << TENANT_SHIFT
MAX_TENANT = (1 << TENANT_BITS) - 1            # 15


def tenant_label(tenant: int) -> int:
    """The label bits encoding `tenant` (1..MAX_TENANT; 0 = none)."""
    if not 0 <= tenant <= MAX_TENANT:
        raise ValueError(
            f"tenant id must be 0..{MAX_TENANT}, got {tenant}")
    return tenant << TENANT_SHIFT


def read_tenant(labels: int) -> int:
    """Extract the tenant id from a slot's label word (0 = untagged)."""
    return (labels & TENANT_MASK) >> TENANT_SHIFT


def stamp_tenant(store, key: str, tenant: int) -> None:
    """Client-side: tag the pending request on `key` with its tenant id
    (best after set, before the bump — like stamp_trace).  Replaces any
    previous tenant tag.  Never raises: a missing key is the caller's
    race to discover."""
    bits = tenant_label(tenant)                # validates range
    try:
        store.label_clear(key, TENANT_MASK)
        if bits:
            store.label_or(key, bits)
    except (KeyError, OSError):
        pass

# --- signal groups -------------------------------------------------------
GROUP_EMBED = 2                # embedding daemon wake group
GROUP_INFER = 3                # completion daemon wake group
GROUP_SEARCH = 4               # search daemon wake group
GROUP_SCRIPT = 5               # pipeline (scripted-chain) lane wake group
GROUP_DEBUG = 63               # sidecar debug group

# --- shard ids / priorities (cooperative advisement) --------------------
SHARD_EMBED = 0x5F10
SHARD_COMPLETE = 0x5F1A
SHARD_SEARCH = 0x5F1B
SHARD_SCRIPT = 0x5F1C
PRIO_EMBED_LIVE = 40
PRIO_EMBED_BACKFILL = 20
PRIO_COMPLETE = 200
PRIO_SEARCH = 150
PRIO_SCRIPT = 100

# --- well-known keys -----------------------------------------------------
KEY_DONE_LANE = "__lane_dw_2"  # pulsed after each committed embedding
KEY_DEBUG = "__debug"          # append-only shared debug log
KEY_SYSTEM_PROMPT = "__system_prompt"
# periodic daemon heartbeats: JSON stats snapshots, debug-labeled so
# the sidecar's group-63 watch surfaces them (the reference's only
# runtime telemetry is the __debug append channel; these are the
# structured counterpart).  Every lane's heartbeat carries the
# dispatch-overlap gauges (PR 7, engine/resident.py): inflight_depth
# (the configured K) + inflight_peak, and on the embedder the
# resident-ring gauges (ring_depth / ring_occupancy /
# resident_iterations / ring_faults) in their own size-droppable
# "dispatch" section — `spt metrics` renders them flat as
# sptpu_<lane>_inflight_depth etc., so saturation of the overlap
# window is visible in production.
KEY_EMBED_STATS = "__embedder_stats"
KEY_COMPLETE_STATS = "__completer_stats"
KEY_SEARCH_STATS = "__searcher_stats"
KEY_SCRIPT_STATS = "__pipeliner_stats"
# disaggregated completion lanes (prefill / decode split): each lane
# type heartbeats under its own key so telemetry, `spt metrics`, and
# the autoscaler read the two phases as separate lanes — a unified
# completer keeps KEY_COMPLETE_STATS untouched
KEY_PREFILL_STATS = "__prefill_stats"
KEY_DECODE_STATS = "__decode_stats"
# the supervisor's own heartbeat (engine/supervisor.py): per-lane
# process state — pid, generation, restart/backoff/breaker counters,
# and the breaker's down marker CLI clients consult before dispatching
# to a lane (daemon_live checks it so a broken lane fails fast instead
# of burning the full submit timeout)
KEY_SUPERVISOR_STATS = "__supervisor_stats"
SEARCH_SCRATCH_PREFIX = "__sqtmp_"   # search query scratch key per pid
# search-daemon results: one JSON row per serviced request, keyed by
# the REQUEST's slot index (__sr_<idx>) — the client polls its request
# key and reads the companion once LBL_SEARCH_REQ clears
SEARCH_RESULT_PREFIX = "__sr_"
# pipeline-lane results: one JSON row per finished script, keyed by
# the REQUEST's slot index (__pr_<idx>) — {"ok": true, "ret": [...]}
# or a typed error record ({"err": "budget_exceeded" | "script_error"
# | "deadline_expired" | "overloaded", ...}); the client polls its
# request key and reads the companion once LBL_SCRIPT_REQ clears
SCRIPT_RESULT_PREFIX = "__pr_"
# stored named scripts (the reference's "programs next to the data"):
# `spt pipeline put NAME file.lua` writes the source under
# __script_<NAME>; a request naming it ({"name": "NAME"}) runs it
# server-side without shipping the source per call
SCRIPT_STORE_PREFIX = "__script_"
# flight-recorder dumps (obs/recorder.py): each daemon publishes its
# ring of per-request wake->commit traces here alongside its stats
# heartbeat; `spt trace tail` reads them cross-process
KEY_EMBED_TRACE = "__embedder_trace"
KEY_COMPLETE_TRACE = "__completer_trace"
KEY_SEARCH_TRACE = "__searcher_trace"
KEY_SCRIPT_TRACE = "__pipeliner_trace"

# context guard: reject inputs >= this fraction of the model window
CTX_GUARD_FRACTION = 0.9

# --- commit-pipeline stage contract --------------------------------------
# The wake->commit path decomposes into these stages; every stats
# surface (the embedder heartbeat's quantiles section,
# flight-recorder event sequences) uses these names
# so dashboards and before/after comparisons line up.  device_wait is
# the time the host BLOCKED on a
# device future; overlapped device time (future in flight while the
# host staged the next batch) is reported separately as overlap_ms /
# overlap_ratio, not as a stage — it costs no wake-path wall time.
PIPELINE_STAGES = ("drain", "tokenize", "dispatch", "device_wait",
                   "commit")

# the completion daemon's per-request decomposition (serial path):
# render = guarded prompt read + system-prompt fetch + template +
# WAITING->SERVICING claim; generate = the token loop incl. streaming
# appends; commit = oom bookkeeping + ctime backfill + READY flip
INFER_STAGES = ("render", "generate", "commit")

# the continuous (block-paged) lane's decomposition, published under
# the same infer.* histogram prefix: join = one row's prompt prefill
# into freshly allocated pages (admission IS a join — there is no
# fresh-batch/live-batch distinction); sample = the host draw of its
# first token; decode = the ASYNC dispatch of a flush_tokens-step
# paged decode chunk (the span every live row shares); collect = the
# host's blocked wait forcing a chunk out of the K-deep in-flight
# window (engine/resident.py — with the window saturated this is
# where the amortized dispatch floor surfaces); flush = a streaming
# append run.  A client-stamped request (stamp_trace) gets a
# flight-recorder entry with its accumulated spans, so `spt trace
# tail` reconstructs batched-lane requests too, not just the serial
# path's.  prefix_hit = the host-side radix walk + shared-page table
# mapping of a prefix-cache hit (engine/prefix_cache.py) — its span
# next to `join` is how `spt trace show` attributes first-token
# latency to cache hits vs suffix prefill.  Under disaggregated
# serving two more stages bracket the page-ownership transfer:
# handoff = the prefill lane's export + record write + DECODE_READY
# flip, adopt = the decode lane's claim + page import + row seating.
CONT_INFER_STAGES = ("join", "sample", "decode", "collect", "flush",
                     "prefix_hit", "handoff", "adopt",
                     # a model with per-row recurrent state: copying a
                     # snapshot into the joining row, and finding the
                     # slot its own snapshot goes to (leaf spans)
                     "state_restore", "state_snapshot",
                     # ... and zeroing the slot of a row that starts
                     # from nothing (leaf span)
                     "state_zero",
                     # a model with a window page group: giving back
                     # the pages a row has slid past, after a join's
                     # prefill pieces and after a chunk (leaf span)
                     "window_release")

# the continuous lane's RUN LOOP, fully accounted (spans only: not in
# CONT_INFER_STAGES, which sizes the per-request flight record).  Every
# pass of Completer.run_continuous (and of the disaggregated lanes'
# loops) is one `loop` span, and inside it every second belongs to one
# LEAF: idle = blocked in signal_wait with nothing live; beat = the
# 2 s beat at the head of a pass (spec demotion check, backpressure
# memo sweep, publish_stats, tier checkpoint); inside one `admit` span
# (a whole admission round, enclosing) gather = finding the waiting
# rows, QoS order, the backpressure memo and the reservation check;
# prepare = render + tokenize + the WAITING->SERVICING claim; then the
# stages prefix_hit, state_restore, state_zero, state_snapshot, join, sample (a
# prefill lane: handoff; a decode lane: adopt); emit = the per-token
# host work behind a join's sample or a collected chunk
# (token_to_piece, streaming appends, finalize, pages freed); inside
# one `chunk` span (a chunk round of a pass with rows live, enclosing:
# deadline kills and the edge scan are its own bookkeeping) the stages
# decode and collect, emit again, and rebid = the shard re-bid.  Those
# stages are leaves too: each opens the profiler annotation
# (utils/trace.py), so a capture names the device's idle gaps after
# them.  flush (inside emit) and window_release (inside join and
# decode) stay sums in their histograms.  A pass is idle, an admission
# round, a chunk round or both, under the beat: admit + chunk + beat
# is the loop's BUSY time, and loop - sum(leaves) its bookkeeping.
CONT_LOOP_PHASES = ("loop", "idle", "beat", "admit", "chunk", "gather",
                    "prepare", "emit", "rebid")

# the search daemon's per-drain decomposition: wake = signal to drain
# entry (the coalescing window's scheduling cost); drain = request
# discovery + param parse + torn-safe query-vector gather; score =
# lane refresh + async device dispatch of the fused top-k programs
# (host-side, the device computes in flight); select = the blocking
# device fetch of the O(k*Q) candidate rows; commit = per-request
# filtering + __sr_<idx> result writes + label clears + bumps
SEARCH_STAGES = ("wake", "drain", "score", "select", "commit")

# the search daemon's RUN LOOP, fully accounted (spans only: not in
# SEARCH_STAGES, which sizes every span record and the `quantiles`
# section).  Every pass of Searcher.run is one `loop` span, and inside
# it every second belongs to one of: idle = blocked in signal_wait;
# search.drain_cycle (whose parts are the SEARCH_STAGES plus, inside
# score, refresh = lane.refresh() and mask = bringing the candidate
# masks up to date); sweep_results / sweep_stages = the two
# heartbeat-cadence key walks; publish = the beat's lane audit +
# publish_stats.  All but `loop` are LEAF phases:
# they also ride the profiler's clock (utils/trace.py), as do the
# drain, select and commit stages.
SEARCH_LOOP_PHASES = ("loop", "idle", "refresh", "mask",
                      "sweep_results", "sweep_stages", "publish")

# the pipeline lane's per-script decomposition: parse = source fetch
# (inline or stored) + chunk compile + sandbox construction; exec =
# host-interpreter wall (every coroutine resume slice of the script's
# own Lua steps); verb = time the script spent suspended on async
# splinter verbs (submit_embed / submit_search / submit_completion /
# sleep — the downstream lanes' service time as the script saw it);
# commit = the __pr_<idx> result write + label clear + bump
SCRIPT_STAGES = ("parse", "exec", "verb", "commit")


def search_result_key(idx: int) -> str:
    return f"{SEARCH_RESULT_PREFIX}{idx}"


def script_result_key(idx: int) -> str:
    return f"{SCRIPT_RESULT_PREFIX}{idx}"


def stored_script_key(name: str) -> str:
    return f"{SCRIPT_STORE_PREFIX}{name}"


def live_epochs(eps):
    """THE liveness rule of a search candidate, on an array of slot
    epochs: written at least once and not mid-write (even, nonzero).
    candidate_mask applies it to a snapshot of the store, the search
    daemon to the epochs its lane staged (Searcher._sync_live)."""
    import numpy as np

    return (eps != 0) & ((eps & np.uint64(1)) == 0)


def candidate_mask(store, bloom: int = 0):
    """THE search candidate mask — one definition the CLI's client-side
    scoring and the search daemon share, so their candidate sets
    cannot diverge: a bloom prefilter enumerates labelled rows; the
    default is every live row (live_epochs of a snapshot)."""
    import numpy as np

    if bloom:
        mask = np.zeros(store.nslots, np.float32)
        mask[store.enumerate_indices(bloom)] = 1.0
        return mask
    return live_epochs(store.epochs()).astype(np.float32)

# latency-probe short-circuit: drains at or below this many candidate
# rows skip the windowed big-batch machinery and dispatch immediately
# on the pre-compiled small-bucket programs (Embedder.probe_batch_max
# overrides per instance)
PROBE_BATCH_MAX_DEFAULT = 8

# --- request trace ids ----------------------------------------------------
# A client that wants its request's wake->commit journey reconstructed
# stamps a trace CONTEXT next to the request label: after set +
# label_or (LBL_EMBED_REQ / LBL_INFER_REQ), ideally before the bump,
# it writes "<trace_id>:<wall_ts>:<slot_epoch>[:<parent>:<span>]"
# into the slot-indexed companion key trace_stamp_key(idx).  The
# epoch field makes stamps self-invalidating (a daemon discards a
# stamp whose epoch doesn't match the request it gathered) — clients
# implementing the convention by hand must include it or forfeit that
# protection.  The two trailing fields are the DISTRIBUTED-tracing
# extension (PR 13): `parent` is the span id this request hangs
# under in the trace tree (0 = root) and `span` is the id assigned to
# THIS request's span — pre-assigned by the stamper so chained hops
# (the pipeline lane's verbs, a client-side rag chain) share one
# trace id across lanes while every hop stays addressable.  Legacy
# 3-field stamps parse as parent=0, span=trace_id.  The servicing
# daemon consumes the stamp when it COMMITS the row (not at drain —
# the stamp must survive a mid-service crash so the restarted lane's
# span still carries the chain identity), appends the request's stage
# events to its flight recorder (SPTPU_TRACE=1) and commits a span
# record into the shared span ring (obs/spans.py, always on) — so
# any single chain is reconstructable cross-process via `spt trace
# show <id>`.  Ids are (pid << 24 | counter): unique across
# concurrent clients without coordination, and the originating pid is
# recoverable (id >> 24).
TRACE_STAMP_PREFIX = "__tr_"

# pending-span staging rows (obs/spans.py): one per in-service traced
# request, keyed by the REQUEST's slot index — the crash-surviving
# half of the span protocol (a restarted lane recovers the chain
# identity, the original queue-enter clock, and the attempt count
# from here).  Orphans (slot epoch moved, or TTL) are swept by
# shed_orphan_stamp's discard path and the lanes' heartbeat-cadence
# sweeps, mirroring the __sr_ reaper.
SPAN_STAGE_PREFIX = "__sp_"

# the shared bounded span ring: committed span records land in
# span_ring_key(head % ring size) slots, the head claimed atomically
# through the BIGUINT counter key — multi-writer safe across all
# four lanes, bounded by construction (old spans overwrite)
SPAN_RING_PREFIX = "__span_"
KEY_SPAN_HEAD = "__span_head"

# the compile-event ring (obs/devtime.py): the named-program
# registry's ledger of jit compile events — {program, lane,
# shapes_key, duration_ms, generation, cause} records land in
# compile_ring_key(head % ring size) slots under the span ring's
# slot-claim discipline (atomic BIGUINT head, bounded by
# construction).  `spt trace export` hangs these on their own
# Perfetto track; scripts/compile_gate_check.py asserts the ring
# holds zero runtime-cause events after warmup.
COMPILE_RING_PREFIX = "__compile_"
KEY_COMPILE_HEAD = "__compile_head"

# telemetry-history rings (engine/telemetry.py): one per scraped
# lane, fixed-size time series of the lane's heartbeat gauges —
# the signal plane the elastic-lane scaling controller reads
TELEMETRY_PREFIX = "__tele_"
KEY_TELEMETRY_STATS = "__telemetry_stats"

# --- elastic lanes: striped replica groups --------------------------------
# A lane may run R replicas behind the SAME label-routing protocol.
# Replicas never coordinate directly: each one drains only its own
# disjoint STRIPE of the request space (a request's stripe is its
# slot index modulo the stripe width — the slot index is what the
# label-word enumeration already hands every drain, the way bloom
# groups partition search candidates), so two replicas can never race
# a claim.  The stripe map is STORE state under stripe_map_key(lane):
# a re-stripe is one epoch-bumped table write that in-flight replicas
# pick up at their next drain — between the write and the pick-up a
# request is at worst serviced by the OLD owner (still exclusive), so
# no request is ever orphaned between stripe owners.  Stripes with
# owner -1 are CLOSED: no replica claims new work from them (the
# supervisor's scale-down drain protocol parks a retiring replica's
# stripes closed until the straggler reclaim re-assigns them).
STRIPE_MAP_PREFIX = "__stripe_"
DEFAULT_STRIPE_WIDTH = 16
# replica-suffixed heartbeat keys: replica 0 keeps the canonical
# KEY_*_STATS name (every existing liveness probe and dashboard reads
# it unchanged), replica N > 0 publishes under "<base>.rN" — `spt
# top` / `spt metrics` / telemetry discover the suffixed keys via
# replica_heartbeat_keys() instead of a hardcoded one-key read
REPLICA_SUFFIX = ".r"
# the scaling controller's wiring (engine/autoscaler.py): the
# supervisor writes the policy (per-lane min:max bounds + controller
# knobs) once at startup, the controller (or `spt scale set`) writes
# desired replica counts into PER-LANE target keys
# (__scale_tgt_<lane> — one writer owns one lane's key at a time, so
# the autoscaler acting on lane A can never clobber an operator's
# concurrent manual hold on lane B the way a shared read-modify-write
# JSON map could), and the supervisor applies them — spawn on
# scale-up, drain-protocol retire on scale-down.  All plain JSON
# store keys, so `spt scale status` is nothing but reads.
KEY_SCALE_POLICY = "__scale_policy"
SCALE_TARGET_PREFIX = "__scale_tgt_"
KEY_AUTOSCALER_STATS = "__autoscaler_stats"

# --- disaggregated prefill/decode handoff ---------------------------------
# The prefill lane commits a row's prompt K/V, samples its first
# token, then hands the row to a decode lane THROUGH THE STORE: a
# JSON handoff record under handoff_key(idx) (generation budget,
# prompt ids for the re-prefill fallback, the sampled carry token,
# byte offsets for crash truncation) plus optional raw wire pages
# under handoff_page_key(idx, j) — the per-layer-stacked K/V bytes of
# each committed page, so a decode lane with its OWN pool imports the
# prefill without recomputing it (handoff_scale_key carries the int8
# page scales when the pool is quantized).  The row's label flips
# SERVICING -> DECODE_READY at the same moment; adoption sets
# SERVICING on top (both bits = decode-phase in flight) and finish
# clears everything to READY.  Crash safety both directions falls out
# of the label machine: a died prefill lane leaves SERVICING-only
# rows its stripe-scoped reclaim resets to WAITING (stale __ho_ keys
# deleted with them), a died decode lane leaves SERVICING|DECODE_READY
# rows that fall back to DECODE_READY (slot value truncated to the
# record's prompt length; greedy decode replays byte-identically).
# Wire keys persist until decode finish and are bounded by the lane
# batch (one in-flight handoff set per prefill seat).
HANDOFF_PREFIX = "__ho_"


def handoff_key(idx: int) -> str:
    return f"{HANDOFF_PREFIX}{idx}"


def handoff_page_key(idx: int, j: int) -> str:
    """Wire page j of slot idx's handoff: raw bytes, all layers
    stacked (layers, kv_heads, page, head_dim) k then v."""
    return f"{HANDOFF_PREFIX}{idx}.p{j}"


def handoff_scale_key(idx: int, j: int) -> str:
    """Wire page j's int8 scales: (layers, kv_heads) f32 k then v."""
    return f"{HANDOFF_PREFIX}{idx}.s{j}"


def write_handoff_record(store, idx: int, rec: dict) -> bool:
    """Land the handoff record for slot idx (debug-labeled so the
    sweep machinery can find strays).  Returns False when the store
    rejects it — the prefill lane then falls back to finishing the
    row itself rather than stranding it half-handed-off."""
    try:
        store.set(handoff_key(idx), json.dumps({"v": 1, **rec}))
        store.label_or(handoff_key(idx), LBL_DEBUG)
        return True
    except (KeyError, OSError):
        return False


def read_handoff_record(store, idx: int) -> dict | None:
    """Slot idx's handoff record, or None (absent / unparseable /
    wrong version)."""
    try:
        rec = json.loads(store.get(handoff_key(idx)).rstrip(b"\0"))
    except (KeyError, OSError, ValueError):
        return None
    if not isinstance(rec, dict) or rec.get("v") != 1:
        return None
    return rec


def clear_handoff(store, idx: int, pages: int = 0) -> None:
    """Retire slot idx's handoff record and its wire pages (decode
    finish, or prefill-crash reclaim).  `pages` bounds the wire-key
    sweep; with 0 the record's own page count is consulted first.
    Never raises."""
    if not pages:
        rec = read_handoff_record(store, idx)
        if rec is not None:
            try:
                pages = int(rec.get("wire_pages", 0))
            except (TypeError, ValueError):
                pages = 0
    try:
        store.unset(handoff_key(idx))
    except (KeyError, OSError):
        pass
    for j in range(max(0, int(pages))):
        for k in (handoff_page_key(idx, j), handoff_scale_key(idx, j)):
            try:
                store.unset(k)
            except (KeyError, OSError):
                pass


def trace_stamp_key(idx: int) -> str:
    return f"{TRACE_STAMP_PREFIX}{idx}"


def span_stage_key(idx: int) -> str:
    return f"{SPAN_STAGE_PREFIX}{idx}"


def span_ring_key(i: int) -> str:
    return f"{SPAN_RING_PREFIX}{i}"


def compile_ring_key(i: int) -> str:
    return f"{COMPILE_RING_PREFIX}{i}"


def telemetry_key(lane: str) -> str:
    return f"{TELEMETRY_PREFIX}{lane}"


def stripe_map_key(lane: str) -> str:
    return f"{STRIPE_MAP_PREFIX}{lane}"


def stripe_of(idx: int, width: int = DEFAULT_STRIPE_WIDTH) -> int:
    """The stripe a request belongs to: its slot index modulo the
    stripe width.  Deterministic, uniform, and derived from the one
    thing every drain already holds for every candidate row."""
    return int(idx) % max(1, int(width))


def replica_stats_key(base: str, replica: int = 0) -> str:
    """Replica r's heartbeat/trace key: the canonical `base` for
    replica 0, `base.rN` for N > 0."""
    r = int(replica)
    return base if r <= 0 else f"{base}{REPLICA_SUFFIX}{r}"


def parse_replica_key(key: str, base: str) -> int | None:
    """Inverse of replica_stats_key: the replica index, or None when
    `key` is not a replica key of `base`."""
    if key == base:
        return 0
    pfx = base + REPLICA_SUFFIX
    if not key.startswith(pfx):
        return None
    try:
        r = int(key[len(pfx):])
    except ValueError:
        return None
    return r if r > 0 else None


def replica_heartbeat_map(store, bases: Sequence[str]
                          ) -> dict[str, list[tuple[int, str]]]:
    """Discover every lane's heartbeat keys in ONE debug-label
    enumeration: {base: [(replica, key), ...]} sorted by replica,
    each list always starting with (0, base).  Suffixed keys are
    found through the bloom prefilter (every heartbeat is
    LBL_DEBUG-labeled), never a per-base key walk — a multi-lane
    render (`spt top` frame, `spt metrics`, a telemetry tick) pays
    one scan, and a scaled lane's extra replicas appear in every
    reader automatically."""
    found: dict[str, dict[int, str]] = {b: {0: b} for b in bases}
    try:
        keys = store.enumerate_keys(LBL_DEBUG)
    except (KeyError, OSError):
        keys = []
    for k in keys:
        for b in bases:
            r = parse_replica_key(k, b)
            if r:
                found[b][r] = k
                break
    return {b: sorted(m.items()) for b, m in found.items()}


def replica_heartbeat_keys(store, base: str) -> list[tuple[int, str]]:
    """One lane's heartbeat keys: [(replica, key), ...] — the
    single-base view of replica_heartbeat_map."""
    return replica_heartbeat_map(store, (base,))[base]


def default_stripe_owners(replicas: Sequence[int] | int,
                          width: int = DEFAULT_STRIPE_WIDTH
                          ) -> dict[int, list[int]]:
    """Round-robin the stripes over the given replica ids (or over
    0..R-1 for an int): every stripe owned, ownership disjoint."""
    ids = (list(range(replicas)) if isinstance(replicas, int)
           else sorted(set(int(r) for r in replicas)))
    if not ids:
        return {}
    out: dict[int, list[int]] = {r: [] for r in ids}
    for s in range(max(1, int(width))):
        out[ids[s % len(ids)]].append(s)
    return out


def read_stripe_map(store, lane: str) -> dict | None:
    """The lane's live stripe map, or None (no map = the single-
    replica deployment: replica 0 owns everything).  Shape:
    {"v": 1, "epoch": E, "width": W,
     "owners": {"<replica>": [stripe, ...]}, "closed": [stripe, ...],
     "pending": {"<replica>": [stripe, ...]}}
    `pending` lists the planned shares of replicas mid scale-up
    handoff: those replicas own NOTHING yet (the incumbents keep
    serving the planned stripes until the promotion write), but they
    are NOT retired — the retire signal is being in neither `owners`
    nor `pending`."""
    try:
        rec = json.loads(store.get(stripe_map_key(lane)).rstrip(b"\0"))
    except (KeyError, OSError, ValueError):
        return None
    if not isinstance(rec, dict) or rec.get("v") != 1:
        return None
    return rec


def write_stripe_map(store, lane: str,
                     owners: dict[int, list[int]], *,
                     width: int = DEFAULT_STRIPE_WIDTH,
                     closed: Sequence[int] = (),
                     pending: dict[int, Sequence[int]]
                     | None = None) -> int:
    """Commit a re-stripe: ONE epoch-bumped table write in-flight
    replicas pick up at their next drain.  Returns the new epoch.
    Never raises — a failed write leaves the previous map standing
    (still a consistent, fully-owned assignment)."""
    prev = read_stripe_map(store, lane)
    epoch = int(prev.get("epoch", 0)) + 1 if prev else 1
    rec = {"v": 1, "epoch": epoch, "width": max(1, int(width)),
           "owners": {str(int(r)): sorted(int(s) for s in ss)
                      for r, ss in owners.items()},
           "closed": sorted(int(s) for s in closed),
           "ts": time.time()}
    if pending:
        rec["pending"] = {str(int(r)): sorted(int(s) for s in ss)
                          for r, ss in pending.items() if ss}
    try:
        store.set(stripe_map_key(lane), json.dumps(rec))
    except (KeyError, OSError):
        return int(prev.get("epoch", 0)) if prev else 0
    return epoch


def clear_stripe_map(store, lane: str) -> None:
    """Drop the lane back to the single-replica default (replica 0
    owns everything).  Never raises."""
    try:
        store.unset(stripe_map_key(lane))
    except (KeyError, OSError):
        pass


class StripeView:
    """A replica's cached view of its lane's stripe map — the one
    stripe-filter every drain shares.  refresh() re-reads the map (a
    drain-entry call: the map is one tiny JSON key, and picking up a
    re-stripe at the NEXT drain is exactly the handoff contract);
    owns(idx) is the candidate filter; `retired` goes True when a
    live map assigns this replica nothing (the supervisor's scale-
    down signal — the replica finishes in-flight work and exits).

    With NO map in the store, replica 0 owns every stripe (the
    pre-elastic single-process deployment, byte-identical behavior)
    and a replica > 0 owns NOTHING — a mis-started extra replica
    without a map must never double-serve."""

    def __init__(self, store, lane: str, replica: int = 0):
        self.store = store
        self.lane = lane
        self.replica = int(replica)
        self.epoch = 0
        self.width = DEFAULT_STRIPE_WIDTH
        self._stripes: frozenset[int] | None = (
            None if self.replica == 0 else frozenset())
        self._have_map = False
        self._pending = False         # scale-up handoff in progress

    def refresh(self) -> None:
        rec = read_stripe_map(self.store, self.lane)
        if rec is None:
            self._have_map = False
            self.epoch = 0
            self.width = DEFAULT_STRIPE_WIDTH
            self._stripes = (None if self.replica == 0
                             else frozenset())
            self._pending = False
            return
        self._have_map = True
        self.epoch = int(rec.get("epoch", 0))
        self.width = max(1, int(rec.get("width",
                                        DEFAULT_STRIPE_WIDTH)))
        owners = rec.get("owners")
        mine = () if not isinstance(owners, dict) else \
            owners.get(str(self.replica), ())
        self._stripes = frozenset(int(s) for s in mine)
        pend = rec.get("pending")
        self._pending = bool(
            isinstance(pend, dict)
            and pend.get(str(self.replica)))

    def owns(self, idx: int) -> bool:
        if self._stripes is None:
            return True
        return stripe_of(idx, self.width) in self._stripes

    @property
    def retired(self) -> bool:
        """True when a live stripe map lists this replica NEITHER as
        an owner NOR as pending — the drain signal: stop claiming,
        finish in-flight, exit.  A PENDING replica (scale-up handoff:
        its share parks closed until the supervisor sees its first
        heartbeat) owns nothing yet but is absolutely not retired.
        Replica 0 never retires (it is the canonical replica the
        liveness probes read)."""
        return (self.replica > 0 and self._have_map
                and not self._stripes and not self._pending)

    def poll_retired(self) -> bool:
        """Force-refresh, then answer `retired` — the run loops'
        heartbeat-cadence check."""
        self.refresh()
        return self.retired

    def snapshot(self) -> dict:
        """The heartbeat's `stripe` section."""
        return {"replica": self.replica, "epoch": self.epoch,
                "width": self.width,
                "stripes": (-1 if self._stripes is None
                            else len(self._stripes))}


def scale_target_key(lane: str) -> str:
    return f"{SCALE_TARGET_PREFIX}{lane}"


def read_scale_target(store, lane: str) -> dict | None:
    """One lane's desired replica count: {"r": N, "src":
    "auto"|"manual", "ts": ...}, or None."""
    try:
        rec = json.loads(
            store.get(scale_target_key(lane)).rstrip(b"\0"))
    except (KeyError, OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) and "r" in rec else None


def read_scale_targets(store) -> dict[str, dict]:
    """Every lane's desired replica count: {lane: {"r": N, "src":
    "auto"|"manual", "ts": ...}}.  Written by the autoscaler and
    `spt scale set` (one PER-LANE key each — no shared-map
    read-modify-write to race), applied by the supervisor."""
    out: dict[str, dict] = {}
    try:
        keys = store.keys_with_prefix(SCALE_TARGET_PREFIX)
    except (KeyError, OSError):
        return out
    for k in keys:
        lane = k[len(SCALE_TARGET_PREFIX):]
        rec = read_scale_target(store, lane)
        if rec is not None:
            out[lane] = rec
    return out


def write_scale_target(store, lane: str, r: int | None, *,
                       src: str = "manual") -> None:
    """Set (or with r=None clear) one lane's desired replica count —
    one whole-key write to the lane's OWN target key, so concurrent
    writers of different lanes can never lose each other's entries.
    A "manual" entry is a HOLD: the autoscaler leaves that lane alone
    until `spt scale set <lane>=auto` clears it.  Never raises."""
    try:
        if r is None:
            store.unset(scale_target_key(lane))
        else:
            store.set(scale_target_key(lane), json.dumps(
                {"v": 1, "r": max(1, int(r)), "src": src,
                 "ts": round(time.time(), 3)}))
    except (KeyError, OSError):
        pass


def read_scale_policy(store) -> dict | None:
    """The supervisor-published scaling policy: {"lanes": {lane:
    {"min": m, "max": M}}, "interval_s": ..., "up_threshold": ...,
    "down_threshold": ..., "cooldown_s": ...}."""
    try:
        rec = json.loads(store.get(KEY_SCALE_POLICY).rstrip(b"\0"))
    except (KeyError, OSError, ValueError):
        return None
    return rec if isinstance(rec, dict) else None


_trace_counter = itertools.count(1)


def next_trace_id() -> int:
    return (os.getpid() << 24) | (next(_trace_counter) & 0xFFFFFF)


def stamp_trace(store, key: str, *, trace_id: int | None = None,
                parent: int = 0,
                span: int | None = None) -> int | None:
    """Client-side: mark the pending request on `key` for flight
    recording + span capture (best after set+label, before the bump —
    a daemon racing the stamp then can't service the row stampless).
    Bare `stamp_trace(store, key)` starts a NEW trace (span id ==
    trace id, the root); passing `trace_id` (+ `parent`) joins an
    existing one — the chained-hop form every client verb and the
    pipeline lane's verbs use.  Returns the SPAN id assigned to this
    request (== the trace id for a root stamp), or None when the
    stamp could not land (tracing must never fail a request).

    LBL_TRACED on the request key is the cheap discovery signal: the
    daemon's candidate filter already reads every row's label word, so
    untraced rows cost one bit-test — never a stamp-key lookup.  The
    stamp embeds the row's CURRENT epoch: a daemon finding a stamp
    whose epoch doesn't match the request it gathered discards it as
    stale (a leftover from a request serviced before the stamp
    landed, or from a pre-tracing daemon run) instead of attributing
    it — and its seconds-old wall clock — to the wrong request."""
    try:
        idx = store.find_index(key)
        if trace_id is None:
            tid = next_trace_id()
            span = tid if span is None else span
        else:
            tid = int(trace_id)
            span = next_trace_id() if span is None else span
        sk = trace_stamp_key(idx)
        store.set(sk, f"{tid}:{time.time():.6f}:{store.epoch_at(idx)}"
                      f":{int(parent)}:{int(span)}")
        store.label_or(sk, LBL_DEBUG)
        store.label_or(key, LBL_TRACED)
        return span
    except (KeyError, OSError):
        return None


def stamp_trace_ctx(store, key: str, trace) -> int | None:
    """Normalize the client verbs' `trace=` argument into a stamp:
    `True` starts a fresh root trace; an int trace id stamps a hop of
    that trace parented on its root; a `(trace_id, parent_span)`
    tuple places the hop explicitly (the pipeline lane's verbs and
    chained client calls use this).  Returns the hop's span id (or
    None — tracing never fails a request)."""
    if not trace:
        return None
    if trace is True:
        return stamp_trace(store, key)
    if isinstance(trace, tuple):
        return stamp_trace(store, key, trace_id=trace[0],
                           parent=trace[1])
    return stamp_trace(store, key, trace_id=int(trace),
                       parent=int(trace))


def read_trace_ctx(store, idx: int, epoch: int | None = None
                   ) -> tuple[int, float, int, int] | None:
    """Daemon-side: (trace_id, client_wall_ts, parent_span, span_id)
    for slot idx, or None.  With `epoch` given (the gathered
    request's epoch), a stamp from a DIFFERENT epoch is stale: it is
    consumed (cleared, label too) and None is returned, so it can
    never corrupt a later request's record.  Legacy 3-field stamps
    read as parent=0, span=trace_id."""
    try:
        raw = store.get(trace_stamp_key(idx)).rstrip(b"\0").decode()
        parts = raw.split(":")
        tid = int(parts[0])
        ts = float(parts[1]) if len(parts) > 1 and parts[1] else 0.0
        e_stamp = int(parts[2]) if len(parts) > 2 and parts[2] else None
        parent = int(parts[3]) if len(parts) > 3 and parts[3] else 0
        span = int(parts[4]) if len(parts) > 4 and parts[4] else tid
    except (KeyError, OSError, ValueError, IndexError):
        return None
    if epoch is not None and e_stamp is not None and e_stamp != epoch:
        clear_trace_stamp(store, idx)         # stale: consume, never
        try:                                  # attribute to this row —
            key = store.key_at(idx)           # and retire the phantom
            if key is not None:               # LBL_TRACED with it
                store.label_clear(key, LBL_TRACED)
        except (KeyError, OSError):
            pass
        return None
    return tid, ts, parent, span


def read_trace_stamp(store, idx: int,
                     epoch: int | None = None) -> tuple[int, float] | None:
    """Legacy 2-field view of read_trace_ctx: (trace_id, wall_ts)."""
    ctx = read_trace_ctx(store, idx, epoch=epoch)
    return None if ctx is None else (ctx[0], ctx[1])


def clear_trace_stamp(store, idx: int) -> None:
    try:
        store.unset(trace_stamp_key(idx))
    except (KeyError, OSError):
        pass


def consume_trace_stamp(store, idx: int,
                        epoch: int | None = None
                        ) -> tuple[int, float] | None:
    """Read AND retire slot idx's trace stamp (companion key +
    LBL_TRACED on the slot's key) — the one consume sequence both
    daemons share, run while the slot still belongs to the gathered
    request (by drain end it may hold a NEW request's fresh stamp).
    Returns (trace_id, client_wall_ts) when the stamp matches `epoch`
    (or no epoch given), else None.  Never raises: tracing must never
    fail a request — a contended slot (Eagain) keeps its stamp one
    more drain."""
    stamp = read_trace_stamp(store, idx, epoch=epoch)
    try:
        clear_trace_stamp(store, idx)
        key = store.key_at(idx)
        if key is not None:
            store.label_clear(key, LBL_TRACED)
    except (KeyError, OSError):
        pass
    return stamp


# --- request deadlines ----------------------------------------------------
# A client with a latency budget stamps an ABSOLUTE wall-clock deadline
# next to its request (after set + label, before the bump — the trace
# stamp discipline): "<deadline_ts>:<slot_epoch>" in the slot-indexed
# companion key deadline_key(idx), flagged by LBL_DEADLINE on the
# request key so unstamped rows cost one bit-test, never a lookup.
# The servicing daemon fails an already-expired request fast (an error
# record / diagnostic instead of a batch slot) and consumes the stamp;
# the epoch field makes stamps self-invalidating exactly like trace
# stamps.  Search requests may alternatively carry {"deadline": ts}
# in their request JSON — the searcher honors either.
DEADLINE_STAMP_PREFIX = "__dl_"


def deadline_key(idx: int) -> str:
    return f"{DEADLINE_STAMP_PREFIX}{idx}"


def stamp_deadline(store, key: str, deadline_ts: float) -> bool:
    """Client-side: attach an absolute wall-clock deadline (seconds
    since the epoch) to the pending request on `key`.  Returns True if
    the stamp landed; never raises (a deadline must never fail the
    request it guards)."""
    try:
        idx = store.find_index(key)
        dk = deadline_key(idx)
        store.set(dk, f"{float(deadline_ts):.6f}:{store.epoch_at(idx)}")
        store.label_or(dk, LBL_DEBUG)
        store.label_or(key, LBL_DEADLINE)
        return True
    except (KeyError, OSError, ValueError):
        return False


def read_deadline(store, idx: int,
                  epoch: int | None = None) -> float | None:
    """Daemon-side: the absolute deadline for slot idx, or None.  With
    `epoch` given (the gathered request's epoch), a stamp from a
    different epoch is stale: consumed, and None returned."""
    try:
        raw = store.get(deadline_key(idx)).rstrip(b"\0").decode()
        parts = raw.split(":")
        ts = float(parts[0])
        e_stamp = int(parts[1]) if len(parts) > 1 and parts[1] else None
    except (KeyError, OSError, ValueError, IndexError):
        return None
    if epoch is not None and e_stamp is not None and e_stamp != epoch:
        clear_deadline(store, idx)            # stale: consume, never
        return None                           # bound the wrong request
    return ts


def clear_deadline(store, idx: int) -> None:
    """Retire slot idx's deadline stamp (companion key + LBL_DEADLINE
    on the slot's key).  Never raises."""
    try:
        store.unset(deadline_key(idx))
    except (KeyError, OSError):
        pass
    try:
        key = store.key_at(idx)
        if key is not None:
            store.label_clear(key, LBL_DEADLINE)
    except (KeyError, OSError):
        pass


def consume_deadline(store, idx: int,
                     epoch: int | None = None) -> float | None:
    """Read AND retire slot idx's deadline stamp — run while the slot
    still belongs to the gathered request."""
    ts = read_deadline(store, idx, epoch=epoch)
    clear_deadline(store, idx)
    return ts


# --- an answer's budget by the request -------------------------------------
# A client that wants fewer new tokens than the daemon's
# --max-new-tokens stamps its own budget next to its request (the
# deadline stamp's discipline: after set, before the bump):
# "<n>:<slot_epoch>" in the slot-indexed companion key max_new_key(idx),
# flagged by LBL_MAX_NEW on the request key so unstamped rows cost one
# bit-test, never a lookup.  The completion daemon's continuous lane
# seats the row with min(stamp, its own budget) and reserves pages for
# that; the stamp is consumed at the claim.  No stamp: the daemon's
# budget, as ever.
MAX_NEW_STAMP_PREFIX = "__mn_"


def max_new_key(idx: int) -> str:
    return f"{MAX_NEW_STAMP_PREFIX}{idx}"


def stamp_max_new(store, key: str, n: int) -> bool:
    """Client-side: attach an answer budget of `n` new tokens (>= 1)
    to the pending request on `key`.  Returns True if the stamp
    landed; never raises (a budget must never fail the request it
    shortens)."""
    try:
        idx = store.find_index(key)
        mk = max_new_key(idx)
        store.set(mk, f"{max(1, int(n))}:{store.epoch_at(idx)}")
        store.label_or(mk, LBL_DEBUG)
        store.label_or(key, LBL_MAX_NEW)
        return True
    except (KeyError, OSError, ValueError):
        return False


def read_max_new(store, idx: int, epoch: int | None = None) -> int | None:
    """Daemon-side: the answer budget stamped for slot idx, or None.
    With `epoch` given (the gathered request's epoch), a stamp from a
    different epoch is stale: consumed, and None returned."""
    try:
        raw = store.get(max_new_key(idx)).rstrip(b"\0").decode()
        parts = raw.split(":")
        n = int(parts[0])
        e_stamp = int(parts[1]) if len(parts) > 1 and parts[1] else None
    except (KeyError, OSError, ValueError, IndexError):
        return None
    if epoch is not None and e_stamp is not None and e_stamp != epoch:
        clear_max_new(store, idx)
        return None
    return max(1, n)


def clear_max_new(store, idx: int) -> None:
    """Retire slot idx's budget stamp (companion key + LBL_MAX_NEW on
    the slot's key).  Never raises."""
    try:
        store.unset(max_new_key(idx))
    except (KeyError, OSError):
        pass
    try:
        key = store.key_at(idx)
        if key is not None:
            store.label_clear(key, LBL_MAX_NEW)
    except (KeyError, OSError):
        pass


# --- typed overload / expiry records --------------------------------------
# The shed contract: a saturated lane past its high-water mark fails
# overflow with THIS record instead of queueing unboundedly or
# silently dropping — clients (engine/client.py retry wrapper) honor
# the retry_after_ms hint.  Search results carry it as the __sr_ JSON
# row; the completer writes it as the slot's value (READY-flipped);
# the embedder has no value channel to spare (the slot holds the
# client's text), so its shed unblocks the client label-only and the
# counters tell the story.
ERR_OVERLOADED = "overloaded"
ERR_DEADLINE = "deadline_expired"


def overloaded_record(retry_after_ms: int) -> dict:
    return {"err": ERR_OVERLOADED,
            "retry_after_ms": int(retry_after_ms)}


def overloaded_payload(retry_after_ms: int) -> bytes:
    """The completer-lane shed value: a typed JSON body a client (or
    the shared retry wrapper) can parse for the retry hint."""
    return json.dumps(overloaded_record(retry_after_ms)).encode()


def parse_error_payload(raw: bytes | str) -> dict | None:
    """{"err": ..., ...} if `raw` is one of the typed error payloads
    above, else None (a normal completion body)."""
    if isinstance(raw, bytes):
        raw = raw.rstrip(b"\0")
        if not raw.startswith(b"{"):
            return None
        try:
            raw = raw.decode()
        except UnicodeDecodeError:
            return None
    elif not raw.startswith("{"):
        return None
    try:
        rec = json.loads(raw)
    except ValueError:
        return None
    if isinstance(rec, dict) and isinstance(rec.get("err"), str):
        return rec
    return None


DEADLINE_EXPIRED_DIAGNOSTIC = json.dumps(
    {"err": ERR_DEADLINE}).encode()


# optional heartbeat sections in the order they go when the record
# passes max_val: the bulkiest and least-read first.  What is not
# named here goes next, largest first; `startup_ms`, `devtime` and
# `spans` — the sections benchmarks and `spt metrics` read from — go
# last.
HEARTBEAT_DROP_FIRST = ("quantiles", "slow_log", "recorder")
HEARTBEAT_DROP_LAST = ("startup_ms", "devtime", "spans")


def publish_heartbeat(store, key: str, payload: dict) -> None:
    """Write a timestamped JSON stats snapshot into a debug-labeled
    key.  Telemetry must never wedge serving: a concurrently deleted
    key (KeyError) or a failed store op (OSError) is swallowed — but a
    snapshot too big for the store's max_val degrades SECTION BY
    SECTION in a FIXED order (HEARTBEAT_DROP_FIRST, then the other
    optional dict/list sections, HEARTBEAT_DROP_LAST at the very end;
    marked truncated) so whatever telemetry fits still lands, and a
    section something reads never goes because it grew past a bulkier
    one nothing reads.  The mark never takes the place of a counter: a
    completer's payload has a `truncated` of its own (completions cut
    at the slot's size, which the benchmark reads as a fault counter),
    and keeps it — there the missing `quantiles` is the sign.

    Every heartbeat carries the publisher's pid: liveness probes
    (heartbeat_live) kill-0 it, so a crashed daemon reads as dead the
    moment it dies instead of after max_age_s of heartbeat decay."""
    rec = {"ts": round(time.time(), 3), "pid": os.getpid(), **payload}
    for _ in range(2 + len(payload)):
        try:
            # compact separators: a sixth fewer bytes under max_val
            store.set(key, json.dumps(rec, separators=(",", ":")))
            store.label_or(key, LBL_DEBUG)
            return
        except KeyError:
            return
        except OSError:
            sections = [k for k, v in rec.items()
                        if isinstance(v, (dict, list))]
            if not sections:
                return
            rec.pop(min(sections, key=lambda k: _drop_rank(k, rec[k])))
            if "truncated" not in payload:
                rec["truncated"] = True


def _drop_rank(name: str, section) -> tuple:
    """Sort key of a heartbeat section: the smallest goes first."""
    if name in HEARTBEAT_DROP_FIRST:
        return (0, HEARTBEAT_DROP_FIRST.index(name))
    if name in HEARTBEAT_DROP_LAST:
        return (2, HEARTBEAT_DROP_LAST.index(name))
    return (1, -len(json.dumps(section)))


def bump_generation(store, heartbeat_key: str) -> int:
    """Monotonic per-lane start counter, bumped at daemon attach() and
    carried in every heartbeat: two snapshots with different
    generations bracket a restart even when the pid was recycled.
    Stored as a BIGUINT companion key (<heartbeat_key>_gen) so it
    survives the daemon that bumped it.  Never raises — a full store
    must not stop a daemon from starting (generation 0 = unknown)."""
    gk = heartbeat_key + "_gen"
    try:
        if gk not in store:
            store.set_uint(gk, 0)
        return int(store.integer_op(gk, N.IOP_INC))
    except (KeyError, OSError, ValueError):
        return 0


def pid_alive(pid: int) -> bool:
    """Same-host liveness probe: kill-0.  EPERM means alive under
    another uid; any lookup failure means gone."""
    if not pid or pid < 0:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except (PermissionError, OSError):
        return True
    return True


def heartbeat_live(store, key: str, *, max_age_s: float = 15.0,
                   lane: str | None = None) -> bool:
    """THE daemon-liveness probe: a heartbeat counts as live when its
    ts is fresh AND its publisher pid still exists AND (with `lane`
    given) the supervisor has not marked the lane down.

    The pid probe is the staleness fix: a daemon that crashed one
    second after publishing used to read as live for max_age_s more
    seconds, costing every client its full submit timeout before the
    local fallback; kill-0 makes the fallback instant.  Heartbeats
    published before the pid field existed (no "pid" key) fall back to
    age-only — never treat an old-format heartbeat as dead."""
    if lane is not None and lane_down(store, lane):
        return False
    try:
        snap = json.loads(store.get(key).rstrip(b"\0"))
        ts = float(snap.get("ts", 0.0))
    except (KeyError, OSError, ValueError, AttributeError, TypeError):
        return False
    pid = snap.get("pid")
    if isinstance(pid, int) and not pid_alive(pid):
        return False
    return (time.time() - ts) < max_age_s


def lane_down(store, lane: str, *, max_age_s: float = 15.0) -> bool:
    """True when a FRESH supervisor heartbeat marks `lane` down (its
    circuit breaker is open).  Clients skip dispatch to a down lane
    instead of burning their submit timeout against a crash loop.  A
    stale or missing supervisor snapshot never vetoes a lane — an
    unsupervised deployment must behave exactly as before."""
    try:
        snap = json.loads(
            store.get(KEY_SUPERVISOR_STATS).rstrip(b"\0"))
    except (KeyError, OSError, ValueError, AttributeError):
        return False
    try:
        if (time.time() - float(snap.get("ts", 0.0))) >= max_age_s:
            return False
        info = snap.get("lanes", {}).get(lane)
        return bool(info) and info.get("state") == "down"
    except (TypeError, AttributeError):
        return False


# labels that mean "a daemon will still service (and consume the
# stamp of) this row" — a TRACED row carrying none of them is an
# orphan whose stamp landed after its request was serviced.
# DECODE_READY counts: a handed-off row is still pending decode-lane
# service, so its stamps must survive the prefill->decode gap.
_REQ_LABELS = (LBL_EMBED_REQ | LBL_INFER_REQ | LBL_SERVICING
               | LBL_SEARCH_REQ | LBL_SCRIPT_REQ | LBL_DECODE_READY)


def clear_span_stage(store, idx: int) -> None:
    """Retire slot idx's pending-span staging row.  Never raises."""
    try:
        store.unset(span_stage_key(idx))
    except (KeyError, OSError):
        pass


def _span_stage_orphaned(store, tgt: int) -> bool:
    """True when the staging row for slot `tgt` no longer belongs to
    a pending request: the slot is gone, its epoch moved past the one
    the span was staged under (a raced rewrite — the NEW occupant
    will stage its own), or no daemon will ever commit it (no request
    labels left).  Staging wire form (obs/spans.py):
    "tid:span:parent:epoch:attempts:t_queue:gap_ms:ts"."""
    try:
        raw = store.get(span_stage_key(tgt)).rstrip(b"\0").decode()
        e = int(raw.split(":")[3])
    except (KeyError, OSError, ValueError, IndexError,
            UnicodeDecodeError):
        return True                   # unreadable staging: retire
    if tgt >= store.nslots or store.key_at(tgt) is None:
        return True
    if store.epoch_at(tgt) != e:
        return True
    return not store.labels_at(tgt) & _REQ_LABELS


def shed_orphan_stamp(store, idx: int, labels: int) -> bool:
    """Retire a trace stamp whose request is no longer pending, so a
    stamp that landed AFTER its request was serviced — with no
    follow-up request ever arriving — cannot leak its __tr_<idx> slot
    and LBL_TRACED forever.  Daemons call this from their discard
    path for rows that carry TRACED or DEBUG labels; handles the
    stamped row itself, a freshly-written stamp slot (__tr_<n>)
    surfacing through the dirty mask, and an orphaned pending-span
    staging row (__sp_<n> whose request slot epoch moved or whose
    labels cleared without a span commit — the raced-rewrite leak).
    Returns True if something was shed."""
    shed = False
    if labels & LBL_TRACED and not labels & _REQ_LABELS:
        consume_trace_stamp(store, idx)
        clear_span_stage(store, idx)
        shed = True
    if labels & LBL_DEADLINE and not labels & _REQ_LABELS:
        clear_deadline(store, idx)
        shed = True
    if labels & LBL_MAX_NEW and not labels & _REQ_LABELS:
        clear_max_new(store, idx)
        shed = True
    if shed:
        return True
    if labels & LBL_DEBUG:
        try:
            key = store.key_at(idx)
        except (KeyError, OSError):
            return False
        for pfx, flag, retire in (
                (TRACE_STAMP_PREFIX, LBL_TRACED, consume_trace_stamp),
                (DEADLINE_STAMP_PREFIX, LBL_DEADLINE, clear_deadline),
                (MAX_NEW_STAMP_PREFIX, LBL_MAX_NEW, clear_max_new)):
            if key and key.startswith(pfx):
                try:
                    tgt = int(key[len(pfx):])
                    tl = store.labels_at(tgt)
                except (ValueError, KeyError, OSError):
                    return False
                if tl & flag and not tl & _REQ_LABELS:
                    retire(store, tgt)
                    return True
        if key and key.startswith(SPAN_STAGE_PREFIX):
            try:
                tgt = int(key[len(SPAN_STAGE_PREFIX):])
            except ValueError:
                return False
            if _span_stage_orphaned(store, tgt):
                clear_span_stage(store, tgt)
                return True
    return False


def attach_trace_sections(payload: dict, tracer, recorder,
                          prefix: str) -> None:
    """Assemble the tracing heartbeat sections in place — ONE
    definition both daemons share, so the section contract (legacy-
    shaped spans, stage quantiles under `prefix`, recorder
    accounting, slow log) cannot diverge between them."""
    # one snapshot feeds both sections: spans keeps the LEGACY
    # aggregate shape only, quantiles carries the full histogram
    # summaries under the pinned stage names — both full would double
    # the payload for zero extra information (publish_heartbeat
    # degrades by size when max_val bites)
    # `spans` is what deltas are read from and goes last under
    # max_val, so it is kept small: no <prefix>e2e (the sum of its
    # stages; `quantiles` has it for `spt top`), one decimal on totals
    snap = tracer.snapshot()
    payload["spans"] = {
        k: {"n": v["n"], "total_ms": round(v["total_ms"], 1),
            "max_ms": round(v["max_ms"], 1 if v["max_ms"] >= 100 else 3)}
        for k, v in snap.items() if k != prefix + "e2e"}
    payload["quantiles"] = {k[len(prefix):]: v
                            for k, v in snap.items()
                            if k.startswith(prefix)}
    payload["recorder"] = recorder.counters()
    slow = recorder.slow_log()
    if slow:
        payload["slow_log"] = slow


def maybe_publish_trace_ring(store, key: str, recorder,
                             last_published: int) -> int:
    """Publish the flight-recorder ring iff new records arrived since
    `last_published` (an identical ring per heartbeat would be pure
    serialization waste).  Returns the new published count."""
    if recorder.recorded != last_published:
        publish_trace_ring(store, key, recorder)
    return recorder.recorded


def publish_trace_ring(store, key: str, recorder, n: int = 32) -> None:
    """Publish a flight recorder's tail into a debug-labeled key.
    Unlike publish_heartbeat's section-by-section degradation — which
    would drop this payload's ONLY section and leave `spt trace tail`
    empty exactly when there is data — an oversized ring halves its
    tail count until it fits: fewer reconstructable requests beat
    none."""
    while n >= 1:
        rec = {"ts": time.time(), "trace": recorder.tail(n)}
        try:
            store.set(key, json.dumps(rec))
            store.label_or(key, LBL_DEBUG)
            return
        except KeyError:
            return
        except OSError:
            n //= 2


CTX_EXCEEDED_DIAGNOSTIC = b"[context exceeded: input too long for model]"
