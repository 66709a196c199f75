"""Streaming completion daemon — the splainference analog.

The TPU-native replacement for the reference's completion sidecar
(splainference.cpp; SURVEY.md §2.2, §3.3).  Clients write a prompt to a
key, set the inference-waiting label (0x1<<60) and bump; this daemon:

  - claims shard 0x5F1A at priority 200 and re-bids every 32 generated
    tokens (splainference.cpp:51-62,355-364);
  - wakes on its signal group, enumerates waiting keys
    (splainference.cpp:582-589);
  - per key: epoch-stable prompt read → fetches the system-prompt key
    FRESH each request (splainference.cpp:114-128,212-215) → renders a
    chat template with bare fallback (splainference.cpp:132-169) →
    flips WAITING→SERVICING + bump → overwrites the slot with the
    rendered prompt (splainference.cpp:266-269) → prefills the decoder
    → token loop sampling top-p 0.9 / temp 0.7, streaming pieces into
    the slot via append flushed at word boundaries or every 8 tokens
    (splainference.cpp:86,102-109,306-365) so readers watch val_len
    grow → truncates at max_val with an oom marker
    (splainference.cpp:336-344) → clears the KV cache, backfills ctime,
    flips SERVICING→READY + bump (splainference.cpp:378-392);
  - appends debug chatter to the shared __debug key
    (splainference.cpp:94-100);
  - cold-start: drains any pre-existing waiting keys
    (splainference.cpp:541-551).

The decoder is a JAX causal LM with a device-resident KV cache
(models/decoder.py); generation compiles once per bucket and never
recompiles in the token loop.
"""
from __future__ import annotations

import dataclasses
import errno
import logging
import os
import time
from collections import deque
from typing import Callable, Iterator

from .. import _native as N
from ..obs.devtime import DEVTIME
from ..obs.recorder import FlightRecorder
from ..obs.spans import SpanWriter
from ..store import Store
from ..utils import faults
from ..utils.faults import fault
from ..utils.trace import tracer
from . import protocol as P
from .prefix_cache import Join, PrefixCache, Seat
from .qos import (AdmissionController, TenantLedger, WaitingRow,
                  parse_tenant_quotas, parse_tenant_weights,
                  prune_idle_counters)

log = logging.getLogger("libsplinter_tpu.completer")

# A generator backend: (prompt_text) -> iterator of byte pieces.
GenerateFn = Callable[[str], Iterator[bytes]]

OOM_MARKER = b"\n[truncated: value buffer full]"


TEMPLATES = ("none", "chatml", "llama2", "llama3")


def render_prompt(user: str, system: str | None,
                  template: str = "chatml") -> str:
    """Chat-template render with bare fallback
    (splainference.cpp:132-169: llama_chat_apply_template else
    'system\\n\\nuser' concatenation).  Supported: chatml, llama2,
    llama3, none.  Unknown names raise — 'auto' must be resolved via
    detect_template() BEFORE construction, never silently rendered as
    some default dialect."""
    if template == "none" or not template:
        return f"{system}\n\n{user}" if system else user
    if template == "llama2":
        sys_block = f"<<SYS>>\n{system}\n<</SYS>>\n\n" if system else ""
        return f"<s>[INST] {sys_block}{user} [/INST]"
    if template == "llama3":
        out = ["<|begin_of_text|>"]
        if system:
            out.append("<|start_header_id|>system<|end_header_id|>\n\n"
                       f"{system}<|eot_id|>")
        out.append("<|start_header_id|>user<|end_header_id|>\n\n"
                   f"{user}<|eot_id|>")
        out.append("<|start_header_id|>assistant<|end_header_id|>\n\n")
        return "".join(out)
    if template == "chatml":
        out = []
        if system:
            out.append(f"<|im_start|>system\n{system}<|im_end|>\n")
        out.append(f"<|im_start|>user\n{user}<|im_end|>\n")
        out.append("<|im_start|>assistant\n")
        return "".join(out)
    raise ValueError(
        f"unknown chat template {template!r} (supported: "
        f"{', '.join(TEMPLATES)}; 'auto' resolves via detect_template)")


def detect_template(chat_template: str | None) -> str:
    """Map a checkpoint's embedded Jinja chat template (GGUF metadata
    tokenizer.chat_template) to the nearest built-in renderer — the
    analog of llama.cpp's template fingerprinting.  Unknown templates
    fall back to bare concatenation rather than guessing a wrong
    special-token dialect."""
    if not chat_template:
        return "none"
    if "<|im_start|>" in chat_template:
        return "chatml"
    if "<|start_header_id|>" in chat_template:
        return "llama3"
    if "[INST]" in chat_template:
        return "llama2"
    return "none"


def _awaits(ids: list[int], match: int, page: int, join: Join) -> bool:
    """A prompt whose prefix walk matched `match` tokens would have
    matched a page more had `join` — a row seated earlier in the same
    admission round, its prefill still to come — been inserted into
    the tree already."""
    end = match + page
    other = join.ids
    return (join.match < end <= min(len(ids), len(other))
            and ids[match:end] == other[match:end]
            and ids[:match] == other[:match])


@dataclasses.dataclass
class CompleterStats:
    wakes: int = 0
    completions: int = 0
    tokens: int = 0
    truncated: int = 0
    raced: int = 0
    vanished: int = 0                 # keys deleted mid-request
    faults: int = 0                   # per-key failures the firewall ate
    reclaimed: int = 0                # stranded SERVICING rows re-queued
    join_backpressure: int = 0        # admissions deferred: pool full
    spec_demotions: int = 0           # speculative -> plain fallbacks
    # -- multi-tenant QoS (engine/qos.py) ----------------------------
    deadline_expired: int = 0         # fast-failed: deadline passed
    shed: int = 0                     # typed overloaded + retry hint
    deferred: int = 0                 # held for a later drain/chunk
    # -- K-deep decode overlap (engine/resident.py): un-awaited paged
    # decode chunks held while the host emits/admits ----------------
    inflight_peak: int = 0
    # mid-decode deadline aborts (continuous lane): rows whose
    # deadline expired at a chunk edge, retired with the typed
    # DEADLINE_EXPIRED record and their pages freed immediately
    killed_mid_decode: int = 0
    # -- the continuous lane's work counters: decode steps dispatched
    # and the live rows they carried (rows a step = their ratio),
    # prompt tokens admitted and how many of them a prefix-cache hit
    # mapped instead of prefilling, and (expert models) the
    # (token, expert) slots decode steps routed to experts held here
    decode_steps: int = 0
    decode_rows: int = 0
    # join prefills dispatched and the rows they carried: an admission
    # round's hits ride one program where the model's suffix program
    # has a row axis (rows a program = their ratio; 1 where it has not)
    join_programs: int = 0
    join_rows: int = 0
    prompt_tokens: int = 0
    prefix_tokens: int = 0
    expert_slots: int = 0
    # -- models with per-row recurrent state (models/kda.py): joins
    # that resumed from a snapshot, snapshots a join left, and prefix
    # tokens a hit gave up because no snapshot sat at their end
    state_restores: int = 0
    state_snapshots: int = 0
    state_cut_tokens: int = 0
    # answers by the request (protocol.stamp_max_new): requests that
    # carried a budget of their own, tokens emitted by the continuous
    # lane and the answers it finished
    budgeted_requests: int = 0
    answer_tokens: int = 0
    answers_finished: int = 0
    # -- models with a window page group (models/afmoe.py): prefix
    # hits that mapped their whole match because the window's tail was
    # still held, and prefix tokens a hit gave up for want of a tail
    # page
    window_resumes: int = 0
    window_cut_tokens: int = 0
    # joins whose mapped tail page some other live row held already (a
    # document's tail under several questions), and window pages rows
    # gave back from inside a decode chunk round (as against at a join)
    window_tail_shares: int = 0
    window_decode_slides: int = 0


class Completer:
    """Drive with run() (blocking loop), run_once() (single drain), or
    process_key() directly.  A fake generate_fn substitutes for the
    decoder in tests (the daemon-level test gap called out in
    SURVEY.md §4)."""

    # lane identity — the disaggregated prefill/decode lanes
    # (engine/disagg.py) subclass this daemon and override these:
    # LANE names the stripe map, span lane, debug prefix and devtime
    # lane; HB_KEY the heartbeat base key; WATCH_BIT the label
    # transition the lane wakes on (a decode lane watches
    # DECODE_READY handoffs, not fresh INFER_REQ arrivals).
    LANE = "completer"
    HB_KEY = P.KEY_COMPLETE_STATS
    WATCH_BIT = P.BIT_INFER_REQ

    def __init__(self, store: Store, generate_fn: GenerateFn | None = None,
                 *, model=None, tokenizer=None,
                 max_new_tokens: int = 256,
                 flush_tokens: int = 8,
                 rebid_tokens: int = 32,
                 template: str = "chatml",
                 group: int = P.GROUP_INFER,
                 batch_cap: int | None = None,
                 page_size: int = 128,
                 pool_pages: int | None = None,
                 kv_dtype: str | None = None,
                 inflight_depth: int | None = None,
                 spec_min_acceptance: float = 0.2,
                 queue_high_water: int | None = None,
                 retry_after_ms: int | None = None,
                 tenant_weights: dict[int, float] | None = None,
                 prefix_cache: bool = True,
                 prefix_cache_pages: int | None = None,
                 state_snapshots: int | None = None,
                 window_pool_pages: int | None = None,
                 prefix_quotas: dict[int, int] | None = None,
                 prefix_default_quota: int | None = None,
                 kv_tier_pages: int = 0,
                 kv_tier_persist: str | None = None,
                 replica: int = 0,
                 audit: dict | None = None):
        self.store = store
        # elastic lanes (protocol.StripeView): replica r drains only
        # its own slot-index stripe; stranded-SERVICING reclaim is
        # stripe-scoped too, so a restarted replica can never steal a
        # live peer's in-flight rows
        self.replica = int(replica)
        self.stripes = P.StripeView(store, self.LANE, self.replica)
        self._hb_key = P.replica_stats_key(self.HB_KEY,
                                           self.replica)
        self._trace_key = P.replica_stats_key(P.KEY_COMPLETE_TRACE,
                                              self.replica)
        self.max_new = max_new_tokens
        self.flush_tokens = flush_tokens
        self.rebid_tokens = rebid_tokens
        # per-lane defaults: the dense drains keep the r05-proven 8
        # (a wider dense batch multiplies (B, max_len, KH, D) cache
        # HBM — the very wall this PR removes), while the continuous
        # lane defaults to 32 because the block-paged pool's HBM
        # scales with live tokens instead of batch x max_len.  An
        # explicit batch_cap applies to both lanes unchanged.
        self.batch_cap = 8 if batch_cap is None else batch_cap
        self.paged_batch_cap = 32 if batch_cap is None else batch_cap
        self.page_size = page_size
        self.pool_pages = pool_pages
        # paged-pool storage dtype (--kv-dtype): "int8" quantizes the
        # continuous lane's KV pool (per-page scales, dequant inside
        # the ragged kernel) so cache bytes per token halve vs bf16 —
        # the headroom --batch-cap/--pool-pages then spend on batch
        # width.  "int4" packs two 4-bit codes per byte on top of the
        # same scale discipline — a QUARTER of bf16's cache bytes, so
        # the same pool serves 4x the batch.  None keeps the model's
        # native dtype.
        if kv_dtype not in (None, "bf16", "f32", "int8", "int4"):
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r} (bf16 | f32 | int8 | int4)")
        self.kv_dtype = kv_dtype
        # K-deep decode overlap on the continuous lane: the chunk
        # pipeline runs K deep — dispatch chunk K, then collect the
        # OLDEST while the newest computes (the token hand-off between
        # chunks rides the device, PendingChunk.last), so the host's
        # emit/flush/admit work overlaps device compute and the
        # per-chunk runtime round trip amortizes.  K counts the chunk
        # being collected: K-1 chunks stay un-awaited between loop
        # iterations (one less than the searcher/embedder windows,
        # whose depth bounds fully un-awaited entries), and 1 =
        # collect each chunk before dispatching the next — the
        # pre-overlap sync cadence.
        self.inflight_depth = (2 if inflight_depth is None
                               else max(1, inflight_depth))
        self.spec_min_acceptance = spec_min_acceptance
        self._spec_hist: list[tuple[int, int]] = []
        self._spec_acceptance_rolling: float | None = None
        self._paged_cache = None
        # multi-tenant QoS (engine/qos.py): every drain/admission
        # cycle orders the waiting keys fairly across tenants (stride
        # credit persists, so a starved tenant leads the next cycle);
        # queue_high_water bounds the waiting backlog — overflow is
        # claimed and READY-flipped with a typed overloaded JSON value
        # carrying retry_after_ms.  Deadline fast-fail is always on
        # for requests carrying a deadline stamp.
        self.qos = AdmissionController(
            weights=tenant_weights, high_water=queue_high_water,
            **({"retry_after_ms": retry_after_ms}
               if retry_after_ms is not None else {}))
        # phase-aware deadline slack: a request whose deadline will
        # pass before the lane's service phase even starts should
        # fast-fail NOW instead of paying prefill first.  The prefill
        # lane (engine/disagg.py) feeds this from a rolling prefill-
        # wall EMA; 0.0 keeps the unified lane's exact-expiry check.
        self.qos_slack_s = 0.0
        self.tenants = TenantLedger()
        self._had_deferred = False
        # join-backpressure memo, idx -> (slot epoch, pages needed):
        # instance state (not a run_continuous local) so the heartbeat
        # can publish its size and the sweep can bound it — under
        # sustained shedding it would otherwise grow per denied key
        self._bp_memo: dict[int, tuple[int, int]] = {}
        self._bp_memo_cap = 4096
        # cross-request prefix sharing (engine/prefix_cache.py): the
        # continuous lane's radix tree over the paged pool.  Built
        # lazily with the pool (plain PagedKVCache only — the paired
        # speculative pools don't share); pages_needed/backpressure
        # then count only the uncached suffix of each admission.
        self._prefix_enabled = bool(prefix_cache)
        self._prefix_cache_pages = prefix_cache_pages
        # snapshot slots of a model with recurrent state (None: one a
        # batch row); PagedKVCache owns them, the prefix tree their use
        self._state_snapshots = state_snapshots
        self._window_pool_pages = window_pool_pages
        self._prefix_quotas = dict(prefix_quotas or {})
        self._prefix_default_quota = prefix_default_quota
        self.prefix_cache = None
        # tiered KV (engine/kv_tier.py): a host-DRAM spill tier under
        # the radix tree — _evict_one demotes zero-ref pages to host
        # RAM instead of dropping, and a radix hit on a demoted page
        # readmits via device_put + block-table write instead of a
        # re-prefill.  kv_tier_persist names a file-backed store
        # segment the warm set checkpoints into (write-record-last,
        # epoch-bumped), so a supervised restart attaches WARM;
        # replica 0 owns the snapshot writes, every replica loads.
        self._tier_pages = max(0, int(kv_tier_pages))
        self._tier_persist_name = kv_tier_persist
        self.kv_tier = None
        self._tier_store = None
        self._tier_restore: tuple[int, str] = (0, "off")
        self._tier_last_save = 0.0
        if template not in TEMPLATES:
            raise ValueError(
                f"unknown chat template {template!r} (supported: "
                f"{', '.join(TEMPLATES)}; resolve 'auto' with "
                "detect_template first)")
        self.template = template
        self.group = group
        self.stats = CompleterStats()
        # flight recorder for the serial (process_key) path: clients
        # stamp infer requests exactly like embed ones
        # (protocol.stamp_trace); batched/continuous paths aggregate
        # through the span histograms only
        self.recorder = FlightRecorder()
        self.spans = SpanWriter(store, self.LANE)
        # disaggregated decode lane (engine/disagg.py): when set,
        # run_continuous's admit() delegates to this callable —
        # admission becomes ADOPTION of DECODE_READY handoffs and the
        # WAITING queue belongs to the prefill lanes
        self._lane_admit = None
        # pending spans between _prepare and _finalize, keyed by the
        # request key (every service path pairs the two); bounded by
        # in-flight work, with a hard cap against pathological leaks
        self._live_spans: dict[str, object] = {}
        self._trace_published = 0      # ring state last published
        # HBM watermarks: pool-occupancy high-water sampled at chunk
        # edges + heartbeats, reset only at attach (generation scope)
        self._pages_used_peak = 0
        self._pool_mb_peak = 0.0
        # one-shot start-up phases (ms), filled by main(); per-expert
        # slot totals of the decode steps (expert models only)
        self.startup_ms: dict[str, float] = {}
        self._expert_totals = None
        self._moe_counts = None
        # audit records (engine/audit.py): for a sample of the
        # continuous lane's requests, the prompt ids, the ids generated
        # and the logits behind the first and the last of them — what
        # a plain reference needs to hold the served path to account
        self.audit = None
        if audit:
            if not getattr(model, "audit_supported", False):
                raise ValueError(
                    "audit records need a model whose decode chunks "
                    "keep a row's logits (audit_supported)")
            from .audit import AuditLog
            self.audit = AuditLog(
                **audit, lanes=getattr(model, "audit_lanes", 1))
        self.generation = 0            # bumped at attach (restart marker)
        self._bid = -1
        self._running = False

        if generate_fn is not None:
            self.generate_fn = generate_fn
        else:
            if model is None:
                from ..models import CompletionModel, DecoderConfig
                # default vocab sized for the byte tokenizer (259 ids,
                # padded to a lane-friendly 512); real checkpoints bring
                # their own matching cfg+tokenizer pair
                model = CompletionModel(DecoderConfig(vocab_size=512))
            if tokenizer is None:
                from ..models import ByteTokenizer
                tokenizer = ByteTokenizer()
            self._model = model
            self._tok = tokenizer
            self.generate_fn = self._model_generate

    # -- wiring ------------------------------------------------------------

    def attach(self) -> None:
        st = self.store
        try:
            self._bid = st.shard_claim(P.SHARD_COMPLETE, N.ADV_WILLNEED,
                                       P.PRIO_COMPLETE, 30_000_000)
        except OSError:
            self._bid = -1
        st.watch_label_register(self.WATCH_BIT, self.group)
        st.bus_attach()   # adopts the bus when a crashed owner
                          # left a dead pid in the header
        self.generation = P.bump_generation(st, self._hb_key)
        # compile events ledgered from here carry this generation —
        # a restart's re-warmup is distinguishable in the ring
        DEVTIME.generation = max(DEVTIME.generation, self.generation)
        self._reclaim_stranded()

    def _reclaim_stranded(self) -> int:
        """Crash recovery: a daemon that died mid-completion leaves
        its key in SERVICING — no label watch fires for it again, so
        without this it is wedged forever.  Each stripe has ONE owner
        (the supervisor's invariant, per-replica under elastic
        lanes), so at attach every SERVICING row in OUR stripes is a
        previous generation's stranded request: flip it back to
        WAITING and let the cold-start drain re-serve it (the client
        sees a restarted stream, same as the reference's crash
        story).  Rows outside our stripes belong to live peer
        replicas mid-service — never touched; a permanently-dead
        replica's rows are the supervisor's straggler reclaim.

        Known bound (mirrors Supervisor._reclaim_closed's): a live
        peer's claim that predates a re-stripe can sit in OUR
        current stripes and would be re-queued here as stranded —
        the window needs an in-flight request to span a stripe
        promotion AND our own crash+respawn; claim-owner stamping
        is the follow-up that would close it."""
        st = self.store
        self.stripes.refresh()
        n = 0
        for idx in st.enumerate_indices(P.LBL_SERVICING):
            if not self.stripes.owns(idx):
                continue
            key = st.key_at(idx)
            if key is None:
                continue
            try:
                st.label_clear(key, P.LBL_SERVICING)
                st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
                n += 1
            except (KeyError, OSError):
                continue
        if n:
            self.stats.reclaimed += n
            self._debug(f"reclaimed {n} stranded SERVICING requests")
        return n

    def _requeue_failed(self, idxs: list[int]) -> int:
        """Firewall tail for run_once: an exception escaping
        process_key/process_batch after _prepare flipped rows to
        SERVICING leaves them label-invisible — the sweep enumerates
        LBL_INFER_REQ and, with the daemon still alive, the attach()
        reclaim never runs.  Flip the failed batch's SERVICING rows
        back to WAITING so the next sweep re-serves them instead of
        wedging their clients until timeout."""
        st = self.store
        n = 0
        for idx in idxs:
            try:
                if not (st.labels_at(idx) & P.LBL_SERVICING):
                    continue
                key = st.key_at(idx)
                if key is None:
                    continue
                st.label_clear(key, P.LBL_SERVICING)
                st.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
                n += 1
            except (KeyError, OSError):
                continue
        if n:
            self.stats.reclaimed += n
            self._debug(f"re-queued {n} SERVICING rows after a drain "
                        "fault")
        return n

    # -- multi-tenant QoS --------------------------------------------------

    def _qos_meta(self, idx: int) -> tuple[int, float | None]:
        """(tenant, deadline) for a waiting slot — tenant from the
        label word (free: one read), deadline from the companion stamp
        only when LBL_DEADLINE flags it."""
        st = self.store
        try:
            labels = st.labels_at(idx)
        except (KeyError, OSError):
            return 0, None
        deadline = None
        if labels & P.LBL_DEADLINE:
            try:
                deadline = P.read_deadline(st, idx,
                                           epoch=st.epoch_at(idx))
            except (KeyError, OSError):
                deadline = None
        return P.read_tenant(labels), deadline

    def _max_new_of(self, idx: int) -> int:
        """The answer budget of a waiting slot: the request's own
        stamp (protocol.stamp_max_new) capped at the daemon's
        --max-new-tokens, or the daemon's where it carries none — one
        bit-test of the label word then, never a lookup."""
        st = self.store
        try:
            if not st.labels_at(idx) & P.LBL_MAX_NEW:
                return self.max_new
            n = P.read_max_new(st, idx, epoch=st.epoch_at(idx))
        except (KeyError, OSError):
            return self.max_new
        return self.max_new if n is None else min(n, self.max_new)

    def _terminal_reject(self, idx: int, payload: bytes,
                         counter: str, tenant: int) -> bool:
        """Claim-and-reject a waiting request without spending a batch
        slot: the slot's value becomes the typed JSON payload
        (overloaded + retry_after_ms, or deadline_expired) and the
        label trifecta lands at READY — the client (engine/client.py)
        parses the record instead of burning its timeout."""
        st = self.store
        span = None
        try:
            if st.epoch_at(idx) & 1:
                return False          # writer active: next cycle
            labels = st.labels_at(idx)
            if not labels & P.LBL_INFER_REQ:
                return False          # recycled since enumeration
            key = st.key_at(idx)
            if key is None:
                return False
            if labels & P.LBL_TRACED:
                # the typed reject is this request's whole service:
                # open + commit its span around the claim (before the
                # payload write moves the epoch), then retire the
                # stamp the span protocol left in place
                span = self.spans.begin(idx, st.epoch_at(idx),
                                        tenant=tenant)
                P.consume_trace_stamp(st, idx)
            st.label_clear(key, P.LBL_INFER_REQ | P.LBL_WAITING)
            st.set(key, payload)
            st.label_or(key, P.LBL_READY)
            st.bump(key)
        except (KeyError, OSError):
            return False
        self.spans.commit(span, status=(
            P.ERR_DEADLINE if counter == "deadline_expired"
            else P.ERR_OVERLOADED))
        P.clear_deadline(st, idx)
        setattr(self.stats, counter,
                getattr(self.stats, counter) + 1)
        self.tenants.bump(tenant, counter)
        return True

    def _admit_waiting(self, idxs: list[int],
                       capacity: int) -> list[int]:
        """Order one cycle's waiting keys through the shared admission
        policy: expired deadlines reject fast, the fairness-ordered
        admit set (up to capacity) is returned for service, overflow
        past queue_high_water is shed with the typed overloaded
        record, the rest stay WAITING (their tenants lead the next
        cycle — stride state persists).  With no QoS config and no
        stamped rows this is a cheap pass-through."""
        if not idxs:
            return idxs
        rows: list[WaitingRow] = []
        tagged = False
        for idx in idxs:
            tenant, deadline = self._qos_meta(idx)
            tagged = tagged or tenant or deadline is not None
            rows.append(WaitingRow(idx, tenant, deadline))
        if not tagged and self.qos.high_water is None \
                and capacity >= len(idxs):
            self._had_deferred = False
            return idxs
        plan = self.qos.plan(rows, capacity,
                             slack_s=self.qos_slack_s)
        for row in plan.expired:
            self._terminal_reject(row.item,
                                  P.DEADLINE_EXPIRED_DIAGNOSTIC,
                                  "deadline_expired", row.tenant)
        for row in plan.shed:
            self._terminal_reject(
                row.item,
                P.overloaded_payload(self.qos.retry_after_ms),
                "shed", row.tenant)
        self.stats.deferred += len(plan.deferred)
        self._had_deferred = bool(plan.deferred)
        return [row.item for row in plan.admit]

    def _sweep_bp_memo(self) -> int:
        """Bound the join-backpressure memo: evict entries whose slot
        epoch moved on (rewritten/recycled — the memo'd pages-needed
        no longer describes the slot's request) or whose request label
        is gone (served, shed, or deadline-rejected).  Runs on the
        heartbeat cadence; under sustained shedding the memo would
        otherwise grow one entry per denied key forever.  A hard size
        cap (_bound_bp_memo, stale-first) backstops even a
        pathological store."""
        st = self.store
        dropped = 0
        for idx, (e, _need) in list(self._bp_memo.items()):
            try:
                if st.epoch_at(idx) != e or \
                        not st.labels_at(idx) & P.LBL_INFER_REQ:
                    del self._bp_memo[idx]
                    dropped += 1
            except (KeyError, OSError):
                self._bp_memo.pop(idx, None)
                dropped += 1
        return dropped + self._bound_bp_memo()

    def _bound_bp_memo(self) -> int:
        """Enforce the memo's hard size cap, evicting by SLOT-EPOCH
        STALENESS first: an entry whose slot epoch moved (or whose
        slot is gone) memoizes a request that no longer exists, while
        a live entry — however old — is a denied request the memo
        exists to keep cheap (evicting it re-pays render+tokenize on
        every subsequent chunk).  The old oldest-insertion policy did
        exactly that backwards: a long-lived denied request was the
        FIRST thing dropped while freshly-stale newcomers survived.
        Insertion-order eviction remains only as the final tiebreak
        among live entries."""
        over = len(self._bp_memo) - self._bp_memo_cap
        if over <= 0:
            return 0
        st = self.store
        dropped = 0
        for idx, (e, _need) in list(self._bp_memo.items()):
            if dropped >= over:
                break
            try:
                stale = st.epoch_at(idx) != e
            except (KeyError, OSError):
                stale = True
            if stale:
                self._bp_memo.pop(idx, None)
                dropped += 1
        while len(self._bp_memo) > self._bp_memo_cap:
            self._bp_memo.pop(next(iter(self._bp_memo)))
            dropped += 1
        return dropped

    def _debug(self, msg: str) -> None:
        """Append to the shared debug log key
        (splainference.cpp:94-100)."""
        st = self.store
        try:
            if P.KEY_DEBUG not in st:
                st.set(P.KEY_DEBUG, b"")
                st.label_or(P.KEY_DEBUG, P.LBL_DEBUG)
            st.append(P.KEY_DEBUG, f"[{self.LANE}] {msg}\n")
        except OSError:
            pass                      # debug channel full: not an error

    # -- model backend -----------------------------------------------------

    def _clip_context(self, ids: list[int], *, bucketed: bool) -> list[int]:
        """Keep the most recent context that still leaves max_new decode
        slots in the window.  Serial prefill parks the decode position
        at the REAL prompt length, so its budget is raw
        (max_len - max_new - 1).  Batched prefill left-pads to a bucket
        and parks at the BUCKET width (models/decoder.py prefill_batch),
        so the batched budget must be the largest bucket that still
        fits — a raw budget would round up into the window and strand
        every row with ~zero decode room."""
        m = self._model
        if bucketed:
            budget = self._batched_budget()
            assert budget is not None, \
                "run_once must route to serial when no bucket fits"
        else:
            budget = m.cfg.max_len - self.max_new - 1
            if budget < 1:
                budget = m.cfg.max_len // 2
        return ids[-budget:] if len(ids) > budget else ids

    def _batched_budget(self) -> int | None:
        """Largest prompt budget the BATCHED path can serve: the widest
        padding bucket strictly inside the window (prefill_batch
        requires max(lens) < max_len and parks the decode position at
        the bucket width), preferring one that also leaves max_new
        decode slots.  None when every bucket is the window itself —
        batched prefill would have zero decode room, so run_once falls
        back to serial serving for that geometry."""
        m = self._model
        usable = [b for b in m.buckets if b < m.cfg.max_len]
        if not usable:
            return None
        fit = [b for b in usable if b + self.max_new <= m.cfg.max_len]
        return fit[-1] if fit else usable[-1]

    def _paged_budget(self) -> int:
        """The PAGED lane's prompt budget: the window less max_new.  A
        paged row keeps its full prompt (paged_prefill_row has a
        bucket for every length inside the window) and ends at its
        own window edge, so nothing ties it to a dense padding
        bucket; a long prompt keeps its HEAD and can hit a shared
        prefix."""
        m = self._model
        budget = m.cfg.max_len - self.max_new
        return budget if budget >= 1 else m.cfg.max_len // 2

    def _clip_paged(self, ids: list[int]) -> list[int]:
        budget = self._paged_budget()
        return ids[-budget:] if len(ids) > budget else ids

    def _model_generate(self, prompt: str) -> Iterator[bytes]:
        m, tok = self._model, self._tok
        ids = self._clip_context(tok.encode(prompt), bucketed=False)
        import numpy as np
        try:
            # chunk-at-a-time on-device decode: the host syncs once per
            # flush_tokens tokens, not once per token
            # (cadence from splainference.cpp:333-354)
            for t in m.generate_tokens(np.asarray(ids, np.int32),
                                       self.max_new,
                                       chunk=max(1, self.flush_tokens)):
                if t == tok.eos_id:
                    break
                yield tok.token_to_piece(t)
        finally:
            m.reset()                 # llama_memory_clear analog

    # -- the completion ----------------------------------------------------

    def _read_rendered(self, idx: int):
        """Guarded prompt read + fresh system-prompt fetch + template
        render — NO side effects, so callers can peek a request (e.g.
        to check it fits a live batch) without claiming it.  Returns
        (key, rendered) or None."""
        st = self.store
        e = st.epoch_at(idx)
        if e & 1:
            return None               # writer active: next wake
        if not st.labels_at(idx) & P.LBL_INFER_REQ:
            return None               # slot recycled since enumeration:
                                      # never service a key that didn't ask
        key = st.key_at(idx)
        if key is None:
            return None
        try:
            prompt = st.get_at(idx).rstrip(b"\0").decode(
                "utf-8", errors="replace")
        except Exception:
            return None
        if st.epoch_at(idx) != e:
            self.stats.raced += 1
            return None               # torn read: re-queued by next wake

        # system prompt fetched fresh each request
        system = None
        try:
            system = st.get(P.KEY_SYSTEM_PROMPT).decode(
                "utf-8", errors="replace")
        except KeyError:
            pass
        return key, render_prompt(prompt, system, self.template)

    def _prepare(self, idx: int, peek: tuple | None = None):
        """The per-key request head (splainference.cpp:190-269):
        _read_rendered plus the claim side effects — WAITING→SERVICING
        flip, slot overwrite with the rendered prompt.  A caller that
        already peeked passes its (key, rendered) to avoid re-reading.
        Returns (key, rendered, t0, stamp) or None; stamp is the
        request's consumed trace stamp (serial path records it, the
        batched/continuous paths aggregate via spans only — consuming
        HERE means no path can leave a stale stamp to corrupt a later
        request's flight record)."""
        fault("completer.render")
        st = self.store
        if peek is None:
            peek = self._read_rendered(idx)
        if peek is None:
            return None
        key, rendered = peek

        stamp = None
        if st.labels_at(idx) & P.LBL_TRACED:
            # span begin consumes the stamp (the unstaged consume-
            # early discipline — exactly the old consume semantics),
            # and the PendingSpan carries the context to _finalize.
            # Consumed even with tracing OFF; recorded only when on.
            span = self.spans.begin(idx, st.epoch_at(idx),
                                    tenant=P.read_tenant(
                                        st.labels_at(idx)))
            if span is not None:
                if len(self._live_spans) > 1024:
                    self._live_spans.clear()   # spans are best-effort
                self._live_spans[key] = span
                stamp = span.stamp if tracer.enabled else None

        # QoS accounting at the claim (the real admission moment):
        # tagged requests count per tenant, and a consumed deadline
        # stamp must not linger to misjudge a later slot occupant
        try:
            labels_now = st.labels_at(idx)
        except (KeyError, OSError):
            labels_now = 0
        if labels_now & (P.TENANT_MASK | P.LBL_DEADLINE):
            self.tenants.bump(P.read_tenant(labels_now), "admitted")
            if labels_now & P.LBL_DEADLINE:
                P.clear_deadline(st, idx)
        if labels_now & P.LBL_MAX_NEW:
            # the answer's budget was read before the claim
            # (_max_new_of); its stamp must not outlive the request
            P.clear_max_new(st, idx)
            self.stats.budgeted_requests += 1

        # WAITING → SERVICING, visible to watchers immediately
        st.label_clear(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        st.label_or(key, P.LBL_SERVICING)
        st.bump(key)

        # slot now holds the rendered prompt; generation appends after it
        t0 = Store.now()
        data = rendered.encode("utf-8")
        try:
            st.set(key, data)
        except OSError:               # rendered prompt alone overflows —
            st.set(key, data[: st.max_val - 1])   # slice BYTES, not chars
        return key, rendered, t0, stamp

    def _finalize(self, key: str, t0: int, n_tok: int,
                  truncated: bool, vanished: bool = False,
                  stages: dict | None = None) -> None:
        """The per-key request tail: oom bookkeeping, ctime backfill
        with tick delta (splainference.cpp:282,383-387),
        SERVICING→READY flip.  A key deleted mid-request must fail
        alone — in a batch, a raising tail would strand the SIBLING
        rows in SERVICING forever — and is counted as vanished, not as
        a completion or a max_val truncation."""
        fault("completer.commit")
        st = self.store
        span = self._live_spans.pop(key, None)
        # the request's device window (dispatch->collect wall across
        # its decode chunks) — drain-scoped, SpanWriter.commit.  Split
        # lanes drain BOTH accumulators: the paged programs register
        # under the lane's own devtime name, the trunk + samplers stay
        # under the canonical "completer" lane.
        device_ms = DEVTIME.take_lane_ms(self.LANE)
        if self.LANE != "completer":
            device_ms += DEVTIME.take_lane_ms("completer")
        if span is None and stages:
            # tail-based retention: a slow request that carried no
            # trace stamp still keeps full INFER_STAGES detail — one
            # `tail: true` span, slow-log-resolvable by trace id
            thr = self.recorder.slow_threshold_ms()
            wall = sum(stages.values())
            if thr is not None and wall > thr:
                tid = self.spans.tail_span(
                    key, wall, stages=stages,
                    extra={"tokens": n_tok},
                    device_ms=device_ms if device_ms > 0 else None)
                if tid is not None:
                    self.recorder.record(
                        tid, key, wall,
                        [[n, round(float(ms), 3)]
                         for n, ms in stages.items()])
        if vanished:
            self.stats.vanished += 1
            self._debug(f"key {key!r} vanished mid-request")
            self.spans.commit(span, status="error", stages=stages)
            return
        if truncated:
            self.stats.truncated += 1
            self._debug(f"completion for {key!r} truncated at max_val")
        try:
            st.stamp(key, which=0, ticks_ago=Store.now() - t0)
        except Exception:
            pass
        try:
            # DECODE_READY cleared too: on the disaggregated decode
            # lane a finishing row carries SERVICING|DECODE_READY and
            # leaving the handoff bit set would invite a re-adoption
            # of a completed request (a no-op clear elsewhere)
            st.label_clear(key, P.LBL_SERVICING | P.LBL_DECODE_READY)
            st.label_or(key, P.LBL_READY)
            st.bump(key)
        except (KeyError, OSError):
            self.stats.vanished += 1
            self._debug(f"key {key!r} vanished mid-request")
            self.spans.commit(span, status="error", stages=stages)
            return
        self.spans.commit(span, stages=stages,
                          extra={"tokens": n_tok},
                          device_ms=device_ms if device_ms > 0
                          else None)
        self.stats.completions += 1
        self.stats.tokens += n_tok
        try:
            tenant = P.read_tenant(st.labels(key))
        except (KeyError, OSError):
            tenant = 0
        if tenant:
            # tenant bits survive the claim (only INFER/WAITING were
            # cleared), so goodput attribution needs no plumbing
            self.tenants.bump(tenant, "served_tokens", n_tok)

    def _rebid(self) -> None:
        if self._bid >= 0:
            try:
                self.store.shard_rebid(self._bid)
            except OSError:
                pass

    # -- disaggregated-lane hooks (engine/disagg.py overrides) -------------

    def _lane_row_done(self, row: dict) -> None:
        """A continuous-lane row retired (finish or mid-decode kill).
        The decode lane deletes the row's handoff record + wire pages
        here; the unified lane has nothing to clean up."""

    def _lane_payload(self, payload: dict) -> None:
        """Lane-specific heartbeat sections (handoff counters,
        adoption gauges) land here just before publish."""

    def process_key(self, idx: int) -> bool:
        """Run one completion for slot idx.  Returns True if serviced.

        With SPTPU_TRACE=1 the request decomposes into the
        protocol.INFER_STAGES histogram spans, and a client-stamped
        request (protocol.stamp_trace) gets a flight-recorder entry
        with the stage event sequence + client-measured wall time."""
        traced = tracer.enabled
        tr0 = time.perf_counter()
        prep = self._prepare(idx)
        if prep is None:
            return False
        tr1 = time.perf_counter()
        key, rendered, t0, stamp = prep
        n_tok, pending = 0, b""
        truncated = vanished = False
        try:
            fault("completer.generate")
            for piece in self.generate_fn(rendered):
                pending += piece
                n_tok += 1
                boundary = piece.endswith((b" ", b"\n", b"\t"))
                if boundary or n_tok % self.flush_tokens == 0:
                    r = self._flush(key, pending)
                    if r != "ok":
                        truncated = r == "full"
                        vanished = r == "gone"
                        break
                    pending = b""
                if self.rebid_tokens and n_tok % self.rebid_tokens == 0:
                    self._rebid()
            if pending and not truncated and not vanished:
                r = self._flush(key, pending)
                truncated = r == "full"
                vanished = r == "gone"
        except Exception as ex:       # model failure must not wedge WAITING
            self._debug(f"generation failed for {key!r}: {ex}")
        tr2 = time.perf_counter()
        self._finalize(key, t0, n_tok, truncated, vanished)
        if traced:
            tr3 = time.perf_counter()
            stages = ((tr1 - tr0) * 1e3, (tr2 - tr1) * 1e3,
                      (tr3 - tr2) * 1e3)
            for name, ms in zip(P.INFER_STAGES, stages):
                tracer.record(f"infer.{name}", ms)
            tracer.record("infer.e2e", (tr3 - tr0) * 1e3)
            if stamp is not None:
                tid, ts = stamp
                wall = ((time.time() - ts) * 1e3 if ts > 0
                        else (tr3 - tr0) * 1e3)
                self.recorder.record(
                    tid, key, wall,
                    [[n, round(ms, 3)]
                     for n, ms in zip(P.INFER_STAGES, stages)])
        return True

    def process_batch(self, idxs: list[int]) -> int:
        """Service up to batch_cap waiting keys as ONE batched decode.

        The reference is strictly serial — one llama.cpp context per
        request (splainference.cpp:414-448, 306-365).  Here the decoder
        left-pads every prompt into one bucket and decodes all rows per
        device step (models/decoder.py generate_batch), so N concurrent
        requests cost ~one request's wall clock.  Per-key protocol is
        IDENTICAL to process_key: label trifecta, rendered-prompt
        overwrite, word-boundary/8-token streaming appends, per-row oom
        truncation, ctime backfill, __debug on failure."""
        import numpy as np

        m, tok = self._model, self._tok
        prepped = []                  # (key, t0, ids)
        done_early = 0
        for idx in idxs:
            prep = self._prepare(idx)
            if prep is None:
                continue
            key, rendered, t0, _stamp = prep   # consumed by _prepare
            ids = self._clip_context(tok.encode(rendered), bucketed=True)
            if not len(ids):
                # an empty prompt must fail alone, not poison the whole
                # batch via prefill_batch's empty-prompt ValueError
                self._finalize(key, t0, 0, False)
                done_early += 1
                continue
            prepped.append((key, t0, np.asarray(ids, np.int32)))
        if not prepped:
            return done_early

        B = len(prepped)
        n_tok = [0] * B
        pending = [b""] * B
        done = [False] * B
        truncated = [False] * B
        vanished = [False] * B
        total = 0
        try:
            fault("completer.generate")
            gen = m.generate_batch([p[2] for p in prepped], self.max_new,
                                   chunk=max(1, self.flush_tokens))
            for col in gen:           # (B,) token column per step
                for r in range(B):
                    if done[r]:
                        continue      # speculative token: discard
                    t = int(col[r])
                    if t == tok.eos_id:
                        done[r] = True
                        continue
                    key = prepped[r][0]
                    piece = tok.token_to_piece(t)
                    pending[r] += piece
                    n_tok[r] += 1
                    boundary = piece.endswith((b" ", b"\n", b"\t"))
                    if boundary or n_tok[r] % self.flush_tokens == 0:
                        res = self._flush(key, pending[r])
                        if res != "ok":
                            truncated[r] = res == "full"
                            vanished[r] = res == "gone"
                            done[r] = True
                        pending[r] = b""
                total += 1
                if self.rebid_tokens and total % self.rebid_tokens == 0:
                    self._rebid()
                if all(done):
                    break
        except Exception as ex:       # model failure must not wedge WAITING
            self._debug(f"batched generation failed: {ex}")
        finally:
            m.reset()
        for r in range(B):
            key, t0, _ = prepped[r]
            if pending[r] and not truncated[r] and not vanished[r]:
                res = self._flush(key, pending[r])
                truncated[r] = res == "full"
                vanished[r] = res == "gone"
            self._finalize(key, t0, n_tok[r], truncated[r], vanished[r])
        return B + done_early

    def _flush(self, key: str, data: bytes) -> str:
        """Append a flushed run; on overflow truncate-and-mark
        (splainference.cpp:336-344).  Returns "ok", "full" (value at
        max_val — an OOM truncation), or "gone" (client deleted the
        key mid-request — stops THIS row without touching its batch,
        and must NOT be reported as a truncation)."""
        st = self.store
        try:
            st.append(key, data)
            return "ok"
        except KeyError:
            return "gone"
        except OSError as ex:
            if ex.errno != errno.EMSGSIZE:
                raise
            try:
                room = st.max_val - 1 - st.value_len(key)
                tail = data[: max(0, room - len(OOM_MARKER))] + OOM_MARKER
                st.append(key, tail[: max(0, room)])
            except (KeyError, OSError):
                pass
            return "full"

    # -- continuous batching (block-paged) --------------------------------

    def _paged_ok(self) -> bool:
        """True when the model can serve the block-paged continuous
        lane (paged_supported).  Any window does: a paged row keeps
        its prompt up to the window less max_new (_paged_budget) and
        every model has a prefill bucket that reaches it."""
        m = getattr(self, "_model", None)
        return (m is not None
                and getattr(m, "paged_supported", False)
                and self.paged_batch_cap >= 2)

    def _ensure_paged_cache(self):
        if self._paged_cache is None:
            kw = ({"state_snapshots": self._state_snapshots}
                  if getattr(self._model, "needs_state", False) else {})
            if getattr(self._model, "needs_window", False):
                kw["window_pool_pages"] = self._window_pool_pages
            self._paged_cache = self._model.init_paged(
                self.paged_batch_cap, page=self.page_size,
                pool_pages=self.pool_pages, kv_dtype=self.kv_dtype, **kw)
            cache = self._paged_cache
            if self._prefix_enabled and hasattr(cache, "map_shared"):
                # (re)bind the radix tree to THIS pool: a rebuilt
                # pool (abort recovery, spec demotion) invalidates
                # every cached page id, so attach() empties the tree
                if self.prefix_cache is None:
                    self.prefix_cache = PrefixCache(
                        self.page_size,
                        max_pages=self._prefix_cache_pages,
                        tenant_quotas=self._prefix_quotas,
                        default_quota=self._prefix_default_quota)
                self.prefix_cache.attach(cache)
                cache.prefix_cache = self.prefix_cache
                if self._tier_pages:
                    self._bind_tier(cache)
        return self._paged_cache

    def _bind_tier(self, cache) -> None:
        """Wire the host-DRAM spill tier under the freshly-attached
        radix tree, then (when persistence is on) load the last good
        snapshot so THIS generation starts warm.  attach() just
        cleared the tree + tier, so a rebuilt pool always reloads
        from the persistent layer rather than trusting stale bids."""
        from .kv_tier import HostTier, TierPersist, tier_geometry
        m = self._model
        if self.kv_tier is None:
            self.kv_tier = HostTier(self._tier_pages)
        self.prefix_cache.bind_tier(
            self.kv_tier,
            export_page=lambda bid, _c=cache, _m=m:
                _m.export_page_bytes(_c, bid),
            import_page=lambda bid, buf, sbuf, _c=cache, _m=m:
                _m.import_page_bytes(_c, bid, buf, sbuf))
        if not self._tier_persist_name:
            return
        geom = tier_geometry(m, cache)
        try:
            if self._tier_store is None:
                self._tier_store = TierPersist(
                    self._tier_persist_name,
                    capacity_pages=self._tier_pages,
                    max_len=m.cfg.max_len,
                    page_bytes=geom["page_bytes"])
            self._tier_restore = self._tier_store.load(
                self.prefix_cache, self.kv_tier, geom)
        except OSError:
            # persistence degraded (segment unopenable) — serve cold
            # with the in-RAM tier only; the reason reaches heartbeat
            self._tier_store = None
            self._tier_restore = (0, "restore_failed")

    def warmup_paged(self) -> None:
        """Pre-compile the continuous lane's whole program set (paged
        prefill buckets + commit scatters + the chunked paged decode
        step) against the SAME pool geometry run_continuous will
        serve with — compile_count stays flat across join/finish/join
        cycles afterwards."""
        if not self._paged_ok():
            return
        cache = self._ensure_paged_cache()
        self._model.warmup_paged(cache,
                                 chunk=max(1, self.flush_tokens),
                                 max_prompt=self._paged_budget())
        if self.kv_tier is not None:
            # spill/readmit ride the handoff gather/scatter programs —
            # warm both so tier traffic never compiles post-warmup
            # (the PR 17 no-recompile gate covers tiered lanes too)
            self._model.warmup_handoff(cache, export=True, adopt=True)

    def _beat(self) -> None:
        """The continuous lane's 2 s beat, one leaf of its loop
        (`infer.beat`).  It runs at the HEAD of a pass, so the
        heartbeat it publishes holds whole passes only and `infer.loop`
        equals its leaves' sum plus the loop's own bookkeeping in every
        snapshot (the rule Searcher.run's docstring states)."""
        with tracer.span("infer.beat", leaf=True):
            # speculative degradation rides the heartbeat cadence on
            # this lane (run_once's per-drain hook never fires here): a
            # tripped floor swaps self._model to the target NOW, and
            # the lane adopts it at its next idle point
            self._maybe_demote_spec()
            # same cadence: bound the join-backpressure memo (evict
            # rewritten / no-longer-waiting slots)
            self._sweep_bp_memo()
            self.publish_stats()
            # warm-layer checkpoint rides the same beat — dirty-gated,
            # so a quiet tier costs one flag read
            self._tier_checkpoint()

    def run_continuous(self, *, idle_timeout_ms: int = 100,
                       stop_after: float | None = None) -> None:
        """Continuous batched serving over the block-paged KV pool:
        requests join and leave the live batch at chunk boundaries
        (vLLM-style slot scheduling over decoder.PagedKVCache +
        ops/paged_attention).

        batch_cap rows decode together, each over its OWN logical
        positions 0..len-1 in pages of a global pool — there is no
        shared window: a joiner prefills its FULL prompt into freshly
        allocated pages at any time (no join budget, no oversized-
        joiner deferral), a finished row's pages return to the pool
        immediately (no full-batch cache reset), and a row ends at
        ITS window edge, not the batch's.  Admission is gated on free
        pages: a request whose worst case (prompt + max_new rounded
        up to a decode-chunk boundary, capped at the window) exceeds
        the pool stays WAITING and
        join_backpressure counts the deferral — backpressure, never a
        mid-decode strand.  Sharded models serve this lane too (PR 8:
        kv-head-sharded pools + shard_map'd ragged kernel,
        parallel/serve.py), as do quantized pools (--kv-dtype int8
        with per-page scales and dequant in-kernel; int4 packs two
        codes per byte on the same discipline) and speculative
        models (PR 9: the wrapper implements the paged surface —
        drafts verify through the paged kernel's multi-query stack;
        a tripped acceptance floor swaps in the target at the next
        idle point; the lockstep target/draft pools shard on kv
        heads like everything else, so spec-paged composes with
        --tp).  Models whose module cannot thread a mesh
        (paged_supported False) and window-only bucket geometries
        fall back to run().

        With SPTPU_TRACE=1 every pass of the loop is one `infer.loop`
        span and every second of a pass belongs to one LEAF
        (protocol.CONT_LOOP_PHASES and the CONT_INFER_STAGES that are
        disjoint in time: idle, beat, gather, prepare, prefix_hit,
        state_restore, state_snapshot, join, sample, emit, decode,
        collect, rebid); `infer.admit` encloses an admission round's
        and `infer.chunk` a chunk round's.
        A leaf also opens the profiler annotation, so a capture names
        the device's idle gaps after it.  Where a request's event list
        wants the same number, the leaf is `tracer.annotation` + the
        local span(row, name, ms) from ONE clock pair; elsewhere it is
        `tracer.span(name, leaf=True)`.  What is left of `infer.loop`
        (deadline kills and the edge scan inside `infer.chunk`, the
        loop's own lines) is its bookkeeping."""
        if not self._paged_ok():
            return self.run(idle_timeout_ms=idle_timeout_ms,
                            stop_after=stop_after)
        import itertools

        import numpy as np

        m = self._model
        st = self.store
        tok_izer = self._tok
        B = self.paged_batch_cap
        cfg = m.cfg
        cache = self._ensure_paged_cache()
        self._running = True
        deadline = (time.monotonic() + stop_after) if stop_after else None
        last = st.signal_count(self.group)
        next_beat = time.monotonic() + 2.0
        self.publish_stats()          # the attach-complete signal

        rows: list[dict | None] = [None] * B
        # K-deep chunk window (engine/resident.py discipline): up to
        # inflight_depth dispatched chunks fly un-awaited; the token
        # hand-off between chunks stays ON DEVICE (PendingChunk.last),
        # and each entry snapshots (row, serial) of the rows live at
        # its dispatch so a lagged collect can never emit into a row a
        # later admission re-seated (the serial is the guard — pages a
        # stale in-flight chunk touches are either still owned by the
        # finished row or fully overwritten by the joiner's commit
        # scatter, which the device executes in dispatch order).
        window: deque = deque()       # (PendingChunk, [(row, serial)])
        serial = itertools.count()
        carry = None                  # device-side last-token column
        # host-fed fresh tokens: a row whose token was produced on the
        # host since the last dispatch (a joiner's prefill sample)
        # rides this column; -1 = take the device carry
        fresh = np.full((B,), -1, np.int32)
        rebid_due = 0                 # decoded steps since last rebid
        step = max(1, self.flush_tokens)   # decode chunk granularity
        # backpressured requests, idx -> (slot epoch, pages needed):
        # admit() runs every chunk, and re-rendering + re-tokenizing a
        # denied prompt each time would burn host CPU alongside device
        # decode — the memo re-checks only free_pages until the slot
        # is rewritten (epoch moves) or the pool might fit it.  The
        # dict is instance state (self._bp_memo) so the heartbeat
        # publishes its size and _sweep_bp_memo bounds it — under
        # sustained shedding it used to leak one entry per denied key
        bp_memo = self._bp_memo
        bp_memo.clear()

        def worst_len(n_ids: int, replay: int = 0,
                      max_new: int | None = None) -> int:
            """Worst-case cache length for an admitted prompt whose
            answer budget is `max_new` (None: the daemon's).  Decode
            appends whole `step`-token chunks (paged_decode_chunk),
            so the final chunk can grow the cache up to step-1 tokens
            PAST the prompt + max_new budget — the admission
            reservation must cover that chunk-boundary ceiling, or a
            fully reserved pool could still raise mid-decode and
            abort every live row.  The first output token comes from
            the prefill sample; the remaining max_new - 1 arrive in
            whole chunks; a fully cached prompt adds the `replay` of
            its last token (prefix_cache.Seat.plan)."""
            budget = self.max_new if max_new is None else max_new
            chunks = -(-(budget - 1) // step) if budget > 1 else 0
            return min(n_ids + chunks * step + replay, cfg.max_len)

        def span(row: dict | None, name: str, ms: float) -> None:
            """Accumulate a stage span: the lane histogram always, the
            row's flight-recorder event list when the request was
            client-stamped (LBL_TRACED)."""
            tracer.record(f"infer.{name}", ms)
            if row is not None and row.get("spans") is not None:
                row["spans"].append([name, round(ms, 3)])

        def _lane_ctx() -> dict:
            """The adoption context a disaggregated decode lane's
            _lane_admit hook seats rows through — everything a join
            would have touched, snapshot-fresh (cache is rebound
            after abort_all, so it must be read HERE, not captured
            at loop entry)."""
            return {"rows": rows, "fresh": fresh, "cache": cache,
                    "serial": serial, "step": step,
                    "worst_len": worst_len, "span": span,
                    "finish": finish}

        def plan(waiting: list, cap: int) -> list:
            """The round's admission order (the head of `gather`).
            Multi-tenant admission before any render: fair order
            across tenants, expired deadlines rejected fast, backlog
            past high water shed typed.  Pool-backpressured rows are
            EXCLUDED from the fairness plan entirely — they are not
            admissible this cycle, and letting the planner "admit"
            them would charge their tenant's stride pass every chunk
            for a row the pool can never seat, pushing that tenant
            behind peers it was never actually served ahead of.
            Their deadlines still matter: an expired blocked row is
            rejected typed right here."""
            plannable = []
            now_wall = time.time()
            for w_idx in waiting:
                memo = bp_memo.get(w_idx)
                if memo is not None \
                        and memo[0] == st.epoch_at(w_idx) \
                        and memo[1] > cache.available_pages:
                    tenant, dl = self._qos_meta(w_idx)
                    if dl is not None and dl <= now_wall:
                        if self._terminal_reject(
                                w_idx, P.DEADLINE_EXPIRED_DIAGNOSTIC,
                                "deadline_expired", tenant):
                            bp_memo.pop(w_idx, None)
                    continue
                plannable.append(w_idx)
            return self._admit_waiting(plannable, cap)

        def admit() -> int:
            """One admission round: the `infer.admit` span around
            fill_rows, whose leaves (protocol.CONT_LOOP_PHASES: gather,
            prepare, prefix_hit, state_restore, state_snapshot, join,
            sample, emit; a decode lane's adopt) account for it."""
            with tracer.span("infer.admit"):
                return fill_rows()

        def fill_rows() -> int:
            """Fill free rows from waiting keys.  EVERY admission is a
            join — the prompt prefills into freshly allocated pages
            right here, whether the batch is empty or mid-decode.
            Reserving prompt + max_new pages up front means decode can
            never exhaust the pool mid-flight; a request the pool
            cannot cover yet stays WAITING (join_backpressure)."""
            free = [r for r in range(B) if rows[r] is None]
            if not free:
                return 0
            if self._lane_admit is not None:
                # disaggregated decode lane (engine/disagg.py):
                # admission is ADOPTION of DECODE_READY handoffs at
                # this chunk edge — the WAITING queue belongs to the
                # prefill lanes, and a joiner's dense prefill never
                # runs here (the whole point of the split)
                return self._lane_admit(free, _lane_ctx())
            looked: set[int] = set()

            def gather() -> list:
                """The waiting requests this round has not looked at,
                in admission order."""
                with tracer.span("infer.gather", leaf=True):
                    self.stripes.refresh()  # admission IS this lane's
                    waiting = [             # drain
                        i for i in st.enumerate_indices(P.LBL_INFER_REQ)
                        if self.stripes.owns(int(i))
                        and int(i) not in looked]
                    order = plan(waiting, len(free)) if waiting else []
                    looked.update(int(i) for i in order)
                    return order

            def round_order(order: list):
                """The round's requests: what waited when it opened
                and, while seated hits await the round's program and
                rows are still free, what has arrived since — clients
                answered together come back over the milliseconds the
                first of them take to seat, and a straggler's program
                of its own would read the weights again."""
                while order:
                    yield from order
                    if not (round_joins and free):
                        return
                    order = gather()

            order = gather()
            if not order:
                return 0
            n = 0
            traced = tracer.enabled
            pc, wgroup = cache.prefix_cache, cache.window
            # the joins that ride the round (the model says which:
            # `rides_round`) are seated first and prefilled together,
            # `round_cap` to a program; the others are rounds of one,
            # served where they are seated
            round_cap = m.round_cap(cache)
            round_joins: list[dict] = []
            for idx in round_order(order):
                if not free:
                    break
                with tracer.span("infer.gather", leaf=True):
                    e = st.epoch_at(idx)
                    memo = bp_memo.get(idx)
                    if memo is not None and memo[0] == e:
                        if memo[1] > cache.available_pages:
                            continue  # still too big: skip the render
                        del bp_memo[idx]  # pool may fit: peek fresh
                # peek BEFORE claiming: a backpressured request stays
                # WAITING untouched (a claim would overwrite its slot
                # with the rendered prompt)
                with tracer.span("infer.prepare", leaf=True):
                    peek = self._read_rendered(idx)
                    if peek is None:
                        continue
                    ids = self._clip_paged(tok_izer.encode(peek[1]))
                # the pages side of the admission (prefix_cache.Seat):
                # the radix-tree walk BEFORE the page math, so that the
                # reservation (and the backpressure memo) counts only
                # what the tree does not hold
                seat = Seat(cache, ids)
                walk_ms = 0.0
                if pc is not None and len(ids):
                    tw = time.perf_counter()
                    with tracer.annotation("infer.prefix_hit"):
                        seat.walk()
                    if traced:
                        # the walk is prefix_hit's first part; the
                        # row's event list takes it once it is seated
                        walk_ms = (time.perf_counter() - tw) * 1e3
                        span(None, "prefix_hit", walk_ms)
                if any(_awaits(ids, seat.match, cache.page, j["join"])
                       for j in round_joins):
                    # the walk ends short of a page a seated row of
                    # this round is about to prefill: the round closes
                    # here, and the request (untouched) hits that page
                    # in the next
                    break
                with tracer.span("infer.gather", leaf=True):
                    # the request's own budget, where it stamped one
                    budget = self._max_new_of(idx)
                    if len(ids):
                        short = seat.plan(
                            worst_len(len(ids), 0, budget),
                            worst_len(len(ids), step, budget))
                        if short is not None:
                            self.stats.join_backpressure += 1
                            bp_memo[idx] = (e, short)
                            self._bound_bp_memo()
                            continue      # pool full: next cycle retries
                    tenant, _dl = self._qos_meta(idx)
                with tracer.span("infer.prepare", leaf=True):
                    prep = self._prepare(idx, peek=peek)
                    if prep is None:
                        continue
                    key, rendered, t0, stamp = prep
                    if not len(ids):
                        self._finalize(key, t0, 0, False)
                        continue
                r = free.pop(0)
                rows[r] = {"key": key, "t0": t0, "n_tok": 0,
                           "pending": b"", "remaining": budget,
                           "stamp": stamp,
                           # deadline retained for the chunk-edge
                           # mid-decode abort (the __dl_ stamp itself
                           # was consumed at the claim)
                           "deadline": _dl, "tenant": tenant,
                           # serial: the lagged-collect guard (a chunk
                           # in flight across this row's re-seat must
                           # never emit into the newcomer); disp_left:
                           # decode steps still dispatchable before
                           # every budgeted token is in flight
                           "serial": next(serial),
                           "disp_left": budget - 1,
                           "spans": ([] if traced and stamp is not None
                                     else None),
                           "wall0": time.perf_counter()}
                if walk_ms and rows[r]["spans"] is not None:
                    rows[r]["spans"].append(
                        ["prefix_hit", round(walk_ms, 3)])
                # prefix_hit, after the walk above: mapping the hit's
                # pages and reserving the row's own — one clock pair
                ta = time.perf_counter()
                w_s0 = wgroup.release_s if wgroup is not None else 0.0
                with tracer.annotation("infer.prefix_hit"):
                    seated = seat.map(r)
                    if seat.hit_bids and tenant:
                        self.tenants.bump(tenant, "prefix_hit_pages",
                                          len(seat.hit_bids))
                    if seat.hit_bids and wgroup is not None:
                        self.stats.window_tail_shares += seat.tail_shared
                        self.stats.window_resumes += int(not seat.wcut)
                    self.stats.prompt_tokens += len(ids)
                    self.stats.prefix_tokens += seat.match
                    self.stats.state_cut_tokens += seat.cut
                    self.stats.window_cut_tokens += seat.wcut
                if not seated:
                    rows[r] = None
                    free.insert(0, r)
                    self._live_spans.pop(key, None)
                    self.stats.join_backpressure += 1
                    self._requeue_failed([idx])
                    continue
                if traced:
                    span(rows[r], "prefix_hit",
                         (time.perf_counter() - ta) * 1e3)
                if seat.state_src is not None:
                    # a hit of a model with per-row recurrent state
                    # (models/kda.py) resumes from the snapshot its
                    # last node owns
                    t_s = time.perf_counter()
                    with tracer.annotation("infer.state_restore"):
                        m.state_restore(cache, seat.state_src, r)
                    self.stats.state_restores += 1
                    if traced:
                        span(rows[r], "state_restore",
                             (time.perf_counter() - t_s) * 1e3)
                zeroed = False
                if seat.state_src is None and not seat.hit_bids \
                        and cache.needs_state:
                    # a prompt from nothing of a model with per-row
                    # recurrent state starts from the zero state: the
                    # slot still holds what its last row left
                    t_s = time.perf_counter()
                    with tracer.annotation("infer.state_zero"):
                        m.state_zero(cache, r)
                    zeroed = True
                    if traced:
                        span(rows[r], "state_zero",
                             (time.perf_counter() - t_s) * 1e3)
                suffix = seat.suffix
                if cache.quantized and suffix:
                    # the quantized append/commit path: the commit
                    # scatter about to run quantizes the prompt's K/V
                    # into int8 pages (per-page scales) — the chaos
                    # matrix crashes HERE to prove a mid-quantized-
                    # commit death restarts clean with no poisoned
                    # pages (tests/chaos_child.py completer_quant)
                    fault("completer.kv_quant_commit")
                if suffix:
                    snap = None
                    if seat.snap_at is not None:
                        # a slot for the snapshot BEFORE the prefill
                        # that fills it (the tree may give up another
                        # snapshot for it; the restore above is
                        # already dispatched)
                        t_s = time.perf_counter()
                        with tracer.annotation("infer.state_snapshot"):
                            slot = cache.alloc_state_slot()
                        if slot is not None:
                            snap = (slot, seat.snap_at)
                        if traced:
                            span(rows[r], "state_snapshot",
                                 (time.perf_counter() - t_s) * 1e3)
                    join = {"join": Join(r, ids, seat.match,
                                         bool(seat.hit_bids), snap,
                                         zeroed),
                            "key": key, "reserve": seat.reserve,
                            "tenant": tenant, "w_s0": w_s0}
                    if m.rides_round(join["join"]):
                        round_joins.append(join)
                        if len(round_joins) == round_cap:
                            join_round(round_joins)
                            round_joins = []
                    else:
                        join_round([join])
                else:                 # reads it over the device carry
                    # FULLY cached prompt: no prefill at all.  The
                    # row enters at lengths = P-1 and the next decode
                    # chunk replays the last prompt token into the
                    # shared tail page's private copy; the chunk's
                    # first sampled column is the row's first output
                    # token, so the full budget stays dispatchable.
                    # The COW runs EAGERLY here — the admission need
                    # counted that page, and deferring the copy to
                    # dispatch would let a later admission consume it
                    # and strand this row mid-decode.  That copy is
                    # this row's whole join.
                    ta = time.perf_counter()
                    with tracer.annotation("infer.join"):
                        m._cow_fixups(cache)
                    if traced:
                        span(rows[r], "join",
                             (time.perf_counter() - ta) * 1e3)
                    rows[r]["disp_left"] = budget
                    fresh[r] = int(ids[-1])
                n += 1
            if round_joins:
                join_round(round_joins)
            return n

        def join_round(joins: list[dict]) -> None:
            """The prefill of rows fill_rows seated — ONE call of the
            model's `join`, which picks the program — and what follows
            their logits.  A round of one comes back with its logits
            on the host and is drawn here; a round of several with its
            first tokens drawn in graph (the rows' states were restored
            at their seats, before the program: it reads no snapshot a
            later seat may have evicted); each snapshot's node takes
            its slot over below, a row at a time.  `infer.join` is
            one annotation from the dispatch to the logits and is
            recorded once a ROW, the round's wall over its rows;
            `infer.sample` is a row's share of what stands between the
            logits and its first token (the window group's reserve,
            the tree's insert, the audit's copy, the draw)."""
            pc, wgroup = cache.prefix_cache, cache.window
            traced = tracer.enabled
            ta = time.perf_counter()
            with tracer.annotation("infer.join"):
                logits, firsts = m.join(cache, [j["join"] for j in joins])
            join_ms = (time.perf_counter() - ta) * 1e3 / len(joins)
            self.stats.join_programs += 1
            self.stats.join_rows += len(joins)
            # the round's logits stay on the device; an audited row
            # brings them to the host
            on_host = None
            for i, j in enumerate(joins):
                jn = j["join"]
                r, ids, match, snap = jn.row, jn.ids, jn.match, jn.snap
                tb = time.perf_counter()
                with tracer.annotation("infer.sample"):
                    if wgroup is not None:
                        # the prefill gave the window pages it slid
                        # past back a piece at a time; what the decode
                        # still needs of the reservation comes now
                        cache.ensure(r, j["reserve"])
                        if traced:
                            span(rows[r], "window_release",
                                 (wgroup.release_s - j["w_s0"]) * 1e3)
                    if pc is not None:
                        # freshly committed full prompt pages join
                        # the tree NOW, donor still live — the next
                        # identical admission maps them even while
                        # this row decodes; the node the snapshot
                        # belongs to takes its slot over
                        ins = pc.insert(ids, cache, r, j["tenant"],
                                        **({"state": snap} if snap
                                           else {}))
                        if snap and pc.holds_snapshot(snap[0]):
                            self.stats.state_snapshots += 1
                        if ins and j["tenant"]:
                            self.tenants.bump(
                                j["tenant"], "prefix_cached_pages", ins)
                    # a model with two audit lanes (engine/audit.py)
                    # keeps one for each way a prompt is served
                    lane = m.audit_lane(match, len(ids) - match,
                                        rows[r]["remaining"]
                                        / max(self.max_new, 1)) \
                        if self.audit is not None \
                        and self.audit.lanes > 1 else 0
                    if self.audit is not None and self.audit.wants(lane):
                        if firsts is not None and on_host is None:
                            on_host = np.asarray(logits)
                        rows[r]["audit"] = self.audit.open(
                            j["key"], ids, match,
                            logits if firsts is None else on_host[i],
                            lane)
                        m.audit_seat(lane, r)
                    if firsts is not None:
                        t = int(firsts[i])
                    else:
                        # splint: ignore[SPL201] reason=the documented host "sample" stage (CONT_INFER_STAGES): one scalar draw per JOIN so the row's first token emits before the next chunk, not per decode step
                        t = int(m.sample(logits))
                if traced:
                    span(rows[r], "join", join_ms)
                    span(rows[r], "sample",
                         (time.perf_counter() - tb) * 1e3)
                with tracer.span("infer.emit", leaf=True):
                    emit(r, t)
                if rows[r] is not None:
                    fresh[r] = t      # host-side token: next dispatch
                                      # reads it over the device carry

        def emit(r: int, t: int) -> None:
            """One sampled token for row r: eos / flush / budget."""
            row = rows[r]
            if row.get("audit") is not None:
                row["audit"].tokens.append(t)
            if t == tok_izer.eos_id:
                finish(r)
                return
            row["pending"] += tok_izer.token_to_piece(t)
            row["n_tok"] += 1
            row["remaining"] -= 1
            boundary = row["pending"].endswith((b" ", b"\n", b"\t"))
            if boundary or row["n_tok"] % self.flush_tokens == 0:
                tf = time.perf_counter()
                res = self._flush(row["key"], row["pending"])
                if tracer.enabled:
                    span(row, "flush",
                         (time.perf_counter() - tf) * 1e3)
                row["pending"] = b""
                if res != "ok":
                    finish(r, truncated=res == "full",
                           vanished=res == "gone")
                    return
            if row["remaining"] <= 0:
                finish(r)

        def finish(r: int, truncated: bool = False,
                   vanished: bool = False) -> None:
            row = rows[r]
            if row.get("audit") is not None:
                self.audit.close(row["audit"])
                m.audit_seat(row["audit"].lane, -1)
            if row["pending"] and not truncated and not vanished:
                res = self._flush(row["key"], row["pending"])
                truncated = res == "full"
                vanished = res == "gone"
            stages = None
            if row.get("spans"):
                stages = {}
                for name, ms in row["spans"]:
                    stages[name] = stages.get(name, 0.0) + ms
            self._finalize(row["key"], row["t0"], row["n_tok"],
                           truncated, vanished, stages=stages)
            self.stats.answers_finished += 1
            self.stats.answer_tokens += row["n_tok"]
            if row.get("stamp") is not None \
                    and row.get("spans") is not None:
                tid, ts = row["stamp"]
                wall = ((time.time() - ts) * 1e3 if ts > 0 else
                        (time.perf_counter() - row["wall0"]) * 1e3)
                self.recorder.record(tid, row["key"], wall,
                                     row["spans"])
            self._lane_row_done(row)  # decode lane: retire the
            cache.free_row(r)         # handoff record + wire pages
            rows[r] = None            # pages back to the pool NOW
            fresh[r] = -1

        def kill_expired() -> int:
            """Mid-decode deadline aborts (PR 10's standing debt):
            at each chunk edge, a live row whose deadline passed is
            retired with the typed DEADLINE_EXPIRED record, its pages
            freed immediately (refcount-aware — shared prefix pages
            just drop one reference), and its batch slot reopened.
            An expired row must stop consuming pool and slots NOW —
            lagged in-flight chunks are serial-guarded, so their
            tokens for the dead row evaporate."""
            now_wall = time.time()
            n = 0
            for r in range(B):
                row = rows[r]
                if row is None or not row.get("deadline") \
                        or row["deadline"] > now_wall:
                    continue
                key = row["key"]
                span_rec = self._live_spans.pop(key, None)
                try:
                    st.label_clear(key, P.LBL_SERVICING
                                   | P.LBL_DECODE_READY)
                    st.set(key, P.DEADLINE_EXPIRED_DIAGNOSTIC)
                    st.label_or(key, P.LBL_READY)
                    st.bump(key)
                except (KeyError, OSError):
                    pass
                self.spans.commit(span_rec, status=P.ERR_DEADLINE)
                if row.get("audit") is not None:
                    self.audit.drop(row["audit"].lane)
                    m.audit_seat(row["audit"].lane, -1)
                self._lane_row_done(row)
                cache.free_row(r)     # pool pages back NOW
                rows[r] = None
                fresh[r] = -1
                self.stats.killed_mid_decode += 1
                self.stats.deadline_expired += 1
                if row.get("tenant"):
                    self.tenants.bump(row["tenant"],
                                      "deadline_expired")
                n += 1
            return n

        def collect(entry) -> None:
            """Resolve one in-flight chunk: force the block (the one
            device->host transfer per chunk) and emit its columns to
            the rows that were live at ITS dispatch — serial-guarded,
            so tokens for a finished-and-re-seated row are discarded,
            never delivered to the newcomer."""
            pend, live = entry
            tc0 = time.perf_counter()
            with tracer.annotation("infer.collect"):
                blk = pend.block()
            # pool-occupancy high-water: chunk edges see the peak
            # (prefills landed, nothing freed yet) — heartbeats alone
            # would miss short bursts
            used = cache.used_pages
            if used > self._pages_used_peak:
                self._pages_used_peak = used
            if tracer.enabled:
                # collect = the host's blocked wait on the chunk; the
                # decode span now measures only the (async) dispatch
                ms = (time.perf_counter() - tc0) * 1e3
                tracer.record("infer.collect", ms)
                for r, ser in live:
                    row = rows[r]
                    if row is not None and row["serial"] == ser \
                            and row.get("spans") is not None:
                        row["spans"].append(["collect", round(ms, 3)])
            # emit: one span a CHUNK for the host work behind its
            # tokens (pieces, streaming appends, finalize, pages freed)
            with tracer.span("infer.emit", leaf=True):
                slots = getattr(pend, "slots", None)
                if slots is not None:
                    # fetched with the step's tokens: block() above was
                    # the wait, this copies (count,) integers
                    slots = np.asarray(slots)
                    # splint: ignore[SPL201] reason=a NumPy sum of the counts copied above, after the chunk's own block(); no device scalar is fetched
                    self.stats.expert_slots += int(slots.sum())
                    self._expert_totals = slots.astype(np.int64) + (
                        0 if self._expert_totals is None
                        else self._expert_totals)
                counts = getattr(pend, "counts", None)
                if counts is not None:
                    # live experts and selections the router's bias
                    # changed, fetched like the slots
                    self._moe_counts = np.asarray(counts, np.int64) + (
                        0 if self._moe_counts is None
                        else self._moe_counts)
                for c in range(pend.n):
                    for r, ser in live:
                        row = rows[r]
                        if row is not None and row["serial"] == ser:
                            if row.get("audit") is not None:
                                # the logits this step sampled from
                                # stay on the device; the record
                                # fetches them when the row finishes
                                row["audit"].steps.append(
                                    (pend.audit, c))
                            emit(r, int(blk[r, c]))

        def chunk_round() -> None:
            """One chunk round, the `infer.chunk` span of a pass with
            rows live: deadline kills and the edge scan (its own
            bookkeeping), then the leaves decode (the next chunk's
            dispatch), rebid, and collect + emit of the oldest chunk
            once inflight_depth are un-awaited."""
            nonlocal carry, rebid_due
            kill_expired()    # chunk-edge deadline aborts

            # per-row edges: a row without window room for the
            # next chunk, or whose whole token budget is
            # already in flight, must not be dispatched again.
            # Its final tokens are still in the window —
            # collect oldest-first until the edge rows have
            # finished (budget-exhausted rows self-finish the
            # moment their last tokens emit, so the common
            # end-of-request edge drains only the entries that
            # carry those tokens, preserving the overlap for
            # the rest of the batch), then force any survivor
            # (a true window-edge row) closed
            edge = [r for r in range(B) if rows[r] is not None
                    and (int(cache.lengths[r]) + step
                         > cfg.max_len
                         or rows[r]["disp_left"] <= 0)]
            if edge:
                while window and any(rows[r] is not None
                                     for r in edge):
                    collect(window.popleft())
                for r in edge:
                    if rows[r] is not None:
                        finish(r)
            if all(r is None for r in rows):
                return

            td = time.perf_counter()
            with tracer.annotation("infer.decode"):
                if getattr(m, "mesh", None) is not None:
                    # pod-sharded lane (ShardedCompletionModel): the
                    # dispatch gets its own fault site so the chaos
                    # matrix can crash/raise inside a sharded decode
                    # specifically (operations.md catalog)
                    fault("completer.sharded_dispatch")
                wgroup = cache.window
                w_s0, w_n0 = (wgroup.release_s, wgroup.released) \
                    if wgroup is not None else (0.0, 0)
                pend = m.paged_decode_chunk_async(
                    cache, fresh, step, carry=carry)
            if wgroup is not None:
                # the chunk's rows slid: what they gave back
                self.stats.window_decode_slides += \
                    wgroup.released - w_n0
                if tracer.enabled:
                    tracer.record("infer.window_release",
                                  (wgroup.release_s - w_s0) * 1e3)
            live = [(r, rows[r]["serial"]) for r in range(B)
                    if rows[r] is not None]
            if tracer.enabled:
                # decode = the async dispatch (host-side);
                # the blocked wait surfaces as the collect
                # span when the window forces the chunk.  One
                # chunk = one histogram sample, whatever the
                # occupancy — per-row recording would make
                # decode quantiles occupancy-weighted, unlike
                # every other stage; traced rows still each
                # get the shared span in their event list
                ms = (time.perf_counter() - td) * 1e3
                tracer.record("infer.decode", ms)
                for r, _ in live:
                    if rows[r].get("spans") is not None:
                        rows[r]["spans"].append(
                            ["decode", round(ms, 3)])
            carry = pend.last
            fresh[:] = -1
            self.stats.decode_steps += step
            self.stats.decode_rows += step * len(live)
            for r, _ in live:
                rows[r]["disp_left"] -= step
            window.append((pend, live))
            self.stats.inflight_peak = max(
                self.stats.inflight_peak, len(window))
            rebid_due += step
            if self.rebid_tokens \
                    and rebid_due >= self.rebid_tokens:
                rebid_due = 0
                with tracer.span("infer.rebid", leaf=True):
                    self._rebid()
            # K-deep window: collect the oldest chunk only
            # once inflight_depth are un-awaited — its emit/
            # flush host work overlaps the newest chunk's
            # device compute, so the per-chunk dispatch floor
            # amortizes instead of serializing
            while len(window) >= self.inflight_depth:
                collect(window.popleft())

        def abort_all(reason: str) -> None:
            """Model failure must not wedge WAITING/SERVICING (the
            invariant process_key/process_batch keep): every live row
            finalizes with what it already streamed and the pool
            starts clean."""
            nonlocal cache, carry
            self._debug(f"continuous batch aborted: {reason}")
            # in-flight chunks may be poisoned by the same failure:
            # drop them (rows finalize with what they streamed)
            window.clear()
            carry = None
            fresh[:] = -1
            for r in range(B):
                if rows[r] is not None:
                    finish(r)
            # the failure may have escaped a DONATING program (commit
            # scatter / decode chunk) after it consumed the device
            # pools but before the reassignment — reusing them would
            # raise "buffer donated" on every admission forever.
            # Rebuild the pool outright: the dense path's
            # reset()-then-fresh-cache recovery, paged edition.
            self._paged_cache = None
            cache = self._ensure_paged_cache()
            bp_memo.clear()

        try:
            while self._running:
                now = time.monotonic()
                if deadline and now > deadline:
                    break
                with tracer.span("infer.loop"):
                    if now >= next_beat:
                        next_beat = now + 2.0
                        self._beat()

                    try:
                        if all(r is None for r in rows):
                            # nothing live: retire any in-flight chunks
                            # (their rows finished — serial guards drop
                            # every column) and reset the device carry
                            while window:
                                collect(window.popleft())
                            carry = None
                            if self._model is not m:
                                # demotion decided mid-run: adopt the
                                # target model at this idle point (no live
                                # rows, no in-flight chunks — the paired
                                # spec pools retire with their wrapper and
                                # a fresh pool serves the plain model)
                                m = self._model
                                self._paged_cache = None
                                cache = self._ensure_paged_cache()
                                bp_memo.clear()
                                self._debug(
                                    "continuous lane adopted the demoted "
                                    "(plain) model")
                            if admit() == 0:
                                if self.replica \
                                        and self.stripes.poll_retired():
                                    # scale-down drain: stripes closed,
                                    # nothing live, window drained — exit
                                    # cleanly and let the supervisor reap
                                    self._debug(
                                        "replica destriped — retiring")
                                    break
                                with tracer.span("infer.idle",
                                                 leaf=True):
                                    got = st.signal_wait(
                                        self.group, last,
                                        timeout_ms=idle_timeout_ms)
                                if got is not None:
                                    last = got
                                    self.stats.wakes += 1
                            continue

                        if any(r is None for r in rows):
                            admit()       # joiners enter at ANY time —
                            # even with chunks in flight: the serial guard
                            # keeps lagged collects out of re-seated rows

                        with tracer.span("infer.chunk"):
                            chunk_round()
                    except Exception as ex:
                        abort_all(str(ex))
        finally:
            # stop()/stop_after mid-batch: never strand keys in
            # SERVICING; the pool is reusable for the next run.
            # In-flight tokens are delivered first — a stopped stream
            # keeps everything that was already decoded.
            try:
                while window:
                    collect(window.popleft())
            except Exception:
                pass              # poisoned futures: keep what landed
            for r in range(B):
                if rows[r] is not None:
                    finish(r)
            cache.reset()
            if self.prefix_cache is not None:
                # a stopped lane returns the WHOLE pool: cached pages
                # are a warm-serving optimization, not a shutdown
                # liability (the zero-leaked-pages contract).  With
                # the tier bound, every reclaimed page DEMOTES to
                # host RAM first — this is demote-on-retire, and the
                # forced checkpoint below persists the full warm set
                # so the replacement generation attaches warm
                self.prefix_cache.reclaim(cache.n_blocks)
            self._tier_checkpoint(force=True)

    def _tier_checkpoint(self, force: bool = False) -> None:
        """Snapshot radix index + host-tier pages into the persistent
        segment (kv_tier.TierPersist.save: payload under the NEW
        epoch first, index record last, old epoch swept after — a
        torn write leaves the previous snapshot authoritative).
        Replica 0 owns the writes; peers only load.  Beat-cadence
        calls are dirty-gated and rate-limited; force is the retire
        path, where the warm set must land before the process exits."""
        if (self._tier_store is None or self.kv_tier is None
                or self.prefix_cache is None or self.replica != 0):
            return
        now = time.monotonic()
        if not force and (not self.kv_tier.dirty
                          or now - self._tier_last_save < 5.0):
            return
        self._tier_last_save = now
        from .kv_tier import tier_geometry
        try:
            self._tier_store.save(
                self.prefix_cache, self.kv_tier,
                tier_geometry(self._model, self._paged_cache))
        except Exception as ex:
            self._debug(f"tier checkpoint failed: {ex}")

    # -- drain loop --------------------------------------------------------

    def run_once(self) -> int:
        """Enumerate waiting keys and service them (cold-start drain and
        per-wake drain are the same sweep, splainference.cpp:541-551).
        With a model backend, waiting keys are served in batches of
        batch_cap through one left-padded decode each; a custom
        generate_fn serves serially (its contract is one prompt)."""
        st = self.store
        self.stripes.refresh()        # a re-stripe lands HERE, at the
        idxs = [i for i in st.enumerate_indices(P.LBL_INFER_REQ)
                if self.stripes.owns(int(i))]   # drain boundary
        if not idxs:
            self._had_deferred = False    # nothing waiting: the
            return 0                      # redrain loop must end
        # multi-tenant admission: fair order across tenants, expired
        # deadlines rejected fast, backlog past high water shed with
        # the typed overloaded record.  With a high-water mark set,
        # one drain also bounds its own work to the mark (deferred
        # rows stay WAITING; run()'s work-conserving re-drain takes
        # them next, in fair slices)
        cap = (len(idxs) if self.qos.high_water is None
               else min(len(idxs), max(1, self.qos.high_water)))
        idxs = self._admit_waiting(idxs, cap)
        if not idxs:
            return 0
        if self._bid >= 0:
            try:
                st.shard_rebid(self._bid)
                st.madvise(self._bid, N.ADV_WILLNEED, timeout_ms=0)
            except OSError:
                pass
        n = 0
        batched = getattr(self, "_model", None) is not None \
            and self.generate_fn == self._model_generate \
            and self.batch_cap > 1 \
            and hasattr(self._model, "prefill_batch") \
            and self._batched_budget() is not None
        # per-key/per-batch exception firewall: generation failures are
        # already contained inside process_key/process_batch, so
        # anything raising through is a protocol/store-level surprise —
        # it must cost ITS keys (any left SERVICING are flipped back to
        # WAITING for the next sweep), never the drain's siblings or
        # the run loop itself
        if batched:
            for lo in range(0, len(idxs), self.batch_cap):
                batch = idxs[lo: lo + self.batch_cap]
                try:
                    n += self.process_batch(batch)
                except Exception as ex:
                    self.stats.faults += 1
                    self._debug(f"batch drain failed: {ex}")
                    self._requeue_failed(batch)
        else:
            for idx in idxs:
                self._rebid()
                try:
                    if self.process_key(idx):
                        n += 1
                except Exception as ex:
                    self.stats.faults += 1
                    self._debug(f"request at slot {idx} failed: {ex}")
                    self._requeue_failed([idx])
        if n:
            self._maybe_demote_spec()
        self.spans.flush()            # oneshot drains land their
        return n                      # spans; run() uses heartbeats

    # -- speculative degradation ------------------------------------------

    def _spec_acceptance(self) -> float | None:
        """The live speculative acceptance rate, or None when the
        model isn't speculative (including after a demotion — the
        rolling rate that triggered it survives in
        _spec_acceptance_rolling for the heartbeat)."""
        m = getattr(self, "_model", None)
        if m is None or not hasattr(m, "acceptance_rate"):
            return None
        try:
            return float(m.acceptance_rate)
        except Exception:
            return None

    def _maybe_demote_spec(self) -> None:
        """Speculative decode graceful degradation: r05 measured 6.0
        tok/s at acceptance=0.05 — a draft that the target rejects is
        strictly WORSE than plain decode (every rejected proposal cost
        a draft forward and bought nothing).  Track a rolling
        acceptance over the recent drains; when it stays under
        spec_min_acceptance with enough proposals behind it, swap the
        model for its own target and decode plain for the rest of the
        run (spec_demotions counts it; 0 disables the floor)."""
        m = getattr(self, "_model", None)
        if (m is None or self.spec_min_acceptance <= 0
                or not hasattr(m, "acceptance_rate")
                or not hasattr(m, "target")):
            return
        if not self._spec_hist:
            self._spec_hist.append((0, 0))
        self._spec_hist.append((m.stats_proposed, m.stats_accepted))
        if len(self._spec_hist) > 8:
            self._spec_hist.pop(0)
        p0, a0 = self._spec_hist[0]
        dp = m.stats_proposed - p0
        da = m.stats_accepted - a0
        if dp < 32:
            return                    # not enough evidence yet
        rate = da / dp
        self._spec_acceptance_rolling = rate
        if rate < self.spec_min_acceptance:
            self.stats.spec_demotions += 1
            self._debug(
                f"speculative acceptance {rate:.3f} < floor "
                f"{self.spec_min_acceptance}: demoting to plain "
                "decode (target model) for the rest of the run")
            self._model = m.target

    def _pool_shard_occupancy(self, tp: int) -> dict:
        """Per-tp-shard view of the paged pool, MEASURED from the
        placed device buffers (not assumed from the host scheduler):
        each key is the tp position a shard's kv-head slice covers,
        `shard_mb` its actual on-device pool bytes (k+v, all layers).
        Page counts are host-global (every shard backs every page at
        1/tp of its bytes) — the bytes are the placement signal: a
        broken placement collapses the key set (a replicated pool
        covers the full kv-head range -> one key) or inflates
        shard_mb, so the dashboard shows it instead of rendering a
        fabricated uniform number."""
        cache = self._paged_cache
        out: dict = {}
        try:
            arr = cache.k_pools[0]
            kh = arr.shape[1]
            per_shard = max(1, kh // tp)
            layers = len(cache.k_pools)

            def positions(a) -> dict[str, int]:
                seen: dict[str, int] = {}
                for sh in a.addressable_shards:
                    sl = (sh.index[1] if len(sh.index) > 1
                          else slice(None))
                    start = sl.start or 0
                    pos = str(start // per_shard)
                    # replicas (the dp axis) carry identical bytes:
                    # keep one measurement per tp position
                    seen.setdefault(pos, sh.data.nbytes)
                return seen

            seen = positions(arr)
            sseen: dict[str, int] = {}
            if getattr(cache, "quantized", False):
                # int8 pools: the per-page scales shard on the same
                # kv-head axis — their bytes belong to the shard too
                sseen = positions(cache.k_scales[0])
            for pos, nbytes in sorted(seen.items()):
                out[pos] = {
                    "free": cache.free_pages,
                    "used": cache.used_pages,
                    "shard_mb": round(
                        (nbytes + sseen.get(pos, 0)) * 2 * layers
                        / 1e6, 3),
                }
        except Exception:
            return {}            # obs must never take the lane down
        return out

    def publish_stats(self) -> None:
        """Heartbeat: JSON stats snapshot into the debug-labeled
        __completer_stats key (the structured counterpart of the
        reference's __debug chatter; sidecar group-63 watch surfaces
        it).  SPTPU_TRACE=1 adds histogram-sourced INFER_STAGES
        quantiles, recorder accounting, and the slow log."""
        self.spans.flush()            # heartbeat cadence, off the
        payload = dataclasses.asdict(self.stats)      # wake path
        payload["spans_obs"] = self.spans.counters()
        payload["generation"] = self.generation
        if self.replica or self.stripes.epoch:
            payload["replica"] = self.replica
            payload["stripe"] = self.stripes.snapshot()
        if not self.stats.killed_mid_decode \
                and self._paged_cache is None:
            payload.pop("killed_mid_decode", None)  # dense lane:
                                                    # dead gauge
        # decode-overlap gauge: inflight_peak pinned here means the
        # chunk window saturates (sptpu_completer_inflight_depth)
        payload["inflight_depth"] = self.inflight_depth
        # join-backpressure memo occupancy: growth here with flat
        # admissions means denied keys are piling up (the sweep
        # bounds it, but the gauge shows the pressure)
        payload["bp_memo"] = len(self._bp_memo)
        if self.qos.high_water is not None:
            payload["qos"] = {
                "queue_high_water": self.qos.high_water,
                "retry_after_ms": self.qos.retry_after_ms}
        tenants = self.tenants.snapshot()
        if tenants:
            # per-tenant admitted/shed/deadline_expired/served_tokens
            # — `spt metrics` renders one labeled series per tenant
            payload["tenants"] = tenants
        prune_idle_counters(
            payload, bool(self.qos.high_water is not None or tenants))
        if not self.stats.budgeted_requests:
            # no request ever stamped a budget of its own: the
            # heartbeat stays as it was (tokens / completions say the
            # same of answers that all run to the daemon's budget)
            for k in ("budgeted_requests", "answer_tokens",
                      "answers_finished"):
                payload.pop(k, None)
        if not self._bp_memo and self._paged_cache is None:
            payload.pop("bp_memo", None)  # dense lane: dead gauge
        acc = self._spec_acceptance()
        if acc is not None:
            # sptpu_completer_spec_acceptance in `spt metrics`
            payload["spec_acceptance"] = round(acc, 4)
        elif self._spec_acceptance_rolling is not None:
            # demoted: keep the rolling rate that tripped the floor
            payload["spec_acceptance"] = round(
                self._spec_acceptance_rolling, 4)
        mesh = getattr(getattr(self, "_model", None), "mesh", None)
        if mesh is not None:
            # pod-sharded lane: the tensor-parallel degree rides the
            # heartbeat (sptpu_completer_tp) so dashboards can tell a
            # sharded daemon from a single-chip one at a glance
            payload["tp"] = int(mesh.shape.get("tp", 1))
        m_now = getattr(self, "_model", None)
        if hasattr(m_now, "stats_proposed"):
            # speculative draft/verify token counters
            # (sptpu_completer_spec_* in `spt metrics`): drafted =
            # proposals the draft generated, verified = positions the
            # target scored, accepted = proposals the target kept
            payload["spec_draft_tokens"] = int(m_now.stats_proposed)
            payload["spec_accepted_tokens"] = int(m_now.stats_accepted)
            payload["spec_verified_tokens"] = int(
                getattr(m_now, "stats_verified", 0))
        if self._paged_cache is not None:
            # sptpu_completer_pages_{free,used} pool gauges
            payload["pages_free"] = self._paged_cache.free_pages
            payload["pages_used"] = self._paged_cache.used_pages
            payload["live_tokens"] = self._paged_cache.live_tokens()
            if self._paged_cache.used_pages > self._pages_used_peak:
                self._pages_used_peak = self._paged_cache.used_pages
            payload["pages_used_peak"] = self._pages_used_peak
            if hasattr(getattr(m_now, "cfg", None), "kv_lora_rank"):
                # a family with latent pages: table pages a grid step
                # of its decode kernel attends — turns the kernel's
                # seconds in a reduced trace into seconds a grid step
                from ..ops.latent_attention import pages_per_step
                payload["latent_decode_pages_per_step"] = \
                    pages_per_step(1, self._paged_cache.page)
        if getattr(self._paged_cache, "needs_state", False):
            # state slots (live rows + snapshots) of a model with
            # recurrent state, beside the page gauges above
            payload["state_slots_used"] = \
                self._paged_cache.state_slots_used
            payload["state_slots"] = self._paged_cache.state_slots - 1
            payload["state_evictions"] = (
                self.prefix_cache.stats.state_evictions
                if self.prefix_cache is not None else 0)
        else:
            for k in ("state_restores", "state_snapshots",
                      "state_cut_tokens"):
                payload.pop(k, None)  # no state: dead gauges
        wgroup = getattr(self._paged_cache, "window", None)
        if wgroup is not None:
            # the window page group beside the global one: pages rows
            # gave back as they slid, pages some row maps in either
            # group at this beat, the window pool's size
            payload["window_pages_released"] = wgroup.released
            payload["window_pages_live"] = wgroup.live_pages
            payload["window_pages_used"] = wgroup.used_pages
            payload["window_pages_used_peak"] = wgroup.used_peak
            payload["window_pool_pages"] = wgroup.n_blocks - 1
            payload["global_pages_live"] = int(
                (self._paged_cache.refcounts > 0).sum())
            if self.prefix_cache is not None:
                payload["window_evictions"] = \
                    self.prefix_cache.stats.window_evictions
        else:
            for k in ("window_resumes", "window_cut_tokens",
                      "window_tail_shares", "window_decode_slides"):
                payload.pop(k, None)  # one page group: dead gauges
        # live keys the grouped-query kernels were asked for, where the
        # model counts them
        payload.update(getattr(m_now, "attn_work", {}))
        pc = self.prefix_cache
        if pc is not None:
            # prefix-cache gauges (sptpu_completer_prefix_* in `spt
            # metrics`; the telemetry lane rings prefix_hits and
            # prefix_shared_pages, `spt top` sparklines them)
            s = pc.stats
            payload["prefix_hits"] = s.hits
            payload["prefix_misses"] = s.misses
            payload["prefix_hit_tokens"] = s.hit_tokens
            payload["prefix_evictions"] = s.evictions
            payload["prefix_shared_pages"] = pc.shared_pages()
            payload["prefix_evictable"] = pc.evictable_count()
            payload["prefix_cow_copies"] = s.cow_copies
            payload["prefix_bytes_saved"] = s.bytes_saved
            for t, pages in pc.tenant_pages().items():
                # per-tenant cache residency beside the QoS ledger
                # counters — the quota-pressure incident view.
                # Untagged traffic (tenant 0) stays out: the tenants
                # section is for tagged deployments (its residency is
                # already prefix_shared_pages), and the convention is
                # that untagged traffic never creates the section
                if t:
                    tenants.setdefault(
                        str(t), {})["prefix_pages"] = pages
            if tenants and "tenants" not in payload:
                payload["tenants"] = tenants
        if self.kv_tier is not None:
            # tiered-KV gauges (sptpu_completer_tier_* in `spt
            # metrics`): occupancy the autoscaler weighs against HBM
            # pages, readmit-rate the runbook triages warm serving
            # by, and the restore verdict (`tier_restored` pages +
            # typed `tier_restore_reason` on a cold fallback) that
            # tells an operator whether a restart attached warm
            tier = self.kv_tier
            payload["tier_pages"] = len(tier)
            payload["tier_mb"] = round(tier.bytes_held() / 2**20, 3)
            payload["tier_spills"] = tier.spills
            payload["tier_spill_failures"] = tier.spill_failures
            payload["tier_demotions"] = tier.demotions
            payload["tier_readmits"] = tier.readmits
            payload["tier_readmit_failures"] = tier.readmit_failures
            payload["tier_capacity_drops"] = tier.capacity_drops
            payload["tier_restored"] = self._tier_restore[0]
            if self._tier_restore[1] not in ("", "off"):
                payload["tier_restore_reason"] = self._tier_restore[1]
            if pc is not None:
                payload["tier_demoted"] = pc.demoted_pages()
            if self._tier_store is not None:
                payload["tier_snapshot_epoch"] = \
                    self._tier_store.epoch
        if self._paged_cache is not None:
            # the pool's storage dtype + bytes MEASURED from the
            # placed device buffers (values + scales): `spt metrics`
            # renders sptpu_completer_kv_pool_info{kv_dtype=...} and
            # sptpu_completer_pool_mb — the honest int8-halves-bytes
            # evidence, not a shape*itemsize estimate
            kvd = getattr(self._paged_cache, "kv_dtype", None)
            if kvd:
                payload["kv_dtype"] = kvd
            try:
                payload["pool_mb"] = self._paged_cache.device_mb()
                if payload["pool_mb"] > self._pool_mb_peak:
                    self._pool_mb_peak = payload["pool_mb"]
                # HBM high-water across pool swaps (abort recovery
                # re-allocates; a restart resets with the generation)
                payload["pool_mb_peak"] = round(self._pool_mb_peak, 3)
            except Exception:
                pass
            if mesh is not None and int(mesh.shape.get("tp", 1)) > 1:
                shards = self._pool_shard_occupancy(
                    int(mesh.shape["tp"]))
                if shards:
                    payload["pages_shard"] = shards
        if self._expert_totals is not None:
            payload["expert_totals"] = [int(x) for x in
                                        self._expert_totals]
        if self._moe_counts is not None:
            payload["experts_live"] = int(self._moe_counts[0])
            payload["router_bias_swaps"] = int(self._moe_counts[1])
        if self.startup_ms:
            payload["startup_ms"] = {
                **{k: round(v, 1) for k, v in self.startup_ms.items()},
                "total": round(sum(self.startup_ms.values()), 1)}
        if self.audit is not None:
            payload["audit_records"] = self.audit.written
        if faults.armed():
            payload["faults"] = faults.stats()
        payload["compile_events"] = DEVTIME.compile_events(self.LANE)
        devtime = DEVTIME.heartbeat_section(self.LANE)
        if self.LANE != "completer":
            # split lanes: the trunk + sampler programs register under
            # the canonical "completer" devtime lane — their compiles
            # and quantiles belong to this daemon's heartbeat too
            payload["compile_events"] += \
                DEVTIME.compile_events("completer")
            devtime.update(DEVTIME.heartbeat_section("completer"))
        if devtime:
            payload["devtime"] = devtime
        self._lane_payload(payload)
        DEVTIME.flush(self.store)
        if tracer.enabled:
            P.attach_trace_sections(payload, tracer, self.recorder,
                                    "infer.")
        P.publish_heartbeat(self.store, self._hb_key, payload)
        if tracer.enabled:
            self._trace_published = P.maybe_publish_trace_ring(
                self.store, self._trace_key, self.recorder,
                self._trace_published)

    def run(self, *, idle_timeout_ms: int = 100,
            stop_after: float | None = None) -> None:
        self._running = True
        last = self.store.signal_count(self.group)
        deadline = (time.monotonic() + stop_after) if stop_after else None
        next_sweep = time.monotonic() + 2.0
        self.publish_stats()          # the attach-complete signal
        self.run_once()               # cold start
        while self._running:
            got = self.store.signal_wait(self.group, last,
                                         timeout_ms=idle_timeout_ms)
            now = time.monotonic()
            # heartbeat cadence is independent of the wake path — a
            # daemon at full load must still look alive to watchers
            do_sweep = now >= next_sweep
            if do_sweep:
                next_sweep = now + 2.0
            # loop-level firewall (run_once already contains per-key
            # failures; this catches gather/store-level surprises)
            try:
                if got is not None:
                    last = got
                    self.stats.wakes += 1
                    self.run_once()
                    # work-conserving under a high-water drain bound:
                    # deferred WAITING rows re-drain immediately in
                    # fair slices instead of waiting out the sweep
                    redrains = 0
                    while self._had_deferred and self._running \
                            and redrains < 256:
                        redrains += 1
                        self.run_once()
                elif do_sweep:
                    self.run_once()
                if do_sweep:
                    self._sweep_bp_memo()
                    self.publish_stats()
                    if self.replica and self.stripes.poll_retired():
                        # scale-down drain: the drains above finished
                        # in-flight work; exit and let the supervisor
                        # reap us
                        log.info("replica %d destriped — retiring",
                                 self.replica)
                        break
            except Exception as ex:
                self.stats.faults += 1
                log.exception("run loop cycle failed; continuing")
                self._debug(f"run loop cycle failed: {ex}")
            if deadline and now > deadline:
                break

    def stop(self) -> None:
        self._running = False


def refuse_for_model(args, model_cls) -> None:
    """Stop main() with ONE typed message when an option is set that
    `model_cls` declares it cannot serve (its `refused_options`: name
    -> reason) — before any weight is made or request read, never by
    falling through to another cache or a wrong page layout."""
    asked = {
        "kv_dtype": args.kv_dtype in ("int8", "int4")
        and f"--kv-dtype {args.kv_dtype}",
        "kv_tier_pages": (args.kv_tier_pages or args.kv_tier_persist)
        and "--kv-tier-pages/--kv-tier-persist",
        "phase": args.phase != "unified" and f"--phase {args.phase}",
        "tp": args.tp > 1 and f"--tp {args.tp}",
        "ep": args.ep > 1 and f"--ep {args.ep}",
        "draft": (args.draft_layers or args.draft_weights)
        and "--draft-layers/--draft-weights",
        "weights": args.weights and f"--weights {args.weights}",
        "weight_quant": (args.quantized or args.weights_int8)
        and "--quantized/--weights-int8",
    }
    reasons = getattr(model_cls, "refused_options", {})
    for name, flag in asked.items():
        if flag and name in reasons:
            raise SystemExit(
                f"unsupported_option: {flag} cannot be served with "
                f"--model ({model_cls.__name__}): {reasons[name]}")
    if not (args.continuous or args.phase != "unified"):
        raise SystemExit(
            "unsupported_option: --model serves the paged lane only; "
            "add --continuous")


def main(argv: list[str] | None = None) -> int:
    """CLI entry: python -m libsplinter_tpu.engine.completer --store NAME"""
    import argparse

    ap = argparse.ArgumentParser(
        description="splinter-tpu completion daemon (streaming JAX "
                    "decoder over the store's label protocol)")
    ap.add_argument("--store", required=True)
    ap.add_argument("--persistent", action="store_true")
    ap.add_argument("--oneshot", action="store_true")
    ap.add_argument("--max-new-tokens", type=int, default=256)
    ap.add_argument("--template", default="auto",
                    help="chat template: auto (fingerprint the GGUF's "
                         "tokenizer.chat_template), chatml, llama2, "
                         "llama3, or none (bare system\\n\\nprompt)")
    ap.add_argument("--temp", type=float, default=0.7)
    ap.add_argument("--top-p", type=float, default=0.9)
    ap.add_argument("--idle-timeout-ms", type=int, default=100)
    ap.add_argument("--replica", type=int, default=0,
                    help="striped replica index (elastic lanes): "
                         "drain only the stripes the lane's stripe "
                         "map assigns this replica; heartbeat "
                         "publishes replica-suffixed "
                         "(__completer_stats.rN)")
    ap.add_argument("--model", default=None, metavar="FILE",
                    help="a model DESCRIPTION file (JSON): a public "
                         "architecture's config.json keys verbatim "
                         "under 'architecture' plus this chip's "
                         "'share' of the deployment (layers kept, "
                         "experts held, vocabulary slice) and 'seed' "
                         "— models/mla.load_model_description.  Serves "
                         "the latent-attention (MLA) + shared-expert "
                         "MoE block with seeded bfloat16 weights on "
                         "the --continuous lane, --n-ctx as its "
                         "window.  Refused with it, each with its "
                         "reason, before the first request: "
                         "--kv-dtype int8|int4, --kv-tier-pages, "
                         "--phase prefill|decode, --tp/--ep > 1, "
                         "--draft-layers/--draft-weights, --weights, "
                         "--quantized/--weights-int8")
    ap.add_argument("--audit-dir", default=None, metavar="DIR",
                    help="write audit records there (engine/audit.py): "
                         "for one --continuous admission in "
                         "--audit-every, the prompt ids, the ids "
                         "generated and the logits behind each of "
                         "them, for a plain reference to check.  "
                         "Needs a model whose decode chunks keep a "
                         "row's logits (--model)")
    ap.add_argument("--audit-every", type=int, default=16, metavar="N")
    ap.add_argument("--weights",
                    help="decoder checkpoint: .safetensors (HF llama "
                         "naming) or .gguf (llama.cpp naming; geometry "
                         "and tokenizer come from the GGUF metadata).  "
                         "The literal value 'int8' is a sentinel: no "
                         "checkpoint, seeded-random weights held "
                         "per-output-channel int8 (shorthand for "
                         "--weights-int8 with no path)")
    ap.add_argument("--n-ctx", type=int, default=None,
                    help="context window / KV-cache length override "
                         "(default: the checkpoint's trained window, or "
                         "2048 for seeded-random weights)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the decoder "
                         "(params + KV cache — incl. the paged block "
                         "pools with --continuous: kv-head-sharded "
                         "pools, shard_map'd ragged kernel) over a "
                         "tp-axis mesh of this many devices "
                         "(parallel.serve; must divide the model's "
                         "heads and kv_heads)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel degree for MoE checkpoints: "
                         "shard the stacked expert FFNs over an ep "
                         "mesh axis (must divide the model's "
                         "expert_count; composes with --tp)")
    ap.add_argument("--batch-cap", type=int, default=None,
                    help="serve up to this many waiting keys "
                         "concurrently (1 = serial, the reference's "
                         "cadence).  Default: 32 with --continuous "
                         "(the block-paged pool's HBM scales with "
                         "live tokens, so batch width no longer pays "
                         "for B x max_len padding), 8 otherwise (a "
                         "wider DENSE batch still multiplies "
                         "B x max_len cache HBM)")
    ap.add_argument("--page-size", type=int, default=128,
                    help="KV pool page size in tokens (continuous "
                         "serving)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="total pages in the paged KV pool (default: "
                         "batch-cap full windows — cap it lower to "
                         "spend cache HBM on batch width instead of "
                         "padding; admission backpressures when the "
                         "pool is full)")
    ap.add_argument("--kv-dtype", choices=("bf16", "f32", "int8", "int4"),
                    default=None,
                    help="paged KV pool storage dtype (continuous "
                         "serving; default: the model's native "
                         "activation dtype).  int8 stores the pool "
                         "quantized with per-page per-kv-head scales "
                         "— cache HBM per token halves vs bf16 "
                         "(quarters vs f32), the ragged paged-"
                         "attention kernel dequantizes in register, "
                         "and the freed bytes buy batch width "
                         "(--batch-cap) inside the same --pool-pages "
                         "envelope.  int4 packs two 4-bit codes per "
                         "byte under the same scale discipline — a "
                         "QUARTER of bf16's cache bytes, 4x the "
                         "batch in the same envelope, at a coarser "
                         "(documented) greedy-agreement tolerance")
    ap.add_argument("--inflight-depth", type=int, default=None,
                    help="continuous lane: paged decode chunk "
                         "pipeline depth — dispatch chunk K, collect "
                         "the oldest while the newest computes (the "
                         "inter-chunk token hand-off stays on-"
                         "device), so host emit/admit work overlaps "
                         "device compute.  Default 2; 1 restores the "
                         "collect-every-chunk sync cadence")
    ap.add_argument("--spec-min-acceptance", type=float, default=0.2,
                    help="speculative decoding floor: when the "
                         "rolling draft acceptance stays below this, "
                         "demote to plain decode for the rest of the "
                         "run (0 disables; the completer heartbeat "
                         "publishes sptpu_completer_spec_acceptance)")
    ap.add_argument("--quantized", action="store_true",
                    help="int8 weight residency: keep attention/MLP "
                         "kernels in HBM as Q8_0-geometry int8 + "
                         "per-block scales (models/quant.py; "
                         "dequantizes before the matmul)")
    ap.add_argument("--weights-int8", action="store_true",
                    help="PER-OUTPUT-CHANNEL int8 weight residency "
                         "(models/quant.py ChannelQuantDense): the "
                         "matmul runs on int8-resident kernels with "
                         "f32 accumulation and dequantizes on the MXU "
                         "OUTPUT — one multiply per output column, no "
                         "per-block float weight rebuild between HBM "
                         "and the MXU.  Mutually exclusive with "
                         "--quantized; '--weights int8' is shorthand "
                         "for this with seeded-random weights")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile prefill buckets + decode "
                         "programs before serving (first requests "
                         "otherwise pay the compiles; .xla_cache "
                         "persists them across restarts)")
    ap.add_argument("--draft-weights",
                    help="speculative decoding: a small draft .gguf "
                         "(same tokenizer family; geometry from its "
                         "metadata) proposes --gamma tokens per "
                         "target forward (models/speculative.py); "
                         "serial serving only")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="SELF-DRAFTING speculative decode: draft "
                         "with a truncated view of the target's own "
                         "first N layers (no second checkpoint; the "
                         "param subtree aliases the target's "
                         "weights).  Unlike --draft-weights this "
                         "serves the batched continuous lane too — "
                         "drafts verify through the paged kernel's "
                         "multi-query stack.  ~3/4 of the target's "
                         "depth is a good starting point")
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculative proposal length per verify step")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: requests join/leave the "
                         "live batch at chunk boundaries instead of "
                         "waiting for whole drains (run_continuous)")
    ap.add_argument("--phase", choices=("unified", "prefill", "decode"),
                    default="unified",
                    help="disaggregated serving (engine/disagg.py): "
                         "'prefill' runs only dense bucket prefill and "
                         "hands each committed row off at "
                         "DECODE_READY; 'decode' adopts handoffs at "
                         "chunk edges and runs only ragged paged "
                         "decode — its K-deep window is never stalled "
                         "by a joiner's prefill.  Both imply "
                         "--continuous.  Default: the unified daemon "
                         "that interleaves the two phases")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request prefix sharing on "
                         "the continuous lane (default on: shared "
                         "prompt prefixes map refcounted pool pages "
                         "into the joiner's block table instead of "
                         "re-prefilling — engine/prefix_cache.py; "
                         "the A/B knob scripts/prefix_hit_check.py "
                         "compares against)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    help="global cap on pool pages the prefix cache "
                         "may retain (default: unlimited — zero-ref "
                         "cached pages are reclaimed LRU-first "
                         "whenever the pool actually needs them)")
    ap.add_argument("--state-snapshots", type=int, default=None,
                    help="snapshot slots of a --model whose layers keep "
                         "recurrent state (models/kda.py): how many "
                         "page-boundary states the prefix cache may "
                         "hold for later prompts to resume from, one "
                         "row's state each, in device memory beside "
                         "the pages (default: one a batch row; 0: "
                         "every prompt prefills from its first token)")
    ap.add_argument("--window-pool-pages", type=int, default=None,
                    help="pages of the WINDOW page group of a --model "
                         "whose layers mix sliding-window and global "
                         "attention (models/afmoe.py): such a model "
                         "keeps two pools, --pool-pages counts the "
                         "global layers' and this the window layers', "
                         "whose pages go back as a row slides past "
                         "them (default: as many as --pool-pages)")
    ap.add_argument("--prefix-quota", default=None,
                    help="per-tenant prefix-cache page quotas, "
                         "TENANT:PAGES[,TENANT:PAGES...] (unlisted "
                         "tenants are unbounded; over-quota inserts "
                         "evict the tenant's own zero-ref pages "
                         "first, then skip)")
    ap.add_argument("--kv-tier-pages", type=int, default=0,
                    help="host-DRAM KV spill tier capacity in pool "
                         "pages (engine/kv_tier.py): evicted zero-ref "
                         "prefix pages demote to host RAM and readmit "
                         "via device_put + block-table write instead "
                         "of a re-prefill (default 0: off)")
    ap.add_argument("--kv-tier-persist", nargs="?", const="auto",
                    default=None,
                    help="checkpoint the radix index + host-tier "
                         "pages into a file-backed persistent store "
                         "segment so restarts and scale-up replicas "
                         "attach WARM (write-record-last, epoch-"
                         "bumped; torn snapshots fall back cold, "
                         "typed in heartbeat).  Optional value names "
                         "the segment; bare flag derives "
                         "<store>-kvtier.  Replica 0 writes, all "
                         "replicas load")
    ap.add_argument("--queue-high-water", type=int, default=None,
                    help="multi-tenant QoS: max waiting backlog — "
                         "overflow is claimed and READY-flipped with "
                         "a typed {\"err\": \"overloaded\", "
                         "\"retry_after_ms\": N} value instead of "
                         "queueing unboundedly (default: never shed)")
    ap.add_argument("--retry-after-ms", type=int, default=None,
                    help="retry hint carried by shed responses")
    ap.add_argument("--tenant-weights", default=None,
                    help="per-tenant fair-share weights, "
                         "TENANT:W[,TENANT:W...] (unlisted weigh 1)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    if args.model:
        # the description says which block family it is; what that
        # family's model cannot serve is refused before anything else
        from ..models.mla import (completion_model_class,
                                  load_model_description)
        model_cfg, model_seed = load_model_description(
            args.model, max_len=args.n_ctx)
        model_cls = completion_model_class(model_cfg)
        refuse_for_model(args, model_cls)
        if args.state_snapshots is not None \
                and not getattr(model_cls, "needs_state", False):
            raise SystemExit(
                "unsupported_option: --state-snapshots cannot be "
                f"served with --model ({model_cls.__name__}): its "
                "layers keep no recurrent state")
        if args.window_pool_pages is not None \
                and not getattr(model_cls, "needs_window", False):
            raise SystemExit(
                "unsupported_option: --window-pool-pages cannot be "
                f"served with --model ({model_cls.__name__}): its "
                "layers keep one page group")
    elif args.state_snapshots is not None:
        raise SystemExit(
            "unsupported_option: --state-snapshots is for a --model "
            "whose layers keep recurrent state")
    elif args.window_pool_pages is not None:
        raise SystemExit(
            "unsupported_option: --window-pool-pages is for a --model "
            "whose layers mix sliding-window and global attention")
    # one-shot start-up phases, ms -> the heartbeat's `startup_ms`
    from .searcher import _Lap, _process_age_ms
    boot: dict[str, float] = {}
    age = _process_age_ms()
    if age is not None:
        boot["process"] = age
    lap = _Lap()
    import jax
    if os.environ.get("SPTPU_FORCE_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    from ..utils.jaxplatform import apply_chip_pin, enable_compile_cache
    if os.environ.get("SPTPU_CHIP_PIN"):
        # supervisor lane placement (spt supervise --pin-chips): a
        # default device among the chips this process can see (its
        # runtime opens all of them — see apply_chip_pin)
        apply_chip_pin(os.environ["SPTPU_CHIP_PIN"])
    enable_compile_cache()
    jax.devices()                 # open the device here, where it is timed
    boot["jax"] = lap.lap()
    store = Store.open(args.store, persistent=args.persistent)
    from ..models import CompletionModel, DecoderConfig
    tokenizer = None
    template = args.template
    if args.weights == "int8":
        # `--weights int8` sentinel: no checkpoint file — run the
        # seeded-random decoder with per-output-channel int8 weight
        # residency (the docs' spelling of --weights-int8)
        args.weights = None
        args.weights_int8 = True
    if args.weights and args.weights.endswith(".gguf"):
        from ..models.gguf import (GgufFile, decoder_config_from_gguf,
                                   load_tokenizer)
        overrides = {"max_len": args.n_ctx} if args.n_ctx else {}
        with GgufFile(args.weights) as gf:   # parse the container once
            cfg = decoder_config_from_gguf(gf, **overrides)
            tokenizer = load_tokenizer(gf)
            if template == "auto":
                # fingerprint the checkpoint's embedded Jinja template
                # (llama.cpp reads the same metadata for its pick)
                template = detect_template(
                    gf.metadata.get("tokenizer.chat_template"))
                log.info("--template auto resolved to %r", template)
    else:
        cfg = DecoderConfig(max_len=args.n_ctx or 2048)
        if args.weights:
            log.warning(
                "--weights %s has no tokenizer metadata; falling back to "
                "the byte-level tokenizer, which will NOT match a real "
                "checkpoint's vocabulary — use the model's .gguf export "
                "for faithful generation", args.weights)
    if template == "auto":
        # no GGUF metadata to fingerprint: the reference's own fallback
        # when llama_chat_apply_template has no template is bare
        # system\n\nprompt concatenation
        template = "none"
        log.info("--template auto with no GGUF metadata: using 'none'")
    if args.quantized and args.weights_int8:
        raise SystemExit(
            "--quantized and --weights-int8 are mutually exclusive: "
            "both claim the attention/MLP kernels (Q8_0 blocks vs "
            "per-output-channel) — pick one weight residency")
    if args.quantized:
        cfg = dataclasses.replace(cfg, quantized=True)
    if args.weights_int8:
        # chaos site: the channel-quantization pass over the loaded
        # checkpoint (CompletionModel.__init__ ->
        # quantize_decoder_params(mode="channel")) — inject here so
        # the supervisor sees the crash BEFORE any program compiles
        fault("completer.weight_quant")
        cfg = dataclasses.replace(cfg, weights_int8=True)
    if args.model:
        cfg, seed = model_cfg, model_seed
        log.info("model description %s: %d layers (%d dense), experts "
                 "%d..+%d of %d, vocabulary %d..+%d, window %d",
                 args.model, cfg.layers, cfg.dense_layers,
                 cfg.experts_first, cfg.experts_held,
                 cfg.n_routed_experts, cfg.vocab_first, cfg.vocab_size,
                 cfg.max_len)
    mesh = None
    if args.tp > 1 or args.ep > 1:
        from ..parallel.mesh import make_mesh
        mesh = make_mesh(tp=args.tp, ep=args.ep)  # dp inferred
        log.info("sharded decode: tp=%d ep=%d", args.tp, args.ep)
    mkw = dict(weights=args.weights, top_p=args.top_p, temp=args.temp)
    from ..models import MoeDecoderConfig, moe_completion_model
    if args.model:
        model = model_cls(cfg, seed=seed, top_p=args.top_p,
                          temp=args.temp)
        jax.block_until_ready(model.params)
        log.info("resident weights: %.2f GB",
                 model.resident_bytes() / 1e9)
    elif isinstance(cfg, MoeDecoderConfig):
        # a Mixtral-family GGUF resolves to the MoE config; the same
        # daemon stack serves it (models/moe.py)
        log.info("MoE checkpoint: %d experts, top-%d routing",
                 cfg.n_experts, cfg.top_k)
        model = moe_completion_model(cfg, mesh, **mkw)
    elif mesh is not None:
        from ..parallel import ShardedCompletionModel
        model = ShardedCompletionModel(cfg, mesh, **mkw)
    else:
        model = CompletionModel(cfg, **mkw)
    if args.draft_weights and args.draft_layers:
        raise SystemExit(
            "--draft-weights and --draft-layers are mutually "
            "exclusive: the first drafts with a separate checkpoint "
            "(serial lane only), the second with a truncated view of "
            "the target (continuous lane capable) — pick one")
    if args.draft_weights:
        from ..models import SpeculativeCompletionModel
        if not args.draft_weights.endswith(".gguf"):
            # a safetensors file carries no geometry metadata, and a
            # draft small enough to be useful is never default-sized —
            # guessing would crash deep in the loader
            raise SystemExit(
                "--draft-weights requires a .gguf draft (geometry and "
                "tokenizer come from its metadata); export the draft "
                "via models/gguf_writer.py if needed")
        from ..models.gguf import GgufFile, decoder_config_from_gguf
        with GgufFile(args.draft_weights) as gf:
            dcfg = decoder_config_from_gguf(gf)
        draft = CompletionModel(dcfg, weights=args.draft_weights,
                                top_p=args.top_p, temp=args.temp)
        model = SpeculativeCompletionModel(model, draft,
                                           gamma=args.gamma)
        log.info("speculative decoding: gamma=%d draft=%s",
                 args.gamma, args.draft_weights)
    elif args.draft_layers:
        from ..models import SpeculativeCompletionModel, self_draft_model
        draft = self_draft_model(model, args.draft_layers)
        model = SpeculativeCompletionModel(model, draft,
                                           gamma=args.gamma)
        log.info("self-drafting speculative decode: first %d of %d "
                 "layers, gamma=%d (drafts verify through the paged "
                 "kernel on the continuous lane)",
                 args.draft_layers, cfg.layers, args.gamma)
    cls = Completer
    if args.phase != "unified":
        from .disagg import DecodeLane, PrefillLane
        cls = PrefillLane if args.phase == "prefill" else DecodeLane
    comp = cls(store, model=model, tokenizer=tokenizer,
                     max_new_tokens=args.max_new_tokens,
                     template=template, batch_cap=args.batch_cap,
                     page_size=args.page_size,
                     pool_pages=args.pool_pages,
                     kv_dtype=args.kv_dtype,
                     inflight_depth=args.inflight_depth,
                     spec_min_acceptance=args.spec_min_acceptance,
                     queue_high_water=args.queue_high_water,
                     retry_after_ms=args.retry_after_ms,
                     tenant_weights=parse_tenant_weights(
                         args.tenant_weights),
                     prefix_cache=not args.no_prefix_cache,
                     prefix_cache_pages=args.prefix_cache_pages,
                     state_snapshots=args.state_snapshots,
                     window_pool_pages=args.window_pool_pages,
                     prefix_quotas=parse_tenant_quotas(
                         args.prefix_quota),
                     kv_tier_pages=args.kv_tier_pages,
                     kv_tier_persist=(
                         f"{args.store}-kvtier"
                         if args.kv_tier_persist == "auto"
                         else args.kv_tier_persist),
                     replica=args.replica,
                     audit=args.audit_dir and {
                         "dir": args.audit_dir,
                         "every": args.audit_every})
    comp.attach()
    boot["weights"] = lap.lap()   # the model's tree + Completer + attach
    continuous = args.continuous or args.phase != "unified"
    if args.warmup:
        t0 = time.monotonic()
        paged = continuous and comp._paged_ok()
        if paged:
            # the continuous lane only ever runs the paged program
            # set (paged prefill buckets + commit scatters + chunked
            # paged decode) — compiling the serial/dense sweep too
            # would roughly double first-boot warmup for programs
            # this lane never executes.  A join/finish/join cycle at
            # serve time must never compile.
            comp.warmup_paged()
        else:
            kw = {}
            if comp.batch_cap > 1 \
                    and hasattr(model, "prefill_batch") \
                    and comp._batched_budget() is not None:
                kw["batch"] = comp.batch_cap   # dense batched shapes
            model.warmup(chunk=comp.flush_tokens, **kw)
        log.info("warmup compiled in %.1fs (.xla_cache persists "
                 "programs across restarts)", time.monotonic() - t0)
        boot["warmup"] = lap.lap()
        for ev in DEVTIME.pending_events():
            # the compile ledger's own durations, by program (a cached
            # program reads as the seconds its fetch took)
            log.info("warmup: %s %s took %.1fs", ev["program"],
                     ev["shapes_key"][-48:], ev["duration_ms"] / 1e3)
    comp.startup_ms.update(boot)
    if args.oneshot:
        n = comp.run_once()
        log.info("oneshot serviced %d completions", n)
        return 0
    try:
        if continuous:
            comp.run_continuous(idle_timeout_ms=args.idle_timeout_ms)
        else:
            comp.run(idle_timeout_ms=args.idle_timeout_ms)
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
