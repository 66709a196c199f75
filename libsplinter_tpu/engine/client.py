"""Client-side resilience — the one retry discipline every submit
path shares.

Before this module each call-site hand-rolled its own timeout loop:
`submit_search` polled with a half-deadline re-pulse, the CLI's
completion path blocked on READY, and neither knew what to do with a
lane that was down or a typed `overloaded` shed record.  The wrapper
here owns that policy once:

  - **fail fast on a down lane**: `protocol.lane_down` (the
    supervisor's circuit breaker) is consulted before every attempt,
    so a request against a crash-looping lane returns immediately
    instead of burning the full submit timeout;
  - **honor `retry_after_ms`**: a typed `overloaded` record (the
    daemons' high-water shed, engine/qos.py) is retried after the
    server's hint — jittered, so a thousand shed clients do not
    re-arrive as one synchronized thundering herd;
  - **jittered exponential backoff** floors the wait when the server
    gave no hint;
  - **give up at the caller's deadline**: the whole retry loop lives
    inside one `timeout_ms` budget; when the budget cannot cover
    another attempt the LAST result (typically the overloaded record)
    is returned so the caller sees WHY it failed, not just that it
    timed out.

Under the retries sits the one bounded wait (`wait_with_repulse`):
it blocks on the request key's LABEL word — the thing every lane's
commit flips (`Store.poll_labels`) — not on the slot's epoch, which a
`label_clear` + `bump` never moves, and counts how its slices ended
(`wait_counters()`).

`submit_completion` is the completer-lane client these semantics were
missing entirely: prompt in, READY-gated value out, typed error
records surfaced as dicts.  `searcher.submit_search` routes through
the same wrapper.
"""
from __future__ import annotations

import random
import threading
import time
from typing import Callable

from . import protocol as P

# retry pacing defaults: base doubles per attempt, jitter U(0.5, 1.5)
# — the supervisor's backoff discipline, client-side
BASE_BACKOFF_MS = 50.0
MAX_BACKOFF_MS = 2000.0


def call_with_retries(attempt: Callable[[float], object], *,
                      timeout_ms: float,
                      store=None, lane: str | None = None,
                      base_backoff_ms: float = BASE_BACKOFF_MS,
                      max_backoff_ms: float = MAX_BACKOFF_MS,
                      rng: random.Random | None = None):
    """Run `attempt(left_ms)` until it yields a non-retryable result
    or the deadline passes.

    `attempt` returns: a dict with {"err": "overloaded", ...} to be
    retried after the hint; any other value (including None = attempt
    timed out, and error dicts like deadline_expired) is terminal and
    returned as-is.  With `store`+`lane` given, a lane whose breaker
    is open short-circuits to None before the first attempt — the
    caller's local fallback runs instantly.
    """
    rng = rng or random
    deadline = time.monotonic() + timeout_ms / 1e3
    result = None
    k = 0
    while True:
        left_ms = (deadline - time.monotonic()) * 1e3
        if left_ms <= 0:
            return result
        if store is not None and lane is not None \
                and P.lane_down(store, lane):
            return result
        result = attempt(left_ms)
        rec = result if isinstance(result, dict) else None
        if rec is None or rec.get("err") != P.ERR_OVERLOADED:
            return result
        # shed: wait out the server's hint (floored by our own
        # backoff), jittered so retries decorrelate, capped by the
        # remaining budget — an unaffordable wait returns the typed
        # record so the caller knows it was shed, not silent
        hint = float(rec.get("retry_after_ms", 0) or 0)
        back = min(base_backoff_ms * (2 ** k), max_backoff_ms)
        wait_ms = max(hint, back) * (0.5 + rng.random())
        k += 1
        left_ms = (deadline - time.monotonic()) * 1e3
        if wait_ms >= left_ms:
            return result
        time.sleep(wait_ms / 1e3)


# sentinel: "not finished yet" for wait_with_repulse's check()
PENDING = object()

# what wait_with_repulse did, whole process, always on (plain ints; the
# submit paths run on many client threads, so they move under one lock
# and are read with wait_counters()):
#   waits           calls of wait_with_repulse (one a request attempt)
#   woken           wait slices ended by the label condition or by the
#                   slot's epoch moving (the daemon's doing)
#   slice_timeouts  slices that ran their 50 ms out with nothing changed
#   repulses        half-budget re-bumps
# A lane whose commit the wait cannot see reads woken 0 and
# slice_timeouts >= 1 a request; a healthy one woken ~ waits.
waits = woken = slice_timeouts = repulses = 0
_counters_lock = threading.Lock()


def wait_counters() -> dict:
    """A consistent copy of the wait counters above."""
    with _counters_lock:
        return {"waits": waits, "woken": woken,
                "slice_timeouts": slice_timeouts, "repulses": repulses}


def wait_with_repulse(store, key: str, left_ms: float, check, *,
                      mask: int, want: int):
    """The shared bounded wait every submit path uses: block until
    `check()` returns something other than PENDING, re-bumping ONCE
    at half budget (the bump may have raced the daemon's signal_wait
    re-arm — the run-loop sweeps narrow but cannot close that window;
    one re-pulse costs a signal, silence costs the whole timeout),
    returning None when the budget runs out.

    `(mask, want)` is the label condition `check()` itself tests
    (`labels(key) & mask == want` = no longer PENDING): every lane
    acknowledges a request by a label flip on the request key, which
    moves no epoch, so the wait sleeps on the label word
    (`Store.poll_labels`: level-triggered, and woken too by the
    slot's epoch moving — a streamed chunk, a rewrite, an unset), in
    slices of at most 50 ms.  One definition, so a fix to the
    re-pulse race can never apply to one lane and miss another."""
    global waits, woken, slice_timeouts, repulses
    stop = time.monotonic() + left_ms / 1e3
    re_pulsed = False
    n_woken = n_timeouts = 0
    try:
        while True:
            res = check()
            if res is not PENDING:
                return res
            rem_ms = (stop - time.monotonic()) * 1e3
            if rem_ms <= 0:
                return None
            if not re_pulsed and rem_ms * 2 <= left_ms:
                try:
                    store.bump(key)
                except (KeyError, OSError):
                    pass
                re_pulsed = True
            try:
                hit = store.poll_labels(key, mask, want,
                                        timeout_ms=int(min(rem_ms, 50)))
            except KeyError:
                hit = True                # unset mid-wait: check() says
            if hit:
                n_woken += 1
            else:
                n_timeouts += 1
    finally:
        with _counters_lock:
            waits += 1
            woken += n_woken
            slice_timeouts += n_timeouts
            repulses += re_pulsed


def _stamp_qos(store, key: str, tenant: int,
               deadline_ts: float | None, trace=None,
               max_new_tokens: int | None = None) -> None:
    """Tag a freshly-written request with its tenant, absolute
    deadline, and trace context (after set, before the bump — the
    stamp discipline).  `trace` follows protocol.stamp_trace_ctx:
    True = new root trace, an int trace id = a hop of that trace,
    (trace_id, parent_span) = explicit tree placement — one trace id
    then spans a whole client-chained pipeline across lanes."""
    if tenant:
        P.stamp_tenant(store, key, tenant)
    if deadline_ts is not None:
        P.stamp_deadline(store, key, deadline_ts)
    if max_new_tokens is not None:
        P.stamp_max_new(store, key, max_new_tokens)
    if trace:
        P.stamp_trace_ctx(store, key, trace)


def submit_completion(store, key: str, prompt: str | bytes, *,
                      timeout_ms: float = 10_000,
                      tenant: int = 0,
                      deadline_ms: float | None = None,
                      trace=None,
                      retry: bool = True,
                      max_new_tokens: int | None = None):
    """The completer-lane client: write `prompt` to `key`, raise the
    INFER request, wait for READY.

    Returns the completed slot value (bytes: rendered prompt +
    streamed generation), a typed error dict ({"err": "overloaded",
    "retry_after_ms": ...} after exhausted retries, {"err":
    "deadline_expired"} for a deadline the daemon declined), or None
    on timeout / down lane.  `deadline_ms` (relative) stamps an
    absolute wall-clock deadline the daemon fast-fails behind;
    `tenant` tags the request for per-tenant admission;
    `max_new_tokens` stamps the request's own answer budget, which
    the continuous lane honours up to its --max-new-tokens.
    """
    deadline_ts = (time.time() + deadline_ms / 1e3
                   if deadline_ms is not None else None)

    def attempt(left_ms: float):
        store.set(key, prompt)
        # a retry (or a recycled key) may still carry READY from the
        # previous completion/shed — left set, the wait loop below
        # would return the raw prompt instantly as the "completion"
        store.label_clear(key, P.LBL_READY | P.LBL_SERVICING)
        _stamp_qos(store, key, tenant, deadline_ts, trace,
                   max_new_tokens)
        store.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        store.bump(key)

        def check():
            try:
                labels = store.labels(key)
            except KeyError:
                return None               # caller deleted it mid-wait
            if not labels & P.LBL_READY:
                return PENDING
            try:
                raw = store.get(key)
            except (KeyError, OSError):
                return None
            rec = P.parse_error_payload(raw)
            return rec if rec is not None else raw.rstrip(b"\0")

        return wait_with_repulse(store, key, left_ms, check,
                                 mask=P.LBL_READY, want=P.LBL_READY)

    if not retry:
        return attempt(timeout_ms)
    return call_with_retries(attempt, timeout_ms=timeout_ms,
                             store=store, lane="completer")


def classify_embed_result(store, key: str, labels: int, *,
                          deadline_ts: float | None = None):
    """THE embed-lane result read — one definition `submit_embed` and
    the pipeline lane's verb polling share, so the subtle label-only
    protocol (the embedder has no value channel: success IS a
    committed vector, shed IS a cleared label with a zero vector)
    cannot drift between them.  Returns PENDING while the request is
    queued, True when the vector landed, else a typed error dict
    ({"err": "ctx_exceeded" | "deadline_expired" | "overloaded"})."""
    import numpy as np

    from .qos import DEFAULT_RETRY_AFTER_MS

    if labels & P.LBL_EMBED_REQ:
        return PENDING
    if labels & P.LBL_CTX_EXCEEDED:
        return {"err": "ctx_exceeded"}
    try:
        vec = store.vec_get(key)
        if vec is not None and np.abs(vec).max() > 0:
            return True
    except (KeyError, OSError):
        pass
    # label-only unblock with no vector: the embed lane's
    # shed/deadline signal (the heartbeat counters say which;
    # client-side the deadline disambiguates)
    if deadline_ts is not None and time.time() >= deadline_ts:
        return {"err": P.ERR_DEADLINE}
    return P.overloaded_record(DEFAULT_RETRY_AFTER_MS)


def submit_embed(store, key: str, text: str | bytes, *,
                 timeout_ms: float = 10_000,
                 tenant: int = 0,
                 deadline_ms: float | None = None,
                 trace=None,
                 retry: bool = True):
    """The embed-lane client that was missing (`submit_search` and
    `submit_completion` exist): write `text` to `key`, raise the
    EMBED request, wait for the daemon to clear it.

    The embedder has no value channel to spare (the slot holds the
    client's text), so its shed/expiry signal is the cleared label
    with NO vector committed — this helper reads that protocol and
    SYNTHESIZES the typed record the other lanes return explicitly:
    True when the vector landed, {"err": "overloaded"|
    "deadline_expired"|"ctx_exceeded"} when the daemon rejected it,
    None on timeout / down lane.  Tenant, deadline, and the shared
    retry wrapper behave exactly as in the sibling helpers."""
    deadline_ts = (time.time() + deadline_ms / 1e3
                   if deadline_ms is not None else None)

    def attempt(left_ms: float):
        store.set(key, text)
        # a reused key may still carry CTX_EXCEEDED from a previous
        # over-long text — left set, a successful re-embed would
        # still classify as rejected
        store.label_clear(key, P.LBL_CTX_EXCEEDED)
        _stamp_qos(store, key, tenant, deadline_ts, trace)
        store.label_or(key, P.LBL_EMBED_REQ | P.LBL_WAITING)
        store.bump(key)

        def check():
            try:
                labels = store.labels(key)
            except KeyError:
                return None               # caller deleted it mid-wait
            return classify_embed_result(store, key, labels,
                                         deadline_ts=deadline_ts)

        return wait_with_repulse(store, key, left_ms, check,
                                 mask=P.LBL_EMBED_REQ, want=0)

    if not retry:
        return attempt(timeout_ms)
    return call_with_retries(attempt, timeout_ms=timeout_ms,
                             store=store, lane="embedder")
