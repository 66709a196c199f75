"""Resident device loops + K-deep dispatch overlap — the common
machinery that breaks the per-drain runtime dispatch floor.

Every dispatch pays a per-call XLA runtime round trip (PERF.md §6,
PR 27, read dispatch + fetch at ~2.5 ms on the v5e), whatever the
kernels cost.  One dispatch per drain therefore floors EVERY hot-lane
latency at that round trip.  Two complementary mechanisms amortize it,
both defined here so the three lane daemons share one contract:

  ResidentRing / RingResult — a **resident multi-batch device
    program**: the host pre-stages up to ring_depth same-shape batches
    into one (depth, B, S) ring, and a single dispatch runs a
    lax.while_loop over the occupied slots (the occupancy is a scalar
    OPERAND, so one compiled program serves every occupancy
    1..depth with no recompiles and no wasted compute on empty
    slots).  The whole ring's results come back in ONE transfer and
    slot views split host-side — per-drain dispatch cost amortizes to
    ~63/occupancy ms.  Output ring buffers are DONATED and recycled
    through a small pool (RingResult.materialize_host returns the
    buffer after the host copy lands), so steady-state ring serving
    allocates nothing.  The embedder's bucketed encode programs are
    the primary user (models/encoder.encode_ring_async).

  InflightWindow — **K-deep in-flight dispatch overlap** for lanes
    where one fused program is impractical (the searcher's QB-bucketed
    top-k drains, the completer's sequential paged decode chunks):
    hold up to `depth` un-awaited dispatches and resolve them in
    COMPLETION order — the host stages/dispatches work k+1..k+K while
    the device computes k, and only blocks when the window is full
    with nothing ready.  Generalizes PR 1's CommitPipeline (which now
    subclasses it); the floor amortizes to ~63/K ms per dispatch.

Fault sites (SPTPU_FAULT; docs/operations.md catalog):
  resident.ring_dispatch   before a ring program dispatch
  resident.ring_collect    before the whole-ring host fetch
"""
from __future__ import annotations

from collections import deque

import numpy as np

from ..utils.faults import fault


def pending_ready(obj) -> bool:
    """True when forcing `obj` will not block: host values are always
    ready; device futures answer is_ready(); containers are ready when
    every leaf is.  Unknown future types claim in-flight so callers
    account the force as a (possibly) blocking wait — the
    PendingEmbeddings.is_ready contract, generalized."""
    if obj is None or isinstance(obj, np.ndarray):
        return True
    if isinstance(obj, (list, tuple)):
        return all(pending_ready(o) for o in obj)
    probe = getattr(obj, "is_ready", None)
    if probe is None:
        return True                    # host value (scalar, bytes, ...)
    try:
        return bool(probe())
    except Exception:
        return False


class InflightWindow:
    """Hold up to `depth` un-awaited dispatches; resolve in COMPLETION
    order.  The skeleton every overlap consumer shares: push() enqueues
    an entry, immediately resolves whatever is already complete, and
    force-resolves the oldest only when the window overflows —
    back-pressure, never a synchronous round trip per dispatch.

    Subclasses implement _entry_ready(entry) and _resolve(entry);
    CommitPipeline (engine/embedder.py) is the original instance,
    CallbackWindow below the generic one."""

    def __init__(self, depth: int):
        self.depth = max(1, depth)
        self._q: deque = deque()
        self.dispatched = 0
        self.inflight_peak = 0       # max un-resolved depth seen

    def __len__(self) -> int:
        return len(self._q)

    def push_entry(self, entry) -> None:
        self._q.append(entry)
        self.dispatched += 1
        self.inflight_peak = max(self.inflight_peak, len(self._q))
        self.drain_ready()
        while len(self._q) > self.depth:
            self._resolve(self._q.popleft())

    def drain_ready(self) -> int:
        """Resolve every entry that has already completed (in queue
        order among the ready ones); never blocks."""
        done = 0
        if self._q:
            still: deque = deque()
            for entry in self._q:
                if self._entry_ready(entry):
                    self._resolve(entry)
                    done += 1
                else:
                    still.append(entry)
            self._q = still
        return done

    def flush(self) -> None:
        """Resolve everything: ready entries first, then block for the
        rest in dispatch order (the unavoidable tail wait — by now it
        overlapped all the host work done since dispatch)."""
        self.drain_ready()
        while self._q:
            self._resolve(self._q.popleft())

    # -- subclass surface ---------------------------------------------------

    def _entry_ready(self, entry) -> bool:
        raise NotImplementedError

    def _resolve(self, entry) -> None:
        raise NotImplementedError


class CallbackWindow(InflightWindow):
    """The generic InflightWindow: entries are (payload, pending) and a
    resolve callback consumes them in completion order.

        win = CallbackWindow(depth, resolve_fn)
        win.push(batch_meta, device_future)   # dispatch side
        ...
        win.flush()                           # drain tail

    resolve_fn(payload, pending, ready) runs exactly once per entry;
    `ready` says whether the force will block (stats attribution).
    The callback owns its own error containment — a raising resolver
    propagates, matching the caller's failure-domain design (the
    searcher wraps its resolver in the per-batch degradation ladder,
    the completer in abort_all)."""

    def __init__(self, depth: int, resolve_fn):
        super().__init__(depth)
        self._resolve_fn = resolve_fn
        self.ready_resolves = 0
        self.blocking_resolves = 0

    def push(self, payload, pending) -> None:
        self.push_entry((payload, pending))

    def _entry_ready(self, entry) -> bool:
        return pending_ready(entry[1])

    def _resolve(self, entry) -> None:
        payload, pending = entry
        ready = pending_ready(pending)
        if ready:
            self.ready_resolves += 1
        else:
            self.blocking_resolves += 1
        self._resolve_fn(payload, pending, ready)


def _wire_to_f32(out: np.ndarray) -> np.ndarray:
    """Upcast a wire-dtype host array to float32 — the one conversion
    every embedding fetch path shares (int8 is the fixed x127 scale:
    components of an L2-normalized embedding lie in [-1, 1], so no
    per-vector scale row exists to apply)."""
    if out.dtype == np.int8:
        return out.astype(np.float32) * np.float32(1.0 / 127.0)
    return out.astype(np.float32, copy=False)


class RingResult:
    """One resident ring dispatch's result: a (depth, B, ...) device
    array covering up to `depth` pre-staged batches.  The whole ring
    fetches in ONE device->host transfer on first materialize (slot
    views split host-side — a per-slot device fetch would re-pay the
    dispatch floor the ring exists to amortize), after which the
    device buffer is handed back to its donation pool via `release`
    for the next ring dispatch to consume.

    jax's async dispatch means a device-side failure surfaces HERE,
    at the fetch, not at dispatch.  A failed fetch caches its error
    (re-raised per slot — never a silent None deref), does NOT pool
    the possibly-poisoned buffer, and slots fall back through `retry`
    (a per-slot re-encode on the battle-tested per-call programs) when
    the caller provided one — so one transient device error costs a
    re-dispatch, not a failed drain."""

    __slots__ = ("_out", "_host", "_release", "_convert", "_retry",
                 "_err", "_mark", "n_valid")

    def __init__(self, out, n_valid: int, *, release=None,
                 convert=_wire_to_f32, retry=None, mark=None):
        self._out = out
        self._host: np.ndarray | None = None
        self._release = release
        self._convert = convert
        self._retry = retry           # (slot_i, n) -> (n, ...) f32
        self._err: Exception | None = None
        self._mark = mark             # devtime DispatchMark: closed at
        # the fetch — the collect point that already exists, so the
        # device window costs no new host sync
        self.n_valid = n_valid

    def is_ready(self) -> bool:
        if self._host is not None or self._err is not None:
            return True
        return pending_ready(self._out)

    def materialize_host(self) -> np.ndarray:
        """Fetch the whole ring (once), recycle the device buffer."""
        if self._host is None:
            if self._err is not None:
                raise self._err
            fault("resident.ring_collect")
            try:
                host = np.asarray(self._out)
            except Exception as ex:
                # poisoned dispatch: cache for the sibling slots and
                # drop the buffer (re-donating it could re-poison the
                # next ring); the pool re-allocates on demand
                self._err = ex
                self._out = None
                self._release = None
                raise
            self._host = host
            mark, self._mark = self._mark, None
            if mark is not None:
                mark.close()
            out, self._out = self._out, None
            rel, self._release = self._release, None
            if rel is not None:
                rel(out)              # host copy landed: re-donatable
        return self._host

    def slot(self, i: int, n: int) -> "RingSlot":
        """A PendingEmbeddings-contract view of ring slot i's first n
        rows (the rest of the slot is batch padding)."""
        return RingSlot(self, i, n)


class RingSlot:
    """One slot of a RingResult under the pending-future contract
    (is_ready / materialize / n) so per-batch consumers — the
    embedder's CommitPipeline — need not know a ring dispatch from a
    per-call one.  A ring whose fetch failed falls back to the
    parent's per-slot `retry` (when armed) before giving up."""

    __slots__ = ("_ring", "i", "n")

    def __init__(self, ring: RingResult, i: int, n: int):
        self._ring = ring
        self.i = i
        self.n = n

    def is_ready(self) -> bool:
        return self._ring.is_ready()

    def materialize(self) -> np.ndarray:
        try:
            host = self._ring.materialize_host()
        except Exception:
            if self._ring._retry is None:
                raise
            return self._ring._retry(self.i, self.n)
        return self._ring._convert(host[self.i][: self.n])
