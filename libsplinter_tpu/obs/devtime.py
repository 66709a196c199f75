"""Device-time & compile attribution: the named-program registry.

PR 13's spans decompose queue-wait vs service on the HOST clock only;
this module is the device-side half.  Every jitted hot program
registers under a stable ``lane.program`` name and the registration
wrapper buys two things the span plane cannot see:

  - COMPILE LEDGER: each call samples the program's jit cache size
    (the same private `_cache_size` idiom compile_count() already
    relies on) before and after the dispatch; growth is a compile
    EVENT — a typed record {program, lane, shapes_key, duration_ms,
    generation, cause} buffered in-process and flushed into a bounded
    store ring (``__compile_<i>``, the span-ring slot-claim
    discipline) on the heartbeat cadence.  A runtime recompile (the
    PR 8 missing-`out_shardings` class, today caught only statically
    by SPL203) becomes an event an operator can SEE, with the shapes
    key that triggered it — not a latency mystery.
  - DEVICE WINDOW: each dispatch leaves a DispatchMark; the mark is
    CLOSED at the collect point that already exists for the result
    (RingResult fetch, PendingEmbeddings/PendingChunk materialize,
    READY flips) — so dispatch->collect wall time per named program
    rides the plane with ZERO new host syncs (SPL201-safe by
    construction).  The window is wall time between dispatch and the
    host observing the result: on a saturated device it converges on
    device execution time (jax's async dispatch returns immediately);
    under light load it includes device idle — a ceiling, never an
    undercount.

Everything here is host-side stdlib + store calls — no jax import —
so lanes, the CLI, and tests import it freely.  The plane is ON by
default and gated under the standing <3% obs budget
(scripts/obs_overhead_check.py phase 3); ``SPTPU_DEVTIME=0`` kills it
(wrappers become transparent pass-throughs).
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

import numpy as np

from .hist import LogHistogram

# in-process compile-event buffer bound: the ledger's source of truth
# is the store ring; the buffer only bridges dispatch -> flush, and a
# pathological compile storm must not grow host memory without bound
_MAX_EVENTS = 256

# warmup-cause compiles are expected (that is what warmup is FOR); the
# gate and the heartbeat counters key off runtime-cause events only
CAUSE_WARMUP = "warmup"
CAUSE_RUNTIME = "runtime"


def _cache_size(fn) -> int | None:
    """Compiled-program count for a jitted callable — the private jax
    API the models' compile_count() methods already lean on; None when
    unavailable (non-jit callable, or the API moved)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


def _shapes_key(args, kwargs) -> str:
    """A stable, compact description of one call's argument geometry —
    what an operator needs to identify WHICH shape bucket escaped
    warmup.  Metadata-only (shape/dtype attributes survive donation;
    no data access), one level of list/tuple recursion (the pool-list
    calling convention), everything else abbreviated by type."""
    def one(a, depth=0):
        try:
            shp = getattr(a, "shape", None)
            if shp is not None:
                dt = getattr(a, "dtype", "?")
                return f"{dt}{list(shp)}"
            if isinstance(a, (list, tuple)) and depth < 2:
                if len(a) > 3:
                    return (f"[{len(a)}x"
                            f"{one(a[0], depth + 1)}]")
                return "[" + ",".join(one(x, depth + 1)
                                      for x in a) + "]"
            if isinstance(a, (int, float, bool)) or a is None:
                return repr(a)
            return type(a).__name__
        except Exception:
            return "?"
    parts = [one(a) for a in args]
    parts += [f"{k}={one(v)}" for k, v in sorted(kwargs.items())]
    return "(" + ",".join(parts) + ")"


class DispatchMark:
    """One in-flight dispatch of a named program.  Created by the
    registration wrapper at dispatch, closed at the result's existing
    collect point; idempotent (a retry path may close twice)."""

    __slots__ = ("_prog", "_reg", "t0", "_closed")

    def __init__(self, prog: "_Program", reg: "DevtimeRegistry",
                 t0: float):
        self._prog = prog
        self._reg = reg
        self.t0 = t0
        self._closed = False

    def close(self) -> float:
        """Record dispatch->collect wall ms against the program and
        its lane; returns the ms (0.0 on a re-close)."""
        if self._closed:
            return 0.0
        self._closed = True
        ms = max(time.perf_counter() - self.t0, 0.0) * 1e3
        self._reg._record(self._prog, ms)
        return ms


def close_mark(mark) -> None:
    """Close a possibly-absent mark — the one-liner every collect
    point uses so `None` (devtime off / untracked dispatch) costs an
    identity check and nothing else."""
    if mark is not None:
        mark.close()


class _Program:
    __slots__ = ("name", "lane", "short", "hist", "compiles",
                 "runtime_compiles", "last_mark")

    def __init__(self, name: str):
        self.name = name
        lane, _, short = name.partition(".")
        self.lane = lane
        self.short = short or name
        self.hist = LogHistogram()
        self.compiles = 0            # all causes (warmup included)
        self.runtime_compiles = 0    # post-warmup: the gate's number
        self.last_mark: DispatchMark | None = None


class DevtimeRegistry:
    """Process-global named-program registry (module singleton
    DEVTIME).  Thread-safe where lanes can race (the event buffer and
    the lane accumulators); per-program dispatch bookkeeping follows
    the lanes' single-drain discipline, same as SpanWriter."""

    def __init__(self):
        self.enabled = os.environ.get("SPTPU_DEVTIME", "1") != "0"
        self.generation = 0          # bumped by supervised restarts
        self._progs: dict[str, _Program] = {}
        self._events: list[dict] = []    # awaiting flush()
        self._runtime_events = 0         # lifetime, survives flush
        self._lane_ms: dict[str, float] = {}
        self._device_ms_total = 0.0
        self._t0 = time.time()
        self._warmup_depth = 0
        self._head_ready = False
        self._lock = threading.Lock()

    # -- registration (the tentpole) ---------------------------------------

    def register(self, name: str, fn):
        """Wrap a jitted program under a stable `lane.program` name.
        The wrapper samples the jit cache around each dispatch (compile
        ledger) and leaves a DispatchMark for the collect point to
        close (device window).  With the plane disabled the original
        callable is returned untouched — zero overhead, and
        `__wrapped__` still points home so compile_count() unwrapping
        is unconditional."""
        prog = self._progs.get(name)
        if prog is None:
            prog = self._progs.setdefault(name, _Program(name))
        if not self.enabled:
            try:
                fn.__wrapped__ = fn
            except AttributeError:
                pass                  # C-level callables: unwrappable
            return fn
        reg = self
        # bind the jit cache probe ONCE: the wrapper sits on the per-
        # dispatch hot path, where two exception-swallowing attribute
        # walks per call are real money (the obs-check devtime arm)
        probe = getattr(fn, "_cache_size", None)

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            if probe is None:
                out = fn(*args, **kwargs)
            else:
                try:
                    before = probe()
                except Exception:
                    before = None
                out = fn(*args, **kwargs)
                if before is not None:
                    try:
                        grew = probe() > before
                    except Exception:
                        grew = False
                    if grew:
                        dur = (time.perf_counter() - t0) * 1e3
                        reg._ledger(prog, _shapes_key(args, kwargs),
                                    dur)
            if reg._warmup_depth == 0:
                # no device window during warmup: those dispatches are
                # dominated by compile time and would poison the lane
                # accumulator the first serving span inherits
                if isinstance(out, np.ndarray):
                    # synchronous host result: the call WAS the device
                    # window, no collect point follows — record
                    # directly, no mark object
                    reg._record(
                        prog, (time.perf_counter() - t0) * 1e3)
                else:
                    prog.last_mark = DispatchMark(prog, reg, t0)
            return out

        wrapped.__wrapped__ = fn
        wrapped.__name__ = getattr(fn, "__name__", name)
        wrapped._devtime_name = name
        return wrapped

    def take_mark(self, name: str) -> DispatchMark | None:
        """Pop the program's most recent dispatch mark — the dispatch
        site hands it to the Pending object whose collect point will
        close it.  None when devtime is off or nothing dispatched."""
        prog = self._progs.get(name)
        if prog is None:
            return None
        mark, prog.last_mark = prog.last_mark, None
        return mark

    @contextmanager
    def warmup_phase(self):
        """Compiles inside this context ledger as cause="warmup" —
        expected, excluded from the gate and the runtime counters.
        Re-entrant (warmup helpers nest)."""
        self._warmup_depth += 1
        try:
            yield
        finally:
            self._warmup_depth -= 1

    # -- recording ---------------------------------------------------------

    def _record(self, prog: _Program, ms: float) -> None:
        prog.hist.record(ms)
        with self._lock:
            self._lane_ms[prog.lane] = \
                self._lane_ms.get(prog.lane, 0.0) + ms
            self._device_ms_total += ms

    def _ledger(self, prog: _Program, shapes_key: str,
                duration_ms: float) -> None:
        warm = self._warmup_depth > 0
        prog.compiles += 1
        rec = {"program": prog.name, "lane": prog.lane,
               "shapes_key": shapes_key,
               "duration_ms": round(duration_ms, 3),
               "generation": self.generation,
               "cause": CAUSE_WARMUP if warm else CAUSE_RUNTIME,
               "ts": round(time.time(), 3)}
        with self._lock:
            if not warm:
                prog.runtime_compiles += 1
                self._runtime_events += 1
            if len(self._events) < _MAX_EVENTS:
                self._events.append(rec)

    # -- read side ---------------------------------------------------------

    def compile_events(self, lane: str | None = None) -> int:
        """Lifetime RUNTIME-cause compile count (optionally one
        lane's) — the number that must stay at zero after warmup."""
        if lane is None:
            return self._runtime_events
        return sum(p.runtime_compiles for p in self._progs.values()
                   if p.lane == lane)

    def pending_events(self) -> list[dict]:
        """Buffered (unflushed) ledger records, all causes — the
        in-process view the gate reads alongside the store ring."""
        with self._lock:
            return list(self._events)

    def take_lane_ms(self, lane: str) -> float:
        """Pop the lane's device-ms accumulator — the drain's span
        commit attaches the window to the spans that rode it."""
        with self._lock:
            return self._lane_ms.pop(lane, 0.0)

    def device_ms_share(self) -> float:
        """Device-window ms as a share of wall time since the registry
        started."""
        wall_ms = max(time.time() - self._t0, 1e-9) * 1e3
        return min(self._device_ms_total / wall_ms, 1.0)

    def heartbeat_section(self, lane: str) -> dict:
        """Per-program device quantiles + compile counters for one
        lane's heartbeat (droppable under max_val like every optional
        section)."""
        out: dict = {}
        for p in self._progs.values():
            if p.lane != lane or (p.hist.n == 0 and p.compiles == 0):
                continue
            ent = {"n": p.hist.n, "compiles": p.compiles,
                   "runtime_compiles": p.runtime_compiles}
            if p.hist.n:
                # n and total_ms never reset: a window's mean is the
                # difference of two heartbeats
                ent["total_ms"] = round(p.hist.total_ms, 1)
                ent["p50_ms"] = round(p.hist.quantile(0.50), 3)
                ent["p99_ms"] = round(p.hist.quantile(0.99), 3)
            out[p.short] = ent
        return out

    # -- the store ring ----------------------------------------------------

    def flush(self, store) -> int:
        """Land buffered compile events in the shared ``__compile_<i>``
        ring — heartbeat-cadence work, never the wake path (the
        SpanWriter.flush discipline, same slot-claim counter)."""
        with self._lock:
            if not self._events:
                return 0
            buf, self._events = self._events, []
        from .. import _native as N
        from ..engine import protocol as P
        from .spans import span_ring_size
        landed = 0
        for rec in buf:
            try:
                if not self._head_ready:
                    if P.KEY_COMPILE_HEAD not in store:
                        store.set_uint(P.KEY_COMPILE_HEAD, 0)
                    self._head_ready = True
                head = int(store.integer_op(P.KEY_COMPILE_HEAD,
                                            N.IOP_INC))
                slot = (head - 1) % span_ring_size(store)
                store.set(P.compile_ring_key(slot), json.dumps(rec))
                landed += 1
            except (KeyError, OSError, ValueError):
                self._head_ready = False
                break                 # full store: ledger degrades,
                # serving is untouched; counters keep the truth
        return landed

    def reset(self) -> None:
        """Forget everything (tests + supervised child re-exec)."""
        with self._lock:
            self._progs.clear()
            self._events.clear()
            self._runtime_events = 0
            self._lane_ms.clear()
            self._device_ms_total = 0.0
            self._t0 = time.time()
            self._warmup_depth = 0
            self._head_ready = False


def collect_compile_events(store) -> list[dict]:
    """Every compile event in the store ring, oldest first — what
    `spt trace export` hangs on the compile track and the gate
    inspects cross-process."""
    from ..engine import protocol as P
    from .spans import span_ring_size
    out: list[dict] = []
    for i in range(span_ring_size(store)):
        try:
            raw = store.get(P.compile_ring_key(i)).rstrip(b"\0")
            rec = json.loads(raw)
        except (KeyError, OSError, ValueError):
            continue
        if isinstance(rec, dict) and "program" in rec:
            out.append(rec)
    out.sort(key=lambda r: r.get("ts", 0.0))
    return out


# the process-global registry every lane and model shares — one ledger
# per daemon, mirroring the models' per-process program caches
DEVTIME = DevtimeRegistry()
