"""Lightweight span tracing for the serving daemons.

The reference's only tracing primitives are raw cycle reads
(splinter_now(), splinter.h:872-893) and post-hoc ctime backfill
(splinter.c:682-707); operators correlate latency by hand.  Here the
daemons get nestable wall-clock spans with near-zero disabled cost:

    from ..utils.trace import tracer
    with tracer.span("drain"):
        ...

Each span name aggregates into a log-bucketed histogram
(obs/hist.LogHistogram, fixed mergeable edges, ~1 us record path), so
the stats heartbeat (engine/protocol.publish_heartbeat) carries true
p50/p90/p99/max per stage — not means dressed up as percentiles.
`spt head __embedder_stats` — or the sidecar's debug watch — shows
where wall time goes without attaching anything, and
Tracer.render_prom() serializes the same histograms in Prometheus
text exposition for `spt metrics`.

Enabled with SPTPU_TRACE=1 (default off: span() returns a shared
no-op, and the disabled hot path pays one dict lookup and nothing
else).

One span, two clocks.  A LEAF phase — `tracer.span(name, leaf=True)`,
or `tracer.annotation(name)` where the call site accumulates the
histogram itself — also opens a jax.profiler.TraceAnnotation, so the
phase lands on the host plane of whatever profiler capture is running,
on the device trace's clock: one capture of a steady window then says
what the host did in every device-idle gap.  Enclosing spans record
the histogram only: a gap is named after the single host event that
overlaps it longest, so an annotation around others would win every
gap and say nothing.  jax is imported on the enabled leaf path alone.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time

from ..obs.hist import LogHistogram


class Tracer:
    """Aggregating span tracer.  Thread-safe; span() is a context
    manager.  Disabled tracers hand back one shared no-op context, so
    the hot path pays a dict lookup and nothing else."""

    def __init__(self, enabled: bool | None = None):
        self.enabled = (os.environ.get("SPTPU_TRACE") == "1"
                        if enabled is None else enabled)
        self._lock = threading.Lock()
        self._agg: dict[str, LogHistogram] = {}

    def record(self, name: str, dt_ms: float) -> None:
        """Record one measured duration under a span name (for call
        sites that already hold the timing — e.g. the commit pipeline's
        device-wait accounting — this skips the span object)."""
        with self._lock:
            h = self._agg.get(name)
            if h is None:
                h = self._agg[name] = LogHistogram()
            h.record(dt_ms)

    _NOOP = contextlib.nullcontext()

    def span(self, name: str, *, leaf: bool = False):
        """Histogram span; leaf=True adds the profiler annotation (the
        module docstring's rule: leaves are disjoint in time on their
        thread, enclosing spans stay off the profiler's clock)."""
        if not self.enabled:
            return self._NOOP
        return _LeafSpan(self, name) if leaf else _Span(self, name)

    def annotation(self, name: str):
        """The profiler-clock half of a leaf span alone, for call
        sites that sum a stage over a drain and record() it once."""
        return _annotation(name) if self.enabled else self._NOOP

    def snapshot(self) -> dict:
        """{name: {n, total_ms, max_ms, p50_ms, p90_ms, p95_ms,
        p99_ms}} — merged into heartbeats.  The n/total_ms/max_ms keys
        predate the histograms and stay for consumers of the old
        aggregate shape."""
        with self._lock:
            return {k: h.snapshot() for k, h in self._agg.items()}

    def quantiles(self, prefix: str | None = None) -> dict:
        """Per-span quantile summaries, optionally filtered to names
        under `prefix` ("embed." -> {"drain": {...}, ...} with the
        prefix stripped) — the heartbeat `quantiles` section."""
        with self._lock:
            items = list(self._agg.items())
        out = {}
        for name, h in items:
            if prefix is not None:
                if not name.startswith(prefix):
                    continue
                name = name[len(prefix):]
            out[name] = h.snapshot()
        return out

    def render_prom(self, counters: dict | None = None, *,
                    prefix: str = "sptpu") -> str:
        """Prometheus text exposition of every span histogram, plus
        optional scalar counter groups: {group: {field: number}}
        renders as <prefix>_<group>_<field>."""
        from ..obs.prom import PromWriter

        w = PromWriter()
        with self._lock:
            items = list(self._agg.items())
        for name, h in items:
            w.histogram(f"{prefix}_span_ms", h, {"span": name},
                        help_="tracer span wall time (ms)")
        for group, mapping in (counters or {}).items():
            w.scalars(f"{prefix}_{group}", mapping)
        return w.render()

    def reset(self) -> None:
        with self._lock:
            self._agg.clear()


class _Span:
    """Enabled-path span context: one slotted object per span (half
    the cost of a generator-based contextmanager on the wake path)."""

    __slots__ = ("_tracer", "_name", "_t0")

    def __init__(self, tracer_: Tracer, name: str):
        self._tracer = tracer_
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._tracer.record(
            self._name, (time.perf_counter() - self._t0) * 1e3)
        return False


def _annotation(name: str):
    import jax.profiler             # enabled leaf path only

    return jax.profiler.TraceAnnotation(name)


class _LeafSpan(_Span):
    """A leaf phase: the histogram span plus a TraceAnnotation of the
    same name and duration."""

    __slots__ = ("_ann",)

    def __enter__(self):
        self._ann = _annotation(self._name)
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


tracer = Tracer()                     # process-wide default

