"""Device-resident cache of the store's vector lane.

The reference scores candidates by walking every slot's inline embedding
on the CPU per query (splinter_cli_cmd_search.c:374-412).  Round 1 of
this framework replaced the math with a fused TPU kernel but still
re-uploaded the whole (nslots, dim) lane host->HBM on every search — at
the 1M x 768 target that is ~3 GB of transfer per query.

StagedLane makes the lane resident in HBM:

  - first use uploads the full lane once;
  - every refresh() asks the store's change journal which slots moved
    since the lane's own cursor (Store.changed_since), compares only
    those rows' epochs against the epochs they were staged at, gathers
    ONLY the changed rows torn-safely (spt_vec_gather), and scatters
    them into the device array in place (donated buffers, jit'd at a
    few padded update-size buckets).  When the journal cannot answer
    (the writers lapped the cursor, or an entry was claimed and never
    written) the refresh is the full comparison instead: a bulk epoch
    snapshot (spt_epochs — one acquire load per slot in C) diffed
    against every staged epoch.  The journal's own answer chooses;
    nothing else does;
    large dirty sets are CHUNKED through the same fixed bucket set —
    the gather of chunk i+1 overlaps the async device scatter of
    chunk i, padding waste is bounded at 2x, and no dirty count ever
    triggers a fresh jit compile (the r05 cliff: one 8,192-row refresh
    padded to a single 32,768-row scatter and cost 53x the 128-row
    path);
  - searches read the device array directly — zero host->device traffic
    for an unchanged lane, O(changed rows) otherwise.

Rows mid-write when the lane looks (odd epoch / seqlock race) are
REMEMBERED and looked at again on the next refresh: their writer's
journal record is behind the cursor already, so no later read of the
journal would bring them back — same retry discipline as every reader
of the store (sptpu.h EAGAIN contract).  Once a heartbeat the owner
runs audit(): the full comparison, counting what the journal had not
delivered (`lane_audit_rows`; anything but 0 is a missing record).
"""
from __future__ import annotations

import functools
import os

import logging

import numpy as np

from ..obs.devtime import DEVTIME
from ..store import Store

# Update sizes are padded up to one of these bucket sizes so the scatter
# jit-compiles a handful of times, not once per distinct dirty count.
_UPDATE_BUCKETS = (64, 512, 4096, 32768)

# Full-upload chunk budget in bytes (rows are derived from dim).  The
# upload streams the lane chunk-by-chunk instead of materialising a
# host copy of the whole (nslots, dim) matrix: at the 1M x 768 target
# the old full-copy path peaked at ~4x the 6.4 GB lane in host RSS
#; streaming peaks at ~1x (the device copy) plus one
# chunk.
_CHUNK_BYTES = 128 << 20

_MADV_DONTNEED = 4

# staged "epoch" of a row the device does not hold in a stable state
# (mid-write when the lane looked): odd, so it equals no published
# epoch and reads as not live
_UNSTAGED = np.uint64(1)
_NO_ROWS = np.empty(0, np.int64)

log = logging.getLogger("libsplinter_tpu.staged_lane")


@functools.lru_cache(maxsize=1)
def _madvise_ctx():
    """(libc, page_size, enabled) resolved once — _advise_dontneed runs
    per chunk (~50x per 1M-row upload)."""
    import ctypes
    import mmap
    import os as _os

    enabled = _os.environ.get("SPTPU_STAGE_DONTNEED", "1") != "0"
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        libc.madvise.restype = ctypes.c_int
    except Exception:
        libc = None
    return libc, mmap.PAGESIZE, enabled


def _advise_dontneed(view: np.ndarray) -> None:
    """Drop a staged slice's shm pages from THIS process's RSS.  The
    store object is tmpfs-backed and the mapping is MAP_SHARED, so
    MADV_DONTNEED only detaches our PTEs — the data stays in the store
    and refaults on the next access (e.g. an O(dirty) gather).  Page
    alignment spill into neighbouring store regions is harmless for
    the same reason.  Best-effort: failure costs memory, not
    correctness.  Disable with SPTPU_STAGE_DONTNEED=0."""
    import ctypes

    libc, page, enabled = _madvise_ctx()
    if libc is None or not enabled:
        return
    try:
        addr = view.__array_interface__["data"][0]
        a0 = addr & ~(page - 1)
        libc.madvise(ctypes.c_void_p(a0),
                     ctypes.c_size_t(view.nbytes + (addr - a0)),
                     ctypes.c_int(_MADV_DONTNEED))
    except Exception:
        pass


@functools.lru_cache(maxsize=None)
def _chunk_update_fn():
    jax = _get_jax()

    def upd(arr, vals, start):
        # vals may arrive in a narrower wire dtype (f16): the device
        # lane stays f32, so the upcast happens on-device where it is
        # free, not on the host where it would double the transfer
        return jax.lax.dynamic_update_slice(
            arr, vals.astype(arr.dtype), (start, 0))

    # ledger-only registration: the donated in-place result has no
    # host collect point (the scatter pipelines under the next
    # gather), so no device window is taken — compile events still
    # attribute to searcher.stage_update
    return DEVTIME.register("searcher.stage_update",
                            jax.jit(upd, donate_argnums=0))


def _get_jax():
    import jax

    return jax


def _bucket(n: int) -> int:
    for b in _UPDATE_BUCKETS:
        if n <= b:
            return b
    return -(-n // _UPDATE_BUCKETS[-1]) * _UPDATE_BUCKETS[-1]


def _chunk_plan(n: int) -> list[int]:
    """Decompose a dirty count into scatter chunk sizes, every one drawn
    from the fixed _UPDATE_BUCKETS set (so no refresh size ever compiles
    a fresh program) with padding waste bounded at 2x.

    The old single-scatter path padded n up to one bucket: 8,192 dirty
    rows became one 32,768-row scatter — a 4x transfer cliff.
    Chunking keeps cost piecewise-linear: take
    the largest bucket that fits while the remainder is big, stop as
    soon as padding the tail wastes no more than 2x.

      8,192  -> [4096, 4096]               (padded 8,192, exact)
      40,000 -> [32768, 4096, 4096]        (padded 40,960, 1.02x)
      128    -> [64, 64]                   (padded 128; old path: 512)
    """
    out: list[int] = []
    smallest, largest = _UPDATE_BUCKETS[0], _UPDATE_BUCKETS[-1]
    while n > 0:
        if n >= largest:
            out.append(largest)
            n -= largest
            continue
        cover = _bucket(n)               # smallest bucket covering n
        if cover <= 2 * n or cover == smallest:
            out.append(cover)            # tail: padding waste <= 2x
            break
        # waste too big: peel off the largest bucket that fits
        fit = max(b for b in _UPDATE_BUCKETS if b <= n)
        out.append(fit)
        n -= fit
    return out


class StagedLane:
    """Owns the HBM copy of a store's vector lane.

    Thread-compatible (single consumer); create one per long-lived
    process (REPL session, search/embedding daemon) and call refresh()
    before each read of .array — or just use topk(), which does both.
    """

    def __init__(self, store: Store, *, device=None, wire: str | None = None):
        """wire: host->device transfer dtype for staging — "f32"
        (default) ships the lane bit-exact; "f16" halves the staged
        bytes (upcast to f32 on-device; ~1e-3 component quantization,
        ranking-equivalent for cosine top-k).  f16 pays a host-side
        astype per chunk, so it wins when link bandwidth is the
        bottleneck (remote runtimes, DCN-attached hosts) and
        loses nothing but exactness on fast PCIe — hence opt-in.
        Resolved from SPTPU_LANE_WIRE when not passed."""
        if store.vec_dim == 0:
            raise ValueError("store has no vector lane (vec_dim=0)")
        wire = wire or os.environ.get("SPTPU_LANE_WIRE", "f32")
        if wire not in ("f32", "f16"):
            raise ValueError(f"wire {wire!r} not in ('f32', 'f16')")
        self.wire = wire
        self._wire_np = np.float16 if wire == "f16" else np.float32
        self._st = store
        self._device = device
        self._arr = None                 # jax.Array (nslots, dim) f32
        self._norms = None               # jax.Array (nslots,) f32
        self._staged = None              # np.uint64 epoch per staged row
        self._cursor = 0                 # change-journal position read to
        self._carry = _NO_ROWS           # rows seen mid-write: next pass
        self._examined: np.ndarray | None = _NO_ROWS  # take_examined()
        # how the lane learns what moved (always on; the search
        # daemon's heartbeat carries them)
        self.lane_slots_scanned = 0      # epochs a refresh looked at
        self.journal_rows = 0            # distinct rows the journal gave
        self.journal_fallbacks = 0       # refreshes that scanned instead
        self.lane_audit_rows = 0         # rows only audit() found
        # transfer accounting (tests + perf docs read these)
        self.full_uploads = 0
        self.rows_staged = 0             # incremental rows transferred
        self.rows_padded = 0             # incl. bucket padding (wire cost)
        self.refreshes = 0
        self.scatter_chunks = 0          # scatter chunks staged
        self.chunk_hist: dict[int, int] = {}   # bucket size -> count
        # resident-ring staging (engine/resident.py discipline): when
        # a refresh's chunk plan repeats a bucket, up to ring_depth
        # same-shape chunks pre-stage into one ring and ONE device
        # dispatch applies them all (similarity.scatter_rows_with_
        # norms_ring) — big refreshes stop paying one ~63 ms runtime
        # round trip per chunk.  <=1 disables (per-chunk dispatch).
        self.ring_depth = int(os.environ.get("SPTPU_LANE_RING_DEPTH",
                                             "8"))
        self.ring_dispatches = 0         # ring programs dispatched
        self.ring_chunks = 0             # chunks applied inside rings

    # -- staging -----------------------------------------------------------

    def _full_upload(self):
        jax = _get_jax()
        jnp = jax.numpy
        st = self._st
        view = st.vectors
        n, d = view.shape
        # the process's default device (a chip pin sets it), not
        # unconditionally device 0
        dev = (self._device or jax.config.jax_default_device
               or jax.devices()[0])
        # the populate pass (or previous reads) may have the whole lane
        # resident; detach it up front so peak RSS during the upload is
        # one device copy + one chunk, not lane + device copy
        _advise_dontneed(view)
        # the cursor BEFORE the first snapshot: a write during the
        # upload is journaled behind it and found by the next refresh
        self._cursor = st.journal_head()
        e1 = st.epochs()
        chunk = max(4096, _CHUNK_BYTES // max(1, d * 4))
        with jax.default_device(dev):
            arr = jnp.zeros((n, d), jnp.float32)
        upd = _chunk_update_fn()
        # row norms are lane-static: maintained here (per-chunk on
        # upload, O(dirty) on refresh) so queries never pay a
        # full-lane norm pass (ops.similarity's vnorm fast path)
        norms_host = np.empty(n, np.float32)
        for lo in range(0, n, chunk):
            hi = min(lo + chunk, n)
            vals = np.ascontiguousarray(view[lo:hi], dtype=np.float32)
            # norms from the exact f32 data; the wire copy may be f16
            norms_host[lo:hi] = np.linalg.norm(vals, axis=1)
            arr = upd(arr, vals.astype(self._wire_np, copy=False),
                      np.int32(lo))
            _advise_dontneed(view[lo:hi])    # staged; drop our PTEs
        e2 = st.epochs()
        stable = (e1 == e2) & ((e1 & 1) == 0)
        # commit the lane to its device explicitly: the refresh scatter
        # signature must match between (upload-produced arr, committed
        # norms) and its own (committed, committed) outputs, or the
        # first refresh of every bucket shape jit-compiles TWICE (the
        # sharding-committedness is part of jax's cache key)
        self._arr = jax.device_put(arr, dev)
        self._norms = jax.device_put(norms_host, dev)
        # rows that moved mid-copy get the odd sentinel and are looked
        # at again by the next refresh (a stable epoch is always even)
        self._staged = np.where(stable, e1, _UNSTAGED)
        self._carry = np.nonzero(~stable)[0]
        self._examined = None
        self.lane_slots_scanned += 2 * n
        self.journal_fallbacks += 1      # first attach: nothing to read
        self.full_uploads += 1

    def refresh(self):
        """Bring the device lane up to date; returns the jax array."""
        self.refreshes += 1
        if self._arr is None:
            self._full_upload()
            return self._arr
        rows, self._cursor, complete = \
            self._st.changed_since(self._cursor)
        if complete:
            self.journal_rows += rows.size
            if rows.size or self._carry.size:
                cand = np.union1d(rows, self._carry)
                self.lane_slots_scanned += cand.size
                self._settle(cand, self._st.epochs_at(cand))
        else:
            self.journal_fallbacks += 1
            self._scan(self._st.epochs())
        return self._arr

    def _scan(self, eps: np.ndarray) -> None:
        """The full comparison: every slot's epoch against its staged
        one.  The cursor was taken before `eps` (changed_since's
        contract), so what moves after the snapshot is journaled."""
        self.lane_slots_scanned += eps.size
        cand = np.union1d(np.nonzero(eps != self._staged)[0],
                          self._carry)
        self._settle(cand, eps[cand])

    def _settle(self, cand: np.ndarray, eps: np.ndarray) -> None:
        """Bring `cand` rows (sorted, distinct) whose store epochs read
        `eps` up to date: an odd row is marked unstaged and carried to
        the next refresh, an even one that differs is re-staged, an
        equal one (a spurious record) costs the comparison."""
        odd = (eps & np.uint64(1)) != 0
        moved = cand[~odd & (eps != self._staged[cand])]
        self._staged[cand[odd]] = _UNSTAGED
        # remembered BEFORE anything is dispatched: a refresh that dies
        # mid-stage must look at all of them again, and the journal
        # names a row once
        self._carry = cand
        if self._examined is not None:
            self._examined = np.union1d(self._examined, cand)
        torn = self._stage_rows(moved) if moved.size else _NO_ROWS
        self._carry = np.union1d(cand[odd], torn)

    def audit(self) -> int:
        """The full comparison, run where the journal is trusted: find
        every row whose epoch is not the staged one and count those
        the journal had NOT delivered (even, changed, in no record
        since the cursor and not carried).  A missing record is the
        one fault that reads as a right answer, and only this can see
        it.  Whatever it finds is staged, counted (`lane_audit_rows`)
        and logged.  Returns the count; 0 on a lane not yet
        uploaded."""
        if self._arr is None:
            return 0
        eps = self._st.epochs()
        # read AFTER the snapshot: a row that moved before it and was
        # journaled is in this range or was settled earlier
        rows, self._cursor, complete = \
            self._st.changed_since(self._cursor)
        if not complete:
            self.journal_fallbacks += 1
            self._scan(self._st.epochs())
            return 0
        self.journal_rows += rows.size
        differ = np.nonzero(eps != self._staged)[0]
        known = np.union1d(rows, self._carry)
        found = np.setdiff1d(differ, known, assume_unique=True)
        found = found[(eps[found] & np.uint64(1)) == 0]
        if found.size:
            self.lane_audit_rows += found.size
            log.error("lane audit: %d rows moved without a journal "
                      "record (first: %s)", found.size, found[:8])
        cand = np.union1d(differ, known)
        if cand.size:
            self._settle(cand, self._st.epochs_at(cand))
        return int(found.size)

    def take_examined(self) -> np.ndarray | None:
        """Rows whose staged epoch may have changed since the last
        call (sorted, distinct), or None for "any of them" (a full
        upload).  For ONE consumer that keeps state derived from the
        staged epochs — the search daemon's liveness mask."""
        got, self._examined = self._examined, _NO_ROWS
        return got

    def staged_epochs(self, rows: np.ndarray | None = None) -> np.ndarray:
        """The epochs the device's rows were staged at (all of them,
        or the listed rows'); odd = the device holds no stable state
        of that row."""
        return self._staged if rows is None else self._staged[rows]

    def _stage_rows(self, changed: np.ndarray) -> np.ndarray:
        """Incremental re-stage of `changed` rows, chunked through the
        fixed bucket set (_chunk_plan).  Each chunk's scatter is a
        fused vals+norms update on donated buffers
        (ops.similarity.scatter_rows_with_norms); when the plan
        repeats a bucket (big refreshes decompose into runs of the
        largest bucket), up to ring_depth same-shape chunks pre-stage
        into a host-fed ring and ONE resident dispatch applies them
        all — per-refresh dispatch cost amortizes to
        ~floor/ring-occupancy instead of one runtime round trip per
        chunk.  No dirty count ever pads to more than 2x its size or
        compiles a fresh program (ring shapes are (ring_depth, bucket)
        with occupancy a scalar operand)."""
        from .similarity import (scatter_rows_with_norms,
                                 scatter_rows_with_norms_ring)

        st = self._st
        plan = _chunk_plan(int(changed.size))
        depth = max(1, self.ring_depth)
        # per-bucket staging buffers: prepared chunks wait here until
        # a ring fills (or the gather ends) — chunks touch disjoint
        # rows, so applying them out of plan order is safe
        staged: dict[int, list[tuple]] = {}
        torn: list[np.ndarray] = []

        def flush(b: int, group: list[tuple]) -> None:
            """Dispatch one scatter (ring or per-call) and ONLY THEN
            record its rows' staged epochs — a buffered chunk lost to
            a mid-refresh exception must stay dirty, never read as
            current against a stale device row."""
            if len(group) == 1:
                rows_p, vals_p, norms_p, rows, eps = group[0]
                self._arr, self._norms = scatter_rows_with_norms(
                    self._arr, self._norms, rows_p, vals_p, norms_p)
            else:
                rows_ring = np.zeros((depth, b), np.int32)
                vals_ring = np.zeros((depth, b, st.vec_dim),
                                     self._wire_np)
                norms_ring = np.zeros((depth, b), np.float32)
                for j, (rows_p, vals_p, norms_p, _, _) in \
                        enumerate(group):
                    rows_ring[j] = rows_p
                    vals_ring[j] = vals_p
                    norms_ring[j] = norms_p
                self._arr, self._norms = scatter_rows_with_norms_ring(
                    self._arr, self._norms, rows_ring, vals_ring,
                    norms_ring, len(group))
                self.ring_dispatches += 1
                self.ring_chunks += len(group)
            for _, _, _, rows, eps in group:
                self._staged[rows] = eps
                self.rows_staged += len(rows)

        for off, vecs, eps in st.vec_gather_iter(changed, plan):
            ok = eps != Store.GATHER_TORN
            n = int(ok.sum())
            if n < ok.size:
                torn.append(changed[off: off + ok.size][~ok])
            if not n:
                continue
            rows = changed[off: off + ok.size][ok]
            g = vecs if n == ok.size else vecs[ok]
            # the chunk length came from the plan, but torn-row drops
            # may let the remainder fit a smaller precompiled bucket
            b = _bucket(n)
            # pad with a duplicate of row 0 — scatter-set with an
            # identical (row, value) pair is idempotent
            rows_p = np.empty(b, np.int32)
            rows_p[:n] = rows
            rows_p[n:] = rows[0]
            vals_p = np.empty((b, g.shape[1]), self._wire_np)
            vals_p[:n] = g
            vals_p[n:] = g[0]
            # norms from the exact f32 gather (not the wire copy)
            norms_p = np.empty(b, np.float32)
            norms_p[:n] = np.linalg.norm(g, axis=1)
            norms_p[n:] = norms_p[0]
            chunk = (rows_p, vals_p, norms_p, rows, eps[ok])
            if depth > 1:
                buf = staged.setdefault(b, [])
                buf.append(chunk)
                if len(buf) >= depth:
                    flush(b, staged.pop(b))
            else:
                flush(b, [chunk])
            self.rows_padded += b
            self.scatter_chunks += 1
            self.chunk_hist[b] = self.chunk_hist.get(b, 0) + 1
        for b, group in staged.items():
            if group:
                flush(b, group)
        if not torn:
            return _NO_ROWS
        out = np.concatenate(torn)
        self._staged[out] = _UNSTAGED
        return out

    def counters(self) -> dict:
        """Transfer/chunk accounting as flat numerics — the shape
        `spt metrics` and Tracer.render_prom() expose (chunk_hist
        flattens to one field per bucket size)."""
        out = {"full_uploads": self.full_uploads,
               "refreshes": self.refreshes,
               "rows_staged": self.rows_staged,
               "rows_padded": self.rows_padded,
               "scatter_chunks": self.scatter_chunks,
               "ring_dispatches": self.ring_dispatches,
               "ring_chunks": self.ring_chunks,
               "lane_slots_scanned": self.lane_slots_scanned,
               "journal_rows": self.journal_rows,
               "journal_fallbacks": self.journal_fallbacks,
               "lane_audit_rows": self.lane_audit_rows}
        for b, n in sorted(self.chunk_hist.items()):
            out[f"chunks_bucket_{b}"] = n
        return out

    @property
    def array(self):
        """The device lane WITHOUT refreshing (last staged state)."""
        if self._arr is None:
            self._full_upload()
        return self._arr

    @property
    def norms(self):
        """Device (nslots,) row L2 norms of the last staged state."""
        if self._arr is None:
            self._full_upload()
        return self._norms

    def invalidate(self) -> None:
        """Drop the device copy (next use re-uploads in full)."""
        self._arr = None
        self._norms = None
        self._staged = None
        self._carry = _NO_ROWS

    # -- queries -----------------------------------------------------------

    def topk(self, query, k: int, mask=None, **kw):
        """Refresh + fused cosine top-k over the device lane.
        Same contract as ops.similarity.cosine_topk."""
        from .similarity import cosine_topk

        arr = self.refresh()
        kw.setdefault("vnorm", self._norms)
        return cosine_topk(arr, query, k, mask, **kw)

    def scores(self, queries, mask=None, **kw):
        from .similarity import cosine_scores

        arr = self.refresh()
        kw.setdefault("vnorm", self._norms)
        return cosine_scores(arr, queries, mask, **kw)
