"""Pod-sharded similarity search: the arena's vector lane is sharded
row-wise across the mesh; each device computes local top-k with the
similarity kernel, then an all-gather over ICI merges the per-shard
candidates — exactly the scale-out path the reference deliberately lacks
(RDMA-hostile: splinter_stress.c:358-359; SURVEY.md §2.7 TPU mapping).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.similarity import FUSED_K_MAX, _fused_topk_fn, cosine_scores


@functools.lru_cache(maxsize=64)
def _topk_program(mesh: Mesh, axis: str, local_n: int, d: int, nq: int,
                  k_local: int, k_final: int, use_pallas: bool,
                  mxu_bf16: bool = False, interpret: bool = False):
    """Compiled sharded top-k, cached per (mesh, shapes, k) so repeated
    queries from a live session don't re-trace/re-compile."""
    # pallas path: the local pass runs the STREAMING fused kernel —
    # each shard's (local_n, Q) score matrix never exists in HBM, and
    # only k_local candidate (score, index) pairs per shard feed the
    # ICI merge.  The jnp fallback (CPU tests) keeps the score-matrix
    # + lax.top_k shape, where XLA fuses it anyway.
    fused = (use_pallas or interpret) and k_local <= FUSED_K_MAX

    def local_then_merge(v_local, q, m_local):
        if fused:
            ls, li, _ = _fused_topk_fn(k_local, 1024, mxu_bf16,
                                       interpret)(v_local, q, m_local,
                                                  None)
            s, i = ls[0], li[0]
        else:
            # local fused scores + top-k on this shard
            scores = cosine_scores(v_local, q, m_local,
                                   use_pallas=use_pallas,
                                   mxu_bf16=mxu_bf16)
            s, i = jax.lax.top_k(scores[:, 0], k_local)
        # globalize indices by shard offset (fused-path filler rows,
        # index -1 at score NEG_INF, stay below every real candidate
        # in the merge and are dropped by callers' score filter)
        shard = jax.lax.axis_index(axis)
        gi = jnp.where(i >= 0, i + shard * local_n, -1)
        # all-gather candidates over ICI, merge, re-top-k
        all_s = jax.lax.all_gather(s, axis)      # (m, k_local)
        all_i = jax.lax.all_gather(gi, axis)     # (m, k_local)
        ms, mi = jax.lax.top_k(all_s.reshape(-1), k_final)
        return ms, all_i.reshape(-1)[mi]

    fn = shard_map(
        local_then_merge, mesh=mesh,
        in_specs=(P(axis, None), P(), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_topk(mesh: Mesh, vectors, query, k: int, mask=None,
                 axis: str = "dp", use_pallas: bool | None = None,
                 mxu_bf16: bool = False, interpret: bool = False
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Top-k over row-sharded vectors.

    vectors: (N, D) logically; physically sharded (N/m, D) per device on
    `axis`.  Returns (scores, GLOBAL indices) of the top k.
    """
    n, d = vectors.shape
    m = mesh.shape[axis]
    assert n % m == 0, "row count must divide the mesh axis"
    local_n = n // m
    # each shard can contribute at most local_n candidates; the merged
    # result still returns up to min(k, n) rows
    k_local = min(k, local_n)
    k_final = min(k, n)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"

    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    query = jnp.asarray(query, jnp.float32)
    if query.ndim == 1:
        query = query[None, :]
    fn = _topk_program(mesh, axis, local_n, d, query.shape[0],
                       k_local, k_final, bool(use_pallas),
                       bool(mxu_bf16), bool(interpret))
    if not isinstance(vectors, jax.Array):
        # a host matrix goes shard by shard to its devices; through
        # jnp.asarray the whole lane would land on device 0 first
        vectors = shard_vectors(
            mesh, np.asarray(vectors, np.float32), axis)
    s, i = fn(vectors.astype(jnp.float32), query,
              jnp.asarray(mask, jnp.float32))
    return np.asarray(s), np.asarray(i)


def shard_vectors(mesh: Mesh, vectors, axis: str = "dp"):
    """Place a host (N, D) matrix row-sharded over the mesh axis."""
    return jax.device_put(
        vectors, NamedSharding(mesh, P(axis, None)))


class PodSearch:
    """End-to-end pod-sharded search over per-host store lanes.

    Every TPU-VM worker runs this SPMD-style with its OWN host-local
    store (SURVEY.md §2.7): each host's (nslots, dim) vector lane —
    zero-padded to the mesh tile — becomes this host's block of one
    global row-sharded device matrix.  Row addressing: global row g
    lives on host g // local_pad at local slot g % local_pad (every
    host's lane is padded to the SAME local_pad, validated at init).
    search() runs the fused local top-k + ICI all-gather merge on the
    mesh, then resolves winning global rows back to (host, key) with
    one DCN process_allgather of the owning hosts' key bytes — device
    data rides ICI, only control/keys ride DCN.

    Staging is epoch-diffed: a refresh with no store writes costs one
    scalar DCN allgather and touches no device data; updates scatter
    only the changed rows into the donated device matrix (same economy
    as ops.StagedLane).  The multi-process path is collectively
    incremental: hosts allgather their dirty COUNTS,
    agree on a shared padded bucket, and every host runs ONE scatter
    program carrying its own changed rows (out-of-bounds sentinel rows
    from less-dirty hosts are dropped by the scatter) — O(max dirty)
    per refresh, never a full restage of every host's lane.

    Single-process (process_count == 1) degrades to sharding the one
    local lane across the local mesh axis — same code path the
    dryrun exercises on the virtual CPU mesh.
    """

    def __init__(self, store, mesh: Mesh | None = None, *,
                 axis: str = "dp"):
        from .mesh import make_mesh
        from .multihost import init_distributed, process_span

        init_distributed()
        self.store = store
        self.axis = axis
        self.mesh = mesh or make_mesh()
        self.pid, self.pcount = process_span()
        self.local_n = store.nslots
        m = self.mesh.shape[axis]
        if m % self.pcount:
            raise ValueError(
                f"mesh axis {axis}={m} not divisible by "
                f"{self.pcount} processes")
        per_host_shards = m // self.pcount
        # pad each host's block with zero rows to the shard tile; zero
        # vectors are never candidates (cosine_scores nonzero mask)
        self.local_pad = -(-self.local_n // per_host_shards) * \
            per_host_shards
        self.per_host_shards = per_host_shards
        self.tile = self.local_pad // per_host_shards
        self.global_n = self.local_pad * self.pcount
        if self.pcount > 1:
            # global-row arithmetic (host = g // local_pad, key resolve,
            # make_array_from_process_local_data's global shape) is only
            # sound if every worker has the same geometry — a mismatched
            # store would yield silently misattributed results.
            from jax.experimental import multihost_utils
            geo = np.asarray(multihost_utils.process_allgather(
                np.array([self.local_n, self.local_pad,
                          store.vec_dim], np.int64)))
            geo = geo.reshape(self.pcount, 3)
            if not (geo == geo[0]).all():
                raise ValueError(
                    "PodSearch requires identical store geometry on "
                    "every worker; got per-host (nslots, local_pad, "
                    f"vec_dim) = {geo.tolist()}")
        self._arr = None
        self._staged: np.ndarray | None = None   # epochs rows staged at
        # transfer accounting (tests + perf docs)
        self.full_stages = 0
        self.rows_staged = 0

    # -- staging -----------------------------------------------------------

    def _gather_local(self) -> np.ndarray:
        """Full torn-safe local lane, zero-padded to local_pad rows.
        Rows mid-write stage as zeros this pass (never candidates) and
        re-stage next refresh via their unchanged staged epoch."""
        rows = np.arange(self.local_n, dtype=np.uint32)
        vecs, eps = self.store.vec_gather(rows)
        torn = eps == self.store.GATHER_TORN
        vecs[torn] = 0.0
        staged = np.where(torn, np.uint64(1), eps)   # odd = restage
        if self.local_pad != self.local_n:
            vecs = np.pad(vecs,
                          ((0, self.local_pad - self.local_n), (0, 0)))
        return vecs, staged

    def _place(self, local: np.ndarray):
        sharding = NamedSharding(self.mesh, P(self.axis, None))
        if self.pcount == 1:
            return shard_vectors(self.mesh, local, self.axis)
        return jax.make_array_from_process_local_data(
            sharding, local, (self.global_n, local.shape[1]))

    def refresh(self):
        """Bring the sharded matrix up to date (epoch-diffed)."""
        if self._arr is None:
            local, self._staged = self._gather_local()
            self._arr = self._place(local)
            self.full_stages += 1
            return self._arr
        e = self.store.epochs()
        changed = np.nonzero(e != self._staged)[0]
        if self.pcount > 1:
            # collective O(dirty) update: rows are PACKED per device
            # shard, so the pod only needs to agree on the max dirty
            # count any single device sees — the scatter then ships
            # per_host_shards * bucket(max_per_device) rows per host,
            # ~per_host_shards x less than bucketing on per-host totals
            # when writes spread across shards.
            from jax.experimental import multihost_utils
            if changed.size:
                dev_counts = np.bincount(changed // self.tile,
                                         minlength=self.per_host_shards)
                local_max = int(dev_counts.max())
            else:
                local_max = 0
            counts = np.asarray(multihost_utils.process_allgather(
                np.array([local_max], np.int32))).ravel()
            maxc = int(counts.max())
            if maxc == 0:
                return self._arr
            bucket = _bucket(maxc)
            # past the point where the scatter ships as many rows as the
            # lane holds, a full restage is strictly cheaper (bulk
            # load).  Every host sees the same maxc, so the branch is
            # collectively consistent.
            if bucket * self.per_host_shards >= self.local_pad:
                local, self._staged = self._gather_local()
                self._arr = self._place(local)
                self.full_stages += 1
            else:
                self._collective_scatter(changed, bucket)
            return self._arr
        if changed.size:
            vecs, eps = self.store.vec_gather(
                changed.astype(np.uint32))
            ok = eps != self.store.GATHER_TORN
            rows = changed[ok]
            if rows.size:
                self._arr = _scatter_sharded(
                    self._arr, jnp.asarray(rows.astype(np.int32)),
                    jnp.asarray(vecs[ok]))
                self._staged[rows] = eps[ok]
                self.rows_staged += int(rows.size)
        return self._arr

    def _collective_scatter(self, changed: np.ndarray, bucket: int):
        """Multi-process incremental restage: scatter this host's changed
        rows (packed per device shard, padded to the pod-agreed per-device
        `bucket`) into the sharded matrix.

        Every worker executes the SAME program (SPMD discipline); devices
        with fewer dirty rows than the bucket pad with an out-of-bounds
        sentinel slot that the scatter drops.  Rows torn mid-gather stage
        as zeros with an odd staged epoch (never candidates, retried next
        refresh) — identical semantics to the full stage."""
        d = self.store.vec_dim
        rows = changed.astype(np.uint32)
        staged_eps = None
        if rows.size:
            vecs, eps = self.store.vec_gather(rows)
            torn = eps == self.store.GATHER_TORN
            vecs[torn] = 0.0
            staged_eps = np.where(torn, np.uint64(1), eps)
        else:
            vecs = np.zeros((0, d), np.float32)

        # per-device rows in shard-local coordinates, packed into the
        # leading columns; sentinel = tile (one past the end -> dropped
        # by mode='drop')
        lrows = np.full((self.per_host_shards, bucket), self.tile,
                        np.int32)
        lvals = np.zeros((self.per_host_shards, bucket, d), np.float32)
        if rows.size:
            dev = rows // self.tile
            off = rows % self.tile
            for dshard in range(self.per_host_shards):
                sel = dev == dshard
                k = int(sel.sum())
                if k:
                    lrows[dshard, :k] = off[sel]
                    lvals[dshard, :k] = vecs[sel]
        m = self.mesh.shape[self.axis]
        sh_r = NamedSharding(self.mesh, P(self.axis, None))
        sh_v = NamedSharding(self.mesh, P(self.axis, None, None))
        grows = jax.make_array_from_process_local_data(
            sh_r, lrows, (m, bucket))
        gvals = jax.make_array_from_process_local_data(
            sh_v, lvals, (m, bucket, d))
        self._arr = _pod_scatter_program(
            self.mesh, self.axis, bucket, self.tile, d)(
                self._arr, grows, gvals)
        # mark rows staged only AFTER the scatter executed: an exception
        # above must leave them dirty so the next refresh retries them
        # (the single-process path has the same ordering)
        if staged_eps is not None:
            self._staged[rows] = staged_eps
        self.rows_staged += int(rows.size)
        return self._arr

    # -- query -------------------------------------------------------------

    def search(self, query, k: int, *, mask=None, refresh: bool = True,
               use_pallas: bool | None = None,
               mxu_bf16: bool = False) -> list[dict]:
        """Global top-k.  Returns [{host, slot, key, similarity}, ...]
        sorted by similarity desc.  mask: optional per-host (nslots,)
        {0,1} candidate prefilter (bloom/regex/scratch exclusion),
        applied on this host's rows.  Must be called collectively (same
        query, same k on every worker) — standard SPMD discipline."""
        if refresh or self._arr is None:
            self.refresh()
        gmask = self._global_mask(mask)
        s, gi = sharded_topk(self.mesh, self._arr, query, k,
                             mask=gmask, axis=self.axis,
                             use_pallas=use_pallas, mxu_bf16=mxu_bf16)
        keep = s > -1e29
        s, gi = s[keep], gi[keep]
        keys = self._resolve_keys(gi)
        out = []
        for score, g, key in zip(s, gi, keys):
            out.append({"host": int(g) // self.local_pad,
                        "slot": int(g) % self.local_pad,
                        "key": key,
                        "similarity": float(score)})
        return out

    def _global_mask(self, local_mask):
        if local_mask is None:
            return None
        lm = np.zeros(self.local_pad, np.float32)
        lm[: self.local_n] = np.asarray(local_mask, np.float32)
        if self.pcount == 1:
            return jax.device_put(
                lm, NamedSharding(self.mesh, P(self.axis)))
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P(self.axis)), lm,
            (self.global_n,))

    def _resolve_keys(self, global_rows: np.ndarray) -> list[str]:
        """Owner hosts contribute key bytes; one DCN allgather merges."""
        from .. import _native as N
        kmax = N.KEY_MAX
        mine = np.zeros((len(global_rows), kmax), np.uint8)
        for j, g in enumerate(np.asarray(global_rows)):
            host = int(g) // self.local_pad
            slot = int(g) % self.local_pad
            if host == self.pid and slot < self.local_n:
                key = self.store.key_at(slot) or ""
                raw = key.encode()[:kmax]
                mine[j, :len(raw)] = np.frombuffer(raw, np.uint8)
        if self.pcount > 1:
            from jax.experimental import multihost_utils
            allk = np.asarray(
                multihost_utils.process_allgather(mine))
            mine = allk.max(axis=0)    # owner's row is the only nonzero
        return [bytes(row[row != 0]).decode(errors="replace")
                for row in mine]


def _bucket(n: int) -> int:
    """Shared pad bucket: few distinct sizes -> few compiled programs."""
    b = 8
    while b < n:
        b *= 8
    return b


@functools.lru_cache(maxsize=64)
def _pod_scatter_program(mesh: Mesh, axis: str, bucket: int, tile: int,
                         d: int):
    """Compiled per-shard scatter for the multi-process incremental
    restage.  Each device owns a (tile, d) block and receives its own
    (bucket,) shard-local row ids + (bucket, d) values; sentinel rows
    (== tile, out of bounds) are dropped."""

    def upd(block, rows, vals):
        return block.at[rows[0]].set(vals[0], mode="drop")

    fn = shard_map(
        upd, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None, None)),
        out_specs=P(axis, None),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=0)


@functools.lru_cache(maxsize=None)
def _scatter_fn():
    @functools.partial(jax.jit, donate_argnums=0)
    def scatter(arr, rows, vals):
        return arr.at[rows].set(vals)
    return scatter


def _scatter_sharded(arr, rows, vals):
    # pad the update to a few bucket sizes so the scatter compiles a
    # handful of times, not per distinct dirty count (cf. StagedLane)
    n = rows.shape[0]
    b = _bucket(n)
    if b != n:
        rows = jnp.concatenate(
            [rows, jnp.broadcast_to(rows[0], (b - n,))])
        vals = jnp.concatenate(
            [vals, jnp.broadcast_to(vals[0], (b - n, vals.shape[1]))])
    return _scatter_fn()(arr, rows, vals)
