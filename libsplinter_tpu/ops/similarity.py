"""Pallas TPU similarity kernels over the store's vector lane.

Replaces the reference CLI's brute-force scalar scan — cosine + euclidean
per candidate computed one float at a time on the CPU
(splinter_cli_cmd_search.c:43-62,374-412; SURVEY.md §3.4) — with a fused
TPU kernel:

  scores tile = (vectors tile  @  queries^T) combined with row norms,
  bloom/regex prefilter applied as a -inf mask inside the kernel,
  then jax.lax.top_k over the fused score matrix.

The vector lane is the store's struct-of-arrays (nslots, dim) float32
matrix, staged to HBM once and re-staged incrementally (dirty rows only)
by the engine.  The kernel runs blocked over N rows; queries are small and
live in VMEM for every block.

On non-TPU backends the same math runs as plain jnp (XLA fuses it fine on
CPU for tests); the pallas path is selected automatically on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.devtime import DEVTIME, close_mark

NEG_INF = -1e30


def _pad_to(x: jnp.ndarray, n: int, axis: int, value=0) -> jnp.ndarray:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _scores_kernel(vec_ref, q_ref, qnorm_ref, mask_ref, out_ref, *,
                   mxu_bf16: bool):
    """One N-tile: fused cosine scores for all queries.

    vec_ref:  (TN, D) f32 vectors tile
    q_ref:    (Q, D)  f32 queries (replicated per block)
    qnorm_ref:(1, Q)  f32 query L2 norms
    mask_ref: (TN, 1) f32 1.0 = candidate, 0.0 = filtered out
    out_ref:  (TN, Q) f32 cosine scores (NEG_INF where filtered)

    mxu_bf16 runs the dot in bfloat16 with f32 accumulation — 2x MXU
    throughput; ~3 decimal digits of score precision, plenty for ranking
    (norms and the divide stay f32).
    """
    v = vec_ref[:]
    if mxu_bf16:
        dots = jnp.dot(v.astype(jnp.bfloat16),
                       q_ref[:].astype(jnp.bfloat16).T,
                       preferred_element_type=jnp.float32)
    else:
        dots = jnp.dot(v, q_ref[:].T, preferred_element_type=jnp.float32)
    vnorm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))       # (TN,1)
    denom = jnp.maximum(vnorm * qnorm_ref[:], 1e-12)              # (TN,Q)
    cos = dots / denom
    # zero rows (un-embedded slots) are excluded HERE, from the norm the
    # kernel already computed in VMEM — a host-side nonzero pre-pass
    # would re-read the whole lane from HBM per query
    keep = (mask_ref[:] > 0.0) & (vnorm > 0.0)                    # (TN,1)
    out_ref[:] = jnp.where(keep, cos, NEG_INF)


# splint: ignore[SPL205] reason=runs inside the registered top-k programs (searcher.topk / searcher.fused_topk); the outer program is the attribution point
@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "mxu_bf16"))
def _cosine_scores_pallas(vectors, queries, mask, *, block_n: int,
                          interpret: bool, mxu_bf16: bool = False):
    n, d = vectors.shape
    q = queries.shape[0]
    qnorm = jnp.linalg.norm(queries, axis=-1, keepdims=True).T    # (1, Q)
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_scores_kernel, mxu_bf16=mxu_bf16),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((q, d), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, q), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_n, q), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, q), jnp.float32),
        interpret=interpret,
    )(vectors, queries, qnorm, mask)


def _cosine_scores_jnp(vectors, queries, mask, vnorm=None):
    dots = vectors @ queries.T
    if vnorm is None:
        vnorm = jnp.linalg.norm(vectors, axis=-1, keepdims=True)
    else:
        vnorm = jnp.asarray(vnorm, jnp.float32).reshape(-1, 1)
    qnorm = jnp.linalg.norm(queries, axis=-1, keepdims=True).T
    cos = dots / jnp.maximum(vnorm * qnorm, 1e-12)
    keep = (mask > 0.0) & (vnorm > 0.0)   # zero rows: never candidates
    return jnp.where(keep, cos, NEG_INF)


def cosine_scores(vectors, queries, mask=None, *, block_n: int = 1024,
                  use_pallas: bool | None = None,
                  mxu_bf16: bool = False, vnorm=None) -> jnp.ndarray:
    """(N, D) vectors x (Q, D) queries -> (N, Q) cosine scores.

    mask: optional (N,) {0,1} prefilter (bloom/regex filtered candidates);
    filtered rows score NEG_INF.  Rows of all zeros (empty slots) also
    score NEG_INF — the exclusion comes from the row norm, computed
    in-kernel (pallas) or from `vnorm` when the caller staged it.
    vnorm: optional precomputed (N,) row L2 norms (lane-static data — a
    StagedLane maintains them O(dirty) so repeated queries skip the
    full-lane norm pass; ignored by the pallas path, whose kernel gets
    the norms for free from the VMEM tile).
    mxu_bf16 (pallas path only, opt-in): bf16 matmul inputs, f32
    accumulation — 2x MXU throughput at ~2e-2 absolute score error.
    Ranking-equivalent in practice, but absolute scores feed user-facing
    --similarity thresholds, so exact f32 stays the default.
    """
    vectors = jnp.asarray(vectors, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim == 1:
        queries = queries[None, :]
    n, d = vectors.shape
    if mask is None:
        mask_col = jnp.ones((n, 1), jnp.float32)
    else:
        mask_col = jnp.asarray(mask, jnp.float32).reshape(n, 1)
    # zero-vector (un-embedded slot) exclusion happens inside the score
    # computation from the row norms it already needs — no extra
    # full-lane pass here

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not use_pallas:
        return _cosine_scores_jnp(vectors, queries, mask_col, vnorm)

    # pad N to the block, Q to the lane width, D to 128 for clean tiling
    q = queries.shape[0]
    n_pad = -(-n // block_n) * block_n
    q_pad = max(8, -(-q // 8) * 8)
    d_pad = -(-d // 128) * 128
    v = _pad_to(_pad_to(vectors, n_pad, 0), d_pad, 1)
    qs = _pad_to(_pad_to(queries, q_pad, 0), d_pad, 1)
    m = _pad_to(mask_col, n_pad, 0)
    out = _cosine_scores_pallas(v, qs, m, block_n=min(block_n, n_pad),
                                interpret=False, mxu_bf16=mxu_bf16)
    return out[:n, :q]


@functools.lru_cache(maxsize=None)
def _scatter_rows_norms_fn():
    def scatter(arr, norms, rows, vals, nvals):
        # vals may arrive in a narrower wire dtype (f16): upcast
        # on-device where it is free; norms are exact f32 from the host
        arr = arr.at[rows].set(vals.astype(arr.dtype))
        norms = norms.at[rows].set(nvals.astype(norms.dtype))
        return arr, norms

    # ledger-only registration: the donated in-place result has no
    # host collect point, so no device window is taken (a dangling
    # mark would just be overwritten) — compile events still attribute
    return DEVTIME.register("searcher.scatter",
                            jax.jit(scatter, donate_argnums=(0, 1)))


def scatter_rows_with_norms(arr, norms, rows, vals, nvals):
    """Fused in-place row update of a staged lane AND its row-norm
    vector in ONE device dispatch (donated buffers — the old two-call
    path paid two dispatches per refresh chunk and briefly held two
    copies of the lane).  Shapes: arr (N, D), norms (N,), rows (B,)
    int32, vals (B, D) any float dtype, nvals (B,) f32.  The (B, D)
    shape must come from a fixed bucket set or every distinct dirty
    count jit-compiles a fresh scatter."""
    return _scatter_rows_norms_fn()(arr, norms, rows, vals, nvals)


@functools.lru_cache(maxsize=None)
def _scatter_rows_norms_ring_fn():
    def scatter(arr, norms, rows_ring, vals_ring, nvals_ring, n):
        def body(carry):
            i, arr, norms = carry
            arr = arr.at[rows_ring[i]].set(
                vals_ring[i].astype(arr.dtype))
            norms = norms.at[rows_ring[i]].set(
                nvals_ring[i].astype(norms.dtype))
            return i + 1, arr, norms

        _, arr, norms = jax.lax.while_loop(
            lambda c: c[0] < n, body, (jnp.int32(0), arr, norms))
        return arr, norms

    # ledger-only registration (see _scatter_rows_norms_fn)
    return DEVTIME.register("searcher.scatter_ring",
                            jax.jit(scatter, donate_argnums=(0, 1)))


def scatter_rows_with_norms_ring(arr, norms, rows_ring, vals_ring,
                                 nvals_ring, n_valid: int):
    """Resident-ring variant of scatter_rows_with_norms: ONE device
    dispatch applies up to `depth` pre-staged same-bucket scatter
    chunks (lax.while_loop over the occupied ring slots — occupancy
    is a scalar operand, so one compiled program per (depth, B, D)
    shape serves 1..depth and never touches empty slots).  Shapes:
    rows_ring (depth, B) int32, vals_ring (depth, B, D) any float
    dtype, nvals_ring (depth, B) f32.  Big refreshes whose chunk plan
    repeats a bucket stop paying one runtime round trip per chunk —
    the engine/resident.py amortization, applied to lane staging.
    Chunks within one refresh touch disjoint rows, so loop order
    inside the ring cannot change the result."""
    return _scatter_rows_norms_ring_fn()(
        arr, norms, rows_ring, vals_ring, nvals_ring,
        jnp.int32(n_valid))


def euclidean_distances(vectors, queries, mask=None) -> jnp.ndarray:
    """(N, D) x (Q, D) -> (N, Q) euclidean distances (inf where masked).
    Computed from norms + dot so it reuses the same fused matmul shape."""
    vectors = jnp.asarray(vectors, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    if queries.ndim == 1:
        queries = queries[None, :]
    dots = vectors @ queries.T
    v2 = jnp.sum(vectors * vectors, axis=-1, keepdims=True)
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True).T
    d2 = jnp.maximum(v2 + q2 - 2.0 * dots, 0.0)
    dist = jnp.sqrt(d2)
    if mask is not None:
        keep = jnp.asarray(mask, jnp.float32).reshape(-1, 1) > 0
        dist = jnp.where(keep, dist, jnp.inf)
    return dist


@functools.lru_cache(maxsize=32)
def _topk_fn(k: int, batch: bool, use_pallas: bool, mxu_bf16: bool,
             block_n: int):
    """One jitted program for score + top-k: the eager per-op dispatch
    over an (N, D) lane costs more than the math on CPU (and leaves
    fusion on the table on TPU), so the whole path compiles once per
    (k, flags, block_n) and is cached.  Callers normalize block_n to
    the default on the non-pallas path (where it is ignored) so
    distinct values don't compile identical programs."""

    def run(vectors, queries, mask, vnorm):
        scores = cosine_scores(vectors, queries, mask,
                               use_pallas=use_pallas, mxu_bf16=mxu_bf16,
                               vnorm=vnorm, block_n=block_n)
        if batch:
            # the batched programs have one shape: no selection passes
            # here, so the fused program's [passes, tiles] reads 0, 0
            return (*jax.lax.top_k(scores.T, k),
                    jnp.zeros(2, jnp.int32))
        return jax.lax.top_k(scores[:, 0], k)

    return DEVTIME.register("searcher.topk", jax.jit(run))


# ---------------------------------------------------------------------------
# fused streaming top-k: score + select in ONE kernel, O(k*Q) output
# ---------------------------------------------------------------------------

# Above this k the iterative in-kernel selection (k VPU passes per
# N-tile) stops paying for the saved HBM traffic; larger k falls back
# to the score-matrix + lax.top_k path.  The CLI's fetch-k growth
# schedule (8, 64, 512) crosses this at its third step.
FUSED_K_MAX = 128

# Up to this many queries a fused dispatch costs ONE scan of the lane
# whatever its width.  Q is the minor (lane) dimension of everything
# the kernel computes after the matmul — the (TN, Q) scores, the
# (K, Q) accumulator, the k_pad selection passes — and a vector
# register is 8 x 128, so 8 query rows and 128 fill the same
# registers; a second lane tile at 256 doubles every selection pass.
# With k_pad passes a tile, on the v5e over 1,572,864 x 768 rows, a
# dispatch took 17.6 ms at 8 queries, 20.1 at 128 and 31.4 at 256;
# since the selection runs only where a tile has entrants (~1 pass a
# tile) the scan is bound by the lane's bytes at every width: 8.82 ms
# at 8, 8.83 at 128, 8.89 at 256, 8.99 at k 64 where the fixed 64
# passes took 74.2 (PERF.md section 6, PR 46).  The search daemon's
# middle batch width is this name (engine/searcher.qb_buckets).
FUSED_Q_LANE = 128


def _fused_topk_kernel(vec_ref, q_ref, qnorm_ref, mask_ref,
                       out_s_ref, out_i_ref, passes_ref, tile_ref, *,
                       k_pad: int, block_n: int, mxu_bf16: bool):
    """One N-tile of the streaming top-k.

    vec_ref:  (TN, D) f32 vectors tile
    q_ref:    (Q, D)  f32 queries (replicated per block)
    qnorm_ref:(1, Q)  f32 query L2 norms
    mask_ref: (TN, 1) f32 1.0 = candidate, 0.0 = filtered out
    out_s_ref:(K, Q)  f32 running top-k scores, sorted desc per query
    out_i_ref:(K, Q)  i32 matching GLOBAL row indices (-1 = filler)
    passes_ref:(1, 1) i32 selection passes run so far (SMEM)
    tile_ref: (TN, Q) f32 VMEM scratch: the tile's scores, taken rows
              masked out as the passes go

    The output blocks map every grid step to block (0, 0), so they
    stay resident across the sequential N-tiles and act as the running
    accumulator.  A tile's score ENTERS a query's accumulator iff it
    is strictly greater than the query's k_pad-th score (the
    accumulator's last row; NEG_INF while it holds fillers, which a
    masked or zero-norm row's NEG_INF never beats).  Accumulator rows
    come from earlier rows of the scan and win ties, so strict `>` is
    lax.top_k's stable smallest-index tie-break, and the fused path is
    rank-identical to the reference score-matrix path.

    The selection runs as many passes as the tile has entrants, not
    k_pad of them: one column max over the tile says whether any query
    has one (none: the accumulator is not touched); then, while some
    query still has one, a pass takes each query's largest remaining
    score (first row on ties), inserts it into that query's sorted
    column — its place is the count of accumulator scores >= it, the
    rows below shift down one, the last falls off — and masks it out
    of the tile.  The threshold rises with every insert, so a tile
    runs at most k_pad passes.  In a lane in no particular order that
    is k_pad in the first tile and none or one in most later ones
    (~0.8 a tile at 48 live queries over 1,536 tiles), and up to ~4
    passes hide under the tile's DMA.  The worst case, a lane ASCENDING
    in a query's score, runs k_pad passes in every tile and costs what
    the old fixed k_pad passes did (19.3 ms against 20.1 on the v5e at
    1,572,864 x 768, k_pad 16; PERF.md section 6, PR 46)."""
    i = pl.program_id(0)
    last = k_pad - 1

    @pl.when(i == 0)
    def _init():
        out_s_ref[:] = jnp.full(out_s_ref.shape, NEG_INF, jnp.float32)
        out_i_ref[:] = jnp.full(out_i_ref.shape, -1, jnp.int32)
        passes_ref[0, 0] = 0

    v = vec_ref[:]
    if mxu_bf16:
        dots = jnp.dot(v.astype(jnp.bfloat16),
                       q_ref[:].astype(jnp.bfloat16).T,
                       preferred_element_type=jnp.float32)
    else:
        dots = jnp.dot(v, q_ref[:].T, preferred_element_type=jnp.float32)
    vnorm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))      # (TN,1)
    denom = jnp.maximum(vnorm * qnorm_ref[:], 1e-12)
    cos = dots / denom
    keep = (mask_ref[:] > 0.0) & (vnorm > 0.0)
    scores = jnp.where(keep, cos, NEG_INF)                       # (TN,Q)
    tile_ref[:] = scores

    def entrants(best):
        # does any query's best remaining score beat its k_pad-th
        return jnp.max((best > out_s_ref[last:, :]).astype(jnp.int32))

    def one_pass(carry):
        n, best, _ = carry                                       # best (1,Q)
        cs = tile_ref[:]
        pos = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 0)
        # float-equality against the max is exact, and "first pos" is
        # what makes ties index-stable
        first = jnp.min(jnp.where(cs == best, pos, block_n), axis=0,
                        keepdims=True)                           # (1,Q)
        cs = jnp.where(pos == first, NEG_INF, cs)
        tile_ref[:] = cs
        acc_s, acc_i = out_s_ref[:], out_i_ref[:]
        kpos = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 0)
        place = jnp.sum((acc_s >= best).astype(jnp.int32), axis=0,
                        keepdims=True)                           # (1,Q)
        # a query whose best is no entrant has place == k_pad: its
        # column is written back as it was
        stay = kpos < place
        put = kpos == place
        out_s_ref[:] = jnp.where(
            stay, acc_s, jnp.where(put, best, pltpu.roll(acc_s, 1, 0)))
        out_i_ref[:] = jnp.where(
            stay, acc_i, jnp.where(put, first + i * block_n,
                                   pltpu.roll(acc_i, 1, 0)))
        best = jnp.max(cs, axis=0, keepdims=True)
        return n + 1, best, entrants(best)

    best = jnp.max(scores, axis=0, keepdims=True)
    n, _, _ = jax.lax.while_loop(
        lambda carry: carry[2] > 0, one_pass,
        (jnp.int32(0), best, entrants(best)))
    passes_ref[0, 0] += n


@functools.lru_cache(maxsize=32)
def _fused_topk_fn(k: int, block_n: int, mxu_bf16: bool,
                   interpret: bool):
    """Compiled fused score+select program, cached per static config
    (query count and lane shape retrace under the same jit).  Returns
    run(vectors, queries, mask, vnorm) -> ((Q, k) scores, (Q, k)
    GLOBAL indices, (2,) int32 [selection passes run, tiles scanned]),
    filler entries (fewer than k candidates) carry score NEG_INF and
    index -1.  vnorm is accepted for signature parity with _topk_fn
    and ignored — the kernel gets row norms for free from the VMEM
    tile."""
    k_pad = max(8, -(-k // 8) * 8)

    def run(vectors, queries, mask, vnorm):
        del vnorm
        vectors = jnp.asarray(vectors, jnp.float32)
        queries = jnp.asarray(queries, jnp.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        n, d = vectors.shape
        q = queries.shape[0]
        if mask is None:
            mask_col = jnp.ones((n, 1), jnp.float32)
        else:
            mask_col = jnp.asarray(mask, jnp.float32).reshape(n, 1)
        n_pad = -(-n // block_n) * block_n
        q_pad = max(8, -(-q // 8) * 8)
        d_pad = -(-d // 128) * 128
        v = _pad_to(_pad_to(vectors, n_pad, 0), d_pad, 1)
        qs = _pad_to(_pad_to(queries, q_pad, 0), d_pad, 1)
        m = _pad_to(mask_col, n_pad, 0)
        qnorm = jnp.linalg.norm(qs, axis=-1, keepdims=True).T    # (1,Qp)
        block = min(block_n, n_pad)
        grid = (n_pad // block,)
        out_s, out_i, passes = pl.pallas_call(
            functools.partial(_fused_topk_kernel, k_pad=k_pad,
                              block_n=block, mxu_bf16=mxu_bf16),
            grid=grid,
            in_specs=[
                pl.BlockSpec((block, d_pad), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((q_pad, d_pad), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, q_pad), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((block, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((k_pad, q_pad), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((k_pad, q_pad), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((k_pad, q_pad), jnp.float32),
                jax.ShapeDtypeStruct((k_pad, q_pad), jnp.int32),
                jax.ShapeDtypeStruct((1, 1), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((block, q_pad), jnp.float32)],
            interpret=interpret,
        )(v, qs, qnorm, m)
        select = jnp.stack([passes[0, 0], jnp.int32(grid[0])])
        return out_s[:k, :q].T, out_i[:k, :q].T, select

    return DEVTIME.register("searcher.fused_topk", jax.jit(run))


def topk_program(k: int, *, batched: bool = True,
                 use_pallas: bool | None = None, mxu_bf16: bool = False,
                 block_n: int = 1024, fused: bool | None = None,
                 interpret: bool = False):
    """The compiled (vectors, queries, mask, vnorm) -> (scores, indices)
    top-k program — the surface the search daemon pre-compiles its
    QB-bucketed batch programs from.  A batched program returns a
    third value, the (2,) int32 [selection passes, tiles] the fused
    kernel ran (0, 0 from the score-matrix path, which runs none).

    fused=None auto-selects: the streaming Pallas kernel whenever the
    pallas path is on and k <= FUSED_K_MAX — the (N, Q) score matrix
    then never exists in HBM and only O(k*Q) leaves the chip; larger k
    (or the jnp backend) takes the score-matrix + lax.top_k path.
    batched=False returns (k,)-shaped results for one query (legacy
    cosine_topk contract); the fused program is always batched and the
    wrapper slices.  interpret runs the kernel in Pallas interpret
    mode (CPU tier-1 parity tests)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if fused is None:
        fused = (use_pallas or interpret) and k <= FUSED_K_MAX
    if not fused:
        if interpret:
            # interpret is the fused kernel's CPU test mode; the
            # legacy fallback's CPU oracle is the jnp math
            use_pallas = False
        return _topk_fn(k, batched, bool(use_pallas), bool(mxu_bf16),
                        int(block_n) if use_pallas else 1024)
    fn = _fused_topk_fn(int(k), int(block_n), bool(mxu_bf16),
                        bool(interpret))
    if batched:
        return fn

    def single(vectors, queries, mask, vnorm):
        s, i, _ = fn(vectors, queries, mask, vnorm)
        return s[0], i[0]

    return single


def cosine_topk(vectors, query, k: int, mask=None, *,
                use_pallas: bool | None = None, mxu_bf16: bool = False,
                vnorm=None, block_n: int = 1024,
                fused: bool | None = None, interpret: bool = False
                ) -> tuple[np.ndarray, np.ndarray]:
    """Top-k most-similar rows for one query.  Returns (scores, indices),
    scores NEG_INF-padded when fewer than k candidates exist (the fused
    path marks filler indices -1; the legacy path leaves them
    arbitrary — filter on score, not index).
    block_n: pallas N-tile (rows of the lane resident in VMEM per grid
    step); the default suits the 1M x 768 target, kernels-phase sweeps
    measure alternatives.  fused=None auto-selects the streaming
    score+select kernel on the pallas path for k <= FUSED_K_MAX."""
    k = min(k, int(np.asarray(vectors.shape[0])))
    fn = topk_program(k, batched=False, use_pallas=use_pallas,
                      mxu_bf16=mxu_bf16, block_n=block_n, fused=fused,
                      interpret=interpret)
    top_s, top_i = fn(vectors, query, mask, vnorm)
    # one combined fetch: device_get starts both host copies async
    # before blocking, so scores+indices cost ONE runtime round trip,
    # not two sequential np.asarray fetches (the difference between
    # 1x and 2x RTT per query on a remote runtime)
    out = tuple(jax.device_get((top_s, top_i)))
    close_mark(DEVTIME.take_mark("searcher.topk"))
    close_mark(DEVTIME.take_mark("searcher.fused_topk"))
    return out


def cosine_topk_batch(vectors, queries, k: int, mask=None, *,
                      use_pallas: bool | None = None,
                      mxu_bf16: bool = False, vnorm=None,
                      block_n: int = 1024, fused: bool | None = None,
                      interpret: bool = False
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Top-k per query.  Returns (Q, k) scores and indices."""
    k = min(k, int(np.asarray(vectors.shape[0])))
    fn = topk_program(k, batched=True, use_pallas=use_pallas,
                      mxu_bf16=mxu_bf16, block_n=block_n, fused=fused,
                      interpret=interpret)
    top_s, top_i, _ = fn(vectors, queries, mask, vnorm)
    out = tuple(jax.device_get((top_s, top_i)))
    close_mark(DEVTIME.take_mark("searcher.topk"))
    close_mark(DEVTIME.take_mark("searcher.fused_topk"))
    return out
