"""Learned sparse attention over a page group: a token attends the
`topk` keys an INDEXER selects for it and no others (the "lightning
indexer" of the DeepSeek-V3.2-Exp report, as models/afmoe.py's
`Indexer` setting serves it).  Three device stages, each a Pallas
kernel with a name a device trace keeps (benchmark/readers/trace_dsa):

  index scan    `dsa_index_scan[_stack]`   I[t, s] = sum_j w[t, j] *
                ReLU(qI[t, j] . kI[s]) over EVERY indexer key of the
                row's pages — the third pool of the page group, one
                head of `DI` a token, kept a token a COLUMN
                (n_blocks, L, 1, DI, page): DI = 64 half-fills the lanes
                of a row-major page (ops/paged_attention, "VALUES A
                TOKEN A COLUMN").  One program reads SCAN_PAGES pages
                of the row (the pool is handed in that many times, each
                with its own page of the table): a 16 KB page costs a
                grid step what 128 KB does.
  selection     `dsa_select[_stack]`   EXACT top-k a query: the k-th
                largest score is found by bisection on the scores' bit
                patterns (32 counts over the row), the ties at it are
                cut by bisection on the POSITION (the lower position
                wins), and the answer is a 0/1 row over the keys.  A
                query that sees `topk` keys or fewer takes them all.
                No sort, no approximation, no block granularity.
  attention     `dsa_sparse_decode` (one token a row) /
                `dsa_sparse_stack` (a suffix's tokens): the page
                group's online softmax (ops/paged_attention's window
                kernel without a window or a sink) under the
                selection's mask — it walks the pages and attends the
                selected keys alone.  A suffix's tokens walk every page
                of their row.  A decode step walks by GROUPS
                (ops/page_groups, made on the host a chunk dispatch at
                a time): the rows whose tables begin with the same run
                of pages — the same document of the prefix tree — read
                that run ONCE, 8 pages a program, their queries stacked
                against it (members x heads-a-kv-head rows in the two
                products), each member under its own selection row and
                its own length, its own softmax in its own scratch
                rows; the pages behind the run are a member's own and
                are read for it alone, by the program a row without a
                sharer runs for all its pages.  The same sums a row,
                whatever the grouping.  Reading the selected token
                columns only is NOT what the pool's layout offers: a
                selected key of one kv head is 256 contiguous bytes, a
                layer-step of 32 rows would be 524,288 copies of 256 B
                (PERF.md section 7).

`indexed_attention` puts them together for one layer and keeps the
DENSE path beside them: a row whose last query sees `topk` keys or
fewer selects every key, and takes ops/paged_attention's
window_paged_attention itself — bit for bit what a layer without an
indexer computes.  Each path runs under a lax.cond on whether any live
row needs it, so a batch of long rows never walks the dense kernel's
grid and a batch of short ones never scans.

On a backend that is no TPU the same math runs as plain jnp (tests
drive the kernels with interpret=True).  FORWARD only; float pools.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .page_groups import FIRST, GROUP_ROWS, LAST, LIVE
from .paged_attention import NEG_INF, stack_block, window_paged_attention

# pages of a row one program of the index scan reads
SCAN_PAGES = 8
# score rows one program of the selection holds (the float32 tile)
SELECT_ROWS = 8
# pages one program of the attention reads: (a decode step, a stack of
# tokens); both divide SCAN_PAGES, so a selection row covers the walk
ATTEND_PAGES = (8, 4)
INT_MIN = -(1 << 31)
# the selection keeps a few whole (rows, keys) arrays in fast memory
# (8 x 33,792 keys are 1 MB each), the stack kernel a 1,024-row block
KERNEL_VMEM = 64 << 20


def _use_pallas(interpret: bool, force_pallas: bool) -> bool:
    return force_pallas or interpret or jax.default_backend() == "tpu"


def scan_width(pages: int, page: int) -> int:
    """Keys a row of the index scan's scores holds: the table's pages
    rounded up to whole programs."""
    return -(-pages // SCAN_PAGES) * SCAN_PAGES * page


# ----------------------------------------------------------- index scan

def _scan_kernel(tab_ref, len_ref, layer_ref, q_ref, w_ref, *rest,
                 page: int, heads: int, block_tokens: int):
    """One (row, query block, SCAN_PAGES pages) program.  q_ref: (1,
    block_tokens x heads, DI) token-major; w_ref: the same rows x 1,
    f32; rest: the SCAN_PAGES pages (1, 1, 1, DI, page) the index maps
    routed here, then out_ref (1, block_tokens, SCAN_PAGES x page)
    f32.  A page wholly past the keys the block's last query sees is
    left unwritten: the selection masks by position before it reads a
    score."""
    ik_refs, out_ref = rest[:SCAN_PAGES], rest[SCAN_PAGES]
    b, qb, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    end = len_ref[b] + (qb + 1) * block_tokens - 1
    for g in range(SCAN_PAGES):
        @pl.when((p * SCAN_PAGES + g) * page < end)
        def _score(g=g):
            s = jnp.dot(q_ref[0], ik_refs[g][0, 0, 0],
                        preferred_element_type=jnp.float32)
            r = jnp.maximum(s, 0.0) * w_ref[0]        # (tokens x heads, page)
            out_ref[0, :, g * page:(g + 1) * page] = \
                jnp.sum(r, 0, keepdims=True) if block_tokens == 1 else \
                jnp.sum(r.reshape(block_tokens, heads, page), 1)


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk / completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _scan_pallas(q2, w2, ik_pool, tables, lengths, layer, *, heads: int,
                 interpret: bool):
    """q2: (B, tokens x heads, DI) token-major; w2: (B, tokens x heads,
    1) f32; ik_pool: (n_blocks, L, 1, DI, page); tables (B, P); lengths
    (B,); layer (1,).  Returns (B, tokens, scan_width) f32."""
    B, RH, DI = q2.shape
    tokens = RH // heads
    page = ik_pool.shape[4]
    P = tables.shape[1]
    steps = -(-P // SCAN_PAGES)
    tq = stack_block(tokens, heads)

    def _q_map(b, qb, p, *pre):
        return (b, qb, 0)

    def _page_map(g):
        def at(b, qb, p, tab, lens, lay):
            return (tab[b, jnp.minimum(p * SCAN_PAGES + g, P - 1)],
                    lay[0], 0, 0, 0)
        return at

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, tokens // tq, steps),
        in_specs=[
            pl.BlockSpec((1, tq * heads, DI), _q_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq * heads, 1), _q_map,
                         memory_space=pltpu.VMEM),
            *(pl.BlockSpec((1, 1, 1, DI, page), _page_map(g),
                           memory_space=pltpu.VMEM)
              for g in range(SCAN_PAGES))],
        out_specs=pl.BlockSpec(
            (1, tq, SCAN_PAGES * page),
            lambda b, qb, p, *pre: (b, qb, p), memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, page=page, heads=heads,
                          block_tokens=tq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (B, tokens, steps * SCAN_PAGES * page), jnp.float32),
        interpret=interpret,
        # the decode step's kernels and a suffix's are told apart by
        # name in a device trace (benchmark/readers/trace_dsa)
        name="dsa_index_scan" if tokens == 1 else "dsa_index_scan_stack",
    )(tables, lengths, layer, q2, w2, *([ik_pool] * SCAN_PAGES))


def index_scores(qi, w, ik_pool, tables, lengths, *, layer,
                 interpret: bool = False, force_pallas: bool = False):
    """The indexer's score of every query against every key of its
    row's pages.  qi: (B, S, HI, DI) in the pool's dtype; w: (B, S, HI)
    float32; ik_pool: (n_blocks, L, 1, DI, page); tables: (B, P);
    lengths: (B,) — query s of row b sees keys j < lengths[b] + s (the
    kernel skips pages past the last of them; nothing else is
    masked); layer: int32 scalar.  Returns (B, S, scan_width) float32:
    I[b, s, j] = sum_h w[b, s, h] * ReLU(qi[b, s, h] . kI[j])."""
    B, S, HI, DI = qi.shape
    page = ik_pool.shape[4]
    T = scan_width(tables.shape[1], page)
    tables = jnp.asarray(tables, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if not _use_pallas(interpret, force_pallas):
        keys = ik_pool[:, layer, 0][tables]           # (B, P, DI, page)
        keys = keys.transpose(0, 2, 1, 3).reshape(B, DI, -1)
        s = jnp.einsum("bshd,bdt->bsht", qi, keys,
                       preferred_element_type=jnp.float32)
        out = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], 2)
        return jnp.pad(out, ((0, 0), (0, 0), (0, T - out.shape[-1])))
    return _scan_pallas(
        qi.reshape(B, S * HI, DI),
        w.astype(jnp.float32).reshape(B, S * HI, 1), ik_pool, tables,
        jnp.asarray(lengths, jnp.int32), layer.reshape(1), heads=HI,
        interpret=interpret)


# ------------------------------------------------------------ selection

def _select_kernel(x_ref, lim_ref, out_ref, *, topk: int, pos_bits: int):
    """SELECT_ROWS queries' scores (rows, T) f32 and how many keys each
    sees, lim (rows, 1) int32 -> (rows, T) f32: 1.0 at the `topk`
    largest scores among keys j < lim (every one of them where lim <=
    topk), ties at the last rank to the lower position, 0.0 elsewhere."""
    f32 = jnp.float32
    x, lim = x_ref[...], lim_ref[...]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # a float's bits, made to order as the floats do under a SIGNED
    # integer compare (-0.0, a negative weight on a product the ReLU
    # cut, ranks with 0.0); a key no query sees sorts below every score
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x),
                                        jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = jnp.where(pos < lim, key, jnp.int32(INT_MIN))
    k = f32(topk)

    # (no boolean array outlives the expression that makes it: Mosaic
    # carries none through a loop)
    def count(mask):
        return jnp.sum(jnp.where(mask, 1.0, 0.0), 1, keepdims=True)

    # the k-th largest key, bit by bit from the top in the UNSIGNED
    # order (u = key ^ INT_MIN): the largest u with count(key >= u) >= k
    def value_bit(i, thr_u):
        cand = thr_u | jnp.left_shift(jnp.int32(1), 31 - i)
        enough = count(key >= (cand ^ jnp.int32(INT_MIN))) >= k
        return jnp.where(enough, cand, thr_u)
    thr = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.zeros_like(lim)) ^ jnp.int32(INT_MIN)
    need = k - count(key > thr)         # ties to take: >= 1 where lim > k

    # the largest position bound q with fewer than `need` ties before
    # it: the ties at positions <= q are exactly the first `need`
    def pos_bit(i, q):
        cand = q | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
        before = count(jnp.logical_and(key == thr, pos < cand))
        return jnp.where(before < need, cand, q)
    q = jax.lax.fori_loop(0, pos_bits, pos_bit, jnp.zeros_like(lim))
    chosen = jnp.logical_or(key > thr,
                            jnp.logical_and(key == thr, pos <= q))
    take = jnp.logical_or(lim <= topk, chosen)
    out_ref[...] = jnp.where(jnp.logical_and(take, pos < lim), 1.0, 0.0)


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("topk", "stack", "interpret"))
def _select_pallas(scores, limits, *, topk: int, stack: bool,
                   interpret: bool):
    """scores: (R, T) f32, R a multiple of SELECT_ROWS; limits: (R, 1)
    int32; stack: the rows are a suffix's tokens (the kernel's name).
    Returns (R, T) f32 of 0 / 1."""
    R, T = scores.shape
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk,
                          pos_bits=max(int(T).bit_length(), 1)),
        grid=(R // SELECT_ROWS,),
        in_specs=[pl.BlockSpec((SELECT_ROWS, T), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((SELECT_ROWS, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((SELECT_ROWS, T), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((R, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=KERNEL_VMEM),
        interpret=interpret,
        name="dsa_select_stack" if stack else "dsa_select",
    )(scores, limits)


def select_topk(scores, limits, *, topk: int, interpret: bool = False,
                force_pallas: bool = False):
    """The exact selection.  scores: (B, S, T) float32; limits: (B, S)
    int32, the keys each query sees (its candidates are positions j <
    limit).  Returns (B, S, T) float32, 1.0 at the `topk` candidates of
    largest score — every candidate where limit <= topk — and 0.0
    elsewhere; among equal scores at the last rank the lower position
    is taken."""
    B, S, T = scores.shape
    limits = jnp.asarray(limits, jnp.int32)
    if not _use_pallas(interpret, force_pallas):
        pos = jnp.arange(T)
        valid = pos < limits[..., None]
        order = jnp.argsort(jnp.where(valid, -scores, jnp.inf), axis=-1,
                            stable=True)
        rank = jnp.argsort(order, axis=-1)
        return ((rank < topk) & valid).astype(jnp.float32)
    rows = B * S
    pad = -rows % SELECT_ROWS
    flat = jnp.pad(scores.reshape(rows, T), ((0, pad), (0, 0)))
    lim = jnp.pad(limits.reshape(rows, 1), ((0, pad), (0, 0)))
    return _select_pallas(flat, lim, topk=topk, stack=S > 1,
                          interpret=interpret)[:rows].reshape(B, S, T)


# ------------------------------------------------- attention, selected keys

def _sparse_kernel(tab_ref, len_ref, layer_ref, q_ref, *rest, page: int,
                   pages: int, scale: float, rep: int, block_tokens: int):
    """One (row, kv-head block, query block, `pages` pages) program of
    the page group's online softmax, under a selection.

      q_ref: (1, hb, R, D), R = rep x block_tokens HEAD-major (row r is
      head r // block_tokens of the kv group, token r % block_tokens);
      rest: `pages` key blocks then as many value blocks, each (1, 1,
      hb, page, D | Dv) — the table's pages w x pages .. — then
      sel_ref (1, block_tokens, pages x page) f32, the selection of the
      block's tokens over those pages' keys, out_ref (1, hb, R, Dv)
      and the scratch m_s / l_s (hb, R, 1), acc_s (hb, R, Dv) f32

    Query token t of the stack attends the keys j < length + t that
    its selection names.  (A page past the table's end is the last
    page read again: its positions lie past every limit, so the
    selection holds zeros there.)"""
    k_refs, v_refs = rest[:pages], rest[pages: 2 * pages]
    sel_ref, out_ref, m_s, l_s, acc_s = rest[2 * pages:]
    b, qb, w = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length = len_ref[b]
    t0 = qb * block_tokens
    hb = q_ref.shape[1]
    span = pages * page

    @pl.when(w == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(w * span < length + t0 + (block_tokens - 1))
    def _accumulate():
        shape = (block_tokens, span)
        j = w * span + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        t = t0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        keep = jnp.where(jnp.logical_and(j < length + t,
                                         sel_ref[0] > 0.5), 1.0, 0.0)
        # a token's mask for each of its rep heads
        keep = jnp.broadcast_to(keep, (rep, span)) if block_tokens == 1 \
            else jnp.concatenate([keep] * rep, 0)
        valid = keep > 0.5
        for h in range(hb):
            k = jnp.concatenate([r[0, 0, h] for r in k_refs], 0)
            v = jnp.concatenate([r[0, 0, h] for r in v_refs], 0)
            logits = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev, l_prev = m_s[h], l_s[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, -1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            pexp = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            m_s[h] = m_new
            l_s[h] = l_prev * corr + jnp.sum(pexp, -1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + jnp.dot(
                pexp.astype(v.dtype), v,
                preferred_element_type=jnp.float32)

    @pl.when(w == pl.num_programs(3) - 1)
    def _write():
        l = l_s[...]
        out = jnp.where(l > 0.0, acc_s[...] / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0] = out.astype(out_ref.dtype)


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=(
    "block_tokens", "q_tokens", "interpret"))
def _sparse_pallas(q4, k_pool, v_pool, sel, tables, lengths, layer, *,
                   block_tokens: int, q_tokens: int, interpret: bool):
    """A suffix's tokens (a decode step is _walk_pallas).  q4: (B, KH,
    q_tokens x rep, D), head-major within each query block; k_pool /
    v_pool: (n_blocks, L, KH, page, D | Dv); sel: (B, q_tokens, T)
    f32; tables (B, P); lengths (B,); layer (1,).  Returns (B, KH,
    q_tokens x rep, Dv)."""
    B, KH, RT, D = q4.shape
    page, Dv = v_pool.shape[3:]
    rep = RT // q_tokens
    P = tables.shape[1]
    R = block_tokens * rep
    # a stack of tokens carries one kv head a program
    # (ops/paged_attention._window_pallas) and reads several pages: the
    # grid step, not the page's bytes, is what one page a step costs
    # (PERF.md section 5)
    hb, pages = 1, ATTEND_PAGES[1]

    def _q_map(b, g, qb, w, *pre):
        return (b, g, qb, 0)

    def _kv_map(i):
        def at(b, g, qb, w, tab, lens, lay):
            return (tab[b, jnp.minimum(w * pages + i, P - 1)], lay[0], g,
                    0, 0)
        return at

    def kv_specs(width):
        return [pl.BlockSpec((1, 1, hb, page, width), _kv_map(i),
                             memory_space=pltpu.VMEM)
                for i in range(pages)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KH // hb, q_tokens // block_tokens, -(-P // pages)),
        in_specs=[
            pl.BlockSpec((1, hb, R, D), _q_map, memory_space=pltpu.VMEM),
            *kv_specs(D), *kv_specs(Dv),
            pl.BlockSpec((1, block_tokens, pages * page),
                         lambda b, g, qb, w, *pre: (b, qb, w),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, hb, R, Dv), _q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((hb, R, 1), jnp.float32),
                        pltpu.VMEM((hb, R, 1), jnp.float32),
                        pltpu.VMEM((hb, R, Dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_sparse_kernel, page=page, pages=pages,
                          scale=1.0 / float(np.sqrt(D)), rep=rep,
                          block_tokens=block_tokens),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, RT, Dv), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=KERNEL_VMEM),
        interpret=interpret,
        name="dsa_sparse_stack",
    )(tables, lengths, layer, q4, *([k_pool] * pages), *([v_pool] * pages),
      sel)


def sparse_paged_attention(q, k_pool, v_pool, sel, tables, lengths, *,
                           layer, groups=None, interpret: bool = False,
                           force_pallas: bool = False):
    """Attention of S new tokens a row over the keys their selections
    name, in ONE LAYER of a page group's pool.  q: (B, S, H, D), token
    t at position lengths[b] - 1 + t; k_pool / v_pool: (n_blocks, L,
    KH, page, D | Dv); sel: (B, S, T >= P x page) float32 of 0 / 1;
    tables: (B, P); lengths: (B,); layer: int32 scalar; groups: for a
    decode step (S = 1), ops/page_groups.decode_groups' arrays of
    these tables — the rows that share pages read them together —, or
    None: every row walks its table alone.  Token t attends {j <
    lengths[b] + t: sel[b, t, j] = 1}; a token whose selection names
    no such key reads zeros.  Returns (B, S, H, Dv) in q's dtype."""
    B, S, H, D = q.shape
    KH, page, Dv = v_pool.shape[2:]
    rep = H // KH
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if not _use_pallas(interpret, force_pallas):
        T = tables.shape[1] * page
        kseq = k_pool[:, layer][tables].transpose(0, 2, 1, 3, 4) \
            .reshape(B, KH, T, D)
        vseq = v_pool[:, layer][tables].transpose(0, 2, 1, 3, 4) \
            .reshape(B, KH, T, Dv)
        logits = jnp.einsum(
            "bskrd,bktd->bskrt",
            q.reshape(B, S, KH, rep, D).astype(jnp.float32),
            kseq.astype(jnp.float32)) / np.sqrt(D)
        valid = (jnp.arange(T)[None, None, :] < (
            lengths[:, None, None] + jnp.arange(S)[None, :, None])) \
            & (sel[..., :T] > 0.5)
        logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
        probs = jnp.where(valid[:, :, None, None, :],
                          jax.nn.softmax(logits, axis=-1), 0.0)
        out = jnp.einsum("bskrt,bktd->bskrd", probs.astype(vseq.dtype),
                         vseq)
        return out.reshape(B, S, H, Dv).astype(q.dtype)
    if S == 1:
        out = _walk_pallas(
            q.reshape(B, KH, rep, D), k_pool, v_pool, sel,
            rows_alone(tables) if groups is None else groups, lengths,
            layer.reshape(1), interpret=interpret)
        return out.reshape(B, 1, H, Dv)
    tq = stack_block(S, rep)
    # head-major rows within a query block: (B, KH, blocks, rep, tq, D)
    q4 = q.reshape(B, S // tq, tq, KH, rep, D).transpose(0, 3, 1, 4, 2, 5) \
        .reshape(B, KH, S * rep, D)
    out = _sparse_pallas(q4, k_pool, v_pool, sel, tables, lengths,
                         layer.reshape(1), block_tokens=tq, q_tokens=S,
                         interpret=interpret)
    return out.reshape(B, KH, S // tq, rep, tq, Dv) \
        .transpose(0, 2, 4, 1, 3, 5).reshape(B, S, H, Dv)


# --------------------------------------- the decode walk, a group a program

def _walk_kernel(item_ref, page_ref, rows_ref, len_ref, layer_ref, q_ref,
                 qrow_ref, *rest, page: int, pages: int, scale: float,
                 rep: int):
    """One ITEM of a decode step's walk (ops/page_groups): `pages`
    pages of K and V, attended by every member of the item's group —
    a chunk of the group's shared run, the members' queries stacked
    into one product — or by the one member whose own pages they are,
    which is the program a row alone runs.

      item_ref (4, W): the item's group slot, chunk of the members'
      tables, member (-1: shared) and flags; rows_ref (members, B): a
      slot's member rows; len_ref (B,): keys a row's token sees
      q_ref: (1, hb, members x rep, D), the slot's queries, member-
      major; qrow_ref: (B, hb, rep, D), every row's
      rest: `pages` key blocks then as many value blocks, each (1, 1,
      hb, page, D | Dv); sel_ref (B, 1, pages x page) f32, every
      row's selection over the chunk's keys; out_ref (1, hb, members x
      rep, Dv); the scratch m_s / l_s (hb, members x rep, 1), acc_s
      (hb, members x rep, Dv) f32, a member's rows its own softmax

    A member attends the keys j < its length that ITS selection
    names; a pad and a row of length 0 attend nothing and read
    zeros."""
    k_refs, v_refs = rest[:pages], rest[pages: 2 * pages]
    sel_ref, out_ref, m_s, l_s, acc_s = rest[2 * pages:]
    w = pl.program_id(0)
    g, c, mem, flag = (item_ref[i, w] for i in range(4))
    hb = q_ref.shape[1]
    members = q_ref.shape[2] // rep
    span = pages * page
    j = c * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)

    @pl.when((flag & FIRST) != 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def keep_of(row):
        """(rep, span) f32 of 0 / 1: the keys of the chunk `row`
        attends (no key where row < 0)."""
        at = jnp.maximum(row, 0)
        length = jnp.where(row >= 0, len_ref[at], 0)
        keep = jnp.where(jnp.logical_and(j < length, sel_ref[at] > 0.5),
                         1.0, 0.0)
        return jnp.broadcast_to(keep, (rep, span))

    def attend(q_of, keep, at):
        """The online softmax of the scratch rows `at` over the
        item's keys.  q_of(h): (R, D); keep: (R, span)."""
        valid = keep > 0.5
        for h in range(hb):
            k = jnp.concatenate([r[0, 0, h] for r in k_refs], 0)
            v = jnp.concatenate([r[0, 0, h] for r in v_refs], 0)
            logits = jax.lax.dot_general(
                q_of(h), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev, l_prev = m_s[h, at], l_s[h, at]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, -1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            pexp = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            m_s[h, at] = m_new
            l_s[h, at] = l_prev * corr + jnp.sum(pexp, -1, keepdims=True)
            acc_s[h, at] = acc_s[h, at] * corr + jnp.dot(
                pexp.astype(v.dtype), v,
                preferred_element_type=jnp.float32)

    live = (flag & LIVE) != 0

    @pl.when(jnp.logical_and(live, mem < 0))
    def _shared():
        attend(lambda h: q_ref[0, h],
               jnp.concatenate([keep_of(rows_ref[i, g])
                                for i in range(members)], 0),
               slice(None))

    row = rows_ref[jnp.maximum(mem, 0), g]

    @pl.when(jnp.logical_and(jnp.logical_and(live, mem >= 0),
                             c * span < len_ref[jnp.maximum(row, 0)]))
    def _own():
        attend(lambda h: qrow_ref[jnp.maximum(row, 0), h], keep_of(row),
               pl.ds(pl.multiple_of(mem * rep, rep), rep))

    @pl.when((flag & LAST) != 0)
    def _write():
        l = l_s[...]
        out = jnp.where(l > 0.0, acc_s[...] / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0] = out.astype(out_ref.dtype)


def rows_alone(tables):
    """ops/page_groups.decode_groups' arrays for a batch in which
    every row is a group of one and reads every chunk of its table:
    the walk a row, for a caller that has no groups (traced: the
    tables may be a program's argument).  A dead row's items run and
    attend nothing."""
    B, P = tables.shape
    pages = ATTEND_PAGES[0]
    chunks = -(-P // pages)
    b = jnp.repeat(jnp.arange(B, dtype=jnp.int32), chunks)
    c = jnp.tile(jnp.arange(chunks, dtype=jnp.int32), B)
    flags = LIVE | jnp.where(c == 0, FIRST, 0) \
        | jnp.where(c == chunks - 1, LAST, 0)
    at = jnp.minimum(c[None, :] * pages
                     + jnp.arange(pages, dtype=jnp.int32)[:, None], P - 1)
    rows = jnp.full((GROUP_ROWS, B), -1, jnp.int32).at[0].set(
        jnp.arange(B, dtype=jnp.int32))
    return {"item": jnp.stack([b, c, jnp.zeros_like(b), flags]),
            "pages": tables[b[None, :], at], "rows": rows,
            "slot": jnp.arange(B, dtype=jnp.int32) * GROUP_ROWS}


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _walk_pallas(q4, k_pool, v_pool, sel, groups, lengths, layer, *,
                 interpret: bool):
    """q4: (B, KH, rep, D); k_pool / v_pool: (n_blocks, L, KH, page, D
    | Dv); sel: (B, 1, T) f32; groups: ops/page_groups.decode_groups'
    arrays; lengths (B,); layer (1,).  Returns (B, KH, rep, Dv)."""
    B, KH, rep, D = q4.shape
    page, Dv = v_pool.shape[3:]
    pages = ATTEND_PAGES[0]
    item, ids, rows, slot = (jnp.asarray(groups[k], jnp.int32) for k in (
        "item", "pages", "rows", "slot"))
    members = rows.shape[0]
    R = members * rep
    # a slot's queries stacked member-major; a pad reads row 0's
    stacked = q4[jnp.maximum(rows.T, 0)].transpose(0, 2, 1, 3, 4) \
        .reshape(B, KH, R, D)

    def _slot_map(w, item, *pre):
        return (item[0, w], 0, 0, 0)

    def _kv_map(i):
        def at(w, item, ids, rows, lens, lay):
            return (ids[i, w], lay[0], 0, 0, 0)
        return at

    def kv_specs(width):
        return [pl.BlockSpec((1, 1, KH, page, width), _kv_map(i),
                             memory_space=pltpu.VMEM)
                for i in range(pages)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # the live items alone, where the groups say how many (at
        # least one: a dead item runs and does nothing)
        grid=(jnp.maximum(groups["live"], 1) if "live" in groups
              else item.shape[1],),
        in_specs=[
            pl.BlockSpec((1, KH, R, D), _slot_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((B, KH, rep, D), lambda w, *pre: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            *kv_specs(D), *kv_specs(Dv),
            pl.BlockSpec((B, 1, pages * page),
                         lambda w, item, *pre: (0, 0, item[1, w]),
                         memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, KH, R, Dv), _slot_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((KH, R, 1), jnp.float32),
                        pltpu.VMEM((KH, R, 1), jnp.float32),
                        pltpu.VMEM((KH, R, Dv), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_walk_kernel, page=page, pages=pages,
                          scale=1.0 / float(np.sqrt(D)), rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, R, Dv), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=KERNEL_VMEM),
        interpret=interpret,
        # the name a device trace keeps (benchmark/readers/trace_dsa)
        name="dsa_sparse_decode",
    )(item, ids, rows, lengths, layer, stacked, q4,
      *([k_pool] * pages), *([v_pool] * pages), sel)
    # a row's answer out of its slot; a dead row reads zeros
    out = out.reshape(B, KH, members, rep, Dv).transpose(0, 2, 1, 3, 4) \
        .reshape(B * members, KH, rep, Dv)
    return jnp.where((slot >= 0)[:, None, None, None],
                     out[jnp.maximum(slot, 0)], 0)


# ------------------------------------------------------- whole-page write

def _write_kernel(bid_ref, layer_ref, new_ref, pool_ref, out_ref):
    out_ref[0, 0] = new_ref[0]


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_pallas(pool, pages, bids, layer, *, interpret: bool):
    block = pool.shape[2:]
    zeros = (0,) * len(block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(pages.shape[0],),
        in_specs=[pl.BlockSpec((1, *block), lambda i, *pre: (i, *zeros),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (1, 1, *block), lambda i, bid, lay: (bid[i], lay[0], *zeros),
            memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        _write_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0}, interpret=interpret,
        name="dsa_page_write",
    )(bids, layer, pages, pool)


def write_pages(pool, pages, bids, *, layer, interpret: bool = False,
                force_pallas: bool = False):
    """Whole pages of a suffix into ONE LAYER of a page group's pool,
    in place: pool[bids[i], layer] = pages[i].  pool: (n_blocks, L,
    *block); pages: (N, *block); bids: (N,) int32 (pages sent to the
    trash block 0 may collide); layer: int32 scalar.  A kernel and not
    an XLA scatter: behind the conds of `indexed_attention` nothing at
    the layer's level holds the pools to their layout, and the
    one-page update then asked for them transposed — two pool copies
    in and two out a program (tests/test_chip_compile.py)."""
    bids = jnp.asarray(bids, jnp.int32).reshape(-1)
    layer = jnp.asarray(layer, jnp.int32)
    pages = pages.astype(pool.dtype)
    if not _use_pallas(interpret, force_pallas):
        return pool.at[bids, layer].set(pages)
    return _write_pallas(pool, pages, bids, layer.reshape(1),
                         interpret=interpret)


# ------------------------------------------------------ one layer's call

def indexed_attention(q, qi, w, k_pool, v_pool, ik_pool, tables, lengths,
                      live, *, layer, topk: int, groups=None,
                      interpret: bool = False):
    """A layer's attention under its indexer.  q: (B, S, H, D); qi:
    (B, S, HI, DI) and w: (B, S, HI) float32, the indexer's queries and
    head weights; the three pools of the page group, every new token's
    rows appended already; tables (B, P); lengths (B,): token t of row
    b sees keys j < lengths[b] + t; live (B,) bool: rows whose answer
    is read.  A live row whose LAST token sees at most `topk` keys
    takes the dense kernel (window_paged_attention: the layer without
    an indexer, bit for bit); every other live row scans, selects a
    token and attends its selection — a decode step's rows by the
    `groups` of sparse_paged_attention.  Returns (B, S, H, Dv)."""
    B, S, H, _ = q.shape
    Dv = v_pool.shape[-1]
    lengths = jnp.asarray(lengths, jnp.int32)
    short = lengths + (S - 1) <= topk
    zeros = jnp.zeros((B, S, H, Dv), q.dtype)

    def dense():
        return window_paged_attention(
            q, k_pool, v_pool, tables,
            jnp.where(live & short, lengths, 0), layer=layer,
            interpret=interpret)

    def sparse():
        own = jnp.where(live & ~short, lengths, 0)
        scores = index_scores(qi, w, ik_pool, tables, own, layer=layer,
                              interpret=interpret)
        limits = jnp.where((own > 0)[:, None],
                           own[:, None] + jnp.arange(S)[None, :], 0)
        sel = select_topk(scores, limits, topk=topk, interpret=interpret)
        return sparse_paged_attention(q, k_pool, v_pool, sel, tables, own,
                                      layer=layer, groups=groups,
                                      interpret=interpret)

    o_dense = jax.lax.cond(jnp.any(live & short), dense, lambda: zeros)
    o_sparse = jax.lax.cond(jnp.any(live & ~short), sparse, lambda: zeros)
    return jnp.where(short[:, None, None, None], o_dense, o_sparse)
