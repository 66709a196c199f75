"""Ragged paged attention over LATENT pages (multi-head latent
attention, MLA, in its absorbed form).

A latent-attention layer caches ONE row a token a layer:

    row = [c (kv_rank) | RoPE(k_r) (rope_dim)]          e.g. 512 | 64

and nothing per head.  Expanded, the same token would cost heads x
(qk_dim + v_dim) values (128 x 320 against 576 at the published
widths of the models that use it, 71x).  Decode therefore attends IN
THE LATENT SPACE: the caller folds each head's key up-projection into
its query (q_lat_h = [q_nope_h W_UK_h^T | q_rope_h], kv_rank +
rope_dim wide) and its value up-projection into the output
(o_h = (p_h c) W_UV_h), and this kernel is what lies between:

    score[h, j] = q_lat[h] . row[j] * scale
    out[h]      = softmax_j(score[h, :]) @ row[:, :kv_rank]

— one shared "kv head" under every query head, so a page's bytes
cross HBM once for all heads of a row and are never expanded there.

A page is stored TRANSPOSED — the pool is (n_blocks, width, page) a
layer, a token a column — because that is how the chip keeps it
anyway: a (n_blocks, page, 576) bfloat16 array gets the page axis as
its minor dimension on a v5e (576 is no multiple of the 128 lanes, 128
is), and a kernel that asked for rows of 576 made XLA copy every pool
to the other layout and back at EVERY dispatch (found by compiling
for a described v5e, PR 26).  Page-minor is also what both matrix
products want: the scores are q @ page, the output p @ page[:rank]^T.
`latent_append` writes a step's new columns in place.

Conventions are ops/paged_attention.py's: block 0 the trash block, an int32
block table (B, P) and ragged lengths (B,) riding scalar prefetch,
an online softmax carried across the page axis in VMEM scratch, pages
wholly past a row's length skipped.  q may carry S stacked tokens a
row (the suffix prefill of a prefix-cache hit, whose S tokens were
appended before the call): token t attends j < lengths[b] + t.

Grid (B, H // G, ceil(P / N)): G heads x S tokens share one program's
(S*G, width) query block, token-major (row // G == token), with G
chosen so that the block stays near 1,024 rows — the whole 128 heads
for a decode step (S == 1), 16 heads for a 64-token suffix stack —,
and a program attends a CHUNK of N table pages: the pool is handed to
the kernel N times, copy i routed to table entry chunk * N + i.  N is
`pages_per_step` of the call's shape and nothing else.  A decode step
takes DECODE_PAGES = 8 (fewer of pages wider than 128 columns: a
chunk spans 1,024 at most): its query block is a row's heads (128 or 32
rows), so a 147 KB page's two products and its bytes are each ~0.2 us
of the chip, and at one page a step the grid step, the mask and the
rescale of the (R, kv_rank) float32 accumulator cost more than the
page did (0.77 us a live page; 0.34 at 8 — PERF.md section 6, PR 49).
The chunk's pages share ONE online-softmax update (one running max,
one `corr`, one rescale; their scores and weighted sums are reduced
elementwise first), unmasked where the row covers the whole chunk; the
chunk that holds the row's end takes its live pages one at a time
under the mask, as every page was taken before, and its pages past the
end do no products.  A stack of tokens keeps N = 1: ~1,024 query rows
make a page's products 2.3 us of a 4.3 us step and its float32 score
tile 512 KB a page.  A table whose width is no multiple of N (66 at
8) is padded with the trash block, whose columns lie past every
length.
Where the rows of one call bring suffixes of their own lengths in one
width (an admission round's hits, models/mla.py), `q_valid` says how
many of a row's S tokens are real and a program skips its query rows'
token blocks (Q_BLOCK_ROWS rows each) past that count: a row of 20
tokens in a 64-token width runs 2 of its 4 blocks, a pad row none.

On non-TPU backends the same math runs as plain jnp over a gathered
page view; tests run the kernel itself with interpret=True, and
tests/test_chip_compile.py compiles it at the published widths for a
described v5e.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (tokens x heads) one program holds: bounds the VMEM
# accumulator at rows x kv_rank f32 (2 MiB at 1,024 x 512)
MAX_Q_ROWS = 1024


def head_group(heads: int, q_tokens: int) -> int:
    """Heads one program carries: the largest divisor of `heads` that
    keeps q_tokens x heads at or under MAX_Q_ROWS (at least 1)."""
    g = max(1, min(heads, MAX_Q_ROWS // max(q_tokens, 1)))
    while heads % g:
        g -= 1
    return g


# query rows one block of a program's queries holds where a row says
# how many of its stacked tokens are real (q_valid): blocks wholly
# past that count are skipped, each still a full MXU tile
Q_BLOCK_ROWS = 256


def q_blocks(q_tokens: int, group: int) -> int:
    """Token blocks a program's (q_tokens x group) query rows split
    into for the skip of pad tokens: as many as keep a block at
    Q_BLOCK_ROWS rows or more and divide the tokens."""
    return math.gcd(q_tokens, max(1, q_tokens * group // Q_BLOCK_ROWS))


# table pages one grid step of the DECODE face attends (one token a
# row).  On the chip, a call of 64 rows at pangu's shape (128 heads,
# 65 live pages a row) / kimi's (32 heads, 70-100 live pages): 3.29 /
# 3.20 ms at 1, 2.07 / 2.14 at 2, 1.59 / 1.61 at 4, 1.40 / 1.43 at 8,
# 1.38 / 1.54 at 16 (PERF.md section 6, PR 49) — at pages of 128
# columns: a chunk spans DECODE_PAGES x 128 columns at most, so that
# wider pages keep its blocks and score tile in VMEM (8 pages of 1,024
# columns do not fit: compiled for a described v5e)
DECODE_PAGES = 8
DECODE_COLUMNS = DECODE_PAGES * 128


def pages_per_step(q_tokens: int, page: int) -> int:
    """Table pages a grid step attends, by the call's shape alone:
    for a decode step (one token a row) DECODE_PAGES, fewer where
    they would span more than DECODE_COLUMNS columns; one for a stack
    of tokens (the module's docstring says why)."""
    if q_tokens > 1:
        return 1
    return max(1, min(DECODE_PAGES, DECODE_COLUMNS // page))


def _latent_kernel(tab_ref, len_ref, *refs, page: int, pages: int,
                   scale: float, group: int, kv_rank: int, blocks: int):
    """One (batch row, head group, chunk of `pages` table pages)
    program.

    tab_ref: (B, P) SMEM block table;  len_ref: (B,) SMEM lengths;
    with blocks > 1 a third prefetched operand, (B,) SMEM: how many of
    the row's stacked tokens are real
    q_ref:   (1, 1, R, W) this row's folded queries, R = S*group,
             token-major;  kv_refs: `pages` blocks (1, W, page), the
             pages the table routed here (a token a column), W =
             kv_rank + rope_dim
    out_ref: (1, 1, R, kv_rank)
    m_s/l_s: (R, 1) f32 running max / sum;  acc_s: (R, kv_rank) f32

    A chunk every query sees whole takes ONE online-softmax update
    over its pages, unmasked; the chunk that holds a row's end takes
    its live pages one at a time under the mask; a chunk past the end
    is skipped.
    """
    nv_ref = refs[0] if blocks > 1 else None
    q_ref = refs[-(pages + 5)]
    kv_refs = refs[-(pages + 4):-4]
    out_ref, m_s, l_s, acc_s = refs[-4:]
    b = pl.program_id(0)
    c = pl.program_id(2)
    length = len_ref[b]
    R = q_ref.shape[2]
    q_tokens = R // group
    # the row's real tokens: the last of them attends furthest
    n_real = q_tokens if nv_ref is None else nv_ref[b]
    first = c * (pages * page)          # the chunk's first column
    end = length + (n_real - 1)         # columns the last token sees

    @pl.when(c == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def accumulate(rows, t0: int, cols, masked: bool):
        """The chunk's pages `cols` into the running softmax of query
        rows `rows`, whose first token is the row's t0-th: one max,
        one rescale of the accumulator, whatever the pages."""
        n = rows.stop - rows.start
        q = q_ref[0, 0, rows]                           # (n, W)
        kvs = [kv_refs[i][0] for i in cols]             # (W, page) each
        logits = [jnp.dot(q, kv, preferred_element_type=jnp.float32)
                  * scale for kv in kvs]                # (n, page) each
        if masked:
            j = jax.lax.broadcasted_iota(jnp.int32, (n, page), 1)
            t = t0 + jax.lax.broadcasted_iota(jnp.int32, (n, page), 0) \
                // group
            valid = [(first + i * page + j) < (length + t) for i in cols]
            logits = [jnp.where(v, x, NEG_INF)
                      for v, x in zip(valid, logits)]
        m_prev, l_prev = m_s[rows], l_s[rows]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, logits), -1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pexp = [jnp.exp(x - m_new) for x in logits]
        if masked:
            pexp = [jnp.where(v, x, 0.0) for v, x in zip(valid, pexp)]
        m_s[rows] = m_new
        l_s[rows] = l_prev * corr + jnp.sum(
            functools.reduce(jnp.add, pexp), -1, keepdims=True)
        acc = acc_s[rows] * corr
        for x, kv in zip(pexp, kvs):
            acc = acc + jax.lax.dot_general(
                x.astype(kv.dtype), kv[:kv_rank],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc_s[rows] = acc

    def attend(cols, masked: bool):
        if blocks == 1:
            accumulate(slice(0, R), 0, cols, masked)
        else:
            tb = q_tokens // blocks
            for i in range(blocks):
                pl.when(i * tb < n_real)(functools.partial(
                    accumulate, slice(i * tb * group,
                                      (i + 1) * tb * group), i * tb,
                    cols, masked))

    if pages == 1:
        pl.when(first < end)(functools.partial(attend, (0,), True))
    else:
        # every query of the block sees every column of the chunk
        whole = first + pages * page <= length

        @pl.when(whole)
        def _inside():
            attend(range(pages), False)

        @pl.when(jnp.logical_and(jnp.logical_not(whole), first < end))
        def _boundary():
            for i in range(pages):
                pl.when(first + i * page < end)(functools.partial(
                    attend, (i,), True))

    @pl.when(c == pl.num_programs(2) - 1)
    def _write():
        l = l_s[...]
        out = jnp.where(l > 0.0, acc_s[...] / jnp.maximum(l, 1e-30),
                        0.0)
        out_ref[0, 0] = out.astype(out_ref.dtype)


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk / completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("kv_rank", "scale", "group",
                                             "interpret"))
def _latent_pallas(q4, pool, tables, lengths, q_valid=None, *,
                   kv_rank: int, scale: float, group: int,
                   interpret: bool):
    """q4: (B, H//group, S*group, W); pool: (n_blocks, W, page);
    tables: (B, P) int32; lengths: (B,) int32; q_valid: None or (B,)
    int32, a row's real tokens.
    Returns (B, H//group, S*group, kv_rank)."""
    B, NG, R, W = q4.shape
    page = pool.shape[2]
    blocks = 1 if q_valid is None else q_blocks(R // group, group)
    prefetch = (tables, lengths) if blocks == 1 \
        else (tables, lengths, q_valid)
    pages = pages_per_step(R // group, page)
    chunks = -(-tables.shape[1] // pages)
    if chunks * pages != tables.shape[1]:
        # a table that is no whole number of chunks ends in the trash
        # block, whose columns lie past every length
        prefetch = (jnp.pad(tables, ((0, 0), (
            0, chunks * pages - tables.shape[1]))),) + prefetch[1:]

    def _q_map(b, g, c, *pre):
        return (b, g, 0, 0)

    def _kv_map(i):
        def at(b, g, c, *pre):
            return (pre[0][b, c * pages + i], 0, 0)
        return at

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, NG, chunks),
        in_specs=[
            pl.BlockSpec((1, 1, R, W), _q_map, memory_space=pltpu.VMEM),
            # the pool once a page of the chunk, each copy routed to
            # its own entry of the table
            *(pl.BlockSpec((1, W, page), _kv_map(i),
                           memory_space=pltpu.VMEM)
              for i in range(pages)),
        ],
        out_specs=pl.BlockSpec((1, 1, R, kv_rank), _q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, kv_rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, page=page, pages=pages,
                          scale=scale, group=group, kv_rank=kv_rank,
                          blocks=blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NG, R, kv_rank), q4.dtype),
        interpret=interpret,
        # the decode step's kernel and the suffix stack's are told
        # apart by name in a device trace (benchmark/readers)
        name=("latent_decode_attention" if R == group
              else "latent_stack_attention"),
    )(*prefetch, q4, *([pool] * pages))


def _latent_ref(q, pool, tables, lengths, *, kv_rank: int, scale: float):
    """Reference math (and the non-TPU serving path): gather every
    table page into a dense (B, P*page, W) view, masked softmax.
    q: (B, S, H, W)."""
    B, S, H, W = q.shape
    seq = pool[tables].transpose(0, 1, 3, 2).reshape(B, -1, W)  # (B,T,W)
    T = seq.shape[1]
    logits = jnp.einsum("bshw,btw->bsht", q.astype(jnp.float32),
                        seq.astype(jnp.float32)) * scale
    valid = jnp.arange(T)[None, None, :] \
        < (lengths[:, None, None] + jnp.arange(S)[None, :, None])
    logits = jnp.where(valid[:, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bsht,btr->bshr", probs.astype(seq.dtype),
                     seq[..., :kv_rank])
    return out.astype(q.dtype)


def latent_paged_attention(q, pool, tables, lengths, *, kv_rank: int,
                           scale: float, q_valid=None,
                           interpret: bool = False,
                           force_pallas: bool = False):
    """Ragged paged attention in the latent space (FORWARD only).

    q: (B, H, W) — one folded query a row, at position lengths[b]-1
    (call after appending the step's latent row) — or (B, S, H, W)
    for S stacked tokens whose rows are all appended already: token t
    attends keys j < lengths[b] + t;
    pool: (n_blocks, W, page) latent pages, a token a column,
    W = kv_rank + rope_dim;
    tables: (B, P) int32; lengths: (B,) int32 (ops/paged_attention's
    contract, trash block 0 included); q_valid: None or (B,) int32 —
    how many of a row's S stacked tokens are real, where rows bring
    suffixes of their own lengths in one width: the kernel skips the
    token blocks past it (what it returns for a pad token is not
    defined, and finite).
    Returns q's leading shape with kv_rank last: the probability-
    weighted latent, to be taken through each head's value
    up-projection by the caller."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, S, H, W = q.shape
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if force_pallas or interpret or jax.default_backend() == "tpu":
        g = head_group(H, S)
        # token-major within a head group: row t*g + i is token t,
        # head (group index)*g + i
        q4 = q.reshape(B, S, H // g, g, W).transpose(0, 2, 1, 3, 4) \
              .reshape(B, H // g, S * g, W)
        out = _latent_pallas(
            q4, pool, tables, lengths,
            None if q_valid is None else jnp.asarray(q_valid, jnp.int32),
            kv_rank=kv_rank, scale=float(scale), group=g,
            interpret=interpret)
        out = out.reshape(B, H // g, S, g, kv_rank) \
                 .transpose(0, 2, 1, 3, 4).reshape(B, S, H, kv_rank)
    else:
        out = _latent_ref(q, pool, tables, lengths, kv_rank=kv_rank,
                          scale=scale)
    return out[:, 0] if squeeze else out


def _append_kernel(bid_ref, off_ref, new_ref, pool_ref, out_ref):
    """Token i of the flattened (row, stack position) list: its page
    comes in whole, column off[i] takes the new latent, the page goes
    back.  Consecutive tokens of one page (a suffix stack) keep
    writing the block that is already resident."""
    i = pl.program_id(0)
    fresh = jnp.logical_or(
        i == 0, bid_ref[i] != bid_ref[jnp.maximum(i - 1, 0)])

    @pl.when(fresh)
    def _load():
        out_ref[...] = pool_ref[...]

    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    out_ref[0] = jnp.where(col == off_ref[i],
                           jnp.broadcast_to(new_ref[0], out_ref.shape[1:]),
                           out_ref[0])


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _append_pallas(pool, new, bids, offs, *, interpret: bool):
    """pool: (n_blocks, W, page), updated in place (aliased); new:
    (N, W, 1); bids/offs: (N,) int32."""
    N = new.shape[0]
    _, W, page = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, W, 1), lambda i, *pre: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, W, page), lambda i, *pre: (pre[0][i], 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, W, page),
                               lambda i, *pre: (pre[0][i], 0, 0),
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        _append_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="latent_append",
    )(bids, offs, new, pool)


def latent_append(pool, latent, bids, offs, *, interpret: bool = False,
                  force_pallas: bool = False):
    """Write new tokens' latents into their pages: pool[bids[i], :,
    offs[i]] = latent[i].  pool: (n_blocks, W, page); latent: (..., W);
    bids/offs: latent's leading shape, int32.  Tokens of one page must
    be adjacent in the flattened order (a row's stack positions are);
    tokens sent to the trash block 0 may collide freely."""
    W = pool.shape[1]
    lat = latent.reshape(-1, W).astype(pool.dtype)
    b = jnp.asarray(bids, jnp.int32).reshape(-1)
    o = jnp.asarray(offs, jnp.int32).reshape(-1)
    if force_pallas or interpret or jax.default_backend() == "tpu":
        return _append_pallas(pool, lat[:, :, None], b, o,
                              interpret=interpret)
    return pool.at[b, :, o].set(lat)
