"""Ragged paged decode attention over a block-paged KV pool.

The decoder's dense KV cache — per-layer (B, max_len, KH, D) tensors —
makes cache HBM scale with B x max_len regardless of how many tokens
each row actually holds, which is exactly why the continuous-batching
lane capped at batch_cap=8 (r05: 612.3 aggregate tok/s) and had to
share one decode window across the batch.  This module is the TPU-
native fix (Ragged Paged Attention, PAPERS.md arxiv 2604.15464): K/V
live in a GLOBAL page pool

    k_pool / v_pool: (n_blocks, KH, page, D)     per layer

and each batch row owns an int32 block table mapping its logical pages
to pool blocks.  Rows are RAGGED — row r's length is lengths[r], there
is no shared position, no window mask padding, and freeing a row
returns its pages to the pool without touching its neighbours.

The decode kernel (one query token per row) runs on grid
(B, KH, n_pages): the block table rides scalar prefetch so each
program's index map gathers exactly its page of the pool
(pltpu.PrefetchScalarGridSpec — the table lands in SMEM before the
body runs), and a flash-style online softmax (running max / sum /
accumulator in VMEM scratch, carried across the page axis) computes
each row's attention over its OWN length.  Pages wholly past a row's
length are skipped (@pl.when), so compute scales with live tokens,
not table width.  Per (b, kh) program the kv page block is
(1, 1, page, D) — each page's bytes cross HBM once per kv head, and
the (rep, page) logits tile never leaves VMEM.

QUANTIZED pools (k_scales/v_scales given): the pools hold int8 values
with one f32 scale per (page block, kv head) — (n_blocks, KH) — and
the kernel dequantizes IN REGISTER inside the page loop: the scales
ride scalar prefetch alongside the block tables (they are per-page
scalars, exactly what SMEM is for), the K logits pick up scale * ks
on the already-f32 MXU output, and V dequantizes on its VMEM block
before the probability matmul.  HBM traffic per page drops to 1/2 of
bf16 (1/4 of f32) + a scalar, which is the whole point: decode is
memory-bound, so cache bytes ARE tokens/sec (ROADMAP item 4;
PowerInfer arxiv 2312.12456, CPU-inference arxiv 2406.07553).  SMEM
is 1 MiB on a v5e and pads the minor axis of a 2-D operand to 128
lanes, so the whole (n_blocks, KH) table there would cost
n_blocks*512 B per side whatever KH is — the v5e compiler refuses it
from 1,024 pages.  What the dispatch prefetches instead is the scale
of each page its block tables name, gathered by XLA before the call
and flattened to 1-D: B*P*KH*4 bytes per side (batch 64 x 16 pages x
12 kv heads = 48 KiB), independent of the pool's size and bounded
like the block tables themselves.

MULTI-QUERY verify (q_tokens > 1): the speculative-decode verifier
scores gamma+1 draft positions in ONE forward.  The kernel already
carries rep query rows per kv head (GQA); q_tokens stacks the S new
tokens' queries on the same axis — (B, KH, S*rep, D), token-major —
and the ragged mask becomes CAUSAL across the stack: query token t
(rows t*rep..(t+1)*rep) attends keys j < lengths[b] + t.  Appending
the S tokens' K/V before the call (models/decoder.CausalAttention)
makes this exactly a batched draft verification through the paged
pool — no serial fallback, no dense window.

Page size: the page is a WHOLE dimension of the kv block, so any size
works on the chip — `page % 128` was never a rule.  The v5e compiler
takes 1..256 (tests/test_chip_compile.py holds 16, 64 and 256) and
pages of 16 and 64 matched the jnp reference on a v5e for bf16, int8
and int4 pools (PR 21).  128 is the serving default and the size
chip_smoke.py runs.
Block 0 of the pool is reserved by convention as the TRASH block
(models/decoder.PagedKVCache): unallocated table entries point at it,
so gathers of unused pages read garbage that the length mask excludes
and scatters from dead rows land harmlessly.

Rows with lengths == 0 are DON'T-CARE: the kernel returns zeros for
them (every page skipped), the jnp reference returns a uniform average
of trash — consumers (the completion daemon) discard dead rows'
outputs before anything can read them, same contract as the flash
kernels' fully-masked rows.

Prefill is NOT this kernel's job: prompt chunks attend through the
dense bucket programs (ops/flash_attention.causal_flash_attention for
long chunks) and their K/V rows are then scattered into freshly
allocated pages (decoder.CompletionModel.paged_prefill_row) — one
compiled program per bucket, like every other program in the serving
stack.  (Quantized pools quantize on that commit scatter, per page.)

On non-TPU backends the same math runs as plain jnp over a gathered
page view (tests exercise the kernel itself via interpret=True).

Tensor-parallel serving (parallel/serve.py) passes mesh= and the whole
dispatch runs under shard_map: pools sharded on the kv-head axis over
`tp`, each device executing the same program over its KH/tp local
heads — the scales shard WITH their kv heads (axis 1 of (n_blocks,
KH)), so the per-device SMEM scales shrink by tp too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# int4-PACKED pools (kv_dtype="int4"): two 4-bit codes per uint8 byte
# along the head dim — pool shape (n_blocks, KH, page, D//2) — with
# the SAME per-(page block, kv head) f32 scale tables as int8 (the
# layout note above: scales were kept separate exactly so packing is a
# value-layout change only).  Codes are symmetric 4-bit (clip ±7,
# scale = page-absmax/7) stored OFFSET-8 (code+8 in [1, 15]) so both
# nibbles unpack with unsigned ops: lo = byte & 0xF, hi = byte >> 4.
# The split-half convention — byte j holds element j (lo) and element
# j + D//2 (hi) — makes the in-register unpack one lane-dim
# concatenate instead of an interleave.
INT4_QMAX = 7.0
_INT4_BIAS = 8


def pack_int4(q):
    """(..., D) int codes in [-7, 7] -> (..., D//2) uint8, split-half
    nibble layout (lo = element j, hi = element j + D//2)."""
    D = q.shape[-1]
    u = (q.astype(jnp.int32) + _INT4_BIAS).astype(jnp.uint8)
    lo, hi = u[..., :D // 2], u[..., D // 2:]
    return lo | (hi << 4)


def unpack_int4(packed):
    """(..., D//2) uint8 -> (..., D) f32 codes in [-8, 7] (the exact
    inverse of pack_int4 on its range; the kernel does the same two
    ops in register inside the page loop)."""
    lo = (packed & 0xF).astype(jnp.float32) - _INT4_BIAS
    hi = (packed >> 4).astype(jnp.float32) - _INT4_BIAS
    return jnp.concatenate([lo, hi], axis=-1)


def _paged_kernel(*refs, page: int, scale: float, rep: int,
                  q_tokens: int, quantized: bool, packed: bool):
    """One (batch row, kv head, page) program.

    refs (quantized=False):
      tab_ref: (B, P) SMEM block table (scalar prefetch)
      len_ref: (B,)   SMEM row lengths (scalar prefetch)
      q_ref:   (1, 1, R, D) this row's queries for this kv head,
               R = q_tokens*rep, token-major
      k_ref/v_ref: (1, 1, page, D) the page the table routed here
      out_ref: (1, 1, R, D)
      m_s/l_s: (R, 1) f32 running max / sum;  acc_s: (R, D) f32
    refs (quantized=True) insert ksc_ref/vsc_ref — (B*P*KH,) f32 in
    SMEM, the scale of row b's page p for kv head h at
    (b*P + p)*KH + h — after len_ref.

    The page axis is innermost, so the scratch carries the online
    softmax across a row's pages and the output block (revisited per
    page) is written once on the last page.  Query token t attends
    keys j < length + t (causal across the q_tokens stack; t == 0
    reproduces the classic single-token ragged mask).
    """
    if quantized:
        (tab_ref, len_ref, ksc_ref, vsc_ref, q_ref, k_ref, v_ref,
         out_ref, m_s, l_s, acc_s) = refs
    else:
        (tab_ref, len_ref, q_ref, k_ref, v_ref,
         out_ref, m_s, l_s, acc_s) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(p == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # the last query token attends keys j < length + q_tokens - 1:
    # pages wholly past that are dead for the whole stack
    @pl.when(p * page < length + (q_tokens - 1))
    def _accumulate():
        q = q_ref[0, 0]                                 # (R, D)
        R = q.shape[0]
        if quantized:
            n_kh = pl.num_programs(1)
            si = (b * n_pages + p) * n_kh + h
            ks = ksc_ref[si]
            vs = vsc_ref[si]
            if packed:
                # int4: nibble-unpack the (page, D//2) uint8 block in
                # register — two mask/shift ops + a lane concatenate —
                # then the int8 path's scale folding applies unchanged.
                # The bytes widen to int32 first: Mosaic lowers no
                # uint8 -> f32 cast.
                ku = k_ref[0, 0].astype(jnp.int32)
                vu = v_ref[0, 0].astype(jnp.int32)
                k = jnp.concatenate(
                    [(ku & 0xF).astype(jnp.float32),
                     (ku >> 4).astype(jnp.float32)], axis=-1) - 8.0
                v = (jnp.concatenate(
                    [(vu & 0xF).astype(jnp.float32),
                     (vu >> 4).astype(jnp.float32)], axis=-1)
                    - 8.0) * vs
            else:
                k = k_ref[0, 0].astype(jnp.float32)     # (page, D) deq
                v = v_ref[0, 0].astype(jnp.float32) * vs  # in-register
            logits = jnp.dot(q.astype(jnp.float32), k.T,
                             preferred_element_type=jnp.float32) \
                * (scale * ks)
        else:
            k = k_ref[0, 0]                             # (page, D)
            v = v_ref[0, 0]
            logits = jnp.dot(q, k.T,
                             preferred_element_type=jnp.float32) * scale
        j = jax.lax.broadcasted_iota(jnp.int32, (R, page), 1)
        # causal ragged mask: query token t = row // rep sees
        # j < length + t (q_tokens == 1 -> the classic j < length)
        t = jax.lax.broadcasted_iota(jnp.int32, (R, page), 0) // rep
        valid = (p * page + j) < (length + t)
        logits = jnp.where(valid, logits, NEG_INF)

        m_prev, l_prev = m_s[...], l_s[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, -1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pexp = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
        m_s[...] = m_new
        l_s[...] = l_prev * corr + jnp.sum(pexp, -1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jnp.dot(
            pexp.astype(v.dtype), v,
            preferred_element_type=jnp.float32)

    @pl.when(p == n_pages - 1)
    def _write():
        l = l_s[...]
        out = jnp.where(l > 0.0, acc_s[...] / jnp.maximum(l, 1e-30),
                        0.0)
        out_ref[0, 0] = out.astype(out_ref.dtype)


def _pallas_call(q4, k_pool, v_pool, scalars, *, interpret: bool,
                 q_tokens: int, quantized: bool):
    """Shared pallas_call builder.  q4: (B, KH, R, D) with
    R = q_tokens*rep; scalars: the prefetch tuple (tables, lengths[,
    k_scales, v_scales])."""
    B, KH, R, D = q4.shape
    rep = R // q_tokens
    page = k_pool.shape[2]
    # int4-packed pools carry D//2 uint8 bytes on the head axis; the
    # kv block shape follows the POOL's last axis while q/out keep D
    Dk = k_pool.shape[3]
    packed = quantized and k_pool.dtype == jnp.uint8
    scale = 1.0 / np.sqrt(D)
    n_pre = len(scalars)

    def _q_map(b, h, p, *pre):
        return (b, h, 0, 0)

    def _kv_map(b, h, p, *pre):
        return (pre[0][b, p], h, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, page, Dk), _kv_map,
                           memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pre,
        grid=(B, KH, scalars[0].shape[1]),
        in_specs=[
            pl.BlockSpec((1, 1, R, D), _q_map,
                         memory_space=pltpu.VMEM),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, R, D), _q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, scale=scale,
                          rep=rep, q_tokens=q_tokens,
                          quantized=quantized, packed=packed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, R, D), q4.dtype),
        interpret=interpret,
    )(*scalars, q4, k_pool, v_pool)


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk / completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret", "q_tokens"))
def _paged_pallas(q4, k_pool, v_pool, tables, lengths, *,
                  interpret: bool, q_tokens: int):
    """q4: (B, KH, q_tokens*rep, D); pools: (n_blocks, KH, page, D);
    tables: (B, P) int32; lengths: (B,) int32.
    Returns (B, KH, q_tokens*rep, D)."""
    return _pallas_call(q4, k_pool, v_pool, (tables, lengths),
                        interpret=interpret, q_tokens=q_tokens,
                        quantized=False)


# splint: ignore[SPL205] reason=runs inside the registered paged programs (quantized pools); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret", "q_tokens"))
def _paged_pallas_quant(q4, k_pool, v_pool, k_scales, v_scales,
                        tables, lengths, *, interpret: bool,
                        q_tokens: int):
    """Quantized variant: int8 pools + (n_blocks, KH) f32 per-page
    per-kv-head scales.  Only the scales of the pages the tables name
    ride the scalar prefetch, gathered here and flattened to
    (B*P*KH,) — see the module docstring for the SMEM arithmetic."""
    return _pallas_call(q4, k_pool, v_pool,
                        (tables, lengths,
                         k_scales[tables].reshape(-1),
                         v_scales[tables].reshape(-1)),
                        interpret=interpret, q_tokens=q_tokens,
                        quantized=True)


def dequantize_pool(pool, scales):
    """(n_blocks, KH, page, D) int8 — or (n_blocks, KH, page, D//2)
    uint8 int4-packed — + (n_blocks, KH) f32 -> f32 values (the
    jnp-reference/fallback dequant; the kernel does this per page in
    register)."""
    if pool.dtype == jnp.uint8:
        return unpack_int4(pool) * scales[:, :, None, None]
    return pool.astype(jnp.float32) * scales[:, :, None, None]


def _paged_ref(q, k_pool, v_pool, tables, lengths, starts=None,
               sinks=None):
    """Reference math: gather every table page into a dense
    (B, KH, P*page, D) view and run the masked softmax — the
    correctness mirror the kernels are pinned against (and the non-TPU
    serving path; XLA fuses the gather fine on CPU).  q may be
    (B, H, D) (single decode token) or (B, S, H, D) (multi-query
    verify: token t attends j < lengths + t).  `starts` (B,), where
    given, is each row's FIRST LIVE KEY (window_paged_attention):
    token t attends starts + t <= j only.  `sinks` (H,), where given,
    is a learned logit a head that joins the softmax's denominator
    and carries no value.  The values may be narrower than the keys
    (v_pool's last axis): the output takes their width."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, S, H, D = q.shape
    KH, page = k_pool.shape[1], k_pool.shape[2]
    rep = H // KH
    kg = k_pool[tables].transpose(0, 2, 1, 3, 4)     # (B, KH, P, pg, D)
    vg = v_pool[tables].transpose(0, 2, 1, 3, 4)
    T = kg.shape[2] * page
    kseq = kg.reshape(B, KH, T, D)
    vseq = vg.reshape(B, KH, T, vg.shape[-1])
    qr = q.reshape(B, S, KH, rep, D)
    logits = jnp.einsum(
        "bskrd,bktd->bskrt", qr.astype(jnp.float32),
        kseq.astype(jnp.float32)) / np.sqrt(D)
    valid = jnp.arange(T)[None, None, :] \
        < (lengths[:, None, None] + jnp.arange(S)[None, :, None])
    if starts is not None:
        valid &= jnp.arange(T)[None, None, :] \
            >= (starts[:, None, None] + jnp.arange(S)[None, :, None])
    logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
    if sinks is not None:
        sink = jnp.broadcast_to(
            jnp.asarray(sinks, jnp.float32).reshape(1, 1, KH, rep, 1),
            logits.shape[:-1] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([logits, sink], -1), axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskrt,bktd->bskrd", probs.astype(vseq.dtype),
                     vseq)
    out = out.reshape(B, S, H, vseq.shape[-1]).astype(q.dtype)
    return out[:, 0] if squeeze else out


def _paged_host(q, k_pool, v_pool, tables, lengths,
                k_scales=None, v_scales=None, *,
                interpret: bool, force_pallas: bool):
    """The single-device dispatch body: Pallas kernel on TPU (or under
    interpret/force_pallas), identical jnp math elsewhere.  Under
    paged_attention's mesh= this runs PER SHARD inside shard_map —
    q/k_pool/v_pool (and the scales) arrive with their local KH/tp kv
    heads (and the matching H/tp query heads), tables/lengths
    replicated, and the math needs no collective: every kv head's
    attention is independent and the GQA head-repeat stays local
    because query heads shard consistently with kv heads."""
    multi = q.ndim == 4
    if multi:
        B, S, H, D = q.shape
    else:
        B, H, D = q.shape
        S = 1
    KH = k_pool.shape[1]
    rep = H // KH
    quantized = k_scales is not None
    use_pallas = (force_pallas or interpret
                  or jax.default_backend() == "tpu")
    if not use_pallas:
        if quantized:
            k_pool = dequantize_pool(k_pool, k_scales)
            v_pool = dequantize_pool(v_pool, v_scales)
        return _paged_ref(q, k_pool, v_pool, tables, lengths)
    # token-major query stacking: rows [t*rep, (t+1)*rep) of each kv
    # head's block are query token t's rep heads (the kernel's
    # row // rep == token-index contract)
    if multi:
        q4 = q.reshape(B, S, KH, rep, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, KH, S * rep, D)
    else:
        q4 = q.reshape(B, KH, rep, D)
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if quantized:
        out = _paged_pallas_quant(
            q4, k_pool, v_pool,
            jnp.asarray(k_scales, jnp.float32),
            jnp.asarray(v_scales, jnp.float32),
            tables, lengths, interpret=interpret, q_tokens=S)
    else:
        out = _paged_pallas(q4, k_pool, v_pool, tables, lengths,
                            interpret=interpret, q_tokens=S)
    if multi:
        return out.reshape(B, KH, S, rep, D).transpose(0, 2, 1, 3, 4) \
                  .reshape(B, S, H, D)
    return out.reshape(B, H, D)


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    k_scales=None, v_scales=None,
                    interpret: bool = False,
                    force_pallas: bool = False,
                    mesh=None):
    """Ragged paged decode attention (FORWARD/serving only).

    q: (B, H, D) — ONE query token per row, at position lengths[b]-1
    (call after appending the step's K/V, so lengths counts it) — or
    (B, S, H, D) for the MULTI-QUERY verify path: S new tokens per
    row whose K/V are ALL already appended at positions
    lengths[b]-1 .. lengths[b]+S-2; query token t attends keys
    j < lengths[b] + t (causal across the stack — exactly the
    speculative verifier's one-forward scoring of gamma+1 drafts);
    k_pool/v_pool: (n_blocks, KH, page, D) — kv heads UNREPEATED (GQA:
    query head h reads kv head h // (H//KH), grouped like
    causal_flash_attention);
    k_scales/v_scales: None for float pools, or (n_blocks, KH) f32
    per-page per-kv-head scales for quantized pools — the kernel
    dequantizes in register inside the page loop (the scales of the
    tables' pages ride scalar prefetch with the tables).  Quantized pools are int8, or
    int4-PACKED when the pool dtype is uint8: (n_blocks, KH, page,
    D//2) bytes holding two offset-8 nibbles each (split-half layout,
    pack_int4/unpack_int4), nibble-unpacked in register;
    tables: (B, P) int32 block table — entry (b, p) is the pool block
    holding row b's tokens [p*page, (p+1)*page); unused entries point
    at the trash block 0;
    lengths: (B,) int32 — row b's FIRST query attends keys
    j < lengths[b].
    Returns q's shape in q's dtype.

    mesh: a Mesh with a tp axis > 1 runs the kernel under shard_map —
    GSPMD cannot partition a Mosaic custom call, so the tensor-
    parallel serving path (parallel.serve.ShardedCompletionModel)
    shards the pools on their kv-head axis and each device runs the
    SAME Pallas program over its local KH/tp heads (block tables and
    lengths stay replicated; the scales shard with their kv heads;
    page scheduling is host-side and unchanged).  No collective is
    needed here: the one psum pair per block comes from the
    row-parallel out-projection sharding, exactly like the dense path.
    """
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as SP

        from jax import shard_map

        q_spec = SP(None, None, "tp", None) if q.ndim == 4 \
            else SP(None, "tp", None)
        pool_spec = SP(None, "tp", None, None)
        in_specs = [q_spec, pool_spec, pool_spec]
        args = [q, k_pool, v_pool]
        if k_scales is not None:
            in_specs += [SP(None, "tp"), SP(None, "tp")]
            args += [k_scales, v_scales]
        in_specs += [SP(), SP()]
        args += [jnp.asarray(tables, jnp.int32),
                 jnp.asarray(lengths, jnp.int32)]

        def body(q, kp, vp, *rest):
            if len(rest) == 4:
                ksc, vsc, tab, lens = rest
            else:
                (tab, lens), ksc, vsc = rest, None, None
            return _paged_host(q, kp, vp, tab, lens, ksc, vsc,
                               interpret=interpret,
                               force_pallas=force_pallas)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=q_spec,
            check_vma=False)
        return fn(*args)
    return _paged_host(q, k_pool, v_pool, tables, lengths,
                       k_scales, v_scales,
                       interpret=interpret, force_pallas=force_pallas)


# ------------------------------------------------- page groups, windows
#
# A model whose layers MIX sliding-window and global attention
# (models/afmoe.py) keeps its pages in GROUPS — the global layers one
# pool, the window layers another, each with its own block table
# (models/decoder.PagedKVCache) — and a group's pool holds all of its
# layers side by side in a page:
#
#     k_pool / v_pool: (n_blocks, L, KH, page, D)        a group
#
# so that one table entry names a page of every layer of the group, a
# page copy is one copy, and a stack of identical layers can run as
# ONE compiled body that is told its layer by a scalar.  The kernel
# below reads such a pool.  It differs from `_paged_kernel` in three
# things: every row carries its FIRST LIVE KEY (`starts`: a window
# layer's `length - window`, which may be negative; a global layer's
# lies below every key), the page axis of the grid WALKS FROM the
# first live page instead of page 0 — a window layer's grid is
# ceil((window + tokens) / page) + 1 pages long whatever the context,
# and the pages behind the window, which the cache has given back and
# whose table entries name the trash block, are never gathered — and
# one program carries `hb` kv heads (all of them for a decode step:
# the grid step, not the page's bytes, is what a 32 KiB page costs).
# The keys and the values may differ in width (k_pool (..., Dk), v_pool
# (..., Dv): the scores scale by 1/sqrt(Dk), the accumulator and the
# output take Dv), and a layer may carry a learned SINK a head — a
# logit in the softmax's denominator with no value — which is the
# online softmax's initial state (m = sink, l = 1, acc = 0) and
# nothing else.  KEYS A TOKEN A COLUMN (`k_cols`): a key width that is
# no multiple of the 128-lane tile (192) would be padded to the next
# one in HBM if a page's tokens were its rows, and the compiler then
# keeps such a pool the other way round and copies it for every call
# (tests/test_chip_compile.py found 5 pool copies a dispatch); the
# model keeps those keys as k_pool (n_blocks, L, KH, Dk, page) —
# whole tiles, the layout of ops/latent_attention's pages — and the
# score is q (R, Dk) @ k (Dk, page) with no transpose at all.
# VALUES A TOKEN A COLUMN (`v_cols`) for the same reason, where a head
# is NARROWER than the tile (64): a (page, 64) block half-fills its
# lanes, and the compiler copied a 1 GB pool of them for every call
# (tests/test_chip_compile.py); kept as v_pool (n_blocks, L, KH, Dv,
# page) the sum is p (R, page) . v (Dv, page) over the lanes of both,
# the form the score of keys a token a row has.

# query rows (tokens x heads of a kv group) one program holds
WINDOW_Q_ROWS = 1024
NO_START = -(1 << 30)


def stack_block(q_tokens: int, rep: int) -> int:
    """Query tokens one program of the stack kernel carries: the
    largest divisor of q_tokens that keeps tokens x rep at or under
    WINDOW_Q_ROWS."""
    t = max(1, min(q_tokens, WINDOW_Q_ROWS // max(rep, 1)))
    while q_tokens % t:
        t -= 1
    return t


def window_walk_pages(window: int, page: int, block_tokens: int) -> int:
    """Pages a query block of `block_tokens` tokens of a window layer
    can touch: window + block_tokens - 1 consecutive keys, at any
    offset in their first page."""
    return -(-(window + block_tokens - 1) // page) + 1


def _window_kernel(tab_ref, len_ref, start_ref, layer_ref, q_ref, k_ref,
                   v_ref, *rest, page: int, scale: float, rep: int,
                   block_tokens: int, n_table: int, sink: bool,
                   k_cols: bool, v_cols: bool = False):
    """One (batch row, kv-head block, query block, walked page)
    program.

      tab_ref: (B, P) SMEM block table;  len_ref / start_ref: (B,)
      SMEM — the row's first query attends start <= j < length;
      layer_ref: (1,) SMEM, the layer of the group's pool
      q_ref:   (1, hb, R, Dk), R = block_tokens * rep, token-major
      k_ref: (1, 1, hb, page, Dk) — (1, 1, hb, Dk, page) where
      `k_cols` — v_ref: (1, 1, hb, page, Dv) — (1, 1, hb, Dv, page)
      where `v_cols`: the page the walk routed here
      rest:  [sink_ref (hb, R, 1) f32 — each query row's sink logit,
      where `sink`], out_ref (1, hb, R, Dv), then the scratch:
      m_s/l_s: (hb, R, 1) f32;  acc_s: (hb, R, Dv) f32

    Query token t of the whole stack attends start + t <= j <
    length + t.  The walk's page w of query block qb is page
    max(0, start + qb * block_tokens) // page + w of the row."""
    sink_ref = rest[0] if sink else None
    out_ref, m_s, l_s, acc_s = rest[-4:]
    b = pl.program_id(0)
    qb = pl.program_id(2)
    w = pl.program_id(3)
    n_walk = pl.num_programs(3)
    length = len_ref[b]
    start = start_ref[b]
    t0 = qb * block_tokens
    first = jnp.maximum(start + t0, 0) // page
    pi = first + w
    hb, R = q_ref.shape[1], q_ref.shape[2]

    @pl.when(w == 0)
    def _init():
        if sink:
            # the sink is a key every query sees, with no value
            m_s[...] = sink_ref[...]
            l_s[...] = jnp.ones_like(l_s)
        else:
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # the block's last token attends j < length + t0 + block_tokens - 1
    @pl.when(jnp.logical_and(
        pi < n_table, pi * page < length + t0 + (block_tokens - 1)))
    def _accumulate():
        j = pi * page + jax.lax.broadcasted_iota(jnp.int32, (R, page), 1)
        t = t0 + jax.lax.broadcasted_iota(jnp.int32, (R, page), 0) // rep
        valid = jnp.logical_and(j < length + t, j >= start + t)
        for h in range(hb):
            q = q_ref[0, h]                             # (R, Dk)
            k = k_ref[0, 0, h]                  # (page, Dk) | (Dk, page)
            v = v_ref[0, 0, h]                  # (page, Dv) | (Dv, page)
            logits = jax.lax.dot_general(
                q, k, (((1,), (0 if k_cols else 1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev, l_prev = m_s[h], l_s[h]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, -1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            pexp = jnp.where(valid, jnp.exp(logits - m_new), 0.0)
            m_s[h] = m_new
            l_s[h] = l_prev * corr + jnp.sum(pexp, -1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + (jax.lax.dot_general(
                pexp.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) if v_cols else jnp.dot(
                pexp.astype(v.dtype), v,
                preferred_element_type=jnp.float32))

    @pl.when(w == n_walk - 1)
    def _write():
        l = l_s[...]
        out = jnp.where(l > 0.0, acc_s[...] / jnp.maximum(l, 1e-30), 0.0)
        out_ref[0] = out.astype(out_ref.dtype)


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk / completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=(
    "n_walk", "block_tokens", "q_tokens", "interpret", "k_cols", "v_cols"))
def _window_pallas(q4, k_pool, v_pool, tables, lengths, starts, layer, *,
                   n_walk: int, block_tokens: int, q_tokens: int,
                   interpret: bool, sinks=None, k_cols: bool = False,
                   v_cols: bool = False):
    """q4: (B, KH, q_tokens*rep, Dk) token-major; k_pool: (n_blocks, L,
    KH, page, Dk) — (n_blocks, L, KH, Dk, page) where `k_cols` —
    v_pool: (n_blocks, L, KH, page, Dv) — (n_blocks, L, KH, Dv, page)
    where `v_cols`; tables (B, P); lengths, starts (B,); layer (1,);
    sinks: None or (KH, rep) f32, a logit a head.  Returns (B, KH,
    q_tokens*rep, Dv)."""
    B, KH, RT, D = q4.shape
    page, Dv = v_pool.shape[3:][::-1] if v_cols else v_pool.shape[3:]
    rep = RT // q_tokens
    P = tables.shape[1]
    R = block_tokens * rep
    # a decode step carries every kv head in one program; a stack of
    # tokens one kv head (its query block is the 1,024 rows already)
    hb = KH if q_tokens == 1 else 1

    def _q_map(b, g, qb, w, *pre):
        return (b, g, qb, 0)

    def _kv_map(b, g, qb, w, tab, lens, sts, lay):
        first = jnp.maximum(sts[b] + qb * block_tokens, 0) // page
        return (tab[b, jnp.minimum(first + w, P - 1)], lay[0], g, 0, 0)

    def kv_spec(*block):
        return pl.BlockSpec((1, 1, hb, *block), _kv_map,
                            memory_space=pltpu.VMEM)

    in_specs = [pl.BlockSpec((1, hb, R, D), _q_map,
                             memory_space=pltpu.VMEM),
                kv_spec(D, page) if k_cols else kv_spec(page, D),
                kv_spec(Dv, page) if v_cols else kv_spec(page, Dv)]
    operands = [q4, k_pool, v_pool]
    if sinks is not None:
        # a block's rows are token-major: row r is head r % rep
        in_specs.append(pl.BlockSpec(
            (hb, R, 1), lambda b, g, qb, w, *pre: (g, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(jnp.tile(
            jnp.asarray(sinks, jnp.float32).reshape(KH, rep),
            (1, block_tokens))[..., None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, KH // hb, q_tokens // block_tokens, n_walk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, R, Dv), _q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((hb, R, 1), jnp.float32),
                        pltpu.VMEM((hb, R, 1), jnp.float32),
                        pltpu.VMEM((hb, R, Dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_window_kernel, page=page,
                          scale=1.0 / float(np.sqrt(D)), rep=rep,
                          block_tokens=block_tokens, n_table=P,
                          sink=sinks is not None, k_cols=k_cols,
                          v_cols=v_cols),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, RT, Dv), q4.dtype),
        interpret=interpret,
        # the decode step's kernel and the suffix stack's are told
        # apart by name in a device trace (benchmark/readers)
        name=("gqa_window_decode" if q_tokens == 1
              else "gqa_window_stack"),
    )(tables, lengths, starts, layer, *operands)


def window_paged_attention(q, k_pool, v_pool, tables, lengths, *, layer,
                           window: int = 0, sinks=None,
                           k_cols: bool = False, v_cols: bool = False,
                           interpret: bool = False,
                           force_pallas: bool = False):
    """Ragged paged attention over ONE LAYER of a page group's pool,
    global or sliding-window (FORWARD only; float pools).

    q: (B, S, H, D) — S new tokens a row, all appended already: token
    t sits at position lengths[b] - 1 + t and attends keys
    j < lengths[b] + t — and, where window > 0, only the last `window`
    of them (0 <= position - j < window);
    k_pool: (n_blocks, L, KH, page, D) — (n_blocks, L, KH, D, page),
    a token a column, where `k_cols` — v_pool: (n_blocks, L, KH, page,
    Dv) — (n_blocks, L, KH, Dv, page) where `v_cols` — kv heads
    unrepeated, Dv the values' own width;
    layer: int32 scalar (traced or not), the layer within the group;
    tables: (B, P) the GROUP's block table; a window group's entries
    behind the window may name the trash block: they are not read;
    sinks: None, or (H,) float32 — a learned logit a head that joins
    every query's softmax denominator and carries no value.
    Returns (B, S, H, Dv) in q's dtype."""
    B, S, H, D = q.shape
    KH = v_pool.shape[2]
    page, Dv = v_pool.shape[3:][::-1] if v_cols else v_pool.shape[3:]
    rep = H // KH
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = (lengths - window if window > 0
              else jnp.full_like(lengths, NO_START))
    layer = jnp.asarray(layer, jnp.int32)
    if not (force_pallas or interpret or jax.default_backend() == "tpu"):
        k_layer, v_layer = k_pool[:, layer], v_pool[:, layer]
        return _paged_ref(q, k_layer.swapaxes(-1, -2) if k_cols
                          else k_layer, v_layer.swapaxes(-1, -2) if v_cols
                          else v_layer, tables, lengths, starts, sinks)
    tq = stack_block(S, rep)
    n_walk = tables.shape[1]
    if window > 0:
        n_walk = min(n_walk, window_walk_pages(window, page, tq))
    q4 = q.reshape(B, S, KH, rep, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, KH, S * rep, D)
    out = _window_pallas(q4, k_pool, v_pool, tables, lengths, starts,
                         layer.reshape(1), n_walk=n_walk,
                         block_tokens=tq, q_tokens=S,
                         interpret=interpret, k_cols=k_cols,
                         v_cols=v_cols,
                         sinks=None if sinks is None
                         else jnp.asarray(sinks).reshape(KH, rep))
    return out.reshape(B, KH, S, rep, Dv).transpose(0, 2, 1, 3, 4) \
              .reshape(B, S, H, Dv)


def _kv_append_kernel(bid_ref, off_ref, lay_ref, new_ref, pool_ref,
                      out_ref, *, sub: int):
    """Row i of the batch: the `sub` tokens of its page that hold
    offset off[i] come in, token off[i] % sub takes the new row in
    every kv head, the tile goes back.  Consecutive rows of one tile
    (dead rows, all sent to the trash block) keep writing the block
    that is already resident."""
    i = pl.program_id(0)
    j = jnp.maximum(i - 1, 0)
    fresh = jnp.logical_or(i == 0, jnp.logical_or(
        bid_ref[i] != bid_ref[j], off_ref[i] // sub != off_ref[j] // sub))

    @pl.when(fresh)
    def _load():
        out_ref[...] = pool_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[2:], 1)
    out_ref[0, 0] = jnp.where(
        row == off_ref[i] % sub,
        jnp.broadcast_to(new_ref[0], out_ref.shape[2:]), out_ref[0, 0])


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_append_pallas(pool, new, bids, offs, layer, *, interpret: bool):
    """pool: (n_blocks, L, KH, page, D), updated in place (aliased);
    new: (N, KH, 1, D); bids/offs: (N,) int32; layer: (1,) int32."""
    N = new.shape[0]
    _, _, KH, page, D = pool.shape
    # the tile of tokens a write carries: the dtype's sublane tile
    # where the page holds whole ones, else the page
    sub = 16 if page % 16 == 0 else page

    def _tile(i, bid, off, lay):
        return (bid[i], lay[0], 0, off[i] // sub, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, KH, 1, D), lambda i, *pre: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, KH, sub, D), _tile,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, KH, sub, D), _tile,
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        functools.partial(_kv_append_kernel, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="gqa_window_append",
    )(bids, offs, layer, new, pool)


def _kv_append_cols_kernel(bid_ref, off_ref, lay_ref, new_ref, pool_ref,
                           out_ref):
    """Row i of the batch, keys a token a COLUMN: its page of the
    layer comes in whole, column off[i] takes the new key in every kv
    head, the page goes back (ops/latent_attention._append_kernel's
    discipline).  Consecutive rows of one page keep writing the block
    that is already resident."""
    i = pl.program_id(0)
    fresh = jnp.logical_or(
        i == 0, bid_ref[i] != bid_ref[jnp.maximum(i - 1, 0)])

    @pl.when(fresh)
    def _load():
        out_ref[...] = pool_ref[...]

    col = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[2:], 2)
    out_ref[0, 0] = jnp.where(
        col == off_ref[i],
        jnp.broadcast_to(new_ref[0], out_ref.shape[2:]), out_ref[0, 0])


# splint: ignore[SPL205] reason=runs inside the registered paged programs; the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_append_cols_pallas(pool, new, bids, offs, layer, *,
                           interpret: bool):
    """pool: (n_blocks, L, KH, D, page), updated in place (aliased);
    new: (N, KH, D, 1); bids/offs: (N,) int32; layer: (1,) int32."""
    N = new.shape[0]
    _, _, KH, D, page = pool.shape

    def _page(i, bid, off, lay):
        return (bid[i], lay[0], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, KH, D, 1), lambda i, *pre: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, KH, D, page), _page,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, KH, D, page), _page,
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        _kv_append_cols_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="gqa_window_append_cols",
    )(bids, offs, layer, new, pool)


def kv_append(pool, new, bids, offs, *, layer, cols: bool = False,
              interpret: bool = False, force_pallas: bool = False):
    """Write one new token a row into a page group's pool:
    pool[bids[i], layer, :, offs[i]] = new[i].  pool: (n_blocks, L, KH,
    page, D) — (n_blocks, L, KH, D, page), a token a column, where
    `cols`; new: (N, KH, D); bids/offs: (N,) int32; layer: int32
    scalar.  Rows sent to the trash block 0 may collide freely.  In
    place on a TPU (an XLA scatter there asks for the pool in another
    layout and copies it both ways, tests/test_chip_compile.py)."""
    b = jnp.asarray(bids, jnp.int32).reshape(-1)
    o = jnp.asarray(offs, jnp.int32).reshape(-1)
    layer = jnp.asarray(layer, jnp.int32)
    new = new.astype(pool.dtype)
    if force_pallas or interpret or jax.default_backend() == "tpu":
        if cols:
            return _kv_append_cols_pallas(pool, new[..., None], b, o,
                                          layer.reshape(1),
                                          interpret=interpret)
        return _kv_append_pallas(pool, new[:, :, None], b, o,
                                 layer.reshape(1), interpret=interpret)
    if cols:
        return pool.at[b, layer, :, :, o].set(new)
    return pool.at[b, layer, :, o].set(new)
