"""TPU-native causal decoder LM (flax) for the completion daemon.

Replaces the reference's llama.cpp completion compute
(splainference.cpp:414-470 loads a GGUF chat model; the token loop at
splainference.cpp:306-365 samples with a top-p 0.9 / temp 0.7 / dist
chain, splainference.cpp:272-279).  Here the decoder is a JAX/flax
module designed for XLA:

  - llama-family geometry: pre-norm RMSNorm, rotary positions, SwiGLU
    MLP, causal attention;
  - a **static-shape KV cache** of length `max_len` carried as an
    explicit pytree — one compiled program per (batch, chunk) shape
    serves both bucketed prefill (chunk = bucket) and token-at-a-time
    decode (chunk = 1), so the generation hot loop never recompiles;
  - bfloat16 activations (MXU-native), float32 logits for sampling;
  - a jit-compiled top-p/temperature sampler (the reference's chain:
    top-p 0.9 → temp 0.7 → dist, splainference.cpp:272-279).

Weights are seeded-random by default (protocol and benchmarks do not
depend on weight values); real checkpoints load through the same param
tree.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..obs.devtime import DEVTIME
from .encoder import _apply_rotary, _rotary_angles  # shared rotary math


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32000
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    kv_heads: int = 12            # grouped-query attention when < heads
    mlp_dim: int = 2048
    max_len: int = 2048           # KV cache length = context window
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # int8 blockwise weight residency (models/quant.py): attention +
    # MLP kernels live in HBM as Q8_0-geometry int8 + per-block scales
    # — half bf16's weight bandwidth on the decode path.  Embeddings,
    # norms, and the LM head stay float.
    quantized: bool = False
    # per-OUTPUT-CHANNEL int8 weight residency (models/quant.py
    # ChannelQuantDense): the projection matmul runs on the MXU with
    # int8 weights widened in register and dequantizes ON THE f32
    # OUTPUT — one f32 scale per output column — instead of the Q8_0
    # block path's dequant-before-matmul.  Mutually exclusive with
    # `quantized` (one residency per tree).
    weights_int8: bool = False
    # prefill chunks at/above this width attend through the causal
    # Pallas kernel (ops/flash_attention.causal_flash_attention): long
    # prompts stop materializing (B, H, S, T) logits in HBM.  0 = off.
    flash_min_seq: int = 512

    @classmethod
    def tiny(cls, **kw) -> "DecoderConfig":
        """Small config for tests and CPU CI."""
        kw = {"vocab_size": 1024, "hidden": 64, "layers": 2, "heads": 4,
              "kv_heads": 2, "mlp_dim": 128, "max_len": 128, **kw}
        return cls(**kw)

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def init_cache(cfg: DecoderConfig, batch: int):
    """Fresh zeroed KV cache: list of (k, v) per layer, each
    (B, max_len, kv_heads, head_dim).  The llama.cpp analog of
    llama_memory_clear (splainference.cpp:378)."""
    shape = (batch, cfg.max_len, cfg.kv_heads, cfg.head_dim)
    z = jnp.zeros(shape, cfg.dtype)
    return [(z, z) for _ in range(cfg.layers)]


def _tp_of(sharding) -> int:
    """The tensor-parallel degree a pool sharding splits kv heads
    over: the mesh size along the axes named at the KV-HEAD position
    (index 1) of its PartitionSpec.  1 for a replicated spec."""
    spec = getattr(sharding, "spec", None)
    if spec is None or len(spec) < 2 or spec[1] is None:
        return 1
    names = spec[1] if isinstance(spec[1], tuple) else (spec[1],)
    tp = 1
    for n in names:
        tp *= sharding.mesh.shape[n]
    return tp


# the paged pool's storage dtypes: "int8" stores values as int8 with
# one f32 scale per (page block, kv head) — (n_blocks, KH) — alongside
# each pool; "int4" PACKS two 4-bit codes per uint8 byte (the pool's
# last axis is head_dim/2 — split-half nibble layout, see
# ops/paged_attention.pack_int4) under the SAME per-(page, kv-head)
# scale plumbing; anything else is the dense float layout.  The scale
# arrays stay separate from the values (not interleaved), which is
# exactly why int4 packing was a value-layout change only.
KV_DTYPES = ("bf16", "f32", "int8", "int4")


def _kv_storage(cfg: DecoderConfig, kv_dtype: str | None):
    """(label, value dtype, quantized?) for a pool's storage.  None
    keeps the model's native activation dtype (the status quo).
    uint8 storage == int4-PACKED (two codes per byte): every consumer
    (kernel, appends, commit, wire) keys packing off the dtype."""
    if kv_dtype is None:
        label = ("bf16" if cfg.dtype == jnp.bfloat16 else
                 "f32" if cfg.dtype == jnp.float32 else
                 str(np.dtype(cfg.dtype)))
        return label, cfg.dtype, False
    if kv_dtype == "int8":
        return "int8", jnp.int8, True
    if kv_dtype == "int4":
        return "int4", jnp.uint8, True
    if kv_dtype == "bf16":
        return "bf16", jnp.bfloat16, False
    if kv_dtype == "f32":
        return "f32", jnp.float32, False
    raise ValueError(
        f"unknown kv_dtype {kv_dtype!r} (supported: {KV_DTYPES})")


def _quant_append(pool, scales, bids, offs, x):
    """Append one token's values into an int8 page with
    RESCALE-ON-APPEND: per (row, kv head), the page's scale grows to
    cover the new token (s_new = max(s_old, |x|_inf / 127)) and the
    page's existing int8 values re-round at the new scale — scales
    are MONOTONIC per page, so re-rounding only happens when the
    running max actually moves (at most a handful of times per page
    in practice) and clipping never occurs.  The whole touched page
    is gathered/rewritten (one page per row per side — the same page
    the append already dirties; attention reads every live page, so
    this extra write is noise against the read traffic the int8
    layout halves).

    pool: (n_blocks, KH, page, D) int8; scales: (n_blocks, KH) f32;
    bids/offs: (B,) block id + in-page slot per row; x: (B, KH, D).
    Dead rows point at the trash block 0 — their (duplicate-index,
    nondeterministic) writes land there harmlessly, same contract as
    the float scatter.

    A write at in-page offset 0 treats the page as FRESH (s_old = 0):
    pages return to the free list with their last owner's scale still
    in the table (free_row is host-only), and without this reset a
    reallocated decode-grown page would quantize its new row at the
    stale — monotonically-grown, possibly huge — old scale forever.
    Offset 0 is exactly the first write of every (re)used page, and
    any existing entries of a page being rewritten at offset 0 are
    stale by construction (they sit at positions >= the writing row's
    length), so discarding their scale is always safe.

    A uint8 pool is int4-PACKED (last axis D/2): the same rescale
    discipline runs over UNPACKED codes at qmax 7 and repacks —
    dispatch is dtype-driven so every append call site stays
    layout-blind."""
    if pool.dtype == jnp.uint8:
        return _quant_append_int4(pool, scales, bids, offs, x)
    s_old = jnp.where(offs[:, None] == 0, 0.0,
                      scales[bids])                    # (B, KH)
    xf = x.astype(jnp.float32)
    s_tok = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    s_new = jnp.maximum(s_old, s_tok)
    safe = jnp.where(s_new > 0, s_new, 1.0)
    pages = pool[bids].astype(jnp.float32)             # (B, KH, pg, D)
    pages = jnp.round(pages * (s_old / safe)[:, :, None, None])
    qtok = jnp.clip(jnp.round(xf / safe[:, :, None]), -127, 127)
    slot = (jnp.arange(pool.shape[2])[None, None, :, None]
            == offs[:, None, None, None])
    pages = jnp.where(slot, qtok[:, :, None, :], pages)
    pool = pool.at[bids].set(pages.astype(jnp.int8))
    scales = scales.at[bids].set(s_new)
    return pool, scales


def _quant_append_int4(pool, scales, bids, offs, x):
    """int4-packed rescale-on-append: identical contract to the int8
    body above (monotone per-page scales, offset-0 fresh reset, trash
    routing) at 4-bit geometry — unpack the touched page's codes,
    re-round at the grown scale, write the token's q4 codes into its
    slot, repack.  Garbage nibbles on never-written tail slots unpack
    to code -8; the rescale ratio <= 1 keeps them in [-8, 7] and the
    ragged length mask excludes them from every read, so they never
    need a clip.

    pool: (n_blocks, KH, page, D/2) uint8; scales: (n_blocks, KH) f32;
    x: (B, KH, D)."""
    from ..ops.paged_attention import INT4_QMAX, pack_int4, unpack_int4
    s_old = jnp.where(offs[:, None] == 0, 0.0,
                      scales[bids])                    # (B, KH)
    xf = x.astype(jnp.float32)
    s_tok = jnp.max(jnp.abs(xf), axis=-1) / INT4_QMAX
    s_new = jnp.maximum(s_old, s_tok)
    safe = jnp.where(s_new > 0, s_new, 1.0)
    pages = unpack_int4(pool[bids])                    # (B, KH, pg, D)
    pages = jnp.round(pages * (s_old / safe)[:, :, None, None])
    qtok = jnp.clip(jnp.round(xf / safe[:, :, None]),
                    -INT4_QMAX, INT4_QMAX)
    slot = (jnp.arange(pool.shape[2])[None, None, :, None]
            == offs[:, None, None, None])
    pages = jnp.where(slot, qtok[:, :, None, :], pages)
    pool = pool.at[bids].set(
        pack_int4(jnp.clip(pages, -8, 7).astype(jnp.int32)))
    scales = scales.at[bids].set(s_new)
    return pool, scales


@functools.lru_cache(maxsize=32)
def _sharded_zeros_prog(shape, dtype, sharding):
    """One cached creation program per (shape, dtype, sharding): the
    continuous lane rebuilds its pool on abort recovery, and a fresh
    jit wrapper per construction would retrace the (trivial) program
    on that hot path."""
    # splint: ignore[SPL205] reason=cold-path pool creation (abort recovery), not a serving dispatch
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding)


def _pool_zeros(shape, dtype, sharding):
    """Zeroed-pool factory.  With a sharding, the zeros are created
    DIRECTLY into it (jit out_shardings) — a host-side jnp.zeros +
    device_put would materialize the whole pool on one device first,
    exactly the HBM spike pod sharding exists to avoid."""
    if sharding is None:
        return lambda: jnp.zeros(shape, dtype)
    return _sharded_zeros_prog(tuple(shape), dtype, sharding)


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """What ONE layer keeps in the paged pool, as the model describes
    it: named pools, each (n_blocks, *block shape), and the values a
    token occupies across them.  The llama-geometry decoders keep a
    key and a value pool of (kv_heads, page, head_dim); a latent-
    attention model (models/mla.py) one pool of (page, latent width).
    Allocation, tables, refcounts, COW bookkeeping and the prefix
    tree never look inside a page, so they serve any layout.

    `state`: what a ROW keeps in this layer whatever its length —
    (name, shape, dtype) arrays, e.g. a gated delta-rule layer's
    recurrent matrix and convolution tail (models/kda.py).  Such a
    layer usually has no pools at all; the cache keeps its arrays in
    STATE SLOTS (PagedKVCache).  A model whose layers differ hands
    the cache one layout a layer.

    `window`: the layer attends the last `window` tokens only (0: all
    of them).  Layouts with a window form the cache's WINDOW GROUP
    (PagedKVCache): pools, page count, table and free list of their
    own, and pages that go back as the row slides past them.
    `layers`: how many of the model's layers the layout's pools hold
    side by side in one page (its block shape then leads with that
    axis) — models/afmoe.py describes a GROUP of layers at once, so
    that one table entry names a page of each."""
    pools: tuple[tuple[str, tuple[int, ...]], ...]
    token_values: int
    state: tuple[tuple[str, tuple[int, ...], Any], ...] = ()
    window: int = 0
    layers: int = 1

    @property
    def key_value(self) -> bool:
        return tuple(n for n, _ in self.pools) == ("k", "v")


def kv_page_layout(cfg, page: int, packed: bool = False) -> PageLayout:
    """The key/value pair of (kv_heads, page, head_dim) — head_dim/2
    bytes when int4-packed."""
    shape = (cfg.kv_heads, page,
             cfg.head_dim // 2 if packed else cfg.head_dim)
    return PageLayout((("k", shape), ("v", shape)),
                      token_values=2 * cfg.kv_heads * cfg.head_dim)


class WindowPages:
    """The WINDOW GROUP of a paged cache: the pages of the layers that
    attend a sliding window (PageLayout.window), kept apart from the
    global layers' because they weigh otherwise (24 layers a page
    against 8 in models/afmoe.py) and live a fraction as long.  The
    discipline is the global group's — block 0 the trash block, a
    (batch, pages_per_row) table, refcounts, a free list, zero-ref
    pages that the prefix tree retains until the allocator wants them
    — with one difference: a row holds pages for a SPAN of its table,
    `[lo, hi)`, not a prefix.  A prefix hit maps the tail it resumes
    on (`map_tail`), `ensure` extends the span forward, and `release`
    gives back every page that lies wholly behind the window of the
    row's next token — its table entry returns to the trash block and
    is never read again (ops/paged_attention.window_paged_attention
    walks from the first live page).

    `span` is the most pages a row holds at once — the window, the
    page its oldest key shares, and the widest program's new tokens —
    so `ensure` never runs further ahead than that: a cold prompt
    longer than the window passes through, a piece at a time."""

    def __init__(self, cache, layout: PageLayout, pool_pages: int,
                 span: int | None, dtype):
        self.cache = cache
        self.window = int(layout.window)
        self.page = cache.page
        self.window_pages = -(-self.window // self.page)
        self.span = int(span) if span else self.window_pages + 2
        if pool_pages < self.span:
            raise ValueError(
                f"window_pool_pages {pool_pages} cannot hold even one "
                f"row's span ({self.span} pages)")
        self.layout = layout
        self.n_blocks = pool_pages + 1
        self.pools = [[jnp.zeros((self.n_blocks, *block), dtype)]
                      for _, block in layout.pools]
        self.tables = np.zeros((cache.batch, cache.pages_per_row),
                               np.int32)
        self.refcounts = np.zeros((self.n_blocks,), np.int64)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._lo = np.zeros((cache.batch,), np.int64)
        self._hi = np.zeros((cache.batch,), np.int64)
        self.released = 0             # pages given back as rows slid
        self.release_s = 0.0          # host seconds spent doing so
        self.used_peak = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    @property
    def live_pages(self) -> int:
        """Pages some row's table names (the tree's zero-ref ones
        left out)."""
        return int((self.refcounts > 0).sum())

    @property
    def available_pages(self) -> int:
        pc = self.cache.prefix_cache
        extra = pc.window_evictable_count() if pc is not None else 0
        return len(self._free) + extra

    def first_live(self, length: int) -> int:
        """The page of the oldest key the row's NEXT token (position
        `length`) attends: every page before it is dead for good."""
        return max(0, int(length) - self.window + 1) // self.page

    def join_pages(self, match: int, tokens: int) -> int:
        """Pages a row needs at the most while it joins at `match`
        mapped tokens and runs to `tokens`."""
        return min(self.cache.pages_needed(tokens)
                   - self.first_live(match), self.span)

    def _alloc(self) -> int:
        if not self._free:
            pc = self.cache.prefix_cache
            if pc is None or not pc.reclaim_window(1):
                raise RuntimeError("paged pool exhausted (window group)")
        bid = self._free.pop()
        self.refcounts[bid] = 1
        return bid

    def _decref(self, bid: int) -> None:
        self.refcounts[bid] -= 1
        if self.refcounts[bid] < 0:
            raise RuntimeError(f"window page {bid} refcount underflow")
        if self.refcounts[bid] == 0:
            pc = self.cache.prefix_cache
            if pc is None or not pc.on_window_zero_ref(bid):
                self._free.append(bid)

    def map_tail(self, row: int, first: int, bids) -> None:
        """Point `row`'s entries `first`.. at pages the prefix tree
        holds: the tail a hit resumes on."""
        if self._hi[row] > self._lo[row]:
            raise ValueError("a tail is mapped into an empty row")
        pc = self.cache.prefix_cache
        for i, bid in enumerate(bids):
            bid = int(bid)
            if bid <= 0 or bid >= self.n_blocks:
                raise ValueError(f"bad shared window page id {bid}")
            self.refcounts[bid] += 1
            if self.refcounts[bid] == 1 and pc is not None:
                pc.on_window_ref(bid)
            self.tables[row, first + i] = bid
        self._lo[row], self._hi[row] = first, first + len(bids)

    def ensure(self, row: int, length: int, tokens: int) -> bool:
        """Extend row's span to cover what a row of `length` tokens
        needs to reach `tokens`, at most `span` pages from its first
        live one; False (nothing allocated) when the pool cannot."""
        lo = self.first_live(length)
        need = min(self.cache.pages_needed(tokens), lo + self.span)
        if self._hi[row] <= self._lo[row]:
            self._lo[row] = self._hi[row] = lo
        have = int(self._hi[row])
        if need <= have:
            return True
        if need - have > self.available_pages:
            return False
        for p in range(have, need):
            self.tables[row, p] = self._alloc()
        self._hi[row] = need
        self.used_peak = max(self.used_peak, self.used_pages)
        return True

    def release(self, row: int, length: int) -> int:
        """Give back row's pages wholly behind the window of position
        `length`.  Returns how many."""
        lo, upto = int(self._lo[row]), min(self.first_live(length),
                                           int(self._hi[row]))
        for p in range(lo, upto):
            self._decref(int(self.tables[row, p]))
            self.tables[row, p] = 0
        if upto > lo:
            self._lo[row] = upto
            self.released += upto - lo
        return max(upto - lo, 0)

    def free_row(self, row: int) -> None:
        for p in range(int(self._lo[row]), int(self._hi[row])):
            self._decref(int(self.tables[row, p]))
        self.tables[row, :] = 0
        self._lo[row] = self._hi[row] = 0

    def cow_target(self, row: int, p_idx: int) -> bool:
        """The page row's next append lands in is one the tree or
        another row also reads."""
        if not self._lo[row] <= p_idx < self._hi[row]:
            return False
        bid = int(self.tables[row, p_idx])
        pc = self.cache.prefix_cache
        return bid > 0 and (self.refcounts[bid] > 1 or (
            pc is not None and pc.retains_window(bid)))

    def commit_cow(self, row: int, p_idx: int, new_bid: int) -> None:
        old = int(self.tables[row, p_idx])
        self.tables[row, p_idx] = new_bid
        self._decref(old)


class PagedKVCache:
    """Block-paged KV pool for the continuous-batching decode lane.

    The dense cache above costs HBM proportional to B x max_len no
    matter how many tokens each row holds; this pool costs HBM
    proportional to its page count — cache memory scales with LIVE
    TOKENS, so batch width can grow (8 -> 32 by default in the
    completion daemon) without the cache exploding.  Per layer:

        k_pool / v_pool: (n_blocks, kv_heads, page, head_dim)

    — or whatever `cfg.page_layout(page)` describes (PageLayout: a
    latent-attention model keeps ONE pool of (n_blocks, width, page));
    `pools` holds a list of per-layer buffers for each pool of the
    layout, `k_pools` / `v_pools` name the key/value pair's —
    plus a host-side (batch, pages_per_row) int32 block table and a
    (batch,) lengths vector.  Block 0 is the reserved TRASH block:
    never allocated, every unused table entry points at it, so dead
    rows' appends land harmlessly and gathers of unused pages read
    garbage the ragged length mask excludes (ops/paged_attention.py).

    Allocation is host-side and page-granular: `ensure(row, tokens)`
    grows a row's table to cover `tokens`, `free_row` returns every
    page to the pool the moment a request finishes.  The admission
    path reserves a row's worst case (prompt + max_new rounded up to
    the decode-chunk boundary, capped at the window) up front, so an
    admitted row can never strand mid-decode on an exhausted pool — backpressure happens at admission, where the
    request can simply stay WAITING.

    `page` may be any size on a TPU too: the kernel's page axis is a
    whole block dimension, the v5e compiler takes 1..256
    (tests/test_chip_compile.py holds 16/64/256), and pages of 16 and
    64 gave the jnp reference's answers on the chip for bf16, int8 and
    int4 pools (PR 21).  128 stays the serving default.

    `kv_dtype="int8"` stores the pools QUANTIZED: int8 values plus a
    per-page per-kv-head f32 scale (k_scales/v_scales, (n_blocks, KH)
    per layer).  Cache HBM per token drops to 1/2 of bf16 (1/4 of
    f32), which on a memory-bound decode lane converts directly into
    batch width inside the same pool-byte envelope.  The commit
    scatter quantizes whole pages (paged_prefill_row) and decode
    appends rescale-on-append (_quant_append); the ragged kernel
    dequantizes in register (ops/paged_attention.py).

    `kv_dtype="int4"` PACKS two 4-bit codes per byte on top of the
    same scale plumbing (the value pools become
    (n_blocks, KH, page, head_dim/2) uint8, split-half nibble layout
    — ops/paged_attention.pack_int4): cache HBM per token drops to
    1/4 of bf16 (1/8 of f32), so the same pool-byte envelope holds
    4x bf16's batch width.  Commit packs whole pages, appends
    unpack/rescale/repack, and the ragged kernel unpacks nibbles
    in-register inside its page loop.  The scale arrays are separate
    buffers, which is exactly why packing changed only the value
    layout.

    STATE SLOTS.  Where a layer's layout names per-row `state`
    (PageLayout.state), the cache owns, for each such layer and array,
    one buffer of (state_slots, *shape): slot b < batch is live row
    b's, slots batch .. batch + state_snapshots - 1 are SNAPSHOTS —
    the state as it stood at a page boundary, owned by a prefix-tree
    node (engine/prefix_cache.py) so that a later prompt can resume
    there — and the last slot is a spare that programs with nothing
    to snapshot write to.  Snapshot slots are allocated and freed on
    the host like pages (alloc_state_slot / free_state_slot; when
    none is free the prefix tree gives up its least useful one), and
    count in the same device budget: `state_slot_bytes` beside
    `kv_bytes_per_token`.  A model without state has zero slots and
    none of this runs.

    PAGE GROUPS.  Where some layers' layouts name a `window`
    (PageLayout.window), those layers' pages form a second group,
    `window` (WindowPages): `tables`, `refcounts`, the free list and
    `pool_pages` here stay the GLOBAL group's; `ensure`, `free_row`
    and the copy-on-write pass work both, `release_window` gives a
    window layer's pages back as its row slides.  A model without a
    window has no such group and none of this runs.

    `sharding` (a NamedSharding, normally P(None, "tp", None, None)
    from ShardedCompletionModel) places the pools sharded on their
    KV-HEAD axis across a tensor-parallel mesh: each device holds
    every page at 1/tp of its bytes, so page scheduling (tables,
    lengths, alloc/free — all host-side) is IDENTICAL to the
    single-chip pool while cache HBM per chip divides by tp.  The
    pools are created directly into the sharding (jit out_shardings)
    so no device ever materializes the full-size buffer.
    `scale_sharding` places the int8 scales split on THEIR kv-head
    axis (index 1 of (n_blocks, KH)) — scales shard with the heads
    they scale.
    """

    def __init__(self, cfg: DecoderConfig, batch: int, *,
                 page: int = 128, pool_pages: int | None = None,
                 kv_dtype: str | None = None,
                 sharding=None, scale_sharding=None,
                 state_snapshots: int | None = None,
                 window_pool_pages: int | None = None,
                 window_span: int | None = None):
        if page < 1:
            raise ValueError("page must be >= 1")
        self.cfg = cfg
        self.batch = batch
        self.page = page
        self.pages_per_row = -(-cfg.max_len // page)
        if pool_pages is None:
            # safe default: the pool can hold every row's full window
            # (== dense HBM at this batch).  Deployments cap it lower
            # (--pool-pages) to spend the savings on batch width.
            pool_pages = batch * self.pages_per_row
        if pool_pages < self.pages_per_row:
            raise ValueError(
                f"pool_pages {pool_pages} cannot hold even one full "
                f"window ({self.pages_per_row} pages)")
        self.n_blocks = pool_pages + 1               # + the trash block
        # the per-layer page layout is the MODEL's to describe
        # (cfg.page_layout); a config without one keeps the key/value
        # pair this pool was written for
        describe = getattr(cfg, "page_layout", None)
        key_value = describe is None
        if key_value and sharding is not None \
                and cfg.kv_heads % _tp_of(sharding):
            raise ValueError(
                f"the sharding's tp={_tp_of(sharding)} axis must "
                f"divide kv_heads={cfg.kv_heads} (pools split on the "
                "kv-head axis)")
        self.sharding = sharding
        self.kv_dtype, store_dtype, self.quantized = \
            _kv_storage(cfg, kv_dtype)
        # int4-PACKED pools store two codes per byte: the value
        # buffer's last axis is head_dim/2 uint8 (split-half nibble
        # layout) — tables, lengths, scales, and the whole host-side
        # allocator are identical to int8's
        self.packed = store_dtype == jnp.uint8
        if not key_value and (self.quantized or sharding is not None):
            described = describe(page)
            if isinstance(described, PageLayout):
                described = (described,)
            raise ValueError(
                "quantized (int8/int4) and kv-head-sharded pools need "
                "the key/value page layout; this model describes "
                f"{sorted({n for lo in described for n, _ in lo.pools})}")
        if self.packed and cfg.head_dim % 2:
            raise ValueError(
                f"kv_dtype=\"int4\" packs two codes per byte along "
                f"head_dim; head_dim={cfg.head_dim} must be even")
        layouts = (kv_page_layout(cfg, page, self.packed)
                   if key_value else describe(page))
        if isinstance(layouts, PageLayout):
            layouts = (layouts,) * cfg.layers
        # a layout a layer; the layers that keep pages share ONE page
        # layout (`layout`), the others keep state only
        self.layouts = tuple(layouts)
        windowed = [lo for lo in self.layouts if lo.pools and lo.window]
        paged = [lo for lo in self.layouts if lo.pools and not lo.window]
        if sum(lo.layers for lo in self.layouts) != cfg.layers \
                or not paged \
                or any(lo.pools != paged[0].pools for lo in paged) \
                or len(windowed) > 1:
            raise ValueError(
                "the model must describe one layout a layer, the "
                "layers that keep pages the same pools, and at most "
                "one window group beside layers that see everything")
        self.layout = paged[0]
        self.paged_layers = len(paged)
        # distinct buffers per layer/pool: the paged programs donate
        # the pools, and XLA rejects donating one buffer twice
        self.pools = []
        for _, block in self.layout.pools:
            zeros = _pool_zeros((self.n_blocks, *block), store_dtype,
                                sharding)
            self.pools.append([zeros() for _ in range(self.paged_layers)])
        # state slots (class docstring): rows, snapshots, one spare
        stateful = [lo for lo in self.layouts if lo.state]
        self.needs_state = bool(stateful)
        self.state_snapshots = (
            (batch if state_snapshots is None else int(state_snapshots))
            if stateful else 0)
        if self.state_snapshots < 0:
            raise ValueError("state_snapshots must be >= 0")
        self.state_slots = (batch + self.state_snapshots + 1
                            if stateful else 0)
        self.states = [
            [jnp.zeros((self.state_slots, *shape), dtype)
             for _, shape, dtype in lo.state] for lo in stateful]
        self._free_state = list(range(self.state_slots - 2, batch - 1, -1))
        if self.quantized:
            szeros = _pool_zeros((self.n_blocks, cfg.kv_heads),
                                 jnp.float32, scale_sharding)
            self.k_scales = [szeros() for _ in range(cfg.layers)]
            self.v_scales = [szeros() for _ in range(cfg.layers)]
        else:
            self.k_scales = self.v_scales = None
        self.tables = np.zeros((batch, self.pages_per_row), np.int32)
        self.lengths = np.zeros((batch,), np.int32)
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._owned: list[list[int]] = [[] for _ in range(batch)]
        # cross-request prefix sharing: per-page refcounts let block
        # tables from different rows point at the same full pages —
        # a page returns to the free list only at refcount zero.  The
        # trash block 0 is never allocated and never counted.
        # `prefix_cache` (engine/prefix_cache.PrefixCache, duck-typed
        # via retains()/reclaim()) may additionally FREEZE pages:
        # zero-ref frozen pages stay allocated (instantly re-mappable)
        # until the allocator actually needs them back.
        self.refcounts = np.zeros((self.n_blocks,), np.int64)
        self.prefix_cache = None
        self._ever_shared = False
        # the window group (class docstring), sized like the global
        # one where nobody says otherwise
        self.window = WindowPages(
            self, windowed[0],
            pool_pages if window_pool_pages is None
            else int(window_pool_pages), window_span,
            store_dtype) if windowed else None

    # the key/value layout's two pools by name (every llama-geometry
    # program reads and reassigns them)
    @property
    def k_pools(self):
        return self.pools[0]

    @k_pools.setter
    def k_pools(self, pools):
        self.pools[0] = pools

    @property
    def v_pools(self):
        return self.pools[1]

    @v_pools.setter
    def v_pools(self, pools):
        self.pools[1] = pools

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Free-list pages plus zero-ref prefix-cache pages the
        allocator can reclaim on demand — the number admission
        backpressure must compare against (free_pages alone would
        deny joiners while a warm cache squats on reclaimable
        pages)."""
        pc = self.prefix_cache
        extra = pc.evictable_count() if pc is not None else 0
        return len(self._free) + extra

    def _alloc_page(self) -> int:
        """Pop one page (refcount 1), evicting zero-ref cached pages
        LRU-first when the free list is dry.  Raises when the pool is
        truly exhausted — callers gate on available_pages first."""
        if not self._free:
            pc = self.prefix_cache
            if pc is None or not pc.reclaim(1):
                raise RuntimeError("paged pool exhausted")
        bid = self._free.pop()
        self.refcounts[bid] = 1
        return bid

    def _decref(self, bid: int) -> None:
        self.refcounts[bid] -= 1
        if self.refcounts[bid] < 0:      # double-free: a scheduler bug
            raise RuntimeError(f"page {bid} refcount underflow")
        if self.refcounts[bid] == 0:
            pc = self.prefix_cache
            if pc is None or not pc.on_zero_ref(bid):
                self._free.append(bid)
            # else: the tree retains it — evictable, not free

    def map_shared(self, row: int, bids: list[int]) -> None:
        """Point `row`'s next table entries at already-committed
        pages (refcount bump — no device work; the admission-time
        'table write' that replaces a whole prefix prefill).  The
        caller sets cache.lengths[row] to the token count the mapped
        prefix covers."""
        have = len(self._owned[row])
        if have + len(bids) > self.pages_per_row:
            raise ValueError("mapped prefix exceeds the row's table")
        pc = self.prefix_cache
        for i, bid in enumerate(bids):
            bid = int(bid)
            if bid <= 0 or bid >= self.n_blocks:
                raise ValueError(f"bad shared page id {bid}")
            self.refcounts[bid] += 1
            if self.refcounts[bid] == 1 and pc is not None:
                pc.on_ref(bid)         # evictable page pinned again
            self._owned[row].append(bid)
            self.tables[row, have + i] = bid
        if bids:
            self._ever_shared = True

    def cow_targets(self) -> list[tuple[int, int]]:
        """(row, page_index) pairs whose NEXT decode append would
        write into a page some other reader holds — shared
        (refcount > 1) or frozen in the prefix tree.  Only the page
        containing position lengths[row] can qualify: shared pages
        cover prompt prefixes only, and every later page was
        privately allocated by ensure().  Cheap no-op for pools that
        never shared a page."""
        if not self._ever_shared and self.prefix_cache is None:
            return []
        out = []
        pc = self.prefix_cache
        for r in range(self.batch):
            length = int(self.lengths[r])
            if length <= 0:
                continue
            p_idx = min(length, self.cfg.max_len - 1) // self.page
            if p_idx >= len(self._owned[r]):
                continue              # contract violation elsewhere
            bid = int(self.tables[r, p_idx])
            if bid == 0:
                continue
            if self.refcounts[bid] > 1 or \
                    (pc is not None and pc.retains(bid)):
                out.append((r, p_idx))
        return out

    def window_cow_targets(self) -> list[tuple[int, int]]:
        """cow_targets for the window group's table."""
        w = self.window
        if w is None or (not self._ever_shared
                         and self.prefix_cache is None):
            return []
        out = []
        for r in range(self.batch):
            length = int(self.lengths[r])
            p_idx = min(length, self.cfg.max_len - 1) // self.page
            if length > 0 and w.cow_target(r, p_idx):
                out.append((r, p_idx))
        return out

    def release_window(self, row: int | None = None) -> int:
        """Give back the window group's pages that lie wholly behind
        the window of `row`'s (every live row's) next token: after a
        prefill piece, after a decode chunk.  Returns how many."""
        w = self.window
        if w is None:
            return 0
        t0 = time.perf_counter()
        rows = range(self.batch) if row is None else (row,)
        n = sum(w.release(r, int(self.lengths[r])) for r in rows
                if self.lengths[r] > 0)
        w.release_s += time.perf_counter() - t0
        return n

    def commit_cow(self, row: int, p_idx: int, new_bid: int) -> None:
        """Host half of a copy-on-write: swap the row's table entry to
        the freshly copied private page and drop its reference on the
        shared original (which stays alive for its other readers, or
        for the tree)."""
        old = int(self.tables[row, p_idx])
        self._owned[row][p_idx] = new_bid
        self.tables[row, p_idx] = new_bid
        self._decref(old)
        pc = self.prefix_cache
        if pc is not None:
            pc.stats.cow_copies += 1

    # -- state slots --------------------------------------------------------

    @property
    def state_spare(self) -> int:
        """The slot a program writes to when it has nothing to keep."""
        return self.state_slots - 1

    @property
    def state_slot_bytes(self) -> int:
        """Device bytes of one state slot across every layer: what a
        live row costs beside its pages, and as much a snapshot."""
        return sum(a.nbytes // self.state_slots
                   for layer in self.states for a in layer)

    @property
    def state_slots_used(self) -> int:
        """Live rows + snapshots held (the heartbeat's gauge)."""
        if not self.needs_state:
            return 0
        return int((self.lengths > 0).sum()) + self.state_snapshots \
            - len(self._free_state)

    def state_slot_available(self) -> bool:
        """A snapshot slot is free, or the prefix tree can give one
        up — what admission asks before it promises a snapshot."""
        pc = self.prefix_cache
        return bool(self._free_state) or (
            pc is not None and pc.snapshots_held() > 0)

    def alloc_state_slot(self) -> int | None:
        """A snapshot slot, evicting the prefix tree's least useful
        snapshot when none is free; None when there is none to give
        (a budget of zero)."""
        if not self._free_state:
            pc = self.prefix_cache
            if pc is None or not pc.evict_snapshot():
                return None
        return self._free_state.pop()

    def free_state_slot(self, slot: int) -> None:
        if not self.batch <= slot < self.state_spare \
                or slot in self._free_state:
            raise RuntimeError(f"bad or double-freed state slot {slot}")
        self._free_state.append(slot)

    def kv_bytes_per_token(self) -> int:
        """Cache bytes one token occupies across every layer and pool
        of the layout — the factor behind the prefix cache's
        bytes_saved gauge.  int4-packed pools store half a byte per
        value.  (A layer that keeps state costs a token nothing:
        state_slot_bytes.)"""
        values = self.paged_layers * self.layout.token_values
        if self.window is not None:
            values += self.window.layout.token_values
        if self.packed:
            return values // 2
        return values * np.dtype(self.pools[0][0].dtype).itemsize

    @property
    def used_pages(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    def pages_needed(self, tokens: int) -> int:
        tokens = min(int(tokens), self.cfg.max_len)
        return -(-tokens // self.page) if tokens > 0 else 0

    def ensure(self, row: int, tokens: int) -> bool:
        """Grow row's table to cover `tokens`; False (nothing
        allocated) when the pool cannot — admission backpressure.
        Pages the row already holds (allocated OR mapped shared)
        count; new pages come off the free list, reclaiming zero-ref
        prefix-cache pages when it runs dry."""
        need = self.pages_needed(tokens)
        have = len(self._owned[row])
        if need > have and need - have > self.available_pages:
            return False
        # the window group covers the row's live span only, and at
        # most its `span` ahead (WindowPages.ensure)
        if self.window is not None and not self.window.ensure(
                row, int(self.lengths[row]), tokens):
            return False
        for p in range(have, need):
            bid = self._alloc_page()
            self._owned[row].append(bid)
            self.tables[row, p] = bid
        return True

    def free_row(self, row: int) -> None:
        """Drop every page reference row holds (request finished):
        refcounts decrement, and a page returns to the free list only
        when its last reader lets go — unless the prefix tree retains
        it, in which case it parks evictable instead."""
        for bid in self._owned[row]:
            self._decref(bid)
        self._owned[row] = []
        self.tables[row, :] = 0
        self.lengths[row] = 0
        if self.window is not None:
            self.window.free_row(row)

    def reset(self) -> None:
        for r in range(self.batch):
            self.free_row(r)

    def live_tokens(self) -> int:
        return int(self.lengths.sum())

    def device_mb(self) -> float:
        """Pool bytes MEASURED from the placed device buffers (values
        + scales, all layers, k and v) — the heartbeat's honest gauge:
        a wrong storage dtype or a broken placement shows up here, a
        computed shape*itemsize estimate would not.  Sums this host's
        addressable shards (on a single chip that is simply the full
        buffers; under tp each chip holds 1/tp — the per-shard view
        rides the completer's pages_shard section)."""
        arrs = [a for pool in self.pools for a in pool]
        if self.window is not None:
            arrs += [a for pool in self.window.pools for a in pool]
        arrs += [a for layer in self.states for a in layer]
        if self.quantized:
            arrs += list(self.k_scales) + list(self.v_scales)
        total = 0
        for a in arrs:
            try:
                total += sum(sh.data.nbytes
                             for sh in a.addressable_shards)
            except Exception:
                total += a.nbytes
        return round(total / 1e6, 3)


class PendingChunk:
    """One in-flight paged decode chunk (paged_decode_chunk_async):
    the (n, batch) sampled block still on device, plus `last` — the
    final sampled column as a DEVICE array, which the next chunk's
    dispatch consumes directly (carry=) so chaining K chunks costs
    zero host round trips.  block() forces the host copy (the one
    transfer per chunk) and transposes to the (batch, n) shape the
    sync path returns."""

    __slots__ = ("_out", "last", "n", "_mark")

    def __init__(self, out, last, n: int, mark=None):
        self._out = out
        self.last = last
        self.n = n
        self._mark = mark             # devtime DispatchMark: closed at
        # block() — the collect point that already exists

    def is_ready(self) -> bool:
        try:
            return bool(self._out.is_ready())
        except AttributeError:
            return True

    def block(self) -> np.ndarray:
        host = np.asarray(self._out).T                 # (batch, n)
        mark, self._mark = self._mark, None
        if mark is not None:
            mark.close()
        return host


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                               + self.eps)
        return (y * scale).astype(self.dtype)


def _proj(cfg: DecoderConfig, features: int, name: str):
    """The decoder's projection layer: nn.Dense, QuantDense for the
    Q8_0 block residency, or ChannelQuantDense for the per-output-
    channel MXU path (--weights int8)."""
    if getattr(cfg, "weights_int8", False):
        from .quant import ChannelQuantDense
        return ChannelQuantDense(features, dtype=cfg.dtype, name=name)
    if cfg.quantized:
        from .quant import QuantDense
        return QuantDense(features, dtype=cfg.dtype, name=name)
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype, name=name)


class CausalAttention(nn.Module):
    cfg: DecoderConfig
    # tensor-parallel serving (parallel/serve.py): the mesh the Pallas
    # attention kernels run under via shard_map — GSPMD cannot
    # partition a Mosaic custom call, so the flash-prefill and ragged
    # paged-decode kernels take the mesh explicitly and each device
    # runs the program over its local H/tp (KH/tp) heads.  None (the
    # single-device default) leaves every kernel call unchanged.
    mesh: Any = None

    @nn.compact
    def __call__(self, x, cache_kv, pos, start=None, lengths=None,
                 tables=None, n_valid=None):
        """x: (B, S, H) chunk at cache slots pos..pos+S-1.
        cache_kv: (k, v) each (B, T, KH, D).  start: None, or (B,)
        left-pad offsets for batched serving — row r's real tokens
        occupy slots start[r].., its rotary position at slot s is
        s - start[r], and slots below start[r] (pad K/V) are masked.
        With start=None the graph is the classic single-request one
        (slot == position).  Returns (out, new_cache).

        PAGED decode (lengths is not None): cache_kv is a per-layer
        (k_pool, v_pool) pair of the global block pool
        (n_blocks, KH, page, D) — or (k_pool, v_pool, k_scales,
        v_scales) for an int8-quantized pool — tables is the (B, P)
        block table and lengths the (B,) per-row token counts.  Row
        r's S new tokens sit at ITS OWN logical positions lengths[r]
        .. lengths[r]+S-1 (no shared pos, no left pad): each token's
        K/V appends into its page of the row's table (quantized pools
        rescale-on-append), and attention runs the ragged paged
        kernel — S == 1 is the decode step (j < lengths[r] + 1),
        S > 1 is the speculative VERIFY stack (token t attends
        j < lengths[r] + 1 + t, causal across the stack, one kernel
        dispatch for all S positions).  pos/start are ignored on this
        path.  `n_valid` (paged path only, traced scalar): appends of
        stack positions s >= n_valid route to the trash block — the
        suffix-prefill programs pad the stack to a bucket, and a pad
        append landing in a real page would poison an int8 page's
        monotonic scale (float pages merely hold garbage that decode
        overwrites before any query attends it, but the quantized
        rescale-on-append never forgets a max)."""
        cfg = self.cfg
        B, S, _ = x.shape
        D = cfg.head_dim
        q = _proj(cfg, cfg.heads * D, "q")(x).reshape(B, S, cfg.heads, D)
        k = _proj(cfg, cfg.kv_heads * D, "k")(x).reshape(
            B, S, cfg.kv_heads, D)
        v = _proj(cfg, cfg.kv_heads * D, "v")(x).reshape(
            B, S, cfg.kv_heads, D)

        # rotary at per-row positions (dynamic under jit)
        cos_t, sin_t = _rotary_angles(cfg.max_len, D, cfg.rope_base)

        if lengths is not None:
            # block-paged decode step (ops/paged_attention.py)
            from ..ops.paged_attention import paged_attention
            quant = len(cache_kv) == 4
            if quant:
                kp, vp, ksc, vsc = cache_kv
            else:
                kp, vp = cache_kv
                ksc = vsc = None
            page = kp.shape[2]
            # append positions, clamped so a contract violation (a row
            # decoded past its window — the scheduler finishes rows
            # first) rewrites ITS last slot instead of wrapping into a
            # neighbour's page
            rp = jnp.minimum(lengths[:, None] + jnp.arange(S)[None, :],
                             cfg.max_len - 1)     # (B, S) positions
            q = _apply_rotary(q, cos_t[rp], sin_t[rp])
            k = _apply_rotary(k, cos_t[rp], sin_t[rp])
            for s in range(S):
                app = rp[:, s]
                bids = jnp.take_along_axis(
                    tables, (app // page)[:, None], axis=1)[:, 0]
                if n_valid is not None:
                    bids = jnp.where(jnp.int32(s) < n_valid, bids, 0)
                offs = app % page
                # dead rows (length 0 everywhere on the host) route to
                # the trash block 0 via their zeroed table entries
                if quant:
                    kp, ksc = _quant_append(kp, ksc, bids, offs,
                                            k[:, s])
                    vp, vsc = _quant_append(vp, vsc, bids, offs,
                                            v[:, s])
                else:
                    kp = kp.at[bids, :, offs, :].set(k[:, s])
                    vp = vp.at[bids, :, offs, :].set(v[:, s])
            att_len = rp[:, 0] + 1
            out = paged_attention(q if S > 1 else q[:, 0], kp, vp,
                                  tables, att_len,
                                  k_scales=ksc, v_scales=vsc,
                                  mesh=self.mesh)
            out = out.reshape(B, S, cfg.heads * D)
            new_kv = (kp, vp, ksc, vsc) if quant else (kp, vp)
            return _proj(cfg, cfg.hidden, "out")(out), new_kv

        idx = pos + jnp.arange(S)                  # cache slots (S,)
        if start is None:
            cos, sin = cos_t[idx], sin_t[idx]      # (S, D/2)
        else:
            rp = jnp.maximum(idx[None, :] - start[:, None], 0)  # (B, S)
            cos, sin = cos_t[rp], sin_t[rp]        # (B, S, D/2)
        q = _apply_rotary(q, cos, sin)
        k = _apply_rotary(k, cos, sin)

        ck, cv = cache_kv
        ck = jax.lax.dynamic_update_slice(ck, k, (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v, (0, pos, 0, 0))

        if cfg.flash_min_seq and S >= cfg.flash_min_seq:
            # long-prompt prefill: blockwise causal kernel — the
            # (B, H, S, T) logits never reach HBM, and the kv heads go
            # in UNREPEATED (the kernel maps query head -> kv head)
            # (serving-only path; the decoder trains nowhere here)
            from ..ops.flash_attention import causal_flash_attention
            out = causal_flash_attention(q, ck, cv, pos, start,
                                         mesh=self.mesh)
        else:
            # short chunks: the shared reference math (one mask
            # implementation across naive / fallback / kernel —
            # ops/flash_attention pins kernel == _causal_jnp)
            from ..ops.flash_attention import _causal_jnp
            rep = cfg.heads // cfg.kv_heads
            kk = jnp.repeat(ck, rep, axis=2) if rep > 1 else ck
            vv = jnp.repeat(cv, rep, axis=2) if rep > 1 else cv
            st0 = start if start is not None \
                else jnp.zeros((B,), jnp.int32)
            out = _causal_jnp(q, kk, vv, pos, st0)
        out = out.reshape(B, S, cfg.heads * D)
        out = _proj(cfg, cfg.hidden, "out")(out)
        return out, (ck, cv)


class DecoderLayer(nn.Module):
    """Pre-norm attention + MLP block.  mlp_cls=None is the dense
    SwiGLU (param names gate/up/down directly under the layer — the
    GGUF/safetensors loaders map onto this tree); a custom mlp_cls
    (e.g. moe.MoeMlp) mounts at name 'moe' instead."""
    cfg: DecoderConfig
    mlp_cls: Any = None
    mesh: Any = None                  # see CausalAttention.mesh

    @nn.compact
    def __call__(self, x, cache_kv, pos, start=None, lengths=None,
                 tables=None, n_valid=None):
        cfg = self.cfg
        a, cache_kv = CausalAttention(cfg, self.mesh, name="attn")(
            RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_attn")(x),
            cache_kv, pos, start, lengths, tables, n_valid)
        x = x + a
        h = RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_mlp")(x)
        if self.mlp_cls is not None:
            return x + self.mlp_cls(cfg, name="moe")(h), cache_kv
        gate = _proj(cfg, cfg.mlp_dim, "gate")(h)
        up = _proj(cfg, cfg.mlp_dim, "up")(h)
        x = x + _proj(cfg, cfg.hidden, "down")(nn.silu(gate) * up)
        return x, cache_kv


class Decoder(nn.Module):
    """Causal LM over a static KV cache.  One program serves prefill
    (S = bucket) and decode (S = 1).  The whole trunk (embed, cache
    threading, final norm, LM head) is shared by every decoder family;
    mlp_cls swaps the per-layer MLP (moe.MoeDecoder passes MoeMlp)."""
    cfg: DecoderConfig
    mlp_cls: Any = None
    mesh: Any = None                  # see CausalAttention.mesh

    @nn.compact
    def __call__(self, token_ids, cache, pos, start=None, lengths=None,
                 tables=None, n_valid=None):
        """token_ids: (B, S) int32; cache: list of per-layer (k, v);
        pos: scalar int32 — cache slot of token_ids[:, 0]; start:
        optional (B,) left-pad offsets (batched serving — see
        CausalAttention).  With lengths/tables given the cache entries
        are (k_pool, v_pool) block pools and the step runs the paged
        decode path (CausalAttention).  Returns (logits (B, S, V)
        float32, new_cache)."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     name="tok_emb")(token_ids)
        new_cache = []
        for i in range(cfg.layers):
            x, kv = DecoderLayer(cfg, self.mlp_cls, self.mesh,
                                 name=f"layer_{i}")(x, cache[i], pos,
                                                    start, lengths,
                                                    tables, n_valid)
            new_cache.append(kv)
        x = RMSNorm(cfg.rms_eps, cfg.dtype, name="ln_out")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False,
                          dtype=jnp.float32, name="lm_head")(x)
        return logits, new_cache


# ---------------------------------------------------------------- sampling

def _nucleus_logits(logits, top_p: float, temp: float):
    """The sampler chain's filter, shared by the categorical draw
    (_sample_graph) and the speculative verifier's explicit
    distribution (speculative._filtered_probs) — the acceptance rule
    is only distribution-exact while both read the SAME chain.
    Returns (order, masked sorted logits).

    One stable sort of (-logits, iota) that keeps its sorted keys: an
    argsort is that sort with the keys thrown away, and gathering them
    back (`logits[order]`) under vmap over (64, 20k) logits was 13 of
    a 36 ms decode step on a v5e (PERF.md)."""
    neg, order = jax.lax.sort_key_val(
        -logits, jnp.arange(logits.shape[-1], dtype=jnp.int32),
        is_stable=True)
    sorted_logits = -neg / temp
    probs = jax.nn.softmax(sorted_logits)
    cum = jnp.cumsum(probs)
    keep = (cum - probs) < top_p          # always keeps the top token
    return order, jnp.where(keep, sorted_logits, -jnp.inf)


def _sample_graph(rng, logits, top_p: float, temp: float):
    """In-graph sampler body (traceable under scan): top-p nucleus
    filter → temperature → categorical draw.  temp <= 0 means greedy."""
    if temp <= 0:
        return jnp.argmax(logits).astype(jnp.int32)
    order, masked = _nucleus_logits(logits, top_p, temp)
    choice = jax.random.categorical(rng, masked)
    return order[choice].astype(jnp.int32)


def _sample_top_p_impl(rng, logits, *, top_p: float = 0.9,
                       temp: float = 0.7):
    """The reference's sampler chain (splainference.cpp:272-279),
    jit-compiled for one-off host-side sampling."""
    return _sample_graph(rng, logits, top_p, temp)


sample_top_p = DEVTIME.register(
    "completer.sample",
    jax.jit(_sample_top_p_impl, static_argnames=("top_p", "temp")))


def _sample_rows(rng, logits, top_p: float, temp: float):
    """Per-row sampling graph shared by every batched path (prefill
    tail and the in-chunk scan step must draw from the SAME sampler):
    logits (B, V) -> (B,) ids."""
    subs = jax.random.split(rng, logits.shape[0])
    return jax.vmap(lambda r, l: _sample_graph(r, l, top_p, temp))(
        subs, logits)


def _sample_top_p_batch_impl(rng, logits, *, top_p: float = 0.9,
                             temp: float = 0.7):
    """Batched sampler: logits (B, V) -> (B,) ids in ONE dispatch
    (B separate sample_top_p calls would pay B device round trips)."""
    return _sample_rows(rng, logits, top_p, temp)


sample_top_p_batch = DEVTIME.register(
    "completer.sample_batch",
    jax.jit(_sample_top_p_batch_impl,
            static_argnames=("top_p", "temp")))


# ------------------------------------------------------------- front end

def join_one(model, cache, join) -> np.ndarray:
    """An admission round of ONE seated request (engine/prefix_cache.py
    `Join`: row, the prompt's ids, the tokens its table maps, hit or
    miss, the snapshot it leaves) through `model`'s one-row programs: a
    miss prefills the whole prompt, a hit the suffix atop the mapped
    prefix.  Returns the last token's logits (V,) on the host — the
    lane draws the first token from them.  This is the one place that
    decides what a round of one runs."""
    skw = ({"snap_at": join.snap[1], "snap_slot": join.snap[0]}
           if join.snap else {})
    if join.hit or join.zeroed:
        # a hit's suffix atop the mapped prefix — or a miss whose state
        # slot the lane zeroed at its seat: the same program from an
        # empty table (models/kda.StateSlotPrograms)
        return model.paged_append_prefill(
            cache, np.asarray(join.ids[join.match:], np.int32), join.row,
            **skw)
    return model.paged_prefill_row(
        cache, np.asarray(join.ids, np.int32), join.row, **skw)


class RowJoins:
    """The admission surface of a model whose prefill programs are one
    row wide (the key/value decoder, its sharded and its speculative
    wrappers): no join rides a round, every request is a round of its
    own.  models/mla.py has the surface of the families with a
    row-batched suffix program."""

    def round_cap(self, cache) -> int:
        """Joins one round's program holds."""
        return 1

    def rides_round(self, join) -> bool:
        """Whether `join` waits for its round's other joins."""
        return False

    def join(self, cache, joins):
        """Prefill a round's seated rows: (logits, first tokens drawn
        in graph — None for a round of one, whose logits (V,) are on
        the host for the lane's draw)."""
        (one,) = joins
        return join_one(self, cache, one), None


class CompletionModel(RowJoins):
    """Bucketed prefill + token-at-a-time decode with persistent cache.

    paged_supported marks the block-paged continuous-batching surface
    (init_paged / paged_prefill_row / paged_decode_chunk) as usable.
    parallel.ShardedCompletionModel serves it tensor-parallel (pools
    sharded on kv heads, the ragged kernel under shard_map); a model
    whose module cannot thread the mesh (a custom module built
    without one) clears the flag and the completion daemon falls back
    to dense serving.

    The generation surface the completion daemon drives:
        pos, logits = model.prefill(prompt_ids)
        tok = model.sample(logits)
        while ...: logits = model.decode_one(tok); tok = model.sample(...)
    Cache state lives on device between calls (no host round-trip of the
    KV tensors).
    """

    paged_supported = True

    def __init__(self, cfg: DecoderConfig, *, seed: int = 0,
                 buckets: tuple[int, ...] = (64, 128, 256, 512, 1024),
                 params: Any = None, weights: str | None = None,
                 top_p: float = 0.9, temp: float = 0.7,
                 module: Any = None, kv_dtype: str | None = None,
                 suffix_buckets: tuple[int, ...] = (16, 64)):
        self.cfg = cfg
        # pad buckets for paged_append_prefill's suffix stacks (the
        # prefix-cache hit path): small on purpose — each program
        # unrolls S sequential page appends per layer, so a bucket-
        # 1024 variant would compile forever for a path whose whole
        # point is that suffixes are short.  Longer suffixes loop the
        # largest bucket.
        self.suffix_buckets = tuple(sorted(
            b for b in suffix_buckets if 0 < b < cfg.max_len)) or (
            min(16, max(1, cfg.max_len - 1)),)
        # default paged-pool storage dtype for init_paged (None = the
        # model's native activation dtype); "int8" turns the whole
        # continuous lane quantized (--kv-dtype on the daemon)
        self.kv_dtype = kv_dtype
        # module override: any flax module with the Decoder call
        # signature (ids, cache, pos) -> (logits, cache) — e.g. the
        # MoE family (models/moe.MoeDecoder)
        self.module = module if module is not None else Decoder(cfg)
        self.buckets = tuple(b for b in buckets if b <= cfg.max_len)
        self.top_p, self.temp = top_p, temp
        if not self.buckets or self.buckets[-1] < cfg.max_len:
            # a prompt longer than the largest bucket (but inside the
            # window) must still have a program to land in
            self.buckets = self.buckets + (cfg.max_len,)
        if params is None and weights is not None:
            if weights.endswith(".gguf"):
                from .gguf import load_decoder_params
                params = load_decoder_params(weights, cfg)
            else:
                params = load_safetensors_params(weights, cfg)
        if cfg.quantized and getattr(cfg, "weights_int8", False):
            raise ValueError(
                "quantized (Q8_0 blocks) and weights_int8 (per-channel"
                " MXU) are two residencies for the same projections — "
                "pick one")
        if params is not None and (cfg.quantized
                                   or getattr(cfg, "weights_int8",
                                              False)):
            # float checkpoints re-quantize into the int8-resident
            # layout (idempotent: already-quantized trees pass through)
            from .quant import quantize_decoder_params
            params = quantize_decoder_params(
                params,
                mode="channel" if getattr(cfg, "weights_int8", False)
                else "block")
        if params is None:
            cache = init_cache(cfg, 1)
            params = self.module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros((1, self.buckets[0]), jnp.int32), cache,
                jnp.int32(0))
        self.params = params
        # devtime attribution lane for the LAZY program caches below
        # (chunk/paged): a disaggregated lane overwrites this
        # ("prefill"/"decode") before warmup so its programs ledger
        # under their phase — prefill.bucket_commit, decode.paged_chunk
        # — while the trunk and samplers (registered eagerly, shared
        # geometry) stay under the canonical completer.* names.
        self.devtime_lane = "completer"
        self._fn = DEVTIME.register("completer.trunk",
                                    jax.jit(self.module.apply))
        self._rng = jax.random.PRNGKey(seed + 1)
        self._cache = None
        self._pos = 0
        self._start = None            # (B,) left-pad offsets when batched
        self._batch = 0
        self._chunk_progs: dict[tuple, Any] = {}
        self._paged_progs: dict[tuple, Any] = {}  # paged decode/commit

    def _devname(self, short: str) -> str:
        """The devtime registration name for a lazily built program:
        `<devtime_lane>.<short>`.  Disaggregated lanes rename the
        commit scatter to its phase-honest name — the prefill lane's
        whole dense pass exists to feed that scatter, so it ledgers
        as prefill.bucket_commit (ROADMAP's name for it), not as a
        generic paged_commit."""
        if self.devtime_lane != "completer" and short == "paged_commit":
            short = "bucket_commit"
        return f"{self.devtime_lane}.{short}"

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def reset(self) -> None:
        """llama_memory_clear analog (splainference.cpp:378)."""
        self._cache = None
        self._pos = 0
        self._start = None
        self._batch = 0

    def _fresh_cache(self, batch: int = 1):
        """Zeroed KV cache for a new request (or a batch of them).
        Subclasses place it with an explicit device sharding
        (parallel.serve)."""
        return init_cache(self.cfg, batch)

    def prefill(self, prompt_ids: np.ndarray) -> np.ndarray:
        """prompt_ids: (P,) int32, P < max_len.  Pads to a bucket, runs
        one prefill program, returns the last real token's logits (V,)."""
        P = len(prompt_ids)
        if P == 0:
            raise ValueError("empty prompt")
        if P >= self.cfg.max_len:
            raise ValueError("prompt exceeds context window")
        b = self.bucket_for(P)
        ids = np.zeros((1, b), np.int32)
        ids[0, :P] = prompt_ids[:P]
        cache = self._fresh_cache()
        logits, cache = self._fn(self.params, jnp.asarray(ids), cache,
                                 jnp.int32(0))
        # cache rows P..b-1 hold pad-token k/v, but they can never leak:
        # a query at absolute position p attends only j <= p, and every
        # row <= p is rewritten with real data (prompt or decoded token)
        # before the first query that could see it.
        self._cache, self._pos = cache, P
        self._start, self._batch = None, 1
        return np.asarray(logits[0, P - 1])

    def decode_one(self, token: int) -> np.ndarray:
        """Append one token at the current position; returns logits (V,)."""
        if self._cache is None:
            raise RuntimeError("prefill first")
        if self._pos >= self.cfg.max_len:
            raise RuntimeError("context window full")
        ids = jnp.full((1, 1), int(token), jnp.int32)
        logits, self._cache = self._fn(self.params, ids, self._cache,
                                       jnp.int32(self._pos))
        self._pos += 1
        return np.asarray(logits[0, 0])

    def sample(self, logits: np.ndarray) -> int:
        self._rng, sub = jax.random.split(self._rng)
        return int(sample_top_p(sub, jnp.asarray(logits),
                                top_p=self.top_p, temp=self.temp))

    def sample_batch(self, logits: np.ndarray) -> np.ndarray:
        """(B, V) logits -> (B,) sampled ids in one dispatch."""
        self._rng, sub = jax.random.split(self._rng)
        return np.asarray(sample_top_p_batch(
            sub, jnp.asarray(logits), top_p=self.top_p,
            temp=self.temp)).astype(np.int32)

    # -- chunked decode (the tokens/sec path) -----------------------------

    def _chunk_program(self, n: int, bp: int = 1):
        """One lax.scan program decoding n slots for bp rows (bp=1 is
        the serial path): per step, forward one token per row, sample
        the next in-graph (_sample_rows — the SAME sampler graph for
        serial, batched, and the prefill tail).  The KV cache never
        round-trips to the host (donated buffer); the host sees only
        the sampled ids per chunk — the reference's 8-token flush
        cadence (splainference.cpp:333-354) becomes the device↔host
        sync boundary instead of a per-token one."""
        # keyed on the sampler settings too: the program closes over
        # top_p/temp, so a consumer mutating them after first use must
        # get a fresh program, not silently reuse the stale one
        key = (n, bp, self.top_p, self.temp)
        fn = self._chunk_progs.get(key)
        if fn is None:
            module, top_p, temp = self.module, self.top_p, self.temp

            def run(params, cache, pos, start, rng, toks):
                def step(carry, _):
                    cache, pos, rng, toks = carry
                    logits, cache = module.apply(
                        params, toks.reshape(-1, 1), cache, pos, start)
                    rng, sub = jax.random.split(rng)
                    nxt = _sample_rows(sub, logits[:, 0], top_p, temp)
                    return (cache, pos + 1, rng, nxt), nxt

                (cache, _, _, _), out = jax.lax.scan(
                    step, (cache, pos, rng, toks), None, length=n)
                return cache, out                  # out: (n, bp)

            fn = DEVTIME.register(self._devname("chunk"),
                                  jax.jit(run, donate_argnums=(1,)))
            self._chunk_progs[key] = fn
            # bound the cache: per-request sampler settings must not
            # retain every stale compiled program for process lifetime —
            # past a handful, drop entries for settings other than the
            # current ones (their programs re-compile if revisited)
            if len(self._chunk_progs) > 8:
                cur = (self.top_p, self.temp)
                self._chunk_progs = {
                    k: v for k, v in self._chunk_progs.items()
                    if k[-2:] == cur}
        return fn

    def decode_chunk(self, token: int, n: int) -> np.ndarray:
        """Append `token`, then decode and sample n tokens on device in
        one program.  Returns the n sampled token ids.  The caller
        checks EOG host-side per token; a mid-chunk EOG wastes at most
        n-1 speculative steps (their cache rows are beyond the final
        position and are reset with the request)."""
        if self._cache is None:
            raise RuntimeError("prefill first")
        if self._pos + n > self.cfg.max_len:
            raise RuntimeError("context window full")
        self._rng, sub = jax.random.split(self._rng)
        self._cache, out = self._chunk_program(n)(
            self.params, self._cache, jnp.int32(self._pos), None, sub,
            jnp.asarray([int(token)], jnp.int32))
        self._pos += n
        return np.asarray(out)[:, 0]

    def generate_tokens(self, prompt_ids: np.ndarray, max_new: int,
                        *, chunk: int = 8, eos_id: int | None = None):
        """Generator of sampled token ids: bucketed prefill, then
        chunk-at-a-time on-device decode (single-token fallback near the
        window/budget tail so no per-length programs compile).

        Contract: with eos_id=None the generator keeps yielding the
        chunk's SPECULATIVE tokens after an end-of-generation token —
        the consumer must detect its own stop condition and break (the
        completion daemon does).  Pass eos_id to have the generator
        stop itself right after yielding that token."""
        logits = self.prefill(np.asarray(prompt_ids, np.int32))
        tok = self.sample(logits)
        yield int(tok)
        if eos_id is not None and tok == eos_id:
            return
        produced = 1
        while produced < max_new:
            room = min(self.cfg.max_len - self._pos,
                       max_new - produced)
            if room <= 0:
                break
            if room < chunk:
                logits = self.decode_one(tok)
                tok = self.sample(logits)
                yield int(tok)
                if eos_id is not None and tok == eos_id:
                    return
                produced += 1
                continue
            toks = self.decode_chunk(tok, chunk)
            for t in toks:
                yield int(t)
                if eos_id is not None and int(t) == eos_id:
                    return
            tok = int(toks[-1])
            produced += chunk

    # -- batched generation (the aggregate-throughput path) ----------------
    #
    # The reference's completion sidecar is strictly serial — one
    # llama.cpp context, one request at a time (splainference.cpp:
    # 414-448).  On TPU that wastes the device: a decode step for one
    # row costs the same dispatch round trip as a decode step for
    # eight.  Batched serving left-pads the
    # prompts into one bucket so every row's NEXT slot is uniform:
    # row r's tokens occupy slots [bucket - P_r, bucket) and decode
    # proceeds at slot bucket, bucket+1, ... for all rows at once —
    # only prefill needs per-row position offsets (`start`).

    def prefill_batch(self, prompts: list[np.ndarray]) -> np.ndarray:
        """Left-padded batched prefill.  prompts: list of (P_i,) int32,
        each 0 < P_i < max_len.  Returns the last real token's logits
        per row, (B, vocab) float32."""
        B = len(prompts)
        if B == 0:
            raise ValueError("empty batch")
        lens = [len(p) for p in prompts]
        if min(lens) == 0:
            raise ValueError("empty prompt")
        if max(lens) >= self.cfg.max_len:
            raise ValueError("prompt exceeds context window")
        b = self.bucket_for(max(lens))
        bp = 1 << max(B - 1, 0).bit_length()     # batch power-of-two pad
        ids = np.zeros((bp, b), np.int32)
        start = np.full((bp,), b, np.int32)      # pad rows: no real slots
        for r, p in enumerate(prompts):
            ids[r, b - lens[r]:] = p
            start[r] = b - lens[r]
        cache = self._fresh_cache(bp)
        start_d = jnp.asarray(start)
        logits, cache = self._fn(self.params, jnp.asarray(ids), cache,
                                 jnp.int32(0), start_d)
        self._cache, self._pos = cache, b
        self._start, self._batch = start_d, B
        # every row's last REAL token sits in the last slot (left pad)
        return np.asarray(logits[:B, b - 1])

    def decode_chunk_batch(self, tokens: np.ndarray, n: int) -> np.ndarray:
        """Append tokens (B,), decode+sample n steps on device for the
        whole batch.  Returns (B, n) sampled ids.  Rows that already
        finished keep decoding speculatively — the caller discards."""
        if self._cache is None or getattr(self, "_start", None) is None:
            raise RuntimeError("prefill_batch first")
        if self._pos + n > self.cfg.max_len:
            raise RuntimeError("context window full")
        bp = self._cache[0][0].shape[0]
        toks = np.zeros((bp,), np.int32)
        toks[: self._batch] = np.asarray(tokens, np.int32)
        self._rng, sub = jax.random.split(self._rng)
        self._cache, out = self._chunk_program(n, bp)(
            self.params, self._cache, jnp.int32(self._pos),
            self._start, sub, jnp.asarray(toks))
        self._pos += n
        return np.asarray(out).T[: self._batch]    # (B, n)

    # -- paged serving (the continuous-batching path) ---------------------
    #
    # The dense batched path above shares ONE window across the batch:
    # prefill parks every row at the same bucket position and the cache
    # resets when every slot frees.  The paged path drops that: each row
    # has its own logical positions 0..len-1 in pages of a global pool
    # (PagedKVCache), a joiner prefills into freshly allocated pages
    # at ANY time with its full context, and a finished row's pages
    # return to the pool immediately.  Prefill itself reuses the
    # serial bucket programs over a bucket-sized dense scratch cache,
    # then one commit program per bucket scatters the rows into pages
    # — prompts keep attending through causal_flash_attention; only
    # the decode step runs the ragged paged kernel.

    def _pool_sharding(self):
        """Device placement for the paged block pools: None here (one
        chip); ShardedCompletionModel returns the kv-head NamedSharding
        so the pools split over the tp mesh axis."""
        return None

    def _pool_scale_sharding(self):
        """Placement for an int8 pool's (n_blocks, KH) scales: None
        here; ShardedCompletionModel splits them on THEIR kv-head
        axis so scales shard with the heads they scale."""
        return None

    def _paged_pool_out_shardings(self, n_pool_lists: int, n_rep: int,
                                  n_scale_lists: int = 0):
        """out_shardings for a paged program returning n_pool_lists
        per-layer pool lists, then n_scale_lists per-layer scale
        lists (int8 pools), then n_rep replicated arrays — or None
        when the pools are unsharded.  Pinning the OUTPUT shardings
        keeps the jit signature stable across the program chain
        (fresh pool -> commit out -> chunk out -> chunk in ...):
        without it the first serve-time call after warmup sees
        GSPMD-chosen output shardings that hash differently from the
        explicitly placed fresh pools and silently recompiles."""
        # seeded-recompile drill (scripts/compile_gate_check.py
        # --seed-recompile): dropping the pin reproduces the exact
        # PR 8 failure class the compile ledger exists to catch — the
        # gate must then FAIL naming the program and its shapes key
        if os.environ.get("SPTPU_SEED_RECOMPILE") == "1":
            return None
        sh = self._pool_sharding()
        if sh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        rep = NamedSharding(sh.mesh, PartitionSpec())
        ssh = self._pool_scale_sharding() or rep
        layers = self.cfg.layers
        return tuple([sh] * layers for _ in range(n_pool_lists)) \
            + tuple([ssh] * layers for _ in range(n_scale_lists)) \
            + (rep,) * n_rep

    def _paged_scratch(self, b: int):
        """The (1, bucket) dense scratch cache paged prefill runs the
        trunk over; subclasses place it with an explicit sharding so
        the commit scatter into a sharded pool stays collective-free."""
        cfg = self.cfg
        z = jnp.zeros((1, b, cfg.kv_heads, cfg.head_dim), cfg.dtype)
        return [(z, z) for _ in range(cfg.layers)]

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None) -> PagedKVCache:
        """Fresh paged pool serving `batch` concurrent rows.  The
        default pool holds batch full windows (== dense HBM at this
        batch); cap pool_pages lower to spend HBM on batch width
        instead of cache padding.  kv_dtype None defers to the
        model's default (the --kv-dtype constructor knob); "int8"
        stores the pool quantized with per-page scales."""
        return PagedKVCache(self.cfg, batch, page=page,
                            pool_pages=pool_pages,
                            kv_dtype=(self.kv_dtype if kv_dtype is None
                                      else kv_dtype),
                            sharding=self._pool_sharding(),
                            scale_sharding=self._pool_scale_sharding())

    def _paged_commit_program(self, bucket: int, page: int,
                              quantized: bool = False,
                              packed: bool = False):
        """One program scattering a (1, bucket) dense prefill cache
        into pool pages at the given block ids (page-granular; the
        tail of the last page holds garbage the length mask hides
        until decode appends overwrite it).

        The QUANTIZED variant is where int8 pools quantize on commit:
        rows past the prompt's n_valid are zeroed FIRST (pad-token
        K/V would otherwise inflate the page scale for nothing), then
        each (page, kv head) gets a symmetric scale d = absmax/127
        and int8 values — the same Q8_0-style geometry as the weight
        residency (models/quant.py), at page granularity.  PACKED
        additionally quantizes at qmax 7 and packs whole pages two
        codes per byte (ops/paged_attention.pack_int4)."""
        key = ("commit", bucket, page, quantized, packed)
        fn = self._paged_progs.get(key)
        if fn is None:
            n_cp = -(-bucket // page)
            pad = n_cp * page - bucket
            qmax = 7.0 if packed else 127.0

            def blocks(x, nvalid=None):
                x = x[0]                           # (bucket, KH, D)
                if nvalid is not None:
                    keep = (jnp.arange(bucket) < nvalid)[:, None, None]
                    x = jnp.where(keep, x, 0)
                if pad:
                    x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
                return x.reshape(n_cp, page, *x.shape[1:]) \
                        .transpose(0, 2, 1, 3)     # (n_cp,KH,pg,D)

            if quantized:
                def run(k_pools, v_pools, k_scales, v_scales, dense,
                        bids, nvalid):
                    def q8(x):
                        xb = blocks(x, nvalid).astype(jnp.float32)
                        d = jnp.max(jnp.abs(xb), axis=(2, 3)) / qmax
                        q = jnp.round(
                            xb / jnp.where(d > 0, d, 1.0)[:, :, None,
                                                          None])
                        q = jnp.clip(q, -qmax, qmax)
                        if packed:
                            from ..ops.paged_attention import pack_int4
                            return pack_int4(q.astype(jnp.int32)), d
                        return q.astype(jnp.int8), d

                    outk, outv, outks, outvs = [], [], [], []
                    for (kd, vd), kp, vp, ks, vs in zip(
                            dense, k_pools, v_pools, k_scales,
                            v_scales):
                        qk, dk = q8(kd)
                        qv, dv = q8(vd)
                        outk.append(kp.at[bids].set(qk))
                        outv.append(vp.at[bids].set(qv))
                        outks.append(ks.at[bids].set(dk))
                        outvs.append(vs.at[bids].set(dv))
                    return outk, outv, outks, outvs

                out_sh = self._paged_pool_out_shardings(
                    2, 0, n_scale_lists=2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("paged_commit"),
                    jax.jit(run, donate_argnums=(0, 1, 2, 3), **kw))
            else:
                def run(k_pools, v_pools, dense, bids):
                    outk, outv = [], []
                    for (kd, vd), kp, vp in zip(dense, k_pools,
                                                v_pools):
                        outk.append(kp.at[bids].set(blocks(kd)))
                        outv.append(vp.at[bids].set(blocks(vd)))
                    return outk, outv

                out_sh = self._paged_pool_out_shardings(2, 0)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("paged_commit"),
                    jax.jit(run, donate_argnums=(0, 1), **kw))
            self._paged_progs[key] = fn
        return fn

    def paged_prefill_row(self, cache: PagedKVCache,
                          prompt_ids: np.ndarray, row: int) -> np.ndarray:
        """Prefill one row's prompt into its pages: bucketed dense
        prefill over a (1, bucket) scratch cache, then the commit
        scatter.  The row keeps its FULL prompt (callers clip only to
        the window budget).  Returns the last real token's logits
        (V,) for sampling the first output token."""
        cfg = self.cfg
        P = len(prompt_ids)
        if P == 0:
            raise ValueError("empty prompt")
        if P >= cfg.max_len:
            raise ValueError("prompt exceeds context window")
        if not cache.ensure(row, P):
            raise RuntimeError(
                f"paged pool exhausted: row {row} needs "
                f"{cache.pages_needed(P)} pages, {cache.free_pages} free")
        b = self.bucket_for(P)
        ids = np.zeros((1, b), np.int32)
        ids[0, :P] = np.asarray(prompt_ids[:P], np.int32)
        # bucket-sized dense scratch (NOT max_len): the same jitted
        # trunk runs with T = bucket, so paged prefill costs one small
        # program per bucket instead of a full-window cache
        scratch = self._paged_scratch(b)
        logits, dense = self._fn(self.params, jnp.asarray(ids), scratch,
                                 jnp.int32(0))
        n_cp = -(-b // cache.page)
        # table entries past the prompt's pages are 0 = trash: the
        # scatter's excess bucket rows land there harmlessly
        bids = cache.tables[row, :n_cp].copy()
        if cache.quantized:
            kp, vp, ks, vs = self._paged_commit_program(
                b, cache.page, True, cache.packed)(
                cache.k_pools, cache.v_pools, cache.k_scales,
                cache.v_scales, dense, jnp.asarray(bids),
                jnp.int32(P))
            cache.k_scales, cache.v_scales = list(ks), list(vs)
        else:
            kp, vp = self._paged_commit_program(b, cache.page)(
                cache.k_pools, cache.v_pools, dense, jnp.asarray(bids))
        cache.k_pools, cache.v_pools = list(kp), list(vp)
        cache.lengths[row] = P
        return np.asarray(logits[0, P - 1])

    # -- prefix-shared serving (refcounted pages + COW) -------------------
    #
    # The radix prefix cache (engine/prefix_cache.py) turns a shared
    # prompt prefix into a host-side table write: map_shared bumps
    # refcounts, and only the UNCACHED suffix still runs a forward
    # pass — through the programs below, which attend over the mapped
    # pages via the same ragged paged kernel decode uses (the suffix's
    # K/V depend on the whole prefix, so a dense scratch prefill
    # cannot serve it).  A fully cached prompt prefills NOTHING: the
    # row enters at lengths = P-1 and the first decode chunk replays
    # the last prompt token — whose append lands inside the shared
    # tail page and so triggers the copy-on-write below.

    def _paged_suffix_program(self, sb: int, quantized: bool = False):
        """One program appending a (1, sb) suffix stack into a row's
        pages (positions lengths..lengths+n_valid-1; pad appends past
        n_valid route to the trash block) and attending through the
        ragged paged kernel — causal across the stack, over the
        mapped prefix.  Returns the pools and the LAST VALID token's
        logits for sampling the row's first output token."""
        key = ("suffix", sb, quantized)
        fn = self._paged_progs.get(key)
        if fn is None:
            module = self.module

            if quantized:
                def run(params, k_pools, v_pools, k_scales, v_scales,
                        table, length, ids, n_valid):
                    cache = list(zip(k_pools, v_pools, k_scales,
                                     v_scales))
                    logits, new_cache = module.apply(
                        params, ids, cache, jnp.int32(0), None,
                        length, table, n_valid)
                    return ([c[0] for c in new_cache],
                            [c[1] for c in new_cache],
                            [c[2] for c in new_cache],
                            [c[3] for c in new_cache],
                            logits[0, n_valid - 1])

                out_sh = self._paged_pool_out_shardings(
                    2, 1, n_scale_lists=2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("suffix_prefill"),
                    jax.jit(run, donate_argnums=(1, 2, 3, 4), **kw))
            else:
                def run(params, k_pools, v_pools, table, length, ids,
                        n_valid):
                    cache = list(zip(k_pools, v_pools))
                    logits, new_cache = module.apply(
                        params, ids, cache, jnp.int32(0), None,
                        length, table, n_valid)
                    return ([c[0] for c in new_cache],
                            [c[1] for c in new_cache],
                            logits[0, n_valid - 1])

                out_sh = self._paged_pool_out_shardings(2, 1)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("suffix_prefill"),
                    jax.jit(run, donate_argnums=(1, 2), **kw))
            self._paged_progs[key] = fn
        return fn

    def paged_append_prefill(self, cache: PagedKVCache, suffix_ids,
                             row: int) -> np.ndarray:
        """Prefill ONLY the uncached suffix of row's prompt, atop the
        cache.lengths[row] tokens its table already maps (shared
        prefix pages + any earlier suffix chunks).  Suffixes longer
        than the largest suffix bucket loop it.  The caller has
        ensure()d the row's worst case; a dry pool here is the same
        contract violation paged_prefill_row raises on.  Returns the
        last real token's logits (V,)."""
        ids = np.asarray(suffix_ids, np.int32)
        if ids.size == 0:
            raise ValueError("empty suffix")
        pos = int(cache.lengths[row])
        if pos + ids.size >= self.cfg.max_len:
            raise ValueError("suffix exceeds context window")
        if not cache.ensure(row, pos + ids.size):
            raise RuntimeError(
                f"paged pool exhausted: row {row} suffix needs "
                f"{cache.pages_needed(pos + ids.size)} pages")
        table = cache.tables[row: row + 1]
        logits = None
        off = 0
        while off < ids.size:
            rem = ids.size - off
            sb = next((b for b in self.suffix_buckets if b >= rem),
                      self.suffix_buckets[-1])
            n = min(rem, sb)
            chunk = np.zeros((1, sb), np.int32)
            chunk[0, :n] = ids[off: off + n]
            args = (self.params, cache.k_pools, cache.v_pools)
            if cache.quantized:
                args += (cache.k_scales, cache.v_scales)
            # host-side copies, not views: lengths is bumped in place
            # right after this asynchronous dispatch (see
            # paged_decode_chunk_async)
            args += (jnp.asarray(np.array(table)),
                     jnp.asarray(np.array(cache.lengths[row: row + 1])),
                     jnp.asarray(chunk), jnp.int32(n))
            out = self._paged_suffix_program(sb, cache.quantized)(*args)
            if cache.quantized:
                kp, vp, ks, vs, logits = out
                cache.k_scales, cache.v_scales = list(ks), list(vs)
            else:
                kp, vp, logits = out
            cache.k_pools, cache.v_pools = list(kp), list(vp)
            cache.lengths[row] += n
            off += n
        return np.asarray(logits)

    def _cow_copy_program(self, quantized: bool = False):
        """One program duplicating pool page `src` into `dst` across
        every layer and side (+ the int8 scales) — the device half of
        a copy-on-write, dispatched BEFORE the table swap so the
        shared original is still intact when read."""
        key = ("cow", quantized)
        fn = self._paged_progs.get(key)
        if fn is None:
            if quantized:
                def run(k_pools, v_pools, k_scales, v_scales, src,
                        dst):
                    return ([p.at[dst].set(p[src]) for p in k_pools],
                            [p.at[dst].set(p[src]) for p in v_pools],
                            [s.at[dst].set(s[src]) for s in k_scales],
                            [s.at[dst].set(s[src]) for s in v_scales])

                out_sh = self._paged_pool_out_shardings(
                    2, 0, n_scale_lists=2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("cow_copy"),
                    jax.jit(run, donate_argnums=(0, 1, 2, 3), **kw))
            else:
                def run(k_pools, v_pools, src, dst):
                    return ([p.at[dst].set(p[src]) for p in k_pools],
                            [p.at[dst].set(p[src]) for p in v_pools])

                out_sh = self._paged_pool_out_shardings(2, 0)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("cow_copy"),
                    jax.jit(run, donate_argnums=(0, 1), **kw))
            self._paged_progs[key] = fn
        return fn

    def _cow_fixups(self, cache) -> int:
        """Copy-on-write pass before a decode dispatch: every row
        whose next append would write into a shared or tree-frozen
        page gets a private copy first, so a writer NEVER mutates a
        page another row (or a future joiner walking the prefix tree)
        reads.  In practice only a fully-cached prompt's replay
        append ever qualifies — partial-hit rows append into their
        privately prefilled tail — so this is one page copy per
        full-cover admission, not a steady-state cost.  Returns pages
        copied."""
        targets = getattr(cache, "cow_targets", None)
        if targets is None:
            return 0
        n = 0
        for row, p_idx in targets():
            src = int(cache.tables[row, p_idx])
            dst = cache._alloc_page()
            if cache.quantized:
                kp, vp, ks, vs = self._cow_copy_program(True)(
                    cache.k_pools, cache.v_pools, cache.k_scales,
                    cache.v_scales, jnp.int32(src), jnp.int32(dst))
                cache.k_scales, cache.v_scales = list(ks), list(vs)
            else:
                kp, vp = self._cow_copy_program(False)(
                    cache.k_pools, cache.v_pools, jnp.int32(src),
                    jnp.int32(dst))
            cache.k_pools, cache.v_pools = list(kp), list(vp)
            cache.commit_cow(row, p_idx, dst)
            n += 1
        return n

    # -- disaggregated handoff (prefill lane -> decode lane) --------------
    #
    # The two lane types hold SEPARATE pools (separate processes, each
    # with its own HBM envelope), so a handoff moves a row's committed
    # pages through the host: the prefill lane gathers each page once
    # (all layers stacked, one device->host copy per page — the same
    # once-per-request cost class as the join itself), lands the bytes
    # in the store, and the decode lane scatters them into its own
    # pool at adoption.  Within ONE pool (unified lane, or a future
    # colocated deployment) adoption stays the refcount table write
    # map_shared already is — these programs are the cross-pool wire.

    def _rep_out_shardings(self, n: int):
        """out_shardings pinning n replicated outputs — None for an
        unsharded pool (the jit default)."""
        if os.environ.get("SPTPU_SEED_RECOMPILE") == "1":
            return None
        sh = self._pool_sharding()
        if sh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return (NamedSharding(sh.mesh, PartitionSpec()),) * n

    def _page_export_program(self, quantized: bool = False):
        """One program gathering pool page `bid` across every layer
        and side into replicated (layers, KH, page, D) stacks (+ the
        (layers, KH) scale stacks for int8 pools) — the device half
        of a handoff export, one dispatch per page."""
        key = ("page_export", quantized)
        fn = self._paged_progs.get(key)
        if fn is None:
            if quantized:
                def run(k_pools, v_pools, k_scales, v_scales, bid):
                    return (jnp.stack([p[bid] for p in k_pools]),
                            jnp.stack([p[bid] for p in v_pools]),
                            jnp.stack([s[bid] for s in k_scales]),
                            jnp.stack([s[bid] for s in v_scales]))
                n_out = 4
            else:
                def run(k_pools, v_pools, bid):
                    return (jnp.stack([p[bid] for p in k_pools]),
                            jnp.stack([p[bid] for p in v_pools]))
                n_out = 2
            out_sh = self._rep_out_shardings(n_out)
            kw = {} if out_sh is None else {"out_shardings": out_sh}
            fn = DEVTIME.register(self._devname("page_export"),
                                  jax.jit(run, **kw))
            self._paged_progs[key] = fn
        return fn

    def _page_import_program(self, quantized: bool = False):
        """One program scattering a handed-off page's stacked host
        arrays into pool page `bid` across every layer and side —
        the device half of an adoption import."""
        key = ("page_import", quantized)
        fn = self._paged_progs.get(key)
        if fn is None:
            if quantized:
                def run(k_pools, v_pools, k_scales, v_scales,
                        kv, vv, ks, vs, bid):
                    return (
                        [p.at[bid].set(kv[i])
                         for i, p in enumerate(k_pools)],
                        [p.at[bid].set(vv[i])
                         for i, p in enumerate(v_pools)],
                        [s.at[bid].set(ks[i])
                         for i, s in enumerate(k_scales)],
                        [s.at[bid].set(vs[i])
                         for i, s in enumerate(v_scales)])

                out_sh = self._paged_pool_out_shardings(
                    2, 0, n_scale_lists=2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("page_import"),
                    jax.jit(run, donate_argnums=(0, 1, 2, 3), **kw))
            else:
                def run(k_pools, v_pools, kv, vv, bid):
                    return (
                        [p.at[bid].set(kv[i])
                         for i, p in enumerate(k_pools)],
                        [p.at[bid].set(vv[i])
                         for i, p in enumerate(v_pools)])

                out_sh = self._paged_pool_out_shardings(2, 0)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("page_import"),
                    jax.jit(run, donate_argnums=(0, 1), **kw))
            self._paged_progs[key] = fn
        return fn

    def _page_wire_dtype(self, cache: PagedKVCache):
        """Wire pages carry the pool's NATIVE storage dtype — int8,
        uint8 for int4-packed pools (the packed bytes go over the
        wire verbatim, halving handoff and tier-shadow bytes), or the
        float dtype."""
        if not cache.quantized:
            return np.dtype(cache.k_pools[0].dtype)
        return np.dtype("uint8") if cache.packed else np.dtype("int8")

    def _page_wire_shape(self, cache: PagedKVCache):
        """One side's stacked wire-page shape — the pool's own value
        geometry (last axis head_dim/2 for int4-packed pools), read
        from the placed buffers so wire and pool can never skew."""
        return (self.cfg.layers, self.cfg.kv_heads, cache.page,
                int(cache.k_pools[0].shape[3]))

    def page_wire_bytes(self, cache: PagedKVCache) -> int:
        """Bytes one exported page occupies on the wire (k + v values
        across every layer; quantized scales ride a separate key).
        int4-packed pools halve this — the wire carries the packed
        bytes."""
        n = 2 * self._page_wire_dtype(cache).itemsize
        for d in self._page_wire_shape(cache):
            n *= d
        return n

    def export_row_pages(self, cache: PagedKVCache, row: int
                         ) -> tuple[list[bytes], list[bytes | None]]:
        """Host copies of every page `row`'s table maps, in table
        order: (page_bytes, scale_bytes) lists, each page's bytes the
        k stack then the v stack ((layers, KH, page, D) each); scale
        entries are None for float pools.  The partial last page is
        exported whole — adoption masks by length, exactly as the
        ragged kernel does."""
        n = len(cache._owned[row])
        prog = self._page_export_program(cache.quantized)
        pages: list[bytes] = []
        scales: list[bytes | None] = []
        for p_idx in range(n):
            bid = jnp.int32(int(cache.tables[row, p_idx]))
            if cache.quantized:
                k, v, ks, vs = prog(cache.k_pools, cache.v_pools,
                                    cache.k_scales, cache.v_scales,
                                    bid)
                pages.append(np.asarray(k).tobytes()
                             + np.asarray(v).tobytes())
                scales.append(np.asarray(ks).tobytes()
                              + np.asarray(vs).tobytes())
            else:
                k, v = prog(cache.k_pools, cache.v_pools, bid)
                pages.append(np.asarray(k).tobytes()
                             + np.asarray(v).tobytes())
                scales.append(None)
        return pages, scales

    def export_page_bytes(self, cache: PagedKVCache, bid: int
                          ) -> tuple[bytes, bytes | None]:
        """Host copy of ONE pool page (k stack then v stack, plus the
        scale stacks for int8 pools) — the spill-tier demotion copy
        (engine/kv_tier.py).  Rides the same jitted gather program as
        the disagg handoff export, so a tier-enabled lane that warmed
        the handoff programs never compiles here."""
        prog = self._page_export_program(cache.quantized)
        b = jnp.int32(int(bid))
        if cache.quantized:
            k, v, ks, vs = prog(cache.k_pools, cache.v_pools,
                                cache.k_scales, cache.v_scales, b)
            return (np.asarray(k).tobytes() + np.asarray(v).tobytes(),
                    np.asarray(ks).tobytes()
                    + np.asarray(vs).tobytes())
        k, v = prog(cache.k_pools, cache.v_pools, b)
        return (np.asarray(k).tobytes() + np.asarray(v).tobytes(),
                None)

    def import_page_bytes(self, cache: PagedKVCache, bid: int,
                          buf: bytes,
                          sbuf: bytes | None = None) -> None:
        """Scatter one wire page's host bytes into pool page `bid` —
        the tier READMISSION: a DRAM hit becomes this device_put plus
        a block-table write instead of a re-prefill.  Same program
        and byte layout as the disagg adoption import."""
        cfg = self.cfg
        prog = self._page_import_program(cache.quantized)
        dt = self._page_wire_dtype(cache)
        shape = self._page_wire_shape(cache)
        half = self.page_wire_bytes(cache) // 2
        if len(buf) != 2 * half:
            raise ValueError(
                f"tier page holds {len(buf)} bytes, "
                f"expected {2 * half}")
        kv = np.frombuffer(buf[:half], dt).reshape(shape)
        vv = np.frombuffer(buf[half:], dt).reshape(shape)
        b = jnp.int32(int(bid))
        if cache.quantized:
            sh = (cfg.layers, cfg.kv_heads)
            sn = cfg.layers * cfg.kv_heads * 4
            if sbuf is None or len(sbuf) != 2 * sn:
                raise ValueError(
                    f"tier scales hold "
                    f"{0 if sbuf is None else len(sbuf)} bytes, "
                    f"expected {2 * sn}")
            ks = np.frombuffer(sbuf[:sn], np.float32).reshape(sh)
            vs = np.frombuffer(sbuf[sn:], np.float32).reshape(sh)
            kp, vp, ksc, vsc = prog(
                cache.k_pools, cache.v_pools, cache.k_scales,
                cache.v_scales, jnp.asarray(kv), jnp.asarray(vv),
                jnp.asarray(ks), jnp.asarray(vs), b)
            cache.k_scales, cache.v_scales = list(ksc), list(vsc)
        else:
            kp, vp = prog(cache.k_pools, cache.v_pools,
                          jnp.asarray(kv), jnp.asarray(vv), b)
        cache.k_pools, cache.v_pools = list(kp), list(vp)

    def paged_adopt_row(self, cache: PagedKVCache, row: int,
                        length: int, pages: list[bytes],
                        scales: list[bytes | None] | None = None
                        ) -> bool:
        """Seat a handed-off row into THIS pool: grow its table to
        cover `length` tokens, then scatter each wire page into its
        freshly allocated block (one dispatch per page).  Returns
        False — nothing imported, nothing allocated beyond what the
        caller already reserved — when the pool cannot hold the row
        (adoption backpressure: the row stays DECODE_READY).  The
        caller is responsible for reserving the row's WORST case
        (prompt + max_new) before importing, the same admission
        contract paged_prefill_row rides."""
        cfg = self.cfg
        need = cache.pages_needed(length)
        if len(pages) < need:
            raise ValueError(
                f"handoff for row {row} carries {len(pages)} pages, "
                f"{need} needed to cover {length} tokens")
        if not cache.ensure(row, length):
            return False
        prog = self._page_import_program(cache.quantized)
        dt = self._page_wire_dtype(cache)
        shape = self._page_wire_shape(cache)
        half = self.page_wire_bytes(cache) // 2
        for p_idx in range(need):
            buf = pages[p_idx]
            if len(buf) != 2 * half:
                raise ValueError(
                    f"wire page {p_idx} holds {len(buf)} bytes, "
                    f"expected {2 * half}")
            kv = np.frombuffer(buf[:half], dt).reshape(shape)
            vv = np.frombuffer(buf[half:], dt).reshape(shape)
            bid = jnp.int32(int(cache.tables[row, p_idx]))
            if cache.quantized:
                sbuf = (scales or [None] * need)[p_idx] or b""
                sh = (cfg.layers, cfg.kv_heads)
                sn = cfg.layers * cfg.kv_heads * 4
                if len(sbuf) != 2 * sn:
                    raise ValueError(
                        f"wire scales {p_idx} hold {len(sbuf)} bytes,"
                        f" expected {2 * sn}")
                ks = np.frombuffer(sbuf[:sn], np.float32).reshape(sh)
                vs = np.frombuffer(sbuf[sn:], np.float32).reshape(sh)
                kp, vp, ksc, vsc = prog(
                    cache.k_pools, cache.v_pools, cache.k_scales,
                    cache.v_scales, jnp.asarray(kv), jnp.asarray(vv),
                    jnp.asarray(ks), jnp.asarray(vs), bid)
                cache.k_scales, cache.v_scales = list(ksc), list(vsc)
            else:
                kp, vp = prog(cache.k_pools, cache.v_pools,
                              jnp.asarray(kv), jnp.asarray(vv), bid)
            cache.k_pools, cache.v_pools = list(kp), list(vp)
        cache.lengths[row] = int(length)
        return True

    def warmup_handoff(self, cache: PagedKVCache, *,
                       export: bool = True, adopt: bool = True
                       ) -> None:
        """Pre-compile the handoff wire programs so the first handoff
        (or adoption) at serve time never pays a jit compile — the
        same no-recompile contract warmup_paged pins for the serving
        programs."""
        with DEVTIME.warmup_phase():
            bid = cache._alloc_page()
            try:
                if export:
                    prog = self._page_export_program(cache.quantized)
                    if cache.quantized:
                        prog(cache.k_pools, cache.v_pools,
                             cache.k_scales, cache.v_scales,
                             jnp.int32(bid))
                    else:
                        prog(cache.k_pools, cache.v_pools,
                             jnp.int32(bid))
                if adopt:
                    cfg = self.cfg
                    dt = self._page_wire_dtype(cache)
                    shape = self._page_wire_shape(cache)
                    z = jnp.zeros(shape, dt)
                    prog = self._page_import_program(cache.quantized)
                    if cache.quantized:
                        zs = jnp.zeros((cfg.layers, cfg.kv_heads),
                                       jnp.float32)
                        kp, vp, ks, vs = prog(
                            cache.k_pools, cache.v_pools,
                            cache.k_scales, cache.v_scales, z, z,
                            zs, zs, jnp.int32(bid))
                        cache.k_scales = list(ks)
                        cache.v_scales = list(vs)
                    else:
                        kp, vp = prog(cache.k_pools, cache.v_pools,
                                      z, z, jnp.int32(bid))
                    cache.k_pools = list(kp)
                    cache.v_pools = list(vp)
            finally:
                cache._decref(bid)

    def _paged_chunk_program(self, n: int, bp: int,
                             quantized: bool = False):
        """lax.scan of n paged decode steps for bp rows: append one
        token per row into its pages, ragged paged attention, sample
        in-graph (_sample_rows — the same sampler graph as every other
        path).  The pool never round-trips to the host (donated).
        Quantized pools thread their per-page scales through the scan
        carry (and donate them too — rescale-on-append rewrites them
        in place).

        The first step's input tokens come from
        where(fresh_mask, fresh, carry): `fresh` is the host-fed
        column (prefill samples of freshly joined rows), `carry` the
        previous chunk's last sampled column — which the program ALSO
        returns as a device array, so K-deep chunk chaining
        (paged_decode_chunk_async) never pays a host round trip for
        the token hand-off."""
        key = ("chunk", n, bp, quantized, self.top_p, self.temp)
        fn = self._paged_progs.get(key)
        if fn is None:
            module, top_p, temp = self.module, self.top_p, self.temp

            if quantized:
                def run(params, k_pools, v_pools, k_scales, v_scales,
                        tables, lengths, rng, fresh, fresh_mask,
                        carry):
                    toks0 = jnp.where(fresh_mask, fresh, carry)

                    def step(carry_s, _):
                        (k_pools, v_pools, k_scales, v_scales,
                         lengths, rng, toks) = carry_s
                        cache = list(zip(k_pools, v_pools,
                                         k_scales, v_scales))
                        logits, new_cache = module.apply(
                            params, toks.reshape(-1, 1), cache,
                            jnp.int32(0), None, lengths, tables)
                        k_pools = [c[0] for c in new_cache]
                        v_pools = [c[1] for c in new_cache]
                        k_scales = [c[2] for c in new_cache]
                        v_scales = [c[3] for c in new_cache]
                        rng, sub = jax.random.split(rng)
                        nxt = _sample_rows(sub, logits[:, 0], top_p,
                                           temp)
                        return (k_pools, v_pools, k_scales, v_scales,
                                lengths + 1, rng, nxt), nxt

                    (k_pools, v_pools, k_scales, v_scales, _, _,
                     _), out = jax.lax.scan(
                        step, (k_pools, v_pools, k_scales, v_scales,
                               lengths, rng, toks0), None, length=n)
                    return (k_pools, v_pools, k_scales, v_scales,
                            out, out[-1])          # out: (n, bp)

                out_sh = self._paged_pool_out_shardings(
                    2, 2, n_scale_lists=2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("paged_chunk"),
                    jax.jit(run, donate_argnums=(1, 2, 3, 4), **kw))
            else:
                def run(params, k_pools, v_pools, tables, lengths, rng,
                        fresh, fresh_mask, carry):
                    toks0 = jnp.where(fresh_mask, fresh, carry)

                    def step(carry_s, _):
                        k_pools, v_pools, lengths, rng, toks = carry_s
                        cache = list(zip(k_pools, v_pools))
                        logits, new_cache = module.apply(
                            params, toks.reshape(-1, 1), cache,
                            jnp.int32(0), None, lengths, tables)
                        k_pools = [c[0] for c in new_cache]
                        v_pools = [c[1] for c in new_cache]
                        rng, sub = jax.random.split(rng)
                        nxt = _sample_rows(sub, logits[:, 0], top_p,
                                           temp)
                        return (k_pools, v_pools, lengths + 1, rng,
                                nxt), nxt

                    (k_pools, v_pools, _, _, _), out = jax.lax.scan(
                        step, (k_pools, v_pools, lengths, rng, toks0),
                        None, length=n)
                    return k_pools, v_pools, out, out[-1]

                out_sh = self._paged_pool_out_shardings(2, 2)
                kw = {} if out_sh is None else {"out_shardings": out_sh}
                fn = DEVTIME.register(
                    self._devname("paged_chunk"),
                    jax.jit(run, donate_argnums=(1, 2), **kw))
            self._paged_progs[key] = fn
            if len(self._paged_progs) > 24:
                cur = (self.top_p, self.temp)
                self._paged_progs = {
                    k: v for k, v in self._paged_progs.items()
                    if k[0] != "chunk" or k[-2:] == cur}
        return fn

    def paged_decode_chunk(self, cache: PagedKVCache, tokens, n: int
                           ) -> np.ndarray:
        """Append tokens (batch,), decode+sample n steps for every
        row of the pool in one program.  Rows with lengths == 0 are
        dead: they decode into the trash block and the caller discards
        their column.  Live rows must have window room for n more
        tokens (the scheduler finishes rows first).  Returns
        (batch, n) sampled ids."""
        return self.paged_decode_chunk_async(cache, tokens, n).block()

    def paged_decode_chunk_async(self, cache: PagedKVCache, tokens,
                                 n: int, carry=None) -> "PendingChunk":
        """K-deep variant: dispatch a decode chunk WITHOUT forcing the
        sampled block.  `tokens` (batch,) int32 host values are the
        fresh first-step inputs for rows in `tokens`'s mask... two
        forms compose per row:

          - a freshly joined row's prefill sample arrives host-side in
            `tokens` with its bit set in the implied mask (tokens >= 0
            entries where carry is absent);
          - a row live since the previous chunk hands its token over
            ON DEVICE via `carry` (the previous PendingChunk's .last)
            — chaining chunks costs zero host syncs, so the host can
            hold K un-awaited chunks while the device stays fed.

        Concretely: pass `carry=prev.last` and set tokens[r] >= 0 only
        for rows whose token was produced host-side since the last
        dispatch (tokens[r] < 0 = use the carry).  With carry=None
        every row reads from `tokens` (the sync path).  Host
        bookkeeping (cache.lengths) advances at DISPATCH, so window
        edge checks already account for in-flight chunks."""
        bp = cache.batch
        for r in range(bp):
            length = int(cache.lengths[r])
            if length > 0 and not cache.ensure(
                    r, min(length + n, self.cfg.max_len)):
                raise RuntimeError(
                    f"paged pool exhausted mid-decode: row {r} "
                    f"(admission must reserve prompt + max_new)")
        # copy-on-write BEFORE the tables snapshot below: a row whose
        # first append this chunk targets a shared/frozen page decodes
        # into its own private copy (prefix sharing's writer barrier)
        self._cow_fixups(cache)
        toks = np.full((bp,), -1, np.int32)
        toks[: len(tokens)] = np.asarray(tokens, np.int32)
        if carry is None:
            fresh_mask = np.ones((bp,), bool)
            carry = np.zeros((bp,), np.int32)
            toks = np.maximum(toks, 0)
        else:
            fresh_mask = toks >= 0
            toks = np.maximum(toks, 0)
        self._rng, sub = jax.random.split(self._rng)
        # HOST-SIDE COPIES of the bookkeeping, never views: the
        # dispatch is asynchronous and cache.lengths/tables are mutated
        # in place right below (and by the next join).  The CPU backend
        # aliases an aligned NumPy buffer without copying, and
        # jnp.array's own copy is a device program that queues BEHIND
        # the work in flight — it then reads lengths that already
        # count chunks dispatched after it (PR 26: a row-0 joiner
        # under an in-flight chunk got another row's positions).  A
        # fresh NumPy array nobody writes again may be aliased freely
        tables = jnp.asarray(np.array(cache.tables))
        lengths = jnp.asarray(np.array(cache.lengths))
        if cache.quantized:
            kp, vp, ks, vs, out, last = self._paged_chunk_program(
                n, bp, True)(
                self.params, cache.k_pools, cache.v_pools,
                cache.k_scales, cache.v_scales,
                tables, lengths,
                sub, jnp.asarray(toks), jnp.asarray(fresh_mask), carry)
            cache.k_scales, cache.v_scales = list(ks), list(vs)
        else:
            kp, vp, out, last = self._paged_chunk_program(n, bp)(
                self.params, cache.k_pools, cache.v_pools,
                tables, lengths,
                sub, jnp.asarray(toks), jnp.asarray(fresh_mask), carry)
        cache.k_pools, cache.v_pools = list(kp), list(vp)
        live = cache.lengths > 0
        cache.lengths[live] = np.minimum(cache.lengths[live] + n,
                                         self.cfg.max_len)
        return PendingChunk(out, last, n,
                            mark=DEVTIME.take_mark(
                                self._devname("paged_chunk")))

    def warmup_paged(self, cache: PagedKVCache, chunk: int = 8,
                     max_prompt: int | None = None) -> None:
        """Pre-compile every paged program the continuous lane hot
        path touches — per-bucket prefill scratch + commit scatter,
        the host sampler, and the chunked paged decode step — so a
        join/finish/join cycle at serve time never compiles
        (compile_count stays flat; the steady-state test pins it).
        max_prompt bounds the bucket sweep: a caller that clips every
        prompt (the continuous lane's window budget) never selects a
        bucket above bucket_for(max_prompt), so warming the ones past
        it — including the max_len bucket, the slowest compile —
        would only inflate startup for dead programs."""
        with DEVTIME.warmup_phase():
            self._warmup_paged_impl(cache, chunk, max_prompt)

    def _warmup_paged_impl(self, cache: PagedKVCache, chunk: int,
                           max_prompt: int | None) -> None:
        chunk_done = False
        cap = (self.bucket_for(max_prompt) if max_prompt is not None
               else self.buckets[-1])
        for b in self.buckets:
            if b > cap:
                break
            n = max(1, min(b, self.cfg.max_len) - 1)
            logits = self.paged_prefill_row(
                cache, np.ones((n,), np.int32), 0)
            self.sample(logits)
            if not chunk_done and n + chunk < self.cfg.max_len:
                self.paged_decode_chunk(
                    cache, np.ones((cache.batch,), np.int32), chunk)
                chunk_done = True
            cache.free_row(0)
        # the prefix-cache hit path's programs (suffix stacks + the
        # COW page copy) — a first cache hit at serve time must not
        # pay a compile either.  Gated on an ATTACHED tree: a lane
        # with sharing disabled never runs these, so warming them
        # would only inflate startup
        if getattr(cache, "prefix_cache", None) is not None:
            quant = getattr(cache, "quantized", False)
            for sb in self.suffix_buckets:
                if sb + chunk >= self.cfg.max_len:
                    break
                self.paged_append_prefill(
                    cache, np.ones((sb,), np.int32), 0)
                cache.free_row(0)
            src, dst = cache._alloc_page(), cache._alloc_page()
            if quant:
                kp, vp, ks, vs = self._cow_copy_program(True)(
                    cache.k_pools, cache.v_pools, cache.k_scales,
                    cache.v_scales, jnp.int32(src), jnp.int32(dst))
                cache.k_scales, cache.v_scales = list(ks), list(vs)
            else:
                kp, vp = self._cow_copy_program(False)(
                    cache.k_pools, cache.v_pools, jnp.int32(src),
                    jnp.int32(dst))
            cache.k_pools, cache.v_pools = list(kp), list(vp)
            cache._decref(src)
            cache._decref(dst)

    def compile_count(self) -> int:
        """Distinct XLA programs compiled across every program cache
        (trunk, chunk/paged dispatch tables) — the obs surface
        the encoder already publishes: a count still growing after
        warmup means some serving geometry escapes the bucket set and
        pays jit compiles on the wake path.  -1 when the private jax
        cache API is unavailable."""
        fns = ([self._fn] + list(self._chunk_progs.values())
               + list(self._paged_progs.values()))
        total = 0
        for f in fns:
            f = getattr(f, "__wrapped__", f)   # devtime wrapper
            try:
                total += int(f._cache_size())
            except Exception:   # private jax API: absence isn't an error
                return -1
        return total

    def generate_batch(self, prompts: list[np.ndarray], max_new: int,
                       *, chunk: int = 8):
        """Generator over token COLUMNS for a batch of prompts: first
        yields the (B,) post-prefill samples, then one (B,) column per
        decoded step, chunk steps dispatched per device round trip.
        Rows past their stop condition yield speculative tokens — the
        consumer tracks per-row completion and discards (same contract
        as generate_tokens with eos_id=None)."""
        logits = self.prefill_batch(prompts)
        toks = self.sample_batch(logits)
        yield toks.copy()
        produced = 1
        while produced < max_new:
            room = min(self.cfg.max_len - self._pos, max_new - produced)
            if room <= 0:
                break
            step = min(chunk, room)
            block = self.decode_chunk_batch(toks, step)   # (B, step)
            for c in range(step):
                yield block[:, c].copy()
            toks = block[:, -1].astype(np.int32)
            produced += step

    @property
    def pos(self) -> int:
        return self._pos

    def warmup(self, chunk: int = 8, batch: int = 1) -> None:
        """Pre-compile prefill buckets, decode-one, and the chunked
        decode program; batch > 1 additionally compiles the batched
        serving shapes (prefill_batch + batched chunk program) under
        the same window guard."""
        with DEVTIME.warmup_phase():
            self._warmup_impl(chunk, batch)

    def _warmup_impl(self, chunk: int, batch: int) -> None:
        for b in self.buckets:
            self.prefill(np.ones((max(1, b - 1),), np.int32))
            self.decode_one(1)
        # the loop leaves _pos parked at max_len (the last bucket IS
        # the window), where no chunk fits — re-prefill short so the
        # chunk program (the serving hot path) actually compiles
        self.reset()
        self.prefill(np.ones((max(1, self.buckets[0] - 1),), np.int32))
        if self._pos + chunk <= self.cfg.max_len:
            self.decode_chunk(1, chunk)
        self.reset()
        if batch > 1:
            # every bucket, like the serial loop above: the first real
            # batched/continuous request routed to a wider bucket must
            # not pay a multi-second on-line compile despite --warmup
            # (ADVICE r3).  prefill_batch pads to b and parks _pos
            # there, so the chunk program only fits when
            # b + chunk <= max_len — but the prefill program itself
            # compiles unconditionally (the widest bucket IS max_len)
            chunk_done = False   # the chunk program is bucket-shape-
            for b in self.buckets:     # independent: compile it once
                n = max(1, b - 1)
                self.prefill_batch([np.ones((n,), np.int32)] * batch)
                if not chunk_done and b + chunk <= self.cfg.max_len:
                    self.decode_chunk_batch(np.ones((batch,), np.int32),
                                            chunk)
                    chunk_done = True
                self.reset()


# ------------------------------------------------------ checkpoint loading

def load_safetensors_params(path: str, cfg: DecoderConfig):
    """Map a HF llama-family safetensors checkpoint onto the flax tree.

    Expected naming (the llama/mistral export convention):
    model.embed_tokens.weight, model.layers.{i}.self_attn.{q,k,v,o}_proj,
    model.layers.{i}.mlp.{gate,up,down}_proj,
    model.layers.{i}.input_layernorm / post_attention_layernorm,
    model.norm.weight, lm_head.weight (tied to embeddings when absent).
    torch Linear weights are (out, in) and transpose into flax kernels.

    Validated in-tree against synthetic checkpoints written by
    `export_safetensors_params` (tests/test_decoder.py); upstream name
    parity cannot be re-verified in this offline image.
    """
    from .encoder import read_safetensors_f32

    tensors = read_safetensors_f32(path)

    def take(name: str):
        if name not in tensors:
            raise KeyError(f"checkpoint {path} lacks {name}; present keys "
                           f"include {sorted(tensors)[:8]}...")
        return np.asarray(tensors[name])

    def kern(name: str):
        return {"kernel": take(name).T.astype(np.float32)}

    tok = take("model.embed_tokens.weight")
    if tok.shape[0] < cfg.vocab_size:
        raise ValueError(
            f"checkpoint vocab {tok.shape[0]} < cfg.vocab_size "
            f"{cfg.vocab_size} — out-of-range rows would gather-clamp "
            "silently; shrink cfg.vocab_size to the checkpoint's")
    p: dict[str, Any] = {
        "tok_emb": {"embedding":
                    tok[:cfg.vocab_size].astype(np.float32)},
        "ln_out": {"scale": take("model.norm.weight").astype(np.float32)},
    }
    if "lm_head.weight" in tensors:
        # same vocab truncation as the embedding (padded-vocab exports),
        # on the ROWS of the (out, in) torch tensor
        head = take("lm_head.weight")
        if head.shape[0] < cfg.vocab_size:
            raise ValueError(
                f"checkpoint lm_head vocab {head.shape[0]} < "
                f"cfg.vocab_size {cfg.vocab_size}")
        p["lm_head"] = {"kernel":
                        head[:cfg.vocab_size].T.astype(np.float32)}
    else:   # tied embeddings
        p["lm_head"] = {"kernel":
                        p["tok_emb"]["embedding"].T.copy()}
    for i in range(cfg.layers):
        n = f"model.layers.{i}"
        p[f"layer_{i}"] = {
            "ln_attn": {"scale":
                        take(f"{n}.input_layernorm.weight")
                        .astype(np.float32)},
            "attn": {
                "q": kern(f"{n}.self_attn.q_proj.weight"),
                "k": kern(f"{n}.self_attn.k_proj.weight"),
                "v": kern(f"{n}.self_attn.v_proj.weight"),
                "out": kern(f"{n}.self_attn.o_proj.weight"),
            },
            "ln_mlp": {"scale":
                       take(f"{n}.post_attention_layernorm.weight")
                       .astype(np.float32)},
            "gate": kern(f"{n}.mlp.gate_proj.weight"),
            "up": kern(f"{n}.mlp.up_proj.weight"),
            "down": kern(f"{n}.mlp.down_proj.weight"),
        }
    return {"params": jax.tree.map(
        lambda x: jnp.asarray(x, jnp.float32), p)}


def export_safetensors_params(params, cfg: DecoderConfig, path: str) -> None:
    """Inverse of load_safetensors_params (llama naming); used by the
    round-trip tests and for interop with torch tooling."""
    from safetensors.numpy import save_file

    p = jax.tree.map(lambda x: np.asarray(x, np.float32), params["params"])
    out: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": p["tok_emb"]["embedding"],
        "model.norm.weight": p["ln_out"]["scale"],
        "lm_head.weight": p["lm_head"]["kernel"].T.copy(),
    }
    for i in range(cfg.layers):
        n = f"model.layers.{i}"
        layer = p[f"layer_{i}"]
        out[f"{n}.input_layernorm.weight"] = layer["ln_attn"]["scale"]
        out[f"{n}.post_attention_layernorm.weight"] = \
            layer["ln_mlp"]["scale"]
        for src, dst in (("q", "q_proj"), ("k", "k_proj"),
                         ("v", "v_proj"), ("out", "o_proj")):
            out[f"{n}.self_attn.{dst}.weight"] = \
                layer["attn"][src]["kernel"].T.copy()
        for name in ("gate", "up", "down"):
            out[f"{n}.mlp.{name}_proj.weight"] = \
                layer[name]["kernel"].T.copy()
    save_file(out, path)
