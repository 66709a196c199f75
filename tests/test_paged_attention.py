"""Paged KV pool + ragged paged attention (ops/paged_attention.py,
models/decoder.PagedKVCache): interpret-mode kernel parity vs the
dense causal reference across ragged length patterns, pool alloc/free
leak checks, and model-level paged decode token-exactness vs serial.
`make decode-check` runs this file + tests/test_paged_continuous.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu.models.decoder import (CompletionModel,
                                            DecoderConfig, PagedKVCache)
from libsplinter_tpu.ops.flash_attention import _causal_jnp
from libsplinter_tpu.ops.paged_attention import _paged_ref, paged_attention


def _build_paged(rng, lengths, *, KH, D, page, P, shuffle=True):
    """Random pools + tables for the given ragged lengths.  Returns
    (k_pool, v_pool, tables, dense_k, dense_v) where dense_* is the
    contiguous (B, T, KH, D) view of each row's tokens."""
    B = len(lengths)
    n_blocks = 1 + sum(-(-int(l) // page) or 1 for l in lengths)
    kp = rng.randn(n_blocks, KH, page, D).astype(np.float32)
    vp = rng.randn(n_blocks, KH, page, D).astype(np.float32)
    tables = np.zeros((B, P), np.int32)
    ids = list(range(1, n_blocks))
    if shuffle:
        rng.shuffle(ids)
    T = P * page
    dense_k = np.zeros((B, T, KH, D), np.float32)
    dense_v = np.zeros((B, T, KH, D), np.float32)
    for b in range(B):
        for p in range(-(-int(lengths[b]) // page)):
            bid = ids.pop()
            tables[b, p] = bid
            dense_k[b, p * page:(p + 1) * page] = kp[bid].transpose(1, 0, 2)
            dense_v[b, p * page:(p + 1) * page] = vp[bid].transpose(1, 0, 2)
    return kp, vp, tables, dense_k, dense_v


def _dense_rows(q, dense_k, dense_v, lengths):
    """Per-row dense causal reference: row b's single query at
    position lengths[b]-1 over its own keys (the math the paged
    kernel must reproduce)."""
    B, H, D = q.shape
    KH = dense_k.shape[2]
    rep = H // KH
    outs = []
    for b in range(B):
        L = int(lengths[b])
        kk = np.repeat(dense_k[b:b + 1, :L], rep, axis=2)
        vv = np.repeat(dense_v[b:b + 1, :L], rep, axis=2)
        ref = _causal_jnp(jnp.asarray(q[b:b + 1].reshape(1, 1, H, D)),
                          jnp.asarray(kk), jnp.asarray(vv),
                          jnp.int32(L - 1), jnp.zeros((1,), jnp.int32))
        outs.append(np.asarray(ref)[0, 0])
    return np.stack(outs)


# length patterns the tentpole calls out — the fast tier runs the one
# batch that exercises every class at once (single-token row, exact
# page boundary, len % page != 0, multi-page straggler); the wider
# grid rides the slow tier so tier-1 stays inside its 870 s budget
RAGGED = [
    ([1, 8, 7, 19], 8, 4),            # the canonical mixed batch
]
RAGGED_HEAVY = [
    ([8, 16, 24, 32], 8, 4),          # every row ON a page boundary
    ([1, 1, 1, 1], 4, 2),             # all single-token
    ([5, 13, 29, 31], 8, 4),          # nothing aligned
]


@pytest.mark.parametrize("lengths,page,P", RAGGED)
def test_kernel_matches_dense_reference(lengths, page, P):
    """Interpret-mode kernel == per-row dense causal attention to fp
    tolerance, with shuffled (non-contiguous) block assignments."""
    rng = np.random.RandomState(7)
    KH, H, D = 2, 4, 16
    kp, vp, tables, dk, dv = _build_paged(rng, lengths, KH=KH, D=D,
                                          page=page, P=P)
    q = rng.randn(len(lengths), H, D).astype(np.float32)
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lengths, np.int32),
        interpret=True))
    ref = _dense_rows(q, dk, dv, lengths)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lengths,page,P", RAGGED)
def test_kernel_matches_jnp_gather_reference(lengths, page, P):
    """Kernel == the jnp gathered-page reference (_paged_ref, the
    non-TPU serving path) on the same pools/tables."""
    rng = np.random.RandomState(3)
    KH, H, D = 2, 6, 8                # rep = 3 (odd GQA grouping)
    kp, vp, tables, _, _ = _build_paged(rng, lengths, KH=KH, D=D,
                                        page=page, P=P)
    q = rng.randn(len(lengths), H, D).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lengths, np.int32))
    out = np.asarray(paged_attention(*args, interpret=True))
    ref = np.asarray(_paged_ref(*args))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_kernel_no_gqa_and_dead_rows():
    """rep == 1 (heads == kv_heads) lowers too, and a lengths == 0
    row (a dead batch slot) returns finite output — zeros from the
    kernel, don't-care by contract."""
    rng = np.random.RandomState(11)
    lengths = [9, 0, 4]
    KH = H = 4
    D, page, P = 8, 4, 4
    kp, vp, tables, dk, dv = _build_paged(rng, lengths, KH=KH, D=D,
                                          page=page, P=P)
    q = rng.randn(3, H, D).astype(np.float32)
    out = np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lengths, np.int32),
        interpret=True))
    assert np.isfinite(out).all()
    assert np.abs(out[1]).max() == 0.0          # dead row: zeros
    ref = _dense_rows(q[[0, 2]], dk[[0, 2]], dv[[0, 2]],
                      [lengths[0], lengths[2]])
    np.testing.assert_allclose(out[[0, 2]], ref, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("lengths,page,P", RAGGED_HEAVY)
def test_kernel_parity_ragged_heavy(lengths, page, P):
    """The rest of the ragged grid (boundary-only, all-single-token,
    unaligned batches) against both references."""
    rng = np.random.RandomState(5)
    KH, H, D = 2, 4, 16
    kp, vp, tables, dk, dv = _build_paged(rng, lengths, KH=KH, D=D,
                                          page=page, P=P)
    q = rng.randn(len(lengths), H, D).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lengths, np.int32))
    out = np.asarray(paged_attention(*args, interpret=True))
    np.testing.assert_allclose(out, _dense_rows(q, dk, dv, lengths),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, np.asarray(_paged_ref(*args)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_kernel_parity_heavy_matrix():
    """Wider sweep: many (lengths, page, KH/H) geometries including
    bf16 pools — the slow tier's exhaustive arm."""
    rng = np.random.RandomState(42)
    for page, P in ((4, 8), (8, 4), (16, 3)):
        for KH, H in ((1, 4), (2, 8), (4, 4)):
            lengths = [int(rng.randint(1, page * P + 1))
                       for _ in range(5)]
            kp, vp, tables, dk, dv = _build_paged(
                rng, lengths, KH=KH, D=16, page=page, P=P)
            q = rng.randn(5, H, 16).astype(np.float32)
            out = np.asarray(paged_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(tables), jnp.asarray(lengths, np.int32),
                interpret=True))
            ref = _dense_rows(q, dk, dv, lengths)
            np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


# ------------------------------------------------------------- pool


def test_pool_alloc_free_no_leak():
    """Every finished row returns ALL its pages: used_pages comes back
    to zero and the free list is duplicate-free."""
    cfg = DecoderConfig.tiny(max_len=128)
    cache = PagedKVCache(cfg, 4, page=16, pool_pages=20)
    assert cache.free_pages == 20 and cache.used_pages == 0
    assert cache.ensure(0, 40)        # 3 pages
    assert cache.ensure(1, 16)        # 1 page (boundary)
    assert cache.ensure(2, 17)        # 2 pages
    assert cache.used_pages == 6
    assert cache.ensure(0, 48)        # grow in place: same 3 pages
    assert cache.used_pages == 6
    assert cache.ensure(0, 49)        # +1
    assert cache.used_pages == 7
    for r in range(4):
        cache.free_row(r)
    assert cache.used_pages == 0
    assert cache.free_pages == 20
    assert sorted(cache._free) == list(range(1, 21))
    assert (cache.tables == 0).all()
    assert (cache.lengths == 0).all()


def test_pool_exhaustion_backpressures_not_partial():
    """ensure() past the pool is an all-or-nothing refusal — nothing
    allocated, nothing leaked — and frees make it succeed again."""
    cfg = DecoderConfig.tiny(max_len=128)
    cache = PagedKVCache(cfg, 2, page=16, pool_pages=8)
    assert cache.ensure(0, 96)        # 6 of 8 pages
    assert not cache.ensure(1, 48)    # needs 3, only 2 free
    assert cache.used_pages == 6      # refusal allocated nothing
    assert len(cache._owned[1]) == 0
    cache.free_row(0)
    assert cache.ensure(1, 48)
    assert cache.used_pages == 3


def test_pool_window_cap_and_trash_block():
    """pages_needed caps at the window (a worst-case reservation can
    always fit an empty pool) and block 0 is never handed out."""
    cfg = DecoderConfig.tiny(max_len=128)
    cache = PagedKVCache(cfg, 2, page=16, pool_pages=8)
    assert cache.pages_needed(10_000) == cache.pages_per_row == 8
    assert cache.ensure(0, 10_000)    # exactly the whole pool
    assert 0 not in cache._owned[0]
    with pytest.raises(ValueError):
        PagedKVCache(cfg, 2, page=16, pool_pages=4)   # < one window


# ------------------------------------------- model-level paged decode


@pytest.fixture(scope="module")
def model():
    # f32 on CPU so greedy argmax comparisons are tie-stable (the
    # suite's convention for token-exactness tests)
    return CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                           buckets=(16, 32), temp=0.0)


@pytest.mark.slow
def test_paged_decode_token_exact_vs_serial(model):
    """Paged prefill + chunked paged decode reproduce the serial
    dense path token for token (greedy), including a row that joins
    mid-flight with shuffled page ownership.  Slow tier: the fast
    sweep keeps the daemon-level token-exactness bar
    (test_paged_continuous.test_paged_continuous_token_exact_vs_dense)
    inside the tier-1 870 s budget."""
    m = model
    A = np.arange(1, 8, dtype=np.int32)
    Bp = np.array([9, 2, 6], np.int32)
    sa = [int(x) for x in m.generate_tokens(A, 16, chunk=4)]
    m.reset()
    sb = [int(x) for x in m.generate_tokens(Bp, 10, chunk=4)]
    m.reset()

    cache = m.init_paged(2, page=16)
    logits = m.paged_prefill_row(cache, A, 0)
    out_a = [int(np.argmax(logits))]
    blk = m.paged_decode_chunk(cache, np.array([out_a[0], 0], np.int32), 6)
    out_a += [int(x) for x in blk[0]]
    jl = m.paged_prefill_row(cache, Bp, 1)     # join mid-decode
    out_b = [int(np.argmax(jl))]
    toks = np.array([int(blk[0][-1]), out_b[0]], np.int32)
    for _ in range(3):
        blk = m.paged_decode_chunk(cache, toks, 3)
        out_a += [int(x) for x in blk[0]]
        out_b += [int(x) for x in blk[1]]
        toks = blk[:, -1].astype(np.int32)
    assert out_a[:16] == sa[:16]
    assert out_b[:10] == sb[:10]
    cache.free_row(0)
    cache.free_row(1)
    assert cache.used_pages == 0


@pytest.mark.slow
def test_paged_join_not_bounded_by_neighbour(model):
    """The dense shared window forbade a joiner whose prompt exceeds
    join_budget(); paged rows have independent windows — a 20-token
    joiner lands with FULL context while a 3-token row decodes, and
    still matches its serial tokens.  Slow tier: `make decode-check`
    (whole-file, no slow filter) keeps the daemon-level regression
    (test_paged_joiner_exceeding_dense_window_untruncated)."""
    m = model
    short = np.array([5, 3, 2], np.int32)
    longp = (np.arange(1, 21, dtype=np.int32) % 900) + 1
    sl = [int(x) for x in m.generate_tokens(longp, 8, chunk=4)]
    m.reset()

    cache = m.init_paged(2, page=16)
    lg = m.paged_prefill_row(cache, short, 0)
    t0 = int(np.argmax(lg))
    blk = m.paged_decode_chunk(cache, np.array([t0, 0], np.int32), 4)
    # dense equivalent: pos=16, join_budget=16 < 20 -> deferred.
    # paged: admitted at once, full prompt, own positions 0..19
    jl = m.paged_prefill_row(cache, longp, 1)
    out_b = [int(np.argmax(jl))]
    toks = np.array([int(blk[0][-1]), out_b[0]], np.int32)
    for _ in range(2):
        blk = m.paged_decode_chunk(cache, toks, 4)
        out_b += [int(x) for x in blk[1]]
        toks = blk[:, -1].astype(np.int32)
    assert out_b[:8] == sl[:8]
    cache.free_row(0)
    cache.free_row(1)


def test_paged_warmup_pins_compile_count(model):
    """After warmup_paged, a join/finish/join cycle (varying prompt
    lengths and batch occupancy) compiles NOTHING new — the
    recompile-on-occupancy-change regression paged decode must not
    reintroduce."""
    m = model
    cache = m.init_paged(2, page=16)
    m.warmup_paged(cache, chunk=4)
    base = m.compile_count()
    assert base > 0
    for prompt in (np.array([1, 2, 3], np.int32),
                   np.arange(1, 12, dtype=np.int32)):
        lg = m.paged_prefill_row(cache, prompt, 0)
        toks = np.array([int(np.argmax(lg)), 0], np.int32)
        m.paged_decode_chunk(cache, toks, 4)
        # second row joins, then both finish
        m.paged_prefill_row(cache, np.array([7, 7], np.int32), 1)
        m.paged_decode_chunk(cache, toks, 4)
        cache.free_row(0)
        cache.free_row(1)
    assert m.compile_count() == base, \
        "paged steady state recompiled on a join/finish/join cycle"


def test_paged_pool_exhaustion_raises_for_unreserved(model):
    """Model-level contract: a decode chunk that must grow a row past
    the pool raises (the daemon's admission reservation makes this
    unreachable in serving)."""
    m = model
    cfg = m.cfg
    cache = m.init_paged(2, page=16, pool_pages=cfg.max_len // 16)
    m.paged_prefill_row(cache, np.arange(1, 15, dtype=np.int32), 0)
    # eat the rest of the pool with row 1
    assert cache.ensure(1, cfg.max_len - 16)
    cache.lengths[1] = 15              # parked at its page boundary
    with pytest.raises(RuntimeError, match="pool exhausted"):
        m.paged_decode_chunk(cache, np.array([1, 1], np.int32), 8)


# ---------------------------------------------- page groups and windows

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("q_tokens", [1, 16, 48],
                         ids=["decode", "stack-16", "stack-48"])
@pytest.mark.parametrize("window", [0, 32, 40],
                         ids=["global", "window-2-pages", "window-40"])
def test_window_kernel_starts_match_the_reference(window, q_tokens, dtype):
    """ops/paged_attention.window_paged_attention — the kernel that
    walks a page group's pool from each row's FIRST LIVE KEY — against
    `_paged_ref` with `starts`, in interpret mode: one token a row and
    stacks of tokens (causal inside the stack, the window sliding a
    token at a time), rows shorter and longer than the window.  Pages
    behind the window are given back (their table entries name the
    trash block) and the trash block is POISONED: not one is read."""
    from libsplinter_tpu.ops.paged_attention import window_paged_attention
    rng = np.random.default_rng(0)
    page, B, KH, rep, D, L, P = 16, 3, 2, 4, 32, 3, 12
    nb = B * P + 1
    kp = jnp.asarray(rng.standard_normal((nb, L, KH, page, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, L, KH, page, D)), dtype)
    tables = np.arange(1, nb).reshape(B, P).astype(np.int32)
    lengths = np.minimum(np.array([5, 100, 150], np.int32),
                         P * page - q_tokens)
    if window:
        for b in range(B):
            tables[b, :max(0, lengths[b] - window) // page] = 0
    q = jnp.asarray(rng.standard_normal((B, q_tokens, KH * rep, D)), dtype)
    got = window_paged_attention(
        q, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan), tables, lengths,
        layer=1, window=window, interpret=True)
    ref = _paged_ref(q, kp.at[0].set(0)[:, 1], vp.at[0].set(0)[:, 1],
                     jnp.asarray(tables), jnp.asarray(lengths),
                     jnp.asarray(lengths - window) if window else None)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kv_append_writes_one_token_a_row_in_place(dtype):
    """ops/paged_attention.kv_append's kernel against the scatter it
    stands in for: each row's new key lands at (its page, the layer,
    every kv head, its offset) and nothing else moves; rows sent to
    the trash block collide freely."""
    from libsplinter_tpu.ops.paged_attention import kv_append
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((9, 3, 2, 32, 16)), dtype)
    new = jnp.asarray(rng.standard_normal((5, 2, 16)), dtype)
    bids = np.array([3, 0, 0, 7, 1], np.int32)
    offs = np.array([5, 0, 0, 31, 16], np.int32)
    got = kv_append(pool, new, bids, offs, layer=2, interpret=True)
    want = kv_append(pool, new, bids, offs, layer=2)
    np.testing.assert_array_equal(np.asarray(got[1:], np.float32),
                                  np.asarray(want[1:], np.float32))
    assert float(jnp.abs(got[3, 2, :, 5] - new[0]).max()) == 0.0
    assert float(jnp.abs(got[:, :2] - pool[:, :2]).max()) == 0.0
