"""The latent-attention (MLA) + shared-expert MoE block (models/mla.py,
ops/latent_attention.py, models/moe.sparse_moe) against its plain
reference (tests/reference_mla.py) at tiny widths on the CPU, seeded
weights: the three attention paths, latent pages with a shared prefix
and a suffix prefill, the shares of an expert layer, the router, the
norm placement, the description loader, residency, every refused
option, and `completer.main` serving `submit_completion` with an
audit record the reference confirms."""
from __future__ import annotations

import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mla as R
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import Join
from libsplinter_tpu.models import mla
from libsplinter_tpu.models.decoder import PagedKVCache
from libsplinter_tpu.models.moe import (grouped_matmul, router_gates,
                                        sparse_moe)
from libsplinter_tpu.ops.latent_attention import (latent_append,
                                                  latent_paged_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = mla.LatentMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                               experts_held=4)
IDS = np.random.default_rng(0).integers(3, CFG.vocab_size, 48) \
    .astype(np.int32)

# a tiny description in the published keys (the shape of
# benchmark/configs/openpangu-ultra-moe-718b-ep16.json's model)
ARCH = {"model_type": "pangu_ultra_moe", "hidden_act": "silu",
        "attention_bias": False, "tie_word_embeddings": False,
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "first_k_dense_replace": 3,
        "num_hidden_layers": 61, "num_nextn_predict_layers": 1,
        "sandwich_norm": True, "rope_theta": 25600000,
        "rms_norm_eps": 1e-5, "max_position_embeddings": 131072,
        "vocab_size": 4096}
SHARE = {"layers": 3, "dense_layers": 1, "experts": [4, 8],
         "vocab": [0, 512]}


@pytest.fixture(scope="module")
def model():
    return mla.LatentCompletionModel(CFG, seed=3, temp=0.0)


@pytest.fixture(scope="module")
def ref_logits(model):
    return R.forward(CFG, model.params, IDS)


def _decode_logits(m, cache, row, tokens):
    """One teacher-forced decode step of every live row (tokens: row
    -> id); returns `row`'s logits and the step's expert slots."""
    toks = np.full((cache.batch,), -1, np.int32)
    for r, t in tokens.items():
        toks[r] = t
    m.audit_row = row
    pend = m.paged_decode_chunk_async(cache, toks, 1)
    pend.block()
    m.audit_row = -1
    return np.asarray(pend.audit)[0], np.asarray(pend.slots)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_expanded_prefill_absorbed_decode_and_reference_agree(
        ref_logits, interpret):
    """Prefill (k_nope, v expanded) == decode (attention in the latent
    space, W_UK/W_UV folded) == the reference's full forward."""
    m = mla.LatentCompletionModel(CFG, seed=3, temp=0.0,
                                  interpret=interpret)
    cache = m.init_paged(2, page=16)
    got = m.paged_prefill_row(cache, IDS[:30], 1)
    np.testing.assert_allclose(got, ref_logits[29], atol=2e-5)
    for t in (30, 31):
        got, slots = _decode_logits(m, cache, 1, {1: IDS[t]})
        np.testing.assert_allclose(got, ref_logits[t], atol=2e-5)
        # one live row, two MoE layers, top-2: at most 4 slots here
        assert 0 <= int(slots.sum()) <= 4


def test_latent_pages_with_shared_prefix_and_suffix_prefill(
        model, ref_logits):
    """Row 1 prefills the prompt; row 2 maps its first two pages (a
    table write), prefills only the suffix over them, then both decode
    through the pool: every logit is the reference's."""
    cache = model.init_paged(4, page=16)
    model.paged_prefill_row(cache, IDS[:40], 1)
    cache.map_shared(2, [int(b) for b in cache.tables[1, :2]])
    cache.lengths[2] = 32
    assert cache.refcounts[cache.tables[1, 0]] == 2
    got = model.paged_append_prefill(cache, IDS[32:45], 2)
    np.testing.assert_allclose(got, ref_logits[44], atol=2e-5)
    got, _ = _decode_logits(model, cache, 2, {1: IDS[40], 2: IDS[45]})
    np.testing.assert_allclose(got, ref_logits[45], atol=2e-5)
    got, _ = _decode_logits(model, cache, 1, {1: IDS[41], 2: IDS[46]})
    np.testing.assert_allclose(got, ref_logits[41], atol=2e-5)
    cache.reset()
    assert cache.used_pages == 0


def _seat_hits(model, spans):
    """A cache of 8 rows whose rows 0..len(spans)-1 each map the first
    a // 16 pages of IDS[:48] (prefilled by a donor row that then
    leaves) and own the pages their suffix IDS[a:b] needs.  Returns
    (cache, [(row, suffix), ...])."""
    cache = model.init_paged(8, page=16, pool_pages=40)
    model.paged_prefill_row(cache, IDS[:48], 7)
    donor = [int(x) for x in cache.tables[7, :3]]
    joins = []
    for r, (a, b) in enumerate(spans):
        if r == 7:
            cache.free_row(7)       # rows 0..6 keep its pages alive
        cache.map_shared(r, donor[:a // 16])
        cache.lengths[r] = a
        assert cache.ensure(r, b)
        joins.append((r, IDS[a:b]))
    if len(spans) < 8:
        cache.free_row(7)
    return cache, joins


@pytest.mark.parametrize("spans, interpret", [
    ([(32, 45)], False),
    ([(32, 45), (16, 30), (32, 33)], False),
    ([(32, 45), (16, 30), (32, 33)], True),
    ([(32, 45), (16, 30), (32, 33), (16, 17), (32, 48), (16, 32),
      (32, 40), (16, 41)], False),
], ids=["round-of-1", "round-of-3", "round-of-3-pallas-interpret",
        "full-rung-of-8"])
def test_row_batched_suffix_prefill_is_the_one_row_programs(
        model, ref_logits, spans, interpret):
    """An admission round's hits through `join` — several in ONE
    dispatch of the suffix program
    (ragged suffix lengths, the rung's other rows dead) — against the
    one-row program a request at a time: the same logits, the same
    latents on every live page, and no other write but the trash
    block's; the first tokens are drawn in graph (greedy here)."""
    m = model if not interpret else mla.LatentCompletionModel(
        CFG, seed=3, temp=0.0, interpret=True)
    one, joins = _seat_hits(m, spans)
    want = [m.paged_append_prefill(one, suffix, r) for r, suffix in joins]
    rows, _ = _seat_hits(m, spans)
    np.testing.assert_array_equal(one.tables, rows.tables)
    before = [np.asarray(p) for p in rows.pools[0]]
    logits, toks = m.join(rows, [Join(r, IDS[:b], a, True)
                                 for r, (a, b) in enumerate(spans)])
    logits = np.asarray(logits)
    if len(spans) == 1:
        # a round of one: the one-row program, the draw left to the lane
        assert toks is None and logits.shape == want[0].shape
        logits, toks = logits[None], logits.argmax(-1)[None]
    assert toks.shape == (len(spans),)
    assert logits.shape[0] == (1 if len(spans) == 1 else 8)
    for i, (a, b) in enumerate(spans):
        np.testing.assert_allclose(logits[i], want[i], atol=2e-5)
        np.testing.assert_allclose(logits[i], ref_logits[b - 1],
                                   atol=2e-5)
        assert toks[i] == int(np.argmax(want[i]))
    np.testing.assert_array_equal(one.lengths, rows.lengths)
    # the pages a suffix may write: from the one its first token lands
    # in to its last token's
    own = {int(rows.tables[r, p]) for r, (a, b) in enumerate(spans)
           for p in range(a // 16, (b - 1) // 16 + 1)}
    for was, got, ref in zip(before, rows.pools[0], one.pools[0]):
        got, ref = np.asarray(got), np.asarray(ref)
        live = sorted(own | {int(b) for r in range(len(spans))
                             for b in rows.tables[r, :spans[r][0] // 16]})
        np.testing.assert_allclose(got[live], ref[live], atol=2e-5)
        wrote = {int(b) for b in
                 np.nonzero((got != was).any(axis=(1, 2)))[0]}
        assert wrote <= own | {0}


def test_in_graph_first_token_draw_has_the_host_draws_support():
    """The round's in-graph draw (decoder._sample_rows, the decode
    chunk's sampler) and the host's one-row draw (m.sample) cut the
    same nucleus: over fixed logits both reach every token inside
    top-p 0.9 at temperature 0.7 and none outside it."""
    from libsplinter_tpu.models.decoder import _sample_rows
    logits = np.full((CFG.vocab_size,), -30.0, np.float32)
    # at temperature 0.7: p = .334 .290 .217 .123 | .029 .007 — the
    # fifth starts at a cumulative .964 >= 0.9 and is cut
    logits[[7, 3, 400, 90, 11, 250]] = [2.0, 1.9, 1.7, 1.3, 0.3, -0.7]
    z = np.exp((logits - logits.max()) / 0.7)
    order = np.argsort(-z)
    cum = np.cumsum(z[order] / z.sum())
    nucleus = {int(t) for t in order[(cum - z[order] / z.sum()) < 0.9]}
    assert nucleus == {7, 3, 400, 90}
    m = mla.LatentCompletionModel(CFG, seed=3, params={}, top_p=0.9,
                                  temp=0.7)
    host = {m.sample(logits) for _ in range(160)}
    graph = {int(t) for t in np.asarray(jax.jit(
        lambda k, l: _sample_rows(k, l, 0.9, 0.7))(
            jax.random.PRNGKey(4), jnp.tile(logits[None], (512, 1))))}
    assert host == graph == nucleus


def _aligned(a: np.ndarray) -> np.ndarray:
    """A copy of `a` whose first byte sits on a 64-byte boundary: the
    CPU backend hands such a buffer to a program WITHOUT copying."""
    raw = np.zeros(a.nbytes + 64, np.uint8)
    off = -raw.ctypes.data % 64
    out = raw[off: off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


@pytest.mark.parametrize("kind", ["latent", "llama"])
def test_row0_joiner_under_a_chunk_in_flight_keeps_its_positions(kind):
    """A suffix prefill dispatched while a decode chunk is in flight
    queues behind it; the host bumps cache.lengths[row] right after
    the dispatch.  The program must see the length of the dispatch,
    not the bumped one — row 0's view of an aligned `lengths` is the
    one the CPU backend aliases (found by the benchmark's audit, PR
    26: every row-0 joiner under an in-flight chunk answered from
    shifted positions)."""
    if kind == "latent":
        m = mla.LatentCompletionModel(CFG, seed=3, temp=0.0)
    else:
        from libsplinter_tpu.models.decoder import (CompletionModel,
                                                    DecoderConfig)
        m = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(32, 64), temp=0.0, seed=1)
    cache = m.init_paged(4, page=16)
    cache.lengths = _aligned(cache.lengths)
    cache.tables = _aligned(cache.tables)
    ids = IDS % 512
    m.paged_prefill_row(cache, ids[:40], 1)
    shared = [int(b) for b in cache.tables[1, :2]]
    cache.map_shared(3, shared)          # row 3 decodes meanwhile
    cache.lengths[3] = 32
    assert cache.ensure(3, 32 + 24)

    def join(busy: bool) -> np.ndarray:
        pend = None
        if busy:
            toks = np.full((4,), -1, np.int32)
            toks[3] = ids[32]
            pend = m.paged_decode_chunk_async(cache, toks, 8)
        cache.map_shared(0, shared)
        cache.lengths[0] = 32
        got = np.array(m.paged_append_prefill(cache, ids[32:45], 0))
        if pend is not None:
            pend.block()
        cache.free_row(0)
        return got

    want = join(busy=False)
    for _ in range(3):
        np.testing.assert_allclose(join(busy=True), want, atol=2e-5)


def test_latent_kernel_and_append_match_the_gathered_reference():
    rng = np.random.default_rng(1)
    B, H, W, rank, page, P, nb = 3, 8, 48, 32, 16, 5, 20
    pool = jnp.asarray(rng.normal(size=(nb, W, page)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, (B, P)), jnp.int32)
    lengths = jnp.asarray([5, 33, 70], jnp.int32)
    for S in (1, 4):
        q = jnp.asarray(rng.normal(size=(B, S, H, W)), jnp.float32)
        want = latent_paged_attention(q, pool, tables, lengths,
                                      kv_rank=rank, scale=0.2)
        got = latent_paged_attention(q, pool, tables, lengths,
                                     kv_rank=rank, scale=0.2,
                                     interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-6)
    # append: three tokens of one page, one of another, two to trash
    lat = jnp.asarray(rng.normal(size=(6, W)), jnp.float32)
    bids = jnp.asarray([4, 4, 4, 9, 0, 0], jnp.int32)
    offs = jnp.asarray([3, 4, 5, 0, 1, 1], jnp.int32)
    want = latent_append(pool, lat, bids, offs)
    got = latent_append(pool, lat, bids, offs, interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[1:],
                                  np.asarray(want)[1:])
    np.testing.assert_array_equal(np.asarray(got)[4, :, 4], lat[1])


# lengths of the rows of one decode call, by what they try (pages of 16
# columns, DECODE_PAGES = 8: a chunk is 128 columns): a row's last
# token in the first / the last column of a page, in the last page of a
# chunk and the first page of the next, a row of one token, a dead row
DECODE_ROWS = {
    "page-edges": [17, 32, 1, 0, 16, 33, 209, 224],
    "chunk-edges": [113, 128, 129, 144, 112, 127, 256, 257],
    "ragged": [1, 1000, 0, 5, 640, 77, 1024, 300],
}


def _own_pages(n_pages, width: int) -> np.ndarray:
    """A block table (rows, width) in which row b maps n_pages[b]
    pages of its own, from block 1 on; the rest is the trash block."""
    tables = np.zeros((len(n_pages), width), np.int32)
    at = 1
    for b, n in enumerate(n_pages):
        tables[b, :n] = np.arange(at, at + n)
        at += n
    return tables


@pytest.mark.parametrize("rows", sorted(DECODE_ROWS))
@pytest.mark.parametrize("heads, width", [(128, 66), (32, 128)])
def test_latent_decode_kernel_attends_chunks_of_pages(heads, width, rows):
    """The decode face attends DECODE_PAGES table pages a grid step
    under one online-softmax update: every live key of every row, a
    table that is no whole number of chunks (66) padded with the trash
    block, zeros for a dead row — at pangu's 128 heads and kimi's 32."""
    from libsplinter_tpu.ops.latent_attention import (DECODE_PAGES,
                                                      pages_per_step)
    assert pages_per_step(1, 16) == pages_per_step(1, 128) \
        == DECODE_PAGES == 8
    # wider pages: a chunk spans 1,024 columns at most
    assert [pages_per_step(1, p) for p in (256, 512, 1024, 2048)] \
        == [4, 2, 1, 1]
    rng = np.random.default_rng(heads + width)
    W, rank, page = 48, 32, 16
    lengths = np.asarray(DECODE_ROWS[rows], np.int32)
    B = len(lengths)
    nb = 1 + int(-(-lengths // page).sum())
    pool = jnp.asarray(rng.normal(size=(nb, W, page)), jnp.float32)
    tables = _own_pages(-(-lengths // page), width)
    q = jnp.asarray(rng.normal(size=(B, heads, W)), jnp.float32)
    want = np.asarray(latent_paged_attention(
        q, pool, tables, lengths, kv_rank=rank, scale=0.2))
    got = np.asarray(latent_paged_attention(
        q, pool, tables, lengths, kv_rank=rank, scale=0.2,
        interpret=True))
    live = lengths > 0
    np.testing.assert_allclose(got[live], want[live], atol=3e-6)
    # a dead row reads zeros (the reference's softmax over no key is
    # uniform over the table)
    assert not got[~live].any()
    assert np.abs(got[live]).max(axis=(1, 2)).min() > 1e-3


@pytest.mark.parametrize("ragged", [False, True],
                         ids=["whole", "q_valid"])
def test_latent_stack_kernel_keeps_one_page_a_step(ragged):
    """A stack of tokens attends ONE page a grid step, as it did: over
    a table of 66 pages, with and without q_valid, what the gathered
    reference gives."""
    from libsplinter_tpu.ops.latent_attention import pages_per_step
    assert pages_per_step(4, 16) == pages_per_step(64, 128) == 1
    rng = np.random.default_rng(11)
    B, S, H, W, rank, page, P = 3, 32, 32, 48, 32, 16, 66
    lengths = np.asarray([1, 500, 1024], np.int32)
    nb = 1 + int(-(-(lengths + S) // page).sum())
    pool = jnp.asarray(rng.normal(size=(nb, W, page)), jnp.float32)
    tables = _own_pages(-(-(lengths + S - 1) // page), P)
    valid = np.asarray([32, 9, 17], np.int32)
    kw = dict(kv_rank=rank, scale=0.2)
    if ragged:
        kw["q_valid"] = valid
    q = jnp.asarray(rng.normal(size=(B, S, H, W)), jnp.float32)
    want = np.asarray(latent_paged_attention(q, pool, tables, lengths,
                                             kv_rank=rank, scale=0.2))
    got = np.asarray(latent_paged_attention(q, pool, tables, lengths,
                                            interpret=True, **kw))
    for b, n in enumerate(valid if ragged else [S] * B):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=3e-6)


def test_latent_kernel_skips_the_blocks_of_pad_tokens():
    """Rows that bring suffixes of their own lengths in one width say
    how many of their stacked tokens are real (q_valid): the kernel
    splits a program's query rows into token blocks and skips those
    wholly past the count — a real token's output is what the unsplit
    kernel gives, a dead row (no real token) computes nothing."""
    from libsplinter_tpu.ops.latent_attention import head_group, q_blocks
    rng = np.random.default_rng(7)
    B, S, H, W, rank, page, P, nb = 4, 32, 32, 48, 32, 16, 6, 24
    assert q_blocks(S, head_group(H, S)) == 4          # 8 tokens a block
    pool = jnp.asarray(rng.normal(size=(nb, W, page)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, nb, (B, P)), jnp.int32)
    lengths = jnp.asarray([5, 33, 60, 1], jnp.int32)
    valid = np.asarray([32, 9, 16, 0], np.int32)
    q = jnp.asarray(rng.normal(size=(B, S, H, W)), jnp.float32)
    want = np.asarray(latent_paged_attention(
        q, pool, tables, lengths, kv_rank=rank, scale=0.2))
    got = np.asarray(latent_paged_attention(
        q, pool, tables, lengths, kv_rank=rank, scale=0.2,
        q_valid=valid, interpret=True))
    for b, n in enumerate(valid):
        np.testing.assert_allclose(got[b, :n], want[b, :n], atol=2e-6)
        blocks_run = -(-int(n) // 8) * 8
        assert np.isfinite(got[b]).all()
        assert not got[b, blocks_run:].any()           # skipped: zeros


def test_grouped_matmul_kernel_matches_ragged_dot():
    rng = np.random.default_rng(2)
    lhs = jnp.asarray(rng.normal(size=(200, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 256, 128)), jnp.float32)
    sizes = jnp.asarray([0, 70, 1, 90], jnp.int32)
    want = np.asarray(grouped_matmul(lhs, rhs, sizes))
    got = np.asarray(grouped_matmul(lhs, rhs, sizes, interpret=True))
    np.testing.assert_allclose(got[:161], want[:161], rtol=1e-5,
                               atol=1e-4)


def _moe_layer(cfg, seed=5):
    """One MoE layer's parameters of `cfg`'s share, float32."""
    lp = mla.init_params(cfg, seed)["layers"][-1]
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)


def test_shares_of_a_layer_sum_to_the_uncut_layer():
    """32 experts over 4 shares of 8: the parts the shares compute,
    with the shared expert — which every chip computes alike —
    counted once, add up to the uncut reference layer."""
    kw = dict(dtype=jnp.float32, n_routed_experts=32, top_k=4,
              layers=1, dense_layers=0)
    whole = mla.LatentMoeConfig.tiny(**kw)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)
    want = R.ffn(whole, _moe_layer(whole), x)
    shared_only = None
    total = jnp.zeros_like(x)
    slots = 0
    for first in range(0, 32, 8):
        cfg = mla.LatentMoeConfig.tiny(experts_first=first,
                                       experts_held=8, **kw)
        lp = _moe_layer(cfg)
        part, n = mla._ffn(cfg, lp, x, None, False)
        if shared_only is None:
            shared_only = R.swiglu(x, lp["shared_gate"], lp["shared_up"],
                                   lp["shared_down"])
        # the share's routed part agrees with the reference's share
        np.testing.assert_allclose(part, R.ffn(cfg, lp, x), atol=2e-5)
        total = total + part - shared_only
        slots += int(n.sum())
    np.testing.assert_allclose(total + shared_only, want, atol=5e-5)
    assert slots == 24 * 4             # every slot landed on one share


def test_router_gates_sigmoid_topk_normalise_scale():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    w = rng.normal(size=(16, 12)).astype(np.float32)
    ids, gates = router_gates(jnp.asarray(x), jnp.asarray(w), top_k=3,
                              score="sigmoid", norm_topk=True, scale=2.5)
    s = 1.0 / (1.0 + np.exp(-(x @ w)))
    for t in range(5):
        top = np.argsort(-s[t])[:3]
        assert set(map(int, ids[t])) == set(map(int, top))
        want = s[t, np.asarray(ids[t])] / s[t, top].sum() * 2.5
        np.testing.assert_allclose(gates[t], want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)


def test_no_token_dropped_when_every_token_picks_the_same_expert():
    """A router that sends all 40 tokens to expert 2 (and 3): the held
    experts see 80 slots, none dropped, and the layer's output is the
    gated sum for every token."""
    rng = np.random.default_rng(7)
    H, M, E = 16, 8, 6
    x = jnp.asarray(np.abs(rng.normal(size=(40, H))) + 0.1, jnp.float32)
    router = np.zeros((H, E), np.float32)
    router[:, 2], router[:, 3] = 2.0, 1.0       # positive x: 2 then 3
    wg, wu = (jnp.asarray(rng.normal(size=(E, H, M)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(E, M, H)), jnp.float32)
    out, slots = sparse_moe(x, jnp.asarray(router), wg, wu, wd, top_k=2,
                            score="sigmoid", scale=1.0)
    assert list(map(int, slots)) == [0, 0, 40, 40, 0, 0]
    ids, gates = router_gates(x, jnp.asarray(router), top_k=2,
                              score="sigmoid")
    want = sum(gates[:, j: j + 1] * R.swiglu(x, wg[e], wu[e], wd[e])
               for j, e in enumerate((2, 3)))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)
    # dead rows are routed nowhere and counted nowhere
    live = jnp.arange(40) < 10
    out, slots = sparse_moe(x, jnp.asarray(router), wg, wu, wd, top_k=2,
                            score="sigmoid", live=live)
    assert int(slots.sum()) == 20 and not np.asarray(out)[10:].any()


def test_sandwich_norm_placement(model):
    """h = x + N2(Attn(N1(x))), y = h + N4(FFN(N3(h))): the layer
    function against the four norms spelled out by hand; moving N2
    inside the attention input changes the result."""
    lp = model.params["layers"][0]
    x = jnp.asarray(np.random.default_rng(8).normal(size=(12, 64)),
                    jnp.float32)
    eps = CFG.rms_eps
    a = R.attention(CFG, lp, R.rms(x, lp["ln_attn_in"], eps))
    h = x + R.rms(a, lp["ln_attn_out"], eps)
    f = R.ffn(CFG, lp, R.rms(h, lp["ln_mlp_in"], eps))
    want = h + R.rms(f, lp["ln_mlp_out"], eps)
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    got, _, _ = mla._layer(
        CFG, lp, x[None], pos,
        lambda qn, qr, lat: (mla._expanded_attention(CFG, lp, qn, qr,
                                                     lat), None),
        None, False)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    pre_only = x + a                      # no N2: a different layer
    assert np.abs(np.asarray(pre_only - h)).max() > 1e-2
    plain = mla.LatentMoeConfig.tiny(dtype=jnp.float32,
                                     sandwich_norm=False)
    assert "ln_attn_out" not in mla.init_params(plain, 0)["layers"][0]


def _describe(tmp_path, arch=ARCH, share=SHARE, **extra):
    path = tmp_path / "model.json"
    path.write_text(json.dumps({"architecture": arch, "share": share,
                                "seed": 11, **extra}))
    return str(path)


def test_description_loader_maps_the_published_keys(tmp_path):
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=256)
    assert (cfg.hidden, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) \
        == (64, 4, 32, 32, 16, 8, 16)
    assert (cfg.dense_mlp_dim, cfg.moe_mlp_dim, cfg.n_routed_experts,
            cfg.top_k, cfg.n_shared_experts) == (128, 32, 16, 4, 1)
    assert (cfg.layers, cfg.dense_layers, cfg.experts_first,
            cfg.experts_held, cfg.vocab_first, cfg.vocab_size) \
        == (3, 1, 4, 8, 0, 512)
    assert cfg.routed_scaling_factor == 2.5 and cfg.norm_topk_prob
    assert cfg.sandwich_norm and cfg.score_fn == "sigmoid"
    assert cfg.rope_base == 25.6e6 and cfg.max_len == 256
    assert seed == 11
    assert cfg.latent_width == 40
    # without a share: the whole model
    whole, _ = mla.load_model_description(
        _describe(tmp_path, share={}), max_len=64)
    assert (whole.layers, whole.dense_layers, whole.experts_held,
            whole.vocab_size) == (61, 3, 16, 4096)


@pytest.mark.parametrize("bad, match", [
    ({"architecture": {**ARCH, "n_group": 8}}, "n_group"),
    ({"architecture": ARCH, "colour": 1}, "colour"),
    ({"architecture": ARCH, "audit": {"every": 1}}, "audit"),
    ({"architecture": ARCH, "share": {"heads": 2}}, "heads"),
    ({"architecture": {**ARCH, "attention_bias": True}}, "bias"),
    ({"architecture": {**ARCH, "num_key_value_heads": 2}}, "kv heads"),
    ({"architecture": {k: v for k, v in ARCH.items()
                       if k != "kv_lora_rank"}}, "kv_lora_rank"),
])
def test_description_loader_rejects(tmp_path, bad, match):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(str(path))


def test_resident_tree_is_bfloat16_with_no_float32_copy():
    """Matrices and the embedding are MADE in bfloat16; only norm
    scales and the router are float32 — the tree's bytes are what a
    bfloat16 model weighs, and the page pool follows the model's
    layout: 40 values a token a layer."""
    cfg = mla.LatentMoeConfig.tiny()
    m = mla.LatentCompletionModel(cfg, seed=1)
    small = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(m.params)[0]:
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 1 or "router" in name:
            assert leaf.dtype == jnp.float32, name
            small += leaf.nbytes
        else:
            assert leaf.dtype == jnp.bfloat16, name
    n_params = sum(a.size for a in jax.tree_util.tree_leaves(m.params))
    assert m.resident_bytes() == 2 * n_params + small // 2
    assert small < 0.05 * m.resident_bytes()
    # the same weights whatever the dtype they are kept in
    f32 = mla.seed_tensor(1, "layers.0.w_dq", (64, 32), 0.125,
                          jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(f32.astype(jnp.bfloat16)),
        np.asarray(m.params["layers"][0]["w_dq"]))
    cache = m.init_paged(2, page=16, pool_pages=8)
    assert cache.layout.pools == (("latent", (40, 16)),)
    assert cache.kv_bytes_per_token() == 3 * 40 * 2
    assert cache.pools[0][0].shape == (9, 40, 16)
    assert cache.device_mb() == round(3 * 9 * 40 * 16 * 2 / 1e6, 3)
    with pytest.raises(ValueError, match="key/value page layout"):
        PagedKVCache(cfg, 2, page=16, kv_dtype="int8")


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--kv-dtype", "int4"], "--kv-dtype int4"),
    (["--kv-tier-pages", "4"], "--kv-tier-pages"),
    (["--phase", "prefill"], "--phase prefill"),
    (["--phase", "decode"], "--phase decode"),
    (["--tp", "2"], "--tp 2"),
    (["--ep", "2"], "--ep 2"),
    (["--draft-layers", "2"], "--draft-layers"),
    (["--weights", "x.gguf"], "--weights x.gguf"),
    (["--weights-int8"], "--weights-int8"),
])
def test_main_refuses_what_the_latent_model_cannot_serve(
        tmp_path, flags, match):
    """One typed message, before the store is opened or a weight made."""
    with pytest.raises(SystemExit) as ex:
        C.main(["--store", "/spt-never-opened", "--continuous",
                "--model", _describe(tmp_path), *flags])
    assert "unsupported_option" in str(ex.value)
    assert match in str(ex.value)


def test_main_refuses_the_dense_lane(tmp_path):
    with pytest.raises(SystemExit, match="--continuous"):
        C.main(["--store", "/spt-never-opened", "--model",
                _describe(tmp_path)])


def test_paged_budget_keeps_a_long_prompts_head(tmp_path):
    """The paged lane's budget is the window less max_new, with a
    prefill bucket that reaches it — not the widest dense bucket."""
    assert mla.prefill_buckets(8448, 128) == (256, 640, 2176, 8448)
    assert mla.prefill_buckets(128, 16) == (32, 128)
    name = f"/spt-mla-budget-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=1024, vec_dim=8)
    try:
        m = mla.LatentCompletionModel(
            mla.LatentMoeConfig.tiny(max_len=2048), seed=1)
        comp = C.Completer(st, model=m, max_new_tokens=64,
                           template="none")
        assert comp._paged_budget() == 2048 - 64
        ids = list(range(3000))
        assert comp._clip_paged(ids) == ids[-1984:]
        assert comp._clip_paged(ids[:1500]) == ids[:1500]
        assert m.bucket_for(1984) == 2048
    finally:
        st.close()
        Store.unlink(name)


def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_latent", os.path.join(
            REPO, "benchmark", "reference", "latent_moe_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_two_copies_of_the_reference_agree(tmp_path):
    """benchmark/reference/latent_moe_block.py (its own weights from
    the seed, layer by layer) == tests/reference_mla.py on the
    program's tree; its float8 control does not."""
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=64)
    params = mla.init_params(cfg, seed)
    seqs = [IDS[:40] % 512, IDS[5:33] % 512]
    pos = [[20, 39], [0, 27]]
    bench = _bench_reference()
    got = bench.forward_logits(ARCH, SHARE, seed, seqs, pos)
    for s, p, g in zip(seqs, pos, got):
        want = R.forward(cfg, params, s)[p]
        np.testing.assert_allclose(g, want, atol=5e-5)
    low = bench.forward_logits(ARCH, SHARE, seed, seqs[:1], pos[:1],
                               f8=True)
    assert bench.rel_err(low[0], got[0]).min() > 0.02


def test_main_serves_submit_completion_and_its_audit_matches(
        tmp_path, monkeypatch):
    """`completer.main --model ... --continuous --warmup`: a request
    sent with submit_completion comes back READY with generated bytes,
    a second one sharing its first page hits the prefix cache, the
    heartbeat carries the lane's counters, and the audit record —
    prompt, generated ids, the logits behind each of them — is what the
    reference's full forward gives."""
    name = f"/spt-mla-main-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    path = _describe(tmp_path)
    seen = {}
    run = C.Completer.run_continuous

    def capture(self, **kw):
        seen["comp"] = self
        return run(self, **kw)
    monkeypatch.setattr(C.Completer, "run_continuous", capture)
    th = threading.Thread(
        target=C.main, daemon=True,
        args=(["--store", name, "--model", path, "--continuous",
               "--warmup", "--batch-cap", "4", "--page-size", "16",
               "--n-ctx", "128", "--max-new-tokens", "6", "--temp", "0",
               "--idle-timeout-ms", "20", "--audit-dir", audit_dir,
               "--audit-every", "1"],))
    th.start()
    try:
        doc = "a long shared document. " * 2          # 48 bytes: 3 pages
        outs = [submit_completion(st, f"q/{i}", doc + q,
                                  timeout_ms=240_000)
                for i, q in enumerate(("what?", "and why is that so?"))]
        assert all(isinstance(o, bytes) and o.startswith(doc.encode())
                   for o in outs)
        comp = seen["comp"]
        # READY reaches the client a moment before the daemon's own
        # thread has counted the completion
        for _ in range(100):
            if comp.stats.completions == 2 and comp.audit.written == 2:
                break
            time.sleep(0.05)
        comp.publish_stats()
        hb = json.loads(st.get(C.P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert hb["completions"] == 2 and hb["faults"] == 0
        assert hb["decode_steps"] >= 8 and hb["decode_rows"] >= 8
        assert hb["prompt_tokens"] == 2 * 49 + 5 + 19
        assert hb["prefix_tokens"] == 48 and hb["prefix_hits"] == 1
        assert hb["kv_dtype"] == "bf16"
        # the latent decode kernel's pages a grid step, beside the
        # overlap gauge (ops/latent_attention.pages_per_step)
        assert hb["latent_decode_pages_per_step"] == 8
        assert hb["audit_records"] == 2
        assert len(hb["expert_totals"]) == 8
        assert sum(hb["expert_totals"]) == hb["expert_slots"] > 0
        assert {"jax", "weights", "warmup", "total"} \
            <= set(hb["startup_ms"])
        assert {"paged_chunk", "suffix_prefill", "bucket_prefill"} \
            <= set(hb["devtime"])
        assert all(hb["devtime"][k]["n"] > 0 for k in
                   ("paged_chunk", "suffix_prefill", "bucket_prefill"))
        cfg, params = comp._model.cfg, comp._model.params
        errs = []
        for i in range(2):
            rec = np.load(os.path.join(audit_dir, f"{i}.npz"))
            prompt, toks = rec["prompt"], rec["tokens"]
            assert prompt[0] == 1 and len(toks) == 6
            assert int(rec["n_prefix"]) == (0, 48)[i]
            full = R.forward(cfg, params,
                             np.concatenate([prompt, toks[:-1]]))
            spread = full.std()
            assert rec["logits"].shape == (6, cfg.vocab_size)
            # row i is what token i was sampled from: the position
            # of the token before it
            for i_tok, got in enumerate(rec["logits"]):
                at = len(prompt) - 1 + i_tok
                errs.append(np.abs(got - full[at]).max() / spread)
        # bfloat16 against float32: rounding everywhere (the median),
        # and now and then a token whose k-th expert flips (the worst)
        assert np.median(errs) < 0.08 and max(errs) < 2.0, errs
    finally:
        if "comp" in seen:
            seen["comp"].stop()
        th.join(timeout=30)
        st.close()
        Store.unlink(name)
