"""Keye-VL-2.0's language block as a SETTING of models/afmoe.py: an
all-global grouped-query stack whose every layer attends the `topk`
keys a learned INDEXER selects, the indexer's keys a THIRD pool of the
page group, softmax routing without a shared expert — model, kernels,
cache manager, prefix tree and the continuous lane, on the CPU at tiny
widths (topk 24, well below the contexts), against the plain float32
references: tests/reference_keye.py (the program's own parameter tree)
and its twin benchmark/reference/sparse_gqa_moe_block.py (its own
weights from the seed, nothing of the program).

Tolerances.  In float32 the program IS the reference to 1e-3 of the
logits' spread (the selection is exact in both, and a near-tie closer
than a float32 rounding does not occur in these prompts).  In bfloat16,
as the chip runs it, two things differ: the activations' roundings
(tests/test_mimo.py: a median of 0.04 at hidden 64) and — this
family's own — the SELECTION: a rounding of an indexer score flips
which of two keys at the 24th rank is attended, and at 24 of ~100 keys
with attention scores of std 3 one key in two dozen can carry most of
a query's mass, through 4 heads and a branch that writes at 0.4 of the
stream (12 layers, not 48): a flip at one token reaches every later
token that attends it, and past topk the tiny bfloat16 stack is
CHAOTIC (a median error of 2 at 8 layers; CPU runs, PR 44).  So the
bfloat16 test holds the logits up to topk (TOL_MEDIAN), counts layer
0's flips against the reference's selection — each must sit at a
near-tie — and everything past topk is held in float32."""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_keye
from test_page_groups import batch
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import PrefixCache
from libsplinter_tpu.models import afmoe, mla
from libsplinter_tpu.models.encoder import _rotary_angles_at
from libsplinter_tpu.models.moe import sparse_moe
from libsplinter_tpu.ops import sparse_attention as sa
from libsplinter_tpu.ops.page_groups import decode_groups
from libsplinter_tpu.ops.paged_attention import window_paged_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, TOPK = 16, 24
TOL_F32, TOL_MEDIAN = 1e-3, 0.15

ARCH = {"model_type": "KeyeVL2", "hidden_act": "silu",
        "tie_word_embeddings": False, "attention_bias": False,
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 16, "num_local_experts": 16,
        "num_experts_per_tok": 16, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "rope_scaling": {"mrope_section": [2, 3, 3],
                         "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": TOPK},
        "sliding_window": None, "use_sliding_window": False,
        "max_window_layers": 12, "max_position_embeddings": 262144,
        "num_hidden_layers": 12, "vocab_size": 4096}
SHARE = {"layers": 6, "dense_layers": 0, "experts": [4, 8],
         "vocab": [0, 512]}
SEED = 11
IDS = np.random.default_rng(0).integers(3, 512, 400).astype(np.int32)


def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_sparse", os.path.join(
            REPO, "benchmark", "reference", "sparse_gqa_moe_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BENCH = _bench_reference()


def _describe(tmp_path, arch=ARCH, share=SHARE, **extra):
    path = str(tmp_path / "model.json")
    with open(path, "w") as f:
        json.dump({"architecture": arch, "share": share, "seed": SEED,
                   **extra}, f)
    return path


@pytest.fixture(scope="module")
def cfg(tmp_path_factory):
    got, seed = mla.load_model_description(
        _describe(tmp_path_factory.mktemp("keye")), max_len=512)
    assert seed == SEED
    return got


@pytest.fixture(scope="module")
def model(cfg):
    return afmoe.IndexedCompletionModel(cfg, seed=SEED)


@pytest.fixture(scope="module")
def model32(cfg):
    return afmoe.IndexedCompletionModel(
        dataclasses.replace(cfg, dtype=jnp.float32), seed=SEED)


@pytest.fixture(scope="module")
def bench_run():
    """The benchmark reference's logits behind every position of
    IDS[:130], and its selections a layer."""
    sels = []
    logits = BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]],
                                  [list(range(130))], block=16,
                                  selections=sels)[0]
    return logits, sels


def _teacher_forced(m, cache, row, tokens):
    out = []
    m.audit_seat(0, row)
    for t in tokens:
        toks = np.full((cache.batch,), -1, np.int32)
        toks[row] = t
        pend = m.paged_decode_chunk_async(cache, toks, 1)
        pend.block()
        out.append(np.asarray(pend.audit)[0, 0])
    m.audit_seat(0, -1)
    return np.stack(out)


# ------------------------------------------------------ the description

def test_description_loader_reads_the_keye_key_set(cfg):
    assert isinstance(cfg, afmoe.WindowMoeConfig)
    assert cfg.indexer == afmoe.Indexer(4, 8, TOPK)
    assert cfg.kinds == ("full",) * 6 and cfg.plan == (0, 1, 6)
    a = cfg.attn("full")
    assert (a.kv_heads, a.qk_dim, a.v_dim, a.rotary_dim, a.window) \
        == (2, 16, 16, 16, 0) and a.rope_base == 1e7
    assert (cfg.score_fn, cfg.n_shared_experts, cfg.dense_layers,
            cfg.model_layers) == ("softmax", 0, 0, 12)
    assert (cfg.qk_norm, cfg.out_gate, cfg.sandwich_norm, cfg.mup) \
        == (True, False, False, False)
    assert mla.completion_model_class(cfg) is afmoe.IndexedCompletionModel
    # one page group, three pools: a table entry names a page of each
    (layout,) = cfg.page_layout(PAGE)
    assert [n for n, _ in layout.pools] == ["k", "v", "ik"]
    assert not layout.key_value and layout.window == 0
    assert layout.token_values == 6 * (2 * 2 * 16 + 8)


@pytest.mark.parametrize("bad, match", [
    ({"sa_config": {**ARCH["sa_config"], "indexer_num_kv_heads": 2}},
     "indexer_num_kv_heads 1"),
    ({"sa_config": {**ARCH["sa_config"], "block_size": 128}},
     "sa_config must hold exactly"),
    ({"sa_config": {k: v for k, v in ARCH["sa_config"].items()
                    if k != "topk"}}, "sa_config must hold exactly"),
    ({"rope_scaling": {"mrope_section": [2, 3, 4]}}, "mrope_section"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}},
     "rope_scaling must be the default"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers must be empty"),
    ({"num_local_experts": 8}, "num_local_experts equal num_experts"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step must be 1"),
    ({"use_sliding_window": True}, "use_sliding_window is not served"),
    ({"sliding_window": 4096}, "sliding_window must be null"),
    ({"attention_bias": True}, "attention_bias is not served"),
    ({"n_shared_experts": 1}, "unknown architecture key"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(_describe(tmp_path, {**ARCH, **bad}))


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "indexer's keys in a third"),
    (["--kv-dtype", "int4"], "indexer's keys in a third"),
    (["--kv-tier-pages", "8"], "host tier's page wire"),
    (["--kv-tier-persist"], "host tier's page wire"),
    (["--phase", "prefill"], "disaggregated hand-off's page wire"),
    (["--tp", "2"], "not sharded on their kv-head axis"),
    (["--ep", "2"], "told the experts it holds"),
    (["--draft-layers", "2"], "speculative wrapper"),
    (["--weights", "x.safetensors"], "seeded weights"),
    (["--quantized"], "int8 weight residencies"),
    (["--state-snapshots", "4"], "keep no recurrent state"),
    (["--window-pool-pages", "4"], "keep one page group"),
], ids=lambda v: "".join(v) if isinstance(v, list) else None)
def test_main_refuses_what_the_indexed_model_cannot_serve(
        tmp_path, flags, match):
    with pytest.raises(SystemExit, match=match) as ex:
        C.main(["--store", f"/spt-keye-refuse-{os.getpid()}", "--model",
                _describe(tmp_path), "--continuous", *flags])
    assert str(ex.value).startswith("unsupported_option: ")


def test_a_cache_with_a_third_pool_refuses_codecs_and_sharding(cfg):
    """Below main(): the cache itself refuses to quantize or shard a
    layout it was DESCRIBED (the page codecs and the kv-head sharding
    know the key/value pair), rather than drop the third pool."""
    m = afmoe.IndexedCompletionModel(cfg, params={})
    with pytest.raises(ValueError, match=r"\['ik', 'k', 'v'\]"):
        m.init_paged(2, page=PAGE, pool_pages=40, kv_dtype="int8")


# ----------------------------------------------------------- the kernels

def _pools(rng, nb=40, L=2, KH=2, D=16, DI=8, dtype=jnp.bfloat16):
    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    return arr(nb, L, KH, PAGE, D), arr(nb, L, KH, PAGE, D), \
        arr(nb, L, 1, DI, PAGE)


@pytest.mark.parametrize("q_tokens", [1, 16, 48])
def test_index_scan_matches_the_plain_sum(q_tokens):
    """I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]) over every key of
    the row's pages, a table of 11 pages scanned 8 pages a program
    (the last program's overshoot re-reads the last page: those keys
    lie past every limit)."""
    rng = np.random.default_rng(q_tokens)
    _, _, ik = _pools(rng)
    B, HI, DI, P = 3, 4, 8, 11
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:B * P]
                         .reshape(B, P), jnp.int32)
    lengths = jnp.asarray([100, 37, 120], jnp.int32)
    qi = jnp.asarray(rng.standard_normal((B, q_tokens, HI, DI)),
                     jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, q_tokens, HI)), jnp.float32)
    got = sa.index_scores(qi, w, ik, tables, lengths, layer=1,
                          interpret=True)
    assert got.shape == (B, q_tokens, sa.scan_width(P, PAGE))
    keys = np.asarray(ik[:, 1, 0], np.float32)[np.asarray(tables)] \
        .transpose(0, 2, 1, 3).reshape(B, DI, P * PAGE)
    want = np.einsum("bsh,bsht->bst", np.asarray(w), np.maximum(
        np.einsum("bshd,bdt->bsht", np.asarray(qi, np.float32), keys), 0))
    seen = np.arange(P * PAGE)[None, None] < (
        np.asarray(lengths)[:, None, None] + np.arange(q_tokens)[None, :,
                                                                 None])
    np.testing.assert_allclose(
        np.where(seen, np.asarray(got)[..., :P * PAGE], 0),
        np.where(seen, want, 0), atol=1e-4)
    np.testing.assert_array_equal(
        np.where(seen, np.asarray(sa.index_scores(
            qi, w, ik, tables, lengths, layer=1))[..., :P * PAGE], 0)
        .round(3), np.where(seen, want, 0).round(3))


@pytest.mark.parametrize("topk", [1, 8, 64, 300])
def test_selection_is_the_exact_top_k_with_ties_to_the_lower_position(
        topk):
    """Against a brute-force count: scores quantised to halves (ties
    everywhere, -0.0 among them), limits from 1 key to all of them."""
    rng = np.random.default_rng(topk)
    B, S, T = 2, 9, 256
    scores = np.round(rng.standard_normal((B, S, T)) * 2) / 2
    limits = rng.integers(1, T + 1, (B, S))
    limits[0, 0], limits[1, 1] = T, min(topk, T)
    want = np.zeros((B, S, T), np.float32)
    for b in range(B):
        for s in range(S):
            n = int(limits[b, s])
            order = sorted(range(n), key=lambda j: (-scores[b, s, j], j))
            want[b, s, order[:topk]] = 1.0
    for kw in ({"interpret": True}, {}):
        got = sa.select_topk(jnp.asarray(scores, jnp.float32),
                             jnp.asarray(limits, jnp.int32), topk=topk,
                             **kw)
        np.testing.assert_array_equal(np.asarray(got), want)
    assert want.sum() == np.minimum(limits, topk).sum()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("q_tokens", [1, 16, 32])
def test_attention_over_a_selection_matches_the_masked_softmax(
        q_tokens, dtype):
    """The kernel under a random selection against the plain masked
    softmax; a row whose selection is empty reads zeros."""
    rng = np.random.default_rng(q_tokens)
    kp, vp, _ = _pools(rng, dtype=dtype)
    B, H, D, P = 3, 4, 16, 11
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:B * P]
                         .reshape(B, P), jnp.int32)
    lengths = jnp.asarray([100, 0, 130], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, q_tokens, H, D)), dtype)
    sel = jnp.asarray(rng.random((B, q_tokens, sa.scan_width(P, PAGE)))
                      < 0.3, jnp.float32).at[1].set(0.0)
    got = sa.sparse_paged_attention(q, kp, vp, sel, tables, lengths,
                                    layer=1, interpret=True)
    want = sa.sparse_paged_attention(q, kp, vp, sel, tables, lengths,
                                     layer=1)
    assert not np.asarray(got[1], np.float32).any()
    for b in (0, 2):
        np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                   np.asarray(want[b], np.float32),
                                   atol=2e-2 if dtype == jnp.bfloat16
                                   else 1e-5)


# rows of a decode batch as (document | None, tokens behind its whole
# pages) or None (dead): documents of 16 and 24 pages (two and three
# programs of 8 pages), PAGE 16, a chunk of 8 steps
WALK_DOCS = (16, 24)
WALK_CASES = {
    "a_group_of_one": [(0, 20)],
    "four_rows_of_one_document_in_scattered_slots":
        [(None, 90), (0, 3), None, (0, 20), (0, 40), (None, 300), (0, 9)],
    "nine_rows_of_one_document_split_at_eight": [(0, 5 + 3 * i)
                                                 for i in range(9)],
    "two_documents_interleaved":
        [(0, 7), (1, 30), (0, 22), (1, 2), (0, 31), (1, 17)],
    "a_dead_row_between_members": [(1, 12), None, (1, 25), None, (1, 3)],
    "members_with_one_two_and_three_own_pages":
        [(0, 2), (0, 16 + 5), (0, 32 + 7), (0, 1)],
    "an_answer_crosses_a_page_edge_inside_the_chunk":
        [(1, 16 - 3), (1, 16 - 8), (1, 5), (1, 32 - 1)],
    "no_sharing_at_all": [(None, 260), (None, 131), (None, 17), None,
                          (None, 396)],
}


def _walk_batch(rng, rows):
    """(pool blocks, tables, lengths at dispatch) of WALK_CASES' rows
    over a table of 28 pages (tests/test_page_groups.batch)."""
    tables, lengths = batch(rng, rows, 28, doc_pages=list(WALK_DOCS))
    return int(tables.max()) + 1, tables, lengths


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_rows_that_share_pages_walk_them_together(case):
    """A decode step's attention by GROUPS (ops/page_groups) — a shared
    page read once, the members' queries stacked against it, each
    under its own selection and length — at the first and the last
    step of a chunk, against the plain masked softmax and against the
    walk a row alone (groups=None: the parent's program, an item a
    (row, chunk)).  In float32 pools the grouped walk equals the walk
    alone to 2e-6 (the same sums in the same order; a product of 8 x
    rep rows may round its last bit otherwise than one of rep) and
    the plain softmax to 1e-5; in bfloat16 to one rounding of the
    output (8e-3).  A row whose selection names ONE key reads that
    key's value bit for bit: no member attends under another's
    selection."""
    rows = WALK_CASES[case]
    rng = np.random.default_rng(sorted(WALK_CASES).index(case))
    nb, tables, lengths = _walk_batch(rng, rows)
    B, H, D, steps = len(rows), 4, 16, 8
    T = sa.scan_width(tables.shape[1], PAGE)
    groups = decode_groups(tables, lengths, page=PAGE, steps=steps,
                           chunk=sa.ATTEND_PAGES[0])
    sizes = sorted(int((col >= 0).sum()) for col in groups["rows"].T
                   if (col >= 0).any())
    assert sum(sizes) == sum(r is not None for r in rows)
    if "no_sharing" in case or "group_of_one" in case:
        assert set(sizes) == {1} and groups["read"] == groups["held"]
    else:
        assert max(sizes) > 1 and groups["read"] < groups["held"]
    if "nine" in case:
        assert sizes == [1, 8]
    walk = {k: v for k, v in groups.items() if k not in ("held", "read")}
    for dtype, tol_alone, tol_plain in ((jnp.float32, 2e-6, 1e-5),
                                        (jnp.bfloat16, 8e-3, 2e-2)):
        kp, vp, _ = _pools(rng, nb=nb, dtype=dtype)
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), dtype)
        for step in (0, steps - 1):
            # keys the step's token sees; a dead row stays at 0
            seen = jnp.asarray(np.where(lengths > 0, lengths + step + 1,
                                        0), jnp.int32)
            sel = jnp.asarray(rng.random((B, 1, T)) < 0.3, jnp.float32)
            args = (q, kp, vp, sel, jnp.asarray(tables), seen)
            got = sa.sparse_paged_attention(*args, layer=1, groups=walk,
                                            interpret=True)
            alone = sa.sparse_paged_attention(*args, layer=1,
                                              interpret=True)
            plain = sa.sparse_paged_attention(*args, layer=1)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(alone, np.float32),
                                       atol=tol_alone)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(plain, np.float32),
                                       atol=tol_plain)
            assert not np.asarray(got, np.float32)[lengths == 0].any()
        # ONE selected key a row, every row another: its value's bits
        at = np.where(lengths > 0, rng.integers(0, 2 ** 30, B)
                      % np.maximum(lengths, 1), 0)
        one = np.zeros((B, 1, T), np.float32)
        one[np.arange(B), 0, at] = 1.0
        got = sa.sparse_paged_attention(
            q, kp, vp, jnp.asarray(one), jnp.asarray(tables),
            jnp.asarray(lengths), layer=1, groups=walk, interpret=True)
        for b in np.flatnonzero(lengths > 0):
            v = np.asarray(vp[tables[b, at[b] // PAGE], 1, :,
                              at[b] % PAGE], np.float32)      # (KH, D)
            np.testing.assert_array_equal(
                np.asarray(got[b, 0], np.float32).reshape(2, 2, D),
                np.broadcast_to(v[:, None], (2, 2, D)))


def test_a_member_under_topk_is_dense_beside_members_that_select():
    """indexed_attention over a group: a member whose token sees at
    most topk keys takes the dense kernel's own bits while the others
    attend their selections — the same answers as without groups, and
    the selection is not the grouping's to touch."""
    rng = np.random.default_rng(7)
    rows = [(0, 2), (0, 5), (0, 60), (0, 33)]      # 16 pages = 256 keys
    topk = 256 + 8                                 # rows 0 and 1 under it
    nb, tables, lengths = _walk_batch(rng, rows)
    kp, vp, ik = _pools(rng, nb=nb)
    B, H, D, HI, DI = 4, 4, 16, 4, 8
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
    qi = jnp.asarray(rng.standard_normal((B, 1, HI, DI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, 1, HI)), jnp.float32)
    groups = decode_groups(tables, lengths, page=PAGE, steps=1,
                           chunk=sa.ATTEND_PAGES[0])
    assert (groups["rows"][:, 0] >= 0).sum() == 4   # one group of four
    walk = {k: v for k, v in groups.items() if k not in ("held", "read")}
    seen = jnp.asarray(lengths + 1)
    args = (q, qi, w, kp, vp, ik, jnp.asarray(tables), seen, seen > 0)
    got = sa.indexed_attention(*args, layer=0, topk=topk, groups=walk,
                               interpret=True)
    alone = sa.indexed_attention(*args, layer=0, topk=topk, interpret=True)
    dense = window_paged_attention(q, kp, vp, jnp.asarray(tables), seen,
                                   layer=0, interpret=True)
    for b in (0, 1):
        np.testing.assert_array_equal(np.asarray(got[b], np.float32),
                                      np.asarray(dense[b], np.float32))
    for b in (2, 3):
        np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                   np.asarray(alone[b], np.float32),
                                   atol=8e-3)
        assert np.abs(np.asarray(got[b], np.float32)
                      - np.asarray(dense[b], np.float32)).max() > 0.02


def test_a_layer_under_topk_is_the_dense_kernel_and_long_rows_select():
    """indexed_attention row by row: a live row whose last token sees
    at most topk keys gets window_paged_attention's own bits, a longer
    one the selection's, a dead one is nobody's."""
    rng = np.random.default_rng(3)
    kp, vp, ik = _pools(rng)
    B, S, H, D, HI, DI, P = 4, 16, 4, 16, 4, 8, 11
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:P * 3]
                         .reshape(3, P)[[0, 1, 2, 0]], jnp.int32)
    lengths = jnp.asarray([9, 100, 40, 0], jnp.int32)   # 9 + 15 = topk
    live = lengths > 0
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    qi = jnp.asarray(rng.standard_normal((B, S, HI, DI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((B, S, HI)), jnp.float32)
    for interpret in (False, True):
        got = sa.indexed_attention(q, qi, w, kp, vp, ik, tables, lengths,
                                   live, layer=0, topk=TOPK,
                                   interpret=interpret)
        dense = window_paged_attention(q, kp, vp, tables, lengths,
                                       layer=0, interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                      np.asarray(dense[0], np.float32))
        sel = sa.select_topk(
            sa.index_scores(qi, w, ik, tables, lengths, layer=0),
            lengths[:, None] + jnp.arange(S)[None], topk=TOPK)
        assert sel[1].sum(-1).max() == TOPK
        want = sa.sparse_paged_attention(q, kp, vp, sel, tables, lengths,
                                         layer=0)
        for b in (1, 2):
            np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                       np.asarray(want[b], np.float32),
                                       atol=2e-2)
            assert np.abs(np.asarray(got[b], np.float32)
                          - np.asarray(dense[b], np.float32)).max() > 0.05


def test_whole_pages_are_written_in_place():
    rng = np.random.default_rng(4)
    kp, _, ik = _pools(rng)
    for pool in (kp, ik):
        pages = jnp.asarray(rng.standard_normal((3, *pool.shape[2:])),
                            pool.dtype)
        got = sa.write_pages(pool, pages, jnp.asarray([7, 0, 9]), layer=1,
                             interpret=True)
        want = np.asarray(pool, np.float32).copy()
        want[[7, 0, 9], 1] = np.asarray(pages, np.float32)
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)


# --------------------------------------------- program against reference

def test_the_two_references_agree(cfg, model, bench_run):
    """tests/reference_keye.py over the program's own (bfloat16) tree
    and the benchmark's reference over its own seeded weights: the
    recipe is restated right, the selections are the same sets."""
    sels = []
    twin = reference_keye.forward(cfg, model.params, IDS[:130],
                                  selections=sels)
    assert BENCH.rel_err(twin, bench_run[0]).max() < TOL_F32
    assert len(sels) == len(bench_run[1]) == 6
    flips = sum(int((a != b).sum()) for a, b in zip(sels, bench_run[1]))
    assert flips <= 4                   # a float32 near-tie or two
    assert sels[0][129].sum() == TOPK and sels[0][10].sum() == 11


@pytest.mark.parametrize("case, prompt, steps, interpret", [
    ("a cold prompt past topk, decode across page boundaries", 100, 24,
     False),
    ("a prompt under topk that grows past it in decode", 20, 12, False),
    ("the kernels themselves, interpreted", 60, 10, True),
])
def test_prefill_then_decode_equals_the_reference_in_float32(
        case, prompt, steps, interpret, model32):
    """The suffix programs from an empty row, then decode steps fed
    the sequence's own tokens through the paged cache: every logit is
    the plain reference's full forward pass's."""
    cfg = model32.cfg
    m = afmoe.IndexedCompletionModel(cfg, params=model32.params,
                                     interpret=interpret)
    cache = m.init_paged(2, page=PAGE, pool_pages=40)
    want = reference_keye.forward(cfg, m.params, IDS[:prompt + steps])
    lg = m.paged_prefill_row(cache, IDS[:prompt], 1)
    got = np.concatenate([lg[None], _teacher_forced(
        m, cache, 1, IDS[prompt: prompt + steps])])
    err = BENCH.rel_err(got, want[prompt - 1: prompt + steps])
    assert err.max() < TOL_F32, err
    cache.free_row(1)
    assert cache.free_pages == 40
    aw = m.attn_work
    assert aw["keys_in_context"] == 6 * sum(range(1, prompt + steps + 1))
    assert aw["select_dense_rows"] == 6 * (
        sum(min(5 * PAGE, prompt - p) + p <= TOPK
            for p in range(0, prompt, 5 * PAGE))
        + max(0, TOPK - prompt))


def test_in_bfloat16_the_selection_flips_only_at_near_ties(
        cfg, model, bench_run):
    """As the chip runs it: prefill + decode in bfloat16 hold the
    reference at the median position, and layer 0's selection, replayed
    from the row's own indexer pages, is the reference's but for keys
    whose score sits within a bfloat16 rounding of the 24th."""
    ref, ref_sels = bench_run
    cache = model.init_paged(2, page=PAGE, pool_pages=40)
    lg = model.paged_prefill_row(cache, IDS[:20], 1)
    got = np.concatenate([lg[None], _teacher_forced(
        model, cache, 1, IDS[20:129])])
    err = BENCH.rel_err(got, ref[19:129])
    # every key selected up to topk: the activations' roundings alone;
    # past it a stack this small is chaotic under its flips (module
    # docstring), and only says that it still computes
    assert err[:TOPK - 19].max() < TOL_MEDIAN, err
    assert np.isfinite(err).all() and np.median(err[TOPK:]) < 3.0
    # layer 0's indexer over the row's pages, the program's own pieces
    lp = jax.tree_util.tree_map(lambda a: a[0], model.params["periods"][0])
    ix, n = cfg.indexer, 129
    x = afmoe._normed(cfg, model.params["tok_emb"][IDS[:n]][None]
                      .astype(jnp.float32), lp["ln_attn_in"])
    cos, sin = _rotary_angles_at(jnp.arange(n), ix.dim, 1e7)
    qi = afmoe._rotate(jnp.einsum("bsh,xh->bsx", x, lp["w_qi"]).reshape(
        1, n, ix.heads, ix.dim).astype(jnp.float32), cos[None], sin[None])
    w = jnp.dot(x, lp["w_wi"], preferred_element_type=jnp.float32) \
        / np.sqrt(ix.heads * ix.dim)
    scores = sa.index_scores(
        qi.astype(cfg.dtype), w, cache.pools[2][0],
        cache.tables[1:2], jnp.asarray([1]), layer=0)
    sel = np.asarray(sa.select_topk(
        scores, 1 + jnp.arange(n)[None], topk=TOPK))[0, :, :n] > 0
    flips = sel != ref_sels[0][:n, :n]
    assert sel.sum(-1).max() == TOPK
    assert 0 < flips.sum() <= 0.01 * sel.sum()     # 10 of 2,820 here
    # a flipped key's score lies at the threshold
    score = np.asarray(scores[0, :, :n])
    for t, s in zip(*np.nonzero(flips)):
        kth = np.sort(score[t, :t + 1])[-TOPK]
        assert abs(score[t, s] - kth) < 0.05 * score[t, :t + 1].std()


def test_a_row_under_topk_is_the_layer_without_an_indexer(model32):
    """The same weights served with the indexer switched off give the
    logits of every position at or under topk — the attention itself
    bit for bit (the kernel-level test above); the programs around it
    fuse their float32 sums in another order, 5e-6 apart."""
    cfg = model32.cfg
    dense = afmoe.WindowCompletionModel(
        dataclasses.replace(cfg, indexer=None), params=model32.params)
    out = []
    for m in (model32, dense):
        cache = m.init_paged(2, page=PAGE, pool_pages=40)
        lg = m.paged_prefill_row(cache, IDS[:18], 1)
        out.append(np.concatenate([lg[None], _teacher_forced(
            m, cache, 1, IDS[18:30])]))
    # positions 17..23 see at most 24 keys
    np.testing.assert_allclose(out[0][:TOPK - 18 + 1],
                               out[1][:TOPK - 18 + 1], atol=2e-5)
    assert np.abs(out[0][-1] - out[1][-1]).max() > 1e-3


def test_a_float8_copy_and_a_recent_only_selection_fail_the_comparison(
        bench_run):
    """What the tolerance is FOR: the reference with every matrix and
    cached key, value and indexer key rounded to float8_e4m3, and the
    reference that attends the last topk positions instead of the
    selected ones, both read far over it past topk."""
    pos = [list(range(40, 130))]
    low = BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]], pos,
                               f8=True, block=16)[0]
    assert np.median(BENCH.rel_err(low, bench_run[0][40:])) > TOL_MEDIAN
    recent = BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]], pos,
                                  recent_only=True, block=16)[0]
    assert np.median(BENCH.rel_err(recent, bench_run[0][40:])) \
        > 2 * TOL_MEDIAN


# ------------------------------------------------- the expert share

def test_eight_shares_with_no_shared_expert_are_the_uncut_layer():
    """Under THIS router's settings — softmax scores over all 32
    experts, plain top-8, renormalised over the selection, NO shared
    expert — the 8 shares of an expert layer add up to the layer with
    every expert held: nothing is counted twice and nothing
    once-for-all."""
    rng = np.random.default_rng(5)
    H, M, E, k = 32, 16, 32, 8
    x = jnp.asarray(rng.standard_normal((24, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, M)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, M, H)) / 4, jnp.float32)
    kw = dict(top_k=k, score="softmax", norm_topk=True, scale=1.0)
    whole, sizes = sparse_moe(x, router, wg, wu, wd, **kw)
    assert int(sizes.sum()) == 24 * k
    parts, held = 0.0, 0
    for c in range(8):
        part, n = sparse_moe(x, router, wg[4 * c: 4 * c + 4],
                             wu[4 * c: 4 * c + 4], wd[4 * c: 4 * c + 4],
                             first=4 * c, **kw)
        parts, held = parts + part, held + int(n.sum())
    assert held == 24 * k
    np.testing.assert_allclose(parts, whole, atol=2e-5)


# ----------------------------------------- three pools, one table entry

def _tree(m, pool_pages=40, batch=3):
    cache = m.init_paged(batch, page=PAGE, pool_pages=pool_pages)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    return cache, pc


def _hit(cache, pc, ids, row):
    bids, match, _ = pc.lookup_tiered(ids)
    cache.map_shared(row, bids)
    pc.commit_hit(ids, match)
    cache.lengths[row] = match
    return bids, match


def test_a_hit_maps_the_indexer_pages_and_equals_a_cold_prefill(model32):
    """A document filed once; a question over it resumes on its pages
    — k, v AND ik under the same table entries, nothing re-indexed —
    and reads the logits a cold prefill of the whole prompt reads; the
    append into the shared tail page copies all three pools; freeing
    and evicting return every page."""
    m = afmoe.IndexedCompletionModel(model32.cfg, params=model32.params)
    cache, pc = _tree(m)
    doc, q1, q2 = IDS[:4 * PAGE], IDS[200:207], IDS[300:311]
    m.paged_prefill_row(cache, doc, 0)
    pc.insert(doc, cache, 0)
    cache.free_row(0)
    assert cache.free_pages + pc.evictable_count() == 40
    cold_cache = m.init_paged(1, page=PAGE, pool_pages=40)
    logits = {}
    for row, q in ((1, q1), (2, q2)):
        ids = np.concatenate([doc, q])
        bids, match = _hit(cache, pc, ids, row)
        assert match == 4 * PAGE and len(bids) == 4
        logits[row] = m.paged_append_prefill(cache, q, row)
        want = m.paged_prefill_row(cold_cache, ids, 0)
        cold_cache.free_row(0)
        np.testing.assert_allclose(logits[row], want, atol=2e-4)
    shared = cache.tables[1, :4]
    np.testing.assert_array_equal(shared, cache.tables[2, :4])
    assert (cache.refcounts[shared] == 2).all()
    # the three pools of a shared page are the document's
    for pool in cache.pools:
        assert np.asarray(pool[0][shared], np.float32).any()
    # an append into a page another reader holds copies it first: all
    # three pools of the page, the new token beside the copy
    cache.free_row(2)
    tail = int(cache.tables[1, 4])          # holds q1's 7 tokens
    before = [np.asarray(p[0], np.float32) for p in cache.pools]
    cache.refcounts[tail] += 1              # another reader of it
    cow0 = pc.stats.cow_copies
    m.paged_decode_chunk(cache, np.asarray([0, int(IDS[5])]), 1)
    new = int(cache.tables[1, 4])
    assert pc.stats.cow_copies == cow0 + 1 and new != tail
    for (name, _), pool, was in zip(cache.layout.pools, cache.pools,
                                    before):
        now = np.asarray(pool[0], np.float32)
        tok = -1 if name == "ik" else -2    # the page's token axis
        np.testing.assert_array_equal(now[tail], was[tail])
        np.testing.assert_array_equal(
            np.take(now[new], range(7), tok),
            np.take(was[tail], range(7), tok))
        # (a suffix program writes whole pages: slot 7 held a pad)
        assert (np.take(now[new], 7, tok)
                != np.take(was[tail], 7, tok)).any()
    cache._decref(tail)
    cache.free_row(1)
    assert cache.free_pages + pc.evictable_count() == 40
    assert pc.reclaim(4) and cache.free_pages == 40


def test_a_chunk_over_a_shared_document_decodes_what_private_pages_do(
        model32):
    """Three rows behind ONE 9-page document of the tree (a shared run
    of one 8-page program of the walk) and a row alone decode a chunk
    of 8 steps — the kernels interpreted — to the tokens and logits the
    same prompts decode from pages of their own; the heartbeat's
    counters say what the walk read of what the rows held."""
    doc = IDS[:9 * PAGE]
    asks = [IDS[200:207], IDS[300:311], IDS[250:252]]
    lone = IDS[150:150 + 9 * PAGE + 5]
    got = {}
    for shared in (True, False):
        m = afmoe.IndexedCompletionModel(model32.cfg, temp=0.0,
                                         params=model32.params,
                                         interpret=True)
        cache, pc = _tree(m, pool_pages=80, batch=5)
        if shared:
            m.paged_prefill_row(cache, doc, 0)
            pc.insert(doc, cache, 0)
            cache.free_row(0)
        firsts = np.full((5,), -1, np.int32)
        for row, q in zip((0, 2, 4), asks):
            if shared:
                _, match = _hit(cache, pc, np.concatenate([doc, q]), row)
                assert match == 9 * PAGE
                logits = m.paged_append_prefill(cache, q, row)
            else:
                logits = m.paged_prefill_row(
                    cache, np.concatenate([doc, q]), row)
            firsts[row] = int(np.argmax(logits))
        firsts[3] = int(np.argmax(m.paged_prefill_row(cache, lone, 3)))
        m.audit_seat(0, 2)
        pend = m.paged_decode_chunk_async(cache, firsts, 8)
        got[shared] = (pend.block(), np.asarray(pend.audit)[:, 0],
                       dict(m.attn_work))
    (toks, logits, work), (want_toks, want_logits, alone) = \
        got[True], got[False]
    np.testing.assert_array_equal(toks[[0, 2, 3, 4]],
                                  want_toks[[0, 2, 3, 4]])
    np.testing.assert_allclose(logits, want_logits, atol=2e-4)
    layers = model32.cfg.layers
    # rows of 151, 155, 146 and 149 tokens + 8 hold 10, 11, 10, 10 pages
    held = layers * 8 * (10 + 11 + 10 + 10)
    assert alone["walk_pages_held"] == alone["walk_pages_read"] == held
    assert work["walk_pages_held"] == held
    assert work["walk_pages_read"] == held - layers * 8 * 2 * 8


# ------------------------------------------------------ an admission round

ROUND = [("a", 4, 1), ("a", 4, PAGE), ("a", 4, 7), ("b", 3, 11)]


def _seated_round(base, joins, batch, **kw):
    m = afmoe.IndexedCompletionModel(base.cfg, params=base.params,
                                     temp=0.0, **kw)
    cache, pc = _tree(m, batch=batch)
    rng = np.random.default_rng(11)
    docs, rows = {}, []
    for name, pages, _ in joins:
        if name not in docs:
            docs[name] = rng.integers(3, base.cfg.vocab_size,
                                      pages * PAGE).astype(np.int32)
            m.paged_prefill_row(cache, docs[name], 0)
            pc.insert(docs[name], cache, 0)
            cache.free_row(0)
    for row, (name, pages, n) in enumerate(joins):
        suffix = rng.integers(3, base.cfg.vocab_size, n).astype(np.int32)
        _hit(cache, pc, np.concatenate([docs[name], suffix]), row)
        assert cache.ensure(row, pages * PAGE + n + 12)
        rows.append((row, suffix))
    for k in m.attn_work:
        m.attn_work[k] = 0
    return m, cache, rows


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "interpret"])
def test_a_round_in_one_program_is_its_joins_one_by_one(model32,
                                                        interpret):
    """paged_append_prefill_rows (4 joins and 2 pad rows) against the
    same joins through the one-row program: logits, the three pools of
    every row's pages, the counters; first tokens the argmax under a
    cold sampler."""
    want_m, want_c, rows = _seated_round(model32, ROUND, 6)
    want = np.stack([want_m.paged_append_prefill(want_c, s, r)
                     for r, s in rows])
    m, cache, rows = _seated_round(model32, ROUND, 6, interpret=interpret)
    assert m.join_rungs(cache) == (1, 6) and m.join_width == PAGE
    assert m.suffix_buckets == (PAGE, 5 * PAGE)
    logits, firsts = m.paged_append_prefill_rows(cache, rows)
    np.testing.assert_allclose(np.asarray(logits)[:4], want, atol=2e-3)
    np.testing.assert_array_equal(firsts,
                                  np.asarray(logits)[:4].argmax(-1))
    np.testing.assert_array_equal(cache.lengths, want_c.lengths)
    for row in range(4):
        held = len(cache._owned[row])
        for a, b in zip(cache.pools, want_c.pools):
            np.testing.assert_allclose(
                np.asarray(a[0][cache.tables[row, :held]], np.float32),
                np.asarray(b[0][want_c.tables[row, :held]], np.float32),
                atol=2e-3)
    assert m.attn_work == want_m.attn_work
    assert m.attn_work["index_keys_join"] > 0


def test_warm_up_leaves_nothing_to_compile(model32):
    """warmup_paged compiles the decode chunk, the two suffix widths,
    the rung and the page copy: a round, a wide hit's pieces and a
    chunk afterwards compile no program."""
    fresh = afmoe.IndexedCompletionModel(model32.cfg,
                                         params=model32.params)
    cache, pc = _tree(fresh, batch=4)
    fresh.warmup_paged(cache, chunk=4)
    assert not [t for t in threading.enumerate()
                if t.name == "compile-beside-warmup"]
    before = fresh.compile_count()
    assert before >= 5
    fresh.paged_append_prefill_rows(
        cache, [(r, np.ones((3,), np.int32)) for r in range(3)])
    fresh.paged_append_prefill(cache, np.ones((3 * PAGE + 2,), np.int32),
                               3)
    fresh.paged_decode_chunk(cache, np.ones((4,), np.int32), 4)
    assert fresh.compile_count() == before


# ------------------------------------------------------------- the lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-keye-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    comp = C.Completer(st, model=model, max_new_tokens=12,
                       template="none", batch_cap=3, page_size=PAGE,
                       pool_pages=40,
                       audit={"dir": audit_dir, "every": 1}, **kw)
    comp.attach()
    th = threading.Thread(target=comp.run_continuous, daemon=True,
                          kwargs={"idle_timeout_ms": 20})
    th.start()

    def ask(key: str, prompt: str):
        out = submit_completion(st, key, prompt, timeout_ms=240_000)
        assert isinstance(out, bytes) and out.startswith(prompt.encode())
        return out
    try:
        yield comp, ask, audit_dir
    finally:
        comp.stop()
        th.join(timeout=30)
        st.close()
        Store.unlink(name)


def _text(n: int, salt: int) -> str:
    return np.random.default_rng(salt).integers(
        0x61, 0x7B, n, dtype=np.uint8).tobytes().decode()


def test_questions_over_a_document_through_run_continuous(tmp_path,
                                                          model32):
    """A 4-page document asked once cold, then three questions at once
    and one alone, through the daemon's own loop: each later one
    resumes on the whole document (its indexer pages with it), the
    audited logits are the reference's for prompt + generated tokens
    (float32: a bfloat16 stack this small is chaotic under its
    selection), the heartbeat carries the indexer's counters, and
    every page comes back."""
    doc = _text(63, 1)                          # + BOS = 64 = 4 pages
    with serving(tmp_path, model32) as (comp, ask, audit_dir):
        ask("q/0", doc + _text(6, 2))
        ts = [threading.Thread(target=ask, args=(
            f"q/{i}", doc + _text(5 + i, 10 + i))) for i in (1, 2, 3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        ask("q/4", doc + _text(9, 20))
        s = comp.stats
        assert s.prefix_tokens == 4 * 64
        for _ in range(200):
            if comp.audit.written >= 3:
                break
            time.sleep(0.02)
        for i in range(3):
            rec = np.load(os.path.join(audit_dir, f"{i}.npz"))
            n = len(rec["prompt"])
            assert int(rec["n_prefix"]) == (64 if i else 0)
            want = reference_keye.forward(
                model32.cfg, model32.params,
                np.concatenate([rec["prompt"], rec["tokens"][:-1]]))
            err = BENCH.rel_err(rec["logits"], want[n - 1:])
            assert err.max() < 5 * TOL_F32, err
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert hb["index_keys_decode"] > 0 and hb["index_keys_join"] > 0
        assert 0 < hb["keys_selected"] < hb["keys_in_context"]
        assert hb["keys_selected_decode"] > 0 and hb["join_kv"] > 0
        assert "window_pool_pages" not in hb
        assert "decode_window_keys" not in hb
        pc, cache = comp.prefix_cache, comp._paged_cache
        for _ in range(250):
            if cache.free_pages + pc.evictable_count() == 40:
                break
            time.sleep(0.02)
        assert cache.free_pages + pc.evictable_count() == 40
