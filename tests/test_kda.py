"""The hybrid stack (models/kda.py): gated delta-rule layers whose
recurrent state lives in the paged cache's STATE SLOTS beside NoPE
latent pages — kernels, model, cache manager, prefix tree and the
continuous lane, on the CPU at tiny widths, against the plain float32
reference (tests/reference_kda.py).

Tolerances.  The model here is built in float32, so program and
reference differ by summation order alone: 2e-4 absolute on logits of
spread ~1 (measured 1e-6..3e-6; the chunked form re-associates a
64-token product).  The Pallas kernels in interpret mode round their
matrix operands to bfloat16 as they do on the chip: 2e-2 of the
outputs' scale (measured 5e-3)."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_kda as R
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import PrefixCache
from libsplinter_tpu.models import kda, mla
from libsplinter_tpu.models.decoder import PagedKVCache
from libsplinter_tpu.models.moe import sparse_moe
from libsplinter_tpu.ops import delta_attention as da

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = kda.HybridMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                               experts_held=4)
IDS = np.random.default_rng(0).integers(3, CFG.vocab_size, 96) \
    .astype(np.int32)
PAGE = 16

# a tiny description in Kimi-Linear's published keys (the shape of
# benchmark/configs/kimi-linear-48b-a3b-ep8-stage0.json's model)
ARCH = {"model_type": "kimi_linear", "hidden_act": "silu",
        "tie_word_embeddings": False, "hidden_size": 64, "head_dim": 72,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 16, "num_shared_experts": 1,
        "num_experts_per_token": 4, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
        "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
        "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
        "num_hidden_layers": 8, "num_nextn_predict_layers": 0,
        "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
        "model_max_length": 1048576, "vocab_size": 4096,
        "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
            "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4}}
SHARE = {"layers": 4, "dense_layers": 1, "experts": [4, 8],
         "vocab": [0, 512]}


@pytest.fixture(scope="module")
def model():
    return kda.HybridCompletionModel(CFG, seed=3, temp=0.0)


@pytest.fixture(scope="module")
def ref_logits(model):
    return R.forward(CFG, model.params, IDS)


def _tokens(T, H, d, seed, strong=False):
    r = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (unit(r.standard_normal((T, H, d))) for _ in range(2))
    v = r.standard_normal((T, H, d))
    # log decays from a thousandth to (strong) -30 a token: the latter
    # overflows exp(-G) inside a chunk unless decays are formed pairwise
    g = -np.exp(r.uniform(np.log(1e-3), np.log(30.0 if strong else 1.0),
                          (T, H, d)))
    b = r.uniform(0, 1, (T, H))
    st = 0.1 * r.standard_normal((H, d, d))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, b, st)]


# ------------------------------------------------------------- kernels

@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
@pytest.mark.parametrize("T, chunk, n_snap, strong", [
    (64, 16, 32, False),      # whole chunks, whole pages of 16
    (96, 32, 64, True),       # forget gates down to -30 a token
    (40, 16, 16, False),      # 40 real tokens in a 48-token bucket
    (5, 16, 0, False),        # shorter than a chunk, nothing to snapshot
])
def test_chunked_prefill_is_the_token_scan(interpret, T, chunk, n_snap,
                                           strong):
    q, k, v, g, b, st = _tokens(T, 2, 16, T, strong)
    want_o, want_st = da.kda_scan(q, k, v, g, b, st, scale=0.25)
    pad = (-T) % chunk

    def padded(a):                    # padding: g = 0, beta = 0, rest 0
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    o, got_st, snap = da.kda_chunk_prefill(
        *(padded(a) for a in (q, k, v, g, b)), st, scale=0.25,
        n_snap=n_snap, chunk=chunk, interpret=interpret)
    tol = 2e-2 if interpret else 2e-5
    np.testing.assert_allclose(o[:T], want_o, atol=tol)
    np.testing.assert_allclose(got_st, want_st, atol=tol)
    _, at_snap = da.kda_scan(q[:n_snap], k[:n_snap], v[:n_snap],
                             g[:n_snap], b[:n_snap], st, scale=0.25)
    np.testing.assert_allclose(snap, at_snap if n_snap else st, atol=tol)


def test_decode_step_kernel_updates_the_rows_slots_in_place():
    q, k, v, g, b, _ = _tokens(3, 8, 16, 7)
    states = jnp.asarray(np.random.default_rng(8).standard_normal(
        (6, 8, 16, 16)), jnp.float32)
    want_o, want_s = da.kda_decode_step(q, k, v, g, b, states, scale=0.25)
    got_o, got_s = da.kda_decode_step(q, k, v, g, b, states, scale=0.25,
                                      interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)
    # slots past the rows (snapshots, the spare) are not touched
    np.testing.assert_array_equal(got_s[3:], states[3:])
    # and a decode step IS one step of the scan (row 0's token)
    o1, s1 = da.kda_scan(q[:1], k[:1], v[:1], g[:1], b[:1], states[0],
                         scale=0.25)
    np.testing.assert_allclose(o1[0], want_o[0], atol=1e-5)
    np.testing.assert_allclose(s1, want_s[0], atol=1e-5)


def test_the_samplers_one_sort_is_argsort_and_gather_bit_for_bit():
    """The shared sampler keeps the sorted keys of ONE stable sort
    instead of gathering them back by an argsort's order: the same
    order (ties too), the same filtered logits, as the chain it
    replaced (`argsort(-logits)`, then `logits[order]`)."""
    from libsplinter_tpu.models.decoder import _nucleus_logits
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((6, 777)), jnp.float32)
    logits = logits.at[2, 10:20].set(logits[2, 5])        # ties
    for row in logits:
        order = jnp.argsort(-row)
        sorted_logits = row[order] / 0.7
        probs = jax.nn.softmax(sorted_logits)
        keep = (jnp.cumsum(probs) - probs) < 0.9
        got_order, got = _nucleus_logits(row, 0.9, 0.7)
        np.testing.assert_array_equal(got_order, order)
        np.testing.assert_array_equal(
            got, jnp.where(keep, sorted_logits, -jnp.inf))


# ------------------------------------------------------ model and cache

def _decode_logits(m, cache, row, token):
    toks = np.full((cache.batch,), -1, np.int32)
    toks[row] = token
    m.audit_row = row
    pend = m.paged_decode_chunk_async(cache, toks, 1)
    pend.block()
    m.audit_row = -1
    return np.asarray(pend.audit)[0]


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_pages_and_state(interpret, model,
                                                     ref_logits):
    """53 prompt tokens (not whole chunks, not whole pages; two suffix
    pieces) then 6 teacher-forced decode steps, against the
    reference's ONE full forward pass."""
    m = model if not interpret else kda.HybridCompletionModel(
        CFG, params=model.params, temp=0.0, interpret=True)
    cache = m.init_paged(2, page=PAGE, pool_pages=16, state_snapshots=1)
    assert m.suffix_buckets == (16, 32, 48, 64, 80) and m.kda_chunk == 16
    tol = 6e-2 if interpret else 2e-4
    got = m.paged_prefill_row(cache, IDS[:53], 1)
    np.testing.assert_allclose(got, ref_logits[52], atol=tol)
    for t in range(53, 59):
        np.testing.assert_allclose(_decode_logits(m, cache, 1, IDS[t]),
                                   ref_logits[t], atol=tol)
    assert cache.lengths[1] == 59 and cache.lengths[0] == 0


def test_prefill_resumed_from_a_snapshot_is_the_uninterrupted_one(
        model, ref_logits):
    """Row 0 prefills 53 tokens and leaves the state after 48 in a
    snapshot slot; row 1 maps its three full pages, restores the
    snapshot and prefills the last five: the same logits, and then the
    same decode."""
    cache = model.init_paged(2, page=PAGE, pool_pages=16,
                             state_snapshots=2)
    slot = cache.alloc_state_slot()
    assert slot == 2
    whole = model.paged_prefill_row(cache, IDS[:53], 0, snap_at=48,
                                    snap_slot=slot)
    cache.map_shared(1, [int(b) for b in cache.tables[0, :3]])
    cache.lengths[1] = 48
    model.state_restore(cache, slot, 1)
    resumed = model.paged_append_prefill(cache, IDS[48:53], 1)
    np.testing.assert_allclose(resumed, whole, atol=2e-5)
    np.testing.assert_allclose(resumed, ref_logits[52], atol=2e-4)
    np.testing.assert_allclose(_decode_logits(model, cache, 1, IDS[53]),
                               ref_logits[53], atol=2e-4)
    # restoring the state of one page EARLIER without re-running that
    # page is what benchmark/sabotage plants: it must be far off
    early = cache.alloc_state_slot()
    cache.free_row(1)
    model.paged_prefill_row(cache, IDS[:53], 0, snap_at=32,
                            snap_slot=early)
    cache.free_row(1)
    cache.map_shared(1, [int(b) for b in cache.tables[0, :3]])
    cache.lengths[1] = 48
    model.state_restore(cache, early, 1)
    wrong = model.paged_append_prefill(cache, IDS[48:53], 1)
    assert np.abs(wrong - ref_logits[52]).max() > 0.05
    with pytest.raises(ValueError, match="whole chunks"):
        model.paged_append_prefill(cache, IDS[53:60], 1, snap_at=56,
                                   snap_slot=early)


def test_state_slots_are_the_caches_to_hand_out():
    cache = PagedKVCache(CFG, 2, page=PAGE, pool_pages=8,
                         state_snapshots=3)
    # rows 0-1, snapshots 2-4, the spare 5; three KDA layers of
    # (4 x 16 x 16 f32 + 3 x 192 f32) a slot; pages in the MLA layer only
    assert (cache.state_slots, cache.state_spare) == (6, 5)
    assert len(cache.states) == 3 and len(cache.pools[0]) == 1
    assert cache.states[0][0].shape == (6, 4, 16, 16)
    assert cache.states[0][1].shape == (6, 3, 192)
    assert cache.state_slot_bytes == 3 * (4 * 16 * 16 + 3 * 192) * 4
    assert cache.kv_bytes_per_token() == 1 * 40 * 4
    assert cache.layouts[0].pools == () and cache.layouts[3].state == ()
    got = [cache.alloc_state_slot() for _ in range(3)]
    assert sorted(got) == [2, 3, 4] and cache.alloc_state_slot() is None
    assert not cache.state_slot_available()
    cache.free_state_slot(3)
    assert cache.alloc_state_slot() == 3
    for bad in (1, 5, 7):
        with pytest.raises(RuntimeError, match="state slot"):
            cache.free_state_slot(bad)
    cache.free_state_slot(2)
    with pytest.raises(RuntimeError, match="double-freed"):
        cache.free_state_slot(2)
    # a model without state has no slots and pays nothing
    plain = PagedKVCache(mla.LatentMoeConfig.tiny(), 2, page=PAGE,
                         pool_pages=8)
    assert plain.state_slots == 0 and plain.states == [] \
        and not plain.needs_state and plain.state_slot_bytes == 0


def _tree(snapshots=3, pool_pages=24):
    cache = PagedKVCache(CFG, 2, page=PAGE, pool_pages=pool_pages,
                         state_snapshots=snapshots)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    return cache, pc


def _insert(cache, pc, ids, snap=True):
    """What an admission does, without the device: row 0 takes pages
    for `ids`, they join the tree with a snapshot at the last full
    page, the row finishes."""
    cache.ensure(0, len(ids))
    at = len(ids) // PAGE * PAGE
    slot = cache.alloc_state_slot() if snap else None
    pc.insert(ids, cache, 0, state=(slot, at) if slot is not None
              else None)
    cache.free_row(0)
    return slot


def test_lookup_ends_at_a_snapshot_and_eviction_frees_the_slot():
    cache, pc = _tree(snapshots=2)
    a = list(range(100, 100 + 3 * PAGE))                 # 3 pages
    s_a = _insert(cache, pc, a)
    assert pc.lookup(a) == (pc.lookup(a)[0], 48) and pc.last_cut == 0
    assert pc.state_slot(a, 48) == s_a
    # one page deeper, no snapshot there: a lookup over all four pages
    # is cut back to the three the snapshot covers
    b = a + list(range(500, 500 + PAGE))
    _insert(cache, pc, b, snap=False)
    bids, match, tier = pc.lookup_tiered(b)
    assert (len(bids), match, tier, pc.last_cut) == (3, 48, [], 16)
    assert pc.state_slot(b, 64) == -1
    # a path with no snapshot on it at all gives up everything
    c = list(range(900, 900 + 2 * PAGE))
    _insert(cache, pc, c, snap=False)
    assert pc.lookup(c) == ([], 0) and pc.last_cut == 32
    # evicting the node frees its slot with its page
    assert cache.state_snapshots - len(cache._free_state) == 1
    assert pc.reclaim(cache.n_blocks) == 6
    assert pc.snapshots_held() == 0 and len(cache._free_state) == 2
    assert pc.stats.state_evictions == 1
    assert len(cache._free) == cache.n_blocks - 1


def test_snapshot_policy_superseded_chain_first_then_least_recent():
    cache, pc = _tree(snapshots=4)
    doc = list(range(100, 100 + 2 * PAGE))
    s_doc = _insert(cache, pc, doc)                       # a shared document
    turn1 = list(range(300, 300 + 2 * PAGE))
    s_t1 = _insert(cache, pc, turn1)                      # a session, turn 1
    turn2 = turn1 + list(range(400, 400 + 2 * PAGE))
    s_t2 = _insert(cache, pc, turn2)                      # ... turn 2
    # a first question of the document resumes from its snapshot and
    # leaves one a page deeper: four held, none free
    assert pc.state_slot(doc, 32) == s_doc
    q1 = doc + list(range(700, 700 + PAGE))
    _insert(cache, pc, q1)
    assert pc.snapshots_held() == 4 and not cache._free_state
    # the session's turn-1 snapshot and the document's each head a
    # chain without a branch that reaches a deeper snapshot: both are
    # superseded, and the one restored longer ago goes — the session's
    assert pc.state_slot(doc, 32) == s_doc
    q2 = doc + list(range(800, 800 + PAGE))
    assert _insert(cache, pc, q2) == s_t1
    assert pc.stats.state_evictions == 1
    assert pc.lookup(turn1) == ([], 0) and pc.lookup(turn2)[1] == 64
    # now two questions BRANCH below the document: it is no chain's
    # head any more, nothing is superseded, and the least recently
    # restored of all gives way — the session's turn 2, never the
    # document every question resumes from
    assert pc.state_slot(doc, 32) == s_doc
    q3 = doc + list(range(900, 900 + PAGE))
    assert _insert(cache, pc, q3) == s_t2
    assert pc.lookup(turn2) == ([], 0) and pc.last_cut == 64
    assert [pc.lookup(p)[1] for p in (doc, q1, q2, q3)] == [32, 48, 48, 48]
    assert pc.snapshots_held() == 4 and pc.stats.state_evictions == 2


# ------------------------------------------------------- the expert share

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Under THIS router's settings — sigmoid scores over all 16
    experts, plain top-4, renormalised over the selection, x 2.446 —
    the 8 shares of an expert layer, the shared expert counted once,
    add up to the layer with every expert held."""
    rng = np.random.default_rng(5)
    H, M, E, k = 32, 16, 16, 4
    x = jnp.asarray(rng.standard_normal((24, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, M)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, M, H)) / 4, jnp.float32)
    shared = tuple(jnp.asarray(rng.standard_normal(s) / 5, jnp.float32)
                   for s in ((H, M), (H, M), (M, H)))
    kw = dict(top_k=k, score="sigmoid", norm_topk=True, scale=2.446)
    whole, sizes = sparse_moe(x, router, wg, wu, wd, shared=shared, **kw)
    assert int(sizes.sum()) == 24 * k
    parts, held = 0.0, 0
    for c in range(8):
        part, n = sparse_moe(x, router, wg[2 * c: 2 * c + 2],
                             wu[2 * c: 2 * c + 2], wd[2 * c: 2 * c + 2],
                             first=2 * c, **kw)
        parts, held = parts + part, held + int(n.sum())
    only_shared = (jax.nn.silu(x @ shared[0]) * (x @ shared[1])) \
        @ shared[2]
    assert held == 24 * k
    np.testing.assert_allclose(parts + only_shared, whole, atol=2e-5)


# ---------------------------------------------------------- descriptions

def _describe(tmp_path, arch=ARCH, share=SHARE, **extra):
    path = tmp_path / "model.json"
    path.write_text(json.dumps({"architecture": arch, "share": share,
                                "seed": 11, **extra}))
    return str(path)


def test_description_loader_picks_the_family_by_model_type(tmp_path):
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=256)
    assert isinstance(cfg, kda.HybridMoeConfig) and seed == 11
    assert cfg.kinds == ("kda", "kda", "kda", "mla")
    assert (cfg.hidden, cfg.heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (64, 4, 32, 16, 8, 16)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel) == (4, 16, 4)
    assert (cfg.dense_mlp_dim, cfg.moe_mlp_dim, cfg.n_routed_experts,
            cfg.top_k, cfg.n_shared_experts) == (128, 32, 16, 4, 1)
    assert (cfg.layers, cfg.dense_layers, cfg.experts_first,
            cfg.experts_held, cfg.vocab_first, cfg.vocab_size) \
        == (4, 1, 4, 8, 0, 512)
    assert cfg.routed_scaling_factor == 2.446 and cfg.norm_topk_prob
    assert cfg.score_fn == "sigmoid" and cfg.max_len == 256
    assert mla.completion_model_class(cfg) is kda.HybridCompletionModel
    whole, _ = mla.load_model_description(
        _describe(tmp_path, share={}), max_len=64)
    assert whole.kinds == ("kda",) * 3 + ("mla",) + ("kda",) * 3 + ("mla",)
    assert (whole.dense_layers, whole.experts_held, whole.vocab_size) \
        == (1, 16, 4096)


def test_pangu_description_loads_to_the_identical_config(tmp_path):
    """The table per model_type replaced a chain of need(...) calls:
    the DeepSeek-V3 key set still fills LatentMoeConfig field for
    field."""
    import test_mla
    cfg, seed = mla.load_model_description(
        _describe(tmp_path, test_mla.ARCH, test_mla.SHARE), max_len=256)
    assert cfg == mla.LatentMoeConfig(
        vocab_size=512, vocab_first=0, hidden=64, layers=3, heads=4,
        q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, dense_layers=1,
        dense_mlp_dim=128, moe_mlp_dim=32, n_routed_experts=16, top_k=4,
        experts_first=4, experts_held=8, n_shared_experts=1,
        norm_topk_prob=True, routed_scaling_factor=2.5,
        score_fn="sigmoid", sandwich_norm=True, rope_base=25.6e6,
        rms_eps=1e-5, max_len=256)
    assert mla.completion_model_class(cfg) is mla.LatentCompletionModel


LIN = ARCH["linear_attn_config"]


@pytest.mark.parametrize("bad, match", [
    ({**ARCH, "model_type": "llama"}, "model_type 'llama'"),
    ({k: v for k, v in ARCH.items() if k != "model_type"}, "model_type"),
    ({**ARCH, "n_routed_experts": 16}, "n_routed_experts"),
    ({**ARCH, "q_lora_rank": 1536}, "q_lora_rank must be null"),
    ({**ARCH, "mla_use_nope": False}, "mla_use_nope"),
    ({**ARCH, "num_expert_group": 8}, "group-limited"),
    ({**ARCH, "moe_layer_freq": 2}, "moe_layer_freq"),
    ({**ARCH, "num_key_value_heads": 2}, "kv heads"),
    ({**ARCH, "linear_attn_config": {**LIN, "gate_rank": 8}}, "gate_rank"),
    ({**ARCH, "linear_attn_config": {**LIN, "kda_layers": [1, 2, 3]}},
     "split layers"),
    ({k: v for k, v in ARCH.items() if k != "linear_attn_config"},
     "linear_attn_config"),
])
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(_describe(tmp_path, bad))


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
    (["--quantized"], "--quantized"),
])
def test_main_refuses_what_the_hybrid_model_cannot_serve(
        tmp_path, flags, match):
    with pytest.raises(SystemExit) as ex:
        C.main(["--store", "/spt-never-opened", "--continuous",
                "--model", _describe(tmp_path), *flags])
    assert "unsupported_option" in str(ex.value)
    assert "HybridCompletionModel" in str(ex.value)
    assert match in str(ex.value)


def test_state_snapshots_is_for_a_model_with_state(tmp_path):
    import test_mla
    pangu = _describe(tmp_path, test_mla.ARCH, test_mla.SHARE)
    for argv in (["--model", pangu, "--continuous"], []):
        with pytest.raises(SystemExit, match="--state-snapshots"):
            C.main(["--store", "/spt-never-opened", *argv,
                    "--state-snapshots", "4"])


# ------------------------------------------------- the continuous lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-kda-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    comp = C.Completer(st, model=model, max_new_tokens=4, template="none",
                       batch_cap=2, page_size=PAGE, pool_pages=32,
                       audit={"dir": audit_dir, "every": 1}, **kw)
    comp.attach()
    th = threading.Thread(target=comp.run_continuous, daemon=True,
                          kwargs={"idle_timeout_ms": 20})
    th.start()

    def ask(i: int, prompt: str):
        """-> (prompt ids, generated ids, the logits behind each)."""
        out = submit_completion(st, f"q/{i}", prompt, timeout_ms=240_000)
        assert isinstance(out, bytes) and out.startswith(prompt.encode())
        for _ in range(200):
            if comp.audit.written > i:
                break
            time.sleep(0.02)
        rec = np.load(os.path.join(audit_dir, f"{i}.npz"))
        return rec["prompt"], rec["tokens"], rec["logits"]
    try:
        yield comp, ask
    finally:
        comp.stop()
        th.join(timeout=30)
        st.close()
        Store.unlink(name)


def _text(n: int, salt: int) -> str:
    return np.random.default_rng(salt).integers(
        0x61, 0x7B, n, dtype=np.uint8).tobytes().decode()


def _against_reference(model, prompt, toks, logits, tol=2e-4):
    full = R.forward(model.cfg, model.params,
                     np.concatenate([prompt, toks[:-1]]))
    for i, got in enumerate(logits):
        np.testing.assert_allclose(got, full[len(prompt) - 1 + i],
                                   atol=tol)


def test_a_session_resumes_from_its_snapshots_through_run_continuous(
        tmp_path, model):
    """Turns of one growing session through the daemon's own loop: each
    turn maps the turn before's pages, restores its snapshot, prefills
    the new tokens and leaves a deeper snapshot; every logit is the
    reference's for the whole prompt served cold."""
    base = _text(40, 1)                       # + BOS = 41 tokens
    turns = [base, base + _text(30, 2), base + _text(30, 2) + _text(25, 3)]
    with serving(tmp_path, model, state_snapshots=2) as (comp, ask):
        for i, t in enumerate(turns):
            _against_reference(model, *ask(i, t))
        s = comp.stats
        # turn 1: cold (snapshot at 32).  turn 2 (71 tokens): resumes at
        # 32, leaves one at 64.  turn 3 (96): resumes at 64, leaves 96?
        # no: 96 // 16 * 16 = 96 > 95 = the lookup's reach, so it leaves
        # one at 96 for a turn 4 to find
        assert (s.state_restores, s.state_snapshots) == (2, 3)
        assert s.prefix_tokens == 32 + 64 and s.state_cut_tokens == 0
        pc = comp.prefix_cache
        # two slots, three snapshots: the superseded one at 32 gave way
        assert pc.stats.state_evictions == 1 and pc.snapshots_held() == 2
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert hb["state_restores"] == 2 and hb["state_snapshots"] == 3
        assert hb["state_evictions"] == 1 and hb["state_cut_tokens"] == 0
        assert hb["state_slots_used"] == 2 and hb["state_slots"] == 4
        assert hb["latent_decode_pages_per_step"] == 8   # latent layers
        assert {"paged_chunk", "suffix_prefill", "state_copy",
                "state_zero"} <= set(hb["devtime"])


def test_a_hit_deeper_than_the_last_snapshot_is_cut_back(tmp_path, model):
    """ONE snapshot slot.  The session's second turn takes it from the
    first; a prompt that then shares three pages with the session but
    none that ends at a snapshot gives the pages up (state_cut_tokens),
    prefills cold, reuses the evicted slot — and is still right."""
    base = _text(40, 4)
    grown = base + _text(30, 5)
    fork = grown[:50] + _text(20, 6)          # diverges inside page 4
    with serving(tmp_path, model, state_snapshots=1) as (comp, ask):
        ask(0, base)
        slot = next(iter(comp.prefix_cache._snapshots))
        ask(1, grown)
        assert comp.stats.state_restores == 1
        assert comp.prefix_cache.stats.state_evictions == 1
        assert next(iter(comp.prefix_cache._snapshots)) == slot
        _against_reference(model, *ask(2, fork))
        s = comp.stats
        assert s.state_cut_tokens == 48 and s.state_restores == 1
        assert s.prefix_tokens == 32          # turn 2's hit and no other
        assert comp.prefix_cache.stats.state_evictions == 2
        assert list(comp.prefix_cache._snapshots) == [slot]


def test_a_fully_cached_prompt_does_not_apply_its_last_token_twice(
        tmp_path, model):
    """A prompt of exactly three pages, asked twice: the second time
    every page is cached with a snapshot at its end — and the lane
    resumes from the snapshot BELOW its last token, never replaying
    the token into a state that already holds it."""
    first = _text(31, 7)                      # 32 tokens: 2 pages
    whole = first + _text(16, 8)              # 48 tokens: 3 pages
    with serving(tmp_path, model, state_snapshots=4) as (comp, ask):
        ask(0, first)
        p1, t1, l1 = ask(1, whole)
        assert len(p1) == 48 and comp.stats.prefix_tokens == 32
        p2, t2, l2 = ask(2, whole)
        # the hit is cut to the snapshot at 32 (strictly below token
        # 47), the last page prefills again
        assert comp.stats.prefix_tokens == 64
        assert comp.stats.state_restores == 2
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_allclose(l2, l1, atol=2e-5)
        _against_reference(model, p2, t2, l2)


def test_a_model_without_state_still_maps_its_full_hit(tmp_path):
    """pangu's family through the same admit: a fully cached prompt
    maps every page and replays its last token, as before."""
    m = mla.LatentCompletionModel(
        mla.LatentMoeConfig.tiny(dtype=jnp.float32), seed=2, temp=0.0)
    text = _text(47, 9)                       # 48 tokens: 3 pages
    with serving(tmp_path, m) as (comp, ask):
        _, t1, _ = ask(0, text)
        assert comp.stats.prefix_tokens == 0
        comp.stats.prompt_tokens = 0
        out = submit_completion(comp.store, "q/again", text,
                                timeout_ms=240_000)
        assert out.startswith(text.encode())
        assert (comp.stats.prompt_tokens, comp.stats.prefix_tokens) \
            == (48, 48)
        assert comp.stats.state_restores == 0
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert "state_restores" not in hb and "state_slots_used" not in hb


# ------------------------------------------------ the benchmark's copy

def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_hybrid", os.path.join(
            REPO, "benchmark", "reference", "hybrid_kda_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_two_copies_of_the_reference_agree(tmp_path):
    """benchmark/reference/hybrid_kda_block.py (its own weights from
    the seed, long prompts in blocks) == tests/reference_kda.py on the
    program's tree; its float8 control does not."""
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=128)
    params = kda.init_params(cfg, seed)
    seqs = [IDS[:70] % 512, IDS[5:33] % 512]
    pos = [[20, 69], [0, 27]]
    bench = _bench_reference()
    got = bench.forward_logits(ARCH, SHARE, seed, seqs, pos, block=32)
    for s, p, g in zip(seqs, pos, got):
        np.testing.assert_allclose(g, R.forward(cfg, params, s)[p],
                                   atol=1e-4)
    low = bench.forward_logits(ARCH, SHARE, seed, seqs[:1], pos[:1],
                               f8=True, block=32)
    assert bench.rel_err(low[0], got[0]).min() > 0.02
