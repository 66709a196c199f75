"""The convolution / attention stack (models/lfm2.py): gated
short-convolution layers whose two-token register lives in the paged
cache's STATE SLOTS beside ONE group of grouped-query key/value pages,
and an expert layer whose router carries a selection bias — model,
cache, prefix tree and the continuous lane, on the CPU at tiny widths,
against the plain float32 reference (tests/reference_lfm2.py).

Tolerances.  The model here is built in float32, so program and
reference differ by summation order alone: 2e-4 absolute on logits of
spread ~1 (measured 1e-6..2e-6).  The Pallas kernels in interpret mode
round their matrix operands to bfloat16 as they do on the chip: 6e-2."""
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

import reference_lfm2 as R
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import Join, PrefixCache
from libsplinter_tpu.models import lfm2, mla
from libsplinter_tpu.models.decoder import PagedKVCache
from libsplinter_tpu.models.moe import (router_bias_swaps, router_gates,
                                        sparse_moe)
from libsplinter_tpu.ops.paged_attention import (kv_append,
                                                 window_paged_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = lfm2.ConvMoeConfig.tiny(dtype=jnp.float32)
IDS = np.random.default_rng(0).integers(3, CFG.vocab_size, 120) \
    .astype(np.int32)
PAGE = 16

# a tiny description in LFM2-24B-A2B's published keys (the shape of
# benchmark/configs/lfm2-24b-a2b-ep1-stage0.json's model)
ARCH = {"model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
        "hidden_size": 64, "intermediate_size": 128,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "conv", "conv",
                        "full_attention", "conv"],
        "max_position_embeddings": 128000, "moe_intermediate_size": 32,
        "norm_eps": 1e-5, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_dense_layers": 2,
        "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": 12, "num_key_value_heads": 2,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 512}
SHARE = {"layers": 7, "dense_layers": 1, "experts": [0, 8],
         "vocab": [0, 512]}


@pytest.fixture(scope="module")
def model():
    return lfm2.ConvCompletionModel(CFG, seed=3, temp=0.0)


@pytest.fixture(scope="module")
def ref(model):
    taps = []
    return R.forward(CFG, model.params, IDS, taps), taps


def _decode_logits(m, cache, row, token):
    toks = np.full((cache.batch,), -1, np.int32)
    toks[row] = token
    m.audit_seat(0, row)
    pend = m.paged_decode_chunk_async(cache, toks, 1)
    pend.block()
    m.audit_seat(0, -1)
    return np.asarray(pend.audit)[0, 0], pend


# ------------------------------------------------------------ the conv Op

def test_conv_suffix_and_decode_step_are_the_token_loop(model):
    """The suffix program's K-tap product behind a register, and the
    decode step's shifted register, against the token-by-token
    reference — from the zero register and from one mid-sequence."""
    lp = jax.tree_util.tree_map(np.asarray, model.params["layers"][0])
    x = np.random.default_rng(1).standard_normal((21, CFG.hidden)) \
        .astype(np.float32)
    want, vs = R.conv_tokens(CFG, lp, x)
    got, full = lfm2.conv_suffix(CFG, lp, jnp.asarray(x),
                                 jnp.zeros((2, CFG.hidden)))
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(full[2:], vs, atol=1e-5)
    # resumed at token 9 from the register the first 9 tokens left
    reg = full[9: 11]
    np.testing.assert_allclose(reg, vs[7: 9], atol=1e-5)
    got2, _ = lfm2.conv_suffix(CFG, lp, jnp.asarray(x[9:]), reg)
    np.testing.assert_allclose(got2, want[9:], atol=2e-5)
    # the decode step, two rows at different places of the sequence
    step, new = lfm2.conv_step(
        CFG, lp, jnp.asarray(x[[9, 15]]),
        jnp.stack([vs[7: 9], vs[13: 15]]))
    np.testing.assert_allclose(step, want[[9, 15]], atol=2e-5)
    np.testing.assert_allclose(new, np.stack([vs[8: 10], vs[14: 16]]),
                               atol=1e-5)


# ------------------------------------------------------ model and cache

@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_pages_and_state(interpret, model,
                                                     ref):
    """53 prompt tokens (not whole pages; one suffix piece of four
    pages) then 6 teacher-forced decode steps, against the reference's
    ONE full forward pass; then a prompt that loops the widest width."""
    logits = ref[0]
    m = model if not interpret else lfm2.ConvCompletionModel(
        CFG, params=model.params, temp=0.0, interpret=True)
    cache = m.init_paged(2, page=PAGE, pool_pages=16, state_snapshots=1)
    assert m.suffix_buckets == (16, 32, 48, 64)
    tol = 6e-2 if interpret else 2e-4
    got = m.paged_prefill_row(cache, IDS[:53], 1)
    np.testing.assert_allclose(got, logits[52], atol=tol)
    for t in range(53, 59):
        got, pend = _decode_logits(m, cache, 1, IDS[t])
        np.testing.assert_allclose(got, logits[t], atol=tol)
    assert cache.lengths[1] == 59 and cache.lengths[0] == 0
    # 6 expert layers x 2 slots of the one live row, each to another
    # expert; the bias's swaps are counted in the same fetch
    assert int(np.asarray(pend.slots).sum()) == 12
    live, swaps = (int(c) for c in np.asarray(pend.counts))
    assert live == 12 and 0 <= swaps <= 12
    if not interpret:
        cache.free_row(1)
        got = m.paged_prefill_row(cache, IDS[:100], 0)   # 64 + 36 tokens
        np.testing.assert_allclose(got, logits[99], atol=tol)
        assert m.attn_work["prefill_kv"] >= 64 + 100


def test_cache_keeps_registers_beside_one_page_group():
    cache = PagedKVCache(CFG, 2, page=PAGE, pool_pages=16,
                         state_snapshots=3)
    # rows 0-1, snapshots 2-4, the spare 5; five convolution layers of
    # (2, 64) float32 a slot; K and V of the two attention layers in
    # one page, both a token a column
    assert (cache.state_slots, cache.state_spare) == (6, 5)
    assert len(cache.states) == 5 and cache.window is None
    assert cache.states[0][0].shape == (6, 2, 64)
    assert cache.state_slot_bytes == 5 * 2 * 64 * 4
    assert [p[0].shape for p in cache.pools] == [(17, 2, 2, 16, PAGE)] * 2
    assert cache.kv_bytes_per_token() == 2 * 2 * 2 * 16 * 4
    assert cache.paged_layers == 1 and cache.layout.layers == 2


def test_a_hit_at_a_tenant_and_at_a_session_boundary_is_a_cold_prefill(
        model, ref):
    """Row 0 prefills the tenant's 32-token system prompt ALONE and
    leaves the snapshot at its end; row 1 (a session's first turn) maps
    its pages, restores it and prefills 21 more, leaving a snapshot at
    48; row 0 (the next turn) resumes THERE.  Every logit is the cold
    prefill's, and each snapshot is the reference's v of the two tokens
    before its boundary, layer by layer."""
    logits, taps = ref
    cache = model.init_paged(2, page=PAGE, pool_pages=16,
                             state_snapshots=2)
    s_sys, s_turn = cache.alloc_state_slot(), cache.alloc_state_slot()
    model.paged_prefill_row(cache, IDS[:32], 0, snap_at=32,
                            snap_slot=s_sys)
    for (reg,), vs in zip(cache.states, taps):
        np.testing.assert_allclose(reg[s_sys], vs[30: 32], atol=2e-5)
    cache.map_shared(1, [int(b) for b in cache.tables[0, :2]])
    cache.lengths[1] = 32
    model.state_restore(cache, s_sys, 1)
    got = model.paged_append_prefill(cache, IDS[32:53], 1, snap_at=48,
                                     snap_slot=s_turn)
    np.testing.assert_allclose(got, logits[52], atol=2e-4)
    for (reg,), vs in zip(cache.states, taps):
        np.testing.assert_allclose(reg[s_turn], vs[46: 48], atol=2e-5)
        np.testing.assert_allclose(reg[1], vs[51: 53], atol=2e-5)
    cache.free_row(0)
    cache.map_shared(0, [int(b) for b in cache.tables[1, :3]])
    cache.lengths[0] = 48
    model.state_restore(cache, s_turn, 0)
    # a SHORT tool result: three tokens behind the restored register
    got = model.paged_append_prefill(cache, IDS[48:51], 0)
    np.testing.assert_allclose(got, logits[50], atol=2e-4)
    got, _ = _decode_logits(model, cache, 0, IDS[51])
    np.testing.assert_allclose(got, logits[51], atol=2e-4)
    # the register ZEROED at the restore instead (what benchmark/
    # sabotage plants) is far off three tokens on
    cache.free_row(0)
    cache.map_shared(0, [int(b) for b in cache.tables[1, :3]])
    cache.lengths[0] = 48
    model.state_zero(cache, 0)
    wrong = model.paged_append_prefill(cache, IDS[48:51], 0)
    assert np.abs(wrong - logits[50]).max() > 0.02
    with pytest.raises(ValueError, match="page boundary"):
        model.paged_append_prefill(cache, IDS[51:60], 0)


# ------------------------------------------------------- the expert layer

def _layer(seed=5, T=24, H=32, M=16, E=64, k=4):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)) / np.sqrt(H),
                         jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, M)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, M, H)) / 4, jnp.float32)
    return x, router, wg, wu, wd, k


def test_the_selection_bias_moves_selections_and_never_gates():
    x, router, *_ = _layer(T=4000, H=64)
    kw = dict(top_k=4, score="sigmoid", norm_topk=True, scale=1.0)
    bias = (np.random.default_rng(2).random(64) - 0.5) * np.sqrt(12) \
        * lfm2.BIAS_STD
    ids0, g0 = router_gates(x, router, **kw)
    ids1, g1 = router_gates(x, router, bias=jnp.asarray(bias, jnp.float32),
                            **kw)
    moved = np.mean([len(set(a) - set(b)) for a, b in
                     zip(np.asarray(ids1), np.asarray(ids0))]) / 4
    # the seeded scale moves 5-20% of the selections
    assert 0.05 < moved < 0.20
    live = jnp.ones((4000,), bool)
    assert int(router_bias_swaps(x, router, jnp.asarray(bias, jnp.float32),
                                 live, top_k=4, score="sigmoid")) \
        == round(moved * 16000)
    assert int(router_bias_swaps(x, router, jnp.asarray(bias, jnp.float32),
                                 ~live, top_k=4, score="sigmoid")) == 0
    # the gates are the scores' own, over their sum: a bias a hundred
    # times larger picks other experts and still weighs them by score
    scores = jax.nn.sigmoid(x @ router)
    ids2, g2 = router_gates(x, router, bias=jnp.asarray(100 * bias,
                                                        jnp.float32), **kw)
    for ids, g in ((ids1, g1), (ids2, g2)):
        s = jnp.take_along_axis(scores, ids, 1)
        np.testing.assert_allclose(g, s / s.sum(-1, keepdims=True),
                                   atol=1e-6)
    assert not np.array_equal(np.asarray(ids2), np.asarray(ids0))


def test_router_gates_without_a_bias_lowers_to_the_parents_graph():
    """`bias=None` adds nothing: the lowered text is the text of the
    function as it stood before the argument existed."""
    def parent(x, router, *, top_k, score, norm_topk, scale):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        topv, topi = jax.lax.top_k(scores, top_k)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-20)
        return topi.astype(jnp.int32), topv * scale
    x, router, *_ = _layer()
    kw = dict(top_k=4, score="sigmoid", norm_topk=True, scale=2.5)

    def text(fn, **more):
        return jax.jit(lambda a, b: fn(a, b, **kw, **more)) \
            .lower(x, router).as_text()
    assert text(router_gates) == text(router_gates, bias=None) \
        == text(parent)


def test_two_half_shares_add_up_to_the_whole_layer():
    """All 64 experts of a layer on the chip (EP1) == the shares [0,
    32) and [32, 64) of an EP2 deployment added up, under THIS router:
    sigmoid scores, top-4 of scores + bias, renormalised, no shared
    expert."""
    x, router, wg, wu, wd, k = _layer()
    bias = jnp.asarray(np.random.default_rng(3).standard_normal(64) * 0.05,
                       jnp.float32)
    kw = dict(top_k=k, score="sigmoid", norm_topk=True, scale=1.0,
              bias=bias)
    whole, sizes = sparse_moe(x, router, wg, wu, wd, **kw)
    assert int(sizes.sum()) == 24 * k and int((sizes > 0).sum()) > 32
    parts = 0.0
    for lo in (0, 32):
        part, n = sparse_moe(x, router, wg[lo: lo + 32], wu[lo: lo + 32],
                             wd[lo: lo + 32], first=lo, **kw)
        parts = parts + part
        np.testing.assert_array_equal(n, sizes[lo: lo + 32])
    np.testing.assert_allclose(parts, whole, atol=2e-5)


# ------------------------------------------------------ 64-wide heads

@pytest.mark.parametrize("chunk", [16, 100, 512])
def test_live_tokens_first_in_chunks_is_the_same_sum(chunk):
    """sparse_moe(live_chunk=): 300 token slots, a third of them live,
    brought to the front and sent through in chunks of `chunk` (19, 3
    and 1 of them; the chunks behind the live tokens skipped) — each
    token's sum and each expert's count are what one dispatch over all
    300 gives, to the bit, and a dead token's row is zero."""
    x, router, wg, wu, wd, k = _layer(T=300)
    rng = np.random.default_rng(2)
    live = jnp.asarray(rng.random(300) < 0.33)
    bias = jnp.asarray(rng.standard_normal(64) * 0.1, jnp.float32)
    kw = dict(top_k=k, score="sigmoid", bias=bias, live=live)
    want, slots = sparse_moe(x, router, wg, wu, wd, **kw)
    got, got_slots = sparse_moe(x, router, wg, wu, wd, live_chunk=chunk,
                                **kw)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_slots, slots)
    assert int(slots.sum()) == 4 * int(live.sum())
    assert not np.asarray(got)[~np.asarray(live)].any()


@pytest.mark.parametrize("q_tokens", [1, 128], ids=["decode", "stack"])
def test_heads_of_64_through_the_window_kernel_in_interpret_mode(q_tokens):
    """32 query heads over 8 key/value heads of 64, K and V both a
    token a column: the Pallas kernel in interpret mode == the jnp
    path over the same pools, whose rows were written by kv_append."""
    rng = np.random.default_rng(7)
    B, H, KH, D, page, P = 2, 32, 8, 64, 128, 3
    lengths = np.array([200, 131], np.int32)
    pools = [jnp.asarray(rng.standard_normal((1 + B * P, 2, KH, D, page)),
                         jnp.bfloat16) for _ in range(2)]
    new = jnp.asarray(rng.standard_normal((B, KH, D)), jnp.bfloat16)
    tables = jnp.asarray(1 + np.arange(B * P).reshape(B, P), jnp.int32)
    bids = tables[jnp.arange(B), (lengths - 1) // page]
    for interp in (False, True):
        got = kv_append(pools[0], new, bids, (lengths - 1) % page, layer=1,
                        cols=True, interpret=interp)
        np.testing.assert_array_equal(
            got[bids[0], 1, :, :, (200 - 1) % page], new[0])
    q = jnp.asarray(rng.standard_normal((B, q_tokens, H, D)), jnp.bfloat16)
    att = lengths if q_tokens == 1 else lengths - q_tokens + 1
    kw = dict(layer=1, k_cols=True, v_cols=True)
    want = window_paged_attention(q, *pools, tables, att, **kw)
    got = window_paged_attention(q, *pools, tables, att, interpret=True,
                                 **kw)
    assert got.shape == (B, q_tokens, H, D)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


# ---------------------------------------------------------- descriptions

def _describe(tmp_path, arch=ARCH, share=SHARE, **extra):
    path = tmp_path / "model.json"
    path.write_text(json.dumps({"architecture": arch, "share": share,
                                "seed": 11, **extra}))
    return str(path)


def test_description_loader_fills_the_conv_config(tmp_path):
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=256)
    assert isinstance(cfg, lfm2.ConvMoeConfig) and seed == 11
    # layers 1-7 of the model: ONE of its two leading dense layers,
    # then a period and a half
    assert cfg.kinds == ("conv", "full", "conv", "conv", "conv", "full",
                         "conv")
    assert cfg == lfm2.ConvMoeConfig.tiny(
        n_routed_experts=8, model_layers=12, max_len=256)
    assert (cfg.head_dim, cfg.rope_base, cfg.expert_bias,
            cfg.n_shared_experts) == (16, 1e6, True, 0)
    assert mla.completion_model_class(cfg) is lfm2.ConvCompletionModel
    whole, _ = mla.load_model_description(
        _describe(tmp_path, share={}), max_len=64)
    assert whole.kinds[:3] == ("conv", "conv", "full") \
        and (whole.layers, whole.dense_layers) == (12, 2)


@pytest.mark.parametrize("bad, match", [
    ({**ARCH, "conv_bias": True}, "conv_bias"),
    ({**ARCH, "layer_types": ARCH["layer_types"][:5]}, "layer_types"),
    ({**ARCH, "layer_types": ["sliding_attention"] * 12}, "layer_types"),
    ({**ARCH, "rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     "rope_parameters"),
    ({**ARCH, "head_dim": 16}, "head_dim"),
    ({k: v for k, v in ARCH.items() if k != "conv_L_cache"},
     "conv_L_cache"),
])
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(_describe(tmp_path, bad))


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--kv-tier-pages", "4"], "--kv-tier-pages"),
    (["--phase", "prefill"], "--phase prefill"),
    (["--tp", "2"], "--tp 2"),
    (["--ep", "2"], "--ep 2"),
    (["--draft-layers", "2"], "--draft-layers"),
    (["--weights", "x.gguf"], "--weights x.gguf"),
    (["--quantized"], "--quantized"),
    (["--window-pool-pages", "8"], "--window-pool-pages"),
])
def test_main_refuses_what_the_conv_model_cannot_serve(tmp_path, flags,
                                                       match):
    """The typed refusals of the other --model families, message for
    message (state: kimi's; page groups: trinity's)."""
    from libsplinter_tpu.models import afmoe, kda
    with pytest.raises(SystemExit) as ex:
        C.main(["--store", "/spt-never-opened", "--continuous",
                "--model", _describe(tmp_path), *flags])
    assert "unsupported_option" in str(ex.value)
    assert "ConvCompletionModel" in str(ex.value)
    assert match in str(ex.value)
    mine = lfm2.ConvCompletionModel.refused_options
    assert mine["kv_tier_pages"] \
        == kda.HybridCompletionModel.refused_options["kv_tier_pages"]
    assert mine["phase"] == kda.HybridCompletionModel.refused_options["phase"]
    assert mine["kv_dtype"] \
        == afmoe.WindowCompletionModel.refused_options["kv_dtype"]
    assert mine["tp"] == afmoe.WindowCompletionModel.refused_options["tp"]


# ------------------------------------------------- the continuous lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-lfm2-{tmp_path.name}"
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


def test_tenant_prompt_then_sessions_then_turns_through_run_continuous(
        tmp_path, model):
    """The cell's three levels through the daemon's own loop: a tenant's
    system prompt alone (32 tokens: its snapshot at its end), two
    sessions under it, a turn that ends on a page boundary and the
    short tool result behind it.  Every logit is the reference's for
    the whole prompt served cold; both audit lanes fill."""
    system = _text(31, 1)                     # + BOS = 32 tokens: 2 pages
    a0 = system + _text(20, 2)                # session a, turn 0: 52
    b0 = system + _text(25, 3)                # session b, turn 0: 57
    a1 = a0 + _text(12, 4)                    # 64 tokens: whole pages
    a2 = a1 + _text(3, 5)                     # the short tool result
    with serving(tmp_path, model, state_snapshots=6) as (comp, ask):
        for i, t in enumerate((system, a0, b0, a1, a2)):
            _against_reference(model, *ask(i, t))
        s = comp.stats
        # system: cold, snapshot at 32.  a0, b0: restore at 32 (the
        # tenant boundary), snapshots at 48.  a1: restores at 48 (the
        # session's), its snapshot at 64 = its end.  a2: restores THERE,
        # three tokens behind its first answer token; no new page, no
        # new snapshot
        assert (s.state_restores, s.state_snapshots) == (4, 4)
        assert s.prefix_tokens == 32 + 32 + 48 + 64
        assert s.state_cut_tokens == 0
        recs = [np.load(os.path.join(tmp_path, "audit", f"{i}.npz"))
                for i in range(5)]
        assert [int(r["n_prefix"]) for r in recs] == [0, 32, 32, 48, 64]
        assert model.audit_lane(64, 3) == 0 and model.audit_lane(32, 20) == 1
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert hb["state_restores"] == 4 and hb["state_snapshots"] == 4
        assert hb["state_slots_used"] == 4 and hb["state_slots"] == 8
        # the window family's live-key counters and the two new ones,
        # for this family; none of the window group's gauges
        assert hb["decode_keys"] > 0 and hb["prefill_keys"] > 0 \
            and hb["prefill_kv"] > 0
        assert 0 < hb["experts_live"] <= hb["expert_slots"]
        assert 0 <= hb["router_bias_swaps"] <= hb["expert_slots"]
        assert "window_pool_pages" not in hb
        assert {"paged_chunk", "suffix_prefill", "state_copy",
                "state_zero"} <= set(hb["devtime"])


def test_the_other_families_heartbeats_carry_none_of_it(tmp_path):
    m = mla.LatentCompletionModel(
        mla.LatentMoeConfig.tiny(dtype=jnp.float32), seed=2, temp=0.0)
    with serving(tmp_path, m) as (comp, ask):
        ask(0, _text(20, 9))
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert not {"experts_live", "router_bias_swaps", "decode_keys",
                    "prefill_keys"} & set(hb)
        assert hb["expert_slots"] > 0


# ------------------------------------------------ the benchmark's copy

def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_conv", os.path.join(
            REPO, "benchmark", "reference", "conv_gqa_moe_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_two_copies_of_the_reference_agree(tmp_path):
    """benchmark/reference/conv_gqa_moe_block.py (its own weights from
    the seed, long prompts in blocks) == tests/reference_lfm2.py on the
    program's tree; its float8 control does not."""
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=128)
    params = lfm2.init_params(cfg, seed)
    seqs = [IDS[:70] % 512, IDS[5:33] % 512]
    pos = [[20, 69], [0, 27]]
    bench = _bench_reference()
    kw = dict(block=32, bias_std=lfm2.BIAS_STD)
    got = bench.forward_logits(ARCH, SHARE, seed, seqs, pos, **kw)
    for s, p, g in zip(seqs, pos, got):
        np.testing.assert_allclose(g, R.forward(cfg, params, s)[p],
                                   atol=1e-4)
    low = bench.forward_logits(ARCH, SHARE, seed, seqs[:1], pos[:1],
                               f8=True, **kw)
    assert bench.rel_err(low[0], got[0]).min() > 0.02


# ------------------------------------- an admission round in one program

# (prefix tokens, suffix tokens, snapshot at | None) a join: one token,
# one page + 1, the four pages of the program's width with the snapshot
# at the last token, a page behind three; then rows without snapshots
_JOINS = [(32, 1, None), (16, 17, 32), (48, 64, 112), (32, 20, None),
          (16, 16, 32), (0, 33, 32), (64, 5, None), (16, 48, 48),
          (32, 64, None)]
ROUNDS = {
    # name: (batch, the joins of the round): the rung and its pad rows
    "three-of-six": (6, _JOINS[:3]),           # rung 6, three pads
    "a-full-rung": (6, _JOINS[:6]),            # rung 6, no pad
    "two-of-twelve": (12, _JOINS[1:3]),        # rung 12, ten pads
    "nine-of-twelve": (12, _JOINS),            # rung 12, three pads
    "no-snapshot": (6, [_JOINS[0], _JOINS[3], _JOINS[6]]),
}


def _seated(model, batch, joins, widest_only, **kw):
    """A model over `model`'s weights and its cache, every join's
    prefix prefilled into its row (a row a join), every state slot
    that is no row's filled with a pattern of its own, so that a write
    to it shows."""
    m = lfm2.ConvCompletionModel(CFG, params=model.params, temp=0.0, **kw)
    cache = m.init_paged(batch, page=PAGE, pool_pages=96,
                         state_snapshots=len(joins))
    if widest_only:
        m.suffix_buckets = m.suffix_buckets[-1:]
    rng = np.random.default_rng(7)
    toks = [(rng.integers(3, CFG.vocab_size, p).astype(np.int32),
             rng.integers(3, CFG.vocab_size, n).astype(np.int32))
            for p, n, _ in joins]
    for row, (prefix, _) in enumerate(toks):
        if len(prefix):
            m.paged_prefill_row(cache, prefix, row)
        else:
            m.state_zero(cache, row)
    mark = jnp.arange(cache.state_slots, dtype=jnp.float32)[:, None, None]
    cache.states = [[jnp.where(mark >= batch, mark + layer, reg)]
                    for layer, (reg,) in enumerate(cache.states)]
    snaps = [None if at is None else (cache.alloc_state_slot(), at)
             for _, _, at in joins]
    return m, cache, [(row, s) for row, (_, s) in enumerate(toks)], snaps


def _one_by_one(m, cache, rows, snaps):
    return np.stack([
        m.paged_append_prefill(cache, suffix, row, **(
            {"snap_slot": snap[0], "snap_at": snap[1]} if snap else {}))
        for (row, suffix), snap in zip(rows, snaps)])


@pytest.mark.parametrize("name", sorted(ROUNDS))
def test_a_round_in_one_program_is_its_joins_one_by_one(model, name):
    """paged_append_prefill_rows against the same joins through
    paged_append_prefill.  Against the one-row program AT THE ROUND'S
    WIDTH everything is equal to the bit — logits, every page but the
    trash block, every state slot but the spare one: each row's
    register, each snapshot, and the untouched pattern of every slot
    the round was not given; against the one-row programs at their own
    widths (another summation order) the logits hold the file's
    tolerance.  The heartbeat's counts do not know how the round was
    joined."""
    batch, joins = ROUNDS[name]
    want_m, want_c, rows, snaps = _seated(model, batch, joins, True)
    want = _one_by_one(want_m, want_c, rows, snaps)
    m, cache, rows, snaps = _seated(model, batch, joins, True)
    m.suffix_buckets = want_m.suffix_buckets
    before = [np.asarray(reg) for (reg,) in cache.states]
    assert len(joins) > 1 and m.join_rungs(cache) == (1, batch)
    logits, firsts = m.paged_append_prefill_rows(
        cache, rows, snaps if any(snaps) else None)
    np.testing.assert_array_equal(np.asarray(logits)[:len(joins)], want)
    np.testing.assert_array_equal(firsts, want.argmax(-1))
    np.testing.assert_array_equal(cache.lengths, want_c.lengths)
    np.testing.assert_array_equal(cache.tables, want_c.tables)
    for got, ref in zip(cache.pools, want_c.pools):
        np.testing.assert_array_equal(got[0][1:], ref[0][1:])
    spare = cache.state_spare
    given = {r for r, _ in rows} | {s[0] for s in snaps if s}
    assert spare not in given and len(given) == len(rows) + sum(
        s is not None for s in snaps)
    for (got,), (ref,), was in zip(cache.states, want_c.states, before):
        np.testing.assert_array_equal(got[:spare], ref[:spare])
        idle = [s for s in range(spare) if s not in given]
        np.testing.assert_array_equal(np.asarray(got)[idle], was[idle])
    for k in ("prefill_keys", "prefill_kv"):
        assert m.attn_work[k] == want_m.attn_work[k]
    # the program counts the experts its tokens reached ONCE a layer
    assert 0 < m.attn_work["prefill_experts_live"] \
        <= want_m.attn_work["prefill_experts_live"]
    narrow_m, narrow_c, rows, snaps = _seated(model, batch, joins, False)
    np.testing.assert_allclose(
        np.asarray(logits)[:len(joins)],
        _one_by_one(narrow_m, narrow_c, rows, snaps), atol=2e-4)


def test_a_round_of_one_is_the_one_row_program_and_bad_rows_are_refused(
        model):
    """`join` decides it: one join runs the one-row program and leaves
    the draw to the lane; the rows program refuses what it cannot
    hold."""
    m, cache, rows, snaps = _seated(model, 6, _JOINS[1:2], False)
    want_m, want_c, *_ = _seated(model, 6, _JOINS[1:2], False)
    (row, suffix), match = rows[0], int(cache.lengths[0])
    one = Join(row, np.concatenate([np.zeros(match, np.int32), suffix]),
               match, True, snaps[0])
    assert m.rides_round(one) and m.round_cap(cache) == 6
    logits, firsts = m.join(cache, [one])
    np.testing.assert_array_equal(
        logits[None], _one_by_one(want_m, want_c, rows, snaps))
    assert firsts is None
    assert not any(k[0] == "suffix" and len(k) > 2
                   for k in m._paged_progs)
    for (got,), (ref,) in zip(cache.states, want_c.states):
        np.testing.assert_array_equal(got[snaps[0][0]], ref[snaps[0][0]])
    m, cache, rows, snaps = _seated(model, 6, _JOINS[:2], False)
    with pytest.raises(ValueError, match="65 tokens"):
        m.paged_append_prefill_rows(
            cache, [rows[0], (1, np.ones((65,), np.int32))])
    with pytest.raises(ValueError, match="snapshot at 48"):
        m.paged_append_prefill_rows(cache, rows, [None, (snaps[1][0], 48)])
    cache.lengths[0] = 33
    with pytest.raises(ValueError, match="page boundary"):
        m.paged_append_prefill_rows(cache, rows)


def test_a_rounds_first_tokens_are_a_function_of_the_seed(model):
    """Drawn in graph by the decode chunk's sampler from the model's
    own key: the same seed draws the same first tokens, another seed
    others, and a cold sampler (temp 0) the logits' argmax."""
    def draw(seed):
        m, cache, rows, snaps = _seated(model, 6, _JOINS[:6], False,
                                        seed=seed)
        m.temp = 0.9
        logits, firsts = m.paged_append_prefill_rows(cache, rows, snaps)
        assert firsts.shape == (6,) and firsts.dtype == np.int32
        return np.asarray(logits), firsts
    (la, a), (lb, b), (lc, c) = draw(11), draw(11), draw(12)
    np.testing.assert_array_equal(la, lc)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any() and (a != la.argmax(-1)).any()


def test_a_round_through_the_kernels_in_interpret_mode(model):
    """The round's rows through the Pallas kernels as the chip runs
    them — pad rows of length 0 over a table of trash blocks, rows
    narrower than the program — against the same round's jnp path."""
    batch, joins = ROUNDS["three-of-six"]
    want_m, want_c, rows, snaps = _seated(model, batch, joins, False)
    want, _ = want_m.paged_append_prefill_rows(want_c, rows, snaps)
    m, cache, rows, snaps = _seated(model, batch, joins, False,
                                    interpret=True)
    logits, _ = m.paged_append_prefill_rows(cache, rows, snaps)
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_allclose(np.asarray(logits)[:3],
                               np.asarray(want)[:3], atol=6e-2)
    for (got,), (ref,) in zip(cache.states, want_c.states):
        np.testing.assert_allclose(got[:cache.state_spare],
                                   ref[:cache.state_spare], atol=6e-2)


def test_a_rounds_experts_take_the_live_tokens_in_chunks(model, monkeypatch):
    """The round's expert layers with its 384 token slots in chunks of
    100 LIVE tokens (the benchmark's 16,384 slots go in chunks of
    8,192): the same logits, pages and registers to the bit."""
    batch, joins = ROUNDS["a-full-rung"]
    want_m, want_c, rows, snaps = _seated(model, batch, joins, False)
    want, _ = want_m.paged_append_prefill_rows(want_c, rows, snaps)
    monkeypatch.setattr(lfm2, "JOIN_MOE_CHUNK", 100)
    m, cache, rows, snaps = _seated(model, batch, joins, False)
    logits, _ = m.paged_append_prefill_rows(cache, rows, snaps)
    np.testing.assert_array_equal(logits, want)
    for (got,), (ref,) in zip(cache.states, want_c.states):
        np.testing.assert_array_equal(got[:cache.state_spare],
                                      ref[:cache.state_spare])
    for got, ref in zip(cache.pools, want_c.pools):
        np.testing.assert_array_equal(got[0][1:], ref[0][1:])
    assert m.attn_work["prefill_experts_live"] \
        == want_m.attn_work["prefill_experts_live"]
