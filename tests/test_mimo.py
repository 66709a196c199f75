"""MiMo-V2-Flash's block as a SETTING of models/afmoe.py: window layers
with a learned attention sink beside global layers, key/value heads and
key/value widths that differ by layer kind, partial rotary positions,
plain pre-norm, no shared expert, and a window of exactly ONE PAGE —
model, kernels, cache manager, prefix tree and the continuous lane, on
the CPU at tiny widths, against the benchmark's plain float32 reference
(benchmark/reference/window_sink_gqa_moe_block.py, which makes its own
weights from the seed and imports nothing of the program).

Tolerances.  The program runs in bfloat16 as it does on the chip (the
recipe rounds every matrix to bfloat16 and the reference restates
that), so program and reference differ by the ACTIVATIONS' roundings:
over the logits of a position, max |program - reference| relative to
the reference logits' standard deviation reads a median of 0.044 and
0.091 at the worst of 30 positions at these widths (hidden 64; the
global layers' scores are seeded at std 3, which a rounding of q or k
moves further than a flat score) with continuous gates
(num_experts_per_tok = n_routed_experts: a top-k router at hidden 64
flips an expert on a rounding at a third of the positions,
tests/test_afmoe.py).  TOL = 0.15 lies 1.65x above the worst sound
position; the reference's own float8 copy reads 0.37 at its BEST
position of 110 (median 0.66), and the window layers without their
sink 1.6 at theirs (median 2.5)."""
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

from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import Join, PrefixCache
from libsplinter_tpu.models import afmoe, mla
from libsplinter_tpu.models.moe import sparse_moe
from libsplinter_tpu.ops.paged_attention import (
    _paged_ref, kv_append, window_paged_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 16
TOL = 0.15
POISON = 1e30

# a tiny description in MiMo-V2-Flash's published keys (the shape of
# benchmark/configs/mimo-v2-flash-309b-ep16.json's model): window 16 =
# one page of 16, an irregular head (global, window) before periods of
# three, 2 window kv heads against 1 global, keys of 24 beside values
# of 16, 8 of a head's 24 dims rotated
PATTERN = [0, 1, 0, 1, 1, 0, 1, 1]
ARCH = {"model_type": "mimo_v2_flash", "hidden_act": "silu",
        "tie_word_embeddings": False, "attention_bias": False,
        "hidden_size": 64, "num_attention_heads": 4, "head_dim": 24,
        "v_head_dim": 16, "num_key_value_heads": 1,
        "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
        "swa_head_dim": 24, "swa_v_head_dim": 16,
        "partial_rotary_factor": 0.334, "rope_theta": 5000000,
        "swa_rope_theta": 10000, "attention_value_scale": 0.707,
        "sliding_window": 16, "sliding_window_size": 16,
        "attention_chunk_size": 16,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False,
        "hybrid_layer_pattern": PATTERN,
        "moe_layer_freq": [0] + [1] * 7, "num_hidden_layers": 8,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 16, "num_experts_per_tok": 16,
        "n_shared_experts": None, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "routed_scaling_factor": None,
        "layernorm_epsilon": 1e-5, "max_position_embeddings": 262144,
        "vocab_size": 4096}
SHARE = {"layers": 8, "dense_layers": 1, "experts": [4, 8],
         "vocab": [0, 512]}
SEED = 7
IDS = np.random.default_rng(0).integers(3, 512, 400).astype(np.int32)


def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_sink", os.path.join(
            REPO, "benchmark", "reference",
            "window_sink_gqa_moe_block.py"))
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
        _describe(tmp_path_factory.mktemp("mimo")), max_len=512)
    assert seed == SEED
    return got


@pytest.fixture(scope="module")
def model(cfg):
    return afmoe.WindowCompletionModel(cfg, seed=SEED)


@pytest.fixture(scope="module")
def ref_logits():
    """The reference's logits behind every position of IDS[:130]."""
    return BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]],
                                [list(range(130))], block=16)[0]


def _poison_free(cache) -> None:
    w = cache.window
    idle = jnp.asarray([0] + list(w._free), jnp.int32)
    for pool in w.pools:
        pool[0] = pool[0].at[idle].set(POISON)


def _teacher_forced(m, cache, row, tokens):
    out = []
    m.audit_seat(0, row)
    for t in tokens:
        toks = np.full((cache.batch,), -1, np.int32)
        toks[row] = t
        pend = m.paged_decode_chunk_async(cache, toks, 1)
        pend.block()
        out.append(np.asarray(pend.audit)[0, 0])
        _poison_free(cache)
    m.audit_seat(0, -1)
    return np.stack(out)


# ---------------------------------------------- the description, the plan

def test_description_loader_reads_the_mimo_key_set(cfg):
    assert isinstance(cfg, afmoe.WindowMoeConfig)
    assert cfg.kinds == tuple("window" if p else "full" for p in PATTERN)
    w, f = cfg.attn("window"), cfg.attn("full")
    assert (w.kv_heads, w.qk_dim, w.v_dim, w.rotary_dim, w.rope_base,
            w.window, w.sink, w.k_cols) \
        == (2, 24, 16, 8, 10000.0, 16, True, True)
    assert (f.kv_heads, f.qk_dim, f.v_dim, f.rotary_dim, f.rope_base,
            f.window, f.sink) == (1, 24, 16, 8, 5000000.0, 0, False)
    assert not (cfg.out_gate or cfg.qk_norm or cfg.sandwich_norm
                or cfg.mup or cfg.n_shared_experts)
    assert cfg.value_scale == 0.707 and cfg.routed_scaling_factor == 1.0
    assert (cfg.dense_layers, cfg.model_layers, cfg.window) == (1, 8, 16)
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held,
            cfg.top_k) == (16, 4, 8, 16)
    assert mla.completion_model_class(cfg) is afmoe.WindowCompletionModel


def test_key_value_heads_and_widths_differ_by_group(cfg, model):
    """Two page groups whose K and V blocks differ in width, and whose
    kv-head counts differ from each other."""
    full, window = cfg.page_layout(PAGE)
    # 24 is no whole number of lane tiles: the keys a token a COLUMN
    assert cfg.attn("window").k_cols and cfg.attn("full").k_cols
    assert full.pools == (("k", (3, 1, 24, PAGE)), ("v", (3, 1, PAGE, 16)))
    assert window.pools == (("k", (5, 2, 24, PAGE)),
                            ("v", (5, 2, PAGE, 16)))
    assert (full.window, window.window) == (0, 16)
    assert (full.token_values, window.token_values) \
        == (3 * 40, 5 * 2 * 40)
    cache = model.init_paged(2, page=PAGE, pool_pages=40,
                             window_pool_pages=12)
    assert cache.pools[0][0].shape == (41, 3, 1, 24, PAGE)
    assert cache.pools[1][0].shape == (41, 3, 1, PAGE, 16)
    assert cache.window.pools[0][0].shape == (13, 5, 2, 24, PAGE)
    assert cache.window.pools[1][0].shape == (13, 5, 2, PAGE, 16)
    assert cache.kv_bytes_per_token() == 2 * (120 + 400)
    # a window of one page: one window page, a decode span of three
    assert cache.window.window_pages == 1
    assert cache.window.span == 1 + 2 + 5


@pytest.mark.parametrize("case, pattern, dense, want", [
    ("MiMo's 48: global, window x 4, then periods of six",
     [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0], 1, (1, 6, 7)),
    ("the benchmark's cut, layers 0-6: no pattern repeats, the four "
     "window layers in a row are periods of one",
     [0, 1, 1, 1, 1, 0, 1], 1, (1, 1, 4)),
    ("three in a row are not worth a scan",
     [0, 1, 1, 1, 0, 1], 1, (4, 4, 0)),
    ("this file's: global, window, then periods of three",
     PATTERN, 1, (1, 3, 2)),
    ("a regular pattern keeps the plan it had",
     [1, 1, 1, 0] * 8, 2, (4, 4, 7)),
], ids=["mimo-48", "mimo-cut-7", "run-of-3", "tiny-8", "afmoe-32"])
def test_plan_finds_the_period_behind_an_irregular_head(
        case, pattern, dense, want):
    cfg = afmoe.WindowMoeConfig.tiny(
        kinds=tuple("window" if p else "full" for p in pattern),
        dense_layers=dense)
    head, period, periods = cfg.plan
    assert (head, period, periods) == want
    # every scanned period is the same kinds, and holds both
    for k in range(1, periods):
        assert cfg.kinds[head + k * period: head + (k + 1) * period] \
            == cfg.kinds[head: head + period]
    if periods and period > 1:
        assert set(cfg.kinds[head: head + period]) == set(cfg.kinds)


@pytest.mark.parametrize("bad, match", [
    ({"sliding_window_size": 32}, "must equal sliding_window"),
    ({"attention_chunk_size": 64}, "must equal sliding_window"),
    ({"swa_num_attention_heads": 8}, "one query head count"),
    ({"topk_method": "group_limited_greedy"}, "topk_method noaux_tc"),
    ({"n_group": 2}, "group-limited routing"),
    ({"n_shared_experts": 2}, "0 or 1 shared expert"),
    ({"attention_bias": True}, "attention_bias is not served"),
    ({"hybrid_layer_pattern": [0, 1, 2, 1, 1, 0, 1, 1]},
     "hybrid_layer_pattern"),
    ({"moe_layer_freq": [0, 1, 0, 1, 1, 1, 1, 1]}, "moe_layer_freq"),
    ({"rms_norm_eps": 1e-6}, "unknown architecture key"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(_describe(tmp_path, {**ARCH, **bad}))


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "page codecs know one pool a layer"),
    (["--kv-dtype", "int4"], "page codecs know one pool a layer"),
    (["--kv-tier-pages", "8"], "carries one page group"),
    (["--phase", "prefill"], "carries one page group"),
    (["--tp", "2"], "not sharded on their kv-head axis"),
    (["--ep", "2"], "told the experts it holds"),
    (["--draft-layers", "2"], "speculative wrapper"),
    (["--weights", "x.safetensors"], "seeded weights"),
    (["--quantized"], "int8 weight residencies"),
    (["--state-snapshots", "4"], "keep no recurrent state"),
], ids=lambda v: "".join(v) if isinstance(v, list) else None)
def test_main_refuses_what_the_sink_model_cannot_serve(
        tmp_path, flags, match):
    with pytest.raises(SystemExit, match=match) as ex:
        C.main(["--store", f"/spt-mimo-refuse-{os.getpid()}", "--model",
                _describe(tmp_path), "--continuous", *flags])
    assert str(ex.value).startswith("unsupported_option: ")


# ----------------------------------------------------------- the kernels

@pytest.mark.parametrize("k_cols", [False, True],
                         ids=["keys-in-rows", "keys-in-columns"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("q_tokens, window, sink", [
    (1, 16, True), (16, 16, True), (48, 16, True), (1, 0, False),
    (16, 0, True), (1, 40, True)],
    ids=["decode-sink", "stack-16-sink", "stack-48-sink",
         "decode-global-plain", "stack-global-sink", "decode-window-40"])
def test_kernel_with_a_sink_and_two_widths_matches_the_reference(
        q_tokens, window, sink, dtype, k_cols):
    """window_paged_attention in interpret mode == `_paged_ref`
    extended with the sink: keys of 24 beside values of 16, the scale
    from the KEY width, the sink in the denominator only, the key
    pages a token a row or a token a column; pages behind the window
    are poisoned."""
    rng = np.random.default_rng(2)
    page, B, KH, rep, Dk, Dv, L, P = 16, 3, 2, 4, 24, 16, 3, 12
    nb = B * P + 1
    kp = jnp.asarray(rng.standard_normal((nb, L, KH, page, Dk)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, L, KH, page, Dv)), dtype)
    tables = np.arange(1, nb).reshape(B, P).astype(np.int32)
    lengths = np.minimum(np.array([5, 100, 150], np.int32),
                         P * page - q_tokens)
    if window:
        for b in range(B):
            tables[b, :max(0, lengths[b] - window) // page] = 0
    q = jnp.asarray(rng.standard_normal((B, q_tokens, KH * rep, Dk)),
                    dtype)
    sinks = jnp.asarray(rng.uniform(-1.0, 4.0, KH * rep), jnp.float32) \
        if sink else None
    kin = kp.at[0].set(jnp.nan)
    got = window_paged_attention(
        q, kin.swapaxes(-1, -2) if k_cols else kin,
        vp.at[0].set(jnp.nan), tables, lengths, layer=1, window=window,
        sinks=sinks, k_cols=k_cols, interpret=True)
    assert got.shape == (B, q_tokens, KH * rep, Dv)
    starts = jnp.asarray(lengths - window) if window else None
    args = (q, kp.at[0].set(0)[:, 1], vp.at[0].set(0)[:, 1],
            jnp.asarray(tables), jnp.asarray(lengths), starts)
    ref = _paged_ref(*args, sinks)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol)
    if sink:
        # the sink takes mass and gives nothing: every output shrinks
        # by the same factor a (row, head), never grows
        plain = np.asarray(_paged_ref(*args), np.float32)
        ref = np.asarray(ref, np.float32)
        assert np.abs(plain - ref).max() > 10 * tol
        ratio = np.linalg.norm(ref, axis=-1) / np.linalg.norm(plain,
                                                              axis=-1)
        assert (ratio < 1.0 + 1e-3).all() and ratio.min() < 0.9


def test_the_sink_is_softmax_with_one_more_key():
    """By hand: one query, three keys, a sink of log(3): the sink
    takes exp(b) / (exp(b) + sum exp(s)) of the mass."""
    q = jnp.zeros((1, 1, 1, 4), jnp.float32)          # every score 0
    kp = jnp.zeros((2, 1, 1, 4, 4), jnp.float32)
    vp = jnp.ones((2, 1, 1, 4, 2), jnp.float32)
    tab, lens = np.array([[1]], np.int32), np.array([3], np.int32)
    for interp in (False, True):
        out = window_paged_attention(
            q, kp, vp, tab, lens, layer=0, window=4,
            sinks=jnp.asarray([float(np.log(3.0))]), interpret=interp)
        np.testing.assert_allclose(np.asarray(out), 0.5, atol=1e-6)


@pytest.mark.parametrize("width, cols", [(24, False), (16, False),
                                         (24, True)],
                         ids=["k-rows-24", "v-rows-16", "k-columns-24"])
def test_kv_append_writes_pools_of_either_width(width, cols):
    """K rows (or columns) of 24 and V rows of 16 through the append
    kernels, against the scatter they stand in for."""
    rng = np.random.default_rng(1)
    shape = (9, 3, 2, width, 32) if cols else (9, 3, 2, 32, width)
    pool = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((5, 2, width)), jnp.bfloat16)
    bids = np.array([3, 3, 0, 7, 1], np.int32)
    offs = np.array([5, 6, 0, 31, 16], np.int32)
    got = kv_append(pool, new, bids, offs, layer=2, cols=cols,
                    interpret=True)
    want = kv_append(pool, new, bids, offs, layer=2, cols=cols)
    np.testing.assert_array_equal(np.asarray(got[1:], np.float32),
                                  np.asarray(want[1:], np.float32))
    at = got[3, 2, :, :, 5] if cols else got[3, 2, :, 5]
    assert float(jnp.abs(at - new[0]).max()) == 0.0
    assert float(jnp.abs(got[:, :2] - pool[:, :2]).max()) == 0.0


def test_partial_rotary_touches_only_the_leading_dims():
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, 5, 2, 24)), jnp.float32)
    pos = jnp.arange(5) + 7
    from libsplinter_tpu.models.encoder import _rotary_angles_at
    cos, sin = _rotary_angles_at(pos, 8, 10000.0)
    out = afmoe._rotate(x, cos[None], sin[None])
    assert out.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert float(jnp.abs(out[..., :8] - x[..., :8]).max()) > 0.1
    # a rotation: the leading dims keep their norm, pair by pair
    np.testing.assert_allclose(
        np.asarray(out[..., :4] ** 2 + out[..., 4:8] ** 2),
        np.asarray(x[..., :4] ** 2 + x[..., 4:8] ** 2), rtol=1e-5)
    # the whole head (AFMoE's setting) is the same function
    cos, sin = _rotary_angles_at(pos, 24, 10000.0)
    full = afmoe._rotate(x, cos[None], sin[None])
    assert float(jnp.abs(full[..., 12:] - x[..., 12:]).max()) > 0.1


# ------------------------------------ prefill, decode, against the reference

@pytest.mark.parametrize("case, prompt, steps, interpret", [
    ("cold prompt past the window, decode across page boundaries",
     100, 24, False),
    ("a prompt shorter than the window", 10, 8, False),
    ("the kernels themselves, interpreted", 60, 12, True),
])
def test_prefill_then_decode_equals_the_reference(
        case, prompt, steps, interpret, cfg, ref_logits):
    """The suffix programs from an empty row, then decode steps fed
    the sequence's own tokens, through both page groups: every logit
    is the plain reference's full forward pass's within TOL, while the
    window group gives back what the row slides past (poisoned)."""
    m = afmoe.WindowCompletionModel(cfg, seed=SEED, interpret=interpret)
    cache = m.init_paged(2, page=PAGE, pool_pages=40,
                         window_pool_pages=12)
    lg = m.paged_prefill_row(cache, IDS[:prompt], 1)
    _poison_free(cache)
    got = _teacher_forced(m, cache, 1, IDS[prompt: prompt + steps])
    got = np.concatenate([lg[None], got])
    want = ref_logits[prompt - 1: prompt + steps]
    err = BENCH.rel_err(got, want)
    assert err.max() < TOL, err
    w, length = cache.window, prompt + steps
    first = max(0, length - cfg.window + 1) // PAGE
    assert (w._lo[1], w._hi[1]) == (first, -(-length // PAGE))
    assert w.released == first and not w.tables[1, :first].any()
    cache.free_row(1)
    assert w.free_pages == 12 and cache.free_pages == 40


def test_a_float8_copy_and_a_dropped_sink_fail_the_same_comparison(
        ref_logits):
    """What the tolerance is FOR: the reference computed with every
    matrix and cached key/value rounded to float8_e4m3, and the window
    layers' softmax without its sink, both read far over TOL at every
    position past the first."""
    pos = [list(range(20, 130))]
    low = BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]], pos,
                               f8=True, block=16)[0]
    assert np.percentile(BENCH.rel_err(low, ref_logits[20:]), 10) > TOL
    bare = BENCH.forward_logits(ARCH, SHARE, SEED, [IDS[:130]], pos,
                                no_sink=True, block=16)[0]
    assert np.percentile(BENCH.rel_err(bare, ref_logits[20:]), 10) > TOL


def test_a_window_layer_with_and_without_its_sink_differ(cfg, model):
    """The program's own switch: the same weights served with the
    window kind's sink turned off give other logits."""
    kinds = tuple((k, afmoe.dataclasses.replace(a, sink=False))
                  for k, a in cfg.attn_kinds)
    bare = afmoe.WindowCompletionModel(
        afmoe.dataclasses.replace(cfg, attn_kinds=kinds),
        params=model.params)
    out = []
    for m in (model, bare):
        cache = m.init_paged(1, page=PAGE, pool_pages=40,
                             window_pool_pages=12)
        out.append(m.paged_prefill_row(cache, IDS[:40], 0))
    assert BENCH.rel_err(out[1][None], out[0][None])[0] > TOL


# ------------------------------------------------- the expert share

def test_sixteen_shares_with_no_shared_expert_are_the_uncut_layer():
    """Under THIS router's settings — sigmoid scores over all 32
    experts, plain top-8, renormalised over the selection, no scaling
    factor, NO shared expert — the 16 shares of an expert layer add up
    to the layer with every expert held: nothing is counted twice and
    nothing once-for-all."""
    rng = np.random.default_rng(5)
    H, M, E, k = 32, 16, 32, 8
    x = jnp.asarray(rng.standard_normal((24, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, M)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, M, H)) / 4, jnp.float32)
    kw = dict(top_k=k, score="sigmoid", norm_topk=True, scale=1.0)
    whole, sizes = sparse_moe(x, router, wg, wu, wd, **kw)
    assert int(sizes.sum()) == 24 * k
    parts, held = 0.0, 0
    for c in range(16):
        part, n = sparse_moe(x, router, wg[2 * c: 2 * c + 2],
                             wu[2 * c: 2 * c + 2], wd[2 * c: 2 * c + 2],
                             first=2 * c, **kw)
        parts, held = parts + part, held + int(n.sum())
    assert held == 24 * k
    np.testing.assert_allclose(parts, whole, atol=2e-5)


# ------------------------------------------- window == page, the tree

def _tree(cfg, pool_pages=40, window_pool_pages=12, batch=3):
    m = afmoe.WindowCompletionModel(cfg, params={})
    cache = m.init_paged(batch, page=PAGE, pool_pages=pool_pages,
                         window_pool_pages=window_pool_pages)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    return cache, pc


def _seat(cache, pc, ids, row):
    """What admit() does to the tables for a prompt, without a model
    (tests/test_afmoe.py has the same lines).  Returns (match, cut)."""
    bids, match, _ = pc.lookup_tiered(ids)
    tail, cut = list(pc.last_window), pc.last_window_cut
    if bids:
        cache.map_shared(row, bids)
        cache.window.map_tail(row, len(bids) - len(tail), tail)
        pc.commit_hit(ids, match)
    cache.lengths[row] = match
    pos = match
    while pos < len(ids):
        n = min(len(ids) - pos, 5 * PAGE)
        assert cache.ensure(row, pos + n)
        pos += n
        cache.lengths[row] = pos
        cache.release_window(row)
    pc.insert(ids, cache, row)
    return match, cut


def test_a_window_of_one_page_slides_a_page_every_page(cfg):
    """300 decode steps of one row: the window group never holds more
    than a span of 3 pages (the window's page, the page of its oldest
    key, the page ahead), the first live page moves every 16 tokens,
    and every page behind the window goes back."""
    cache, _ = _tree(cfg, pool_pages=40, window_pool_pages=12, batch=1)
    w = cache.window
    assert cache.ensure(0, 5)
    cache.lengths[0] = 5
    moved = 0
    for step in range(300):
        length = int(cache.lengths[0])
        assert cache.ensure(0, length + 1)          # the chunk's page
        cache.lengths[0] = length + 1
        moved += cache.release_window(0)
        held = int(w._hi[0] - w._lo[0])
        assert held <= 3 and w.used_pages == held
        assert w._lo[0] == w.first_live(length + 1) \
            == max(0, length + 1 - 15) // PAGE
        assert not w.tables[0, :w._lo[0]].any()
    assert moved == w.released == (305 - 15) // PAGE == 18
    assert len(cache._owned[0]) == -(-305 // PAGE)  # the global group
    cache.free_row(0)
    assert w.free_pages == 12


def test_two_rows_share_a_documents_tail_and_the_tree_keeps_it(cfg):
    """A document of 4 pages, questions of under a page: every
    question resumes on the document's LAST page in the window group.
    Refcount 2 -> 1 -> 0 as the rows slide off it, the tree holding it
    throughout; a later question still hits."""
    cache, pc = _tree(cfg)
    w = cache.window
    doc = IDS[:64]
    q = [np.concatenate([doc, IDS[100 + 10 * i: 106 + 10 * i]])
         for i in range(3)]
    assert _seat(cache, pc, q[0], 0) == (0, 0)      # cold, files 4 pages
    tail = int(w.tables[0, 3])
    assert pc.window_pages() == 1 and pc.retains_window(tail)
    cache.free_row(0)
    assert w.refcounts[tail] == 0 and pc.window_evictable_count() == 1
    assert _seat(cache, pc, q[1], 0) == (64, 0)
    assert _seat(cache, pc, q[2], 1) == (64, 0)
    assert w.tables[0, 3] == w.tables[1, 3] == tail
    assert w.refcounts[tail] == 2
    assert w.tables[0, 4] != w.tables[1, 4]         # a page each for q
    # row 0 decodes past the document's page: 70 + 10 > 64 + 15
    for row, want in ((0, 1), (1, 0)):
        cache.lengths[row] = 80
        assert cache.release_window(row) == 1
        assert w.refcounts[tail] == want and w.tables[row, 3] == 0
    assert pc.retains_window(tail) and pc.window_evictable_count() == 1
    assert tail not in w._free
    cache.free_row(0)
    cache.free_row(1)
    assert _seat(cache, pc, q[0], 2) == (64, 0)     # still a hit
    assert pc.stats.window_evictions == 0
    cache.reset()


def test_a_first_question_of_a_whole_page_takes_the_documents_tail(cfg):
    """THE FINDING (ROADMAP B1.7): a question of a page or more files
    a page BELOW the document; while the document's path has that one
    continuation the tree takes the document's tail for superseded
    (`_shed_window`: "as far up as the path has NO BRANCH"), and the
    next question on the document finds its global pages and no window
    to resume on: window_cut_tokens reads the whole document.  The
    cold prefill that follows files the tail again, under a node that
    has a child now, and short questions keep it; a second long
    question branches the path and both tails stay."""
    cache, pc = _tree(cfg)
    doc = IDS[:64]
    long_q = np.concatenate([doc, IDS[100:120]])    # 20 tokens > a page
    short = [np.concatenate([doc, IDS[200 + 10 * i: 206 + 10 * i]])
             for i in range(2)]
    assert _seat(cache, pc, short[0], 0) == (0, 0)
    cache.free_row(0)
    assert _seat(cache, pc, short[1], 0) == (64, 0)     # the tail held
    cache.free_row(0)
    # the first long question resumes on the tail too — and its insert
    # files page 4 below the document and sheds the document's page 3
    assert _seat(cache, pc, long_q, 0) == (64, 0)
    cache.free_row(0)
    assert pc.window_pages() == 1 and pc.stats.window_evictions == 1
    assert pc.lookup_tiered(short[0])[1] == 0 \
        and pc.last_window_cut == 64
    assert _seat(cache, pc, short[0], 0) == (0, 64)     # served cold
    cache.free_row(0)
    assert _seat(cache, pc, short[1], 0) == (64, 0)     # filed again
    cache.free_row(0)
    other = np.concatenate([doc, IDS[300:320]])
    assert _seat(cache, pc, other, 0) == (64, 0)        # a branch now
    cache.free_row(0)
    assert _seat(cache, pc, short[0], 0) == (64, 0)
    assert pc.stats.window_evictions == 1
    cache.reset()


# ------------------------------------------------- the continuous lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-mimo-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    comp = C.Completer(st, model=model, max_new_tokens=12,
                       template="none", batch_cap=3, page_size=PAGE,
                       pool_pages=40, window_pool_pages=14,
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


def test_questions_share_a_documents_tail_through_run_continuous(
        tmp_path, model):
    """A 4-page document asked once cold, then three questions at
    once and one alone, through the daemon's own loop: each resumes on
    the whole document and its one tail page, the later ones of the
    burst find the tail held by a live row already
    (window_tail_shares), every row leaves the tail from INSIDE a
    decode chunk (window_decode_slides), and every audited logit (one
    row a lane is audited at a time) is the reference's for prompt +
    generated tokens."""
    doc = _text(63, 1)                          # + BOS = 64 = 4 pages
    with serving(tmp_path, model) as (comp, ask, audit_dir):
        ask("q/0", doc + _text(6, 2))
        s, w = comp.stats, comp._paged_cache.window
        assert (s.window_resumes, s.window_tail_shares) == (0, 0)
        slid0 = s.window_decode_slides
        assert slid0 >= 1                       # 70 + 12 tokens > 64 + 15
        ts = [threading.Thread(target=ask, args=(
            f"q/{i}", doc + _text(5 + i, 10 + i))) for i in (1, 2, 3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert s.window_tail_shares >= 1
        ask("q/4", doc + _text(9, 20))
        assert s.window_resumes == 4 and s.window_cut_tokens == 0
        assert s.prefix_tokens == 4 * 64
        assert s.window_decode_slides - slid0 == 4
        for _ in range(200):
            if comp.audit.written >= 3:
                break
            time.sleep(0.02)
        seqs, pos, got = [], [], []
        for i in range(3):
            rec = np.load(os.path.join(audit_dir, f"{i}.npz"))
            n = len(rec["prompt"])
            seqs.append(np.concatenate([rec["prompt"],
                                        rec["tokens"][:-1]]))
            pos.append(list(range(n - 1, n - 1 + len(rec["tokens"]))))
            got.append(rec["logits"])
            assert int(rec["n_prefix"]) == (64 if i else 0)
        want = BENCH.forward_logits(ARCH, SHARE, SEED, seqs, pos,
                                    block=16)
        for g, r in zip(got, want):
            assert BENCH.rel_err(g, r).max() < TOL
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert hb["window_tail_shares"] == s.window_tail_shares
        assert hb["window_decode_slides"] == s.window_decode_slides
        assert hb["window_resumes"] == 4
        assert hb["decode_window_keys"] > 0 and hb["prefill_kv"] > 0
        # every page in either group: free, or the tree's at zero refs
        pc, cache = comp.prefix_cache, comp._paged_cache
        for _ in range(250):
            if w.free_pages + pc.window_evictable_count() == 14:
                break
            time.sleep(0.02)
        assert w.free_pages + pc.window_evictable_count() == 14
        assert cache.free_pages + pc.evictable_count() == 40


def test_a_one_group_model_has_neither_counter(tmp_path):
    """The shared lane with ONE page group: the two new counters are
    dead gauges there, as the window group's others are."""
    cfg = mla.LatentMoeConfig.tiny(dtype=jnp.float32)
    m = mla.LatentCompletionModel(cfg, seed=1)
    name = f"/spt-mimo-one-{os.getpid()}"
    Store.unlink(name)
    st = Store.create(name, nslots=64, max_val=2048, vec_dim=8)
    try:
        comp = C.Completer(st, model=m, max_new_tokens=2,
                           template="none", batch_cap=2, page_size=PAGE,
                           pool_pages=16)
        comp.attach()
        comp.publish_stats()
        hb = json.loads(st.get(C.P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert "window_tail_shares" not in hb
        assert "window_decode_slides" not in hb
    finally:
        st.close()
        Store.unlink(name)


# ------------------------------------- an admission round in one program
#
# Both small configurations of models/afmoe.py: this file's (bfloat16,
# a window of ONE page, keys a token a column, a sink) and
# tests/test_afmoe.py's (float32, a window of two pages, the output
# gate and the sandwich norms).

# (document, its whole pages, suffix tokens) a join: three questions on
# one document — they share its window tail —, one of them ONE token
# and one a whole page; a fourth on a document of its own
ROUND = [("a", 4, 1), ("a", 4, PAGE), ("a", 4, 7), ("b", 3, 11)]


@pytest.fixture(scope="module", params=["afmoe", "mimo"])
def round_model(request, cfg):
    if request.param == "afmoe":
        cfg = afmoe.WindowMoeConfig.tiny(dtype=jnp.float32,
                                         experts_first=2, experts_held=4)
    return afmoe.WindowCompletionModel(cfg, seed=SEED)


def _seated_round(base, joins, batch, warm_chunk=0, **kw):
    """A model over `base`'s weights, its cache (warmed up for chunks
    of `warm_chunk` steps, if any) and its tree: every
    document prefilled once and filed, every join seated as
    completer.fill_rows seats a hit — the document's pages mapped in
    both groups, the row's reservation made, its suffix not yet
    prefilled.  Returns (model, cache, [(row, suffix)])."""
    cfg = base.cfg
    m = afmoe.WindowCompletionModel(cfg, params=base.params, temp=0.0,
                                    **kw)
    cache = m.init_paged(batch, page=PAGE, pool_pages=40,
                         window_pool_pages=24)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    if warm_chunk:
        m.warmup_paged(cache, chunk=warm_chunk)
    rng = np.random.default_rng(11)
    docs, rows = {}, []
    for name, pages, _ in joins:
        if name not in docs:
            docs[name] = rng.integers(3, cfg.vocab_size,
                                      pages * PAGE).astype(np.int32)
            m.paged_prefill_row(cache, docs[name], 0)
            pc.insert(docs[name], cache, 0)
            cache.free_row(0)
    for row, (name, pages, n) in enumerate(joins):
        suffix = rng.integers(3, cfg.vocab_size, n).astype(np.int32)
        ids = np.concatenate([docs[name], suffix])
        bids, match, _ = pc.lookup_tiered(ids)
        tail = list(pc.last_window)
        assert match == pages * PAGE and tail and not pc.last_window_cut
        cache.map_shared(row, bids)
        cache.window.map_tail(row, len(bids) - len(tail), tail)
        pc.commit_hit(ids, match)
        cache.lengths[row] = match
        assert cache.ensure(row, len(ids) + 12)
        rows.append((row, suffix))
    for k in m.attn_work:
        m.attn_work[k] = 0
    return m, cache, rows


def _row_pages(cache, row):
    """The pages row holds, by group: [K, V] x (pages, ...)."""
    w = cache.window
    lo, hi = int(w._lo[row]), int(w._hi[row])
    held = len(cache._owned[row])
    return ([np.asarray(p[0][cache.tables[row, :held]], np.float32)
             for p in cache.pools]
            + [np.asarray(p[0][w.tables[row, lo:hi]], np.float32)
               for p in w.pools])


def _assert_same_round(cfg, got, want, n):
    """Another row count is another summation order: float32 holds
    tests/test_afmoe.py's tolerance; in bfloat16 a rounding may fall
    the other way (measured here: 5e-7 of the logits' spread, a
    hundredth of what the reference is held to is the limit)."""
    (m, cache, logits), (want_m, want_c, want_logits) = got, want
    logits = np.asarray(logits)[:n]
    if cfg.dtype == jnp.float32:
        np.testing.assert_allclose(logits, want_logits, atol=2e-4)
        atol = 2e-4
    else:
        assert BENCH.rel_err(logits, want_logits).max() < 0.01 * TOL
        atol = 2e-2
    np.testing.assert_array_equal(cache.lengths, want_c.lengths)
    np.testing.assert_array_equal(cache.window._lo, want_c.window._lo)
    np.testing.assert_array_equal(cache.window._hi, want_c.window._hi)
    assert cache.window.released == want_c.window.released
    assert cache.window.free_pages == want_c.window.free_pages
    assert cache.free_pages == want_c.free_pages
    for row in range(n):
        for a, b in zip(_row_pages(cache, row), _row_pages(want_c, row)):
            np.testing.assert_allclose(a, b, atol=atol)
    assert m.attn_work == want_m.attn_work


@pytest.mark.parametrize("batch", [4, 6], ids=["a-full-rung", "two-pads"])
def test_a_round_in_one_program_is_its_joins_one_by_one(round_model,
                                                        batch):
    """paged_append_prefill_rows against the same joins through
    paged_append_prefill: each row's logits, its pages of BOTH groups
    (read through its tables: the two orders of ensure and release
    hand out other page ids), its window span, what went back to the
    window group's free list, and the kernels' live-key counts; the
    rows that share a document's tail read it and write beside it; a
    pad row writes the trash block alone; the first tokens are the
    logits' argmax under a cold sampler."""
    cfg = round_model.cfg
    want_m, want_c, rows = _seated_round(round_model, ROUND, batch)
    shared = want_c.window.tables[:3, 3]
    assert shared[0] > 0 and (shared == shared[0]).all()
    assert want_c.window.refcounts[shared[0]] == 3
    want = np.stack([want_m.paged_append_prefill(want_c, s, r)
                     for r, s in rows])
    m, cache, rows = _seated_round(round_model, ROUND, batch)
    assert m.join_rungs(cache) == (1, batch) and m.join_width == PAGE
    idle = [np.asarray(p[0]) for p in cache.pools + cache.window.pools]
    logits, firsts = m.paged_append_prefill_rows(cache, rows)
    assert np.asarray(logits).shape == (batch, cfg.vocab_size)
    _assert_same_round(cfg, (m, cache, logits), (want_m, want_c, want),
                       len(rows))
    np.testing.assert_array_equal(firsts,
                                  np.asarray(logits)[:4].argmax(-1))
    # every page no row of the round was writing is as it was: the
    # documents', the tree's, the free ones (block 0 takes the pads')
    wrote = {0} | {int(cache.tables[r, p]) for r, s in rows
                   for p in range(len(cache._owned[r]))
                   if p * PAGE >= cache.lengths[r] - len(s)}
    wwrote = {0} | {int(cache.window.tables[r, p]) for r, s in rows
                    for p in range(cache.pages_per_row)
                    if (cache.lengths[r] - len(s)) // PAGE <= p
                    < -(-cache.lengths[r] // PAGE)}
    for pools, was, skip in ((cache.pools, idle[:2], wrote),
                             (cache.window.pools, idle[2:], wwrote)):
        keep = [b for b in range(was[0].shape[0]) if b not in skip]
        for pool, before in zip(pools, was):
            np.testing.assert_array_equal(np.asarray(pool[0])[keep],
                                          before[keep])


def test_a_round_of_one_is_the_one_row_program_and_bad_rows_are_refused(
        round_model):
    """`join` decides it: one join runs the one-row program and leaves
    the draw to the lane; a suffix wider than the rows program does
    not ride a round, and the rows program refuses it."""
    m, cache, rows = _seated_round(round_model, ROUND, 4)
    want_m, want_c, _ = _seated_round(round_model, ROUND, 4)

    def hit(row, suffix):
        match = int(cache.lengths[row])
        return Join(row, np.concatenate(
            [np.zeros(match, np.int32), suffix]), match, True)
    logits, firsts = m.join(cache, [hit(*rows[1])])
    np.testing.assert_array_equal(
        logits, want_m.paged_append_prefill(want_c, *rows[1][::-1]))
    assert firsts is None and m.round_cap(cache) == 4
    assert not any(k[0] == "suffix" and len(k) > 2
                   for k in m._paged_progs)
    # a suffix wider than the rows program: the completer serves it as
    # a round of one, a piece at a time
    wide = np.ones((PAGE + 1,), np.int32)
    assert m.rides_round(hit(*rows[0])) \
        and not m.rides_round(hit(2, wide))
    with pytest.raises(ValueError, match=f"{PAGE + 1} tokens in a "
                                         f"{PAGE}-token program"):
        m.paged_append_prefill_rows(cache, [rows[0], (2, wide)])
    cache.lengths[0] += 3
    with pytest.raises(ValueError, match="page boundary"):
        m.paged_append_prefill_rows(cache, [rows[0], rows[2]])


def test_a_round_through_the_kernels_in_interpret_mode(round_model):
    """The round's rows through the Pallas kernels as the chip runs
    them — a pad row of length 0 over tables of trash blocks, rows
    narrower than the program, a shared tail — against the same
    round's jnp path."""
    cfg = round_model.cfg
    want_m, want_c, rows = _seated_round(round_model, ROUND[:3], 4)
    want, _ = want_m.paged_append_prefill_rows(want_c, rows)
    m, cache, rows = _seated_round(round_model, ROUND[:3], 4,
                                   interpret=True)
    logits, _ = m.paged_append_prefill_rows(cache, rows)
    assert np.isfinite(np.asarray(logits)[:3]).all()
    if cfg.dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(logits)[:3],
                                   np.asarray(want)[:3], atol=2e-2)
    else:
        assert BENCH.rel_err(np.asarray(logits)[:3],
                             np.asarray(want)[:3]).max() < TOL
    for row in range(3):
        for a, b in zip(_row_pages(cache, row), _row_pages(want_c, row)):
            np.testing.assert_allclose(a, b, atol=0.1)


def test_warm_up_leaves_nothing_for_a_round_to_compile(round_model):
    """warmup_paged compiles the one-row widths and, in a thread
    beside them, the rung: a round, a wide hit's pieces and a chunk
    afterwards compile no program, and the thread is gone; a compile
    that fails in the thread fails the warm-up."""
    m, cache, rows = _seated_round(round_model, ROUND, 4, warm_chunk=4)
    assert not [t for t in threading.enumerate()
                if t.name == "compile-beside-warmup"]
    assert ("suffix", 4, PAGE, m.top_p, m.temp) in m._paged_progs
    before = m.compile_count()
    assert before >= len(m.suffix_buckets) + 2
    m.paged_append_prefill_rows(cache, rows[1:])
    m.paged_append_prefill(cache, np.ones((3 * PAGE + 2,), np.int32), 0)
    m.paged_decode_chunk(cache, np.ones((4,), np.int32), 4)
    assert m.compile_count() == before and cache.lengths.min() > 48

    def refused(*a):
        raise RuntimeError("the compiler said no")
    cold = afmoe.WindowCompletionModel(m.cfg, params=m.params)
    cold._paged_progs[("suffix", 4, PAGE, cold.top_p, cold.temp)] = \
        type("Refusing", (), {"lower": refused})()
    with pytest.raises(RuntimeError, match="said no"):
        cold.warmup_paged(cold.init_paged(4, page=PAGE, pool_pages=40,
                                          window_pool_pages=24), chunk=4)
