"""The mixed sliding-window / global stack (models/afmoe.py): grouped
key/value pages in TWO page groups, a window group whose pages go back
as a row slides, prefix hits that resume on a held tail — model, cache
manager, prefix tree and the continuous lane, on the CPU at tiny
widths whose window (32 tokens, pages of 16) is SMALLER than the
contexts tested, against the plain float32 reference
(tests/reference_afmoe.py).

Tolerances.  The model here is built in float32, so program and
reference differ by summation order alone: 2e-4 absolute on logits of
spread ~1 (measured 1e-6..3e-6).  The Pallas kernel in interpret mode
rounds its probabilities to the pool's dtype as it does on the chip."""
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

import reference_afmoe as R
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.engine.prefix_cache import PrefixCache
from libsplinter_tpu.models import afmoe, mla
from libsplinter_tpu.models.moe import sparse_moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = afmoe.WindowMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                                 experts_held=4)
IDS = np.random.default_rng(0).integers(3, CFG.vocab_size, 160) \
    .astype(np.int32)
PAGE = 16
POISON = 1e30          # finite: a masked key times 0 stays 0

# a tiny description in Trinity-Mini's published keys (the shape of
# benchmark/configs/trinity-mini-26b-a3b-ep8.json's model)
ARCH = {"model_type": "afmoe", "hidden_act": "silu",
        "tie_word_embeddings": False, "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "num_dense_layers": 1,
        "num_hidden_layers": 8, "global_attn_every_n_layers": 4,
        "layer_types": (["sliding_attention"] * 3
                        + ["full_attention"]) * 2,
        "sliding_window": 32, "mup_enabled": True, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
        "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
        "load_balance_coeff": 0.001, "use_grouped_mm": True,
        "max_position_embeddings": 131072, "vocab_size": 4096}
SHARE = {"layers": 8, "dense_layers": 1, "experts": [4, 8],
         "vocab": [0, 512]}


@pytest.fixture(scope="module")
def model():
    return afmoe.WindowCompletionModel(CFG, seed=3)


@pytest.fixture(scope="module")
def ref_logits(model):
    return R.forward(CFG, model.params, IDS)


def _poison_free(cache) -> None:
    """Every window-group page no row holds, and the trash block,
    filled with POISON: a read of a page that went back would show."""
    w = cache.window
    idle = jnp.asarray([0] + list(w._free), jnp.int32)
    for pool in w.pools:
        pool[0] = pool[0].at[idle].set(POISON)


def _teacher_forced(m, cache, row, tokens):
    """Feed `tokens` one decode step each; returns the logits behind
    every NEXT token, (len(tokens), V)."""
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


# ------------------------------------------- prefill, decode, the window

@pytest.mark.parametrize("case, prompt, steps, interpret", [
    ("cold prompt past the window", 100, 4, False),
    ("decode across a page boundary of the window", 60, 24, False),
    ("a prompt shorter than the window", 20, 6, False),
    ("the kernels themselves, interpreted", 60, 10, True),
])
def test_prefill_then_decode_through_the_two_groups(
        case, prompt, steps, interpret, ref_logits):
    """A prompt from an empty row, a piece at a time, then decode steps
    fed the sequence's own tokens: every logit is the full forward
    pass's, while the window group gives back what the row slides past
    — and what it gave back is poisoned."""
    m = afmoe.WindowCompletionModel(
        CFG, params=afmoe.init_params(CFG, 3), interpret=interpret)
    cache = m.init_paged(2, page=PAGE, pool_pages=32, window_pool_pages=12)
    w = cache.window
    assert w.span == 2 + 2 + 5 and w.window_pages == 2
    tol = 2e-4 if not interpret else 2e-2
    lg = m.paged_prefill_row(cache, IDS[:prompt], 1)
    np.testing.assert_allclose(lg, ref_logits[prompt - 1], atol=tol)
    _poison_free(cache)
    got = _teacher_forced(m, cache, 1, IDS[prompt: prompt + steps])
    np.testing.assert_allclose(got, ref_logits[prompt: prompt + steps],
                               atol=tol)
    length = prompt + steps
    assert cache.lengths[1] == length
    # the window group holds the live span and nothing behind it; the
    # global group every page
    first = max(0, length - CFG.window + 1) // PAGE
    assert (w._lo[1], w._hi[1]) == (first, -(-length // PAGE))
    assert w.released == first and not w.tables[1, :first].any()
    assert (w.tables[1, first: w._hi[1]] > 0).all()
    assert len(cache._owned[1]) == -(-length // PAGE)
    assert w.used_pages <= w.span
    cache.free_row(1)
    assert w.free_pages == 12 and cache.free_pages == 32


def test_the_period_under_the_scan_is_the_unrolled_stack(model,
                                                         ref_logits):
    """The same weights as ONE unrolled stack (a pattern that does not
    repeat evenly) give the scanned stack's logits: the scan changes
    how the program is compiled, not what it computes."""
    assert CFG.plan == (4, 4, 1)
    flat = R.layer_list(CFG, model.params)
    cut = afmoe.WindowMoeConfig.tiny(
        dtype=jnp.float32, experts_first=2, experts_held=4,
        kinds=CFG.kinds[:7], model_layers=8)
    assert cut.plan == (4, 4, 0)
    params = {**model.params, "head": flat[:4], "periods": [],
              "tail": flat[4:7]}
    m = afmoe.WindowCompletionModel(cut, params=params)
    cache = m.init_paged(1, page=PAGE, pool_pages=16, window_pool_pages=10)
    lg = m.paged_prefill_row(cache, IDS[:70], 0)
    np.testing.assert_allclose(lg, R.forward(cut, params, IDS[:70])[-1],
                               atol=2e-4)


# ------------------------------------------------- the expert share

def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Under THIS router's settings — sigmoid scores over all 16
    experts, plain top-4, renormalised over the selection (route_norm),
    x route_scale 2.826 — the 8 shares of an expert layer, the shared
    expert counted once, add up to the layer with every expert held."""
    rng = np.random.default_rng(5)
    H, M, E, k = 32, 16, 16, 4
    x = jnp.asarray(rng.standard_normal((24, H)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((H, E)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, M)) / 6, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, M, H)) / 4, jnp.float32)
    shared = tuple(jnp.asarray(rng.standard_normal(s) / 5, jnp.float32)
                   for s in ((H, M), (H, M), (M, H)))
    kw = dict(top_k=k, score="sigmoid", norm_topk=True, scale=2.826)
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
    path = str(tmp_path / "model.json")
    with open(path, "w") as f:
        json.dump({"architecture": arch, "share": share, "seed": 7,
                   **extra}, f)
    return path


def test_description_loader_reads_the_afmoe_key_set(tmp_path):
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=256)
    assert isinstance(cfg, afmoe.WindowMoeConfig) and seed == 7
    assert cfg.kinds == ("window", "window", "window", "full") * 2
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.window) \
        == (4, 2, 16, 32)
    assert (cfg.n_routed_experts, cfg.experts_first, cfg.experts_held,
            cfg.top_k) == (16, 4, 8, 4)
    assert cfg.routed_scaling_factor == 2.826 and cfg.norm_topk_prob
    assert cfg.mup and cfg.dense_layers == 1 and cfg.model_layers == 8
    assert mla.completion_model_class(cfg) is afmoe.WindowCompletionModel
    full, window = cfg.page_layout(16)
    assert (full.window, full.layers, window.window, window.layers) \
        == (0, 2, 32, 6)
    assert full.pools[0] == ("k", (2, 2, 16, 16))
    # the share may keep a stage: the kinds follow the kept layers
    cut, _ = mla.load_model_description(_describe(
        tmp_path, share={**SHARE, "layers": 4}), max_len=256)
    assert cut.kinds == ("window", "window", "window", "full")
    assert cut.model_layers == 8


@pytest.mark.parametrize("bad, match", [
    ({"kv_lora_rank": 32}, "unknown architecture key"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling is not served"),
    ({"n_group": 2}, "group-limited routing"),
    ({"num_limited_groups": 2}, "group-limited routing"),
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"hidden_act": "gelu"}, "SwiGLU"),
    ({"num_shared_experts": 2}, "0 or 1 shared expert"),
    ({"global_attn_every_n_layers": 2}, "layer_types must name"),
    ({"layer_types": ["full_attention"] * 7}, "layer_types must name"),
], ids=lambda v: next(iter(v)) if isinstance(v, dict) else None)
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(_describe(tmp_path, {**ARCH, **bad}))


def test_a_published_key_left_out_is_an_error(tmp_path):
    arch = {k: v for k, v in ARCH.items() if k != "sliding_window"}
    with pytest.raises(ValueError, match="lacks 'sliding_window'"):
        mla.load_model_description(_describe(tmp_path, arch))


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "page codecs know one pool a layer"),
    (["--kv-tier-pages", "8"], "carries one page group"),
    (["--phase", "prefill"], "carries one page group"),
    (["--tp", "2"], "not sharded on their kv-head axis"),
    (["--ep", "2"], "told the experts it holds"),
    (["--draft-layers", "2"], "speculative wrapper"),
    (["--weights", "x.safetensors"], "seeded weights"),
    (["--quantized"], "int8 weight residencies"),
    (["--state-snapshots", "4"], "keep no recurrent state"),
], ids=lambda v: v[0] if isinstance(v, list) else None)
def test_main_refuses_what_the_window_model_cannot_serve(
        tmp_path, flags, match):
    with pytest.raises(SystemExit, match=match) as ex:
        C.main(["--store", f"/spt-afmoe-refuse-{os.getpid()}", "--model",
                _describe(tmp_path), "--continuous", *flags])
    assert str(ex.value).startswith("unsupported_option: ")


def test_window_pool_pages_is_for_a_model_with_a_window(tmp_path):
    with pytest.raises(SystemExit, match="mix sliding-window"):
        C.main(["--store", f"/spt-afmoe-refuse-{os.getpid()}",
                "--continuous", "--window-pool-pages", "8"])


# ------------------------------------------- the tree's window pages

def _tree(pool_pages=24, window_pool_pages=12, batch=3):
    m = afmoe.WindowCompletionModel(CFG, params={})
    cache = m.init_paged(batch, page=PAGE, pool_pages=pool_pages,
                         window_pool_pages=window_pool_pages)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    return cache, pc


def _seat(cache, pc, ids, row):
    """What admit() does to the tables for a prompt, without a model:
    map the hit and its tail, walk the suffix a piece at a time
    (ensure, advance, release), insert.  Returns (match, cut)."""
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


def test_a_hit_resumes_where_the_windows_tail_is_held():
    """Window 32 = 2 pages.  A session's chain keeps window pages on
    its newest tail only; a hit maps every global page and the 2-page
    tail; a prompt that leaves the chain where no tail is held gives
    the match up."""
    cache, pc = _tree()
    w = cache.window
    s1 = IDS[:100]                                  # 6 full pages
    assert _seat(cache, pc, s1, 0) == (0, 0)
    # the row slid: of its 7 window pages it holds the last 3 (first
    # live page 4), and the tree filed the two full ones among them
    assert (w._lo[0], w._hi[0]) == (4, 7) and pc.window_pages() == 2
    assert pc.shared_pages() == 6
    cache.free_row(0)
    assert pc.window_evictable_count() == 2 and w.free_pages == 10
    # turn 2 = turn 1 + 40 tokens: resumes at 96 on pages 4, 5
    s2 = IDS[:140]
    bids, match, _ = pc.lookup_tiered(s2)
    assert match == 96 and len(pc.last_window) == 2 \
        and pc.last_window_cut == 0
    assert _seat(cache, pc, s2, 1) == (96, 0)
    # the superseded tail went: the tree holds the newest two again
    assert pc.window_pages() == 2 and pc.stats.window_evictions == 2
    assert pc.shared_pages() == 8
    # a prompt that shares 5 pages and then differs would resume at
    # 80, whose window lies on pages 3, 4: the chain let page 3 go
    other = np.concatenate([IDS[:80], IDS[:30][::-1]])
    bids, match, _ = pc.lookup_tiered(other)
    assert (bids, match) == ([], 0) and pc.last_window_cut == 80
    cache.free_row(1)
    # reclaiming the tail's window pages leaves the global pages and
    # cuts the next hit
    assert pc.reclaim_window(5) == 2 and pc.window_pages() == 0
    assert pc.shared_pages() == 8 and w.free_pages == 12
    assert pc.lookup_tiered(s2)[1] == 0 and pc.last_window_cut == 128


def test_a_branch_keeps_its_tail_for_every_continuation():
    """Two prompts share a 5-page document and go on differently for
    more than a page each: once the tree has seen the branch it keeps
    the document's tail, and both continue to resume on it."""
    cache, pc = _tree()
    doc = IDS[:80]
    a = np.concatenate([doc, IDS[100:140]])
    b = np.concatenate([doc, IDS[120:160]])
    _seat(cache, pc, a, 0)
    cache.free_row(0)
    # b resumes at the document's end only if pages 3, 4 are held:
    # a's insert shed page 3 (the chain had no branch yet) — b pays
    # the prefill once and files the tail again, under a branch now
    match_b, cut_b = _seat(cache, pc, b, 1)
    assert (match_b, cut_b) == (0, 80)
    cache.free_row(1)
    assert _seat(cache, pc, a, 0)[1] == 0
    cache.free_row(0)
    for prompt, row in ((a, 0), (b, 1), (a, 2)):
        match, cut = _seat(cache, pc, prompt, row)
        assert match >= 80 and cut == 0
    cache.reset()


def test_a_never_hit_page_goes_before_one_that_served_a_hit():
    """Eviction in both groups prefers pages that never served a hit:
    fresh prompts passing through do not push a session's tail out."""
    cache, pc = _tree(pool_pages=24, window_pool_pages=12)
    sess = IDS[:64]
    _seat(cache, pc, sess, 0)
    cache.free_row(0)
    _seat(cache, pc, IDS[:80], 0)                   # the session's turn 2
    cache.free_row(0)
    held = set(pc._by_wbid)
    for k in range(6):                              # fresh one-pagers
        fresh = (IDS[:40] + 7 * (k + 1)) % CFG.vocab_size
        _seat(cache, pc, fresh, 1)
        cache.free_row(1)
    assert cache.window.free_pages < 6              # the tree squats
    # demand more than the free list holds: fresh pages go, the tail stays
    assert cache.ensure(2, 9 * PAGE)
    assert held <= set(pc._by_wbid)
    assert pc.lookup_tiered(IDS[:120])[1] == 80
    cache.reset()


# ------------------------------------------------- the continuous lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-afmoe-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    comp = C.Completer(st, model=model, max_new_tokens=4, template="none",
                       batch_cap=2, page_size=PAGE, pool_pages=32,
                       window_pool_pages=14,
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


def test_a_session_resumes_on_its_tail_through_run_continuous(tmp_path,
                                                              model):
    """Turns of one growing session and a fresh prompt between them,
    through the daemon's own loop: a cold prompt past the window, a
    prefix hit on a held tail, a hit cut because the tail was taken
    away — every logit the reference's for the whole prompt."""
    base = _text(70, 1)                       # + BOS = 71 tokens
    turns = [base, base + _text(40, 2), base + _text(40, 2) + _text(30, 3)]
    with serving(tmp_path, model) as (comp, ask):
        _against_reference(model, *ask(0, turns[0]))
        s, w = comp.stats, comp._paged_cache.window
        assert (s.window_resumes, s.window_cut_tokens) == (0, 0)
        assert w.released >= 2                # 71 + 4 tokens slid past 32
        _against_reference(model, *ask(1, _text(25, 9)))    # a short one
        _against_reference(model, *ask(2, turns[1]))
        assert (s.window_resumes, s.prefix_tokens) == (1, 64)
        assert s.window_cut_tokens == 0
        # the tail goes (another tenant's pressure, here by hand): the
        # next turn finds its global pages and no window to resume on
        pc = comp.prefix_cache
        assert pc.reclaim_window(99) >= 2
        _against_reference(model, *ask(3, turns[2]))
        assert s.window_resumes == 1 and s.window_cut_tokens == 96
        assert s.prefix_tokens == 64          # served from nothing
        # a turn's suffix of three pages is wider than the rows program
        # (one page): every join here was a round of one
        assert model.join_rungs(comp._paged_cache) == (1, 2)
        assert (s.join_programs, s.join_rows) == (4, 4)
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        assert hb["window_resumes"] == 1 and hb["window_cut_tokens"] == 96
        assert hb["window_pages_released"] == w.released > 6
        assert hb["window_pool_pages"] == 14
        assert hb["window_pages_live"] == 0 == hb["global_pages_live"]
        assert hb["window_pages_used"] == pc.window_pages() > 0
        assert "state_restores" not in hb
        # no latent pages: the latent decode kernel's gauge is absent
        assert "latent_decode_pages_per_step" not in hb
        assert {"paged_chunk", "suffix_prefill"} <= set(hb["devtime"])
        # every page in either group: free, or the tree's at zero refs
        cache = comp._paged_cache
        assert w.free_pages + pc.window_evictable_count() == 14
        assert cache.free_pages + pc.evictable_count() == 32


def test_a_fully_cached_prompt_replays_its_last_token_in_both_groups(
        tmp_path, model):
    """A prompt of whole pages asked twice: the second maps every page,
    copies the tail page of EACH group before the replayed token is
    appended, and answers as the first did."""
    prompt = _text(63, 5)                     # + BOS = 64 = 4 pages
    with serving(tmp_path, model) as (comp, ask):
        _against_reference(model, *ask(0, prompt))
        before = comp.prefix_cache.stats.cow_copies
        out = submit_completion(comp.store, "q/again", prompt,
                                timeout_ms=240_000)
        assert isinstance(out, bytes) and out.startswith(prompt.encode())
        assert comp.stats.prefix_tokens == 64
        assert comp.prefix_cache.stats.cow_copies == before + 1
        assert comp.stats.window_resumes == 1
        w, pc = comp._paged_cache.window, comp.prefix_cache

        def unmapped():
            return w.free_pages + pc.window_evictable_count()
        # the answer is readable a moment before its row is freed
        for _ in range(250):
            if unmapped() == 14:
                break
            time.sleep(0.02)
        assert unmapped() == 14


def test_a_model_with_one_group_takes_the_path_it_took(tmp_path):
    """The shared cache manager and tree with ONE page group (the
    latent family): no window group, no window bookkeeping, the
    heartbeat without its gauges."""
    cfg = mla.LatentMoeConfig.tiny(dtype=jnp.float32)
    m = mla.LatentCompletionModel(cfg, seed=1)
    cache = m.init_paged(2, page=PAGE, pool_pages=16)
    assert cache.window is None and cache.release_window() == 0
    assert cache.window_cow_targets() == []
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    ids = IDS[:40] % cfg.vocab_size
    m.paged_prefill_row(cache, ids, 0)
    pc.insert(ids, cache, 0)
    bids, match, _ = pc.lookup_tiered(ids)
    assert match == 32 and pc.last_window == [] \
        and pc.last_window_cut == 0 and pc.window_pages() == 0


# ------------------------------------------------ the benchmark's copy

def _bench_reference():
    spec = importlib.util.spec_from_file_location(
        "bench_reference_window", os.path.join(
            REPO, "benchmark", "reference", "window_gqa_moe_block.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_two_copies_of_the_reference_agree(tmp_path):
    """benchmark/reference/window_gqa_moe_block.py (its own weights
    from the seed, queries in blocks, a sliding layer against the
    window's keys only) == tests/reference_afmoe.py on the program's
    tree; its float8 control and a window a page short do not."""
    cfg, seed = mla.load_model_description(_describe(tmp_path),
                                           max_len=256)
    params = afmoe.init_params(cfg, seed)
    seqs = [IDS[:100] % 512, IDS[5:33] % 512]
    pos = [[10, 99], [0, 27]]
    bench = _bench_reference()
    got = bench.forward_logits(ARCH, SHARE, seed, seqs, pos, block=32)
    for s, p, g in zip(seqs, pos, got):
        np.testing.assert_allclose(g, R.forward(cfg, params, s)[p],
                                   atol=1e-4)
    low = bench.forward_logits(ARCH, SHARE, seed, seqs[:1], pos[:1],
                               f8=True, block=32)
    assert bench.rel_err(low[0], got[0]).min() > 0.02
    late = bench.forward_logits(ARCH, SHARE, seed, seqs[:1], pos[:1],
                                block=32, late=PAGE)
    err = bench.rel_err(late[0], got[0])
    # position 10 has 11 keys: the shorter window changes nothing there
    assert err[0] < 1e-4 and err[1] > 0.02
