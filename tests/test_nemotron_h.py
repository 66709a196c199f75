"""models/nemotron_h.py — ONE mixer a layer (Mamba-2 state-space layers
with state slots, un-gated relu^2 experts, grouped-query attention
without positions) served through the paged lane, held to the
benchmark's plain reference (benchmark/reference/ssm_gqa_moe_block.py:
token-by-token scan, float32, no kernels) on LOGITS.

Tolerances.  The program here runs in float32 over the recipe's own
bfloat16-rounded weights (the reference restates the recipe, rounding
included), so program and reference differ by summation order alone:
2e-4 on logits of unit spread; through the Pallas kernels in interpret
mode the same."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_nemotron_h as RN
from libsplinter_tpu import Store
from libsplinter_tpu.engine import completer as C
from libsplinter_tpu.engine.client import submit_completion
from libsplinter_tpu.models import kda, lfm2, mla, nemotron_h as NH
from libsplinter_tpu.models.moe import router_bias_swaps

PAGE = 16
IDS = np.random.default_rng(0).integers(3, 500, 120).astype(np.int32)
TOL = 2e-4
R = RN.reference()


@pytest.fixture(scope="module")
def described(tmp_path_factory):
    """(float32 config, seed, the recipe's weights as float32)."""
    cfg16, seed = mla.load_model_description(
        RN.describe(tmp_path_factory.mktemp("nemotron")), max_len=256)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), NH.init_params(cfg16, seed))
    return dataclasses.replace(cfg16, dtype=jnp.float32), seed, params


@pytest.fixture(scope="module")
def model(described):
    cfg, seed, params = described
    return NH.SsmCompletionModel(cfg, seed=seed, params=params, temp=0.0)


@pytest.fixture(scope="module")
def ref_logits(described):
    """The reference's full forward pass over IDS, every position."""
    taps = []
    logits = R.forward_logits(RN.ARCH, RN.SHARE, described[1], [IDS],
                              [list(range(len(IDS)))], block=16,
                              taps=taps)[0]
    return logits, taps


def _decode_logits(m, cache, row, token):
    toks = np.full((cache.batch,), -1, np.int32)
    toks[row] = token
    m.audit_seat(0, row)
    pend = m.paged_decode_chunk_async(cache, toks, 1)
    pend.block()
    m.audit_seat(0, -1)
    return np.asarray(pend.audit)[0, 0]


# ---------------------------------------------------------- the loader

def test_description_loader_fills_the_config(described, tmp_path):
    cfg = described[0]
    assert isinstance(cfg, NH.SsmMoeConfig) and described[1] == 5
    assert cfg.kinds == tuple(NH.PATTERN[c] for c in "MEMEM*EMEMEM*")
    assert cfg == NH.SsmMoeConfig.tiny(model_layers=14, max_len=256,
                                       dtype=jnp.float32)
    assert (cfg.d_inner, cfg.conv_width) == (32, 32 + 2 * 2 * 16)
    assert mla.completion_model_class(cfg) is NH.SsmCompletionModel
    whole, _ = mla.load_model_description(
        RN.describe(tmp_path, share={"layers": 14}), max_len=64)
    assert whole.layers == 14 and whole.kinds[-1] == "moe"
    part, _ = mla.load_model_description(
        RN.describe(tmp_path, share={"experts": [2, 4],
                                     "vocab": [128, 256]}), max_len=64)
    assert (part.experts_first, part.experts_held, part.vocab_first,
            part.vocab_size) == (2, 4, 128, 256)


@pytest.mark.parametrize("bad, match", [
    ({"hybrid_override_pattern": "MEMEM*EMEMEM-E"}, "dense '-' layer"),
    ({"hybrid_override_pattern": "MEMEM*"}, "hybrid_override_pattern"),
    ({"n_group": 2}, "group-limited routing"),
    ({"topk_group": 2}, "group-limited routing"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"layer_norm_epsilon": 1e-6}, "layer_norm_epsilon"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"hidden_act": "silu"}, "unknown architecture key"),
])
def test_description_loader_rejects(tmp_path, bad, match):
    with pytest.raises(ValueError, match=match):
        mla.load_model_description(RN.describe(tmp_path, arch=bad))


@pytest.mark.parametrize("flags, match", [
    (["--kv-dtype", "int8"], "--kv-dtype int8"),
    (["--kv-tier-pages", "4"], "--kv-tier-pages"),
    (["--phase", "prefill"], "--phase prefill"),
    (["--tp", "2"], "--tp 2"),
    (["--ep", "2"], "--ep 2"),
    (["--draft-layers", "2"], "--draft-layers"),
    (["--weights", "x.gguf"], "--weights x.gguf"),
    (["--quantized"], "--quantized"),
])
def test_main_refuses_what_the_model_cannot_serve(tmp_path, flags, match):
    """The typed refusals of the other --model families, one message
    an option (state: kimi's; the page group: lfm2's)."""
    with pytest.raises(SystemExit) as ex:
        C.main(["--store", "/spt-never-opened", "--continuous",
                "--model", RN.describe(tmp_path), *flags])
    assert "unsupported_option" in str(ex.value)
    assert "SsmCompletionModel" in str(ex.value)
    assert match in str(ex.value)
    mine = NH.SsmCompletionModel.refused_options
    theirs = kda.HybridCompletionModel.refused_options
    assert mine["kv_tier_pages"] == theirs["kv_tier_pages"]
    assert mine["phase"] == theirs["phase"]
    assert set(mine) == set(lfm2.ConvCompletionModel.refused_options)


# ------------------------------------------------------- the programs

def test_cache_keeps_state_beside_one_page_group(model):
    cfg = model.cfg
    cache = model.init_paged(3, page=PAGE, pool_pages=16,
                             state_snapshots=2)
    # rows 0-2, snapshots 3-4, the spare 5; six state-space layers of
    # (4 x 8 x 16 f32 + 3 x 96) a slot; K and V of the two attention
    # layers in ONE group, a token a row; the five expert layers nothing
    assert (cache.state_slots, cache.state_spare) == (6, 5)
    assert len(cache.states) == 6 and cache.paged_layers == 1
    assert [tuple(a.shape) for a in cache.states[0]] \
        == [(6, 4, 8, 16), (6, 3, 96)]
    assert cache.states[0][0].dtype == jnp.float32
    assert [tuple(p[0].shape) for p in cache.pools] \
        == [(17, 2, 2, PAGE, 16)] * 2
    assert cache.state_slot_bytes == 6 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert model.suffix_buckets == (16, 32, 64, 128)
    assert model.join_rungs(cache) == (1,) and model.snap_granule == 16
    # three audit lanes, by the answer's budget as a share of the
    # daemon's (96 and 192 of 512)
    assert [model.audit_lane(0, 9, b / 512) for b in (64, 96, 97, 191,
                                                      192, 512)] \
        == [0, 0, 1, 1, 2, 2] and model.audit_lanes == 3


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "pallas-interpret"])
def test_prefill_then_decode_through_pages_and_state(interpret, model,
                                                     ref_logits):
    """41 prompt tokens (not whole chunks, not whole pages) then 5
    teacher-forced decode steps, against the reference's ONE full
    forward pass; the slot's state is the scan's."""
    m = model if not interpret else NH.SsmCompletionModel(
        model.cfg, params=model.params, temp=0.0, interpret=True)
    cache = m.init_paged(2, page=PAGE, pool_pages=16, state_snapshots=1)
    want, _ = ref_logits
    got = m.paged_prefill_row(cache, IDS[:41], 1)
    np.testing.assert_allclose(got, want[40], atol=TOL)
    for t in range(41, 46):
        np.testing.assert_allclose(_decode_logits(m, cache, 1, IDS[t]),
                                   want[t], atol=TOL)
    assert cache.lengths[1] == 46 and cache.lengths[0] == 0
    assert m.attn_work["ssd_prefill_tokens"] >= 41 \
        and m.attn_work["ssd_decode_rows"] >= 5


def test_the_slot_holds_the_scans_state(model, ref_logits):
    cache = model.init_paged(2, page=PAGE, pool_pages=16,
                             state_snapshots=1)
    model.paged_prefill_row(cache, IDS, 0)
    _, taps = ref_logits
    for (s, _), want in zip(cache.states, taps):
        np.testing.assert_allclose(s[0], want[0], atol=2e-5)


def test_a_snapshot_written_at_n_snap_and_restored(model, ref_logits):
    """Row 0 prefills 53 tokens and leaves the state after 48 in a
    snapshot slot; row 1 maps its three full pages, restores the
    snapshot and prefills the last five: the same logits, and then the
    same decode.  The snapshot is what a cold prefill of 48 leaves."""
    want, _ = ref_logits
    cache = model.init_paged(3, page=PAGE, pool_pages=24,
                             state_snapshots=2)
    slot = cache.alloc_state_slot()
    whole = model.paged_prefill_row(cache, IDS[:53], 0, snap_at=48,
                                    snap_slot=slot)
    model.paged_prefill_row(cache, IDS[:48], 2)
    for s, conv in cache.states:
        np.testing.assert_allclose(s[slot], s[2], atol=1e-6)
        np.testing.assert_array_equal(conv[slot], conv[2])
    cache.map_shared(1, [int(b) for b in cache.tables[0, :3]])
    cache.lengths[1] = 48
    model.state_restore(cache, slot, 1)
    resumed = model.paged_append_prefill(cache, IDS[48:53], 1)
    np.testing.assert_allclose(resumed, whole, atol=2e-5)
    np.testing.assert_allclose(resumed, want[52], atol=TOL)
    np.testing.assert_allclose(_decode_logits(model, cache, 1, IDS[53]),
                               want[53], atol=TOL)
    with pytest.raises(ValueError, match="whole chunks"):
        model.paged_append_prefill(cache, IDS[53:60], 1, snap_at=56,
                                   snap_slot=slot)


def test_a_cold_seat_into_a_used_slot_starts_from_zero(model, ref_logits):
    """A row another prompt just left: the newcomer's logits are the
    reference's — and without the zeroing (what benchmark/sabotage
    plants) they are not."""
    want, _ = ref_logits
    cache = model.init_paged(1, page=PAGE, pool_pages=16,
                             state_snapshots=1)
    model.paged_prefill_row(cache, IDS[60:117][::-1].copy(), 0)
    _decode_logits(model, cache, 0, 7)
    cache.free_row(0)
    zeroed = model.attn_work["state_zeroed"]
    got = model.paged_prefill_row(cache, IDS[:41], 0)
    np.testing.assert_allclose(got, want[40], atol=TOL)
    assert model.attn_work["state_zeroed"] == zeroed + 1
    cache.free_row(0)
    model.paged_prefill_row(cache, IDS[60:117][::-1].copy(), 0)
    cache.free_row(0)
    cache.lengths[0] = 0
    stale = model.paged_append_prefill(cache, IDS[:41], 0)
    assert np.abs(stale - want[40]).max() > 20 * TOL


# ------------------------------------------------------------ the share

def test_eight_shares_and_the_shared_expert_add_up_to_the_layer(
        described):
    """The routed parts of all the shares plus the shared expert
    counted once are the uncut layer — in the reference, and each
    share's program against its reference."""
    cfg, seed, params = described
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.standard_normal((24, cfg.hidden)), jnp.float32)
    i = 1                                   # the first expert layer
    whole = R.expert_mixer(RN.ARCH, seed, i, [y], 0, 8)[0]
    shared = R.expert_mixer(RN.ARCH, seed, i, [y], 0, 0)[0]
    parts = [R.expert_mixer(RN.ARCH, seed, i, [y], e, 1, shared=False)[0]
             for e in range(8)]
    np.testing.assert_allclose(shared + sum(parts), whole, atol=2e-5)
    assert float(jnp.abs(parts[3]).max()) > 1e-3
    # the program's expert mixer over experts 2..5
    sub = dataclasses.replace(cfg, experts_first=2, experts_held=4)
    lp = dict(params["layers"][i])
    lp["exp_up"], lp["exp_down"] = lp["exp_up"][2:6], lp["exp_down"][2:6]
    assert lp["exp_up"].shape == (4, 32, 64)      # an output column a row
    got, slots, counts = NH._experts(sub, lp, y[None],
                                     jnp.ones((1, 24), bool), False)
    want = R.expert_mixer(RN.ARCH, seed, i, [y], 2, 4)[0]
    np.testing.assert_allclose(got[0], want, atol=TOL)
    assert int(slots.sum()) <= 24 * 2 and int(counts[0]) <= 4


def test_the_seeded_bias_moves_a_tenth_of_the_selections():
    """At the PUBLISHED router (2,688 -> 128 experts, 6 a token,
    sigmoid scores of unit-scale logits) the seeded bias changes 5-20%
    of the selections: the mechanism is served."""
    H, E, T = 2688, 128, 512
    x = jnp.asarray(np.random.default_rng(5).standard_normal((T, H)),
                    jnp.float32)
    router = mla.seed_tensor(7, "layers.1.router", (H, E),
                             1.0 / np.sqrt(H), jnp.float32)
    bias = mla.seed_tensor(7, "layers.1.router_bias", (E,), NH.BIAS_STD,
                           jnp.float32)
    swaps = int(router_bias_swaps(x, router, bias, jnp.ones((T,), bool),
                                  top_k=6, score="sigmoid"))
    assert 0.05 < swaps / (T * 6) < 0.20, swaps / (T * 6)


def test_how_long_a_seeded_head_remembers():
    """The recipe's decay at the PUBLISHED widths (64 heads a layer, 12
    layers; dt = softplus(unit-scale projection + dt_bias)): the share
    of heads whose decay leaves >= 1/e after 128 and after 1,024
    tokens.  Few heads remember a prompt: PERF.md section 7 has the
    reading and what it means for the sabotage's margin."""
    cfg = NH.SsmMoeConfig.tiny(ssm_heads=64, hidden=8, ssm_groups=8)
    rng = np.random.default_rng(6)
    keep128, keep1k = [], []
    for i in range(12):
        p = f"layers.{2 * i}."
        u = [mla.seed_tensor(3, p + n, (64,), 1 / np.sqrt(12), jnp.float32,
                             0.5) for n in ("dt_bias", "a_log")]
        step = jnp.maximum(jnp.exp(u[0] * np.log(100.0) + np.log(1e-3)),
                           cfg.time_step_floor)
        dt_bias = step + jnp.log(-jnp.expm1(-step))
        a = 1.0 + 15.0 * u[1]
        # a token's decay rate: the mean over unit-scale projections
        rate = np.asarray(jnp.mean(jax.nn.softplus(
            jnp.asarray(rng.standard_normal((4096, 1)), jnp.float32)
            + dt_bias), 0) * a)
        keep128.append(np.mean(rate * 128 <= 1.0))
        keep1k.append(np.mean(rate * 1024 <= 1.0))
    assert 0.005 < np.mean(keep128) < 0.12, np.mean(keep128)
    assert np.mean(keep1k) < 0.01, np.mean(keep1k)


# ------------------------------------------------- the continuous lane

@contextlib.contextmanager
def serving(tmp_path, model, **kw):
    name = f"/spt-nemo-{tmp_path.name}"
    Store.unlink(name)
    st = Store.create(name, nslots=128, max_val=2048, vec_dim=8)
    audit_dir = str(tmp_path / "audit")
    comp = C.Completer(st, model=model, max_new_tokens=6, template="none",
                       batch_cap=2, page_size=PAGE, pool_pages=32,
                       audit={"dir": audit_dir, "every": 1}, **kw)
    comp.attach()
    th = threading.Thread(target=comp.run_continuous, daemon=True,
                          kwargs={"idle_timeout_ms": 20})
    th.start()

    def ask(i: int, prompt: str, **skw):
        """-> (prompt ids, generated ids, the logits behind each)."""
        out = submit_completion(st, f"q/{i}", prompt, timeout_ms=240_000,
                                **skw)
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


def test_fresh_prompts_with_their_own_budgets_through_run_continuous(
        tmp_path, model, described):
    """The cell's traffic through the daemon's own loop: fresh prompts
    into one row after another, each with its own budget; a prompt that
    extends an earlier one resumes from its snapshot.  Every logit is
    the reference's for the whole prompt served cold."""
    seed = described[1]
    a = _text(40, 1)                          # + BOS = 41 tokens
    b = _text(75, 2)
    a2 = a[:31] + _text(20, 3)                # shares a's first 2 pages
    before = dict(model.attn_work)            # the fixture's, so far
    with serving(tmp_path, model, state_snapshots=3) as (comp, ask):
        for i, (t, n) in enumerate(((a, 3), (b, None), (a2, 5))):
            prompt, toks, logits = ask(
                i, t, **({} if n is None else {"max_new_tokens": n}))
            assert len(toks) == (n or 6)
            seq = np.concatenate([prompt, toks[:-1]])
            want = R.forward_logits(
                RN.ARCH, RN.SHARE, seed, [seq],
                [list(range(len(prompt) - 1, len(seq)))], block=16)[0]
            np.testing.assert_allclose(logits, want, atol=TOL)
        s = comp.stats
        assert (s.state_restores, s.budgeted_requests) == (1, 2)
        assert (s.answers_finished, s.answer_tokens) == (3, 14)
        comp.publish_stats()
        hb = json.loads(comp.store.get(C.P.KEY_COMPLETE_STATS)
                        .rstrip(b"\0"))
        # the two cold seats zeroed their slots (a2 restored a's)
        assert hb["state_zeroed"] - before["state_zeroed"] == 2 \
            and hb["state_restores"] == 1
        assert hb["ssd_prefill_tokens"] - before["ssd_prefill_tokens"] \
            == 41 + 76 + (52 - 32)
        assert hb["ssd_decode_rows"] - before["ssd_decode_rows"] >= 14 - 3
        assert hb["answer_tokens"] == 14 and hb["answers_finished"] == 3
        assert 0 < hb["experts_live"] <= hb["expert_slots"]
        assert hb["prefill_experts_live"] > 0 \
            and hb["prefill_expert_slots"] > 0
        assert {"paged_chunk", "suffix_prefill", "state_copy",
                "state_zero"} <= set(hb["devtime"])
