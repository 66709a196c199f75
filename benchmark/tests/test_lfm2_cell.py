"""The parts `lfm2-agent-loops-shared-system` brings: the payload's
rounds (an aligned turn, then a short tool result 1-8 tokens past the
boundary), the think-time loop against test_traffic.py's stand-in
call, work_moe against a hand count, the readers trace_moe and
trace_full_gqa on a small reduced capture (nothing to read where the
program has no such kernel, counters or keys), and the cell's
rehearsals on the CPU: sound, its float8 control, its sabotage
(`state_zeroed_at_restore`, the configuration's), and the traced one
printing the metrics that are its own.  (test_rehearse.py runs the
same three for every cell of BENCHMARK.json; here they are held to
WHICH comparison fails.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_cell.py -q"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, HERE)

import traffic as T                               # noqa: E402
import work                                       # noqa: E402
import work_gqa                                   # noqa: E402
import work_moe                                   # noqa: E402
from readers import trace_full_gqa, trace_moe     # noqa: E402
from test_traffic import StandIn                  # noqa: E402

CELL = "lfm2-agent-loops-shared-system"
CFG = json.load(open(os.path.join(
    BENCH_DIR, "configs", "lfm2-24b-a2b-ep1-stage0.json")))
MIX = json.load(open(os.path.join(BENCH_DIR, "traffic", f"{CELL}.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))


# ------------------------------------------------------------ the payload

def test_a_short_turn_lands_1_to_8_tokens_past_a_page_boundary():
    make = T.part("payloads", "agent_sessions").make
    spec = MIX["payload"]
    pay = make(spec, 2**31 + 40, None, {})
    page, n_sys = spec["page"], spec["system_tokens"]
    assert len(pay["script"]) == 96 and len(pay["system"]) == 16
    assert np.bincount(pay["tenant_of"]).tolist() == [6] * 16
    assert all(len(s) == n_sys == 64 * page for s in pay["system_ids"])
    n_short = 0
    for ends, short in zip(pay["ends"], pay["short"]):
        assert 64 <= ends[0] <= 384 and not short[0] and len(ends) >= 21
        adds = np.diff(ends)
        for k in np.nonzero(short)[0]:
            # the turn before brought the WHOLE prompt to whole pages,
            # by 64-191 tokens; this one adds 1-8
            assert (n_sys + ends[k - 1]) % page == 0
            assert 64 <= adds[k - 2] <= 191 and 1 <= adds[k - 1] <= 8
            n_short += 1
        plain = np.delete(adds, np.r_[np.nonzero(short)[0] - 1,
                                      np.nonzero(short)[0] - 2])
        assert plain.min() >= 64 and plain.max() <= 384
        # one short turn a round of eight
        assert abs(short[1:17].sum() - 2) <= 1
    assert n_short > 96 * 2
    # another seed: the same multiset of size rows, other contents
    other = make(spec, 7, None, {})
    rows = lambda p: sorted(tuple(np.diff(e)) for e in p["ends"])
    assert rows(other) == rows(pay)
    assert other["script"][0] != pay["script"][0]
    assert [tuple(e) for e in other["ends"]] \
        != [tuple(e) for e in pay["ends"]]


def test_think_time_loop_against_the_stand_in_call():
    loop = T.part("loops", "closed_clients_think")
    call = StandIn(ms=5.0)
    mix = {"clients": 4, "think_ms": [20, 40], "shape_seed": 40}
    ticks = []
    res = loop.run(call, mix, 0.6, seed=0, start_at=0, on_tick=ticks.append)
    assert res["failed"] == 0 and res["attempted"] == len(res["records"])
    assert {c for _, c in call.seen} == {0, 1, 2, 3}
    assert all(i % 4 == c for i, c in call.seen)
    # a cycle is the call's 5 ms + 20-40 ms of think time: 13-24 a
    # client in 0.6 s, against ~100 with no think time
    per = [sum(c == k for _, c in call.seen) for k in range(4)]
    assert all(12 <= n <= 25 for n in per), per
    starts = sorted(r["t"] for r in res["records"])
    # a request that began inside the window and ended after it is
    # attempted, not completed
    assert res["attempted"] - 4 <= res["completed"] <= res["attempted"]
    assert starts[-1] < 0.6
    # the same gaps for every seed (the seed must not change the work)
    again = StandIn(ms=5.0)
    res2 = loop.run(again, mix, 0.6, seed=99, start_at=0)
    assert abs(res2["attempted"] - res["attempted"]) <= 4
    # no think time: loops/closed_clients.py's rate
    fast = StandIn(ms=5.0)
    res3 = loop.run(fast, {**mix, "think_ms": [0, 0]}, 0.3, seed=0)
    assert res3["attempted"] > 4 * 30


# ------------------------------------------------------ work and readers

def test_work_moe_against_a_hand_count():
    """One expert layer's decode event of the cell: 96 rows x 4 slots
    over all 64 experts of 2,048 x 1,536."""
    flops, bytes_ = work_moe.expert_ffn(64, 384, 2048, 1536)
    # a slot: three products of 2 x 2,048 x 1,536
    assert flops == 384 * 3 * 2 * 2048 * 1536
    # a live expert's three matrices once, in bfloat16: 18,874,368 B;
    # a slot's rows in and out
    assert bytes_ == 64 * 18_874_368 + 384 * 2 * 3 * (2048 + 1536)
    # memory-bound on a v5e by a wide margin: 6 FLOP a byte
    assert flops / bytes_ < 10
    # an expert nobody chose earns nothing
    assert work_moe.expert_ffn(32, 384, 2048, 1536)[1] \
        < 0.51 * bytes_


def _ctx(ops, modules, start, end, cfg=CFG):
    return {"trace": {"ops": ops, "modules": modules}, "hb_start": start,
            "hb_end": end, "config": cfg, "peaks": PEAKS,
            "device": {"kind": "TPU v5 lite"}}


def _metric(name):
    return json.load(open(os.path.join(
        BENCH_DIR, "metrics", f"{name}.json")))["args"]


def test_trace_moe_reads_a_small_capture():
    """Two chunk programs of 8 steps and three suffix pieces in the
    capture; the grouped products' seconds put the whole at half its
    bound."""
    peak = work.peak_for(PEAKS, "TPU v5 lite")
    bw = peak["hbm_bytes_per_s"]
    layers, steps, pieces = 8, 16, 3
    t_step = layers * work_moe.expert_ffn(63, 360, 2048, 1536)[1] / bw
    # a piece of 200 suffix tokens: 800 slots a layer over 64 experts
    t_piece = layers * work_moe.expert_ffn(64, 800, 2048, 1536)[1] / bw
    bound = steps * t_step + pieces * t_piece
    hb0 = {"devtime": {"suffix_prefill": {"n": 5}}}
    hb1 = {"experts_live": 40 * layers * 63, "expert_slots": 40 * layers * 360,
           "decode_steps": 40, "prefill_experts_live": 10 * layers * 64,
           "prompt_tokens": 10 * 9000, "prefix_tokens": 10 * 8800,
           "devtime": {"suffix_prefill": {"n": 15}}}
    ops = {"gmm.3": bound, "gmm.17": bound, "fusion.9": 5.0,
           "gqa_window_decode.2": 1.0}
    mods = {"jit_lfm2_paged_chunk(12)": [2, 1.0],
            "jit_lfm2_suffix_prefill(4)": [3, 1.0]}
    got = trace_moe.read(_ctx(ops, mods, hb0, hb1),
                         **_metric("expert_ffn_roofline.lfm2"))
    assert got == pytest.approx(50.0, rel=1e-9)


def test_trace_full_gqa_reads_a_small_capture():
    """96 rows at 9,600 tokens, the configuration's TWO attention
    layers (its seven convolution layers are no event of this kernel),
    heads of 64 from hidden_size / num_attention_heads."""
    peak = work.peak_for(PEAKS, "TPU v5 lite")
    rows, ctx_len, steps = 96, 9600, 16
    flops, bytes_ = work_gqa.gqa_attention(rows * ctx_len, rows * ctx_len,
                                           rows, 32, 8, 64)
    assert bytes_ == rows * ctx_len * 2048 + rows * 2 * 32 * 64 * 2
    bound = steps * 2 * bytes_ / peak["hbm_bytes_per_s"]
    hb1 = {"decode_keys": steps * rows * ctx_len,
           "decode_rows": steps * rows, "decode_steps": steps}
    ops = {"gqa_window_decode.7": bound, "gqa_window_decode.9": bound,
           "gqa_window_stack.3": 4.0}
    mods = {"jit_lfm2_paged_chunk(12)": [2, 1.0],
            "jit_lfm2_suffix_prefill(4)": [6, 1.0]}
    got = trace_full_gqa.read(_ctx(ops, mods, {}, hb1),
                              **_metric("gqa_decode_roofline.lfm2"))
    assert got == pytest.approx(50.0, rel=1e-9)
    # the prefill form: 6 pieces of 200 live tokens over 9,000
    q, hit, calls = 200, 9000, 6
    keys = sum(hit + 1 + i for i in range(q))
    hb0 = {"devtime": {"suffix_prefill": {"n": 2}}}
    hb1 = {"prefill_keys": calls * keys, "prefill_kv": calls * (hit + q),
           "prompt_tokens": calls * (hit + q), "prefix_tokens": calls * hit,
           "devtime": {"suffix_prefill": {"n": 2 + calls}}}
    f, b = work_gqa.gqa_attention(keys, hit + q, q, 32, 8, 64)
    want = 100.0 * calls * 2 * max(b / peak["hbm_bytes_per_s"],
                                   f / peak["bf16_flops"]) / 4.0
    got = trace_full_gqa.read(_ctx(ops, mods, hb0, hb1),
                              **_metric("gqa_prefill_roofline.lfm2"))
    assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100


@pytest.mark.parametrize("case", ["no such kernel", "no counters",
                                  "another configuration", "no trace"])
def test_the_readers_find_nothing_where_there_is_nothing(case):
    """What the parent, or another family's cell, gives the readers:
    None, not an exception."""
    ops = {"gmm.3": 1.0, "gqa_window_decode.7": 1.0}
    mods = {"jit_lfm2_paged_chunk(1)": [2, 1.0]}
    hb = {"experts_live": 10, "expert_slots": 10, "decode_steps": 8,
          "prefill_experts_live": 1, "prompt_tokens": 9, "prefix_tokens": 1,
          "decode_keys": 10, "decode_rows": 8,
          "devtime": {"suffix_prefill": {"n": 1}}}
    ctx = _ctx(ops, mods, {}, hb)
    if case == "no such kernel":
        ctx["trace"]["ops"] = {"latent_decode.1": 1.0}
    elif case == "no counters":
        ctx["hb_end"] = {"decode_steps": 8}
    elif case == "another configuration":
        ctx["config"] = {"num_attention_heads": 32}
    else:
        ctx["trace"] = None
    assert trace_moe.read(ctx, **_metric("expert_ffn_roofline.lfm2")) is None
    assert trace_full_gqa.read(
        ctx, **_metric("gqa_decode_roofline.lfm2")) is None


# ------------------------------------------------------- the rehearsals

def _run(*extra, seconds="4"):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 40), "--seconds", seconds,
         "--rehearse", *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    compared = {ln.split()[1]: ln.endswith(" ok") for ln in lines
                if ln.startswith("compared: ")}
    return json.loads(lines[-1]), compared


def test_rehearsal_is_correct_on_both_audit_lanes():
    rec, compared = _run()
    assert rec["correct"] is True and rec["failed"] == 0
    assert all(compared.values()) and {
        "short_join_first_position_err_mean", "short_joins_sampled",
        "other_joins_sampled"} <= set(compared)


def test_control_fails_on_precision():
    rec, compared = _run("--control")
    assert rec["correct"] is False and not compared["logit_err_p90"]


def test_the_zeroed_restore_fails_on_the_short_joins():
    """The planted fault leaves prompts, pages and every other count
    sound: what fails is the short joins' first position (and, at
    these widths, the percentile with it)."""
    rec, compared = _run("--sabotage", CFG["sabotage"])
    assert rec["correct"] is False and rec["failed"] == 0
    assert not compared["short_join_first_position_err_mean"]
    assert compared["prompts_that_are_no_turn"] \
        and compared["sampled_turns_served_cold"] \
        and compared["short_joins_sampled"]


def test_traced_rehearsal_prints_the_cells_own_metrics():
    rec, _ = _run("--trace", "1")
    assert rec["correct"] is True and rec["failed"] == 0
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    # every join resumed from a snapshot: the tenant's at a session's
    # first turn, the session's own after it
    assert m["state_resume_pct.complete"] == 100.0
    assert m["prefix_hit_pct.complete"] > 80.0
    assert m["join_rows_per_program.complete"] == 1.0
    assert m["expert_slots_per_step.lfm2"] > 0
    # the rehearsal's router keeps ALL its experts a token: no selection
    # for a bias to change
    assert m["router_bias_swap_pct.lfm2"] == 0.0
    assert {"state_copy_ms.complete", "state_evictions_per_join.complete",
            "loop_join_pct.complete", "join_ms.complete"} <= set(m)
    # no device kernel on the CPU: the rooflines have nothing to read
    assert not {"gqa_decode_roofline.lfm2", "gqa_prefill_roofline.lfm2",
                "expert_ffn_roofline.lfm2"} & set(m)
