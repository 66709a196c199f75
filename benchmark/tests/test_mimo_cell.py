"""The parts `mimo-docqa-32k-shared` brings: work_swa against a hand
count, the reader trace_swa on a small reduced capture (decode and
prefill; nothing to read where the program has no such kernel,
counters or keys), and the cell's traced rehearsal on the CPU printing
the metrics that are its own.  The rehearsal itself, its float8
control and its sabotage (`window_sink_dropped`, the configuration's)
run as cases of test_rehearse.py, which takes its cells from
BENCHMARK.json.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_mimo_cell.py -q"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import work_swa                                   # noqa: E402
from readers import trace_swa                     # noqa: E402

CELL = "mimo-docqa-32k-shared"
CFG = json.load(open(os.path.join(
    BENCH_DIR, "configs", "mimo-v2-flash-309b-ep16.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))


def test_work_swa_against_a_hand_count():
    """One global layer's decode event of the cell: 64 rows of 32,900
    keys each, 64 query heads over 4 kv heads, keys of 192 beside
    values of 128."""
    keys = 64 * 32_900
    flops, bytes_ = work_swa.swa_attention(keys, keys, 64, 64, 4, 192, 128)
    # a (head, key): 2 x 192 for the score + 2 x 128 for the value sum
    assert flops == 64 * keys * (384 + 256)
    # a token's K row 4 x 192 x 2 B and V row 4 x 128 x 2 B = 2,560 B;
    # a query token's q row 64 x 192 x 2 B in, its o row 64 x 128 x 2 B out
    assert bytes_ == keys * 2560 + 64 * (24_576 + 16_384)
    # memory-bound on a v5e by a wide margin: 3.3 FLOP a byte
    assert flops / bytes_ < 64
    # the window kind: 8 kv heads, the last 128 keys a row
    flops_w, bytes_w = work_swa.swa_attention(64 * 128, 64 * 128, 64, 64,
                                              8, 192, 128)
    assert bytes_w == 64 * 128 * 5120 + 64 * 40_960
    assert flops_w == 64 * 64 * 128 * 640


def _ctx(ops, modules, start, end, cfg=CFG):
    return {"trace": {"ops": ops, "modules": modules}, "hb_start": start,
            "hb_end": end, "config": cfg, "peaks": PEAKS,
            "device": {"kind": "TPU v5 lite"}}


def test_trace_swa_reads_a_small_capture():
    """Two chunk programs of 8 steps in the capture, 64 rows at 32,900
    tokens: 2 global layers reading 64 x 32,900 tokens of 2,560 B and 5
    window layers reading 64 x 128 of 5,120 B a step, against kernel
    seconds that put the whole at exactly half its bound."""
    import work
    peak = work.peak_for(PEAKS, "TPU v5 lite")
    bw = peak["hbm_bytes_per_s"]
    rows, ctx_len, steps = 64, 32_900, 16
    t_global = (rows * ctx_len * 2560 + rows * 40_960) / bw
    t_window = (rows * 128 * 5120 + rows * 40_960) / bw
    bound = steps * (2 * t_global + 5 * t_window)
    hb0 = {"decode_keys": 0, "decode_window_keys": 0, "decode_rows": 0,
           "decode_steps": 0}
    hb1 = {"decode_keys": steps * rows * ctx_len,
           "decode_window_keys": steps * rows * 128,
           "decode_rows": steps * rows, "decode_steps": steps}
    ops = {"gqa_window_decode.7": bound, "gqa_window_decode.9": bound,
           "gqa_window_stack.3": 5.0, "fusion.1": 9.0}
    mods = {"jit_afmoe_paged_chunk(123)": [2, 1.0],
            "jit_afmoe_suffix_prefill(4)": [7, 1.0]}
    spec = json.load(open(os.path.join(
        BENCH_DIR, "metrics", "swa_sink_decode_roofline.json")))
    got = trace_swa.read(_ctx(ops, mods, hb0, hb1), **spec["args"])
    assert got == pytest.approx(50.0, rel=1e-9)
    # the prefill form: 7 one-page pieces, each 100 live question
    # tokens over a 32,768-token document
    q, doc, calls = 100, 32_768, 7
    keys = sum(doc + 1 + i for i in range(q))
    wkeys = q * 128
    hb0 = {k: 0 for k in ("prefill_keys", "prefill_window_keys",
                          "prefill_kv", "prefill_window_kv",
                          "prompt_tokens", "prefix_tokens")}
    hb0["devtime"] = {"suffix_prefill": {"n": 3}}
    hb1 = {"prefill_keys": calls * keys,
           "prefill_window_keys": calls * wkeys,
           "prefill_kv": calls * (doc + q),
           "prefill_window_kv": calls * (127 + q),
           "prompt_tokens": calls * (doc + q), "prefix_tokens": calls * doc,
           "devtime": {"suffix_prefill": {"n": 3 + calls}}}
    f_g, b_g = work_swa.swa_attention(keys, doc + q, q, 64, 4, 192, 128)
    f_w, b_w = work_swa.swa_attention(wkeys, 127 + q, q, 64, 8, 192, 128)
    want = 100.0 * calls * (
        2 * max(b_g / bw, f_g / peak["bf16_flops"])
        + 5 * max(b_w / bw, f_w / peak["bf16_flops"])) / 5.0
    spec = json.load(open(os.path.join(
        BENCH_DIR, "metrics", "swa_sink_prefill_roofline.json")))
    got = trace_swa.read(_ctx(ops, mods, hb0, hb1), **spec["args"])
    assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100


@pytest.mark.parametrize("case", ["no such kernel", "no counters",
                                  "another configuration", "no trace"])
def test_trace_swa_finds_nothing_where_there_is_nothing(case):
    """What the parent, or another family's cell, gives the reader:
    None, not an exception."""
    args = json.load(open(os.path.join(
        BENCH_DIR, "metrics", "swa_sink_decode_roofline.json")))["args"]
    ops = {"gqa_window_decode.7": 1.0}
    mods = {"jit_afmoe_paged_chunk(1)": [2, 1.0]}
    hb = {"decode_keys": 10, "decode_window_keys": 10, "decode_rows": 8,
          "decode_steps": 8}
    ctx = _ctx(ops, mods, {}, hb)
    if case == "no such kernel":
        ctx["trace"]["ops"] = {"latent_decode.1": 1.0}
    elif case == "no counters":
        ctx["hb_end"] = {"decode_steps": 8}
    elif case == "another configuration":
        ctx["config"] = {"layer_types": ["full_attention"],
                         "num_attention_heads": 32}
    else:
        ctx["trace"] = None
    assert trace_swa.read(ctx, **args) is None


def test_traced_rehearsal_prints_the_cells_own_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 38), "--seconds", "4", "--rehearse",
         "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rec = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert rec["correct"] is True and rec["failed"] == 0
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    # every question resumed on its whole document and its one tail
    # page; every answer left that page from inside a decode chunk
    assert m["window_resume_pct.complete"] == 100.0
    assert m["window_cut_tokens_per_join.complete"] == 0.0
    assert 0.8 <= m["window_decode_slides_per_answer.complete"] <= 1.2
    assert 0.0 < m["window_tail_shared_pct.complete"] <= 100.0
    assert m["expert_slots_per_step.mimo"] > 0
    assert m["prefix_hit_pct.complete"] > 80.0
    # no device kernel on the CPU: the rooflines have nothing to read
    assert "swa_sink_decode_roofline" not in m
