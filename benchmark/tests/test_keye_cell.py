"""The parts `keye-docqa-32k-sparse` brings: work_dsa against a
brute-force count, the reader trace_dsa on a small reduced capture
(its four modes; nothing to read where the program has no such kernel,
counters or keys), the sabotage's selection, and the cell's traced
rehearsal on the CPU printing the metrics that are its own.  The
rehearsal itself, its float8 control and its sabotage
(`sparse_select_recent_only`, the configuration's) run as cases of
test_rehearse.py, which takes its cells from BENCHMARK.json.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_keye_cell.py -q"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import work                                       # noqa: E402
import work_dsa                                   # noqa: E402
from readers import trace_dsa                     # noqa: E402

CELL = "keye-docqa-32k-sparse"
CFG = json.load(open(os.path.join(
    BENCH_DIR, "configs", "keye-vl2-30b-a3b-ep8-stage0.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))


def _metric(name):
    return json.load(open(os.path.join(BENCH_DIR, "metrics",
                                       f"{name}.json")))["args"]


def test_work_dsa_against_a_brute_force_count():
    """A decode step of 3 rows at contexts of 5,000 / 2,048 / 700 keys
    with topk 2,048, key by key and head by head."""
    ctxs, topk, hi, di, heads, kvh, d = (5000, 2048, 700), 2048, 16, 64, \
        32, 4, 128
    flops = bytes_ = 0
    for n in ctxs:
        if n > topk:                    # a dense row scores no key
            for _key in range(n):
                flops += hi * (2 * di + 2)      # products, ReLU x weight
                bytes_ += di * 2                # its one indexer key
            bytes_ += hi * di * 2 + hi * 4      # the token's qI and w
    pairs = sum(n for n in ctxs if n > topk)
    assert work_dsa.index_scan(pairs, pairs, 1, hi, di) == (flops, bytes_)
    flops = bytes_ = 0
    for n in ctxs:
        for _key in range(min(n, topk)):
            flops += heads * (2 * d + 2 * d)    # score, value sum
            bytes_ += kvh * 2 * d * 2           # its K and V rows
        bytes_ += heads * 2 * d * 2             # the token's q in, o out
    picked = sum(min(n, topk) for n in ctxs)
    assert work_dsa.selected_attention(picked, picked, 3, heads, kvh, d) \
        == (flops, bytes_)
    # the cell's decode step a (row, layer): 2,048 tokens x 2,048 B
    _, b = work_dsa.selected_attention(2048, 2048, 0, 32, 4, 128)
    assert b == 2048 * 2048


def _ctx(ops, modules, start, end, cfg=CFG):
    return {"trace": {"ops": ops, "modules": modules}, "hb_start": start,
            "hb_end": end, "config": cfg, "peaks": PEAKS,
            "device": {"kind": "TPU v5 lite"}}


ROWS, CTX, LAYERS, STEPS, CALLS, Q = 32, 32_900, 8, 16, 4, 100


def _beats():
    """Two chunk programs of 8 steps and 4 suffix calls of 16 rows x
    100 question tokens over 32,768-token documents in the window."""
    picked = ROWS * 2048 * LAYERS
    join_pairs = 16 * sum(32_768 + 1 + i for i in range(Q)) * LAYERS
    hb0 = {"devtime": {"suffix_prefill": {"n": 3}}}
    hb1 = {"index_keys_decode": STEPS * ROWS * CTX * LAYERS,
           "index_keys_join": CALLS * join_pairs,
           "keys_in_context": STEPS * ROWS * CTX * LAYERS
           + CALLS * join_pairs,
           "keys_selected": STEPS * picked + CALLS * 16 * Q * 2048 * LAYERS,
           "keys_selected_decode": STEPS * picked,
           "join_kv": CALLS * 16 * (32_768 + Q) * LAYERS,
           "decode_rows": STEPS * ROWS, "decode_steps": STEPS,
           "prompt_tokens": CALLS * 16 * (32_768 + Q),
           "prefix_tokens": CALLS * 16 * 32_768,
           "devtime": {"suffix_prefill": {"n": 3 + CALLS}}}
    return hb0, hb1


MODS = {"jit_dsa_paged_chunk(12)": [2, 1.0],
        "jit_dsa_suffix_prefill(4)": [CALLS, 1.0]}


def test_trace_dsa_reads_a_small_capture():
    peak = work.peak_for(PEAKS, "TPU v5 lite")

    def t_min(fb):
        return max(fb[1] / peak["hbm_bytes_per_s"],
                   fb[0] / peak["bf16_flops"])
    hb0, hb1 = _beats()
    step_scan = t_min(work_dsa.index_scan(
        ROWS * CTX * LAYERS, ROWS * CTX * LAYERS, ROWS * LAYERS, 16, 64))
    call_pairs = 16 * sum(32_768 + 1 + i for i in range(Q)) * LAYERS
    call_scan = t_min(work_dsa.index_scan(
        call_pairs, 16 * (32_768 + Q) * LAYERS, 16 * Q * LAYERS, 16, 64))
    scan_bound = STEPS * step_scan + CALLS * call_scan
    step_att = t_min(work_dsa.selected_attention(
        ROWS * 2048 * LAYERS, ROWS * 2048 * LAYERS, ROWS * LAYERS, 32, 4,
        128))
    call_att = t_min(work_dsa.selected_attention(
        16 * Q * 2048 * LAYERS, 16 * (32_768 + Q) * LAYERS,
        16 * Q * LAYERS, 32, 4, 128))
    ops = {"dsa_index_scan.3": scan_bound, "dsa_index_scan_stack.9":
           scan_bound, "dsa_sparse_decode.1": 4 * STEPS * step_att,
           "dsa_sparse_stack.2": 5 * CALLS * call_att,
           "dsa_select.5": 0.032, "dsa_select_stack.6": 7.0,
           "fusion.1": 9.0}
    ctx = _ctx(ops, MODS, hb0, hb1)
    read = trace_dsa.read
    assert read(ctx, **_metric("index_scan_roofline.keye")) \
        == pytest.approx(50.0, rel=1e-9)
    assert read(ctx, **_metric("sparse_decode_roofline.keye")) \
        == pytest.approx(25.0, rel=1e-9)
    assert read(ctx, **_metric("sparse_prefill_roofline.keye")) \
        == pytest.approx(20.0, rel=1e-9)
    # the decode chunk's selection alone: 32 ms over 16 steps
    assert read(ctx, **_metric("select_ms_per_step.keye")) \
        == pytest.approx(2.0, rel=1e-9)
    # a decode step's attention is bound by its bytes: 2,048 x 2,048 B
    # a (row, layer), the queries' rows beside them
    assert step_att == pytest.approx(
        (ROWS * LAYERS * (2048 * 2048 + 32 * 512))
        / peak["hbm_bytes_per_s"], rel=1e-9)


@pytest.mark.parametrize("case", ["no such kernel", "no counters",
                                  "another configuration", "no trace"])
@pytest.mark.parametrize("metric", [
    "index_scan_roofline.keye", "sparse_decode_roofline.keye",
    "sparse_prefill_roofline.keye", "select_ms_per_step.keye"])
def test_trace_dsa_finds_nothing_where_there_is_nothing(metric, case):
    """What the parent, or another family's cell, gives the reader:
    None, not an exception."""
    hb0, hb1 = _beats()
    ops = {"dsa_index_scan.3": 1.0, "dsa_sparse_decode.1": 1.0,
           "dsa_sparse_stack.2": 1.0, "dsa_select.5": 1.0}
    ctx = _ctx(ops, dict(MODS), hb0, hb1)
    if case == "no such kernel":
        ctx["trace"]["ops"] = {"gqa_window_decode.1": 1.0}
    elif case == "no counters":
        ctx["hb_end"] = {"decode_steps": 8, "decode_rows": 64}
    elif case == "another configuration":
        ctx["config"] = {"num_attention_heads": 32, "head_dim": 128,
                         "share": {"layers": 7}}
    else:
        ctx["trace"] = None
    assert trace_dsa.read(ctx, **_metric(metric)) is None


def test_the_sabotage_selects_the_last_topk_positions():
    import importlib.util
    import numpy as np
    spec = importlib.util.spec_from_file_location(
        "sab", os.path.join(BENCH_DIR, "sabotage",
                            "sparse_select_recent_only.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.path.insert(0, REPO)
    from libsplinter_tpu.ops import sparse_attention as sa
    sound = sa.select_topk
    try:
        mod.apply()
        got = np.asarray(sa.select_topk(
            np.zeros((1, 3, 40), np.float32),
            np.asarray([[5, 30, 40]], np.int32), topk=8))
    finally:
        sa.select_topk = sound
    for row, lim in zip(got[0], (5, 30, 40)):
        want = np.zeros(40)
        want[max(0, lim - 8): lim] = 1
        np.testing.assert_array_equal(row, want)


def test_traced_rehearsal_prints_the_cells_own_metrics():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 44), "--seconds", "4", "--rehearse",
         "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rec = json.loads([ln for ln in p.stdout.splitlines()
                      if ln.strip()][-1])
    assert rec["correct"] is True and rec["failed"] == 0
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    # contexts of 70-90 keys under a topk of 16: a fifth attended
    assert 15.0 < m["selected_key_pct.keye"] < 30.0
    assert m["expert_slots_per_step.keye"] > 0
    assert m["prefix_hit_pct.complete"] > 80.0
    assert m["join_rows_per_program.complete"] >= 1.0
    # no device kernel on the CPU: the rooflines have nothing to read
    for name in ("index_scan_roofline.keye", "sparse_decode_roofline.keye",
                 "sparse_prefill_roofline.keye",
                 "select_ms_per_step.keye"):
        assert name not in m
