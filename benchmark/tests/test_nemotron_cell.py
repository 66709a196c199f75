"""The parts `nemotron-chat-cold-budgeted` brings: the payload
`fresh_prompts` (log-uniform sizes and budgets from shape_seed, contents
and order from the seed), work_ssd against a brute-force count, the
reader trace_ssd on a small reduced capture (its three modes; nothing
to read where the program has no such kernel or counters), and the
cell's rehearsals on the CPU: `correct`, its float8 control not, its
two sabotages not, the traced one printing the metrics that are its
own.  (The rehearsal, control and the configuration's sabotage also run
as cases of test_rehearse.py, which takes its cells from
BENCHMARK.json.)

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nemotron_cell.py -q"""
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

import traffic                                    # noqa: E402
import work                                       # noqa: E402
import work_ssd                                   # noqa: E402
from readers import trace_ssd                     # noqa: E402

CELL = "nemotron-chat-cold-budgeted"
CFG = json.load(open(os.path.join(
    BENCH_DIR, "configs", "nemotron3-nano-30b-a3b-ep8-stage0.json")))
MIX = json.load(open(os.path.join(BENCH_DIR, "traffic", f"{CELL}.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))


def _metric(name):
    return json.load(open(os.path.join(BENCH_DIR, "metrics",
                                       f"{name}.json")))["args"]


def test_fresh_prompts_sizes_from_shape_seed_contents_from_seed():
    make = traffic.part("payloads", "fresh_prompts").make
    a, b = (make(MIX["payload"], seed, None, {}) for seed in (1, 2**31 + 9))
    sizes = [sorted(len(p) for p in pay["prompt_ids"]) for pay in (a, b)]
    assert sizes[0] == sizes[1]                   # the same multiset
    assert sorted(a["budgets"]) == sorted(b["budgets"])
    assert a["prompts"][:4] != b["prompts"][:4]
    assert list(a["budgets"][:64]) != list(b["budgets"][:64])
    n = np.array(sizes[0])
    assert n.min() >= 128 and n.max() <= 1024 and len(n) == 4096
    # log-uniform: the median is the geometric mean of the ends
    assert 330 < np.median(n) < 400 and 200 < np.mean(a["budgets"]) < 230
    assert 160 < np.median(a["budgets"]) < 200
    assert a["budgets"].min() >= 64 and a["budgets"].max() <= 512
    for ids, text in zip(a["prompt_ids"][:8], a["prompts"][:8]):
        assert ids[0] == 1 and len(ids) == len(text) + 1
        assert bytes(int(t) - 3 for t in ids[1:]) == text
    # no two prompts share a first page
    assert len({p[:127] for p in a["prompts"]}) == 4096


def test_work_ssd_against_a_brute_force_count():
    """A decode step of 3 live rows, head by head; a prefill call of
    200 live tokens; 7 slots over 3 live un-gated experts."""
    H, P, N, G = 64, 64, 128, 8
    flops = bytes_ = 0
    for _row in range(3):
        for _head in range(H):
            flops += P * N          # the decay's multiply
            flops += 2 * P * N      # the outer product and its add
            flops += 2 * P * N      # S C: multiply-add
            bytes_ += 2 * P * N * 4         # the state in and out
            bytes_ += (2 * P + 1) * 4       # x in, y out, dt
        bytes_ += 2 * G * N * 4             # B and C
    assert work_ssd.ssd_decode(3, H, P, N, G) == (flops, bytes_)
    f, b = work_ssd.ssd_prefill(200, H, P, N, G)
    assert f == 200 * H * 5 * P * N
    assert b == 200 * 4 * (2 * H * P + H + 2 * G * N) + H * 2 * P * N * 4
    f, b = work_ssd.expert_ffn_ungated(3, 7, 2688, 1856)
    assert f == 7 * 2 * (2 * 2688 * 1856)
    assert b == 2 * (3 * 2 * 2688 * 1856 + 7 * (2 * 2688 + 2 * 1856))
    # the cell's decode step a layer: 128 rows x 2.10 MB of state twice
    assert 0.53e9 < work_ssd.ssd_decode(128, H, P, N, G)[1] < 0.55e9


def _ctx(ops, modules, start, end, cfg=CFG):
    return {"trace": {"ops": ops, "modules": modules}, "hb_start": start,
            "hb_end": end, "config": cfg, "peaks": PEAKS,
            "device": {"kind": "TPU v5 lite"}}


def _beats():
    """Ten decode programs of 8 steps at 100 live rows and five suffix
    pieces of 400 live tokens in the window."""
    hb0 = {"devtime": {"suffix_prefill": {"n": 3}}}
    hb1 = {"decode_steps": 80, "ssd_decode_rows": 8000,
           "ssd_prefill_tokens": 2000,
           "devtime": {"suffix_prefill": {"n": 8}},
           "experts_live": 80 * 11 * 16, "expert_slots": 80 * 11 * 75,
           "prefill_experts_live": 5 * 11 * 16,
           "prefill_expert_slots": 5 * 11 * 300}
    return hb0, hb1


def test_trace_ssd_reads_a_small_capture():
    peak = work.peak_for(PEAKS, "TPU v5 lite")

    def t_min(fb):
        return max(fb[1] / peak["hbm_bytes_per_s"],
                   fb[0] / peak["bf16_flops"])
    modules = {"jit_nemotron_paged_chunk": [10, 1.0],
               "jit_nemotron_suffix_prefill": [5, 0.2]}
    ops = {"ssd_decode_step": 2.0, "ssd_chunk_prefill": 0.5,
           "gmm": 1.5, "fusion.1": 9.0}
    ctx = _ctx(ops, modules, *_beats())
    got = trace_ssd.read(ctx, **_metric("ssd_decode_roofline.nemotron"))
    want = 100 * 10 * 8 * 12 * t_min(work_ssd.ssd_decode(
        100, 64, 64, 128, 8)) / 2.0
    assert got == pytest.approx(want)
    got = trace_ssd.read(ctx, **_metric("ssd_prefill_roofline.nemotron"))
    want = 100 * 5 * 12 * t_min(work_ssd.ssd_prefill(
        400, 64, 64, 128, 8)) / 0.5
    assert got == pytest.approx(want)
    got = trace_ssd.read(ctx, **_metric("expert_ffn_roofline.nemotron"))
    want = 100 * (10 * 8 * 11 * t_min(work_ssd.expert_ffn_ungated(
        16, 75, 2688, 1856)) + 5 * 11 * t_min(work_ssd.expert_ffn_ungated(
            16, 300, 2688, 1856))) / 1.5
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", ["ssd_decode_roofline.nemotron",
                                    "ssd_prefill_roofline.nemotron",
                                    "expert_ffn_roofline.nemotron"])
@pytest.mark.parametrize("case", ["no-trace", "no-kernel", "no-counters",
                                  "another-config"])
def test_trace_ssd_finds_nothing_where_there_is_nothing(metric, case):
    """The parent's program has no such kernel, program or counter:
    the reader answers None and does not raise."""
    modules = {"jit_nemotron_paged_chunk": [10, 1.0],
               "jit_nemotron_suffix_prefill": [5, 0.2]}
    ops = {"ssd_decode_step": 2.0, "ssd_chunk_prefill": 0.5, "gmm": 1.5}
    hb0, hb1 = _beats()
    ctx = _ctx(ops, modules, hb0, hb1)
    if case == "no-trace":
        ctx["trace"] = None
    elif case == "no-kernel":
        ctx["trace"]["ops"] = {"fusion.1": 1.0}
    elif case == "no-counters":
        ctx["hb_end"] = {"decode_steps": 80}
    else:
        ctx["config"] = {"hidden_size": 2048, "share": {"layers": 9}}
    assert trace_ssd.read(ctx, **_metric(metric)) is None


def _rehearse(*extra):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 47), "--seconds", "3", "--rehearse",
         *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads([ln for ln in p.stdout.splitlines()
                       if ln.strip()][-1]), p.stdout


def test_traced_rehearsal_prints_the_cells_own_metrics():
    rec, _ = _rehearse("--trace", "1")
    assert rec["correct"] is True and rec["failed"] == 0
    m = {k: v["value"] for k, v in rec["metrics"].items()}
    # budgets of 4-24 log-uniform: ~11; 24 would say they are ignored
    assert 8.0 < m["answer_tokens_per_query.nemotron"] < 15.0
    assert m["expert_slots_per_step.nemotron"] > 0
    assert m["prefix_hit_pct.complete"] < 5.0          # every join cold
    assert m["join_rows_per_program.complete"] == 1.0
    assert m["state_evictions_per_join.complete"] > 0.5
    # no device kernel on the CPU: the rooflines have nothing to read
    for name in ("ssd_decode_roofline.nemotron",
                 "ssd_prefill_roofline.nemotron",
                 "expert_ffn_roofline.nemotron"):
        assert name not in m


@pytest.mark.parametrize("extra", [
    ("--control",), ("--sabotage", "state_stale_at_cold_seat"),
    ("--sabotage", "ssd_decode_decay_dropped")], ids=lambda e: e[-1])
def test_control_and_sabotages_are_not_correct(extra):
    rec, out = _rehearse(*extra)
    assert rec["correct"] is False and rec["failed"] == 0
    assert "logit_err_p90" in out and "FAILED" in out
