"""Reader `trace_mla`: the latent-attention decode kernel against its
roofline, from the run's one jax.profiler capture (reduced by
benchmark/tracereduce.py), the lane's heartbeat counters and
work_mla.latent_decode.

args: {"kernel": regex over operation names, "program": regex over
program (module) names, "steps_per_program": decode steps one program
event runs, "mode": "roofline_pct"}

The capture gives the kernel's device seconds (operations matching
`kernel`) and how many decode programs it held (events matching
`program`); one kernel event is one layer of one step, so events =
programs x steps_per_program x the configuration's layers.  What an
event had to do comes from the LIVE tokens only: the heartbeat's
counters over the window give the live rows a step (decode_rows /
decode_steps) and a live row's mean context (prompt tokens an answer +
half the tokens generated an answer).  Roofline share = 100 x events x
max(bytes / peak HBM bytes/s, FLOPs / peak bf16 FLOP/s) / kernel
seconds.  A program (the parent's, say) that has no such kernel or
counters leaves nothing to read -> None."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path
import work_mla


def delta(ctx, key: str):
    a, b = ctx.get("hb_start") or {}, ctx.get("hb_end") or {}
    if not isinstance(b.get(key), (int, float)):
        return None
    return float(b[key]) - float(a.get(key) or 0.0)


def read(ctx, kernel: str, program: str, steps_per_program: int,
         mode: str = "roofline_pct"):
    red = ctx.get("trace")
    if not red:
        return None
    k_rx, p_rx = re.compile(kernel), re.compile(program)
    secs = sum(s for name, s in red["ops"].items() if k_rx.search(name))
    programs = sum(c for name, (c, _) in red["modules"].items()
                   if p_rx.search(name))
    need = [delta(ctx, k) for k in ("decode_rows", "decode_steps",
                                    "prompt_tokens", "completions",
                                    "tokens")]
    if secs <= 0 or not programs or None in need or not need[1] \
            or not need[3]:
        return None
    if mode != "roofline_pct":
        raise ValueError(f"unknown trace_mla reader mode {mode!r}")
    rows_d, steps_d, prompt_d, answers_d, tokens_d = need
    cfg = ctx["config"]
    rows = rows_d / steps_d
    context = prompt_d / answers_d + 0.5 * tokens_d / answers_d
    flops, bytes_ = work_mla.latent_decode(
        rows, rows * context, int(cfg["num_attention_heads"]),
        int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"]))
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
    t_min = max(bytes_ / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops"])
    events = programs * int(steps_per_program) * int(cfg["share"]["layers"])
    return 100.0 * events * t_min / secs
