"""Reader `trace_kda`: a kernel of the hybrid stack against its
roofline, from the run's one jax.profiler capture (reduced by
benchmark/tracereduce.py), the lane's heartbeat counters and
work_kda / work_mla.

args: {"kernel": regex over operation names, "program": regex over
program (module) names, "mode": ..., "steps_per_program": decode steps
one program event runs (decode modes), "chunk": tokens a chunk of the
prefill's state walk holds (kda_prefill_pct)}

The capture gives the kernel's device seconds (operations matching
`kernel`) and how many programs it held (events matching `program`);
one kernel event is one layer of the kind the mode names, of one step
or call, so events = programs x steps x that kind's layers (counted
from the configuration's linear_attn_config lists).  What an event had
to do comes from the LIVE work only, averaged over the window by the
heartbeat's counters:

  kda_decode_pct     live rows a step (decode_rows / decode_steps)
                     -> work_kda.kda_decode
  kda_prefill_pct    live suffix tokens a call ((prompt_tokens -
                     prefix_tokens) / devtime suffix_prefill n)
                     -> work_kda.kda_prefill: the state's walk from
                     chunk to chunk, which is what the named kernel
                     does; the state-free work inside a chunk runs as
                     XLA fusions no regex can name and is in neither
                     the work nor the seconds
  latent_decode_pct  live rows a step and a live row's mean context
                     (prompt tokens an answer + half the tokens
                     generated an answer) -> work_mla.latent_decode

Share = 100 x events x max(bytes / peak HBM bytes/s, FLOPs / peak bf16
FLOP/s) / kernel seconds.  A program that has no such kernel or
counters (the parent's, say) leaves nothing to read -> None."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path
import work_kda
import work_mla


def delta(ctx, path: str):
    def dig(d):
        for part in path.split("/"):
            if not isinstance(d, dict) or part not in d:
                return None
            d = d[part]
        return d if isinstance(d, (int, float)) else None
    hi = dig(ctx.get("hb_end") or {})
    if hi is None:
        return None
    return float(hi) - float(dig(ctx.get("hb_start") or {}) or 0.0)


def read(ctx, kernel: str, program: str, mode: str,
         steps_per_program: int = 1, chunk: int = 0):
    red = ctx.get("trace")
    if not red:
        return None
    k_rx, p_rx = re.compile(kernel), re.compile(program)
    secs = sum(s for name, s in red["ops"].items() if k_rx.search(name))
    programs = sum(c for name, (c, _) in red["modules"].items()
                   if p_rx.search(name))
    cfg = ctx["config"]
    lin = cfg.get("linear_attn_config")
    if secs <= 0 or not programs or not isinstance(lin, dict):
        return None
    heads, d = int(lin["num_heads"]), int(lin["head_dim"])
    if mode == "kda_prefill_pct":
        need = [delta(ctx, k) for k in (
            "prompt_tokens", "prefix_tokens", "devtime/suffix_prefill/n")]
        if None in need or not need[2] or int(chunk) <= 0:
            return None
        layers = len(lin["kda_layers"])
        flops, bytes_ = work_kda.kda_prefill(
            (need[0] - need[1]) / need[2], heads, d, int(chunk))
    else:
        need = [delta(ctx, k) for k in (
            "decode_rows", "decode_steps", "prompt_tokens", "completions",
            "tokens")]
        if None in need or not need[1] or not need[3]:
            return None
        rows = need[0] / need[1]
        if mode == "kda_decode_pct":
            layers = len(lin["kda_layers"])
            flops, bytes_ = work_kda.kda_decode(rows, heads, d)
        elif mode == "latent_decode_pct":
            layers = len(lin["full_attn_layers"])
            context = need[2] / need[3] + 0.5 * need[4] / need[3]
            flops, bytes_ = work_mla.latent_decode(
                rows, rows * context, int(cfg["num_attention_heads"]),
                int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"]))
        else:
            raise ValueError(f"unknown trace_kda reader mode {mode!r}")
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
    t_min = max(bytes_ / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops"])
    return 100.0 * programs * int(steps_per_program) * layers * t_min / secs
