"""Reader `trace_full_gqa`: the grouped-query attention kernel of a
stack whose attention layers all see every earlier token — beside
layers of another kind that keep no pages at all — against its
roofline, from the run's one jax.profiler capture (reduced by
benchmark/tracereduce.py), the lane's heartbeat counters and work_gqa.

args: {"kernel": regex over operation names, "program": regex over
program (module) names, "mode": "decode" | "prefill",
"steps_per_program": decode steps one program event runs,
"layer_type": the entry of the configuration's layer_types that names
an attention layer}

(readers/trace_gqa.py counts every layer that is not a sliding one as
a global attention layer and reads head_dim as a number: a
configuration whose other layers are convolutions, and whose head
width is hidden_size / num_attention_heads, needs this reader.)

The capture gives the kernel's device seconds (operations matching
`kernel`) and how many programs it held (events matching `program`);
one kernel event is one attention layer of one decode step or of one
suffix piece.  What an event had to do comes from the LIVE keys only,
which the lane counts as it dispatches (heartbeat: decode_keys a step;
prefill_keys and the distinct tokens read, prefill_kv, a piece).

Share = 100 x programs x steps x attention layers x t / kernel
seconds, t = max(bytes / peak HBM bytes/s, FLOPs / peak bf16 FLOP/s)
of an event (work_gqa.gqa_attention).  A program that has no such
kernel or counters (the parent's, say) leaves nothing to read ->
None."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path
import work_gqa


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
         steps_per_program: int = 1, layer_type: str = "full_attention"):
    red = ctx.get("trace")
    if not red:
        return None
    k_rx, p_rx = re.compile(kernel), re.compile(program)
    secs = sum(s for name, s in red["ops"].items() if k_rx.search(name))
    programs = sum(c for name, (c, _) in red["modules"].items()
                   if p_rx.search(name))
    cfg = ctx["config"]
    types = cfg.get("layer_types")
    if secs <= 0 or not programs or not isinstance(types, list):
        return None
    layers = sum(t == layer_type for t in types)
    if mode == "decode":
        need = [delta(ctx, k) for k in (
            "decode_keys", "decode_rows", "decode_steps")]
        if None in need or not need[2]:
            return None
        event = (need[0] / need[2], need[0] / need[2], need[1] / need[2])
    elif mode == "prefill":
        need = [delta(ctx, k) for k in (
            "prefill_keys", "prefill_kv", "prompt_tokens", "prefix_tokens",
            "devtime/suffix_prefill/n")]
        if None in need or not need[4]:
            return None
        event = (need[0] / need[4], need[1] / need[4],
                 (need[2] - need[3]) / need[4])
    else:
        raise ValueError(f"unknown trace_full_gqa reader mode {mode!r}")
    try:
        heads, kv_heads = int(cfg["num_attention_heads"]), \
            int(cfg["num_key_value_heads"])
        d = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
    except (KeyError, TypeError, ValueError):
        return None
    if not layers:
        return None
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
    flops, bytes_ = work_gqa.gqa_attention(*event, heads, kv_heads, d)
    t_min = max(bytes_ / peak["hbm_bytes_per_s"],
                flops / peak["bf16_flops"])
    return 100.0 * programs * int(steps_per_program) * layers * t_min / secs
