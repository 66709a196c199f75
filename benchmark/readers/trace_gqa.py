"""Reader `trace_gqa`: the grouped-query attention kernel of a stack
that mixes sliding-window and global layers against its roofline, from
the run's one jax.profiler capture (reduced by benchmark/
tracereduce.py), the lane's heartbeat counters and work_gqa.

args: {"kernel": regex over operation names, "program": regex over
program (module) names, "mode": "decode" | "prefill",
"steps_per_program": decode steps one program event runs}

The capture gives the kernel's device seconds (operations matching
`kernel`) and how many programs it held (events matching `program`);
one kernel event is one layer of one decode step or of one suffix
piece, so events = programs x steps x layers, of which the global
layers and the window layers (counted from the configuration's
layer_types) did different work.  What an event had to do comes from
the LIVE keys only, which the lane counts as it dispatches
(heartbeat: decode_keys / decode_window_keys a step, prefill_keys /
prefill_window_keys and the distinct tokens read prefill_kv /
prefill_window_kv a piece): a global layer's whole row, a window
layer's min(length, sliding_window).

Share = 100 x programs x steps x (global layers x t_global + window
layers x t_window) / kernel seconds, t = max(bytes / peak HBM bytes/s,
FLOPs / peak bf16 FLOP/s) of an event of the kind.  A program that has
no such kernel or counters (the parent's, say) leaves nothing to read
-> None."""
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
         steps_per_program: int = 1):
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
    n_window = sum(t == "sliding_attention" for t in types)
    n_global = len(types) - n_window
    if mode == "decode":
        need = [delta(ctx, k) for k in (
            "decode_keys", "decode_window_keys", "decode_rows",
            "decode_steps")]
        if None in need or not need[3]:
            return None
        steps = need[3]
        events = [(need[0] / steps, need[0] / steps, need[2] / steps),
                  (need[1] / steps, need[1] / steps, need[2] / steps)]
    elif mode == "prefill":
        need = [delta(ctx, k) for k in (
            "prefill_keys", "prefill_window_keys", "prefill_kv",
            "prefill_window_kv", "prompt_tokens", "prefix_tokens",
            "devtime/suffix_prefill/n")]
        if None in need or not need[6]:
            return None
        calls = need[6]
        q = (need[4] - need[5]) / calls
        events = [(need[0] / calls, need[2] / calls, q),
                  (need[1] / calls, need[3] / calls, q)]
    else:
        raise ValueError(f"unknown trace_gqa reader mode {mode!r}")
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
    t_min = []
    for keys, kv_tokens, q_tokens in events:
        flops, bytes_ = work_gqa.gqa_attention(
            keys, kv_tokens, q_tokens, int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
        t_min.append(max(bytes_ / peak["hbm_bytes_per_s"],
                         flops / peak["bf16_flops"]))
    return 100.0 * programs * int(steps_per_program) \
        * (n_global * t_min[0] + n_window * t_min[1]) / secs
