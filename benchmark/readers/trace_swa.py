"""Reader `trace_swa`: the grouped-query attention kernel of a stack
whose window layers (with a sink) and global layers differ in
key/value heads and whose keys and values differ in width
(MiMo-V2-Flash's setting of models/afmoe.py) against its roofline,
from the run's one jax.profiler capture (reduced by benchmark/
tracereduce.py), the lane's heartbeat counters and work_swa.

args: {"kernel": regex over operation names, "program": regex over
program (module) names, "mode": "decode" | "prefill",
"steps_per_program": decode steps one program event runs}

As readers/trace_gqa.py: the capture gives the kernel's device seconds
and how many programs it held; one kernel event is one layer of one
decode step or of one suffix piece, so events = programs x steps x
layers, of which the global layers and the window layers (counted from
the configuration's hybrid_layer_pattern, 0 = global) did different
work with different heads: num_key_value_heads / head_dim / v_head_dim
against swa_num_key_value_heads / swa_head_dim / swa_v_head_dim.  What
an event had to do comes from the LIVE keys only, which the lane
counts as it dispatches (heartbeat: decode_keys / decode_window_keys a
step, prefill_keys / prefill_window_keys and the distinct tokens read
prefill_kv / prefill_window_kv a piece).

Share = 100 x programs x steps x (global layers x t_global + window
layers x t_window) / kernel seconds, t = max(bytes / peak HBM bytes/s,
FLOPs / peak bf16 FLOP/s) of an event of the kind.  A program that has
no such kernel, counters or configuration keys (the parent's, say)
leaves nothing to read -> None."""
import re

import traffic       # benchmark/traffic.py: run.py puts benchmark/ on sys.path
import work
import work_swa

KIND_KEYS = (
    ("num_key_value_heads", "head_dim", "v_head_dim"),              # global
    ("swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim"))  # window


# the heartbeat's difference over the window, as trace_gqa reads it
delta = traffic.part("readers", "trace_gqa").delta


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
    pattern = cfg.get("hybrid_layer_pattern")
    if secs <= 0 or not programs or not isinstance(pattern, list) \
            or any(k not in cfg for keys in KIND_KEYS for k in keys):
        return None
    n_window = sum(1 for p in pattern if p)
    n_layers = (len(pattern) - n_window, n_window)
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
        raise ValueError(f"unknown trace_swa reader mode {mode!r}")
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
    total = 0.0
    for (keys, kv_tokens, q_tokens), names, n in zip(events, KIND_KEYS,
                                                     n_layers):
        flops, bytes_ = work_swa.swa_attention(
            keys, kv_tokens, q_tokens, int(cfg["num_attention_heads"]),
            *(int(cfg[k]) for k in names))
        total += n * max(bytes_ / peak["hbm_bytes_per_s"],
                         flops / peak["bf16_flops"])
    return 100.0 * programs * int(steps_per_program) * total / secs
