"""Reader `trace_moe`: a routed expert layer's grouped products against
their roofline, from the run's one jax.profiler capture (reduced by
benchmark/tracereduce.py), the lane's heartbeat counters and work_moe.

args: {"kernel": regex over operation names, "decode_program" /
"prefill_program": regexes over program (module) names,
"steps_per_program": decode steps one decode program event runs}

The capture gives the grouped products' device seconds (operations
matching `kernel`, three a layer) and how many programs of each kind
it held.  The reduced capture keeps operations by NAME, and a grouped
product has the same name in the decode chunk and in the suffix
prefill — so the seconds are those of BOTH kinds of program, and so is
the work: what an event had to do comes from the live experts and the
slots only, which the lane counts (heartbeat):

  a decode step     experts_live / decode_steps live experts and
                    expert_slots / decode_steps slots, summed over
                    the expert layers
  a suffix piece    prefill_experts_live / devtime suffix_prefill n
                    live experts, and (prompt_tokens - prefix_tokens)
                    x experts a token x expert layers / n slots

Share = 100 x (decode programs x steps x t_step + suffix programs x
t_piece) / seconds, t = max(bytes / peak HBM bytes/s, FLOPs / peak bf16
FLOP/s) of the expert layers of one step or piece (work_moe.expert_ffn
a layer, at the layer's mean).  Expert layers are the configuration's
layers less its leading dense ones; every expert of a layer is on the
chip (share.experts holds them all), or the counters would count the
held ones only and the reader says nothing.  A program that has no
such kernel or counters (the parent's, say) leaves nothing to read ->
None."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path
import work_moe


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


def read(ctx, kernel: str, decode_program: str, prefill_program: str,
         steps_per_program: int = 1):
    red = ctx.get("trace")
    if not red:
        return None
    k_rx = re.compile(kernel)
    secs = sum(s for name, s in red["ops"].items() if k_rx.search(name))

    def programs(rx):
        rx = re.compile(rx)
        return sum(c for name, (c, _) in red["modules"].items()
                   if rx.search(name))
    n_decode, n_prefill = programs(decode_program), programs(prefill_program)
    cfg = ctx["config"]
    share = cfg.get("share") or {}
    try:
        layers = int(share["layers"]) - int(share["dense_layers"])
        hidden, width = int(cfg["hidden_size"]), \
            int(cfg["moe_intermediate_size"])
        top_k, held = int(cfg["num_experts_per_tok"]), share["experts"]
        whole = list(held) == [0, int(cfg["num_experts"])]
    except (KeyError, TypeError, ValueError):
        return None
    need = [delta(ctx, k) for k in (
        "experts_live", "expert_slots", "decode_steps",
        "prefill_experts_live", "prompt_tokens", "prefix_tokens",
        "devtime/suffix_prefill/n")]
    if secs <= 0 or not n_decode or layers <= 0 or not whole \
            or None in need or not need[2]:
        return None
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])

    def t_min(live, slots):
        """Seconds the expert layers of one event need at the least."""
        flops, bytes_ = work_moe.expert_ffn(live / layers, slots / layers,
                                            hidden, width)
        return layers * max(bytes_ / peak["hbm_bytes_per_s"],
                            flops / peak["bf16_flops"])
    total = n_decode * int(steps_per_program) * t_min(
        need[0] / need[2], need[1] / need[2])
    if n_prefill and need[6]:
        total += n_prefill * t_min(
            need[3] / need[6],
            (need[4] - need[5]) * top_k * layers / need[6])
    return 100.0 * total / secs
