"""Reader `trace_ssd`: a kernel of the state-space / expert / attention
stack against its roofline, from the run's one jax.profiler capture
(reduced by benchmark/tracereduce.py), the lane's heartbeat counters
and work_ssd.

args: {"kernel": regex over operation names, "mode": ...,
"decode_program" / "prefill_program": regexes over program (module)
names, "steps_per_program": decode steps one decode program event runs}

The capture gives the kernel's device seconds (operations matching
`kernel`) and how many programs of each kind it held; what an event
had to do comes from the LIVE work only, averaged over the window by
the heartbeat's counters:

  ssd_decode   one event = one state-space layer of one decode step;
               live rows a step = ssd_decode_rows / decode_steps
               -> work_ssd.ssd_decode
  ssd_prefill  one event = one state-space layer of one suffix piece;
               live tokens a piece = ssd_prefill_tokens / devtime
               suffix_prefill n -> work_ssd.ssd_prefill (the MODEL's
               count a token; the kernel's seconds hold the chunk-local
               products too)
  expert_ffn   the un-gated experts' grouped products (two a layer),
               which have ONE name in the decode chunk and in the
               suffix prefill — so seconds and work both cover both
               kinds of program: a decode step's experts_live /
               decode_steps live experts and expert_slots /
               decode_steps slots, a suffix piece's
               prefill_experts_live / n and prefill_expert_slots / n,
               each summed over the expert layers
               -> work_ssd.expert_ffn_ungated a layer, at the layer's
               mean

Share = 100 x sum over events of max(bytes / peak HBM bytes/s, FLOPs /
peak bf16 FLOP/s) / kernel seconds.  Layers of a kind are counted from
the configuration's hybrid_override_pattern (M / E).  A program that
has no such kernel or counters (the parent's, say) leaves nothing to
read -> None."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path
import work_ssd


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


def read(ctx, kernel: str, mode: str, decode_program: str = "^$",
         prefill_program: str = "^$", steps_per_program: int = 1):
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
    pattern = cfg.get("hybrid_override_pattern")
    if secs <= 0 or not isinstance(pattern, str):
        return None
    ssm_layers, moe_layers = pattern.count("M"), pattern.count("E")
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])

    def t_min(flops, bytes_):
        return max(bytes_ / peak["hbm_bytes_per_s"],
                   flops / peak["bf16_flops"])
    try:
        dims = tuple(int(cfg[k]) for k in (
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups"))
        hidden, width = int(cfg["hidden_size"]), \
            int(cfg["moe_intermediate_size"])
    except (KeyError, TypeError, ValueError):
        return None
    if mode == "ssd_decode":
        need = [delta(ctx, k) for k in ("ssd_decode_rows", "decode_steps")]
        if None in need or not need[1] or not n_decode or not ssm_layers:
            return None
        total = n_decode * int(steps_per_program) * ssm_layers * t_min(
            *work_ssd.ssd_decode(need[0] / need[1], *dims))
    elif mode == "ssd_prefill":
        need = [delta(ctx, k) for k in ("ssd_prefill_tokens",
                                        "devtime/suffix_prefill/n")]
        if None in need or not need[1] or not n_prefill or not ssm_layers:
            return None
        total = n_prefill * ssm_layers * t_min(
            *work_ssd.ssd_prefill(need[0] / need[1], *dims))
    elif mode == "expert_ffn":
        need = [delta(ctx, k) for k in (
            "experts_live", "expert_slots", "decode_steps",
            "prefill_experts_live", "prefill_expert_slots",
            "devtime/suffix_prefill/n")]
        if None in need or not need[2] or not n_decode or not moe_layers:
            return None

        def layers_t(live, slots):
            return moe_layers * t_min(*work_ssd.expert_ffn_ungated(
                live / moe_layers, slots / moe_layers, hidden, width))
        total = n_decode * int(steps_per_program) * layers_t(
            need[0] / need[2], need[1] / need[2])
        if n_prefill and need[5]:
            total += n_prefill * layers_t(need[3] / need[5],
                                          need[4] / need[5])
    else:
        raise ValueError(f"unknown trace_ssd reader mode {mode!r}")
    return 100.0 * total / secs
