"""Reader `trace_dsa`: the three device stages of attention under a
learned indexer (ops/sparse_attention: index scan, exact selection,
attention over the selected keys) from the run's one jax.profiler
capture (reduced by benchmark/tracereduce.py), the lane's heartbeat
counters and work_dsa.

args: {"kernel": regex over operation names, "decode_program" /
"prefill_program": regexes over program (module) names,
"steps_per_program": decode steps one decode program event runs,
"mode": "scan" | "decode" | "prefill" | "ms_per_step"}

The capture gives the kernel's device seconds and how many programs of
each kind it held.  What an event had to do comes from the lane's
counters, which are sums over rows AND layers (heartbeat):

  a decode step    index_keys_decode / decode_steps pairs scored (one
                   query a row: the distinct keys read are as many),
                   keys_selected_decode / decode_steps selected pairs
                   (and tokens read), decode_rows x layers queries
  a suffix call    index_keys_join / n pairs scored and join_kv / n
                   distinct context tokens under them, (keys_selected -
                   keys_selected_decode) / n selected pairs,
                   (prompt_tokens - prefix_tokens) x layers / n queries
                   (n: devtime suffix_prefill n)

  scan      share = 100 x (decode programs x steps x t_step + suffix
            programs x t_call) / seconds: the scan has one name's stem
            in both kinds of program
  decode    share = 100 x decode programs x steps x t_step / seconds
  prefill   share = 100 x suffix programs x t_call / seconds
  ms_per_step   1e3 x seconds / (decode programs x steps)

t = max(bytes / peak HBM bytes/s, FLOPs / peak bf16 FLOP/s).  A program
that has no such kernel, counters or configuration keys (the parent's,
say) leaves nothing to read -> None."""
import re

import traffic       # benchmark/traffic.py: run.py puts benchmark/ on sys.path
import work
import work_dsa

# the heartbeat's difference over the window, as trace_gqa reads it
delta = traffic.part("readers", "trace_gqa").delta


def read(ctx, kernel: str, decode_program: str, prefill_program: str,
         mode: str, steps_per_program: int = 1):
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
    try:
        sa, layers = cfg["sa_config"], int(cfg["share"]["layers"])
        heads, kv_heads, d = (int(cfg[k]) for k in (
            "num_attention_heads", "num_key_value_heads", "head_dim"))
        hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    except (KeyError, TypeError, ValueError):
        return None
    need = {k: delta(ctx, k) for k in (
        "index_keys_decode", "index_keys_join", "keys_selected",
        "keys_selected_decode", "join_kv", "decode_rows", "decode_steps",
        "prompt_tokens", "prefix_tokens", "devtime/suffix_prefill/n")}
    if secs <= 0 or None in need.values() or not need["decode_steps"]:
        return None
    steps = n_decode * int(steps_per_program)
    if mode == "ms_per_step":
        return 1e3 * secs / steps if steps else None
    peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])

    def t_min(flops, bytes_):
        return max(bytes_ / peak["hbm_bytes_per_s"],
                   flops / peak["bf16_flops"])
    per_step = 1.0 / need["decode_steps"]
    calls = need["devtime/suffix_prefill/n"]
    per_call = 1.0 / calls if calls else 0.0
    q_step = need["decode_rows"] * layers * per_step
    q_call = (need["prompt_tokens"] - need["prefix_tokens"]) * layers \
        * per_call
    total = 0.0
    if mode == "scan":
        total += steps * t_min(*work_dsa.index_scan(
            need["index_keys_decode"] * per_step,
            need["index_keys_decode"] * per_step, q_step, hi, di))
        total += n_prefill * t_min(*work_dsa.index_scan(
            need["index_keys_join"] * per_call, need["join_kv"] * per_call,
            q_call, hi, di))
    elif mode == "decode":
        picked = need["keys_selected_decode"] * per_step
        total += steps * t_min(*work_dsa.selected_attention(
            picked, picked, q_step, heads, kv_heads, d))
    elif mode == "prefill":
        total += n_prefill * t_min(*work_dsa.selected_attention(
            (need["keys_selected"] - need["keys_selected_decode"])
            * per_call, need["join_kv"] * per_call, q_call, heads,
            kv_heads, d))
    else:
        raise ValueError(f"unknown trace_dsa reader mode {mode!r}")
    return 100.0 * total / secs if total else None
