"""Reader `trace`: device time of operations or programs whose name
matches a pattern, from the run's one jax.profiler capture (reduced by
benchmark/tracereduce.py), with an optional work-count function.

args: {"line": "ops" | "modules", "pattern": regex, "mode": ...}
modes
  share_of_busy_pct   100 * matched seconds / device busy seconds
  ms_per_event        1e3 * matched seconds / matched events (modules)
  hbm_roofline_pct    every matched program event is one scan of the
                      lane: 100 * (events * bytes of the configuration's
                      stored `rows`, work.topk_scan, / peak HBM bytes/s)
                      / matched seconds.  The bound is memory: its
                      FLOPs at the bf16 peak need less time than its
                      bytes at the HBM peak, which the reader checks.
Nothing matched -> None (the metric is left out of the line)."""
import re

import work          # benchmark/work.py: run.py puts benchmark/ on sys.path


def read(ctx, line: str, pattern: str, mode: str):
    red = ctx.get("trace")
    if not red:
        return None
    rx = re.compile(pattern)
    if line == "modules":
        hits = [(c, s) for name, (c, s) in red["modules"].items()
                if rx.search(name)]
        count, secs = sum(c for c, _ in hits), sum(s for _, s in hits)
    else:
        hits = [s for name, s in red["ops"].items() if rx.search(name)]
        count, secs = len(hits), sum(hits)
    if not hits or secs <= 0:
        return None
    if mode == "share_of_busy_pct":
        return 100.0 * secs / red["busy_s"]
    if mode == "ms_per_event":
        return 1e3 * secs / count
    if mode == "hbm_roofline_pct":
        peak = work.peak_for(ctx["peaks"], ctx["device"]["kind"])
        cfg = ctx["config"]
        flops, bytes_ = work.topk_scan(int(cfg["rows"]),
                                       int(cfg["row_dim"]), 32)
        t_mem = bytes_ / peak["hbm_bytes_per_s"]
        if flops / peak["bf16_flops"] > t_mem:
            raise ValueError("the scan is not memory-bound at this "
                             "shape: the roofline reader needs its bound "
                             "reconsidered")
        return 100.0 * count * t_mem / secs
    raise ValueError(f"unknown trace reader mode {mode!r}")
