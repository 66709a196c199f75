"""Reader `heartbeat_list`: a statistic of a LIST the lane's heartbeat
carries, as it grew over the window.

args: {"path": "expert_totals", "stat": "max_over_mean"}
The list at `path` (slash-separated) holds running totals that never
reset; the window's own are the element-wise difference between the
heartbeat after the window and the one before it (a list the earlier
heartbeat lacks counts as zeros).  Nothing to read (no list, or
nothing grew) -> None."""


def dig(d, path: str):
    for part in path.split("/"):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d if isinstance(d, list) else None


def read(ctx, path: str, stat: str = "max_over_mean"):
    hi = dig(ctx.get("hb_end") or {}, path)
    if not hi:
        return None
    lo = dig(ctx.get("hb_start") or {}, path) or [0] * len(hi)
    if len(lo) != len(hi):
        return None
    grew = [float(b) - float(a) for a, b in zip(lo, hi)]
    total = sum(grew)
    if total <= 0:
        return None
    if stat == "max_over_mean":
        return max(grew) / (total / len(grew))
    raise ValueError(f"unknown heartbeat_list stat {stat!r}")
