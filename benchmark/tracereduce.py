"""From a jax.profiler capture to numbers: the reduction every PR uses.

Two steps, so that the arithmetic can be checked on a small recorded
capture without the profiler's file format:

  extract(path)  .xplane.pb -> {"planes": [{"name", "lines": [{"name",
                 "events": [[name, start_ns, dur_ns], ...]}]}]}
                 (jax.profiler.ProfileData; needs jax, touches no device)
  reduce(cap)    that dict -> window_s, busy_s (union of the intervals
                 in which an operation ran on the device, averaged over
                 the device planes that ran any: the chips used),
                 per-operation and per-module
                 seconds, the longest idle gaps and what the host was
                 doing in them

Device planes are the ones named "/device:TPU:<n>".  On such a plane
the line "XLA Ops" holds one event per operation that ran, and "XLA
Modules" one per jitted program; "Steps" and the like are summaries and
are not counted as work.  A capture with no device plane (the CPU
rehearsal) has busy_s 0.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# operations that only enclose others (the ring's while loop and its
# like): inside the busy union, but not credited with time of their own
WRAPPERS = ("while", "conditional", "call")


def op_name(raw: str) -> str:
    """The trace names an operation by its whole HLO line,
    "%fusion.12 = f32[...] fusion(...)": keep "fusion.12"."""
    return raw.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def extract(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals: list[tuple[int, int]]) -> tuple[int, list]:
    """Total length of the union of [start, end) intervals, and the
    merged intervals themselves, in order."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def gaps_ns(merged: list, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of [lo, hi) left by the merged busy ones."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def host_activity(host_events: list, gap: tuple[int, int]) -> str:
    """What the host was doing in an idle gap: the host event that
    overlaps it longest; "no host event" when the traced host threads
    were silent (sleeping in the store's wait, or in untraced Python)."""
    best, best_ns = "no host event", 0
    for name, s, d in host_events:
        ov = min(s + d, gap[1]) - max(s, gap[0])
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce(cap: dict | str, top: int = 10) -> dict:
    if isinstance(cap, str):
        cap = extract(cap)
    lo, hi = None, None
    for plane in cap["planes"]:
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    if lo is None:
        return {"window_s": 0.0, "busy_s": 0.0, "ops": {}, "modules": {},
                "devices": 0, "breakdown": {"device_ops": [],
                                            "idle_gaps": []}}
    # the chips used: a cell that holds a host of four for its steady
    # CPU and serves from one of them has three planes with no event
    chips = [p for p in cap["planes"] if p["name"].startswith(DEVICE_PREFIX)]
    dev = [p for p in chips
           if any(line["events"] for line in p["lines"]
                  if line["name"] in (OPS_LINE, MODULES_LINE))]
    host_events = [ev for p in cap["planes"] if p not in chips
                   for line in p["lines"] for ev in line["events"]]
    ops: dict[str, float] = {}
    modules: dict[str, list] = {}
    busy_total, gap_by_host = 0, {}
    for plane in dev:
        intervals = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                for raw, s, d in line["events"]:
                    intervals.append((s, s + d))
                    name = op_name(raw)
                    if name.split(".")[0] in WRAPPERS:
                        continue
                    ops[name] = ops.get(name, 0.0) + d / 1e9
            elif line["name"] == MODULES_LINE:
                for name, s, d in line["events"]:
                    m = modules.setdefault(name, [0, 0.0])
                    m[0] += 1
                    m[1] += d / 1e9
        if not intervals:                  # no op line: count modules
            intervals = [(s, s + d) for line in plane["lines"]
                         if line["name"] == MODULES_LINE
                         for _, s, d in line["events"]]
        busy, merged = union_ns(intervals)
        busy_total += busy
        longest = sorted(gaps_ns(merged, lo, hi),
                         key=lambda g: g[0] - g[1])[:top * 4]
        for g in longest:
            what = host_activity(host_events, g)
            gap_by_host[what] = gap_by_host.get(what, 0.0) \
                + (g[1] - g[0]) / 1e9 / len(dev)
    n = max(len(dev), 1)
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy_total / 1e9 / n,
        "devices": len(dev),
        "ops": {k: v / n for k, v in ops.items()},
        "modules": {k: [c, v / n] for k, (c, v) in modules.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                gap_by_host.items(), key=lambda kv: -kv[1])[:top]]}}


def main(argv=None) -> int:
    """tracereduce.py <trace dir> <out.json>: reduce the directory's
    capture and write the result (run.py calls this in a child, so that
    it never imports JAX itself)."""
    import json
    import sys
    argv = sys.argv[1:] if argv is None else argv
    path = find_xplane(argv[0])
    if not path:
        print("the capture left no .xplane.pb", file=sys.stderr)
        return 1
    with open(argv[1], "w") as f:
        json.dump(reduce(path), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
