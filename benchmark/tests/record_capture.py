#!/usr/bin/env python3
"""How benchmark/tests/capture_small.json was made: the first
`--ms` milliseconds of a real capture, in tracereduce's extracted form.

    python benchmark/tests/record_capture.py <dir with the capture> out.json --ms 300 --skip-ms 1500
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tracereduce  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=150.0)
    ap.add_argument("--skip-ms", type=float, default=0.0)
    a = ap.parse_args()
    cap = tracereduce.extract(tracereduce.find_xplane(a.trace_dir))
    starts = [ev[1] for p in cap["planes"] for ln in p["lines"]
              for ev in ln["events"]]
    lo = min(starts) + int(a.skip_ms * 1e6)
    hi = lo + int(a.ms * 1e6)
    for p in cap["planes"]:
        for ln in p["lines"]:
            # the trace names an operation by its whole HLO line; the
            # reduction reads only its left-hand side
            ln["events"] = [[n.split(" = ", 1)[0], s - lo, d]
                            for n, s, d in ln["events"]
                            if lo <= s and s + d <= hi]
        p["lines"] = [ln for ln in p["lines"] if ln["events"]]
    cap["planes"] = [p for p in cap["planes"] if p["lines"]]
    with open(a.out, "w") as f:
        json.dump(cap, f, separators=(",", ":"))
    print(a.out, os.path.getsize(a.out), "bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
