"""CPU fast gate for the dispatch-floor work (`make dispatch-check`).

BENCH_r05 attributed ~94% of the p50 set->vector to the per-call
runtime dispatch (null_dispatch_ms ~63 ms); PR 7's resident ring runs
K batches per dispatch so the floor amortizes to ~floor/K.  This gate
asserts the amortization actually holds on this backend:

  - resident per-drain host overhead shrinks MONOTONICALLY with depth
    (15% noise headroom per step, best-of-ROUNDS to dampen scheduler
    jitter);
  - depth-8 amortized cost is at least 2x below depth 1 (the bench
    phase's acceptance bar is 4x on the measurement backend; the CI
    gate keeps generous slack for loaded shared runners).

The K-overlap rows are measured and printed for attribution but not
gated: on CPU each dispatch's HOST cost dominates the round trip, so
overlap amortizes little here — its win is the device round trip,
which only the TPU bench row (phase `dispatch`) can show.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROUNDS = int(os.environ.get("DISPATCH_CHECK_ROUNDS", "3"))
DEPTHS = (1, 2, 4, 8)


def main() -> int:
    from bench_series import dispatch_depth_rows

    best: dict[int, dict] = {}
    for _ in range(ROUNDS):
        for row in dispatch_depth_rows(DEPTHS, reps=20):
            d = row["depth"]
            if (d not in best or row["resident_ms_per_drain"]
                    < best[d]["resident_ms_per_drain"]):
                best[d] = row
    rows = [best[d] for d in DEPTHS]
    print(json.dumps(rows, indent=1))

    res = [r["resident_ms_per_drain"] for r in rows]
    ok = True
    for i in range(1, len(res)):
        if res[i] > res[i - 1] * 1.15:
            print(f"FAIL: resident per-drain cost rose "
                  f"{res[i - 1]:.4f} -> {res[i]:.4f} ms at depth "
                  f"{DEPTHS[i]} (must shrink monotonically)")
            ok = False
    if res[-1] > res[0] / 2:
        print(f"FAIL: depth-{DEPTHS[-1]} amortized cost "
              f"{res[-1]:.4f} ms not >=2x below depth-1 {res[0]:.4f} ms")
        ok = False
    if ok:
        print(f"OK: resident per-drain {res[0]:.4f} ms @1 -> "
              f"{res[-1]:.4f} ms @{DEPTHS[-1]} "
              f"({res[0] / max(res[-1], 1e-9):.1f}x amortization)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
