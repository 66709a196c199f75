"""StagedLane restage cost at scale.

Thin standalone wrapper over bench_series.phase_restage (the single
implementation the unified series also runs): builds a real
native store with N populated slots and a (N, dim) f32 vector lane,
then measures full-upload vs O(dirty) refresh (clean / 128-dirty /
8192-dirty) and appends a `staged_lane_restage` record to
bench_results.jsonl.

Backend: host CPU by DEFAULT (the O(dirty) property is host-side
bookkeeping + transfer volume).  RESTAGE_TPU=1 runs on the chip
instead (and fails there if JAX finds none).

MEMORY at the 1M default: nslots rounds N up to a power of two with
2x headroom, so N=1M maps a 2^21 x 768 f32 lane = ~6.4 GB of shm;
the streaming upload (128 MB chunks + MADV_DONTNEED on staged slices)
peaks at ~1.3x the lane — budget ~9 GB (measured 8.46 GB; before the
round-5 diet the full-host-copy path needed ~25 GB).

Env: RESTAGE_N (default 1,000,000 cpu / 131,072 tpu), RESTAGE_DIM
(768), RESTAGE_TPU=1.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_series import shim_main  # noqa: E402


if __name__ == "__main__":
    if os.environ.get("RESTAGE_TPU") != "1":
        os.environ["BENCH_CPU"] = "1"
    raise SystemExit(shim_main("restage"))
