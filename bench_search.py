"""Similarity-kernel benchmark: cosine top-k over a large vector lane.

Thin standalone wrapper over bench_series.phase_search (the single
implementation every entry point runs).  BASELINE.md
row: "Cosine top-k over 1M-vector arena — Pallas kernel (beat the
reference's O(N*768) scalar scan, splinter_cli_cmd_search.c:374-412)".

Prints ONE JSON line {"metric": "search_queries_per_sec", ...};
vs_baseline = kernel qps / numpy host-scan qps.  The detail section
carries fused-vs-unfused q/s, the fused QB sweep {1, 32, 256}, and
the search daemon's coalescing stats + heartbeat-sourced stage
quantiles (bench_series.phase_search).  Appends to
bench_results.jsonl.

Run alone: a chip belongs to one process.  Env:
BENCH_CPU=1, SEARCH_N (default 1,000,000 on TPU / 100,000 on CPU),
SEARCH_D (768), SEARCH_K (10), SEARCH_REPS (20), SEARCHD_N (8192),
SEARCHD_WAVES (8).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_series import shim_main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(shim_main("search"))
