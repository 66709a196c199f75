"""Decomposition profile: where does an embedding batch's time go?

Thin standalone wrapper over bench_series.phase_profile (the single
implementation every entry point runs): steady-state
device ms, sync-dispatch ms, and async-pipelined ms per (batch,
bucket) shape.  Prints ONE JSON line and appends to
bench_results.jsonl.

Run alone: a chip belongs to one process.  BENCH_CPU=1 for a
host-CPU run.  Env: PROFILE_SHAPES, PROFILE_REPS.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_series import shim_main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(shim_main("profile"))
