"""Decode-path benchmark: completion tokens/sec + daemon e2e latency.

Thin standalone wrapper over bench_series' decode phases (the single
implementation every entry point runs):

  decode         prefill latency, chunked / per-token-sync / wide-chunk
                 / batched / speculative tokens per second (the
                 reference's cadence is a serial per-token llama.cpp
                 decode with an 8-token flush, splainference.cpp:333-354;
                 vs_baseline = chunked / per-token-sync on the SAME
                 hardware and weights), plus the paged-vs-dense KV
                 sweep: block-paged decode at batch {8, 32, 64} inside
                 a FIXED pool of 8 windows' pages (the r05 dense
                 batch=8 cache HBM envelope) — ledgered under the
                 kv_cache_dense / kv_cache_paged detail labels
  decode_daemon  completion-daemon e2e + continuous serving (now the
                 block-paged lane: batch_cap 32 default)

Prints ONE JSON line {"metric": "decode_tokens_per_sec", ...}; every
phase record appends to bench_results.jsonl.

Run alone: a chip belongs to one process.  Env:
BENCH_CPU=1, DECODE_TOKENS (256), DECODE_CHUNK (8),
DECODE_GEOMETRY=tiny|flagship, DECODE_QUANT=1 (int8 weight residency),
DECODE_DAEMON=0 (skip the daemon phase), DECODE_PAGED=0 (skip the
paged sweep), DECODE_PAGED_SWEEP=8,32,64 (batch widths; CPU default 8).
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_series import shim_main  # noqa: E402

if __name__ == "__main__":
    phases = ["decode_quant" if os.environ.get("DECODE_QUANT") == "1"
              else "decode"]
    if os.environ.get("DECODE_DAEMON", "1") == "1":
        phases.append("decode_daemon")
    raise SystemExit(shim_main(*phases))
