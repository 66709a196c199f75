"""Headline benchmark: end-to-end embedding throughput per chip.

    python bench.py                  # the whole series, on the chip
    BENCH_CPU=1 python bench.py      # CPU quick-track (embed only),
                                     # labelled as such in its record

Runs bench_series.main() in THIS process — one JAX process holds the
chip, measures every phase, and appends each record to
bench_results.jsonl the moment it lands.  Prints the headline as ONE
JSON line:
  {"metric": "embeddings_per_sec_per_chip", "value": N, "unit":
   "embeddings/s", "vs_baseline": N, ...}

Baseline: BASELINE.md targets >= 100k embeddings/s on a v5e-8 for
Nomic-Embed-Text-v1.5, i.e. 12,500 embeddings/s/chip; vs_baseline is
value / 12500 (>1.0 beats the target's per-chip share).

Exit code: 0 only if every requested phase succeeded.  Without a TPU
(and without BENCH_CPU=1) it exits non-zero before measuring anything.

Env knobs: BENCH_PHASES (default: the full series), BENCH_CPU=1, plus
the per-phase knobs documented in bench_series.py.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    from bench_series import main
    raise SystemExit(main())
