#!/usr/bin/env python
"""CI gate: the disaggregated pair loses nothing under a prefill
burst and hands its pages over the wire, in-process.

Runs PrefillLane + DecodeLane on one shared store (ISSUE 18) and
drives `spt loadgen`'s prefill-burst scenario through a 1x -> 10x ->
1x prompt-heavy rate step while a steady decode-floor tenant streams
underneath.  It counts, it does not time: how far a burst moves the
decode floor's inter-chunk latency is a device question, and no
benchmark cell runs the split pair yet (PERF.md §7).  Asserted:

  - ZERO admitted-request loss (loadgen's `lost` classification is
    the drain-protocol contract, same as scale_step_check);
  - the handoff plane actually ran: prefill handed off wire pages and
    decode adopted them (handoff_refill == 0 — the store is sized so
    real page export/import is what runs, not the re-prefill
    fallback).

Run: JAX_PLATFORMS=cpu python scripts/disagg_check.py
(make disagg-check wires it into make check.)
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax.numpy as jnp  # noqa: E402

from libsplinter_tpu import Store  # noqa: E402
from libsplinter_tpu.cli.loadgen import (LoadGenerator,  # noqa: E402
                                         TenantSpec)
from libsplinter_tpu.engine import protocol as P  # noqa: E402
from libsplinter_tpu.engine.disagg import (DecodeLane,  # noqa: E402
                                           PrefillLane)
from libsplinter_tpu.models.decoder import (CompletionModel,  # noqa: E402
                                            DecoderConfig)

STORE = f"/spt-disagg-check-{os.getpid()}"
RATE = 2.0                          # 1x offered rate per class (req/s)
BURST_PROFILE = [(1.0, 2.0), (10.0, 6.0), (1.0, 2.0)]


def main() -> int:
    Store.unlink(STORE)
    # max_val 16384 > page_wire_bytes(tiny f32, page=8) = 4096: the
    # gate exercises the REAL wire export/import, never the fallback
    store = Store.create(STORE, nslots=1024, max_val=16384, vec_dim=8)
    model = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(32,), temp=0.0, seed=1,
                            suffix_buckets=(8,))
    kw = dict(model=model, max_new_tokens=10, flush_tokens=2,
              template="none", batch_cap=4, page_size=8)
    lanes = [PrefillLane(store, **kw), DecodeLane(store, **kw)]
    ths: list[threading.Thread] = []
    try:
        for d in lanes:
            d.attach()
        ths = [threading.Thread(
            target=d.run_continuous,
            kwargs=dict(idle_timeout_ms=10, stop_after=300.0),
            daemon=True) for d in lanes]
        for th in ths:
            th.start()

        # warm the pair end-to-end (prefill bucket + decode chunk
        # compiles) so the burst meets a serving pair
        for i in range(3):
            key = f"__warm/{i}"
            store.set(key, f"warm {i} up")
            store.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
            store.bump(key)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if all(store.labels(f"__warm/{i}") & P.LBL_READY
                   for i in range(3)):
                break
            time.sleep(0.05)
        else:
            print("FAIL: warmup requests never completed")
            return 1

        rep = LoadGenerator(
            store, [TenantSpec(tenant=1, rate=RATE,
                               deadline_ms=60_000)],
            scenario="prefill-burst", rate_profile=BURST_PROFILE,
            corpus=16, seed=12, drain_s=45.0).run()
        pf, dl = lanes[0]._lane_stats, lanes[1]._lane_stats
        counts = {"issued": rep["issued"],
                  "completed": rep["ok"] + rep["ok_late"],
                  "lost": rep["lost"],
                  "handoffs": pf.get("handoffs", 0),
                  "handoff_failed": pf.get("handoff_failed", 0),
                  "adopted": dl.get("adopted", 0),
                  "readopted": dl.get("readopted", 0),
                  "handoff_refill": dl.get("handoff_refill", 0),
                  "adopt_backpressure": dl.get("adopt_backpressure", 0)}

        fails = []
        if counts["lost"]:
            fails.append(f"{counts['lost']} admitted requests LOST "
                         "(zero-loss contract)")
        if not counts["completed"]:
            fails.append("no request completed")
        if not counts["handoffs"]:
            fails.append("prefill lane recorded zero handoffs")
        if not counts["adopted"]:
            fails.append("decode lane adopted zero rows")
        if counts["handoff_refill"]:
            fails.append(f"{counts['handoff_refill']} adoptions fell "
                         "back to re-prefill (wire path not "
                         "exercised)")
        print(json.dumps({"check": "disagg", "ok": not fails,
                          "fails": fails, **counts}))
        return 1 if fails else 0
    finally:
        for d in lanes:
            d.stop()
        for th in ths:
            th.join(timeout=30)
        store.close()
        Store.unlink(STORE)


if __name__ == "__main__":
    raise SystemExit(main())
