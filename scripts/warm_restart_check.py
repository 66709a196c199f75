#!/usr/bin/env python
"""CI gate: a supervised kill-and-restart comes back WARM.

Runs one tiered completer lane (`--kv-tier-pages` + a persistent
`--kv-tier-persist` segment, ISSUE 19) under `spt supervise`, drives
`spt loadgen` through it, SIGKILLs the lane MID-LOAD, and asserts the
warm-restart contract at smoke scale.  It counts, it does not time:
what a readmission saves over a re-prefill in milliseconds is the
chip's to say, and no benchmark cell restarts a lane yet (PERF.md §7).

  - zero admitted-request loss through the kill (the respawned lane
    reclaims every stranded claim — loadgen's `lost` classification);
  - the respawn attaches WARM: the persistent radix index restores
    (heartbeat tier_restored > 0, no typed tier_restore_reason);
  - the hot prompts served before the kill come back via DRAM/file
    readmission, not re-prefill: over the three hot prompts the
    respawn counts tier_readmits > 0, no prefix miss, and every full
    page of every prompt mapped (`prefix_tokens`, as the completer
    counts them);
  - greedy bytes for those prompts are identical across the restart.

Run: JAX_PLATFORMS=cpu python scripts/warm_restart_check.py
(make warm-check wires it into make check.)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

STORE = f"/spt-warm-check-{os.getpid()}"
PAGE = 8
WARM_PROMPTS = [f"the warm set prompt number {i} stays hot"
                for i in range(3)]


def child(store_name: str, persist_name: str) -> int:
    """The supervised lane: a tiny tiered completer with the
    persistent warm layer armed (what `spt supervise --tier-pages N
    --tier-persist` fans out at production scale)."""
    import jax.numpy as jnp

    from libsplinter_tpu import Store
    from libsplinter_tpu.engine.completer import Completer
    from libsplinter_tpu.models.decoder import (CompletionModel,
                                                DecoderConfig)
    st = Store.open(store_name)
    model = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(32,), temp=0.0, seed=1,
                            suffix_buckets=(8,))
    # the tier holds the hot set AND everything the kill window's own
    # prompts spill (~220 pages): a tier the load overflows drops the
    # hot set before the respawn can readmit it
    comp = Completer(st, model=model, max_new_tokens=10,
                     flush_tokens=2, template="none", batch_cap=4,
                     page_size=PAGE, kv_tier_pages=1024,
                     kv_tier_persist=persist_name)
    comp.attach()
    comp.run_continuous(idle_timeout_ms=10, stop_after=900.0)
    return 0


def main() -> int:
    from libsplinter_tpu import Store
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec
    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.kv_tier import TierPersist
    from libsplinter_tpu.engine.supervisor import Supervisor

    persist = f"{STORE}-kvtier"
    Store.unlink(STORE)
    TierPersist.unlink(persist)
    # max_val 16384: same sizing as disagg_check — roomy values, the
    # tier's own persistence lives in its own segment
    store = Store.create(STORE, nslots=1024, max_val=16384, vec_dim=8)

    def spawn(lane):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             STORE, persist])

    sup = Supervisor(STORE, lanes=("completer",), spawn_fn=spawn,
                     store=store, backoff_base_ms=100,
                     backoff_max_ms=2000, breaker_threshold=8,
                     breaker_window_s=120, startup_grace_s=300)
    sup_t = threading.Thread(target=sup.run,
                             kwargs={"poll_interval_s": 0.1,
                                     "stop_after": 900.0})
    sup_t.start()

    def submit(key, prompt):
        store.set(key, prompt)
        store.label_or(key, P.LBL_INFER_REQ | P.LBL_WAITING)
        store.bump(key)

    def await_ready(keys, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(store.labels(k) & P.LBL_READY for k in keys):
                return True
            time.sleep(0.05)
        return False

    def heartbeat():
        return json.loads(
            store.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))

    def wait_heartbeat(generation, ok, pause, timeout=60):
        """The first heartbeat of lane generation `generation` or later
        that `ok` accepts, read every `pause` seconds."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            snap = heartbeat()
            if snap.get("generation", 0) >= generation and ok(snap):
                return snap
            time.sleep(pause)
        return None

    try:
        # warm the lane AND plant the hot set the restart must revive
        warm_keys = [f"__warm/{i}" for i in range(len(WARM_PROMPTS))]
        for k, p in zip(warm_keys, WARM_PROMPTS):
            submit(k, p)
        if not await_ready(warm_keys, 240):
            print("FAIL: warmup requests never completed")
            return 1
        pre_bytes = [store.get(k).rstrip(b"\0") for k in warm_keys]
        gen_hb = heartbeat().get("generation", 0)
        # let a dirty-gated checkpoint beat land so the snapshot
        # covers the hot set's inserts
        time.sleep(6.0)

        # SIGKILL mid-load: a loadgen window is in flight when the
        # lane dies — the respawn must reclaim every claim
        holder: dict = {}
        kt = threading.Thread(
            target=lambda: holder.update(rep=LoadGenerator(
                store, [TenantSpec(tenant=1, rate=2.0,
                                   deadline_ms=120_000)],
                scenario="prefill-burst", rate_profile=[(1.0, 8.0)],
                corpus=16, seed=32, drain_s=90.0).run()))
        kt.start()
        time.sleep(2.0)
        lane = sup.lanes["completer"]
        gen_before = lane.generation
        proc = lane.proc
        if proc is None:
            print("FAIL: no live lane process to kill")
            return 1
        proc.kill()                  # no checkpoint, no cleanup
        kt.join()
        rep_kill = holder["rep"]

        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if lane.generation > gen_before and lane.pid:
                break
            time.sleep(0.1)
        else:
            print("FAIL: supervisor never respawned the lane")
            return 1

        # every request of the kill window is terminal, so the
        # respawn's counters stand still: two reads more than one 2 s
        # beat apart that agree are the state the hot set meets
        seen: list[int] = []

        def still(snap):
            seen.append(snap.get("completions", 0))
            return len(seen) > 1 and seen[-1] == seen[-2]

        before = wait_heartbeat(gen_hb + 1, still, 2.2)
        if before is None:
            print("FAIL: the respawned lane published no heartbeat")
            return 1

        # the SAME prompts through the respawned lane: must come back
        # byte-identical via the restored index + readmission
        rewarm_keys = [f"__rewarm/{i}"
                       for i in range(len(WARM_PROMPTS))]
        for k, p in zip(rewarm_keys, WARM_PROMPTS):
            submit(k, p)
        if not await_ready(rewarm_keys, 240):
            print("FAIL: post-restart requests never completed")
            return 1
        post_bytes = [store.get(k).rstrip(b"\0") for k in rewarm_keys]
        served = before.get("completions", 0) + len(WARM_PROMPTS)
        snap = wait_heartbeat(
            gen_hb + 1, lambda s: s.get("completions", 0) >= served, 0.2)
        if snap is None:
            print("FAIL: the heartbeat never counted the hot set")
            return 1

        def hot(key):
            return snap.get(key, 0) - before.get(key, 0)

        # byte tokenizer: BOS + one token per char; only FULL pages
        # are shared, the tail of each prompt is suffix-prefilled
        hot_tokens = sum(len(p) + 1 for p in WARM_PROMPTS)
        hot_mapped = sum((len(p) + 1) // PAGE * PAGE
                         for p in WARM_PROMPTS)
        counts = {
            "lost": rep_kill["lost"], "issued": rep_kill["issued"],
            "restarts": lane.restarts,
            "bytes_identical": post_bytes == pre_bytes,
            "tier_restored": snap.get("tier_restored", 0),
            "tier_restore_reason": snap.get("tier_restore_reason", ""),
            "hot_prompt_tokens": hot("prompt_tokens"),
            "hot_prefix_tokens": hot("prefix_tokens"),
            "hot_tier_readmits": hot("tier_readmits"),
            "hot_prefix_hits": hot("prefix_hits"),
            "hot_prefix_misses": hot("prefix_misses"),
        }

        fails = []
        if counts["lost"]:
            fails.append(f"{counts['lost']} admitted requests LOST "
                         "(zero-loss contract)")
        if lane.restarts < 1:
            fails.append("the lane never restarted (kill not seen)")
        if post_bytes != pre_bytes:
            fails.append("hot-prompt bytes changed across the "
                         "restart (greedy must be identical)")
        if not counts["tier_restored"]:
            fails.append("respawn attached COLD (tier_restored == 0 "
                         "— persistent index not restored)")
        if counts["tier_restore_reason"]:
            fails.append("typed cold fallback: tier_restore_reason="
                         f"{counts['tier_restore_reason']!r}")
        if counts["hot_prompt_tokens"] != hot_tokens:
            fails.append(f"the heartbeat counted "
                         f"{counts['hot_prompt_tokens']} hot prompt "
                         f"tokens, expected {hot_tokens}")
        if counts["hot_tier_readmits"] < 1:
            fails.append("no readmissions: the warm set was "
                         "re-prefilled, not readmitted")
        if counts["hot_prefix_misses"] or \
                counts["hot_prefix_hits"] != len(WARM_PROMPTS):
            fails.append(f"hot set: {counts['hot_prefix_hits']} hits, "
                         f"{counts['hot_prefix_misses']} misses of "
                         f"{len(WARM_PROMPTS)} prompts")
        if counts["hot_prefix_tokens"] != hot_mapped:
            fails.append(f"hot set re-prefilled: mapped "
                         f"{counts['hot_prefix_tokens']} of "
                         f"{hot_mapped} full-page tokens")
        print(json.dumps({"check": "warm_restart", "ok": not fails,
                          "fails": fails, **counts}))
        return 1 if fails else 0
    finally:
        sup.stop()
        sup_t.join(timeout=30)
        sup.shutdown()
        store.close()
        Store.unlink(STORE)
        TierPersist.unlink(persist)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        raise SystemExit(child(sys.argv[2], sys.argv[3]))
    raise SystemExit(main())
