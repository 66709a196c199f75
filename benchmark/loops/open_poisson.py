"""Loop `open_poisson`: arrivals on a seeded Poisson schedule at
`rate_per_s`, each request one client call from a pool of `threads`,
timed from when it was DUE; every request due inside the window is
sent and waited for, and how late the generator ran is reported.

The arrival loop follows libsplinter_tpu/cli/loadgen.py (one dispatcher
thread, absolute due times, no catch-up bursts); that file imports the
program, so the benchmark keeps its own copy.  No cell of
BENCHMARK.json uses this loop yet (PERF.md, Open questions:
search-single, embed-trickle); benchmark/tests/test_traffic.py drives
it against a stand-in call."""
import queue
import threading
import time

import numpy as np


def poisson_due_times(rate: float, seconds: float, shape_seed: int,
                      seed: int) -> np.ndarray:
    """Due times (s from the window's start).  The gaps are
    rate*seconds exponential draws from shape_seed, rescaled to fill
    the window exactly, then permuted by `seed`: every seed offers the
    same number of requests with the same multiset of gaps."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng([int(shape_seed), 4]).exponential(
        1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng([int(seed), 5]).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]


def lateness_ms(due: np.ndarray, sent: np.ndarray) -> dict:
    """How late the generator ran: sent - due, in ms."""
    late = (np.asarray(sent) - np.asarray(due)) * 1e3
    return {"p50": float(np.percentile(late, 50)),
            "p95": float(np.percentile(late, 95)),
            "max": float(late.max()), "n": int(late.size)}


def run(call, mix: dict, seconds: float, seed: int, start_at: int = 0,
        on_tick=None) -> dict:
    due = poisson_due_times(float(mix["rate_per_s"]), seconds,
                            int(mix.get("shape_seed", 0)), seed)
    n = len(due)
    q: queue.Queue = queue.Queue()
    recs: list[dict | None] = [None] * n
    sent = np.zeros(n)

    def worker(w: int) -> None:
        while True:
            j = q.get()
            if j is None:
                return
            rec = {"due": float(due[j])}
            rec["ok"] = call.request(start_at + j, w, rec)
            rec["ms"] = (time.perf_counter() - t0 - due[j]) * 1e3
            recs[j] = rec

    pool = [threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(int(mix["threads"]))]
    t0 = time.perf_counter()
    for t in pool:
        t.start()
    for j in range(n):
        wait = t0 + due[j] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[j] = time.perf_counter() - t0
        q.put(j)
        if on_tick:
            on_tick(sent[j])
    for _ in pool:
        q.put(None)
    for t in pool:
        t.join()
    flat = [r for r in recs if r is not None]
    return {"completed": sum(r["ok"] for r in flat),
            "elapsed_s": seconds, "records": flat, "attempted": n,
            "failed": sum(not r["ok"] for r in flat) + n - len(flat),
            "lateness_ms": lateness_ms(due, sent)}
