"""Loop `closed_clients_think`: `clients` threads, each asking again
`think_ms[0]`..`think_ms[1]` milliseconds after its call returned — the
time an agent's tool runs between the model's tool call and the next
turn.  Client c sends requests c, c+n, c+2n, ... of the pool; its gaps
come from the mix's `shape_seed` and its own number (the same gaps for
every seed: the seed must not change the amount of work), uniform on
the range.  A request counts where it FINISHED: the rate is what came
back inside the window.  think_ms [0, 0] is loops/closed_clients.py."""
import threading
import time

import numpy as np


def run(call, mix: dict, seconds: float, seed: int, start_at: int = 0,
        on_tick=None) -> dict:
    n_clients = int(mix["clients"])
    lo, hi = (float(v) / 1e3 for v in mix["think_ms"])
    t0 = time.perf_counter()
    t_end = t0 + seconds
    recs: list[list[dict]] = [[] for _ in range(n_clients)]

    def worker(c: int) -> None:
        gaps = np.random.default_rng(
            [int(mix.get("shape_seed", 0)), 11, c])
        i = start_at + c
        while True:
            ts = time.perf_counter()
            if ts >= t_end:
                return
            rec = {"t": ts - t0}
            rec["ok"] = call.request(i, c, rec)
            rec["ms"] = (time.perf_counter() - ts) * 1e3
            recs[c].append(rec)
            i += n_clients
            think = float(gaps.uniform(lo, hi))
            if time.perf_counter() + think >= t_end:
                return
            time.sleep(think)

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    while any(t.is_alive() for t in threads):
        time.sleep(0.05)
        if on_tick:
            on_tick(time.perf_counter() - t0)
    for t in threads:
        t.join()
    flat = [r for rs in recs for r in rs]
    inside = [r for r in flat if r["t"] + r["ms"] / 1e3 <= seconds]
    return {"completed": sum(r["ok"] for r in inside),
            "elapsed_s": seconds, "records": flat,
            "attempted": len(flat),
            "failed": sum(not r["ok"] for r in flat)}
