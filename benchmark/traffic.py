"""The one general traffic generator.

A traffic mix is a data file under benchmark/traffic/: parameters and
nothing else.  This module reads it and puts three parts together, each
a file found by the name the mix gives — so a mix of a new kind is new
files, and no file that is there is edited:

  payloads/<payload.kind>.py  make(spec, seed, st, prepared) -> payload
                              what the requests carry, from the seed
  calls/<call>.py             Call(st, mix, payload) with
                                prepare()                  once, before the daemon starts
                                request(i, client, rec) -> bool   one request through the
                                                           program's own client call
                                warm_up(bursts, base) -> int      the cell's own shapes;
                                                           returns the next ordinal
  loops/<loop>.py             run(call, mix, seconds, seed, start_at, on_tick) -> result
                              the arrival discipline

A result is {"completed", "elapsed_s", "attempted", "failed",
"records": [{"t" or "due", "ms", "ok", ...}]} and whatever else the loop
wants printed (`lateness_ms`).  Every seed gets the same multiset of
sizes and gaps (drawn from the mix's own `shape_seed`), in another order
and with other contents: the seed must not change the amount of work.
No cell's, mix's or configuration's name appears here or in any part.
"""
from __future__ import annotations

import importlib.util
import os
import threading

HERE = os.path.dirname(os.path.abspath(__file__))


def part(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Mix:
    """One traffic mix, ready to drive the store `st`."""

    def __init__(self, st, spec: dict, seed: int, prepared: dict):
        self.spec, self.seed = spec, int(seed)
        self.payload = part("payloads", spec["payload"]["kind"]).make(
            spec["payload"], seed, st, prepared)
        self.call = part("calls", spec["call"]).Call(st, spec, self.payload)
        self.loop = part("loops", spec["loop"])
        self.call.prepare()

    def warm_up(self, base: int) -> int:
        return self.call.warm_up(self.spec.get("warmup", {}).get(
            "bursts", []), base)

    def run(self, seconds: float, start_at: int = 0, on_tick=None) -> dict:
        return self.loop.run(self.call, self.spec, seconds, self.seed,
                             start_at, on_tick)


def burst(call, n: int, base: int) -> int:
    """n concurrent one-request clients (a warm-up burst); returns how
    many failed."""
    bad = []

    def one(c):
        if not call.request(base + c, c, {}):
            bad.append(c)
    ts = [threading.Thread(target=one, args=(c,)) for c in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return len(bad)
