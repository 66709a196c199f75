"""CI gate: the pipeline lane collapses per-stage client round trips
(`make pipeline-check`).

Runs the SAME rag-churn chain two ways against one in-process stack
(stub encoder/generator — this exercises orchestration, not model
math): the client-side scenario (one submit+poll round trip per
ingest -> search -> complete hop) and the stored-script scenario
(ONE pipeline-lane request, the chain server-side).  It counts the
requests the CLIENT puts on the wire per completed chain — N hops
against one — and holds both runs to the standing zero-admitted-loss
invariant.  What a saved round trip is worth in milliseconds depends
on the machine between client and store, and no benchmark cell reads
it yet (PERF.md §7).
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from libsplinter_tpu import Store  # noqa: E402
from libsplinter_tpu.cli.loadgen import (LoadGenerator,  # noqa: E402
                                         TenantSpec)
from libsplinter_tpu.engine import protocol as P  # noqa: E402
from libsplinter_tpu.engine.completer import Completer  # noqa: E402
from libsplinter_tpu.engine.embedder import Embedder  # noqa: E402
from libsplinter_tpu.engine.pipeliner import Pipeliner  # noqa: E402
from libsplinter_tpu.engine.searcher import Searcher  # noqa: E402

CLIENT_HOPS = 3       # rag-churn client side: ingest, search, complete


class ClientWire:
    """The load generator's handle on the store, counting the requests
    the client raises (every submit is one `label_or` of a request
    bit with WAITING — one round trip the client then polls out).
    The daemons hold the store itself, so what a script dispatches
    server-side is not counted."""

    def __init__(self, store):
        self._store = store
        self.requests = 0

    def label_or(self, key, mask):
        if mask & P.LBL_WAITING:
            self.requests += 1
        return self._store.label_or(key, mask)

    def __getattr__(self, name):
        return getattr(self._store, name)


def main() -> int:
    name = f"/spt-plcheck-{os.getpid()}"
    st = Store.create(name, nslots=512, max_val=1024, vec_dim=32)

    def enc(texts):
        out = np.zeros((len(texts), st.vec_dim), np.float32)
        for i, t in enumerate(texts):
            out[i, hash(t) % st.vec_dim] = 1.0
        return out

    emb = Embedder(st, encoder_fn=enc, max_ctx=64)
    sr = Searcher(st)
    comp = Completer(st, generate_fn=lambda p: iter([b"answer"]),
                     template="none")
    pl = Pipeliner(st)
    daemons = (emb, sr, comp, pl)
    for d in daemons:
        d.attach()
    ths = [threading.Thread(target=d.run,
                            kwargs=dict(idle_timeout_ms=10,
                                        stop_after=180.0),
                            daemon=True) for d in daemons]
    for t in ths:
        t.start()
    time.sleep(0.2)

    def chains(scenario: str) -> dict:
        wire = ClientWire(st)
        gen = LoadGenerator(wire, [TenantSpec(1, 10.0, deadline_ms=8000)],
                            duration_s=3.0, corpus=8, seed=11,
                            scenario=scenario)
        scripts0 = pl.stats.scripts_completed
        rep = gen.run()
        return {"issued": rep["issued"],
                "completed": rep["ok"] + rep["ok_late"],
                "lost": rep["lost"], "client_requests": wire.requests,
                "lane_scripts": pl.stats.scripts_completed - scripts0}

    try:
        client = chains("rag-churn")
        script = chains("rag-churn-script")
    finally:
        for d in daemons:
            d.stop()
        for t in ths:
            t.join(timeout=15)
        st.close()
        Store.unlink(name)

    fails = []
    for tag, rep in (("client", client), ("script", script)):
        if rep["lost"]:
            fails.append(f"{tag}: {rep['lost']} admitted requests LOST")
        # one chain may still be in flight when the window closes
        if rep["completed"] < max(1, rep["issued"] - 1):
            fails.append(f"{tag}: {rep['completed']} of "
                         f"{rep['issued']} chains completed")
    if not (CLIENT_HOPS * client["completed"]
            <= client["client_requests"]
            <= CLIENT_HOPS * client["issued"]):
        fails.append(f"client-side chain raised "
                     f"{client['client_requests']} requests for "
                     f"{client['completed']} completed of "
                     f"{client['issued']} chains, expected "
                     f"{CLIENT_HOPS} per chain")
    if client["lane_scripts"]:
        fails.append("the client-side chain reached the pipeline lane")
    if script["client_requests"] != script["issued"]:
        fails.append(f"stored script raised "
                     f"{script['client_requests']} client requests for "
                     f"{script['issued']} chains, expected ONE each")
    if script["lane_scripts"] != script["completed"]:
        fails.append(f"pipeline lane completed "
                     f"{script['lane_scripts']} scripts for "
                     f"{script['completed']} chains")
    print(json.dumps({"check": "pipeline_roundtrip", "ok": not fails,
                      "fails": fails, "client": client,
                      "script": script}))
    return 1 if fails else 0


if __name__ == "__main__":
    raise SystemExit(main())
