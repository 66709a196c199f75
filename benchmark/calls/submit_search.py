"""Call `submit_search`: the program's own search client call
(libsplinter_tpu.engine.searcher.submit_search), as a user makes it —
write the query vector to the client's own key, ask for the top k, get
the hits back.  Mix parameters: k, timeout_ms, clients (or threads).
The payload is a (pool, dim) array of query vectors."""
import time

import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


class Call:
    def __init__(self, st, mix: dict, payload):
        # the program's client call, bound once: request() sits in the
        # measured window
        from libsplinter_tpu.engine.searcher import submit_search
        self.st, self.payload, self.submit = st, payload, submit_search
        self.k = int(mix.get("k", 10))
        self.timeout_ms = int(mix.get("timeout_ms", 10_000))
        self.n_clients = int(mix.get("clients", mix.get("threads", 1)))

    @staticmethod
    def key(client: int) -> str:
        return f"__sq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def request(self, i: int, client: int, rec: dict) -> bool:
        key, qi = self.key(client), i % len(self.payload)
        t0 = time.perf_counter_ns()
        self.st.vec_set(key, self.payload[qi])
        rec["write_ns"] = time.perf_counter_ns() - t0
        out = self.submit(self.st, key, self.k, timeout_ms=self.timeout_ms)
        rec["q"], rec["out"] = qi, out
        return isinstance(out, dict) and "i" in out \
            and len(out["i"]) == self.k

    def warm_up(self, bursts, base: int) -> int:
        """Each burst is that many concurrent one-request clients, so
        the daemon meets the batch sizes the window will bring."""
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
