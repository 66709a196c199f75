"""Call `submit_completion`: the program's own completion client call
(libsplinter_tpu.engine.client.submit_completion), as a user makes it —
write the prompt to the client's own key, raise the request, wait for
READY, read the slot back (prompt + streamed answer).  Mix parameters:
clients, timeout_ms, warmup.each_document_first.  The payload is
payloads/documents.py's: request i asks document doc_of[i] its
question i (both cycling the pool)."""
import threading

import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


class Call:
    def __init__(self, st, mix: dict, payload):
        from libsplinter_tpu.engine.client import submit_completion
        self.st, self.payload, self.submit = st, payload, submit_completion
        self.timeout_ms = int(mix.get("timeout_ms", 120_000))
        self.n_clients = int(mix.get("clients", mix.get("threads", 1)))
        self.docs_first = int(mix.get("warmup", {}).get(
            "each_document_first", 0))

    @staticmethod
    def key(client: int) -> str:
        return f"__cq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def ask(self, client: int, doc: int, q: int, rec: dict) -> bool:
        prompt = self.payload["text"][doc] + self.payload["questions"][q]
        out = self.submit(self.st, self.key(client), prompt,
                          timeout_ms=self.timeout_ms)
        rec["q"] = (doc, q)
        rec["out_bytes"] = len(out) if isinstance(out, bytes) else -1
        # the slot comes back holding the prompt and whatever of the
        # answer's tokens are bytes; a typed error or a timeout is not
        return isinstance(out, bytes) and out.startswith(prompt)

    def request(self, i: int, client: int, rec: dict) -> bool:
        q = i % len(self.payload["questions"])
        return self.ask(client, int(self.payload["doc_of"][q]), q, rec)

    def warm_up(self, bursts, base: int) -> int:
        """Every document asked once, `each_document_first` at a time
        (the daemon's prefix cache then holds them all), then the
        bursts: that many concurrent one-request clients each."""
        n_docs = len(self.payload["text"])
        step = max(self.docs_first, 1)
        for lo in range(0, n_docs if self.docs_first else 0, step):
            bad = []

            def one(c, d):
                if not self.ask(c, d, (base + d) % len(
                        self.payload["questions"]), {}):
                    bad.append(d)
            ts = [threading.Thread(target=one, args=(d - lo, d))
                  for d in range(lo, min(lo + step, n_docs))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if bad:
                raise RuntimeError(f"warm-up documents {bad} failed")
        base += n_docs if self.docs_first else 0
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
