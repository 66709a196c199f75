"""Call `submit_completion_budget`: the program's own completion client
call with the REQUEST'S OWN answer budget
(libsplinter_tpu.engine.client.submit_completion(...,
max_new_tokens=n)), as a user of a chat endpoint with a per-request
token cap makes it — write the prompt to the client's own key, stamp
the budget, raise the request, wait for READY, read the slot back
(prompt + streamed answer).  Mix parameters: clients, timeout_ms.  The
payload is payloads/fresh_prompts.py's: request i asks prompt i with
budget i (cycling the pool)."""
import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


class Call:
    def __init__(self, st, mix: dict, payload):
        from libsplinter_tpu.engine.client import submit_completion
        self.st, self.payload, self.submit = st, payload, submit_completion
        self.timeout_ms = int(mix.get("timeout_ms", 120_000))
        self.n_clients = int(mix.get("clients", mix.get("threads", 1)))

    @staticmethod
    def key(client: int) -> str:
        return f"__cq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def request(self, i: int, client: int, rec: dict) -> bool:
        q = i % len(self.payload["prompts"])
        prompt, budget = self.payload["prompts"][q], \
            int(self.payload["budgets"][q])
        out = self.submit(self.st, self.key(client), prompt,
                          timeout_ms=self.timeout_ms,
                          max_new_tokens=budget)
        rec["q"] = q
        rec["budget"] = budget
        rec["out_bytes"] = len(out) if isinstance(out, bytes) else -1
        # the slot comes back holding the prompt and whatever of the
        # answer's tokens are bytes; a typed error or a timeout is not
        return isinstance(out, bytes) and out.startswith(prompt)

    def warm_up(self, bursts, base: int) -> int:
        """The bursts: that many concurrent one-request clients each."""
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
