"""Call `submit_mixed`: the program's own completion client call
(libsplinter_tpu.engine.client.submit_completion) from two classes of
client in one queue.  Clients 0 .. long_clients - 1 each own a
long-lived session and send its NEXT turn every time (calls/
submit_session_turn.py's rule: the whole history so far plus the
script's next increment); every other client sends a FRESH prompt that
shares nothing, request i the pool's prompt i.  Either way: the prompt
written to the client's own key, the request raised, READY waited for,
the slot read back.  Mix parameters: clients, long_clients, timeout_ms,
answer_tokens, warmup.each_session_first.  The payload is payloads/
sessions_and_fresh.py's.  A turn that would run past the script, or
leave the answer no room in it, fails its request; rec["class"] says
which class a request was, rec["turn"] a session's turn."""
import threading
import time

import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


class Call:
    def __init__(self, st, mix: dict, payload):
        from libsplinter_tpu.engine.client import submit_completion
        self.st, self.payload, self.submit = st, payload, submit_completion
        self.timeout_ms = int(mix.get("timeout_ms", 120_000))
        self.n_clients = int(mix["clients"])
        self.n_long = int(mix["long_clients"])
        self.first = int(mix.get("warmup", {}).get(
            "each_session_first", 0))
        self.room = int(mix.get("answer_tokens", 0))
        if self.n_long > len(payload["text"]):
            raise ValueError("more long clients than sessions: a "
                             "session has one owner")
        self.turn = [0] * self.n_long         # each touched by its owner only

    @staticmethod
    def key(client: int) -> str:
        return f"__cq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def request(self, i: int, client: int, rec: dict) -> bool:
        if client >= self.n_long:
            rec["class"] = "short"
            pool = self.payload["fresh_text"]
            prompt = pool[i % len(pool)]
            rec["prompt_tokens"] = len(prompt) + 1
        else:
            rec["class"] = "long"
            ends, t = self.payload["ends"][client], self.turn[client]
            rec["turn"] = t
            total = len(self.payload["text"][client]) + 1
            if t >= len(ends) or int(ends[t]) + self.room > total:
                time.sleep(0.05)              # past the script: a failed
                return False                  # request, not a hot loop
            self.turn[client] = t + 1
            # BOS is a token and no byte: ends[t] tokens are ends[t] - 1 bytes
            prompt = self.payload["text"][client][:int(ends[t]) - 1]
            rec["prompt_tokens"] = int(ends[t])
        out = self.submit(self.st, self.key(client), prompt,
                          timeout_ms=self.timeout_ms)
        rec["out_bytes"] = len(out) if isinstance(out, bytes) else -1
        return isinstance(out, bytes) and out.startswith(prompt)

    def warm_up(self, bursts, base: int) -> int:
        """Every session's turn 0 asked once, `each_session_first` at a
        time (the daemon's prefix cache then holds every base history
        and the window's tail at its end), then the bursts: that many
        concurrent clients, each sending its class's next request."""
        step = max(self.first, 1)
        for lo in range(0, self.n_long if self.first else 0, step):
            bad = []

            def one(c):
                if not self.request(base + c, c, {}):
                    bad.append(c)
            ts = [threading.Thread(target=one, args=(c,))
                  for c in range(lo, min(lo + step, self.n_long))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if bad:
                raise RuntimeError(f"warm-up sessions {bad} failed")
        base += self.n_long if self.first else 0
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
