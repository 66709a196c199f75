"""Call `submit_session_turn`: the program's own completion client
call (libsplinter_tpu.engine.client.submit_completion), as the owner
of a long-lived session makes it — client c owns session c and every
request of its is that session's NEXT turn: the whole history so far
(the turn before's prompt plus the script's next increment) written to
the client's own key, the request raised, READY waited for, the slot
read back.  Mix parameters: clients, timeout_ms, answer_tokens,
warmup.each_session_first.  The payload is payloads/sessions.py's.  A
turn that would run past the script, or leave the answer no room in
it, fails its request; rec["turn"] says which turn a request was."""
import threading
import time

import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


class Call:
    def __init__(self, st, mix: dict, payload):
        from libsplinter_tpu.engine.client import submit_completion
        self.st, self.payload, self.submit = st, payload, submit_completion
        self.timeout_ms = int(mix.get("timeout_ms", 120_000))
        self.n_clients = int(mix.get("clients", mix.get("threads", 1)))
        self.first = int(mix.get("warmup", {}).get(
            "each_session_first", 0))
        self.room = int(mix.get("answer_tokens", 0))
        if self.n_clients > len(payload["text"]):
            raise ValueError("more clients than sessions: a session "
                             "has one owner")
        self.turn = [0] * self.n_clients      # each touched by its owner only

    @staticmethod
    def key(client: int) -> str:
        return f"__cq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def request(self, i: int, client: int, rec: dict) -> bool:
        ends, t = self.payload["ends"][client], self.turn[client]
        rec["turn"] = t
        total = len(self.payload["text"][client]) + 1
        if t >= len(ends) or int(ends[t]) + self.room > total:
            time.sleep(0.05)                  # past the script: a failed
            return False                      # request, not a hot loop
        self.turn[client] = t + 1
        # BOS is a token and no byte: ends[t] tokens are ends[t] - 1 bytes
        prompt = self.payload["text"][client][:int(ends[t]) - 1]
        out = self.submit(self.st, self.key(client), prompt,
                          timeout_ms=self.timeout_ms)
        rec["prompt_tokens"] = int(ends[t])
        rec["out_bytes"] = len(out) if isinstance(out, bytes) else -1
        return isinstance(out, bytes) and out.startswith(prompt)

    def warm_up(self, bursts, base: int) -> int:
        """Every session's turn 0 asked once, `each_session_first` at a
        time (the daemon's prefix cache then holds every base history
        and a snapshot at its end), then the bursts: that many
        concurrent clients, each sending its session's next turn."""
        step = max(self.first, 1)
        for lo in range(0, self.n_clients if self.first else 0, step):
            bad = []

            def one(c):
                if not self.request(base + c, c, {}):
                    bad.append(c)
            ts = [threading.Thread(target=one, args=(c,))
                  for c in range(lo, min(lo + step, self.n_clients))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if bad:
                raise RuntimeError(f"warm-up sessions {bad} failed")
        base += self.n_clients if self.first else 0
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
