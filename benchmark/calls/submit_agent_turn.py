"""Call `submit_agent_turn`: the program's own completion client call
(libsplinter_tpu.engine.client.submit_completion), as the owner of an
agent session makes it — client c owns session c and every request of
its is that session's NEXT turn: its tenant's system prompt and the
session's script so far (the turn before's prompt plus the next tool
result) written to the client's own key, the request raised, READY
waited for, the slot read back.  Mix parameters: clients, timeout_ms,
answer_tokens, warmup.each_system_first, warmup.each_session_first.
The payload is payloads/agent_sessions.py's.  A turn past the script's
listed turns fails its request; rec["turn"] says which turn a request
was, rec["short"] whether it was a short tool result."""
import threading
import time

import traffic          # benchmark/traffic.py: run.py puts benchmark/ on sys.path


def together(fn, items, what: str) -> None:
    """fn(item) -> bool for every item at once; raises on a failure."""
    bad = []

    def one(it):
        if not fn(it):
            bad.append(it)
    ts = [threading.Thread(target=one, args=(it,)) for it in items]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if bad:
        raise RuntimeError(f"warm-up {what} {bad} failed")


class Call:
    def __init__(self, st, mix: dict, payload):
        from libsplinter_tpu.engine.client import submit_completion
        self.st, self.payload, self.submit = st, payload, submit_completion
        self.timeout_ms = int(mix.get("timeout_ms", 120_000))
        self.n_clients = int(mix.get("clients", mix.get("threads", 1)))
        warm = mix.get("warmup", {})
        self.systems_first = int(warm.get("each_system_first", 0))
        self.sessions_first = int(warm.get("each_session_first", 0))
        if self.n_clients > len(payload["script"]):
            raise ValueError("more clients than sessions: a session "
                             "has one owner")
        self.turn = [0] * self.n_clients      # each touched by its owner only

    @staticmethod
    def key(client: int) -> str:
        return f"__cq_bench_{client}"

    def prepare(self) -> None:
        for c in range(self.n_clients):
            self.st.set(self.key(c), "placeholder")

    def ask(self, client: int, prompt: bytes, rec: dict) -> bool:
        out = self.submit(self.st, self.key(client), prompt,
                          timeout_ms=self.timeout_ms)
        rec["prompt_tokens"] = len(prompt) + 1       # BOS is no byte
        rec["out_bytes"] = len(out) if isinstance(out, bytes) else -1
        return isinstance(out, bytes) and out.startswith(prompt)

    def request(self, i: int, client: int, rec: dict) -> bool:
        pay, t = self.payload, self.turn[client]
        ends = pay["ends"][client]
        rec["turn"] = t
        if t >= len(ends):
            time.sleep(0.05)                  # past the script: a failed
            return False                      # request, not a hot loop
        self.turn[client] = t + 1
        rec["short"] = bool(pay["short"][client][t])
        return self.ask(client, pay["system"][int(pay["tenant_of"][client])]
                        + pay["script"][client][:int(ends[t])], rec)

    def warm_up(self, bursts, base: int) -> int:
        """Every system prompt asked once ALONE, `each_system_first`
        at a time (the daemon's prefix cache then holds its pages and
        the snapshot at its end, which every session of the tenant
        restores at its first join); then every session's turn 0,
        `each_session_first` at a time; then the bursts: that many
        concurrent clients, each sending its session's next turn."""
        systems = self.payload["system"]
        step = max(self.systems_first, 1)
        for lo in range(0, len(systems) if self.systems_first else 0,
                        step):
            together(lambda d: self.ask(d - lo, systems[d], {}),
                     range(lo, min(lo + step, len(systems))),
                     "system prompts")
        step = max(self.sessions_first, 1)
        for lo in range(0, self.n_clients if self.sessions_first else 0,
                        step):
            together(lambda c: self.request(base + c, c, {}),
                     range(lo, min(lo + step, self.n_clients)),
                     "sessions")
        base += self.n_clients if self.sessions_first else 0
        for n in bursts:
            bad = traffic.burst(self, int(n), base)
            if bad:
                raise RuntimeError(f"{bad} warm-up requests failed")
            base += int(n)
        return base
