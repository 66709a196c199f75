"""Payload `sessions`: long-lived sessions that grow a turn at a time.
spec: {"kind": "sessions", "sessions": 64, "script_tokens": 16384,
"base_tokens": 8192, "increment_tokens": [256, 512], "shape_seed": n}.

A session is a SCRIPT of script_tokens tokens under the program's byte
tokenizer: BOS + (script_tokens - 1) printable bytes.  Its turn t asks
the script's first ends[t] tokens: the base history plus the first
t + 1 increments — so every turn's prompt is the turn before's prompt
and a few hundred tokens more, as an agent or chat front end resends
its history.  Increment SIZES come from shape_seed (the same multiset
for every seed: the seed must not change the amount of work); which
session gets which row of them, and every script's contents, come from
--seed.  Turns are listed as far as they fit the script.
Returns {"text": [bytes per session], "ids": [int32 token ids per
session, BOS first], "ends": [int array per session: the prompt length
in tokens of each of its turns]}."""
import numpy as np

BOS, BYTE0 = 1, 3          # the byte tokenizer: BOS 1, byte b at 3 + b


def make(spec: dict, seed: int, st, prepared: dict) -> dict:
    rng = np.random.default_rng([int(seed), 5])
    n, total = int(spec["sessions"]), int(spec["script_tokens"])
    base = int(spec["base_tokens"])
    lo, hi = (int(v) for v in spec["increment_tokens"])
    most = (total - base) // lo          # turns, were every one smallest
    sizes = np.random.default_rng([int(spec.get("shape_seed", 0)), 7]) \
        .integers(lo, hi + 1, (n, most))
    ends = base + np.cumsum(sizes[rng.permutation(n)], axis=1)
    text = [rng.integers(0x20, 0x7F, total - 1, dtype=np.uint8).tobytes()
            for _ in range(n)]
    return {
        "text": text,
        "ids": [np.concatenate([[BOS], np.frombuffer(t, np.uint8)
                                .astype(np.int32) + BYTE0])
                .astype(np.int32) for t in text],
        "ends": [e[e <= total] for e in ends]}
