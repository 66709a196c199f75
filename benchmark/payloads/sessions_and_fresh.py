"""Payload `sessions_and_fresh`: two classes of request for ONE queue —
long-lived sessions that grow a turn at a time, and fresh prompts that
share nothing.
spec: {"kind": "sessions_and_fresh", "sessions": 8, "script_tokens":
24576, "base_tokens": 16384, "increment_tokens": [256, 512],
"fresh_prompts": 768, "fresh_tokens": [256, 1024], "shape_seed": n}.

A session is payloads/sessions.py's, by the same rule: a SCRIPT of
script_tokens tokens under the program's byte tokenizer (BOS +
script_tokens - 1 printable bytes); its turn t asks the script's first
ends[t] tokens, the base history plus the first t + 1 increments.  A
fresh prompt is BOS + printable bytes of its own: fresh_prompts of
them, of fresh_tokens[0]..[1] tokens.  Increment and fresh-prompt
SIZES come from shape_seed (the same multisets for every seed: the
seed must not change the amount of work); which session gets which
row of increments, the order of the fresh sizes, and every content
come from --seed.
Returns {"text", "ids", "ends"} for the sessions as sessions.py does,
and {"fresh_text": [bytes], "fresh_ids": [int32 ids, BOS first]}."""
import numpy as np

BOS, BYTE0 = 1, 3          # the byte tokenizer: BOS 1, byte b at 3 + b


def _ids(text: bytes) -> np.ndarray:
    return np.concatenate([[BOS], np.frombuffer(text, np.uint8)
                           .astype(np.int32) + BYTE0]).astype(np.int32)


def make(spec: dict, seed: int, st, prepared: dict) -> dict:
    rng = np.random.default_rng([int(seed), 5])
    shape = np.random.default_rng([int(spec.get("shape_seed", 0)), 7])
    n, total = int(spec["sessions"]), int(spec["script_tokens"])
    base = int(spec["base_tokens"])
    lo, hi = (int(v) for v in spec["increment_tokens"])
    most = (total - base) // lo          # turns, were every one smallest
    sizes = shape.integers(lo, hi + 1, (n, most))
    ends = base + np.cumsum(sizes[rng.permutation(n)], axis=1)
    f_lo, f_hi = (int(v) for v in spec["fresh_tokens"])
    f_sizes = rng.permutation(shape.integers(
        f_lo, f_hi + 1, int(spec["fresh_prompts"])))
    text = [rng.integers(0x20, 0x7F, total - 1, dtype=np.uint8).tobytes()
            for _ in range(n)]
    fresh = [rng.integers(0x20, 0x7F, int(s) - 1, dtype=np.uint8).tobytes()
             for s in f_sizes]
    return {"text": text, "ids": [_ids(t) for t in text],
            "ends": [e[e <= total] for e in ends],
            "fresh_text": fresh, "fresh_ids": [_ids(t) for t in fresh]}
