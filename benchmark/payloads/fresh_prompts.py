"""Payload `fresh_prompts`: every request a FRESH prompt that shares
nothing with any other, and an answer budget of its own.  spec:
{"kind": "fresh_prompts", "prompt_tokens": [128, 1024],
"answer_tokens": [64, 512], "requests": 4096, "shape_seed": n}.

A prompt is BOS + (n - 1) printable bytes under the program's byte
tokenizer, so n whole tokens; n is LOG-UNIFORM on prompt_tokens and the
budget log-uniform on answer_tokens (exp of a uniform draw between the
logs of the ends, rounded).  Both size multisets come from shape_seed
(the same for every seed: the seed must not change the amount of
work); contents, and the order in which the pool's (prompt size,
budget) pairs are asked, come from --seed.  The first byte after BOS is
drawn like the rest: two prompts share a first page only by chance
(95^-127).  Returns {"prompts": [bytes], "prompt_ids": [int32 token
ids, BOS first], "budgets": int array}."""
import numpy as np

BOS, BYTE0 = 1, 3          # the byte tokenizer: BOS 1, byte b at 3 + b


def printable(rng, n: int) -> bytes:
    return rng.integers(0x20, 0x7F, n, dtype=np.uint8).tobytes()


def log_uniform(rng, lo: int, hi: int, n: int) -> np.ndarray:
    return np.clip(np.rint(np.exp(rng.uniform(
        np.log(lo), np.log(hi), n))), lo, hi).astype(np.int64)


def make(spec: dict, seed: int, st, prepared: dict) -> dict:
    n = int(spec["requests"])
    shape = np.random.default_rng([int(spec.get("shape_seed", 0)), 7])
    sizes = log_uniform(shape, *map(int, spec["prompt_tokens"]), n)
    budgets = log_uniform(shape, *map(int, spec["answer_tokens"]), n)
    rng = np.random.default_rng([int(seed), 5])
    order = rng.permutation(n)
    prompts = [printable(rng, int(sizes[j]) - 1) for j in order]
    return {
        "prompts": prompts,
        "prompt_ids": [np.concatenate(
            [[BOS], np.frombuffer(t, np.uint8).astype(np.int32) + BYTE0])
            .astype(np.int32) for t in prompts],
        "budgets": budgets[order]}
