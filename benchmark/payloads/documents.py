"""Payload `documents`: a few long shared documents, each asked many
short fresh questions.  spec: {"kind": "documents", "documents": 32,
"document_tokens": 8192, "question_tokens": [16, 64], "questions":
4096, "shape_seed": n}.

A document is BOS + (document_tokens - 1) printable bytes under the
program's byte tokenizer, so `document_tokens` whole tokens of shared
prefix; a question is 16..64 printable bytes.  Question SIZES come
from shape_seed (the same multiset for every seed: the seed must not
change the amount of work); contents, and which document request i
asks, come from --seed.  Returns {"text": [bytes per document],
"docs": [int32 token ids per document, BOS first], "questions":
[bytes], "doc_of": int array over the question pool}."""
import numpy as np

BOS, BYTE0 = 1, 3          # the byte tokenizer: BOS 1, byte b at 3 + b


def printable(rng, n: int) -> bytes:
    return rng.integers(0x20, 0x7F, n, dtype=np.uint8).tobytes()


def make(spec: dict, seed: int, st, prepared: dict) -> dict:
    rng = np.random.default_rng([int(seed), 5])
    n_docs, n_q = int(spec["documents"]), int(spec["questions"])
    text = [printable(rng, int(spec["document_tokens"]) - 1)
            for _ in range(n_docs)]
    lo, hi = spec["question_tokens"]
    sizes = np.random.default_rng([int(spec.get("shape_seed", 0)), 7]) \
        .integers(int(lo), int(hi) + 1, n_q)
    order = rng.permutation(n_q)
    return {
        "text": text,
        "docs": [np.concatenate([[BOS], np.frombuffer(t, np.uint8)
                                 .astype(np.int32) + BYTE0])
                 .astype(np.int32) for t in text],
        "questions": [printable(rng, int(sizes[j])) for j in order],
        "doc_of": rng.integers(0, n_docs, n_q)}
