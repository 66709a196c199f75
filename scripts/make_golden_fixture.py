"""Regenerate the pinned end-to-end checkpoint golden fixture.

Produces (committed under tests/fixtures/):
  - golden_encoder.gguf  — tiny nomic-geometry encoder, fixed-seed
    weights, with a REAL trained HF WordPiece vocab embedded as
    tokenizer.ggml metadata (tokenizer.ggml.model="bert");
  - golden_expected.json — for a fixed set of input texts: the exact
    token ids and the exact (out_dim,) embedding vectors the cold
    load→tokenize→encode chain must reproduce.

The e2e test (tests/test_golden_e2e.py) opens the .gguf with NO
side-channel configuration — config, tokenizer, and weights all come
from the file — and must reproduce both ids and vectors exactly
(reference analog: executing a published checkpoint,
splinference.cpp:423-447).

Determinism: the HF `tokenizers` WordPiece trainer is NOT run-to-run
deterministic (hash-order tie-breaking), so the trained vocab is itself
a pinned artifact — tests/fixtures/golden_vocab.txt, trained ONCE by
the HF Rust trainer and committed; this script retrains only if that
file is missing.  With the vocab pinned, regeneration is fully
deterministic (weights from a fixed PRNG seed, float32 on the CPU
backend) and must be a no-op diff unless the model/tokenizer code
changed — in which case the diff IS the signal that the golden must be
re-pinned deliberately.

Usage:  python scripts/make_golden_fixture.py
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from libsplinter_tpu.utils.jaxplatform import force_cpu  # noqa: E402

force_cpu()

import numpy as np  # noqa: E402

# SPTPU_GOLDEN_OUT overrides the output dir (the determinism test
# regenerates into a tempdir and byte-compares)
FIXDIR = os.environ.get("SPTPU_GOLDEN_OUT") or \
    os.path.join(ROOT, "tests", "fixtures")

CORPUS = [
    "the seqlock store commits vectors epoch gated",
    "a signal pulse wakes the embedding daemon",
    "tpu meshes shard the arena row wise over ici",
    "bloom labels route keys to interest groups",
    "the completion daemon streams chunked tokens",
    "matryoshka truncation keeps the leading dimensions",
    "ring attention rotates key value blocks around the pod",
    "pallas kernels fuse similarity and top k",
] * 4

TEXTS = [
    "the daemon commits epoch gated vectors",
    "pallas kernels shard the arena",
    "a wake pulse routes bloom labels",
    "unseen wordforms backoff to subword pieces",
]

VOCAB_SIZE = 384
SEED = 7
OUT_DIM = 32


VOCAB_PIN = os.path.join(ROOT, "tests", "fixtures", "golden_vocab.txt")


def pinned_vocab() -> list[str]:
    """The committed vocab if present; otherwise train and pin it."""
    if os.path.exists(VOCAB_PIN):
        with open(VOCAB_PIN, encoding="utf-8") as f:
            return [ln.rstrip("\n") for ln in f]
    vocab = train_vocab()
    os.makedirs(os.path.dirname(VOCAB_PIN), exist_ok=True)
    with open(VOCAB_PIN, "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")
    print(f"trained and pinned new vocab -> {VOCAB_PIN}")
    return vocab


def train_vocab() -> list[str]:
    from tokenizers import Tokenizer, models, normalizers, pre_tokenizers
    from tokenizers.trainers import WordPieceTrainer

    tok = Tokenizer(models.WordPiece(unk_token="[UNK]"))
    tok.normalizer = normalizers.Sequence(
        [normalizers.NFD(), normalizers.Lowercase(),
         normalizers.StripAccents()])
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    trainer = WordPieceTrainer(
        vocab_size=VOCAB_SIZE, show_progress=False,
        special_tokens=["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"])
    tok.train_from_iterator(CORPUS, trainer)
    vocab = tok.get_vocab()
    return [t for t, _ in sorted(vocab.items(), key=lambda kv: kv[1])]


def main() -> int:
    import jax

    from libsplinter_tpu.models.encoder import (EmbeddingModel,
                                                EncoderConfig)
    from libsplinter_tpu.models.gguf_writer import export_encoder_gguf

    os.makedirs(FIXDIR, exist_ok=True)
    vocab = pinned_vocab()
    print(f"WordPiece vocab: {len(vocab)} tokens")

    cfg = EncoderConfig.tiny(vocab_size=len(vocab), out_dim=OUT_DIM,
                             dtype=jax.numpy.float32)
    model = EmbeddingModel(cfg, seed=SEED, buckets=(32,))
    gguf_path = os.path.join(FIXDIR, "golden_encoder.gguf")
    export_encoder_gguf(model.params, cfg, gguf_path,
                        tokenizer_vocab=vocab)
    print(f"wrote {gguf_path} ({os.path.getsize(gguf_path)} bytes)")

    # -- compute the expected outputs through the COLD-LOAD path ----------
    from libsplinter_tpu.models.gguf import (GgufFile,
                                             encoder_config_from_gguf,
                                             load_tokenizer)

    with GgufFile(gguf_path) as gf:
        cold_cfg = encoder_config_from_gguf(
            gf, out_dim=OUT_DIM, dtype=jax.numpy.float32)
        tok = load_tokenizer(gf)
    cold = EmbeddingModel(cold_cfg, weights=gguf_path, buckets=(32,))

    expected = {"texts": [], "config": {
        "vocab_size": cold_cfg.vocab_size, "hidden": cold_cfg.hidden,
        "layers": cold_cfg.layers, "out_dim": OUT_DIM, "seed": SEED}}
    for text in TEXTS:
        ids = tok.encode(text)
        arr = np.full((1, 32), tok.pad_id, np.int32)
        arr[0, : len(ids)] = ids
        vec = cold.encode_ids(arr, np.array([len(ids)], np.int32))[0]
        expected["texts"].append({
            "text": text,
            "token_ids": [int(i) for i in ids],
            "vector": [float(f"{v:.8e}") for v in np.asarray(vec)],
        })
        print(f"  {text!r}: {len(ids)} ids, |v|="
              f"{np.linalg.norm(vec):.4f}")

    out = os.path.join(FIXDIR, "golden_expected.json")
    with open(out, "w") as f:
        json.dump(expected, f, indent=1)
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
