"""Pinned end-to-end checkpoint golden.

tests/fixtures/golden_encoder.gguf is a committed checkpoint: tiny
nomic-geometry encoder weights + a REAL trained HF WordPiece vocab, all
embedded in one self-describing GGUF.  These tests open it COLD — the
config, tokenizer, and weights all come from the file, no side-channel
setup — and must reproduce the committed token ids and embedding
vectors exactly.  Any regression anywhere in the
load→tokenize→encode chain (container parse, vocab handling, config
derivation, param mapping, encoder forward, matryoshka truncation)
breaks this as one artifact.

Regenerate deliberately with scripts/make_golden_fixture.py (a diff in
the fixture is the signal that the pinned behavior changed).

Reference analog: executing a published GGUF checkpoint end to end
(splinference.cpp:423-447).
"""
from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

FIXDIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures")
GGUF = os.path.join(FIXDIR, "golden_encoder.gguf")
EXPECTED = os.path.join(FIXDIR, "golden_expected.json")


@pytest.fixture(scope="module")
def golden():
    with open(EXPECTED) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cold_model():
    """The entire chain bootstrapped from the .gguf alone."""
    from libsplinter_tpu.models.encoder import EmbeddingModel
    from libsplinter_tpu.models.gguf import (GgufFile,
                                             encoder_config_from_gguf,
                                             load_tokenizer)
    with GgufFile(GGUF) as gf:
        cfg = encoder_config_from_gguf(gf, out_dim=32, dtype=jnp.float32)
        tok = load_tokenizer(gf)
    model = EmbeddingModel(cfg, weights=GGUF, buckets=(32,))
    return cfg, tok, model


def test_config_derived_from_container(cold_model, golden):
    cfg, _, _ = cold_model
    assert cfg.vocab_size == golden["config"]["vocab_size"]
    assert cfg.hidden == golden["config"]["hidden"]
    assert cfg.layers == golden["config"]["layers"]
    assert cfg.variant == "nomic"


def test_token_ids_pinned(cold_model, golden):
    _, tok, _ = cold_model
    for case in golden["texts"]:
        assert tok.encode(case["text"]) == case["token_ids"], case["text"]


def test_vectors_pinned(cold_model, golden):
    _, tok, model = cold_model
    for case in golden["texts"]:
        ids = case["token_ids"]
        arr = np.full((1, 32), tok.pad_id, np.int32)
        arr[0, : len(ids)] = ids
        vec = model.encode_ids(arr, np.array([len(ids)], np.int32))[0]
        np.testing.assert_allclose(
            np.asarray(vec), np.asarray(case["vector"], np.float32),
            rtol=0, atol=2e-6, err_msg=case["text"])


def test_vectors_unit_norm(cold_model, golden):
    """The encoder L2-normalizes (matryoshka-truncated) outputs."""
    for case in golden["texts"]:
        assert np.linalg.norm(case["vector"]) == pytest.approx(1.0,
                                                               abs=1e-5)


def test_unseen_text_uses_subword_backoff(cold_model):
    """A word absent from the trained vocab must decompose into ##pieces
    (or [UNK]), not crash — the WordPiece contract on real vocabs."""
    _, tok, model = cold_model
    ids = tok.encode("quixotic zephyrs")
    assert len(ids) >= 2
    arr = np.full((1, 32), tok.pad_id, np.int32)
    arr[0, : len(ids)] = ids[:32]
    vec = model.encode_ids(arr, np.array([min(len(ids), 32)], np.int32))[0]
    assert np.isfinite(np.asarray(vec)).all()


@pytest.mark.slow
def test_fixture_regeneration_is_deterministic():
    """make_golden_fixture.py must reproduce the committed gguf byte for
    byte (same trained vocab, same seeded weights, same layout) — proof
    the fixture is regenerable, not a snowflake binary."""
    import subprocess
    import sys
    import tempfile

    root = os.path.dirname(FIXDIR.rstrip(os.sep))
    root = os.path.dirname(root)
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, SPTPU_GOLDEN_OUT=td)
        proc = subprocess.run(
            [sys.executable,
             os.path.join(root, "scripts", "make_golden_fixture.py")],
            env=env, capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(os.path.join(td, "golden_encoder.gguf"), "rb") as f:
            fresh = f.read()
        with open(GGUF, "rb") as f:
            committed = f.read()
        assert fresh == committed, (
            "regenerated fixture differs from the committed one — the "
            "load/tokenize/encode chain changed; re-pin deliberately "
            "with scripts/make_golden_fixture.py")
