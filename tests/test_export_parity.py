"""Real-export parity pack.

The model path had only ever loaded GGUF files produced by this repo's
own writer — a mirrored misunderstanding of the format or of llama.cpp's
tensor-name conventions would pass every test.  This suite closes that
hole offline (the image has no network and no real checkpoint):

  - tests/fixtures/llamacpp_export_manifest.json FREEZES the metadata
    keys + tensor names/shapes the public llama.cpp converters emit for
    the llama / bert / nomic-bert families (sha256-pinned below so it
    can't drift silently);
  - a minimal GGUF v3 writer implemented HERE, straight from the GGUF
    spec (magic/version/kv types/ggml-reversed dims/32-byte alignment)
    and deliberately NOT importing models/gguf_writer.py, materialises
    the manifest with seeded random weights;
  - models/gguf.py must then derive the right config from the metadata,
    consume EVERY non-derived tensor (a converter-emitted tensor the
    loader silently ignores is a parity bug), produce correctly-shaped
    trees, run a forward pass, and build working tokenizers from the
    tokenizer.ggml.* metadata alone.

Reference behavior being mirrored: the reference loads real Nomic GGUF
and chat-model files end to end (splinference.cpp:423-447,
splainference.cpp:414-448).
"""
from __future__ import annotations

import hashlib
import json
import os
import struct

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "tests", "fixtures",
                        "llamacpp_export_manifest.json")

# sha256 of the frozen manifest — update ONLY when deliberately
# extending the parity surface, never to make a loader change pass
MANIFEST_SHA256 = \
    "863cb6749640832739077de647733e93f33c390e7f575df1b6c38623f5e3460c"


# --------------------------------------------------------------------------
# independent GGUF v3 writer (from the spec; no repo writer imported)
# --------------------------------------------------------------------------

_GGUF_MAGIC = b"GGUF"
_GGUF_VERSION = 3
_ALIGN = 32
# value types per the spec
_T_U32, _T_F32, _T_STR, _T_ARR, _T_U64, _T_F64 = 4, 6, 8, 9, 10, 12
_T_I32 = 5


def _s(b: bytes) -> bytes:
    return struct.pack("<Q", len(b)) + b


def _kv(key: str, vtype: int, payload: bytes) -> bytes:
    return _s(key.encode()) + struct.pack("<I", vtype) + payload


def _kv_auto(key: str, val) -> bytes:
    if isinstance(val, bool):
        raise TypeError("bool kv not needed here")
    if isinstance(val, int):
        return _kv(key, _T_U32, struct.pack("<I", val))
    if isinstance(val, float):
        return _kv(key, _T_F32, struct.pack("<f", val))
    if isinstance(val, str):
        return _kv(key, _T_STR, _s(val.encode()))
    if isinstance(val, list) and val and isinstance(val[0], str):
        body = b"".join(_s(x.encode()) for x in val)
        return _kv(key, _T_ARR,
                   struct.pack("<IQ", _T_STR, len(val)) + body)
    if isinstance(val, list) and val and isinstance(val[0], float):
        return _kv(key, _T_ARR,
                   struct.pack("<IQ", _T_F32, len(val)) +
                   struct.pack(f"<{len(val)}f", *val))
    if isinstance(val, list):
        return _kv(key, _T_ARR,
                   struct.pack("<IQ", _T_I32, len(val)) +
                   struct.pack(f"<{len(val)}i", *val))
    raise TypeError(f"unsupported kv {key}={val!r}")


def write_spec_gguf(path: str, metadata: dict, tensors: dict) -> None:
    """tensors: name -> np.float32 array (numpy-order shape).  Dims are
    written REVERSED (ggml ne order: ne[0] = fastest-varying), F32,
    offsets aligned to 32 inside the tensor-data region."""
    infos = []
    blobs = []
    off = 0
    for name, arr in tensors.items():
        arr = np.ascontiguousarray(arr, dtype=np.float32)
        dims = arr.shape[::-1]
        info = (_s(name.encode()) +
                struct.pack("<I", len(dims)) +
                struct.pack(f"<{len(dims)}Q", *dims) +
                struct.pack("<I", 0) +             # GGML_TYPE_F32
                struct.pack("<Q", off))
        infos.append(info)
        raw = arr.tobytes()
        pad = (-len(raw)) % _ALIGN
        blobs.append(raw + b"\0" * pad)
        off += len(raw) + pad
    kvs = [_kv_auto(k, v) for k, v in metadata.items()]
    head = (_GGUF_MAGIC + struct.pack("<I", _GGUF_VERSION) +
            struct.pack("<Q", len(tensors)) +
            struct.pack("<Q", len(kvs)))
    body = head + b"".join(kvs) + b"".join(infos)
    pad = (-len(body)) % _ALIGN
    with open(path, "wb") as f:
        f.write(body + b"\0" * pad + b"".join(blobs))


# --------------------------------------------------------------------------
# fixtures
# --------------------------------------------------------------------------

def _manifest() -> dict:
    with open(MANIFEST) as f:
        return json.load(f)


def _seeded_tensors(spec: dict) -> dict:
    rng = np.random.default_rng(7)
    return {name: rng.standard_normal(shape).astype(np.float32) * 0.05
            for name, shape in spec["tensors"].items()}


def _materialise(tmp_path, model_key: str) -> tuple[str, dict, dict]:
    spec = _manifest()["models"][model_key]
    md = dict(spec["metadata"])
    if "spm_tokens" in spec:
        md["tokenizer.ggml.tokens"] = spec["spm_tokens"]
        md["tokenizer.ggml.scores"] = [
            0.0 if i < 3 else -float(i) for i in
            range(len(spec["spm_tokens"]))]
        md["tokenizer.ggml.token_type"] = spec["spm_token_types"]
    if "wordpiece_tokens" in spec:
        md["tokenizer.ggml.tokens"] = spec["wordpiece_tokens"]
    tensors = _seeded_tensors(spec)
    path = str(tmp_path / f"{model_key}.gguf")
    write_spec_gguf(path, md, tensors)
    return path, spec, tensors


class _Recorder:
    """Wrap GgufFile.tensor to record which names a loader consumes."""

    def __init__(self, monkeypatch):
        from libsplinter_tpu.models.gguf import GgufFile
        self.read: set[str] = set()
        orig = GgufFile.tensor

        def spy(gf, name):
            self.read.add(name)
            return orig(gf, name)

        monkeypatch.setattr(GgufFile, "tensor", spy)


# --------------------------------------------------------------------------
# the manifest itself
# --------------------------------------------------------------------------

def test_manifest_is_frozen():
    with open(MANIFEST, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    assert digest == MANIFEST_SHA256, (
        f"llamacpp_export_manifest.json changed (sha256 {digest}); if "
        f"the parity surface was deliberately extended, update the pin")


# --------------------------------------------------------------------------
# llama decoder family
# --------------------------------------------------------------------------

def test_llama_decoder_config_and_full_consumption(tmp_path, monkeypatch):
    from libsplinter_tpu.models.gguf import (
        decoder_config_from_gguf, load_decoder_params,
    )
    path, spec, tensors = _materialise(tmp_path, "llama_decoder")
    cfg = decoder_config_from_gguf(path)
    assert cfg.hidden == 64 and cfg.layers == 2
    assert cfg.heads == 4 and cfg.kv_heads == 2
    assert cfg.mlp_dim == 128 and cfg.max_len == 128
    assert cfg.vocab_size == len(spec["spm_tokens"])
    assert cfg.rope_base == 10000.0
    assert abs(cfg.rms_eps - 1e-5) < 1e-12

    rec = _Recorder(monkeypatch)
    params = load_decoder_params(path, cfg)
    unread = (set(spec["tensors"]) - rec.read
              - set(spec["derived_tensors"]))
    assert not unread, (
        f"converter-emitted tensors the loader never consumed: "
        f"{sorted(unread)}")
    # spot-check mapping + transposition (ggml numpy view is (out, in);
    # flax kernels are (in, out))
    p = params["params"]
    np.testing.assert_allclose(
        np.asarray(p["layer_0"]["attn"]["q"]["kernel"]),
        tensors["blk.0.attn_q.weight"].T, rtol=1e-5)
    assert p["layer_1"]["down"]["kernel"].shape == (128, 64)
    assert p["lm_head"]["kernel"].shape == (64, 32)


def test_llama_decoder_forward_runs(tmp_path):
    import jax.numpy as jnp

    from libsplinter_tpu.models.decoder import Decoder, init_cache
    from libsplinter_tpu.models.gguf import (
        decoder_config_from_gguf, load_decoder_params,
    )
    path, _, _ = _materialise(tmp_path, "llama_decoder")
    cfg = decoder_config_from_gguf(path)
    params = load_decoder_params(path, cfg)
    model = Decoder(cfg)
    cache = init_cache(cfg, 1)
    ids = np.array([[1, 4, 5, 8]], np.int32)
    logits, _ = model.apply(params, jnp.asarray(ids), cache,
                            jnp.int32(0))
    assert logits.shape[0] == 1 and logits.shape[-1] == cfg.vocab_size
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_spm_tokenizer_from_metadata(tmp_path):
    from libsplinter_tpu.models.gguf import load_tokenizer
    path, spec, _ = _materialise(tmp_path, "llama_decoder")
    tok = load_tokenizer(path)
    toks = spec["spm_tokens"]
    ids = tok.encode("the quick fox")
    assert ids, "empty encoding"
    assert ids[0] == 1, "llama.cpp semantics: BOS (<s>) leads"
    text = "".join(toks[i] for i in ids[1:] if i < len(toks))
    assert text.replace("▁", " ").strip() == "the quick fox"
    # control tokens parse atomically (llama.cpp parse_special):
    # id 1 appears TWICE — the leading BOS plus the literal "<s>"
    # (character-piece tokenization of "<s>" would leave count at 1)
    ids2 = list(tok.encode("<s>the"))
    assert ids2.count(1) == 2, ids2


# --------------------------------------------------------------------------
# bert / nomic-bert encoder families
# --------------------------------------------------------------------------

@pytest.mark.parametrize("key,variant", [
    ("bert_encoder", "bert"),
    ("nomic_bert_encoder", "nomic"),
])
def test_encoder_config_and_full_consumption(tmp_path, monkeypatch,
                                             key, variant):
    from libsplinter_tpu.models.gguf import (
        encoder_config_from_gguf, load_encoder_params,
    )
    path, spec, tensors = _materialise(tmp_path, key)
    cfg = encoder_config_from_gguf(path)
    assert cfg.variant == variant
    assert cfg.hidden == 32 and cfg.layers == 1 and cfg.heads == 2
    assert cfg.mlp_dim == 64
    assert cfg.vocab_size == len(spec["wordpiece_tokens"])
    assert abs(cfg.layer_norm_eps - 1e-12) < 1e-20

    rec = _Recorder(monkeypatch)
    params = load_encoder_params(path, cfg)
    unread = (set(spec["tensors"]) - rec.read
              - set(spec["derived_tensors"]))
    assert not unread, (
        f"converter-emitted tensors the loader never consumed: "
        f"{sorted(unread)}")
    # token_types row 0 must be folded into the embedding table
    folded = (tensors["token_embd.weight"]
              + tensors["token_types.weight"][0][None, :])
    np.testing.assert_allclose(
        np.asarray(params["params"]["tok_emb"]["embedding"]), folded,
        rtol=1e-5)


@pytest.mark.parametrize("key", ["bert_encoder", "nomic_bert_encoder"])
def test_encoder_forward_runs(tmp_path, key):
    from libsplinter_tpu.models.encoder import Encoder
    from libsplinter_tpu.models.gguf import (
        encoder_config_from_gguf, load_encoder_params,
    )
    path, _, _ = _materialise(tmp_path, key)
    cfg = encoder_config_from_gguf(path)
    params = load_encoder_params(path, cfg)
    model = Encoder(cfg)
    ids = np.array([[2, 5, 14, 3]], np.int32)   # [CLS] store ##s [SEP]
    mask = np.ones_like(ids)
    out = np.asarray(model.apply(params, ids, mask))
    assert out.shape[0] == 1 and out.shape[-1] == cfg.hidden
    assert np.isfinite(out).all()
    # pooled embeddings come back L2-normalised (reference forces mean
    # pooling + normalise, splinference.cpp:435)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1), 1.0,
                               rtol=1e-4)


def test_bert_wordpiece_tokenizer_from_metadata(tmp_path):
    from libsplinter_tpu.models.gguf import load_tokenizer
    path, spec, _ = _materialise(tmp_path, "bert_encoder")
    tok = load_tokenizer(path)
    toks = spec["wordpiece_tokens"]
    # greedy longest-match + ## continuation, ids ARE vocab positions
    ids = tok.encode("stores the")
    want = [toks.index("[CLS]"), toks.index("store"), toks.index("##s"),
            toks.index("the"), toks.index("[SEP]")]
    assert list(ids) == want, (ids, want)
    # unknown word falls back to [UNK]
    ids2 = tok.encode("zzz")
    assert toks.index("[UNK]") in list(ids2)
