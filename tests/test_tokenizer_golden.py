"""Golden cross-validation of the from-scratch tokenizers against the
HuggingFace `tokenizers` library (an independent Rust implementation of
the same algorithms llama.cpp mirrors).

Real checkpoints are unreachable in this offline image, so realistic
vocabularies are TRAINED here with HF trainers on a fixed corpus, then
both implementations must produce identical token ids on held-out text
(tokenizer parity evidence).  Training is
deterministic for a fixed corpus, so these are stable goldens.
"""
from __future__ import annotations

import json

import pytest

tokenizers = pytest.importorskip("tokenizers")

from tokenizers import (Tokenizer, models, normalizers,  # noqa: E402
                        pre_tokenizers, trainers)

from libsplinter_tpu.models.gguf import (ByteBpeTokenizer,  # noqa: E402
                                         UnigramTokenizer)
from libsplinter_tpu.models.tokenizer import \
    WordPieceTokenizer  # noqa: E402

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "pack my box with five dozen liquor jugs",
    "seqlock arenas stage vectors to TPU HBM lanes",
    "hello world, hello tokenizer cross validation!",
    "writers CAS the epoch odd, publish, then release it even",
    "cosine similarity over a million vectors in pallas",
] * 40

HELD_OUT = [
    "the quick liquor jugs jump!",
    "hello TPU world",
    "a writer publishes vectors",
    "dog-gone lazy, isn't it?",
    "boxy foxes pack jugs",
]


@pytest.fixture(scope="module")
def hf_bpe():
    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tr = trainers.BpeTrainer(
        vocab_size=400, special_tokens=["<|endoftext|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet())
    tok.train_from_iterator(CORPUS, tr)
    return tok


def test_byte_bpe_matches_hf_rust_bpe(hf_bpe):
    state = json.loads(hf_bpe.to_str())
    vocab = state["model"]["vocab"]                 # piece -> id
    tokens = [p for p, _ in sorted(vocab.items(), key=lambda kv: kv[1])]
    merges = [f"{a} {b}" for a, b in state["model"]["merges"]]
    mine = ByteBpeTokenizer(tokens, merges)
    for text in HELD_OUT:
        want = hf_bpe.encode(text, add_special_tokens=False).ids
        got = mine.encode(text, add_bos=False)
        assert got == want, (text, got, want)
        assert mine.decode(got) == text


def test_byte_bpe_decode_inverts_unicode(hf_bpe):
    state = json.loads(hf_bpe.to_str())
    vocab = state["model"]["vocab"]
    tokens = [p for p, _ in sorted(vocab.items(), key=lambda kv: kv[1])]
    merges = [f"{a} {b}" for a, b in state["model"]["merges"]]
    mine = ByteBpeTokenizer(tokens, merges)
    for text in ["héllo wörld", "naïve café", "“smart quotes”"]:
        assert mine.decode(mine.encode(text, add_bos=False)) == text


@pytest.fixture(scope="module")
def hf_unigram():
    tok = Tokenizer(models.Unigram())
    tok.normalizer = normalizers.Sequence([
        normalizers.Replace(" ", "▁"),
        normalizers.Prepend("▁"),
    ])
    tr = trainers.UnigramTrainer(vocab_size=200,
                                 special_tokens=["<unk>"],
                                 unk_token="<unk>")
    tok.train_from_iterator(CORPUS, tr)
    return tok


def test_unigram_viterbi_matches_hf(hf_unigram):
    state = json.loads(hf_unigram.to_str())
    vocab = state["model"]["vocab"]                 # [[piece, score]...]
    tokens = [p for p, _ in vocab]
    scores = [s for _, s in vocab]
    mine = UnigramTokenizer(tokens, scores, bos_token_id=-1,
                            eos_token_id=-1, unknown_token_id=0)
    for text in HELD_OUT:
        want = hf_unigram.encode(text, add_special_tokens=False).ids
        got = mine.encode(text, add_bos=False)
        assert got == want, (
            text,
            [tokens[i] for i in got],
            [tokens[i] for i in want])


def test_wordpiece_matches_hf():
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
             "the", "quick", "brown", "fox", "jump", "##s", "##ed",
             "over", "lazy", "dog", "hello", "world", "##ly", "li",
             "##quo", "##r", ",", "!", "'", "t", "isn", "##n"]
    hf = Tokenizer(models.WordPiece(
        vocab={t: i for i, t in enumerate(vocab)}, unk_token="[UNK]",
        max_input_chars_per_word=100))
    hf.normalizer = normalizers.BertNormalizer(lowercase=True)
    hf.pre_tokenizer = pre_tokenizers.BertPreTokenizer()
    mine = WordPieceTokenizer.from_vocab_list(vocab)
    for text in ["the quick brown fox jumps!", "Hello worldly dog,",
                 "liquor", "unknownword here"]:
        want = hf.encode(text, add_special_tokens=False).ids
        got = mine.encode(text)[1:-1]               # strip [CLS]/[SEP]
        assert got == want, (text, got, want)
