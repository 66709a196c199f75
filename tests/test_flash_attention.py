"""Blockwise Pallas attention (ops/flash_attention.py): the kernel
(interpret mode on CPU) must match the naive masked-softmax math the
encoder otherwise runs, across shapes, masks, and padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu.ops.flash_attention import (_mha_jnp,
                                                 flash_attention)


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1, shape).astype(np.float32)


@pytest.mark.parametrize("B,S,H,D,bq", [
    (2, 64, 4, 16, 32),      # multi-block
    (1, 128, 2, 8, 128),     # single block
    (3, 48, 1, 32, 32),      # S not a multiple of block_q: padded
    (4, 128, 12, 64, 256),   # the encoder's head geometry, default block
])
def test_kernel_matches_naive(B, S, H, D, bq):
    q, k, v = (_rand((B, S, H, D), s) for s in (1, 2, 3))
    lens = np.random.default_rng(4).integers(1, S + 1, B)
    mask = np.arange(S)[None, :] < lens[:, None]
    got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          jnp.asarray(mask), block_q=bq, interpret=True)
    want = _mha_jnp(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                    jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_row_is_finite():
    """A fully padded batch row (mask all False) must produce finite
    output (uniform softmax), matching the naive path's -1e9 bias
    behavior — pooling excludes the row anyway."""
    B, S, H, D = 2, 32, 2, 8
    q, k, v = (jnp.asarray(_rand((B, S, H, D), s)) for s in (1, 2, 3))
    mask = jnp.asarray(np.array([[True] * S, [False] * S]))
    out = flash_attention(q, k, v, mask, block_q=16, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


def test_padded_keys_do_not_leak():
    """Scores behind the mask must not influence output: growing the
    padded tail with garbage leaves valid rows unchanged."""
    B, S, H, D = 1, 32, 2, 8
    q, k, v = (_rand((B, S, H, D), s) for s in (1, 2, 3))
    valid = 20
    mask = np.arange(S)[None, :] < valid
    a = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        jnp.asarray(mask), block_q=16, interpret=True)
    k2, v2 = k.copy(), v.copy()
    k2[:, valid:] = 999.0
    v2[:, valid:] = -999.0
    b = flash_attention(jnp.asarray(q), jnp.asarray(k2),
                        jnp.asarray(v2), jnp.asarray(mask),
                        block_q=16, interpret=True)
    np.testing.assert_allclose(np.asarray(a)[:, :valid],
                               np.asarray(b)[:, :valid],
                               rtol=1e-6, atol=1e-6)


def test_flash_gradients_match_naive():
    """Training through the kernel: jax.grad over the Pallas forward
    (custom VJP recomputes the backward via the jnp reference) equals
    jax.grad through the naive math."""
    B, S, H, D = 2, 32, 2, 8
    q, k, v = (jnp.asarray(_rand((B, S, H, D), s)) for s in (1, 2, 3))
    mask = jnp.asarray(np.arange(S)[None, :] < np.array([[S], [20]])
                       .reshape(2, 1))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask, block_q=16,
                                       interpret=True) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_mha_jnp(q, k, v, mask) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("S,bq,lens,hi", [
    (48, 32, (48, 20, 1), False),  # S not a block multiple: padded bwd
    (32, 16, (32, 0, 7), False),   # one fully-masked row in the batch
    (48, 32, (48, 20, 0), False),  # BOTH: padding + a fully-masked row
    (48, 32, (48, 20, 1), True),   # every MXU dot at Precision.HIGHEST
])
def test_flash_gradients_padded_and_masked(S, bq, lens, hi):
    """Gradient parity under the module's contract: fully-masked rows
    are pooling-excluded don't-cares, so the loss (like the encoder's
    pool_normalize) multiplies outputs by row validity — their
    cotangents are zero and the padded-uniform fallback can't leak."""
    B, H, D = 3, 2, 8
    q, k, v = (jnp.asarray(_rand((B, S, H, D), s)) for s in (4, 5, 6))
    mask = jnp.asarray(np.arange(S)[None, :] <
                       np.asarray(lens).reshape(B, 1))
    roww = mask.any(axis=1).astype(jnp.float32)[:, None, None, None]

    def lf(q, k, v):
        return jnp.sum((flash_attention(q, k, v, mask, block_q=bq,
                                        interpret=True, hi_prec=hi)
                        * roww) ** 2)

    def ln(q, k, v):
        return jnp.sum((_mha_jnp(q, k, v, mask) * roww) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(ln, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_encoder_flash_path_matches_naive(monkeypatch):
    """Encoder-level: the same params produce (near-)identical pooled
    embeddings whether attention runs naive or through the ACTUAL
    Pallas kernel — on CPU flash_attention would silently fall back to
    jnp, so the test forces interpret mode through the encoder's own
    call site (covering the transpose/mask/padding plumbing)."""
    import functools

    import libsplinter_tpu.ops.flash_attention as fa
    from libsplinter_tpu.models import EmbeddingModel, EncoderConfig

    monkeypatch.setattr(
        fa, "flash_attention",
        functools.partial(fa.flash_attention, interpret=True))

    base = EncoderConfig.tiny(dtype=jnp.float32)          # naive (S<512)
    flash = EncoderConfig.tiny(dtype=jnp.float32, flash_min_seq=16)
    m_base = EmbeddingModel(base, buckets=(32,), seed=11)
    m_flash = EmbeddingModel(flash, buckets=(32,), seed=11,
                             params=m_base.params)
    ids = np.random.default_rng(5).integers(
        0, base.vocab_size, (4, 32)).astype(np.int32)
    lens = np.array([32, 7, 19, 1], np.int32)
    a = m_base.encode_ids(ids, lens)
    b = m_flash.encode_ids(ids, lens)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
