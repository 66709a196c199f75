"""Causal (decoder-prefill) flash kernel: blockwise attention over the
KV cache with the slot-causal + left-pad-start mask, equal to the
decoder's naive masked softmax."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig
from libsplinter_tpu.ops.flash_attention import (_causal_jnp,
                                                 causal_flash_attention)


def _rand(shape, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1, shape).astype(np.float32)


@pytest.mark.parametrize("S,T,bq,pos,starts,kh,H,D", [
    (32, 64, 16, 0, (0, 5), 2, 4, 8),   # slot 0, left-padded rows
    (24, 64, 16, 8, (0, 0), 2, 4, 8),   # joiner-style offset, padded S
    (16, 32, 16, 16, (4, 12), 2, 4, 8),  # chunk at the window tail
    (32, 64, 16, 0, (0, 3), 1, 4, 8),   # GQA: 4 query heads, 1 kv head
    # serving head width: 8 query heads on 2 kv heads of 64, the tail
    # half of the window, default block
    (64, 128, 256, 64, (0, 7), 2, 8, 64),
])
def test_causal_kernel_matches_naive(S, T, bq, pos, starts, kh, H, D):
    B = 2
    q = jnp.asarray(_rand((B, S, H, D), 1))
    kk = jnp.asarray(_rand((B, T, kh, D), 2))     # UNREPEATED kv heads
    vv = jnp.asarray(_rand((B, T, kh, D), 3))
    start = jnp.asarray(np.asarray(starts, np.int32))
    got = causal_flash_attention(q, kk, vv, jnp.int32(pos), start,
                                 block_q=bq, interpret=True)
    rep = H // kh
    kkr = jnp.repeat(kk, rep, axis=2)
    vvr = jnp.repeat(vv, rep, axis=2)
    want = _causal_jnp(q, kkr, vvr, jnp.int32(pos), start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decoder_flash_prefill_matches_naive(monkeypatch):
    """Same params: generation through the causal kernel prefill
    equals the naive-path generation token for token, serial and
    batched (left-padded starts).  interpret is forced through the
    decoder's own call site so CI exercises the ACTUAL kernel, not
    the CPU jnp fallback."""
    import functools

    import libsplinter_tpu.ops.flash_attention as fa

    monkeypatch.setattr(
        fa, "causal_flash_attention",
        functools.partial(fa.causal_flash_attention, interpret=True))
    base = DecoderConfig.tiny(dtype=jnp.float32)          # naive
    flsh = DecoderConfig.tiny(dtype=jnp.float32, flash_min_seq=16)
    mb = CompletionModel(base, buckets=(16, 32), temp=0.0, seed=3)
    mf = CompletionModel(flsh, buckets=(16, 32), temp=0.0,
                         params=mb.params)
    prompts = [np.arange(1, 20, dtype=np.int32),          # bucket 32
               np.array([5, 4, 3], np.int32)]
    for p in prompts:
        want = [int(x) for x in mb.generate_tokens(p, 10, chunk=4)]
        mb.reset()
        got = [int(x) for x in mf.generate_tokens(p, 10, chunk=4)]
        mf.reset()
        assert got == want, (got, want)
    bwant = [list(map(int, c))
             for c in mb.generate_batch(prompts, 8, chunk=4)]
    mb.reset()
    bgot = [list(map(int, c))
            for c in mf.generate_batch(prompts, 8, chunk=4)]
    mf.reset()
    assert bgot == bwant


def test_causal_kernel_requires_no_grad():
    """Serving-only contract: jax.grad through the kernel path raises
    instead of silently producing wrong gradients."""
    q = jnp.asarray(_rand((1, 16, 2, 8), 1))
    kv = jnp.asarray(_rand((1, 32, 2, 8), 2))

    def loss(q):
        return jnp.sum(causal_flash_attention(
            q, kv, kv, jnp.int32(0), None, block_q=16,
            interpret=True) ** 2)

    # the forward itself must be healthy — otherwise ANY failure would
    # satisfy the raises check below without testing the contract
    assert np.isfinite(float(loss(q)))
    with pytest.raises(Exception):
        jax.grad(loss)(q)
