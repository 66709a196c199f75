"""Ring attention — sequence/context parallelism over an ICI ring.

The reference *rejects* long inputs outright (splinference.cpp:226-233
marks anything >= 0.9*n_ctx CONTEXT_EXCEEDED) or pre-chunks documents at
ingest time (splinter_cli_cmd_ingest.c:8-33).  The TPU build makes long
context a first-class capability instead: the sequence axis is sharded
over the mesh's `sp` axis and attention runs blockwise with an online
(flash-style) softmax while K/V shards rotate around the ring via
`lax.ppermute` — each device only ever holds O(S/n) keys, and the
rotation rides ICI neighbor links (no all-gather, no O(S) memory).

Design notes (TPU/XLA):
  - the per-step block matmuls are (S/n x D) x (D x S/n) einsums — large,
    static-shaped, bfloat16-friendly MXU work;
  - the step loop is a Python loop over the *static* axis size, so XLA
    sees a fixed unrolled schedule and can overlap the ppermute of step
    i+1 with the matmul of step i;
  - softmax statistics are carried in float32 regardless of input dtype;
  - reverse-mode autodiff works through ppermute (its transpose is the
    inverse rotation), so the same primitive serves training; each block
    step is wrapped in jax.checkpoint to keep backward memory at
    O(S/n) per device.

Must be called inside shard_map (or an equivalent axis context) where
`axis_name` is bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


NEG_INF = -1e9          # masked-score bias (finite: keeps softmax NaN-free)
ACC_MIN = -1e30         # initial running max


def _block_scores(q, k, scale):
    # q: (B, Sq, H, D)  k: (B, Sk, H, D)  ->  (B, H, Sq, Sk) in f32
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _online_update(carry, q, k, v, bias):
    """One flash-attention accumulation step.

    carry = (o, m, l): o (B,Sq,H,D) f32 accumulator, m (B,H,Sq) running
    max, l (B,H,Sq) running denominator.  bias (B,H,Sq,Sk) additive.
    """
    o, m, l = carry
    s = _block_scores(q, k, 1.0) + bias          # scale folded into bias path
    m_blk = s.max(axis=-1)
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)                   # rescale old accumulator
    p = jnp.exp(s - m_new[..., None])            # (B,H,Sq,Sk)
    l = l * alpha + p.sum(axis=-1)
    o_blk = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    o = o * alpha.transpose(0, 2, 1)[..., None] + o_blk
    return o, m_new, l


def ring_attention(q, k, v, kv_mask, *, axis_name: str,
                   causal: bool = False, scale: float | None = None,
                   axis_size: int | None = None):
    """Blockwise ring attention over sequence shards.

    q, k, v:  (B, S_local, H, D) — this device's sequence chunk.
    kv_mask:  (B, S_local) bool — key/value validity (padding) for the
              LOCAL chunk; it rotates around the ring with k/v.
    causal:   apply a causal mask using global positions (chunk i holds
              positions [i*S_local, (i+1)*S_local)).
    Returns   (B, S_local, H, D) in q.dtype.
    """
    if axis_size is not None:
        n = axis_size
    else:
        n = jax.lax.axis_size(axis_name)
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    qf = q.astype(jnp.float32) * scale
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o = jnp.zeros((B, S, H, D), jnp.float32)
    m = jnp.full((B, H, S), ACC_MIN, jnp.float32)
    den = jnp.zeros((B, H, S), jnp.float32)

    step_fn = jax.checkpoint(_online_update)

    kr, vr, maskr = k, v, kv_mask
    for step in range(n):
        src = (my - step) % n                    # chunk index now held
        bias = jnp.where(maskr[:, None, None, :], 0.0, NEG_INF)
        if causal:
            q_pos = my * S + jnp.arange(S)
            kv_pos = src * S + jnp.arange(S)
            cmask = q_pos[:, None] >= kv_pos[None, :]
            bias = bias + jnp.where(cmask[None, None], 0.0, NEG_INF)
        o, m, den = step_fn((o, m, den), qf, kr, vr, bias)
        if step != n - 1:
            kr = lax.ppermute(kr, axis_name, perm)
            vr = lax.ppermute(vr, axis_name, perm)
            maskr = lax.ppermute(maskr, axis_name, perm)

    out = o / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def dense_reference(q, k, v, kv_mask, *, causal: bool = False,
                    scale: float | None = None):
    """Single-device dense attention with identical masking semantics —
    the correctness oracle for ring_attention tests."""
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    s = _block_scores(q, k, scale)
    bias = jnp.where(kv_mask[:, None, None, :], 0.0, NEG_INF)
    if causal:
        pos = jnp.arange(S)
        bias = bias + jnp.where(pos[:, None] >= pos[None, :],
                                0.0, NEG_INF)[None, None]
    p = jax.nn.softmax(s + bias, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def ring_attention_sharded(mesh, q, k, v, kv_mask, *, axis: str = "sp",
                           causal: bool = False):
    """Convenience wrapper: shard q/k/v on the sequence axis over `axis`
    and run ring_attention under shard_map.  Batch rides `dp` when the
    mesh has one."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    batch_ax = "dp" if "dp" in mesh.axis_names else None
    qkv_spec = P(batch_ax, axis)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal,
                          axis_size=mesh.shape[axis]),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(q, k, v, kv_mask)
