"""Blockwise (flash-style) bidirectional attention for long buckets.

The encoder's naive attention materializes (B, H, S, S) float32 logits
in HBM — at S=2048 that is 16 MB per (batch row, head) and it caps the
batch size long before the MXU saturates.  The reference never faces
this because it REJECTS long inputs outright (splinference.cpp:226-233
marks >=0.9*n_ctx as context-exceeded); this framework embeds them, so
the long-bucket path gets a Pallas kernel:

  grid = (B, H, S / block_q); each program computes one query block's
  attention with the full K/V for its (batch, head) resident in VMEM —
  the (block_q, S) logits tile lives ONLY in VMEM, nothing quadratic
  ever reaches HBM.  Softmax runs in f32 with the finite NEG_INF mask
  (all-masked rows — fully padded batch rows — degrade to a uniform
  distribution instead of NaN, matching the naive path's -1e9 bias).

  Fully-masked rows are DON'T-CARE values: the encoder's pooling
  multiplies by the mask, so their outputs never reach the loss and
  their cotangents are zero in training.  When S is padded to a block
  multiple their uniform fallback spreads over S' instead of S — a
  difference visible only to a consumer that reads excluded rows
  directly (tests pin the contract with encoder-semantics cotangents).

K/V VMEM budget: S * D * 4 B * 2 = 1 MB at S=2048, D=64 — comfortably
inside VMEM, so no online-softmax streaming is needed at the window
sizes this encoder serves (the ring-attention path, parallel/
ring_attention.py, covers sequences beyond one chip).

On non-TPU backends the same math runs as plain jnp (tests exercise the
kernel itself via interpret=True).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mha_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, *, scale: float,
                precision=None):
    """One (batch, head, q-block) program.

    q_ref:   (1, 1, BQ, D)   query block
    k_ref:   (1, 1, S, D)    full keys for this (b, h)
    v_ref:   (1, 1, S, D)    full values
    mask_ref:(1, 1, S)       f32 key validity (1.0 = real token)
    out_ref: (1, 1, BQ, D)
    """
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    logits = jnp.dot(q, k.T, precision=precision,
                     preferred_element_type=jnp.float32) * scale
    m = mask_ref[0]                               # (1, S) broadcasts
    logits = jnp.where(m > 0.0, logits, NEG_INF)  # (BQ, S)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out_ref[0, 0] = jnp.dot(p.astype(v.dtype), v, precision=precision,
                            preferred_element_type=jnp.float32
                            ).astype(out_ref.dtype)


# splint: ignore[SPL205] reason=runs inside the registered trunk programs (embedder.encode / completer.trunk); the outer program is the attribution point
@functools.partial(jax.jit,
                   static_argnames=("block_q", "interpret", "hi_prec"))
def _flash_pallas(q, k, v, maskf, *, block_q: int, interpret: bool,
                  hi_prec: bool = False):
    """q/k/v: (B, H, S, D); maskf: (B, 1, S) f32.  Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    scale = 1.0 / np.sqrt(D)
    grid = (B, H, S // block_q)
    prec = jax.lax.Precision.HIGHEST if hi_prec else None
    return pl.pallas_call(
        functools.partial(_mha_kernel, scale=scale, precision=prec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S, D), lambda b, h, i: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, S), lambda b, h, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, maskf)


def _mha_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, mask_ref,
                    dq_ref, dk_ref, dv_ref, *, scale: float,
                    block_q: int, precision=None):
    """Blockwise backward for one (batch, head): recomputes each
    (block_q, S) probability tile in VMEM (the standard flash-attention
    backward identity), accumulating dK/dV across query blocks and
    writing dQ per block — nothing quadratic ever reaches HBM.

    refs are (1, 1, S, D) per (b, h) except mask (1, 1, S); outputs
    mirror inputs.  Derivation: with P = softmax(QK^T*scale + maskbias),
    D_i = rowsum(dO_i ∘ O_i):
        dV = P^T dO
        dS = P ∘ (dO V^T - D)
        dQ = dS K * scale ;  dK = dS^T Q * scale
    """
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    m = mask_ref[0]                                # (1, S)
    S, D = k.shape

    def body(i, carry):
        dk_acc, dv_acc = carry                     # f32: bf16 outputs
        sl = pl.dslice(i * block_q, block_q)       # must not compound
        q = q_ref[0, 0, sl]                        # per-block rounding
        o = o_ref[0, 0, sl]
        do = do_ref[0, 0, sl]
        logits = jnp.dot(q, k.T, precision=precision,
                         preferred_element_type=jnp.float32) * scale
        logits = jnp.where(m > 0.0, logits, NEG_INF)
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits)
        p = p / jnp.sum(p, axis=-1, keepdims=True)       # (BQ, S) f32
        dof = do.astype(jnp.float32)
        of = o.astype(jnp.float32)
        d_i = jnp.sum(dof * of, axis=-1, keepdims=True)  # (BQ, 1)
        dp = jnp.dot(dof, v.astype(jnp.float32).T, precision=precision,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - d_i) * scale                      # (BQ, S)
        dq_ref[0, 0, sl] = jnp.dot(
            ds, k.astype(jnp.float32), precision=precision,
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_acc += jnp.dot(ds.T, q.astype(jnp.float32), precision=precision,
                          preferred_element_type=jnp.float32)
        dv_acc += jnp.dot(p.T, dof, precision=precision,
                          preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    zero = jnp.zeros((S, D), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(0, S // block_q, body,
                                       (zero, zero))
    dk_ref[0, 0] = dk_acc.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_acc.astype(dv_ref.dtype)


# splint: ignore[SPL205] reason=training-only backward pass, not a serving hot path
@functools.partial(jax.jit,
                   static_argnames=("block_q", "interpret", "hi_prec"))
def _flash_bwd_pallas(q, k, v, o, do, maskf, *, block_q: int,
                      interpret: bool, hi_prec: bool = False):
    """q/k/v/o/do: (B, H, S, D); maskf: (B, 1, S).
    Returns (dq, dk, dv) each (B, H, S, D)."""
    B, H, S, D = q.shape
    scale = 1.0 / np.sqrt(D)
    grid = (B, H)
    full = pl.BlockSpec((1, 1, S, D), lambda b, h: (b, h, 0, 0),
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((B, H, S, D), q.dtype)
    prec = jax.lax.Precision.HIGHEST if hi_prec else None
    return pl.pallas_call(
        functools.partial(_mha_bwd_kernel, scale=scale,
                          block_q=min(block_q, S), precision=prec),
        grid=grid,
        in_specs=[full, full, full, full, full,
                  pl.BlockSpec((1, 1, S), lambda b, h: (b, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[full, full, full],
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(q, k, v, o, do, maskf)


def _causal_kernel(q_ref, k_ref, v_ref, pos_ref, start_ref, out_ref, *,
                   scale: float):
    """One (batch, head, q-block) program of DECODER PREFILL attention:
    queries at cache slots pos..pos+S-1 attend keys j with
    start[b] <= j <= pos + i (the decoder's causal + left-pad mask,
    models/decoder.py CausalAttention).  Full cache K/V for the
    (batch, head) resident in VMEM; the (block_q, T) logits tile never
    reaches HBM.

    q_ref: (1, 1, BQ, D); k/v_ref: (1, 1, T, D); pos_ref: (1,) SMEM;
    start_ref: (B,) SMEM — the FULL left-pad vector (Mosaic requires
    rank-1 SMEM blocks be whole-array or 128-multiples, so slicing one
    row per program via a (1,) block does not lower); each program
    reads its own row by program_id(0);
    out_ref: (1, 1, BQ, D).
    """
    b = pl.program_id(0)
    i = pl.program_id(2)
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    BQ = q.shape[0]
    T = k.shape[0]
    pos = pos_ref[0]
    start = start_ref[b]
    logits = jnp.dot(q, k.T,
                     preferred_element_type=jnp.float32) * scale
    qi = pos + i * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, T), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (BQ, T), 1)
    visible = (kj <= qi) & (kj >= start)
    logits = jnp.where(visible, logits, NEG_INF)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out_ref[0, 0] = jnp.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32
                            ).astype(out_ref.dtype)


# splint: ignore[SPL205] reason=runs inside the registered decode programs (completer.chunk / completer.paged_chunk); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def _causal_flash_pallas(q, k, v, pos, start, *, block_q: int,
                         interpret: bool):
    """q: (B, H, S, D); k/v: (B, KH, T, D) — KH may be smaller than H
    (GQA): the index map routes query head h to kv head h // rep, so
    the repeated K/V never materializes in HBM.  pos: (1,) i32;
    start: (B,) i32.  Returns (B, H, S, D)."""
    B, H, S, D = q.shape
    KH, T = k.shape[1], k.shape[2]
    rep = H // KH
    scale = 1.0 / np.sqrt(D)
    grid = (B, H, S // block_q)
    kv_spec = pl.BlockSpec((1, 1, T, D),
                           lambda b, h, i: (b, h // rep, 0, 0),
                           memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_causal_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1,), lambda b, h, i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda b, h, i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, pos, start)


def _causal_flash_host(q, kk, vv, pos, start, *, block_q: int,
                       interpret: bool):
    """The per-device Pallas dispatch (pad S to a block multiple,
    transpose to head-major, kernel, undo).  Under mesh= this runs
    PER SHARD inside shard_map with the local H/tp query heads and
    KH/tp kv heads — the GQA head→kv-head routing stays local because
    query heads shard consistently with kv heads."""
    B, S, H, D = q.shape
    bq = min(block_q, S)
    pad = (-S) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qt = q.transpose(0, 2, 1, 3)
    kt = kk.transpose(0, 2, 1, 3)
    vt = vv.transpose(0, 2, 1, 3)
    out = _causal_flash_pallas(
        qt, kt, vt, jnp.asarray(pos, jnp.int32).reshape(1),
        jnp.asarray(start, jnp.int32), block_q=bq,
        interpret=interpret)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :S] if pad else out


def causal_flash_attention(q, kk, vv, pos, start=None, *,
                           block_q: int = 256, interpret: bool = False,
                           force_pallas: bool = False, mesh=None):
    """Decoder-prefill attention without HBM-quadratic logits
    (FORWARD/serving only — the decoder trains nowhere in this
    framework, so no VJP is defined; jax.grad through this raises).

    q: (B, S, H, D) queries at cache slots pos..pos+S-1;
    kk/vv: (B, T, KH, D) the updated cache — pass kv heads UNREPEATED
    (GQA): the kernel maps query head h to kv head h // (H//KH), so
    the repeated cache never hits HBM;
    pos: scalar int32; start: None or (B,) left-pad offsets.
    Returns (B, S, H, D).

    mesh: a Mesh with a tp axis > 1 runs the kernel under shard_map —
    GSPMD cannot partition a Mosaic custom call, which is why sharded
    serving used to demote flash_min_seq to 0 and prefill through the
    naive path (parallel/serve.py pre-PR-8).  With the mesh threaded,
    queries shard on their head axis and the cache on its kv-head
    axis, each device runs the same kernel over its local heads, and
    the jnp fallback (non-TPU, no interpret) stays un-shard_map'd:
    GSPMD partitions plain einsums natively.
    """
    B, S, H, D = q.shape
    if start is None:
        start = jnp.zeros((B,), jnp.int32)
    use_pallas = (force_pallas or interpret
                  or jax.default_backend() == "tpu")
    if not use_pallas:
        rep = H // kk.shape[2]
        if rep > 1:                   # the einsum fallback needs H heads
            kk = jnp.repeat(kk, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        return _causal_jnp(q, kk, vv, pos, start)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as SP

        from jax import shard_map

        body = functools.partial(_causal_flash_host, block_q=block_q,
                                 interpret=interpret)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(SP(None, None, "tp", None),   # q: heads
                      SP(None, None, "tp", None),   # kk: kv heads
                      SP(None, None, "tp", None),   # vv
                      SP(), SP()),                  # pos / start
            out_specs=SP(None, None, "tp", None),
            check_vma=False)
        return fn(q, kk, vv, jnp.asarray(pos, jnp.int32),
                  jnp.asarray(start, jnp.int32))
    return _causal_flash_host(q, kk, vv, pos, start, block_q=block_q,
                              interpret=interpret)


def _causal_jnp(q, kk, vv, pos, start):
    """Reference math — mirrors models/decoder.py CausalAttention's
    masked softmax exactly (slot-causal + per-row start)."""
    D = q.shape[-1]
    S = q.shape[1]
    T = kk.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
    idx = pos + jnp.arange(S)
    visible = (jnp.arange(T)[None, :] <= idx[:, None])[None, :, :] \
        & (jnp.arange(T)[None, None, :] >= start[:, None, None])
    logits = jnp.where(visible[:, None], logits.astype(jnp.float32),
                       NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


def _mha_jnp(q, k, v, mask):
    """Reference math, (B, S, H, D) layout — identical to the encoder's
    naive path (encoder.py SelfAttention) up to the finite mask value."""
    D = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    bias = jnp.where(mask[:, None, None, :], 0.0, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32) + bias,
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _to_kernel_layout(tensors, mask, bq: int):
    """Shared pad/transpose for forward AND backward (they must agree
    or padded-case gradients silently diverge): (B, S, H, D) tensors →
    (B, H, S', D) with S' a block multiple, mask → (B, 1, S') f32.
    Returns (transposed list, maskf, pad)."""
    S = tensors[0].shape[1]
    pad = (-S) % bq
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        tensors = [jnp.pad(t, widths) for t in tensors]
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return ([t.transpose(0, 2, 1, 3) for t in tensors],
            mask.astype(jnp.float32)[:, None, :], pad)


def _flash_fwd_only(q, k, v, mask, block_q: int, interpret: bool,
                    hi_prec: bool = False):
    """The Pallas forward: pad S to a block multiple, transpose to
    (B, H, S, D), run the kernel, undo."""
    S = q.shape[1]
    bq = min(block_q, S)
    (qt, kt, vt), maskf, pad = _to_kernel_layout([q, k, v], mask, bq)
    out = _flash_pallas(qt, kt, vt, maskf, block_q=bq,
                        interpret=interpret, hi_prec=hi_prec)
    out = out.transpose(0, 2, 1, 3)
    return out[:, :S] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_diff(q, k, v, mask, block_q, interpret, hi_prec):
    """Differentiable wrapper: a raw pallas_call has no autodiff rule,
    and the encoder's TRAINING path hits this kernel whenever a long
    bucket trains (train.py over S >= flash_min_seq).  Forward runs
    the forward kernel; backward runs the blockwise backward kernel
    (_mha_bwd_kernel) — probability tiles are recomputed in VMEM per
    query block, so the TRAINING path is as HBM-linear as inference."""
    return _flash_fwd_only(q, k, v, mask, block_q, interpret, hi_prec)


def _flash_diff_fwd(q, k, v, mask, block_q, interpret, hi_prec):
    out = _flash_fwd_only(q, k, v, mask, block_q, interpret, hi_prec)
    return out, (q, k, v, mask, out)


def _flash_diff_bwd(block_q, interpret, hi_prec, res, g):
    q, k, v, mask, out = res
    S = q.shape[1]
    bq = min(block_q, S)
    (qt, kt, vt, ot, gt), maskf, pad = _to_kernel_layout(
        [q, k, v, out, g], mask, bq)
    dq, dk, dv = _flash_bwd_pallas(qt, kt, vt, ot, gt, maskf,
                                   block_q=bq, interpret=interpret,
                                   hi_prec=hi_prec)

    def unpadded(x):
        x = x.transpose(0, 2, 1, 3)
        return x[:, :S] if pad else x

    return unpadded(dq), unpadded(dk), unpadded(dv), None


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, mask, *, block_q: int = 256,
                    interpret: bool = False,
                    force_pallas: bool = False,
                    hi_prec: bool = False):
    """Bidirectional masked attention without HBM-quadratic logits.

    q/k/v: (B, S, H, D); mask: (B, S) bool key validity.
    Returns (B, S, H, D) in q's dtype.  The Pallas kernel runs on TPU
    (or under interpret/force_pallas for tests); other backends use the
    identical jnp math.  Differentiable either way: the custom VJP
    runs the BLOCKWISE backward kernel (probability tiles recomputed
    in VMEM, dK/dV accumulated in f32), so training stays HBM-linear
    like the forward.

    hi_prec=True runs every MXU dot at Precision.HIGHEST (the
    multi-pass f32 decomposition) — the correctness-check arm: at
    default precision Mosaic truncates f32 dot INPUTS to bf16 exactly
    like XLA does for the naive einsums, so kernel-vs-naive diffs are
    dominated by their different rounding orders (~5e-3 relative,
    deterministic), not kernel bugs.  Matching HIGHEST on both sides
    isolates the algorithm (agrees to ~1e-4); serving/training keep
    the fast default."""
    use_pallas = (force_pallas or interpret
                  or jax.default_backend() == "tpu")
    if not use_pallas:
        return _mha_jnp(q, k, v, mask)
    return _flash_diff(q, k, v, mask, block_q, interpret, hi_prec)
