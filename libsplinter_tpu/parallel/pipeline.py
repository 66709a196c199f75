"""Pipeline parallelism for the encoder: GPipe-style microbatching over
the mesh's `pp`-capable axis.

The reference has no model execution to pipeline (single-context
llama.cpp per daemon, SURVEY.md §2.7); this is the TPU-first path for
encoders whose layer stack exceeds one chip's HBM.  The design follows
the JAX SPMD recipe rather than a scheduler thread pool:

  - the transformer LAYER stack is the pipelined region: layer params
    stack into a leading (stages, layers_per_stage, ...) axis and shard
    P(axis) — each device physically holds only its stage's layers;
  - inside one shard_map, a lax.scan runs the GPipe schedule: at step t
    stage s processes microbatch (t - s); activations hop stage→stage
    with lax.ppermute (ICI neighbor traffic, no host involvement);
    warm-up/drain bubble steps compute garbage that is masked out of
    the output buffer;
  - embedding lookup and the pooling head replicate (they are a tiny
    fraction of FLOPs/bytes); the last stage's collected outputs are
    re-replicated with one psum;
  - everything is differentiable (ppermute/scan/where), so jax.grad
    through pipeline_encode yields pipeline-parallel training with no
    extra machinery.

Exact-parity contract: pipeline_encode(...) == Encoder.apply(...) for
any stage count and microbatch split — pinned by
tests/test_pipeline.py on the virtual CPU mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.encoder import EncoderConfig, EncoderLayer, pool_normalize


def stack_layer_params(params, cfg: EncoderConfig):
    """Stack layer_0..layer_{L-1} subtrees into leading-axis arrays."""
    p = params["params"] if "params" in params else params
    layers = [p[f"layer_{i}"] for i in range(cfg.layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def stage_params(params, cfg: EncoderConfig, mesh: Mesh,
                 axis: str = "pp"):
    """ONE-TIME setup for the pipeline: split the param tree into
    (outer, staged) and PLACE them —

      outer  = non-layer params (tok_emb, ln_emb) replicated;
      staged = layer params stacked to (stages, layers_per_stage, ...)
               and sharded P(axis), so each device physically holds
               only its own stage's layers.

    This is where the HBM win happens: pass the result to
    make_pipeline_encode_fn / pipeline_encode_staged and the full
    layer stack never materializes on any single chip.  (The
    convenience wrapper pipeline_encode() stages a replicated tree on
    every call — fine for tests and parity checks, NOT the
    big-model path.)"""
    stages = mesh.shape[axis]
    if cfg.layers % stages:
        raise ValueError(f"layers={cfg.layers} must divide into "
                         f"{stages} pipeline stages")
    per = cfg.layers // stages
    p = params["params"] if "params" in params else params
    outer = {k: v for k, v in p.items() if not k.startswith("layer_")}
    stacked = stack_layer_params(params, cfg)
    stacked = jax.tree.map(
        lambda a: a.reshape((stages, per) + a.shape[1:]), stacked)
    staged = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P(axis))),
        stacked)
    outer = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P())), outer)
    return outer, staged


def pipeline_encode(cfg: EncoderConfig, mesh: Mesh, params,
                    token_ids, attn_mask, *, microbatches: int,
                    axis: str = "pp"):
    """Convenience wrapper: stage a (replicated) param tree and run one
    pipelined forward.  token_ids: (B, S) int32; attn_mask: (B, S)
    bool.  Returns (B, out_dim) float32 — identical to Encoder.apply
    on the same params.  For repeated use (and for models that only
    fit BECAUSE of pipelining) call stage_params() once and use
    make_pipeline_encode_fn / pipeline_encode_staged instead."""
    outer, staged = stage_params(params, cfg, mesh, axis)
    return pipeline_encode_staged(cfg, mesh, outer, staged,
                                  token_ids, attn_mask,
                                  microbatches=microbatches, axis=axis)


def pipeline_encode_staged(cfg: EncoderConfig, mesh: Mesh, outer, staged,
                           token_ids, attn_mask, *, microbatches: int,
                           axis: str = "pp"):
    """Pipelined encoder forward over pre-staged params (stage_params).
    Differentiable w.r.t. (outer, staged)."""
    if cfg.variant != "nomic":
        raise ValueError("pipeline_encode supports the rotary 'nomic' "
                         "variant (bert adds a position table)")
    if cfg.ring_axis:
        raise ValueError(
            "pipeline_encode is mutually exclusive with ring_axis: the "
            "layers would treat the replicated sequence as sp-local "
            "chunks and silently mis-position/mis-pool — compose pp "
            "with dp/tp instead")
    stages = mesh.shape[axis]
    B, S = token_ids.shape
    M = microbatches
    if B % M:
        raise ValueError(f"batch {B} must divide into {M} microbatches")
    mb = B // M

    # replicated pre-stage: the SAME nn modules Encoder.__call__ runs,
    # applied over the outer params (no math duplicated to drift)
    x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype) \
        .apply({"params": outer["tok_emb"]}, jnp.asarray(token_ids))
    x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype) \
        .apply({"params": outer["ln_emb"]}, x)

    x_mb = x.reshape(M, mb, S, cfg.hidden)
    m_mb = jnp.asarray(attn_mask, bool).reshape(M, mb, S)

    layer = EncoderLayer(cfg)

    def stage_fn(stage_params, xin, mask):
        def body(h, lp):
            return layer.apply({"params": lp}, h, mask), None
        out, _ = jax.lax.scan(body, xin, stage_params)
        return out

    def pipelined(stage_params, x_mb, m_mb):
        # stage_params arrives as (1, per, ...): this device's stage
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        s = jax.lax.axis_index(axis)
        n_steps = M + stages - 1
        zero = jnp.zeros((mb, S, cfg.hidden), cfg.dtype)
        out_buf = jnp.zeros((M, mb, S, jnp.shape(x_mb)[-1]), cfg.dtype)

        def step(carry, t):
            recv, out_buf = carry
            mb_idx = jnp.clip(t - s, 0, M - 1)   # my microbatch this step
            inp = jnp.where(s == 0, x_mb[mb_idx], recv)
            out = stage_fn(stage_params, inp, m_mb[mb_idx])
            # collect at the last stage (valid once the pipe is full)
            done_idx = jnp.clip(t - (stages - 1), 0, M - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                out_buf, out, done_idx, 0)
            take = jnp.logical_and(s == stages - 1, t >= stages - 1)
            out_buf = jnp.where(take, upd, out_buf)
            # hop stage s -> s+1 (no wraparound: stage 0 feeds fresh
            # microbatches; a device with no sender receives zeros)
            nxt = jax.lax.ppermute(
                out, axis, [(i, i + 1) for i in range(stages - 1)])
            return (nxt, out_buf), None

        (_, out_buf), _ = jax.lax.scan(
            step, (zero, out_buf), jnp.arange(n_steps))
        # pool BEFORE re-replicating: the end-of-pipe collective then
        # carries (M, mb, out_dim), not the S-times-larger activations.
        # The head is the shared pool_normalize (encoder.py) so the
        # tail cannot drift from Encoder.__call__; on non-last stages
        # out_buf is all zeros, so the pooled value is zeros too (no
        # NaN) and the where+psum discards it.
        pooled = pool_normalize(cfg, out_buf, m_mb)   # (M, mb, out)
        return jax.lax.psum(
            jnp.where(s == stages - 1, pooled, 0.0), axis)

    fn = shard_map(
        pipelined, mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(staged, x_mb, m_mb).reshape(B, cfg.out_dim)


def make_pipeline_encode_fn(cfg: EncoderConfig, mesh: Mesh, params, *,
                            microbatches: int, axis: str = "pp"):
    """Stage the params ONCE (each device keeps only its stage's
    layers; see stage_params) and return a jitted
    fn(token_ids, attn_mask) -> (B, out_dim)."""
    outer, staged = stage_params(params, cfg, mesh, axis)

    @jax.jit
    def fn(token_ids, attn_mask):
        return pipeline_encode_staged(
            cfg, mesh, outer, staged, token_ids, attn_mask,
            microbatches=microbatches, axis=axis)
    return fn
