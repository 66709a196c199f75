"""Distributed contrastive training step for the embedding encoder.

The reference has no training at all (SURVEY.md §2.7) — embedding models
arrive as GGUF files.  A TPU-native framework owns its weights, so this
module provides the canonical way embedding encoders are actually
produced: in-batch InfoNCE over text pairs, sharded dp×tp over a device
mesh.  Shardings are declared with jax.sharding; XLA inserts the psum /
all-gather collectives over ICI.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..models import Encoder, EncoderConfig
from .mesh import (batch_sharding, param_shardings, replicated,
                   shard_params)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def info_nce_loss(za: jnp.ndarray, zb: jnp.ndarray,
                  temperature: float = 0.05) -> jnp.ndarray:
    """Symmetric in-batch InfoNCE: row i of za matches row i of zb."""
    logits = (za @ zb.T) / temperature
    labels = jnp.arange(za.shape[0])
    l_ab = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    l_ba = optax.softmax_cross_entropy_with_integer_labels(logits.T, labels)
    return (l_ab.mean() + l_ba.mean()) / 2.0


def make_train_step(cfg: EncoderConfig, optimizer=None,
                    temperature: float = 0.05):
    """Returns (init_fn, step_fn).  step_fn(state, batch) -> (state, loss).
    batch: dict(ids_a, mask_a, ids_b, mask_b)."""
    module = Encoder(cfg)
    optimizer = optimizer or optax.adamw(1e-4, weight_decay=0.01)

    def init_fn(rng, sample_ids, sample_mask):
        params = module.init(rng, sample_ids, sample_mask)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    def loss_fn(params, batch):
        za = module.apply(params, batch["ids_a"], batch["mask_a"])
        zb = module.apply(params, batch["ids_b"], batch["mask_b"])
        return info_nce_loss(za, zb, temperature)

    def step_fn(state: TrainState, batch) -> tuple[TrainState, jnp.ndarray]:
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    return init_fn, step_fn


def make_sharded_train_step(cfg: EncoderConfig, mesh, optimizer=None,
                            temperature: float = 0.05):
    """jit the train step over the mesh with dp batch sharding and tp
    parameter sharding.  Returns (sharded_init, sharded_step)."""
    init_fn, step_fn = make_train_step(cfg, optimizer, temperature)
    bsh = batch_sharding(mesh)

    def sharded_init(rng, sample_ids, sample_mask):
        state = init_fn(rng, sample_ids, sample_mask)
        p_sh = param_shardings(state.params, mesh)
        params = shard_params(state.params, mesh)
        # optimizer state mirrors the param tree sharding where shaped
        # like params; scalars replicate
        def opt_place(x):
            return jax.device_put(x, replicated(mesh))
        opt_state = jax.tree_util.tree_map(opt_place, state.opt_state)
        state = TrainState(params, opt_state,
                           jax.device_put(state.step, replicated(mesh)))

        batch_shardings = {k: bsh for k in
                           ("ids_a", "mask_a", "ids_b", "mask_b")}
        opt_shardings = jax.tree_util.tree_map(
            lambda x: replicated(mesh), state.opt_state)
        state_shardings = TrainState(p_sh, opt_shardings, replicated(mesh))
        step = jax.jit(
            step_fn,
            in_shardings=(state_shardings, batch_shardings),
            out_shardings=(state_shardings, replicated(mesh)),
        )
        return state, step

    return sharded_init


def make_ring_train_step(cfg: EncoderConfig, mesh, optimizer=None,
                         temperature: float = 0.05):
    """Sequence-parallel (ring attention) training step under shard_map.

    cfg.ring_axis names the mesh sequence axis (conventionally "sp");
    batches arrive sharded (batch over dp) x (sequence over sp), each
    device runs the encoder on its O(S/n_sp) chunk with K/V rotating over
    ICI, and embeddings are all-gathered over dp for in-batch InfoNCE.

    Gradient correctness: the per-device losses are N identical replicas
    of the global loss (N = n_dp * n_sp), so the joint backward computes
    d(N*L)/dtheta spread across the devices' local parameter cotangents;
    psum over both axes then /N recovers the exact gradient (the same
    broadcast-transpose argument that makes replicated-parameter pmap
    training work).

    Returns (init_fn, step_fn); step_fn(state, batch) -> (state, loss)
    with batch dict(ids_a, mask_a, ids_b, mask_b) as GLOBAL arrays.
    """
    if not cfg.ring_axis or cfg.ring_axis not in mesh.axis_names:
        raise ValueError("cfg.ring_axis must name a mesh axis (e.g. 'sp')")
    axis = cfg.ring_axis
    n_total = mesh.shape["dp"] * mesh.shape[axis]
    module = Encoder(cfg)
    optimizer = optimizer or optax.adamw(1e-4, weight_decay=0.01)

    def init_fn(rng, sample_ids, sample_mask):
        # init with a dense twin: identical param tree, no axis context
        dense = Encoder(dataclasses.replace(cfg, ring_axis=None))
        params = dense.init(rng, sample_ids, sample_mask)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    def local_step(state, ids_a, mask_a, ids_b, mask_b):
        def loss_fn(params):
            za = module.apply(params, ids_a, mask_a)
            zb = module.apply(params, ids_b, mask_b)
            za_g = lax.all_gather(za, "dp", axis=0, tiled=True)
            zb_g = lax.all_gather(zb, "dp", axis=0, tiled=True)
            return info_nce_loss(za_g, zb_g, temperature)
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, ("dp", axis)) / n_total, grads)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    batch_spec = P("dp", axis)
    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), batch_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(P(), P()),
        check_vma=False,
    ))

    def step_fn(state: TrainState, batch) -> tuple[TrainState, jnp.ndarray]:
        return step(state, batch["ids_a"], batch["mask_a"],
                    batch["ids_b"], batch["mask_b"])

    return init_fn, step_fn
