"""Ring attention / sequence parallelism vs the dense oracle.

The reference rejects long inputs (splinference.cpp:226-233) — long
context is a net-new first-class capability here, so correctness is
pinned to a single-device dense attention reference on the virtual
8-device CPU mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from libsplinter_tpu.models import Encoder, EncoderConfig
from libsplinter_tpu.parallel import (dense_reference, make_mesh,
                                      make_ring_train_step, make_train_step,
                                      ring_attention_sharded)


@pytest.fixture(scope="module")
def qkvm():
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 32, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
               for _ in range(3))
    mask = jnp.asarray(rng.random((B, S)) > 0.2)
    return q, k, v, mask


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(qkvm, causal):
    q, k, v, mask = qkvm
    mesh = make_mesh(dp=2, tp=1, sp=4)
    ref = dense_reference(q, k, v, mask, causal=causal)
    out = ring_attention_sharded(mesh, q, k, v, mask, causal=causal)
    assert float(jnp.abs(out - ref).max()) < 1e-5


@pytest.mark.parametrize("causal", [False, True])
def test_ring_gradient_matches_dense(qkvm, causal):
    """d/dq AND d/dk, d/dv — the k/v cotangents flow back through the
    ppermute transpose (inverse ring rotation), the novel backward path."""
    q, k, v, mask = qkvm
    mesh = make_mesh(dp=2, tp=1, sp=4)

    def loss_ring(q, k, v):
        return (ring_attention_sharded(mesh, q, k, v, mask,
                                       causal=causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (dense_reference(q, k, v, mask, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        assert float(jnp.abs(a - b).max()) < 1e-4, f"d/d{name} mismatch"


def test_sp8_full_ring(qkvm):
    """All 8 devices on the ring (sp=8, no dp)."""
    q, k, v, mask = qkvm
    mesh = make_mesh(dp=1, tp=1, sp=8)
    ref = dense_reference(q, k, v, mask)
    out = ring_attention_sharded(mesh, q, k, v, mask)
    assert float(jnp.abs(out - ref).max()) < 1e-5


@pytest.fixture(scope="module")
def enc_setup():
    rng = np.random.default_rng(1)
    cfg = EncoderConfig.tiny(out_dim=16, dtype=jnp.float32)
    B, S = 4, 32
    ids = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
    lens = rng.integers(S // 2, S + 1, size=(B,))
    mask = np.arange(S)[None] < lens[:, None]
    return cfg, ids, mask


@pytest.mark.parametrize("variant", ["nomic", "bert"])
def test_sequence_parallel_encoder_matches_dense(enc_setup, variant):
    """The encoder run sequence-sharded over sp (ring attention, global
    rotary/absolute positions, psum'd mean pool) reproduces the dense
    single-device embeddings."""
    cfg, ids, mask = enc_setup
    cfg = dataclasses.replace(cfg, variant=variant)
    dense = Encoder(cfg)
    params = dense.init(jax.random.PRNGKey(0), ids, mask)
    ref = dense.apply(params, ids, mask)

    mesh = make_mesh(dp=2, tp=1, sp=4)
    ring = Encoder(dataclasses.replace(cfg, ring_axis="sp"))
    fn = shard_map(lambda p, i, m: ring.apply(p, i, m), mesh=mesh,
                   in_specs=(P(), P("dp", "sp"), P("dp", "sp")),
                   out_specs=P("dp"), check_vma=False)
    out = fn(params, jnp.asarray(ids), jnp.asarray(mask))
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_ring_train_step_matches_dense(enc_setup):
    """One SGD step of the sequence-parallel trainer == one step of the
    single-device trainer (validates the psum/N gradient argument)."""
    cfg, ids, mask = enc_setup
    mesh = make_mesh(dp=2, tp=1, sp=4)
    opt = optax.sgd(0.1)
    init_d, step_d = make_train_step(cfg, optimizer=opt)
    init_r, step_r = make_ring_train_step(
        dataclasses.replace(cfg, ring_axis="sp"), mesh, optimizer=opt)

    batch = {"ids_a": jnp.asarray(ids), "mask_a": jnp.asarray(mask),
             "ids_b": jnp.asarray((ids + 7) % cfg.vocab_size),
             "mask_b": jnp.asarray(mask)}
    sd = init_d(jax.random.PRNGKey(0), ids[:1], mask[:1])
    sr = init_r(jax.random.PRNGKey(0), ids[:1], mask[:1])
    sd2, ld = step_d(sd, batch)
    sr2, lr = step_r(sr, batch)
    assert abs(float(ld) - float(lr)) < 1e-5
    deltas = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), sd2.params, sr2.params)
    assert max(jax.tree_util.tree_leaves(deltas)) < 1e-5
    assert int(sr2.step) == 1


def test_ring_train_step_rejects_missing_axis(enc_setup):
    cfg, ids, mask = enc_setup
    mesh = make_mesh(dp=8, tp=1, sp=1)
    with pytest.raises(ValueError):
        make_ring_train_step(cfg, mesh)  # no ring_axis set
