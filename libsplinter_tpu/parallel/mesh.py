"""Device mesh helpers for pod-scale execution.

The reference is single-machine by design ("Multi-machine replication —
use a real database", README.md:139-146); the scale-out path is net-new
here (SURVEY.md §2.7): shard the arena per host, run the encoder and the
similarity kernels over a jax.sharding.Mesh, and let XLA place
collectives on ICI.

Axes:
  dp — data parallel (batch)
  tp — tensor parallel (hidden/heads)
  sp — sequence parallel (long-context; ring attention rides this axis)
  ep — expert parallel (MoE expert dimension; models/moe.py)
  pp — pipeline parallel (layer stages; parallel/pipeline.py)
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(dp: int | None = None, tp: int = 1, sp: int = 1,
              ep: int = 1, pp: int = 1, devices=None) -> Mesh:
    """Build a (dp, tp, sp, ep, pp) mesh.  dp=None uses all remaining
    devices.  ep/pp default to 1, so existing (dp, tp, sp) call sites
    and partition specs are unaffected."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    rest = tp * sp * ep * pp
    if dp is None:
        if n % rest:
            raise ValueError(
                f"{n} devices not divisible by tp*sp*ep*pp={rest}")
        dp = n // rest
    if dp * rest != n:
        raise ValueError(f"dp*tp*sp*ep*pp={dp * rest} != #devices={n}")
    arr = np.asarray(devices).reshape(dp, tp, sp, ep, pp)
    return Mesh(arr, axis_names=("dp", "tp", "sp", "ep", "pp"))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp"))


def kv_pool_sharding(mesh: Mesh) -> NamedSharding:
    """Placement of a paged KV block pool (n_blocks, kv_heads, page,
    head_dim — or head_dim/2 uint8 for int4-PACKED pools, which shard
    identically because packing only narrows the unsharded last axis)
    for tensor-parallel decode: split on the KV-HEAD axis over tp, so
    every device holds every page at 1/tp of its bytes and the
    host-side page scheduler never changes (parallel/serve.py
    ShardedCompletionModel._pool_sharding; the shard_map'd ragged
    kernel in ops/paged_attention.py expects exactly this spec)."""
    return NamedSharding(mesh, P(None, "tp", None, None))


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """Placement of a quantized (int8 or int4-packed) paged pool's
    per-page per-kv-head scales (n_blocks, kv_heads): split on THEIR
    kv-head axis over tp — the scales shard with the heads they
    scale, so the shard_map'd quantized ragged kernel's
    scalar-prefetch tables shrink by tp alongside the pools
    (ops/paged_attention.py)."""
    return NamedSharding(mesh, P(None, "tp"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_pspec(path: tuple, leaf) -> P:
    """Tensor-parallel partition spec for encoder parameters.

    Megatron-style within each block: qkv/gate/up Dense kernels shard
    their OUTPUT dim on tp (column parallel); out/down Dense kernels shard
    their INPUT dim on tp (row parallel) so the pair needs one
    psum per block, which XLA inserts from these shardings.  Embeddings
    shard the vocab axis; everything else is replicated.
    """
    names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
    joined = "/".join(str(n) for n in names)
    colp = any(k in joined for k in ("qkv", "gate", "up"))
    rowp = any(k in joined for k in ("attn/out", "mlp/down"))
    if leaf.ndim == 2:
        if colp and joined.endswith("kernel"):
            return P(None, "tp")          # column parallel
        if rowp and joined.endswith("kernel"):
            return P("tp", None)          # row parallel
        # weights_int8 (quant.ChannelQuantDense): the int8 kernel
        # shards exactly like the float kernel it replaced
        if colp and joined.endswith("wq"):
            return P(None, "tp")
        if rowp and joined.endswith("wq"):
            return P("tp", None)
        if "tok_emb" in joined or "pos_emb" in joined:
            return P("tp", None)          # vocab-sharded embedding
    if leaf.ndim == 1 and joined.endswith("wscale"):
        # per-output-channel scales shard WITH the output columns on
        # column-parallel layers (scaling the local partial product
        # is exact — the multiply distributes over the later psum);
        # row-parallel outputs are full-width, so scales replicate
        return P("tp") if colp else P()
    return P()


def shard_params(params, mesh: Mesh, *, pspec_fn=None):
    """Place a param tree onto the mesh.  pspec_fn(path, leaf) -> P
    defaults to the encoder's param_pspec (serve.py passes the decoder
    rules)."""
    pspec_fn = pspec_fn or param_pspec
    def place(path, leaf):
        return jax.device_put(
            leaf, NamedSharding(mesh, pspec_fn(path, leaf)))
    return jax.tree_util.tree_map_with_path(place, params)


def param_shardings(params, mesh: Mesh):
    """The NamedSharding tree matching shard_params (for jit in_shardings)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_pspec(path, leaf)),
        params)
