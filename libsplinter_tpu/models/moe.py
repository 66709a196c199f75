"""Mixture-of-Experts layers: sparse token -> expert dispatch over the
experts THIS device holds, in two settings of one layer.

The reference serves only dense llama-family GGUF checkpoints through
llama.cpp (splainference.cpp:414-448); MoE is a net-new model family on
the TPU side.  `sparse_moe` is the layer:

  - the router scores EVERY expert of the model (its published width)
    in float32 and keeps the top-k: softmax scores renormalised over
    the selection (the Mixtral convention, `MoeMlp`), or sigmoid
    scores normalised over the selection and scaled (the DeepSeek-V3
    family's, models/mla.py), with an optional shared expert that
    every token passes through;
  - the layer is TOLD which experts it holds — `first` and the leading
    axis of the stacked (count, hidden, width) tensors — and computes
    the part of the result its own experts give, for only the tokens
    routed to them: (token, expert) slots are sorted by held expert,
    the rows gathered, three GROUPED matrix products (two where
    the experts have no gate matrix) run over the
    ragged groups (jax.experimental.pallas.ops.tpu.megablox on a TPU,
    jax.lax.ragged_dot elsewhere), and the gated rows scatter-add
    back.  Work follows the slots that landed here, not tokens x
    experts.  Nothing is dropped under any routing: the row buffer
    holds the worst case (every token choosing min(k, count) held
    experts) and long sequences go through in chunks;
  - what the absent experts would have added is left out, here and in
    the plain reference alike (models/mla.py's share of an
    expert-parallel deployment).  With first == 0 and every expert
    held the layer is the whole model's.

Expert parallelism for the Mixtral form stays what it was: shard the
stacked tensors' E axis over the mesh's `ep` axis
(parallel/serve.moe_param_pspec) and let GSPMD place the grouped
products; `--ep` serves it.

MoeDecoder is call-compatible with Decoder (ids, cache, pos) ->
(logits, cache): the SAME CompletionModel / ShardedCompletionModel /
completion-daemon stack serves it via the `module=` override, and
attention still shards on tp independently of ep.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from .decoder import DecoderConfig


@dataclasses.dataclass(frozen=True)
class MoeDecoderConfig(DecoderConfig):
    n_experts: int = 8
    top_k: int = 2

    @classmethod
    def tiny(cls, **kw) -> "MoeDecoderConfig":
        kw = {"vocab_size": 1024, "hidden": 64, "layers": 2, "heads": 4,
              "kv_heads": 2, "mlp_dim": 128, "max_len": 128,
              "n_experts": 4, "top_k": 2, **kw}
        return cls(**kw)


# tokens one dispatch takes: longer sequences go through in chunks of
# this many, so the worst-case row buffer (tokens x min(k, count) rows
# of hidden width) stays bounded while nothing is ever dropped
MOE_CHUNK_TOKENS = 2048
# megablox tiles (rows, contraction, output columns); rows are padded
# to the first, the others shrink to divide the weight's shape
GMM_TILES = (128, 1024, 1024)


def _router_scores(x, router, score: str):
    """(T, E) float32 scores over ALL of the model's experts."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    if score == "sigmoid":
        return jax.nn.sigmoid(logits)
    raise ValueError(f"unknown router score function {score!r}")


def router_gates(x, router, *, top_k: int, score: str = "softmax",
                 norm_topk: bool = True, scale: float = 1.0, bias=None):
    """The routing every share of a layer computes alike, in float32
    over ALL of the model's experts.  x: (T, H); router: (H, E).
    Returns (ids (T, k) int32, gates (T, k) f32): the k best experts
    a token and the weight of each — softmax or sigmoid scores,
    normalised over the selection (norm_topk), times `scale`.
    bias: None, or (E,) float32 — a learned SELECTION bias (the
    auxiliary-loss-free balancing of the DeepSeek-V3 line): the k best
    are those of scores + bias, their gates the scores alone."""
    scores = _router_scores(x, router, score)
    if bias is None:
        topv, topi = jax.lax.top_k(scores, top_k)
    else:
        _, topi = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if norm_topk:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-20)
    return topi.astype(jnp.int32), topv * scale


def router_bias_swaps(x, router, bias, live, *, top_k: int, score: str):
    """How many of the LIVE tokens' selections the bias changed: the
    (token, expert) slots of the top-k of scores + bias that the top-k
    of the scores alone does not hold.  x: (T, H); live: (T,) bool.
    Returns an int32 scalar."""
    scores = _router_scores(x, router, score)
    _, with_b = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    _, without = jax.lax.top_k(scores, top_k)
    kept = (with_b[:, :, None] == without[:, None, :]).any(-1)
    return jnp.sum(~kept & live[:, None], dtype=jnp.int32)


def _tile(dim: int, want: int) -> int:
    """The largest multiple of 128 at or under `want` that divides
    `dim`, or `dim` itself."""
    t = min(want, dim) // 128 * 128
    while t >= 128:
        if dim % t == 0:
            return t
        t -= 128
    return dim


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool = False,
                   transpose_rhs: bool = False):
    """lhs[rows of group g] @ rhs[g] for consecutive row groups.
    lhs: (M, K); rhs: (G, K, N) — (G, N, K) where `transpose_rhs`:
    lhs[rows of g] @ rhs[g]^T, for a matrix whose output width N is no
    whole number of 128-lane tiles (the chip keeps such a tensor with
    N off the lanes and would copy it for the kernel at every call,
    tests/test_chip_compile.py) —; group_sizes: (G,) int32, sum <= M;
    rows past the last group come back zero.  The megablox Pallas
    kernel on a TPU (its grid follows the rows present, not M x G),
    jax.lax.ragged_dot elsewhere."""
    if interpret or jax.default_backend() == "tpu":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        M, K = lhs.shape
        N = rhs.shape[1 if transpose_rhs else 2]
        tm = GMM_TILES[0]
        pad = (-M) % tm
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
                  tiling=(tm, _tile(K, GMM_TILES[1]),
                          _tile(N, GMM_TILES[2])),
                  transpose_rhs=transpose_rhs, interpret=interpret)
        return out[:M] if pad else out
    return jax.lax.ragged_dot(
        lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, group_sizes)


def _dispatch(x, ids, gates, live, wg, wu, wd, first: int,
              interpret: bool, bank=None, up_rows: bool = False):
    """One chunk of tokens through the held experts.  x: (T, H);
    ids/gates: (T, k); live: (T,) bool; up_rows: wu is (count, M, H),
    an output column a ROW (sparse_moe).  Returns ((T, H) f32 partial
    sum, (count,) int32 slots each held expert received)."""
    T, H = x.shape
    k = ids.shape[1]
    gated = wg is not None
    count = wd.shape[-3]
    local = ids - first
    held = (local >= 0) & (local < count) & live[:, None]
    # slots sorted by held expert; the rest sort behind under `count`
    group = jnp.where(held, local, count).reshape(-1)        # (T*k,)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=count + 1)[:count].astype(
        jnp.int32)
    # worst case: every token picks min(k, count) experts held here
    rows = T * min(k, count)
    order = order[:rows]
    tok = order // k
    xs = x[tok]                                              # (rows, H)
    groups = sizes
    if bank is not None:
        # the layer's experts are bank `bank` of a stack of layers'
        # (banks, count, ...): every other bank an empty group, so
        # that the grouped product reads the stack where it lies — a
        # slice of it would be copied for the kernel at every layer
        banks = wu.shape[0]
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((banks * count,), jnp.int32), sizes,
            (bank * count,))
        wg, wu, wd = (w if w is None
                      else w.reshape(banks * count, *w.shape[2:])
                      for w in (wg, wu, wd))
    h = grouped_matmul(xs, wg, groups, interpret=interpret) \
        if gated else None
    u = grouped_matmul(xs, wu, groups, interpret=interpret,
                       transpose_rhs=up_rows)
    # SwiGLU, or without a gate matrix down(relu(up(x))^2)
    mid = nn.silu(h) * u if gated else jnp.square(nn.relu(u))
    y = grouped_matmul(mid.astype(x.dtype), wd, groups,
                       interpret=interpret)
    # rows past the last group belong to no expert held here, and the
    # grouped product leaves them unwritten: mask, do not multiply
    mine = (jnp.arange(rows) < sizes.sum())[:, None]
    y = jnp.where(mine, y.astype(jnp.float32)
                  * gates.reshape(-1)[order][:, None], 0.0)
    return jnp.zeros((T, H), jnp.float32).at[tok].add(y), sizes


def sparse_moe(x, router, wg, wu, wd, *, top_k: int, first: int = 0,
               score: str = "softmax", norm_topk: bool = True,
               scale: float = 1.0, shared=None, live=None,
               interpret: bool = False, bank=None, route_x=None,
               bias=None, live_chunk: int | None = None,
               up_rows: bool = False):
    """The expert layer over the experts held here.

    x: (..., H) activations; router: (H, E) over ALL E experts of the
    model; wg/wu: (count, H, M), wd: (count, M, H) — experts
    first..first+count-1; wg None: UN-GATED experts, down(relu(up(x))^2)
    (two grouped products, not three; the shared expert's gate is then
    None too); up_rows: wu is (count, M, H), the transpose — for a
    width M that is no whole number of lane tiles (grouped_matmul);
    shared: None or (gate (H, Ms), up (H, Ms),
    down (Ms, H)) of an expert every token passes through, counted
    here in full; live: None or a bool mask of x's leading shape —
    tokens outside it are routed nowhere and counted nowhere (the
    dead rows of a paged decode step); bank: None, or the int32
    index (traced or not) of THIS layer in wg/wu/wd that stack several
    layers' experts as (banks, count, ...) — a stack of identical
    layers under one scanned body (models/afmoe.py); route_x: None,
    or x as the ROUTER sees it, where the caller has it unrounded
    (float32, x's shape): which experts a token takes is the layer's
    one discontinuous decision, and a rounding of its input flips it
    where two experts score alike; bias: None, or the router's (E,)
    selection bias (router_gates); live_chunk: None, or the tokens a
    chunk for a caller MOST of whose tokens are dead (the pad tokens
    of an admission round's rows, models/lfm2.py): the live tokens
    are brought to the front first and a chunk that holds none is
    skipped, so the experts' weights are read once for every
    live_chunk LIVE tokens and not once for every MOE_CHUNK_TOKENS
    token slots.
    Returns (out (..., H) in x's dtype — the shared expert plus the
    gated sum over the HELD experts among each token's top-k, (count,)
    int32 — the slots each held expert received)."""
    lead, H = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, H)
    T = x2.shape[0]
    live2 = jnp.ones((T,), bool) if live is None else live.reshape(-1)
    ids, gates = router_gates(
        x2 if route_x is None else route_x.reshape(-1, H), router,
        top_k=top_k, score=score, norm_topk=norm_topk, scale=scale,
        bias=bias)
    chunk = live_chunk or MOE_CHUNK_TOKENS
    if T <= chunk:
        out, sizes = _dispatch(x2, ids, gates, live2, wg, wu, wd,
                               first, interpret, bank, up_rows)
    else:
        n = -(-T // chunk)
        pad = n * chunk - T

        def chunks(a):
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape(n, chunk, *a.shape[1:])

        def one(c):
            return _dispatch(*c, wg, wu, wd, first, interpret, bank,
                             up_rows)

        def one_if_live(c):
            return jax.lax.cond(
                c[3].any(), one,
                lambda c: (jnp.zeros((chunk, H), jnp.float32),
                           jnp.zeros((wd.shape[-3],), jnp.int32)), c)
        args = (x2, ids, gates, live2)
        if live_chunk:
            order = jnp.argsort(~live2, stable=True)
            args = tuple(a[order] for a in args)
        out, sizes = jax.lax.map(one_if_live if live_chunk else one,
                                 tuple(chunks(a) for a in args))
        out = out.reshape(-1, H)[:T]
        if live_chunk:
            out = out[jnp.argsort(order)]
        sizes = sizes.sum(0)
    if shared is not None:
        sg, su, sd = shared
        mid = jnp.square(nn.relu(jnp.dot(x2, su))) if sg is None \
            else nn.silu(jnp.dot(x2, sg)) * jnp.dot(x2, su)
        out = out + jnp.dot(mid, sd).astype(jnp.float32)
    return out.astype(x.dtype).reshape(*lead, H), sizes


class _Router(nn.Module):
    """The router's (hidden, experts) float32 kernel, under the
    parameter path an nn.Dense named the same would give it."""
    n_experts: int

    @nn.compact
    def __call__(self, hidden: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (hidden, self.n_experts))


class MoeMlp(nn.Module):
    """The Mixtral setting of `sparse_moe`: softmax gates renormalised
    over the top-k, no shared expert, every expert held (stacked
    (E, ...) weights; `ep` shards their E axis)."""
    cfg: MoeDecoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        E, H, M = cfg.n_experts, cfg.hidden, cfg.mlp_dim
        # the nn.Dense parameter tree (router/kernel) checkpoints and
        # the GGUF loader map onto
        router = _Router(E, name="router")(H)

        if cfg.quantized:
            # int8-resident expert stacks (models/quant.py): same HBM
            # halving as the dense projections, dequantized in-graph
            from .quant import expert_weight
            wg = expert_weight(self, "gate_experts", E, H, M, cfg.dtype)
            wu = expert_weight(self, "up_experts", E, H, M, cfg.dtype)
            wd = expert_weight(self, "down_experts", E, M, H, cfg.dtype)
        else:
            init = nn.initializers.lecun_normal()
            wg = self.param("gate_experts", init, (E, H, M)).astype(
                cfg.dtype)
            wu = self.param("up_experts", init, (E, H, M)).astype(
                cfg.dtype)
            wd = self.param("down_experts", init, (E, M, H)).astype(
                cfg.dtype)
        out, _ = sparse_moe(x.astype(cfg.dtype), router, wg, wu, wd,
                            top_k=cfg.top_k)
        return out


def MoeDecoder(cfg: MoeDecoderConfig, mesh=None):
    """Causal MoE LM: the shared Decoder trunk (embed, cache threading,
    final norm, LM head — decoder.Decoder) with MoeMlp as each layer's
    MLP.  Same call signature; param tree differs only inside each
    layer (layer_i/moe/...).  mesh threads through to the attention
    kernels for sharded serving (decoder.CausalAttention.mesh)."""
    from .decoder import Decoder

    return Decoder(cfg, mlp_cls=MoeMlp, mesh=mesh)


def moe_completion_model(cfg: MoeDecoderConfig, mesh=None, **kw) -> Any:
    """CompletionModel over the MoE family; pass a mesh for sharded
    (tp attention + ep experts) serving."""
    from .decoder import CompletionModel

    module = MoeDecoder(cfg, mesh=mesh)
    if mesh is None:
        return CompletionModel(cfg, module=module, **kw)
    ep = mesh.shape.get("ep", 1)
    if cfg.n_experts % ep:
        raise ValueError(
            f"n_experts={cfg.n_experts} must divide the ep={ep} mesh "
            "axis (expert tensors shard their E dimension)")
    from ..parallel.serve import ShardedCompletionModel
    return ShardedCompletionModel(cfg, mesh, module=module, **kw)
