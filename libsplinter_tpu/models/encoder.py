"""TPU-native text embedding encoder (flax).

Replaces the reference's llama.cpp GGUF embedding sidecar compute
(splinference.cpp:423-448 loads a Nomic-Embed GGUF and runs serial CPU
decode; see SURVEY.md §2.2).  Here the encoder is a JAX/flax module
compiled once per (batch, seqlen) bucket and run on TPU:

  - Nomic-BERT geometry by default (bert-base sized: 12 layers, 768
    hidden, 12 heads, vocab 30528) with rotary position embeddings and a
    SwiGLU MLP — the nomic-embed-text-v1.5 architecture family;
  - a `bert` variant (learned absolute positions, GELU MLP) for vanilla
    BERT-style checkpoints;
  - mean pooling over valid tokens + L2 normalisation, with optional
    matryoshka truncation (v1.5's resizable dimensionality);
  - bfloat16 activations/params on TPU (MXU-native), float32 output.

Weights load from a safetensors file when one is provided; otherwise the
model runs with seeded random init (the protocol and the benchmarks do
not depend on the weight values).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..obs.devtime import DEVTIME


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    vocab_size: int = 30528
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 2048
    variant: str = "nomic"        # "nomic" (rotary+swiglu) | "bert"
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.bfloat16     # activation dtype
    out_dim: int = 768            # matryoshka truncation target
    # buckets at/above this width attend through the blockwise Pallas
    # kernel (ops/flash_attention.py): no HBM-quadratic logits, so long
    # buckets keep real batch sizes.  0 disables (always naive).
    flash_min_seq: int = 512
    # Sequence parallelism: when set, inputs are the LOCAL chunk of a
    # sequence sharded over this mesh axis and attention runs as ring
    # attention (must be applied inside shard_map with the axis bound).
    ring_axis: str | None = None
    # per-output-channel int8 weight residency (models/quant.py
    # ChannelQuantDense — the decoder's weights_int8 path, shared):
    # attention/MLP kernels live as int8 + one f32 scale per output
    # column, matmul first, dequant on the f32 output; biases,
    # embeddings, and norms stay float.
    weights_int8: bool = False

    @classmethod
    def tiny(cls, **kw) -> "EncoderConfig":
        """Small config for tests and CPU CI; kw overrides any field."""
        base = dict(vocab_size=1024, hidden=64, layers=2, heads=4,
                    mlp_dim=128, max_len=128)
        base.update(kw)
        return cls(**base)


def _rotary_angles(seq_len: int, head_dim: int,
                   base: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    return _rotary_angles_at(pos, head_dim, base)


def _rotary_angles_at(pos: jnp.ndarray, head_dim: int,
                      base: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Rotary cos/sin at explicit (possibly offset) positions — sequence-
    parallel shards need GLOBAL positions for their local chunk."""
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = jnp.einsum("s,d->sd", pos.astype(jnp.float32), freqs)  # (S, half)
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rotary(x: jnp.ndarray, cos: jnp.ndarray,
                  sin: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D).  Rotates pairs (x1, x2) = (x[..., :half], rest).
    cos/sin: (S, D/2) shared across the batch, or (B, S, D/2) per-row
    (left-padded batched decode offsets each row's positions)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _dense(cfg: EncoderConfig, features: int, name: str):
    """The encoder's projection module: plain Dense, or the shared
    per-output-channel int8 residency when cfg.weights_int8 (same
    module NAME either way, so checkpoints convert in place via
    quant.quantize_encoder_params)."""
    if cfg.weights_int8:
        from .quant import ChannelQuantDense
        return ChannelQuantDense(features, dtype=cfg.dtype,
                                 use_bias=True, name=name)
    return nn.Dense(features, dtype=cfg.dtype, name=name)


class SelfAttention(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        head_dim = cfg.hidden // cfg.heads
        B, S, _ = x.shape
        qkv = _dense(cfg, 3 * cfg.hidden, "qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, cfg.heads, head_dim)
        k = k.reshape(B, S, cfg.heads, head_dim)
        v = v.reshape(B, S, cfg.heads, head_dim)
        if cfg.variant == "nomic":
            if cfg.ring_axis:
                # S here is the LOCAL chunk; rotary needs global positions
                shard = jax.lax.axis_index(cfg.ring_axis)
                pos = shard * S + jnp.arange(S)
                cos, sin = _rotary_angles_at(pos, head_dim)
            else:
                cos, sin = _rotary_angles(S, head_dim)
            q = _apply_rotary(q, cos, sin)
            k = _apply_rotary(k, cos, sin)
        if cfg.ring_axis:
            from ..parallel.ring_attention import ring_attention
            out = ring_attention(q, k, v, mask, axis_name=cfg.ring_axis)
        elif cfg.flash_min_seq and S >= cfg.flash_min_seq:
            from ..ops.flash_attention import flash_attention
            out = flash_attention(q, k, v, mask)
        else:
            # short buckets: the plain masked-softmax math, shared with
            # the kernel's fallback so the three attention paths cannot
            # drift (ops/flash_attention._mha_jnp)
            from ..ops.flash_attention import _mha_jnp
            out = _mha_jnp(q, k, v, mask)
        out = out.reshape(B, S, cfg.hidden)
        return _dense(cfg, cfg.hidden, "out")(out)


class Mlp(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.variant == "nomic":
            gate = _dense(cfg, cfg.mlp_dim, "gate")(x)
            up = _dense(cfg, cfg.mlp_dim, "up")(x)
            h = nn.silu(gate) * up
        else:
            h = nn.gelu(_dense(cfg, cfg.mlp_dim, "up")(x))
        return _dense(cfg, cfg.hidden, "down")(h)


class EncoderLayer(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        # post-LN (BERT family): sublayer -> residual -> LN
        a = SelfAttention(cfg, name="attn")(x, mask)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_attn")(x + a)
        m = Mlp(cfg, name="mlp")(x)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_mlp")(x + m)
        return x


class Encoder(nn.Module):
    """Bidirectional encoder producing L2-normalised mean-pooled
    embeddings (the reference forces mean pooling: splinference.cpp:435)."""
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, token_ids, attn_mask):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.hidden, dtype=cfg.dtype,
                     name="tok_emb")(token_ids)
        if cfg.variant == "bert":
            pos = jnp.arange(token_ids.shape[1])[None, :]
            if cfg.ring_axis:   # local chunk -> global absolute positions
                sp = jax.lax.axis_size(cfg.ring_axis)
                if sp * token_ids.shape[1] > cfg.max_len:
                    raise ValueError(
                        f"bert variant: global sequence {sp}x"
                        f"{token_ids.shape[1]} exceeds the learned position "
                        f"table max_len={cfg.max_len}; raise max_len or use "
                        "the rotary 'nomic' variant for long context")
                pos = pos + jax.lax.axis_index(cfg.ring_axis) * pos.shape[1]
            x = x + nn.Embed(cfg.max_len, cfg.hidden, dtype=cfg.dtype,
                             name="pos_emb")(pos)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ln_emb")(x)
        for i in range(cfg.layers):
            x = EncoderLayer(cfg, name=f"layer_{i}")(x, attn_mask)
        return pool_normalize(cfg, x, attn_mask,
                              ring_axis=cfg.ring_axis)


def pool_normalize(cfg: EncoderConfig, x, attn_mask, *,
                   ring_axis: str | None = None):
    """The encoder's output head: masked mean pool in f32 (stable
    norms), matryoshka truncation to out_dim, L2 normalize.  Shared by
    Encoder.__call__ and the pipeline-parallel forward
    (parallel/pipeline.py) so the tail cannot drift between them.
    x: (..., S, hidden); attn_mask: (..., S)."""
    xf = x.astype(jnp.float32)
    m = attn_mask.astype(jnp.float32)[..., None]
    sums = (xf * m).sum(axis=-2)
    counts = m.sum(axis=-2)
    if ring_axis:
        # pool over the full sequence: reduce across shards so every
        # sp member holds the replicated global embedding
        sums = jax.lax.psum(sums, ring_axis)
        counts = jax.lax.psum(counts, ring_axis)
    pooled = sums / jnp.maximum(counts, 1.0)
    pooled = pooled[..., : cfg.out_dim]            # matryoshka truncation
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


class PendingEmbeddings:
    """An encode dispatched but not yet forced.  jax's async dispatch
    means the TPU computes (and the transfers fly) while the
    host does other work; materialize() blocks for the result.  The
    batch may have been padded — only the first `n` rows are real."""

    __slots__ = ("_out", "n", "_mark")

    def __init__(self, out, n: int, mark=None):
        self._out = out
        self.n = n
        self._mark = mark             # devtime DispatchMark: closed at
        # materialize — the collect point that already exists

    def is_ready(self) -> bool:
        """True when materialize() will not block: the device compute
        (and any transfer) behind this future has completed, or the
        result is already host memory.  The commit pipeline uses this
        to resolve futures in COMPLETION order — commit whatever is
        done, keep staging while the rest computes."""
        out = self._out
        if isinstance(out, np.ndarray):
            return True
        try:
            return bool(out.is_ready())
        except AttributeError:
            # unknown future type: claim in-flight so callers account
            # the materialize as a (possibly) blocking wait
            return False

    def materialize(self) -> np.ndarray:
        # fetch in the model's wire dtype (f16 halves, int8 quarters
        # the device->host bytes on the commit path), hand f32 to
        # callers via the shared wire upcast (engine/resident.py —
        # ring slot views apply the identical conversion).
        from ..engine.resident import _wire_to_f32

        host = _wire_to_f32(np.asarray(self._out)[: self.n])
        mark, self._mark = self._mark, None
        if mark is not None:
            mark.close()
        return host


def _batch_pad(n: int) -> int:
    """Next power of two >= n: the batch dimension must come from a
    small fixed set or every odd-sized drain compiles a fresh XLA
    program (~10 s on TPU) on what should be the hot path."""
    return 1 << max(n - 1, 0).bit_length()


class EmbeddingModel:
    """Bucketed, jit-compiled embedding front end.

    Sequences are padded to the nearest bucket and batches to the next
    power of two, so XLA compiles a small, fixed set of programs (no
    recompiles on the hot path — SURVEY.md §7 "pre-compiled buckets").
    The attention mask is derived from the lengths INSIDE the program:
    the host ships (B, S) ids + (B,) lengths, not a second (B, S)
    boolean — half the transfer where host<->device bytes dominate
    small-batch latency.
    """

    def __init__(self, cfg: EncoderConfig, *, seed: int = 0,
                 buckets: tuple[int, ...] = (16, 32, 64, 128, 256, 512,
                                             1024, 2048),
                 params: Any = None, weights: str | None = None,
                 fetch_dtype: str | None = None):
        """fetch_dtype: None returns f32 embeddings from the device.
        "f16"/"bf16" cast the (already f32-pooled, L2-normalized)
        output on-device and fetch 2 bytes/component — half the
        device->host transfer on the vector-commit path, which is the
        serving bottleneck when host link bandwidth (not the MXU) caps
        throughput.  f16 is the better 2-byte wire: components of a
        unit vector lie in [-1, 1], where f16's 10 mantissa bits beat
        bf16's 7 (no range to protect).  "int8" fetches 1
        byte/component at a FIXED x127 scale (again: unit vectors need
        no per-vector scale row) — quarter the bytes, ~4e-3 rounding
        error, still ranking-equivalent for cosine retrieval.
        materialize() always hands the caller f32."""
        self.cfg = cfg
        self.module = Encoder(cfg)
        if fetch_dtype not in (None, "f16", "bf16", "int8"):
            raise ValueError(f"fetch_dtype {fetch_dtype!r} not in "
                             f"(None, 'f16', 'bf16', 'int8')")
        self.fetch_dtype = fetch_dtype
        # always include max_len itself: a long-context checkpoint whose
        # window exceeds the default bucket list must not have texts
        # between buckets[-1] and the window silently truncated.
        # Sorted + deduped: buckets_for's searchsorted requires
        # ascending order or it routes lengths to oversized buckets.
        self.buckets = tuple(sorted(
            {b for b in buckets if b < cfg.max_len} | {cfg.max_len}))
        self._buckets_arr = np.asarray(self.buckets, np.int64)
        if params is None and weights is not None:
            if weights.endswith(".gguf"):
                from .gguf import load_encoder_params
                params = load_encoder_params(weights, cfg)
            else:
                params = load_safetensors_params(weights, cfg)
        if params is None:
            dummy = (jnp.zeros((1, self.buckets[0]), jnp.int32),
                     jnp.ones((1, self.buckets[0]), jnp.bool_))
            params = self.module.init(jax.random.PRNGKey(seed), *dummy)
        elif cfg.weights_int8:
            # a float tree (checkpoint or caller-supplied) under a
            # weights_int8 module: convert kernels to {wq, wscale}
            # in place (idempotent — already-converted trees pass)
            from .quant import quantize_encoder_params
            params = quantize_encoder_params(params)
        self.params = params

        wire = {None: None, "f16": jnp.float16,
                "bf16": jnp.bfloat16, "int8": jnp.int8}[fetch_dtype]

        def fwd(params, token_ids, lengths):
            mask = jnp.arange(token_ids.shape[1])[None, :] < \
                lengths[:, None]
            out = self.module.apply(params, token_ids, mask)
            if wire is None:
                return out
            if wire == jnp.int8:
                return jnp.clip(jnp.round(out * 127.0),
                                -127.0, 127.0).astype(jnp.int8)
            return out.astype(wire)

        self._fwd = fwd               # the ring program re-traces THIS
        self._wire = wire             # (same graph -> same numerics)
        self._fn = DEVTIME.register("embedder.encode", jax.jit(fwd))
        self._ring_fn = None          # resident multi-batch program
        self._ring_pool: dict = {}    # (depth, B) -> spare out buffers

    def compile_count(self) -> int:
        """Distinct XLA programs compiled for the encode fn (one per
        (batch, bucket) shape) plus the resident ring program (one per
        (ring_depth, batch, bucket) shape — ring OCCUPANCY is a scalar
        operand, so varying it must never grow this count).  Obs
        surface: this riding the heartbeat makes a shape leak visible
        — a count still growing after warmup means some drain
        geometry escapes the bucket set and is paying jit compiles on
        the wake path."""
        try:
            fn = getattr(self._fn, "__wrapped__", self._fn)
            n = int(fn._cache_size())
            if self._ring_fn is not None:
                rf = getattr(self._ring_fn, "__wrapped__",
                             self._ring_fn)
                n += int(rf._cache_size())
            return n
        except Exception:      # private jax API: absence is not an error
            return -1

    def bucket_for(self, length: int) -> int:
        for b in self.buckets:
            if length <= b:
                return b
        return self.buckets[-1]

    def buckets_for(self, lengths: np.ndarray) -> np.ndarray:
        """Vectorised bucket_for: (N,) lengths -> (N,) bucket widths."""
        i = np.searchsorted(self._buckets_arr, lengths, side="left")
        return self._buckets_arr[np.minimum(i, len(self.buckets) - 1)]

    def encode_ids_async(self, token_ids: np.ndarray,
                         lengths: np.ndarray) -> PendingEmbeddings:
        """Dispatch an encode without forcing the result.  token_ids:
        (B, S) int32 with S a bucket width; lengths: (B,) valid counts.
        The batch is padded to a power of two (padded rows have
        length 0 and mean-pool to the zero vector; rows are
        independent, so real rows' numerics are unchanged)."""
        n = token_ids.shape[0]
        bpad = _batch_pad(n)
        if bpad != n:
            token_ids = np.concatenate(
                [token_ids, np.zeros((bpad - n, token_ids.shape[1]),
                                     token_ids.dtype)])
            lengths = np.concatenate(
                [lengths, np.zeros(bpad - n, lengths.dtype)])
        out = self._fn(self.params, jnp.asarray(token_ids),
                       jnp.asarray(lengths.astype(np.int32)))
        return PendingEmbeddings(out, n,
                                 mark=DEVTIME.take_mark(
                                     "embedder.encode"))

    def encode_ids(self, token_ids: np.ndarray,
                   lengths: np.ndarray) -> np.ndarray:
        """token_ids: (B, S) int32 already padded to a bucket length;
        lengths: (B,) valid lengths.  Returns (B, out_dim) float32."""
        return self.encode_ids_async(token_ids, lengths).materialize()

    # -- resident multi-batch ring -----------------------------------------

    def _ring_program(self):
        """The resident device loop: ONE dispatch services up to
        ring_depth pre-staged (B, S) batches — a lax.while_loop over
        the occupied ring slots, each iteration the SAME fwd graph the
        per-call path jits (identical numerics by construction).  The
        occupancy `n` is a scalar operand: one compiled program per
        (depth, B, S) shape serves every occupancy 1..depth, skipping
        empty slots outright.  The output ring is donated — callers
        recycle it through _ring_pool (RingResult release)."""
        if self._ring_fn is None:
            fwd = self._fwd

            def run(params, ids_ring, lens_ring, n, out_ring):
                def body(carry):
                    i, acc = carry
                    vecs = fwd(params, ids_ring[i], lens_ring[i])
                    acc = jax.lax.dynamic_update_index_in_dim(
                        acc, vecs.astype(acc.dtype), i, 0)
                    return i + 1, acc

                _, acc = jax.lax.while_loop(
                    lambda c: c[0] < n, body, (jnp.int32(0), out_ring))
                return acc

            self._ring_fn = DEVTIME.register(
                "embedder.ring", jax.jit(run, donate_argnums=(4,)))
        return self._ring_fn

    def encode_ring_async(self, ids_ring: np.ndarray,
                          lens_ring: np.ndarray, n_valid: int,
                          *, retry=None):
        """Dispatch ONE resident program over a host-fed ring of
        pre-staged batches.  ids_ring: (depth, B, S) int32 with S a
        bucket width and B a fixed (power-of-two) batch pad; lens_ring:
        (depth, B) valid counts (0 = padding row); n_valid: occupied
        slot count (slots past it are never computed).  Returns a
        RingResult whose slot(i, n) views satisfy the
        PendingEmbeddings contract — the whole ring fetches in one
        transfer on first materialize.  `retry` ((slot_i, n) -> f32
        rows) arms the per-slot fallback for collect-time device
        failures (async dispatch surfaces errors at the fetch)."""
        from ..engine.resident import RingResult
        from ..utils.faults import fault

        depth, B = int(ids_ring.shape[0]), int(ids_ring.shape[1])
        if not 1 <= n_valid <= depth:
            raise ValueError(f"n_valid {n_valid} outside 1..{depth}")
        fault("resident.ring_dispatch")
        pool = self._ring_pool.setdefault((depth, B), [])
        out = pool.pop() if pool else jnp.zeros(
            (depth, B, self.cfg.out_dim), self._wire or jnp.float32)
        res = self._ring_program()(
            self.params, jnp.asarray(ids_ring, jnp.int32),
            jnp.asarray(lens_ring.astype(np.int32)),
            jnp.int32(n_valid), out)
        return RingResult(res, n_valid, release=pool.append,
                          retry=retry,
                          mark=DEVTIME.take_mark("embedder.ring"))

    def warmup_ring(self, depth: int, batch: int,
                    buckets: tuple[int, ...] | None = None) -> None:
        """Pre-compile the resident ring program for each bucket at
        the serving (depth, batch-pad) geometry.  One probe per bucket
        at occupancy 1 suffices — occupancy is an operand, so a drain
        at ANY occupancy reuses the same program (compile_count stays
        flat; tests pin it)."""
        if depth <= 1:
            return
        bpad = _batch_pad(batch)
        with DEVTIME.warmup_phase():
            for b in buckets or self.buckets:
                ids = np.zeros((depth, bpad, b), np.int32)
                lens = np.zeros((depth, bpad), np.int32)
                lens[0, :] = b
                self.encode_ring_async(ids, lens, 1).materialize_host()

    def warmup(self, batch_sizes: tuple[int, ...] = (8,)) -> None:
        """Pre-compile each (batch, bucket) program off the hot path."""
        with DEVTIME.warmup_phase():
            for bsz in batch_sizes:
                for b in self.buckets:
                    ids = np.zeros((bsz, b), np.int32)
                    lens = np.full((bsz,), b, np.int32)
                    self.encode_ids(ids, lens)


def read_safetensors_f32(path: str) -> dict[str, np.ndarray]:
    """Read every tensor in a safetensors file as float32 numpy.

    Real HF exports ship bf16/fp16 (bf16 is the llama default), which the
    numpy framework of safetensors cannot represent — so tensors load
    through the flax framework (jax handles bfloat16 natively) and are
    cast to float32 masters here.
    """
    from safetensors import safe_open

    out: dict[str, np.ndarray] = {}
    with safe_open(path, framework="flax") as f:
        for k in f.keys():
            t = f.get_tensor(k)
            out[k] = np.asarray(jnp.asarray(t, jnp.float32))
    return out


def _hf_layer_names(cfg: EncoderConfig, i: int) -> dict[str, list[str]]:
    """Logical slot -> candidate HF tensor names for layer i, covering both
    checkpoint families this encoder loads:

      - "nomic" (nomic-ai/nomic-embed-text-v1.5 style nomic_bert naming:
        fused Wqkv, SwiGLU fc11/fc12/fc2, norm1/norm2);
      - "bert" (classic bert-base naming: split query/key/value,
        intermediate/output dense, attention.output.LayerNorm).

    Each logical slot lists aliases in priority order so minor naming
    drift across checkpoint exports still resolves.
    """
    n = f"encoder.layers.{i}"          # nomic family
    b = f"encoder.layer.{i}"           # bert family
    return {
        "qkv.weight": [f"{n}.attn.Wqkv.weight", f"{b}.attn.Wqkv.weight"],
        "qkv.bias": [f"{n}.attn.Wqkv.bias", f"{b}.attn.Wqkv.bias"],
        "q.weight": [f"{b}.attention.self.query.weight"],
        "q.bias": [f"{b}.attention.self.query.bias"],
        "k.weight": [f"{b}.attention.self.key.weight"],
        "k.bias": [f"{b}.attention.self.key.bias"],
        "v.weight": [f"{b}.attention.self.value.weight"],
        "v.bias": [f"{b}.attention.self.value.bias"],
        "attn_out.weight": [f"{n}.attn.out_proj.weight",
                            f"{b}.attention.output.dense.weight"],
        "attn_out.bias": [f"{n}.attn.out_proj.bias",
                          f"{b}.attention.output.dense.bias"],
        "ln_attn.weight": [f"{n}.norm1.weight",
                           f"{b}.attention.output.LayerNorm.weight"],
        "ln_attn.bias": [f"{n}.norm1.bias",
                         f"{b}.attention.output.LayerNorm.bias"],
        "gate.weight": [f"{n}.mlp.fc11.weight"],
        "gate.bias": [f"{n}.mlp.fc11.bias"],
        "up.weight": [f"{n}.mlp.fc12.weight", f"{b}.intermediate.dense.weight"],
        "up.bias": [f"{n}.mlp.fc12.bias", f"{b}.intermediate.dense.bias"],
        "down.weight": [f"{n}.mlp.fc2.weight", f"{b}.output.dense.weight"],
        "down.bias": [f"{n}.mlp.fc2.bias", f"{b}.output.dense.bias"],
        "ln_mlp.weight": [f"{n}.norm2.weight",
                          f"{b}.output.LayerNorm.weight"],
        "ln_mlp.bias": [f"{n}.norm2.bias", f"{b}.output.LayerNorm.bias"],
    }


_HF_TOP_NAMES = {
    "tok_emb": ["embeddings.word_embeddings.weight",
                "bert.embeddings.word_embeddings.weight"],
    "pos_emb": ["embeddings.position_embeddings.weight",
                "bert.embeddings.position_embeddings.weight"],
    "ln_emb.weight": ["emb_ln.weight", "embeddings.LayerNorm.weight",
                      "bert.embeddings.LayerNorm.weight"],
    "ln_emb.bias": ["emb_ln.bias", "embeddings.LayerNorm.bias",
                    "bert.embeddings.LayerNorm.bias"],
}


def load_safetensors_params(path: str, cfg: EncoderConfig):
    """Map a HF safetensors checkpoint onto this encoder's flax tree.

    Handles the two checkpoint families the config declares (`variant`):
    nomic_bert naming (fused attn.Wqkv, SwiGLU fc11/fc12/fc2 — the
    nomic-embed-text-v1.5 export) and classic bert-base naming (split
    query/key/value, GELU intermediate/output).  torch Linear weights are
    (out, in) and are transposed into flax (in, out) kernels; split
    q/k/v checkpoints are fused into the qkv Dense along the output axis
    in q,k,v order (the same packing nomic's Wqkv uses).

    Validated in-tree against synthetic checkpoints exported by
    `export_safetensors_params` (tests/test_model.py); name parity against
    upstream exports cannot be re-verified in this offline image, so
    unresolved tensors fail loudly with the full candidate list.
    """
    tensors = read_safetensors_f32(path)

    def take(aliases: list[str], *, required: bool = True):
        for a in aliases:
            if a in tensors:
                return np.asarray(tensors[a])
        if required:
            raise KeyError(
                f"checkpoint {path} has none of {aliases}; present keys "
                f"include {sorted(tensors)[:8]}...")
        return None

    def linear(prefix_names, bias_names):
        w = take(prefix_names)
        bvec = take(bias_names)
        return {"kernel": w.T.astype(np.float32),
                "bias": bvec.astype(np.float32)}

    p: dict[str, Any] = {}
    tok = take(_HF_TOP_NAMES["tok_emb"])
    if tok.shape[0] < cfg.vocab_size:
        raise ValueError(
            f"checkpoint vocab {tok.shape[0]} < cfg.vocab_size "
            f"{cfg.vocab_size} — out-of-range ids would gather-clamp "
            "silently; shrink cfg.vocab_size to the checkpoint's")
    p["tok_emb"] = {"embedding": tok[:cfg.vocab_size].astype(np.float32)}
    if cfg.variant == "bert":
        pos = take(_HF_TOP_NAMES["pos_emb"])
        if pos.shape[0] < cfg.max_len:
            raise ValueError(
                f"checkpoint has {pos.shape[0]} position rows < "
                f"cfg.max_len {cfg.max_len} — positions past "
                f"{pos.shape[0] - 1} would clamp silently; lower "
                "cfg.max_len to the checkpoint's trained length")
        p["pos_emb"] = {"embedding": pos[:cfg.max_len].astype(np.float32)}
    p["ln_emb"] = {"scale": take(_HF_TOP_NAMES["ln_emb.weight"]),
                   "bias": take(_HF_TOP_NAMES["ln_emb.bias"])}

    for i in range(cfg.layers):
        names = _hf_layer_names(cfg, i)
        layer: dict[str, Any] = {}
        fused_w = take(names["qkv.weight"], required=False)
        if fused_w is not None:
            qkv = {"kernel": fused_w.T.astype(np.float32),
                   "bias": take(names["qkv.bias"]).astype(np.float32)}
        else:
            qw, kw, vw = (take(names["q.weight"]), take(names["k.weight"]),
                          take(names["v.weight"]))
            qb, kb, vb = (take(names["q.bias"]), take(names["k.bias"]),
                          take(names["v.bias"]))
            qkv = {"kernel": np.concatenate(
                       [qw.T, kw.T, vw.T], axis=1).astype(np.float32),
                   "bias": np.concatenate([qb, kb, vb]).astype(np.float32)}
        layer["attn"] = {
            "qkv": qkv,
            "out": linear(names["attn_out.weight"], names["attn_out.bias"]),
        }
        layer["ln_attn"] = {"scale": take(names["ln_attn.weight"]),
                            "bias": take(names["ln_attn.bias"])}
        mlp: dict[str, Any] = {
            "up": linear(names["up.weight"], names["up.bias"]),
            "down": linear(names["down.weight"], names["down.bias"]),
        }
        if cfg.variant == "nomic":
            mlp["gate"] = linear(names["gate.weight"], names["gate.bias"])
        layer["mlp"] = mlp
        layer["ln_mlp"] = {"scale": take(names["ln_mlp.weight"]),
                           "bias": take(names["ln_mlp.bias"])}
        p[f"layer_{i}"] = layer

    # params stay float32 masters; activation dtype is cfg.dtype at apply
    return {"params": jax.tree.map(
        lambda x: jnp.asarray(x, jnp.float32), p)}


def export_safetensors_params(params, cfg: EncoderConfig, path: str,
                              *, family: str | None = None) -> None:
    """Write the flax tree as a HF-style safetensors checkpoint (inverse of
    load_safetensors_params; used by the round-trip tests and for interop
    with torch tooling).  family defaults to cfg.variant and must match the
    tree's architecture — a nomic tree has a gate the bert naming cannot
    carry."""
    from safetensors.numpy import save_file

    family = family or cfg.variant
    p = jax.tree.map(lambda x: np.asarray(x, np.float32), params["params"])
    out: dict[str, np.ndarray] = {}

    def put_linear(wname: str, bname: str, leaf) -> None:
        out[wname] = leaf["kernel"].T.copy()
        out[bname] = leaf["bias"].copy()

    out["embeddings.word_embeddings.weight"] = p["tok_emb"]["embedding"]
    if cfg.variant == "bert":
        out["embeddings.position_embeddings.weight"] = \
            p["pos_emb"]["embedding"]
    if family == "nomic":
        out["emb_ln.weight"] = p["ln_emb"]["scale"]
        out["emb_ln.bias"] = p["ln_emb"]["bias"]
    else:
        out["embeddings.LayerNorm.weight"] = p["ln_emb"]["scale"]
        out["embeddings.LayerNorm.bias"] = p["ln_emb"]["bias"]

    for i in range(cfg.layers):
        layer = p[f"layer_{i}"]
        if family == "nomic":
            n = f"encoder.layers.{i}"
            put_linear(f"{n}.attn.Wqkv.weight", f"{n}.attn.Wqkv.bias",
                       layer["attn"]["qkv"])
            put_linear(f"{n}.attn.out_proj.weight",
                       f"{n}.attn.out_proj.bias", layer["attn"]["out"])
            out[f"{n}.norm1.weight"] = layer["ln_attn"]["scale"]
            out[f"{n}.norm1.bias"] = layer["ln_attn"]["bias"]
            put_linear(f"{n}.mlp.fc11.weight", f"{n}.mlp.fc11.bias",
                       layer["mlp"]["gate"])
            put_linear(f"{n}.mlp.fc12.weight", f"{n}.mlp.fc12.bias",
                       layer["mlp"]["up"])
            put_linear(f"{n}.mlp.fc2.weight", f"{n}.mlp.fc2.bias",
                       layer["mlp"]["down"])
            out[f"{n}.norm2.weight"] = layer["ln_mlp"]["scale"]
            out[f"{n}.norm2.bias"] = layer["ln_mlp"]["bias"]
        else:
            b = f"encoder.layer.{i}"
            kern = layer["attn"]["qkv"]["kernel"]
            bias = layer["attn"]["qkv"]["bias"]
            h = cfg.hidden
            for j, part in enumerate(("query", "key", "value")):
                out[f"{b}.attention.self.{part}.weight"] = \
                    kern[:, j * h:(j + 1) * h].T.copy()
                out[f"{b}.attention.self.{part}.bias"] = \
                    bias[j * h:(j + 1) * h].copy()
            put_linear(f"{b}.attention.output.dense.weight",
                       f"{b}.attention.output.dense.bias",
                       layer["attn"]["out"])
            out[f"{b}.attention.output.LayerNorm.weight"] = \
                layer["ln_attn"]["scale"]
            out[f"{b}.attention.output.LayerNorm.bias"] = \
                layer["ln_attn"]["bias"]
            put_linear(f"{b}.intermediate.dense.weight",
                       f"{b}.intermediate.dense.bias", layer["mlp"]["up"])
            put_linear(f"{b}.output.dense.weight", f"{b}.output.dense.bias",
                       layer["mlp"]["down"])
            out[f"{b}.output.LayerNorm.weight"] = layer["ln_mlp"]["scale"]
            out[f"{b}.output.LayerNorm.bias"] = layer["ln_mlp"]["bias"]

    save_file(out, path)
