"""Latent-attention (MLA) + sparse shared-expert MoE decoder block, as
the DeepSeek-V3 family of public configs describes it (openPangu-Ultra-
MoE among them), served through the completion daemon's paged lane as
ONE CHIP'S SHARE of an expert-parallel deployment.

The layer, from the published keys (x: hidden; all matrices without
bias; RMSNorm eps `rms_norm_eps`):

    h = x + N2(Attn(N1(x)))          sandwich_norm: four norms a layer
    y = h + N4(FFN(N3(h)))           (without it: h = x + Attn(N1(x)) ...)

    Attn:  cq = Nq(x W_DQ)                        (q_lora_rank)
           q_h = cq W_UQ_h = [q_nope_h | q_rope_h]   (nope | rope) a head
           [c | k_r] = x W_DKV;  c = Nkv(c)       (kv_lora_rank | rope)
           RoPE(rope_theta) on q_rope_h and on the ONE shared k_r
           [k_nope_h | v_h] = c W_UKV_h           (nope | v) a head
           score_h = (q_nope_h.k_nope_h + q_rope_h.k_r) / sqrt(nope+rope)
           o = concat_h(softmax(score_h) v_h) W_O
    FFN:   layers 0..first_k_dense_replace-1: SwiGLU(intermediate_size);
           after them: shared SwiGLU expert + sum of the top-k routed
           SwiGLU experts (models/moe.sparse_moe: float32 router over
           ALL n_routed_experts, sigmoid scores, normalised over the
           selection, times routed_scaling_factor).

**The cache holds `[c | RoPE(k_r)]` a token a layer and nothing else**
(`LatentMoeConfig.page_layout`: one pool of (n_blocks, kv_rank + rope,
page) a layer — a token a column, the layout the chip gives a page
anyway, ops/latent_attention.py).  Prefill expands k_nope and v from the latents of its
own bucket and attends blockwise; decode and the suffix prefill of a
prefix-cache hit attend IN THE LATENT SPACE over the paged pool
(ops/latent_attention.py): W_UK folds into the query, W_UV into the
output, the pages are never expanded in HBM.

The share: `experts_first` / `experts_held` say which routed experts
live here (the router keeps its published width and top-k, the layer
computes its own experts' part plus the shared expert and leaves the
rest out), `vocab_first` / `vocab_size` which rows of the vocabulary,
`layers` / `dense_layers` how deep the stack kept here is.  Nothing
stands in for the absent chips.

Departures from the published model, each also in the configuration
file that uses it: the multi-token-prediction module
(`num_nextn_predict_layers`) is not served; the router's score
function is not in the config and is taken as sigmoid without
group-limited routing or a selection bias (the key set's family
convention); the RoPE pairing is this repo's split-half
`_apply_rotary`.

WEIGHTS are made from a seed, tensor by tensor, directly in their
resident dtype (matrices and the embedding bfloat16; norm scales and
the router float32) — no float32 tree ever exists.  The recipe
(`seed_tensor`) is written so that a plain reference can make the
same values without importing this module:

    key   = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                               zlib.crc32(name) & 0x7fffffff)
    bits  = jax.random.bits(key, shape, uint32)
    u     = (bits >> 8) * 2**-24                   in [0, 1), exact
    value = mean + (u - 0.5) * sqrt(12) * std      one f32 rounding
    value.astype(dtype)

with std = 1/sqrt(fan_in) for a matrix (fan_in = its first axis), 1
for the embedding, and mean 1, std 0.1 for a norm scale.  Names are
`layers.<i>.<tensor>` with i the layer's index in the kept stack, and
`layers.<i>.experts.<e>.<gate|up|down>` with e the expert's index in
the WHOLE model — so every share of a layer draws the same weights
the uncut layer would.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import DEVTIME, close_mark
from .decoder import (PageLayout, PagedKVCache, PendingChunk,
                      _sample_rows, join_one, sample_top_p)
from .encoder import _apply_rotary, _rotary_angles_at
from ..ops.latent_attention import latent_append, latent_paged_attention
from .moe import sparse_moe

DESCRIPTION_KEYS = frozenset(("architecture", "share", "seed", "note"))
SHARE_KEYS = frozenset(("layers", "dense_layers", "experts", "vocab"))


@dataclasses.dataclass(frozen=True)
class LatentMoeConfig:
    vocab_size: int               # rows of the vocabulary held here
    hidden: int
    layers: int                   # layers kept here
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_layers: int             # leading dense layers among `layers`
    dense_mlp_dim: int
    moe_mlp_dim: int
    n_routed_experts: int         # the router's width: ALL experts
    top_k: int
    experts_first: int = 0        # routed experts held here:
    experts_held: int | None = None   # first .. first + held - 1
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    score_fn: str = "sigmoid"
    sandwich_norm: bool = True
    vocab_first: int = 0
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    max_len: int = 2048
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held",
                               self.n_routed_experts - self.experts_first)
        if not 0 <= self.experts_first \
                <= self.experts_first + self.experts_held \
                <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.experts_first}..+{self.experts_held} "
                f"lie outside the router's {self.n_routed_experts}")
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError("dense_layers must lie in 0..layers")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (RoPE pairs)")

    @classmethod
    def tiny(cls, **kw) -> "LatentMoeConfig":
        """Small config for tests and CPU rehearsals."""
        kw = {"vocab_size": 512, "hidden": 64, "layers": 3, "heads": 4,
              "q_lora_rank": 32, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "dense_layers": 1, "dense_mlp_dim": 128,
              "moe_mlp_dim": 32, "n_routed_experts": 8, "top_k": 2,
              "routed_scaling_factor": 2.5, "max_len": 128, **kw}
        return cls(**kw)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def page_layout(self, page: int) -> PageLayout:
        """One pool a layer: a token's `[c | RoPE(k_r)]` row."""
        return PageLayout((("latent", (self.latent_width, page)),),
                          token_values=self.latent_width)


_REQUIRED = object()


@dataclasses.dataclass(frozen=True)
class Key:
    """How ONE published key of a family is honoured: the config
    field it fills (None: read by the family's own lines, or checked
    only), its default (absent: the key is required), the cast, and —
    where only some values can be served — `only` with the refusal."""
    field: str | None = None
    default: Any = _REQUIRED
    cast: Any = int
    only: tuple | None = None
    why: str = ""


def _served(why: str, value=False, cast=bool):
    """A key whose one served value is its default."""
    return Key(None, value, cast, only=(value,), why=why)


# Published key -> Key, a table a `model_type`.  Keys with field None
# and no `only` are structural: depth, experts, vocabulary and window
# are cut by the share, and the family's `finish` reads what is its
# own (layer kinds, grouped heads).
_COMMON = {
    "model_type": Key(None, cast=str),
    "hidden_act": _served("only the SwiGLU (silu) activation is served",
                          "silu", str),
    "attention_bias": _served("attention_bias is not served"),
    "tie_word_embeddings": _served("tied embeddings are not served"),
    "hidden_size": Key("hidden"),
    "num_attention_heads": Key("heads"),
    "num_key_value_heads": Key(None, None),
    "intermediate_size": Key("dense_mlp_dim"),
    "moe_intermediate_size": Key("moe_mlp_dim"),
    "rms_norm_eps": Key("rms_eps", 1e-5, float),
    "num_hidden_layers": Key(None),
    "vocab_size": Key(None),
}
# what the two latent-attention families share beside _COMMON
_LATENT = {
    "kv_lora_rank": Key("kv_lora_rank"),
    "qk_nope_head_dim": Key("qk_nope_head_dim"),
    "qk_rope_head_dim": Key("qk_rope_head_dim"),
    "v_head_dim": Key("v_head_dim"),
    "routed_scaling_factor": Key("routed_scaling_factor", 1.0, float),
    "first_k_dense_replace": Key(None, 0),
    "num_nextn_predict_layers": Key(None, 0),
}
_ONE_GROUP = dict(default=1, only=(1,),
                  why="group-limited routing is not served: n_group, "
                      "topk_group, num_expert_groups and "
                      "num_limited_groups must be 1")
_SHARED_EXPERT = dict(default=0, only=(0, 1),
                      why="0 or 1 shared expert is served")
FAMILIES: dict[str, dict] = {
    # the DeepSeek-V3 key set (openPangu-Ultra-MoE): models/mla.py
    "pangu_ultra_moe": {
        "window": "max_position_embeddings", "experts": "n_routed_experts",
        "keys": {
            **_COMMON, **_LATENT,
            "max_position_embeddings": Key(None, None),
            "q_lora_rank": Key("q_lora_rank"),
            "n_routed_experts": Key("n_routed_experts"),
            "num_experts_per_tok": Key("top_k"),
            "n_shared_experts": Key("n_shared_experts", **_SHARED_EXPERT),
            "norm_topk_prob": Key("norm_topk_prob", True, bool),
            "scoring_func": Key("score_fn", "sigmoid", str),
            "sandwich_norm": Key("sandwich_norm", False, bool),
            "rope_theta": Key("rope_base", 10000.0, float),
        }},
    # the Kimi-Linear key set: models/kda.py
    "kimi_linear": {
        "window": "model_max_length", "experts": "num_experts",
        "keys": {
            **_COMMON, **_LATENT,
            "model_max_length": Key(None, None),
            "head_dim": Key(None, None),     # the config's, unused by the block
            "q_lora_rank": Key(None, None, lambda v: v, only=(None,),
                               why="this family's queries have no "
                                   "low-rank step: q_lora_rank must be "
                                   "null"),
            "mla_use_nope": _served("the latent layers of this family "
                                    "carry no positions: mla_use_nope "
                                    "must be true", True),
            "rope_theta": Key(None, None, float),    # nothing rotates
            "rope_scaling": Key(None, None, lambda v: v, only=(None,),
                                why="rope_scaling is not served"),
            "linear_attn_config": Key(None, cast=dict),
            "num_experts": Key("n_routed_experts"),
            "num_experts_per_token": Key("top_k"),
            "num_shared_experts": Key("n_shared_experts",
                                      **_SHARED_EXPERT),
            "moe_renormalize": Key("norm_topk_prob", True, bool),
            "moe_router_activation_func": Key("score_fn", "sigmoid", str),
            "moe_layer_freq": Key(None, 1, only=(1,),
                                  why="every layer past the dense ones "
                                      "is an expert layer: "
                                      "moe_layer_freq must be 1"),
            # grouped top-k over ONE group of which ONE is kept is
            # plain top-k; anything else is another router
            "num_expert_group": Key(None, 1, only=(1,),
                                    why="group-limited routing is not "
                                        "served: num_expert_group 1"),
            "topk_group": Key(None, 1, only=(1,),
                              why="group-limited routing is not "
                                  "served: topk_group 1"),
            "use_grouped_topk": Key(None, False, bool),
        }},
    # the AFMoE key set (Trinity-Mini): models/afmoe.py
    "afmoe": {
        "window": "max_position_embeddings", "experts": "num_experts",
        "dense": "num_dense_layers",
        "keys": {
            **_COMMON,
            "max_position_embeddings": Key(None, None),
            "num_key_value_heads": Key("kv_heads"),
            "head_dim": Key("head_dim"),
            "layer_types": Key(None, cast=list),
            "global_attn_every_n_layers": Key(None),
            "sliding_window": Key("window"),
            "num_dense_layers": Key(None, 0),
            "mup_enabled": Key("mup", False, bool),
            "rope_theta": Key("rope_base", 10000.0, float),
            "rope_scaling": Key(None, None, lambda v: v, only=(None,),
                                why="rope_scaling is not served"),
            "num_experts": Key("n_routed_experts"),
            "num_experts_per_tok": Key("top_k"),
            "num_shared_experts": Key("n_shared_experts",
                                      **_SHARED_EXPERT),
            "route_norm": Key("norm_topk_prob", True, bool),
            "route_scale": Key("routed_scaling_factor", 1.0, float),
            "score_func": Key("score_fn", "sigmoid", str),
            "n_group": Key(None, **_ONE_GROUP),
            "topk_group": Key(None, **_ONE_GROUP),
            "num_expert_groups": Key(None, **_ONE_GROUP),
            "num_limited_groups": Key(None, **_ONE_GROUP),
            # training and implementation hints: accepted, unused
            "load_balance_coeff": Key(None, None, float),
            "use_grouped_mm": Key(None, None, bool),
        }},
    # the MiMo-V2-Flash key set: models/afmoe.py with another setting
    # a kind (window layers with a sink; heads and widths by kind)
    "mimo_v2_flash": {
        "window": "max_position_embeddings", "experts": "n_routed_experts",
        # the leading zeros of moe_layer_freq
        "dense": lambda arch: next(
            (i for i, f in enumerate(arch["moe_layer_freq"]) if f),
            len(arch["moe_layer_freq"])),
        "keys": {
            **{k: v for k, v in _COMMON.items() if k != "rms_norm_eps"},
            "layernorm_epsilon": Key("rms_eps", 1e-5, float),
            "max_position_embeddings": Key(None, None),
            "head_dim": Key("head_dim"),
            "v_head_dim": Key(None),
            "swa_num_attention_heads": Key(None),
            "swa_num_key_value_heads": Key(None),
            "swa_head_dim": Key(None),
            "swa_v_head_dim": Key(None),
            "hybrid_layer_pattern": Key(None, cast=list),
            "sliding_window": Key("window"),
            "sliding_window_size": Key(None),
            "attention_chunk_size": Key(None, None),
            "add_swa_attention_sink_bias": Key(None, False, bool),
            "add_full_attention_sink_bias": Key(None, False, bool),
            "attention_value_scale": Key("value_scale", 1.0, float),
            "partial_rotary_factor": Key(None, 1.0, float),
            "rope_theta": Key("rope_base", 10000.0, float),
            "swa_rope_theta": Key(None, 10000.0, float),
            "moe_layer_freq": Key(None, cast=list),
            "n_routed_experts": Key("n_routed_experts"),
            "num_experts_per_tok": Key("top_k"),
            # null in the published config: none
            "n_shared_experts": Key("n_shared_experts", 0,
                                    only=(None, 0, 1),
                                    why=_SHARED_EXPERT["why"]),
            "norm_topk_prob": Key("norm_topk_prob", True, bool),
            "scoring_func": Key("score_fn", "sigmoid", str),
            "routed_scaling_factor": Key("routed_scaling_factor", 1.0,
                                         float),
            "n_group": Key(None, **_ONE_GROUP),
            "topk_group": Key(None, **_ONE_GROUP),
            # the selection bias of noaux_tc is zero at seeded weights
            "topk_method": Key(None, "noaux_tc", str,
                               only=("noaux_tc", "greedy"),
                               why="topk_method noaux_tc (a selection "
                                   "bias, zero at seeded weights) or "
                                   "greedy is served"),
        }},
    # the LFM2-MoE key set: models/lfm2.py (gated short-convolution
    # layers with state slots beside grouped-query key/value pages)
    "lfm2_moe": {
        "window": "max_position_embeddings", "experts": "num_experts",
        "dense": "num_dense_layers",
        "keys": {
            **{k: v for k, v in _COMMON.items() if k != "rms_norm_eps"},
            "norm_eps": Key("rms_eps", 1e-5, float),
            "max_position_embeddings": Key(None, None),
            "num_key_value_heads": Key("kv_heads"),
            "layer_types": Key(None, cast=list),
            "conv_L_cache": Key("conv_kernel"),
            "conv_bias": _served("conv_bias is not served"),
            "num_dense_layers": Key(None, 0),
            "rope_parameters": Key(None, cast=dict),
            "num_experts": Key("n_routed_experts"),
            "num_experts_per_tok": Key("top_k"),
            "norm_topk_prob": Key("norm_topk_prob", True, bool),
            "routed_scaling_factor": Key("routed_scaling_factor", 1.0,
                                         float),
            "use_expert_bias": Key("expert_bias", False, bool),
        }},
    # the Keye-VL-2.0 language block's key set: models/afmoe.py with an
    # INDEXER in front of every (global) layer's attention — grouped
    # queries that attend the `sa_config.topk` keys it selects; every
    # layer an expert layer, softmax routing, no shared expert
    "KeyeVL2": {
        "window": "max_position_embeddings", "experts": "num_experts",
        "dense": lambda arch: 0,
        "keys": {
            **_COMMON,
            "max_position_embeddings": Key(None, None),
            "num_key_value_heads": Key("kv_heads"),
            "head_dim": Key("head_dim"),
            "rope_theta": Key("rope_base", 10000.0, float),
            "rope_scaling": Key(None, cast=dict),
            "sa_config": Key(None, cast=dict),
            "num_experts": Key("n_routed_experts"),
            "num_local_experts": Key(None),
            "num_experts_per_tok": Key("top_k"),
            "norm_topk_prob": Key("norm_topk_prob", True, bool),
            "decoder_sparse_step": Key(
                None, 1, only=(1,), why="every layer is an expert "
                "layer: decoder_sparse_step must be 1"),
            "mlp_only_layers": Key(None, cast=list),
            "use_sliding_window": _served("use_sliding_window is not "
                                          "served"),
            "sliding_window": Key(None, None, lambda v: v, only=(None,),
                                  why="sliding_window must be null"),
            "max_window_layers": Key(None, None),    # unused without one
        }},
    # the Nemotron-H key set: models/nemotron_h.py (ONE mixer a layer
    # by a pattern: Mamba-2 state-space layers with state slots, un-
    # gated relu^2 experts, grouped-query attention without positions)
    "nemotron_h": {
        "window": "max_position_embeddings", "experts": "n_routed_experts",
        "dense": lambda arch: 0,
        "keys": {
            **{k: v for k, v in _COMMON.items()
               if k not in ("hidden_act", "rms_norm_eps",
                            "moe_intermediate_size")},
            "norm_eps": Key("rms_eps", 1e-5, float),
            "layer_norm_epsilon": Key(None, None, float),
            "max_position_embeddings": Key(None, None),
            "num_key_value_heads": Key("kv_heads"),
            "head_dim": Key("head_dim"),
            "hybrid_override_pattern": Key(None, cast=str),
            "mamba_num_heads": Key("ssm_heads"),
            "mamba_head_dim": Key("ssm_head_dim"),
            "n_groups": Key("ssm_groups"),
            "ssm_state_size": Key("ssm_state"),
            "conv_kernel": Key("conv_kernel"),
            "chunk_size": Key("chunk"),
            # d_inner is mamba_num_heads x mamba_head_dim, whatever
            # `expand` says
            "expand": Key(None, None),
            "time_step_min": Key("time_step_min", 0.001, float),
            "time_step_max": Key("time_step_max", 0.1, float),
            "time_step_floor": Key("time_step_floor", 1e-4, float),
            "mamba_hidden_act": _served("only mamba_hidden_act silu is "
                                        "served", "silu", str),
            "mlp_hidden_act": _served("only the un-gated relu2 experts "
                                      "are served: mlp_hidden_act relu2",
                                      "relu2", str),
            "use_conv_bias": _served("the convolution carries its bias: "
                                     "use_conv_bias must be true", True),
            "mamba_proj_bias": _served("mamba_proj_bias is not served"),
            "mlp_bias": _served("mlp_bias is not served"),
            "use_bias": _served("use_bias is not served"),
            "moe_intermediate_size": Key("moe_mlp_dim"),
            "moe_shared_expert_intermediate_size": Key("shared_mlp_dim"),
            "n_routed_experts": Key("n_routed_experts"),
            "num_experts_per_tok": Key("top_k"),
            "n_shared_experts": Key("n_shared_experts", **_SHARED_EXPERT),
            "norm_topk_prob": Key("norm_topk_prob", True, bool),
            "routed_scaling_factor": Key("routed_scaling_factor", 1.0,
                                         float),
            "n_group": Key(None, **_ONE_GROUP),
            "topk_group": Key(None, **_ONE_GROUP),
            "sliding_window": Key(None, None, lambda v: v, only=(None,),
                                  why="sliding_window must be null"),
            # vestigial in this family: its attention applies no
            # rotary embedding
            "rope_theta": Key(None, None, float),
            "partial_rotary_factor": Key(None, None, float),
            # training and implementation hints: accepted, unused (the
            # served stream is float32 whatever residual_in_fp32 says)
            "rescale_prenorm_residual": Key(None, None, bool),
            "residual_in_fp32": Key(None, None, bool),
            "use_mamba_kernels": Key(None, None, bool),
            "num_logits_to_keep": Key(None, None),
        }},
}
LAYER_TYPES = {"sliding_attention": "window", "full_attention": "full"}
SA_CONFIG_KEYS = frozenset(("indexer_head_dim", "indexer_num_heads",
                            "indexer_num_kv_heads", "kv_chunk_size",
                            "q_chunk_size", "topk"))
LINEAR_ATTN_KEYS = frozenset(("full_attn_layers", "kda_layers", "head_dim",
                              "num_heads", "short_conv_kernel_size"))


def _no_grouped_heads(arch, heads: int) -> None:
    if int(arch.get("num_key_value_heads") or heads) != heads:
        raise ValueError("latent attention has no grouped kv heads: "
                         "num_key_value_heads must equal "
                         "num_attention_heads")


def _finish_latent(arch, fields, path):
    _no_grouped_heads(arch, fields["heads"])
    return LatentMoeConfig(**fields)


def _finish_hybrid(arch, fields, path):
    from .kda import HybridMoeConfig
    _no_grouped_heads(arch, fields["heads"])
    lin = arch["linear_attn_config"]
    extra = set(lin) - LINEAR_ATTN_KEYS
    if extra or set(lin) != LINEAR_ATTN_KEYS:
        raise ValueError(
            f"{path}: linear_attn_config must hold exactly "
            f"{sorted(LINEAR_ATTN_KEYS)} (unknown: {sorted(extra)})")
    n_layers = int(arch["num_hidden_layers"])
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda & full or kda | full != set(range(1, n_layers + 1)):
        raise ValueError(
            f"{path}: kda_layers and full_attn_layers must split layers "
            f"1..{n_layers} between them")
    # the lists count layers from 1; the share keeps the first `layers`
    kinds = tuple("kda" if i + 1 in kda else "mla"
                  for i in range(fields.pop("layers")))
    return HybridMoeConfig(
        kinds=kinds, model_layers=n_layers,
        kda_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        conv_kernel=int(lin["short_conv_kernel_size"]), **fields)


def _finish_window(arch, fields, path):
    from .afmoe import WindowMoeConfig
    n_layers = int(arch["num_hidden_layers"])
    types, every = arch["layer_types"], int(
        arch["global_attn_every_n_layers"])
    if len(types) != n_layers or set(types) - set(LAYER_TYPES) or any(
            (t == "full_attention") != ((i + 1) % every == 0)
            for i, t in enumerate(types)):
        raise ValueError(
            f"{path}: layer_types must name {n_layers} layers, each "
            f"among {sorted(LAYER_TYPES)}, every "
            f"global_attn_every_n_layers-th ({every}) full_attention")
    # the share keeps the first `layers`
    kinds = tuple(LAYER_TYPES[t] for t in types[:fields.pop("layers")])
    return WindowMoeConfig(kinds=kinds, model_layers=n_layers, **fields)


def _finish_sink_window(arch, fields, path):
    from .afmoe import AttnKind, WindowMoeConfig
    n_layers = int(arch["num_hidden_layers"])
    pattern, freq = arch["hybrid_layer_pattern"], arch["moe_layer_freq"]
    dense = FAMILIES["mimo_v2_flash"]["dense"](arch)
    if len(pattern) != n_layers or set(pattern) - {0, 1} \
            or len(freq) != n_layers or any(
                f != int(i >= dense) for i, f in enumerate(freq)):
        raise ValueError(
            f"{path}: hybrid_layer_pattern (0 = global, 1 = sliding "
            f"window) and moe_layer_freq (leading dense layers 0, then "
            f"1) must each name {n_layers} layers")
    if int(arch["sliding_window_size"]) != fields["window"] or int(
            arch.get("attention_chunk_size") or fields["window"]) \
            != fields["window"]:
        raise ValueError(
            f"{path}: sliding_window_size and attention_chunk_size "
            "must equal sliding_window")
    if int(arch["swa_num_attention_heads"]) != fields["heads"]:
        raise ValueError(
            f"{path}: swa_num_attention_heads must equal "
            "num_attention_heads (one query head count is served)")
    # null in the published config: no shared expert, no scaling
    fields["n_shared_experts"] = int(fields["n_shared_experts"] or 0)
    fields["routed_scaling_factor"] = float(
        fields["routed_scaling_factor"] or 1.0)
    factor = float(arch.get("partial_rotary_factor", 1.0))

    def kind(kv, d, dv, base, window, sink):
        # keys whose width is no whole number of 128-lane tiles are
        # kept a token a column (ops/paged_attention)
        return AttnKind(int(kv), int(d), int(dv),
                        int(int(d) * factor) // 2 * 2, float(base),
                        int(window), bool(sink), int(d) % 128 != 0)
    kinds = (
        ("window", kind(arch["swa_num_key_value_heads"],
                        arch["swa_head_dim"], arch["swa_v_head_dim"],
                        arch.get("swa_rope_theta", 10000.0),
                        fields["window"],
                        arch.get("add_swa_attention_sink_bias", False))),
        ("full", kind(arch["num_key_value_heads"], arch["head_dim"],
                      arch["v_head_dim"], fields["rope_base"], 0,
                      arch.get("add_full_attention_sink_bias", False))))
    return WindowMoeConfig(
        kinds=tuple("window" if p else "full"
                    for p in pattern[:fields.pop("layers")]),
        model_layers=n_layers,
        kv_heads=int(arch["num_key_value_heads"]), attn_kinds=kinds,
        out_gate=False, qk_norm=False, sandwich_norm=False, mup=False,
        **fields)


def _finish_conv(arch, fields, path):
    from .lfm2 import ConvMoeConfig
    n_layers = int(arch["num_hidden_layers"])
    types, rope = arch["layer_types"], arch["rope_parameters"]
    if len(types) != n_layers or set(types) - {"conv", "full_attention"}:
        raise ValueError(
            f"{path}: layer_types must name {n_layers} layers, each "
            "'conv' or 'full_attention'")
    if set(rope) - {"rope_theta", "rope_type"} \
            or rope.get("rope_type", "default") != "default":
        raise ValueError(f"{path}: rope_parameters must hold rope_theta "
                         "and rope_type 'default' (rope scaling is not "
                         "served)")
    # the share keeps its `dense_layers` as the LAST of the model's
    # leading dense layers, then the layers after them: leading dense
    # layers beyond the share's count are passed over, not relabelled
    layers = fields.pop("layers")
    skip = int(arch.get("num_dense_layers", 0)) - fields["dense_layers"]
    if skip + layers > n_layers:
        raise ValueError("the share keeps more layers than the model has")
    return ConvMoeConfig(
        kinds=tuple("conv" if t == "conv" else "full"
                    for t in types[skip: skip + layers]),
        model_layers=n_layers, head_dim=fields["hidden"] // fields["heads"],
        rope_base=float(rope.get("rope_theta", 10000.0)), **fields)


def _finish_indexed(arch, fields, path):
    from .afmoe import AttnKind, Indexer, WindowMoeConfig
    sa, rope = arch["sa_config"], arch["rope_scaling"]
    if set(sa) != SA_CONFIG_KEYS or int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError(
            f"{path}: sa_config must hold exactly {sorted(SA_CONFIG_KEYS)}"
            " with indexer_num_kv_heads 1 (the indexer's keys are one "
            "head a token; q_chunk_size / kv_chunk_size are the "
            "published kernels' tiling and change no value)")
    d = fields["head_dim"]
    # a text token's three position ids are equal, so the sections of
    # a multimodal rotary embedding are one plain rotation
    if set(rope) - {"mrope_section", "rope_type", "type"} \
            or {rope.get("rope_type", "default"),
                rope.get("type", "default")} != {"default"} \
            or sum(rope.get("mrope_section", [d // 2])) != d // 2:
        raise ValueError(
            f"{path}: rope_scaling must be the default type, its "
            f"mrope_section adding up to head_dim / 2 = {d // 2}")
    if arch["mlp_only_layers"] or int(arch["num_local_experts"]) \
            != fields["n_routed_experts"]:
        raise ValueError(f"{path}: mlp_only_layers must be empty and "
                         "num_local_experts equal num_experts")
    kind = AttnKind(fields["kv_heads"], d, d, d, fields["rope_base"], 0)
    return WindowMoeConfig(
        kinds=("full",) * fields.pop("layers"), window=0,
        model_layers=int(arch["num_hidden_layers"]),
        attn_kinds=(("full", kind),), n_shared_experts=0,
        score_fn="softmax", out_gate=False, qk_norm=True,
        sandwich_norm=False, mup=False,
        indexer=Indexer(int(sa["indexer_num_heads"]),
                        int(sa["indexer_head_dim"]), int(sa["topk"])),
        **fields)


def _finish_ssm(arch, fields, path):
    from .nemotron_h import PATTERN, SsmMoeConfig
    n_layers = int(arch["num_hidden_layers"])
    pattern = arch["hybrid_override_pattern"]
    if len(pattern) != n_layers or set(pattern) - set(PATTERN):
        raise ValueError(
            f"{path}: hybrid_override_pattern must name {n_layers} "
            f"layers, each among {sorted(PATTERN)} (M: Mamba-2, E: "
            "experts, *: attention; a dense '-' layer is not served)")
    eps = arch.get("layer_norm_epsilon")
    if eps is not None and float(eps) != fields["rms_eps"]:
        raise ValueError(f"{path}: layer_norm_epsilon and norm_eps "
                         "must agree")
    fields.pop("dense_mlp_dim")       # the `-` layers' width: none kept
    fields.pop("dense_layers")
    # the share keeps the first `layers`
    return SsmMoeConfig(
        kinds=tuple(PATTERN[c] for c in pattern[:fields.pop("layers")]),
        model_layers=n_layers, **fields)


FAMILIES["pangu_ultra_moe"]["finish"] = _finish_latent
FAMILIES["kimi_linear"]["finish"] = _finish_hybrid
FAMILIES["afmoe"]["finish"] = _finish_window
FAMILIES["mimo_v2_flash"]["finish"] = _finish_sink_window
FAMILIES["lfm2_moe"]["finish"] = _finish_conv
FAMILIES["KeyeVL2"]["finish"] = _finish_indexed
FAMILIES["nemotron_h"]["finish"] = _finish_ssm


def load_model_description(path: str, *, max_len: int | None = None):
    """A model description file -> (config, seed): a LatentMoeConfig,
    a models/kda.HybridMoeConfig, a models/afmoe.WindowMoeConfig
    (AFMoE's setting, MiMo-V2-Flash's or Keye-VL-2.0's, the last with
    an indexer), a models/lfm2.ConvMoeConfig or a
    models/nemotron_h.SsmMoeConfig,
    by the architecture's `model_type` (FAMILIES).

    {"architecture": {published keys verbatim, at their published
                      values},
     "share": {"layers": n, "dense_layers": n, "experts": [first,
               count], "vocab": [first, count]},      (default: whole)
     "seed": n}
    An unknown key anywhere or an unknown model_type is an error, as is
    a published value the block cannot honour (a bias, tied
    embeddings, another activation, grouped kv heads)."""
    with open(path) as f:
        d = json.load(f)
    extra = set(d) - DESCRIPTION_KEYS
    if extra:
        raise ValueError(f"unknown key(s) in {path}: {sorted(extra)}")
    arch = d.get("architecture")
    if not isinstance(arch, dict):
        raise ValueError(f"{path} has no 'architecture' section")
    family = FAMILIES.get(arch.get("model_type"))
    if family is None:
        raise ValueError(
            f"{path}: model_type {arch.get('model_type')!r} is not "
            f"served (known: {sorted(FAMILIES)})")
    keys = family["keys"]
    extra = set(arch) - set(keys)
    if extra:
        raise ValueError(
            f"unknown architecture key(s) in {path}: {sorted(extra)} "
            f"(known: {sorted(keys)})")
    share = d.get("share", {})
    extra = set(share) - SHARE_KEYS
    if extra:
        raise ValueError(f"unknown share key(s) in {path}: "
                         f"{sorted(extra)}")
    fields = {}
    for name, key in keys.items():
        if name not in arch:
            if key.default is _REQUIRED:
                raise ValueError(f"{path}: architecture lacks {name!r}")
            value = key.default
        else:
            value = arch[name] if arch[name] is None else \
                key.cast(arch[name])
        if key.only is not None and value not in key.only:
            raise ValueError(key.why)
        if key.field is not None:
            fields[key.field] = value
    n_layers = int(arch["num_hidden_layers"])
    dense_key = family.get("dense", "first_k_dense_replace")
    first_dense = dense_key(arch) if callable(dense_key) \
        else int(arch.get(dense_key, 0))
    layers = int(share.get("layers", n_layers))
    dense = int(share.get("dense_layers", min(first_dense, layers)))
    e_first, e_held = share.get("experts", [0, fields["n_routed_experts"]])
    vocab = int(arch["vocab_size"])
    v_first, v_held = share.get("vocab", [0, vocab])
    if not 0 <= v_first <= v_first + v_held <= vocab:
        raise ValueError("the vocabulary slice lies outside vocab_size")
    if layers > n_layers or dense > first_dense:
        raise ValueError("the share keeps more layers than the model has")
    limit = arch.get(family["window"])
    window = int(max_len or limit or 2048)
    if limit is not None and window > int(limit):
        raise ValueError(f"the window exceeds {family['window']}")
    fields.update(
        vocab_size=int(v_held), vocab_first=int(v_first), layers=layers,
        dense_layers=dense, experts_first=int(e_first),
        experts_held=int(e_held), max_len=window)
    return family["finish"](arch, fields, path), int(d.get("seed", 0))


def completion_model_class(cfg):
    """The paged serving model of a loaded description's config."""
    if isinstance(cfg, LatentMoeConfig):
        return LatentCompletionModel
    from .afmoe import (IndexedCompletionModel, WindowCompletionModel,
                        WindowMoeConfig)
    if isinstance(cfg, WindowMoeConfig):
        return WindowCompletionModel if cfg.indexer is None \
            else IndexedCompletionModel
    from .lfm2 import ConvCompletionModel, ConvMoeConfig
    if isinstance(cfg, ConvMoeConfig):
        return ConvCompletionModel
    from .nemotron_h import SsmCompletionModel, SsmMoeConfig
    if isinstance(cfg, SsmMoeConfig):
        return SsmCompletionModel
    from .kda import HybridCompletionModel
    return HybridCompletionModel


# ------------------------------------------------------------- weights

# splint: ignore[SPL205] reason=start-up only: makes one weight tensor from the seed before any request; the serving programs are registered in LatentCompletionModel._program
@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _seeded(key, mean, std, *, shape, dtype):
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return (mean + (u - 0.5) * (jnp.float32(math.sqrt(12.0)) * std)
            ).astype(dtype)


def seed_tensor(seed: int, name: str, shape, std: float,
                dtype=jnp.bfloat16, mean: float = 0.0):
    """THE weight recipe (module docstring): one named tensor from the
    seed, made on the device directly in `dtype`."""
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
        zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _seeded(key, jnp.float32(mean), jnp.float32(std),
                   shape=tuple(int(s) for s in shape), dtype=dtype)


def ffn_params(cfg, seed: int, p: str, dense: bool, mat, down=None):
    """A layer's feed-forward tensors under the name prefix `p`: the
    dense SwiGLU, or the router, the shared expert and the HELD routed
    experts (names carry the expert's index in the WHOLE model).
    mat(name, shape) makes a matrix; `down` the ones that write into
    the residual stream, where a family scales them (models/kda.py)."""
    H, down = cfg.hidden, down or mat
    if dense:
        I = cfg.dense_mlp_dim
        return {"w_gate": mat(p + "w_gate", (H, I)),
                "w_up": mat(p + "w_up", (H, I)),
                "w_down": down(p + "w_down", (I, H))}
    M = cfg.moe_mlp_dim
    lp = {"router": seed_tensor(seed, p + "router",
                                (H, cfg.n_routed_experts),
                                1.0 / math.sqrt(H), jnp.float32)}
    if getattr(cfg, "expert_bias", False):
        # the router's selection bias (moe.router_gates): learned by
        # the balancing rule in a trained model, seeded NON-zero here
        # so that the mechanism is served (models/lfm2.py, WEIGHTS)
        lp["router_bias"] = seed_tensor(
            seed, p + "router_bias", (cfg.n_routed_experts,),
            cfg.expert_bias_std, jnp.float32)
    if cfg.n_shared_experts:
        lp["shared_gate"] = mat(p + "shared.gate", (H, M))
        lp["shared_up"] = mat(p + "shared.up", (H, M))
        lp["shared_down"] = down(p + "shared.down", (M, H))
    held = range(cfg.experts_first, cfg.experts_first + cfg.experts_held)
    for part, shape, make in (("gate", (H, M), mat), ("up", (H, M), mat),
                              ("down", (M, H), down)):
        lp["exp_" + part] = jnp.stack([
            make(f"{p}experts.{e}.{part}", shape) for e in held])
    return lp


def init_params(cfg: LatentMoeConfig, seed: int) -> dict:
    """The resident tree of this share, tensor by tensor."""
    H, dt = cfg.hidden, cfg.dtype

    def mat(name, shape):
        return seed_tensor(seed, name, shape, 1.0 / math.sqrt(shape[0]),
                           dt)

    def norm(name, n):
        return seed_tensor(seed, name, (n,), 0.1, jnp.float32, 1.0)

    layers = []
    for i in range(cfg.layers):
        p = f"layers.{i}."
        lp = {
            "ln_attn_in": norm(p + "ln_attn_in", H),
            "ln_mlp_in": norm(p + "ln_mlp_in", H),
            "w_dq": mat(p + "w_dq", (H, cfg.q_lora_rank)),
            "ln_q": norm(p + "ln_q", cfg.q_lora_rank),
            "w_uq": mat(p + "w_uq", (cfg.q_lora_rank,
                                     cfg.heads * cfg.qk_head_dim)),
            "w_dkv": mat(p + "w_dkv", (H, cfg.latent_width)),
            "ln_kv": norm(p + "ln_kv", cfg.kv_lora_rank),
            "w_ukv": mat(p + "w_ukv", (
                cfg.kv_lora_rank,
                cfg.heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "w_o": mat(p + "w_o", (cfg.heads * cfg.v_head_dim, H)),
        }
        if cfg.sandwich_norm:
            lp["ln_attn_out"] = norm(p + "ln_attn_out", H)
            lp["ln_mlp_out"] = norm(p + "ln_mlp_out", H)
        lp.update(ffn_params(cfg, seed, p, i < cfg.dense_layers, mat))
        layers.append(lp)
    return {
        "tok_emb": seed_tensor(seed, f"tok_emb.{cfg.vocab_first}",
                               (cfg.vocab_size, H), 1.0, dt),
        "layers": layers,
        "ln_out": norm("ln_out", H),
        "lm_head": mat(f"lm_head.{cfg.vocab_first}",
                       (H, cfg.vocab_size)),
    }


# -------------------------------------------------------------- forward

def _rms(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _attn_inputs(cfg: LatentMoeConfig, lp, x, pos):
    """x: (B, S, H) normed; pos: (B, S) int32 positions.  Returns
    (q_nope (B, S, heads, nope), q_rope (B, S, heads, rope) rotated,
    latent (B, S, kv_rank + rope) = [Nkv(c) | RoPE(k_r)])."""
    B, S, _ = x.shape
    cq = _rms(jnp.dot(x, lp["w_dq"]), lp["ln_q"], cfg.rms_eps)
    q = jnp.dot(cq, lp["w_uq"]).reshape(B, S, cfg.heads, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = q[..., cfg.qk_nope_head_dim:]
    ckr = jnp.dot(x, lp["w_dkv"])
    c = _rms(ckr[..., :cfg.kv_lora_rank], lp["ln_kv"], cfg.rms_eps)
    cos, sin = _rotary_angles_at(pos.reshape(-1), cfg.qk_rope_head_dim,
                                 cfg.rope_base)
    cos = cos.reshape(B, S, -1)
    sin = sin.reshape(B, S, -1)
    q_rope = _apply_rotary(q_rope, cos, sin)
    k_r = _apply_rotary(ckr[..., None, cfg.kv_lora_rank:], cos, sin)
    return q_nope, q_rope, jnp.concatenate([c, k_r[:, :, 0]], -1)


def _up_kv(cfg: LatentMoeConfig, lp):
    """W_UKV as (kv_rank, heads, nope | v) -> (W_UK, W_UV)."""
    w = lp["w_ukv"].reshape(cfg.kv_lora_rank, cfg.heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# query rows one block of the expanded prefill attends with: bounds
# the (heads, block, S) float32 score tile XLA materialises
PREFILL_BLOCK_Q = 128


def _expanded_attention(cfg: LatentMoeConfig, lp, q_nope, q_rope,
                        latent):
    """PREFILL: causal attention of one row's S tokens over its own
    latents, with k_nope and v expanded from them (never cached).
    q_*: (1, S, heads, .); latent: (1, S, W).  Returns (1, S, heads,
    v).  Blockwise over queries so that no (heads, S, S) tile exists."""
    S = q_nope.shape[1]
    w_uk, w_uv = _up_kv(cfg, lp)
    c = latent[0, :, :cfg.kv_lora_rank]
    k_r = latent[0, :, cfg.kv_lora_rank:]
    k_nope = jnp.einsum("sr,rhd->shd", c, w_uk)
    v = jnp.einsum("sr,rhd->shd", c, w_uv)
    bq = min(PREFILL_BLOCK_Q, S)
    pad = (-S) % bq
    qn, qr = q_nope[0], q_rope[0]
    if pad:
        qn = jnp.pad(qn, ((0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(qr, ((0, pad), (0, 0), (0, 0)))
    nb = (S + pad) // bq
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)

    def block(args):
        qn_b, qr_b, i0 = args
        s = (jnp.einsum("qhd,khd->hqk", qn_b, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhr,kr->hqk", qr_b, k_r,
                          preferred_element_type=jnp.float32)) * scale
        qi = i0 + jnp.arange(bq)[:, None]
        s = jnp.where((jnp.arange(S)[None, :] <= qi)[None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, (
        qn.reshape(nb, bq, *qn.shape[1:]),
        qr.reshape(nb, bq, *qr.shape[1:]),
        jnp.arange(nb, dtype=jnp.int32) * bq))
    return out.reshape(nb * bq, cfg.heads, cfg.v_head_dim)[None, :S]


def _absorbed_attention(cfg: LatentMoeConfig, lp, q_nope, q_rope, pool,
                        tables, att_len, interpret: bool, q_valid=None):
    """DECODE / SUFFIX: attention in the latent space over the paged
    pool, whose rows for these S tokens are appended already.
    q_*: (B, S, heads, .); q_valid: None or (B,), a row's real tokens
    among the S.  Returns (B, S, heads, v)."""
    w_uk, w_uv = _up_kv(cfg, lp)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    o_lat = latent_paged_attention(
        jnp.concatenate([q_lat, q_rope], -1), pool, tables, att_len,
        kv_rank=cfg.kv_lora_rank, q_valid=q_valid,
        scale=1.0 / math.sqrt(cfg.qk_head_dim), interpret=interpret)
    return jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)


def _ffn(cfg: LatentMoeConfig, lp, x, live, interpret: bool, bank=None,
         route_x=None, live_chunk=None):
    """The layer's feed-forward on normed x: dense SwiGLU, or the
    shared expert + this share of the routed ones (bank: the layer's
    index in expert tensors that stack several layers'; route_x: x
    before it was rounded to the model's dtype, for the router;
    live_chunk: the chunking of a caller most of whose tokens are
    dead: moe.sparse_moe).  Returns (out, slots each held expert
    received | None)."""
    if "router" not in lp:
        return jnp.dot(jax.nn.silu(jnp.dot(x, lp["w_gate"]))
                       * jnp.dot(x, lp["w_up"]), lp["w_down"]), None
    shared = None
    if "shared_gate" in lp:
        shared = (lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return sparse_moe(
        x, lp["router"], lp["exp_gate"], lp["exp_up"], lp["exp_down"],
        top_k=cfg.top_k, first=cfg.experts_first, score=cfg.score_fn,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        shared=shared, live=live, interpret=interpret, bank=bank,
        route_x=route_x, bias=lp.get("router_bias"),
        live_chunk=live_chunk)


def _layer(cfg: LatentMoeConfig, lp, x, pos, attend, live,
           interpret: bool):
    """One block.  attend(q_nope, q_rope, latent) -> ((B, S, heads, v),
    whatever the cache path hands back)."""
    B, S, _ = x.shape
    q_nope, q_rope, latent = _attn_inputs(
        cfg, lp, _rms(x, lp["ln_attn_in"], cfg.rms_eps), pos)
    o, kept = attend(q_nope, q_rope, latent)
    a = jnp.dot(o.reshape(B, S, cfg.heads * cfg.v_head_dim), lp["w_o"])
    if cfg.sandwich_norm:
        a = _rms(a, lp["ln_attn_out"], cfg.rms_eps)
    h = x + a
    f, slots = _ffn(cfg, lp, _rms(h, lp["ln_mlp_in"], cfg.rms_eps),
                    live, interpret)
    if cfg.sandwich_norm:
        f = _rms(f, lp["ln_mlp_out"], cfg.rms_eps)
    return h + f, kept, slots


def _logits(cfg: LatentMoeConfig, params, x):
    """Final norm + untied head over the vocabulary slice, float32."""
    return jnp.dot(_rms(x, params["ln_out"], cfg.rms_eps),
                   params["lm_head"],
                   preferred_element_type=jnp.float32)


def _sum_slots(cfg: LatentMoeConfig, per_layer):
    got = [s for s in per_layer if s is not None]
    if not got:
        return jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
    return functools.reduce(jnp.add, got)


def forward_prefill(cfg: LatentMoeConfig, params, ids, n_valid, *,
                    interpret: bool = False):
    """One row's prompt from position 0, expanded attention.  ids:
    (1, S) padded to a bucket; n_valid: how many are real.  Returns
    (hidden (1, S, H), [latent rows (S, W) a layer])."""
    S = ids.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    live = (jnp.arange(S) < n_valid)[None]
    x = params["tok_emb"][ids]
    latents = []
    for lp in params["layers"]:
        x, lat, _ = _layer(
            cfg, lp, x, pos,
            lambda qn, qr, lat, lp=lp: (
                _expanded_attention(cfg, lp, qn, qr, lat), lat[0]),
            live, interpret)
        latents.append(lat)
    return x, latents


def forward_paged(cfg: LatentMoeConfig, params, ids, pools, tables,
                  lengths, n_valid=None, *, interpret: bool = False):
    """S new tokens a row atop what its table maps, absorbed
    attention over the latent pages (S == 1: a decode step; S > 1: the
    suffix prefill of prefix-cache hits, pad appends past n_valid
    routed to the trash block).  ids: (B, S); pools: a latent pool a
    layer; tables: (B, P); lengths: (B,); n_valid: None, one count for
    every row, or (B,) a row's own (0: a pad row, which writes the
    trash block alone).  Returns (hidden (B, S, H), new pools, slots
    each held expert received)."""
    B, S = ids.shape
    page = pools[0].shape[2]
    pos = jnp.minimum(lengths[:, None] + jnp.arange(S)[None, :],
                      cfg.max_len - 1).astype(jnp.int32)
    bids = jnp.take_along_axis(tables, pos // page, axis=1)
    live = (lengths > 0)[:, None] & jnp.ones((1, S), bool)
    q_valid = None
    if n_valid is not None:
        ok = jnp.arange(S)[None, :] < jnp.reshape(n_valid, (-1, 1))
        bids = jnp.where(ok, bids, 0)
        live = live & ok
        if jnp.ndim(n_valid):
            # rows of their own lengths in one width: the attention
            # skips the pad tokens' blocks (the one-row program pads
            # to the next width at most, and runs as it always has)
            q_valid = n_valid
    offs = pos % page
    att_len = pos[:, 0] + 1
    x = params["tok_emb"][ids]
    new_pools, slots = [], []
    for lp, pool in zip(params["layers"], pools):
        def attend(qn, qr, lat, lp=lp, pool=pool):
            pool = latent_append(pool, lat, bids, offs,
                                 interpret=interpret)
            return _absorbed_attention(cfg, lp, qn, qr, pool, tables,
                                       att_len, interpret, q_valid), pool
        x, pool, s = _layer(cfg, lp, x, pos, attend, live, interpret)
        new_pools.append(pool)
        slots.append(s)
    return x, new_pools, _sum_slots(cfg, slots)


# ------------------------------------------------------------- front end

class LatentPendingChunk(PendingChunk):
    """A paged decode chunk of the latent model: beside the sampled
    block, `slots` — (count,) int32 expert-slots the chunk's LIVE rows
    sent to each held expert, all steps and layers — and `audit`, the
    (n, V) float32 logits of the one row the dispatch was told to
    keep; `counts`, where the model keeps them (models/lfm2.py): (2,)
    int32 — experts that received a slot, summed over the chunk's
    steps and expert layers, and selections the router's bias
    changed.  All stay on the device until asked for; after block()
    reading them waits for nothing."""

    __slots__ = ("slots", "audit", "counts")

    def __init__(self, out, last, n, mark, slots, audit, counts=None):
        super().__init__(out, last, n, mark)
        self.slots = slots
        self.audit = audit
        self.counts = counts


def prefill_buckets(max_len: int, page: int) -> tuple[int, ...]:
    """The paged lane's prefill buckets, from the window and the page
    size alone: the window in whole pages, then a quarter of it
    (rounded up to pages) at a time down to four pages or fewer — four
    programs for a 66-page window (66, 17, 5, 2 pages)."""
    p = -(-max_len // page)
    out = []
    while True:
        out.append(p * page)
        if p <= 4:
            break
        p = -(-p // 4)
    return tuple(sorted(out))


class LatentCompletionModel:
    """The paged serving surface the continuous lane drives
    (init_paged / paged_prefill_row / paged_append_prefill /
    paged_decode_chunk_async / warmup_paged / sample) over the latent
    block, and nothing of CompletionModel's dense-cache surface:
    completer.main refuses the lanes that would need it."""

    paged_supported = True
    audit_supported = True
    # rows whose logits a decode chunk keeps (engine/audit.py)
    audit_lanes = 1
    # what a device trace shows the programs as: jit_<prefix>_<short>
    # (benchmark/readers count on it)
    program_prefix = "latent"
    # what completer.main refuses for a model of this class, and why
    refused_options = {
        "kv_dtype": "latent pages are stored in the model's dtype: "
                    "the int8/int4 page codecs are per (page, kv "
                    "head) and a latent row has no kv heads",
        "kv_tier_pages": "the host tier's page wire carries key/value "
                         "pools only",
        "phase": "the disaggregated hand-off's page wire carries "
                 "key/value pools only",
        "tp": "latent pools have no kv-head axis to shard; attention "
              "is data-parallel in this deployment",
        "ep": "this share is told the experts it holds by the model "
              "description, not by a mesh",
        "draft": "the speculative wrapper pairs key/value pools",
        "weights": "the description serves seeded weights; no "
                   "checkpoint loader maps onto this tree",
        "weight_quant": "the int8 weight residencies cover the dense "
                        "llama projections only",
    }

    def __init__(self, cfg: LatentMoeConfig, *, seed: int = 0,
                 params: Any = None, top_p: float = 0.9,
                 temp: float = 0.7,
                 suffix_buckets: tuple[int, ...] = (16, 64),
                 interpret: bool = False):
        self.cfg = cfg
        self.suffix_buckets = tuple(sorted(
            b for b in suffix_buckets if 0 < b < cfg.max_len)) or (
            min(16, max(1, cfg.max_len - 1)),)
        self.buckets = prefill_buckets(cfg.max_len, 128)
        self.top_p, self.temp = top_p, temp
        self.interpret = interpret
        self.params = init_params(cfg, seed) if params is None else params
        self.devtime_lane = "completer"
        self._rng = jax.random.PRNGKey(seed + 1)
        self._paged_progs: dict[tuple, Any] = {}
        # the batch row whose per-step logits the next decode chunks
        # keep (LatentPendingChunk.audit); -1: none
        self.audit_row = -1

    def audit_seat(self, lane: int, row: int) -> None:
        """The batch row `lane` audits from the next chunk on (-1:
        none)."""
        self.audit_row = row

    def audit_lane(self, match: int, n_suffix: int,
                   budget_share: float = 1.0) -> int:
        """The audit lane of a join that mapped `match` tokens and
        prefilled `n_suffix`, its answer's budget `budget_share` of the
        daemon's, for a model that keeps several (`audit_lanes`): a
        resumed row 0, one from nothing 1."""
        return int(not match)

    def resident_bytes(self) -> int:
        return sum(a.nbytes for a in jax.tree_util.tree_leaves(
            self.params))

    def compile_count(self) -> int:
        total = 0
        for f in self._paged_progs.values():
            f = getattr(f, "__wrapped__", f)
            try:
                total += int(f._cache_size())
            except Exception:   # private jax API: absence isn't an error
                return -1
        return total

    def _devname(self, short: str) -> str:
        return f"{self.devtime_lane}.{short}"

    def bucket_for(self, length: int) -> int:
        return next((b for b in self.buckets if length <= b),
                    self.buckets[-1])

    def sample(self, logits: np.ndarray) -> int:
        """One host-side draw from a join's logits (V,)."""
        self._rng, sub = jax.random.split(self._rng)
        return int(sample_top_p(sub, jnp.asarray(logits),
                                top_p=self.top_p, temp=self.temp))

    # -- paged serving -----------------------------------------------------

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None) -> PagedKVCache:
        self.buckets = prefill_buckets(self.cfg.max_len, page)
        return PagedKVCache(self.cfg, batch, page=page,
                            pool_pages=pool_pages, kv_dtype=kv_dtype)

    def _program(self, key: tuple, short: str, build, donate=(1,)):
        """The jitted, devtime-registered program under `key`; build()
        makes its function, named <program_prefix>_<short>."""
        fn = self._paged_progs.get(key)
        if fn is None:
            run = build()
            run.__name__ = f"{self.program_prefix}_{short}"
            fn = DEVTIME.register(
                self._devname(short),
                jax.jit(run, donate_argnums=donate))
            self._paged_progs[key] = fn
        return fn

    def _prefill_program(self, bucket: int, page: int):
        cfg, interp = self.cfg, self.interpret

        def build():
            def run(params, pools, ids, bids, n_valid):
                x, latents = forward_prefill(cfg, params, ids, n_valid,
                                             interpret=interp)
                # whole pages, a token a column
                pools = [p.at[bids].set(
                    lat.reshape(-1, page, lat.shape[-1])
                    .transpose(0, 2, 1).astype(p.dtype))
                    for p, lat in zip(pools, latents)]
                last = jax.lax.dynamic_index_in_dim(
                    x[0], n_valid - 1, 0, keepdims=False)
                return pools, _logits(cfg, params, last)
            return run
        return self._program(("prefill", bucket, page), "bucket_prefill",
                             build)

    def paged_prefill_row(self, cache: PagedKVCache,
                          prompt_ids: np.ndarray, row: int) -> np.ndarray:
        """Prefill one row's whole prompt (expanded attention over a
        bucket) and commit its latent rows into the row's pages.
        Returns the last real token's logits (V,)."""
        P = len(prompt_ids)
        if P == 0:
            raise ValueError("empty prompt")
        if P >= self.cfg.max_len:
            raise ValueError("prompt exceeds context window")
        if not cache.ensure(row, P):
            raise RuntimeError(
                f"paged pool exhausted: row {row} needs "
                f"{cache.pages_needed(P)} pages, {cache.free_pages} free")
        b = self.bucket_for(P)
        ids = np.zeros((1, b), np.int32)
        ids[0, :P] = np.asarray(prompt_ids[:P], np.int32)
        # entries past the prompt's pages are 0 = trash: the bucket's
        # excess rows land there
        bids = np.zeros((b // cache.page,), np.int32)
        n_own = min(len(bids), cache.tables.shape[1])
        bids[:n_own] = cache.tables[row, :n_own]
        fn = self._prefill_program(b, cache.page)
        pools, logits = fn(self.params, cache.pools[0], jnp.asarray(ids),
                           jnp.asarray(bids), jnp.int32(P))
        mark = DEVTIME.take_mark(self._devname("bucket_prefill"))
        cache.pools[0] = list(pools)
        cache.lengths[row] = P
        out = np.asarray(logits)
        close_mark(mark)
        return out

    def _suffix_program(self, sb: int):
        cfg, interp = self.cfg, self.interpret

        def build():
            def run(params, pools, table, length, ids, n_valid):
                x, pools, _ = forward_paged(
                    cfg, params, ids, pools, table, length, n_valid,
                    interpret=interp)
                last = jax.lax.dynamic_index_in_dim(
                    x[0], n_valid - 1, 0, keepdims=False)
                return pools, _logits(cfg, params, last)
            return run
        return self._program(("suffix", sb), "suffix_prefill", build)

    def paged_append_prefill(self, cache: PagedKVCache, suffix_ids,
                             row: int) -> np.ndarray:
        """Prefill ONLY the uncached suffix of row's prompt atop the
        cache.lengths[row] tokens its table already maps, attending
        in the latent space.  Suffixes longer than the largest suffix
        bucket loop it.  Returns the last real token's logits (V,)."""
        ids = np.asarray(suffix_ids, np.int32)
        if ids.size == 0:
            raise ValueError("empty suffix")
        pos = int(cache.lengths[row])
        if pos + ids.size >= self.cfg.max_len:
            raise ValueError("suffix exceeds context window")
        if not cache.ensure(row, pos + ids.size):
            raise RuntimeError(
                f"paged pool exhausted: row {row} suffix needs "
                f"{cache.pages_needed(pos + ids.size)} pages")
        table = cache.tables[row: row + 1]
        logits, mark, off = None, None, 0
        while off < ids.size:
            rem = ids.size - off
            sb = next((b for b in self.suffix_buckets if b >= rem),
                      self.suffix_buckets[-1])
            n = min(rem, sb)
            chunk = np.zeros((1, sb), np.int32)
            chunk[0, :n] = ids[off: off + n]
            pools, logits = self._suffix_program(sb)(
                self.params, cache.pools[0],
                # host-side copies: lengths is bumped right below, and
                # an aliased view would be read after it
                # (decoder.paged_decode_chunk_async)
                jnp.asarray(np.array(table)),
                jnp.asarray(np.array(cache.lengths[row: row + 1])),
                jnp.asarray(chunk), jnp.int32(n))
            close_mark(mark)
            mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
            cache.pools[0] = list(pools)
            cache.lengths[row] += n
            off += n
        out = np.asarray(logits)
        close_mark(mark)
        return out

    # -- an admission round (the lane's ONE call: `join`) ------------------

    # row counts of the family's row-batched suffix programs beside the
    # one-row program, each at most the lane's batch (None: the batch):
    # the batch, and 8 between for the few rows that come back out of
    # step with a batch (at the batch's rung their round would push a
    # whole batch's pad tokens through the dense layers)
    JOIN_ROWS: tuple = (8, None)

    def join_rungs(self, cache: PagedKVCache) -> tuple[int, ...]:
        """The row counts this model's suffix programs come in,
        ascending: 1 (paged_append_prefill) and the family's JOIN_ROWS.
        A round's joins ride the smallest rung that holds them
        (paged_append_prefill_rows); a family whose suffix program has
        no row axis has none, answers (1,) and is joined a request at
        a time."""
        return tuple(sorted({1, *(min(r or cache.batch, cache.batch)
                                  for r in self.JOIN_ROWS)}))

    @property
    def join_width(self) -> int:
        """Tokens a row of the row-batched suffix programs holds: a
        hit whose suffix is longer is a round of one."""
        return self.suffix_buckets[-1]

    def round_cap(self, cache: PagedKVCache) -> int:
        """Joins one round's program holds: the widest rung."""
        return self.join_rungs(cache)[-1]

    def rides_round(self, join) -> bool:
        """Whether `join` waits for its round's other joins: a prefix
        hit whose suffix the rows program holds.  A miss, and a hit
        wider than that, is a round of one, served where it is seated.
        (A family with state slots whose rows program could not leave
        a join's snapshot would refuse `join.snap` here.)"""
        return join.hit and len(join.ids) - join.match <= self.join_width

    def join(self, cache: PagedKVCache, joins):
        """Prefill the seated rows of ONE admission round
        (engine/prefix_cache.py `Join`s, at most round_cap of them, all
        of which ride unless there is one): one join runs the one-row
        program (decoder.join_one) and hands back (logits (V,) on the
        host, None) — the lane draws; several run the rows program in
        one dispatch and hand back (logits on the device, a row a
        join, the first tokens drawn in graph)."""
        if len(joins) == 1:
            return join_one(self, cache, joins[0]), None
        snaps = [j.snap for j in joins]
        return self.paged_append_prefill_rows(
            cache, [(j.row, np.asarray(j.ids[j.match:], np.int32))
                    for j in joins],
            *([snaps] if any(snaps) else ()))

    def _suffix_rows_program(self, rows: int, sb: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, tables, lengths, ids, n_valid, rng):
                x, pools, _ = forward_paged(
                    cfg, params, ids, pools, tables, lengths, n_valid,
                    interpret=interp)
                last = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                logits = _logits(cfg, params, last)
                return pools, logits, _sample_rows(rng, logits, top_p,
                                                   temp)
            return run
        return self._program(("suffix", rows, sb, top_p, temp),
                             "suffix_prefill", build)

    def _round_inputs(self, cache: PagedKVCache, joins):
        """The host half of a round's dispatch: every join's pages
        reserved, and (ids (rows, sb), n_valid (rows,), tables (rows,
        P), lengths (rows,)) of the smallest rung that holds the joins
        at the rows programs' width, its other rows pads."""
        rows = next(r for r in self.join_rungs(cache)
                    if r >= len(joins))
        sb = self.join_width
        ids = np.zeros((rows, sb), np.int32)
        n_valid = np.zeros((rows,), np.int32)
        tables = np.zeros((rows, cache.tables.shape[1]), np.int32)
        lengths = np.zeros((rows,), np.int32)
        for i, (row, suffix) in enumerate(joins):
            n = len(suffix)
            pos = int(cache.lengths[row])
            if not 0 < n <= sb:
                raise ValueError(f"a suffix of {n} tokens in a "
                                 f"{sb}-token program")
            if pos + n >= self.cfg.max_len:
                raise ValueError("suffix exceeds context window")
            if not cache.ensure(row, pos + n):
                raise RuntimeError(
                    f"paged pool exhausted: row {row} suffix needs "
                    f"{cache.pages_needed(pos + n)} pages")
            ids[i, :n] = suffix
            n_valid[i] = n
            tables[i] = cache.tables[row]
            lengths[i] = pos
        return ids, n_valid, tables, lengths

    def paged_append_prefill_rows(self, cache: PagedKVCache, joins):
        """paged_append_prefill for the hits of ONE admission round in
        one dispatch: joins is [(row, suffix_ids), ...], every suffix
        at most the widest suffix bucket, every row seated with its
        prefix mapped (cache.lengths[row] tokens).  The program is the
        smallest rung of join_rungs that holds them at the widest
        suffix width (a rung has one program), its other rows pads
        (length 0, no token valid: they attend nothing, reach no
        expert and write the trash block), and draws each row's first
        token in graph with the decode chunk's sampler.  Returns
        (logits — a device array whose row i is joins[i]'s last real
        token's (V,) float32 —, first tokens (len(joins),) on the
        host)."""
        ids, n_valid, tables, lengths = self._round_inputs(cache, joins)
        rows, sb = ids.shape
        self._rng, sub = jax.random.split(self._rng)
        pools, logits, toks = self._suffix_rows_program(rows, sb)(
            self.params, cache.pools[0], jnp.asarray(tables),
            jnp.asarray(lengths), jnp.asarray(ids), jnp.asarray(n_valid),
            sub)
        mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
        cache.pools[0] = list(pools)
        for i, (row, _) in enumerate(joins):
            cache.lengths[row] += int(n_valid[i])
        toks = np.asarray(toks)[:len(joins)]
        close_mark(mark)
        return logits, toks

    def _cow_fixups(self, cache) -> int:
        """Copy-on-write pass before a decode dispatch (see
        CompletionModel._cow_fixups): one page copy a layer."""
        n = 0
        for row, p_idx in cache.cow_targets():
            src = int(cache.tables[row, p_idx])
            dst = cache._alloc_page()
            cache.pools[0] = list(self._cow_program()(
                cache.pools[0], jnp.int32(src), jnp.int32(dst)))
            cache.commit_cow(row, p_idx, dst)
            n += 1
        return n

    def _cow_program(self):
        def build():
            def run(pools, src, dst):
                return [p.at[dst].set(p[src]) for p in pools]
            return run
        return self._program(("cow",), "cow_copy", build, donate=(0,))

    def _chunk_program(self, n: int, bp: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, tables, lengths, rng, fresh,
                    fresh_mask, carry, audit_row):
                toks0 = jnp.where(fresh_mask, fresh, carry)
                row = jnp.clip(audit_row, 0, bp - 1)

                def step(carry_s, _):
                    pools, lengths, rng, toks, slots = carry_s
                    x, pools, s = forward_paged(
                        cfg, params, toks.reshape(-1, 1), pools, tables,
                        lengths, interpret=interp)
                    logits = _logits(cfg, params, x[:, 0])
                    rng, sub = jax.random.split(rng)
                    nxt = _sample_rows(sub, logits, top_p, temp)
                    return ((pools, lengths + 1, rng, nxt, slots + s),
                            (nxt, logits[row]))

                zero = jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
                (pools, _, _, _, slots), (out, kept) = jax.lax.scan(
                    step, (pools, lengths, rng, toks0, zero), None,
                    length=n)
                return pools, out, out[-1], slots, kept
            return run
        return self._program(("chunk", n, bp, top_p, temp),
                             "paged_chunk", build)

    def _chunk_inputs(self, cache: PagedKVCache, tokens, n: int, carry):
        """The host half of a chunk's dispatch: every live row's pages
        for n more tokens, the copy-on-write pass, and (fresh_mask,
        host-fed tokens, device carry)."""
        bp = cache.batch
        for r in range(bp):
            length = int(cache.lengths[r])
            if length > 0 and not cache.ensure(
                    r, min(length + n, self.cfg.max_len)):
                raise RuntimeError(
                    f"paged pool exhausted mid-decode: row {r} "
                    f"(admission must reserve prompt + max_new)")
        self._cow_fixups(cache)
        toks = np.full((bp,), -1, np.int32)
        toks[: len(tokens)] = np.asarray(tokens, np.int32)
        if carry is None:
            fresh_mask = np.ones((bp,), bool)
            # a device array like every later carry (the previous
            # chunk's .last): a NumPy one is another call signature,
            # which the compile ledger counts as a program
            carry = jnp.zeros((bp,), jnp.int32)
        else:
            fresh_mask = toks >= 0
        toks = np.maximum(toks, 0)
        return fresh_mask, toks, carry

    def _advance(self, cache: PagedKVCache, n: int) -> None:
        live = cache.lengths > 0
        cache.lengths[live] = np.minimum(cache.lengths[live] + n,
                                         self.cfg.max_len)

    def paged_decode_chunk_async(self, cache: PagedKVCache, tokens,
                                 n: int, carry=None
                                 ) -> LatentPendingChunk:
        """CompletionModel.paged_decode_chunk_async's contract, over
        latent pages; the chunk also carries its expert-slot counts
        and the audited row's logits."""
        bp = cache.batch
        fresh_mask, toks, carry = self._chunk_inputs(cache, tokens, n,
                                                     carry)
        self._rng, sub = jax.random.split(self._rng)
        pools, out, last, slots, kept = self._chunk_program(n, bp)(
            self.params, cache.pools[0],
            jnp.asarray(np.array(cache.tables)),
            jnp.asarray(np.array(cache.lengths)), sub, jnp.asarray(toks),
            jnp.asarray(fresh_mask), carry, jnp.int32(self.audit_row))
        cache.pools[0] = list(pools)
        self._advance(cache, n)
        return LatentPendingChunk(
            out, last, n, DEVTIME.take_mark(self._devname("paged_chunk")),
            slots, kept)

    def paged_decode_chunk(self, cache: PagedKVCache, tokens, n: int
                           ) -> np.ndarray:
        return self.paged_decode_chunk_async(cache, tokens, n).block()

    def warmup_paged(self, cache: PagedKVCache, chunk: int = 8,
                     max_prompt: int | None = None) -> None:
        """Every program the lane can dispatch for this window: the
        prefill buckets up to the prompt budget, the sampler, the
        decode chunk, and (with a prefix tree attached) the suffix
        buckets and the page copy."""
        with DEVTIME.warmup_phase():
            self._warmup_paged_impl(cache, chunk, max_prompt)

    def _warmup_paged_impl(self, cache: PagedKVCache, chunk: int,
                           max_prompt: int | None) -> None:
        cap = (self.bucket_for(max_prompt) if max_prompt is not None
               else self.buckets[-1])
        chunk_done = False
        for b in self.buckets:
            if b > cap:
                break
            n = max(1, min(b, self.cfg.max_len - 1) - 1)
            self.sample(self.paged_prefill_row(
                cache, np.ones((n,), np.int32), 0))
            if not chunk_done and n + chunk < self.cfg.max_len:
                self.paged_decode_chunk(
                    cache, np.ones((cache.batch,), np.int32), chunk)
                chunk_done = True
            cache.free_row(0)
        if getattr(cache, "prefix_cache", None) is not None:
            for sb in self.suffix_buckets:
                if sb + chunk >= self.cfg.max_len:
                    break
                self.paged_append_prefill(
                    cache, np.ones((sb,), np.int32), 0)
                cache.free_row(0)
            else:
                self._warm_join_rungs(cache)
            self._warm_cow(cache)

    def _warm_join_rungs(self, cache: PagedKVCache) -> None:
        """The row-batched rungs, at their width.  A rung's program
        is its shape: one row more than the rung below holds compiles
        and runs it."""
        sb = self.join_width
        for below in self.join_rungs(cache)[:-1]:
            self.paged_append_prefill_rows(
                cache, [(r, np.ones((sb,), np.int32))
                        for r in range(below + 1)])
            for r in range(below + 1):
                cache.free_row(r)

    def _warm_cow(self, cache: PagedKVCache) -> None:
        src, dst = cache._alloc_page(), cache._alloc_page()
        cache.pools[0] = list(self._cow_program()(
            cache.pools[0], jnp.int32(src), jnp.int32(dst)))
        cache._decref(src)
        cache._decref(dst)
