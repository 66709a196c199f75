"""A decoder stack whose mixer is a GATED SHORT CONVOLUTION in most
layers and grouped-query attention in the rest, with a sparse MoE whose
router carries a selection bias — as the LFM2-MoE family of public
configs describes it (LFM2-24B-A2B: three convolution layers to one
attention layer), served through the completion daemon's paged lane as
one chip's share of a deployment (models/mla.py holds the share's
conventions and the weight recipe; this module reuses its feed-forward
and chunk hand-off, models/kda.py's state-slot programs and
models/afmoe.py's page-group programs).

The layer (x: hidden; matrices without bias; RMSNorm eps `norm_eps`;
pre-norm, two norms a layer):

    h = x + Op(N1(x));   y = h + FFN(N2(h))

conv Op (layer_types[i] == "conv"; K = conv_L_cache taps a channel):

    [B | C | X] = u W_in                      (hidden -> 3 x hidden)
    v = B * X
    c_t = sum_{j < K} w[j] * v_{t-(K-1)+j}    depthwise, causal, no
                                              activation
    Op = (C * c) W_out

    What a ROW carries between tokens is v's last K - 1 values a
    channel — `(K - 1, hidden)`, 8 KB in bfloat16 at K = 3, whatever
    the context: `ConvMoeConfig.page_layout` describes it as the
    layer's `state`, and PagedKVCache keeps it in STATE SLOTS (one a
    live row, the rest snapshots the prefix tree owns).  The suffix
    program is the K-tap product over the suffix behind the row's
    register; the decode step shifts the register by one.

attention Op ("full_attention"): q = u W_Q (heads x d), k, v = u W_K,
    u W_V (kv_heads x d), d = hidden / heads; q, k <- RMSNorm over d,
    then RoPE(rope_theta) on the whole head (split-half pairs); causal
    softmax over every earlier token; W_O.  K and V live in ONE page
    group, both a token a COLUMN — (n_blocks, L, kv_heads, d, page):
    a head of 64 half-fills the 128 lanes of a row, and the chip's
    compiler copies a pool kept that way at every call
    (ops/paged_attention, tests/test_chip_compile.py).

FFN: models/mla._ffn — dense SwiGLU in the leading layers, after them
    `num_experts` routed experts and no shared one: s = sigmoid(u W_g)
    in float32, the top-k of s + b (`use_expert_bias`: the bias enters
    the SELECTION only), gates the selected s over their sum
    (`norm_topk_prob`) times `routed_scaling_factor`.

PROGRAMS: kimi's contract (models/kda.py) — ONE prefill program, the
suffix prefill from (pages + state) in whole-page widths from one page
to SUFFIX_PAGES, which also writes into a slot the caller names the
register as it stood after `n_snap` of its tokens; a prompt the prefix
cache does not know is the same program from a zeroed register and an
empty table, looped in its widest width.  The decode chunk is mla's, n
steps with the sampler in graph, and beside the slots each expert
received it counts the experts that received any and the selections
the bias changed (LatentPendingChunk.counts).

WEIGHTS: mla.seed_tensor, names `layers.<i>.<tensor>`, the scaled
recipe of models/kda.py (every matrix that writes INTO the residual
stream — w_out, w_o, w_down, experts.<e>.down — at std / sqrt(2 x the
whole model's layers)).  This family's own, restated by the plain
reference:
    conv  (K, hidden) float32 taps, std 1/sqrt(K): B, C and X come out
          at unit scale, and so do v = B * X, the taps' sum and the
          gated product the Op puts through W_out;
    router_bias (experts,) float32, std BIAS_STD, mean 0: at sigmoid
          scores of unit-scale logits the bias then changes 5-20% of a
          token's top-k (tests/test_lfm2.py measures it; at zero the
          mechanism would not be served at all).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_attention import kv_append, window_paged_attention
from .afmoe import GroupPagePrograms, _rotate
from .decoder import PageLayout, PagedKVCache, _sample_rows
from .encoder import _rotary_angles_at
from .kda import StateSlotPrograms, _head, _normed
from .mla import (LatentCompletionModel, LatentPendingChunk, _ffn, _rms,
                  _sum_slots, ffn_params, seed_tensor)
from .moe import router_bias_swaps
from ..obs.devtime import DEVTIME, close_mark

KINDS = ("conv", "full")
# pages of the widest suffix program: a tool result or a user turn of a
# few hundred tokens fits one call, a cold prompt loops in it
SUFFIX_PAGES = 4
# LIVE tokens of a round's rows one pass over the experts takes
# (moe.sparse_moe live_chunk): a round's rows are 512 token slots each
# and about a third of them real; in chunks of 2,048 SLOTS the 32-row
# program read every expert's weights eight times
JOIN_MOE_CHUNK = 8192
BIAS_STD = 0.015
# joins whose suffix is at most this many tokens audit in lane 0: their
# first answer token still lies inside the reach of the stacked
# convolutions' restored registers (audit_lane)
SHORT_SUFFIX = 16


@dataclasses.dataclass(frozen=True)
class ConvMoeConfig:
    vocab_size: int               # rows of the vocabulary held here
    hidden: int
    kinds: tuple[str, ...]        # a kind ("conv" | "full") a kept layer
    heads: int
    kv_heads: int
    head_dim: int
    conv_kernel: int              # taps a channel (conv_L_cache)
    dense_layers: int             # leading dense layers among `layers`
    dense_mlp_dim: int
    moe_mlp_dim: int
    n_routed_experts: int         # the router's width: ALL experts
    top_k: int
    experts_first: int = 0
    experts_held: int | None = None
    n_shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    score_fn: str = "sigmoid"
    expert_bias: bool = True      # a selection bias a routed expert
    expert_bias_std: float = BIAS_STD
    vocab_first: int = 0
    rope_base: float = 1e6
    rms_eps: float = 1e-5
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    state_dtype: Any = None       # the register's; None: `dtype`
    # layers of the WHOLE model (the share may keep fewer): what the
    # seeded output projections are scaled by (module docstring)
    model_layers: int | None = None

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
        if self.state_dtype is None:
            object.__setattr__(self, "state_dtype", self.dtype)
        if self.model_layers is None:
            object.__setattr__(self, "model_layers", len(self.kinds))
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
        if set(self.kinds) - set(KINDS) or "full" not in self.kinds:
            raise ValueError(f"layer kinds must be among {KINDS}, with "
                             "at least one attention layer (the cache "
                             "keeps pages)")
        if self.heads % self.kv_heads or self.head_dim % 2 \
                or self.conv_kernel < 2:
            raise ValueError("kv_heads must divide heads, head_dim be "
                             "even (RoPE pairs) and conv_L_cache >= 2")

    @classmethod
    def tiny(cls, **kw) -> "ConvMoeConfig":
        """Small config for tests and CPU rehearsals: a leading dense
        convolution layer and two periods, as the benchmark's cut."""
        kw = {"vocab_size": 512, "hidden": 64,
              "kinds": ("conv", "full", "conv", "conv", "conv", "full",
                        "conv"),
              "heads": 4, "kv_heads": 2, "head_dim": 16, "conv_kernel": 3,
              "dense_layers": 1, "dense_mlp_dim": 128, "moe_mlp_dim": 32,
              "n_routed_experts": 8, "top_k": 2, "max_len": 256, **kw}
        return cls(**kw)

    @property
    def layers(self) -> int:
        return len(self.kinds)

    def group_index(self, i: int) -> int:
        """Layer i's index among the layers of its kind."""
        return sum(k == self.kinds[i] for k in self.kinds[:i])

    def page_layout(self, page: int) -> tuple[PageLayout, ...]:
        """The attention layers' K and V side by side in ONE page
        group, both a token a column; a convolution layer no pool at
        all and its register a row."""
        n, kh, d = self.kinds.count("full"), self.kv_heads, self.head_dim
        group = PageLayout((("k", (n, kh, d, page)),
                            ("v", (n, kh, d, page))),
                           token_values=n * kh * 2 * d, layers=n)
        state = PageLayout((), token_values=0, state=(
            ("u", (self.conv_kernel - 1, self.hidden), self.state_dtype),))
        return (group,) + (state,) * self.kinds.count("conv")


# ------------------------------------------------------------- weights

def init_params(cfg: ConvMoeConfig, seed: int) -> dict:
    """The resident tree of this share, tensor by tensor."""
    H, dt, d = cfg.hidden, cfg.dtype, cfg.head_dim
    out_scale = 1.0 / math.sqrt(2.0 * cfg.model_layers)

    def mat(name, shape, scale=1.0):
        return seed_tensor(seed, name, shape,
                           scale / math.sqrt(shape[0]), dt)

    def out(name, shape):             # writes into the residual stream
        return mat(name, shape, out_scale)

    def norm(name, n):
        return seed_tensor(seed, name, (n,), 0.1, jnp.float32, 1.0)

    layers = []
    for i, kind in enumerate(cfg.kinds):
        p = f"layers.{i}."
        lp = {"ln_mix_in": norm(p + "ln_mix_in", H),
              "ln_mlp_in": norm(p + "ln_mlp_in", H)}
        if kind == "conv":
            lp.update({
                "w_in": mat(p + "w_in", (H, 3 * H)),
                "conv": seed_tensor(seed, p + "conv", (cfg.conv_kernel, H),
                                    1.0 / math.sqrt(cfg.conv_kernel),
                                    jnp.float32),
                "w_out": out(p + "w_out", (H, H))})
        else:
            lp.update({
                "w_q": mat(p + "w_q", (H, cfg.heads * d)),
                "w_k": mat(p + "w_k", (H, cfg.kv_heads * d)),
                "w_v": mat(p + "w_v", (H, cfg.kv_heads * d)),
                "q_norm": norm(p + "q_norm", d),
                "k_norm": norm(p + "k_norm", d),
                "w_o": out(p + "w_o", (cfg.heads * d, H))})
        lp.update(ffn_params(cfg, seed, p, i < cfg.dense_layers, mat, out))
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

def _conv_inputs(cfg: ConvMoeConfig, lp, xn):
    """xn: (..., H) normed.  Returns (v = B * X in the register's
    dtype, the output gate C (..., H) float32)."""
    f32 = jnp.float32
    b, c, x = jnp.split(jnp.dot(xn, lp["w_in"]), 3, axis=-1)
    return (b.astype(f32) * x.astype(f32)).astype(cfg.state_dtype), \
        c.astype(f32)


def _conv_out(cfg: ConvMoeConfig, lp, c, conv):
    """(C * c) W_out, float32.  c: the gate; conv: the taps' sum."""
    return jnp.dot((c * conv).astype(cfg.dtype), lp["w_out"],
                   preferred_element_type=jnp.float32)


def conv_suffix(cfg: ConvMoeConfig, lp, xn, reg):
    """The Op over S tokens of ONE row behind its register.  xn: (S,
    H) normed; reg: (K - 1, H), v of the row's last K - 1 tokens.
    Returns (the Op's output (S, H) float32, `full` (K - 1 + S, H):
    the register followed by the suffix's v — token t's sits at index
    K - 1 + t, so full[n: n + K - 1] is the register after n tokens)."""
    S, K = xn.shape[0], cfg.conv_kernel
    v, c = _conv_inputs(cfg, lp, xn)
    full = jnp.concatenate([reg, v], 0)
    conv = sum(full[j: j + S].astype(jnp.float32) * lp["conv"][j]
               for j in range(K))
    return _conv_out(cfg, lp, c, conv), full


def conv_step(cfg: ConvMoeConfig, lp, xn, reg):
    """The Op's decode step.  xn: (B, H) normed; reg: (B, K - 1, H).
    Returns (output (B, H) float32, the register shifted by one)."""
    v, c = _conv_inputs(cfg, lp, xn)
    win = jnp.concatenate([reg, v[:, None]], 1)           # (B, K, H)
    conv = jnp.sum(win.astype(jnp.float32) * lp["conv"][None], 1)
    return _conv_out(cfg, lp, c, conv), win[:, 1:]


def _attn_mix(cfg: ConvMoeConfig, lp, xn, pos, pools, write, gl, tables,
              att_len, interpret: bool):
    """The attention Op over the page group: project, norm, rotate,
    put the new tokens' K and V into their pages, attend.  xn: (B, S,
    H) normed; pools: (k, v).  Returns ((B, S, H) float32, pools)."""
    B, S, _ = xn.shape
    d, f32 = cfg.head_dim, jnp.float32

    def proj(w, heads):
        return jnp.dot(xn, w).reshape(B, S, heads, d)
    q = _rms(proj(lp["w_q"], cfg.heads).astype(f32), lp["q_norm"],
             cfg.rms_eps)
    k = _rms(proj(lp["w_k"], cfg.kv_heads).astype(f32), lp["k_norm"],
             cfg.rms_eps)
    cos, sin = _rotary_angles_at(pos.reshape(-1), d, cfg.rope_base)
    cos, sin = cos.reshape(B, S, -1), sin.reshape(B, S, -1)
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    kp, vp = pools
    kp = write(kp, k.astype(kp.dtype), gl)
    vp = write(vp, proj(lp["w_v"], cfg.kv_heads).astype(vp.dtype), gl)
    o = window_paged_attention(
        q.astype(cfg.dtype), kp, vp, tables, att_len, layer=gl,
        k_cols=True, v_cols=True, interpret=interpret)
    return jnp.dot(o.reshape(B, S, cfg.heads * d), lp["w_o"],
                   preferred_element_type=f32), (kp, vp)


def _finish_layer(cfg: ConvMoeConfig, lp, x, a, live, interpret,
                  live_chunk=None):
    """The residual stream stays float32 from the embedding to the
    head (models/kda.py); the router reads the normed stream unrounded
    (moe.sparse_moe, and `live_chunk`).  Returns (x, slots each held
    expert received | None, (2,) int32 [experts that received any,
    selections the bias changed] | None)."""
    h = x + a
    hn = _rms(h, lp["ln_mlp_in"], cfg.rms_eps)
    f, slots = _ffn(cfg, lp, hn.astype(cfg.dtype), live, interpret,
                    route_x=hn, live_chunk=live_chunk)
    counts = None
    if slots is not None:
        swaps = router_bias_swaps(
            hn.reshape(-1, cfg.hidden), lp["router"], lp["router_bias"],
            live.reshape(-1), top_k=cfg.top_k, score=cfg.score_fn
        ) if "router_bias" in lp else jnp.int32(0)
        counts = jnp.stack([jnp.sum(slots > 0, dtype=jnp.int32), swaps])
    return h + f.astype(jnp.float32), slots, counts


def forward_decode(cfg: ConvMoeConfig, params, toks, pools, states,
                   tables, lengths, *, interpret: bool = False):
    """One new token a row: batch row b over state slot b and the
    pages its table maps.  toks: (B,); pools: (k, v), each (n_blocks,
    L, kv_heads, d, page); states: [[register (slots, K - 1, H)]] a
    convolution layer; tables: (B, P); lengths: (B,).  Returns (hidden
    (B, H), pools, states, slots each held expert received, counts)."""
    B = toks.shape[0]
    page = pools[0].shape[4]
    pos = jnp.minimum(lengths, cfg.max_len - 1).astype(jnp.int32)
    bids = jnp.take_along_axis(tables, (pos // page)[:, None], axis=1)
    offs = pos % page
    live = (lengths > 0)[:, None]

    def write(pool, new, gl):
        return kv_append(pool, new[:, 0], bids[:, 0], offs, layer=gl,
                         cols=True, interpret=interpret)
    x = params["tok_emb"][toks][:, None].astype(jnp.float32)  # (B, 1, H)
    new_states, slots, counts = [], [], []
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        xn = _normed(cfg, x, lp["ln_mix_in"])
        if kind == "conv":
            (reg,) = states[len(new_states)]
            a, new = conv_step(cfg, lp, xn[:, 0], reg[:B])
            new_states.append([reg.at[:B].set(new)])
            a = a[:, None]
        else:
            a, pools = _attn_mix(cfg, lp, xn, pos[:, None], pools, write,
                                 cfg.group_index(i), tables, pos + 1,
                                 interpret)
        x, s, c = _finish_layer(cfg, lp, x, a, live, interpret)
        slots.append(s)
        counts.append(c)
    return (x[:, 0], pools, new_states, _sum_slots(cfg, slots),
            sum((c for c in counts if c is not None),
                jnp.zeros((2,), jnp.int32)))


def forward_suffix(cfg: ConvMoeConfig, params, ids, pools, states, table,
                   length, n_valid, row, n_snap, snap_slot, *,
                   interpret: bool = False):
    """S new tokens of ONE row atop the `length` tokens its table maps
    — whole pages of them — and the registers in slot `row` (a prompt
    from nothing: length 0, a zeroed slot).  ids: (1, S) padded to
    whole pages, n_valid real; the registers after the first n_snap
    tokens go to slot `snap_slot`.  Returns (hidden (1, S, H), pools,
    states, the experts that received a slot, summed over the expert
    layers)."""
    S = ids.shape[1]
    page = pools[0].shape[4]
    n_p = S // page
    pos = jnp.minimum(length[:, None] + jnp.arange(S)[None, :],
                      cfg.max_len - 1).astype(jnp.int32)
    ok = jnp.arange(S)[None, :] < n_valid                 # (1, S)
    bids = jax.lax.dynamic_slice_in_dim(table[0], length[0] // page, n_p)
    tail = cfg.conv_kernel - 1

    def write(pool, new, gl):         # whole pages, a token a column
        rows = new[0].reshape(n_p, page, *new.shape[2:])
        return pool.at[bids, gl].set(rows.transpose(0, 2, 3, 1))
    x = params["tok_emb"][ids].astype(jnp.float32)
    new_states, live_experts = [], jnp.int32(0)
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        xn = _normed(cfg, x, lp["ln_mix_in"])
        if kind == "conv":
            (reg,) = states[len(new_states)]
            a, full = conv_suffix(cfg, lp, xn[0], reg[row])

            def reg_at(n, full=full):
                return jax.lax.dynamic_slice_in_dim(full, n, tail, 0)
            # the snapshot first: where none is asked for its slot is
            # the spare one, never the row's own
            new_states.append([reg.at[snap_slot].set(reg_at(n_snap))
                               .at[row].set(reg_at(n_valid))])
            a = a[None]
        else:
            a, pools = _attn_mix(cfg, lp, xn, pos, pools, write,
                                 cfg.group_index(i), table, pos[:, 0] + 1,
                                 interpret)
        x, _, c = _finish_layer(cfg, lp, x, a, ok, interpret)
        if c is not None:
            live_experts = live_experts + c[0]
    return x, pools, new_states, live_experts


def forward_suffix_rows(cfg: ConvMoeConfig, params, ids, pools, states,
                        tables, lengths, n_valid, row_slot, n_snap,
                        snap_slot, *, interpret: bool = False):
    """forward_suffix with a ROW axis: the hits of one admission round
    in one program, so a layer's weights are read once a round.  ids:
    (R, S) padded to whole pages, n_valid (R,) real (0: a pad row, its
    length 0, its table trash blocks, its slot the spare one); row i's
    registers are read from slot row_slot[i] and written back there as
    they stand after n_valid[i] tokens, and to slot snap_slot[i] as
    they stood after n_snap[i] (the spare slot where the row leaves no
    snapshot: no live slot is ever written twice); its keys go to
    tables[i] from page lengths[i] // page on, a page none of its real
    tokens reaches to the trash block.  Pad tokens reach no expert.
    Returns what forward_suffix does, hidden (R, S, H)."""
    R, S = ids.shape
    page = pools[0].shape[4]
    n_p = S // page
    at = jnp.arange(S)[None, :]
    pos = jnp.minimum(lengths[:, None] + at,
                      cfg.max_len - 1).astype(jnp.int32)
    ok = at < n_valid[:, None]                            # (R, S)
    piece = jnp.arange(n_p)[None, :]
    held = jnp.take_along_axis(
        tables, jnp.minimum(lengths[:, None] // page + piece,
                            tables.shape[1] - 1), axis=1)
    bids = jnp.where(piece * page < n_valid[:, None], held, 0).reshape(-1)
    tail = cfg.conv_kernel - 1

    def write(pool, new, gl):         # whole pages, a token a column
        pages = new.reshape(R * n_p, page, *new.shape[2:])
        return pool.at[bids, gl].set(pages.transpose(0, 2, 3, 1))
    x = params["tok_emb"][ids].astype(jnp.float32)
    new_states, live_experts = [], jnp.int32(0)
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        xn = _normed(cfg, x, lp["ln_mix_in"])
        if kind == "conv":
            (reg,) = states[len(new_states)]
            a, full = jax.vmap(
                lambda xr, rr, lp=lp: conv_suffix(cfg, lp, xr, rr))(
                    xn, reg[row_slot])

            def reg_at(n, full=full):
                return jax.vmap(lambda f, k: jax.lax.dynamic_slice_in_dim(
                    f, k, tail, 0))(full, n)
            new_states.append([reg.at[snap_slot].set(reg_at(n_snap))
                               .at[row_slot].set(reg_at(n_valid))])
        else:
            a, pools = _attn_mix(cfg, lp, xn, pos, pools, write,
                                 cfg.group_index(i), tables, pos[:, 0] + 1,
                                 interpret)
        x, _, c = _finish_layer(cfg, lp, x, a, ok, interpret,
                                live_chunk=JOIN_MOE_CHUNK)
        if c is not None:
            live_experts = live_experts + c[0]
    return x, pools, new_states, live_experts


# ------------------------------------------------------------- front end

class ConvCompletionModel(StateSlotPrograms, GroupPagePrograms,
                          LatentCompletionModel):
    """LatentCompletionModel's paged serving surface over the
    convolution / attention stack: state slots (StateSlotPrograms)
    beside ONE group of key/value pages (GroupPagePrograms)."""

    # lane 0: a join whose suffix is at most SHORT_SUFFIX tokens — the
    # answer still depends on what the restore brought —, lane 1: the
    # rest (engine/audit.py)
    audit_lanes = 2
    program_prefix = "lfm2"
    refused_options = {
        **LatentCompletionModel.refused_options,
        "kv_dtype": "the page groups are stored in the model's dtype: "
                    "the int8/int4 page codecs know one pool a layer, "
                    "not a group's",
        "kv_tier_pages": "the host tier's page wire carries key/value "
                         "pools only, and no recurrent state",
        "phase": "the disaggregated hand-off's page wire carries "
                 "key/value pools only, and no recurrent state",
        "tp": "the page groups are not sharded on their kv-head axis; "
              "attention is data-parallel in this deployment",
    }

    def __init__(self, cfg: ConvMoeConfig, *, seed: int = 0,
                 params: Any = None, top_p: float = 0.9,
                 temp: float = 0.7, interpret: bool = False):
        super().__init__(
            cfg, seed=seed,
            params=init_params(cfg, seed) if params is None else params,
            top_p=top_p, temp=temp, suffix_buckets=(16,),
            interpret=interpret)
        self.audit_rows = [-1] * self.audit_lanes
        # what the attention kernel was asked to do, in LIVE keys —
        # running totals the heartbeat carries (models/afmoe.py's, for
        # the one group this family has) — and the experts a suffix
        # piece's tokens reached, summed over its expert layers (the
        # decode chunk's count rides LatentPendingChunk.counts)
        self._attn_work = dict.fromkeys(
            ("decode_keys", "prefill_keys", "prefill_kv",
             "prefill_experts_live"), 0)
        self._prefill_live: list = []     # device scalars not yet added
        self._set_page(128)

    @property
    def attn_work(self) -> dict:
        """The totals, the finished suffix pieces' counts folded in (a
        piece's logits are fetched before the next is dispatched, so
        these reads wait for nothing)."""
        pending, self._prefill_live = self._prefill_live, []
        self._attn_work["prefill_experts_live"] += sum(
            int(x) for x in pending)
        return self._attn_work

    def audit_seat(self, lane: int, row: int) -> None:
        self.audit_rows[lane] = row

    def audit_lane(self, match: int, n_suffix: int,
                   budget_share: float = 1.0) -> int:
        return int(n_suffix > SHORT_SUFFIX)

    def _set_page(self, page: int) -> None:
        """Every suffix bucket is whole pages (a snapshot sits on a
        page boundary, and the keys are written a page at a time):
        each width from one page to SUFFIX_PAGES; a longer suffix (a
        cold prompt) loops in the widest."""
        self.suffix_buckets = tuple(
            n * page for n in range(1, SUFFIX_PAGES + 1)
            if n * page < self.cfg.max_len) or (page,)
        self.buckets = self.suffix_buckets

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None,
                   state_snapshots: int | None = None) -> PagedKVCache:
        self._set_page(page)
        return PagedKVCache(self.cfg, batch, page=page,
                            pool_pages=pool_pages, kv_dtype=kv_dtype,
                            state_snapshots=state_snapshots)

    # -- prefill -----------------------------------------------------------

    def _suffix_program(self, sb: int):
        cfg, interp = self.cfg, self.interpret

        def build():
            def run(params, pools, states, table, length, ids, n_valid,
                    row, n_snap, snap_slot):
                x, pools, states, live = forward_suffix(
                    cfg, params, ids, pools, states, table, length,
                    n_valid, row, n_snap, snap_slot, interpret=interp)
                last = jax.lax.dynamic_index_in_dim(
                    x[0], n_valid - 1, 0, keepdims=False)
                return pools, states, _head(cfg, params, last), live
            return run
        return self._program(("suffix", sb), "suffix_prefill", build,
                             donate=(1, 2))

    def _suffix_piece(self, cache: PagedKVCache, row: int, sb: int,
                      piece, n: int, n_snap: int, snap_slot: int):
        pos = int(cache.lengths[row])
        if pos % cache.page:
            raise ValueError(
                f"a suffix starts at a page boundary; row {row} holds "
                f"{pos} tokens")
        pools, states, logits, live = self._suffix_program(sb)(
            self.params, self._pools(cache)["full"], cache.states,
            self._tables(cache, row)["full"],
            jnp.asarray(np.array(cache.lengths[row: row + 1])),
            jnp.asarray(piece), jnp.int32(n), jnp.int32(row),
            jnp.int32(n_snap), jnp.int32(snap_slot))
        self._keep(cache, {"full": pools})
        cache.states = states
        aw = self._attn_work
        aw["prefill_keys"] += n * pos + n * (n + 1) // 2
        aw["prefill_kv"] += pos + n
        self._prefill_live.append(live)
        return logits

    # -- an admission round's hits in one program -----------------------------

    # rows of the row-batched suffix program (join_rungs): ONE rung
    # beside the one-row programs, and it leaves each row's snapshot
    # itself, so a round takes a join whether or not it leaves one.  32
    # is where a round's expert products turn compute-bound (~500 real
    # tokens an expert: more rows a program save a host round trip and
    # no weight read) and leaves the chip room — its temporaries at the
    # benchmark's widths are 1.06 GB beside 12.79 GB of arguments, 82%
    # of a v5e (tests/test_chip_compile.py).  No rung between: a
    # program is 20-30 s of a cold start that has none to spare
    # (PERF.md section 6, PR 41), and in 96 sessions' traffic a round
    # is one row (the first back) or the rung's fill
    JOIN_ROWS = (32,)

    def _suffix_rows_program(self, rows: int, sb: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, states, tables, lengths, ids, n_valid,
                    row_slot, n_snap, snap_slot, rng):
                x, pools, states, live = forward_suffix_rows(
                    cfg, params, ids, pools, states, tables, lengths,
                    n_valid, row_slot, n_snap, snap_slot, interpret=interp)
                last = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                logits = _head(cfg, params, last)
                return (pools, states, logits,
                        _sample_rows(rng, logits, top_p, temp), live)
            return run
        return self._program(("suffix", rows, sb, top_p, temp),
                             "suffix_prefill", build, donate=(1, 2))

    def paged_append_prefill_rows(self, cache: PagedKVCache, joins,
                                  snaps=None):
        """mla.paged_append_prefill_rows over (pages + state): joins is
        [(row, suffix_ids), ...], every row seated with its prefix
        mapped and its registers RESTORED into its slot already (the
        program reads no snapshot: a later seat of the round may have
        evicted the one a row resumed from); snaps[i] is None or
        (slot, snap_at) as paged_append_prefill takes them.  Pad rows
        and rows that leave no snapshot write the spare slot.  Returns
        (logits on the device, first tokens on the host)."""
        snaps = snaps or [None] * len(joins)
        ids, n_valid, tables, lengths = self._round_inputs(cache, joins)
        rows, sb = ids.shape
        n_snap = np.zeros((rows,), np.int32)
        row_slot, snap_slot = np.full((2, rows), cache.state_spare,
                                      np.int32)
        aw = self._attn_work
        for i, ((row, _), snap) in enumerate(zip(joins, snaps)):
            n, pos = int(n_valid[i]), int(lengths[i])
            if pos % cache.page:
                raise ValueError(
                    f"a suffix starts at a page boundary; row {row} holds "
                    f"{pos} tokens")
            row_slot[i] = row
            if snap is not None:
                if not pos < snap[1] <= pos + n \
                        or (snap[1] - pos) % self.snap_granule:
                    raise ValueError(f"a snapshot at {snap[1]} outside "
                                     f"{pos}..{pos + n}")
                snap_slot[i], n_snap[i] = snap[0], snap[1] - pos
            aw["prefill_keys"] += n * pos + n * (n + 1) // 2
            aw["prefill_kv"] += pos + n
        self._rng, sub = jax.random.split(self._rng)
        pools, states, logits, toks, live = self._suffix_rows_program(
            rows, sb)(
            self.params, self._pools(cache)["full"], cache.states,
            *(jnp.asarray(a) for a in (tables, lengths, ids, n_valid,
                                       row_slot, n_snap, snap_slot)), sub)
        mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
        self._keep(cache, {"full": pools})
        cache.states = states
        self._prefill_live.append(live)
        for i, (row, _) in enumerate(joins):
            cache.lengths[row] += int(n_valid[i])
        toks = np.asarray(toks)[:len(joins)]
        close_mark(mark)
        return logits, toks

    # -- decode ------------------------------------------------------------

    def _chunk_program(self, n: int, bp: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, states, tables, lengths, rng, fresh,
                    fresh_mask, carry, audit_rows):
                toks0 = jnp.where(fresh_mask, fresh, carry)
                row = jnp.clip(audit_rows, 0, bp - 1)     # a row a lane

                def step(carry_s, _):
                    pools, states, lengths, rng, toks, slots, counts = \
                        carry_s
                    x, pools, states, s, c = forward_decode(
                        cfg, params, toks, pools, states, tables,
                        lengths, interpret=interp)
                    logits = _head(cfg, params, x)
                    rng, sub = jax.random.split(rng)
                    nxt = _sample_rows(sub, logits, top_p, temp)
                    return ((pools, states, lengths + 1, rng, nxt,
                             slots + s, counts + c), (nxt, logits[row]))

                zero = jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
                (pools, states, _, _, _, slots, counts), (out, kept) = \
                    jax.lax.scan(step, (pools, states, lengths, rng,
                                        toks0, zero,
                                        jnp.zeros((2,), jnp.int32)),
                                 None, length=n)
                return pools, states, out, out[-1], slots, counts, kept
            return run
        return self._program(("chunk", n, bp, top_p, temp),
                             "paged_chunk", build, donate=(1, 2))

    def paged_decode_chunk_async(self, cache: PagedKVCache, tokens,
                                 n: int, carry=None
                                 ) -> LatentPendingChunk:
        bp = cache.batch
        fresh_mask, toks, carry = self._chunk_inputs(cache, tokens, n,
                                                     carry)
        self._rng, sub = jax.random.split(self._rng)
        live = cache.lengths[cache.lengths > 0].astype(np.int64)
        self._attn_work["decode_keys"] += int(
            (live[:, None] + 1 + np.arange(n)[None, :]).sum())
        pools, states, out, last, slots, counts, kept = \
            self._chunk_program(n, bp)(
                self.params, self._pools(cache)["full"], cache.states,
                self._tables(cache)["full"],
                jnp.asarray(np.array(cache.lengths)), sub,
                jnp.asarray(toks), jnp.asarray(fresh_mask), carry,
                jnp.asarray(self.audit_rows, jnp.int32))
        self._keep(cache, {"full": pools})
        cache.states = states
        self._advance(cache, n)
        return LatentPendingChunk(
            out, last, n, DEVTIME.take_mark(self._devname("paged_chunk")),
            slots, kept, counts)

    # -- warm-up -----------------------------------------------------------

    def _warmup_paged_impl(self, cache: PagedKVCache, chunk: int,
                           max_prompt: int | None) -> None:
        """Every program the lane can dispatch: the suffix widths (the
        first from nothing, so the zeroing runs too), a restore, the
        decode chunk, the page copy."""
        chunk_done = False
        for sb in self.suffix_buckets:
            n = max(1, min(sb, self.cfg.max_len - 1 - chunk))
            self.sample(self.paged_prefill_row(
                cache, np.ones((n,), np.int32), 0))
            if not chunk_done and n + chunk < self.cfg.max_len:
                self.paged_decode_chunk(
                    cache, np.ones((cache.batch,), np.int32), chunk)
                chunk_done = True
            cache.free_row(0)
        self.state_restore(cache, cache.state_spare, 0)
        if self.suffix_buckets[-1] + chunk < self.cfg.max_len:
            self._warm_join_rungs(cache)
        self._warm_cow(cache)
