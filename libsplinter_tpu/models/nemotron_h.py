"""A decoder stack of ONE mixer a layer — a Mamba-2 state-space layer,
a sparse MoE of un-gated relu^2 experts, or grouped-query attention
without positions, by a pattern — as the Nemotron-H family of public
configs describes it (NVIDIA-Nemotron-3-Nano-30B-A3B: `MEMEM*EMEMEM*…`,
23 state-space layers, 23 expert layers, 6 attention layers), served
through the completion daemon's paged lane as one chip's share of a
deployment (models/mla.py holds the share's conventions and the weight
recipe; this module reuses its chunk hand-off, models/kda.py's
state-slot programs and models/afmoe.py's page-group programs).

The layer (x: hidden; matrices without bias; RMSNorm eps `norm_eps`;
pre-norm, ONE norm a layer):

    h = x + Mixer_i(N_i(x)),   Mixer_i by hybrid_override_pattern[i]

M — Mamba-2 (H heads of P, d_inner = H P; G groups of B and C, state
    N, K conv taps; ops/ssd_scan.py):

    [z | xBC | dt] = u W_in                  d_inner | d_inner + 2 G N | H
    xBC = silu(conv_K(xBC) + b_conv)         depthwise, causal
    [x | B | C] = xBC                        head h reads group h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        float32
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    Mixer = groupRMSNorm_{d_inner / G}(y * silu(z)) W_out     (gate first)

    What a ROW carries between tokens is S (H x P x N, float32) and
    the last K - 1 inputs of the convolution (K - 1, d_inner + 2 G N) —
    a fixed size, whatever the context: `SsmMoeConfig.page_layout`
    describes it as the layer's `state`, and PagedKVCache keeps it in
    STATE SLOTS (one a live row, the rest snapshots the prefix tree
    owns).

E — experts: s = sigmoid(u W_r) in float32, the top-k of s + b (the
    bias enters the SELECTION only), gates the selected s over their
    sum (`norm_topk_prob`) times `routed_scaling_factor`;
    Mixer = sum_e g_e relu(u Wup_e)^2 Wdown_e + relu(u Wup_s)^2 Wdown_s
    (moe.sparse_moe without a gate matrix).  An expert layer carries
    no cache at all.

* — attention: q = u W_Q (heads x d), k, v = u W_K, u W_V (kv_heads x
    d), NO rotary embedding and no other position signal, causal
    softmax at d^-0.5 over every earlier token, W_O.  K and V live in
    ONE page group, a token a row — (n_blocks, L, kv_heads, page, d).

PROGRAMS: kimi's contract (models/kda.py) — ONE prefill program, the
suffix prefill from (pages + state) in whole-page widths (1, 2, 4 and
8 pages: a cold prompt of up to 1,024 tokens is one call), which also
writes into a slot the caller names the state as it stood after
`n_snap` of its tokens; a prompt the prefix cache does not know is the
same program from a zeroed state and an empty table.  The decode chunk
is mla's, n steps with the sampler in graph, and beside the slots each
expert received it counts the experts that received any and the
selections the bias changed (LatentPendingChunk.counts).

WEIGHTS: mla.seed_tensor, names `layers.<i>.<tensor>`, the scaled
recipe of models/kda.py (every matrix that writes INTO the residual
stream — w_out, w_o, shared.down, experts.<e>.down — at std / sqrt(2 x
the whole model's layers)).  This family's own, from the config's
keys, restated by the plain reference:
    dt_bias (H,) float32: softplus^-1(dt), dt = exp(u (log
          time_step_max - log time_step_min) + log time_step_min)
          floored at time_step_floor, u uniform on [0, 1)
    a_log (H,) float32:  log(1 + 15 u), u uniform on [0, 1): A in
          -[1, 16)
    d_skip (H,) float32: 1
    conv  (K, d_inner + 2 G N) float32 taps, std 1/sqrt(K); conv_bias
          float32, std CONV_BIAS_STD
    router_bias (experts,) float32, std BIAS_STD, mean 0 (models/
          lfm2.py: NON-zero so that the mechanism is served).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import DEVTIME
from ..ops.paged_attention import kv_append, window_paged_attention
from ..ops.ssd_scan import CHUNK, ssd_chunk_prefill, ssd_decode_step
from .afmoe import GroupPagePrograms
from .decoder import PageLayout, PagedKVCache, _sample_rows
from .kda import StateSlotPrograms, _head, _normed
from .mla import (LatentCompletionModel, LatentPendingChunk, _rms,
                  _sum_slots, seed_tensor)
from .moe import router_bias_swaps, sparse_moe

KINDS = ("ssm", "moe", "full")
PATTERN = {"M": "ssm", "E": "moe", "*": "full"}
# pages of the suffix programs' widths: a cold prompt of up to 8 pages
# is one call, padded by less than its own length
SUFFIX_PAGES = (1, 2, 4, 8)
BIAS_STD = 0.015
CONV_BIAS_STD = 0.1
# audit lanes by the answer's budget as a share of the daemon's: at or
# under SHORT_SHARE lane 0, at or over LONG_SHARE lane 2, between them
# lane 1 (of a daemon's 512: 96 and 192)
SHORT_SHARE, LONG_SHARE = 3 / 16, 3 / 8


@dataclasses.dataclass(frozen=True)
class SsmMoeConfig:
    vocab_size: int               # rows of the vocabulary held here
    hidden: int
    kinds: tuple[str, ...]        # a kind ("ssm" | "moe" | "full") a layer
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int               # groups of B and C (n_groups)
    ssm_state: int
    conv_kernel: int
    moe_mlp_dim: int
    shared_mlp_dim: int
    n_routed_experts: int         # the router's width: ALL experts
    top_k: int
    chunk: int = CHUNK
    experts_first: int = 0
    experts_held: int | None = None
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    score_fn: str = "sigmoid"
    expert_bias: bool = True      # a selection bias a routed expert
    expert_bias_std: float = BIAS_STD
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    vocab_first: int = 0
    rms_eps: float = 1e-5
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # layers of the WHOLE model (the share may keep fewer): what the
    # seeded output projections are scaled by (module docstring)
    model_layers: int | None = None
    dense_layers: int = 0         # the pattern's `-` layers: not served

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
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
        if set(self.kinds) - set(KINDS) or "full" not in self.kinds \
                or self.dense_layers:
            raise ValueError(f"layer kinds must be among {KINDS}, with "
                             "at least one attention layer (the cache "
                             "keeps pages) and no dense layer")
        if self.heads % self.kv_heads or self.conv_kernel < 2 \
                or self.ssm_heads % self.ssm_groups:
            raise ValueError("kv_heads must divide heads, n_groups divide "
                             "mamba_num_heads, and conv_kernel be >= 2")

    @classmethod
    def tiny(cls, **kw) -> "SsmMoeConfig":
        """Small config for tests and CPU rehearsals: the head of the
        published pattern and one period."""
        kw = {"vocab_size": 512, "hidden": 64,
              "kinds": tuple(PATTERN[c] for c in "MEMEM*EMEMEM*"),
              "heads": 4, "kv_heads": 2, "head_dim": 16, "ssm_heads": 4,
              "ssm_head_dim": 8, "ssm_groups": 2, "ssm_state": 16,
              "conv_kernel": 4, "chunk": 16, "moe_mlp_dim": 32,
              "shared_mlp_dim": 64, "n_routed_experts": 8, "top_k": 2,
              "routed_scaling_factor": 2.5, "max_len": 256, **kw}
        return cls(**kw)

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    def group_index(self, i: int) -> int:
        """Layer i's index among the layers of its kind."""
        return sum(k == self.kinds[i] for k in self.kinds[:i])

    def page_layout(self, page: int) -> tuple[PageLayout, ...]:
        """The attention layers' K and V side by side in ONE page
        group, a token a row; a state-space layer no pool at all and
        its state a row's; an expert layer nothing."""
        n, kh, d = self.kinds.count("full"), self.kv_heads, self.head_dim
        group = PageLayout((("k", (n, kh, page, d)),
                            ("v", (n, kh, page, d))),
                           token_values=n * kh * 2 * d, layers=n)
        state = PageLayout((), token_values=0, state=(
            ("s", (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
             self.state_dtype),
            ("conv", (self.conv_kernel - 1, self.conv_width), self.dtype)))
        nothing = PageLayout((), token_values=0)
        return (group,) + (state,) * self.kinds.count("ssm") \
            + (nothing,) * self.kinds.count("moe")


# ------------------------------------------------------------- weights

def init_params(cfg: SsmMoeConfig, seed: int) -> dict:
    """The resident tree of this share, tensor by tensor."""
    H, dt, d, f32 = cfg.hidden, cfg.dtype, cfg.head_dim, jnp.float32
    out_scale = 1.0 / math.sqrt(2.0 * cfg.model_layers)

    def mat(name, shape, scale=1.0):
        return seed_tensor(seed, name, shape,
                           scale / math.sqrt(shape[0]), dt)

    def out(name, shape):             # writes into the residual stream
        return mat(name, shape, out_scale)

    def norm(name, n):
        return seed_tensor(seed, name, (n,), 0.1, f32, 1.0)

    def unit(name, n):                # u uniform on [0, 1)
        return seed_tensor(seed, name, (n,), 1.0 / math.sqrt(12.0), f32,
                           0.5)

    layers = []
    for i, kind in enumerate(cfg.kinds):
        p = f"layers.{i}."
        lp = {"ln_in": norm(p + "ln_in", H)}
        if kind == "ssm":
            DI, CW, SH = cfg.d_inner, cfg.conv_width, cfg.ssm_heads
            lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)
            step = jnp.maximum(jnp.exp(unit(p + "dt_bias", SH) * (hi - lo)
                                       + lo), cfg.time_step_floor)
            lp.update({
                "w_in": mat(p + "w_in", (H, DI + CW + SH)),
                "conv": seed_tensor(seed, p + "conv", (cfg.conv_kernel, CW),
                                    1.0 / math.sqrt(cfg.conv_kernel), f32),
                "conv_bias": seed_tensor(seed, p + "conv_bias", (CW,),
                                         CONV_BIAS_STD, f32),
                # softplus^-1 of the step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(1.0 + 15.0 * unit(p + "a_log", SH)),
                "d_skip": jnp.ones((SH,), f32),
                "ln_gate": norm(p + "ln_gate", DI),
                "w_out": out(p + "w_out", (DI, H))})
        elif kind == "full":
            lp.update({
                "w_q": mat(p + "w_q", (H, cfg.heads * d)),
                "w_k": mat(p + "w_k", (H, cfg.kv_heads * d)),
                "w_v": mat(p + "w_v", (H, cfg.kv_heads * d)),
                "w_o": out(p + "w_o", (cfg.heads * d, H))})
        else:
            M, MS = cfg.moe_mlp_dim, cfg.shared_mlp_dim
            lp["router"] = seed_tensor(seed, p + "router",
                                       (H, cfg.n_routed_experts),
                                       1.0 / math.sqrt(H), f32)
            if cfg.expert_bias:
                lp["router_bias"] = seed_tensor(
                    seed, p + "router_bias", (cfg.n_routed_experts,),
                    cfg.expert_bias_std, f32)
            if cfg.n_shared_experts:
                lp["shared_up"] = mat(p + "shared.up", (H, MS))
                lp["shared_down"] = out(p + "shared.down", (MS, H))
            held = range(cfg.experts_first,
                         cfg.experts_first + cfg.experts_held)
            # an output column a ROW, (held, M, H): a width M that is
            # no whole number of 128-lane tiles (1,856) would lie off
            # the lanes on the chip and be copied for the grouped
            # product at every call (moe.grouped_matmul transpose_rhs)
            lp["exp_up"] = jnp.stack([
                mat(f"{p}experts.{e}.up", (H, M)).T for e in held])
            lp["exp_down"] = jnp.stack([
                out(f"{p}experts.{e}.down", (M, H)) for e in held])
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

def _ssm_proj(cfg: SsmMoeConfig, lp, xn):
    """xn: (..., hidden) normed.  Returns (z (..., d_inner) the gate,
    xBC (..., conv_width) the convolution's input in the model's dtype,
    dt (..., H) float32 the step after softplus)."""
    DI, CW = cfg.d_inner, cfg.conv_width
    zxd = jnp.dot(xn, lp["w_in"])
    dt = jax.nn.softplus(zxd[..., DI + CW:].astype(jnp.float32)
                         + lp["dt_bias"])
    return zxd[..., :DI], zxd[..., DI: DI + CW], dt


def _ssm_split(cfg: SsmMoeConfig, lp, conv):
    """conv: (..., conv_width) float32, the taps' sum.  Returns x (...,
    H, P), B and C (..., G, N), float32."""
    DI, G, N = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    y = jax.nn.silu(conv + lp["conv_bias"])
    lead = y.shape[:-1]
    return (y[..., :DI].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            y[..., DI: DI + G * N].reshape(*lead, G, N),
            y[..., DI + G * N:].reshape(*lead, G, N))


def _ssm_out(cfg: SsmMoeConfig, lp, y, x, z):
    """y: (..., H, P) float32 the scan's output; x its input (the skip
    D x); z: (..., d_inner) the gate.  Gate first, then the RMS norm
    over each of the G groups of d_inner / G, then W_out, float32."""
    lead, G = y.shape[:-2], cfg.ssm_groups
    y = (y + lp["d_skip"][:, None] * x).reshape(*lead, cfg.d_inner) \
        * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*lead, G, cfg.d_inner // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg.rms_eps)
    y = yg.reshape(*lead, cfg.d_inner) * lp["ln_gate"]
    return jnp.dot(y.astype(cfg.dtype), lp["w_out"],
                   preferred_element_type=jnp.float32)


def _attn_mix(cfg: SsmMoeConfig, lp, xn, pools, write, gl, tables,
              att_len, interpret: bool):
    """The attention mixer over the page group: project, put the new
    tokens' K and V into their pages, attend — no position enters.
    xn: (B, S, hidden) normed; pools: (k, v).  Returns ((B, S, hidden)
    float32, pools)."""
    B, S, _ = xn.shape
    d = cfg.head_dim

    def proj(w, heads):
        return jnp.dot(xn, w).reshape(B, S, heads, d)
    kp, vp = pools
    kp = write(kp, proj(lp["w_k"], cfg.kv_heads).astype(kp.dtype), gl)
    vp = write(vp, proj(lp["w_v"], cfg.kv_heads).astype(vp.dtype), gl)
    o = window_paged_attention(
        proj(lp["w_q"], cfg.heads).astype(cfg.dtype), kp, vp, tables,
        att_len, layer=gl, interpret=interpret)
    return jnp.dot(o.reshape(B, S, cfg.heads * d), lp["w_o"],
                   preferred_element_type=jnp.float32), (kp, vp)


def _experts(cfg: SsmMoeConfig, lp, xn, live, interpret: bool):
    """The expert mixer.  xn: (..., hidden) the normed stream, float32
    — the router reads it unrounded (moe.sparse_moe route_x).  Returns (the mixer's
    output float32, slots each held expert received, (2,) int32
    [experts that received any, selections the bias changed])."""
    shared = (None, lp["shared_up"], lp["shared_down"]) \
        if "shared_up" in lp else None
    f, slots = sparse_moe(
        xn.astype(cfg.dtype), lp["router"], None, lp["exp_up"],
        lp["exp_down"], top_k=cfg.top_k, first=cfg.experts_first,
        score=cfg.score_fn, norm_topk=cfg.norm_topk_prob,
        scale=cfg.routed_scaling_factor, shared=shared, live=live,
        interpret=interpret, route_x=xn, bias=lp.get("router_bias"),
        up_rows=True)
    swaps = router_bias_swaps(
        xn.reshape(-1, cfg.hidden), lp["router"], lp["router_bias"],
        live.reshape(-1), top_k=cfg.top_k, score=cfg.score_fn
    ) if "router_bias" in lp else jnp.int32(0)
    return f.astype(jnp.float32), slots, jnp.stack(
        [jnp.sum(slots > 0, dtype=jnp.int32), swaps])


def forward_decode(cfg: SsmMoeConfig, params, toks, pools, states,
                   tables, lengths, *, interpret: bool = False):
    """One new token a row: batch row b over state slot b and the
    pages its table maps.  toks: (B,); pools: (k, v), each (n_blocks,
    L, kv_heads, page, d); states: [[S (slots, H, P, N), conv (slots,
    K - 1, conv_width)]] a state-space layer; tables: (B, P); lengths:
    (B,).  The residual stream stays float32 from the embedding to the
    head (models/kda.py).  Returns (hidden (B, hidden), pools, states,
    slots each held expert received, counts)."""
    B = toks.shape[0]
    page = pools[0].shape[3]
    pos = jnp.minimum(lengths, cfg.max_len - 1).astype(jnp.int32)
    bids = jnp.take_along_axis(tables, (pos // page)[:, None], axis=1)
    offs = pos % page
    live = (lengths > 0)[:, None]

    def write(pool, new, gl):
        return kv_append(pool, new[:, 0], bids[:, 0], offs, layer=gl,
                         interpret=interpret)
    x = params["tok_emb"][toks][:, None].astype(jnp.float32)  # (B, 1, H)
    new_states, slots, counts = [], [], []
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        if kind == "moe":
            a, s, c = _experts(cfg, lp, _rms(x, lp["ln_in"], cfg.rms_eps),
                               live, interpret)
            slots.append(s)
            counts.append(c)
        elif kind == "ssm":
            xn = _normed(cfg, x, lp["ln_in"])[:, 0]
            st, conv = states[len(new_states)]
            z, xbc, dt = _ssm_proj(cfg, lp, xn)
            win = jnp.concatenate([conv[:B], xbc[:, None]], 1)
            xs, bm, cm = _ssm_split(cfg, lp, jnp.sum(
                win.astype(jnp.float32) * lp["conv"][None], 1))
            y, st = ssd_decode_step(xs, dt, -jnp.exp(lp["a_log"]), bm, cm,
                                    st, interpret=interpret)
            new_states.append([st, conv.at[:B].set(win[:, 1:])])
            a = _ssm_out(cfg, lp, y, xs, z)[:, None]
        else:
            a, pools = _attn_mix(cfg, lp, _normed(cfg, x, lp["ln_in"]),
                                 pools, write, cfg.group_index(i), tables,
                                 pos + 1, interpret)
        x = x + a
    return (x[:, 0], pools, new_states, _sum_slots(cfg, slots),
            sum(counts, jnp.zeros((2,), jnp.int32)))


def forward_suffix(cfg: SsmMoeConfig, params, ids, pools, states, table,
                   length, n_valid, row, n_snap, snap_slot, *,
                   interpret: bool = False):
    """S new tokens of ONE row atop the `length` tokens its table maps
    — whole pages of them — and the state in slot `row` (a prompt from
    nothing: length 0, a zeroed slot).  ids: (1, S) padded to whole
    pages, n_valid real; the state after the first n_snap tokens (whole
    chunks) goes to slot `snap_slot`.  Returns (hidden (1, S, hidden),
    pools, states, (2,) int32 [the experts that received a slot, the
    slots the held experts received], summed over the expert
    layers)."""
    S = ids.shape[1]
    page = pools[0].shape[3]
    n_p = S // page
    pos = jnp.minimum(length[:, None] + jnp.arange(S)[None, :],
                      cfg.max_len - 1).astype(jnp.int32)
    ok = jnp.arange(S)[None, :] < n_valid                 # (1, S)
    bids = jax.lax.dynamic_slice_in_dim(table[0], length[0] // page, n_p)
    tail = cfg.conv_kernel - 1

    def write(pool, new, gl):         # whole pages, a token a row
        rows = new[0].reshape(n_p, page, *new.shape[2:])
        return pool.at[bids, gl].set(rows.transpose(0, 2, 1, 3))
    x = params["tok_emb"][ids].astype(jnp.float32)
    new_states, live_experts = [], jnp.zeros((2,), jnp.int32)
    for i, (lp, kind) in enumerate(zip(params["layers"], cfg.kinds)):
        if kind == "moe":
            a, s, c = _experts(cfg, lp, _rms(x, lp["ln_in"], cfg.rms_eps),
                               ok, interpret)
            live_experts = live_experts + jnp.stack(
                [c[0], jnp.sum(s, dtype=jnp.int32)])
        elif kind == "ssm":
            xn = _normed(cfg, x, lp["ln_in"])[0]
            st, conv = states[len(new_states)]
            z, xbc, dt = _ssm_proj(cfg, lp, xn)
            full = jnp.concatenate([conv[row], xbc], 0)   # (tail + S, .)
            xs, bm, cm = _ssm_split(cfg, lp, sum(
                full[j: j + S].astype(jnp.float32) * lp["conv"][j]
                for j in range(cfg.conv_kernel)))
            # a padding token neither decays the state nor enters it
            y, s_end, s_snap = ssd_chunk_prefill(
                xs, jnp.where(ok[0][:, None], dt, 0.0),
                -jnp.exp(lp["a_log"]), bm, cm, st[row], n_snap=n_snap,
                chunk=cfg.chunk, dot_dtype=cfg.dtype, interpret=interpret)

            def tail_at(n, full=full):
                return jax.lax.dynamic_slice_in_dim(full, n, tail, 0)
            # the snapshot first: where none is asked for its slot is
            # the spare one, never the row's own
            new_states.append([
                st.at[snap_slot].set(s_snap).at[row].set(s_end),
                conv.at[snap_slot].set(tail_at(n_snap))
                    .at[row].set(tail_at(n_valid))])
            a = _ssm_out(cfg, lp, y, xs, z)[None]
        else:
            a, pools = _attn_mix(cfg, lp, _normed(cfg, x, lp["ln_in"]),
                                 pools, write, cfg.group_index(i), table,
                                 pos[:, 0] + 1, interpret)
        x = x + a
    return x, pools, new_states, live_experts


# ------------------------------------------------------------- front end

class SsmCompletionModel(StateSlotPrograms, GroupPagePrograms,
                         LatentCompletionModel):
    """LatentCompletionModel's paged serving surface over the
    state-space / expert / attention stack: state slots
    (StateSlotPrograms) beside ONE group of key/value pages
    (GroupPagePrograms)."""

    # a lane a class of answer budgets (audit_lane; engine/audit.py): an
    # audited row holds its lane until it finishes, and an answer of
    # 512 tokens would keep every short one out of the sample
    audit_lanes = 3
    program_prefix = "nemotron"
    refused_options = {
        **LatentCompletionModel.refused_options,
        "kv_dtype": "the page group is stored in the model's dtype: "
                    "the int8/int4 page codecs know one pool a layer, "
                    "not a group's",
        "kv_tier_pages": "the host tier's page wire carries key/value "
                         "pools only, and no recurrent state",
        "phase": "the disaggregated hand-off's page wire carries "
                 "key/value pools only, and no recurrent state",
        "tp": "neither the page group nor the state slots are sharded "
              "on a head axis; attention and the state-space layers "
              "are data-parallel in this deployment",
    }

    def __init__(self, cfg: SsmMoeConfig, *, seed: int = 0,
                 params: Any = None, top_p: float = 0.9,
                 temp: float = 0.7, interpret: bool = False):
        super().__init__(
            cfg, seed=seed,
            params=init_params(cfg, seed) if params is None else params,
            top_p=top_p, temp=temp, suffix_buckets=(16,),
            interpret=interpret)
        # what the state-space kernels were asked to do — running
        # totals the heartbeat carries (benchmark/work_ssd.py turns
        # them into the kernels' rooflines): live rows the decode steps
        # stepped and real tokens the suffix pieces scanned, a LAYER's
        # count each — and the experts a suffix piece's tokens reached
        # and the slots they filled, summed over its expert layers (the
        # decode chunk's counts ride LatentPendingChunk)
        self._work = dict.fromkeys(
            ("ssd_decode_rows", "ssd_prefill_tokens", "state_zeroed",
             "prefill_experts_live", "prefill_expert_slots"), 0)
        self._prefill_live: list = []     # device scalars not yet added
        self.audit_rows = [-1] * self.audit_lanes
        self._set_page(128)

    def audit_seat(self, lane: int, row: int) -> None:
        self.audit_rows[lane] = row

    def audit_lane(self, match: int, n_suffix: int,
                   budget_share: float = 1.0) -> int:
        return 0 if budget_share <= SHORT_SHARE \
            else 2 if budget_share >= LONG_SHARE else 1

    @property
    def attn_work(self) -> dict:
        """The totals, the finished suffix pieces' counts folded in (a
        piece's logits are fetched before the next is dispatched, so
        these reads wait for nothing)."""
        pending, self._prefill_live = self._prefill_live, []
        for live, slots in (np.asarray(x) for x in pending):
            self._work["prefill_experts_live"] += int(live)
            self._work["prefill_expert_slots"] += int(slots)
        return self._work

    def _set_page(self, page: int) -> None:
        """The scan's chunk divides the page (a snapshot sits on a page
        boundary) and every suffix width is whole pages: SUFFIX_PAGES
        of them; a longer suffix loops in the widest."""
        self.snap_granule = math.gcd(self.cfg.chunk, page)
        self.suffix_buckets = tuple(
            n * page for n in SUFFIX_PAGES
            if n * page < self.cfg.max_len) or (page,)
        self.buckets = self.suffix_buckets

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None,
                   state_snapshots: int | None = None) -> PagedKVCache:
        self._set_page(page)
        if page % self.cfg.chunk and self.cfg.chunk % page:
            raise ValueError(
                f"the page ({page}) and the scan's chunk "
                f"({self.cfg.chunk}) must divide one another")
        return PagedKVCache(self.cfg, batch, page=page,
                            pool_pages=pool_pages, kv_dtype=kv_dtype,
                            state_snapshots=state_snapshots)

    def state_zero(self, cache: PagedKVCache, row: int):
        super().state_zero(cache, row)
        self._work["state_zeroed"] += 1

    # -- prefill -----------------------------------------------------------

    def _suffix_program(self, sb: int):
        interp = self.interpret
        cfg = dataclasses.replace(self.cfg, chunk=self.snap_granule)

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
        return self._program(("suffix", sb, cfg.chunk), "suffix_prefill",
                             build, donate=(1, 2))

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
        self._work["ssd_prefill_tokens"] += n
        self._prefill_live.append(live)
        return logits

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
        self._work["ssd_decode_rows"] += n * int(
            (cache.lengths > 0).sum())
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
