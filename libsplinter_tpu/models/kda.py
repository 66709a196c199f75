"""A HYBRID decoder stack — gated delta-rule (KDA) layers whose
recurrent state lives beside NoPE latent-attention (MLA) pages — with a
sparse shared-expert MoE, as the Kimi-Linear family of public configs
describes it, served through the completion daemon's paged lane as one
chip's share of an expert-parallel deployment (models/mla.py holds the
share's conventions and the weight recipe; this module reuses its
feed-forward, absorbed attention and chunk hand-off).

The layer (x: hidden; matrices without bias; RMSNorm eps
`rms_norm_eps`; pre-norm, no sandwich):

    h = x + Mix(N1(x));   y = h + FFN(N2(h))

KDA mixer (layers in `linear_attn_config.kda_layers`; H heads of d):

    q = l2norm_d(silu(conv(x Wq)))  k = l2norm_d(silu(conv(x Wk)))
    v = silu(conv(x Wv))      conv: causal depthwise, kernel
                              short_conv_kernel_size, a tap a channel
    g = -exp(A_log_h) * softplus(x Wf_a Wf_b + dt_bias)   (H x d)
    b = sigmoid(x Wb)                                     (H)
    S_t = (I - b k k^T) diag(exp(g)) S_{t-1} + b k v^T;  o = S_t^T q / sqrt(d)
    Mix = (rmsnorm_d(o) * sigmoid(x Wg_a Wg_b)) Wo

    (ops/delta_attention.py: the chunkwise prefill and the one-token
    step).  What a ROW carries between tokens is S (H x d x d, float32)
    and the last kernel-1 inputs of the three convolutions — a fixed
    size, whatever the context: `HybridMoeConfig.page_layout` describes
    it as the layer's `state`, and PagedKVCache keeps it in STATE SLOTS
    (one a live row, the rest snapshots the prefix tree owns).

MLA mixer (layers in `full_attn_layers`): q_h = x WQ_h (no low-rank
step), [c | k_r] = x W_DKV, c = Nkv(c), NO RoPE on either rope-width
part (`mla_use_nope`), scores and values as models/mla.py.  The cache
row is the same `[c | k_r]`, so decode and suffix prefill run through
ops/latent_attention.py unchanged.

FFN: models/mla._ffn — dense SwiGLU in the leading layers, after them
the shared expert + this share of the routed ones.

PROGRAMS.  A model with recurrent state has ONE prefill program, the
chunked suffix prefill from (pages + state): a prompt the prefix cache
does not know is the same program from a zeroed state and an empty
table, looped in its largest bucket — no separate bucket prefill, so a
13-layer stack compiles its suffix widths (one to SUFFIX_PAGES pages)
and the decode chunk and nothing else.  The suffix program also writes, into a slot the caller
names, the state as it stood after `n_snap` of its tokens: the
snapshot at the prompt's last full page, taken without a second pass.

WEIGHTS: mla.seed_tensor, names `layers.<i>.<tensor>`; two recipes of
this family's own, restated by the plain references:
    A_log   (H,) float32:    mean log(16)/2, std log(16)/sqrt(12)  — uniform on [0, log 16]
    dt_bias (H*d,) float32:  mean -4, std 1
and the convolution taps as a (kernel, channels) matrix, std
1/sqrt(kernel).  Every matrix that writes INTO the residual stream
(w_o, w_down, shared.down, experts.<e>.down) has its std divided by
sqrt(2 x the whole model's layers), the usual scaled initialisation of
deep pre-norm stacks: with unit-scale branches a seeded 13-layer stack
amplifies a 0.4% rounding difference to 13-60% of its logits' spread
(measured on the chip, PERF.md), and no reference could tell a fault
from bfloat16.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import DEVTIME, close_mark
from ..ops.delta_attention import (CHUNK, kda_chunk_prefill,
                                   kda_decode_step)
from ..ops.latent_attention import latent_append
from .decoder import PageLayout, PagedKVCache, _sample_rows
from .mla import (LatentCompletionModel, LatentPendingChunk,
                  _absorbed_attention, _ffn, _rms, _sum_slots, ffn_params,
                  seed_tensor)

KINDS = ("kda", "mla")
# pages of the widest suffix program: a follow-up turn of a few hundred
# tokens fits one call, a cold prompt loops in it; the program's
# temporaries grow with it (0.6 GB at 5 x 128 tokens of 13 layers)
SUFFIX_PAGES = 5


@dataclasses.dataclass(frozen=True)
class HybridMoeConfig:
    vocab_size: int               # rows of the vocabulary held here
    hidden: int
    kinds: tuple[str, ...]        # a kind ("kda" | "mla") a kept layer
    heads: int                    # MLA heads
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kda_heads: int
    kda_head_dim: int
    conv_kernel: int
    dense_layers: int             # leading dense layers among `layers`
    dense_mlp_dim: int
    moe_mlp_dim: int
    n_routed_experts: int         # the router's width: ALL experts
    top_k: int
    experts_first: int = 0
    experts_held: int | None = None
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    score_fn: str = "sigmoid"
    vocab_first: int = 0
    rms_eps: float = 1e-5
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # layers of the WHOLE model (the share may keep fewer): what the
    # seeded output projections are scaled by (module docstring)
    model_layers: int | None = None

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
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError("dense_layers must lie in 0..layers")
        if set(self.kinds) - set(KINDS) or not self.kinds:
            raise ValueError(f"layer kinds must be among {KINDS}")

    @classmethod
    def tiny(cls, **kw) -> "HybridMoeConfig":
        """Small config for tests and CPU rehearsals: one period."""
        kw = {"vocab_size": 512, "hidden": 64,
              "kinds": ("kda", "kda", "kda", "mla"), "heads": 4,
              "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16, "kda_heads": 4,
              "kda_head_dim": 16, "conv_kernel": 4, "dense_layers": 1,
              "dense_mlp_dim": 128, "moe_mlp_dim": 32,
              "n_routed_experts": 8, "top_k": 2,
              "routed_scaling_factor": 2.446, "max_len": 128, **kw}
        return cls(**kw)

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def page_layout(self, page: int) -> tuple[PageLayout, ...]:
        """A layout a LAYER: an MLA layer one pool of `[c | k_r]` rows,
        a KDA layer no pool at all and a fixed state a row."""
        d = self.kda_head_dim
        latent = PageLayout((("latent", (self.latent_width, page)),),
                            token_values=self.latent_width)
        state = PageLayout((), token_values=0, state=(
            ("s", (self.kda_heads, d, d), self.state_dtype),
            ("conv", (self.conv_kernel - 1, 3 * self.kda_width),
             self.dtype)))
        return tuple(state if k == "kda" else latent for k in self.kinds)


# ------------------------------------------------------------- weights

def init_params(cfg: HybridMoeConfig, seed: int) -> dict:
    """The resident tree of this share, tensor by tensor."""
    H, dt, KW = cfg.hidden, cfg.dtype, cfg.kda_width
    rank = cfg.kda_head_dim

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
        if kind == "kda":
            lp.update({
                "w_q": mat(p + "w_q", (H, KW)),
                "w_k": mat(p + "w_k", (H, KW)),
                "w_v": mat(p + "w_v", (H, KW)),
                # taps of the three depthwise convolutions, side by
                # side in the order the projections are concatenated
                "conv": jnp.concatenate([
                    seed_tensor(seed, p + f"conv_{n}",
                                (cfg.conv_kernel, KW),
                                1.0 / math.sqrt(cfg.conv_kernel),
                                jnp.float32) for n in "qkv"], -1),
                "w_fa": mat(p + "w_fa", (H, rank)),
                "w_fb": mat(p + "w_fb", (rank, KW)),
                "a_log": seed_tensor(
                    seed, p + "a_log", (cfg.kda_heads,),
                    math.log(16.0) / math.sqrt(12.0), jnp.float32,
                    math.log(16.0) / 2),
                "dt_bias": seed_tensor(seed, p + "dt_bias", (KW,), 1.0,
                                       jnp.float32, -4.0),
                "w_b": mat(p + "w_b", (H, cfg.kda_heads)),
                "w_ga": mat(p + "w_ga", (H, rank)),
                "w_gb": mat(p + "w_gb", (rank, KW)),
                "ln_o": norm(p + "ln_o", cfg.kda_head_dim),
                "w_o": out(p + "w_o", (KW, H)),
            })
        else:
            lp.update({
                "w_q": mat(p + "w_q", (H, cfg.heads * cfg.qk_head_dim)),
                "w_dkv": mat(p + "w_dkv", (H, cfg.latent_width)),
                "ln_kv": norm(p + "ln_kv", cfg.kv_lora_rank),
                "w_ukv": mat(p + "w_ukv", (
                    cfg.kv_lora_rank,
                    cfg.heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
                "w_o": out(p + "w_o", (cfg.heads * cfg.v_head_dim, H)),
            })
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

def _mla_inputs(cfg: HybridMoeConfig, lp, x):
    """x: (B, S, H) normed.  Returns (q_nope, q_rope (B, S, heads, .),
    latent (B, S, kv_rank + rope) = [Nkv(c) | k_r]) — no positions."""
    B, S, _ = x.shape
    q = jnp.dot(x, lp["w_q"]).reshape(B, S, cfg.heads, cfg.qk_head_dim)
    ckr = jnp.dot(x, lp["w_dkv"])
    c = _rms(ckr[..., :cfg.kv_lora_rank], lp["ln_kv"], cfg.rms_eps)
    return (q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:],
            jnp.concatenate([c, ckr[..., cfg.kv_lora_rank:]], -1))


def _mla_mix(cfg: HybridMoeConfig, lp, xn, pool, bids, offs, tables,
             att_len, interpret: bool):
    """The latent layer over the paged pool: append the new tokens'
    rows, attend in the latent space.  xn: (B, S, H) normed.  Returns
    (the mixer's output (B, S, H) float32, the pool)."""
    B, S, _ = xn.shape
    qn, qr, lat = _mla_inputs(cfg, lp, xn)
    pool = latent_append(pool, lat, bids, offs, interpret=interpret)
    o = _absorbed_attention(cfg, lp, qn, qr, pool, tables, att_len,
                            interpret)
    return jnp.dot(o.reshape(B, S, cfg.heads * cfg.v_head_dim), lp["w_o"],
                   preferred_element_type=jnp.float32), pool


def _kda_proj(cfg: HybridMoeConfig, lp, x):
    """x: (..., H) normed.  Returns (the three convolutions' inputs
    side by side (..., 3 KW) in the model's dtype, g (..., KH, d)
    float32 <= 0, beta (..., KH) float32, the output gate (..., KW))."""
    f32 = jnp.float32
    lead = x.shape[:-1]
    cat = jnp.concatenate([jnp.dot(x, lp["w_q"]), jnp.dot(x, lp["w_k"]),
                           jnp.dot(x, lp["w_v"])], -1)
    f = jnp.dot(jnp.dot(x, lp["w_fa"]), lp["w_fb"]).astype(f32)
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        f + lp["dt_bias"]).reshape(*lead, cfg.kda_heads, cfg.kda_head_dim)
    beta = jax.nn.sigmoid(jnp.dot(x, lp["w_b"]).astype(f32))
    gate = jax.nn.sigmoid(
        jnp.dot(jnp.dot(x, lp["w_ga"]), lp["w_gb"]).astype(f32))
    return cat, g, beta, gate


def _kda_qkv(cfg: HybridMoeConfig, conv_out):
    """conv_out: (..., 3 KW) float32 -> q, k (l2-normalised), v, each
    (..., KH, d) float32."""
    y = jax.nn.silu(conv_out).reshape(*conv_out.shape[:-1], 3,
                                      cfg.kda_heads, cfg.kda_head_dim)

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    return l2(y[..., 0, :, :]), l2(y[..., 1, :, :]), y[..., 2, :, :]


def _kda_out(cfg: HybridMoeConfig, lp, o, gate):
    """o: (..., KH, d) float32 -> the mixer's output (..., H)."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_eps) \
        * lp["ln_o"]
    o = o.reshape(*o.shape[:-2], cfg.kda_width) * gate
    return jnp.dot(o.astype(cfg.dtype), lp["w_o"],
                   preferred_element_type=jnp.float32)


def _normed(cfg, x, scale):
    """RMSNorm of the float32 residual stream, handed to the matrix
    products in the model's dtype."""
    return _rms(x, scale, cfg.rms_eps).astype(cfg.dtype)


def _finish_layer(cfg, lp, x, a, live, interpret):
    """The residual stream x stays float32 from the embedding to the
    head: only what feeds a matrix product is rounded to the model's
    dtype.  (Thirteen layers of bfloat16 residual adds read 0.13-0.6
    against the float32 reference on the chip, PERF.md.)"""
    h = x + a.astype(jnp.float32)
    f, slots = _ffn(cfg, lp, _normed(cfg, h, lp["ln_mlp_in"]), live,
                    interpret)
    return h + f.astype(jnp.float32), slots


def _head(cfg, params, x):
    """Final norm + untied head over the vocabulary slice, float32."""
    return jnp.dot(_normed(cfg, x, params["ln_out"]), params["lm_head"],
                   preferred_element_type=jnp.float32)


def forward_decode(cfg: HybridMoeConfig, params, toks, pools, states,
                   tables, lengths, *, interpret: bool = False):
    """One new token a row: every batch row b over state slot b and
    the pages its table maps.  toks: (B,); pools: a latent pool an MLA
    layer; states: [S (slots, KH, d, d), conv (slots, K-1, 3 KW)] a KDA
    layer; tables: (B, P); lengths: (B,).  Returns (hidden (B, H), new
    pools, new states, slots each held expert received)."""
    B = toks.shape[0]
    page = pools[0].shape[2] if pools else 1
    pos = jnp.minimum(lengths, cfg.max_len - 1).astype(jnp.int32)
    bids = jnp.take_along_axis(tables, (pos // page)[:, None], axis=1)
    offs = (pos % page)[:, None]
    live = (lengths > 0)[:, None]
    x = params["tok_emb"][toks][:, None].astype(jnp.float32)  # (B, 1, H)
    scale = 1.0 / math.sqrt(cfg.kda_head_dim)
    new_pools, new_states, slots = [], [], []
    for lp, kind in zip(params["layers"], cfg.kinds):
        xn = _normed(cfg, x, lp["ln_mix_in"])
        if kind == "kda":
            s, conv = states[len(new_states)]
            cat, g, beta, gate = _kda_proj(cfg, lp, xn[:, 0])
            win = jnp.concatenate([conv[:B], cat[:, None]], 1)
            q, k, v = _kda_qkv(cfg, jnp.sum(
                win.astype(jnp.float32) * lp["conv"][None], 1))
            o, s = kda_decode_step(q, k, v, g, beta, s, scale=scale,
                                   interpret=interpret)
            new_states.append([s, conv.at[:B].set(win[:, 1:])])
            a = _kda_out(cfg, lp, o, gate)[:, None]
        else:
            a, pool = _mla_mix(cfg, lp, xn, pools[len(new_pools)], bids,
                               offs, tables, pos + 1, interpret)
            new_pools.append(pool)
        x, s_ = _finish_layer(cfg, lp, x, a, live, interpret)
        slots.append(s_)
    return x[:, 0], new_pools, new_states, _sum_slots(cfg, slots)


def forward_suffix(cfg: HybridMoeConfig, params, ids, pools, states,
                   table, length, n_valid, row, n_snap, snap_slot, *,
                   chunk: int = CHUNK, interpret: bool = False):
    """S new tokens of ONE row atop the `length` tokens its table maps
    and the state in slot `row` (a prompt from nothing: length 0, a
    zeroed slot).  ids: (1, S) padded to a bucket, n_valid real; the
    state after the first n_snap tokens (a multiple of `chunk`) goes
    to slot `snap_slot`.  Returns (hidden (1, S, H), pools, states)."""
    S = ids.shape[1]
    page = pools[0].shape[2] if pools else 1
    pos = jnp.minimum(length[:, None] + jnp.arange(S)[None, :],
                      cfg.max_len - 1).astype(jnp.int32)
    ok = jnp.arange(S)[None, :] < n_valid                 # (1, S)
    bids = jnp.where(ok, jnp.take_along_axis(table, pos // page, axis=1),
                     0)
    offs = pos % page
    att_len = pos[:, 0] + 1
    x = params["tok_emb"][ids].astype(jnp.float32)
    scale = 1.0 / math.sqrt(cfg.kda_head_dim)
    tail = cfg.conv_kernel - 1
    new_pools, new_states = [], []
    for lp, kind in zip(params["layers"], cfg.kinds):
        xn = _normed(cfg, x, lp["ln_mix_in"])
        if kind == "kda":
            s, conv = states[len(new_states)]
            cat, g, beta, gate = _kda_proj(cfg, lp, xn[0])
            full = jnp.concatenate([conv[row], cat], 0)   # (tail + S, .)
            taps = lp["conv"]
            y = sum(full[j: j + S].astype(jnp.float32) * taps[j]
                    for j in range(cfg.conv_kernel))
            q, k, v = _kda_qkv(cfg, y)
            okt = ok[0]
            o, s_end, s_snap = kda_chunk_prefill(
                q, k, v, jnp.where(okt[:, None, None], g, 0.0),
                jnp.where(okt[:, None], beta, 0.0), s[row], scale=scale,
                n_snap=n_snap, chunk=chunk, interpret=interpret)

            def tail_at(n):
                return jax.lax.dynamic_slice_in_dim(full, n, tail, 0)
            # the snapshot first: where no snapshot is asked for its
            # slot is the spare one, never the row's own
            new_states.append([
                s.at[snap_slot].set(s_snap).at[row].set(s_end),
                conv.at[snap_slot].set(tail_at(n_snap))
                    .at[row].set(tail_at(n_valid))])
            a = _kda_out(cfg, lp, o, gate)[None]
        else:
            a, pool = _mla_mix(cfg, lp, xn, pools[len(new_pools)], bids,
                               offs, table, att_len, interpret)
            new_pools.append(pool)
        x, _ = _finish_layer(cfg, lp, x, a, ok, interpret)
    return x, new_pools, new_states


# ------------------------------------------------------------- front end

class StateSlotPrograms:
    """What a cache with STATE SLOTS asks of its model, whatever the
    state is (this module's recurrent matrix and convolution tails,
    models/lfm2.py's two-token register): state_restore / state_zero
    over every layer's arrays, and the suffix prefill's loop — pieces
    of the widest suffix width from (pages + state), the piece that
    passes `snap_at` leaving the snapshot.  The family brings
    `_suffix_piece`, the dispatch of ONE piece, and `snap_granule`,
    the token count a snapshot's distance from the mapped length is a
    multiple of.  The warm-up below runs every program such a lane can
    dispatch; a family with more of them (models/lfm2.py's rungs)
    brings its own."""

    needs_state = True
    snap_granule = 1
    # one row a suffix program, until the family's recurrence has a row
    # axis (ROADMAP.md A1): an admission round then joins a request at
    # a time.  A family that has one (models/lfm2.py) names its rows
    # and brings `paged_append_prefill_rows(cache, joins, snaps)` —
    # every row's state restored into its slot BEFORE the call
    # (state_restore, a dispatch a row at its seat), each row's end
    # state and snapshot written by the program, the spare slot for a
    # row that leaves none
    JOIN_ROWS = ()

    def _state_program(self, short: str, body):
        def build():
            def run(states, *a):
                return [[body(arr, *a) for arr in layer]
                        for layer in states]
            return run
        return self._program((short,), short, build, donate=(0,))

    def state_restore(self, cache: PagedKVCache, src: int, row: int):
        """Copy snapshot slot `src` into `row`'s slot (a prefix hit)."""
        fn = self._state_program(
            "state_copy", lambda a, src, dst: a.at[dst].set(a[src]))
        cache.states = fn(cache.states, jnp.int32(src), jnp.int32(row))

    def state_zero(self, cache: PagedKVCache, row: int):
        """A prompt from nothing starts from the zero state."""
        fn = self._state_program(
            "state_zero",
            lambda a, dst: a.at[dst].set(jnp.zeros_like(a[0])))
        cache.states = fn(cache.states, jnp.int32(row))

    def paged_prefill_row(self, cache: PagedKVCache,
                          prompt_ids: np.ndarray, row: int, *,
                          snap_at: int | None = None,
                          snap_slot: int | None = None) -> np.ndarray:
        """A whole prompt from nothing: the zero state, an empty table,
        and the suffix program over it."""
        if len(prompt_ids) == 0:
            raise ValueError("empty prompt")
        cache.lengths[row] = 0
        self.state_zero(cache, row)
        return self.paged_append_prefill(cache, prompt_ids, row,
                                         snap_at=snap_at,
                                         snap_slot=snap_slot)

    def paged_append_prefill(self, cache: PagedKVCache, suffix_ids,
                             row: int, *, snap_at: int | None = None,
                             snap_slot: int | None = None) -> np.ndarray:
        """Prefill the suffix of row's prompt atop the
        cache.lengths[row] tokens its table maps and the state in its
        slot.  With `snap_at` (a token count of the whole prompt, whole
        granules past the mapped length) the state after that many
        tokens is left in state slot `snap_slot`.  Returns the last
        real token's logits (V,)."""
        ids = np.asarray(suffix_ids, np.int32)
        if ids.size == 0:
            raise ValueError("empty suffix")
        pos = int(cache.lengths[row])
        if pos + ids.size >= self.cfg.max_len:
            raise ValueError("suffix exceeds context window")
        if snap_at is not None and (
                snap_slot is None or not pos < snap_at <= pos + ids.size
                or (snap_at - pos) % self.snap_granule):
            raise ValueError(
                f"a snapshot at {snap_at} is not whole chunks of "
                f"{self.snap_granule} inside {pos}..{pos + ids.size}")
        if not cache.ensure(row, pos + ids.size):
            raise RuntimeError(
                f"paged pool exhausted: row {row} suffix needs "
                f"{cache.pages_needed(pos + ids.size)} pages")
        spare = cache.state_spare
        logits, mark, off = None, None, 0
        while off < ids.size:
            rem = ids.size - off
            sb = next((b for b in self.suffix_buckets if b >= rem),
                      self.suffix_buckets[-1])
            n = min(rem, sb)
            piece = np.zeros((1, sb), np.int32)
            piece[0, :n] = ids[off: off + n]
            here = snap_at is not None and pos + off < snap_at <= pos + off + n
            logits = self._suffix_piece(
                cache, row, sb, piece, n,
                snap_at - pos - off if here else 0,
                snap_slot if here else spare)
            close_mark(mark)
            mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
            cache.lengths[row] += n
            off += n
        out = np.asarray(logits)
        close_mark(mark)
        return out

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
        self._warm_cow(cache)


class HybridCompletionModel(StateSlotPrograms, LatentCompletionModel):
    """LatentCompletionModel's paged serving surface over the hybrid
    stack, plus what a cache with state slots asks of its model
    (StateSlotPrograms)."""

    program_prefix = "hybrid"
    refused_options = {
        **LatentCompletionModel.refused_options,
        "kv_tier_pages": "the host tier's page wire carries key/value "
                         "pools only, and no recurrent state",
        "phase": "the disaggregated hand-off's page wire carries "
                 "key/value pools only, and no recurrent state",
        "tp": "latent pools and recurrent state have no kv-head axis to "
              "shard; both mixers are data-parallel in this deployment",
    }

    def __init__(self, cfg: HybridMoeConfig, *, seed: int = 0,
                 params: Any = None, top_p: float = 0.9,
                 temp: float = 0.7, interpret: bool = False):
        super().__init__(
            cfg, seed=seed,
            params=init_params(cfg, seed) if params is None else params,
            top_p=top_p, temp=temp, suffix_buckets=(16,),
            interpret=interpret)
        self._set_page(128)

    def _set_page(self, page: int) -> None:
        """The chunk of the delta rule divides the page (a snapshot
        sits on a page boundary) and every suffix bucket is whole
        pages: each width from one page to SUFFIX_PAGES, so a suffix
        pads by less than a page in every layer; a longer one (a cold
        prompt) loops in the widest."""
        self.kda_chunk = self.snap_granule = math.gcd(CHUNK, page)
        self.suffix_buckets = tuple(
            n * page for n in range(1, SUFFIX_PAGES + 1)
            if n * page < self.cfg.max_len) or (self.kda_chunk,)
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
        cfg, interp, chunk = self.cfg, self.interpret, self.kda_chunk

        def build():
            def run(params, pools, states, table, length, ids, n_valid,
                    row, n_snap, snap_slot):
                x, pools, states = forward_suffix(
                    cfg, params, ids, pools, states, table, length,
                    n_valid, row, n_snap, snap_slot, chunk=chunk,
                    interpret=interp)
                last = jax.lax.dynamic_index_in_dim(
                    x[0], n_valid - 1, 0, keepdims=False)
                return pools, states, _head(cfg, params, last)
            return run
        return self._program(("suffix", sb, chunk), "suffix_prefill",
                             build, donate=(1, 2))

    def _suffix_piece(self, cache: PagedKVCache, row: int, sb: int,
                      piece, n: int, n_snap: int, snap_slot: int):
        pools, states, logits = self._suffix_program(sb)(
            self.params, cache.pools[0], cache.states,
            # host-side copies: lengths is bumped right after
            # (mla.paged_append_prefill)
            jnp.asarray(np.array(cache.tables[row: row + 1])),
            jnp.asarray(np.array(cache.lengths[row: row + 1])),
            jnp.asarray(piece), jnp.int32(n), jnp.int32(row),
            jnp.int32(n_snap), jnp.int32(snap_slot))
        cache.pools[0] = list(pools)
        cache.states = states
        return logits

    # -- decode ------------------------------------------------------------

    def _chunk_program(self, n: int, bp: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, states, tables, lengths, rng, fresh,
                    fresh_mask, carry, audit_row):
                toks0 = jnp.where(fresh_mask, fresh, carry)
                row = jnp.clip(audit_row, 0, bp - 1)

                def step(carry_s, _):
                    pools, states, lengths, rng, toks, slots = carry_s
                    x, pools, states, s = forward_decode(
                        cfg, params, toks, pools, states, tables,
                        lengths, interpret=interp)
                    logits = _head(cfg, params, x)
                    rng, sub = jax.random.split(rng)
                    nxt = _sample_rows(sub, logits, top_p, temp)
                    return ((pools, states, lengths + 1, rng, nxt,
                             slots + s), (nxt, logits[row]))

                zero = jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
                (pools, states, _, _, _, slots), (out, kept) = \
                    jax.lax.scan(step, (pools, states, lengths, rng,
                                        toks0, zero), None, length=n)
                return pools, states, out, out[-1], slots, kept
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
        pools, states, out, last, slots, kept = self._chunk_program(
            n, bp)(
            self.params, cache.pools[0], cache.states,
            jnp.asarray(np.array(cache.tables)),
            jnp.asarray(np.array(cache.lengths)), sub, jnp.asarray(toks),
            jnp.asarray(fresh_mask), carry, jnp.int32(self.audit_row))
        cache.pools[0] = list(pools)
        cache.states = states
        self._advance(cache, n)
        return LatentPendingChunk(
            out, last, n, DEVTIME.take_mark(self._devname("paged_chunk")),
            slots, kept)
