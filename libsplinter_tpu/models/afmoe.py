"""A decoder stack whose layers MIX sliding-window and global
attention over grouped key/value heads, with a sparse (shared-expert
or not) MoE, served through the completion daemon's paged lane as one
chip's share of an expert-parallel deployment (models/mla.py holds the
share's conventions and the weight recipe; this module reuses its
feed-forward and its chunk hand-off).  The block is a DESCRIPTION A
KIND of layer (`AttnKind`: key/value heads, key and value widths,
rotated dims and their base, window, sink) plus the block's own
switches (`out_gate`, `qk_norm`, `sandwich_norm`, `mup`,
`value_scale`, `n_shared_experts`, `indexer`), and three public
families are settings of it: AFMoE (Trinity-Mini; the equations just
below), MiMo-V2-Flash and Keye-VL-2.0's language block (further down).
Every switch is static: a setting compiles the operations it names and
no others.

AFMoE's layer (x: hidden; matrices without bias; RMSNorm eps
`rms_norm_eps`; four norms a layer):

    x0 = E[token] * sqrt(hidden_size)                  (mup_enabled)
    h = x + N2(Attn(N1(x)));   y = h + N4(FFN(N3(h)))

    Attn(u): q = u W_Q -> heads x d;  k, v = u W_K, u W_V -> kv_heads x d
             g = sigmoid(u W_G)                        (heads x d)
             q, k <- RMSNorm over d (q_norm, k_norm), before any rotation
             sliding layer: RoPE(rope_theta) on q and k; query i sees
                            keys j with 0 <= i - j < sliding_window
             full layer:    no positions; query i sees every j <= i
             o_h = softmax(q_h . k_{h // rep} / sqrt(d)) v_{h // rep}
             Attn = (concat_h(o_h) * g) W_O
    FFN:     models/mla._ffn — dense SwiGLU in the leading layers,
             after them the shared expert + this share of the routed
             ones (sigmoid scores, normalised over the selection,
             times route_scale).

MiMo-V2-Flash's layer (plain pre-norm: two norms, no output gate, no
q/k norm, no muP, no shared expert; eps `layernorm_epsilon`):

    x0 = E[token];   h = x + Attn(N1(x));   y = h + FFN(N2(h))

    Attn(u): q = u W_Q -> heads x dk;  k = u W_K -> KH x dk;
             v = (u W_V -> KH x dv) * attention_value_scale
             KH, and RoPE's base, by the layer's kind; RoPE on the
             LEADING rotary dims of q and k only (partial_rotary_factor)
             s_ij = q_i . k_j / sqrt(dk); the window and the causal
             mask as above
             sink layer (the window kind): p_ij = exp(s_ij) /
                 (exp(b_h) + sum_j exp(s_ij)), b_h learned, one a head
                 — a key with no value (ops/paged_attention)
             Attn = concat_h(sum_j p_ij v_j) W_O        (heads x dv -> H)

INDEXER (Keye-VL-2.0's language block, `mla.FAMILIES["KeyeVL2"]`: the
"lightning indexer" of the DeepSeek-V3.2-Exp report with `sa_config`'s
sizes).  Every layer global, MiMo's plain pre-norm, q/k RMSNorm a head
and RoPE on the whole head; in front of the attention, from the same
normed input u:

    qI = u W_qI (indexer heads x dim);  kI = LayerNorm(u W_kI) (ONE
         head of dim: scale ki_norm, bias ki_bias);  both rotated like
         q and k;  w = u W_w / sqrt(heads x dim)
    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])            s <= t
    S_t = the `topk` positions of largest I[t, .] — every s <= t while
          t < topk; of equal scores the lower position first
    o_h = softmax over s in S_t of (q_h . k / sqrt(d)) v

one selection a token a layer, shared by all heads.  The indexer's key
is what a token leaves behind BESIDE its K and V: the global group's
page holds a third pool, `ik` (n_blocks, L, 1, dim, page) — a token a
column —, under the same table entry, so allocation, a prefix hit's
mapping, copy-on-write and eviction move all three and nothing is ever
re-indexed.  ops/sparse_attention.py holds the three device stages
(scan, exact selection, attention over the selected keys) and the
DENSE path a row takes while its last token sees `topk` keys or fewer:
window_paged_attention itself, bit for bit the layer without an
indexer.  There is no window group; the serving class is
IndexedCompletionModel.

PAGES IN TWO GROUPS.  The cache holds K (after its norm and its
rotation, where it has them) and V a token a layer, each in a pool of
its own width.  A sliding layer
needs the last `sliding_window` tokens' only, so the model describes
its pages as two groups (`page_layout`, decoder.PageLayout.window):
the GLOBAL layers' pools (n_blocks, L_global, kv_heads, page, dk | dv)
and the WINDOW layers' (n_blocks_w, L_window, kv_heads_w, page, dk | dv),
each with its own table, page count and free list
(decoder.PagedKVCache / WindowPages).  The cache gives a window
group's page back when its row has slid past it; the attention
kernel (ops/paged_attention.window_paged_attention) walks a window
layer's pages from the first live one.

PROGRAMS.  ONE prefill program, the suffix prefill over (pages +
table), in whole-page widths from one page to SUFFIX_PAGES: the new
tokens start at a page boundary (a prefix hit maps whole pages; a
prompt the prefix cache does not know is the same program from an
empty row, looped in its widest bucket, giving window pages back as it
passes the window — no separate bucket prefill), so their keys and
values are written a page at a time.  The same program with a ROW axis
(`forward_suffix_rows`, ONE page wide) takes the hits of an admission
round whose suffix is a page or less in one dispatch, each row over
its own two tables, first tokens drawn in graph; a wider hit stays a
round of one.  The decode chunk is mla's: n steps with the sampler in
graph.  Past a head that may be irregular the layers repeat with the
period of the layer pattern
(`WindowMoeConfig.plan`), and the periods run as
ONE compiled body under lax.scan (the stack's weights a period's
stacked; a layer is told its index in its group's pool by the loop's
counter): 32 layers compile as 8.

WEIGHTS: mla.seed_tensor, names `layers.<i>.<tensor>`.  Two things are
this family's own, restated by the plain reference:
    the embedding has std 1/sqrt(hidden_size), so that after the muP
    multiplier the stream starts at unit scale;
    the norms that write INTO the residual stream (the sandwich's
    second and fourth) are scaled by s = 1/sqrt(2 x the whole model's
    layers) (a tenth of the mean as std): with unit branches a seeded
    32-layer stack amplifies a bfloat16 rounding past what a reference
    can tell from a fault (PR 30's finding, models/kda.py; under a
    sandwich norm scaling w_o would change nothing).  ln_attn_out has
    mean ATTN_OUT x s = 2 s and ln_mlp_out MLP_OUT x s = s / 2: at s
    for both, a routed expert that a rounding flips in or out of a
    token's top-k moved the logits as far as a window layer that lost
    a page of its keys (measured on the chip, PERF.md section 6), and
    the attention over paged keys is what this family's pages are for.
A block WITHOUT the sandwich (MiMo) has no norm behind a branch, so
the matrices that write into the stream carry the scale (models/kda.py's
recipe) and the embedding has std 1:
    w_down, experts.<e>.down: std s / sqrt(fan_in);
    w_q: std PRE_Q_GAIN[kind] / sqrt(hidden) — a global layer's scores
    at std 3, so that of 32.9k keys a few dozen carry a query's mass
    (at std 1 every key carries 1/12,000th and the layer's output is
    the same mean for every query, which no fault in a page could
    move); a window layer's at std 1;
    sink (heads,) float32, uniform on [2, 5]: at unit-scale scores
    over 128 keys the sink takes 3% (b = 2) to 41% (b = 5) of a row's
    softmax mass, 14% at the mean;
    w_o: std ATTN_OUT x s x PRE_O_UNIT[kind] / sqrt(fan_in), PRE_O_UNIT
    the inverse of the std of a head's output under the lines above
    at the benchmark cell's context (an average over keys is narrower
    than a value: 0.082 a window layer, 0.117 a global one at 32.9k
    keys; CPU arithmetic, PERF.md section 6), so that the attention
    branch writes at 2 s as AFMoE's does.
Under an INDEXER (Keye) the same pre-norm recipe with three changes,
each from CPU arithmetic at the published widths before any limit was
read (PR 44; PERF.md section 6):
    a norm behind w_q (q_norm) takes any gain w_q carries, so the gain
    of 3 sits on q_norm's scale (3 +- 0.3) and w_q stays at 1/sqrt(H);
    w_o: std INDEXED_ATTN_OUT x s x INDEXED_O_UNIT / sqrt(fan_in) —
    INDEXED_O_UNIT 3.2, the inverse of a head's output std over 2,048
    selected keys at scores of std 3 (0.32), and INDEXED_ATTN_OUT 0.25,
    not AFMoE's 2: a seeded indexer is INDEPENDENT of the attention it
    selects for, so a key that a bfloat16 rounding flips in or out at
    the topk-th rank carries as much of a query's mass as any other
    (a trained indexer ranks by that mass: its flips carry none), the
    attention output moves by sqrt(2 x flips / topk) and the flips
    follow the stream's own error — a square-root map whose fixed
    point is 4 b^2 of the stream for a branch that writes at b.  At
    2 s (b = 0.2) four layers read a median logit error of 1.03 of the
    logits' spread against the float32 reference, at 0.5 s 0.086, at
    0.25 s 0.041 (the activations' own roundings), while the wrong
    selection reads 2.8 / 0.84 / 0.43: 0.25 keeps the fault ten times
    the sound run;
    w_qi, w_ki, w_wi at 1/sqrt(H), ki_norm 1 +- 0.1, ki_bias 0 +- 0.1:
    I is scale-free (every score and every rounding error scales with
    the three alike), so no scale widens its spread against bfloat16.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import DEVTIME, close_mark
from ..ops.page_groups import decode_groups
from ..ops.paged_attention import kv_append, window_paged_attention
from ..ops.sparse_attention import (ATTEND_PAGES, indexed_attention,
                                    write_pages)
from .decoder import PageLayout, PagedKVCache, _sample_rows
from .encoder import _rotary_angles_at
from .mla import (LatentCompletionModel, LatentPendingChunk, _ffn, _rms,
                  _sum_slots, ffn_params, seed_tensor)

KINDS = ("window", "full")
# the seeded post-branch norms' means, in units of 1/sqrt(2 x layers)
# (module docstring, WEIGHTS)
ATTN_OUT, MLP_OUT = 2.0, 0.5
# a block without the sandwich: the gain of w_q and the inverse of a
# head's output std, by kind (module docstring, WEIGHTS)
PRE_Q_GAIN = {"window": 1.0, "full": 3.0}
PRE_O_UNIT = {"window": 12.0, "full": 8.5}
SINK_RANGE = (2.0, 5.0)
# under an indexer (module docstring, INDEXER): the inverse of a head's
# output std over `topk` = 2,048 keys at scores of std 3, and the
# attention branch's scale in units of 1/sqrt(2 x layers)
INDEXED_O_UNIT, INDEXED_ATTN_OUT = 3.2, 0.25
# identical layers in a row that `plan` puts under a scan as periods
# of one: from four on (a cut stack whose pattern repeats nowhere still
# compiles its run of window layers once)
RUN_SCAN = 4
# pages of the widest suffix program: a follow-up turn of a few hundred
# tokens fits one call, a cold prompt loops in it
SUFFIX_PAGES = 5
# tokens a chunk of the rows program's expert layers: its LIVE tokens
# first (moe.sparse_moe live_chunk), so that a round's rows x page
# token slots read the experts' weights once
JOIN_MOE_CHUNK = 8192


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """What the layers of ONE kind ("window" | "full") attend with."""
    kv_heads: int
    qk_dim: int                   # a head's query and key width
    v_dim: int                    # a head's value width
    rotary_dim: int               # leading dims of q, k rotated (0: none)
    rope_base: float
    window: int                   # tokens attended (0: every one)
    sink: bool = False            # a learned logit a head in the softmax
    # the keys' pages hold a token a COLUMN, (kv_heads, qk_dim, page):
    # for a key width that is no multiple of the 128-lane tile
    # (ops/paged_attention, "KEYS A TOKEN A COLUMN")
    k_cols: bool = False


@dataclasses.dataclass(frozen=True)
class Indexer:
    """The learned selection in front of the GLOBAL layers' attention
    (module docstring, INDEXER)."""
    heads: int                    # indexer query heads
    dim: int                      # an indexer head's width
    topk: int                     # keys a token attends


@dataclasses.dataclass(frozen=True)
class WindowMoeConfig:
    vocab_size: int               # rows of the vocabulary held here
    hidden: int
    kinds: tuple[str, ...]        # a kind ("window" | "full") a kept layer
    heads: int
    kv_heads: int                 # AFMoE's one setting of both kinds;
    head_dim: int                 # `attn_kinds`, where given, overrides
    window: int                   # tokens a window layer attends
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
    rope_base: float = 10000.0
    rms_eps: float = 1e-5
    mup: bool = True              # the embedding times sqrt(hidden)
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    # layers of the WHOLE model (the share may keep fewer): what the
    # norms that write into the residual stream are scaled by
    model_layers: int | None = None
    # the block's switches (module docstring); the defaults are AFMoE's
    out_gate: bool = True         # Attn = (o * sigmoid(u W_G)) W_O
    qk_norm: bool = True          # RMSNorm over a head's q and k
    sandwich_norm: bool = True    # a norm behind each branch as well
    value_scale: float = 1.0      # v <- v * value_scale
    # ((kind, AttnKind), ...): None is AFMoE's — one kv_heads, one
    # head_dim, RoPE(rope_base) on the window layers' whole heads only
    attn_kinds: tuple | None = None
    # None: every key a layer's mask lets through is attended
    indexer: Indexer | None = None

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
        kh, d = self.kv_heads, self.head_dim
        object.__setattr__(self, "attn_kinds", tuple(
            self.attn_kinds or (
                ("window", AttnKind(kh, d, d, d, self.rope_base,
                                    self.window)),
                ("full", AttnKind(kh, d, d, 0, self.rope_base, 0)))))
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
                             "at least one full layer")
        if "window" in self.kinds and self.window < 1:
            raise ValueError("a window layer needs sliding_window >= 1")
        if self.indexer is not None and (
                "window" in self.kinds or self.indexer.dim % 2
                or self.indexer.topk < 1 or self.attn("full").k_cols):
            raise ValueError(
                "an indexer selects among a GLOBAL layer's keys: every "
                "layer full attention over row-major key pages, an even "
                "indexer_head_dim (RoPE pairs) and topk >= 1")
        for kind in set(self.kinds):
            a = self.attn(kind)
            if self.heads % a.kv_heads or a.rotary_dim % 2 \
                    or a.rotary_dim > a.qk_dim:
                raise ValueError("kv_heads must divide heads and the "
                                 "rotated dims be even and within "
                                 "head_dim")
            if a.window != (self.window if kind == "window" else 0):
                raise ValueError(f"a {kind} layer's window is "
                                 f"{a.window}")

    @classmethod
    def tiny(cls, **kw) -> "WindowMoeConfig":
        """Small config for tests and CPU rehearsals: two periods, the
        second under the scan."""
        kw = {"vocab_size": 512, "hidden": 64,
              "kinds": ("window", "window", "window", "full") * 2,
              "heads": 4, "kv_heads": 2, "head_dim": 16, "window": 32,
              "dense_layers": 1, "dense_mlp_dim": 128, "moe_mlp_dim": 32,
              "n_routed_experts": 8, "top_k": 2,
              "routed_scaling_factor": 2.826, "max_len": 256, **kw}
        return cls(**kw)

    @property
    def layers(self) -> int:
        return len(self.kinds)

    def attn(self, kind: str) -> AttnKind:
        return dict(self.attn_kinds)[kind]

    @property
    def plan(self) -> tuple[int, int, int]:
        """(head, period, periods): the first `head` layers run
        unrolled, then `periods` identical periods of `period` layers
        under one scanned body, then whatever is left, unrolled.  The
        pattern may start IRREGULARLY (MiMo: global, window x 4, then
        periods of six): the kinds repeat with `period` from some
        layer `first` on, the head is `first` plus the whole periods
        that cover the dense layers, and of every (first, period) the
        one that puts most layers under a scan of two periods or more,
        each holding every kind of layer, is taken — the earliest
        among equals, which is the pattern's own start where nothing
        repeats twice.  Where no pattern repeats, RUN_SCAN or more
        identical layers in a row are periods of one (MiMo's cut,
        layers 0-6: the dense layer, window x 4 scanned, a tail of
        two)."""
        n, kinds = self.layers, self.kinds
        best = None
        for first in range(n):
            period = next(p for p in range(1, n - first + 1)
                          if all(kinds[i] == kinds[i - p]
                                 for i in range(first + p, n)))
            head = min(n, first + -(-max(self.dense_layers - first, 0)
                                    // period) * period)
            periods = (n - head) // period
            whole = set(kinds[head: head + period]) == set(kinds)
            gain = periods * period if periods > 1 and whole else 0
            if best is None or gain > best[0]:
                best = (gain, head, period, periods)
        # a run of identical layers past the dense ones is a period of
        # one, taken where it beats every repeating pattern
        start = self.dense_layers
        while start < n:
            end = start
            while end < n and kinds[end] == kinds[start]:
                end += 1
            if end - start >= max(RUN_SCAN, best[0] + 1):
                best = (end - start, start, 1, end - start)
            start = end
        return best[1:]

    def group_index(self, i: int) -> int:
        """Layer i's index within its group's pool."""
        return sum(k == self.kinds[i] for k in self.kinds[:i])

    def page_layout(self, page: int) -> tuple[PageLayout, ...]:
        """A layout a GROUP: the global layers' K and V side by side
        in one page, and the window layers'."""
        out = []
        for kind in ("full", "window"):
            n = self.kinds.count(kind)
            if n:
                a = self.attn(kind)
                # the indexer's keys ride the global group as a THIRD
                # pool, one head a token a column: a table entry names
                # a page of all three
                ix = self.indexer if kind == "full" else None
                out.append(PageLayout(
                    (("k", (n, a.kv_heads, a.qk_dim, page) if a.k_cols
                      else (n, a.kv_heads, page, a.qk_dim)),
                     ("v", (n, a.kv_heads, page, a.v_dim)),
                     *((("ik", (n, 1, ix.dim, page)),) if ix else ())),
                    token_values=n * (a.kv_heads * (a.qk_dim + a.v_dim)
                                      + (ix.dim if ix else 0)),
                    window=a.window, layers=n))
        return tuple(out)


# ------------------------------------------------------------- weights

def _layer_params(cfg: WindowMoeConfig, seed: int, i: int) -> dict:
    H, dt, kind = cfg.hidden, cfg.dtype, cfg.kinds[i]
    a = cfg.attn(kind)
    p = f"layers.{i}."
    out_mean = 1.0 / math.sqrt(2.0 * cfg.model_layers)
    # without a norm behind the branches the matrices that write into
    # the stream carry the scale (module docstring, WEIGHTS)
    pre = not cfg.sandwich_norm
    # a norm behind w_q takes any gain w_q carries: there the q norm's
    # scale carries it
    q_gain = PRE_Q_GAIN[kind] if pre else 1.0

    def mat(name, shape, gain=1.0):
        return seed_tensor(seed, name, shape,
                           gain / math.sqrt(shape[0]), dt)

    def norm(name, n, mean=1.0):
        return seed_tensor(seed, name, (n,), 0.1 * mean, jnp.float32,
                           mean)

    ix = cfg.indexer if kind == "full" else None
    lp = {"ln_attn_in": norm(p + "ln_attn_in", H),
          "ln_mlp_in": norm(p + "ln_mlp_in", H),
          # kept TRANSPOSED, (out, hidden): the layout the chip's
          # compiler asks for under both programs — as (hidden, out)
          # it copied all three at every dispatch
          # (tests/test_chip_compile.py)
          "w_q": mat(p + "w_q", (H, cfg.heads * a.qk_dim),
                     1.0 if cfg.qk_norm else q_gain).T,
          "w_k": mat(p + "w_k", (H, a.kv_heads * a.qk_dim)).T,
          "w_v": mat(p + "w_v", (H, a.kv_heads * a.v_dim)).T,
          "w_o": mat(p + "w_o", (cfg.heads * a.v_dim, H),
                     out_mean * (INDEXED_ATTN_OUT * INDEXED_O_UNIT if ix
                                 else ATTN_OUT * PRE_O_UNIT[kind])
                     if pre else 1.0)}
    if cfg.sandwich_norm:
        lp["ln_attn_out"] = norm(p + "ln_attn_out", H, ATTN_OUT * out_mean)
        lp["ln_mlp_out"] = norm(p + "ln_mlp_out", H, MLP_OUT * out_mean)
    if cfg.out_gate:
        lp["w_g"] = mat(p + "w_g", (H, cfg.heads * a.v_dim))
    if cfg.qk_norm:
        lp["q_norm"] = norm(p + "q_norm", a.qk_dim, q_gain)
        lp["k_norm"] = norm(p + "k_norm", a.qk_dim)
    if ix:
        lp["w_qi"] = mat(p + "w_qi", (H, ix.heads * ix.dim)).T
        lp["w_ki"] = mat(p + "w_ki", (H, ix.dim))
        lp["w_wi"] = mat(p + "w_wi", (H, ix.heads))
        lp["ki_norm"] = norm(p + "ki_norm", ix.dim)
        lp["ki_bias"] = seed_tensor(seed, p + "ki_bias", (ix.dim,), 0.1,
                                    jnp.float32)
    if a.sink:
        lo, hi = SINK_RANGE
        lp["sink"] = seed_tensor(seed, p + "sink", (cfg.heads,),
                                 (hi - lo) / math.sqrt(12.0), jnp.float32,
                                 (lo + hi) / 2.0)
    lp.update(ffn_params(
        cfg, seed, p, i < cfg.dense_layers, mat,
        (lambda name, shape: mat(name, shape, out_mean)) if pre
        else None))
    return lp


def init_params(cfg: WindowMoeConfig, seed: int) -> dict:
    """The resident tree of this share, tensor by tensor: `head` the
    unrolled leading layers, `periods` one dict a position of the
    period with every leaf stacked over the scanned periods, `tail`
    what is left."""
    H, dt = cfg.hidden, cfg.dtype
    head, period, n = cfg.plan
    periods = []
    for j in range(period if n else 0):
        made = [_layer_params(cfg, seed, head + k * period + j)
                for k in range(n)]
        periods.append(jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *made))
        del made
    return {
        # at unit scale once the muP multiplier, where there is one,
        # has been applied
        "tok_emb": seed_tensor(seed, f"tok_emb.{cfg.vocab_first}",
                               (cfg.vocab_size, H),
                               1.0 / math.sqrt(H) if cfg.mup else 1.0,
                               dt),
        "head": [_layer_params(cfg, seed, i) for i in range(head)],
        "periods": periods,
        "tail": [_layer_params(cfg, seed, i)
                 for i in range(head + n * period, cfg.layers)],
        "ln_out": seed_tensor(seed, "ln_out", (H,), 0.1, jnp.float32,
                              1.0),
        "lm_head": seed_tensor(seed, f"lm_head.{cfg.vocab_first}",
                               (H, cfg.vocab_size), 1.0 / math.sqrt(H),
                               dt),
    }


# -------------------------------------------------------------- forward

def _normed(cfg, x, scale):
    """RMSNorm of the float32 residual stream, handed to the matrix
    products in the model's dtype."""
    return _rms(x, scale, cfg.rms_eps).astype(cfg.dtype)


def _rotate(x, cos, sin):
    """Split-half rotation pairs over the LEADING 2 x cos.shape[-1]
    dims of x, in float32; what lies behind them passes through.
    x: (B, S, heads, d); cos/sin: (B, S, r/2)."""
    half = cos.shape[-1]
    x1, x2 = x[..., :half], x[..., half: 2 * half]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            *([x[..., 2 * half:]]
                              if 2 * half < x.shape[-1] else [])], -1)


BANKED = ("exp_gate", "exp_up", "exp_down")


def _layer(cfg: WindowMoeConfig, lp, kind: str, gl, x, pos, live, pools,
           write, tables, att_len, interpret: bool, bank=None,
           live_chunk=None):
    """One block over its group's pool.  x: (B, S, H) float32; pos:
    (B, S); pools: {"full": (k, v), "window": (k, v)}; write[kind](pool,
    new (B, S, kv_heads, width), layer, cols) puts the new tokens'
    rows (columns, where the pool keeps a token a column) into their
    pages; gl: the layer's index in its group (traced under the
    scan); bank: under the scan, the period's index in the expert
    tensors, which stay stacked; live_chunk: the expert layer's
    chunking where most tokens are dead (both: moe.sparse_moe).  Every
    `if` below reads the configuration, not the data: a setting
    compiles its own operations only.  Returns (x, pools, expert slots
    | None)."""
    B, S, _ = x.shape
    ak, f32 = cfg.attn(kind), jnp.float32
    xn = _normed(cfg, x, lp["ln_attn_in"])
    def proj(w, heads, width):          # w: (heads x width, hidden)
        return jnp.einsum("bsh,xh->bsx", xn, w).reshape(B, S, heads,
                                                        width)
    q = proj(lp["w_q"], cfg.heads, ak.qk_dim)
    k = proj(lp["w_k"], ak.kv_heads, ak.qk_dim)
    v = proj(lp["w_v"], ak.kv_heads, ak.v_dim)
    if cfg.qk_norm:
        q = _rms(q.astype(f32), lp["q_norm"], cfg.rms_eps)
        k = _rms(k.astype(f32), lp["k_norm"], cfg.rms_eps)
    if ak.rotary_dim:
        cos, sin = _rotary_angles_at(pos.reshape(-1), ak.rotary_dim,
                                     ak.rope_base)
        cos, sin = cos.reshape(B, S, -1), sin.reshape(B, S, -1)
        q, k = _rotate(q.astype(f32), cos, sin), \
            _rotate(k.astype(f32), cos, sin)
    if cfg.value_scale != 1.0:
        v = v.astype(f32) * cfg.value_scale
    kp, vp, *ikp = pools[kind]
    kp = write[kind](kp, k.astype(kp.dtype), gl, ak.k_cols)
    vp = write[kind](vp, v.astype(vp.dtype), gl, False)
    if ikp:
        # the indexer (module docstring, INDEXER): its queries, head
        # weights and the token's ONE key, rotated like q and k, the
        # key into the group's third pool
        ix = cfg.indexer
        qi = proj(lp["w_qi"], ix.heads, ix.dim).astype(f32)
        ki = jnp.dot(xn, lp["w_ki"], preferred_element_type=f32)
        ki = ki - jnp.mean(ki, -1, keepdims=True)
        ki = (ki * jax.lax.rsqrt(jnp.mean(ki * ki, -1, keepdims=True)
                                 + cfg.rms_eps) * lp["ki_norm"]
              + lp["ki_bias"])[:, :, None]
        icos, isin = _rotary_angles_at(pos.reshape(-1), ix.dim,
                                       ak.rope_base)
        icos, isin = icos.reshape(B, S, -1), isin.reshape(B, S, -1)
        ikp = write[kind](ikp[0], _rotate(ki, icos, isin)
                          .astype(ikp[0].dtype), gl, True)
        wi = jnp.dot(xn, lp["w_wi"], preferred_element_type=f32) \
            / math.sqrt(ix.heads * ix.dim)
        pools = {**pools, kind: (kp, vp, ikp)}
        o = indexed_attention(
            q.astype(cfg.dtype), _rotate(qi, icos, isin).astype(cfg.dtype),
            wi, kp, vp, ikp, tables[kind], att_len, live[:, 0], layer=gl,
            topk=ix.topk, groups=tables.get("walk"), interpret=interpret)
    else:
        pools = {**pools, kind: (kp, vp)}
        o = window_paged_attention(
            q.astype(cfg.dtype), kp, vp, tables[kind], att_len, layer=gl,
            window=ak.window, sinks=lp["sink"] if ak.sink else None,
            k_cols=ak.k_cols, interpret=interpret)
    o = o.reshape(B, S, cfg.heads * ak.v_dim)
    if cfg.out_gate:
        gate = jax.nn.sigmoid(jnp.dot(xn, lp["w_g"]).astype(f32))
        o = (o.astype(f32) * gate).astype(cfg.dtype)
    a = jnp.dot(o, lp["w_o"], preferred_element_type=f32)
    if cfg.sandwich_norm:
        a = _rms(a, lp["ln_attn_out"], cfg.rms_eps)
    h = x + a
    # the router reads the normed stream unrounded (moe.sparse_moe)
    hn = _rms(h, lp["ln_mlp_in"], cfg.rms_eps)
    f, slots = _ffn(cfg, lp, hn.astype(cfg.dtype), live, interpret, bank,
                    route_x=hn, live_chunk=live_chunk)
    f = f.astype(f32)
    if cfg.sandwich_norm:
        f = _rms(f, lp["ln_mlp_out"], cfg.rms_eps)
    return h + f, pools, slots


def _stack(cfg: WindowMoeConfig, params, x, pos, live, pools, write,
           tables, att_len, interpret: bool, live_chunk=None):
    """Every kept layer: the head unrolled, the periods under one
    scanned body, the tail unrolled.  Returns (x, pools, slots)."""
    head, period, n = cfg.plan
    slots = []

    def one(lp, i, gl, x, pools, bank=None):
        return _layer(cfg, lp, cfg.kinds[i], gl, x, pos, live, pools,
                      write, tables, att_len, interpret, bank, live_chunk)

    for i, lp in enumerate(params["head"]):
        x, pools, s = one(lp, i, cfg.group_index(i), x, pools)
        slots.append(s)
    if n:
        # layer head + k * period + j of period k: its group index is
        # the head's count of its kind + k * (the period's) + j's
        base = [cfg.group_index(head + j) for j in range(period)]
        per = [cfg.kinds[head:head + period].count(cfg.kinds[head + j])
               for j in range(period)]

        # the expert tensors stay whole, a period a bank: a slice of
        # them would be copied for the grouped kernel at every layer
        banked = [{k: v for k, v in lp.items() if k in BANKED}
                  for lp in params["periods"]]
        sliced = [{k: v for k, v in lp.items() if k not in BANKED}
                  for lp in params["periods"]]

        def body(carry, xs):
            x, pools, tot = carry
            lps, k = xs
            got = []
            for j, lp in enumerate(lps):
                x, pools, s = one({**lp, **banked[j]}, head + j,
                                  base[j] + k * per[j], x, pools, bank=k)
                got.append(s)
            return (x, pools, tot + _sum_slots(cfg, got)), None

        zero = jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
        (x, pools, tot), _ = jax.lax.scan(
            body, (x, pools, zero),
            (sliced, jnp.arange(n, dtype=jnp.int32)))
        slots.append(tot)
    for t, lp in enumerate(params["tail"]):
        i = head + n * period + t
        x, pools, s = one(lp, i, cfg.group_index(i), x, pools)
        slots.append(s)
    return x, pools, _sum_slots(cfg, slots)


def _embed(cfg, params, ids):
    x = params["tok_emb"][ids].astype(jnp.float32)
    return x * math.sqrt(cfg.hidden) if cfg.mup else x


def _head(cfg, params, x):
    """Final norm + untied head over the vocabulary slice, float32."""
    return jnp.dot(_normed(cfg, x, params["ln_out"]), params["lm_head"],
                   preferred_element_type=jnp.float32)


def forward_decode(cfg: WindowMoeConfig, params, toks, pools, tables,
                   lengths, *, interpret: bool = False):
    """One new token a row over the pages its tables map.  toks: (B,);
    pools: {"full": (k, v), "window": (k, v)}, each (n_blocks, L,
    kv_heads, page, width), the kind's own kv_heads and the pool's own
    width; tables: the same keys, (B, P) — and, under an indexer,
    "walk": the rows that share pages, grouped for the attention's
    walk (ops/page_groups.decode_groups' arrays of these tables);
    lengths: (B,).
    Returns (hidden (B, H), pools, slots each held expert received)."""
    page = pools["full"][1].shape[3]
    pos = jnp.minimum(lengths, cfg.max_len - 1).astype(jnp.int32)
    offs = pos % page
    write = {}
    for kind in pools:
        bids = jnp.take_along_axis(tables[kind], (pos // page)[:, None],
                                   axis=1)

        def put(pool, new, gl, cols, bids=bids[:, 0]):
            return kv_append(pool, new[:, 0], bids, offs, layer=gl,
                             cols=cols, interpret=interpret)
        write[kind] = put
    x, pools, slots = _stack(
        cfg, params, _embed(cfg, params, toks)[:, None], pos[:, None],
        (lengths > 0)[:, None], pools, write, tables, pos + 1, interpret)
    return x[:, 0], pools, slots


def _set_pages(cfg: WindowMoeConfig, pool, bids, gl, pages,
               interpret: bool):
    """A suffix's whole pages into layer gl of a group's pool: an XLA
    scatter, and under an indexer the page-write kernel
    (ops/sparse_attention.write_pages has the reason)."""
    if cfg.indexer is None:
        return pool.at[bids, gl].set(pages)
    return write_pages(pool, pages, bids, layer=gl, interpret=interpret)


def forward_suffix(cfg: WindowMoeConfig, params, ids, pools, tables,
                   length, n_valid, *, interpret: bool = False):
    """S new tokens of ONE row atop the `length` tokens its tables map
    — a multiple of the page (a prompt from nothing: length 0).  ids:
    (1, S) padded to whole pages, n_valid real.  Returns (hidden (1, S,
    H), pools)."""
    S = ids.shape[1]
    page = pools["full"][1].shape[3]
    n_p = S // page
    pos = jnp.minimum(length[:, None] + jnp.arange(S)[None, :],
                      cfg.max_len - 1).astype(jnp.int32)
    ok = jnp.arange(S)[None, :] < n_valid                 # (1, S)
    write = {}
    for kind, tab in tables.items():
        bids = jax.lax.dynamic_slice_in_dim(tab[0], length[0] // page,
                                            n_p)

        def put(pool, new, gl, cols, bids=bids):
            rows = new[0].reshape(n_p, page, *new.shape[2:])
            return _set_pages(cfg, pool, bids, gl,
                              rows.transpose(0, 2, 3, 1) if cols
                              else rows.transpose(0, 2, 1, 3), interpret)
        write[kind] = put
    x, pools, _ = _stack(cfg, params, _embed(cfg, params, ids), pos, ok,
                         pools, write, tables, pos[:, 0] + 1, interpret)
    return x, pools


def forward_suffix_rows(cfg: WindowMoeConfig, params, ids, pools, tables,
                        lengths, n_valid, *, interpret: bool = False):
    """forward_suffix with a ROW axis: the hits of one admission round
    in one program, so a layer's weights are read once a round.  ids:
    (R, S) padded to whole pages, n_valid (R,) real (0: a pad row, its
    length 0, its tables trash blocks); tables: a (R, P) table a
    group; row i's keys and values go to its own tables from page
    lengths[i] // page on, a page none of its real tokens reaches to
    the trash block.  Pad tokens reach no expert.  Returns (hidden (R,
    S, H), pools)."""
    R, S = ids.shape
    page = pools["full"][1].shape[3]
    n_p = S // page
    at = jnp.arange(S)[None, :]
    pos = jnp.minimum(lengths[:, None] + at,
                      cfg.max_len - 1).astype(jnp.int32)
    ok = at < n_valid[:, None]                            # (R, S)
    piece = jnp.arange(n_p)[None, :]
    write = {}
    for kind, tab in tables.items():
        held = jnp.take_along_axis(
            tab, jnp.minimum(lengths[:, None] // page + piece,
                             tab.shape[1] - 1), axis=1)
        bids = jnp.where(piece * page < n_valid[:, None], held,
                         0).reshape(-1)

        def put(pool, new, gl, cols, bids=bids):
            pages = new.reshape(R * n_p, page, *new.shape[2:])
            return _set_pages(cfg, pool, bids, gl,
                              pages.transpose(0, 2, 3, 1) if cols
                              else pages.transpose(0, 2, 1, 3), interpret)
        write[kind] = put
    x, pools, _ = _stack(cfg, params, _embed(cfg, params, ids), pos, ok,
                         pools, write, tables, pos[:, 0] + 1, interpret,
                         live_chunk=JOIN_MOE_CHUNK)
    return x, pools


# ------------------------------------------------------------- front end

class GroupPagePrograms:
    """The buffers and the copy-on-write of a cache whose pages come
    in GROUPS — one K and one V pool a group (and, under an indexer,
    its keys' pool beside them), every layer of the group side by
    side in a page (PageLayout.layers): the global group alone
    (models/lfm2.py), or the global group and a window group beside
    it (this module).  Whatever pools a group's layout names travel
    together: one table entry, one page copy."""

    @staticmethod
    def _pools(cache: PagedKVCache) -> dict:
        out = {"full": tuple(p[0] for p in cache.pools)}
        if cache.window is not None:
            out["window"] = tuple(p[0] for p in cache.window.pools)
        return out

    @staticmethod
    def _keep(cache: PagedKVCache, pools: dict) -> None:
        for kept, new in zip(cache.pools, pools["full"]):
            kept[0] = new
        if cache.window is not None:
            for kept, new in zip(cache.window.pools, pools["window"]):
                kept[0] = new

    @staticmethod
    def _tables(cache: PagedKVCache, row: int | None = None) -> dict:
        """Host-side copies (lengths and tables move right after a
        dispatch: mla.paged_append_prefill)."""
        rows = slice(None) if row is None else slice(row, row + 1)
        out = {"full": jnp.asarray(np.array(cache.tables[rows]))}
        if cache.window is not None:
            out["window"] = jnp.asarray(np.array(
                cache.window.tables[rows]))
        return out

    def _cow_program(self):
        def build():
            def run(pools, src, dst):
                return [p.at[dst].set(p[src]) for p in pools]
            return run
        return self._program(("cow",), "cow_copy", build, donate=(0,))

    def _copy_page(self, pools, src: int, dst: int) -> None:
        """One page of a group, every layer of it, every pool."""
        for kept, new in zip(pools, self._cow_program()(
                [p[0] for p in pools], jnp.int32(src), jnp.int32(dst))):
            kept[0] = new

    def _cow_fixups(self, cache) -> int:
        """Copy-on-write pass before a decode dispatch, a group at a
        time: one page copy holds every layer of the group."""
        n, w = 0, cache.window
        for row, p_idx in cache.cow_targets():
            dst = cache._alloc_page()
            self._copy_page(cache.pools, int(cache.tables[row, p_idx]), dst)
            cache.commit_cow(row, p_idx, dst)
            n += 1
        for row, p_idx in cache.window_cow_targets():
            dst = w._alloc()
            self._copy_page(w.pools, int(w.tables[row, p_idx]), dst)
            w.commit_cow(row, p_idx, dst)
            n += 1
        return n

    def _warm_cow(self, cache: PagedKVCache) -> None:
        w = cache.window
        for pools, alloc, free in (
                (cache.pools, cache._alloc_page, cache._decref),
                *(((w.pools, w._alloc, w._decref),) if w is not None
                  else ())):
            src, dst = alloc(), alloc()
            self._copy_page(pools, src, dst)
            free(src)
            free(dst)


class WindowCompletionModel(GroupPagePrograms,
                            LatentCompletionModel):
    """LatentCompletionModel's paged serving surface over the mixed
    window / global stack and its two page groups
    (GroupPagePrograms)."""

    needs_window = True
    # lane 0: a row that resumed from the prefix cache, lane 1: one
    # that prefilled from nothing (engine/audit.py)
    audit_lanes = 2
    program_prefix = "afmoe"
    refused_options = {
        **LatentCompletionModel.refused_options,
        "kv_dtype": "the page groups are stored in the model's dtype: "
                    "the int8/int4 page codecs know one pool a layer, "
                    "not a group's",
        "kv_tier_pages": "the host tier's page wire carries one page "
                         "group, and this model keeps two",
        "phase": "the disaggregated hand-off's page wire carries one "
                 "page group, and this model keeps two",
        "tp": "the page groups are not sharded on their kv-head axis; "
              "attention is data-parallel in this deployment",
    }

    def __init__(self, cfg: WindowMoeConfig, *, seed: int = 0,
                 params: Any = None, top_p: float = 0.9,
                 temp: float = 0.7, interpret: bool = False):
        super().__init__(
            cfg, seed=seed,
            params=init_params(cfg, seed) if params is None else params,
            top_p=top_p, temp=temp, suffix_buckets=(16,),
            interpret=interpret)
        self.audit_rows = [-1] * self.audit_lanes
        # what the attention kernels were asked to do, in LIVE keys —
        # running totals the heartbeat carries (benchmark/work_gqa.py
        # turns them into the kernels' rooflines): keys attended by
        # the decode steps' and the suffix pieces' real tokens in a
        # global layer and in a window layer, and the distinct tokens
        # whose K and V a suffix piece read
        self.attn_work = dict.fromkeys(
            ("decode_keys", "decode_window_keys", "prefill_keys",
             "prefill_window_keys", "prefill_kv", "prefill_window_kv"), 0)
        self._set_page(128)

    def audit_seat(self, lane: int, row: int) -> None:
        self.audit_rows[lane] = row

    def _set_page(self, page: int) -> None:
        """Every suffix bucket is whole pages, each width from one
        page to SUFFIX_PAGES: a suffix pads by less than a page, a
        longer one (a cold prompt) loops in the widest."""
        self.suffix_buckets = tuple(
            n * page for n in range(1, SUFFIX_PAGES + 1)
            if n * page < self.cfg.max_len) or (page,)
        self.buckets = self.suffix_buckets

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None,
                   window_pool_pages: int | None = None) -> PagedKVCache:
        self._set_page(page)
        # what a row holds of the window group at the most: the
        # window, the page its oldest key shares, the widest suffix
        # program's pages, and the page a decode chunk may run into
        span = -(-self.cfg.window // page) + 2 \
            + self.suffix_buckets[-1] // page
        return PagedKVCache(self.cfg, batch, page=page,
                            pool_pages=pool_pages, kv_dtype=kv_dtype,
                            window_pool_pages=window_pool_pages,
                            window_span=span)

    # rows of the row-batched suffix program (join_rungs): ONE rung
    # beside the one-row programs (a rung is a program, 25-30 s of a
    # start from an empty compile cache), ONE page wide.  16, not a
    # lane's whole batch: three programs of 16 cost what one of 48 does
    # (0.765 against 0.783 s on a v5e at MiMo's widths: PERF.md section
    # 6), and a round is dispatched the moment it is full, so the
    # device starts on a generation's first 16 hits while the others
    # are still arriving.  The rung of 48 read 12.0 answers/s on the
    # one machine whose clients came back slowly (a round of the whole
    # batch waits for the slowest with the device idle); whether 16
    # does better THERE is not measured.  Compiled for a described v5e
    # (tests/test_chip_compile.py test_window_suffix_rows_program): 16
    # rows x 128 tokens at MiMo's widths 10.74 GB of arguments + 0.45
    # GB of temporaries, 66% of the chip's 16.9 GB; at Trinity's 14.60
    # + 0.18 GB, 87.5%
    JOIN_ROWS = (16,)
    @property
    def join_width(self) -> int:
        """ONE page: a pad token costs the dense layers what a live
        one does, and the stack kernel walks a pad query block's whole
        table (PERF.md section 7), so a round takes the hits of a page
        or less and a wider one is a round of one, a piece at a time
        with its window pages given back between pieces."""
        return self.suffix_buckets[0]

    # -- prefill -----------------------------------------------------------

    def _suffix_program(self, sb: int):
        cfg, interp = self.cfg, self.interpret

        def build():
            def run(params, pools, tables, length, ids, n_valid):
                x, pools = forward_suffix(cfg, params, ids, pools, tables,
                                          length, n_valid,
                                          interpret=interp)
                last = jax.lax.dynamic_index_in_dim(
                    x[0], n_valid - 1, 0, keepdims=False)
                return pools, _head(cfg, params, last)
            return run
        return self._program(("suffix", sb), "suffix_prefill", build)

    def paged_prefill_row(self, cache: PagedKVCache,
                          prompt_ids: np.ndarray, row: int) -> np.ndarray:
        """A whole prompt from nothing: an empty row, and the suffix
        program over it."""
        if len(prompt_ids) == 0:
            raise ValueError("empty prompt")
        cache.lengths[row] = 0
        return self.paged_append_prefill(cache, prompt_ids, row)

    def paged_append_prefill(self, cache: PagedKVCache, suffix_ids,
                             row: int) -> np.ndarray:
        """Prefill the suffix of row's prompt atop the
        cache.lengths[row] tokens its tables map — whole pages of
        them: a prefix hit maps whole pages and a row from nothing
        has none.  A piece at a time: each piece's pages are ensured
        in both groups, and the window group's pages the row has slid
        past go back before the next.  Returns the last real token's
        logits (V,)."""
        ids = np.asarray(suffix_ids, np.int32)
        if ids.size == 0:
            raise ValueError("empty suffix")
        pos, page = int(cache.lengths[row]), cache.page
        if pos % page:
            raise ValueError(
                f"a suffix starts at a page boundary; row {row} holds "
                f"{pos} tokens")
        if pos + ids.size >= self.cfg.max_len:
            raise ValueError("suffix exceeds context window")
        logits, mark, off = None, None, 0
        while off < ids.size:
            rem = ids.size - off
            sb = next((b for b in self.suffix_buckets if b >= rem),
                      self.suffix_buckets[-1])
            n = min(rem, sb)
            if not cache.ensure(row, pos + off + n):
                raise RuntimeError(
                    f"paged pool exhausted: row {row} suffix needs "
                    f"{cache.pages_needed(pos + off + n)} pages")
            piece = np.zeros((1, sb), np.int32)
            piece[0, :n] = ids[off: off + n]
            pools, logits = self._suffix_program(sb)(
                self.params, self._pools(cache), self._tables(cache, row),
                jnp.asarray(np.array(cache.lengths[row: row + 1])),
                jnp.asarray(piece), jnp.int32(n))
            close_mark(mark)
            mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
            self._keep(cache, pools)
            self._count_prefill(pos + off, n)
            cache.lengths[row] += n
            off += n
            cache.release_window(row)
        out = np.asarray(logits)
        close_mark(mark)
        return out

    def _count_decode(self, ctx: np.ndarray) -> None:
        """attn_work of one decode chunk: ctx (rows, steps), the keys
        each live row's token of each step attends."""
        self.attn_work["decode_keys"] += int(ctx.sum())
        self.attn_work["decode_window_keys"] += int(
            np.minimum(ctx, self.cfg.window).sum())

    def _count_prefill(self, pos: int, n: int) -> None:
        """attn_work of one suffix piece: n real tokens atop pos."""
        ctx = pos + 1 + np.arange(n)              # keys a token attends
        W, aw = self.cfg.window, self.attn_work
        aw["prefill_keys"] += int(ctx.sum())
        aw["prefill_window_keys"] += int(np.minimum(ctx, W).sum())
        aw["prefill_kv"] += pos + n
        aw["prefill_window_kv"] += min(pos + n, W - 1 + n)

    # -- an admission round's hits in one program -----------------------------

    def _suffix_rows_program(self, rows: int, sb: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, tables, lengths, ids, n_valid, rng):
                x, pools = forward_suffix_rows(
                    cfg, params, ids, pools, tables, lengths, n_valid,
                    interpret=interp)
                last = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[:, None, None],
                    axis=1)[:, 0]
                logits = _head(cfg, params, last)
                return pools, logits, _sample_rows(rng, logits, top_p,
                                                   temp)
            return run
        return self._program(("suffix", rows, sb, top_p, temp),
                             "suffix_prefill", build)

    def paged_append_prefill_rows(self, cache: PagedKVCache, joins):
        """mla.paged_append_prefill_rows over the two page groups:
        joins is [(row, suffix_ids), ...], every suffix at most
        `join_width` tokens, every row seated with its prefix mapped in
        both groups.  The window group's pages of a row's whole suffix
        are held before the dispatch (the span a seat reserves covers
        the widest program's) and what each row slid past goes back
        after it.  Returns (logits on the device, first tokens on the
        host)."""
        for row, _ in joins:
            if cache.lengths[row] % cache.page:
                raise ValueError(
                    f"a suffix starts at a page boundary; row {row} holds "
                    f"{cache.lengths[row]} tokens")
        ids, n_valid, full, lengths = self._round_inputs(cache, joins)
        tables = {"full": full}
        if cache.window is not None:
            tables["window"] = np.zeros_like(full)
        for i, (row, _) in enumerate(joins):
            if cache.window is not None:
                tables["window"][i] = cache.window.tables[row]
            self._count_prefill(int(lengths[i]), int(n_valid[i]))
        self._rng, sub = jax.random.split(self._rng)
        pools, logits, toks = self._suffix_rows_program(*ids.shape)(
            self.params, self._pools(cache),
            {k: jnp.asarray(v) for k, v in tables.items()},
            jnp.asarray(lengths), jnp.asarray(ids), jnp.asarray(n_valid),
            sub)
        mark = DEVTIME.take_mark(self._devname("suffix_prefill"))
        self._keep(cache, pools)
        for i, (row, _) in enumerate(joins):
            cache.lengths[row] += int(n_valid[i])
            cache.release_window(row)
        toks = np.asarray(toks)[:len(joins)]
        close_mark(mark)
        return logits, toks

    # -- decode ------------------------------------------------------------

    def _chunk_program(self, n: int, bp: int):
        cfg, interp = self.cfg, self.interpret
        top_p, temp = self.top_p, self.temp

        def build():
            def run(params, pools, tables, lengths, rng, fresh,
                    fresh_mask, carry, audit_rows):
                toks0 = jnp.where(fresh_mask, fresh, carry)
                row = jnp.clip(audit_rows, 0, bp - 1)     # a row a lane

                def step(carry_s, _):
                    pools, lengths, rng, toks, slots = carry_s
                    x, pools, s = forward_decode(
                        cfg, params, toks, pools, tables, lengths,
                        interpret=interp)
                    logits = _head(cfg, params, x)
                    rng, sub = jax.random.split(rng)
                    nxt = _sample_rows(sub, logits, top_p, temp)
                    # under an indexer a dead row stays at length 0:
                    # counted up with the others it would be a LIVE
                    # row under topk from the chunk's second step on,
                    # and the whole batch would walk the dense kernel's
                    # grid for it (0.26 s of a 3 s capture: PERF.md
                    # section 6)
                    grown = lengths + 1 if cfg.indexer is None \
                        else lengths + (lengths > 0)
                    return ((pools, grown, rng, nxt, slots + s),
                            (nxt, logits[row]))

                zero = jnp.zeros((max(cfg.experts_held, 1),), jnp.int32)
                (pools, _, _, _, slots), (out, kept) = jax.lax.scan(
                    step, (pools, lengths, rng, toks0, zero), None,
                    length=n)
                return pools, out, out[-1], slots, kept
            return run
        return self._program(("chunk", n, bp, top_p, temp),
                             "paged_chunk", build)

    def _chunk_tables(self, cache: PagedKVCache, n: int) -> dict:
        """The tables a chunk of n steps rides on."""
        return self._tables(cache)

    def paged_decode_chunk_async(self, cache: PagedKVCache, tokens,
                                 n: int, carry=None
                                 ) -> LatentPendingChunk:
        bp = cache.batch
        fresh_mask, toks, carry = self._chunk_inputs(cache, tokens, n,
                                                     carry)
        self._rng, sub = jax.random.split(self._rng)
        live = cache.lengths[cache.lengths > 0].astype(np.int64)
        self._count_decode(live[:, None] + 1 + np.arange(n)[None, :])
        pools, out, last, slots, kept = self._chunk_program(n, bp)(
            self.params, self._pools(cache), self._chunk_tables(cache, n),
            jnp.asarray(np.array(cache.lengths)), sub, jnp.asarray(toks),
            jnp.asarray(fresh_mask), carry,
            jnp.asarray(self.audit_rows, jnp.int32))
        self._keep(cache, pools)
        self._advance(cache, n)
        # the chunk holds its own copy of the tables: what its rows
        # slid past goes back now, and a later dispatch that takes the
        # pages runs after it on the device
        cache.release_window()
        return LatentPendingChunk(
            out, last, n, DEVTIME.take_mark(self._devname("paged_chunk")),
            slots, kept)

    # -- warm-up -----------------------------------------------------------

    def _warmup_paged_impl(self, cache: PagedKVCache, chunk: int,
                           max_prompt: int | None) -> None:
        """Every program the lane can dispatch: the suffix widths, the
        decode chunk, the round's rung, the page copy of each group.
        The rung compiles in a thread beside the others
        (_compile_beside): a start from an empty compile cache is
        ~3 s longer for it, not its 25-30 (PERF.md section 5)."""
        rung = (self.join_rungs(cache)[-1] > 1
                and self.join_width + chunk < self.cfg.max_len)
        compiled = self._compile_beside(cache) if rung else None
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
        if rung:
            compiled()
            self._warm_join_rungs(cache)
        self._warm_cow(cache)

    def _compile_beside(self, cache: PagedKVCache):
        """Start compiling the round's rung in a thread (XLA's compile
        drops the interpreter lock): `jit(...).lower(shapes).compile()`,
        and the call that follows finds the executable on the lowering
        the two share (the persistent compilation cache, where that
        misses).  Returns the wait for it, which raises what the
        compile raised."""
        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        rows, sb = self.join_rungs(cache)[-1], self.join_width
        fn = self._suffix_rows_program(rows, sb)
        args = jax.tree_util.tree_map(
            spec, (self.params, self._pools(cache))) + (
            {k: i32(rows, cache.tables.shape[1])
             for k in self._pools(cache)},
            i32(rows), i32(rows, sb), i32(rows), spec(self._rng))
        failed = []

        def compile_it():
            try:
                getattr(fn, "__wrapped__", fn).lower(*args).compile()
            except Exception as e:
                failed.append(e)
        thread = threading.Thread(target=compile_it, daemon=True,
                                  name="compile-beside-warmup")
        thread.start()

        def wait():
            thread.join()
            if failed:
                raise failed[0]
        return wait


class IndexedCompletionModel(WindowCompletionModel):
    """WindowCompletionModel over ONE page group whose layers attend
    the keys an indexer selects (`cfg.indexer`; module docstring,
    INDEXER): the pools k, v and ik of a page travel together through
    GroupPagePrograms, there is no window group, and the programs a
    start compiles are the decode chunk, the one-page and the widest
    suffix width and the round's rung."""

    needs_window = False
    program_prefix = "dsa"
    refused_options = {
        **WindowCompletionModel.refused_options,
        "kv_dtype": "the page group is stored in the model's dtype: "
                    "the int8/int4 page codecs know a key and a value "
                    "pool, and this model keeps the indexer's keys in "
                    "a third",
        "kv_tier_pages": "the host tier's page wire carries a key and a "
                         "value pool, and this model keeps the "
                         "indexer's keys in a third",
        "phase": "the disaggregated hand-off's page wire carries a key "
                 "and a value pool, and this model keeps the indexer's "
                 "keys in a third",
    }

    def __init__(self, cfg: WindowMoeConfig, **kw):
        if cfg.indexer is None:
            raise ValueError("this model serves a configuration with "
                             "an indexer")
        super().__init__(cfg, **kw)
        # what the three stages were asked to do, summed over rows,
        # layers and steps — running totals the heartbeat carries
        # (benchmark/work_dsa.py turns them into rooflines): indexer
        # keys scored, by the decode steps and by the joins; keys the
        # tokens saw and keys they attended (`topk` at the most), the
        # decode steps' share of the latter; distinct tokens whose K
        # and V a join's attention read; row-layers that took the
        # dense path (every key selected); pages the decoding rows'
        # tables held and pages the attention's walk read for them — a
        # page rows share once a group (_chunk_tables)
        self.attn_work = dict.fromkeys(
            ("index_keys_decode", "index_keys_join", "keys_in_context",
             "keys_selected", "keys_selected_decode", "join_kv",
             "select_dense_rows", "walk_pages_held", "walk_pages_read"),
            0)

    def _set_page(self, page: int) -> None:
        """Two suffix widths: one page (a question behind a document
        the tree holds) and the widest (a cold prompt loops in it)."""
        super()._set_page(page)
        self.suffix_buckets = tuple(sorted({self.suffix_buckets[0],
                                            self.suffix_buckets[-1]}))
        self.buckets = self.suffix_buckets

    def _chunk_tables(self, cache: PagedKVCache, n: int) -> dict:
        """Beside the tables, the rows that hold the same document
        grouped for the attention's walk (ops/page_groups: the tables
        do not move inside a chunk), and what the grouping saves."""
        walk = decode_groups(cache.tables, cache.lengths, page=cache.page,
                             steps=n, chunk=ATTEND_PAGES[0])
        for k in ("held", "read"):
            self.attn_work["walk_pages_" + k] += \
                self.cfg.layers * n * walk.pop(k)
        return {**self._tables(cache),
                "walk": {k: jnp.asarray(v) for k, v in walk.items()}}

    def _count(self, ctx: np.ndarray, dense: np.ndarray, where: str):
        """ctx: keys each token sees; dense: which of them took the
        dense path (they score no indexer key)."""
        aw, n, k = self.attn_work, self.cfg.layers, self.cfg.indexer.topk
        picked = n * int(np.minimum(ctx, k).sum())
        aw["index_keys_" + where] += n * int(ctx[~dense].sum())
        aw["keys_in_context"] += n * int(ctx.sum())
        aw["keys_selected"] += picked
        return picked

    def _count_decode(self, ctx: np.ndarray) -> None:
        dense = ctx <= self.cfg.indexer.topk
        self.attn_work["keys_selected_decode"] += self._count(
            ctx, dense, "decode")
        self.attn_work["select_dense_rows"] += \
            self.cfg.layers * int(dense.sum())

    def _count_prefill(self, pos: int, n: int) -> None:
        ctx = pos + 1 + np.arange(n)
        dense = pos + n <= self.cfg.indexer.topk  # the whole piece
        self._count(ctx, np.full(n, dense), "join")
        self.attn_work["join_kv"] += self.cfg.layers * (pos + n)
        self.attn_work["select_dense_rows"] += self.cfg.layers * int(dense)
