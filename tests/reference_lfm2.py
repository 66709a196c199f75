"""The plain reference of the convolution / attention stack
(models/lfm2.py): `jax.numpy`, float32, matmul precision "highest", no
kernel, no cache, no page, no state slot, no batch.  The gated short
convolution is a plain loop over TOKENS of the published lines

    [B | C | X] = u_t W_in;   v_t = B * X
    c_t = sum_{j < K} w[j] * v_{t-(K-1)+j}        (v before token 0: 0)
    Op_t = (C * c_t) W_out

keeping nothing but the last K - 1 values of v; attention is the full
(S, S) causal softmax, every query head against its group's keys;
every routed expert is a dense sum (reference_mla.ffn) over gates whose
top-k is taken over scores + bias and whose values are the scores'.

A second copy lives under benchmark/reference/ and makes its own
weights from the seed; this one takes a parameter tree (the program's,
cast to float32).  tests/test_lfm2.py holds the two to each other."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_mla import rms, rope, swiglu


def conv_tokens(cfg, lp, x, reg=None):
    """The conv Op token by token.  x: (S, H) normed; reg: None or (K -
    1, H), v of the tokens before x.  Returns (Op (S, H), the v of
    every token (S, H))."""
    K, H = cfg.conv_kernel, x.shape[1]
    reg = np.zeros((K - 1, H), np.float32) if reg is None \
        else np.asarray(reg, np.float32)
    taps = np.asarray(lp["conv"], np.float32)
    out, vs = [], []
    for t in range(x.shape[0]):
        b, c, xx = np.split(np.asarray(x[t] @ lp["w_in"]), 3)
        win = np.concatenate([reg, (b * xx)[None]])       # (K, H)
        out.append((c * (win * taps).sum(0)) @ np.asarray(lp["w_out"]))
        vs.append(win[-1])
        reg = win[1:]
    return np.stack(out), np.stack(vs)


def attention(cfg, lp, x):
    """x: (S, H) normed.  Full causal grouped-query attention."""
    S, d = x.shape[0], cfg.head_dim
    pos, rep = jnp.arange(S), cfg.heads // cfg.kv_heads
    q = rms((x @ lp["w_q"]).reshape(S, cfg.heads, d), lp["q_norm"],
            cfg.rms_eps)
    k = rms((x @ lp["w_k"]).reshape(S, cfg.kv_heads, d), lp["k_norm"],
            cfg.rms_eps)
    v = (x @ lp["w_v"]).reshape(S, cfg.kv_heads, d)
    q, k = rope(q, pos, cfg.rope_base), rope(k, pos, cfg.rope_base)
    s = jnp.einsum("qgrd,kgd->grqk", q.reshape(S, cfg.kv_heads, rep, d),
                   k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s,
                  -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1), v)
    return o.reshape(S, cfg.heads * d) @ lp["w_o"]


def router_gates(cfg, lp, x, bias=True):
    """(S, E) gate matrix over ALL experts: zero outside the top-k of
    scores + bias, the scores' own values inside it."""
    scores = jax.nn.sigmoid(x @ lp["router"])
    pick = scores + lp["router_bias"] if bias and "router_bias" in lp \
        else scores
    _, topi = jax.lax.top_k(pick, cfg.top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    topv = scores[rows, topi]
    if cfg.norm_topk_prob:
        topv = topv / topv.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[rows, topi].set(
        topv * cfg.routed_scaling_factor)


def ffn(cfg, lp, x, experts=None):
    """experts: None (the share's) or a range of experts of the whole
    model whose part of the sum is wanted."""
    if "router" not in lp:
        return swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    gates = router_gates(cfg, lp, x)
    out = jnp.zeros_like(x)
    for j in range(cfg.experts_held):
        e = cfg.experts_first + j
        if experts is None or e in experts:
            out = out + gates[:, e: e + 1] * swiglu(
                x, lp["exp_gate"][j], lp["exp_up"][j], lp["exp_down"][j])
    return out


def forward(cfg, params, ids, taps=None) -> np.ndarray:
    """ids: (S,) -> logits (S, V) float32 over the vocabulary slice.
    taps: None or a list that receives, a convolution layer, the v of
    every token (S, H)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["tok_emb"][jnp.asarray(ids)]
        for lp, kind in zip(p["layers"], cfg.kinds):
            xn = rms(x, lp["ln_mix_in"], cfg.rms_eps)
            if kind == "conv":
                a, vs = conv_tokens(cfg, lp, xn)
                if taps is not None:
                    taps.append(vs)
            else:
                a = attention(cfg, lp, xn)
            h = x + a
            x = h + ffn(cfg, lp, rms(h, lp["ln_mlp_in"], cfg.rms_eps))
        return np.asarray(
            rms(x, p["ln_out"], cfg.rms_eps) @ p["lm_head"])
