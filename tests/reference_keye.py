"""The plain reference of the global stack under an indexer
(models/afmoe.py with `cfg.indexer`, Keye-VL-2.0's language block):
`jax.numpy`, float32, matmul precision "highest", no kernel, no cache,
no page, no scan — one full causal forward over a whole sequence:

    q, k <- RMSNorm a head, then RoPE on the whole head; v plain
    the indexer, from the layer's normed input u:
        qI = u W_qI (heads x dim), kI = LayerNorm(u W_kI) (ONE head),
        both rotated like q and k; w = u W_w / sqrt(heads x dim)
        I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])     for s <= t
        S_t = the topk positions of largest I[t, .] — every s <= t
        while t < topk; of equal scores the LOWER position first
    head h of token t: softmax over s in S_t of q.k / sqrt(d), times v

every routed expert of the share a dense sum (reference_mla.ffn).

A second copy lives under benchmark/reference/ and makes its own
weights from the seed; this one takes a parameter tree (the program's,
cast to float32).  tests/test_keye.py holds the two to each other."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_afmoe import layer_list
from reference_mla import ffn, rms, rope


def selection(cfg, lp, x):
    """x: (S, H) normed -> (S, S) bool: token t attends key s."""
    S, ix = x.shape[0], cfg.indexer
    pos = jnp.arange(S)
    base = cfg.attn("full").rope_base
    qi = rope((x @ lp["w_qi"].T).reshape(S, ix.heads, ix.dim), pos, base)
    ki = x @ lp["w_ki"]
    ki = ki - ki.mean(-1, keepdims=True)
    ki = ki * jax.lax.rsqrt((ki * ki).mean(-1, keepdims=True)
                            + cfg.rms_eps) * lp["ki_norm"] + lp["ki_bias"]
    ki = rope(ki[:, None], pos, base)[:, 0]
    w = (x @ lp["w_wi"]) / math.sqrt(ix.heads * ix.dim)
    score = jnp.einsum("th,ths->ts", w, jax.nn.relu(
        jnp.einsum("thd,sd->ths", qi, ki)))
    causal = pos[None, :] <= pos[:, None]
    # a stable descending sort: equal scores keep the lower position first
    order = jnp.argsort(jnp.where(causal, -score, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1)
    return causal & (rank < ix.topk), score


def attention(cfg, lp, x):
    """x: (S, H) normed -> (S, H)."""
    a = cfg.attn("full")
    S, D = x.shape[0], a.qk_dim
    rep = cfg.heads // a.kv_heads
    pos = jnp.arange(S)
    q = rope(rms((x @ lp["w_q"].T).reshape(S, cfg.heads, D), lp["q_norm"],
                 cfg.rms_eps), pos, a.rope_base)
    k = rope(rms((x @ lp["w_k"].T).reshape(S, a.kv_heads, D),
                 lp["k_norm"], cfg.rms_eps), pos, a.rope_base)
    v = (x @ lp["w_v"].T).reshape(S, a.kv_heads, a.v_dim)
    ok, _ = selection(cfg, lp, x)
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, 1)) \
        / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, jnp.repeat(v, rep, 1))
    return o.reshape(S, cfg.heads * a.v_dim) @ lp["w_o"]


def forward(cfg, params, ids, *, selections: list | None = None
            ) -> np.ndarray:
    """ids: (S,) -> logits (S, V) float32 over the vocabulary slice;
    `selections`, where given, takes each layer's (S, S) selection."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["tok_emb"][jnp.asarray(ids)]
        for lp in layer_list(cfg, p):
            u = rms(x, lp["ln_attn_in"], cfg.rms_eps)
            if selections is not None:
                selections.append(np.asarray(selection(cfg, lp, u)[0]))
            h = x + attention(cfg, lp, u)
            x = h + ffn(cfg, lp, rms(h, lp["ln_mlp_in"], cfg.rms_eps))
        return np.asarray(
            rms(x, p["ln_out"], cfg.rms_eps) @ p["lm_head"])
