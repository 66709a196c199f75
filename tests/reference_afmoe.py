"""The plain reference of the mixed sliding-window / global stack
(models/afmoe.py): `jax.numpy`, float32, matmul precision "highest",
no kernel, no cache, no page, no scan over periods — one full causal
forward over a whole sequence, every score a head an (S, S) tile, the
window a mask on it:

    sliding layer: RoPE on q and k; query i sees 0 <= i - j < window
    full layer:    no positions;    query i sees j <= i

every routed expert of the share a dense sum (reference_mla.ffn).

A second copy lives under benchmark/reference/ and makes its own
weights from the seed; this one takes a parameter tree (the program's,
cast to float32).  tests/test_afmoe.py holds the two to each other."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_mla import ffn, rms, rope


def layer_list(cfg, params) -> list[dict]:
    """The program's tree (head / stacked periods / tail) a layer."""
    head, period, n = cfg.plan
    out = list(params["head"])
    for k in range(n):
        out += [jax.tree_util.tree_map(lambda a: a[k], lp)
                for lp in params["periods"]]
    return out + list(params["tail"])


def attention(cfg, lp, kind, x):
    """x: (S, H) normed -> (S, H)."""
    S, D = x.shape[0], cfg.head_dim
    rep = cfg.heads // cfg.kv_heads
    # the program keeps these three as (out, hidden)
    q = rms((x @ lp["w_q"].T).reshape(S, cfg.heads, D), lp["q_norm"],
            cfg.rms_eps)
    k = rms((x @ lp["w_k"].T).reshape(S, cfg.kv_heads, D), lp["k_norm"],
            cfg.rms_eps)
    v = (x @ lp["w_v"].T).reshape(S, cfg.kv_heads, D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = j <= i
    if kind == "window":
        q, k = rope(q, jnp.arange(S), cfg.rope_base), \
            rope(k, jnp.arange(S), cfg.rope_base)
        ok &= i - j < cfg.window
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, 1)) \
        / math.sqrt(D)
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, jnp.repeat(v, rep, 1))
    return (o.reshape(S, cfg.heads * D)
            * jax.nn.sigmoid(x @ lp["w_g"])) @ lp["w_o"]


def forward(cfg, params, ids) -> np.ndarray:
    """ids: (S,) -> logits (S, V) float32 over the vocabulary slice."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["tok_emb"][jnp.asarray(ids)]
        if cfg.mup:
            x = x * math.sqrt(cfg.hidden)
        for lp, kind in zip(layer_list(cfg, p), cfg.kinds):
            a = attention(cfg, lp, kind,
                          rms(x, lp["ln_attn_in"], cfg.rms_eps))
            h = x + rms(a, lp["ln_attn_out"], cfg.rms_eps)
            f = ffn(cfg, lp, rms(h, lp["ln_mlp_in"], cfg.rms_eps))
            x = h + rms(f, lp["ln_mlp_out"], cfg.rms_eps)
        return np.asarray(
            rms(x, p["ln_out"], cfg.rms_eps) @ p["lm_head"])
