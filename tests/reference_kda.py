"""The plain reference of the hybrid stack (models/kda.py): gated
delta-rule (KDA) layers + NoPE latent attention + the shared-expert
MoE share — `jax.numpy`, float32, matmul precision "highest", no
kernel, no cache, no chunk, no batch.  KDA is a plain `lax.scan` over
tokens of the three published lines

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t / sqrt(d)

with S kept key-major (d_k x d_v), NOT the program's value-major
layout; MLA is expanded, every head; every routed expert of the share
is a dense sum (reference_mla.ffn).

A second copy lives under benchmark/reference/ and makes its own
weights from the seed; this one takes a parameter tree (the program's,
cast to float32).  tests/test_kda.py holds the two to each other."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_mla import ffn, rms


def kda_mixer(cfg, lp, x):
    """x: (S, H) normed -> (S, H)."""
    S = x.shape[0]
    KH, d, K = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel
    cat = jnp.concatenate([x @ lp["w_q"], x @ lp["w_k"], x @ lp["w_v"]],
                          -1)
    padded = jnp.concatenate([jnp.zeros((K - 1, cat.shape[1])), cat])
    y = jax.nn.silu(sum(padded[j: j + S] * lp["conv"][j]
                        for j in range(K))).reshape(S, 3, KH, d)

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = l2(y[:, 0]), l2(y[:, 1]), y[:, 2]
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        (x @ lp["w_fa"] @ lp["w_fb"] + lp["dt_bias"]).reshape(S, KH, d))
    b = jax.nn.sigmoid(x @ lp["w_b"])                     # (S, KH)

    def step(st, xs):                                     # st: (KH, dk, dv)
        q_t, k_t, v_t, g_t, b_t = xs
        sd = jnp.exp(g_t)[:, :, None] * st
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", sd, k_t))
        st = sd + k_t[:, :, None] * u[:, None, :]
        return st, jnp.einsum("hkv,hk->hv", st, q_t) / math.sqrt(d)

    _, o = jax.lax.scan(step, jnp.zeros((KH, d, d)), (q, k, v, g, b))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_eps) * lp["ln_o"]
    gate = jax.nn.sigmoid(x @ lp["w_ga"] @ lp["w_gb"])
    return (o.reshape(S, KH * d) * gate) @ lp["w_o"]


def mla_mixer(cfg, lp, x):
    """x: (S, H) normed.  Full causal MLA without positions."""
    S = x.shape[0]
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    q = (x @ lp["w_q"]).reshape(S, cfg.heads, cfg.qk_head_dim)
    ckr = x @ lp["w_dkv"]
    c = rms(ckr[:, :cfg.kv_lora_rank], lp["ln_kv"], cfg.rms_eps)
    k_r = ckr[:, cfg.kv_lora_rank:]
    kv = (c @ lp["w_ukv"]).reshape(S, cfg.heads, nope + vd)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("qhr,kr->hqk", q[..., nope:], k_r)) \
        / math.sqrt(cfg.qk_head_dim)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.reshape(S, cfg.heads * vd) @ lp["w_o"]


def forward(cfg, params, ids) -> np.ndarray:
    """ids: (S,) -> logits (S, V) float32 over the vocabulary slice."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["tok_emb"][jnp.asarray(ids)]
        for lp, kind in zip(p["layers"], cfg.kinds):
            mix = kda_mixer if kind == "kda" else mla_mixer
            h = x + mix(cfg, lp, rms(x, lp["ln_mix_in"], cfg.rms_eps))
            x = h + ffn(cfg, lp, rms(h, lp["ln_mlp_in"], cfg.rms_eps))
        return np.asarray(
            rms(x, p["ln_out"], cfg.rms_eps) @ p["lm_head"])
