"""The plain reference of the latent-attention + shared-expert MoE
block (models/mla.py): `jax.numpy`, float32, matmul precision
"highest", no kernel, no cache, no batching — one full causal forward
over a whole sequence, straight from the published equations, with
the SHARE of an expert-parallel deployment (only the held experts'
part of each routed sum, plus the shared expert).

A second copy lives under benchmark/reference/ and makes its own
weights from the seed; this one takes a parameter tree (the program's,
cast to float32), so that tests can also hand it perturbed weights.
tests/test_mla.py holds the two copies to each other."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def rope(x, pos, base):
    """x: (S, ..., D) rotated in split-half pairs (x[..., :D/2],
    x[..., D/2:]) at positions pos (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def attention(cfg, lp, x):
    """x: (S, H) normed.  Full causal MLA, every head expanded."""
    S = x.shape[0]
    pos = jnp.arange(S)
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    cq = rms(x @ lp["w_dq"], lp["ln_q"], cfg.rms_eps)
    q = (cq @ lp["w_uq"]).reshape(S, cfg.heads, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos,
                                         cfg.rope_base)
    ckr = x @ lp["w_dkv"]
    c = rms(ckr[:, :cfg.kv_lora_rank], lp["ln_kv"], cfg.rms_eps)
    k_r = rope(ckr[:, cfg.kv_lora_rank:], pos, cfg.rope_base)
    kv = (c @ lp["w_ukv"]).reshape(S, cfg.heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhr,kr->hqk", q_rope, k_r)) / math.sqrt(nope + rp)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return o.reshape(S, cfg.heads * vd) @ lp["w_o"]


def router_gates(cfg, lp, x):
    """(S, E) gate matrix over ALL experts: zero outside the top-k."""
    logits = x @ lp["router"]
    scores = jax.nn.sigmoid(logits) if cfg.score_fn == "sigmoid" \
        else jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        topv = topv / topv.sum(-1, keepdims=True)
    topv = topv * cfg.routed_scaling_factor
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)


def ffn(cfg, lp, x):
    if "router" not in lp:
        return swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
    gates = router_gates(cfg, lp, x)
    out = jnp.zeros_like(x)
    if "shared_gate" in lp:
        out = swiglu(x, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    for j in range(cfg.experts_held):          # the held experts only
        e = cfg.experts_first + j
        out = out + gates[:, e: e + 1] * swiglu(
            x, lp["exp_gate"][j], lp["exp_up"][j], lp["exp_down"][j])
    return out


def layer(cfg, lp, x):
    a = attention(cfg, lp, rms(x, lp["ln_attn_in"], cfg.rms_eps))
    if cfg.sandwich_norm:
        a = rms(a, lp["ln_attn_out"], cfg.rms_eps)
    h = x + a
    f = ffn(cfg, lp, rms(h, lp["ln_mlp_in"], cfg.rms_eps))
    if cfg.sandwich_norm:
        f = rms(f, lp["ln_mlp_out"], cfg.rms_eps)
    return h + f


def forward(cfg, params, ids) -> np.ndarray:
    """ids: (S,) -> logits (S, V) float32 over the vocabulary slice."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), params)
        x = p["tok_emb"][jnp.asarray(ids)]
        for lp in p["layers"]:
            x = layer(cfg, lp, x)
        return np.asarray(
            rms(x, p["ln_out"], cfg.rms_eps) @ p["lm_head"])
