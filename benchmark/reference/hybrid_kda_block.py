"""The plain reference of a HYBRID decoder stack — gated delta-rule
(KDA) layers beside NoPE latent-attention (MLA) layers, with a
shared-expert MoE — served as ONE CHIP'S SHARE of an expert-parallel
deployment: `jax.numpy`, float32, matmul precision "highest", no
kernel, no cache, no chunked delta rule, no state slot — one full
causal forward over prompt + generated tokens, layer by layer, each
layer's weights made from the seed when its turn comes.

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys; x: hidden, RMSNorm
eps rms_norm_eps, pre-norm):

    h = x + Mix(N1(x));   y = h + FFN(N2(h))
    KDA (linear_attn_config.kda_layers; H heads of d, kernel K):
        q = l2norm(silu(conv(x Wq)))  k = l2norm(silu(conv(x Wk)))
        v = silu(conv(x Wv))          conv: causal depthwise, K taps
        g = -exp(A_log_h) softplus(x Wf_a Wf_b + dt_bias);  b = sigmoid(x Wb)
        S' = diag(exp(g_t)) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t / sqrt(d);  Mix = (rmsnorm_d(o) sigmoid(x Wg_a Wg_b)) Wo
      — a plain lax.scan over the tokens, S key-major (d_k x d_v)
    MLA (full_attn_layers): q_h = x WQ_h; [c | k_r] = x W_DKV,
        c = Nkv(c); no positions; [k_nope_h | v_h] = c W_UKV_h;
        score_h = (q_nope_h.k_nope_h + q_r_h.k_r) / sqrt(nope + rope);
        causal softmax — computed a block of queries at a time, so
        that a 14k-token prompt never holds an (S, S) tile a head
    FFN: the leading dense layers SwiGLU(intermediate_size); after them
        the shared SwiGLU expert + sum over the HELD experts among each
        token's top-k of gate * SwiGLU expert: float32 router over ALL
        experts, sigmoid scores, plain top-k, gates renormalised over
        the selection, x routed_scaling_factor.

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and kda.py docstrings), restated in `seed_tensor` and in the
layer loop below: A_log, dt_bias, and the 1 / sqrt(2 x layers) on what
writes into the residual stream included.

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of turns admitted and finished inside
the window — the prompt ids it admitted, the ids it generated, its
float32 logits behind EVERY generated token — as
reference/latent_moe_block.py does, with the same two numbers held to
limits (the 90th percentile of the positions' errors: precision; the
worst position: a gross error such as a state restored from the wrong
page boundary), and for the same reason (a sparse expert layer is not
continuous where two experts score alike).  A prompt must be a
session's script up to one of its turns.  The CONTROL rounds every
matrix, every cached latent and the recurrent STATE, after every
token, to float8_e4m3: it has to fail.
"""
from __future__ import annotations

import glob
import json
import math
import os
import subprocess
import sys
import time
import zlib

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
QUERY_BLOCK = 512                    # queries an MLA score tile holds
# (exponent bits, mantissa bits) for lax.reduce_precision: roundings
# are made with it and not with a pair of converts, which the chip's
# compiler may drop as excess precision (the first control run on the
# chip read 0.0007: nothing had been rounded)
BF16, F8 = (8, 7), (4, 3)            # bfloat16; float8_e4m3


# ------------------------------------------------------------- weights

def seed_tensor(seed, name, shape, std, mean=0.0, bf16=True, f8=False):
    """The program's recipe, value for value (reference/
    latent_moe_block.py has the same lines): threefry bits from
    fold_in(PRNGKey(seed % (2**31-1)), crc32(name) & 0x7fffffff), the
    top 24 bits as u in [0, 1), mean + (u - 0.5) * sqrt(12) * std in
    float32, rounded to bfloat16 where the program keeps bfloat16 —
    returned as float32.  f8: the control's extra rounding, to
    float8_e4m3's 4 exponent and 3 mantissa bits."""
    import jax
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)),
        zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _make(tuple(int(s) for s in shape), bool(bf16), bool(f8))(
        key, float(mean), float(std))


_MAKERS: dict = {}


def _make(shape, bf16: bool, f8: bool):
    fn = _MAKERS.get((shape, bf16, f8))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def make(key, mean, std):
            bits = jax.random.bits(key, shape, jnp.uint32)
            u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
            w = mean + (u - 0.5) * (jnp.float32(math.sqrt(12.0)) * std)
            if bf16:
                w = jax.lax.reduce_precision(w, *BF16)
            if f8:
                w = jax.lax.reduce_precision(w, *F8)
            return w
        fn = _MAKERS[(shape, bf16, f8)] = jax.jit(make)
    return fn


def layer_kinds(model: dict, layers: int) -> list[str]:
    """The kept layers' kinds: the config's lists count from 1."""
    kda = set(model["linear_attn_config"]["kda_layers"])
    return ["kda" if i + 1 in kda else "mla" for i in range(layers)]


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False, block: int = QUERY_BLOCK):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads = g_("hidden_size"), g_("num_attention_heads")
    kv_rank, nope = g_("kv_lora_rank"), g_("qk_nope_head_dim")
    rope, vd = g_("qk_rope_head_dim"), g_("v_head_dim")
    lin = g_("linear_attn_config")
    KH, d, K = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    KW = KH * d
    dense_dim, moe_dim = g_("intermediate_size"), g_("moe_intermediate_size")
    n_experts, top_k = g_("num_experts"), g_("num_experts_per_token")
    scale, eps = g_("routed_scaling_factor"), g_("rms_norm_eps")
    layers, dense_layers = share["layers"], share["dense_layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    kinds = layer_kinds(model, layers)
    n = len(seqs)
    # padded to whole query blocks (padding sits after every real
    # token, where nothing causal looks)
    S = -(-max(len(s) for s in seqs) // block) * block
    ids = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s

    def low(a):
        return jax.lax.reduce_precision(a, *F8) if f8 else a

    def mat(name, shape, scale=1.0):
        return seed_tensor(seed, name, shape,
                           scale / math.sqrt(shape[0]), f8=f8)

    # what writes into the residual stream: std / sqrt(2 x the WHOLE
    # model's layers) (the program's recipe, models/kda.py)
    out_scale = 1.0 / math.sqrt(2.0 * g_("num_hidden_layers"))

    def res(name, shape):
        return mat(name, shape, out_scale)

    def vec(name, width, std=0.1, mean=1.0):
        return seed_tensor(seed, name, (width,), std, mean=mean, bf16=False)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    def kda(lw, x):                    # x: (n, S, H) normed
        cat = jnp.concatenate([x @ lw["w_q"], x @ lw["w_k"],
                               x @ lw["w_v"]], -1)
        pad = jnp.concatenate([jnp.zeros((n, K - 1, 3 * KW)), cat], 1)
        y = jax.nn.silu(sum(pad[:, j: j + S] * lw["conv"][j]
                            for j in range(K))).reshape(n, S, 3, KH, d)
        q, k, v = l2(y[:, :, 0]), l2(y[:, :, 1]), y[:, :, 2]
        g = -jnp.exp(lw["a_log"])[:, None] * jax.nn.softplus(
            (x @ lw["w_fa"] @ lw["w_fb"] + lw["dt_bias"])
            .reshape(n, S, KH, d))
        b = jax.nn.sigmoid(x @ lw["w_b"])                 # (n, S, KH)

        def step(st, xs):              # st: (n, KH, d_k, d_v)
            q_t, k_t, v_t, g_t, b_t = xs
            sd = jnp.exp(g_t)[..., None] * st
            u = b_t[..., None] * (v_t - jnp.einsum("nhkv,nhk->nhv", sd,
                                                   k_t))
            st = low(sd + k_t[..., None] * u[:, :, None, :])
            return st, jnp.einsum("nhkv,nhk->nhv", st, q_t) / math.sqrt(d)

        _, o = jax.lax.scan(
            step, jnp.zeros((n, KH, d, d)),
            tuple(a.swapaxes(0, 1) for a in (q, k, v, g, b)))
        o = o.swapaxes(0, 1)                              # (n, S, KH, d)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * lw["ln_o"]
        gate = jax.nn.sigmoid(x @ lw["w_ga"] @ lw["w_gb"])
        return (o.reshape(n, S, KW) * gate) @ lw["w_o"]

    def mla(lw, x):                    # x: (S, H) normed, one sequence
        q = (x @ lw["w_q"]).reshape(S, heads, nope + rope)
        ckr = x @ lw["w_dkv"]
        c = low(rms(ckr[:, :kv_rank], lw["ln_kv"]))
        k_r = low(ckr[:, kv_rank:])
        kv = (c @ lw["w_ukv"]).reshape(S, heads, nope + vd)

        def blk(i0):
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            s = (jnp.einsum("qhd,khd->hqk", qb[..., :nope], kv[..., :nope])
                 + jnp.einsum("qhr,kr->hqk", qb[..., nope:], k_r)) \
                / math.sqrt(nope + rope)
            ok = jnp.arange(S)[None, :] <= (i0 + jnp.arange(block))[:, None]
            p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", p, kv[..., nope:])

        o = jax.lax.map(blk, jnp.arange(0, S, block))
        return o.reshape(S, heads * vd) @ lw["w_o"]

    def gates(router, x):              # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ router)
        topv, topi = jax.lax.top_k(scores, top_k)
        if model.get("moe_renormalize", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], topi].set(topv * scale)

    kda_fn, mla_fn, ffn_dense = jax.jit(kda), jax.jit(mla), jax.jit(swiglu)
    add_expert = jax.jit(lambda f, y, ge, wg, wu, wd:
                         f + ge * swiglu(y, wg, wu, wd))
    gate_fn = jax.jit(gates)
    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{v_first}", (v_held, H), 1.0,
                          f8=f8)
        x = emb[jnp.asarray(ids)]                         # (n, S, H)
        del emb
        for i, kind in enumerate(kinds):
            p = f"layers.{i}."
            xn = rms(x, vec(p + "ln_mix_in", H))
            if kind == "kda":
                lw = {"w_q": mat(p + "w_q", (H, KW)),
                      "w_k": mat(p + "w_k", (H, KW)),
                      "w_v": mat(p + "w_v", (H, KW)),
                      "conv": jnp.concatenate([
                          seed_tensor(seed, p + f"conv_{c}", (K, KW),
                                      1.0 / math.sqrt(K), bf16=False,
                                      f8=f8) for c in "qkv"], -1),
                      "w_fa": mat(p + "w_fa", (H, d)),
                      "w_fb": mat(p + "w_fb", (d, KW)),
                      "a_log": vec(p + "a_log", KH,
                                   math.log(16.0) / math.sqrt(12.0),
                                   math.log(16.0) / 2),
                      "dt_bias": vec(p + "dt_bias", KW, 1.0, -4.0),
                      "w_b": mat(p + "w_b", (H, KH)),
                      "w_ga": mat(p + "w_ga", (H, d)),
                      "w_gb": mat(p + "w_gb", (d, KW)),
                      "ln_o": vec(p + "ln_o", d),
                      "w_o": res(p + "w_o", (KW, H))}
                a = kda_fn(lw, xn)
            else:
                lw = {"w_q": mat(p + "w_q", (H, heads * (nope + rope))),
                      "w_dkv": mat(p + "w_dkv", (H, kv_rank + rope)),
                      "ln_kv": vec(p + "ln_kv", kv_rank),
                      "w_ukv": mat(p + "w_ukv",
                                   (kv_rank, heads * (nope + vd))),
                      "w_o": res(p + "w_o", (heads * vd, H))}
                a = jnp.stack([mla_fn(lw, xn[j]) for j in range(n)])
            h = x + a
            del lw, a, xn
            n3 = vec(p + "ln_mlp_in", H)
            out = []
            for j in range(n):         # a sequence at a time: memory
                y = rms(h[j], n3)
                if i < dense_layers:
                    f = ffn_dense(y, mat(p + "w_gate", (H, dense_dim)),
                                  mat(p + "w_up", (H, dense_dim)),
                                  res(p + "w_down", (dense_dim, H)))
                else:
                    ge = gate_fn(seed_tensor(
                        seed, p + "router", (H, n_experts),
                        1.0 / math.sqrt(H), bf16=False), y)
                    f = jnp.zeros_like(y)
                    if model.get("num_shared_experts", 0):
                        f = ffn_dense(
                            y, mat(p + "shared.gate", (H, moe_dim)),
                            mat(p + "shared.up", (H, moe_dim)),
                            res(p + "shared.down", (moe_dim, H)))
                    for e in range(e_first, e_first + e_held):
                        q_ = f"{p}experts.{e}."
                        f = add_expert(f, y, ge[:, e: e + 1],
                                       mat(q_ + "gate", (H, moe_dim)),
                                       mat(q_ + "up", (H, moe_dim)),
                                       res(q_ + "down", (moe_dim, H)))
                out.append(h[j] + f)
            x = jnp.stack(out)
            del h, out
        head = mat(f"lm_head.{v_first}", (H, v_held))
        ln_out = vec("ln_out", H)
        return [np.asarray(rms(x[j, jnp.asarray(pos)], ln_out) @ head)
                for j, pos in enumerate(positions)]


# ------------------------------------------------------------ the check

def published(cfg: dict) -> dict:
    """The configuration's model keys at their PUBLISHED values."""
    return {**{k: cfg[k] for k in cfg["model_keys"]},
            **cfg.get("published", {})}


def job_main(path: str) -> int:
    """The child: runs the forward on the device the run was given."""
    job = json.load(open(path))
    sys.path.insert(0, os.path.dirname(HERE))
    import host                          # benchmark/host.py
    host.check_device(job["chips"], job["rehearse"])
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(HERE)), ".xla_cache"))
    data = np.load(job["records"], allow_pickle=False)
    seqs, positions = [], []
    for i in range(int(data["n"])):
        prompt, toks = data[f"prompt{i}"], data[f"tokens{i}"]
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        positions.append(list(range(len(prompt) - 1,
                                    len(prompt) - 1 + len(toks))))
    out = {}
    for name, f8 in (("ref", False),) + ((("f8", True),)
                                         if job["control"] else ()):
        got = forward_logits(job["model"], job["share"], job["seed"],
                             seqs, positions, f8=f8,
                             block=int(job["block"]))
        for i, g in enumerate(got):
            out[f"{name}{i}"] = g
    np.savez(job["out"], **out)
    return 0


def rel_err(got, ref) -> np.ndarray:
    """(positions, V) each -> (positions,) max error over the
    vocabulary relative to the reference's spread at the position."""
    return np.max(np.abs(got - ref), -1) / np.maximum(np.std(ref, -1),
                                                      1e-12)


def check(run) -> dict:
    t0 = time.perf_counter()
    cfg, spec = run.cfg, run.cfg["reference"]
    lim = spec["limits"]
    paths = sorted(glob.glob(os.path.join(run.work, "audit", "*.npz")))
    recs = []
    for p in paths:
        d = np.load(p, allow_pickle=False)
        if d["t_admit"] >= run.t0_wall and d["t_done"] <= run.t1_wall \
                and len(d["tokens"]) >= 1:
            recs.append(d)
    rng = np.random.default_rng([int(run.args.seed), 17])
    want = int(spec["sample"])
    pick = [recs[int(i)] for i in rng.choice(
        len(recs), min(want, len(recs)), replace=False)] if recs else []
    pay = run.mix.payload
    turn_ends = [set(int(e) for e in ends) for ends in pay["ends"]]

    def a_turn(prompt) -> bool:
        """The prompt is some session's script up to one of its turns."""
        return any(len(prompt) in ends
                   and np.array_equal(prompt, ids[:len(prompt)])
                   for ids, ends in zip(pay["ids"], turn_ends))
    foreign = sum(not a_turn(d["prompt"]) for d in pick)
    cold = sum(int(d["n_prefix"]) == 0 for d in pick)
    p90 = worst = float("inf")
    n_pos = 0
    note = ""
    if pick:
        work = os.path.join(run.work, "reference")
        os.makedirs(work, exist_ok=True)
        arrays = {"n": len(pick)}
        for i, d in enumerate(pick):
            arrays[f"prompt{i}"] = d["prompt"]
            arrays[f"tokens{i}"] = d["tokens"]
        np.savez(os.path.join(work, "records.npz"), **arrays)
        job = {"model": published(cfg), "share": cfg["share"],
               "seed": int(run.prepared["weights_seed"]),
               "chips": run.cell["chips"],
               "rehearse": bool(run.args.rehearse),
               "control": bool(run.args.control),
               "block": int(spec.get("query_block", QUERY_BLOCK)),
               "records": os.path.join(work, "records.npz"),
               "out": os.path.join(work, "logits.npz")}
        with open(os.path.join(work, "job.json"), "w") as f:
            json.dump(job, f)
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--job",
             os.path.join(work, "job.json")],
            env=run.env, capture_output=True, text=True, timeout=2400)
        if p.returncode != 0:
            raise RuntimeError("the reference's child failed: "
                               + p.stderr[-1500:])
        out = np.load(job["out"])
        errs = [rel_err(out[f"f8{i}"] if run.args.control
                        else d["logits"], out[f"ref{i}"])
                for i, d in enumerate(pick)]
        flat = np.concatenate(errs)
        n_pos = len(flat)
        p90, worst = float(np.percentile(flat, 90)), float(flat.max())
        apart = np.concatenate([rel_err(out[f"ref{i}"][1:],
                                        out[f"ref{i}"][:-1])
                                for i in range(len(pick))] or [[0.0]])
        note = (f"; {n_pos} positions, median {np.median(flat):.4f}, "
                f"neighbouring positions' logits differ by "
                f"{np.median(apart):.2f} (median), "
                f"{int((flat > 2 * lim['max_logit_err']).sum())} over "
                f"twice the precision limit; prompts of "
                + " ".join(str(len(d["prompt"])) for d in pick)
                + " tokens; per answer p90/worst "
                + " ".join(f"{np.percentile(e, 90):.3f}/{e.max():.3f}"
                           for e in errs)
                + ("; CONTROL: the reference itself with matrices, "
                   "latents and the recurrent state rounded to "
                   "float8_e4m3, in the daemon's place"
                   if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_worst_position", worst, lim["max_logit_err_worst"],
         "<="),
        ("prompts_not_a_session_turn", foreign, 0, "<="),
        ("sampled_turns_served_cold", cold,
         int(spec.get("max_cold", len(pick))), "<="),
        ("answers_sampled", len(pick), min(want, max(len(recs), 1)),
         ">="),
        ("audit_records_in_window", len(recs),
         int(spec.get("min_records", 1)), ">=")],
        "note": f"{len(pick)} of {len(recs)} audit records inside the "
                f"window ({len(paths)} written) against a float32 "
                f"'highest' forward of prompt + generated tokens, "
                f"errors relative to the reference logits' standard "
                f"deviation, {time.perf_counter() - t0:.1f}s{note}"}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--job":
        raise SystemExit(job_main(sys.argv[2]))
    raise SystemExit("usage: hybrid_kda_block.py --job JOB.json")
