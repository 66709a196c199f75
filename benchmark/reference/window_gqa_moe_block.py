"""The plain reference of a decoder stack that MIXES sliding-window and
global attention over grouped key/value heads, with a shared-expert
MoE — served as ONE CHIP'S SHARE of an expert-parallel deployment:
`jax.numpy`, float32, matmul precision "highest", no kernel, no cache,
no page, no page group, no scan over periods — one full causal forward
over prompt + generated tokens, a sequence at a time, layer by layer,
each layer's weights made from the seed when its turn comes, used for
every sampled sequence and dropped.

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys; x: hidden, RMSNorm
eps rms_norm_eps; four norms a layer):

    x0 = E[token] * sqrt(hidden_size)                  (mup_enabled)
    h = x + N2(Attn(N1(x)));   y = h + N4(FFN(N3(h)))
    Attn(u): q = u W_Q (heads x d);  k, v = u W_K, u W_V (kv_heads x d)
        g = sigmoid(u W_G);  q, k <- RMSNorm over d (q_norm, k_norm)
        layer_types[i] == "sliding_attention": RoPE(rope_theta) on q and
            k (split-half pairs); query i sees keys 0 <= i - j < window
        "full_attention": no positions; query i sees every j <= i
        o_h = softmax(q_h . k_{h // rep} / sqrt(d)) v_{h // rep}
        Attn = (concat_h(o_h) * g) W_O
      — a block of queries at a time: a full layer against every key
      up to the block's end, a sliding layer against the window + block
      keys that end there, so that a 20k-token prompt never holds an
      (S, S) tile a head
    FFN: the leading num_dense_layers SwiGLU(intermediate_size); after
        them the shared SwiGLU expert + sum over the HELD experts among
        each token's top-k of gate * SwiGLU expert: float32 router over
        ALL experts, sigmoid scores, plain top-k (n_group = topk_group =
        1; the selection bias is zero at seeded weights), gates
        renormalised over the selection (route_norm), x route_scale.

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and afmoe.py docstrings), restated in `seed_tensor` and in the
layer loop below: the embedding at std 1/sqrt(hidden_size), and the
two norms that write into the residual stream at means 2 s (after
attention) and s / 2 (after the feed-forward), s = 1/sqrt(2 x layers).

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of requests admitted and finished
inside the window — the prompt ids it admitted, the ids it generated,
its float32 logits behind EVERY generated token — with the two numbers
of reference/latent_moe_block.py held to limits (the 90th percentile
of the positions' errors: precision; the worst position: a gross
error), over a sample that must hold BOTH classes of the mix: long
session turns whose context has passed `min_long_context` tokens (so
window pages had been given back under them and the hit resumed on a
tail) and short fresh prompts.  A prompt must be a session's script up
to one of its turns, or one of the mix's fresh prompts, whole.  The
CONTROL rounds every matrix and every cached key and value to
float8_e4m3: it has to fail.
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
QUERY_BLOCK = 256                    # queries a score tile holds
# (exponent bits, mantissa bits) for lax.reduce_precision: roundings
# are made with it and not with a pair of converts, which the chip's
# compiler may drop as excess precision (PR 30)
BF16, F8 = (8, 7), (4, 3)            # bfloat16; float8_e4m3


# ------------------------------------------------------------- weights

def seed_tensor(seed, name, shape, std, mean=0.0, bf16=True, f8=False):
    """The program's recipe, value for value (reference/
    latent_moe_block.py has the same lines): threefry bits from
    fold_in(PRNGKey(seed % (2**31-1)), crc32(name) & 0x7fffffff), the
    top 24 bits as u in [0, 1), mean + (u - 0.5) * sqrt(12) * std in
    float32, rounded to bfloat16 where the program keeps bfloat16 —
    returned as float32.  f8: the control's extra rounding."""
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


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False, block: int = QUERY_BLOCK,
                   late: int = 0):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control.  late: sliding layers see
    `late` fewer of their oldest keys (what a planted fault does; 0 in
    every comparison)."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads, kvh = g_("hidden_size"), g_("num_attention_heads"), \
        g_("num_key_value_heads")
    D, W = g_("head_dim"), g_("sliding_window") - late
    rep = heads // kvh
    dense_dim, moe_dim = g_("intermediate_size"), g_("moe_intermediate_size")
    n_experts, top_k = g_("num_experts"), g_("num_experts_per_tok")
    scale, eps, theta = g_("route_scale"), g_("rms_norm_eps"), \
        g_("rope_theta")
    layers, dense_layers = share["layers"], share["dense_layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    sliding = [t == "sliding_attention"
               for t in g_("layer_types")[:layers]]
    out_mean = 1.0 / math.sqrt(2.0 * g_("num_hidden_layers"))

    def low(a):
        return jax.lax.reduce_precision(a, *F8) if f8 else a

    def mat(name, shape):
        return seed_tensor(seed, name, shape, 1.0 / math.sqrt(shape[0]),
                           f8=f8)

    def vec(name, width, mean=1.0):
        return seed_tensor(seed, name, (width,), 0.1 * mean, mean=mean,
                           bf16=False)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def rope(x):                        # (S, heads, D) at positions 0..
        half = D // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32)
                                 / half))
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def attend(lw, x, slide: bool):     # x: (S, H) normed, S % block == 0
        S = x.shape[0]
        q = rms((x @ lw["w_q"]).reshape(S, heads, D), lw["q_norm"])
        k = rms((x @ lw["w_k"]).reshape(S, kvh, D), lw["k_norm"])
        v = (x @ lw["w_v"]).reshape(S, kvh, D)
        if slide:
            q, k = rope(q), rope(k)
        k, v = low(k), low(v)           # what the cache would hold
        q = q.reshape(S, kvh, rep, D)
        # a sliding block sees the W + block keys that end with it
        span = min(W + block, S) if slide else S
        pad = span if slide else 0
        kp = jnp.concatenate([jnp.zeros((pad, kvh, D)), k])
        vp = jnp.concatenate([jnp.zeros((pad, kvh, D)), v])

        def blk(i0):
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            j0 = i0 + block - span if slide else 0      # first key seen
            kb = jax.lax.dynamic_slice_in_dim(kp, j0 + pad, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(vp, j0 + pad, span, 0)
            s = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(D)
            i = (i0 + jnp.arange(block))[:, None]
            j = (j0 + jnp.arange(span))[None, :]
            ok = (j <= i) & (j >= 0)
            if slide:
                ok &= i - j < W
            p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", p, vb)

        o = jax.lax.map(blk, jnp.arange(0, S, block))
        return (o.reshape(S, heads * D)
                * jax.nn.sigmoid(x @ lw["w_g"])) @ lw["w_o"]

    def gates(router, x):               # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ router)
        topv, topi = jax.lax.top_k(scores, top_k)
        if model.get("route_norm", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], topi].set(topv * scale)

    attend_fn = jax.jit(attend, static_argnums=2)
    ffn_dense, gate_fn = jax.jit(swiglu), jax.jit(gates)
    add_expert = jax.jit(lambda f, y, ge, wg, wu, wd:
                         f + ge * swiglu(y, wg, wu, wd))
    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{v_first}", (v_held, H),
                          1.0 / math.sqrt(H), f8=f8)
        # a sequence at its class's length — the long ones padded to the
        # longest of them, the others to the longest of those, in
        # eights of query blocks — so that one compiled program serves
        # a class, and the next run's (padding sits after every real
        # token, where nothing causal looks)
        def padded(n):
            return -(-n // (8 * block)) * 8 * block
        cut = padded(max(len(s) for s in seqs)) // 2
        sizes = [padded(max(len(t) for t in seqs
                            if (len(t) > cut) == (len(s) > cut)))
                 for s in seqs]
        xs = []
        for s, size in zip(seqs, sizes):
            ids = np.zeros((size,), np.int32)
            ids[:len(s)] = s
            x = emb[jnp.asarray(ids)]
            xs.append(x * math.sqrt(H) if model.get("mup_enabled") else x)
        del emb
        for i in range(layers):
            p = f"layers.{i}."
            lw = {"w_q": mat(p + "w_q", (H, heads * D)),
                  "w_k": mat(p + "w_k", (H, kvh * D)),
                  "w_v": mat(p + "w_v", (H, kvh * D)),
                  "w_g": mat(p + "w_g", (H, heads * D)),
                  "q_norm": vec(p + "q_norm", D),
                  "k_norm": vec(p + "k_norm", D),
                  "w_o": mat(p + "w_o", (heads * D, H))}
            n1, n2 = vec(p + "ln_attn_in", H), \
                vec(p + "ln_attn_out", H, 2.0 * out_mean)
            hs = [x + rms(attend_fn(lw, rms(x, n1), sliding[i]), n2)
                  for x in xs]
            del lw, xs
            n3, n4 = vec(p + "ln_mlp_in", H), \
                vec(p + "ln_mlp_out", H, 0.5 * out_mean)
            ys = [rms(h, n3) for h in hs]
            if i < dense_layers:
                w = (mat(p + "w_gate", (H, dense_dim)),
                     mat(p + "w_up", (H, dense_dim)),
                     mat(p + "w_down", (dense_dim, H)))
                fs = [ffn_dense(y, *w) for y in ys]
            else:
                router = seed_tensor(seed, p + "router", (H, n_experts),
                                     1.0 / math.sqrt(H), bf16=False)
                ges = [gate_fn(router, y) for y in ys]
                fs = [jnp.zeros_like(y) for y in ys]
                if model.get("num_shared_experts", 0):
                    w = (mat(p + "shared.gate", (H, moe_dim)),
                         mat(p + "shared.up", (H, moe_dim)),
                         mat(p + "shared.down", (moe_dim, H)))
                    fs = [ffn_dense(y, *w) for y in ys]
                for e in range(e_first, e_first + e_held):
                    q_ = f"{p}experts.{e}."
                    w = (mat(q_ + "gate", (H, moe_dim)),
                         mat(q_ + "up", (H, moe_dim)),
                         mat(q_ + "down", (moe_dim, H)))
                    fs = [add_expert(f, y, ge[:, e: e + 1], *w)
                          for f, y, ge in zip(fs, ys, ges)]
                del ges
            xs = [h + rms(f, n4) for h, f in zip(hs, fs)]
            del hs, ys, fs, w
        head = mat(f"lm_head.{v_first}", (H, v_held))
        ln_out = vec("ln_out", H)
        return [np.asarray(rms(x[jnp.asarray(pos)], ln_out) @ head)
                for x, pos in zip(xs, positions)]


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
    pay = run.mix.payload
    turn_ends = [set(int(e) for e in ends) for ends in pay["ends"]]
    fresh = {len(p): [] for p in pay["fresh_ids"]}
    for p in pay["fresh_ids"]:
        fresh[len(p)].append(p)

    def a_turn(prompt) -> bool:
        """The prompt is some session's script up to one of its turns."""
        return any(len(prompt) in ends
                   and np.array_equal(prompt, ids[:len(prompt)])
                   for ids, ends in zip(pay["ids"], turn_ends))

    def a_fresh(prompt) -> bool:
        return any(np.array_equal(prompt, p)
                   for p in fresh.get(len(prompt), ()))

    long_ctx = int(spec["min_long_context"])
    longs = [d for d in recs if a_turn(d["prompt"])]
    shorts = [d for d in recs if a_fresh(d["prompt"])]
    foreign = len(recs) - len(longs) - len(shorts)
    rng = np.random.default_rng([int(run.args.seed), 17])

    def some(pool, n):
        return [pool[int(i)] for i in rng.choice(
            len(pool), min(n, len(pool)), replace=False)] if pool else []
    pick_long = some(longs, int(spec["sample_long"]))
    pick_short = some(shorts, int(spec["sample_short"]))
    pick = pick_long + pick_short
    deep = sum(len(d["prompt"]) > long_ctx for d in pick_long)
    cold = sum(int(d["n_prefix"]) == 0 for d in pick_long)
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
                + " ".join(f"{len(d['prompt'])}(hit {int(d['n_prefix'])})"
                           for d in pick)
                + " tokens; per answer p90/worst "
                + " ".join(f"{np.percentile(e, 90):.3f}/{e.max():.3f}"
                           for e in errs)
                + ("; CONTROL: the reference itself with matrices and "
                   "cached keys and values rounded to float8_e4m3, in "
                   "the daemon's place" if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_worst_position", worst, lim["max_logit_err_worst"],
         "<="),
        ("prompts_of_neither_class", foreign, 0, "<="),
        ("sampled_long_turns_served_cold", cold,
         int(spec.get("max_cold", 0)), "<="),
        ("long_answers_past_the_window_sampled", deep,
         int(spec["min_long"]), ">="),
        ("short_answers_sampled", len(pick_short),
         int(spec["min_short"]), ">=")],
        "note": f"{len(pick_long)} long + {len(pick_short)} short of "
                f"{len(longs)} + {len(shorts)} audit records inside the "
                f"window ({len(paths)} written) against a float32 "
                f"'highest' forward of prompt + generated tokens, "
                f"errors relative to the reference logits' standard "
                f"deviation, {time.perf_counter() - t0:.1f}s{note}"}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--job":
        raise SystemExit(job_main(sys.argv[2]))
    raise SystemExit("usage: window_gqa_moe_block.py --job JOB.json")
