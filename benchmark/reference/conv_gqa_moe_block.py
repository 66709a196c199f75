"""The plain reference of a decoder stack whose mixer is a GATED SHORT
CONVOLUTION in most layers and grouped-query attention in the rest,
with a sparse MoE whose router carries a selection bias — served as
one chip's share of a deployment: `jax.numpy`, float32, matmul
precision "highest", no kernel, no cache, no page, no state slot, no
snapshot — one full causal forward over prompt + generated tokens, a
sequence at a time, layer by layer, each layer's weights made from the
seed when its turn comes, used for every sampled sequence and dropped.

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys; x: hidden, RMSNorm
eps norm_eps, two norms a layer, no bias anywhere):

    h = x + Op(N1(x));   y = h + FFN(N2(h))
    layer_types[i] == "conv":  [B | C | X] = u W_in (hidden -> 3 x
        hidden);  v = B * X;  c_t = sum_{j < K} w[j] * v_{t-(K-1)+j}
        (K = conv_L_cache taps a channel, causal, v before the first
        token 0, no activation);  Op = (C * c) W_out
    "full_attention":  q = u W_Q (heads x d), k, v = u W_K, u W_V
        (kv_heads x d), d = hidden / heads;  q, k <- RMSNorm over d;
        RoPE(rope_theta) on the whole head (split-half pairs);
        o_h = softmax(q_h . k_{h // rep} / sqrt(d)) v_{h // rep} over
        every j <= i;  Op = concat_h(o_h) W_O
      — a block of queries at a time against every key up to the
      block's end, so that a 10k-token prompt never holds an (S, S)
      tile a head
    FFN: the kept leading dense layers SwiGLU(intermediate_size); after
        them the sum over ALL num_experts experts of gate * SwiGLU
        expert, no shared one: s = sigmoid(u W_g) in float32, the
        top-k of s + b (use_expert_bias: the bias enters the SELECTION
        only), gates the selected s over their sum (norm_topk_prob)
        times routed_scaling_factor.

The share keeps `share.layers` layers: its `dense_layers` are the LAST
of the model's num_dense_layers leading dense layers, then the layers
after them (layer_types from num_dense_layers - dense_layers on).

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and lfm2.py docstrings), restated in `seed_tensor` and in the
layer loop below: every matrix at std 1/sqrt(fan_in) in bfloat16, the
ones that write into the residual stream (w_out, w_o, w_down,
experts.<e>.down) divided by sqrt(2 x the whole model's layers), the
convolution taps (K, hidden) float32 at std 1/sqrt(K), norm scales 1
+- 0.1, the router float32, its bias (experts,) float32 at std
`assumed.router_bias_std`.

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of requests admitted and finished
inside the window — the prompt ids it admitted, the ids it generated,
its float32 logits behind EVERY generated token.  A prompt must be a
session's system prompt followed by its script up to one of its turns,
whole.  The sample holds both of the daemon's audit lanes: SHORT joins
(a suffix of at most `short_suffix` tokens behind the restored
snapshot), the shortest first — the only answers that still depend on
WHAT the restore brought —, and the rest.  Three numbers are held to
limits: the 90th percentile of the positions' errors (precision), the
worst position (a gross error), and the mean error of the short joins'
FIRST positions (the restore).  The CONTROL rounds every matrix, every
cached key and value and the convolution's register to float8_e4m3: it
has to fail.
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
                   bias_std: float = 0.015, taps=None):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control.  bias_std: the seeded
    selection bias's (the configuration's assumed.router_bias_std).
    taps: None or a list that receives, a convolution layer, each
    sequence's v (S, hidden) — what the program's registers hold."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads, kvh = g_("hidden_size"), g_("num_attention_heads"), \
        g_("num_key_value_heads")
    D, K = H // heads, g_("conv_L_cache")
    rep = heads // kvh
    dense_dim, moe_dim = g_("intermediate_size"), g_("moe_intermediate_size")
    n_experts, top_k = g_("num_experts"), g_("num_experts_per_tok")
    scale, eps = float(model.get("routed_scaling_factor", 1.0)), \
        g_("norm_eps")
    theta = float(g_("rope_parameters")["rope_theta"])
    layers, dense_layers = share["layers"], share["dense_layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    skip = int(model.get("num_dense_layers", 0)) - dense_layers
    conv = [t == "conv" for t in g_("layer_types")[skip: skip + layers]]
    out_scale = 1.0 / math.sqrt(2.0 * g_("num_hidden_layers"))

    def low(a):
        return jax.lax.reduce_precision(a, *F8) if f8 else a

    def mat(name, shape, gain=1.0):
        return seed_tensor(seed, name, shape, gain / math.sqrt(shape[0]),
                           f8=f8)

    def vec(name, width):
        return seed_tensor(seed, name, (width,), 0.1, mean=1.0, bf16=False)

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

    def conv_op(lw, x):                 # x: (S, H) normed
        S = x.shape[0]
        b, c, xx = jnp.split(x @ lw["w_in"], 3, axis=-1)
        v = low(b * xx)                 # what the register would hold
        full = jnp.concatenate([jnp.zeros((K - 1, H)), v])
        mixed = sum(full[j: j + S] * lw["conv"][j] for j in range(K))
        return (c * mixed) @ lw["w_out"], v

    def attend(lw, x):                  # x: (S, H) normed, S % block == 0
        S = x.shape[0]
        q = rope(rms((x @ lw["w_q"]).reshape(S, heads, D), lw["q_norm"]))
        k = rope(rms((x @ lw["w_k"]).reshape(S, kvh, D), lw["k_norm"]))
        v = (x @ lw["w_v"]).reshape(S, kvh, D)
        k, v = low(k), low(v)           # what the cache would hold
        q = q.reshape(S, kvh, rep, D)

        def blk(i0):
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            s = jnp.einsum("qgrd,kgd->grqk", qb, k) / math.sqrt(D)
            ok = jnp.arange(S)[None, :] <= (i0 + jnp.arange(block))[:, None]
            p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", p, v)

        o = jax.lax.map(blk, jnp.arange(0, S, block))
        return o.reshape(S, heads * D) @ lw["w_o"]

    def gates(router, bias, x):         # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ router)
        _, topi = jax.lax.top_k(scores + bias, top_k)
        rows = jnp.arange(x.shape[0])[:, None]
        topv = scores[rows, topi]
        if model.get("norm_topk_prob", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[rows, topi].set(topv * scale)

    # a layer's steps, each ONE compiled program (an eager line is a
    # program an operation, and a cold check pays for each)
    @jax.jit
    def conv_layer(lw, n1, x):
        a, v = conv_op(lw, rms(x, n1))
        return x + a, v

    attn_layer = jax.jit(lambda lw, n1, x: x + attend(lw, rms(x, n1)))
    normed = jax.jit(rms)
    dense_layer = jax.jit(lambda h, y, wg, wu, wd: h + swiglu(y, wg, wu, wd))
    gate_fn = jax.jit(gates)
    add_expert = jax.jit(lambda f, y, ge, wg, wu, wd:
                         f + ge * swiglu(y, wg, wu, wd))
    head_fn = jax.jit(lambda x, pos, ln, head: rms(x[pos], ln) @ head)
    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{v_first}", (v_held, H), 1.0,
                          f8=f8)
        # every sequence padded to the longest, in fours of query
        # blocks, so that one compiled program serves the sample
        # (padding sits after every real token, where nothing causal
        # looks)
        size = -(-max(len(s) for s in seqs) // (4 * block)) * 4 * block
        xs = []
        for s in seqs:
            ids = np.zeros((size,), np.int32)
            ids[:len(s)] = s
            xs.append(emb[jnp.asarray(ids)])
        del emb
        for i in range(layers):
            p = f"layers.{i}."
            n1 = vec(p + "ln_mix_in", H)
            if conv[i]:
                lw = {"w_in": mat(p + "w_in", (H, 3 * H)),
                      "conv": seed_tensor(seed, p + "conv", (K, H),
                                          1.0 / math.sqrt(K), bf16=False),
                      "w_out": mat(p + "w_out", (H, H), out_scale)}
                got = [conv_layer(lw, n1, x) for x in xs]
                hs = [h for h, _ in got]
                if taps is not None:
                    taps.append([np.asarray(v)[:len(s)]
                                 for (_, v), s in zip(got, seqs)])
                del got
            else:
                lw = {"w_q": mat(p + "w_q", (H, heads * D)),
                      "w_k": mat(p + "w_k", (H, kvh * D)),
                      "w_v": mat(p + "w_v", (H, kvh * D)),
                      "q_norm": vec(p + "q_norm", D),
                      "k_norm": vec(p + "k_norm", D),
                      "w_o": mat(p + "w_o", (heads * D, H), out_scale)}
                hs = [attn_layer(lw, n1, x) for x in xs]
            del lw, xs
            n2 = vec(p + "ln_mlp_in", H)
            ys = [normed(h, n2) for h in hs]
            if i < dense_layers:
                w = (mat(p + "w_gate", (H, dense_dim)),
                     mat(p + "w_up", (H, dense_dim)),
                     mat(p + "w_down", (dense_dim, H), out_scale))
                xs = [dense_layer(h, y, *w) for h, y in zip(hs, ys)]
            else:
                router = seed_tensor(seed, p + "router", (H, n_experts),
                                     1.0 / math.sqrt(H), bf16=False)
                bias = seed_tensor(seed, p + "router_bias", (n_experts,),
                                   bias_std, bf16=False) \
                    if model.get("use_expert_bias") \
                    else jnp.zeros((n_experts,))
                ges = [gate_fn(router, bias, y) for y in ys]
                xs = hs                 # the routed sum lands on h
                for e in range(e_first, e_first + e_held):
                    q_ = f"{p}experts.{e}."
                    w = (mat(q_ + "gate", (H, moe_dim)),
                         mat(q_ + "up", (H, moe_dim)),
                         mat(q_ + "down", (moe_dim, H), out_scale))
                    xs = [add_expert(x, y, ge[:, e: e + 1], *w)
                          for x, y, ge in zip(xs, ys, ges)]
                del ges
            del hs, ys, w
        head = mat(f"lm_head.{v_first}", (H, v_held))
        ln_out = vec("ln_out", H)
        return [np.asarray(head_fn(x, jnp.asarray(pos), ln_out, head))
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
                             block=int(job["block"]),
                             bias_std=float(job["bias_std"]))
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
    n_sys = len(pay["system_ids"][0])

    def a_turn(prompt) -> bool:
        """The prompt is some session's system prompt and its script up
        to one of its turns."""
        h = len(prompt) - n_sys
        return any(h in set(int(e) for e in ends)
                   and np.array_equal(prompt[:n_sys],
                                      pay["system_ids"][int(tenant)])
                   and np.array_equal(prompt[n_sys:], script[:h])
                   for tenant, script, ends in zip(
                       pay["tenant_of"], pay["script_ids"], pay["ends"]))

    def suffix(d) -> int:
        return len(d["prompt"]) - int(d["n_prefix"])

    turns = [d for d in recs if a_turn(d["prompt"])]
    foreign = len(recs) - len(turns)
    short_max = int(spec["short_suffix"])
    shorts = sorted((d for d in turns if 0 < int(d["n_prefix"])
                     and suffix(d) <= short_max), key=suffix)
    rest = [d for d in turns if suffix(d) > short_max]
    rng = np.random.default_rng([int(run.args.seed), 17])
    pick_short = shorts[:int(spec["sample_short"])]
    pick_rest = [rest[int(i)] for i in rng.choice(
        len(rest), min(int(spec["sample_rest"]), len(rest)),
        replace=False)] if rest else []
    pick = pick_short + pick_rest
    cold = sum(int(d["n_prefix"]) == 0 for d in pick)
    p90 = worst = first = float("inf")
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
               "bias_std": float(cfg["assumed"]["router_bias_std"]),
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
        if pick_short:
            first = float(np.mean([e[0] for e in errs[:len(pick_short)]]))
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
                + " tokens; per answer first/p90/worst "
                + " ".join(f"{e[0]:.3f}/{np.percentile(e, 90):.3f}/"
                           f"{e.max():.3f}" for e in errs)
                + ("; CONTROL: the reference itself with matrices, cached "
                   "keys and values and the convolution's register "
                   "rounded to float8_e4m3, in the daemon's place"
                   if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_worst_position", worst, lim["max_logit_err_worst"],
         "<="),
        ("short_join_first_position_err_mean", first,
         lim["max_short_join_err"], "<="),
        ("prompts_that_are_no_turn", foreign, 0, "<="),
        ("sampled_turns_served_cold", cold, int(spec.get("max_cold", 0)),
         "<="),
        ("short_joins_sampled", len(pick_short), int(spec["min_short"]),
         ">="),
        ("other_joins_sampled", len(pick_rest), int(spec["min_rest"]),
         ">=")],
        "note": f"{len(pick_short)} short joins (suffixes "
                f"{[suffix(d) for d in pick_short]}) + {len(pick_rest)} "
                f"others of {len(shorts)} + {len(rest)} audit records "
                f"inside the window ({len(paths)} written) against a "
                f"float32 'highest' forward of prompt + generated tokens, "
                f"errors relative to the reference logits' standard "
                f"deviation, {time.perf_counter() - t0:.1f}s{note}"}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--job":
        raise SystemExit(job_main(sys.argv[2]))
    raise SystemExit("usage: conv_gqa_moe_block.py --job JOB.json")
