"""The plain reference of a decoder stack that mixes sliding-window
layers WITH A LEARNED ATTENTION SINK and global layers, key/value heads
and key/value widths that differ by layer kind, partial rotary
positions, and a sparse MoE without a shared expert — MiMo-V2-Flash's
block, served as ONE CHIP'S SHARE of an expert-parallel deployment:
`jax.numpy`, float32, matmul precision "highest", no kernel, no cache,
no page, no page group, no scan over layers — one full causal forward over prompt
+ generated tokens, a sequence at a time, layer by layer, each layer's
weights made from the seed when its turn comes, used for every sampled
sequence and dropped.

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys; RMSNorm eps
layernorm_epsilon; no bias anywhere):

    x0 = E[token];   h = x + Attn_kind(N1(x));   y = h + FFN(N2(h))
    Attn_kind(u): q = u W_Q -> heads x head_dim
        k = u W_K -> KH x head_dim;  v = (u W_V -> KH x v_head_dim)
            * attention_value_scale
        KH = num_key_value_heads in a global layer
            (hybrid_layer_pattern[i] == 0), swa_num_key_value_heads in
            a window layer; the widths swa_head_dim / swa_v_head_dim
        RoPE on the first int(head_dim x partial_rotary_factor) dims
            (rounded down to even) of q and k, split-half pairs, base
            rope_theta (global) / swa_rope_theta (window)
        s_ij = q_i . k_j / sqrt(head_dim); global: j <= i; window:
            0 <= i - j < sliding_window
        window layer (add_swa_attention_sink_bias): p_ij = exp(s_ij) /
            (exp(b_h) + sum_j exp(s_ij)), b_h learned, one a head: a
            key with no value; global layer
            (add_full_attention_sink_bias false): plain softmax
        Attn = concat_h(sum_j p_ij v_j) W_O
      — a block of queries at a time: a global layer against every
      key up to the sequence's end, a window layer against the window
      + block keys that end with the block, so that a 32.9k-token
      prompt never holds an (S, S) tile a head
    FFN: layers with moe_layer_freq[i] == 0 SwiGLU(intermediate_size);
        the others the sum over the HELD experts among each token's
        top-k of gate * SwiGLU expert (moe_intermediate_size): float32
        router over ALL n_routed_experts, sigmoid scores, plain top-k
        (n_group = topk_group = 1; noaux_tc's selection bias is zero at
        seeded weights), gates renormalised over the selection
        (norm_topk_prob), no scaling factor, no shared expert.  What
        the absent experts would add is left out, as in the program.
        An expert is computed over the tokens routed to it (a gather,
        checked for overflow), not over all 32.9k: the same sum.

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and afmoe.py docstrings), restated in `seed_tensor` and in the
layer loop below: the embedding at std 1; every matrix std
1/sqrt(fan_in) except w_q at Q_GAIN[kind] / sqrt(hidden), w_o at
2 s O_UNIT[kind] / sqrt(fan_in) and the feed-forward's down matrices at
s / sqrt(fan_in), s = 1/sqrt(2 x num_hidden_layers); the sinks uniform
on [2, 5]; norm scales 1 +- 0.1 and the router in float32.

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of requests admitted and finished
inside the window — the prompt ids it admitted, the ids it generated,
its float32 logits behind EVERY generated token — with the two numbers
of reference/latent_moe_block.py held to limits (the 90th percentile
of the positions' errors: precision; the worst position: a gross
error).  Every audited prompt must be one of the payload's documents
followed by a question, and must have resumed from the prefix cache on
the whole document (a cold 32.8k-token prefill inside the window is a
fault of the tree's retention, which the cell exists to hold).  The
CONTROL rounds every matrix and every cached key and value to
float8_e4m3: it has to fail.  `no_sink` (tests only) is what the
sabotage plants: the window layers' softmax without its sink.

The device work runs in a child of its own (`--job`), after the
daemon has gone: run.py never imports JAX.  A sequence's stream waits
on the HOST between layers, so the device holds one sequence and one
layer at a time whatever the sample's size.
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
QUERY_BLOCK = 128                    # queries a score tile holds
# (exponent bits, mantissa bits) for lax.reduce_precision: roundings
# are made with it and not with a pair of converts, which the chip's
# compiler may drop as excess precision (PR 30)
BF16, F8 = (8, 7), (4, 3)            # bfloat16; float8_e4m3
# the seeded recipe of a block without post-branch norms
# (libsplinter_tpu/models/afmoe.py, WEIGHTS)
Q_GAIN = {"window": 1.0, "full": 3.0}
O_UNIT = {"window": 12.0, "full": 8.5}
ATTN_OUT = 2.0
SINK_RANGE = (2.0, 5.0)


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


def kinds_of(model: dict) -> dict:
    """The two kinds of layer as the published keys describe them."""
    g_ = model.__getitem__
    factor = float(model.get("partial_rotary_factor", 1.0))

    def kind(kvh, d, dv, theta, window, sink):
        return {"kv_heads": int(kvh), "d": int(d), "dv": int(dv),
                "rot": int(int(d) * factor) // 2 * 2,
                "theta": float(theta), "window": int(window),
                "sink": bool(sink)}
    return {
        "window": kind(g_("swa_num_key_value_heads"), g_("swa_head_dim"),
                       g_("swa_v_head_dim"), g_("swa_rope_theta"),
                       g_("sliding_window"),
                       model.get("add_swa_attention_sink_bias", False)),
        "full": kind(g_("num_key_value_heads"), g_("head_dim"),
                     g_("v_head_dim"), g_("rope_theta"), 0,
                     model.get("add_full_attention_sink_bias", False))}


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False, block: int = QUERY_BLOCK,
                   no_sink: bool = False):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control.  no_sink: the window layers'
    softmax without its sink (what the planted fault does; False in
    every comparison)."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads = g_("hidden_size"), g_("num_attention_heads")
    dense_dim, moe_dim = g_("intermediate_size"), \
        g_("moe_intermediate_size")
    n_experts, top_k = g_("n_routed_experts"), g_("num_experts_per_tok")
    eps = float(model.get("layernorm_epsilon", 1e-5))
    v_scale = float(model.get("attention_value_scale", 1.0))
    r_scale = float(model.get("routed_scaling_factor") or 1.0)
    layers, dense_layers = share["layers"], share["dense_layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    kinds = kinds_of(model)
    kind_of = ["window" if p else "full"
               for p in g_("hybrid_layer_pattern")[:layers]]
    s_out = 1.0 / math.sqrt(2.0 * g_("num_hidden_layers"))

    def low(a):
        return jax.lax.reduce_precision(a, *F8) if f8 else a

    def mat(name, shape, gain=1.0):
        return seed_tensor(seed, name, shape, gain / math.sqrt(shape[0]),
                           f8=f8)

    def vec(name, width, mean=1.0):
        return seed_tensor(seed, name, (width,), 0.1 * mean, mean=mean,
                           bf16=False)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def attend(lw, x, kind: str):       # x: (S, H) normed, S % block == 0
        ak = kinds[kind]
        kvh, D, Dv, rot, W = (ak[k] for k in
                              ("kv_heads", "d", "dv", "rot", "window"))
        rep, S = heads // kvh, x.shape[0]

        def rope(t):                    # (S, n, D) at positions 0..
            half = rot // 2
            freqs = 1.0 / (ak["theta"] ** (
                jnp.arange(half, dtype=jnp.float32) / half))
            ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            t1, t2 = t[..., :half], t[..., half:rot]
            return jnp.concatenate([t1 * cos - t2 * sin,
                                    t2 * cos + t1 * sin, t[..., rot:]], -1)

        q = (x @ lw["w_q"]).reshape(S, heads, D)
        k = (x @ lw["w_k"]).reshape(S, kvh, D)
        v = (x @ lw["w_v"]).reshape(S, kvh, Dv) * v_scale
        if rot:
            q, k = rope(q), rope(k)
        k, v = low(k), low(v)           # what the cache would hold
        q = q.reshape(S, kvh, rep, D)
        # a window block sees the W + block keys that end with it
        span = min(W + block, S) if W else S
        pad = span if W else 0
        kp = jnp.concatenate([jnp.zeros((pad, kvh, D)), k])
        vp = jnp.concatenate([jnp.zeros((pad, kvh, Dv)), v])
        sink = ak["sink"] and not no_sink

        def blk(i0):
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            j0 = i0 + block - span if W else 0          # first key seen
            kb = jax.lax.dynamic_slice_in_dim(kp, j0 + pad, span, 0)
            vb = jax.lax.dynamic_slice_in_dim(vp, j0 + pad, span, 0)
            s = jnp.einsum("qgrd,kgd->grqk", qb, kb) / math.sqrt(D)
            i = (i0 + jnp.arange(block))[:, None]
            j = (j0 + jnp.arange(span))[None, :]
            ok = (j <= i) & (j >= 0)
            if W:
                ok &= i - j < W
            s = jnp.where(ok[None, None], s, -jnp.inf)
            if sink:                    # a key with no value
                b = jnp.broadcast_to(
                    lw["sink"].reshape(kvh, rep, 1, 1),
                    (kvh, rep, block, 1))
                p = jax.nn.softmax(jnp.concatenate([s, b], -1),
                                   -1)[..., :-1]
            else:
                p = jax.nn.softmax(s, -1)
            return jnp.einsum("grqk,kgd->qgrd", p, vb)

        o = jax.lax.map(blk, jnp.arange(0, S, block))
        return o.reshape(S, heads * Dv) @ lw["w_o"]

    def gates(router, x):               # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ router)
        topv, topi = jax.lax.top_k(scores, top_k)
        if model.get("norm_topk_prob", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], topi].set(topv * r_scale)

    def attn_layer(lw, x, kind: str):
        return x + attend(lw, rms(x, lw["ln_attn_in"]), kind)

    def dense_layer(lw, h):
        return h + swiglu(rms(h, lw["ln_mlp_in"]), *lw["dense"])

    def expert_layer(lw, h, cap: int):
        """An expert reads the tokens routed to it and no others: a
        gather of at most `cap` rows (the caller checks the counts and
        asks again with every row if one ran over), the same sum as
        gate x expert over every token, an expert at a time."""
        y = rms(h, lw["ln_mlp_in"])
        ge = gates(lw["router"], y)[:, e_first: e_first + e_held]
        S = y.shape[0]
        ypad = jnp.concatenate([y, jnp.zeros((1, H))])
        gpad = jnp.concatenate([ge, jnp.zeros((1, e_held))])

        def one(f, xs):
            w, col = xs                 # an expert's matrices, its gates
            idx = jnp.nonzero(col[:S] > 0, size=cap, fill_value=S)[0]
            out = swiglu(ypad[idx], *w) * col[idx][:, None]
            return f.at[idx].add(out, mode="drop"), (col > 0).sum()

        f, counts = jax.lax.scan(one, jnp.zeros_like(y),
                                 (lw["experts"], gpad.T))
        return h + f, counts

    def attn_weights(i):
        p, kind = f"layers.{i}.", kind_of[i]
        ak = kinds[kind]
        lw = {"ln_attn_in": vec(p + "ln_attn_in", H),
              "w_q": mat(p + "w_q", (H, heads * ak["d"]), Q_GAIN[kind]),
              "w_k": mat(p + "w_k", (H, ak["kv_heads"] * ak["d"])),
              "w_v": mat(p + "w_v", (H, ak["kv_heads"] * ak["dv"])),
              "w_o": mat(p + "w_o", (heads * ak["dv"], H),
                         ATTN_OUT * s_out * O_UNIT[kind])}
        if ak["sink"]:
            lo, hi = SINK_RANGE
            lw["sink"] = seed_tensor(
                seed, p + "sink", (heads,), (hi - lo) / math.sqrt(12.0),
                mean=(lo + hi) / 2.0, bf16=False)
        return lw

    def ffn_weights(i):
        p = f"layers.{i}."
        lw = {"ln_mlp_in": vec(p + "ln_mlp_in", H)}
        if i < dense_layers:
            lw["dense"] = (mat(p + "w_gate", (H, dense_dim)),
                           mat(p + "w_up", (H, dense_dim)),
                           mat(p + "w_down", (dense_dim, H), s_out))
            return lw
        lw["router"] = seed_tensor(seed, p + "router", (H, n_experts),
                                   1.0 / math.sqrt(H), bf16=False)
        lw["experts"] = tuple(
            jnp.stack([mat(f"{p}experts.{e}.{part}", shape, gain)
                       for e in range(e_first, e_first + e_held)])
            for part, shape, gain in (("gate", (H, moe_dim), 1.0),
                                      ("up", (H, moe_dim), 1.0),
                                      ("down", (moe_dim, H), s_out)))
        return lw

    attn_fn = jax.jit(attn_layer, static_argnums=2)
    dense_fn = jax.jit(dense_layer)
    expert_fn = jax.jit(expert_layer, static_argnums=2)

    def compiled(size: int, cap: int) -> dict:
        """The layer programs of this stack, each compiled ahead of its
        first use and all SIDE BY SIDE (a thread each): a cold run
        waits for the longest compile, not for their sum.  Shapes come
        from the weight makers themselves, traced and not run."""
        import threading
        x = jax.ShapeDtypeStruct((size, H), jnp.float32)
        jobs = [(("attn", k), attn_fn, attn_weights, kind_of.index(k),
                 (k,)) for k in sorted(set(kind_of))]
        if dense_layers:
            jobs.append((("ffn", True), dense_fn, ffn_weights, 0, ()))
        if layers > dense_layers:
            jobs.append((("ffn", False), expert_fn, ffn_weights,
                         dense_layers, (cap,)))
        out = {}

        def build(key, fn, make, i, static):
            with jax.default_matmul_precision("highest"):
                out[key] = fn.lower(jax.eval_shape(lambda: make(i)), x,
                                    *static).compile()
        threads = [threading.Thread(target=build, args=j) for j in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if len(out) != len(jobs):
            raise RuntimeError("a layer program of the reference did "
                               "not compile")
        return out

    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{v_first}", (v_held, H), 1.0,
                          f8=f8)
        # every sequence padded to the longest, in eights of query
        # blocks: one compiled program a kind of layer, and the next
        # run's (padding sits after every real token, where nothing
        # causal looks)
        size = -(-max(len(s) for s in seqs) // (8 * block)) * 8 * block
        # four times an expert's even share of the tokens, or all
        cap = min(size, max(256, 4 * size * top_k // n_experts))
        run = compiled(size, cap)
        xs = []                         # the streams, on the HOST
        for s in seqs:
            ids = np.zeros((size,), np.int32)
            ids[:len(s)] = s
            xs.append(np.asarray(emb[jnp.asarray(ids)]))
        del emb
        for i in range(layers):
            aw, fw = attn_weights(i), ffn_weights(i)
            dense = i < dense_layers
            for n, x in enumerate(xs):
                h = run["attn", kind_of[i]](aw, jnp.asarray(x))
                if dense:
                    y = run["ffn", True](fw, h)
                else:
                    y, counts = run["ffn", False](fw, h)
                    if int(counts.max()) > cap:     # an uneven router
                        y, _ = expert_fn(fw, h, size)
                xs[n] = np.asarray(y)
                del h, y
            del aw, fw
        head = mat(f"lm_head.{v_first}", (H, v_held))
        ln_out = vec("ln_out", H)
        return [np.asarray(rms(jnp.asarray(x[np.asarray(pos)]), ln_out)
                           @ head)
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
    rng = np.random.default_rng([int(run.args.seed), 17])
    want = int(spec["sample"])
    pick = [recs[int(i)] for i in rng.choice(
        len(recs), min(want, len(recs)), replace=False)] if recs else []
    docs = run.mix.payload["docs"]
    foreign = sum(not any(np.array_equal(d["prompt"][:len(doc)], doc)
                          for doc in docs) for d in pick)
    # a question resumes on its whole document: the pages under it and
    # the window's tail page were all still held
    doc_len = len(docs[0]) // int(spec["page"]) * int(spec["page"])
    cold = sum(int(d["n_prefix"]) < doc_len for d in pick)
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
            env=run.env, capture_output=True, text=True, timeout=1500)
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
        ("prompts_not_a_payload_document", foreign, 0, "<="),
        ("sampled_answers_not_resumed_on_their_document", cold,
         int(spec.get("max_cold", 0)), "<="),
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
    raise SystemExit("usage: window_sink_gqa_moe_block.py --job JOB.json")
