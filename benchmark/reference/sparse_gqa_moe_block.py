"""The plain reference of a decoder stack whose every layer attends the
keys a LEARNED INDEXER selects (the DeepSeek-V3.2-Exp report's
"lightning indexer", as Keye-VL-2.0's language block configures it in
`sa_config`), grouped queries, and a sparse MoE without a shared
expert — served as ONE CHIP'S SHARE of an expert-parallel deployment:
`jax.numpy`, float32, matmul precision "highest", no kernel, no cache,
no page, no scan over layers — one full causal forward over prompt +
generated tokens, a sequence at a time, layer by layer, each layer's
weights made from the seed when its turn comes, used for every sampled
sequence and dropped.

It imports nothing of the program (the seeded-tensor recipe, the
relative error and the published keys are reference/
window_sink_gqa_moe_block.py's, found by name).  The equations are the
published config's (the configuration file's top-level keys; RMSNorm
eps rms_norm_eps; no bias anywhere):

    x0 = E[token];   h = x + Attn(N1(x));   y = h + MoE(N2(h))
    Attn(u): q = u W_Q -> heads x head_dim;  k, v = u W_K, u W_V ->
            num_key_value_heads x head_dim
        q, k <- RMSNorm over head_dim (q_norm, k_norm), then RoPE on
            the whole head, split-half pairs, base rope_theta (a text
            token's three position ids under mrope_section are equal)
        the indexer (sa_config), from the same u:
            qI = u W_qI -> indexer_num_heads x indexer_head_dim
            kI = LayerNorm(u W_kI) -> ONE head of indexer_head_dim
            both rotated like q and k;  w = u W_w / sqrt(heads x dim)
            I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])     s <= t
            S_t = the `topk` positions of largest I[t, .] — every s <=
                t while t < topk; of equal scores the LOWER position
        head h of token t: softmax over s in S_t of q . k / sqrt(d)
        Attn = concat_h(sum_s p v_s) W_O
      — a block of queries at a time against every key up to the
      sequence's end, so that a 32.9k-token prompt never holds an
      (S, S) tile a head
    MoE, every layer: the sum over the HELD experts among each token's
        top-k of gate * SwiGLU expert (moe_intermediate_size): float32
        router over ALL num_experts, softmax scores, plain top-k, gates
        renormalised over the selection (norm_topk_prob), no shared
        expert.  What the absent experts would add is left out, as in
        the program.

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and afmoe.py docstrings), restated in the layer loop below: the
embedding at std 1; every matrix std 1/sqrt(fan_in) except w_o at
2 s O_UNIT / sqrt(fan_in) and the experts' down at s / sqrt(fan_in),
s = 1/sqrt(2 x num_hidden_layers); q_norm's scale Q_GAIN (1 +- 0.1)
— the norm behind w_q takes any gain w_q carries —, every other norm
scale 1 +- 0.1, ki_bias 0 +- 0.1, and the router, all float32.

What `check` compares is the TIMED PATH'S OWN output, as reference/
window_sink_gqa_moe_block.py does: the daemon's audit records of
requests admitted and finished inside the window, its float32 logits
behind EVERY generated token, every audited prompt one of the
payload's documents followed by a question and resumed from the prefix
cache on the whole document.  The CONTROL rounds every matrix, every
cached key and value AND every cached indexer key to float8_e4m3: it
has to fail.  `recent_only` (tests only) is what the sabotage plants:
the selection replaced by the last `topk` positions.

The device work runs in a child of its own (`--job`), after the daemon
has gone: run.py never imports JAX.
"""
from __future__ import annotations

import glob
import json
import math
import os
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import traffic                           # benchmark/traffic.py  # noqa: E402

_SINK = traffic.part("reference", "window_sink_gqa_moe_block")
seed_tensor, rel_err, published = \
    _SINK.seed_tensor, _SINK.rel_err, _SINK.published
F8 = _SINK.F8
QUERY_BLOCK = 128                    # queries a score tile holds
# the seeded recipe (libsplinter_tpu/models/afmoe.py, WEIGHTS)
Q_GAIN, O_UNIT, ATTN_OUT = 3.0, 3.2, 0.25


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False, block: int = QUERY_BLOCK,
                   recent_only: bool = False, selections: list | None = None):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control.  recent_only: the selection
    replaced by the last `topk` positions (what the planted fault
    does; False in every comparison).  selections: where a list, each
    (sequence, layer)'s (S, S) bool selection is appended (tests)."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads, kvh, D = g_("hidden_size"), g_("num_attention_heads"), \
        g_("num_key_value_heads"), g_("head_dim")
    moe_dim = g_("moe_intermediate_size")
    n_experts, top_k = g_("num_experts"), g_("num_experts_per_tok")
    eps, theta = float(model.get("rms_norm_eps", 1e-5)), \
        float(g_("rope_theta"))
    sa = g_("sa_config")
    HI, DI, topk = int(sa["indexer_num_heads"]), \
        int(sa["indexer_head_dim"]), int(sa["topk"])
    layers = share["layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    rep = heads // kvh
    s_out = 1.0 / math.sqrt(2.0 * g_("num_hidden_layers"))

    def low(a):
        return jax.lax.reduce_precision(a, *F8) if f8 else a

    def mat(name, shape, gain=1.0):
        return seed_tensor(seed, name, shape, gain / math.sqrt(shape[0]),
                           f8=f8)

    def vec(name, width, mean=1.0, std=None):
        return seed_tensor(seed, name, (width,),
                           0.1 * mean if std is None else std, mean=mean,
                           bf16=False)

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    def attend(lw, x, keep: bool):      # x: (S, H) normed, S % block == 0
        S = x.shape[0]

        def rope(t):                    # (S, n, d) at positions 0..
            half = t.shape[-1] // 2
            freqs = 1.0 / (theta ** (
                jnp.arange(half, dtype=jnp.float32) / half))
            ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
            cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin,
                                    t2 * cos + t1 * sin], -1)

        q = rope(rms((x @ lw["w_q"]).reshape(S, heads, D), lw["q_norm"]))
        k = rope(rms((x @ lw["w_k"]).reshape(S, kvh, D), lw["k_norm"]))
        v = (x @ lw["w_v"]).reshape(S, kvh, D)
        qi = rope((x @ lw["w_qi"]).reshape(S, HI, DI))
        ki = x @ lw["w_ki"]
        ki = ki - ki.mean(-1, keepdims=True)
        ki = ki * jax.lax.rsqrt((ki * ki).mean(-1, keepdims=True) + eps) \
            * lw["ki_norm"] + lw["ki_bias"]
        ki = rope(ki[:, None])[:, 0]
        w = (x @ lw["w_wi"]) / math.sqrt(HI * DI)
        k, v, ki = low(k), low(v), low(ki)  # what the cache would hold
        q = q.reshape(S, kvh, rep, D)
        j = jnp.arange(S)[None, :]

        def blk(i0):
            i = (i0 + jnp.arange(block))[:, None]
            causal = j <= i
            if recent_only:
                sel = causal & (i - j < topk)
            else:
                score = jnp.einsum("qh,qhk->qk", jax.lax.dynamic_slice_in_dim(
                    w, i0, block, 0), jax.nn.relu(jnp.einsum(
                        "qhd,kd->qhk",
                        jax.lax.dynamic_slice_in_dim(qi, i0, block, 0), ki)))
                # the top-k of equal scores keeps the lower position
                _, idx = jax.lax.top_k(
                    jnp.where(causal, score, -jnp.inf), min(topk, S))
                sel = jnp.zeros((block, S), bool).at[
                    jnp.arange(block)[:, None], idx].set(True) & causal
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            s = jnp.einsum("qgrd,kgd->grqk", qb, k) / math.sqrt(D)
            p = jax.nn.softmax(jnp.where(sel[None, None], s, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", p, v), \
                (sel if keep else jnp.zeros((), bool))

        o, sel = jax.lax.map(blk, jnp.arange(0, S, block))
        return o.reshape(S, heads * D) @ lw["w_o"], sel

    def gates(router, x):               # (S, E): zero outside the top-k
        scores = jax.nn.softmax(x @ router, -1)
        topv, topi = jax.lax.top_k(scores, top_k)
        if model.get("norm_topk_prob", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[
            jnp.arange(x.shape[0])[:, None], topi].set(topv)

    def layer(lw, x, keep: bool):
        a, sel = attend(lw, rms(x, lw["ln_attn_in"]), keep)
        h = x + a
        y = rms(h, lw["ln_mlp_in"])
        ge = gates(lw["router"], y)[:, e_first: e_first + e_held]

        def one(f, xs):                 # an expert at a time, dense
            wts, col = xs
            return f + swiglu(y, *wts) * col[:, None], None
        f, _ = jax.lax.scan(one, jnp.zeros_like(y), (lw["experts"], ge.T))
        return h + f, sel

    def weights(i):
        p = f"layers.{i}."
        return {
            "ln_attn_in": vec(p + "ln_attn_in", H),
            "ln_mlp_in": vec(p + "ln_mlp_in", H),
            "w_q": mat(p + "w_q", (H, heads * D)),
            "w_k": mat(p + "w_k", (H, kvh * D)),
            "w_v": mat(p + "w_v", (H, kvh * D)),
            "w_o": mat(p + "w_o", (heads * D, H), ATTN_OUT * s_out * O_UNIT),
            "q_norm": vec(p + "q_norm", D, Q_GAIN),
            "k_norm": vec(p + "k_norm", D),
            "w_qi": mat(p + "w_qi", (H, HI * DI)),
            "w_ki": mat(p + "w_ki", (H, DI)),
            "w_wi": mat(p + "w_wi", (H, HI)),
            "ki_norm": vec(p + "ki_norm", DI),
            "ki_bias": vec(p + "ki_bias", DI, 0.0, 0.1),
            "router": seed_tensor(seed, p + "router", (H, n_experts),
                                  1.0 / math.sqrt(H), bf16=False),
            "experts": tuple(
                jnp.stack([mat(f"{p}experts.{e}.{part}", shape, gain)
                           for e in range(e_first, e_first + e_held)])
                for part, shape, gain in (("gate", (H, moe_dim), 1.0),
                                          ("up", (H, moe_dim), 1.0),
                                          ("down", (moe_dim, H), s_out)))}

    keep = selections is not None
    layer_fn = jax.jit(layer, static_argnums=2)
    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{v_first}", (v_held, H), 1.0,
                          f8=f8)
        # every sequence padded to the longest, in eights of query
        # blocks: one compiled program, and the next run's (padding
        # sits after every real token, where nothing causal looks)
        size = -(-max(len(s) for s in seqs) // (8 * block)) * 8 * block
        xs = []                         # the streams, on the HOST
        for s in seqs:
            ids = np.zeros((size,), np.int32)
            ids[:len(s)] = s
            xs.append(np.asarray(emb[jnp.asarray(ids)]))
        del emb
        for i in range(layers):
            lw = weights(i)
            for n, x in enumerate(xs):
                y, sel = layer_fn(lw, jnp.asarray(x), keep)
                xs[n] = np.asarray(y)
                if keep:
                    m = len(seqs[n])
                    selections.append(
                        np.asarray(sel).reshape(size, size)[:m, :m])
                del y, sel
            del lw
        head = mat(f"lm_head.{v_first}", (H, v_held))
        ln_out = vec("ln_out", H)
        return [np.asarray(rms(jnp.asarray(x[np.asarray(pos)]), ln_out)
                           @ head)
                for x, pos in zip(xs, positions)]


# ------------------------------------------------------------ the check

def job_main(path: str) -> int:
    """The child: runs the forward on the device the run was given."""
    job = json.load(open(path))
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
    # a question resumes on its whole document: every page under it —
    # keys, values and the indexer's keys — was still held
    doc_len = len(docs[0]) // int(spec["page"]) * int(spec["page"])
    cold = sum(int(d["n_prefix"]) < doc_len for d in pick)
    p50 = p90 = worst = float("inf")
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
        p50, p90 = float(np.median(flat)), float(np.percentile(flat, 90))
        worst = float(flat.max())
        apart = np.concatenate([rel_err(out[f"ref{i}"][1:],
                                        out[f"ref{i}"][:-1])
                                for i in range(len(pick))] or [[0.0]])
        note = (f"; {len(flat)} positions, "
                f"neighbouring positions' logits differ by "
                f"{np.median(apart):.2f} (median), "
                f"{int((flat > 2 * lim['max_logit_err']).sum())} over "
                f"twice the precision limit; prompts of "
                + " ".join(f"{len(d['prompt'])}(hit {int(d['n_prefix'])})"
                           for d in pick)
                + " tokens; per answer p50/p90/worst "
                + " ".join(f"{np.median(e):.3f}/{np.percentile(e, 90):.3f}"
                           f"/{e.max():.3f}" for e in errs)
                + ("; CONTROL: the reference itself with matrices and "
                   "cached keys, values and indexer keys rounded to "
                   "float8_e4m3, in the daemon's place"
                   if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_median", p50, lim["max_logit_err_median"], "<="),
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
    raise SystemExit("usage: sparse_gqa_moe_block.py --job JOB.json")
