"""The plain reference of a latent-attention (MLA) + shared-expert MoE
decoder block served as ONE CHIP'S SHARE of an expert-parallel
deployment: `jax.numpy`, float32, matmul precision "highest", no
kernel, no cache, no batching tricks — one full causal forward over
prompt + generated tokens, layer by layer, each layer's weights made
from the seed when its turn comes and freed after it (the float32 tree
of the cell's configuration is 19.7 GB; one layer's is 4 GB).

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys):

    h = x + N2(Attn(N1(x))),  y = h + N4(FFN(N3(h)))       sandwich norms
    Attn: cq = Nq(x W_DQ); q_h = cq W_UQ_h = [q_nope_h | q_rope_h];
          [c | k_r] = x W_DKV, c = Nkv(c); RoPE on q_rope_h and k_r
          (split-half pairs, theta rope_theta); [k_nope_h | v_h] =
          c W_UKV_h; score_h = (q_nope_h.k_nope_h + q_rope_h.k_r) /
          sqrt(nope + rope); causal softmax; o = concat_h(p_h v_h) W_O
    FFN:  the leading dense layers SwiGLU(intermediate_size); after
          them the shared SwiGLU expert + sum over the HELD experts
          among each token's top-k of gate * SwiGLU expert, the router
          float32 over ALL experts, sigmoid scores, gates normalised
          over the top-k and times routed_scaling_factor.  What the
          absent experts would add is left out, as in the program.

and the weights follow the program's written recipe (docs and
libsplinter_tpu/models/mla.py's docstring), restated in `seed_tensor`.

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of requests admitted and finished
inside the measured window — the prompt ids it admitted, the ids it
generated, and its float32 logits behind EVERY generated token.  The
reference runs prompt + generated tokens in one forward, and a
position's error is max |daemon - reference| over the vocabulary
slice, relative to the standard deviation of the reference's logits
there.  Two numbers over all compared positions (answers sampled x
tokens generated) are held to limits, because a sparse expert layer is
not continuous in its input: where a token's k-th and (k+1)-th expert
score nearly alike, bfloat16 rounding picks the other one, and that
position's logits move by far more than rounding does (a few
positions in a hundred, the expanded-prefill path and the paged
decode path alike; tests and PERF.md).  So the 90th PERCENTILE of the
positions' errors is held to the precision limit — a lower precision
moves every position, as the control shows, and so does a wrong page,
weight or scale; one wholly wrong answer among those sampled is more
than a tenth of the positions — and the WORST position to a
gross-error limit, which a position fed another's state passes by a
factor (sabotage/latent_audit_row_swapped.py plants that).  The
CONTROL (run.py --control) puts in the daemon's place this same
forward with every matrix and every cached latent rounded to
float8_e4m3: it has to fail.

The device work runs in a child of its own (`--job`), after the
daemon has gone: run.py never imports JAX.
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


# ------------------------------------------------------------- weights

def seed_tensor(seed, name, shape, std, mean=0.0, bf16=True, f8=False):
    """The program's recipe, value for value: threefry bits from
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
    """One jitted maker per (shape, roundings): a layer's tensors
    repeat the shapes of the layer before."""
    fn = _MAKERS.get((shape, bf16, f8))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def make(key, mean, std):
            bits = jax.random.bits(key, shape, jnp.uint32)
            u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
            w = mean + (u - 0.5) * (jnp.float32(math.sqrt(12.0)) * std)
            if bf16:
                w = w.astype(jnp.bfloat16).astype(jnp.float32)
            if f8:
                w = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return w
        fn = _MAKERS[(shape, bf16, f8)] = jax.jit(make)
    return fn


class Block:
    """The share's sizes from a configuration's keys."""

    def __init__(self, model: dict, share: dict):
        g = model.__getitem__
        self.hidden = g("hidden_size")
        self.heads = g("num_attention_heads")
        self.q_rank, self.kv_rank = g("q_lora_rank"), g("kv_lora_rank")
        self.nope, self.rope = g("qk_nope_head_dim"), g("qk_rope_head_dim")
        self.vd = g("v_head_dim")
        self.dense_dim = g("intermediate_size")
        self.moe_dim = g("moe_intermediate_size")
        self.n_experts = g("n_routed_experts")
        self.top_k = g("num_experts_per_tok")
        self.shared = g("n_shared_experts")
        self.norm_topk = g("norm_topk_prob")
        self.scale = g("routed_scaling_factor")
        self.sandwich = model.get("sandwich_norm", False)
        self.theta = g("rope_theta")
        self.eps = g("rms_norm_eps")
        self.layers = share["layers"]
        self.dense_layers = share["dense_layers"]
        self.e_first, self.e_held = share["experts"]
        self.v_first, self.v_held = share["vocab"]


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control — every matrix and every
    cached latent rounded to float8_e4m3."""
    import jax
    import jax.numpy as jnp
    b = Block(model, share)
    H = b.hidden
    n = len(seqs)
    # padded to whole blocks of 128 (padding sits after every real
    # token, where causal attention never looks): runs whose longest
    # sequence differs by a few tokens share their compiled programs
    S = -(-max(len(s) for s in seqs) // 128) * 128
    ids = np.zeros((n, S), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s

    def mat(name, shape):
        return seed_tensor(seed, name, shape, 1.0 / math.sqrt(shape[0]),
                           f8=f8)

    def norm(name, width):
        return seed_tensor(seed, name, (width,), 0.1, mean=1.0,
                           bf16=False)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + b.eps) * scale

    def rope(x):                       # (S, ..., D), position = row
        half = x.shape[-1] // 2
        freqs = 1.0 / (b.theta ** (jnp.arange(half, dtype=jnp.float32)
                                   / half))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
        shape = (S,) + (1,) * (x.ndim - 2) + (half,)
        cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1)

    def swiglu(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd

    causal = jnp.tril(jnp.ones((S, S), bool))
    HEAD_BLOCK = 8                     # heads a score tile holds

    def attention(lw, x):              # x: (S, H) normed, one sequence
        cq = rms(x @ lw["w_dq"], lw["ln_q"])
        q = (cq @ lw["w_uq"]).reshape(S, b.heads, b.nope + b.rope)
        ckr = x @ lw["w_dkv"]
        c = rms(ckr[:, :b.kv_rank], lw["ln_kv"])
        k_r = rope(ckr[:, b.kv_rank:])
        if f8:                         # the control's cache precision
            c = c.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            k_r = k_r.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        kv = (c @ lw["w_ukv"]).reshape(S, b.heads, b.nope + b.vd)
        q_nope, q_rope = q[..., :b.nope], rope(q[..., b.nope:])
        outs = []
        for h0 in range(0, b.heads, HEAD_BLOCK):
            hs = slice(h0, h0 + HEAD_BLOCK)
            s = (jnp.einsum("qhd,khd->hqk", q_nope[:, hs],
                            kv[:, hs, :b.nope])
                 + jnp.einsum("qhr,kr->hqk", q_rope[:, hs], k_r)) \
                / math.sqrt(b.nope + b.rope)
            s = jnp.where(causal[None], s, -jnp.inf)
            outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                   kv[:, hs, b.nope:]))
        return jnp.concatenate(outs, 1).reshape(S, b.heads * b.vd) \
            @ lw["w_o"]

    def gates(lw, x):                  # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ lw["router"])
        topv, topi = jax.lax.top_k(scores, b.top_k)
        if b.norm_topk:
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[
            jnp.arange(S)[:, None], topi].set(topv * b.scale)

    # jitted once, not once a layer: every layer has the same shapes
    att, ffn_dense = jax.jit(attention), jax.jit(swiglu)
    add_expert = jax.jit(lambda f, y, ge, wg, wu, wd:
                         f + ge * swiglu(y, wg, wu, wd))
    gate_fn = jax.jit(gates)
    with jax.default_matmul_precision("highest"):
        emb = seed_tensor(seed, f"tok_emb.{b.v_first}", (b.v_held, H),
                          1.0, f8=f8)
        x = emb[jnp.asarray(ids)]                      # (n, S, H)
        del emb
        for i in range(b.layers):
            p = f"layers.{i}."
            lw = {
                "ln_q": norm(p + "ln_q", b.q_rank),
                "ln_kv": norm(p + "ln_kv", b.kv_rank),
                "w_dq": mat(p + "w_dq", (H, b.q_rank)),
                "w_uq": mat(p + "w_uq", (b.q_rank,
                                         b.heads * (b.nope + b.rope))),
                "w_dkv": mat(p + "w_dkv", (H, b.kv_rank + b.rope)),
                "w_ukv": mat(p + "w_ukv", (b.kv_rank,
                                           b.heads * (b.nope + b.vd))),
                "w_o": mat(p + "w_o", (b.heads * b.vd, H)),
            }
            n1, n3 = norm(p + "ln_attn_in", H), norm(p + "ln_mlp_in", H)
            a = jnp.stack([att(lw, rms(x[j], n1)) for j in range(n)])
            if b.sandwich:
                a = rms(a, norm(p + "ln_attn_out", H))
            h = x + a
            del lw, a
            y = rms(h, n3).reshape(n * S, H)
            if i < b.dense_layers:
                f = ffn_dense(
                    y, mat(p + "w_gate", (H, b.dense_dim)),
                    mat(p + "w_up", (H, b.dense_dim)),
                    mat(p + "w_down", (b.dense_dim, H)))
            else:
                router = seed_tensor(seed, p + "router",
                                     (H, b.n_experts), 1.0 / math.sqrt(H),
                                     bf16=False)
                g = jnp.concatenate([
                    gate_fn({"router": router}, y[j * S:(j + 1) * S])
                    for j in range(n)])
                f = jnp.zeros_like(y)
                if b.shared:
                    f = ffn_dense(
                        y, mat(p + "shared.gate", (H, b.moe_dim)),
                        mat(p + "shared.up", (H, b.moe_dim)),
                        mat(p + "shared.down", (b.moe_dim, H)))
                for e in range(b.e_first, b.e_first + b.e_held):
                    q = f"{p}experts.{e}."
                    f = add_expert(f, y, g[:, e: e + 1],
                            mat(q + "gate", (H, b.moe_dim)),
                            mat(q + "up", (H, b.moe_dim)),
                            mat(q + "down", (b.moe_dim, H)))
            f = f.reshape(n, S, H)
            if b.sandwich:
                f = rms(f, norm(p + "ln_mlp_out", H))
            x = h + f
            del h, f, y
        head = mat(f"lm_head.{b.v_first}", (H, b.v_held))
        ln_out = norm("ln_out", H)
        return [np.asarray(rms(x[j, jnp.asarray(pos)], ln_out) @ head)
                for j, pos in enumerate(positions)]


# ------------------------------------------------------------ the check

def published(cfg: dict) -> dict:
    """The configuration's model keys at their PUBLISHED values (the
    file keeps the reduced ones at top level and the published ones
    under `published`)."""
    keys = cfg["model_keys"]
    return {**{k: cfg[k] for k in keys}, **cfg.get("published", {})}


def job_main(path: str) -> int:
    """The child: runs the forward on the device the run was given."""
    job = json.load(open(path))
    sys.path.insert(0, os.path.dirname(HERE))
    import host                          # benchmark/host.py
    host.check_device(job["chips"], job["rehearse"])
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the benchmark's cache (README): the reference's programs
        # are the same from one run to the next
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(HERE)), ".xla_cache"))
    data = np.load(job["records"], allow_pickle=False)
    n = int(data["n"])
    seqs, positions = [], []
    for i in range(n):
        prompt, toks = data[f"prompt{i}"], data[f"tokens{i}"]
        seqs.append(np.concatenate([prompt, toks[:-1]]))
        # token j was sampled from the logits at the position of the
        # token before it
        positions.append(list(range(len(prompt) - 1,
                                    len(prompt) - 1 + len(toks))))
    out = {}
    for name, f8 in (("ref", False),) + ((("f8", True),)
                                         if job["control"] else ()):
        got = forward_logits(job["model"], job["share"], job["seed"],
                             seqs, positions, f8=f8)
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
        # what a position fed another's state would read: the
        # reference's own logits one position apart
        apart = np.concatenate([rel_err(out[f"ref{i}"][1:],
                                        out[f"ref{i}"][:-1])
                                for i in range(len(pick))] or [[0.0]])
        note = (f"; {n_pos} positions, median {np.median(flat):.4f}, "
                f"neighbouring positions' logits differ by "
                f"{np.median(apart):.2f} (median), "
                f"{int((flat > 2 * lim['max_logit_err']).sum())} over "
                f"twice the precision limit; per answer p90/worst "
                + " ".join(f"{np.percentile(e, 90):.3f}/{e.max():.3f}"
                           for e in errs)
                + ("; CONTROL: the reference itself with matrices and "
                   "latents rounded to float8_e4m3, in the daemon's "
                   "place" if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_worst_position", worst, lim["max_logit_err_worst"],
         "<="),
        ("prompts_not_a_payload_document", foreign, 0, "<="),
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
    raise SystemExit("usage: latent_moe_block.py --job JOB.json")
