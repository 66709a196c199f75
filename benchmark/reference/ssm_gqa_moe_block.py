"""The plain reference of a decoder stack of ONE mixer a layer — a
Mamba-2 state-space layer, a sparse MoE of un-gated relu^2 experts, or
grouped-query attention without positions, by a pattern — served as
one chip's share of a deployment: `jax.numpy`, float32, matmul
precision "highest", no kernel, no cache, no page, no state slot, no
snapshot, no chunked scan — one full causal forward over prompt +
generated tokens, a sequence at a time, layer by layer, each layer's
weights made from the seed when its turn comes, used for every sampled
sequence and dropped.

It imports nothing of the program.  The equations are the published
config's (the configuration file's top-level keys; x: hidden, RMSNorm
eps norm_eps, ONE norm a layer, no bias but the convolution's):

    h = x + Mixer_i(N_i(x)),  Mixer_i by hybrid_override_pattern[i]
    "M" (Mamba-2; H = mamba_num_heads heads of P = mamba_head_dim, G =
        n_groups groups of B and C, N = ssm_state_size, K =
        conv_kernel; d_inner = H P):
        [z | xBC | dt] = u W_in          (d_inner | d_inner + 2 G N | H)
        xBC = silu(conv_K(xBC) + b_conv)   depthwise, causal, the
                                           inputs before the first
                                           token 0
        [x | B | C] = xBC;  head h reads group h // (H / G)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
          — a `lax.scan` a token at a time from S = 0
        Mixer = groupRMSNorm_{d_inner / G}(y * silu(z)) * w W_out
    "E": s = sigmoid(u W_r); the top-k of s + b (the bias enters the
        SELECTION only); g = s[picked] / sum (norm_topk_prob) *
        routed_scaling_factor;  Mixer = sum_e g_e relu(u Wup_e)^2 Wdown_e
        + relu(u Wup_s)^2 Wdown_s  (the shared expert, width
        moe_shared_expert_intermediate_size) — over the experts of
        share.experts only: what the absent ones would add is left out
    "*": q = u W_Q (heads x d), k, v = u W_K, u W_V (kv_heads x d);
        NO rotary embedding, no other position signal;
        o_h = softmax(q_h . k_{h // rep} / sqrt(d)) v_{h // rep} over
        every j <= i;  Mixer = concat_h(o_h) W_O
      — a block of queries at a time against every key

Departures from the published description, as the configuration file
lists them: the selection bias b has no key in the config (the
family's published modelling code carries it) and is seeded; rope_theta
and partial_rotary_factor are vestigial; `expand` is unused (d_inner is
H P); layers past share.layers, experts outside share.experts and
vocabulary rows outside share.vocab are not computed.

Weights follow the program's written recipe (libsplinter_tpu/models/
mla.py and nemotron_h.py docstrings), restated in `seed_tensor` and in
the layer loop below.

What `check` compares is the TIMED PATH'S OWN output: the daemon's
audit records (engine/audit.py) of requests admitted inside the window
— the prompt ids it admitted, the ids it generated, its float32 logits
behind EVERY generated token.  A prompt must be one of the payload's
fresh prompts, whole, served cold (n_prefix 0), and its answer as long
as the request's own budget (or ended by the end-of-sequence token
before it).  The sample holds SHORT budgets and LONG ones.  Two numbers
are held to limits: the 90th percentile of the positions' errors
(precision) and the worst position (a gross error).  The CONTROL rounds
every matrix, every cached key and value, the convolution's inputs and
the state after every token to float8_e4m3: it has to fail.
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
# (exponent bits, mantissa bits) for lax.reduce_precision (PR 30)
BF16, F8 = (8, 7), (4, 3)            # bfloat16; float8_e4m3
KINDS = {"M": "ssm", "E": "moe", "*": "full"}


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


def ssm_rates(model: dict, seed: int, i: int):
    """Layer i's (dt_bias, A_log), each (H,) float32: dt_bias =
    softplus^-1(dt), dt log-uniform on [time_step_min, time_step_max]
    floored at time_step_floor; A_log = log(U[1, 16))."""
    import jax.numpy as jnp
    H = model["mamba_num_heads"]
    p = f"layers.{i}."

    def unit(name):                     # u uniform on [0, 1)
        return seed_tensor(seed, name, (H,), 1.0 / math.sqrt(12.0),
                           mean=0.5, bf16=False)
    lo = math.log(model.get("time_step_min", 0.001))
    hi = math.log(model.get("time_step_max", 0.1))
    step = jnp.maximum(jnp.exp(unit(p + "dt_bias") * (hi - lo) + lo),
                       model.get("time_step_floor", 1e-4))
    return step + jnp.log(-jnp.expm1(-step)), \
        jnp.log(1.0 + 15.0 * unit(p + "a_log"))


def expert_mixer(model: dict, seed: int, i: int, ys, e_first: int,
                 e_held: int, shared: bool = True, f8: bool = False,
                 bias_std: float = 0.015):
    """The expert mixer of layer i over the normed streams `ys` (a
    list of (S, hidden) arrays): the routed experts e_first ..
    e_first + e_held - 1 of the WHOLE model's router, plus — where
    `shared` — the shared expert.  Returns a list of (S, hidden)."""
    import jax
    import jax.numpy as jnp
    H, M = model["hidden_size"], model["moe_intermediate_size"]
    MS = model["moe_shared_expert_intermediate_size"]
    E, top_k = model["n_routed_experts"], model["num_experts_per_tok"]
    scale = float(model.get("routed_scaling_factor", 1.0))
    out_scale = 1.0 / math.sqrt(2.0 * model["num_hidden_layers"])
    p = f"layers.{i}."

    def mat(name, shape, gain=1.0):
        return seed_tensor(seed, name, shape, gain / math.sqrt(shape[0]),
                           f8=f8)

    def gates(router, bias, x):         # (S, E): zero outside the top-k
        scores = jax.nn.sigmoid(x @ router)
        _, topi = jax.lax.top_k(scores + bias, top_k)
        rows = jnp.arange(x.shape[0])[:, None]
        topv = scores[rows, topi]
        if model.get("norm_topk_prob", True):
            topv = topv / topv.sum(-1, keepdims=True)
        return jnp.zeros_like(scores).at[rows, topi].set(topv * scale)

    def relu2(x, wu, wd):
        return jnp.square(jax.nn.relu(x @ wu)) @ wd

    gate_fn = jax.jit(gates)
    add_expert = jax.jit(lambda f, y, ge, wu, wd:
                         f + ge * relu2(y, wu, wd))
    router = seed_tensor(seed, p + "router", (H, E), 1.0 / math.sqrt(H),
                         bf16=False)
    bias = seed_tensor(seed, p + "router_bias", (E,), bias_std,
                       bf16=False)
    ges = [gate_fn(router, bias, y) for y in ys]
    fs = [jnp.zeros_like(y) for y in ys]
    if shared and model.get("n_shared_experts", 1):
        w = (mat(p + "shared.up", (H, MS)),
             mat(p + "shared.down", (MS, H), out_scale))
        fs = [f + jax.jit(relu2)(y, *w) for f, y in zip(fs, ys)]
    for e in range(e_first, e_first + e_held):
        q_ = f"{p}experts.{e}."
        w = (mat(q_ + "up", (H, M)), mat(q_ + "down", (M, H), out_scale))
        fs = [add_expert(f, y, ge[:, e: e + 1], *w)
              for f, y, ge in zip(fs, ys, ges)]
    return fs


def forward_logits(model: dict, share: dict, seed: int, seqs, positions,
                   f8: bool = False, block: int = QUERY_BLOCK,
                   bias_std: float = 0.015, conv_bias_std: float = 0.1,
                   taps=None):
    """seqs: token-id arrays (ragged); positions: for each, the
    positions whose logits are wanted.  Returns a list of (len(pos),
    V) float32 arrays.  f8: the control.  taps: None or a list that
    receives, a state-space layer, each sequence's final state (H, P,
    N) — what the program's slots hold after the sequence."""
    import jax
    import jax.numpy as jnp
    g_ = model.__getitem__
    H, heads, kvh, D = g_("hidden_size"), g_("num_attention_heads"), \
        g_("num_key_value_heads"), g_("head_dim")
    SH, P, G, N, K = g_("mamba_num_heads"), g_("mamba_head_dim"), \
        g_("n_groups"), g_("ssm_state_size"), g_("conv_kernel")
    DI = SH * P
    CW = DI + 2 * G * N
    rep = heads // kvh
    eps = g_("norm_eps")
    layers = share["layers"]
    e_first, e_held = share["experts"]
    v_first, v_held = share["vocab"]
    kinds = [KINDS[c] for c in g_("hybrid_override_pattern")[:layers]]
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

    def ssm_op(lw, u):                  # u: (S, H) normed
        S = u.shape[0]
        zxd = u @ lw["w_in"]
        z, xbc, dt = zxd[:, :DI], low(zxd[:, DI: DI + CW]), \
            zxd[:, DI + CW:]
        full = jnp.concatenate([jnp.zeros((K - 1, CW)), xbc])
        xbc = jax.nn.silu(sum(full[j: j + S] * lw["conv"][j]
                              for j in range(K)) + lw["conv_bias"])
        x = xbc[:, :DI].reshape(S, SH, P)
        bm = jnp.repeat(xbc[:, DI: DI + G * N].reshape(S, G, N),
                        SH // G, axis=1)
        cm = jnp.repeat(xbc[:, DI + G * N:].reshape(S, G, N),
                        SH // G, axis=1)
        dt = jax.nn.softplus(dt + lw["dt_bias"])            # (S, SH)
        a = -jnp.exp(lw["a_log"])

        def step(st, xs):
            x_t, b_t, c_t, dt_t = xs
            st = low(st * jnp.exp(dt_t * a)[:, None, None]
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return st, jnp.einsum("hpn,hn->hp", st, c_t)

        st, y = jax.lax.scan(step, jnp.zeros((SH, P, N)), (x, bm, cm, dt))
        y = (y + x).reshape(S, DI) * jax.nn.silu(z)         # D = 1
        yg = y.reshape(S, G, DI // G)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
        return (yg.reshape(S, DI) * lw["ln_gate"]) @ lw["w_out"], st

    def attend(lw, x):                  # x: (S, H) normed, S % block == 0
        S = x.shape[0]
        q = (x @ lw["w_q"]).reshape(S, kvh, rep, D)
        k = low((x @ lw["w_k"]).reshape(S, kvh, D))  # what the cache
        v = low((x @ lw["w_v"]).reshape(S, kvh, D))  # would hold

        def blk(i0):
            qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)
            s = jnp.einsum("qgrd,kgd->grqk", qb, k) / math.sqrt(D)
            ok = jnp.arange(S)[None, :] <= (i0 + jnp.arange(block))[:, None]
            p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), -1)
            return jnp.einsum("grqk,kgd->qgrd", p, v)

        o = jax.lax.map(blk, jnp.arange(0, S, block))
        return o.reshape(S, heads * D) @ lw["w_o"]

    # a layer's steps, each ONE compiled program
    ssm_layer = jax.jit(lambda lw, n1, x: x + ssm_op(lw, rms(x, n1))[0])
    attn_layer = jax.jit(lambda lw, n1, x: x + attend(lw, rms(x, n1)))
    normed = jax.jit(rms)
    final_state = jax.jit(lambda lw, n1, x: ssm_op(lw, rms(x, n1))[1])
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
        for i, kind in enumerate(kinds):
            p = f"layers.{i}."
            n1 = vec(p + "ln_in", H)
            if kind == "ssm":
                dt_bias, a_log = ssm_rates(model, seed, i)
                lw = {"w_in": mat(p + "w_in", (H, DI + CW + SH)),
                      "conv": seed_tensor(seed, p + "conv", (K, CW),
                                          1.0 / math.sqrt(K), bf16=False),
                      "conv_bias": seed_tensor(seed, p + "conv_bias",
                                               (CW,), conv_bias_std,
                                               bf16=False),
                      "dt_bias": dt_bias, "a_log": a_log,
                      "ln_gate": vec(p + "ln_gate", DI),
                      "w_out": mat(p + "w_out", (DI, H), out_scale)}
                if taps is not None:
                    taps.append([np.asarray(final_state(
                        lw, n1, x[:len(s)])) for x, s in zip(xs, seqs)])
                xs = [ssm_layer(lw, n1, x) for x in xs]
            elif kind == "full":
                lw = {"w_q": mat(p + "w_q", (H, heads * D)),
                      "w_k": mat(p + "w_k", (H, kvh * D)),
                      "w_v": mat(p + "w_v", (H, kvh * D)),
                      "w_o": mat(p + "w_o", (heads * D, H), out_scale)}
                xs = [attn_layer(lw, n1, x) for x in xs]
            else:
                lw = None
                fs = expert_mixer(model, seed, i,
                                  [normed(x, n1) for x in xs], e_first,
                                  e_held, f8=f8, bias_std=bias_std)
                xs = [x + f for x, f in zip(xs, fs)]
                del fs
            del lw
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
                             bias_std=float(job["bias_std"]),
                             conv_bias_std=float(job["conv_bias_std"]))
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
    eos = int(spec.get("eos_id", 2))
    by_head = {}
    for j, ids in enumerate(pay["prompt_ids"]):
        by_head.setdefault((len(ids), int(ids[1]), int(ids[-1])),
                           []).append(j)

    def request_of(prompt):
        """The pool index of the request whose prompt this is."""
        key = (len(prompt), int(prompt[1]), int(prompt[-1]))
        return next((j for j in by_head.get(key, ())
                     if np.array_equal(prompt, pay["prompt_ids"][j])), None)

    known, foreign, off_budget = [], 0, 0
    for d in recs:
        j = request_of(d["prompt"])
        if j is None:
            foreign += 1
            continue
        budget, n = int(pay["budgets"][j]), len(d["tokens"])
        if not (n == budget or (n < budget and int(d["tokens"][-1]) == eos)):
            off_budget += 1
        known.append((d, budget))
    shorts = [d for d, b in known if b <= int(spec["short_budget"])]
    longs = [d for d, b in known if b >= int(spec["long_budget"])]
    rng = np.random.default_rng([int(run.args.seed), 17])

    def some(pool, n):
        return [pool[int(i)] for i in rng.choice(
            len(pool), min(int(n), len(pool)), replace=False)] \
            if pool else []
    pick_short = some(shorts, spec["sample_short"])
    pick_long = some(longs, spec["sample_long"])
    pick = pick_short + pick_long
    hits = sum(int(d["n_prefix"]) > 0 for d in pick)
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
               "bias_std": float(cfg["assumed"]["router_bias_std"]),
               "conv_bias_std": float(cfg["assumed"]["conv_bias_std"]),
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
                                for i in range(len(pick))
                                if len(out[f"ref{i}"]) > 1] or [[0.0]])
        note = (f"; {n_pos} positions, median {np.median(flat):.4f}, "
                f"neighbouring positions' logits differ by "
                f"{np.median(apart):.2f} (median), "
                f"{int((flat > 2 * lim['max_logit_err']).sum())} over "
                f"twice the precision limit; prompt+answer tokens "
                + " ".join(f"{len(d['prompt'])}+{len(d['tokens'])}"
                           for d in pick)
                + "; per answer first/p90/worst "
                + " ".join(f"{e[0]:.3f}/{np.percentile(e, 90):.3f}/"
                           f"{e.max():.3f}" for e in errs)
                + ("; CONTROL: the reference itself with matrices, cached "
                   "keys and values, the convolution's inputs and the "
                   "state after every token rounded to float8_e4m3, in "
                   "the daemon's place" if run.args.control else ""))
    return {"compared": [
        ("logit_err_p90", p90, lim["max_logit_err"], "<="),
        ("logit_err_worst_position", worst, lim["max_logit_err_worst"],
         "<="),
        ("prompts_that_are_no_request", foreign, 0, "<="),
        ("answers_off_their_budget", off_budget, 0, "<="),
        ("sampled_answers_served_from_a_hit", hits, 0, "<="),
        ("short_budgets_sampled", len(pick_short), int(spec["min_short"]),
         ">="),
        ("long_budgets_sampled", len(pick_long), int(spec["min_long"]),
         ">=")],
        "note": f"{len(pick_short)} short-budget (<= "
                f"{spec['short_budget']}) + {len(pick_long)} long-budget "
                f"(>= {spec['long_budget']}) answers of {len(shorts)} + "
                f"{len(longs)} among {len(recs)} audit records inside the "
                f"window ({len(paths)} written) against a float32 "
                f"'highest' forward of prompt + generated tokens, errors "
                f"relative to the reference logits' standard deviation, "
                f"{time.perf_counter() - t0:.1f}s{note}"}


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--job":
        raise SystemExit(job_main(sys.argv[2]))
    raise SystemExit("usage: ssm_gqa_moe_block.py --job JOB.json")
