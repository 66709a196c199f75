"""Speculative decoding: a draft decoder proposes, the target verifies.

The reference decodes strictly serially — one llama.cpp forward per
token (splainference.cpp:306-365).  The chunked scan (decoder.py)
already amortizes the host sync; speculative decoding additionally
amortizes the TARGET MODEL's sequential depth: a cheap draft model
runs gamma autoregressive steps, then the target scores all gamma+1
positions in ONE forward (its KV cache ingests the whole proposal like
a prefill), and the standard rejection rule keeps the target's exact
distribution:

  accept draft token x_i with prob min(1, p_t(x_i) / p_d(x_i));
  at the first rejection resample from normalize(max(p_t - p_d, 0));
  if all gamma accepted, sample one bonus token from the target's
  last-position distribution.

Greedy (temp=0) degenerates to: accept while the draft token equals
the target argmax — so speculative greedy output is BYTE-IDENTICAL to
target-only greedy output (the correctness bar in tests).

SELF-DRAFTING (self_draft_model): the draft is a truncated VIEW of
the target's own weights — the first k layers plus the shared
embedding / final norm / LM head, zero extra checkpoint bytes (the
param subtree ALIASES the target's arrays).  Because the residual
stream of a pre-norm transformer accumulates layer outputs, the
truncated read-out correlates strongly with the full one
(LayerSkip-style self-speculation, arxiv 2404.16710) — r05 measured
acceptance 0.05 with a random tiny draft; the first-3/4-layers view
measures ~0.5 even on seeded-random weights, and a real checkpoint
only improves it.

PAGED serving (the continuous-batching lane): the wrapper implements
the SAME paged surface as CompletionModel (init_paged /
paged_prefill_row / paged_decode_chunk(_async) / warmup_paged), so
`paged_supported` is True and the completion daemon drives it
unchanged.  Target and draft each own a block pool of identical page
geometry (SpecPagedCache pairs them; the draft pool is shallower —
fewer layers); a batched propose+verify+accept step runs as ONE
program: the draft proposes gamma tokens through gamma paged decode
steps, then the target scores all gamma+1 positions in ONE forward
THROUGH THE PAGED KERNEL — the multi-query ragged mask
(ops/paged_attention q_tokens: token t attends j < length + t) is
exactly a batched draft verification, no serial fallback, no dense
window.  Rejected positions' K/V go stale in their pages and are
overwritten by the next step's appends (the paged rewind: lengths
advance only past ACCEPTED history).  Per-row acceptance is ragged,
so a host-side per-row FIFO adapts the variable-length spec yield to
the daemon's fixed (batch, n) chunk cadence; rows whose FIFO is
already full ride a step with their outputs discarded (lengths not
advanced — the same stale-rewrite contract), and rows too close to
their window edge (or out of reserved pages) fall back to a plain
paged step for that iteration so the spec path can never strand the
pool.  Quantized (int8) pools compose: both pools quantize, the
verify stack dequantizes in register like every other paged dispatch.

The whole propose+verify+accept step is ONE jitted program per
(gamma,) [serial] or (gamma, batch) [paged] — draft scan, target
forward, acceptance scan, resampling all stay on device; the host
sees only (tokens, n_valid) per step, so a speculative step costs the
same runtime round trips as one chunked decode step.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devtime import DEVTIME
from .decoder import (CompletionModel, Decoder, RowJoins,
                      _nucleus_logits)


def _filtered_probs(logits, top_p: float, temp: float):
    """The sampler chain's categorical distribution (decoder.py
    _sample_graph draws from exactly this — both read the shared
    _nucleus_logits filter).  temp<=0 is greedy: a one-hot at
    argmax."""
    if temp <= 0:
        return jax.nn.one_hot(jnp.argmax(logits), logits.shape[-1],
                              dtype=jnp.float32)
    order, masked = _nucleus_logits(logits, top_p, temp)
    p_sorted = jax.nn.softmax(masked)
    # scatter back to vocab order
    return jnp.zeros_like(p_sorted).at[order].set(p_sorted)


class _ReadySpecChunk:
    """A resolved paged-spec chunk wearing the PendingChunk contract
    (models/decoder.py): the spec wrapper computes synchronously (its
    gamma-deep step already amortizes depth), so block() is a no-op
    fetch and `last` hands the final column to the daemon's carry
    protocol (which the wrapper then supersedes with its own per-row
    input state — see paged_decode_chunk_async)."""

    __slots__ = ("_block", "last", "n")

    def __init__(self, block: np.ndarray):
        self._block = block
        self.last = block[:, -1].copy()
        self.n = block.shape[1]

    def is_ready(self) -> bool:
        return True

    def block(self) -> np.ndarray:
        return self._block


def self_draft_model(target: CompletionModel,
                     draft_layers: int) -> CompletionModel:
    """A draft that is the target's OWN first `draft_layers` layers:
    the param tree aliases the target's arrays (tok_emb / ln_out /
    lm_head shared, layer_0..layer_{k-1} referenced) — no second
    checkpoint, no extra HBM beyond the (tiny) duplicate jit programs.
    Works for float and int8-resident (cfg.quantized) targets alike;
    sampler settings copy from the target so the acceptance rule
    divides by the right proposal distribution."""
    cfg = target.cfg
    if not 1 <= draft_layers < cfg.layers:
        raise ValueError(
            f"draft_layers {draft_layers} must be in [1, "
            f"{cfg.layers - 1}] (a full-depth draft is just the "
            "target)")
    mod = target.module
    if not isinstance(mod, Decoder) or mod.mlp_cls is not None:
        raise ValueError(
            "self-drafting needs the plain Decoder trunk (layer_i "
            "subtrees slice cleanly); custom/MoE modules need their "
            "own draft checkpoint")
    dcfg = dataclasses.replace(cfg, layers=draft_layers)
    p = target.params["params"]
    sub = {k: p[k] for k in ("tok_emb", "ln_out", "lm_head")}
    for i in range(draft_layers):
        sub[f"layer_{i}"] = p[f"layer_{i}"]
    mesh = getattr(target, "mesh", None)
    if mesh is not None:
        # pod-sharded target -> pod-sharded draft: the truncated view
        # must allocate ITS pools and programs under the same mesh so
        # the fused spec step's out_shardings cover both halves.
        # shard_decoder_params re-places the aliased subtree, but the
        # arrays are already laid out per decoder_param_pspec (the
        # layer_i names are identical), so the device_put is a no-op
        # alias, not a copy.
        from ..parallel.serve import ShardedCompletionModel
        return ShardedCompletionModel(
            dcfg, mesh=mesh, params={"params": sub},
            buckets=target.buckets, top_p=target.top_p,
            temp=target.temp, module=Decoder(dcfg, mesh=mesh),
            kv_dtype=target.kv_dtype)
    return CompletionModel(
        dcfg, params={"params": sub}, buckets=target.buckets,
        top_p=target.top_p, temp=target.temp,
        module=Decoder(dcfg, mesh=mod.mesh),
        kv_dtype=target.kv_dtype)


class SpecPagedCache:
    """Paired (target, draft) block pools for paged speculative
    serving — the completion daemon sees ONE cache with the
    PagedKVCache surface; every scheduling operation (ensure /
    free_row / reset) mirrors onto both pools so their page tables
    stay in lockstep (same page geometry, same pool_pages; the draft
    pool is merely shallower).  `lengths` IS the target pool's array
    (token counts are identical by construction).

    pages_needed over-reserves by the spec step's overshoot — a step
    appends up to gamma+1 tokens of K/V past the accepted history
    (rejected positions go stale in place), and the FIFO that adapts
    ragged acceptance to the daemon's fixed chunk cadence can hold up
    to a chunk + gamma produced-but-undelivered tokens — so an
    admitted row can never strand the pool mid-step (the admission
    invariant run_continuous relies on)."""

    def __init__(self, target_cache, draft_cache, gamma: int):
        self.target = target_cache
        self.draft = draft_cache
        self.gamma = gamma
        self.fifo = [deque() for _ in range(target_cache.batch)]
        self.next_input = np.zeros((target_cache.batch,), np.int64)

    # -- the PagedKVCache surface the daemon schedules against ------
    @property
    def batch(self) -> int:
        return self.target.batch

    @property
    def page(self) -> int:
        return self.target.page

    @property
    def pages_per_row(self) -> int:
        return self.target.pages_per_row

    @property
    def lengths(self):
        return self.target.lengths

    @property
    def tables(self):
        return self.target.tables

    @property
    def free_pages(self) -> int:
        return min(self.target.free_pages, self.draft.free_pages)

    @property
    def available_pages(self) -> int:
        # the admission gate (engine/completer): paired spec pools
        # never attach a prefix cache, so available == free on both
        return min(self.target.available_pages,
                   self.draft.available_pages)

    @property
    def used_pages(self) -> int:
        return self.target.used_pages

    @property
    def quantized(self) -> bool:
        return self.target.quantized

    # paired pools attach no prefix tree, and the key/value decoder has
    # neither a window group nor state slots: the target's answers
    @property
    def prefix_cache(self):
        return self.target.prefix_cache

    @property
    def window(self):
        return self.target.window

    @property
    def needs_state(self) -> bool:
        return self.target.needs_state

    @property
    def packed(self) -> bool:
        return self.target.packed

    @property
    def kv_dtype(self) -> str:
        return self.target.kv_dtype

    @property
    def sharding(self):
        """The target pool's placement (None unsharded) — the paired
        pools shard identically (both halves' init_paged thread their
        model's _pool_sharding), so one handle represents both."""
        return self.target.sharding

    @property
    def k_pools(self):                 # obs surface (shard gauges)
        return self.target.k_pools

    @property
    def _margin(self) -> int:
        # the spec overshoot margin (see class docstring): stale
        # verify appends (gamma+1) plus the FIFO's undelivered tail
        return 2 * (self.gamma + 1)

    def pages_needed(self, tokens: int) -> int:
        return self.target.pages_needed(
            min(int(tokens) + self._margin, self.target.cfg.max_len))

    def ensure(self, row: int, tokens: int) -> bool:
        # reserve the SAME margin pages_needed advertises — admission
        # checks pages_needed against free_pages and then calls
        # ensure; reserving less here would let a later admission
        # consume the margin and strand this row's spec step on an
        # exhausted pool mid-decode (the invariant run_continuous's
        # scheduler relies on)
        tokens = min(int(tokens) + self._margin,
                     self.target.cfg.max_len)
        if not self.target.ensure(row, tokens):
            return False
        if not self.draft.ensure(row, tokens):
            # identical geometry + lockstep scheduling make this
            # unreachable; roll back defensively all the same
            return False
        return True

    def free_row(self, row: int) -> None:
        self.target.free_row(row)
        self.draft.free_row(row)
        self.fifo[row].clear()
        self.next_input[row] = 0

    def reset(self) -> None:
        for r in range(self.batch):
            self.free_row(r)

    def live_tokens(self) -> int:
        return self.target.live_tokens()

    def device_mb(self) -> float:
        return round(self.target.device_mb() + self.draft.device_mb(),
                     3)


class SpeculativeCompletionModel(RowJoins):
    """generate_tokens-compatible front end over (target, draft) —
    AND a paged continuous-batching model (the CompletionModel paged
    surface) when both halves support it: the completion daemon's
    run_continuous drives this wrapper unchanged, so speculative
    decode serves the batched block-paged lane, not just the serial
    one.

    Both models must share tokenizer/vocab; sampler settings come from
    the TARGET (the draft's own top_p/temp fields are ignored — the
    proposal distribution must be the one the acceptance rule divides
    by, so both use the target's chain).
    """

    def __init__(self, target: CompletionModel, draft: CompletionModel,
                 *, gamma: int = 4, seed: int = 0):
        if target.cfg.vocab_size != draft.cfg.vocab_size:
            raise ValueError("target/draft vocab mismatch")
        if gamma < 1:
            raise ValueError("gamma must be >= 1")
        self.target = target
        self.draft = draft
        self.gamma = gamma
        self.cfg = target.cfg
        self._rng = jax.random.PRNGKey(seed + 17)
        self._progs: dict[tuple, Any] = {}
        self.stats_proposed = 0            # draft tokens proposed
        self.stats_accepted = 0            # proposals the target kept
        self.stats_verified = 0            # positions target-scored

    # -- the paged-serving contract (CompletionModel surface) -------

    @property
    def paged_supported(self) -> bool:
        """True when the continuous block-paged lane can serve this
        wrapper: both halves paged-capable.  Pod-sharded targets
        compose — the paired pools shard on kv heads like every other
        paged pool and the fused step program pins out_shardings for
        BOTH pools (the same no-silent-recompile contract the plain
        chunk program carries), so spec-paged decode runs under
        --tp N unchanged."""
        return (getattr(self.target, "paged_supported", False)
                and getattr(self.draft, "paged_supported", False))

    @property
    def buckets(self):
        return self.target.buckets

    @property
    def kv_dtype(self):
        return self.target.kv_dtype

    def sample(self, logits) -> int:
        return self.target.sample(logits)

    def sample_batch(self, logits):
        return self.target.sample_batch(logits)

    # -- the fused propose+verify+accept program ---------------------------

    def _step_program(self, gamma: int):
        key = (gamma, self.target.top_p, self.target.temp)
        fn = self._progs.get(key)
        if fn is not None:
            return fn
        t_mod, d_mod = self.target.module, self.draft.module
        top_p, temp = self.target.top_p, self.target.temp
        fprobs = functools.partial(_filtered_probs, top_p=top_p,
                                   temp=temp)

        def run(tp, dp, tcache, dcache, pos, rng, tok):
            # -- draft: gamma autoregressive steps, keeping its
            #    (filtered) proposal distribution per step
            def dstep(carry, _):
                dcache, dpos, rng, tok = carry
                logits, dcache = d_mod.apply(dp, tok.reshape(1, 1),
                                             dcache, dpos)
                p = fprobs(logits[0, 0])
                rng, sub = jax.random.split(rng)
                nxt = jax.random.categorical(
                    sub, jnp.log(jnp.maximum(p, 1e-30))).astype(jnp.int32)
                return (dcache, dpos + 1, rng, nxt), (nxt, p)

            (dcache, _, rng, _), (toks, dprobs) = jax.lax.scan(
                dstep, (dcache, pos, rng, tok), None, length=gamma)
            # the scan fed [tok, d_1..d_{gamma-1}] (slots pos..pos+g-1);
            # ingest d_gamma too so an all-accept step leaves no K/V
            # hole at slot pos+gamma for the next step to attend into
            _, dcache = d_mod.apply(dp, toks[gamma - 1].reshape(1, 1),
                                    dcache, pos + gamma)

            # -- target: ONE forward over [tok, d_1..d_gamma]
            seq = jnp.concatenate([tok.reshape(1), toks]).reshape(1, -1)
            tlogits, tcache = t_mod.apply(tp, seq, tcache, pos)
            tprobs = jax.vmap(fprobs)(tlogits[0])     # (gamma+1, V)

            # -- acceptance scan (first rejection sticks)
            def astep(carry, i):
                rng, n_acc, rejected = carry
                rng, sub = jax.random.split(rng)
                x = toks[i]
                ratio = tprobs[i, x] / jnp.maximum(dprobs[i, x], 1e-30)
                ok = (~rejected) & (jax.random.uniform(sub) <
                                    jnp.minimum(ratio, 1.0))
                return (rng, n_acc + ok.astype(jnp.int32),
                        rejected | ~ok), ok

            (rng, n_acc, _), _ = jax.lax.scan(
                astep, (rng, jnp.int32(0), jnp.bool_(False)),
                jnp.arange(gamma))

            # -- the step's final token: resampled residual at the
            #    first rejected position, or a bonus draw at gamma
            resid = jnp.maximum(tprobs[n_acc] - jnp.where(
                n_acc < gamma, dprobs[jnp.minimum(n_acc, gamma - 1)],
                jnp.zeros_like(tprobs[0])), 0.0)
            resid_sum = resid.sum()
            dist = jnp.where(resid_sum > 1e-30, resid / resid_sum,
                             tprobs[n_acc])
            rng, sub = jax.random.split(rng)
            if temp <= 0:
                final = jnp.argmax(dist).astype(jnp.int32)
            else:
                final = jax.random.categorical(
                    sub, jnp.log(jnp.maximum(dist, 1e-30))
                ).astype(jnp.int32)

            # accepted tokens then the final token, then zero padding
            out = jnp.zeros((gamma + 1,), jnp.int32)
            idx = jnp.arange(gamma + 1)
            out = jnp.where(idx < n_acc, jnp.pad(toks, (0, 1)), out)
            out = jnp.where(idx == n_acc, final, out)
            return tcache, dcache, rng, out, n_acc + 1

        fn = DEVTIME.register("completer.spec_step",
                              jax.jit(run, donate_argnums=(2, 3)))
        self._progs[key] = fn
        if len(self._progs) > 8:
            cur = (self.target.top_p, self.target.temp)
            self._progs = {k: v for k, v in self._progs.items()
                           if k[-2:] == cur}
        return fn

    # -- the paged (batched) propose+verify+accept program -----------------

    def _paged_step_program(self, gamma: int, bp: int,
                            quantized: bool):
        """ONE device program for a batched speculative step over the
        block pools: the draft proposes gamma tokens via gamma paged
        decode steps (lax.scan over ITS pool), the extra d_gamma
        ingest closes the all-accept K/V hole, then the target scores
        all gamma+1 positions in ONE multi-query paged forward (the
        ragged kernel's q_tokens stack — token t attends
        j < lengths + t), and a vmapped acceptance scan + residual
        resample finishes on device.  The host sees only
        (out (bp, gamma+1), n_valid (bp,)) per step.  Pools (and int8
        scales) are donated — the spec lane recycles buffers exactly
        like the plain chunk program."""
        key = ("pstep", gamma, bp, quantized,
               self.target.top_p, self.target.temp)
        fn = self._progs.get(key)
        if fn is not None:
            return fn
        t_mod, d_mod = self.target.module, self.draft.module
        top_p, temp = self.target.top_p, self.target.temp
        fprobs = functools.partial(_filtered_probs, top_p=top_p,
                                   temp=temp)

        def zip_cache(pools):
            return [tuple(layer) for layer in zip(*pools)]

        def unzip_cache(cache):
            return tuple(list(side) for side in zip(*cache))

        def run(tp, dp, t_pools, d_pools, t_tables, t_lengths,
                d_tables, d_lengths, rng, toks):
            # -- draft: gamma batched paged decode steps, keeping the
            #    (filtered) proposal distribution per step
            def dstep(carry, _):
                dcache, dlen, rng, tok = carry
                logits, dcache = d_mod.apply(
                    dp, tok.reshape(-1, 1), dcache, jnp.int32(0),
                    None, dlen, d_tables)
                p = jax.vmap(fprobs)(logits[:, 0])       # (bp, V)
                rng, sub = jax.random.split(rng)
                subs = jax.random.split(sub, bp)
                nxt = jax.vmap(lambda r, pr: jax.random.categorical(
                    r, jnp.log(jnp.maximum(pr, 1e-30))))(
                    subs, p).astype(jnp.int32)
                return (dcache, dlen + 1, rng, nxt), (nxt, p)

            (dcache, _, rng, _), (dtoks, dprobs) = jax.lax.scan(
                dstep, (zip_cache(d_pools), d_lengths, rng, toks),
                None, length=gamma)
            # the scan fed [tok, d_1..d_{gamma-1}]; ingest d_gamma too
            # so an all-accept step leaves no K/V hole
            _, dcache = d_mod.apply(
                dp, dtoks[gamma - 1].reshape(-1, 1), dcache,
                jnp.int32(0), None, d_lengths + gamma, d_tables)

            # -- target: ONE multi-query paged forward over
            #    [tok, d_1..d_gamma] per row (q_tokens = gamma+1)
            seq = jnp.concatenate([toks[None], dtoks], 0).T
            tlogits, tcache = t_mod.apply(
                tp, seq, zip_cache(t_pools), jnp.int32(0), None,
                t_lengths, t_tables)
            tprobs = jax.vmap(jax.vmap(fprobs))(tlogits)

            # -- per-row acceptance scan + residual resample
            def accept_row(rng_r, d_r, dp_r, tp_r):
                # d_r (g,), dp_r (g, V), tp_r (g+1, V)
                def astep(carry, i):
                    rng_r, n_acc, rejected = carry
                    rng_r, sub = jax.random.split(rng_r)
                    x = d_r[i]
                    ratio = tp_r[i, x] / jnp.maximum(dp_r[i, x],
                                                     1e-30)
                    ok = (~rejected) & (jax.random.uniform(sub)
                                        < jnp.minimum(ratio, 1.0))
                    return (rng_r, n_acc + ok.astype(jnp.int32),
                            rejected | ~ok), ok

                (rng_r, n_acc, _), _ = jax.lax.scan(
                    astep, (rng_r, jnp.int32(0), jnp.bool_(False)),
                    jnp.arange(gamma))
                resid = jnp.maximum(tp_r[n_acc] - jnp.where(
                    n_acc < gamma,
                    dp_r[jnp.minimum(n_acc, gamma - 1)],
                    jnp.zeros_like(tp_r[0])), 0.0)
                rs = resid.sum()
                dist = jnp.where(rs > 1e-30, resid / rs, tp_r[n_acc])
                rng_r, sub = jax.random.split(rng_r)
                if temp <= 0:
                    final = jnp.argmax(dist).astype(jnp.int32)
                else:
                    final = jax.random.categorical(
                        sub, jnp.log(jnp.maximum(dist, 1e-30))
                    ).astype(jnp.int32)
                idx = jnp.arange(gamma + 1)
                out = jnp.where(idx < n_acc, jnp.pad(d_r, (0, 1)),
                                jnp.int32(0))
                out = jnp.where(idx == n_acc, final, out)
                return out, n_acc + 1

            rng, sub = jax.random.split(rng)
            subs = jax.random.split(sub, bp)
            out, n_valid = jax.vmap(accept_row)(
                subs, dtoks.T, dprobs.transpose(1, 0, 2), tprobs)
            return (unzip_cache(tcache), unzip_cache(dcache), out,
                    n_valid)

        # sharded pools: pin BOTH halves' output placements (pools +
        # scales per each model's own layer count, out/n_valid
        # replicated) — the same signature-stability contract the
        # plain chunk program pins (SPL203); without it the first
        # serve-time spec step after warmup silently recompiles
        # against GSPMD-chosen output shardings
        nsc = 2 if quantized else 0
        t_sh = self.target._paged_pool_out_shardings(
            2, 0, n_scale_lists=nsc)
        out_sh = None
        if t_sh is not None:
            d_sh = self.draft._paged_pool_out_shardings(
                2, 0, n_scale_lists=nsc)
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.target._pool_sharding().mesh,
                                PartitionSpec())
            out_sh = (t_sh, d_sh, rep, rep)
        kw = {} if out_sh is None else {"out_shardings": out_sh}
        fn = DEVTIME.register("completer.spec_paged_step",
                              jax.jit(run, donate_argnums=(2, 3),
                                      **kw))
        self._progs[key] = fn
        if len(self._progs) > 8:
            cur = (self.target.top_p, self.target.temp)
            self._progs = {k: v for k, v in self._progs.items()
                           if k[-2:] == cur}
        return fn

    # -- paged serving surface (run_continuous drives this) ----------------

    def init_paged(self, batch: int, *, page: int = 128,
                   pool_pages: int | None = None,
                   kv_dtype: str | None = None) -> SpecPagedCache:
        """Paired pools of IDENTICAL page geometry (the draft's is
        shallower — fewer layers); kv_dtype threads to both, so int8
        quantized pools and speculative decode compose."""
        t = self.target.init_paged(batch, page=page,
                                   pool_pages=pool_pages,
                                   kv_dtype=kv_dtype)
        d = self.draft.init_paged(batch, page=page,
                                  pool_pages=pool_pages,
                                  kv_dtype=kv_dtype)
        return SpecPagedCache(t, d, self.gamma)

    def paged_prefill_row(self, cache: SpecPagedCache, prompt_ids,
                          row: int):
        """Prefill the row into BOTH pools (the draft shares the
        prompt's pages-worth of K/V from its own shallower trunk);
        returns the TARGET's last-token logits for sampling the first
        output token, like the base surface."""
        logits = self.target.paged_prefill_row(cache.target,
                                               prompt_ids, row)
        self.draft.paged_prefill_row(cache.draft, prompt_ids, row)
        cache.fifo[row].clear()
        return logits

    def _pools_of(self, pc):
        if pc.quantized:
            return (pc.k_pools, pc.v_pools, pc.k_scales, pc.v_scales)
        return (pc.k_pools, pc.v_pools)

    def _store_pools(self, pc, pools):
        if pc.quantized:
            kp, vp, ks, vs = pools
            pc.k_scales, pc.v_scales = list(ks), list(vs)
        else:
            kp, vp = pools
        pc.k_pools, pc.v_pools = list(kp), list(vp)

    def _spec_step(self, cache: SpecPagedCache, col: np.ndarray):
        """Dispatch one batched spec step and land the pools back in
        the caches.  Host bookkeeping (lengths, FIFO, stats) is the
        CALLER's job — it knows which rows consume the step."""
        bp = cache.batch
        fn = self._paged_step_program(self.gamma, bp, cache.quantized)
        self._rng, sub = jax.random.split(self._rng)
        t_pools, d_pools, out, n_valid = fn(
            self.target.params, self.draft.params,
            self._pools_of(cache.target), self._pools_of(cache.draft),
            jnp.asarray(cache.target.tables),
            jnp.asarray(cache.target.lengths),
            jnp.asarray(cache.draft.tables),
            jnp.asarray(cache.draft.lengths),
            sub, jnp.asarray(col, jnp.int32))
        self._store_pools(cache.target, t_pools)
        self._store_pools(cache.draft, d_pools)
        host = np.asarray(out), np.asarray(n_valid)
        mark = DEVTIME.take_mark("completer.spec_paged_step")
        if mark is not None:
            mark.close()    # np.asarray above IS the collect point
        return host

    def _plain_step(self, cache: SpecPagedCache, col: np.ndarray,
                    freeze: list[int]):
        """One NON-speculative paged step on both pools (same input
        column; the draft's sample is discarded — its K/V ingest is
        the point, so the draft cache never grows holes).  Rows in
        `freeze` keep their lengths (their appends stale-rewrite, the
        same contract as a rejected proposal)."""
        t_before = cache.target.lengths.copy()
        d_before = cache.draft.lengths.copy()
        blk = self.target.paged_decode_chunk(cache.target, col, 1)
        self.draft.paged_decode_chunk(cache.draft, col, 1)
        for r in freeze:
            cache.target.lengths[r] = t_before[r]
            cache.draft.lengths[r] = d_before[r]
        return blk[:, 0]

    def paged_decode_chunk_async(self, cache: SpecPagedCache, tokens,
                                 n: int, carry=None):
        """The daemon's chunk contract — (batch, n) sampled ids per
        dispatch — served speculatively: spec steps run until every
        live row's FIFO holds n tokens, then the chunk pops exactly n
        per row (ragged acceptance is absorbed by the FIFO, surplus
        carries to the next chunk).  Per iteration, a row already
        sated discards its outputs (lengths frozen — stale-rewrite),
        and if any advancing row lacks window/page room for the full
        gamma+1 stack the iteration degrades to a plain paged step,
        so the spec path can never strand the pool or overrun a
        window.  `tokens[r] >= 0` marks a freshly joined row (its
        prefill sample); the device-carry protocol of the base model
        is subsumed by the wrapper's own per-row input state, so the
        returned chunk is already resolved (is_ready() True) — the
        daemon's K-deep window degrades to sync for the spec lane,
        which the step's internal gamma-deep batching more than
        repays."""
        bp = cache.batch
        toks = np.full((bp,), -1, np.int64)
        toks[: len(tokens)] = np.asarray(tokens).astype(np.int64)
        for r in range(bp):
            if toks[r] >= 0:           # freshly joined / host-fed row
                cache.next_input[r] = toks[r]
                cache.fifo[r].clear()

        def live_rows():
            return [r for r in range(bp)
                    if cache.target.lengths[r] > 0]

        rounds = 0
        while any(len(cache.fifo[r]) < n for r in live_rows()):
            rounds += 1
            if rounds > 4 * n + 8:     # each round adds >= 1 token to
                raise RuntimeError(    # every needy row — unreachable
                    "paged speculative chunk failed to converge")
            rows = live_rows()
            advance = [r for r in rows if len(cache.fifo[r]) < n]
            frozen = [r for r in rows if r not in advance]
            col = np.zeros((bp,), np.int64)
            for r in rows:
                col[r] = cache.next_input[r]
            g = self.gamma
            # batch-wide: ONE infeasible advancing row (window edge /
            # pool margin) degrades the whole iteration to a plain
            # step rather than splitting the batch into two device
            # programs.  Deliberate: the daemon's own edge check
            # force-finishes rows within `step` of their window
            # before dispatching, so only rows in the narrow
            # (gamma+1)-past-step band ever trip this, and they are
            # about to finish anyway.
            spec_ok = all(
                int(cache.target.lengths[r]) + g + 1
                <= self.cfg.max_len
                and cache.ensure(
                    r, int(cache.target.lengths[r]) + g + 1)
                for r in advance)
            if spec_ok:
                out, n_valid = self._spec_step(cache, col)
                for r in advance:
                    nv = int(n_valid[r])
                    cache.fifo[r].extend(
                        int(x) for x in out[r, :nv])
                    cache.next_input[r] = int(out[r, nv - 1])
                    cache.target.lengths[r] += nv
                    cache.draft.lengths[r] += nv
                    self.stats_proposed += g
                    self.stats_accepted += nv - 1
                    self.stats_verified += g + 1
                # frozen rows: outputs discarded, lengths untouched —
                # their in-page appends stale-rewrite next round
            else:
                outc = self._plain_step(cache, col, frozen)
                for r in advance:
                    cache.fifo[r].append(int(outc[r]))
                    cache.next_input[r] = int(outc[r])

        block = np.zeros((bp, n), np.int32)
        for r in live_rows():
            for c in range(n):
                block[r, c] = cache.fifo[r].popleft()
        return _ReadySpecChunk(block)

    def paged_decode_chunk(self, cache: SpecPagedCache, tokens,
                           n: int) -> np.ndarray:
        return self.paged_decode_chunk_async(cache, tokens, n).block()

    def warmup_paged(self, cache: SpecPagedCache, chunk: int = 8,
                     max_prompt: int | None = None) -> None:
        """Pre-compile the whole spec-paged program set: both halves'
        prefill buckets + commit scatters + plain chunk programs (the
        window-edge fallback) AND the fused spec step, against the
        SAME pool geometry run_continuous will serve with —
        compile_count stays flat across join/finish/join cycles."""
        with DEVTIME.warmup_phase():
            self._warmup_paged_spec(cache, chunk, max_prompt)

    def _warmup_paged_spec(self, cache: SpecPagedCache, chunk: int,
                           max_prompt: int | None) -> None:
        self.target.warmup_paged(cache.target, chunk=chunk,
                                 max_prompt=max_prompt)
        self.draft.warmup_paged(cache.draft, chunk=chunk,
                                max_prompt=max_prompt)
        # the plain single-step fallback programs (n=1)
        self.target.paged_decode_chunk(
            cache.target, np.ones((cache.batch,), np.int32), 1)
        self.draft.paged_decode_chunk(
            cache.draft, np.ones((cache.batch,), np.int32), 1)
        cache.target.reset()
        cache.draft.reset()
        # one spec chunk through a real (tiny) row drills the fused
        # step program; stats from the drill are rolled back so the
        # acceptance gauges only ever measure real traffic
        stats = (self.stats_proposed, self.stats_accepted,
                 self.stats_verified)
        logits = self.paged_prefill_row(
            cache, np.ones((3,), np.int32), 0)
        toks = np.full((cache.batch,), -1, np.int64)
        toks[0] = int(np.argmax(logits))
        self.paged_decode_chunk(cache, toks, max(1, chunk))
        cache.reset()
        (self.stats_proposed, self.stats_accepted,
         self.stats_verified) = stats

    def compile_count(self) -> int:
        """Distinct XLA programs across target + draft + the spec
        step cache (the obs surface the daemon pins flat after
        warmup).  -1 when the private jax API is unavailable."""
        t = self.target.compile_count()
        d = self.draft.compile_count()
        if t < 0 or d < 0:
            return -1
        total = t + d
        for f in self._progs.values():
            f = getattr(f, "__wrapped__", f)   # devtime wrapper
            try:
                total += int(f._cache_size())
            except Exception:
                return -1
        return total

    # -- generation surface ------------------------------------------------

    def reset(self) -> None:
        self.target.reset()
        self.draft.reset()

    def generate_tokens(self, prompt_ids: np.ndarray, max_new: int,
                        *, eos_id: int | None = None, chunk: int = 0):
        """Generator of sampled ids (generate_tokens contract,
        decoder.py).  `chunk` is accepted for signature compatibility
        and ignored — the speculative step IS the chunk."""
        t, d = self.target, self.draft
        logits = t.prefill(np.asarray(prompt_ids, np.int32))
        d.prefill(np.asarray(prompt_ids, np.int32))
        tok = t.sample(logits)
        yield int(tok)
        if eos_id is not None and tok == eos_id:
            return
        produced = 1
        while produced < max_new:
            room = min(t.cfg.max_len - t._pos - 1,
                       d.cfg.max_len - d._pos - 1,
                       max_new - produced)
            if room <= 0:
                break
            g = min(self.gamma, room)
            prog = self._step_program(g)
            self._rng, sub = jax.random.split(self._rng)
            t._cache, d._cache, _, out, n_valid = prog(
                t.params, d.params, t._cache, d._cache,
                jnp.int32(t._pos), sub, jnp.int32(int(tok)))
            out = np.asarray(out)
            n_valid = int(n_valid)
            mark = DEVTIME.take_mark("completer.spec_step")
            if mark is not None:
                mark.close()    # int(n_valid) was the collect point
            # both caches hold rows written beyond the accepted
            # history; parking pos at the accepted end makes them
            # unreachable until overwritten (decoder.py prefill note)
            t._pos += n_valid
            d._pos += n_valid
            self.stats_proposed += g
            self.stats_accepted += n_valid - 1
            self.stats_verified += g + 1
            for i in range(n_valid):
                tokn = int(out[i])
                yield tokn
                produced += 1
                if eos_id is not None and tokn == eos_id:
                    return
                if produced >= max_new:
                    return
            tok = int(out[n_valid - 1])

    def warmup(self, chunk: int = 8) -> None:
        """Pre-compile the prefill + step programs (one short
        generation); further prompt buckets compile on first use and
        persist in the XLA cache.  `chunk` accepted for surface
        compatibility with CompletionModel.warmup."""
        with DEVTIME.warmup_phase():
            n = min(8, self.cfg.max_len - self.gamma - 3)
            ids = np.ones((max(1, n),), np.int32)
            for _ in self.generate_tokens(ids, self.gamma + 1):
                pass
            self.reset()

    @property
    def acceptance_rate(self) -> float:
        return (self.stats_accepted / self.stats_proposed
                if self.stats_proposed else 0.0)
