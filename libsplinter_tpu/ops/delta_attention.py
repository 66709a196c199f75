"""Gated delta-rule attention (KDA: a linear-attention layer with a
per-channel forget gate) — the chunked prefill and the one-token
recurrent step.

A KDA head keeps a STATE S (d_k x d_v, float32) instead of a cache
that grows.  Per token, with a_t = exp(g_t) in (0, 1]^{d_k} the
per-channel decay, b_t in (0, 1) the write strength and q_t, k_t
l2-normalised over d:

    S'  = diag(a_t) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t * scale

The state is stored VALUE-MAJOR, St[j, i] = S[i, j] (d_v x d_k): the
key axis then lies along the chip's lanes, so decay, key and query are
row vectors that broadcast down the sublanes for nothing and `S'^T k`
is a lane reduction.  Every function here takes and returns St.

`kda_decode_step` applies one token to each of the first B state
slots in place: on a TPU a Pallas kernel whose traffic is the state
read and written once (2 x 64 KiB a head), elsewhere the same lines in
jnp.

`kda_chunk_prefill` is the chunkwise form over one row's T tokens.
Inside a chunk of C tokens, with G_t the running sum of g inside the
chunk and U the chunk's "pseudo values":

    A[t, s]   = b_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
    (I + A) [W | U0] = [b k exp(G) | b v]         unit lower triangular
    U = U0 - W S_0
    O = scale ((q exp(G)) S_0 + Aqk U),  Aqk[t, s] = sum_c q_t k_s exp(G_t - G_s), s <= t
    S_C = diag(exp(G_C)) S_0 + (k exp(G_C - G))^T U

Every exponent above is <= 0: the pairwise decays are formed as
exp(G_t - G_s) under the causal mask, in float32, never as a product
of exp(G_t) and exp(-G_s) (a forget gate of -20 a token overflows the
second within a chunk).  What does not depend on the state — A, Aqk,
the triangular solve — is computed for all chunks at once in XLA; the
part that carries the state from chunk to chunk (three matrix products
a chunk a head) is the Pallas kernel `kda_chunk_prefill`, which also
hands back the state as it stood after `n_snap` tokens (a multiple of
the chunk): what a prefix cache snapshots at a page boundary.

Padding tokens are given g = 0 and b = 0 by the caller: they leave the
state as it was.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a chunk of the prefill holds: what a snapshot boundary must be
# a multiple of.  The chunk-local work (the pairwise decays, the
# triangular solve) grows with tokens x CHUNK, the kernel's grid with
# tokens / CHUNK: at 64 the former was 15 ms of a 51 ms join on the
# chip and the kernel 1 ms (PERF.md), so 32.
CHUNK = 32
# heads one program of the decode kernel carries (8 x 64 KiB of state
# in, as much out, double-buffered: 2 MiB of VMEM)
DECODE_HEAD_BLOCK = 8

_NT = (((1,), (1,)), ((), ()))       # a @ b^T


def _on_tpu(interpret: bool, force_pallas: bool) -> bool:
    return force_pallas or interpret or jax.default_backend() == "tpu"


# ---------------------------------------------------------------- decode

def _decode_ref(q, k, v, g, beta, st):
    """q, k, v, g: (B, H, d) float32 (q scaled); beta: (B, H);
    st: (B, H, d_v, d_k).  Returns (o (B, H, d_v), new st)."""
    sd = st * jnp.exp(g)[:, :, None, :]
    kv = jnp.einsum("bhvk,bhk->bhv", sd, k)
    u = beta[..., None] * (v - kv)
    sn = sd + u[..., :, None] * k[:, :, None, :]
    return jnp.einsum("bhvk,bhk->bhv", sn, q), sn


def _decode_kernel(q_ref, k_ref, bk_ref, a_ref, bv_ref, s_ref, o_ref,
                   so_ref, *, hb: int):
    """One (row, head block) program.  *_ref rows: (1, hb, d);
    s_ref / so_ref: (1, hb, d_v, d_k), aliased."""
    d = s_ref.shape[-1]
    for i in range(hb):
        sd = s_ref[0, i] * a_ref[0, i:i + 1, :]
        kv = jnp.sum(sd * bk_ref[0, i:i + 1, :], axis=1, keepdims=True)
        # b v as a column under every key lane: [j, :] = (b v)[j]
        bv = jnp.broadcast_to(bv_ref[0, i:i + 1, :], (d, d)).T
        sn = sd + (bv - kv) * k_ref[0, i:i + 1, :]
        so_ref[0, i] = sn
        o = jax.lax.dot_general(
            jnp.broadcast_to(q_ref[0, i:i + 1, :], (8, d)), sn, _NT,
            preferred_element_type=jnp.float32)
        o_ref[0, i:i + 1, :] = o[0:1]


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(q, k, bk, a, bv, states, *, interpret: bool):
    B, H, d = q.shape
    hb = DECODE_HEAD_BLOCK
    while H % hb:
        hb //= 2
    row = pl.BlockSpec((1, hb, d), lambda b, h: (b, h, 0),
                       memory_space=pltpu.VMEM)
    mat = pl.BlockSpec((1, hb, d, d), lambda b, h: (b, h, 0, 0),
                       memory_space=pltpu.VMEM)
    o, states = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        grid=(B, H // hb),
        in_specs=[row, row, row, row, row, mat],
        out_specs=[row, mat],
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={5: 1},
        interpret=interpret,
        name="kda_decode_step",
    )(q, k, bk, a, bv, states)
    return o, states


def kda_decode_step(q, k, v, g, beta, states, *, scale: float,
                    interpret: bool = False, force_pallas: bool = False):
    """One token a row over the rows' state slots, in place.

    q, k: (B, H, d) l2-normalised; v: (B, H, d); g: (B, H, d) the log
    decay (<= 0); beta: (B, H); states: (slots, H, d_v, d_k) float32,
    slots >= B — row b's state is slot b, the slots past B (snapshots)
    are not touched.  Returns (o (B, H, d_v) float32, states)."""
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    beta = beta.astype(f32)
    B = q.shape[0]
    if _on_tpu(interpret, force_pallas):
        bcol = beta[..., None]
        return _decode_pallas(q * scale, k, k * bcol, jnp.exp(g),
                              v * bcol, states, interpret=interpret)
    o, sn = _decode_ref(q * scale, k, v, g, beta, states[:B])
    return o, states.at[:B].set(sn)


# --------------------------------------------------------------- prefill

def _intra_chunk(q, k, v, g, beta, scale: float, C: int):
    """What a chunk needs that does not depend on the state, for all
    chunks at once.  q, k, v, g: (T, H, d) float32; beta: (T, H).
    Returns (N, H, ...) arrays: W (C, d_k), U0T (d_v, C), Qg (C, d_k),
    Aqk (C, C), Kd (C, d_k), dC (1, d_k)."""
    T, H, d = q.shape
    N = T // C

    def split(x):
        return x.reshape(N, C, H, -1).transpose(0, 2, 1, 3)

    q, k, v, g = (split(x) for x in (q, k, v, g))
    b = split(beta[..., None])                            # (N, H, C, 1)
    G = jnp.cumsum(g, axis=2)
    t = jnp.arange(C)
    causal = (t[:, None] >= t[None, :])[..., None]        # s <= t
    E = jnp.where(causal, jnp.exp(jnp.minimum(
        G[:, :, :, None, :] - G[:, :, None, :, :], 0.0)), 0.0)
    ks = k[:, :, None, :, :]
    kk = jnp.sum(k[:, :, :, None, :] * ks * E, -1)        # (N, H, C, C)
    qk = jnp.sum(q[:, :, :, None, :] * ks * E, -1)
    A = b * jnp.where(t[:, None] > t[None, :], kk, 0.0)
    eG = jnp.exp(G)
    X = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype),
        jnp.concatenate([b * k * eG, b * v], -1),
        lower=True, unit_diagonal=True)
    Gc = G[:, :, -1:, :]
    return (X[..., :d], X[..., d:].swapaxes(-1, -2), q * eG * scale,
            qk * scale, k * jnp.exp(Gc - G), jnp.exp(Gc))


def _inter_chunk_ref(W, U0T, Qg, Aqk, Kd, dC, st, n_snap, C: int):
    """The state's walk over the chunks, in jnp.  st: (H, d_v, d_k)."""
    def step(carry, xs):
        st, snap, n = carry
        w, u0t, qg, aqk, kd, dc = xs
        ut = u0t - jnp.einsum("hvk,hck->hvc", st, w)
        o = jnp.einsum("hck,hvk->hcv", qg, st) \
            + jnp.einsum("hcs,hvs->hcv", aqk, ut)
        st = st * dc + jnp.einsum("hvc,hck->hvk", ut, kd)
        snap = jnp.where((n + 1) * C == n_snap, st, snap)
        return (st, snap, n + 1), o

    (st, snap, _), o = jax.lax.scan(
        step, (st, st, jnp.int32(0)), (W, U0T, Qg, Aqk, Kd, dC))
    return o, st, snap                                    # o: (N, H, C, d)


def _chunk_kernel(snap_ref, w_ref, u0t_ref, qg_ref, aqk_ref, kd_ref,
                  dc_ref, s0_ref, o_ref, sf_ref, ss_ref, st_s, *, C: int):
    """One (head, chunk) program; the chunk axis is sequential and
    st_s (d_v, d_k) float32 carries the state along it."""
    n = pl.program_id(1)
    bf = jnp.bfloat16

    @pl.when(n == 0)
    def _load():
        st_s[...] = s0_ref[0]
        ss_ref[0] = s0_ref[0]             # n_snap == 0: the state given

    st = st_s[...]
    sb = st.astype(bf)
    ut = u0t_ref[0, 0] - jax.lax.dot_general(
        sb, w_ref[0, 0].astype(bf), _NT,
        preferred_element_type=jnp.float32)               # (d_v, C)
    utb = ut.astype(bf)
    o = jax.lax.dot_general(qg_ref[0, 0].astype(bf), sb, _NT,
                            preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(aqk_ref[0, 0].astype(bf), utb, _NT,
                              preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    st = st * dc_ref[0, 0] + jnp.dot(
        utb, kd_ref[0, 0].astype(bf), preferred_element_type=jnp.float32)
    st_s[...] = st

    @pl.when((n + 1) * C == snap_ref[0])
    def _snap():
        ss_ref[0] = st

    @pl.when(n == pl.num_programs(1) - 1)
    def _final():
        sf_ref[0] = st


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("C", "interpret"))
def _inter_chunk_pallas(W, U0T, Qg, Aqk, Kd, dC, st, n_snap, *, C: int,
                        interpret: bool):
    N, H, _, d = W.shape

    def blk(*shape):
        return pl.BlockSpec((1, 1, *shape), lambda h, n, *pre: (n, h, 0, 0),
                            memory_space=pltpu.VMEM)

    head = pl.BlockSpec((1, d, d), lambda h, n, *pre: (h, 0, 0),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, N),
        in_specs=[blk(C, d), blk(d, C), blk(C, d), blk(C, C), blk(C, d),
                  blk(1, d), head],
        out_specs=[blk(C, d), head, head],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_chunk_kernel, C=C),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, H, C, d), jnp.float32),
                   jax.ShapeDtypeStruct(st.shape, st.dtype),
                   jax.ShapeDtypeStruct(st.shape, st.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk_prefill",
    )(jnp.reshape(n_snap, (1,)).astype(jnp.int32), W, U0T, Qg, Aqk, Kd,
      dC, st)


def kda_chunk_prefill(q, k, v, g, beta, state, *, scale: float,
                      n_snap=0, chunk: int = CHUNK,
                      interpret: bool = False, force_pallas: bool = False):
    """One row's T tokens through the chunkwise delta rule.

    q, k: (T, H, d) l2-normalised; v: (T, H, d); g: (T, H, d) log decay
    (<= 0); beta: (T, H); state: (H, d_v, d_k) float32, the state before
    the first token; T a multiple of `chunk` (pad with g = 0, beta = 0);
    n_snap: a traced int32, a multiple of `chunk` in 0..T.
    Returns (o (T, H, d_v) float32, the state after all T tokens, the
    state after the first n_snap tokens)."""
    T, H, d = q.shape
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"{T} tokens are not whole chunks of {C}")
    f32 = jnp.float32
    parts = _intra_chunk(*(x.astype(f32) for x in (q, k, v, g, beta)),
                         float(scale), C)
    n_snap = jnp.asarray(n_snap, jnp.int32)
    if _on_tpu(interpret, force_pallas):
        o, st, snap = _inter_chunk_pallas(*parts, state, n_snap, C=C,
                                          interpret=interpret)
    else:
        o, st, snap = _inter_chunk_ref(*parts, state, n_snap, C)
    return o.transpose(0, 2, 1, 3).reshape(T, H, d), st, snap


def kda_scan(q, k, v, g, beta, state, *, scale: float):
    """The recurrence itself, a token at a time (`lax.scan` of the
    decode lines): what the chunked form is tested against.  Shapes as
    kda_chunk_prefill; returns (o, the state after all tokens)."""
    def step(st, xs):
        o, st = _decode_ref(*(x[None] for x in xs), st[None])
        return st[0], o[0]

    f32 = jnp.float32
    st, o = jax.lax.scan(step, state, (
        q.astype(f32) * scale, k.astype(f32), v.astype(f32),
        g.astype(f32), beta.astype(f32)))
    return o, st
