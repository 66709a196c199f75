"""Mamba-2's state-space scan (SSD: a scalar decay a head, grouped B
and C) — the chunked prefill and the one-token recurrent step.

A head keeps a STATE S (P x N, float32: P the head's width, N the
state size) instead of a cache that grows.  Per token, with dt_t > 0
the head's step, A < 0 its decay rate, x_t (P,) its input and B_t, C_t
(N,) the input and output maps of the head's GROUP (head h reads group
h // (H / G)):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t                       (the caller adds the skip D x_t)

The state is stored (slots, H, P, N): N = 128 lies along the chip's
lanes, so B and C are row vectors that broadcast down the sublanes for
nothing and `S C` is a lane reduction.

`ssd_decode_step` applies one token to each of the first B state slots
in place: on a TPU the Pallas kernel `ssd_decode_step`, whose traffic
is the state read and written once (2 x 32 KiB a head), elsewhere the
same lines in jnp.

`ssd_chunk_prefill` is the chunkwise form over ONE row's T tokens.
Inside a chunk of C tokens, with L_t the running sum of dt A inside
the chunk (inclusive) and xd_t = dt_t x_t:

    Y   = (M o (Cm Bm^T)) Xd + exp(L) o (Cm S_0^T)     M[t, s] = exp(L_t - L_s), s <= t
    S_C = exp(L_C) S_0 + (Xd o exp(L_C - L))^T Bm

Every exponent is <= 0: the pairwise decays are formed as
exp(L_t - L_s) under the causal mask, in float32.  ALL of it — the
chunk-local products and the state's walk from chunk to chunk — runs
in the one Pallas kernel `ssd_chunk_prefill`, a (group, chunk) grid
whose chunk axis is sequential: the group's Cm Bm^T is formed once for
its H / G heads, the state of those heads rides a VMEM scratch, and the
kernel hands back the state as it stood after `n_snap` tokens (a
multiple of the chunk): what a prefix cache snapshots at a page
boundary.  The caller makes what is elementwise over tokens (dt, the
running sums, xd and their transposes).

Padding tokens are given dt = 0 by the caller: no decay, no input —
they leave the state as it was.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tokens a chunk of the prefill holds (the published chunk_size): what
# a snapshot boundary must be a multiple of
CHUNK = 128
# heads one program of the decode kernel carries at most (32 x 32 KiB
# of state in, as much out, double-buffered: 4 MiB of VMEM)
DECODE_HEAD_BLOCK = 32

_NT = (((1,), (1,)), ((), ()))       # a @ b^T


def _on_tpu(interpret: bool, force_pallas: bool) -> bool:
    return force_pallas or interpret or jax.default_backend() == "tpu"


def _per_head(m, heads: int):
    """(..., G, N) -> (..., H, N): head h reads group h // (H / G)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


# ---------------------------------------------------------------- decode

def _decode_ref(xd, da, bm, cm, st):
    """xd: (B, H, P) = dt x; da: (B, H) the decay exp(dt A); bm, cm:
    (B, G, N); st: (B, H, P, N).  Returns (y (B, H, P), new st)."""
    H = xd.shape[1]
    sn = st * da[:, :, None, None] \
        + xd[..., None] * _per_head(bm, H)[:, :, None, :]
    return jnp.einsum("bhpn,bhn->bhp", sn, _per_head(cm, H)), sn


def _decode_kernel(x_ref, a_ref, b_ref, c_ref, s_ref, o_ref, so_ref, *,
                   hb: int, hpg: int):
    """One (row, head block) program.  x_ref / o_ref: (1, 1, P, hb), a
    head a LANE (a head's x is the column its state's rows scale by);
    a_ref: (1, hb, N) the decay, a row a head; b_ref / c_ref: (1, 1,
    hb / hpg, N); s_ref / so_ref: (1, hb, P, N), aliased."""
    for i in range(hb):
        g = i // hpg
        sn = s_ref[0, i] * a_ref[0, i:i + 1, :] \
            + x_ref[0, 0, :, i:i + 1] * b_ref[0, 0, g:g + 1, :]
        so_ref[0, i] = sn
        o_ref[0, 0, :, i:i + 1] = jnp.sum(
            sn * c_ref[0, 0, g:g + 1, :], axis=1, keepdims=True)


def decode_head_block(heads: int, groups: int) -> int:
    """Heads one decode program carries: whole groups, at most
    DECODE_HEAD_BLOCK heads (one group where a group is wider)."""
    hpg = heads // groups
    k = max((k for k in range(1, groups + 1)
             if groups % k == 0 and k * hpg <= DECODE_HEAD_BLOCK),
            default=1)
    return k * hpg


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.paged_chunk); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(xd, da, bm, cm, states, *, interpret: bool):
    B, H, P = xd.shape
    G, N = bm.shape[1:]
    hpg = H // G
    hb = decode_head_block(H, G)
    nb, gb = H // hb, hb // hpg
    # a head a lane, so that a head's x is a column of its block
    xt = xd.reshape(B, nb, hb, P).swapaxes(2, 3)
    col = pl.BlockSpec((1, 1, P, hb), lambda b, h: (b, h, 0, 0),
                       memory_space=pltpu.VMEM)
    row = pl.BlockSpec((1, hb, N), lambda b, h: (b, h, 0),
                       memory_space=pltpu.VMEM)
    grp = pl.BlockSpec((1, 1, gb, N), lambda b, h: (b, h, 0, 0),
                       memory_space=pltpu.VMEM)
    mat = pl.BlockSpec((1, hb, P, N), lambda b, h: (b, h, 0, 0),
                       memory_space=pltpu.VMEM)
    o, states = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, hpg=hpg),
        grid=(B, nb),
        in_specs=[col, row, grp, grp, mat],
        out_specs=[col, mat],
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssd_decode_step",
    )(xt, jnp.broadcast_to(da[..., None], (B, H, N)),
      bm.reshape(B, nb, gb, N), cm.reshape(B, nb, gb, N), states)
    return o.swapaxes(2, 3).reshape(B, H, P), states


def ssd_decode_step(x, dt, a, bm, cm, states, *, interpret: bool = False,
                    force_pallas: bool = False):
    """One token a row over the rows' state slots, in place.

    x: (B, H, P); dt: (B, H) the step (after softplus, > 0); a: (H,)
    the decay rate (< 0); bm, cm: (B, G, N), G dividing H; states:
    (slots, H, P, N) float32, slots >= B — row b's state is slot b,
    the slots past B (snapshots) are not touched.  Returns (y (B, H,
    P) float32 WITHOUT the skip D x, states)."""
    f32 = jnp.float32
    x, dt, bm, cm = (v.astype(f32) for v in (x, dt, bm, cm))
    B = x.shape[0]
    xd, da = x * dt[..., None], jnp.exp(dt * a.astype(f32))
    if _on_tpu(interpret, force_pallas):
        return _decode_pallas(xd, da, bm, cm, states, interpret=interpret)
    y, sn = _decode_ref(xd, da, bm, cm, states[:B])
    return y, states.at[:B].set(sn)


# --------------------------------------------------------------- prefill

def _chunk_sums(dt, a, C: int):
    """The running sums of dt A inside each chunk.  dt: (T, H); a:
    (H,).  Returns L (N, H, C) float32, <= 0."""
    T, H = dt.shape
    return jnp.cumsum((dt * a).reshape(T // C, C, H), axis=1) \
        .transpose(0, 2, 1)


def _chunk_ref(xd, L, bm, cm, st, n_snap, C: int, dd):
    """The chunkwise form in jnp, a chunk a scan step.  xd: (T, H, P);
    L: (N, H, C); bm, cm: (T, G, N); st: (H, P, N)."""
    T, H, P = xd.shape
    N = T // C
    f32 = jnp.float32
    t = jnp.arange(C)
    causal = t[:, None] >= t[None, :]

    def step(carry, xs):
        st, snap, n = carry
        xc, l, b, c = xs            # (C, H, P), (H, C), (C, G, .) x 2
        bh, ch = _per_head(b, H).astype(dd), _per_head(c, H).astype(dd)
        cb = jnp.einsum("thn,shn->hts", ch, bh,
                        preferred_element_type=f32)
        m = jnp.where(causal, jnp.exp(jnp.minimum(
            l[:, :, None] - l[:, None, :], 0.0)), 0.0)
        y = jnp.einsum("hts,shp->thp", (cb * m).astype(dd),
                       xc.astype(dd), preferred_element_type=f32) \
            + jnp.exp(l).T[..., None] * jnp.einsum(
                "thn,hpn->thp", ch, st.astype(dd),
                preferred_element_type=f32)
        end = l[:, -1]
        st = st * jnp.exp(end)[:, None, None] + jnp.einsum(
            "shp,shn->hpn",
            (xc * jnp.exp(end[:, None] - l).T[..., None]).astype(dd), bh,
            preferred_element_type=f32)
        snap = jnp.where((n + 1) * C == n_snap, st, snap)
        return (st, snap, n + 1), y

    (st, snap, _), y = jax.lax.scan(
        step, (st, st, jnp.int32(0)),
        (xd.reshape(N, C, H, P), L, bm.reshape(N, C, *bm.shape[1:]),
         cm.reshape(N, C, *cm.shape[1:])))
    return y.reshape(T, H, P), st, snap


def _chunk_kernel(snap_ref, x_ref, xt_ref, b_ref, c_ref, lr_ref, lc_ref,
                  s0_ref, y_ref, sf_ref, ss_ref, st_s, *, C: int,
                  hpg: int, dd):
    """One (group, chunk) program; the chunk axis is sequential and
    st_s (hpg, P, N) float32 carries the group's heads' states along
    it.  x_ref: (hpg, C, P) dt x; xt_ref: (hpg, P, C) the same, a token
    a lane; b_ref / c_ref: (1, C, N); lr_ref: (1, hpg, C) the running
    sums, a row a head; lc_ref: (1, 1, C, hpg) the same, a lane a
    head."""
    n = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(n == 0)
    def _load():
        st_s[...] = s0_ref[...]
        ss_ref[...] = s0_ref[...]         # n_snap == 0: the state given

    bm, cm = b_ref[0].astype(dd), c_ref[0].astype(dd)
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=f32)
    causal = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    for j in range(hpg):
        lr = lr_ref[0, j:j + 1, :]                        # (1, C)
        lc = lc_ref[0, 0, :, j:j + 1]                     # (C, 1)
        m = jnp.where(causal, jnp.exp(jnp.minimum(lc - lr, 0.0)), 0.0)
        st = st_s[j]
        y = jnp.dot((cb * m).astype(dd), x_ref[j].astype(dd),
                    preferred_element_type=f32) \
            + jnp.exp(lc) * jax.lax.dot_general(
                cm, st.astype(dd), _NT, preferred_element_type=f32)
        y_ref[j] = y
        # the chunk's whole decay, along the lanes first: a (1, 1)
        # value does not broadcast over sublanes and lanes at once
        end = lr[:, C - 1:C]
        st_s[j] = st * jnp.exp(jnp.broadcast_to(end, (1, st.shape[1]))) \
            + jnp.dot((xt_ref[j] * jnp.exp(
                jnp.broadcast_to(end, (1, C)) - lr)).astype(dd), bm,
                preferred_element_type=f32)

    @pl.when((n + 1) * C == snap_ref[0])
    def _snap():
        ss_ref[...] = st_s[...]

    @pl.when(n == pl.num_programs(1) - 1)
    def _final():
        sf_ref[...] = st_s[...]


# splint: ignore[SPL205] reason=runs inside the registered paged programs (completer.suffix_prefill); the outer program is the attribution point
@functools.partial(jax.jit, static_argnames=("C", "dd", "interpret"))
def _chunk_pallas(xd, L, bm, cm, st, n_snap, *, C: int, dd,
                  interpret: bool):
    T, H, P = xd.shape
    G, N = bm.shape[1:]
    hpg, NC = H // G, T // C
    xh = xd.transpose(1, 0, 2)                            # (H, T, P)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, NC),
        in_specs=[
            spec((hpg, C, P), lambda g, n, *pre: (g, n, 0)),
            spec((hpg, P, C), lambda g, n, *pre: (g, 0, n)),
            spec((1, C, N), lambda g, n, *pre: (g, n, 0)),
            spec((1, C, N), lambda g, n, *pre: (g, n, 0)),
            spec((1, hpg, C), lambda g, n, *pre: (n, g, 0)),
            spec((1, 1, C, hpg), lambda g, n, *pre: (n, g, 0, 0)),
            spec((hpg, P, N), lambda g, n, *pre: (g, 0, 0))],
        out_specs=[
            spec((hpg, C, P), lambda g, n, *pre: (g, n, 0)),
            spec((hpg, P, N), lambda g, n, *pre: (g, 0, 0)),
            spec((hpg, P, N), lambda g, n, *pre: (g, 0, 0))],
        scratch_shapes=[pltpu.VMEM((hpg, P, N), jnp.float32)])
    y, sf, ss = pl.pallas_call(
        functools.partial(_chunk_kernel, C=C, hpg=hpg, dd=dd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, T, P), jnp.float32),
                   jax.ShapeDtypeStruct(st.shape, st.dtype),
                   jax.ShapeDtypeStruct(st.shape, st.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk_prefill",
    )(jnp.reshape(n_snap, (1,)).astype(jnp.int32), xh,
      xh.swapaxes(1, 2), bm.transpose(1, 0, 2), cm.transpose(1, 0, 2), L,
      L.reshape(NC, G, hpg, C).swapaxes(2, 3), st)
    return y.transpose(1, 0, 2), sf, ss


def ssd_chunk_prefill(x, dt, a, bm, cm, state, *, n_snap=0,
                      chunk: int = CHUNK, dot_dtype=jnp.float32,
                      interpret: bool = False, force_pallas: bool = False):
    """One row's T tokens through the chunkwise scan.

    x: (T, H, P); dt: (T, H) the step (after softplus; 0 for a padding
    token); a: (H,) the decay rate (< 0); bm, cm: (T, G, N); state: (H,
    P, N) float32, the state before the first token; T a multiple of
    `chunk`; n_snap: a traced int32, a multiple of `chunk` in 0..T;
    dot_dtype: what the matrix products' operands are rounded to (the
    model's dtype; sums are float32).
    Returns (y (T, H, P) float32 WITHOUT the skip D x, the state after
    all T tokens, the state after the first n_snap tokens)."""
    T = x.shape[0]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"{T} tokens are not whole chunks of {C}")
    f32 = jnp.float32
    x, dt, bm, cm = (v.astype(f32) for v in (x, dt, bm, cm))
    xd, L = x * dt[..., None], _chunk_sums(dt, a.astype(f32), C)
    n_snap = jnp.asarray(n_snap, jnp.int32)
    dd = jnp.dtype(dot_dtype)
    if _on_tpu(interpret, force_pallas):
        return _chunk_pallas(xd, L, bm, cm, state, n_snap, C=C, dd=dd,
                             interpret=interpret)
    return _chunk_ref(xd, L, bm, cm, state, n_snap, C, dd)


def ssd_scan(x, dt, a, bm, cm, state):
    """The recurrence itself, a token at a time (`lax.scan` of the
    decode lines): what the chunked form is tested against.  Shapes as
    ssd_chunk_prefill; returns (y, the state after all tokens)."""
    f32 = jnp.float32

    def step(st, xs):
        x, dt, b, c = xs
        y, st = _decode_ref((x * dt[:, None])[None], jnp.exp(dt * a)[None],
                            b[None], c[None], st[None])
        return st[0], y[0]

    st, y = jax.lax.scan(step, state, tuple(
        v.astype(f32) for v in (x, dt, bm, cm)))
    return y, st
