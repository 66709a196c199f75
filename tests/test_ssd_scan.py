"""ops/ssd_scan.py — Mamba-2's state-space scan: the chunked prefill
against the token-by-token recurrence, the Pallas kernels (interpret
mode) against their jnp forms, the snapshot at `n_snap`, padding
tokens — and models/moe.py's UN-GATED dispatch against a dense loop."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu.models.moe import router_gates, sparse_moe
from libsplinter_tpu.ops import ssd_scan as S

# (T, H, P, G, N, chunk): tiny; two chunks of the published shape
SHAPES = {"tiny": (48, 4, 8, 2, 16, 16), "wide": (256, 16, 64, 2, 128, 128)}


def _data(T, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 3),
            -jnp.exp(jax.random.uniform(k[2], (H,)) * 2.7),
            jax.random.normal(k[3], (T, G, N)),
            jax.random.normal(k[4], (T, G, N)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunked_prefill_is_the_token_scan(shape, interpret):
    """From a state that is not zero, with a tail of padding tokens (dt
    0: they leave the state alone) and the snapshot one chunk in."""
    *dims, C = SHAPES[shape]
    x, dt, a, bm, cm, st = _data(*dims)
    T = dims[0]
    dt = dt.at[T - 5:].set(0.0)
    want_y, want_s = S.ssd_scan(x, dt, a, bm, cm, st)
    _, snap = S.ssd_scan(x[:C], dt[:C], a, bm[:C], cm[:C], st)
    _, unpadded = S.ssd_scan(x[:T - 5], dt[:T - 5], a, bm[:T - 5],
                             cm[:T - 5], st)
    y, s_end, s_snap = S.ssd_chunk_prefill(
        x, dt, a, bm, cm, st, n_snap=jnp.int32(C), chunk=C,
        interpret=interpret)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, atol=3e-6 * scale)
    np.testing.assert_allclose(s_end, want_s, atol=2e-5)
    np.testing.assert_allclose(s_end, unpadded, atol=2e-5)
    np.testing.assert_allclose(s_snap, snap, atol=2e-5)
    # no snapshot asked for: the state that was given
    _, _, s0 = S.ssd_chunk_prefill(x, dt, a, bm, cm, st, chunk=C,
                                   interpret=interpret)
    np.testing.assert_array_equal(s0, st)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_decode_kernel_is_the_jnp_step_and_leaves_snapshots_alone(shape):
    T, H, P, G, N, _ = SHAPES[shape]
    x, dt, a, bm, cm, st = _data(T, H, P, G, N, seed=1)
    B = 3
    states = jnp.stack([st * (i + 1) for i in range(5)])
    want_y, want_s = S.ssd_decode_step(x[:B], dt[:B], a, bm[:B], cm[:B],
                                       states)
    y, s = S.ssd_decode_step(x[:B], dt[:B], a, bm[:B], cm[:B], states,
                             interpret=True)
    np.testing.assert_allclose(y, want_y, atol=1e-5 * float(
        jnp.abs(want_y).max()))
    np.testing.assert_allclose(s, want_s, atol=1e-5)
    np.testing.assert_array_equal(s[B:], states[B:])
    # one step IS one token of the scan
    y1, s1 = S.ssd_scan(x[:1], dt[:1], a, bm[:1], cm[:1], states[0])
    np.testing.assert_allclose(y[0], y1[0], atol=1e-5 * float(
        jnp.abs(y1).max()))
    np.testing.assert_allclose(s[0], s1, atol=1e-5)


def test_bfloat16_operands_stay_near_the_scan():
    """The serving path rounds the products' operands to bfloat16 and
    sums in float32: a percent of the output's scale, not more."""
    *dims, C = SHAPES["wide"]
    x, dt, a, bm, cm, st = _data(*dims, seed=2)
    want, _ = S.ssd_scan(x, dt, a, bm, cm, st)
    for interpret in (False, True):
        got, _, _ = S.ssd_chunk_prefill(x, dt, a, bm, cm, st, chunk=C,
                                        dot_dtype=jnp.bfloat16,
                                        interpret=interpret)
        assert float(jnp.abs(got - want).max()) \
            < 0.02 * float(jnp.abs(want).max())


@pytest.mark.parametrize("heads, groups, want", [
    (64, 8, 32), (4, 2, 4), (128, 1, 128), (64, 64, 32), (24, 3, 24)])
def test_decode_head_block_holds_whole_groups(heads, groups, want):
    hb = S.decode_head_block(heads, groups)
    assert hb == want and heads % hb == 0 and hb % (heads // groups) == 0


def test_ragged_chunks_are_refused():
    x, dt, a, bm, cm, st = _data(40, 4, 8, 2, 16)
    with pytest.raises(ValueError, match="whole chunks"):
        S.ssd_chunk_prefill(x, dt, a, bm, cm, st, chunk=16)


# ------------------------------------------------ the un-gated dispatch

@pytest.mark.parametrize("up_rows", [False, True], ids=["cols", "rows"])
@pytest.mark.parametrize("interpret", [False, True], ids=["ragged", "gmm"])
def test_ungated_dispatch_is_the_dense_loop(interpret, up_rows):
    """sparse_moe without a gate matrix — down(relu(up(x))^2), the
    shared expert un-gated too — against a loop over every (token,
    held expert) pair; dead tokens reach nobody."""
    rng = np.random.default_rng(3)
    T, H, M, MS, E, k, first, held = 40, 128, 128, 256, 8, 3, 2, 4
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((T, H)), f32)
    router = jnp.asarray(rng.standard_normal((H, E)) / 8, f32)
    wu = jnp.asarray(rng.standard_normal((held, H, M)) / 11, f32)
    wd = jnp.asarray(rng.standard_normal((held, M, H)) / 11, f32)
    su = jnp.asarray(rng.standard_normal((H, MS)) / 11, f32)
    sd = jnp.asarray(rng.standard_normal((MS, H)) / 16, f32)
    bias = jnp.asarray(rng.standard_normal(E) * 0.1, f32)
    live = jnp.asarray(rng.random(T) < 0.8)
    kw = dict(top_k=k, score="sigmoid", scale=2.5, bias=bias)
    got, sizes = sparse_moe(
        x, router, None, wu.swapaxes(1, 2) if up_rows else wu, wd,
        first=first, shared=(None, su, sd), live=live,
        interpret=interpret, up_rows=up_rows, **kw)
    ids, gates = router_gates(x, router, **kw)
    want = np.square(np.maximum(np.asarray(x @ su), 0)) @ np.asarray(sd)
    count = np.zeros((held,), np.int64)
    for t in range(T):
        if not bool(live[t]):
            continue
        for e, g in zip(np.asarray(ids[t]), np.asarray(gates[t])):
            if first <= e < first + held:
                h = np.square(np.maximum(
                    np.asarray(x[t] @ wu[e - first]), 0))
                want[t] += g * (h @ np.asarray(wd[e - first]))
                count[e - first] += 1
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(sizes, count)
