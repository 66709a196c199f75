"""Fused streaming top-k parity vs the score-matrix + lax.top_k
reference path.

The Pallas kernel runs in INTERPRET mode so the CPU tier-1 suite
covers the actual kernel body (block accumulator, in-VMEM select,
tie-break, filler contract), not a shadow implementation.  Reference
ranking = stable argsort over cosine_scores on the same backend —
identical tie-break semantics to lax.top_k (smallest index first).
`make search-check` runs this file.
"""
import numpy as np
import pytest

from libsplinter_tpu.ops.similarity import (FUSED_K_MAX, NEG_INF,
                                            cosine_scores, cosine_topk,
                                            cosine_topk_batch,
                                            topk_program)

BLOCK = 64          # small tile: several grid steps per tiny lane


def _ref_topk(vectors, queries, mask, k, mxu_bf16=False):
    """(Q, k) reference scores + indices: the unfused path's math with
    lax.top_k's stable smallest-index tie-break."""
    if mxu_bf16:
        import jax.numpy as jnp
        from libsplinter_tpu.ops.similarity import _cosine_scores_pallas
        n, d = vectors.shape
        npad = -(-n // BLOCK) * BLOCK
        dpad = -(-d // 128) * 128
        q = queries.shape[0]
        qpad = max(8, -(-q // 8) * 8)
        v = np.zeros((npad, dpad), np.float32)
        v[:n, :d] = vectors
        qs = np.zeros((qpad, dpad), np.float32)
        qs[:q, :d] = queries
        m = np.zeros((npad, 1), np.float32)
        m[:n, 0] = np.ones(n) if mask is None else mask
        scores = np.asarray(_cosine_scores_pallas(
            jnp.asarray(v), jnp.asarray(qs), jnp.asarray(m),
            block_n=BLOCK, interpret=True, mxu_bf16=True))[:n, :q]
    else:
        scores = np.asarray(cosine_scores(vectors, queries, mask,
                                          use_pallas=False))
    out_s = np.empty((queries.shape[0], k), np.float32)
    out_i = np.empty((queries.shape[0], k), np.int64)
    for c in range(queries.shape[0]):
        order = np.argsort(-scores[:, c], kind="stable")[:k]
        out_s[c] = scores[order, c]
        out_i[c] = order
    return out_s, out_i


def _assert_parity(vectors, queries, mask, k, mxu_bf16=False):
    """Fused results must be rank-identical to the reference wherever
    real candidates exist, and carry the (NEG_INF, -1) filler beyond
    them."""
    got_s, got_i = cosine_topk_batch(
        vectors, queries, min(k, len(vectors)), mask, fused=True,
        interpret=True, use_pallas=True, block_n=BLOCK,
        mxu_bf16=mxu_bf16)
    ref_s, ref_i = _ref_topk(vectors, queries, mask,
                             min(k, len(vectors)), mxu_bf16)
    for c in range(queries.shape[0]):
        valid = ref_s[c] > -1e29
        np.testing.assert_allclose(got_s[c][valid], ref_s[c][valid],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(got_i[c][valid], ref_i[c][valid])
        filler = ~valid
        assert (got_s[c][filler] <= -1e29).all()
        assert (got_i[c][filler] == -1).all()


def _lane(rng, n, d, kind):
    """Candidate value distributions per dtype family.  bf16/int8 data
    is quantized-then-dequantized f32 — dense with exact-tie mass, the
    regime where a sloppy selector's tie-break diverges first."""
    x = rng.normal(size=(n, d)).astype(np.float32)
    if kind == "bf16":
        import jax.numpy as jnp
        return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)
    if kind == "int8":
        scale = np.abs(x).max() / 127.0
        return (np.round(x / scale) * scale).astype(np.float32)
    return x


@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("n", [64, 200, 333])   # 333: N % block != 0
@pytest.mark.parametrize("k", [1, 7, 20])
def test_parity_dtypes_and_shapes(kind, n, k):
    rng = np.random.default_rng(hash((kind, n, k)) % 2**31)
    vectors = _lane(rng, n, 48, kind)
    queries = _lane(rng, 4, 48, kind)
    _assert_parity(vectors, queries, None, k)


@pytest.mark.parametrize("pattern", ["random", "prefix", "all_off",
                                     "zeros_and_mask"])
def test_mask_patterns(pattern):
    rng = np.random.default_rng(5)
    vectors = rng.normal(size=(150, 32)).astype(np.float32)
    queries = rng.normal(size=(3, 32)).astype(np.float32)
    mask = np.ones(150, np.float32)
    if pattern == "random":
        mask = (rng.random(150) > 0.5).astype(np.float32)
    elif pattern == "prefix":
        mask[:97] = 0.0
    elif pattern == "all_off":
        mask[:] = 0.0
    elif pattern == "zeros_and_mask":
        vectors[10:40] = 0.0          # un-embedded slots
        mask[60:80] = 0.0             # bloom-filtered rows
    _assert_parity(vectors, queries, mask, 12)


def test_exact_ties_index_stable():
    """Duplicated / colinear rows score EXACTLY equal; the fused
    selector must return the same (smallest-first) winners as
    lax.top_k."""
    rng = np.random.default_rng(11)
    vectors = (rng.integers(-3, 4, size=(130, 24)).astype(np.float32)
               / 3.0)
    vectors[77] = vectors[5]
    vectors[99] = vectors[5] * 2.5    # colinear: same cosine
    vectors[128] = vectors[5]
    queries = vectors[[5, 40]]
    _assert_parity(vectors, queries, None, 10)


def test_k_exceeds_valid_rows():
    rng = np.random.default_rng(3)
    vectors = np.zeros((96, 16), np.float32)
    vectors[[4, 50, 91]] = rng.normal(size=(3, 16)).astype(np.float32)
    q = rng.normal(size=16).astype(np.float32)
    s, i = cosine_topk(vectors, q, 10, fused=True, interpret=True,
                       use_pallas=True, block_n=32)
    assert (s[3:] <= -1e29).all() and (i[3:] == -1).all()
    assert set(i[:3].tolist()) == {4, 50, 91}


def test_bf16_fused_matches_bf16_reference():
    rng = np.random.default_rng(17)
    vectors = rng.standard_normal((256, 128)).astype(np.float32)
    vectors /= np.linalg.norm(vectors, axis=1, keepdims=True)
    queries = rng.standard_normal((8, 128)).astype(np.float32)
    _assert_parity(vectors, queries, None, 10, mxu_bf16=True)


@pytest.mark.parametrize("n,d,k,near", [
    (100, 40, 6, None),
    # the lane's width (six 128-lane tiles) and the search default
    # k, the query a perturbed lane row: that row must win
    (2048, 768, 10, 1234),
])
def test_single_query_contract(n, d, k, near):
    rng = np.random.default_rng(23)
    vectors = rng.normal(size=(n, d)).astype(np.float32)
    q = rng.normal(size=d).astype(np.float32)
    if near is not None:
        q = vectors[near] + 0.05 * q
    s, i = cosine_topk(vectors, q, k, fused=True, interpret=True,
                       use_pallas=True, block_n=BLOCK)
    ref_s, ref_i = _ref_topk(vectors, q[None, :], None, k)
    np.testing.assert_allclose(s, ref_s[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(i, ref_i[0])
    assert s.shape == (k,) and i.shape == (k,)
    if near is not None:
        assert i[0] == near


def test_program_selection():
    """fused=None auto-selects the streaming kernel up to FUSED_K_MAX
    and falls back to the score-matrix path beyond it."""
    fused = topk_program(8, fused=None, interpret=True,
                         use_pallas=True)
    legacy = topk_program(FUSED_K_MAX + 1, fused=None, interpret=True,
                          use_pallas=True)
    rng = np.random.default_rng(0)
    v = rng.normal(size=(FUSED_K_MAX + 50, 16)).astype(np.float32)
    q = rng.normal(size=(1, 16)).astype(np.float32)
    sf, _, _ = fused(v, q, None, None)
    sl, _, none_run = legacy(v, q, None, None)
    assert np.asarray(none_run).tolist() == [0, 0]   # one shape for both
    assert np.asarray(sf).shape == (1, 8)
    assert np.asarray(sl).shape == (1, FUSED_K_MAX + 1)


def test_fused_output_is_o_of_kq():
    """Acceptance: the fused program's outputs are O(k*Q) shaped —
    nothing N-sized leaves the kernel."""
    import jax
    fn = topk_program(5, fused=True, interpret=True, use_pallas=True)
    rng = np.random.default_rng(1)
    v = rng.normal(size=(512, 32)).astype(np.float32)
    q = rng.normal(size=(3, 32)).astype(np.float32)
    shapes = [np.asarray(x).shape
              for x in jax.tree_util.tree_leaves(fn(v, q, None, None))]
    # scores, indices, and the [passes, tiles] count
    assert shapes == [(3, 5), (3, 5), (2,)]
    # and the jaxpr-level output of the pallas_call itself is k*Q
    # padded, never (N, Q): the kernel's out_shape is (k_pad, q_pad)
    from libsplinter_tpu.ops.similarity import _fused_topk_fn
    closed = jax.make_jaxpr(_fused_topk_fn(5, 128, False, True))(
        v, q, np.ones(512, np.float32), None)

    def _pallas_eqns(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", None)
                if sub is not None:
                    yield from _pallas_eqns(sub)

    eqns = list(_pallas_eqns(closed.jaxpr))
    assert eqns, "fused path must lower through pallas_call"
    for eqn in eqns:
        for var in eqn.outvars:
            # k=5 padded to 8, not N; the pass counter is one word
            assert var.aval.shape in ((8, 8), (1, 1))


# ---------------------------------------------------------------------------
# the selection follows the tile's entrants (PR 46): lanes that drive
# the pass loop to its ends, and the pass count itself
# ---------------------------------------------------------------------------

TILES = 6
K_PAD = 16          # k = 10 rounds up to two sublane groups


def _ramp(n, d, ascending):
    """Rows whose cosine with the first axis falls strictly with the
    row index (or rises): every tile of an ascending lane is full of
    entrants, no tile of a descending one after the first has any."""
    theta = np.linspace(0.1, 1.4, n)
    v = np.zeros((n, d), np.float32)
    v[:, 0], v[:, 1] = np.cos(theta), np.sin(theta)
    return v[::-1].copy() if ascending else v


def _axis_query(d, nq=1):
    q = np.zeros((nq, d), np.float32)
    q[:, 0] = 1.0
    return q


def _selection_case(name):
    """(vectors, queries, mask, k, mxu_bf16) of one named lane."""
    rng = np.random.default_rng(46)
    n, d, k = BLOCK * TILES, 32, 10
    vectors = rng.normal(size=(n, d)).astype(np.float32)
    queries = rng.normal(size=(5, d)).astype(np.float32)
    mask, bf16 = None, False
    if name in ("descending", "ascending"):
        vectors = _ramp(n, d, name == "ascending")
        queries = _axis_query(d, 3)
        queries[1, 1] = 0.5           # the same order, other scores
        queries[2, 0] = -1.0          # and the opposite order
    elif name == "all_equal":
        vectors = np.tile(vectors[:1], (n, 1))
    elif name == "pad_query":
        queries[[1, 3]] = 0.0         # zero norm: every score 0.0
    elif name == "mask_few":
        mask = np.zeros(n, np.float32)
        mask[[3, BLOCK + 1, 2 * BLOCK, n - 1]] = 1.0
    elif name == "short_lane":
        vectors = vectors[:20]
    elif name == "mxu_bf16":
        vectors = rng.standard_normal((n, 128)).astype(np.float32)
        queries = rng.standard_normal((5, 128)).astype(np.float32)
        bf16 = True
    elif name == "near_rows":
        # the cell's own queries: stored rows with a little noise
        queries = (vectors[[7, 200, 383]]
                   + 0.05 * rng.normal(size=(3, d))).astype(np.float32)
    else:
        raise ValueError(name)
    return vectors, queries, mask, k, bf16


@pytest.mark.parametrize("name", [
    "descending", "ascending", "all_equal", "pad_query", "mask_few",
    "short_lane", "mxu_bf16", "near_rows"])
def test_selection_cases(name):
    # the reference's stable argsort is "smallest index wins" for
    # all_equal, and _assert_parity holds mask_few's places past its
    # four candidates to the (NEG_INF, -1) filler
    vectors, queries, mask, k, bf16 = _selection_case(name)
    _assert_parity(vectors, queries, mask, k, mxu_bf16=bf16)


def _select_count(vectors, queries, mask=None, k=10):
    """[passes, tiles] of one fused dispatch."""
    fn = topk_program(k, fused=True, interpret=True, use_pallas=True,
                      block_n=BLOCK)
    return [int(x) for x in np.asarray(fn(vectors, queries, mask,
                                          None)[2])]


@pytest.mark.parametrize("name,passes,tiles", [
    # k_pad in tile 0 (its 16 largest, one a pass) and none after
    ("descending", K_PAD, TILES),
    ("descending_first_tile", K_PAD, 1),
    # every tile's 16 largest beat everything before them
    ("ascending", K_PAD * TILES, TILES),
    # ties with the k_pad-th never enter: tile 0 fills, then nothing
    ("all_equal", K_PAD, TILES),
    # nothing is a candidate: the accumulator is never touched
    ("all_masked", 0, TILES),
    # a tile with fewer candidates than k_pad runs one pass each
    ("short_lane", 5, 1),
])
def test_pass_count(name, passes, tiles):
    d = 32
    mask = None
    queries = _axis_query(d)
    if name.startswith("descending") or name == "ascending":
        vectors = _ramp(BLOCK * TILES, d, name == "ascending")
        if name.endswith("first_tile"):
            vectors = vectors[:BLOCK]
    elif name == "all_equal":
        vectors = np.tile(_ramp(1, d, False), (BLOCK * TILES, 1))
    elif name == "all_masked":
        vectors = _ramp(BLOCK * TILES, d, False)
        mask = np.zeros(len(vectors), np.float32)
    else:
        # 5 rows: the pad queries beside the live one (all scores 0.0)
        # have their 5 entrants in the same 5 passes
        vectors = _ramp(5, d, False)
    assert _select_count(vectors, queries, mask) == [passes, tiles]


@pytest.mark.parametrize("k", [1, 10, 64])
def test_pass_count_bounds(k):
    """A lane in no order: at least the first tile's fill, never more
    than k_pad a tile, and far fewer than that once the accumulator
    holds real scores."""
    rng = np.random.default_rng(k)
    vectors = rng.normal(size=(BLOCK * 16, 32)).astype(np.float32)
    queries = rng.normal(size=(4, 32)).astype(np.float32)
    k_pad = max(8, -(-k // 8) * 8)
    passes, tiles = _select_count(vectors, queries, k=k)
    assert tiles == 16
    assert min(k_pad, BLOCK) <= passes <= k_pad * tiles
    assert passes < k_pad * tiles // 2
