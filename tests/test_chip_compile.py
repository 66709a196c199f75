"""The main path's kernels and programs, compiled at real widths for a
DESCRIBED TPU v5e — no chip attached, nothing runs.

Interpret-mode tests cannot see what the chip's compiler refuses: PR 21
found int8 paged attention out of SMEM from 1,024 pool pages and the
int4 nibble unpack using a cast Mosaic does not lower, both green in
every interpret-mode test.  These compiles guard the serving path's
kernels on every later PR at no chip time (~2 s each; the two whole
encoder steps take longer).

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described only inside the module-scoped `topo` fixture —
never at import, never in a skipif/parametrize argument, not autouse,
not in conftest.py — so every xdist worker collects the same tests and
only the worker that runs this file loads libtpu.  Compiles happen in
the test's own process, with the persistent compilation cache off
(a described-device executable can be written to it but not read
back).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from libsplinter_tpu.engine.searcher import qb_buckets

H, D, PAGE = 12, 64, 128           # the default decoder's attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_cosine_scores_kernel(one_chip):
    from libsplinter_tpu.ops.similarity import _cosine_scores_pallas
    n, d, q = 65_536, 768, 8
    _compile(lambda v, qs, m: _cosine_scores_pallas(
                 v, qs, m, block_n=1024, interpret=False),
             _spec(one_chip, (n, d), jnp.float32),
             _spec(one_chip, (q, d), jnp.float32),
             _spec(one_chip, (n, 1), jnp.float32))


@pytest.mark.parametrize("k", [10, 64])
@pytest.mark.parametrize("nq", qb_buckets())
def test_fused_topk_kernel(one_chip, k, nq):
    """The search daemon's program over the smoke's 262,144 x 768
    lane, at each width of its batch schedule: the middle one is the
    program a coalesced drain of 9-128 requests runs."""
    from libsplinter_tpu.ops.similarity import _fused_topk_fn
    n, d = 262_144, 768
    txt = _compile(
        lambda v, q, m: _fused_topk_fn(k, 1024, False, False)(
            v, q, m, None),
        _spec(one_chip, (n, d), jnp.float32),
        _spec(one_chip, (nq, d), jnp.float32),
        _spec(one_chip, (n,), jnp.float32)).as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("b,s,dtype", [(64, 512, jnp.bfloat16),
                                       (8, 2048, jnp.float32)])
def test_flash_attention_kernel(one_chip, b, s, dtype):
    from libsplinter_tpu.ops.flash_attention import flash_attention
    qkv = _spec(one_chip, (b, s, H, D), dtype)
    _compile(lambda q, k, v, m: flash_attention(
                 q, k, v, m, force_pallas=True),
             qkv, qkv, qkv, _spec(one_chip, (b, s), jnp.bool_))


def test_causal_flash_attention_kernel(one_chip):
    from libsplinter_tpu.ops.flash_attention import \
        causal_flash_attention
    b, s, t = 1, 512, 2048
    kv = _spec(one_chip, (b, t, H, D), jnp.bfloat16)
    _compile(lambda q, k, v, pos: causal_flash_attention(
                 q, k, v, pos, force_pallas=True),
             _spec(one_chip, (b, s, H, D), jnp.bfloat16), kv, kv,
             _spec(one_chip, (), jnp.int32))


def _paged_specs(sh, kind, n_blocks, q_tokens, b=64, pages_per_row=16):
    qshape = (b, q_tokens, H, D) if q_tokens > 1 else (b, H, D)
    pool_dt, dk = {"bf16": (jnp.bfloat16, D), "int8": (jnp.int8, D),
                   "int4": (jnp.uint8, D // 2)}[kind]
    pool = _spec(sh, (n_blocks, H, PAGE, dk), pool_dt)
    specs = [_spec(sh, qshape, jnp.bfloat16), pool, pool,
             _spec(sh, (b, pages_per_row), jnp.int32),
             _spec(sh, (b,), jnp.int32)]
    if kind != "bf16":
        scale = _spec(sh, (n_blocks, H), jnp.float32)
        specs += [scale, scale]
    return specs


def _paged(q, kp, vp, tabs, lens, ks=None, vs=None, *, mesh=None):
    from libsplinter_tpu.ops.paged_attention import paged_attention
    return paged_attention(q, kp, vp, tabs, lens, k_scales=ks,
                           v_scales=vs, force_pallas=True, mesh=mesh)


@pytest.mark.parametrize("q_tokens", [1, 5])
def test_paged_attention_bf16(one_chip, q_tokens):
    """Single-query decode and the speculative verifier's multi-query
    stack: batch 64 x 2,048 ctx / page 128."""
    _compile(_paged, *_paged_specs(one_chip, "bf16", 1025, q_tokens))


@pytest.mark.parametrize("n_blocks", [1025, 4096])
@pytest.mark.parametrize("kind", ["int8", "int4"])
def test_paged_attention_quantized_pools(one_chip, kind, n_blocks):
    """PR 21's two refusals: the scale tables must not ride SMEM whole
    (refused from 1,024 pages), and the int4 unpack must lower."""
    _compile(_paged, *_paged_specs(one_chip, kind, n_blocks, 1))


@pytest.mark.parametrize("page", [16, 64, 256])
def test_paged_attention_page_sizes(one_chip, page):
    """The page is a whole dimension of the kv block, so the compiler
    takes sizes that are not multiples of the 128-lane tile."""
    specs = _paged_specs(one_chip, "int8", 1025, 1)
    pool = _spec(one_chip, (1025, H, page, D), jnp.int8)
    specs[1] = specs[2] = pool
    _compile(_paged, *specs)


@pytest.mark.parametrize("b,s", [(4096, 64), (64, 2048)])
def test_flagship_encoder_step(one_chip, monkeypatch, b, s):
    """EncoderConfig() as it stands — the naive-attention bucket the
    embedder batches widest, and the longest bucket (flash path)."""
    from libsplinter_tpu.models import Encoder, EncoderConfig
    # the encoder picks the flash kernel from the backend it sees,
    # and this process sees the CPU: steer it here, in the test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    module = Encoder(EncoderConfig())
    ids = _spec(one_chip, (b, s), jnp.int32)
    mask = _spec(one_chip, (b, s), jnp.bool_)
    params = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        jax.eval_shape(module.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32),
                       jnp.ones((1, 16), jnp.bool_)))
    compiled = _compile(module.apply, params, ids, mask)
    assert ("tpu_custom_call" in compiled.as_text()) == (s >= 512)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 12 * 2**30      # fits a 16 GB chip


def test_sharded_topk_four_devices(topo):
    """The row-sharded lane with the all-gather merge, on a 4-device
    mesh of the described chips: the kernel is there and so is the
    collective.  The program asks for an all-gather of 4 x k
    candidates; the v5e compiler turns one that small into a
    dynamic-update-slice + all-reduce over the same four devices, so
    the compiled text is held to "a collective over all four"."""
    from libsplinter_tpu.parallel.sharded_search import _topk_program
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("dp",))
    n, d, k = 262_144, 768, 10
    lowered = _topk_program(mesh, "dp", n // 4, d, 1, k, k, True).lower(
        _spec(NamedSharding(mesh, P("dp", None)), (n, d), jnp.float32),
        _spec(NamedSharding(mesh, P()), (1, d), jnp.float32),
        _spec(NamedSharding(mesh, P("dp")), (n,), jnp.float32))
    assert "all_gather" in lowered.as_text()
    txt = lowered.compile().as_text()
    assert "tpu_custom_call" in txt
    assert ("all-gather" in txt or "all-reduce" in txt) \
        and "replica_groups={{0,1,2,3}}" in txt


@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_paged_attention_tp4(topo, kind):
    """`--tp 4` serving: the ragged kernel under shard_map on a tp
    mesh of the four described chips, pools and scales split on their
    kv-head axis (3 of the 12 heads per device)."""
    from libsplinter_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(tp=4, devices=list(topo.devices))
    rep = NamedSharding(mesh, P())
    specs = _paged_specs(rep, kind, 1025, 1)
    heads = NamedSharding(mesh, P(None, "tp", None))
    pools = NamedSharding(mesh, P(None, "tp", None, None))
    specs[0] = _spec(heads, specs[0].shape, specs[0].dtype)
    for i in (1, 2):
        specs[i] = _spec(pools, specs[i].shape, specs[i].dtype)
    for i in range(5, len(specs)):
        specs[i] = _spec(NamedSharding(mesh, P(None, "tp")),
                         specs[i].shape, specs[i].dtype)
    compiled = _compile(functools.partial(_paged, mesh=mesh), *specs)
    assert "tpu_custom_call" in compiled.as_text()


# ---- the latent-attention (MLA) + expert block at published widths ----
# (benchmark/configs/openpangu-ultra-moe-718b-ep16.json: 128 heads over
# a 512 + 64 latent, pages of 128 tokens, 2,560 of them, a 66-page
# window, 16 held experts of 7,680 x 2,048)

LAT_W, LAT_RANK, LAT_HEADS, LAT_POOL = 576, 512, 128, (2561, 576, 128)


@pytest.mark.parametrize("q_tokens, rows, heads, table",
                         [(1, 64, 128, 66), (16, 1, 128, 66),
                          (64, 1, 128, 66), (64, 8, 128, 66),
                          (64, 64, 128, 66), (1, 64, 32, 128)],
                         ids=["decode-64-rows", "suffix-16", "suffix-64",
                              "suffix-64-of-8-rows",
                              "suffix-64-of-64-rows",
                              "decode-64-rows-32-heads-128-pages"])
def test_latent_attention_kernel(one_chip, q_tokens, rows, heads, table):
    """The latent decode kernel (S == 1, all 128 heads a program,
    DECODE_PAGES table pages a grid step: pangu's 66-page table, which
    is no whole number of chunks, and kimi's 32 heads over 128 pages)
    and the suffix stacks (S x 64 / 16 heads, one page a step), over
    transposed pages: the 576-wide contraction and the 512-wide output
    are no multiples of what interpret mode checks."""
    from libsplinter_tpu.ops.latent_attention import (_latent_pallas,
                                                      head_group)
    g = head_group(heads, q_tokens)
    # the rows of a round bring their own lengths (q_valid): the kernel
    # that skips the pad tokens' blocks
    ragged = rows > 1 and q_tokens > 1
    compiled = _compile(
        lambda q4, pool, t, l, *nv: _latent_pallas(
            q4, pool, t, l, *nv, kv_rank=LAT_RANK, scale=192 ** -0.5,
            group=g, interpret=False),
        _spec(one_chip, (rows, heads // g, q_tokens * g, LAT_W),
              jnp.bfloat16),
        _spec(one_chip, LAT_POOL, jnp.bfloat16),
        _spec(one_chip, (rows, table), jnp.int32),
        *[_spec(one_chip, (rows,), jnp.int32)] * (2 if ragged else 1))
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    # the pool reaches the kernel in the layout it is kept in: no copy
    assert not [ln for ln in txt.split("ENTRY")[1].splitlines()
                if "2561,576,128" in ln and " copy(" in ln]


def test_latent_append_kernel(one_chip):
    """A decode step's 64 new latent columns written in place."""
    from libsplinter_tpu.ops.latent_attention import _append_pallas
    compiled = _compile(
        lambda pool, new, b, o: _append_pallas(pool, new, b, o,
                                               interpret=False),
        _spec(one_chip, LAT_POOL, jnp.bfloat16),
        _spec(one_chip, (64, LAT_W, 1), jnp.bfloat16),
        _spec(one_chip, (64,), jnp.int32),
        _spec(one_chip, (64,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [512, 16384],
                         ids=["decode-64x8", "prefill-chunk-2048x8"])
def test_expert_grouped_matmul(one_chip, monkeypatch, rows):
    """The grouped product over the 16 held experts, at the row counts
    a decode step and a prefill chunk bring (worst case: every token's
    8 slots held here)."""
    from libsplinter_tpu.models.moe import grouped_matmul
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k, n in ((7680, 2048), (2048, 7680)):
        compiled = _compile(
            grouped_matmul,
            _spec(one_chip, (rows, k), jnp.bfloat16),
            _spec(one_chip, (16, k, n), jnp.bfloat16),
            _spec(one_chip, (16,), jnp.int32))
        assert "tpu_custom_call" in compiled.as_text()


def _pangu_model(one_chip):
    """The benchmark's configuration (5 layers, 9.85 GB of weights) as
    shapes on the described chip: (model, params)."""
    from libsplinter_tpu.models import mla
    cfg = mla.LatentMoeConfig(
        vocab_size=19200, hidden=7680, layers=5, heads=LAT_HEADS,
        q_lora_rank=1536, kv_lora_rank=LAT_RANK, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, dense_layers=1,
        dense_mlp_dim=18432, moe_mlp_dim=2048, n_routed_experts=256,
        top_k=8, experts_first=48, experts_held=16,
        routed_scaling_factor=2.5, rope_base=25.6e6, max_len=8448)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: mla.init_params(cfg, 0)))
    return mla.LatentCompletionModel(cfg, params=params), params


def _latent_case(one_chip, program):
    """(program, its arguments as shapes on the described chip) of the
    benchmark's latent configuration: "rows-64" (the full rung of the
    row-batched suffix prefill, 64 rows x 64 tokens) or "chunk"."""
    m, params = _pangu_model(one_chip)
    pools = [_spec(one_chip, LAT_POOL, jnp.bfloat16)] * 5

    def i32(*shape):
        return _spec(one_chip, shape, jnp.int32)
    key = _spec(one_chip, (2,), jnp.uint32)
    if program == "chunk":
        fn = m._chunk_program(8, 64)
        args = (i32(64, 66), i32(64), key, i32(64),
                _spec(one_chip, (64,), jnp.bool_), i32(64), i32())
    else:
        fn = m._suffix_rows_program(64, 64)
        args = (i32(64, 66), i32(64), i32(64, 64), i32(64), key)
    return getattr(fn, "__wrapped__", fn), (params, pools, *args), None


def test_latent_suffix_rows_program(one_chip, monkeypatch):
    """The full rung of the row-batched suffix prefill (64 rows x 64
    tokens: an admission round of the benchmark's cell in ONE program):
    it compiles, its 4,096 tokens' temporaries fit the chip beside the
    weights and the pages, and no pool is copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, _ = _latent_case(one_chip, "rows-64")
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print("suffix rows 64x64: arguments",
          mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 11.5e9          # weights + pool
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert not [ln for ln in compiled.as_text().split("ENTRY")[1]
                .splitlines() if "2561,576,128" in ln and " copy(" in ln]


def test_latent_decode_chunk_program(one_chip, monkeypatch):
    """The whole 8-step decode chunk of the benchmark's configuration
    (5 layers, 64 rows, 9.85 GB of weights + 1.89 GB of pages): it
    compiles, fits the chip beside its arguments, and keeps the pools
    in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, _ = _latent_case(one_chip, "chunk")
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 11.5e9          # weights + pool
    assert mem.temp_size_in_bytes < 1.5e9               # no pool copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


# ---- the hybrid stack (KDA state slots + NoPE latent pages) at
# published widths (benchmark/configs/kimi-linear-48b-a3b-ep8-stage0.json:
# 32 KDA heads of 128, 64 rows + 96 snapshots + the spare = 161 state
# slots, 8,192 latent pages of 128 tokens, a 128-page window, 32 held
# experts of 2,304 x 1,024, layers 1-13)

KDA_H, KDA_D, KDA_SLOTS = 32, 128, 161


def test_kda_decode_step_kernel(one_chip):
    """One token a row over the rows' state slots: the 64 KiB state of
    a head goes through VMEM once, in place (no copy of the 338 MB
    slot array), the b v column is made by an in-kernel transpose."""
    from libsplinter_tpu.ops.delta_attention import _decode_pallas
    row = _spec(one_chip, (64, KDA_H, KDA_D), jnp.float32)
    compiled = jax.jit(
        lambda q, k, bk, a, bv, s: _decode_pallas(q, k, bk, a, bv, s,
                                                  interpret=False),
        donate_argnums=(5,)).lower(
        row, row, row, row, row,
        _spec(one_chip, (KDA_SLOTS, KDA_H, KDA_D, KDA_D),
              jnp.float32)).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1e6                 # in place


@pytest.mark.parametrize("tokens", [128, 640])
def test_kda_chunk_prefill_kernel(one_chip, tokens):
    """The chunkwise delta rule over a suffix bucket: the pairwise
    decays of a chunk (tokens x 32 x 128 a head) stay inside their
    reduction — nothing of that size reaches HBM."""
    from libsplinter_tpu.ops.delta_attention import kda_chunk_prefill
    tok = _spec(one_chip, (tokens, KDA_H, KDA_D), jnp.float32)
    compiled = _compile(
        lambda q, k, v, g, b, s, n: kda_chunk_prefill(
            q, k, v, g, b, s, scale=KDA_D ** -0.5, n_snap=n,
            force_pallas=True),
        tok, tok, tok, tok, _spec(one_chip, (tokens, KDA_H), jnp.float32),
        _spec(one_chip, (KDA_H, KDA_D, KDA_D), jnp.float32),
        _spec(one_chip, (), jnp.int32))
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    big = f"= f32[{tokens // 32},{KDA_H},32,32,{KDA_D}]"
    assert not [ln for ln in txt.splitlines()
                if big in ln and " fusion(" in ln]


def _hybrid_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's hybrid configuration."""
    from libsplinter_tpu.models import kda
    kinds = tuple("mla" if (i + 1) % 4 == 0 else "kda" for i in range(13))
    cfg = kda.HybridMoeConfig(
        vocab_size=20480, hidden=2304, kinds=kinds, heads=32,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, kda_heads=KDA_H, kda_head_dim=KDA_D,
        conv_kernel=4, dense_layers=1, dense_mlp_dim=9216,
        moe_mlp_dim=1024, n_routed_experts=256, top_k=8,
        experts_first=0, experts_held=32, routed_scaling_factor=2.446,
        max_len=16384)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: kda.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = kda.HybridCompletionModel(cfg, params=params)
    pools = [_spec(one_chip, (8193, 576, 128), jnp.bfloat16)] * 3
    states = [[_spec(one_chip, (KDA_SLOTS, KDA_H, KDA_D, KDA_D),
                     jnp.float32),
               _spec(one_chip, (KDA_SLOTS, 3, 3 * KDA_H * KDA_D),
                     jnp.bfloat16)] for _ in range(10)]
    i32 = _spec(one_chip, (), jnp.int32)
    if program == "chunk":
        fn = m._chunk_program(8, 64)
        args = (_spec(one_chip, (64, 128), jnp.int32),
                _spec(one_chip, (64,), jnp.int32),
                _spec(one_chip, (2,), jnp.uint32),
                _spec(one_chip, (64,), jnp.int32),
                _spec(one_chip, (64,), jnp.bool_),
                _spec(one_chip, (64,), jnp.int32), i32)
    else:
        fn = m._suffix_program(640)
        args = (_spec(one_chip, (1, 128), jnp.int32),
                _spec(one_chip, (1,), jnp.int32),
                _spec(one_chip, (1, 640), jnp.int32), i32, i32, i32, i32)
    return getattr(fn, "__wrapped__", fn), (params, pools, states,
                                            *args), weights


@pytest.mark.parametrize("program", ["chunk", "suffix-640"])
def test_hybrid_programs_at_published_widths(one_chip, monkeypatch,
                                             program):
    """The 8-step decode chunk and the widest suffix prefill of the
    benchmark's hybrid configuration (13 layers unrolled, 6.92 GB of
    weights + 3.62 GB of pages + 3.49 GB of state slots): each
    compiles, fits the chip beside its arguments, and keeps pools and
    state slots in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _hybrid_case(one_chip, program)
    assert 6.90e9 < weights < 6.93e9      # 3,450M parameters, bfloat16
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 14.0e9   # weights, pages, slots
    assert mem.temp_size_in_bytes < 0.6e9        # no pool or slot copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15e9


# ---- the mixed sliding-window / global stack at published widths
# (benchmark/configs/trinity-mini-26b-a3b-ep8.json: 32 heads over 4
# key/value heads of 128, sliding_window 2,048, pages of 128 tokens in
# two groups — 1,536 global pages of 8 layers, 448 window pages of 24 —
# a 192-page table, 16 held experts of 2,048 x 1,024, all 32 layers)

GQA_H, GQA_KH, GQA_D, GQA_W, GQA_P = 32, 4, 128, 2048, 192
GQA_POOLS = {"full": (1537, 8, GQA_KH, PAGE, GQA_D),
             "window": (449, 24, GQA_KH, PAGE, GQA_D)}


@pytest.mark.parametrize("q_tokens, rows, kind", [
    (1, 32, "window"), (1, 32, "full"), (640, 1, "window"),
    (640, 1, "full"), (128, 1, "full")],
    ids=["decode-window", "decode-full", "stack-640-window",
         "stack-640-full", "stack-128-full"])
def test_window_attention_kernel(one_chip, q_tokens, rows, kind):
    """The grouped-query kernel over a page group's pool: a decode step
    (all 4 kv heads a program) and the suffix stacks (128 tokens x 8
    heads a program), walking a window layer's 17-18 pages or a global
    layer's table — the pool reaches it in the layout it is kept in."""
    from libsplinter_tpu.ops.paged_attention import (
        _window_pallas, stack_block, window_walk_pages)
    tq = stack_block(q_tokens, GQA_H // GQA_KH)
    n_walk = (window_walk_pages(GQA_W, PAGE, tq) if kind == "window"
              else GQA_P)
    assert (tq, n_walk) == ((1, 17) if q_tokens == 1 else (128, 18)) \
        or kind == "full"
    pool = _spec(one_chip, GQA_POOLS[kind], jnp.bfloat16)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    compiled = _compile(
        lambda q4, kp, vp, t, l, s, lay: _window_pallas(
            q4, kp, vp, t, l, s, lay, n_walk=n_walk, block_tokens=tq,
            q_tokens=q_tokens, interpret=False),
        _spec(one_chip, (rows, GQA_KH, q_tokens * GQA_H // GQA_KH, GQA_D),
              jnp.bfloat16),
        pool, pool, i32(shape=(rows, GQA_P)), i32(shape=(rows,)),
        i32(shape=(rows,)), i32(shape=(1,)))
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    shape = ",".join(str(d) for d in GQA_POOLS[kind])
    assert not [ln for ln in txt.split("ENTRY")[1].splitlines()
                if shape in ln and " copy(" in ln]


def _window_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's window / global configuration."""
    from libsplinter_tpu.models import afmoe
    cfg = afmoe.WindowMoeConfig(
        vocab_size=25024, hidden=2048,
        kinds=("window", "window", "window", "full") * 8, heads=GQA_H,
        kv_heads=GQA_KH, head_dim=GQA_D, window=GQA_W, dense_layers=2,
        dense_mlp_dim=6144, moe_mlp_dim=1024, n_routed_experts=128,
        top_k=8, experts_first=0, experts_held=16,
        routed_scaling_factor=2.826, max_len=24576)
    assert cfg.plan == (4, 4, 7)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: afmoe.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = afmoe.WindowCompletionModel(cfg, params=params)
    pools = {k: (_spec(one_chip, s, jnp.bfloat16),) * 2
             for k, s in GQA_POOLS.items()}
    i32 = _spec(one_chip, (), jnp.int32)
    if program == "chunk":
        fn = m._chunk_program(8, 32)
        tables = {k: _spec(one_chip, (32, GQA_P), jnp.int32)
                  for k in GQA_POOLS}
        args = (_spec(one_chip, (32,), jnp.int32),
                _spec(one_chip, (2,), jnp.uint32),
                _spec(one_chip, (32,), jnp.int32),
                _spec(one_chip, (32,), jnp.bool_),
                _spec(one_chip, (32,), jnp.int32),
                _spec(one_chip, (2,), jnp.int32))
    elif program.startswith("rows-"):
        fn, tables, args = _window_rows(one_chip, m, int(program[5:]),
                                        GQA_P, GQA_POOLS)
    else:
        fn = m._suffix_program(640)
        tables = {k: _spec(one_chip, (1, GQA_P), jnp.int32)
                  for k in GQA_POOLS}
        args = (_spec(one_chip, (1,), jnp.int32),
                _spec(one_chip, (1, 640), jnp.int32), i32)
    return getattr(fn, "__wrapped__", fn), (params, pools, tables,
                                            *args), weights


def _window_rows(one_chip, m, rows, pages, groups):
    """(program, tables, the other arguments) of the window / global
    family's row-batched suffix prefill: `rows` rows, one page wide."""
    def vec(n, dtype=jnp.int32):
        return _spec(one_chip, (n,), dtype)
    return (m._suffix_rows_program(rows, PAGE),
            {k: _spec(one_chip, (rows, pages), jnp.int32) for k in groups},
            (vec(rows), _spec(one_chip, (rows, PAGE), jnp.int32),
             vec(rows), vec(2, jnp.uint32)))


@pytest.mark.parametrize("program", ["chunk", "suffix-640"])
def test_window_programs_at_published_widths(one_chip, monkeypatch,
                                             program):
    """The 8-step decode chunk and the widest suffix prefill of the
    benchmark's window / global configuration (32 layers as a 4-layer
    head + 7 scanned periods; 8.53 GB of weights + 3.22 GB of global
    pages + 2.82 GB of window pages): each compiles, fits the chip
    beside its arguments, and keeps both groups' pools in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _window_case(one_chip, program)
    assert 8.54e9 < weights < 8.56e9      # 4,267M parameters: 8,550,653,952 B
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 14.5e9   # weights, both groups
    assert mem.temp_size_in_bytes < 0.6e9        # no pool copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9


# ---- the window / global stack in MiMo-V2-Flash's setting at published
# widths (benchmark/configs/mimo-v2-flash-309b-ep16.json: 64 heads over
# 8 window / 4 global key/value heads, keys of 192 beside values of 128,
# a sink a head in the window layers, sliding_window 128 = one page;
# 4,608 global pages of 2 layers, 256 window pages of 5, a 258-page
# table, 16 held experts of 4,096 x 2,048, layers 0-6)

SWA_H, SWA_DK, SWA_DV, SWA_W, SWA_P = 64, 192, 128, 128, 258
SWA_KH = {"full": 4, "window": 8}
SWA_POOLS = {"full": (4609, 2, 4, PAGE), "window": (257, 5, 8, PAGE)}


def _swa_pools(one_chip):
    """Keys a token a column (192 is no whole number of lane tiles),
    values a token a row."""
    return {k: (_spec(one_chip, (*s[:3], SWA_DK, PAGE), jnp.bfloat16),
                _spec(one_chip, (*s, SWA_DV), jnp.bfloat16))
            for k, s in SWA_POOLS.items()}


@pytest.mark.parametrize("q_tokens, rows, kind", [
    (1, 48, "window"), (1, 48, "full"), (128, 1, "window"),
    (128, 1, "full"), (640, 1, "window"), (640, 1, "full")],
    ids=["decode-window-sink", "decode-full", "stack-128-window-sink",
         "stack-128-full", "stack-640-window-sink", "stack-640-full"])
def test_sink_window_attention_kernel(one_chip, q_tokens, rows, kind):
    """The same kernel with keys of 192 (not a multiple of the 128-lane
    tile) beside values of 128, groups of 8 and of 16 query heads, and
    a window layer's sink: a decode step walks a window layer's 2
    pages or a global layer's table; neither pool is copied (the keys
    a token a ROW were: the compiler keeps a 192-wide row-major pool
    the other way round and copied it for every call)."""
    from libsplinter_tpu.ops.paged_attention import (
        _window_pallas, stack_block, window_walk_pages)
    kh = SWA_KH[kind]
    rep = SWA_H // kh
    tq = stack_block(q_tokens, rep)
    n_walk = (window_walk_pages(SWA_W, PAGE, tq) if kind == "window"
              else SWA_P)
    if kind == "window":
        assert (tq, n_walk) == ((1, 2) if q_tokens == 1 else (128, 3))
    else:
        assert tq == (1 if q_tokens == 1 else 64)
    kp, vp = _swa_pools(one_chip)[kind]
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    sink = kind == "window"
    compiled = _compile(
        lambda q4, kp, vp, t, l, s, lay, *sk: _window_pallas(
            q4, kp, vp, t, l, s, lay, n_walk=n_walk, block_tokens=tq,
            q_tokens=q_tokens, interpret=False, k_cols=True,
            sinks=sk[0] if sk else None),
        _spec(one_chip, (rows, kh, q_tokens * rep, SWA_DK), jnp.bfloat16),
        kp, vp, i32(shape=(rows, SWA_P)), i32(shape=(rows,)),
        i32(shape=(rows,)), i32(shape=(1,)),
        *([_spec(one_chip, (kh, rep), jnp.float32)] if sink else []))
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    for pool in (kp, vp):
        shape = ",".join(str(d) for d in pool.shape)
        assert not [ln for ln in txt.split("ENTRY")[1].splitlines()
                    if shape in ln and " copy(" in ln]


def _sink_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's MiMo configuration: "chunk",
    "suffix-<width>" (one row) or "rows-<rung>" (an admission round's
    rows, one page wide)."""
    from libsplinter_tpu.models import afmoe
    kinds = tuple("window" if p else "full" for p in (0, 1, 1, 1, 1, 0, 1))
    cfg = afmoe.WindowMoeConfig(
        vocab_size=19072, hidden=4096, kinds=kinds, heads=SWA_H,
        kv_heads=4, head_dim=SWA_DK, window=SWA_W, dense_layers=1,
        dense_mlp_dim=16384, moe_mlp_dim=2048, n_routed_experts=256,
        top_k=8, experts_first=0, experts_held=16, n_shared_experts=0,
        max_len=33024, model_layers=48, mup=False, out_gate=False,
        qk_norm=False, sandwich_norm=False, value_scale=0.707,
        attn_kinds=(
            ("window", afmoe.AttnKind(8, SWA_DK, SWA_DV, 64, 1e4, SWA_W,
                                      True, True)),
            ("full", afmoe.AttnKind(4, SWA_DK, SWA_DV, 64, 5e6, 0,
                                    False, True))))
    assert cfg.plan == (1, 1, 4)          # window x 4 under one scanned body
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: afmoe.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = afmoe.WindowCompletionModel(cfg, params=params)
    pools = _swa_pools(one_chip)
    i32 = _spec(one_chip, (), jnp.int32)
    if program == "chunk":
        fn = m._chunk_program(8, 48)
        tables = {k: _spec(one_chip, (48, SWA_P), jnp.int32)
                  for k in SWA_POOLS}
        args = (_spec(one_chip, (48,), jnp.int32),
                _spec(one_chip, (2,), jnp.uint32),
                _spec(one_chip, (48,), jnp.int32),
                _spec(one_chip, (48,), jnp.bool_),
                _spec(one_chip, (48,), jnp.int32),
                _spec(one_chip, (2,), jnp.int32))
    elif program.startswith("rows-"):
        fn, tables, args = _window_rows(one_chip, m, int(program[5:]),
                                        SWA_P, SWA_POOLS)
    else:
        width = int(program.split("-")[1])
        fn = m._suffix_program(width)
        tables = {k: _spec(one_chip, (1, SWA_P), jnp.int32)
                  for k in SWA_POOLS}
        args = (_spec(one_chip, (1,), jnp.int32),
                _spec(one_chip, (1, width), jnp.int32), i32)
    return getattr(fn, "__wrapped__", fn), (params, pools, tables,
                                            *args), weights


@pytest.mark.parametrize("program", ["chunk", "suffix-128", "suffix-640"])
def test_sink_window_programs_at_published_widths(one_chip, monkeypatch,
                                                  program):
    """The 8-step decode chunk of 48 rows, the one-page suffix prefill
    (a question over its document) and the widest (a cold document's
    pieces) of the benchmark's MiMo configuration (7 layers: the dense
    one, four window layers as ONE scanned body, a tail of two;
    6.87 GB of weights; the pools as the compiler lays them out): each
    compiles, fits the chip beside its arguments, and keeps both
    groups' pools in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _sink_case(one_chip, program)
    assert 6.86e9 < weights < 6.88e9        # 3,430M parameters
    mem = fn.lower(*args).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 10.5e9   # weights, both groups
    assert mem.temp_size_in_bytes < 1.0e9        # no pool copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# ---- the convolution / attention stack at published widths
# (benchmark/configs/lfm2-24b-a2b-ep1-stage0.json: 7 gated short-
# convolution layers whose (2, 2048) register lives in 257 state slots
# — 96 rows, 160 snapshots, the spare — beside 2 attention layers of 32
# heads over 8 key/value heads of 64 in ONE group of 4,096 pages, K and
# V a token a column; ALL 64 experts of 2,048 x 1,536 in each of 8
# expert layers; the whole 65,536-row vocabulary; layers 1-9)

CONV_H, CONV_KH, CONV_D, CONV_P, CONV_SLOTS = 32, 8, 64, 130, 257
CONV_POOL = (4097, 2, CONV_KH, CONV_D, PAGE)


@pytest.mark.parametrize("q_tokens, rows", [(1, 96), (128, 1), (512, 1)],
                         ids=["decode", "stack-128", "stack-512"])
def test_narrow_head_attention_kernel(one_chip, q_tokens, rows):
    """Heads of 64 — half a lane tile: keys AND values a token a
    column, and neither pool is copied.  (Values a token a ROW were:
    the compiler pads a (page, 64) block's rows to 128 lanes and
    copied the 1 GB pool for every call.)"""
    from libsplinter_tpu.ops.paged_attention import (_window_pallas,
                                                     stack_block)
    rep = CONV_H // CONV_KH
    tq = stack_block(q_tokens, rep)
    pool = _spec(one_chip, CONV_POOL, jnp.bfloat16)
    i32 = functools.partial(_spec, one_chip, dtype=jnp.int32)
    compiled = _compile(
        lambda q4, kp, vp, t, l, s, lay: _window_pallas(
            q4, kp, vp, t, l, s, lay, n_walk=CONV_P, block_tokens=tq,
            q_tokens=q_tokens, interpret=False, k_cols=True, v_cols=True),
        _spec(one_chip, (rows, CONV_KH, q_tokens * rep, CONV_D),
              jnp.bfloat16),
        pool, pool, i32(shape=(rows, CONV_P)), i32(shape=(rows,)),
        i32(shape=(rows,)), i32(shape=(1,)))
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    shape = ",".join(str(d) for d in CONV_POOL)
    assert not [ln for ln in txt.split("ENTRY")[1].splitlines()
                if shape in ln and " copy(" in ln]
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


def _conv_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's LFM2 configuration: "chunk",
    "suffix-<width>" (one row) or "rows-<rung>" (an admission round's
    rows at the widest suffix width)."""
    from libsplinter_tpu.models import lfm2
    cfg = lfm2.ConvMoeConfig(
        vocab_size=65536, hidden=2048,
        kinds=("conv",) + ("full", "conv", "conv", "conv") * 2,
        heads=CONV_H, kv_heads=CONV_KH, head_dim=CONV_D, conv_kernel=3,
        dense_layers=1, dense_mlp_dim=11776, moe_mlp_dim=1536,
        n_routed_experts=64, top_k=4, max_len=16512, model_layers=40)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: lfm2.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = lfm2.ConvCompletionModel(cfg, params=params)
    pools = (_spec(one_chip, CONV_POOL, jnp.bfloat16),) * 2
    states = [[_spec(one_chip, (CONV_SLOTS, 2, 2048), jnp.bfloat16)]
              for _ in range(7)]
    i32 = _spec(one_chip, (), jnp.int32)

    def vec(n, dtype=jnp.int32):
        return _spec(one_chip, (n,), dtype)
    kind, _, n = program.partition("-")
    if kind == "chunk":
        fn = m._chunk_program(8, 96)
        args = (_spec(one_chip, (96, CONV_P), jnp.int32), vec(96),
                vec(2, jnp.uint32), vec(96), vec(96, jnp.bool_), vec(96),
                vec(2))
    elif kind == "suffix":
        fn = m._suffix_program(int(n))
        args = (_spec(one_chip, (1, CONV_P), jnp.int32), vec(1),
                _spec(one_chip, (1, int(n)), jnp.int32), i32, i32, i32, i32)
    else:
        r = int(n)
        fn = m._suffix_rows_program(r, 512)
        args = (_spec(one_chip, (r, CONV_P), jnp.int32), vec(r),
                _spec(one_chip, (r, 512), jnp.int32), vec(r), vec(r),
                vec(r), vec(r), vec(2, jnp.uint32))
    return getattr(fn, "__wrapped__", fn), (params, pools, states,
                                            *args), weights


def _no_pool_copied(compiled, pool_shape) -> bool:
    shape = ",".join(str(d) for d in pool_shape)
    return not [ln for ln in compiled.as_text().split("ENTRY")[1]
                .splitlines() if shape in ln and " copy(" in ln]


@pytest.mark.parametrize("program", ["chunk", "suffix-128", "suffix-512"])
def test_conv_programs_at_published_widths(one_chip, monkeypatch, program):
    """The 8-step decode chunk of 96 rows, the one-page suffix prefill
    (a short tool result behind its snapshot) and the widest (a cold
    system prompt's pieces) of the benchmark's LFM2 configuration (9
    layers unrolled; 10.62 GB of weights, 9.66 GB of them the 8 expert
    layers' 64 experts): each compiles, fits the chip beside its
    arguments, and keeps the page group's pools and the state slots in
    place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _conv_case(one_chip, program)
    assert 10.62e9 < weights < 10.64e9    # 5,312M parameters
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.7e9   # weights, pages, slots
    assert mem.temp_size_in_bytes < 1.0e9        # no pool copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    assert _no_pool_copied(compiled, CONV_POOL)


# the rung was chosen by these figures (models/lfm2.py JOIN_ROWS):
# temporaries of 1.06 GB at 32 rows x 512 tokens with the experts taking
# the round's live tokens 8,192 at a time (0.85 GB in chunks of 2,048
# token slots, which read the experts' weights eight times a program) —
# the program stands at 13.85 GB of arguments + temporaries, 82% of the
# chip's 16.9 GB; ~30 MB a row, so 96 rows would pass 90%
def test_conv_suffix_rows_program(one_chip, monkeypatch):
    """The row-batched suffix prefill (an admission round's hits x 512
    tokens in ONE program, snapshots and first tokens included): it
    compiles, its temporaries stay under the figure the rung was chosen
    by, arguments + temporaries under 90% of the chip, and neither the
    pools nor the state slots are copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, _ = _conv_case(one_chip, "rows-32")
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print("suffix rows 32 x 512: arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 12.7e9   # weights, pages, slots
    assert mem.temp_size_in_bytes < 1.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.9 * 16.9e9
    assert _no_pool_copied(compiled, CONV_POOL)
    assert _no_pool_copied(compiled, (CONV_SLOTS, 2, 2048))


# the rung's figures (models/afmoe.py JOIN_ROWS quotes them): MiMo 16
# rows x 128 tokens 10,735,366,144 B of arguments + 450,188,800 B of
# temporaries = 66.2% of the chip's 16.9 GB; Trinity 16 x 128
# 14,599,017,984 + 181,220,352 B = 87.5% (the cell's two pools beside
# 8.55 GB of weights).  At 48 and 32 rows — a round of the cells'
# whole batch in one program — they read 10.74 + 1.73 GB (73.8%) and
# 14.60 + 0.48 GB (89.2%, 0.13 GB under the line)
@pytest.mark.parametrize("family, rows", [("mimo", 16), ("trinity", 16)])
def test_window_suffix_rows_program(one_chip, monkeypatch, family, rows):
    """The window / global family's row-batched suffix prefill (an
    admission round's hits x ONE page in one program, first tokens
    included) at MiMo's and at Trinity's published widths: it
    compiles, arguments + temporaries stay under 90% of the chip beside
    that cell's pools, and neither group's pools are copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    case = {"mimo": _sink_case, "trinity": _window_case}[family]
    fn, args, _ = case(one_chip, f"rows-{rows}")
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print(family, f"suffix rows {rows} x {PAGE}: arguments",
          mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 0.9 * 16.9e9
    for pool in jax.tree_util.tree_leaves(args[1]):
        assert _no_pool_copied(compiled, pool.shape)


# ---- the global stack under an indexer at published widths
# (benchmark/configs/keye-vl2-30b-a3b-ep8-stage0.json: 8 identical
# layers as ONE scanned body, 32 heads over 4 key/value heads of 128,
# an indexer of 16 heads of 64 that selects 2,048 keys a token, its
# keys the third pool of a page: 2,304 pages of 8 layers, a 258-page
# table, 16 held experts of 2,048 x 768 of 128, 32 rows)

DSA_H, DSA_KH, DSA_D, DSA_HI, DSA_DI, DSA_TOPK = 32, 4, 128, 16, 64, 2048
DSA_P, DSA_ROWS, DSA_LAYERS, DSA_BLOCKS = 258, 32, 8, 2305
DSA_POOLS = ((DSA_BLOCKS, DSA_LAYERS, DSA_KH, PAGE, DSA_D),) * 2 + (
    (DSA_BLOCKS, DSA_LAYERS, 1, DSA_DI, PAGE),)


def _walk_specs(one_chip, rows):
    """ops/page_groups.decode_groups' arrays for `rows` tables of
    DSA_P pages, as shapes."""
    from libsplinter_tpu.ops import page_groups as pg
    from libsplinter_tpu.ops.sparse_attention import ATTEND_PAGES
    got = pg.decode_groups(np.zeros((rows, DSA_P), np.int32),
                           np.zeros((rows,), np.int32), page=PAGE,
                           steps=8, chunk=ATTEND_PAGES[0])
    return {k: _spec(one_chip, v.shape, v.dtype) for k, v in got.items()
            if k not in ("held", "read")}


@pytest.mark.parametrize("stage", ["scan", "select", "attend"])
@pytest.mark.parametrize("q_tokens, rows", [(1, 32), (128, 16), (640, 1)],
                         ids=["decode", "round", "cold"])
def test_sparse_attention_kernels(one_chip, stage, q_tokens, rows):
    """The three stages of ops/sparse_attention at the benchmark's
    widths — a decode step of 32 rows, a round of 16 joins a page
    wide, a cold prompt's 5-page piece — each over a 32.9k-token row:
    they compile and copy no pool."""
    from libsplinter_tpu.ops import sparse_attention as sa
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    k, v, ik = (_spec(one_chip, s, bf) for s in DSA_POOLS)
    keys = sa.scan_width(DSA_P, PAGE)
    tab, ln = _spec(one_chip, (rows, DSA_P), i32), \
        _spec(one_chip, (rows,), i32)
    lay, sel = _spec(one_chip, (), i32), \
        _spec(one_chip, (rows, q_tokens, keys), f32)
    if stage == "scan":
        compiled = _compile(
            lambda qi, w, ik, tab, ln, lay: sa.index_scores(
                qi, w, ik, tab, ln, layer=lay, force_pallas=True),
            _spec(one_chip, (rows, q_tokens, DSA_HI, DSA_DI), bf),
            _spec(one_chip, (rows, q_tokens, DSA_HI), f32), ik, tab, ln,
            lay)
    elif stage == "select":
        compiled = _compile(
            lambda sc, lim: sa.select_topk(sc, lim, topk=DSA_TOPK,
                                           force_pallas=True),
            sel, _spec(one_chip, (rows, q_tokens), i32))
    else:
        # a decode step's walk by the host's groups (an item a grid
        # step: ops/page_groups); a suffix's tokens have none
        walk = _walk_specs(one_chip, rows) if q_tokens == 1 else None
        compiled = _compile(
            lambda q, k, v, sel, tab, ln, lay, walk:
            sa.sparse_paged_attention(q, k, v, sel, tab, ln, layer=lay,
                                      groups=walk, force_pallas=True),
            _spec(one_chip, (rows, q_tokens, DSA_H, DSA_D), bf), k, v, sel,
            tab, ln, lay, walk)
    assert "tpu_custom_call" in compiled.as_text()
    for shape in DSA_POOLS:
        assert _no_pool_copied(compiled, shape)


def _indexed_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's Keye configuration: "chunk",
    "suffix-<width>" (one row) or "rows-<rung>"."""
    from libsplinter_tpu.models import afmoe
    cfg = afmoe.WindowMoeConfig(
        vocab_size=18992, hidden=2048, kinds=("full",) * DSA_LAYERS,
        heads=DSA_H, kv_heads=DSA_KH, head_dim=DSA_D, window=0,
        dense_layers=0, dense_mlp_dim=6144, moe_mlp_dim=768,
        n_routed_experts=128, top_k=8, experts_first=0, experts_held=16,
        n_shared_experts=0, score_fn="softmax", max_len=33024,
        model_layers=48, rms_eps=1e-6, mup=False, out_gate=False,
        qk_norm=True, sandwich_norm=False,
        attn_kinds=(("full", afmoe.AttnKind(DSA_KH, DSA_D, DSA_D, DSA_D,
                                            1e7, 0)),),
        indexer=afmoe.Indexer(DSA_HI, DSA_DI, DSA_TOPK))
    assert cfg.plan == (0, 1, DSA_LAYERS)     # ONE scanned body
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: afmoe.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = afmoe.IndexedCompletionModel(cfg, params=params)
    pools = {"full": tuple(_spec(one_chip, s, jnp.bfloat16)
                           for s in DSA_POOLS)}
    i32 = _spec(one_chip, (), jnp.int32)
    if program == "chunk":
        fn = m._chunk_program(8, DSA_ROWS)
        tables = {"full": _spec(one_chip, (DSA_ROWS, DSA_P), jnp.int32),
                  "walk": _walk_specs(one_chip, DSA_ROWS)}
        args = (_spec(one_chip, (DSA_ROWS,), jnp.int32),
                _spec(one_chip, (2,), jnp.uint32),
                _spec(one_chip, (DSA_ROWS,), jnp.int32),
                _spec(one_chip, (DSA_ROWS,), jnp.bool_),
                _spec(one_chip, (DSA_ROWS,), jnp.int32),
                _spec(one_chip, (2,), jnp.int32))
    elif program.startswith("rows-"):
        fn, tables, args = _window_rows(one_chip, m, int(program[5:]),
                                        DSA_P, ("full",))
    else:
        width = int(program.split("-")[1])
        fn = m._suffix_program(width)
        tables = {"full": _spec(one_chip, (1, DSA_P), jnp.int32)}
        args = (_spec(one_chip, (1,), jnp.int32),
                _spec(one_chip, (1, width), jnp.int32), i32)
    return getattr(fn, "__wrapped__", fn), (params, pools, tables,
                                            *args), weights


@pytest.mark.parametrize("program", ["chunk", "suffix-128", "suffix-640",
                                     "rows-16"])
def test_indexed_programs_at_published_widths(one_chip, monkeypatch,
                                              program):
    """The 8-step decode chunk of 32 rows, the one-page and the widest
    suffix prefill and the round's rung of the benchmark's Keye
    configuration (8 layers as one scanned body; 1.71 GB of weights
    beside 5.13 GB of pages in three pools): each compiles, fits the
    chip beside its arguments and copies none of the three pools."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _indexed_case(one_chip, program)
    assert 1.70e9 < weights < 1.72e9          # 853M parameters
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print("keye", program, "arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes, "weights", weights)
    assert mem.argument_size_in_bytes > 6.8e9    # weights + three pools
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    if program == "chunk":
        # the chunk as PR 44 left it (a walk a row: 6,846,360,576 B of
        # arguments, 12,535,296 B of temporaries) with the walk's
        # groups beside the tables: under 0.1 GB more
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 6_846_360_576 + 12_535_296 + 0.1e9
    for shape in DSA_POOLS:
        assert _no_pool_copied(compiled, shape)


# the lowered text (kernel payloads, which carry source lines, left
# out) of the sibling families' programs as PR 39 left them: a change
# to code they share with the stack above that alters their programs
# shows here, and a PR that means to change them says so by changing
# the line

# ------------------------------------- the state-space / expert family

SSM_ROWS, SSM_SLOTS, SSM_P, SSM_BLOCKS = 128, 161, 16, 2561
SSM_POOL = (SSM_BLOCKS, 4, 2, PAGE, 128)
SSM_STATE = (SSM_SLOTS, 64, 64, 128)


def test_ssd_decode_step_kernel(one_chip):
    """The state-space step over the benchmark's 128 rows of 161 state
    slots (64 heads of 64 x 128 float32 a slot and layer, 338 MB):
    compiles for the chip and keeps the slots in place."""
    from libsplinter_tpu.ops.ssd_scan import ssd_decode_step
    f32 = jnp.float32
    compiled = jax.jit(
        lambda x, dt, a, b, c, s: ssd_decode_step(
            x, dt, a, b, c, s, force_pallas=True),
        donate_argnums=(5,)).lower(
        _spec(one_chip, (SSM_ROWS, 64, 64), f32),
        _spec(one_chip, (SSM_ROWS, 64), f32), _spec(one_chip, (64,), f32),
        _spec(one_chip, (SSM_ROWS, 8, 128), f32),
        _spec(one_chip, (SSM_ROWS, 8, 128), f32),
        _spec(one_chip, SSM_STATE, f32)).compile()
    assert "ssd_decode_step" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


@pytest.mark.parametrize("tokens", [128, 1024])
def test_ssd_chunk_prefill_kernel(one_chip, tokens):
    """The chunked prefill of one row (chunk 128, 8 groups of 8 heads a
    program, bfloat16 operands) at the narrowest and the widest suffix
    width."""
    from libsplinter_tpu.ops.ssd_scan import ssd_chunk_prefill
    f32 = jnp.float32
    compiled = jax.jit(
        lambda x, dt, a, b, c, s, n: ssd_chunk_prefill(
            x, dt, a, b, c, s, n_snap=n, dot_dtype=jnp.bfloat16,
            force_pallas=True)).lower(
        _spec(one_chip, (tokens, 64, 64), f32),
        _spec(one_chip, (tokens, 64), f32), _spec(one_chip, (64,), f32),
        _spec(one_chip, (tokens, 8, 128), f32),
        _spec(one_chip, (tokens, 8, 128), f32),
        _spec(one_chip, (64, 64, 128), f32),
        _spec(one_chip, (), jnp.int32)).compile()
    assert "ssd_chunk_prefill" in compiled.as_text()


def _ssm_case(one_chip, program):
    """(program, its arguments as shapes on the described chip, bytes
    of weights) of the benchmark's Nemotron configuration: "chunk" or
    "suffix-<width>" (one row)."""
    from libsplinter_tpu.models import nemotron_h as nh
    cfg = nh.SsmMoeConfig(
        vocab_size=16384, hidden=2688,
        kinds=tuple(nh.PATTERN[c] for c in "MEMEM*EMEMEM*EMEMEM*EMEMEM*"),
        heads=32, kv_heads=2, head_dim=128, ssm_heads=64, ssm_head_dim=64,
        ssm_groups=8, ssm_state=128, conv_kernel=4, moe_mlp_dim=1856,
        shared_mlp_dim=3712, n_routed_experts=128, top_k=6,
        experts_first=0, experts_held=16, routed_scaling_factor=2.5,
        max_len=2048, model_layers=52)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: nh.init_params(cfg, 0)))
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    m = nh.SsmCompletionModel(cfg, params=params)
    pools = (_spec(one_chip, SSM_POOL, jnp.bfloat16),) * 2
    states = [[_spec(one_chip, SSM_STATE, jnp.float32),
               _spec(one_chip, (SSM_SLOTS, 3, 6144), jnp.bfloat16)]
              for _ in range(12)]
    i32 = _spec(one_chip, (), jnp.int32)

    def vec(n, dtype=jnp.int32):
        return _spec(one_chip, (n,), dtype)
    kind, _, n = program.partition("-")
    if kind == "chunk":
        fn = m._chunk_program(8, SSM_ROWS)
        args = (_spec(one_chip, (SSM_ROWS, SSM_P), jnp.int32),
                vec(SSM_ROWS), vec(2, jnp.uint32), vec(SSM_ROWS),
                vec(SSM_ROWS, jnp.bool_), vec(SSM_ROWS), vec(3))
    else:
        fn = m._suffix_program(int(n))
        args = (_spec(one_chip, (1, SSM_P), jnp.int32), vec(1),
                _spec(one_chip, (1, int(n)), jnp.int32), i32, i32, i32, i32)
    return getattr(fn, "__wrapped__", fn), (params, pools, states,
                                            *args), weights


@pytest.mark.parametrize("program", ["chunk", "suffix-128", "suffix-1024"])
def test_ssm_programs_at_published_widths(one_chip, monkeypatch, program):
    """The 8-step decode chunk of 128 rows, the one-page suffix prefill
    and the widest (a cold 1,024-token prompt in one call) of the
    benchmark's Nemotron configuration (27 layers unrolled; 5.25 GB of
    weights, 4.06 GB of state slots, 1.34 GB of pages): each compiles,
    fits the chip beside its arguments, and keeps the page group's
    pools and the state slots in place."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args, weights = _ssm_case(one_chip, program)
    assert 5.24e9 < weights < 5.27e9      # 2,626M parameters
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    print(program, "arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 10.6e9   # weights, pages, slots
    assert mem.temp_size_in_bytes < 1.5e9        # no pool or slot copies
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert _no_pool_copied(compiled, SSM_POOL)
    assert _no_pool_copied(compiled, SSM_STATE)


SIBLING_PROGRAMS = {
    # PR 49: the latent decode kernel takes its pool once a page of a
    # grid step's chunk (ops/latent_attention.DECODE_PAGES operands
    # where one was; kimi's 128-page table needs no padding); the
    # suffix programs run the stack face, whose call did not change
    ("hybrid", "chunk"): "f393daedf2180a38",
    ("hybrid", "suffix-640"): "defcd610c0408b44",
    ("window", "chunk"): "ffe29bfc3cbbf3a7",
    ("window", "suffix-640"): "5b6661e293abaabe",
    # the same stack in MiMo's setting, as PR 43 left it: the indexer
    # (PR 44) is a static switch of the code the three share
    ("sink", "chunk"): "8851522810dddde6",
    ("sink", "suffix-128"): "2e657f381a632f4f",
    ("sink", "rows-16"): "060c4bc9ab027d5c",
    # the latent family's round (its expert layer runs in chunks of
    # 2,048 token slots: moe.sparse_moe without live_chunk) and chunk
    ("latent", "rows-64"): "30435683c3a2a679",
    # (PR 49: DECODE_PAGES pool operands and a 66-page table padded
    # to 72 in the chunk program; the round's program is the parent's)
    ("latent", "chunk"): "c4221038bb65705e",
    # this family's own chunk and one-row suffix programs, as PR 40
    # left them: the row axis (PR 41) is a program beside them
    ("conv", "chunk"): "c3ecbe24226ac7b8",
    ("conv", "suffix-128"): "0ea61ece1929056c",
    ("conv", "suffix-512"): "5abeb208f2b7f3bd",
}


@pytest.mark.parametrize("family, program", sorted(SIBLING_PROGRAMS))
def test_sibling_programs_are_the_parents(one_chip, monkeypatch, family,
                                          program):
    import hashlib
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    case = {"hybrid": _hybrid_case, "window": _window_case,
            "sink": _sink_case, "conv": _conv_case,
            "latent": _latent_case}[family]
    fn, args, _ = case(one_chip, program)
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                  'backend_config = ""', fn.lower(*args).as_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == SIBLING_PROGRAMS[family, program]
