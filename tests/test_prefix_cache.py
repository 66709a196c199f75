"""Cross-request prefix sharing (ISSUE 14 / ROADMAP item 2): the
refcounted copy-on-write page pool + host-side radix prefix cache
(engine/prefix_cache.py, models/decoder.PagedKVCache).

Covers: the refcount churn drill (randomized join/finish/evict cycles
leak nothing, double-free nothing, and keep refcount-0 <=> free-list
XOR tree-retention), COW-vs-private byte-exact greedy decode (f32 and
int8, single-chip and tp=2), the >= 4x rows-per-page-budget
multiplier, LRU eviction + tenant quotas, the mid-flight joiner that
maps a prefix another live row is still decoding from, the
bp-memo staleness-eviction regression, heartbeat gauges, the loadgen
shared-prefix knob, and the supervised completer.prefix_map chaos
drill.  `make prefix-check` runs this file + the hot-admission gate
(scripts/prefix_hit_check.py).
"""
from __future__ import annotations

import json
import random
import subprocess
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.engine.prefix_cache import PrefixCache
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig

PAGE = 8
CFG = DecoderConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return CompletionModel(CFG, buckets=(32, 64), temp=0.0, seed=1,
                           suffix_buckets=(8, 16))


def _mkstore(tmp_path, tag, **kw):
    name = f"/spt-{tag}-{tmp_path.name}"
    Store.unlink(name)
    kw.setdefault("nslots", 128)
    kw.setdefault("max_val", 4096)
    kw.setdefault("vec_dim", 8)
    return name, Store.create(name, **kw)


def _attach_pc(cache, **kw):
    pc = PrefixCache(cache.page, **kw)
    pc.attach(cache)
    cache.prefix_cache = pc
    return pc


def _check_invariants(cache, pc):
    """The churn drill's page-accounting invariants."""
    refs = np.zeros(cache.n_blocks, np.int64)
    for owned in cache._owned:
        for bid in owned:
            refs[bid] += 1
    # refcounts == table references, exactly
    assert np.array_equal(refs[1:], cache.refcounts[1:]), \
        (refs.tolist(), cache.refcounts.tolist())
    free = set(cache._free)
    assert len(free) == len(cache._free), "free list duplicate"
    tree = {bid for bid in range(1, cache.n_blocks)
            if pc is not None and pc.retains(bid)}
    assert not free & tree, "page both free and tree-retained"
    for bid in range(1, cache.n_blocks):
        if refs[bid] > 0:
            assert bid not in free, f"page {bid} live AND free"
        else:
            assert bid in free or bid in tree, \
                f"page {bid} leaked (zero-ref, not free, not cached)"
    if pc is not None:
        # the O(1) incremental counter must track a brute recount
        brute = sum(1 for bid in tree if cache.refcounts[bid] == 0)
        assert pc.evictable_count() == brute, \
            (pc.evictable_count(), brute)


# ---------------------------------------------------------------- mechanics

def test_map_shared_refcounts_and_full_cover_cow(model):
    """Full-cover joiner: table write + replay chunk, byte-identical
    to private serving, exactly one COW copy, int8-frozen-scale
    discipline covered by the int8 variant below."""
    cache = model.init_paged(4, page=PAGE)
    pc = _attach_pc(cache)
    prompt = (np.arange(1, 25, dtype=np.int32) % 200) + 1  # 3 pages
    l0 = model.paged_prefill_row(cache, prompt, 0)
    assert pc.insert(prompt, cache, 0, tenant=1) == 3
    bids, match = pc.lookup(prompt)
    assert match == 24 and len(bids) == 3
    cache.map_shared(1, bids)
    cache.lengths[1] = 23
    assert all(cache.refcounts[b] == 2 for b in bids)
    assert cache.ensure(1, 32)
    toks = np.full((4,), -1, np.int32)
    toks[0] = int(np.argmax(l0))
    toks[1] = int(prompt[-1])          # the replay token
    out = model.paged_decode_chunk(cache, toks, 7)
    donor = [int(toks[0])] + [int(x) for x in out[0][:6]]
    joiner = [int(x) for x in out[1]]
    assert joiner == donor
    assert pc.stats.cow_copies == 1
    # the COW'd tail is private now; the shared original kept its refs
    assert cache.refcounts[bids[-1]] == 1
    cache.free_row(0)
    cache.free_row(1)
    _check_invariants(cache, pc)
    # all three pages retained zero-ref (evictable), none leaked
    assert pc.evictable_count() == 3
    assert cache.available_pages == cache.n_blocks - 1


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_cow_vs_private_byte_exact(model, kv_dtype):
    """COW-vs-private byte-exact greedy decode, f32 and int8 pools.
    For int8 the shared pages are frozen read-only: their per-page
    scales must stay byte-stable across the join + decode (the
    stale-scale hazard is structurally gone)."""
    cache = model.init_paged(4, page=PAGE, kv_dtype=kv_dtype)
    pc = _attach_pc(cache)
    prompt = (np.arange(3, 27, dtype=np.int32) % 150) + 2
    l0 = model.paged_prefill_row(cache, prompt, 0)
    pc.insert(prompt, cache, 0)
    shared_bids = [int(cache.tables[0, j]) for j in range(3)]
    if kv_dtype == "int8":
        ks0 = [np.asarray(s)[shared_bids].copy()
               for s in cache.k_scales]
        vs0 = [np.asarray(s)[shared_bids].copy()
               for s in cache.v_scales]
    bids, match = pc.lookup(prompt)
    assert match == len(prompt)
    cache.map_shared(1, bids)
    cache.lengths[1] = len(prompt) - 1
    cache.ensure(1, 40)
    toks = np.full((4,), -1, np.int32)
    toks[0] = int(np.argmax(l0))
    toks[1] = int(prompt[-1])
    out = model.paged_decode_chunk(cache, toks, 8)
    assert [int(x) for x in out[1]] == \
        [int(toks[0])] + [int(x) for x in out[0][:7]]
    assert pc.stats.cow_copies == 1
    if kv_dtype == "int8":
        for s, before in zip(cache.k_scales, ks0):
            assert np.array_equal(np.asarray(s)[shared_bids], before)
        for s, before in zip(cache.v_scales, vs0):
            assert np.array_equal(np.asarray(s)[shared_bids], before)
    cache.free_row(0)
    cache.free_row(1)
    _check_invariants(cache, pc)


def test_suffix_prefill_matches_private(model):
    """Partial hit: mapped prefix + paged suffix prefill must produce
    the same first token and decode stream as a private full
    prefill — across a suffix long enough to loop the largest
    suffix bucket."""
    cache = model.init_paged(4, page=PAGE)
    pc = _attach_pc(cache)
    prefix = (np.arange(1, 17, dtype=np.int32) % 90) + 1   # 2 pages
    model.paged_prefill_row(cache, prefix, 0)
    pc.insert(prefix, cache, 0)
    for extra in (3, 21):              # < and > the 16-token bucket
        tail = (np.arange(extra, dtype=np.int32) % 50) + 5
        full = np.concatenate([prefix, tail])
        bids, match = pc.lookup(full)
        assert match == 16
        cache.map_shared(1, bids)
        cache.lengths[1] = match
        assert cache.ensure(1, len(full) + 8)
        lg = model.paged_append_prefill(cache, full[match:], 1)
        ref_cache = model.init_paged(2, page=PAGE)
        lr = model.paged_prefill_row(ref_cache, full, 0)
        t, tr = int(np.argmax(lg)), int(np.argmax(lr))
        assert t == tr
        ta = np.full((4,), -1, np.int32)
        ta[1] = t
        tb = np.full((2,), -1, np.int32)
        tb[0] = tr
        assert [int(x) for x in model.paged_decode_chunk(
            cache, ta, 6)[1]] == \
            [int(x) for x in model.paged_decode_chunk(
                ref_cache, tb, 6)[0]]
        cache.free_row(1)
    cache.free_row(0)
    _check_invariants(cache, pc)


def test_refcount_churn_drill(model):
    """Randomized join/map/finish/evict cycles over a tiny pool:
    zero leaked pages, zero double-frees, refcount-0 <=> free-list
    XOR tree-retention — checked after every step."""
    cache = model.init_paged(6, page=PAGE, pool_pages=48)
    pc = _attach_pc(cache)
    rng = random.Random(7)
    prompts = [((np.arange(1, 1 + n, dtype=np.int32) * m) % 120) + 1
               for n, m in ((16, 3), (24, 5), (16, 7), (32, 11))]
    live: dict[int, int] = {}          # row -> prompt idx
    for step in range(120):
        op = rng.random()
        free_rows = [r for r in range(6) if r not in live]
        if op < 0.5 and free_rows:
            r = free_rows[0]
            pi = rng.randrange(len(prompts))
            ids = prompts[pi]
            bids, match = pc.lookup(ids)
            need = (cache.pages_needed(len(ids) + PAGE)
                    - len(bids) + 1)
            if need > cache.available_pages:
                continue               # backpressure: the honest path
            if match == len(ids):
                cache.map_shared(r, bids)
                pc.commit_hit(ids, match)
                cache.lengths[r] = match - 1
                cache.ensure(r, len(ids) + PAGE)
                # the completer COWs the replay page eagerly at
                # admission (the need check counted it) — mirror that
                model._cow_fixups(cache)
            elif match:
                cache.map_shared(r, bids)
                pc.commit_hit(ids, match)
                cache.lengths[r] = match
                cache.ensure(r, len(ids) + PAGE)
                model.paged_append_prefill(cache, ids[match:], r)
            else:
                pc.note_miss()
                model.paged_prefill_row(cache, ids, r)
                cache.ensure(r, len(ids) + PAGE)
            pc.insert(ids, cache, r, tenant=pi % 3)
            live[r] = pi
        elif op < 0.75 and live:
            # decode only within every live row's reservation (the
            # real lane's admission contract; a row at its budget
            # would otherwise exhaust the pool mid-decode)
            if all(cache.pages_needed(int(cache.lengths[r]) + 2)
                   <= len(cache._owned[r]) for r in live):
                toks = np.full((6,), -1, np.int32)
                for r in live:
                    toks[r] = 9
                model.paged_decode_chunk(cache, toks, 2)
        elif op < 0.92 and live:
            r = rng.choice(list(live))
            cache.free_row(r)
            del live[r]
        else:
            pc.reclaim(rng.randrange(1, 4))
        _check_invariants(cache, pc)
    for r in list(live):
        cache.free_row(r)
    _check_invariants(cache, pc)
    pc.reclaim(cache.n_blocks)
    assert cache.free_pages == cache.n_blocks - 1
    assert pc.shared_pages() == 0


def _check_window_invariants(cache, pc):
    """_check_invariants for the WINDOW page group of a cache that
    keeps two (models/afmoe.py): refcounts == table references; a
    zero-ref page is free XOR retained by the tree; nothing is both;
    a row's table names pages inside its span only."""
    w = cache.window
    refs = np.zeros(w.n_blocks, np.int64)
    for r in range(cache.batch):
        lo, hi = int(w._lo[r]), int(w._hi[r])
        assert not w.tables[r, :lo].any() and not w.tables[r, hi:].any()
        assert (w.tables[r, lo:hi] > 0).all()
        assert hi - lo <= w.span
        for bid in w.tables[r, lo:hi]:
            refs[bid] += 1
    assert np.array_equal(refs[1:], w.refcounts[1:])
    free = set(w._free)
    assert len(free) == len(w._free), "window free list duplicate"
    tree = {bid for bid in range(1, w.n_blocks) if pc.retains_window(bid)}
    assert not free & tree, "window page both free and tree-retained"
    for bid in range(1, w.n_blocks):
        if refs[bid] > 0:
            assert bid not in free, f"window page {bid} live AND free"
        else:
            assert (bid in free) != (bid in tree), \
                f"window page {bid} leaked or doubly held"
    assert pc.window_evictable_count() == sum(
        1 for bid in tree if w.refcounts[bid] == 0)
    assert pc.window_pages() == len(tree)


def test_two_group_churn_drill():
    """The churn drill over a cache with TWO page groups (a window of
    32 tokens = 2 pages of 16, prompts of 3-9 pages): sessions that
    grow, fresh prompts, decode chunks that slide rows past pages,
    finishes, reclaims in either group — both groups' invariants after
    every step, and every page the window group is not holding
    POISONED before every dispatch: a read of a page that went back
    would blow the logits up."""
    from libsplinter_tpu.models import afmoe
    cfg = afmoe.WindowMoeConfig.tiny(
        dtype=jnp.float32, kinds=("window", "full"), dense_layers=0)
    m = afmoe.WindowCompletionModel(cfg, seed=2)
    B = 4
    cache = m.init_paged(B, page=PAGE, pool_pages=40, window_pool_pages=24)
    pc = _attach_pc(cache)
    w = cache.window
    rng = random.Random(11)
    nrng = np.random.default_rng(3)
    scripts = [nrng.integers(3, cfg.vocab_size, 150).astype(np.int32)
               for _ in range(3)]
    turn = [0, 0, 0]
    live: dict[int, object] = {}

    def poison():
        idle = jnp.asarray([0] + list(w._free), jnp.int32)
        for pool in w.pools:
            pool[0] = pool[0].at[idle].set(1e30)

    def sound(logits):
        assert np.isfinite(logits).all() and np.abs(logits).max() < 1e4

    for step in range(90):
        op = rng.random()
        free_rows = [r for r in range(B) if r not in live]
        poison()
        if op < 0.5 and free_rows:
            r = free_rows[0]
            if rng.random() < 0.6:
                s = rng.randrange(3)
                if s in live.values():
                    continue           # a session has one owner
                turn[s] = min(turn[s] + 1, 5)
                ids, tag = scripts[s][:40 + 20 * turn[s]], s
            else:
                ids = nrng.integers(3, cfg.vocab_size,
                                    rng.randrange(20, 70)).astype(np.int32)
                tag = None
            bids, match, _ = pc.lookup_tiered(ids)
            tail = list(pc.last_window)
            reserve = len(ids) + PAGE
            if match == len(ids):
                bids, match, tail = [], 0, []     # keep the drill simple
            if cache.pages_needed(reserve) - len(bids) \
                    > cache.available_pages \
                    or w.join_pages(match, reserve) - len(tail) \
                    > w.available_pages:
                continue               # backpressure: the honest path
            if bids:
                cache.map_shared(r, bids)
                w.map_tail(r, len(bids) - len(tail), tail)
                pc.commit_hit(ids, match)
                cache.lengths[r] = match
                sound(m.paged_append_prefill(cache, ids[match:], r))
            else:
                pc.note_miss()
                sound(m.paged_prefill_row(cache, ids, r))
            assert cache.ensure(r, reserve)
            pc.insert(ids, cache, r)
            live[r] = tag
        elif op < 0.75 and live:
            if all(int(cache.lengths[r]) + 4 <= 150 + PAGE and
                   cache.pages_needed(int(cache.lengths[r]) + 4)
                   <= len(cache._owned[r]) for r in live):
                toks = np.full((B,), -1, np.int32)
                for r in live:
                    toks[r] = 9
                m.audit_seat(0, next(iter(live)))
                pend = m.paged_decode_chunk_async(cache, toks, 4)
                pend.block()
                sound(np.asarray(pend.audit)[:, 0])
        elif op < 0.9 and live:
            r = rng.choice(list(live))
            cache.free_row(r)
            del live[r]
        elif op < 0.95:
            pc.reclaim(rng.randrange(1, 4))
        else:
            pc.reclaim_window(rng.randrange(1, 4))
        _check_invariants(cache, pc)
        _check_window_invariants(cache, pc)
    assert w.released > 0 and pc.stats.window_evictions > 0
    for r in list(live):
        cache.free_row(r)
    _check_invariants(cache, pc)
    _check_window_invariants(cache, pc)
    pc.reclaim(cache.n_blocks)
    assert cache.free_pages == cache.n_blocks - 1
    assert w.free_pages == w.n_blocks - 1 and pc.window_pages() == 0


def test_rows_per_envelope_at_least_4x(model):
    """The fixed page budget must seat >= 4x more concurrent rows
    under sharing than under private paging: the admission math
    (worst-case reservation minus hit pages plus the COW page) at
    cache level, the same arithmetic run_continuous uses."""
    prompt_pages, budget = 15, 64
    prompt = (np.arange(1, 1 + prompt_pages * PAGE,
                        dtype=np.int32) % 200) + 1
    worst = cache_pages = prompt_pages + 1     # prompt + 1 growth page

    private = model.init_paged(32, page=PAGE, pool_pages=budget)
    n_private = 0
    for r in range(32):
        if not private.ensure(r, worst * PAGE):
            break
        n_private += 1

    shared = model.init_paged(32, page=PAGE, pool_pages=budget)
    pc = _attach_pc(shared)
    model.paged_prefill_row(shared, prompt, 0)
    shared.ensure(0, worst * PAGE)
    pc.insert(prompt, shared, 0)
    n_shared = 1
    for r in range(1, 32):
        bids, match = pc.lookup(prompt)
        need = shared.pages_needed(worst * PAGE) - len(bids) + 1
        if need > shared.available_pages:
            break
        shared.map_shared(r, bids)
        shared.lengths[r] = match - 1
        shared.ensure(r, worst * PAGE)
        model._cow_fixups(shared)      # the replay page is real cost
        n_shared += 1
    assert cache_pages == worst
    assert n_shared >= 4 * n_private, (n_shared, n_private)


def test_eviction_lru_and_reprefill(model):
    """Zero-ref cached pages evict LRU-first under allocation
    pressure; an evicted prefix simply misses and re-prefills
    correctly (no dangling page ids)."""
    cache = model.init_paged(4, page=PAGE, pool_pages=16)
    pc = _attach_pc(cache)
    a = (np.arange(1, 17, dtype=np.int32) % 80) + 1
    b = ((np.arange(1, 17, dtype=np.int32) * 3) % 80) + 1
    for ids in (a, b):
        model.paged_prefill_row(cache, ids, 0)
        pc.insert(ids, cache, 0)
        cache.free_row(0)
    assert pc.shared_pages() == 4
    _, mb = pc.lookup(b)
    pc.commit_hit(b, mb)               # touch b: a becomes LRU
    # pressure: a 13-page allocation must reclaim a's pages first
    assert cache.ensure(1, 13 * PAGE)
    assert pc.stats.evictions >= 1
    bids_a, match_a = pc.lookup(a)
    assert match_a < len(a)            # a (partially) evicted
    cache.free_row(1)
    # the evicted prefix re-prefills and re-inserts cleanly
    model.paged_prefill_row(cache, a, 2)
    pc.insert(a, cache, 2)
    cache.free_row(2)
    _check_invariants(cache, pc)


def test_tenant_quota_enforced(model):
    """Per-tenant page quotas (engine/qos.py parse_tenant_quotas
    grammar): over-quota inserts evict the tenant's own zero-ref
    pages first, then skip with quota_rejects."""
    from libsplinter_tpu.engine.qos import parse_tenant_quotas
    assert parse_tenant_quotas("1:2,2:8") == {1: 2, 2: 8}
    with pytest.raises(ValueError):
        parse_tenant_quotas("1=2")
    cache = model.init_paged(4, page=PAGE)
    pc = _attach_pc(cache, tenant_quotas={1: 2})
    ids = (np.arange(1, 25, dtype=np.int32) % 90) + 1   # 3 pages
    model.paged_prefill_row(cache, ids, 0)
    # live row: nothing evictable, so the 3rd page must be rejected
    assert pc.insert(ids, cache, 0, tenant=1) == 2
    assert pc.stats.quota_rejects == 1
    assert pc.tenant_pages() == {1: 2}
    cache.free_row(0)                  # pages go zero-ref
    # a different prefix for the same tenant now evicts its own LRU
    other = ((np.arange(1, 17, dtype=np.int32) * 7) % 90) + 1
    model.paged_prefill_row(cache, other, 1)
    assert pc.insert(other, cache, 1, tenant=1) == 2
    assert pc.tenant_pages() == {1: 2}
    assert pc.stats.evictions >= 2
    cache.free_row(1)
    _check_invariants(cache, pc)


# ------------------------------------------------------------- tp=2 parity

def test_sharded_prefix_sharing_byte_exact_tp2():
    """PR 8 composition: tables/refcounts are host-global and the
    pools shard on kv heads, so prefix sharing under tp=2 (virtual
    8-device CPU mesh) must be byte-exact with the single-chip
    shared path AND with single-chip private serving — including the
    COW page copy running on sharded pools."""
    from libsplinter_tpu.parallel import (ShardedCompletionModel,
                                          make_mesh)
    base = CompletionModel(CFG, buckets=(32,), temp=0.0, seed=1,
                           suffix_buckets=(8,))
    tp = ShardedCompletionModel(CFG, make_mesh(dp=4, tp=2),
                                params=base.params, buckets=(32,),
                                temp=0.0, seed=1, suffix_buckets=(8,))
    prompt = (np.arange(2, 26, dtype=np.int32) % 170) + 1
    seqs = {}
    for tag, m in (("chip", base), ("tp", tp)):
        cache = m.init_paged(4, page=PAGE)
        pc = _attach_pc(cache)
        l0 = m.paged_prefill_row(cache, prompt, 0)
        pc.insert(prompt, cache, 0)
        bids, match = pc.lookup(prompt)
        assert match == len(prompt)
        cache.map_shared(1, bids)
        cache.lengths[1] = len(prompt) - 1
        cache.ensure(1, 40)
        toks = np.full((4,), -1, np.int32)
        toks[0] = int(np.argmax(l0))
        toks[1] = int(prompt[-1])
        out = m.paged_decode_chunk(cache, toks, 6)
        assert pc.stats.cow_copies == 1
        seqs[tag] = ([int(toks[0])] + [int(x) for x in out[0][:5]],
                     [int(x) for x in out[1]])
        donor, joiner = seqs[tag]
        assert joiner == donor, tag
    assert seqs["chip"] == seqs["tp"]


# ------------------------------------------------------ completer end-to-end

def _submit(st, key, prompt):
    st.set(key, prompt)
    st.label_or(key, P.LBL_INFER_REQ)
    st.bump(key)


def _await_ready(st, keys, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(st.labels(k) & P.LBL_READY for k in keys):
            return True
        time.sleep(0.05)
    return False


# 23 chars + BOS = 24 tokens = 3 exact pages: repeats are full-cover
HOT_PROMPT = "abcdefghijklmnopqrstuvw"


def test_continuous_byte_identical_with_midflight_joiner(tmp_path,
                                                         model):
    """Acceptance: greedy decode byte-identical cache-on vs
    cache-off, INCLUDING a joiner that maps a prefix another live
    row is still decoding from (the donor is mid-decode when the
    joiner is submitted)."""
    outs = {}
    for tag, enable in (("off", False), ("on", True)):
        name, st = _mkstore(tmp_path, f"pfx-{tag}")
        try:
            comp = Completer(st, model=model, max_new_tokens=24,
                             flush_tokens=2, template="none",
                             batch_cap=4, page_size=PAGE,
                             prefix_cache=enable)
            comp.attach()
            _submit(st, "donor", HOT_PROMPT)
            th = threading.Thread(
                target=comp.run_continuous,
                kwargs=dict(idle_timeout_ms=20, stop_after=60.0),
                daemon=True)
            th.start()
            # wait until the donor is claimed and streaming, then
            # join with the identical prompt mid-decode
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    if st.value_len("donor") > len(HOT_PROMPT):
                        break
                except KeyError:
                    pass
                time.sleep(0.005)
            _submit(st, "joiner", HOT_PROMPT)
            assert _await_ready(st, ["donor", "joiner"])
            comp.stop()
            th.join(timeout=15)
            outs[tag] = (st.get("donor").rstrip(b"\0"),
                         st.get("joiner").rstrip(b"\0"))
            if enable:
                assert comp.prefix_cache.stats.hits >= 1
                assert comp.prefix_cache.stats.cow_copies >= 1
        finally:
            st.close()
            Store.unlink(name)
    assert outs["on"] == outs["off"]
    # identical prompts, greedy: donor and joiner streams match too
    assert outs["on"][0] == outs["on"][1]


def test_heartbeat_prefix_gauges(tmp_path, model):
    """The prefix_* gauges ride the completer heartbeat (flat fields:
    `spt metrics` renders sptpu_completer_prefix_*, the telemetry
    ring and `spt top` sparkline prefix_hits) and the per-tenant
    residency lands in the tenants section."""
    name, st = _mkstore(tmp_path, "pfx-hb")
    try:
        comp = Completer(st, model=model, max_new_tokens=4,
                         flush_tokens=2, template="none", batch_cap=4,
                         page_size=PAGE)
        comp.attach()
        keys = [f"h/{i}" for i in range(3)]
        for k in keys:
            st.set(k, HOT_PROMPT)
            P.stamp_tenant(st, k, 2)
            st.label_or(k, P.LBL_INFER_REQ)
            st.bump(k)
        th = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=20, stop_after=30.0),
            daemon=True)
        th.start()
        assert _await_ready(st, keys)
        # snapshot while the lane is LIVE: shutdown releases the
        # whole pool (the zero-leaked-pages contract), emptying the
        # tree — residency gauges are a live-lane signal
        comp.publish_stats()
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        comp.stop()
        th.join(timeout=15)
        assert snap["prefix_hits"] >= 1
        assert snap["prefix_misses"] >= 1
        assert snap["prefix_shared_pages"] >= 3
        assert snap["prefix_bytes_saved"] > 0
        for field in ("prefix_evictions", "prefix_cow_copies",
                      "prefix_hit_tokens", "prefix_evictable"):
            assert field in snap
        assert snap["tenants"]["2"]["prefix_pages"] >= 3
        assert snap["tenants"]["2"]["prefix_hit_pages"] >= 3
        # stopped lane: pool returned whole, tree emptied
        assert comp._paged_cache.used_pages == 0
        assert comp.prefix_cache.shared_pages() == 0
    finally:
        st.close()
        Store.unlink(name)


def test_bp_memo_evicts_stale_epochs_first(tmp_path):
    """Regression (ISSUE 14 satellite): under the hard cap the memo
    used next(iter(...)) — insertion order — so a long-lived denied
    request (the exact entry the memo exists for) was evicted while
    freshly-STALE newcomers survived.  Staleness now evicts first."""
    name, st = _mkstore(tmp_path, "bpmemo")
    try:
        comp = Completer(st, generate_fn=lambda p: iter([b"x"]),
                         template="none")
        comp._bp_memo_cap = 3
        keys = [f"m/{i}" for i in range(4)]
        for k in keys:
            st.set(k, "p")
            st.label_or(k, P.LBL_INFER_REQ)
        idxs = [st.find_index(k) for k in keys]
        # entry 0: LIVE (epoch matches), inserted FIRST
        comp._bp_memo[idxs[0]] = (st.epoch_at(idxs[0]), 5)
        # entries 1..3: stale (memo'd epoch is behind the slot's)
        for i in (1, 2, 3):
            e = st.epoch_at(idxs[i])
            st.set(keys[i], "rewritten")   # epoch moves
            comp._bp_memo[idxs[i]] = (e, 5)
        dropped = comp._bound_bp_memo()
        assert dropped == 1
        assert idxs[0] in comp._bp_memo, \
            "live denied entry evicted while stale entries survived"
        assert len(comp._bp_memo) == comp._bp_memo_cap
        # sweep still clears the remaining stale entries wholesale
        comp._sweep_bp_memo()
        assert list(comp._bp_memo) == [idxs[0]]
    finally:
        st.close()
        Store.unlink(name)


# ----------------------------------------------------------------- loadgen

def test_loadgen_shared_prefix_knob_deterministic():
    """`--shared-prefix P:LEN`: seeded and deterministic — two
    generators with one seed draw the identical prompt mix, ~P of it
    from the pooled hot prefixes of exactly LEN chars."""
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec

    def prompts(seed):
        gen = LoadGenerator(None, [TenantSpec(1, 10.0)], seed=seed,
                            scenario="shared-prefix",
                            shared_prefix=(0.9, 64))
        return [gen._complete_prompt() for _ in range(80)]

    a, b = prompts(3), prompts(3)
    assert a == b
    pooled = [p for p in a if len(p) == 64]
    assert len(set(pooled)) <= 4
    assert 0.75 <= len(pooled) / len(a) <= 1.0
    assert prompts(4) != a
    with pytest.raises(ValueError):
        LoadGenerator(None, [TenantSpec(1, 1.0)],
                      shared_prefix=(1.5, 64))


def test_loadgen_shared_prefix_reports_hit_rate(tmp_path, model):
    """The shared-prefix scenario against a live continuous completer:
    the summary carries the completer's cache hit rate beside the
    per-tenant SLO rows, and nothing is lost."""
    from libsplinter_tpu.cli.loadgen import LoadGenerator, TenantSpec
    name, st = _mkstore(tmp_path, "pfx-lg", nslots=256)
    try:
        comp = Completer(st, model=model, max_new_tokens=4,
                         flush_tokens=2, template="none", batch_cap=4,
                         page_size=PAGE)
        comp.attach()
        th = threading.Thread(
            target=comp.run_continuous,
            kwargs=dict(idle_timeout_ms=10, stop_after=120.0),
            daemon=True)
        th.start()
        gen = LoadGenerator(st, [TenantSpec(1, 12.0,
                                            deadline_ms=20_000)],
                            duration_s=2.0, seed=5,
                            scenario="shared-prefix",
                            shared_prefix=(0.9, 3 * PAGE - 1),
                            drain_s=30.0)
        rep = gen.run()
        comp.publish_stats()           # don't race the 2s heartbeat
        pfx = gen._prefix_cache_report()
        comp.stop()
        th.join(timeout=15)
        assert rep["lost"] == 0
        assert rep["ok"] >= 1
        assert pfx is not None and pfx["hits"] >= 1
        assert pfx["hit_rate"] > 0.3
    finally:
        st.close()
        Store.unlink(name)


# ------------------------------------------------------------------- chaos

@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_prefix_map_crash_strands_nothing(tmp_path,
                                                     monkeypatch):
    """The completer.prefix_map fault site: the lane crashes mid
    table-mapping on its first prefix-cache HIT (request claimed,
    refcount bumps about to happen).  `spt supervise` restarts it;
    pool, refcounts, and tree died with the process, so the restarted
    lane serves the reclaimed request from a clean pool — no stranded
    refcounts, no lost request, and a THIRD request round-trips."""
    import os

    from libsplinter_tpu.engine.supervisor import Supervisor

    name, st = _mkstore(tmp_path, "pfx-chaos", nslots=256)
    child = os.path.join(os.path.dirname(__file__), "chaos_child.py")
    monkeypatch.setenv("SPTPU_FAULT", "completer.prefix_map:crash@1")
    monkeypatch.setenv("SPTPU_CHAOS_RUN_S", "600")
    try:
        # both submitted upfront with one prompt: the first admission
        # misses (inserts), the second HITS -> crash mid-mapping
        _submit(st, "c1", HOT_PROMPT)
        _submit(st, "c2", HOT_PROMPT)
        holder: dict = {}

        def spawn(lane):
            return subprocess.Popen(
                [sys.executable, child, "completer_prefix", name],
                env=holder["sup"]._child_env(lane))

        sup = Supervisor(name, lanes=("completer",), spawn_fn=spawn,
                         store=st, backoff_base_ms=100,
                         backoff_max_ms=2000, breaker_threshold=8,
                         breaker_window_s=120, startup_grace_s=300)
        holder["sup"] = sup
        t = threading.Thread(target=sup.run,
                             kwargs={"poll_interval_s": 0.1,
                                     "stop_after": 240.0})
        t.start()
        try:
            assert _await_ready(st, ["c1", "c2"], timeout=180), \
                sup.lanes
            assert sup.lanes["completer"].restarts >= 1
            # post-crash hit path works too (generation-2 lane,
            # fault stripped): same prompt, fresh tree
            _submit(st, "c3", HOT_PROMPT)
            assert _await_ready(st, ["c3"], timeout=120)
            for k in ("c1", "c2", "c3"):
                assert not st.labels(k) & (P.LBL_INFER_REQ
                                           | P.LBL_SERVICING)
        finally:
            sup.stop()
            t.join()
            sup.shutdown()
    finally:
        st.close()
        Store.unlink(name)
