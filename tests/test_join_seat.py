"""The two calls an admission is made of, whatever the lane and the
model family: `Seat` (engine/prefix_cache.py — the pages side: walk,
plan, map) and the model's `join` (models/decoder.py RowJoins,
models/mla.py LatentCompletionModel — which program prefills a round).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu.engine.kv_tier import HostTier
from libsplinter_tpu.engine.prefix_cache import Join, PrefixCache, Seat
from libsplinter_tpu.models import afmoe, kda, lfm2, mla
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig

PAGE = 16
RNG = np.random.default_rng(5)
# a document of two pages and a bit, and what follows it
DOC = RNG.integers(3, 500, 2 * PAGE + 5).astype(np.int32)


def _mimo_cfg(tmp_path_factory):
    import test_mimo as T
    return mla.load_model_description(
        T._describe(tmp_path_factory.mktemp("mimo-join")), max_len=512)[0]


def _tiny(kind: str, tmp_path_factory):
    """(model, init_paged keywords) of one `--model` family's tiny
    configuration, or of the key/value decoder."""
    f32 = {"dtype": jnp.float32}
    held = {"experts_first": 2, "experts_held": 4}
    if kind == "key-value":
        return CompletionModel(DecoderConfig.tiny(max_len=256, **f32),
                               buckets=(64,), suffix_buckets=(16,),
                               temp=0.0, seed=3), {}
    if kind == "pangu":
        return mla.LatentCompletionModel(
            mla.LatentMoeConfig.tiny(**f32, **held), seed=3, temp=0.0), {}
    if kind == "kimi":
        return kda.HybridCompletionModel(
            kda.HybridMoeConfig.tiny(**f32, **held), seed=3,
            temp=0.0), {"state_snapshots": 6}
    if kind == "lfm2":
        return lfm2.ConvCompletionModel(
            lfm2.ConvMoeConfig.tiny(**f32), seed=3,
            temp=0.0), {"state_snapshots": 6}
    cfg = (afmoe.WindowMoeConfig.tiny(**f32, **held) if kind == "trinity"
           else _mimo_cfg(tmp_path_factory))
    return afmoe.WindowCompletionModel(cfg, seed=3, temp=0.0), \
        {"window_pool_pages": 24}


def _pool(m, kw, batch=4):
    cache = m.init_paged(batch, page=PAGE, pool_pages=40, **kw)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    return cache


def _seat(m, cache, row, ids, reserve=8):
    """Seat `ids` in `row` as a lane does — walk, plan, map, the
    restore, the snapshot's slot — and hand back its Join."""
    ids = [int(t) for t in ids]
    seat = Seat(cache, ids)
    seat.walk()
    assert seat.plan(len(ids) + reserve, len(ids) + reserve + 4) is None
    assert seat.map(row) and len(seat.suffix)
    if seat.state_src is not None:
        m.state_restore(cache, seat.state_src, row)
    snap = None
    if seat.snap_at is not None:
        snap = (cache.alloc_state_slot(), seat.snap_at)
    return Join(row, ids, seat.match, bool(seat.hit_bids), snap)


def _filed(m, kw):
    """A pool whose tree holds DOC (prefilled by row 0 through `join`,
    inserted, the row freed).  Returns (cache, that miss's logits)."""
    cache = _pool(m, kw)
    miss = _seat(m, cache, 0, DOC)
    assert not miss.hit and miss.match == 0
    logits, firsts = m.join(cache, [miss])
    assert firsts is None
    cache.prefix_cache.insert(miss.ids, cache, 0, 0, **(
        {"state": miss.snap} if miss.snap else {}))
    cache.free_row(0)
    return cache, logits


def _skw(join):
    return ({"snap_at": join.snap[1], "snap_slot": join.snap[0]}
            if join.snap else {})


@pytest.mark.parametrize("kind", ["pangu", "kimi", "trinity", "mimo",
                                  "lfm2", "key-value"])
def test_join_runs_todays_programs(kind, tmp_path_factory):
    """`join` over every family: a miss gives paged_prefill_row's
    logits, a hit paged_append_prefill's, a round of several — where a
    round holds several — the rows program's logits and first tokens;
    `firsts` is None exactly for a round of one."""
    m, kw = _tiny(kind, tmp_path_factory)
    # `want`: today's calls by hand; `got`: the same seats through join
    want_c, got_c = _pool(m, kw), None
    miss = _seat(m, want_c, 0, DOC)
    want = m.paged_prefill_row(want_c, DOC, 0, **_skw(miss))
    got_c, got = _filed(m, kw)
    np.testing.assert_array_equal(got, want)
    want_c.prefix_cache.insert(miss.ids, want_c, 0, 0, **(
        {"state": miss.snap} if miss.snap else {}))
    want_c.free_row(0)

    # the second leaves a snapshot where the family keeps state (at 48)
    tails = [RNG.integers(3, 500, n).astype(np.int32)
             for n in (3, PAGE, 1)]
    prompts = [np.concatenate([DOC[:2 * PAGE], t]) for t in tails]
    hit = _seat(m, want_c, 1, prompts[0])
    assert hit.hit and hit.match == 2 * PAGE
    assert m.rides_round(hit) == (kind != "key-value")
    want = m.paged_append_prefill(want_c, tails[0], 1, **_skw(hit))
    got, firsts = m.join(got_c, [_seat(m, got_c, 1, prompts[0])])
    assert firsts is None
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_c.lengths, want_c.lengths)

    if m.round_cap(want_c) == 1:
        assert kind in ("kimi", "key-value")
        return
    for c in (want_c, got_c):
        c.free_row(1)
    joins = [_seat(m, want_c, r, p) for r, p in enumerate(prompts)]
    snaps = [j.snap for j in joins]
    want, want_firsts = m.paged_append_prefill_rows(
        want_c, [(j.row, tails[j.row]) for j in joins],
        *([snaps] if any(snaps) else ()))
    got, firsts = m.join(
        got_c, [_seat(m, got_c, r, p) for r, p in enumerate(prompts)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(firsts, want_firsts)
    assert firsts.shape == (3,)
    np.testing.assert_array_equal(got_c.lengths, want_c.lengths)


# ---- the seat's plan

POOL = 20


def _seat_pool(kind: str, tmp_path_factory):
    """A pool of POOL pages (the window group's: as many) whose tree
    holds DOC's two pages at zero references, by kind: plain key/value
    pages, a window group beside them, state slots beside them (the
    snapshot at the second page's end), and key/value pages demoted to
    the host tier."""
    name = {"plain": "key-value", "tier": "key-value",
            "window": "trinity", "state": "lfm2"}[kind]
    m, kw = _tiny(name, tmp_path_factory)
    if kind == "window":
        kw = {"window_pool_pages": POOL}
    cache = m.init_paged(4, page=PAGE, pool_pages=POOL, **kw)
    pc = PrefixCache(PAGE)
    pc.attach(cache)
    cache.prefix_cache = pc
    if kind == "tier":
        pc.bind_tier(
            HostTier(8),
            export_page=lambda bid: m.export_page_bytes(cache, bid),
            import_page=lambda bid, buf, sbuf: m.import_page_bytes(
                cache, bid, buf, sbuf))
    doc = _seat(m, cache, 0, DOC[:2 * PAGE + 1], reserve=0)
    m.join(cache, [doc])
    pc.insert(doc.ids, cache, 0, 0,
              **({"state": doc.snap} if doc.snap else {}))
    cache.free_row(0)
    if kind == "tier":
        assert pc.reclaim(2) == 2 and pc.demoted_pages() == 2
    return m, cache


@pytest.mark.parametrize("keep_suffix", [False, True],
                         ids=["unified", "prefill-lane"])
@pytest.mark.parametrize("kind", ["plain", "window", "state", "tier"])
def test_seat_plan_counts_what_ensure_takes(kind, keep_suffix,
                                            tmp_path_factory):
    """The plan is pure and exact: the `need` it reports is what map's
    ensure then takes from the pool; a zero-ref hit page is not counted
    as supply (a pool that has `need` pages and not one more is denied
    while the hit's pages would be pinned out of it); a fully covered
    prompt reserves its copy-on-write page; the prefill lane's variant
    always leaves a token to prefill."""
    m, cache = _seat_pool(kind, tmp_path_factory)
    pc = cache.prefix_cache
    stateful = kind == "state"
    # DOC's two pages, whole — and for the model with state a page and
    # a token more: its hit ends where a snapshot sits (at 32), strictly
    # below the last token, and it leaves one of its own (at 48)
    ids = [int(t) for t in DOC[:2 * PAGE]] + (
        list(range(7, 7 + PAGE + 1)) if stateful else [])
    was = (pc.stats.hits, pc.stats.misses, cache.free_pages,
           cache.refcounts.copy(), cache.tables.copy())
    seat = Seat(cache, ids, keep_suffix=keep_suffix)
    seat.walk()
    reserve = len(ids) + 8
    assert seat.plan(reserve, reserve + 4) is None
    # pure: no counter moved, no page taken, no table written
    assert (pc.stats.hits, pc.stats.misses, cache.free_pages) == was[:3]
    np.testing.assert_array_equal(cache.refcounts, was[3])
    np.testing.assert_array_equal(cache.tables, was[4])
    # the prefill lane gives a fully covered prompt's last page up
    pages = 1 if keep_suffix and not stateful else 2
    assert len(seat.hit_bids) + len(seat.tier_nodes) == pages
    assert bool(seat.tier_nodes) == (kind == "tier")
    assert seat.full_cover == (not keep_suffix and not stateful)
    if keep_suffix:
        assert seat.match + len(seat.tier_nodes) * PAGE < len(ids)
    assert seat.reserve == reserve + (4 if seat.full_cover else 0)
    assert seat.need == cache.pages_needed(seat.reserve) \
        - len(seat.hit_bids) + int(seat.full_cover)
    assert seat.pinned == len(seat.hit_bids)        # all at zero refs
    assert seat.snap_at == (48 if stateful else None)

    # the same seat over a pool that has `need` pages available and
    # not one more: denied while the hit's zero-ref pages count as
    # supply, held once as many pages more are free
    hog = [cache._alloc_page()
           for _ in range(cache.available_pages - seat.need)]
    tight = Seat(cache, ids, keep_suffix=keep_suffix)
    tight.walk()
    denied = tight.plan(reserve, reserve + 4)
    assert (tight.need, tight.pinned) == (seat.need, seat.pinned)
    # (a host-tier node pins nothing until it is readmitted)
    assert denied == (tight.need + tight.pinned if tight.pinned else None)
    for b in hog[:tight.pinned]:
        cache._decref(b)
    assert tight.plan(reserve, reserve + 4) is None

    avail = cache.available_pages
    assert tight.map(1)
    # the pinned pages left the supply and the row's own were taken
    # (readmitted pages: both); the copy-on-write page waits for the
    # replay
    assert avail - cache.available_pages \
        == tight.need + tight.pinned - int(tight.full_cover)
    assert cache.available_pages == int(tight.full_cover)
    assert len(tight.hit_bids) == pages and tight.match == pages * PAGE
    assert len(cache._owned[1]) == cache.pages_needed(tight.reserve)
    assert cache.lengths[1] == (len(ids) - 1 if tight.full_cover
                                else tight.match)
    assert (pc.stats.hits, pc.stats.misses) == (was[0] + 1, was[1])
    assert bool(len(tight.suffix)) == (not tight.full_cover)
    if stateful:
        assert tight.state_src >= cache.batch       # a snapshot's slot
    else:
        assert tight.state_src is None
    if kind == "window":
        assert tight.wtail \
            and cache.window.refcounts[tight.wtail[-1]] == 1
