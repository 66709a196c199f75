"""ops/page_groups.decode_groups: the rows of a decode batch that hold
the same pages, grouped on the host (NumPy only).  What a kernel that
takes the groups relies on is held here for any batch; the kernel
itself is tests/test_keye.py's."""
from __future__ import annotations

import numpy as np
import pytest

from libsplinter_tpu.ops.page_groups import (FIRST, GROUP_ROWS, LAST, LIVE,
                                             decode_groups, item_room)

PAGE, CHUNK, STEPS = 16, 8, 8


def batch(rng, rows, pages, n_docs=4, doc_pages=None):
    """tables (B, P), lengths (B,): each row None (dead) or (document |
    None, tokens of its own behind the document's whole pages)."""
    doc_pages = doc_pages or [int(rng.integers(1, pages - 2))
                              for _ in range(n_docs)]
    ids = iter(rng.permutation(np.arange(1, 1 + sum(doc_pages)
                                         + len(rows) * pages)))
    docs = [[next(ids) for _ in range(n)] for n in doc_pages]
    tables = np.zeros((len(rows), pages), np.int32)
    lengths = np.zeros((len(rows),), np.int32)
    for b, row in enumerate(rows):
        if row is None:
            continue
        doc, own = row
        lead = docs[doc] if doc is not None else []
        lengths[b] = min(len(lead) * PAGE + own, pages * PAGE - STEPS)
        held = -(-(int(lengths[b]) + STEPS) // PAGE)
        tables[b, :held] = (lead + [next(ids) for _ in range(pages)])[:held]
    return tables, lengths


def members_of(g):
    return [[int(r) for r in col if r >= 0] for col in g["rows"].T]


def check(tables, lengths, g):
    """Every property a kernel over the items relies on."""
    B, P = tables.shape
    item, pages, rows, slot = g["item"], g["pages"], g["rows"], g["slot"]
    W = item_room(B, P, CHUNK)
    assert item.shape == (4, W) and pages.shape == (CHUNK, W)
    assert rows.shape == (GROUP_ROWS, B) and slot.shape == (B,)
    assert all(a.dtype == np.int32 for a in (item, pages, rows, slot))
    live = set(np.flatnonzero(lengths > 0).tolist())
    mem = members_of(g)
    # every live row in exactly one group, a dead row in none
    assert sorted(r for m in mem for r in m) == sorted(live)
    for r in range(B):
        assert (slot[r] >= 0) == (r in live)
        if r in live:
            assert rows[slot[r] % GROUP_ROWS, slot[r] // GROUP_ROWS] == r
    n = int((item[3] & LIVE != 0).sum())
    assert (item[3, :n] & LIVE).all() and not item[3, n:].any()
    # a dead item repeats the last live one's block indexes
    if n:
        assert (item[:3, n:] == item[:3, n - 1: n]).all()
        assert (pages[:, n:] == pages[:, n - 1: n]).all()
    need = np.minimum(-(-(lengths.astype(int) + STEPS) // PAGE), P)
    read = 0
    for gi, m in enumerate(mem):
        if not m:
            assert not (item[0, :n] == gi).any()
            continue
        at = np.flatnonzero(item[0, :n] == gi)
        assert (np.diff(at) == 1).all()           # side by side
        assert item[3, at[0]] & FIRST and item[3, at[-1]] & LAST
        assert not (item[3, at[1:]] & FIRST).any()
        assert not (item[3, at[:-1]] & LAST).any()
        shared = at[item[2, at] < 0]
        run = len(shared)
        assert (item[1, shared] == np.arange(run)).all()
        assert (shared == at[:run]).all()          # the run comes first
        assert run == 0 or len(m) > 1
        # the run is the members' common leading run, under the
        # shortest member's whole pages
        assert run * CHUNK <= min(int(lengths[r]) // PAGE for r in m)
        for r in m:
            assert (tables[r, :run * CHUNK]
                    == tables[m[0], :run * CHUNK]).all()
        for w in shared:
            c = item[1, w]
            assert (pages[:, w]
                    == tables[m[0], c * CHUNK:(c + 1) * CHUNK]).all()
            read += CHUNK
        # behind the run each member reads its own pages, all of them
        for k, r in enumerate(m):
            own = at[item[2, at] == k]
            chunks = -(-int(need[r]) // CHUNK)
            assert (item[1, own] == np.arange(run, chunks)).all()
            for w in own:
                for i in range(CHUNK):
                    p = item[1, w] * CHUNK + i
                    if p < need[r]:
                        assert pages[i, w] == tables[r, p]
                        read += 1
                    else:                   # no copy: the page before
                        assert pages[i, w] == (pages[i, w - 1] if w else 0)
    assert g["held"] == int(need[sorted(live)].sum())
    assert g["read"] == read <= g["held"]
    return mem


@pytest.mark.parametrize("seed", range(12))
def test_any_batch_is_partitioned_into_groups_of_static_shape(seed):
    rng = np.random.default_rng(seed)
    B, P = int(rng.integers(1, 24)), int(rng.integers(3, 40))
    rows = [None if rng.random() < 0.2 else
            (None if rng.random() < 0.3 else int(rng.integers(4)),
             int(rng.integers(1, 5 * PAGE))) for _ in range(B)]
    tables, lengths = batch(rng, rows, P)
    check(tables, lengths, decode_groups(tables, lengths, page=PAGE,
                                         steps=STEPS, chunk=CHUNK))


def test_rows_of_one_document_form_groups_of_at_most_eight():
    rng = np.random.default_rng(0)
    rows = [(0, 20)] * 9 + [None] + [(1, 5), (None, 300), (1, 40)]
    tables, lengths = batch(rng, rows, 30, doc_pages=[17, 24])
    g = decode_groups(tables, lengths, page=PAGE, steps=STEPS, chunk=CHUNK)
    mem = check(tables, lengths, g)
    sizes = sorted(len(m) for m in mem if m)
    assert sizes == [1, 1, 2, 8]                   # nine rows split at 8
    # document 1's two rows share 24 pages = 3 whole chunks
    pair = next(m for m in mem if sorted(m) == [10, 12])
    gi = mem.index(pair)
    assert int(((g["item"][0] == gi) & (g["item"][2] < 0)
                & (g["item"][3] & LIVE != 0)).sum()) == 3


def test_a_row_joins_the_rows_it_shares_most_with():
    """Rows 0 and 1 share a chunk with rows 2 and 3, which share four:
    two groups, not one group of a one-chunk run."""
    ids = np.arange(1, 200)
    tables = np.zeros((4, 40), np.int32)
    tables[:, :8] = ids[:8]
    tables[:2, 8:34] = ids[8:34]
    tables[2:, 8:34] = ids[40:66]
    for r in range(4):
        tables[r, 34:36] = ids[100 + 2 * r: 102 + 2 * r]
    lengths = np.full((4,), 34 * PAGE + 5, np.int32)
    g = decode_groups(tables, lengths, page=PAGE, steps=STEPS, chunk=CHUNK)
    mem = check(tables, lengths, g)
    assert sorted(sorted(m) for m in mem if m) == [[0, 1], [2, 3]]
    assert g["read"] == 2 * 32 + 4 * 3            # a run of 4 chunks each


def test_the_cells_shape_reads_a_quarter_of_what_its_rows_hold():
    """32 rows, four to each of 8 documents of 256 pages, two pages of
    a row's own: 8 x 256 + 32 x 2 pages read of the 32 x 258 held; the
    same rows over documents of their own read what they hold."""
    rng = np.random.default_rng(1)
    B, P, page = 32, 258, 128
    ids = rng.permutation(np.arange(1, 1 + 8 * 256 + B * 258))
    tables = np.zeros((B, P), np.int32)
    alone = np.zeros((B, P), np.int32)
    lengths = np.zeros((B,), np.int32)
    docs = rng.permutation(np.arange(B) % 8)
    for b in range(B):
        tables[b, :256] = ids[docs[b] * 256:(docs[b] + 1) * 256]
        tables[b, 256:] = ids[8 * 256 + 2 * b: 8 * 256 + 2 * b + 2]
        alone[b] = ids[8 * 256 + b * 258: 8 * 256 + (b + 1) * 258]
        lengths[b] = 256 * page + 121 + int(rng.integers(0, 50))
    g = decode_groups(tables, lengths, page=page, steps=8, chunk=8)
    assert (g["held"], g["read"]) == (32 * 258, 8 * 256 + 32 * 2)
    assert sorted(len(m) for m in members_of(g) if m) == [4] * 8
    assert int((g["item"][3] & LIVE != 0).sum()) == 8 * 32 + 32
    g = decode_groups(alone, lengths, page=page, steps=8, chunk=8)
    assert g["held"] == g["read"] == 32 * 258
    assert int((g["item"][3] & LIVE != 0).sum()) == 32 * 33
    assert (g["item"][2] >= 0).all()               # no shared item


def test_no_live_row_is_no_live_item():
    g = decode_groups(np.zeros((3, 9), np.int32), np.zeros((3,), np.int32),
                      page=PAGE, steps=STEPS, chunk=CHUNK)
    assert not g["item"][3].any() and (g["slot"] == -1).all()
    assert g["held"] == g["read"] == 0
