"""Rows of a decode batch that hold the same pages, grouped on the
host: what a paged decode kernel needs to read a shared page ONCE for
the rows that share it.  NumPy only and blind to the model family — it
reads block tables and lengths (PagedKVCache.tables / .lengths) and
returns arrays of static shapes that ride a chunk program as arguments
beside the tables (ops/sparse_attention's decode walk is the first
kernel that takes them).

A GROUP is up to `members` live rows whose tables begin with the same
run of pages.  Its SHARED RUN is the longest common run of leading
table entries, in whole programs of `chunk` pages, that lies under
every member's whole pages at dispatch: a chunk's steps append behind
`lengths`, so no step writes into the run, and every position of it is
under every member's length at every step.  What a member's table
holds behind the run is its OWN: read for it alone.  A row alone with
its pages is a group of one whose pages are all its own; a dead row is
in no group.

The kernel's grid is the list of ITEMS: one program each, a group's
items side by side — the run's chunks (every member attends them, its
queries stacked with the others'), then each member's own chunks.  The
list has room for every row alone (rows x chunks of a table); the
items past the last live one repeat its block indexes, so they cost a
grid step and no copy.
"""
from __future__ import annotations

import numpy as np

# rows a group holds at the most: members x (query heads a kv head) is
# the M of the kernel's two products
GROUP_ROWS = 8
# an item's flags
LIVE, FIRST, LAST = 1, 2, 4


def item_room(rows: int, pages: int, chunk: int) -> int:
    """Items a batch of `rows` tables of `pages` entries can need."""
    return rows * -(-pages // chunk)


def decode_groups(tables, lengths, *, page: int, steps: int, chunk: int,
                  members: int = GROUP_ROWS) -> dict:
    """Groups and items of one chunk dispatch.  tables: (B, P) page
    ids; lengths: (B,) tokens a row holds at dispatch (0: dead); page:
    tokens a page; steps: decode steps of the chunk (its last step
    attends lengths + steps keys); chunk: pages one item reads.

    Returns int32 arrays whose shapes depend on (B, P, chunk, members)
    alone, W = item_room(B, P, chunk):

      item   (4, W)  a row an item: the group's slot g, the chunk c of
                     the members' tables it reads, the member (its
                     index in the group) whose own pages these are or
                     -1 for a chunk of the shared run, flags LIVE |
                     FIRST | LAST (of its group)
      pages  (chunk, W)  the page ids the item reads; a page the item
                     has no use for (past what its row needs) repeats
                     the item before it, as every dead item does
      rows   (members, B)  the member rows of each group slot, -1 pads
      slot   (B,)    g * members + member index of a live row, -1 dead
      live   ()      the live items: they come first, and a grid need
                     not run past them
      held   pages the live rows' tables hold (what a walk a row reads)
      read   pages the items read: a shared page once a group
    """
    tables = np.asarray(tables)
    lengths = np.asarray(lengths).astype(np.int64)
    B, P = tables.shape
    W = item_room(B, P, chunk)
    item = np.zeros((4, W), np.int32)
    pages = np.zeros((chunk, W), np.int32)
    rows = np.full((members, B), -1, np.int32)
    slot = np.full((B,), -1, np.int32)
    live = np.flatnonzero(lengths > 0)
    need = np.minimum(-(-(lengths + steps) // page), P)
    whole = np.minimum(lengths // page, P)

    # the live rows in the order of their leading pages; an entry past
    # a row's whole pages matches no other row's
    lead = np.where(np.arange(P)[None, :] < whole[:, None], tables,
                    -1 - np.arange(B)[:, None])
    order = live[np.lexsort(lead[live].T[::-1])] if live.size else live
    same = lead[order[1:]] == lead[order[:-1]]
    # whole chunks a row shares with the next in the order (with any
    # row further on it shares the least of the steps between)
    near = np.append(np.where(same.all(1), P, same.argmin(1)) // chunk,
                     0).astype(np.int64)

    groups: list[tuple[list[int], int]] = []      # (member rows, run)
    for i, r in enumerate(order):
        if groups and i:
            mem, run = groups[-1]
            common = min(run, int(near[i - 1]))
            k = len(mem)
            # a row joins the group before it if it shares a chunk
            # with it, no less than with the row behind it, and the
            # group reads no more pages with it than without
            if k < members and common >= 1 and common >= near[i] \
                    and k * common >= (k - 1) * run:
                mem.append(int(r))
                groups[-1] = (mem, common)
                continue
        groups.append(([int(r)], int(whole[r]) // chunk))

    # the items, a group's side by side: (slot, chunk, member, row read)
    its = []
    for g, (mem, run) in enumerate(groups):
        run = run if len(mem) > 1 else 0
        rows[:len(mem), g] = mem
        slot[mem] = g * members + np.arange(len(mem))
        own = [np.arange(run, -(-int(need[r]) // chunk)) for r in mem]
        c = np.concatenate([np.arange(run), *own])
        m = np.concatenate([np.full(run, -1),
                            np.repeat(np.arange(len(mem)),
                                      [len(o) for o in own])])
        flags = np.full(len(c), LIVE)
        flags[0] |= FIRST
        flags[-1] |= LAST
        its.append(np.stack([np.full(len(c), g), c, m, flags,
                             np.asarray(mem)[np.maximum(m, 0)]]))
    if not its:
        return {"item": item, "pages": pages, "rows": rows, "slot": slot,
                "live": np.int32(0), "held": 0, "read": 0}
    its = np.concatenate(its, 1)
    w = its.shape[1]
    item[:, :w] = its[:4]
    item[:3, w:] = its[:3, -1:]
    at = its[1][None, :] * chunk + np.arange(chunk)[:, None]
    ok = at < need[its[4]][None, :]
    # a page of no use repeats the page the item before read there
    last = np.maximum.accumulate(np.where(ok, np.arange(w)[None, :], -1), 1)
    ids = tables[its[4][None, :], np.minimum(at, P - 1)]
    pages[:, :w] = np.where(
        last >= 0, np.take_along_axis(ids, np.maximum(last, 0), 1), 0)
    pages[:, w:] = pages[:, w - 1: w]
    held, read = int(need[live].sum()), int(ok.sum())
    return {"item": item, "pages": pages, "rows": rows, "slot": slot,
            "live": np.int32(w), "held": held, "read": read}
