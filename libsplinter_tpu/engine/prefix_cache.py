"""Host-side radix prefix cache over the block-paged KV pool.

Millions of requests share the same system prompts, few-shot headers,
and RAG boilerplate — and before this module every admission
re-prefilled and re-committed identical pages: a 67 ms bucket-64
prefill that could have been a host-side block-table write.  The
block-paged pool (models/decoder.PagedKVCache) already reads
exclusively through per-row block tables, so the ragged paged kernel
(ops/paged_attention) serves SHARED pages with zero changes — all the
sharing machinery is host-side:

  - **Refcounted pages** (PagedKVCache.refcounts): block tables from
    different rows point at the same full pages; a page returns to
    the free list only when its refcount hits zero.
  - **This tree**: full-page prefixes indexed by token ids, page
    granular — node j of a chain holds the pool page with the K/V of
    tokens [j*page, (j+1)*page) computed IN CONTEXT of the whole
    prefix (K/V at position p depend on every token before p, so a
    page is only reusable under the exact token prefix it was
    computed under — hence a radix tree, not a flat page hash).
  - **Copy-on-write** (PagedKVCache / CompletionModel._cow_fixups):
    a decode append whose target page is shared (or tree-frozen)
    copies the page first, so a writer never mutates a page another
    row — or a future joiner — reads.  Tree pages are otherwise
    FROZEN read-only; for int8 pools that means their per-page scales
    never rescale, which *removes* the stale-scale hazard
    quantize-on-commit pools otherwise carry.

  - **State snapshots** (models with per-row recurrent state,
    models/kda.py): pages alone cannot resume such a model — a hit is
    worth nothing without the recurrent state as it stood at the
    hit's last token.  A node may therefore own one of the pool's
    STATE SLOTS (PagedKVCache: `state`), holding the state after the
    node's last token.  When the bound pool says its model needs
    state, lookup()/lookup_tiered() return the longest match that
    ENDS at a node with a snapshot (deeper pages are not mapped;
    `last_cut` says how many tokens that gave up), insert() attaches
    the snapshot the prefill took at the prompt's last full page, and
    evicting a node frees its slot.  One snapshot weighs tens of
    pages (21.7 MB against 0.44 MB at Kimi-Linear's widths), so a
    prompt leaves ONE, at its last full page, and the budget is the
    pool's slot count.  When the pool needs a slot and none is free,
    evict_snapshot() gives up first a snapshot that is SUPERSEDED —
    the nodes below it form a chain without a branch that reaches a
    deeper snapshot, which serves every prompt the shallower one
    would (a growing session leaves such a chain a turn) — and among
    those, or failing any, the least recently restored.  A snapshot
    where paths branch (a shared document under many questions) is
    what LRU alone would also keep.  For a model without state none
    of this runs.

  - **Window pages** (models whose layers mix sliding-window and
    global attention, models/afmoe.py): the pool keeps such layers'
    pages in a group of their own (PagedKVCache.window) and gives
    them back as a row slides past them, so a node holds a
    global-group page and MAY hold a window-group page (`wbid`).  A
    hit can resume at a node only if the nodes that hold the window
    of its first new token — ceil(window / page) of them, ending at
    it — still hold theirs: lookup()/lookup_tiered() return the
    longest such match (`last_window` the tail's window pages to map,
    `last_window_cut` the tokens given up), insert() files the window
    pages the row still holds, and then lets go of those no resume
    needs any more: on a chain WITHOUT A BRANCH only the newest tail
    is kept (a growing session leaves a chain a turn; the tail behind
    the newest turn's tail is superseded).  Window pages at zero
    references are reclaimed on their own (reclaim_window), a page
    that never served a hit first: a node that loses its window page
    keeps its global one.

Lifecycle: pages enter the tree at admission (after the committing
row's prefill), while the donor row is still live — a mid-flight
joiner may map a prefix another row is actively decoding from (the
donor's appends only ever touch pages past its prompt).  When every
mapping row finishes, the page's refcount hits zero and it becomes
EVICTABLE: it stays allocated (and instantly re-mappable) until the
pool actually needs the page back, at which point eviction takes the
least-recently-matched zero-ref chain tails first.  Per-tenant page
quotas (engine/qos.py `parse_tenant_quotas`, surfaced through the
tenant ledger in the completer heartbeat) bound how much of the pool
any one tenant's prefixes may squat on.

Invariants the churn drill (tests/test_prefix_cache.py) pins:
refcount 0 <=> (free list membership XOR tree retention); no page is
ever in the free list while a table or the tree references it; a
row's mapped prefix path has monotonically non-increasing refcounts
root -> tail (rows always map whole prefixes), so a zero-ref node's
entire subtree is zero-ref and leaf-first eviction can always make
progress.
"""
from __future__ import annotations

import dataclasses
import itertools

from ..utils.faults import fault


@dataclasses.dataclass
class PrefixCacheStats:
    """Counters the completer heartbeat publishes (prefix_* gauges in
    `spt metrics`, ring history in the telemetry lane, sparklines in
    `spt top`)."""

    hits: int = 0             # admissions matching >= 1 full page
    misses: int = 0           # admissions matching nothing
    hit_tokens: int = 0       # prompt tokens served from the tree
    inserts: int = 0          # pages registered
    evictions: int = 0        # pages reclaimed for the free list
    cow_copies: int = 0       # copy-on-write page copies
    quota_rejects: int = 0    # inserts skipped: tenant over quota
    bytes_saved: int = 0      # KV bytes not re-prefilled/committed
    state_evictions: int = 0  # snapshots given up for their slot
    window_evictions: int = 0  # window pages reclaimed or superseded


class _Node:
    __slots__ = ("toks", "bid", "parent", "children", "lru", "tenant",
                 "tier", "state", "state_lru", "wbid", "hot")

    def __init__(self, toks: tuple, bid: int, parent, tenant: int):
        self.toks = toks            # this page's token ids (exact)
        self.bid = bid              # pool block id holding its K/V
        self.parent = parent        # _Node | None (root child)
        self.children: dict[tuple, _Node] = {}
        self.lru = 0                # last-matched clock tick
        self.tenant = tenant
        # 0 = HBM-resident (bid is a live pool page), 1 = demoted to
        # the host-DRAM tier (bid is -1; the bytes live in the bound
        # HostTier and readmit() device_puts them back on a hit).
        # Leaf-first eviction demotes tails before parents, so on any
        # root->leaf path the tier-1 nodes are a contiguous SUFFIX —
        # the invariant lookup_tiered and readmit ride.
        self.tier = 0
        # the pool's state slot holding the recurrent state after this
        # page's last token (-1: none), and when it was last restored
        self.state = -1
        self.state_lru = 0
        # the window group's page of the same tokens (0: none, given
        # back or never filed), and whether the node ever served a hit
        # or extends a path that did: what eviction keeps longest
        self.wbid = 0
        self.hot = False


class PrefixCache:
    """One instance per continuous-batching completer, bound to its
    pool via attach() (re-bound — and emptied — whenever the lane
    rebuilds the pool: abort recovery, spec demotion).  All methods
    are called from the single lane thread; there is no locking, by
    the same single-owner contract as the pool's host scheduler."""

    def __init__(self, page: int, *, max_pages: int | None = None,
                 tenant_quotas: dict[int, int] | None = None,
                 default_quota: int | None = None):
        if page < 1:
            raise ValueError("page must be >= 1")
        self.page = page
        self.max_pages = max_pages
        self.tenant_quotas = dict(tenant_quotas or {})
        self.default_quota = default_quota
        self.stats = PrefixCacheStats()
        self._cache = None            # the bound PagedKVCache
        self._children: dict[tuple, _Node] = {}   # root level
        self._by_bid: dict[int, _Node] = {}
        self._tenant_pages: dict[int, int] = {}
        self._clock = itertools.count(1)
        # zero-ref tree pages, maintained INCREMENTALLY on the pool's
        # refcount 0<->1 transitions (on_zero_ref / on_ref) — the
        # admission path reads evictable_count per waiting request,
        # so an O(tree) scan there would tax the lane thread
        self._zero_ref = 0
        # host-DRAM spill tier (engine/kv_tier.HostTier): eviction
        # demotes frozen pages here instead of dropping them, and a
        # DRAM hit readmits via device_put instead of re-prefilling.
        # Bound by the owning lane (bind_tier) together with the
        # per-page export/import callables closed over its model+pool.
        self.tier = None
        self._export_page = None      # (bid) -> (bytes, bytes|None)
        self._import_page = None      # (bid, bytes, bytes|None)
        self._demoted = 0             # tier-1 node count (gauge)
        # nodes that own a state snapshot, and how many tokens the
        # last lookup gave up for want of one (module docstring)
        self._snapshots: dict[int, _Node] = {}    # state slot -> node
        self.last_cut = 0
        # window-group pages the tree holds, how many of them no row
        # maps, and what the last lookup found: the tail's window
        # pages to map and the tokens it gave up for want of one
        self._by_wbid: dict[int, _Node] = {}
        self._zero_ref_w = 0
        self.last_window: list[int] = []
        self.last_window_cut = 0
        # the page keys of the prompts being admitted: an admission
        # walks the tree up to four times over the same ids (lookup,
        # commit_hit, state_slot, insert), and at ~80 pages of 128
        # tokens each walk's tuples cost more than the walk.  One memo
        # a row of the pool: a round of hits seats all its rows
        # before it inserts any (completer.join_round)
        self._key_memo: dict[int, tuple] = {}

    def _keys(self, ids) -> list[tuple]:
        """The token-id tuple of every full page of `ids`, built once
        for as long as the caller keeps handing in the same object."""
        memo = self._key_memo.get(id(ids))
        if memo is None or memo[0] is not ids or memo[1] != len(ids):
            page = self.page
            flat = [int(t) for t in ids]
            memo = (ids, len(ids), [
                tuple(flat[j * page:(j + 1) * page])
                for j in range(len(flat) // page)])
            self._key_memo.pop(id(ids), None)
            while len(self._key_memo) >= max(
                    getattr(self._cache, "batch", 1), 1):
                del self._key_memo[next(iter(self._key_memo))]
            self._key_memo[id(ids)] = memo
        return memo[2]

    @property
    def needs_state(self) -> bool:
        return bool(getattr(self._cache, "needs_state", False))

    @property
    def _window(self):
        """The bound pool's window group (None: it has none)."""
        return getattr(self._cache, "window", None)

    # -- binding -----------------------------------------------------------

    def attach(self, cache) -> None:
        """Bind (or re-bind) to a pool.  The tree references pool
        block ids, so a rebuilt pool invalidates every node — the old
        pool's pages died with it and must not be returned anywhere.
        Host-tier shadows are keyed by node, so they die with the
        tree (the persistent warm layer, if any, survives and the
        owning lane re-loads it after re-binding)."""
        self._cache = cache
        self._children = {}
        self._by_bid = {}
        self._tenant_pages = {}
        self._zero_ref = 0
        self._demoted = 0
        self._snapshots = {}
        self.last_cut = 0
        self._by_wbid = {}
        self._zero_ref_w = 0
        self.last_window, self.last_window_cut = [], 0
        if self._window is not None and self.needs_state:
            raise ValueError("a pool with state slots AND a window "
                             "group is not served")
        if self.tier is not None:
            self.tier.clear()

    def bind_tier(self, tier, export_page=None,
                  import_page=None) -> None:
        """Arm the DRAM spill tier: `export_page(bid)` host-copies
        one frozen pool page, `import_page(bid, buf, sbuf)` scatters
        one back (models/decoder.py export_page_bytes /
        import_page_bytes, closed over the CURRENT pool — the lane
        re-binds after every pool rebuild)."""
        self.tier = tier
        self._export_page = export_page
        self._import_page = import_page

    # -- lookup / mapping ---------------------------------------------------

    def lookup(self, ids, upto: int | None = None
               ) -> tuple[list[int], int]:
        """Walk the tree over `ids` at page granularity (over its
        first `upto` tokens, where given).  Returns
        (matched block ids in prefix order, matched token count).
        PURE: no stats, no LRU touch — a lookup whose admission is
        then denied (backpressure, raced slot) must neither inflate
        the hit rate the runbook triages on nor refresh LRU stamps
        for a prefix that never got served.  The admitting caller
        records the outcome via commit_hit() / note_miss()."""
        page = self.page
        bids: list[int] = []
        states: list[int] = []
        wbids: list[int] = []
        cur = self._children
        for chunk in self._keys(ids)[:(len(ids) if upto is None
                                       else upto) // page]:
            node = cur.get(chunk)
            if node is None:
                break
            bids.append(node.bid)
            states.append(node.state)
            wbids.append(node.wbid)
            cur = node.children
        bids = bids[:self._state_cut(states)]
        bids = bids[:self._window_cut(wbids[:len(bids)])]
        return bids, len(bids) * page

    def _window_cut(self, wbids: list[int]) -> int:
        """How many of a match's HBM nodes a pool with a window group
        can resume on: the longest prefix whose last nodes — those
        that hold the window of the token after it — still hold their
        window pages (all of them for a pool without the group).
        Leaves that tail in `last_window` and the tokens given up in
        `last_window_cut`."""
        w = self._window
        keep = len(wbids)
        self.last_window, self.last_window_cut = [], 0
        if w is None:
            return keep
        run = 0                  # nodes in a row, ending here, with one
        best = 0
        for k, wb in enumerate(wbids, 1):
            run = run + 1 if wb > 0 else 0
            if run >= k - w.first_live(k * self.page):
                best = k
        self.last_window = wbids[w.first_live(best * self.page): best]
        self.last_window_cut = (keep - best) * self.page
        return best

    def _state_cut(self, states: list[int]) -> int:
        """How many of a match's HBM nodes a model with state can use:
        up to the deepest one that owns a snapshot (all of them for a
        model without state).  Leaves the tokens given up in
        `last_cut` — the one thing a lookup writes."""
        keep = len(states)
        if self.needs_state:
            while keep and states[keep - 1] < 0:
                keep -= 1
        self.last_cut = (len(states) - keep) * self.page
        return keep

    def state_slot(self, ids, match: int) -> int:
        """The state slot of the node a `match`-token hit ends at
        (-1: none), its restore clock touched."""
        node, cur = None, self._children
        for chunk in self._keys(ids)[:match // self.page]:
            node = cur.get(chunk)
            if node is None:
                return -1
            cur = node.children
        if node is None or node.state < 0:
            return -1
        node.state_lru = next(self._clock)
        return node.state

    def lookup_tiered(self, ids, upto: int | None = None
                      ) -> tuple[list[int], int, list["_Node"]]:
        """lookup() extended through the DRAM tier: returns
        (hbm_bids, hbm_match_tokens, tier_nodes) where tier_nodes are
        the consecutive DEMOTED nodes continuing the match past the
        HBM prefix (the tier-1-suffix invariant: demotion is
        leaf-first, so they can only trail).  The caller prices them
        as readmit cost — a device_put per page — against the
        re-prefill a miss would pay, and readmit() brings them back.
        PURE like lookup(): no stats, no LRU touch."""
        page = self.page
        bids: list[int] = []
        states: list[int] = []
        wbids: list[int] = []
        nodes: list[_Node] = []
        cur = self._children
        tier = self.tier
        for chunk in self._keys(ids)[:(len(ids) if upto is None
                                       else upto) // page]:
            node = cur.get(chunk)
            if node is None:
                break
            if node.tier:
                if tier is None or not tier.has(node):
                    break             # shadow gone: unservable tail
                nodes.append(node)
            elif nodes:
                break                 # defensive: HBM past a demote
            else:
                bids.append(node.bid)
                states.append(node.state)
                wbids.append(node.wbid)
            cur = node.children
        if self.needs_state:
            # no state rides the host tier: demoted pages end the match
            bids, nodes = bids[:self._state_cut(states)], []
        else:
            self.last_cut = 0
        keep = self._window_cut(wbids[:len(bids)])
        if keep < len(bids):
            bids, nodes = bids[:keep], []
        return bids, len(bids) * page, nodes

    def commit_hit(self, ids, match: int) -> None:
        """An admission actually mapped `match` tokens of `ids`: count
        the hit and LRU-touch the served path (re-walk — match/page
        node hops, cheap next to the admission it accompanies)."""
        tick = next(self._clock)
        cur = self._children
        for chunk in self._keys(ids)[:match // self.page]:
            node = cur.get(chunk)
            if node is None:
                break                  # evicted mid-admission: stale
            node.lru = tick
            node.hot = True
            cur = node.children
        self.stats.hits += 1
        self.stats.hit_tokens += match

    def note_miss(self) -> None:
        self.stats.misses += 1

    # -- insertion ----------------------------------------------------------

    def insert(self, ids, cache, row: int, tenant: int = 0,
               state: tuple[int, int] | None = None) -> int:
        """Register the FULL prompt pages of `row` (its table entries
        for pages [0, len(ids)//page)) under their token prefix.
        Pages already present are skipped (the hit path mapped them;
        the row's own duplicates stay private).  Returns pages
        inserted.  A page enters FROZEN: the pool will copy-on-write
        before any append could touch it, and for int8 pools its
        scale never rescales again.

        `state`: (state slot, token count) — the snapshot the prefill
        took after that many tokens (whole pages).  The node that
        ends there takes the slot over; if the walk does not reach
        it, or it has one already, the slot goes back to the pool."""
        if cache is not self._cache:
            if state is not None:
                cache.free_state_slot(state[0])
            return 0                  # stale pool: never adopt its ids
        page = self.page
        inserted = 0
        parent = None
        cur = self._children
        window = self._window
        depth = 0
        tick = next(self._clock)
        for j, chunk in enumerate(self._keys(ids)):
            node = cur.get(chunk)
            if node is None:
                bid = int(cache.tables[row, j])
                if bid == 0 or bid in self._by_bid:
                    break             # trash / already-owned: stop
                if not self._admit_page(tenant):
                    break
                node = _Node(chunk, bid, parent, tenant)
                node.hot = parent is not None and parent.hot
                cur[chunk] = node
                self._by_bid[bid] = node
                self._tenant_pages[tenant] = \
                    self._tenant_pages.get(tenant, 0) + 1
                self.stats.inserts += 1
                inserted += 1
                # write-through: the page is frozen as of THIS
                # registration, so its host shadow is taken now —
                # demotion later is pure bookkeeping, and the warm
                # snapshot covers the live set, not just evictees
                self._spill(node)
            elif node.tier:
                # a demoted node on the row's freshly prefilled path:
                # the row holds an identical page (same token chain =>
                # same K/V), so promote the node onto the row's block
                bid = int(cache.tables[row, j])
                if bid == 0 or bid in self._by_bid:
                    break
                if not self._admit_page(node.tenant):
                    break
                node.bid = bid
                node.tier = 0
                self._demoted -= 1
                self._by_bid[bid] = node
                self._tenant_pages[node.tenant] = \
                    self._tenant_pages.get(node.tenant, 0) + 1
                self.stats.inserts += 1
                inserted += 1
                if self.tier is not None and not self.tier.has(node):
                    self._spill(node)
            node.lru = tick
            if window is not None and node.wbid <= 0:
                # the window page the row still holds for these tokens
                # (none once the row has slid past them)
                wb = int(window.tables[row, j])
                if wb > 0 and wb not in self._by_wbid:
                    node.wbid = wb
                    self._by_wbid[wb] = node
            parent = node
            depth += 1
            cur = node.children
            if state is not None and (j + 1) * page == state[1] \
                    and node.state < 0:
                node.state, node.state_lru = state[0], tick
                self._snapshots[state[0]] = node
                state = None
        if state is not None:
            cache.free_state_slot(state[0])
        if window is not None and parent is not None:
            self._shed_window(parent, depth)
        return inserted

    # -- window pages -------------------------------------------------------

    def _drop_window(self, node) -> None:
        """The tree lets go of node's window page: back to the free
        list if no row maps it (a row that does gives it back itself)."""
        wb, w = node.wbid, self._window
        if wb <= 0:
            return
        del self._by_wbid[wb]
        node.wbid = 0
        if w.refcounts[wb] == 0:
            self._zero_ref_w -= 1
            w._free.append(wb)
        self.stats.window_evictions += 1

    def _shed_window(self, tail, depth: int) -> None:
        """After an insert that ends at `tail` (`depth` nodes deep):
        the nodes that hold the window of the token after it keep
        their window pages; above them, as far up as the path has NO
        BRANCH, the pages are superseded — whoever resumes on this
        path resumes at the newer tail — and go."""
        keep = depth - self._window.first_live(depth * self.page)
        node = tail
        while node is not None and len(node.children) <= 1:
            if keep > 0:
                keep -= 1
            elif node.wbid > 0:
                self._drop_window(node)
            else:
                break                 # shed by an earlier insert
            node = node.parent

    def retains_window(self, bid: int) -> bool:
        return bid in self._by_wbid

    def on_window_zero_ref(self, bid: int) -> bool:
        if bid in self._by_wbid:
            self._zero_ref_w += 1
            return True
        return False

    def on_window_ref(self, bid: int) -> None:
        if bid in self._by_wbid:
            self._zero_ref_w -= 1

    def window_evictable_count(self) -> int:
        return self._zero_ref_w if self._cache is not None else 0

    def window_pages(self) -> int:
        return len(self._by_wbid)

    def reclaim_window(self, n: int) -> int:
        """Give up to `n` zero-ref window pages back to the window
        group's free list: a page that never served a hit first, the
        least recently matched among equals.  The nodes keep their
        global pages."""
        w = self._window
        done = 0
        while done < n:
            victim = min((nd for nd in self._by_wbid.values()
                          if w.refcounts[nd.wbid] == 0), default=None,
                         key=lambda nd: (nd.hot, nd.lru))
            if victim is None:
                break
            self._drop_window(victim)
            done += 1
        return done

    # -- state snapshots ----------------------------------------------------

    def snapshots_held(self) -> int:
        return len(self._snapshots)

    def holds_snapshot(self, slot: int) -> bool:
        return slot in self._snapshots

    def _drop_snapshot(self, node) -> None:
        if node.state >= 0:
            del self._snapshots[node.state]
            self._cache.free_state_slot(node.state)
            node.state = -1
            self.stats.state_evictions += 1

    @staticmethod
    def _superseded(node) -> bool:
        """A chain without a branch leads from `node` to a deeper
        snapshot (module docstring)."""
        while len(node.children) == 1:
            node = next(iter(node.children.values()))
            if node.state >= 0:
                return True
        return False

    def evict_snapshot(self) -> bool:
        """Give one snapshot's slot back to the pool: a superseded one
        first, the least recently restored among equals.  The node
        keeps its page."""
        victim = min(self._snapshots.values(), default=None,
                     key=lambda n: (not self._superseded(n),
                                    n.state_lru))
        if victim is None:
            return False
        self._drop_snapshot(victim)
        return True

    def _spill(self, node) -> bool:
        """Take the host-DRAM shadow of a frozen page (fault site
        `tier.spill` — a death mid-spill leaves the HBM copy
        authoritative and the shadow simply untaken).  Overflow
        victims the tier's LRU drops are pruned: a tier-1 node
        without bytes is unservable."""
        tier = self.tier
        if tier is None or self._export_page is None or node.bid <= 0:
            return False
        try:
            fault("tier.spill")
            buf, sbuf = self._export_page(node.bid)
        except Exception:
            tier.spill_failures += 1
            return False              # HBM copy stays authoritative
        tier.spills += 1
        for dead in tier.put(node, buf, sbuf):
            self._drop_tiered(dead)
        return True

    def _drop_tiered(self, node) -> None:
        """A node's host shadow was dropped (tier capacity).  An
        HBM-resident node just loses its shadow (re-spilled on the
        next insert touch); a DRAM-resident one is unservable — prune
        its whole subtree (all tier-1 by the suffix invariant)."""
        if node.tier == 0:
            return
        siblings = (node.parent.children if node.parent is not None
                    else self._children)
        siblings.pop(node.toks, None)
        stack = [node]
        while stack:
            n2 = stack.pop()
            if self.tier is not None:
                self.tier.drop(n2)
            if n2.tier:
                self._demoted -= 1
            stack.extend(n2.children.values())
            n2.children = {}

    def _admit_page(self, tenant: int) -> bool:
        """Quota + global-cap gate for one insert.  Over quota, the
        tenant's own least-recent zero-ref tail evicts first; only
        when the tenant has nothing reclaimable is the insert
        skipped (quota_rejects)."""
        quota = self.tenant_quotas.get(tenant, self.default_quota)
        if quota is not None and \
                self._tenant_pages.get(tenant, 0) >= quota:
            if not self._evict_one(tenant=tenant):
                self.stats.quota_rejects += 1
                return False
        if self.max_pages is not None and \
                len(self._by_bid) >= self.max_pages:
            if not self._evict_one():
                return False
        return True

    # -- pool hooks (called by PagedKVCache) --------------------------------

    def retains(self, bid: int) -> bool:
        """True when the tree references `bid` — the pool asks on
        every COW decision (a frozen page must never be appended
        into, even at refcount 1)."""
        return bid in self._by_bid

    def on_zero_ref(self, bid: int) -> bool:
        """The pool's refcount for `bid` just hit zero.  True = the
        tree retains it (keep it OFF the free list; it is now
        evictable), False = not ours, free normally."""
        if bid in self._by_bid:
            self._zero_ref += 1
            return True
        return False

    def on_ref(self, bid: int) -> None:
        """`bid` went 0 -> 1 references (a joiner mapped an evictable
        page): it is pinned again, not reclaimable."""
        if bid in self._by_bid:
            self._zero_ref -= 1

    def reclaim(self, n: int) -> int:
        """Evict up to `n` least-recently-matched zero-ref pages back
        to the pool's free list (leaf-first; evicting a tail exposes
        its parent).  Returns pages actually reclaimed — the pool's
        allocator calls this when its free list runs dry."""
        done = 0
        while done < n and self._evict_one():
            done += 1
        return done

    def _evict_one(self, tenant: int | None = None) -> bool:
        cache = self._cache
        if cache is None:
            return False
        victim = None
        for node in self._by_bid.values():
            if any(c.tier == 0 for c in node.children.values()):
                continue              # leaf-first among HBM residents
                                      # (cascade exposes it; tier-1
                                      # children already gave back
                                      # their pages)
            if cache.refcounts[node.bid] != 0:
                continue              # mapped by a live row
            if tenant is not None and node.tenant != tenant:
                continue
            # a page that never served a hit goes before one that did
            if victim is None or (node.hot, node.lru) < (victim.hot,
                                                         victim.lru):
                victim = node
        if victim is None:
            return False
        tier = self.tier
        if tier is not None and not tier.has(victim):
            # no shadow yet (write-through failed or was LRU-dropped):
            # one more chance to demote instead of drop
            self._spill(victim)
        bid = victim.bid
        self._drop_snapshot(victim)   # its page goes: so does its state
        self._drop_window(victim)     # and its window page
        if tier is not None and tier.has(victim):
            # DEMOTE: the HBM page returns to the pool, the node
            # survives DRAM-resident — a future hit readmits it with
            # a device_put instead of a re-prefill.  Same path parks
            # paused sessions' prefixes.
            del self._by_bid[bid]
            self._tenant_pages[victim.tenant] = \
                max(0, self._tenant_pages.get(victim.tenant, 0) - 1)
            self._zero_ref -= 1        # victims are zero-ref by test
            cache._free.append(bid)
            victim.bid = -1
            victim.tier = 1
            self._demoted += 1
            tier.demotions += 1
            self.stats.evictions += 1
            return True
        siblings = (victim.parent.children if victim.parent is not None
                    else self._children)
        siblings.pop(victim.toks, None)
        # dropping an interior node strands any tier-1 children it
        # still carried (their shadows become unreachable chains)
        for child in victim.children.values():
            child.parent = None
            self._drop_tiered(child)
        victim.children = {}
        del self._by_bid[bid]
        self._tenant_pages[victim.tenant] = \
            max(0, self._tenant_pages.get(victim.tenant, 0) - 1)
        self._zero_ref -= 1            # victims are zero-ref by test
        cache._free.append(bid)
        self.stats.evictions += 1
        return True

    # -- DRAM tier: readmission + warm restore ------------------------------

    def readmit(self, nodes, cache) -> list[int]:
        """Bring demoted pages back to HBM in path order: alloc +
        device_put + re-registration, no re-prefill (fault site
        `tier.readmit` fires before each page's alloc so the chaos
        drill can die between a DRAM hit and its import — the shadow
        stays intact and the node stays DRAM-resident).  Pages return
        holding refcount 1; the caller transfers that reference into
        the admitted row's block table (decref-then-map_shared, like
        any freshly committed page).  Stops at the first failure —
        the admission simply prefills the remaining suffix."""
        if cache is not self._cache or self.tier is None \
                or self._import_page is None:
            return []
        tier = self.tier
        out: list[int] = []
        for node in nodes:
            if node.tier == 0:
                break                  # raced back already: stale list
            ent = tier.get(node)       # LRU-touches the shadow
            if ent is None:
                break
            try:
                fault("tier.readmit")
                bid = cache._alloc_page()
            except Exception:
                tier.readmit_failures += 1
                break
            try:
                self._import_page(bid, ent[0], ent[1])
            except Exception:
                cache.refcounts[bid] = 0
                cache._free.append(bid)
                tier.readmit_failures += 1
                break
            node.bid = bid
            node.tier = 0
            self._demoted -= 1
            self._by_bid[bid] = node
            self._tenant_pages[node.tenant] = \
                self._tenant_pages.get(node.tenant, 0) + 1
            node.lru = next(self._clock)
            tier.readmits += 1
            out.append(bid)
        return out

    def adopt_tiered(self, ids, tenant: int = 0):
        """Warm-restore adoption: create (or extend) the chain of
        DRAM-resident nodes covering `ids`' full pages and return the
        tail node (None for sub-page chains).  Restored nodes carry
        no HBM page — the first hit readmits them."""
        page = self.page
        n_full = len(ids) // page
        if n_full == 0:
            return None
        cur = self._children
        parent = None
        node = None
        for j in range(n_full):
            chunk = tuple(int(t) for t in ids[j * page:(j + 1) * page])
            node = cur.get(chunk)
            if node is None:
                node = _Node(chunk, -1, parent, tenant)
                node.tier = 1
                self._demoted += 1
                cur[chunk] = node
            parent = node
            cur = node.children
        return node

    # -- gauges -------------------------------------------------------------

    def evictable_count(self) -> int:
        """Zero-ref tree pages: reclaimable capacity the admission
        path may count on top of the free list (a zero-ref node's
        whole subtree is zero-ref — see the module invariants — so
        every one of them is reachable by leaf-first eviction).
        O(1): maintained incrementally on the pool's refcount
        transitions; the churn drill pins it against a brute-force
        recount."""
        return self._zero_ref if self._cache is not None else 0

    def shared_pages(self) -> int:
        return len(self._by_bid)

    def demoted_pages(self) -> int:
        """DRAM-resident (tier 1) node count — the heartbeat's tier
        occupancy gauge, O(1) like evictable_count."""
        return self._demoted

    def tenant_pages(self) -> dict[int, int]:
        return {t: n for t, n in self._tenant_pages.items() if n}


# ---------------------------------------------------------------- the seat

@dataclasses.dataclass(frozen=True)
class Join:
    """One seated request as its model's `join` sees it: the batch
    row, the prompt's ids, how many of them the row's table maps
    already, whether that is a prefix hit (the suffix prefills atop
    it) or a miss (the whole prompt prefills), the snapshot the
    prefill leaves — (state slot, token count) or None —, and whether
    the lane has zeroed the row's state slot already (a miss of a
    model with state slots, zeroed at its seat under a span of its
    own)."""

    row: int
    ids: list
    match: int
    hit: bool
    snap: tuple[int, int] | None = None
    zeroed: bool = False


class Seat:
    """The PAGES side of one admission, for the unified lane
    (completer.fill_rows) and the prefill lane (disagg._handoff_one)
    alike, in the three steps a lane sequences inside its own spans:

      walk()  PURE: the tiered prefix walk and what it may not use.
      plan()  PURE: the reservation and whether the pool holds it —
              a denied request stays WAITING, untouched.
      map()   after the claim: the table writes, the tree's counters,
              the row's reservation.

    A pool without a prefix tree (prefix sharing off, the paired
    speculative pools) skips walk(): every prompt is a miss.

    `keep_suffix` is the prefill lane's variant: the hand-off needs
    the last position's logits for the first token (the unified lane's
    replay of a fully cached prompt needs a decode chunk that lane
    never runs), so the walk ends a token short of the prompt and a
    fully covered prompt gives up the LAST page of its match — a
    host-tier node where the match ends in one (it costs nothing
    readmitted yet; an HBM page forfeits committed work)."""

    def __init__(self, cache, ids, *, keep_suffix: bool = False):
        self.cache, self.pc, self.ids = cache, cache.prefix_cache, ids
        self.keep_suffix = keep_suffix
        self.hit_bids: list[int] = []   # HBM pages the hit maps
        self.tier_nodes: list = []      # demoted pages trailing them
        self.match = 0                  # tokens the row's table maps
        # tokens the walk gave up for want of a snapshot / of a window
        # tail, and the window group's pages the hit resumes on
        self.cut = self.wcut = 0
        self.wtail: list[int] = []
        self.tail_shared = False        # some row still reads that tail
        self.full_cover = False
        self.reserve = self.need = self.pinned = 0
        # the snapshot the join should leave (a token count; None: none)
        # and the slot a hit's state is restored from
        self.snap_at = self.state_src = None

    @property
    def suffix(self):
        """The tokens left to prefill."""
        return self.ids[self.match:]

    def walk(self) -> None:
        cache, pc, ids = self.cache, self.pc, self.ids
        page = cache.page
        # a model with state resumes STRICTLY below its last token and
        # only where a snapshot sits: replaying the last token, as a
        # fully cached prompt does, would apply it to the state twice
        bids, match, nodes = pc.lookup_tiered(
            ids, len(ids) - 1 if self.keep_suffix or cache.needs_state
            else None)
        if match + len(nodes) * page == len(ids) and len(ids) < 2:
            # a fully-covered 1-token prompt would enter at lengths 0 —
            # the DEAD-row sentinel; serve it as a miss (page size 1 is
            # a test-only geometry anyway)
            bids, match, nodes = [], 0, []
        self.hit_bids, self.match, self.tier_nodes = bids, match, nodes
        self.cut, self.wcut = pc.last_cut, pc.last_window_cut
        self.wtail = list(pc.last_window) if bids else []

    def plan(self, reserve: int, replay_reserve: int | None = None
             ) -> int | None:
        """Price the seat: `reserve` is the token count the row's
        table must cover at the most, `replay_reserve` the same for a
        FULLY cached prompt (it enters one token short and the next
        decode chunk replays that token into a private copy of the
        shared tail page).  Every hit page is a page the pool does not
        need free: `need` counts the uncached rest only, plus that
        copy.  Returns None when the pool holds the seat, else the
        pages that must be available before it is worth asking again
        (the backpressure memo's value)."""
        cache, ids, bids = self.cache, self.ids, self.hit_bids
        self.full_cover = bool(bids or self.tier_nodes) and (
            self.match + len(self.tier_nodes) * cache.page == len(ids))
        cow = int(self.full_cover)
        self.reserve = replay_reserve if self.full_cover else reserve
        self.need = cache.pages_needed(self.reserve) - len(bids) + cow
        # zero-ref hit pages count in available_pages as reclaimable
        # supply, but map() is about to PIN them — they cannot also
        # feed this row's new allocations, so they come off the supply
        # side, or a warm near-full pool would admit a row whose
        # ensure() then comes up short
        self.pinned = sum(1 for b in bids if cache.refcounts[b] == 0)
        # the snapshot the join will leave: the state after the
        # prompt's last full page, if the hit ends short of it and the
        # pool keeps snapshots at all
        at = len(ids) // cache.page * cache.page
        self.snap_at = at if (
            cache.needs_state and self.pc is not None
            and cache.state_snapshots > 0 and at > self.match) else None
        # the window group's reservation: what the row holds at the
        # most while it joins and decodes, less the tail it maps
        # (pinned like the hit's global pages)
        w = cache.window
        short_w = w is not None and (
            w.join_pages(self.match, self.reserve) - len(self.wtail) + cow
            > w.available_pages - sum(
                1 for b in self.wtail if w.refcounts[b] == 0))
        if self.need > cache.available_pages - self.pinned or short_w \
                or (self.snap_at is not None
                    and not cache.state_slot_available()):
            return self.need + self.pinned
        return None

    def map(self, row: int) -> bool:
        """Seat the planned request in `row`: map the hit (readmitting
        its demoted tail), count it, reserve the row's pages.  False —
        the row freed again — when the reservation fails all the same:
        the pinned-aware gate of plan() makes that unreachable, but a
        row seated WITHOUT its reservation would strand mid-decode and
        abort the whole batch, so the lane re-queues it."""
        cache, pc, ids = self.cache, self.pc, self.ids
        if self.hit_bids or self.tier_nodes:
            # the chaos matrix crashes HERE (mid table-mapping, after
            # the claim): the restarted lane rebuilds pool + tree from
            # scratch, so a death between refcount bumps strands nothing
            fault("completer.prefix_map")
            if self.hit_bids:
                # pin the HBM prefix FIRST: readmission allocations
                # below can trigger reclaim, and an unpinned zero-ref
                # hit page would be fair game for the very eviction
                # pass serving it
                cache.map_shared(row, self.hit_bids)
                w = cache.window
                if w is not None:
                    self.tail_shared = any(w.refcounts[b] > 0
                                           for b in self.wtail)
                    w.map_tail(row, len(self.hit_bids) - len(self.wtail),
                               self.wtail)
            if self.tier_nodes:
                # DRAM hit: readmitted pages come back holding refcount
                # 1; drop each to zero-ref (tree-retained, off the free
                # list) then map — map_shared's 0→1 bump re-pins them
                # for this row with the tree reference accounted
                # exactly once.  A partial readmission (pool pressure,
                # injected fault) just shortens the hit — the rest
                # re-prefills
                tier_bids = pc.readmit(self.tier_nodes, cache)
                for b in tier_bids:
                    cache._decref(b)
                if tier_bids:
                    cache.map_shared(row, tier_bids)
                self.hit_bids = self.hit_bids + tier_bids
                self.match += len(tier_bids) * cache.page
                if len(tier_bids) < len(self.tier_nodes):
                    self.full_cover = False
            if self.hit_bids:
                cache.lengths[row] = (len(ids) - 1 if self.full_cover
                                      else self.match)
                if cache.needs_state:
                    self.state_src = pc.state_slot(ids, self.match)
                    if self.state_src < 0:
                        raise RuntimeError("a hit ends at a node "
                                           "without a state snapshot")
        if pc is not None:
            if self.hit_bids:
                # hit/LRU recorded only now — a denied or raced
                # admission must not inflate the hit rate the runbook
                # triages on
                pc.commit_hit(ids, self.match)
                pc.stats.bytes_saved += \
                    self.match * cache.kv_bytes_per_token()
            else:
                pc.note_miss()       # nothing matched, or no readmit held
        if cache.ensure(row, self.reserve):
            return True
        cache.free_row(row)
        return False
