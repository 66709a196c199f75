"""The block-paged continuous-batching lane (completer.run_continuous
over PagedKVCache): token-exact paged-vs-dense serving, the
no-shared-window joiner guarantee, pool backpressure, page-leak
freedom across request lifecycles, heartbeat gauges, and speculative
demotion.  `make decode-check` runs this file +
tests/test_paged_attention.py.
"""
from __future__ import annotations

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from libsplinter_tpu import Store
from libsplinter_tpu.engine import protocol as P
from libsplinter_tpu.engine.completer import Completer
from libsplinter_tpu.models.decoder import CompletionModel, DecoderConfig


def _mkstore(tmp_path, tag, **kw):
    name = f"/spt-{tag}-{tmp_path.name}"
    Store.unlink(name)
    kw.setdefault("nslots", 128)
    kw.setdefault("max_val", 4096)
    kw.setdefault("vec_dim", 8)
    return name, Store.create(name, **kw)


def _submit(st, key, prompt):
    st.set(key, prompt)
    st.label_or(key, P.LBL_INFER_REQ)
    st.bump(key)


def _await_ready(st, keys, timeout=75):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(st.labels(k) & P.LBL_READY for k in keys):
            return True
        time.sleep(0.05)
    return False


def _run_bg(comp, stop_after=90.0):
    th = threading.Thread(
        target=comp.run_continuous,
        kwargs=dict(idle_timeout_ms=20, stop_after=stop_after),
        daemon=True)
    th.start()
    time.sleep(0.2)
    return th


def test_paged_continuous_token_exact_vs_dense(tmp_path):
    """Greedy completions must be byte-identical whether the keys
    were served through the dense batched drain or the paged
    continuous lane — the paged-vs-dense token-exactness bar at a
    fixed weight seed (dense == serial is already pinned by
    tests/test_batch_decode.py)."""
    out: dict[str, bytes] = {}
    model = CompletionModel(
        DecoderConfig.tiny(dtype=jnp.float32), buckets=(32,),
        temp=0.0, seed=1)
    for tag in ("dense", "paged"):
        name, st = _mkstore(tmp_path, f"pvd-{tag}")
        try:
            comp = Completer(st, model=model, max_new_tokens=10,
                             flush_tokens=4, template="none",
                             batch_cap=4, page_size=16)
            comp.attach()
            for i in range(3):
                _submit(st, f"q/{i}", f"say {i} things")
            if tag == "paged":
                th = _run_bg(comp)
                assert _await_ready(st, [f"q/{i}" for i in range(3)])
                comp.stop()
                th.join(timeout=5)
            else:
                assert comp.run_once() == 3
            out[tag] = b"|".join(
                st.get(f"q/{i}").rstrip(b"\0") for i in range(3))
        finally:
            st.close()
            Store.unlink(name)
    assert out["dense"] == out["paged"]


@pytest.mark.slow
def test_paged_joiner_exceeding_dense_window_untruncated(tmp_path):
    """THE no-shared-window regression test: while a short row is
    mid-decode, a joiner arrives whose prompt is longer than the
    dense batch's remaining window would have allowed (dense
    join_budget would defer or clip it).  Paged serving admits it
    immediately, keeps the FULL prompt, and its completion is
    byte-identical to serving it alone."""
    model = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32,
                                               max_len=128),
                            buckets=(16, 64), temp=0.0, seed=1)
    # 160 byte tokens: far past the dense live batch's join_budget
    # (16 at pos=16), inside the paged lane's own per-row budget
    long_prompt = ("tok " * 40).encode()

    # ground truth: the long prompt served ALONE through the SAME
    # paged lane (identical context budget), nobody else in the batch
    name, st = _mkstore(tmp_path, "alone")
    try:
        comp = Completer(st, model=model, max_new_tokens=30,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16)
        comp.attach()
        th = _run_bg(comp)
        _submit(st, "long", long_prompt)
        assert _await_ready(st, ["long"]), comp.stats
        comp.stop()
        th.join(timeout=5)
        alone = st.get("long").rstrip(b"\0")
    finally:
        st.close()
        Store.unlink(name)

    name, st = _mkstore(tmp_path, "joined")
    try:
        comp = Completer(st, model=model, max_new_tokens=30,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16)
        comp.attach()
        th = _run_bg(comp, stop_after=120.0)
        _submit(st, "short", b"hi")
        time.sleep(0.8)                # batch live, short mid-decode
        _submit(st, "long", long_prompt)
        assert _await_ready(st, ["short", "long"], timeout=100), \
            comp.stats
        comp.stop()
        th.join(timeout=5)
        val = st.get("long").rstrip(b"\0")
        assert val.startswith(long_prompt.rstrip()), "prompt clipped"
        assert val == alone, \
            "joiner's completion differs from serving it alone"
        assert st.labels("short") & P.LBL_READY
    finally:
        st.close()
        Store.unlink(name)


def test_paged_pool_backpressure_and_recovery(tmp_path):
    """A pool too small for two concurrent worst-case rows admits one
    request, backpressures the second (it STAYS WAITING, untouched),
    and serves it after the first finishes — join_backpressure counts
    the deferral and no pages leak."""
    name, st = _mkstore(tmp_path, "bp")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128,
                                                   dtype=jnp.float32),
                                buckets=(16, 32), temp=0.0)
        # 8 pages of 16 = one full window: the second worst-case
        # reservation (prompt + max_new) cannot fit while the first
        # row is live
        comp = Completer(st, model=model, max_new_tokens=100,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16, pool_pages=8)
        comp.attach()
        th = _run_bg(comp, stop_after=120.0)
        _submit(st, "first", b"aaaa bbbb cccc dddd")
        _submit(st, "second", b"eeee ffff gggg hhhh")
        assert _await_ready(st, ["first", "second"], timeout=100), \
            comp.stats
        comp.stop()
        th.join(timeout=5)
        assert comp.stats.completions == 2
        assert comp.stats.join_backpressure > 0, comp.stats
        assert comp._paged_cache.used_pages == 0, "pages leaked"
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_paged_lifecycle_frees_pages_and_counts(tmp_path):
    """Staggered arrivals across several chunks: every key gets the
    full label protocol, and after the drain the pool is empty (every
    finished row returned all its pages).  Slow tier: the fast sweep
    covers the same protocol via tests/test_continuous.py and the
    leak check via the backpressure test."""
    name, st = _mkstore(tmp_path, "life")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=24,
                         flush_tokens=4, template="none", batch_cap=4,
                         page_size=16)
        comp.attach()
        th = _run_bg(comp)
        for i in range(2):
            _submit(st, f"w1/{i}", f"first wave {i}")
        time.sleep(1.0)
        for i in range(3):
            _submit(st, f"w2/{i}", f"second wave {i}")
        keys = [f"w1/{i}" for i in range(2)] + \
            [f"w2/{i}" for i in range(3)]
        assert _await_ready(st, keys), comp.stats
        comp.stop()
        th.join(timeout=5)
        for k in keys:
            labels = st.labels(k)
            assert labels & P.LBL_READY, (k, comp.stats)
            assert not labels & (P.LBL_INFER_REQ | P.LBL_SERVICING), k
            assert len(st.get(k).rstrip(b"\0")) > len(k) + 8
        assert comp.stats.completions == 5
        assert comp._paged_cache.used_pages == 0, "pages leaked"
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_paged_heartbeat_pool_gauges(tmp_path):
    """The completer heartbeat carries the paged-pool gauges
    (pages_free / pages_used -> sptpu_completer_pages_{free,used})
    once the continuous lane has a pool.  Slow tier: warmup_paged
    dominates the runtime and the gauges ride every backpressure /
    churn assertion too (tier-1 870 s budget)."""
    name, st = _mkstore(tmp_path, "hb")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16,), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=8,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16)
        comp.attach()
        comp.warmup_paged()            # creates the pool
        comp.publish_stats()
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert snap["pages_used"] == 0
        assert snap["pages_free"] == comp._paged_cache.free_pages
        assert "join_backpressure" in snap
        assert "live_tokens" in snap
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_paged_continuous_traces_requests(tmp_path, monkeypatch):
    """Satellite: the continuous lane stamps CONT_INFER_STAGES spans
    and records client-stamped (LBL_TRACED) requests in the flight
    recorder — `spt trace tail` works on the batched lane.  Slow
    tier: tier-1 870 s budget (`make check`'s full sweep runs it)."""
    from libsplinter_tpu.engine import completer as cmod

    monkeypatch.setattr(cmod.tracer, "enabled", True)
    cmod.tracer.reset()
    name, st = _mkstore(tmp_path, "trace")
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=12,
                         flush_tokens=4, template="none", batch_cap=2,
                         page_size=16)
        comp.attach()
        st.set("traced", b"tell me a story")
        st.label_or("traced", P.LBL_INFER_REQ)
        tid = P.stamp_trace(st, "traced")
        assert tid is not None
        st.bump("traced")
        th = _run_bg(comp)
        assert _await_ready(st, ["traced"]), comp.stats
        comp.stop()
        th.join(timeout=5)
        recs = comp.recorder.tail(8)
        assert recs, "traced request missing from the flight recorder"
        rec = recs[-1]
        assert rec["id"] == tid and rec["key"] == "traced"
        stages = {name for name, _ in rec["events"]}
        assert "join" in stages and "decode" in stages, rec
        assert stages <= set(P.CONT_INFER_STAGES), rec
        # the span histograms publish under the infer.* prefix so the
        # heartbeat quantiles + `spt metrics` pick them up
        snap = cmod.tracer.snapshot()
        assert "infer.join" in snap and "infer.decode" in snap
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_spec_acceptance_heartbeat_and_demotion(tmp_path):
    """Satellite: a speculative model with hopeless acceptance
    publishes sptpu_completer_spec_acceptance and is demoted to its
    target below --spec-min-acceptance; serving continues.  Slow
    tier for the 870 s tier-1 budget — `make decode-check` runs the
    whole file (no slow filter), so the gate keeps this test."""
    from libsplinter_tpu.models import SpeculativeCompletionModel

    name, st = _mkstore(tmp_path, "spec")
    try:
        # disjoint seeds: the draft proposes junk the target rejects
        t = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(16,), temp=0.0, seed=2)
        d = CompletionModel(
            DecoderConfig.tiny(dtype=jnp.float32, layers=1),
            buckets=(16,), temp=0.0, seed=99)
        spec = SpeculativeCompletionModel(t, d, gamma=4)
        comp = Completer(st, model=spec, max_new_tokens=40,
                         flush_tokens=4, template="none", batch_cap=1,
                         spec_min_acceptance=0.95)
        comp.attach()
        _submit(st, "q1", b"first question")
        assert comp.run_once() == 1
        comp.publish_stats()
        snap = json.loads(st.get(P.KEY_COMPLETE_STATS).rstrip(b"\0"))
        assert "spec_acceptance" in snap
        assert snap["spec_acceptance"] < 0.95
        assert comp.stats.spec_demotions == 1, comp.stats
        assert comp._model is t, "completer still speculative"
        # plain decode keeps serving after the demotion
        _submit(st, "q2", b"second question")
        assert comp.run_once() == 1
        assert st.labels("q2") & P.LBL_READY
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_spec_demotion_respects_floor_zero(tmp_path):
    """--spec-min-acceptance 0 disables the demotion entirely.  Slow
    tier for the 870 s tier-1 budget (`make decode-check` and `make
    check` run it)."""
    from libsplinter_tpu.models import SpeculativeCompletionModel

    name, st = _mkstore(tmp_path, "spec0")
    try:
        t = CompletionModel(DecoderConfig.tiny(dtype=jnp.float32),
                            buckets=(16,), temp=0.0, seed=2)
        d = CompletionModel(
            DecoderConfig.tiny(dtype=jnp.float32, layers=1),
            buckets=(16,), temp=0.0, seed=99)
        spec = SpeculativeCompletionModel(t, d, gamma=4)
        comp = Completer(st, model=spec, max_new_tokens=40,
                         flush_tokens=4, template="none", batch_cap=1,
                         spec_min_acceptance=0.0)
        comp.attach()
        _submit(st, "q", b"a question")
        assert comp.run_once() == 1
        assert comp.stats.spec_demotions == 0
        assert comp._model is spec
    finally:
        st.close()
        Store.unlink(name)


@pytest.mark.slow
def test_paged_continuous_churn_no_leak(tmp_path):
    """Heavy tier: three waves of staggered joins/finishes through a
    deliberately tight pool — every request completes, backpressure
    engages, and the pool ends empty."""
    name, st = _mkstore(tmp_path, "churn", nslots=256)
    try:
        model = CompletionModel(DecoderConfig.tiny(max_len=128),
                                buckets=(16, 32), temp=0.0)
        comp = Completer(st, model=model, max_new_tokens=20,
                         flush_tokens=4, template="none", batch_cap=4,
                         page_size=16, pool_pages=16)
        comp.attach()
        th = _run_bg(comp, stop_after=300.0)
        keys = []
        for wave in range(3):
            for i in range(5):
                k = f"c/{wave}/{i}"
                keys.append(k)
                _submit(st, k, f"wave {wave} question {i} ")
            time.sleep(0.5)
        assert _await_ready(st, keys, timeout=240), comp.stats
        comp.stop()
        th.join(timeout=5)
        assert comp.stats.completions == len(keys)
        assert comp._paged_cache.used_pages == 0, "pages leaked"
    finally:
        st.close()
        Store.unlink(name)


# ---- admission rounds (run_continuous.fill_rows / join_round) over a
# model whose suffix program has a row axis (models/mla.py join)

_DOC = "the quick brown fox jumps over the lazy "   # + BOS: 41 tokens


@pytest.fixture(scope="module")
def latent_models():
    """The tiny latent model twice over the same weights: as it is
    (`rows`) and joined a request at a time (`one-row`)."""
    from libsplinter_tpu.models import mla

    class OneRow(mla.LatentCompletionModel):
        """The same weights, joined a request at a time."""

        def join_rungs(self, cache):
            return (1,)

    cfg = mla.LatentMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                                   experts_held=4)
    return {"rows": mla.LatentCompletionModel(cfg, seed=3, temp=0.0),
            "one-row": OneRow(cfg, seed=3, temp=0.0)}


class _RoundLane:
    """A continuous lane of 6 rows over `model` whose admission waits
    at a gate, so that a burst of requests is ONE round, and whose
    model and prefix tree log every call the round makes."""

    SPIED = (("paged_prefill_row", "miss", lambda c, ids, row, **k: row),
             ("paged_append_prefill", "suffix",
              lambda c, ids, row, **k: row),
             ("paged_append_prefill_rows", "rows",
              lambda c, joins, *snaps: tuple(r for r, _ in joins)),
             # a model with state slots: (snapshot slot, row)
             ("state_restore", "restore", lambda c, src, row: (src, row)),
             ("sample", "sample", lambda logits: None),
             ("_cow_fixups", "cow", lambda c: None),
             ("paged_decode_chunk_async", "chunk", lambda *a, **k: None))

    def __init__(self, tmp_path, model, tag, pool_pages=40, **kw):
        self.model, self.log, self.at_rows = model, [], None
        self.name, self.st = _mkstore(tmp_path, tag)
        self.comp = Completer(self.st, model=model, max_new_tokens=4,
                              flush_tokens=4, template="none",
                              batch_cap=6, page_size=16,
                              pool_pages=pool_pages, **kw)
        self.comp.attach()
        self.comp._ensure_paged_cache()
        self.gate, self.parked = threading.Event(), threading.Event()
        self.gate.set()

    def _spy(self, obj, name, tag, what):
        orig = getattr(obj, name)

        def wrapped(*a, **k):
            self.log.append((tag, what(*a, **k)))
            if tag == "rows" and self.at_rows is not None:
                self.at_rows()
            return orig(*a, **k)
        setattr(obj, name, wrapped)

    def __enter__(self):
        self.spied = [s for s in self.SPIED if hasattr(self.model, s[0])]
        for name, tag, what in self.spied:
            self._spy(self.model, name, tag, what)
        self._spy(self.comp.prefix_cache, "insert", "insert",
                  lambda ids, c, row, *a, **k: row)
        cache = self.comp._paged_cache
        if getattr(cache, "needs_state", False):
            alloc = cache.alloc_state_slot

            def logged():
                slot = alloc()
                self.log.append(("slot", slot))
                return slot
            cache.alloc_state_slot = logged
        refresh = self.comp.stripes.refresh

        def gated():
            if not self.gate.is_set():
                self.parked.set()
                self.gate.wait()
            self.log.append(("gather", None))
            return refresh()
        self.comp.stripes.refresh = gated
        self.th = _run_bg(self.comp, stop_after=300.0)
        return self

    def __exit__(self, *exc):
        self.gate.set()
        self.comp.stop()
        self.th.join(timeout=30)
        for name, _, _ in self.spied:
            delattr(self.model, name)      # the class's own again
        self.st.close()
        Store.unlink(self.name)

    def burst(self, prompts: dict) -> tuple[dict, list]:
        """Submit `prompts` (key -> text) while admission waits, let
        ONE round see them all.  Returns (key -> answer, the log from
        the round's first call to the first decode chunk after it)."""
        self.parked.clear()
        self.gate.clear()
        assert self.parked.wait(30)
        mark = len(self.log)
        for k, text in prompts.items():
            _submit(self.st, k, text)
        self.gate.set()
        assert _await_ready(self.st, list(prompts), timeout=240), \
            self.comp.stats
        log = self.log[mark:]
        if ("chunk", None) in log:
            log = log[:log.index(("chunk", None))]
        self.gathers = log.count(("gather", None))
        return ({k: self.st.get(k).rstrip(b"\0") for k in prompts},
                [e for e in log if e[0] != "gather"])


@pytest.fixture(scope="module")
def round_answers():
    """What each scenario's prompts are answered with, by model kind:
    greedy answers must not depend on how the round was joined."""
    return {}


def _same_answers(round_answers, scenario, kind, got):
    other = round_answers.setdefault(scenario, {})
    other[kind] = got
    if len(other) == 2:
        assert other["rows"] == other["one-row"]


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_a_round_of_hits_is_one_dispatch_or_todays_sequence(
        tmp_path, latent_models, round_answers, kind):
    """Three prefix hits in one admission round: a model whose suffix
    program has a row axis prefills them in ONE dispatch (join_programs
    1, join_rows 3) after seating all three; a model with rung 1 makes
    today's calls in today's order — prefill, insert, sample, request
    by request — and counts a program a row."""
    with _RoundLane(tmp_path, latent_models[kind], f"hits-{kind}") as ln:
        ln.burst({"d": _DOC})
        s = ln.comp.stats
        p0, r0 = s.join_programs, s.join_rows
        out, log = ln.burst({f"q/{i}": _DOC + q for i, q in enumerate(
            ("who?", "what now?", "where to, and why?"))})
        if kind == "rows":
            assert [t for t, _ in log] == ["rows"] + ["insert"] * 3
            assert sorted(log[0][1]) == sorted(r for _, r in log[1:])
            assert (s.join_programs - p0, s.join_rows - r0) == (1, 3)
            # rows were left free: the round looked once more for
            # arrivals before it dispatched (and the next pass, ahead
            # of its chunk, a third time)
            assert ln.gathers == 3
        else:
            assert ln.gathers == 2         # the round's, the next pass's
            assert [t for t, _ in log] == ["suffix", "insert",
                                           "sample"] * 3
            for i in range(0, 9, 3):
                assert log[i][1] == log[i + 1][1]
            assert (s.join_programs - p0, s.join_rows - r0) == (3, 3)
        assert s.prefix_tokens == 3 * 32
        _same_answers(round_answers, "hits", kind, out)


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_a_miss_a_cached_prompt_and_a_wide_suffix_are_rounds_of_one(
        tmp_path, latent_models, round_answers, kind):
    """One round holding two hits, a miss, a fully cached prompt and a
    suffix wider than the widest suffix program: the last three are
    served where they stand, each in the old order (prefill, insert,
    sample; the cached prompt: its page copy alone), and the two hits
    ride one program behind them."""
    with _RoundLane(tmp_path, latent_models[kind], f"mix-{kind}") as ln:
        ln.burst({"d": _DOC})
        s = ln.comp.stats
        p0, r0 = s.join_programs, s.join_rows
        out, log = ln.burst({
            "hit/0": _DOC + "and then?",
            "hit/1": _DOC + "so?",
            "miss": "an unrelated prompt of its own",
            "cached": _DOC[:31],                 # + BOS: two whole pages
            "wide": _DOC + "w" * 60})            # a suffix of 69 tokens
        tags = [t for t, _ in log]
        assert tags.count("miss") == 1 and tags.count("cow") == 1
        i = tags.index("miss")
        assert tags[i:i + 3] == ["miss", "insert", "sample"]
        assert log[i][1] == log[i + 1][1]
        if kind == "rows":
            assert tags.count("suffix") == 1 and tags.count("rows") == 1
            assert tags[-3:] == ["rows", "insert", "insert"]
            assert sorted(log[-3][1]) == sorted(r for _, r in log[-2:])
            assert (s.join_programs - p0, s.join_rows - r0) == (3, 4)
        else:
            assert tags.count("suffix") == 3 and "rows" not in tags
            assert (s.join_programs - p0, s.join_rows - r0) == (4, 4)
        i = [k for k, (t, r) in enumerate(log) if t == "suffix"
             and tags[k:k + 3] == ["suffix", "insert", "sample"]]
        assert len(i) == tags.count("suffix")
        _same_answers(round_answers, "mix", kind, out)


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_a_request_that_needs_a_page_of_the_round_waits_and_then_hits(
        tmp_path, latent_models, round_answers, kind):
    """Two prompts of one round share a page that neither finds in the
    tree yet: whichever is seated first closes the round before the
    other, which joins in the next and HITS that page — the tree ends
    up with what row-by-row admission leaves, and no page is prefilled
    twice."""
    with _RoundLane(tmp_path, latent_models[kind], f"dep-{kind}") as ln:
        ln.burst({"d": _DOC})
        s, pc = ln.comp.stats, ln.comp.prefix_cache.stats
        p0, r0, ins0 = s.join_programs, s.join_rows, pc.inserts
        longer = _DOC + "a page more of shared "     # 63 tokens
        out, _ = ln.burst({"first": longer[:-2], "second": longer,
                           "aside": _DOC + "hm?"})
        # 32 + 32 (the third page of whichever came first) + 48
        assert s.prefix_tokens == 32 + 32 + 48
        assert pc.inserts - ins0 == 1
        assert s.join_rows - r0 == 3
        assert s.join_programs - p0 == (2 if kind == "rows" else 3)
        _same_answers(round_answers, "dep", kind, out)


def test_a_round_takes_in_what_arrives_while_its_hits_are_seated(
        tmp_path, latent_models):
    """Clients answered together come back over the milliseconds the
    first of them take to seat: requests that arrive while a round's
    hits are being seated join THAT round's program, not one of their
    own."""
    with _RoundLane(tmp_path, latent_models["rows"], "late") as ln:
        ln.burst({"d": _DOC})
        late = {f"late/{i}": _DOC + f"{i}, the late one?" for i in (0, 1)}
        claim, unsent = ln.comp._prepare, list(late)

        def claim_then_arrive(idx, peek=None):
            got = claim(idx, peek=peek)
            while unsent:
                k = unsent.pop()
                _submit(ln.st, k, late[k])
            return got
        ln.comp._prepare = claim_then_arrive
        s = ln.comp.stats
        p0, r0 = s.join_programs, s.join_rows
        out, log = ln.burst({"early/0": _DOC + "first?",
                             "early/1": _DOC + "second, then?"})
        assert _await_ready(ln.st, list(late), timeout=240)
        rows = [what for t, what in ln.log if t == "rows"]
        assert len(rows) == 1 and len(rows[0]) == 4
        assert (s.join_programs - p0, s.join_rows - r0) == (1, 4)
        assert all(ln.st.get(k).startswith(late[k].encode())
                   for k in late)


def test_backpressure_inside_a_round_leaves_the_denied_request_waiting(
        tmp_path, latent_models):
    """A pool that seats two of a round's three hits: the two are
    prefilled in one dispatch while the third is still WAITING,
    untouched (its prompt and its label as submitted); it joins when
    pages come back."""
    # 8 pages: the document's two in the tree, three a hit (68 tokens
    # + the decode's 4 = five pages, two of them mapped), so the third
    # hit finds none
    with _RoundLane(tmp_path, latent_models["rows"], "bp",
                    pool_pages=8) as ln:
        ln.burst({"d": _DOC})
        prompts = {f"q/{i}": _DOC + f"{i}: what of the other fox?"
                   for i in range(3)}
        seen = {}

        def at_rows():
            for k in prompts:
                seen[k] = (ln.st.labels(k), ln.st.get(k).rstrip(b"\0"))
        ln.at_rows = at_rows
        out, _ = ln.burst(prompts)
        s = ln.comp.stats
        assert s.join_backpressure >= 1
        rows = [what for t, what in ln.log if t == "rows"]
        assert len(rows) == 1 and len(rows[0]) == 2
        waiting = [k for k, (lab, val) in seen.items()
                   if lab & P.LBL_INFER_REQ]
        assert len(waiting) == 1
        assert seen[waiting[0]][1] == prompts[waiting[0]].encode()
        assert not seen[waiting[0]][0] & (P.LBL_SERVICING | P.LBL_READY)
        assert all(out[k].startswith(prompts[k].encode())
                   for k in prompts)
        assert s.completions == 4 and s.faults == 0


def test_a_sampling_lane_draws_in_todays_order(tmp_path):
    """temp > 0 and a fixed seed: a burst that makes one round of
    several and one round of one emits the tokens that a same-seed
    model emits when it is driven BY HAND through today's call sequence
    in the lane's order — each request seated (Seat: walk, plan, map),
    a round of several through paged_append_prefill_rows (its first
    tokens drawn in graph), a round of one through paged_prefill_row /
    paged_append_prefill and the host's `sample`, the tree's insert,
    the decode chunks.  `join` and the seat consume the model's key in
    that order and no other."""
    from libsplinter_tpu.engine.prefix_cache import PrefixCache, Seat
    from libsplinter_tpu.models import mla

    cfg = mla.LatentMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                                   experts_held=4)
    lane_m, hand_m = (mla.LatentCompletionModel(cfg, seed=3, temp=0.7)
                      for _ in range(2))
    events = []

    def record(obj, name, what):
        inner = getattr(obj, name)

        def wrapped(*a, **k):
            events.append(what(*a, **k))
            return inner(*a, **k)
        setattr(obj, name, wrapped)

    prompts = {"q/0": _DOC + "who?", "q/1": _DOC + "what now?",
               "wide": _DOC + "w" * 60, "d": _DOC}
    with _RoundLane(tmp_path, lane_m, "draws") as ln:
        for name, what in (
                ("paged_prefill_row", lambda c, ids, row, **k: ("one", row)),
                ("paged_append_prefill",
                 lambda c, ids, row, **k: ("one", row)),
                ("paged_append_prefill_rows",
                 lambda c, joins: ("rows", [r for r, _ in joins])),
                ("sample", lambda logits: ("sample",)),
                ("paged_decode_chunk_async",
                 lambda c, toks, n, carry=None:
                 ("chunk", np.array(toks), n, carry is None))):
            record(lane_m, name, what)
        record(ln.comp.prefix_cache, "insert",
               lambda ids, c, row, *a, **k: ("insert", row, list(ids)))
        record(ln.comp._paged_cache, "free_row", lambda row: ("free", row))
        got, _ = ln.burst({"d": prompts["d"]})
        more, _ = ln.burst({k: prompts[k] for k in ("q/0", "q/1", "wide")})
        got.update(more)
        tok = ln.comp._tok
    assert [len(e[1]) for e in events if e[0] == "rows"] == [2]
    assert sum(e[0] == "one" for e in events) == 2   # the document, `wide`

    cache = hand_m.init_paged(6, page=16, pool_pages=40)
    pc = PrefixCache(16)
    pc.attach(cache)
    cache.prefix_cache = pc
    key_of = {tuple(tok.encode(p)): k for k, p in prompts.items()}
    seated, tokens, logits, last = {}, {}, None, None

    def seat(row, at):
        """Seat in `row` the prompt whose insert follows event `at`."""
        ids = next(e[2] for e in events[at:]
                   if e[0] == "insert" and e[1] == row)
        st = Seat(cache, ids)
        st.walk()
        # max_new 4 in chunks of 4: one chunk past the prompt
        assert st.plan(len(ids) + 4, len(ids) + 8) is None and st.map(row)
        seated[row] = key_of[tuple(ids)]
        return ids, st

    for at, e in enumerate(events):
        if e[0] == "one":
            ids, st = seat(e[1], at)
            logits = (hand_m.paged_append_prefill(
                cache, np.asarray(st.suffix, np.int32), e[1])
                if st.hit_bids else hand_m.paged_prefill_row(
                    cache, np.asarray(ids, np.int32), e[1]))
            row = e[1]
        elif e[0] == "rows":
            joins = [(r, np.asarray(seat(r, at)[1].suffix, np.int32))
                     for r in e[1]]
            _, firsts = hand_m.paged_append_prefill_rows(cache, joins)
            for r, t in zip(e[1], firsts):
                tokens[seated[r]] = [int(t)]
        elif e[0] == "sample":
            tokens[seated[row]] = [hand_m.sample(logits)]
        elif e[0] == "insert":
            pc.insert(e[2], cache, e[1], 0)
        elif e[0] == "chunk":
            pend = hand_m.paged_decode_chunk_async(
                cache, e[1], e[2], carry=None if e[3] else last)
            out, last = pend.block(), pend.last
            for r, k in seated.items():
                tokens[k] += [int(t) for t in out[r]]
        else:
            seated.pop(e[1], None)
            cache.free_row(e[1])
    for k, p in prompts.items():
        want = p.encode()
        for t in tokens[k][:4]:
            if t == tok.eos_id:
                break
            want += tok.token_to_piece(t)
        assert got[k] == want.rstrip(b"\0"), k


# ---- the same rounds over a model WITH STATE SLOTS whose rows program
# leaves the snapshots (models/lfm2.py)

@pytest.fixture(scope="module")
def conv_models():
    """The tiny convolution / attention model twice over the same
    weights: as it is (`rows`) and answering `(1,)` (`one-row`), which
    is how every family with state was joined before it had a row
    axis and how kimi's still is."""
    from libsplinter_tpu.models import lfm2

    class OneRow(lfm2.ConvCompletionModel):
        def join_rungs(self, cache):
            return (1,)

    cfg = lfm2.ConvMoeConfig.tiny(dtype=jnp.float32)
    rows = lfm2.ConvCompletionModel(cfg, seed=3, temp=0.0)
    return {"rows": rows,
            "one-row": OneRow(cfg, params=rows.params, temp=0.0)}


def _snapshot_slot(ln, prompt: str) -> tuple[int, int]:
    """(the slot of the snapshot at `prompt`'s last page boundary,
    that boundary)."""
    ids = ln.comp._tok.encode(prompt)
    at = len(ids) // 16 * 16
    return ln.comp.prefix_cache.state_slot(ids, at), at


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_joins_that_leave_snapshots_ride_one_round(
        tmp_path, conv_models, round_answers, kind):
    """Three hits on the document's snapshot in one admission round,
    two of which leave a snapshot of their own (at 48 and at 64 tokens)
    and one that does not: the rows model restores each at its seat,
    then prefills all three in ONE dispatch (a program counted once,
    its rows each), and each snapshot's node holds the slot its join
    was given — no two the same; a model with state that answers (1,)
    is served as it always was, restore, prefill, insert, draw, request
    by request."""
    with _RoundLane(tmp_path, conv_models[kind], f"snap-{kind}",
                    state_snapshots=6) as ln:
        ln.burst({"d": _DOC})
        s = ln.comp.stats
        p0, r0 = s.join_programs, s.join_rows
        src, at = _snapshot_slot(ln, _DOC)
        assert (s.state_snapshots, at) == (1, 32) and src >= 6
        prompts = {"q/0": _DOC + "who goes there?",              # 56
                   "q/1": _DOC + "hm?",                          # 44
                   "q/2": _DOC + "what now, fox, and why not sooner?"}
        out, log = ln.burst(prompts)
        tags = [t for t, _ in log]
        slots = [what for t, what in log if t == "slot"]
        assert [what[0] for t, what in log if t == "restore"] == [src] * 3
        assert len(set(slots)) == 2 and src not in slots
        if kind == "rows":
            assert tags[-4:] == ["rows"] + ["insert"] * 3
            assert "suffix" not in tags and "sample" not in tags
            assert sorted(log[-4][1]) == sorted(r for _, r in log[-3:])
            assert (s.join_programs - p0, s.join_rows - r0) == (1, 3)
        else:
            per = [t for t in tags if t != "slot"]
            assert per == ["restore", "suffix", "insert", "sample"] * 3
            assert (s.join_programs - p0, s.join_rows - r0) == (3, 3)
        assert (s.state_restores, s.state_snapshots) == (3, 3)
        held = [_snapshot_slot(ln, prompts[k]) for k in ("q/0", "q/2")]
        assert [at for _, at in held] == [48, 64]
        assert sorted(slot for slot, _ in held) == sorted(slots)
        assert _snapshot_slot(ln, prompts["q/1"]) == (src, 32)
        assert s.prefix_tokens == 3 * 32 and s.faults == 0
        _same_answers(round_answers, "snap", kind, out)


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_a_seat_may_evict_the_snapshot_an_earlier_row_of_the_round_resumed(
        tmp_path, conv_models, round_answers, kind):
    """A budget of two snapshots: the round's first row resumes from
    the document's and takes the free slot, the second resumes from it
    too and its snapshot's slot is THE DOCUMENT'S, evicted for it.  The
    rows were restored at their seats, so the round's program — which
    writes that slot — answers as the one-row model does."""
    with _RoundLane(tmp_path, conv_models[kind], f"evict-{kind}",
                    state_snapshots=2) as ln:
        ln.burst({"d": _DOC})
        src, _ = _snapshot_slot(ln, _DOC)
        prompts = {"q/0": _DOC + "who goes there?",
                   "q/1": _DOC + "and who went before?"}
        out, log = ln.burst(prompts)
        s = ln.comp.stats
        assert s.state_restores == 2 and s.faults == 0
        if kind == "rows":
            tags = [t for t, _ in log]
            assert tags == ["restore", "slot", "restore", "slot", "rows",
                            "insert", "insert"]
            assert [log[0][1][0], log[2][1][0], log[3][1]] == [src] * 3
            assert log[1][1] != src
            assert ln.comp.prefix_cache.stats.state_evictions == 1
            # both snapshots are the tree's now, the document's is not
            assert sorted(_snapshot_slot(ln, p)[0]
                          for p in prompts.values()) \
                == sorted([log[1][1], src])
            assert _snapshot_slot(ln, _DOC)[0] == -1
        _same_answers(round_answers, "evict", kind, out)


# ---- the same rounds over a model with a WINDOW GROUP beside the
# global pages (models/afmoe.py: rungs 1 and the batch, ONE page wide)

@pytest.fixture(scope="module")
def window_models():
    """The tiny window / global model twice over the same weights: as
    it is (`rows`) and answering `(1,)` (`one-row`), which is how the
    family was joined before it had a row axis."""
    from libsplinter_tpu.models import afmoe

    class OneRow(afmoe.WindowCompletionModel):
        def join_rungs(self, cache):
            return (1,)

    cfg = afmoe.WindowMoeConfig.tiny(dtype=jnp.float32, experts_first=2,
                                     experts_held=4)
    rows = afmoe.WindowCompletionModel(cfg, seed=3, temp=0.0)
    return {"rows": rows,
            "one-row": OneRow(cfg, params=rows.params, temp=0.0)}


@pytest.mark.parametrize("kind", ["rows", "one-row"])
def test_window_group_hits_ride_one_round_and_a_wide_one_goes_alone(
        tmp_path, monkeypatch, window_models, round_answers, kind):
    """Three hits of a page or less and one of three pages, waiting
    together on a document whose window tail they share: the three ride
    ONE dispatch (a program counted once, its rows each) and the wide
    one is a round of one, a piece at a time as before; every row
    still records its `window_release`, and the audit's lane-0 record
    (a resumed row) holds the batched row's first logits, the
    reference's for the whole prompt."""
    import reference_afmoe as R
    from libsplinter_tpu.engine import completer as cmod

    monkeypatch.setattr(cmod.tracer, "enabled", True)
    cmod.tracer.reset()
    model = window_models[kind]
    audit_dir = str(tmp_path / "audit")
    with _RoundLane(tmp_path, model, f"win-{kind}", window_pool_pages=24,
                    audit={"dir": audit_dir, "every": 1}) as ln:
        # the document, a first wide question (which files a page below
        # it: the tree then gives the document's tail up, ROADMAP
        # B1.7) and a short one that files the tail again, under a
        # node with a child now: from here on it stays
        for warm in ({"d": _DOC}, {"w": _DOC + "v" * 30},
                     {"d/2": _DOC + "eh?"}):
            ln.burst(warm)
        s, w = ln.comp.stats, ln.comp._paged_cache.window
        p0, r0 = s.join_programs, s.join_rows
        res0, cut0, share0, hit0 = (s.window_resumes, s.window_cut_tokens,
                                    s.window_tail_shares, s.prefix_tokens)
        rel0 = cmod.tracer.snapshot()["infer.window_release"]["n"]
        prompts = {"q/0": _DOC + "who?", "q/1": _DOC + "so?",
                   "q/2": _DOC + "why so?",              # 16: a whole page
                   "wide": _DOC + "w" * 30}              # 39: three pages
        out, log = ln.burst(prompts)
        tags = [t for t, _ in log]
        assert (s.window_resumes - res0, s.window_cut_tokens - cut0) \
            == (4, 0)
        assert s.window_tail_shares - share0 == 3   # all but the first
        assert s.prefix_tokens - hit0 == 4 * 32 and s.faults == 0
        assert cmod.tracer.snapshot()["infer.window_release"]["n"] \
            - rel0 >= 4
        if kind == "rows":
            assert tags.count("rows") == 1 and tags.count("suffix") == 1
            assert tags[-4:] == ["rows"] + ["insert"] * 3
            assert sorted(log[-4][1]) == sorted(r for _, r in log[-3:])
            i = tags.index("suffix")
            assert tags[i:i + 3] == ["suffix", "insert", "sample"]
            assert (s.join_programs - p0, s.join_rows - r0) == (2, 4)
        else:
            assert tags == ["suffix", "insert", "sample"] * 4
            assert (s.join_programs - p0, s.join_rows - r0) == (4, 4)
        # one row a lane is audited at a time, and the wide hit, joined
        # at its seat, took lane 0 above: two more hits, alone in their
        # round, and the first of them is the lane's
        def settled():
            for _ in range(250):
                if w.live_pages == 0:
                    return ln.comp.audit.written
                time.sleep(0.02)
        written = settled()
        ln.burst({"a/0": _DOC + "and?", "a/1": _DOC + "or?"})
        assert settled() > written
        rec = np.load(f"{audit_dir}/{written}.npz")
        assert int(rec["n_prefix"]) == 32 and str(rec["key"])[:2] == "a/"
        full = R.forward(model.cfg, model.params, np.concatenate(
            [rec["prompt"], rec["tokens"][:-1]]))
        np.testing.assert_allclose(
            rec["logits"], full[len(rec["prompt"]) - 1:], atol=2e-4)
        pc = ln.comp.prefix_cache
        assert w.free_pages + pc.window_evictable_count() == 24
        _same_answers(round_answers, "window", kind, out)
