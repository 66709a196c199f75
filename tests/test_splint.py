"""splint (libsplinter_tpu/analysis/): registry extraction against
the live protocol.py, per-rule positive/negative fixtures, suppression
+ baseline semantics, the live-tree gate, and the meta-test keeping
the rule catalog and the docs rule table in sync.

The analysis package is loaded STANDALONE (by path, stdlib-only) —
this tier must run without jax or the built native lib, exactly like
`make lint-check` promises.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_splint():
    spec = importlib.util.spec_from_file_location(
        "_splint_load", os.path.join(
            ROOT, "libsplinter_tpu", "analysis", "_load.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.load()


@pytest.fixture(scope="module")
def splint():
    return _load_splint()


@pytest.fixture(scope="module")
def R(splint):
    return sys.modules[splint.__name__ + ".registry"]


@pytest.fixture(scope="module")
def core(splint):
    return sys.modules[splint.__name__ + ".core"]


@pytest.fixture(scope="module")
def runner(splint):
    return sys.modules[splint.__name__ + ".runner"]


# ------------------------------------------------------------ fixtures

PROTO_OK = """\
LBL_A = 0x1                    # label a
LBL_B = 0x40                   # label b
LBL_HIGH = 0x1 << 57           # high label
TENANT_SHIFT = 48
TENANT_BITS = 4
TENANT_MASK = ((1 << TENANT_BITS) - 1) << TENANT_SHIFT
BIT_A = 0
BIT_B = 6
PIPELINE_STAGES = ("drain", "commit")
SEARCH_STAGES = ("wake", "drain", "score", "select", "commit")
KEY_EMBED_STATS = "__embedder_stats"
SEARCH_RESULT_PREFIX = "__sr_"
"""

PROTO_RELPATH = "libsplinter_tpu/engine/protocol.py"


def make_ctx(splint, R, core, files=None, proto=PROTO_OK, docs=None,
             tests_text="", fault_docs=None):
    files = files or {}
    reg = R.extract_registry(source=proto)
    return core.Context(
        registry=reg,
        files={rel: core.SourceFile(rel, text)
               for rel, text in files.items()},
        fault_sites=R.fault_sites(sources=files),
        fault_site_docs=(R.FAULT_SITE_DOCS if fault_docs is None
                         else fault_docs),
        docs=docs or {},
        tests_text=tests_text,
        protocol_relpath=PROTO_RELPATH)


def run_rule(splint, R, core, runner, rule_id, **kw):
    ctx = make_ctx(splint, R, core, **kw)
    return [f for f in runner.run_rules(ctx, [rule_id])]


# --------------------------------------- registry vs live protocol.py

def test_registry_extracts_live_protocol(splint):
    reg = splint.extract_registry()
    assert reg.labels["LBL_EMBED_REQ"].mask == 0x1
    assert reg.labels["LBL_READY"].mask == 1 << 62
    assert reg.labels["LBL_SEARCH_REQ"].bits == (57,)
    assert reg.fields["TENANT_MASK"].bits == tuple(range(48, 52))
    assert reg.stages["PIPELINE_STAGES"] == (
        "drain", "tokenize", "dispatch", "device_wait", "commit")
    assert reg.stages["CONT_INFER_STAGES"] == (
        "join", "sample", "decode", "collect", "flush", "prefix_hit",
        "handoff", "adopt", "state_restore", "state_snapshot",
        "state_zero", "window_release")
    assert reg.keys["KEY_SEARCH_STATS"] == "__searcher_stats"
    assert reg.prefixes["SEARCH_RESULT_PREFIX"] == "__sr_"
    assert reg.prefixes["DEADLINE_STAMP_PREFIX"] == "__dl_"
    assert reg.bit_indices["BIT_INFER_REQ"] == 60
    # the label comment rides into the registry (doc-table source)
    assert "wakes the embedding daemon" in \
        reg.labels["LBL_EMBED_REQ"].comment


def test_live_fault_sites_discovered(splint):
    sites = {s.site for s in splint.fault_sites(ROOT)}
    assert {"searcher.gather", "embedder.encode", "completer.render",
            "completer.kv_quant_commit", "resident.ring_collect",
            "supervisor.poll", "store.set", "store.vec_commit"} <= sites
    assert sites <= set(splint.FAULT_SITE_DOCS)


# ----------------------------------------------------- the live gate

def test_live_tree_is_clean(runner):
    """THE acceptance gate: splint exits 0 on the tree at HEAD.  Any
    new finding must be fixed, suppressed with a reason, or (outside
    the engine layer) baselined — see docs/operations.md."""
    rep = runner.scan(ROOT)
    assert rep.clean, "\n" + rep.render()
    # the shipped suppressions: the two documented intentional host
    # syncs plus the SPL205 inner-kernel / cold-path registrations;
    # anything more deserves a fresh look at this list
    reasons = {f.file for f, _ in rep.suppressed}
    assert reasons == {"libsplinter_tpu/engine/completer.py",
                       "libsplinter_tpu/engine/embedder.py",
                       "libsplinter_tpu/models/decoder.py",
                       "libsplinter_tpu/models/mla.py",
                       "libsplinter_tpu/ops/delta_attention.py",
                       "libsplinter_tpu/ops/flash_attention.py",
                       "libsplinter_tpu/ops/latent_attention.py",
                       "libsplinter_tpu/ops/paged_attention.py",
                       "libsplinter_tpu/ops/similarity.py",
                       "libsplinter_tpu/ops/sparse_attention.py",
                       "libsplinter_tpu/ops/ssd_scan.py"}


def test_baseline_has_no_engine_entries(core):
    """The committed baseline must be empty of engine-layer findings
    (and in fact ships empty): hot-path hazards are fixed or
    justified inline, never backlogged."""
    path = os.path.join(ROOT, core.BASELINE_RELPATH)
    entries = core.load_baseline(path)
    assert not {e for e in entries
                if "libsplinter_tpu/engine/" in e}
    assert entries == set()            # ships empty — keep it so


# ------------------------------------------- SPL101/SPL108: registry

def test_label_collision_detected(splint, R, core, runner):
    bad = PROTO_OK + "LBL_EVIL = 0x40        # collides with LBL_B\n"
    fs = run_rule(splint, R, core, runner, "SPL101", proto=bad)
    assert len(fs) == 1 and fs[0].rule == "SPL101"
    assert "LBL_EVIL" in fs[0].message and "bit 6" in fs[0].message


def test_label_field_collision_detected(splint, R, core, runner):
    bad = PROTO_OK + "LBL_EVIL = 0x1 << 50   # inside TENANT_MASK\n"
    fs = run_rule(splint, R, core, runner, "SPL101", proto=bad)
    assert len(fs) == 1 and "TENANT_MASK" in fs[0].message


def test_live_protocol_has_no_collisions(splint, R, core, runner):
    with open(os.path.join(ROOT, PROTO_RELPATH)) as f:
        live = f.read()
    assert run_rule(splint, R, core, runner, "SPL101",
                    proto=live) == []
    assert run_rule(splint, R, core, runner, "SPL108",
                    proto=live) == []


def test_bit_index_mismatch_detected(splint, R, core, runner):
    bad = PROTO_OK.replace("BIT_B = 6", "BIT_B = 7")
    fs = run_rule(splint, R, core, runner, "SPL108", proto=bad)
    assert len(fs) == 1 and "BIT_B=7" in fs[0].message


# ------------------------------------------- SPL102: raw bit literals

def test_raw_high_shift_flagged(splint, R, core, runner):
    src = "MASK = 1 << 57\n"
    fs = run_rule(splint, R, core, runner, "SPL102",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "bit 57" in fs[0].message


def test_raw_literal_in_label_api_flagged(splint, R, core, runner):
    src = "def f(store, key):\n    store.label_or(key, 0x40)\n"
    fs = run_rule(splint, R, core, runner, "SPL102",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "label_or" in fs[0].message


def test_raw_literal_in_label_bitop_flagged(splint, R, core, runner):
    src = "def f(labels):\n    return labels & 0x40\n"
    fs = run_rule(splint, R, core, runner, "SPL102",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1


def test_innocent_literals_not_flagged(splint, R, core, runner):
    # 0x40 == 64 as a size, a non-label bitop, protocol.py itself
    src = ("def f(v, store):\n"
           "    buf = bytearray(0x40)\n"
           "    store.set('k', 'x' * 64)\n"
           "    return v & 0x3F\n")
    assert run_rule(splint, R, core, runner, "SPL102", files={
        "libsplinter_tpu/engine/foo.py": src,
        PROTO_RELPATH: "LBL_B = 0x40\nX = LBL_B & 0x40\n"}) == []


# --------------------------------------- SPL103/SPL104: fault sites

def test_undocumented_fault_site_flagged(splint, R, core, runner):
    src = "def f():\n    fault('new.site')\n"
    fs = run_rule(splint, R, core, runner, "SPL103",
                  files={"libsplinter_tpu/engine/foo.py": src},
                  tests_text="new.site")
    assert len(fs) == 1 and "FAULT_SITE_DOCS" in fs[0].message


def test_documented_site_missing_from_ops_doc(splint, R, core, runner):
    src = "def f():\n    fault('new.site')\n"
    fs = run_rule(splint, R, core, runner, "SPL103",
                  files={"libsplinter_tpu/engine/foo.py": src},
                  fault_docs={"new.site": "somewhere"},
                  docs={"operations": "no table here"})
    assert len(fs) == 1 and "operations.md" in fs[0].message
    fs = run_rule(splint, R, core, runner, "SPL103",
                  files={"libsplinter_tpu/engine/foo.py": src},
                  fault_docs={"new.site": "somewhere"},
                  docs={"operations": "| `new.site` | somewhere |"})
    assert fs == []


def test_chaos_unreached_site_flagged(splint, R, core, runner):
    src = "def f():\n    fault('lonely.site')\n"
    fs = run_rule(splint, R, core, runner, "SPL104",
                  files={"libsplinter_tpu/engine/foo.py": src},
                  tests_text="tests mention other.site only")
    assert len(fs) == 1 and "lonely.site" in fs[0].message
    assert run_rule(splint, R, core, runner, "SPL104",
                    files={"libsplinter_tpu/engine/foo.py": src},
                    tests_text="SPTPU_FAULT=lonely.site:crash@1") == []


# ----------------------------------------- SPL105: metrics/heartbeat

METRICS_RELPATH = "libsplinter_tpu/cli/metrics.py"


def test_hardcoded_heartbeat_key_flagged(splint, R, core, runner):
    src = ("from ..engine import protocol as P\n"
           "KEYS = [P.KEY_EMBED_STATS]\n"
           "BAD = '__embedder_stats'\n")
    fs = run_rule(splint, R, core, runner, "SPL105",
                  files={METRICS_RELPATH: src})
    assert len(fs) == 1 and "hardcoded" in fs[0].message


def test_unrendered_heartbeat_key_flagged(splint, R, core, runner):
    proto = PROTO_OK + 'KEY_NEWLANE_STATS = "__newlane_stats"\n'
    src = "from ..engine import protocol as P\nK = P.KEY_EMBED_STATS\n"
    fs = run_rule(splint, R, core, runner, "SPL105", proto=proto,
                  files={METRICS_RELPATH: src})
    assert len(fs) == 1 and "KEY_NEWLANE_STATS" in fs[0].message


def test_unknown_store_key_flagged(splint, R, core, runner):
    src = "K = '__mystery_key'\n"
    fs = run_rule(splint, R, core, runner, "SPL105",
                  files={METRICS_RELPATH: src})
    assert len(fs) == 2     # hardcoded-unknown + unrendered KEY_EMBED
    assert any("not a registered" in f.message for f in fs)


# ------------------------------------------- SPL106: doc-table drift

def test_doc_table_drift_flagged(splint, R, core, runner):
    fs = run_rule(splint, R, core, runner, "SPL106",
                  docs={"operations": "stale", "bloom-labels": "stale"})
    assert {f.rule for f in fs} == {"SPL106"} and len(fs) == 2


def test_doc_tables_in_sync_pass(splint, R, core, runner):
    reg = R.extract_registry(source=PROTO_OK)
    files = {"libsplinter_tpu/engine/foo.py":
             "def f():\n    fault('searcher.gather')\n"}
    ctx = make_ctx(splint, R, core, files=files, docs={})
    ctx.docs = {"bloom-labels": R.render_label_table(reg),
                "operations": R.render_fault_table(ctx.fault_sites)}
    assert runner.run_rules(ctx, ["SPL106"]) == []


# ------------------------------------------- SPL107: stage names

def test_stage_typo_flagged(splint, R, core, runner):
    src = ("def f(tracer):\n"
           "    tracer.record('search.scoree', 1.0)\n"
           "    tracer.record('search.score', 1.0)\n"
           "    tracer.record('search.e2e', 1.0)\n")
    fs = run_rule(splint, R, core, runner, "SPL107",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "scoree" in fs[0].message


PROTO_PHASES = PROTO_OK + (
    'SEARCH_LOOP_PHASES = ("loop", "idle", "refresh", "mask",\n'
    '                      "sweep_results", "sweep_stages", "publish")\n')


@pytest.mark.parametrize("call", [
    "tracer.span('search.sweep_stages', leaf=True)",
    "tracer.span('search.loop')",
    "tracer.annotation('search.select')",
    "tracer.record('search.mask', 1.0)"])
def test_loop_phase_from_the_tuple_passes(splint, R, core, runner, call):
    """The search daemon's run-loop phases are pinned in
    SEARCH_LOOP_PHASES, beside SEARCH_STAGES: SPL107 reads both."""
    fs = run_rule(splint, R, core, runner, "SPL107", proto=PROTO_PHASES,
                  files={"libsplinter_tpu/engine/foo.py":
                         f"def f(tracer):\n    {call}\n"})
    assert fs == []


@pytest.mark.parametrize("call,typo", [
    ("tracer.span('search.sweep_stage', leaf=True)", "sweep_stage"),
    ("tracer.annotation('search.selct')", "selct"),
    ("tracer.span('embed.idle', leaf=True)", "idle")])
def test_misspelt_loop_phase_flagged(splint, R, core, runner, call, typo):
    """A typo in a phase name, or a search phase under another lane's
    prefix, still fails the lint."""
    fs = run_rule(splint, R, core, runner, "SPL107", proto=PROTO_PHASES,
                  files={"libsplinter_tpu/engine/foo.py":
                         f"def f(tracer):\n    {call}\n"})
    assert len(fs) == 1 and typo in fs[0].message


def test_live_registry_has_the_loop_phases(splint):
    reg = splint.extract_registry()
    assert reg.phases["SEARCH_LOOP_PHASES"] == (
        "loop", "idle", "refresh", "mask", "sweep_results",
        "sweep_stages", "publish")
    # phases are span names only: nothing sizes by them
    assert "SEARCH_LOOP_PHASES" not in reg.stages
    assert "idle" not in reg.stage_names()


CONT_PHASES_SRC = (
    'CONT_INFER_STAGES = ("join", "sample", "decode", "collect")\n'
    'CONT_LOOP_PHASES = ("loop", "idle", "beat", "admit", "chunk",\n'
    '                    "gather", "prepare", "emit", "rebid")\n')
PROTO_CONT_PHASES = PROTO_OK + CONT_PHASES_SRC


@pytest.mark.parametrize("call", [
    "tracer.span('infer.loop')",
    "tracer.span('infer.admit')",
    "tracer.span('infer.chunk')",
    "tracer.span('infer.gather', leaf=True)",
    "tracer.span('infer.emit', leaf=True)",
    "tracer.annotation('infer.join')",
    "tracer.record('infer.collect', 1.0)"])
def test_cont_loop_phase_from_the_tuple_passes(splint, R, core, runner,
                                               call):
    """The continuous lane's run-loop phases are pinned in
    CONT_LOOP_PHASES, beside CONT_INFER_STAGES: SPL107 reads both
    under the infer. prefix."""
    fs = run_rule(splint, R, core, runner, "SPL107",
                  proto=PROTO_CONT_PHASES,
                  files={"libsplinter_tpu/engine/foo.py":
                         f"def f(tracer):\n    {call}\n"})
    assert fs == []


@pytest.mark.parametrize("call,typo", [
    ("tracer.span('infer.gathr', leaf=True)", "gathr"),
    ("tracer.span('search.gather', leaf=True)", "gather"),
    ("tracer.annotation('infer.sweep_stages')", "sweep_stages")])
def test_misspelt_cont_loop_phase_flagged(splint, R, core, runner, call,
                                          typo):
    """A typo, a completer phase under the searcher's prefix, or a
    searcher phase under the completer's still fails the lint."""
    fs = run_rule(splint, R, core, runner, "SPL107",
                  proto=PROTO_PHASES + CONT_PHASES_SRC,
                  files={"libsplinter_tpu/engine/foo.py":
                         f"def f(tracer):\n    {call}\n"})
    assert len(fs) == 1 and typo in fs[0].message


def test_span_helper_rejects_a_loop_phase(splint, R, core, runner):
    """Phases are spans only: the per-request span(row, name, ms)
    helper takes stage names, so a phase there is flagged."""
    src = ("def f(span, r):\n"
           "    span(r, 'join', 1.0)\n"
           "    span(r, 'gather', 1.0)\n")
    fs = run_rule(splint, R, core, runner, "SPL107",
                  proto=PROTO_CONT_PHASES,
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "gather" in fs[0].message


def test_live_registry_has_the_cont_loop_phases(splint):
    """(d) CONT_LOOP_PHASES is a phases tuple, not a stages tuple: it
    does not size the per-request flight record."""
    reg = splint.extract_registry()
    assert reg.phases["CONT_LOOP_PHASES"] == (
        "loop", "idle", "beat", "admit", "chunk", "gather", "prepare",
        "emit", "rebid")
    assert "CONT_LOOP_PHASES" not in reg.stages
    for phase in ("gather", "prepare", "emit", "beat", "admit"):
        assert phase not in reg.stage_names()


def test_span_helper_stage_checked(splint, R, core, runner):
    src = ("def f(span, r):\n"
           "    span(r, 'wake', 1.0)\n"
           "    span(r, 'jion', 1.0)\n")
    fs = run_rule(splint, R, core, runner, "SPL107",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "jion" in fs[0].message


# ------------------------------------------- SPL201: host syncs

DRAIN_BAD = """\
import jax
import numpy as np

class D:
    def run_continuous(self):
        pend = self.dispatch()
        toks = jax.device_get(pend)
        t = int(self.m.sample(toks))
        return toks, t

    def _dispatch_ring(self):
        vecs = np.asarray(self.encoder_fn(['x']), np.float32)
        pend2 = self.dispatch()
        pend2.block_until_ready()
        return vecs

    def helper(self):
        return jax.device_get(self.x)    # not a drain fn: allowed

    def _service(self):
        n = int(self.count)              # Name arg: no fetch
        lens = np.asarray(self.lens)     # Name arg: no fetch
        return n, lens
"""


def test_host_sync_in_drain_flagged(splint, R, core, runner):
    fs = run_rule(splint, R, core, runner, "SPL201",
                  files={"libsplinter_tpu/engine/foo.py": DRAIN_BAD})
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 4, msgs
    assert any("device_get" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any("int(" in m for m in msgs)
    # exactly the four hazard lines — helper()'s device_get (not a
    # drain fn) and _service's Name-arg coercions stay clean
    assert sorted(f.line for f in fs) == [7, 8, 12, 14]


def test_acceptance_seeded_device_get_fails_gate(splint, R, core,
                                                 runner):
    """The ISSUE's acceptance drill: seed a device_get into a
    run_continuous body and the gate must fail with a file:line ·
    RULE_ID report."""
    src = ("import jax\n"
           "def run_continuous(self):\n"
           "    return jax.device_get(self.pend)\n")
    ctx = make_ctx(splint, R, core,
                   files={"libsplinter_tpu/engine/evil.py": src})
    rep = runner.scan(ctx=ctx, use_baseline=False,
                      rule_ids=["SPL201"])
    assert not rep.clean
    line = rep.render().splitlines()[0]
    assert re.match(r"libsplinter_tpu/engine/evil\.py:3 · SPL201 · ",
                    line)


# ----------------------------------- suppression + baseline semantics

def test_suppression_with_reason_suppresses(splint, R, core, runner):
    src = ("import jax\n"
           "def run_continuous(self):\n"
           "    # splint: ignore[SPL201] reason=measured: the fetch "
           "overlaps the next dispatch\n"
           "    return jax.device_get(self.pend)\n")
    ctx = make_ctx(splint, R, core,
                   files={"libsplinter_tpu/engine/foo.py": src})
    rep = runner.scan(ctx=ctx, use_baseline=False,
                      rule_ids=["SPL201", "SPL001"])
    assert [f.rule for f in rep.findings] == []
    assert len(rep.suppressed) == 1
    assert "overlaps" in rep.suppressed[0][1].reason


def test_suppression_without_reason_is_a_finding(splint, R, core,
                                                 runner):
    src = ("import jax\n"
           "def run_continuous(self):\n"
           "    return jax.device_get(self.pend)  "
           "# splint: ignore[SPL201]\n")
    ctx = make_ctx(splint, R, core,
                   files={"libsplinter_tpu/engine/foo.py": src})
    rep = runner.scan(ctx=ctx, use_baseline=False,
                      rule_ids=["SPL201", "SPL001"])
    # the SPL201 is suppressed, but the naked suppression is SPL001
    assert [f.rule for f in rep.findings] == ["SPL001"]


def test_suppression_unknown_rule_is_a_finding(splint, R, core,
                                               runner):
    src = "x = 1  # splint: ignore[SPL999] reason=no such rule\n"
    ctx = make_ctx(splint, R, core,
                   files={"libsplinter_tpu/engine/foo.py": src})
    rep = runner.scan(ctx=ctx, use_baseline=False,
                      rule_ids=["SPL001"])
    assert [f.rule for f in rep.findings] == ["SPL001"]


def test_baseline_hides_only_matching_findings(splint, R, core,
                                               runner, tmp_path):
    src = ("import jax\n"
           "def run_continuous(self):\n"
           "    return jax.device_get(self.pend)\n")
    ctx = make_ctx(splint, R, core,
                   files={"libsplinter_tpu/engine/foo.py": src})
    rep = runner.scan(ctx=ctx, use_baseline=False,
                      rule_ids=["SPL201"])
    assert len(rep.findings) == 1
    base = tmp_path / "base.txt"
    base.write_text(rep.findings[0].fingerprint() + "\n")
    rep2 = runner.scan(ctx=make_ctx(
        splint, R, core,
        files={"libsplinter_tpu/engine/foo.py": src}),
        baseline_path=str(base), rule_ids=["SPL201"])
    assert rep2.clean and len(rep2.baselined) == 1
    # a DIFFERENT finding (another hazard class, so another
    # fingerprint) is not baselined
    src2 = src.replace("jax.device_get(self.pend)",
                       "self.pend.block_until_ready()")
    rep3 = runner.scan(ctx=make_ctx(
        splint, R, core,
        files={"libsplinter_tpu/engine/foo.py": src2}),
        baseline_path=str(base), rule_ids=["SPL201"])
    assert not rep3.clean


def test_write_baseline_refuses_engine_findings(runner, tmp_path):
    """The no-engine-entries policy lives in the MECHANISM: an
    engine-layer finding refuses to baseline (nothing written), so
    the documented workflow cannot mask a hot-path hazard."""
    pkg = tmp_path / "libsplinter_tpu" / "engine"
    pkg.mkdir(parents=True)
    (pkg / "protocol.py").write_text(PROTO_OK)
    (pkg / "evil.py").write_text(
        "import jax\ndef run_continuous(s):\n"
        "    return jax.device_get(s.p)\n")
    with pytest.raises(ValueError, match="engine-layer"):
        runner.update_baseline(str(tmp_path))
    base = tmp_path / "libsplinter_tpu" / "analysis" / \
        "splint_baseline.txt"
    assert not base.exists()
    # the same hazard outside the engine layer baselines fine
    ops = tmp_path / "libsplinter_tpu" / "ops"
    ops.mkdir()
    (pkg / "evil.py").rename(ops / "evil.py")
    base.parent.mkdir()
    runner.update_baseline(str(tmp_path))
    assert "SPL201" in base.read_text()


def test_write_baseline_roundtrip(splint, R, core, tmp_path):
    f = core.Finding("libsplinter_tpu/ops/x.py", 3, "SPL102", "msg")
    path = tmp_path / "b.txt"
    core.write_baseline(str(path), [f])
    assert core.load_baseline(str(path)) == {f.fingerprint()}


# ------------------------------------------- SPL202/203/204 fixtures

def test_donated_buffer_reuse_flagged(splint, R, core, runner):
    src = ("import jax\n"
           "def build():\n"
           "    fn = jax.jit(step, donate_argnums=(0,))\n"
           "    pool = make_pool()\n"
           "    out = fn(pool, x)\n"
           "    return pool.shape\n")          # reuse after donation
    fs = run_rule(splint, R, core, runner, "SPL202",
                  files={"libsplinter_tpu/models/foo.py": src})
    assert len(fs) == 1 and "'pool'" in fs[0].message


def test_donated_rebind_is_clean(splint, R, core, runner):
    src = ("import jax\n"
           "def build():\n"
           "    fn = jax.jit(step, donate_argnums=(0,))\n"
           "    pool = make_pool()\n"
           "    pool = fn(pool, x)\n"         # rebound on the line
           "    return pool.shape\n")
    assert run_rule(splint, R, core, runner, "SPL202", files={
        "libsplinter_tpu/models/foo.py": src}) == []


def test_donating_call_spanning_lines_is_clean(splint, R, core,
                                               runner):
    """The donated argument's own load inside a WRAPPED donating call
    is pre-donation — it must not flag (this codebase wraps at ~72
    chars, so multi-line calls are the norm)."""
    src = ("import jax\n"
           "def build():\n"
           "    fn = jax.jit(step, donate_argnums=(0,))\n"
           "    pool = make_pool()\n"
           "    out = fn(\n"
           "        pool, x)\n"
           "    return out\n")
    assert run_rule(splint, R, core, runner, "SPL202", files={
        "libsplinter_tpu/models/foo.py": src}) == []
    # ...while a post-call read of the wrapped call's donated arg
    # still flags
    bad = src.replace("return out", "return pool.shape")
    fs = run_rule(splint, R, core, runner, "SPL202", files={
        "libsplinter_tpu/models/foo.py": bad})
    assert len(fs) == 1 and "'pool'" in fs[0].message


def test_unknown_rule_selection_fails_loudly(splint, R, core, runner):
    """`--rules SPL999` must error, never run zero rules and report a
    clean tree (the fault-spec-typo lesson)."""
    ctx = make_ctx(splint, R, core)
    with pytest.raises(ValueError, match="SPL999"):
        runner.run_rules(ctx, ["SPL999"])
    with pytest.raises(ValueError, match="SPL999"):
        runner.scan(ctx=ctx, rule_ids=["SPL101", "SPL999"])


def test_pool_jit_without_out_shardings_flagged(splint, R, core,
                                                runner):
    src = ("import jax\n"
           "def make(cache):\n"
           "    pools = cache.k_pools\n"
           "    fn = jax.jit(run, donate_argnums=(0,))\n"
           "    return fn(pools)\n")
    fs = run_rule(splint, R, core, runner, "SPL203",
                  files={"libsplinter_tpu/models/foo.py": src})
    assert len(fs) == 1 and "out_shardings" in fs[0].message


def test_pool_jit_with_pin_or_kw_idiom_clean(splint, R, core, runner):
    direct = ("import jax\n"
              "def make(cache, sh):\n"
              "    pools = cache.k_pools\n"
              "    fn = jax.jit(run, out_shardings=sh)\n"
              "    return fn(pools)\n")
    kw_idiom = ("import jax\n"
                "def make(self, cache):\n"
                "    pools = cache.k_pools\n"
                "    out_sh = self._paged_pool_out_shardings(1, 0)\n"
                "    kw = {} if out_sh is None else "
                "{'out_shardings': out_sh}\n"
                "    fn = jax.jit(run, **kw)\n"
                "    return fn(pools)\n")
    for src in (direct, kw_idiom):
        assert run_rule(splint, R, core, runner, "SPL203", files={
            "libsplinter_tpu/models/foo.py": src}) == []


def test_unregistered_jit_program_flagged(splint, R, core, runner):
    src = ("import jax\n"
           "def _chunk_fn(n):\n"
           "    def run(x):\n"
           "        return x + n\n"
           "    return jax.jit(run, donate_argnums=(0,))\n")
    fs = run_rule(splint, R, core, runner, "SPL205",
                  files={"libsplinter_tpu/models/foo.py": src})
    assert len(fs) == 1 and "DEVTIME.register" in fs[0].message \
        and "_chunk_fn" in fs[0].message
    # the same factory returning through DEVTIME.register is clean
    ok = src.replace(
        "return jax.jit(run, donate_argnums=(0,))",
        "return DEVTIME.register('completer.chunk',\n"
        "        jax.jit(run, donate_argnums=(0,)))")
    assert run_rule(splint, R, core, runner, "SPL205", files={
        "libsplinter_tpu/models/foo.py": ok}) == []


def test_spl205_scope_and_module_level_semantics(splint, R, core,
                                                 runner):
    # a partial(jax.jit, ...) decorator on a module-level function is
    # a jit program too — flagged when no scope registers it
    deco = ("import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def _kernel(x, n):\n"
            "    return x * n\n")
    fs = run_rule(splint, R, core, runner, "SPL205",
                  files={"libsplinter_tpu/ops/foo.py": deco})
    assert len(fs) == 1 and fs[0].line == 3
    # a module-level jit assignment registered in the same statement
    # is clean; unregistered flags
    mod = ("import jax\n"
           "prog = DEVTIME.register('searcher.topk', jax.jit(run))\n"
           "bare = jax.jit(other)\n")
    fs = run_rule(splint, R, core, runner, "SPL205",
                  files={"libsplinter_tpu/ops/foo.py": mod})
    assert len(fs) == 1 and fs[0].line == 3
    # module-level pallas_call is a program of its own; inside a
    # function it is an internal of the enclosing jit program
    pal = ("import jax\n"
           "grid_fn = pl.pallas_call(kern, grid=(4,))\n"
           "def scores(x):\n"
           "    return pl.pallas_call(kern, grid=(4,))(x)\n")
    fs = run_rule(splint, R, core, runner, "SPL205",
                  files={"libsplinter_tpu/ops/foo.py": pal})
    assert len(fs) == 1 and fs[0].line == 2 \
        and "pallas_call" in fs[0].message
    # engine/ and parallel/ trees are out of scope — programs there
    # are built by the models/ops factories this rule already covers
    assert run_rule(splint, R, core, runner, "SPL205", files={
        "libsplinter_tpu/engine/foo.py": deco,
        "libsplinter_tpu/parallel/foo.py": deco}) == []


def test_global_rng_in_fault_path_flagged(splint, R, core, runner):
    src = ("import random\n"
           "def step():\n"
           "    fault('x.y')\n"
           "    if random.random() < 0.5:\n"
           "        return 1\n")
    fs = run_rule(splint, R, core, runner, "SPL204",
                  files={"libsplinter_tpu/engine/foo.py": src})
    assert len(fs) == 1 and "random.random" in fs[0].message
    # a seeded instance draw is fine
    ok = src.replace("random.random()", "rng.random()")
    assert run_rule(splint, R, core, runner, "SPL204", files={
        "libsplinter_tpu/engine/foo.py": ok}) == []


# ----------------------------------------------- meta + report shape

def test_rule_catalog_matches_docs_table(core):
    """The docs/operations.md rule table is generated from the rule
    registry — ids must match EXACTLY (a rule that runs undocumented
    or a documented rule that doesn't run both fail)."""
    with open(os.path.join(ROOT, "docs", "operations.md")) as f:
        ops = f.read()
    begin = ops.index("splint:rule-catalog:begin")
    end = ops.index("splint:rule-catalog:end")
    table = ops[begin:end]
    doc_ids = set(re.findall(r"\| `(SPL\d+)` \|", table))
    assert doc_ids == set(core.RULES)


def test_rule_table_render_matches_committed(core):
    with open(os.path.join(ROOT, "docs", "operations.md")) as f:
        ops = f.read()
    assert core.render_rule_table() in ops, \
        "docs rule table stale — run scripts/gen_api_docs.py"


def test_report_line_format(core):
    f = core.Finding("a/b.py", 7, "SPL101", "boom")
    assert f.render() == "a/b.py:7 · SPL101 · boom"
    assert f.fingerprint() == "SPL101 · a/b.py · boom"


def test_every_rule_has_fixture_coverage():
    """Each cataloged rule id must appear in this test file beyond
    the catalog itself — a rule without a fixture is unverified."""
    splint = _load_splint()
    with open(os.path.abspath(__file__)) as f:
        me = f.read()
    for rid in splint.RULES:
        assert me.count(rid) >= 1, f"no fixture exercises {rid}"
