"""Fault-injection layer (utils/faults.py): spec compilation, trigger
semantics, actions, and the crash action's unclean-exit contract.
`make chaos-check` runs this tier alongside the crash-recovery
matrix."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from libsplinter_tpu.store import Eagain
from libsplinter_tpu.utils import faults
from libsplinter_tpu.utils.faults import (CRASH_EXIT_CODE, FaultInjected,
                                          FaultSpecError, fault)

pytestmark = pytest.mark.chaos

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with no faults armed."""
    faults.disarm()
    yield
    faults.disarm()


# ------------------------------------------------------------- parsing

def test_parse_full_spec():
    n = faults.arm("searcher.commit:crash@3,embedder.encode:raise@p0.1,"
                   "store.set:eagain,completer.commit:stall250@2-4")
    assert n == 4
    s = faults.stats()
    assert s["searcher.commit"]["spec"] == "searcher.commit:crash@3"
    assert s["embedder.encode"]["spec"] == "embedder.encode:raise@p0.1"
    assert s["store.set"]["spec"] == "store.set:eagain"
    assert s["completer.commit"]["spec"] == "completer.commit:stall250@2-4"


def test_registered_sites_shares_the_grammar():
    """registered_sites() is the spec-grammar entry point splint and
    the chaos drills share: spec -> site names in spec order, armed
    plan by default, and a typo fails at parse like arm() would."""
    assert faults.registered_sites(
        "searcher.commit:crash@3, embedder.encode:raise@p0.1,"
        "completer.commit:stall250@2-4") == (
        "searcher.commit", "embedder.encode", "completer.commit")
    assert faults.registered_sites("") == ()
    faults.arm("store.set:eagain")
    assert faults.registered_sites() == ("store.set",)
    faults.disarm()
    assert faults.registered_sites() == ()
    with pytest.raises(FaultSpecError):
        faults.registered_sites("store.set-eagain")


def test_parse_rejects_garbage():
    for bad in ("nosite", "a.b:explode", "a.b:raise@p7", "a.b:crash@0",
                "a.b:crash@5-2", "a.b:stallfast", "a.b:raise@x"):
        with pytest.raises(FaultSpecError):
            faults.arm(bad)


def test_arm_reads_env(monkeypatch):
    monkeypatch.setenv("SPTPU_FAULT", "x.y:raise@1")
    assert faults.arm() == 1
    assert faults.armed()
    monkeypatch.delenv("SPTPU_FAULT")
    assert faults.arm() == 0
    assert not faults.armed()


# ------------------------------------------------------------ triggers

def test_nth_hit_fires_once():
    faults.arm("s.x:raise@3")
    fault("s.x")
    fault("s.x")
    with pytest.raises(FaultInjected):
        fault("s.x")
    fault("s.x")                      # 4th hit: window passed
    st = faults.stats()["s.x"]
    assert st["hits"] == 4 and st["fired"] == 1


def test_hit_range_defeats_retry_ladders():
    faults.arm("s.x:raise@2-3")
    fault("s.x")                      # hit 1: clean
    for _ in range(2):                # hits 2..3: fire
        with pytest.raises(FaultInjected):
            fault("s.x")
    fault("s.x")                      # hit 4: clean again


def test_every_hit_without_trigger():
    faults.arm("s.x:raise")
    for _ in range(3):
        with pytest.raises(FaultInjected):
            fault("s.x")
    assert faults.stats()["s.x"]["fired"] == 3


def test_probability_deterministic_under_seed(monkeypatch):
    monkeypatch.setenv("SPTPU_FAULT_SEED", "1234")
    faults.arm("s.x:raise@p0.5")
    outcomes = []
    for _ in range(64):
        try:
            fault("s.x")
            outcomes.append(False)
        except FaultInjected:
            outcomes.append(True)
    assert 8 < sum(outcomes) < 56     # actually probabilistic
    faults.arm("s.x:raise@p0.5")      # same seed: same sequence
    outcomes2 = []
    for _ in range(64):
        try:
            fault("s.x")
            outcomes2.append(False)
        except FaultInjected:
            outcomes2.append(True)
    assert outcomes == outcomes2


def test_unmatched_site_is_free():
    faults.arm("s.x:raise")
    fault("other.site")               # no entry: no-op
    assert "other.site" not in faults.stats()


# ------------------------------------------------------------- actions

def test_eagain_action_raises_store_eagain():
    faults.arm("s.x:eagain@1")
    with pytest.raises(Eagain):
        fault("s.x")


def test_stall_action_sleeps():
    faults.arm("s.x:stall80@1")
    t0 = time.perf_counter()
    fault("s.x")
    assert (time.perf_counter() - t0) >= 0.06
    t0 = time.perf_counter()
    fault("s.x")                      # past the window: no stall
    assert (time.perf_counter() - t0) < 0.05


def test_crash_action_is_unclean_exit():
    """crash = os._exit(137): no atexit, no finally — the closest
    Python gets to dying at the faulted instruction.  Loads faults.py
    by file path so the child skips the full package import."""
    path = os.path.join(ROOT, "libsplinter_tpu", "utils", "faults.py")
    code = (
        "import atexit, importlib.util, sys\n"
        "atexit.register(lambda: print('ATEXIT RAN'))\n"
        f"spec = importlib.util.spec_from_file_location('flt', {path!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "sys.modules['flt'] = m\n"    # dataclasses resolve via sys.modules
        "spec.loader.exec_module(m)\n"
        "m.arm('s.x:crash@1')\n"
        "try:\n"
        "    m.fault('s.x')\n"
        "finally:\n"
        "    print('FINALLY RAN')\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == CRASH_EXIT_CODE
    assert "FINALLY RAN" not in out.stdout
    assert "ATEXIT RAN" not in out.stdout


def test_disarmed_fault_is_noop_hot_path():
    fault("anything.at.all")          # must simply return


# ---------------------------------------------------- daemon heartbeat

def test_armed_faults_ride_the_searcher_heartbeat(store_2k):
    """With SPTPU_FAULT armed, the daemon heartbeat carries the site
    accounting so `spt metrics` can show which points a drill hit."""
    import json

    from libsplinter_tpu.engine import protocol as P
    from libsplinter_tpu.engine.searcher import Searcher

    store = store_2k             # room for the whole heartbeat: the
    faults.arm("searcher.gather:stall1@999")   # armed, never fires
    sr = Searcher(store)         # 1 KiB fixture drops sections
    sr.attach()
    sr.run_once()
    sr.publish_stats()
    snap = json.loads(store.get(P.KEY_SEARCH_STATS).rstrip(b"\0"))
    assert snap["faults"]["searcher.gather"]["hits"] >= 1
    assert snap["faults"]["searcher.gather"]["fired"] == 0
    assert snap["generation"] == 1
    assert snap["pid"] == os.getpid()
