"""Run the native C test tiers from pytest so `pytest tests/` covers the
whole stack (reference: CTest wires splinter_test + stress + chi_sao,
CMakeLists.txt:267-329)."""
import pathlib
import subprocess

import pytest

NATIVE = pathlib.Path(__file__).parent.parent / "native"


def _build(target: str) -> None:
    subprocess.run(["make", "-s", target], cwd=NATIVE, check=True,
                   capture_output=True, timeout=300)


def test_native_tap_unit_suite():
    """The C TAP behavioral suite, both shm and file backends."""
    _build("tests")
    r = subprocess.run([str(NATIVE / "build" / "spt_unit")],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"TAP failures:\n{r.stdout}"
    assert "0 failed" in r.stdout


@pytest.mark.slow
def test_native_stress_short():
    """MRSW integrity under fire, short run (CTest runs 7.5 s;
    CI-speed 2 s here — the full duration is `make check`)."""
    _build("tests")
    r = subprocess.run([str(NATIVE / "build" / "spt_stress"),
                        "--duration-ms", "2000"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "corrupt=0" in r.stdout


@pytest.mark.parametrize("writers", [1, 4])
def test_native_stress_journal_follower(writers):
    """spt_stress's follower consumes the change journal the way the
    device lane does, beside concurrent writers; when they stop, an
    audit of every slot must find nothing that moved without a
    record (journal + audit = the slots that moved, audit share 0)."""
    import re

    _build("tests")
    r = subprocess.run([str(NATIVE / "build" / "spt_stress"),
                        "--duration-ms", "400", "--writers", str(writers),
                        "--readers", "2", "--keys", "500"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    m = re.search(r"journal: rows=(\d+) .* moved=(\d+) audit=(\d+)",
                  r.stdout)
    assert m, r.stdout
    rows, moved, audit = map(int, m.groups())
    assert rows > 0 and moved == 500 and audit == 0
    assert "corrupt=0" in r.stdout


@pytest.mark.parametrize("writers,lap", [(1, False), (4, False), (4, True)])
def test_native_stress_label_follower(writers, lap):
    """spt_stress's raisers raise a label on the hot keys against a
    follower that learns who asks from the change journal alone, the
    way the search daemon's gather does (held rows, deferrals); with
    --label-lap it lets the writers lap its cursor and walks every
    slot instead (-EOVERFLOW).  Every raise is served: none left."""
    import re

    _build("tests")
    r = subprocess.run([str(NATIVE / "build" / "spt_stress"),
                        "--duration-ms", "600", "--writers", str(writers),
                        "--readers", "2", "--keys", "500"]
                       + (["--label-lap"] if lap else []),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    m = re.search(r"labels: raised=(\d+) served=(\d+) passes=(\d+) "
                  r"fallbacks=(\d+) deferred=(\d+) left=(\d+)", r.stdout)
    assert m, r.stdout
    raised, served, passes, fallbacks, deferred, left = map(int, m.groups())
    assert raised == served > 0 and left == 0 and deferred > 0
    if lap:
        assert fallbacks > 0, r.stdout
    assert "LABEL FAILURE" not in r.stderr
