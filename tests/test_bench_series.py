"""The unified bench series runner (bench_series.py) is the
measurement spine: one process must yield the whole evidence set, with
per-phase fencing so one bad phase can't erase the rest — and a
non-zero exit whenever a phase failed or no chip was found.  These
tests drive the orchestration logic with stub phases (fast) and real
phases (tiny shapes, the labelled BENCH_CPU=1 quick-track) end to
end."""
from __future__ import annotations

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench_series  # noqa: E402


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setattr(bench_series, "RESULTS_LOG", str(path))
    # the suite runs on the CPU: every bench test is the explicit,
    # labelled quick-track (test_no_chip_is_an_error unsets it)
    monkeypatch.setenv("BENCH_CPU", "1")
    return path


def read_ledger(path):
    return [json.loads(ln) for ln in path.read_text().splitlines()]


def test_phase_fencing_and_status(ledger, monkeypatch):
    """A failing phase logs + moves on; later phases still record —
    and the run exits non-zero: no success after a failed phase."""
    calls = []

    def ok_phase(ctx):
        calls.append("ok")
        return ctx.record({"metric": "m_ok", "value": 1.0,
                           "unit": "u", "vs_baseline": 0.0})

    def bad_phase(ctx):
        calls.append("bad")
        raise RuntimeError("synthetic failure")

    monkeypatch.setitem(bench_series.PHASE_FNS, "embed", bad_phase)
    monkeypatch.setitem(bench_series.PHASE_FNS, "profile", ok_phase)
    ctx = bench_series.run_series(phases=("embed", "profile"))
    assert calls == ["bad", "ok"]
    assert ctx.phase_status == {"embed": "failed", "profile": "ok"}
    assert ctx.headline is None
    recs = read_ledger(ledger)
    assert len(recs) == 1 and recs[0]["metric"] == "m_ok"
    assert "ts" in recs[0]
    assert recs[0]["cpu_quick_track"] is True     # labels itself
    monkeypatch.setenv("BENCH_PHASES", "embed,profile")
    assert bench_series.main() == 1
    assert bench_series.shim_main("profile", "embed") == 1
    assert bench_series.shim_main("profile") == 0


def test_no_chip_is_an_error(ledger, monkeypatch):
    """A run meant for the chip raises when JAX finds none — before
    any phase runs, at CPU sizes or otherwise."""
    ran = []
    monkeypatch.setitem(bench_series.PHASE_FNS, "embed",
                        lambda ctx: ran.append("embed"))
    monkeypatch.delenv("BENCH_CPU")
    with pytest.raises(RuntimeError, match="no TPU"):
        bench_series.run_series(phases=("embed",))
    assert not ran
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    ctx.backend = "cpu"
    for phase in (bench_series.phase_embed, bench_series.phase_kernels,
                  bench_series.phase_multichip):
        with pytest.raises(RuntimeError, match="no TPU"):
            phase(ctx)


def test_unknown_device_kind_raises(monkeypatch):
    """MFU is normalized against a known peak or not at all."""
    import jax

    class Dev:
        device_kind = "TPU v99 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(RuntimeError, match="no peak FLOP/s known"):
        bench_series._tpu_peak_flops()
    Dev.device_kind = "TPU v5 lite"
    assert bench_series._tpu_peak_flops() == (197e12, "TPU v5 lite")


def test_deadline_skips_nonembed_phases(ledger, monkeypatch):
    """Past the window, non-embed phases skip; embed always runs."""
    ran = []
    monkeypatch.setitem(
        bench_series.PHASE_FNS, "embed",
        lambda ctx: ran.append("embed") or ctx.record(
            {"metric": "e", "value": 1.0, "unit": "u",
             "vs_baseline": 0.0}))
    monkeypatch.setitem(
        bench_series.PHASE_FNS, "kernels",
        lambda ctx: ran.append("kernels"))
    ctx = bench_series.run_series(
        phases=("embed", "kernels"),
        deadline_epoch=time.time() + 5)   # < every non-embed floor
    assert ran == ["embed"]
    assert ctx.phase_status == {"embed": "ok", "kernels": "skipped"}


def test_embed_phase_real(ledger, monkeypatch):
    """The REAL phase_embed, driven end to end at tiny sizes: the
    headline lands on the ctx and in the ledger the moment the phase
    completes (the ledger, not a side file, is what survives a later
    phase's failure)."""
    monkeypatch.setenv("BENCH_TEXTS", "8")
    monkeypatch.setenv("BENCH_BATCH", "4")
    monkeypatch.setenv("BENCH_BUCKETS", "32")
    monkeypatch.setenv("BENCH_P50_PROBES", "2")
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    import jax
    ctx.backend = jax.default_backend()
    ctx.n_devices = len(jax.devices())
    rec = bench_series.phase_embed(ctx)
    assert rec["metric"] == "embeddings_per_sec_per_chip"
    assert rec["value"] > 0
    assert ctx.headline is rec
    # the ledger got the same record (with a timestamp and the label)
    led = read_ledger(ledger)
    assert led[0]["metric"] == "embeddings_per_sec_per_chip"
    assert led[0]["value"] == rec["value"] and "ts" in led[0]
    assert led[0]["cpu_quick_track"] is True
    assert led[0]["detail"]["p50_samples"] == 2


def test_series_complete_requires_all_phases(ledger, monkeypatch, capsys):
    """ADVICE r4 (medium): series_complete means ALL_PHASES ran ok — a
    phase-restricted run must report false even when everything it was
    asked to run succeeded."""
    def embed_phase(ctx):
        ctx.headline = ctx.record(
            {"metric": "embeddings_per_sec_per_chip", "value": 5.0,
             "unit": "u", "vs_baseline": 0.1})

    monkeypatch.setitem(bench_series.PHASE_FNS, "embed", embed_phase)
    monkeypatch.setenv("BENCH_PHASES", "embed")
    assert bench_series.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["series_complete"] is False

    for name in bench_series.ALL_PHASES:
        if name != "embed":
            monkeypatch.setitem(
                bench_series.PHASE_FNS, name, lambda ctx: None)
    monkeypatch.setenv("BENCH_PHASES", ",".join(bench_series.ALL_PHASES))
    assert bench_series.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["series_complete"] is True


def test_store_ops_phase_real(ledger, monkeypatch):
    """The store_ops phase end to end at a short duration: runs the
    native stress harnesses in --json mode, asserts integrity, and
    ledgers the reference-contract comparison."""
    import subprocess

    build = os.path.join(ROOT, "native", "build")
    if not os.path.exists(os.path.join(build, "spt_stress")):
        subprocess.run(["make", "tests"],
                       cwd=os.path.join(ROOT, "native"), check=True)
    monkeypatch.setenv("STORE_OPS_MS", "300")
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    rec = bench_series.phase_store_ops(ctx)
    assert rec["value"] > 0
    d = rec["detail"]
    assert d["mrsw_raw"]["corrupt"] == 0
    assert d["mrmw"]["corrupt"] == 0
    assert d["mrmw"]["writers"] == 32
    assert d["write_cpo"] > 0
    assert d["reference"]["write_cpo"] == 937.0
    led = read_ledger(ledger)
    assert led[0]["metric"] == "store_ops_per_sec"


def test_kernels_phase_real(ledger, monkeypatch):
    """The kernels phase end to end at tiny sizes: every kernel runs
    (interpret mode off-TPU), numerics checked vs the jnp oracle, and
    the record carries ok flags."""
    monkeypatch.setenv("KERNELS_SEQ", "64")
    monkeypatch.setenv("KERNELS_ROWS", "1024")
    monkeypatch.setenv("KERNELS_REPS", "2")
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    import jax
    ctx.backend = jax.default_backend()
    rec = bench_series.phase_kernels(ctx)
    assert rec["value"] == 1.0, rec          # every ok flag true
    d = rec["detail"]
    assert d["flash_fwd"]["ok"] and d["flash_bwd"]["ok"]
    assert d["causal_prefill_gqa"]["ok"] and d["cosine_topk"]["ok"]
    assert read_ledger(ledger)[0]["metric"] == "kernels_smoke"


@pytest.mark.slow
def test_multichip_phase_real(ledger, monkeypatch):
    """The pod-sharded paged arm end to end on the virtual 8-device
    CPU mesh (tiny geometry): batch {32, 64} rows ledger with the
    LOUD cpu_mesh_smoke label."""
    monkeypatch.setenv("MULTICHIP_TOKENS", "8")
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    import jax
    ctx.backend = jax.default_backend()
    ctx.n_devices = len(jax.devices())
    rec = bench_series.phase_multichip(ctx)
    d = rec["detail"]
    assert d["n_devices"] == 8 and d["tp"] >= 2
    assert d["cpu_mesh_smoke"] is True       # never a perf claim here
    assert set(d["tokens_per_sec_by_batch"]) == {"32", "64"}
    assert all(v > 0 for v in d["tokens_per_sec_by_batch"].values())
    assert rec["vs_baseline"] == 0.0     # no one-chip row to divide by
    assert read_ledger(ledger)[0]["metric"] == \
        "multichip_paged_tokens_per_sec"


def test_multichip_phase_single_device_skips(ledger, monkeypatch):
    """A single-chip claim cannot shard: the phase ledgers an explicit
    skip row (series_complete stays true) instead of failing."""
    ctx = bench_series.SeriesCtx(time.time() + 3600)
    ctx.backend = "cpu"
    ctx.n_devices = 1
    rec = bench_series.phase_multichip(ctx)
    assert "skipped" in rec["detail"]
    assert read_ledger(ledger)[0]["value"] == 0.0
