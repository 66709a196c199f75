"""chip_smoke.py is the quickest proof that the system still starts on
the chip — these tests keep the SCRIPT honest where there is no chip:
the CPU rehearsal runs every phase through the daemons' real main()s,
a CPU run without --rehearse fails loudly, and the two start-up rules
the script leans on hold (importing the package leaves jax alone; the
compile cache is placed from outside or at one fixed path).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
DEVICE_KEYS = {"platform", "kind", "count"}


def _run(*args, env=None, timeout=600):
    """(returncode, [json of every stdout line])."""
    p = subprocess.run([sys.executable, SMOKE, *args], env=env,
                       capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.strip()]
    return p.returncode, lines, p.stderr


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("XLA_FLAGS", None)      # conftest's 8 devices are not ours
    return env


def _check_last(last, *, ok, count):
    assert set(last) >= {"ok", "device"} and last["ok"] is ok
    assert set(last["device"]) == DEVICE_KEYS
    # a rehearsal can never claim the chip
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == count


def test_rehearse_runs_every_phase(tmp_path):
    cache = tmp_path / "xla"
    rc, lines, err = _run(
        "--rehearse", env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert rc == 0, (lines[-1:], err[-2000:])
    _check_last(lines[-1], ok=True, count=1)
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(phases) == ["build", "embed", "search", "complete"]
    for rec in phases.values():
        assert rec["ok"] is True and rec["seconds"] > 0
        assert set(rec["device"]) == DEVICE_KEYS
    emb, sr, comp = (phases[p] for p in ("embed", "search", "complete"))
    assert emb["texts"] == 96 and emb["min_cos"] >= emb["bar"]
    assert emb["compile_seconds"] > 0 and not any(
        emb["faults"].values())
    assert sr["queries"] == 14 and sr["coalesced_max"] > 1
    assert sr["max_score_err"] <= sr["tol"]
    assert not any(sr["faults"].values())
    for kind in ("bf16", "int8", "int4"):
        lane = comp[f"paged_{kind}"]
        assert lane["first_token_equal"] is True
        assert lane["agreement"] >= comp["judge"]["agree_bar"][kind]
        assert comp["kernel_vs_jnp"]["kernels"][kind]["rel_err"] \
            <= comp["kernel_vs_jnp"]["tol"]
    assert comp["paged_int8"]["pool_pages"] >= 1025
    assert comp["paged_int4"]["pool_pages"] >= 1025
    # JAX opened its cache where the environment said (the directory
    # exists; entries appear only for compiles over the 0.5 s floor,
    # which the rehearsal's tiny programs stay under)
    assert cache.is_dir()


def test_rehearse_multichip_on_four_virtual_devices():
    rc, lines, err = _run("--rehearse", "--multichip", env=_cpu_env())
    assert rc == 0, (lines[-1:], err[-2000:])
    _check_last(lines[-1], ok=True, count=4)
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    # the four-chip paths and their comparisons, and no other phase
    assert list(phases) == ["build", "pod_search", "tp_decode"]
    place = phases["pod_search"]["placement"]
    assert place["devices"] == 4 and len(set(place["shard_bytes"])) == 1
    assert sum(place["shard_bytes"]) == place["total_bytes"]
    tp = phases["tp_decode"]
    assert set(tp["pages_shard"]) == {"0", "1", "2", "3"}
    assert tp["tp4"]["first_token_equal"] is True
    assert tp["tp4"]["agreement"] >= tp["judge"]["bar"]


def test_cpu_without_rehearse_fails_loudly():
    rc, lines, _ = _run(env=_cpu_env(), timeout=120)
    assert rc != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert "no TPU" in lines[-1]["error"]
    assert not any("phase" in ln for ln in lines)   # nothing ran


def test_importing_the_package_leaves_jax_alone():
    """The smoke's parent (like any supervisor of chip children) must
    be able to use the store and the client protocol without opening
    the chip."""
    code = ("import sys; import libsplinter_tpu; "
            "import libsplinter_tpu.engine.client; "
            "import libsplinter_tpu.engine.searcher; "
            "import libsplinter_tpu.obs.devtime; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT)
    assert p.returncode == 0


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_is_placed_from_outside(env_dir):
    """With JAX_COMPILATION_CACHE_DIR set, nothing sets a directory in
    code; unset, it is the one fixed <repo>/.xla_cache."""
    code = (
        "import jax\n"
        "from libsplinter_tpu.utils.jaxplatform import "
        "enable_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "got = enable_compile_cache()\n"
        "print(repr((before, got, "
        "jax.config.jax_compilation_cache_dir)))\n")
    env = _cpu_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, check=True)
    before, got, after = eval(out.stdout.strip().splitlines()[-1])
    if env_dir:
        assert before == got == after == env_dir     # left alone
    else:
        assert before is None
        assert got == after == os.path.join(ROOT, ".xla_cache")


def test_single_guarded_cache_call_site():
    hits = subprocess.run(
        ["grep", "-rn", "--include=*.py", "jax_compilation_cache_dir",
         "libsplinter_tpu", "benchmark", "chip_smoke.py", "scripts",
         "__graft_entry__.py"],
        cwd=ROOT, capture_output=True, text=True).stdout.splitlines()
    # the benchmark's plain references import nothing of the package
    # they check, so each keeps its own (equally guarded) site
    assert sorted(h.split(":")[0] for h in hits) == [
        "benchmark/reference/conv_gqa_moe_block.py",
        "benchmark/reference/hybrid_kda_block.py",
        "benchmark/reference/latent_moe_block.py",
        "benchmark/reference/sparse_gqa_moe_block.py",
        "benchmark/reference/ssm_gqa_moe_block.py",
            "benchmark/reference/window_gqa_moe_block.py",
        "benchmark/reference/window_sink_gqa_moe_block.py",
        "libsplinter_tpu/utils/jaxplatform.py"], hits


def test_chip_pin_raises_on_a_bad_ordinal():
    import jax

    from libsplinter_tpu.utils.jaxplatform import apply_chip_pin
    was = jax.config.jax_default_device
    try:
        for bad in ("chip0", "", str(len(jax.devices())), "-1"):
            with pytest.raises(ValueError, match="SPTPU_CHIP_PIN"):
                apply_chip_pin(bad)
        apply_chip_pin("1")
        assert jax.config.jax_default_device == jax.devices()[1]
    finally:
        jax.config.update("jax_default_device", was)
