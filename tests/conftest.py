"""Test config: force JAX onto a virtual 8-device CPU mesh (multi-chip
sharding is validated without TPU hardware; tests/test_chip_compile.py
separately compiles the chip paths for a described v5e) and provide
per-test stores."""
import os

# Tests run on the CPU.  A pytest plugin may have imported jax before
# this conftest runs, but the backend initializes lazily, so the
# config values below still land first.  The env var and the XLA flag
# are for the daemons that tests spawn as subprocesses; any inherited
# device count is REPLACED — the suite's sharding tests assume 8.
os.environ["JAX_PLATFORMS"] = "cpu"

import re as _re

os.environ["XLA_FLAGS"] = (_re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""))
    + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import uuid

import pytest

from libsplinter_tpu import Store


@pytest.fixture
def store():
    name = f"/spt-test-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    st = Store.create(name, nslots=256, max_val=1024, vec_dim=32)
    yield st
    st.close()
    Store.unlink(name)


@pytest.fixture
def store_2k():
    """As `store`, with 2 KiB values: room for a whole daemon
    heartbeat, which the 1 KiB fixture truncates section by section."""
    name = f"/spt-test-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    st = Store.create(name, nslots=256, max_val=2048, vec_dim=32)
    yield st
    st.close()
    Store.unlink(name)


@pytest.fixture
def store_novec():
    name = f"/spt-test-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    st = Store.create(name, nslots=64, max_val=256, vec_dim=0)
    yield st
    st.close()
    Store.unlink(name)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: longer-running stress tiers")
    config.addinivalue_line(
        "markers", "obs: observability tier (histograms, flight "
        "recorder, exposition) — `make obs-check` runs these")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / crash-recovery tier "
        "(SPTPU_FAULT, supervisor) — `make chaos-check` runs these")
