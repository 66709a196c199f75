"""JAX platform selection helpers.

A chip belongs to one process at a time: the process that first
touches JAX opens every chip of its host and keeps them until it
exits.  So CPU-by-contract entry points (CLI, tests, dry runs) call
force_cpu() BEFORE any device access, and a chip host runs its daemons
either one process per host or — on several chips — one process per
chip with the runtime's own visibility variables set before start.

Reference analog: the splinter CLI never touches the accelerator at
all (scoring is scalar C, splinter_cli_cmd_search.c:43-62); here quick
CLI commands must actively stay off the chip a daemon usually holds.
"""
from __future__ import annotations

import os

_REPO_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..",
    ".xla_cache"))


def force_cpu(num_devices: int | None = None) -> None:
    """Pin this process's JAX onto the CPU backend: sets
    JAX_PLATFORMS=cpu (inherited by subprocesses) and the matching
    config value.  Call before JAX initialises; once a backend is up
    the platform no longer changes and asking for `num_devices`
    raises RuntimeError — the caller asked too late."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    if num_devices is not None:
        jax.config.update("jax_num_cpu_devices", num_devices)


def enable_compile_cache() -> str:
    """Turn on XLA's persistent compilation cache and return its
    directory.

    Drain batches have data-dependent (power-of-two) batch shapes; the
    first encounter of a shape costs a multi-second compile.  With the
    persistent cache, every shape compiles ONCE per machine — daemon
    restarts and repeated bench runs start warm.  Call before the
    first jit execution.

    The directory is placed from OUTSIDE: where JAX_COMPILATION_CACHE_DIR
    is set JAX already reads it and nothing here overrides it; otherwise
    it is the one fixed path `<repo>/.xla_cache` (the path is part of
    the cache key, so it must never move).
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _REPO_CACHE
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def apply_chip_pin(spec: str) -> None:
    """Bind this process's jax.default_device to device ordinal `spec`
    (the supervisor's --pin-chips plumbing: children receive it as
    SPTPU_CHIP_PIN before warmup).  Raises ValueError on a spec that
    is not an ordinal of this process's devices: a pin that silently
    did not take would leave every replica on device 0.

    The pin chooses among the devices this process can SEE; it does
    not keep the runtime from opening the others (README "One process
    per chip").
    """
    import jax

    try:
        ordinal = int(str(spec).strip())
    except (TypeError, ValueError):
        raise ValueError(
            f"SPTPU_CHIP_PIN={spec!r} is not a device ordinal") from None
    devices = jax.devices()
    if not 0 <= ordinal < len(devices):
        raise ValueError(
            f"SPTPU_CHIP_PIN={ordinal} out of range: this process "
            f"sees {len(devices)} device(s)")
    jax.config.update("jax_default_device", devices[ordinal])
