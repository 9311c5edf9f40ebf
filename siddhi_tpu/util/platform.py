"""Process-level JAX set-up: the virtual CPU mesh for tests and rehearsals,
and the persistent compilation cache for anything that runs on the chip.

``JAX_PLATFORMS=cpu`` in the environment is enough to get the CPU backend;
``force_cpu_platform`` additionally sets the virtual device count, which jax
only accepts before the backend starts. Used by ``tests/conftest.py``, the
test worker scripts and ``__graft_entry__``.

``configure_compile_cache`` is called by every entry point that compiles for
the chip (``chip_smoke.py``, ``benchmarks/run.py``, ``python -m
siddhi_tpu.service``) so that a second process does not compile what the
first one already did.
"""

from __future__ import annotations

import os
import re

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: where compiled executables persist when the environment names no place:
#: one fixed directory inside the checkout (git-ignored). Never derived from
#: tempfile, a pid or the clock — a cache that moves is never hit.
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def set_host_device_count_flag(flags: str, n_devices: int) -> str:
    """Return ``flags`` with ``--xla_force_host_platform_device_count`` set
    to exactly ``n_devices``, replacing any inherited count rather than
    trusting it (it may be smaller than what we need). For child processes
    whose device count must be fixed through the environment, before their
    interpreter imports jax."""
    flag = "--xla_force_host_platform_device_count"
    if flag in flags:
        return re.sub(rf"{flag}=\S+", f"{flag}={n_devices}", flags)
    return (flags + f" {flag}={n_devices}").strip()


def force_cpu_platform(n_devices: int) -> None:
    """Select the CPU backend with ``n_devices`` virtual devices. Call it
    before the backend starts: jax raises RuntimeError on a device-count
    change afterwards (re-asserting the current count is fine)."""
    os.environ["JAX_PLATFORMS"] = "cpu"  # inherited by worker subprocesses

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def configure_compile_cache() -> str | None:
    """Enable jax's persistent compilation cache; returns the directory it
    uses, or None where it stays off. Call it before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
    sets no other path; otherwise the cache lives at ``COMPILE_CACHE_DIR``
    — except on the CPU backend, which is left uncached unless the variable
    asks for it: XLA:CPU executables are tied to the host's CPU features
    (the loader logs a page of them on every hit, enough to fill a worker's
    stderr pipe) and cost seconds, not minutes, to rebuild.

    Every executable is kept, whatever its compile time: on the v5e the
    flagship deployment builds 29 programs, of which 27 (the filter step,
    read-back packing, one-op eager programs) compile in under a second
    each — jax's default one-second floor would rebuild those in every
    process (PR 21: 27 misses cold, 29 hits in the next process)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.default_backend() == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
