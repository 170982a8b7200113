"""The virtual-CPU platform for tests and CPU-sized tools: several host
devices stand in for a multi-chip mesh (pjit partitioning, collectives,
checkpoint shard round-trips) without hardware.

Stdlib-only at module level (jax is imported lazily inside functions),
so this is importable before jax in conftest-style preambles.
"""
from __future__ import annotations

import os
import re
from typing import Optional


def set_cpu_env(n_devices: Optional[int] = None,
                env: Optional[dict] = None) -> dict:
    """Set JAX_PLATFORMS=cpu (+ host device count) on ``env`` (default:
    os.environ). An existing device-count flag with a DIFFERENT value is
    replaced, not kept — otherwise a caller needing 8 devices inherits an
    ambient count of 4 forever. Returns the mapping for chaining."""
    env = os.environ if env is None else env
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = env.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", want, flags)
        else:
            flags = (flags + " " + want).strip()
        env["XLA_FLAGS"] = flags
    return env


def force_cpu_platform(n_devices: Optional[int] = None) -> bool:
    """In-process forcing, before any backend initializes. Returns True
    when the live backend is CPU with at least ``n_devices`` devices (or
    just CPU when n_devices is None); False means a backend with the
    wrong platform/count already exists and the caller needs a fresh
    process (see :func:`cpu_child_env`)."""
    set_cpu_env(n_devices)
    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != "cpu":
        return False
    return n_devices is None or len(devices) >= n_devices


def cpu_child_env(n_devices: Optional[int] = None,
                  repo_root: Optional[str] = None) -> dict:
    """Environment for a child process that must come up on the virtual
    CPU platform, with ``repo_root`` importable."""
    env = set_cpu_env(n_devices, dict(os.environ))
    if repo_root:
        env["PYTHONPATH"] = (
            repo_root + os.pathsep + env.get("PYTHONPATH", "")
        ).rstrip(os.pathsep)
    return env
