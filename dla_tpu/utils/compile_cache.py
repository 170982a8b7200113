"""Placement of JAX's persistent compilation cache.

A cold XLA:TPU compile of a 7B-width train or decode step takes a large
share of a short run, so every entry point that compiles (the training
and eval CLIs, ``bench.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile. The directory is
chosen from outside when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads
that variable itself, so nothing is touched here — and is otherwise one
fixed path inside the checkout. The path is never derived from a temp
name, pid or timestamp: a cache that moves between runs never hits.
"""
from __future__ import annotations

import contextlib
import os
import sys
from pathlib import Path

import jax

#: the in-checkout default (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compile cache at its directory and return
    that directory; start the process's compile accounting (one
    ``jax.monitoring`` listener: cache hits and misses, lowering and
    compile seconds of every jitted function). Safe to call more than
    once."""
    # here, not at the top: the model and kernel modules import this one
    from dla_tpu.telemetry.xla_introspect import install_compile_accounting
    install_compile_accounting()
    # An executable carries the op_name of each of its instructions (named
    # scopes, JAX's jvp / transpose / remat frames), and a trace's device
    # time is read through them (telemetry.xla_introspect.hlo_scopes). By
    # default the cache key leaves that metadata out, so a run could be
    # handed an executable another version of the code compiled, with that
    # version's names: key on the metadata too.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


@contextlib.contextmanager
def cached_bytecode():
    """Imports made inside keep their Python bytecode beside the XLA
    cache (``<cache dir>/pycache``) and find it there the next time.

    For the modules a process imports late and at a cost: the Pallas
    stack behind a kernel is 129 modules, and where the interpreter
    keeps no bytecode (``PYTHONDONTWRITEBYTECODE=1`` and no
    ``__pycache__`` in the installation: most containers, this
    repository's among them) 0.83 s of its 1.24 s import is
    ``compile()`` of their sources, at every process start (PERF.md,
    PR 35); from the cache the import is 0.2 s. Only while the
    persistent compile cache is on and local: the same operator's
    choice, the same directory, the same staleness rule as any
    ``.pyc`` (source size and mtime). The interpreter's two switches
    are process-wide, so another thread's imports during the block land
    in the cache too; that is harmless, and both are restored."""
    root = jax.config.jax_compilation_cache_dir
    if (not root or not jax.config.jax_enable_compilation_cache
            or "://" in root or sys.pycache_prefix is not None):
        yield
        return
    before = sys.pycache_prefix, sys.dont_write_bytecode
    sys.pycache_prefix = os.path.join(os.path.abspath(root), "pycache")
    sys.dont_write_bytecode = False
    try:
        yield
    finally:
        sys.pycache_prefix, sys.dont_write_bytecode = before
