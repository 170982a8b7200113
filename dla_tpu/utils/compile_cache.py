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

import os
from pathlib import Path

import jax

#: the in-checkout default (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compile cache at its directory and return
    that directory. Safe to call more than once."""
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
