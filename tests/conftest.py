"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

This is how the suite exercises multi-chip SPMD (pjit partitioning,
collectives, checkpoint shard round-trips) without TPU hardware —
SURVEY.md sec 4's test strategy.
"""
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# force the 8-device virtual CPU platform before any backend initializes
from _cpuhost import force_cpu_platform  # noqa: E402

assert force_cpu_platform(8), (
    "could not force an 8-device virtual CPU platform (a backend with the "
    "wrong platform or device count already initialized in this process); "
    "run pytest in a fresh interpreter")

import jax  # noqa: E402

# The suite calls the CLI main()s in-process, and those point JAX's
# persistent compile cache at <repo>/.jax_cache. Tests stay off it: a
# result must not depend on what an earlier session left on disk.
jax.config.update("jax_enable_compilation_cache", False)

# CPU async dispatch queues eager computations behind an in-flight
# semaphore shared process-wide; late in the suite (hundreds of jitted
# programs, host callbacks, and 8-virtual-device collectives behind us)
# a dispatch of a sharded eager op can block forever on that semaphore /
# collective rendezvous — reproduced as a futex-wait hang with an idle
# runtime pool in test_train_rlhf's minibatch jnp.take. Synchronous
# dispatch sidesteps the queue entirely; throughput here is bounded by
# the computations themselves, so the cost is noise.
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'`: long soaks (e.g. the serving
    # chaos soak) register here so deselection works without warnings
    config.addinivalue_line(
        "markers", "slow: long-running soak/stress test, excluded from "
        "the tier-1 `-m 'not slow'` run")


@pytest.fixture(scope="session", autouse=True)
def lock_witness(tmp_path_factory):
    """Install the runtime lock witness (docs/ANALYSIS.md) for the whole
    tier-1 run: every repo-created threading.Lock/RLock reports its
    acquisition order, so the concurrency-heavy tests double as
    lock-order probes. A cycle in the observed graph fails the session
    and leaves postmortem_lock_cycle.json for tools/dla_doctor.py.
    Disable with DLA_WITNESS=0."""
    if os.environ.get("DLA_WITNESS", "1") == "0":
        yield None
        return
    from dla_tpu.analysis.witness import install_witness, uninstall_witness
    witness = install_witness()
    yield witness
    out = str(tmp_path_factory.mktemp("lock-witness"))
    cycles = witness.check(out)
    uninstall_witness()
    assert not cycles, (
        "runtime lock-order cycle observed during the test session "
        f"(postmortem in {out}/postmortem_lock_cycle.json): {cycles}")


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    assert len(jax.devices()) == 8, (
        "expected 8 virtual CPU devices; run tests via "
        "JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return build_mesh(MeshConfig(data=2, fsdp=2, model=2, sequence=1))


@pytest.fixture(scope="session")
def tiny_cfg():
    from dla_tpu.models.config import get_model_config
    return get_model_config("tiny")
