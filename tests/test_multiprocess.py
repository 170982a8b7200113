"""Two-process jax.distributed CPU test (round-2 verdict next-step 7):
exercises the code paths that silently no-op at process_count() == 1 —
make_array_from_process_local_data, local_numpy's multi-host branch, the
cross-host barrier, and per-host checkpoint shard writes — then restores
the 2-host checkpoint in THIS single process onto a different topology
(the bug class that only appears at process_count > 1 and eats 70B runs).
"""
import json
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_worker_world(worker: str, n_procs: int, devices_per_proc: int,
                      extra_args, ok_marker: str, timeout: int):
    """Launch ``worker`` as an n-process jax.distributed world and assert
    every rank exits 0 and prints its OK marker. Returns the outputs."""
    sys.path.insert(0, str(REPO_ROOT))
    from _cpuhost import cpu_child_env

    port = _free_port()
    env = cpu_child_env(devices_per_proc, str(REPO_ROOT))
    procs = [
        subprocess.Popen(
            [sys.executable, str(REPO_ROOT / "tests" / worker),
             str(port), str(rank), *map(str, extra_args)],
            env=env, cwd=str(REPO_ROOT),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for rank in range(n_procs)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail(f"{worker} world timed out")
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"{worker} rank {rank} failed:\n{out[-4000:]}"
        assert ok_marker.format(rank=rank) in out
    return outs


@pytest.fixture(scope="module")
def two_host_checkpoint(tmp_path_factory):
    """Run the 2-process worker world to completion; yield its ckpt dir."""
    outdir = tmp_path_factory.mktemp("dist_ckpt")
    _run_worker_world("_dist_worker.py", 2, 4, [outdir],
                      "[worker {rank}] OK", timeout=300)
    return outdir


def test_two_process_world_and_shard_writes(two_host_checkpoint):
    """Both workers passed their in-world asserts (global mean over the
    2-host batch, local_numpy slices); the checkpoint they wrote must be
    sharded — one file per index region, no gather through host 0."""
    ckpt = two_host_checkpoint / "step_00000007"
    index = json.loads((ckpt / "index.json").read_text())
    w_meta = index["leaves"]["w"]
    assert "shards" in w_meta, "w should be written as per-region shards"
    # fsdp=2 x model=2 -> 4 distinct index regions
    assert len(w_meta["shards"]) == 4, w_meta["shards"]
    for sh in w_meta["shards"]:
        assert (ckpt / sh["file"]).is_file(), sh
    # replicated leaf: multi-host arrays aren't fully addressable, so it
    # goes through the shard path as ONE whole-array region written by
    # its replica-0 owner (no duplicate writes from the other host)
    b_meta = index["leaves"]["b"]
    assert len(b_meta["shards"]) == 1, b_meta
    assert b_meta["shards"][0]["index"] == [[0, 12]]
    assert (two_host_checkpoint / "latest").read_text().strip() == \
        "step_00000007"


def test_four_process_rlhf_phase_chain(tmp_path):
    """Four-process RLHF smoke (r4 VERDICT item 8): SFT writes its
    checkpoint chain across 4 hosts, then the RLHF loop loads the
    policy through the `latest` pointer and runs rollout steps whose
    prompt sampling and rollout-row assembly are sharded per host
    (train_rlhf.py local_bs = batch / process_count). 2 virtual devices
    per process = one 8-device world."""
    _run_worker_world("_rlhf_dist_worker.py", 4, 2, [tmp_path],
                      "[rlhf-worker {rank}] OK", timeout=600)


def test_cross_topology_restore_from_two_hosts(two_host_checkpoint):
    """Restore the 2-process checkpoint in this single process onto a
    different mesh layout; values must round-trip exactly."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dla_tpu.checkpoint.checkpointer import Checkpointer
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    # different topology than the writers': all 8 devices on fsdp
    mesh = build_mesh(MeshConfig(data=1, fsdp=8, model=1, sequence=1))
    template = {"w": jnp.zeros((16, 12), jnp.float32),
                "b": jnp.zeros((12,), jnp.float32)}
    shardings = {"w": NamedSharding(mesh, P("fsdp", None)),
                 "b": NamedSharding(mesh, P())}
    ck = Checkpointer(str(two_host_checkpoint))
    tree, aux = ck.restore(template, shardings=shardings)
    assert aux["who"] == "dist_worker"
    want = np.arange(16 * 12, dtype=np.float32).reshape(16, 12)
    np.testing.assert_array_equal(np.asarray(tree["w"]), want)
    np.testing.assert_array_equal(np.asarray(tree["b"]),
                                  np.arange(12, dtype=np.float32))
    assert tree["w"].sharding.spec == P("fsdp", None)
