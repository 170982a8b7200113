"""Scale-out dress rehearsal (r4 VERDICT item 8): the virtual-mesh
memory-analysis tool must compile real configs at 16 and 32 devices and
report per-device numbers that scale with the mesh."""
import json
import subprocess
import sys
from pathlib import Path

import pytest
import yaml

REPO_ROOT = Path(__file__).resolve().parent.parent


def _run_rehearsal(tmp_path, n_devices, mesh_override, **model_over):
    sys.path.insert(0, str(REPO_ROOT))
    from _cpuhost import cpu_child_env

    cfg = yaml.safe_load(
        (REPO_ROOT / "config" / "sft_llama2_70b_v5e256.yaml").read_text())
    cfg["model"]["model_name_or_path"] = "tiny-gqa"
    cfg["model"]["max_seq_length"] = 128
    cfg["model"].update(model_over)
    cfg["optimization"]["micro_batch_size"] = 2
    cfg["optimization"]["total_batch_size"] = (
        2 * mesh_override.get("fsdp", 1) * mesh_override.get("data", 1)
        * int(cfg["hardware"]["gradient_accumulation_steps"]))
    p = tmp_path / "rehearse.yaml"
    p.write_text(yaml.safe_dump(cfg))
    mesh_s = ",".join(f"{k}={v}" for k, v in mesh_override.items())
    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "scale_rehearsal.py"),
         str(p), str(n_devices), mesh_s],
        env=cpu_child_env(n_devices, str(REPO_ROOT)),
        cwd=str(REPO_ROOT), capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_16_devices(tmp_path):
    r = _run_rehearsal(tmp_path, 16, {"fsdp": 8, "model": 2})
    assert r["n_devices"] == 16
    assert r["mesh"]["fsdp"] == 8 and r["mesh"]["model"] == 2
    assert r["per_device"]["total_gb"] > 0
    assert r["fits_v5e"] is True  # tiny model trivially fits


def test_rehearsal_32_devices_args_shrink(tmp_path):
    """Per-device argument bytes (params + opt shards) must shrink as
    the fsdp axis widens — the partitioned-residency claim itself."""
    r16 = _run_rehearsal(tmp_path, 16, {"fsdp": 8, "model": 2})
    r32 = _run_rehearsal(tmp_path, 32, {"fsdp": 16, "model": 2})
    assert r32["per_device"]["arguments_gb"] < r16["per_device"]["arguments_gb"]


def test_rehearsal_pp_config_compiles(tmp_path):
    """PP configs rehearse in their real dtype: the tool disables
    XLA:CPU's all-reduce-promotion pass (which check-fails on the
    pipeline shard_map program, "Invalid binary instruction opcode
    copy" — CPU-only pass, bisected r5; irrelevant to a compile-only
    analysis and never run on TPU)."""
    r = _run_rehearsal(tmp_path, 16, {"stage": 2, "fsdp": 4, "model": 2},
                       pipeline_microbatches=4)
    assert r["mesh"]["stage"] == 2
    assert r["per_device"]["total_gb"] > 0
