"""When the command must refuse: no TPU, no program. It exits with a code
other than 0 and prints no result line; a CPU number never appears under
a device metric's name."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

from perfbench.tests.conftest import ROOT

ARGS = ["--workload", "sft_packed_1chip", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def run(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *ARGS, *extra], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def no_result_line(stdout: str) -> bool:
    lines = [l for l in stdout.splitlines() if l.strip()]
    return not lines or not lines[-1].lstrip().startswith("{")


def test_without_a_tpu_there_is_no_result():
    proc = run(ROOT)
    assert proc.returncode != 0
    assert no_result_line(proc.stdout)
    assert "no TPU" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(Path(ROOT) / "BENCHMARK.json", tmp_path)
    shutil.copytree(Path(ROOT) / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result_line(proc.stdout)
    assert "program is not in this directory" in proc.stderr


def test_a_rehearsal_says_so_on_every_line_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "serve_decode_heavy", "--seed", str(2 ** 31 + 3), "--seconds", "2",
         "--trace", "0", "--rehearsal"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert no_result_line(proc.stdout)
    ours = [l for l in (proc.stdout + proc.stderr).splitlines()
            if "perfbench" in l or l.startswith("REHEARSAL")]
    assert ours and all(l.startswith("REHEARSAL") for l in ours)
    assert '"correct": true' in proc.stdout
