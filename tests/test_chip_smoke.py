"""chip_smoke.py on the CPU: it must refuse to stand in for a chip run,
its tiny rehearsal must pass every phase, the compile-cache helper must
be placeable from outside, and the peak tables must refuse a chip they
do not know."""
import json

import jax
import pytest

import chip_smoke
from dla_tpu.telemetry.mfu import hbm_bw_for, peak_flops_for
from dla_tpu.utils import compile_cache


def test_refuses_to_run_without_a_tpu(tmp_path, capsys):
    rc = chip_smoke.main(["--out", str(tmp_path)])
    captured = capsys.readouterr()
    assert rc != 0
    assert "platform is 'cpu'" in captured.err
    assert captured.out == ""          # no result line to mistake for a pass
    assert not (tmp_path / "report.json").exists()


def test_rehearsal_passes_every_phase(tmp_path, capsys):
    rc = chip_smoke.main(["--rehearsal", "--out", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [ln for ln in out.splitlines() if "[chip_smoke]" in ln]
    assert lines and all(ln.startswith("REHEARSAL ") for ln in lines)
    for phase in ("kernels", "trainer", "server"):
        assert f"PASS {phase}" in out
    # the multi-device trainer variant ran: the suite has 8 CPU devices
    assert "'model': 2" in out and "over 8 device(s)" in out
    last = out.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")   # never a bare chip-pass line
    assert json.loads(last[len("REHEARSAL "):])["ok"] is True
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["rehearsal"] and all(report["phases"].values())
    assert not (tmp_path / "work").exists()     # GBs on the chip
    assert jax.config.read("jax_dump_ir_to") is None


def test_compile_cache_is_placed_from_outside_or_at_the_fixed_path(
        monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper set nothing in code
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = compile_cache.enable_compile_cache()
        assert fixed == str(compile_cache.DEFAULT_CACHE_DIR)
        assert fixed == compile_cache.enable_compile_cache()  # never moves
        assert compile_cache.DEFAULT_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_CACHE_DIR.parent / "chip_smoke.py"
                ).is_file()                    # inside the checkout
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("lookup", [peak_flops_for, hbm_bw_for])
def test_unknown_accelerator_kind_is_an_error(lookup):
    assert lookup("TPU v5 lite", "tpu") > 0
    with pytest.raises(ValueError, match="some new chip"):
        lookup("some new chip", "tpu")
