"""tools/sweep_bench.py and tools/sweep_decode.py: variants bind to
run_variant, the decode sweep runs end to end at toy scale on the CPU,
and a sweep whose variant failed says so with its exit code."""
import subprocess
import sys
from pathlib import Path

import pytest

TOOLS = Path(__file__).resolve().parents[1] / "tools"


@pytest.mark.parametrize("tool", ["sweep_bench.py", "sweep_decode.py"])
def test_failed_variant_fails_the_sweep(tool):
    """The parent starts one child per variant and stays off jax itself;
    a child that exits non-zero must fail the whole sweep instead of
    ending in a cheerful "done"."""
    proc = subprocess.run(
        [sys.executable, str(TOOLS / tool), "no_such_variant_a",
         "no_such_variant_b"], capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "FAILED" in proc.stderr and "done" not in proc.stdout


def test_sweep_variants_bind_to_run_variant():
    """Every sweep variant must bind cleanly to run_variant's signature
    (a typo'd kwarg would only surface on the TPU, mid-measurement)."""
    import importlib.util
    import inspect
    import os

    tools_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    for fname in ("sweep_bench.py", "sweep_decode.py"):
        path = os.path.join(tools_dir, fname)
        spec = importlib.util.spec_from_file_location(fname[:-3], path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sig = inspect.signature(mod.run_variant)
        assert mod.VARIANTS, f"{fname} has no variants"
        for name, kw in mod.VARIANTS.items():
            sig.bind(name, **kw)  # raises TypeError on a bad kwarg


def test_sweep_decode_run_variant_smoke():
    """tools/sweep_decode.py run_variant end to end at toy scale on CPU:
    the artifact row must carry its metric fields, with finite values
    and a prefill-subtracted ms/token."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import sweep_decode

    row = sweep_decode.run_variant(
        "smoke", batch=2, prompt=8, new=4, hidden=32, inter=64,
        layers=2, heads=2, kv_heads=1)
    # host-timer noise can push the prefill-SUBTRACTED fields near zero
    # on a contended CPU; the unsubtracted one must be strictly positive
    assert row["ms_per_token_incl_prefill"] > 0, row
    import math
    for key in ("ms_per_token", "decode_tok_s_chip"):
        assert math.isfinite(row[key]), (key, row)
    # a roofline is a statement about a chip's HBM: none on a CPU
    assert row["roofline_ms"] is None and row["x_roofline"] is None
    assert row["params_m"] >= 0
    assert row["variant"] == "smoke"


def test_sweep_decode_int8_variant_smoke():
    """The int8-weights + int8-KV variant path (quantize_weights + the
    kernel gates) survives the same toy-scale drive."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import sweep_decode

    row = sweep_decode.run_variant(
        "smoke8", batch=2, prompt=8, new=4, hidden=32, inter=64,
        layers=2, heads=2, kv_heads=1, kv_dtype="int8", weights="int8")
    assert row["ms_per_token"] > 0
    assert row["kv"] == "int8" and row["weights"] == "int8"


def test_sweep_decode_selfspec_variant_smoke():
    """Self-speculative variant: int8 tree drafts for its own target;
    must deliver tokens with a sane acceptance rate at toy scale."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import sweep_decode

    row = sweep_decode.run_variant(
        "smoke_spec", batch=2, prompt=8, new=6, hidden=32, inter=64,
        layers=2, heads=2, kv_heads=1, speculative="selfint8", gamma=3)
    assert row["emitted"] > 0
    assert 0.0 <= row["accept_rate"] <= 1.0
    assert row["spec"] == "selfint8"
    assert row["verify_rounds"] >= 1
    import math
    assert math.isfinite(row["ms_per_token"])  # prefill-subtracted
