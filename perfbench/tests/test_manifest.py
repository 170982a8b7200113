"""``BENCHMARK.json`` against the contract and against the files it names;
and the proof that a later PR adds a configuration, a traffic mix and a
per-layer metric as new files plus manifest entries, editing nothing."""
import json
import re
import shutil
from pathlib import Path

import pytest

from perfbench.lib import manifest as mf
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


def test_top_level_keys_and_limits(manifest):
    data = manifest.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((Path(ROOT) / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= data["run_seconds"] <= 51 and isinstance(
        data["run_seconds"], int)
    # a full check with all 24 cells has to fit the driver's budget
    runs = 2 + 14 * 24
    assert runs * (data["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert data["command"][:2] == ["python3", "perfbench/run.py"]
    for path in data["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", path)
        assert (Path(ROOT) / path).is_dir()


def test_names_units_and_text_fields(manifest):
    data = manifest.data
    named = (data["configs"] + data["workloads"] + data["end_to_end"]
             + data["per_layer"])
    for entry in named:
        assert mf.NAME.match(entry["name"]), entry["name"]
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in data[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in data["end_to_end"] + data["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in data["end_to_end"] + data["per_layer"]:
        assert mf.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in data["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in data["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for c in data["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert mf.NAME.match(key)
            assert not re.search(r"(_dim|_rank|_size)$|head_dim|per_tok",
                                 key), "a width may never be reduced"
    for w in data["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert mf.NAME.match(w["traffic"]) and mf.NAME.match(w["config"])
    for e in data["configs"] + data["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
        assert "\t" not in e["why"]
    four = [w for w in data["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(data["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_file_is_found(manifest):
    data = manifest.data
    used = {w["config"] for w in data["workloads"]}
    assert used == {c["name"] for c in data["configs"]}
    files = [c["file"] for c in data["configs"]]
    assert len(files) == len(set(files))
    for c in data["configs"]:
        assert c["file"].startswith("perfbench/")
        cfg = manifest.config(c["name"])
        assert cfg["source"] == c["source"]
        # depth is the only key that differs from the published config
        assert set(cfg["reduced_from"]) == set(c["reduced"])
        assert (cfg["hidden_size"], cfg["intermediate_size"]) == (4096, 14336)
        assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["vocab_size"], cfg["sliding_window"]) == (
            32, 8, 32000, 4096)
        assert hasattr(manifest.reference(cfg["reference"]), "hidden_states")
    for w in data["workloads"]:
        mix = manifest.traffic(w["traffic"])
        driver = manifest.driver(mix["driver"])
        assert callable(driver.run)
        assert driver.ANNOTATIONS and driver.PROGRAMS
    for m in data["per_layer"]:
        reader = manifest.layer_metric(m["name"])
        assert callable(reader.read)
        assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.MOVES,
                reader.SOURCE) == (m["layer"], m["unit"], m["better"],
                                   m["moves"], m["source"]), m["name"]
    # every entry has its reader (a reader or a configuration file may
    # wait on disk for the cell that will name it), and no file has a
    # stray name
    entries = {m["name"] for m in data["per_layer"]}
    on_disk = {p.stem for p in (manifest.bench / "layer_metrics").glob("*.py")}
    assert entries <= on_disk
    for path in manifest.bench.rglob("*"):
        if ".run" in path.parts or "__pycache__" in path.parts:
            continue
        assert re.fullmatch(r"[A-Za-z0-9_.-]+", path.name), path


def test_every_cell_reports_what_the_contract_asks(manifest):
    data = manifest.data
    assert any(m["name"] == "setup_s" and "workloads" not in m
               and m["bound"] <= 0.1 for m in data["end_to_end"])
    cells = {w["name"] for w in data["workloads"]}
    for m in data["end_to_end"] + data["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    for w in data["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_for("end_to_end", w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = manifest.metrics_for("per_layer", w["name"])
        assert layer
        mix = manifest.traffic(w["traffic"])
        for m in layer:
            # what a per-layer metric moves is reported in the same cell
            assert m["moves"] in e2e, (w["name"], m["name"])
            # and its reader is written for this cell's driver
            assert mix["driver"] in manifest.layer_metric(m["name"]).DRIVERS
    for m in data["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_new_files_are_picked_up_with_no_file_edited(manifest, tmp_path):
    """A later PR's view: copy the benchmark, drop in a configuration, a
    traffic mix, a driver and a per-layer metric, add manifest entries,
    and the loader finds them all. No existing file is touched."""
    root = tmp_path / "repo"
    shutil.copytree(manifest.bench, root / "perfbench",
                    ignore=shutil.ignore_patterns(".run", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    data = json.loads((Path(ROOT) / "BENCHMARK.json").read_text())

    bench = root / "perfbench"
    cfg = manifest.config("mistral7b_serve_d16")
    cfg["num_hidden_layers"] = 12
    (bench / "configs" / "mistral7b_serve_d12.json").write_text(json.dumps(cfg))
    mix = manifest.traffic("decode_heavy_closed")
    mix["clients"], mix["pairing"] = 2, [1, 0]
    (bench / "traffic" / "two_callers.json").write_text(json.dumps(mix))
    (bench / "drivers" / "serve_open_loop.py").write_text(
        "ANNOTATIONS = ('serve',)\nPROGRAMS = {}\n\n\ndef run(bench):\n"
        "    return {}\n")
    (bench / "layer_metrics" / "queue_wait_ms.py").write_text(
        'LAYER = "scheduler"\nUNIT = "ms"\nBETTER = "lower"\n'
        'MOVES = "itl_p99_ms"\nSOURCE = "program_counter"\n'
        'DRIVERS = ("serve_closed_loop",)\n\n\ndef read(ctx):\n'
        '    return ctx.counters.get("queue_wait_ms")\n')
    data["configs"].append({
        "name": "mistral7b_serve_d12", "source": cfg["source"],
        "file": "perfbench/configs/mistral7b_serve_d12.json",
        "reduced": ["num_hidden_layers"], "why": "a later PR's"})
    data["workloads"].append({
        "name": "serve_two_callers", "config": "mistral7b_serve_d12",
        "traffic": "two_callers", "chips": 1, "why": "a later PR's"})
    data["per_layer"].append({
        "name": "queue_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "itl_p99_ms", "workloads": ["serve_two_callers"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    later = Manifest(root)
    cell = later.workload("serve_two_callers")
    assert later.config(cell["config"])["num_hidden_layers"] == 12
    got = later.traffic(cell["traffic"])
    assert got["clients"] == 2
    assert later.driver(got["driver"]).ANNOTATIONS
    assert later.driver("serve_open_loop").run(None) == {}
    names = [m["name"] for m in later.metrics_for(
        "per_layer", "serve_two_callers")]
    assert names == ["queue_wait_ms"]

    class Ctx:
        counters = {"queue_wait_ms": 1.5}
    assert later.layer_metric("queue_wait_ms").read(Ctx) == 1.5
    from perfbench.lib import traffic
    assert traffic.ClosedLoopTraffic(got, 3, 32000).n == 2
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_unknown_names_fail_loudly(manifest):
    with pytest.raises(KeyError):
        manifest.workload("no_such_cell")
    with pytest.raises(FileNotFoundError):
        manifest.traffic("no_such_mix")
    with pytest.raises(FileNotFoundError):
        manifest.layer_metric("no_such_metric")
