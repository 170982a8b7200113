#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child that touches JAX. Finds the cell's configuration,
traffic mix, driver and per-layer metric readers by the names in
``BENCHMARK.json``, runs the cell on the chips this machine has, and
prints one JSON object as the last line of its standard output. Fails,
with no result line, without a TPU or without the program.

``--rehearsal`` is the CPU dry run of the whole control flow at a tiny
size (``perfbench/rehearsal.json``): it says REHEARSAL on every line,
prints no result line and is never chosen automatically.
"""
import time

T_START = time.perf_counter()     # process start, as near as Python gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU dry run; proves nothing about the chip")
    ap.add_argument("--rehearsal-devices", type=int, default=0,
                    help="virtual CPU devices for a rehearsal (4 for a "
                         "four-chip cell)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from perfbench.lib import rehearsal as rehearsal_sizes
    from perfbench.lib.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.workload(args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    seconds = float(args.seconds if args.seconds is not None
                    else manifest.data["run_seconds"])
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.rehearsal_devices:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={args.rehearsal_devices}").strip()
        config, traffic = rehearsal_sizes.shrink(
            manifest.bench / "rehearsal.json", config, traffic)
    driver = manifest.driver(traffic["driver"])

    # reach the chip first and time it apart: libtpu's start-up took 6 to
    # 12 s and varied by seconds between identical runs (PERF.md), and no
    # change to this repository can move it, so it is not part of setup_s
    import jax
    t_attach = time.perf_counter()
    jax.devices()
    attach_s = time.perf_counter() - t_attach

    try:
        from dla_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"[perfbench] the program is not in this directory: {exc}",
              file=sys.stderr)
        return 3
    from perfbench.lib import sut
    from perfbench.lib.harness import Bench, RunContext
    from perfbench.lib.peaks import peaks_for
    from perfbench.lib.tracing import TraceWindow

    # the persistent compile cache first, at the program's fixed path
    # inside the checkout (or where JAX_COMPILATION_CACHE_DIR says)
    enable_compile_cache()
    device = sut.require_devices(int(cell["chips"]), args.rehearsal)
    peaks = None if args.rehearsal else peaks_for(device["kind"])
    scratch = manifest.bench / ".run" / cell["name"]
    scratch.mkdir(parents=True, exist_ok=True)
    bench = Bench(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, rehearsal=args.rehearsal,
        t_start=T_START, attach_s=attach_s,
        compiles=sut.CompileWatch().install(),
        tracer=TraceWindow(bool(args.trace), seconds, scratch / "trace"),
        scratch=scratch)
    bench.say(f"cell {cell['name']}: {cell['config']} x {cell['traffic']} "
              f"on {device['count']} x {device['kind']}, seed {args.seed}, "
              f"window {seconds:g}s, trace {args.trace}; reaching the device "
              f"took {attach_s:.1f}s")

    out = driver.run(bench)       # dict, see drivers/*.py

    correct = bool(out["correct"]) and bench.compiles.in_window == 0
    if bench.compiles.in_window:
        bench.say(f"NOT CORRECT: {bench.compiles.in_window} compile(s) "
                  f"inside the window: {bench.compiles.names[:8]}")
    device = dict(device)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "device": device}

    if not args.trace:
        wanted = manifest.metrics_for("end_to_end", cell["name"])
        line["metrics"] = {
            m["name"]: {"value": float(out["end_to_end"][m["name"]]),
                        "unit": m["unit"]} for m in wanted}
    else:
        from perfbench.lib import xplane
        trace = bench.tracer.load()
        if trace is None or not any(d.ops for d in trace.devices):
            bench.say("the traced window holds no device operation")
            if not args.rehearsal:
                return 4
            trace = window = None       # the CPU has no device plane
        else:
            window = xplane.window_of(trace)
        ctx = RunContext(
            cell=cell, config=config, traffic=traffic,
            chips=int(cell["chips"]), window_s=out["window_s"],
            device=device, peaks=peaks, end_to_end=out["end_to_end"],
            counters=out["counters"], samples=out["samples"],
            annotations=tuple(driver.ANNOTATIONS),
            programs=dict(driver.PROGRAMS), trace=trace,
            trace_window=window)
        metrics = {}
        for m in manifest.metrics_for("per_layer", cell["name"]):
            value = manifest.layer_metric(m["name"]).read(ctx)
            if value is not None:        # nothing to read: left out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        line["metrics"] = metrics
        if trace is not None:
            device["busy_s"] = xplane.busy_seconds(trace, window)
            device["window_s"] = window[1] - window[0]
            line["breakdown"] = {
                "device_ops": [[k, v] for k, v in
                               xplane.top_ops(trace, window)],
                "idle_gaps": [[k, v] for k, v in xplane.attribute_gaps(
                    trace, window, ctx.annotations)]}
    text = json.dumps(line)
    if args.rehearsal:
        print("REHEARSAL (no result line; a CPU run measures nothing) "
              + text, flush=True)
    else:
        print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
