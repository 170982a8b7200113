#!/usr/bin/env python3
"""What the program's own spans say about a cell's last traced run:

    python3 perfbench/spans_report.py <cell> [--json]

Reads the xplane that ``run.py --workload <cell> --trace 1`` left under
``perfbench/.run/<cell>/trace`` and prints, with nothing but the spans of
``dla_tpu/utils/profiling.py`` and the device's own lines:

- self time per span per step (median over the traced steps, and what the
  children of the step span sum to against the step span itself);
- the device's idle seconds by the innermost program span over each gap;
- serving: the per-request table (queue wait, prefill service, TTFT);
- training: device time by scope (forward / remat / backward / optimizer /
  unscoped) per train-step execution.

PERF.md section 5 is written from this. It needs no chip and no program:
only the trace (and, for the scope split, the ``scopes.json`` the traced
run left beside it where the trace's events do not carry ``op_name``).
"""
import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench.lib import spans, stats, xplane  # noqa: E402

TRAIN_STEP = re.compile(r"jit__train_step")


def report(cell: str) -> dict:
    path = xplane.find_xplane(str(spans.trace_dir(cell)))
    if path is None:
        raise SystemExit(f"no traced run of {cell!r} under "
                         f"{spans.trace_dir(cell)}")
    trace = spans.load(path)
    prog = spans.program_spans(trace.host)
    step = next((n for n in spans.STEP_SPANS if spans.has(prog, n)), None)
    out = {"cell": cell, "xplane": path,
           "xplane_mib": Path(path).stat().st_size / 2 ** 20,
           "step_span": step, "program_spans": len(prog)}
    if step is None:
        return out

    # ---- self time per span per step
    all_steps = spans.steps(prog, step)
    whole = [e - s for _, s, e, _ in all_steps]
    names = sorted({s[0] for s in prog
                    if s[0] != step and not s[0].startswith("serve_req_")})
    per = {}
    for name in names:
        secs = spans.per_step(prog, (name,), step, own=True)
        present = [x for x in secs if x > 0]
        per[name] = {
            "mean_ms": 1e3 * sum(secs) / len(secs),
            "median_ms_over_all_steps": 1e3 * stats.median(secs),
            "median_ms_where_present":
                1e3 * stats.median(present) if present else 0.0,
            "steps_with_it": len(present)}
    own = spans.per_step(prog, (step,), step, own=True)
    out["steps"] = len(whole)
    out["step_ms_median"] = 1e3 * stats.median(whole)
    out["step_ms_mean"] = 1e3 * sum(whole) / len(whole)
    out["step_self_ms_mean"] = 1e3 * sum(own) / len(own)
    out["children_cover_pct"] = 100.0 * (1 - sum(own) / sum(whole))
    out["self_time_per_step"] = {k: v for k, v in per.items()
                                 if v["steps_with_it"]}

    # ---- the loop around the step span: what sits between two steps
    t_first, t_last = all_steps[0][1], all_steps[-1][1]
    parents, _ = spans.nesting(prog)
    between = {}
    for (name, s, e, _), parent in zip(prog, parents):
        if (parent is None and t_first <= s < t_last
                and not name.startswith("serve_req_")):
            between.setdefault(name, []).append(e - s)
    n = len(all_steps) - 1
    if n:
        out["period_ms_mean"] = 1e3 * (t_last - t_first) / n
        out["period_ms_median"] = 1e3 * stats.median(
            [b[1] - a[1] for a, b in zip(all_steps, all_steps[1:])])
        out["loop_spans"] = {
            name: {"mean_ms_per_step": 1e3 * sum(xs) / n,
                   "median_ms": 1e3 * stats.median(xs), "count": len(xs)}
            for name, xs in between.items()}
        out["loop_cover_pct"] = 100.0 * sum(
            sum(xs) for xs in between.values()) / (t_last - t_first)

    # ---- idle by innermost program span
    if trace.ops:
        window = (trace.ops[0][1], max(e for _, _, e in trace.ops))
        idle = spans.device_idle(trace.ops, window)
        by = spans.idle_by_span(trace.host, idle)
        total = sum(by.values())
        out["window_s"] = window[1] - window[0]
        out["idle_s"] = total
        out["idle_by_span_s"] = dict(sorted(by.items(),
                                            key=lambda kv: -kv[1]))
        out["idle_outside_pct"] = (100.0 * by.get("outside", 0.0) / total
                                   if total else 0.0)

    # ---- the request lifecycle
    reqs = spans.requests(trace.host)
    if reqs:
        def ms(a, b):
            xs = spans.request_intervals(trace.host, a, b)
            return {"n": len(xs),
                    "median_ms": 1e3 * stats.median(xs) if xs else None,
                    "max_ms": 1e3 * max(xs) if xs else None}
        out["requests_seen"] = len(reqs)
        out["queue_wait"] = ms("submit", "admit")
        out["prefill_service"] = ms("admit", "first_token")
        out["ttft"] = ms("submit", "first_token")
        out["decode"] = ms("first_token", "finish")
        out["preempt_marks"] = sum(r["preempts"] for r in reqs.values())
        kv = spans.kv_reads(trace.host)
        if kv:
            out["kv_reads"] = kv
            out["kv_read_amplification"] = (kv["read_tokens"]
                                            / max(kv["live_tokens"], 1.0))

    # ---- device time by scope, per train-step execution
    runs = [(s, e) for n, s, e in trace.modules if TRAIN_STEP.search(n)]
    if runs and trace.ops:
        carried = any(spans.op_name_of(t) for t, _, _ in trace.ops[:64])
        scopes = None if carried else spans.saved_scope_map(cell)
        if carried or scopes:
            rows = spans.scope_seconds(trace.ops, runs[1:-1] or runs, scopes)
            med = {k: 1e3 * stats.median([r[k] for r in rows])
                   for k in spans.SCOPES}
            out["train_step_executions"] = len(rows)
            out["scope_ms_median"] = med
            out["scope_ms_sum"] = sum(med.values())
            out["op_name_from"] = "event text" if carried else "scopes.json"
        else:
            out["scope_ms_median"] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object instead of the tables")
    args = ap.parse_args(argv)
    out = report(args.cell)
    if args.json:
        print(json.dumps(out))
        return 0
    print(f"{out['cell']}: {out['xplane']} ({out['xplane_mib']:.1f} MiB), "
          f"{out['program_spans']} program spans")
    if out["step_span"] is None:
        print("no step span of the program in this trace")
        return 0
    print(f"\n{out['steps']} `{out['step_span']}` steps: median "
          f"{out['step_ms_median']:.3f} ms, mean {out['step_ms_mean']:.3f} "
          f"ms; the step span's own time {out['step_self_ms_mean']:.3f} ms "
          f"mean, so its children cover {out['children_cover_pct']:.2f}%")
    print(f"{'span inside the step':26} {'mean ms/step':>12} {'median':>9} "
          f"{'where present':>14} {'steps':>6}")
    for name, row in sorted(out["self_time_per_step"].items(),
                            key=lambda kv: -kv[1]["mean_ms"]):
        print(f"{name:26} {row['mean_ms']:12.3f} "
              f"{row['median_ms_over_all_steps']:9.3f} "
              f"{row['median_ms_where_present']:14.3f} "
              f"{row['steps_with_it']:6d}")
    if "loop_spans" in out:
        print(f"\nstep to step: median {out['period_ms_median']:.3f} ms, "
              f"mean {out['period_ms_mean']:.3f} ms; the program's "
              f"outermost spans cover {out['loop_cover_pct']:.2f}% of it")
        for name, row in sorted(out["loop_spans"].items(),
                                key=lambda kv: -kv[1]["mean_ms_per_step"]):
            print(f"  {name:24} {row['mean_ms_per_step']:10.3f} ms/step  "
                  f"median {row['median_ms']:9.3f}  n {row['count']}")
    if "idle_s" in out:
        print(f"\ndevice idle {out['idle_s']:.3f} s of "
              f"{out['window_s']:.3f} s; by innermost program span "
              f"({out['idle_outside_pct']:.1f}% outside every span):")
        for name, secs in out["idle_by_span_s"].items():
            print(f"  {name:26} {secs:8.4f} s")
    if "requests_seen" in out:
        print(f"\n{out['requests_seen']} requests seen, "
              f"{out['preempt_marks']} preempt marks")
        for key in ("queue_wait", "prefill_service", "ttft", "decode"):
            row = out[key]
            if row["n"]:
                print(f"  {key:16} n {row['n']:3d}  median "
                      f"{row['median_ms']:9.2f} ms  max {row['max_ms']:9.2f}")
        if "kv_reads" in out:
            print(f"  kv: read {out['kv_reads']['read_tokens']:.0f} columns "
                  f"for {out['kv_reads']['live_tokens']:.0f} live tokens in "
                  f"{out['kv_reads']['slots']:.0f} slot-steps: "
                  f"amplification {out['kv_read_amplification']:.3f}")
    if out.get("scope_ms_median"):
        print(f"\ndevice time by scope, median over "
              f"{out['train_step_executions']} train-step executions "
              f"(op_name from {out['op_name_from']}):")
        for key, val in out["scope_ms_median"].items():
            print(f"  {key:10} {val:9.3f} ms")
        print(f"  {'sum':10} {out['scope_ms_sum']:9.3f} ms")
    elif "scope_ms_median" in out:
        print("\nno op_name for the device's operations: neither in the "
              "events nor in scopes.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
