"""``perfbench/lib/spans.py`` on synthetic tuples, the new readers on a
context with and without spans, and ``spans_report.py`` on a hand-made
trace. No chip, no program."""
import types

import pytest

from perfbench.lib import spans, xplane
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT

NEW = ("request_queue_wait_ms", "prefill_service_ms", "schedule_host_ms",
       "engine_host_self_ms", "decode_args_host_ms", "kv_read_mib_per_token",
       "kv_read_amplification", "metrics_fetch_ms", "batch_put_ms",
       "train_fwd_device_ms", "train_remat_device_ms", "train_bwd_device_ms",
       "train_opt_device_ms", "train_metrics_device_ms")


def sp(name, t0, t1, /, **args):
    return (name, float(t0), float(t1), args)


def mark(name, at, /, **args):
    return sp(name, at, at + 1e-6, **args)


def one_step(t, rid=None, decode=True, fetch=0.030):
    """One `serve` step of 50 ms starting at t: schedule 1, admit 2, an
    optional final chunk for `rid`, a decode phase, post 1."""
    out = [sp("serve", t, t + 0.050, step_num=int(t * 20), host_ns=0),
           sp("serve_schedule", t, t + 0.001),
           sp("serve_admit", t + 0.001, t + 0.003, queued=1)]
    if rid is not None:
        out += [mark("serve_req_admit", t + 0.002, rid=rid, slot=0,
                     cached_tokens=0),
                sp("serve_prefill_chunk", t + 0.003, t + 0.005, rid=rid,
                   slot=0, start=0, nvalid=4, last=1),
                sp("serve_chunk_fetch", t + 0.005, t + 0.009, rid=rid),
                sp("serve_first_token", t + 0.009, t + 0.010, rid=rid),
                mark("serve_req_first_token", t + 0.0095, rid=rid)]
    if decode:
        out += [sp("serve_decode", t + 0.010, t + 0.048, slots=2,
                   live_tokens=100, read_tokens=400),
                sp("serve_decode_args", t + 0.010, t + 0.013),
                sp("serve_decode_dispatch", t + 0.013, t + 0.014),
                sp("serve_decode_fetch", t + 0.014, t + 0.014 + fetch),
                sp("serve_emit", t + 0.045, t + 0.047, slots=2)]
    out.append(sp("serve_post", t + 0.048, t + 0.049))
    return out


def serving_trace():
    host = [mark("serve_req_submit", 0.95, rid=7, prompt_len=4, max_new=9)]
    host += one_step(1.00)
    host += one_step(1.05, rid=7)
    host += one_step(1.10, rid=3)      # rid 3: submitted before the window
    host += [mark("serve_req_finish", 1.147, rid=7, status="length",
                  tokens=9),
             sp("PjitFunction(f)", 1.013, 1.0135)]     # a runtime event
    return spans._sorted(host)


def test_nesting_self_time_with_nested_and_sibling_spans():
    host = spans._sorted([
        sp("serve", 0.0, 10.0), sp("serve_admit", 1.0, 4.0),
        sp("serve_first_token", 2.0, 3.0),           # nested in admit
        sp("serve_decode", 4.0, 9.0),                # sibling of admit
        sp("serve_decode_args", 4.0, 5.0), sp("serve_decode_fetch", 5.0, 9.0),
        sp("ExecuteOnLocalDevices", 4.5, 4.7)])      # runtime: ignored
    own = spans.self_times(host)
    assert own == {"serve": [2.0], "serve_admit": [2.0],
                   "serve_first_token": [1.0], "serve_decode": [0.0],
                   "serve_decode_args": [1.0], "serve_decode_fetch": [4.0]}
    parents, _ = spans.nesting(spans.program_spans(host))
    assert parents == [None, 0, 1, 0, 3, 3]
    # a step's children sum to the step less its own time
    assert sum(sum(v) for v in own.values()) == pytest.approx(10.0)


def test_per_step_counts_whole_steps_and_drops_the_edges():
    host = serving_trace()
    # a decode_args span cut loose at the window's edge, outside any step
    host = spans._sorted(host + [sp("serve_decode_args", 0.90, 0.91)])
    args = spans.per_step(host, ("serve_decode_args",), "serve")
    assert args == pytest.approx([0.003, 0.003, 0.003])
    sched = spans.per_step(host, ("serve_schedule", "serve_admit"), "serve")
    assert sched == pytest.approx([0.003] * 3)
    chunk = spans.per_step(host, ("serve_chunk_fetch",), "serve")
    assert chunk == pytest.approx([0.0, 0.004, 0.004])
    own = spans.per_step(host, ("serve",), "serve", own=True)
    assert own[0] == pytest.approx(0.050 - 0.001 - 0.002 - 0.038 - 0.001)


def test_requests_group_marks_by_rid_and_skip_what_the_window_cut():
    host = serving_trace()
    reqs = spans.requests(host)
    assert set(reqs) == {7, 3}
    assert reqs[7]["prompt_len"] == 4 and reqs[7]["status"] == "length"
    assert "submit" not in reqs[3] and "finish" not in reqs[3]
    # rid 3's admit is there, its submit fell before the window: not a
    # queue wait; its prefill service still counts
    assert spans.request_intervals(host, "submit", "admit") == \
        pytest.approx([1.052 - 0.95])
    assert spans.request_intervals(host, "admit", "first_token") == \
        pytest.approx([0.0075, 0.0075])
    assert spans.request_intervals(host, "submit", "first_token") == \
        pytest.approx([1.0595 - 0.95])
    # a request re-admitted after a preemption keeps its first admit
    again = spans._sorted(host + [
        mark("serve_req_preempt", 1.12, rid=7),
        mark("serve_req_admit", 1.13, rid=7, slot=1, cached_tokens=0)])
    assert spans.requests(again)[7]["admit"] == pytest.approx(1.052)
    assert spans.requests(again)[7]["preempts"] == 1


def test_kv_reads_amplification_with_an_empty_slot():
    # 2 slots of window 8: one holds 4 tokens, the other is empty
    host = [sp("serve_decode", 0.0, 1.0, slots=1, live_tokens=4,
               read_tokens=16),
            sp("serve_decode", 1.0, 2.0, slots=1, live_tokens=5,
               read_tokens=16),
            sp("serve_decode", 2.0, 3.0)]       # the parent's: no arguments
    sums = spans.kv_reads(host)
    assert sums == {"slots": 2.0, "live_tokens": 9.0, "read_tokens": 32.0}
    assert sums["read_tokens"] / sums["live_tokens"] == pytest.approx(32 / 9)
    assert spans.kv_reads([sp("serve_decode", 0.0, 1.0)]) is None
    cfg = Manifest(ROOT).config("mistral7b_serve_d16")
    assert spans.kv_bytes_per_token(cfg) == 2 * 16 * 8 * 128 * 2 == 65536


def test_scope_seconds_with_a_while_container():
    fwd = 'op_name="jit(_train_step)/while/body/closed_call/jvp()/mul"'
    ops = spans._sorted([
        ("%while.3 = (f32[]) while(...)", 0.0, 6.0),             # container
        (f"%fusion.1 = f32[4] fusion(...), metadata={{{fwd}}}", 0.0, 2.0),
        ("%fusion.2 = f32[4] fusion(%a), kind=kLoop", 2.0, 3.0),  # by map
        ("%custom-call.9 = bf16[8] custom-call(...)", 3.0, 6.0),
        ("%fusion.5 = f32[4] fusion(%b)", 6.0, 7.0),
        ("%reduce.6 = f32[] fusion(%b)", 7.0, 7.25),
        ("%copy.4 = f32[4] copy(%c)", 7.5, 8.0),                 # no name
        ("%fusion.5 = f32[4] fusion(%b)", 11.0, 12.0)])          # next run
    scopes = {
        "fusion.2": "jit(_train_step)/transpose(jvp())/checkpoint/"
                    "rematted_computation/dot_general",
        "custom-call.9": "jit(_train_step)/transpose(jvp())/checkpoint/dot",
        "fusion.5": "jit(_train_step)/optimizer/add",
        "reduce.6": "jit(_train_step)/step_metrics/reduce_sum",
        "while.3": "jit(_train_step)/while"}
    rows = spans.scope_seconds(ops, [(0.0, 8.0), (10.0, 12.5)], scopes)
    assert rows[0] == pytest.approx({
        "optimizer": 1.0, "metrics": 0.25, "remat": 1.0, "backward": 3.0,
        "forward": 2.0, "unscoped": 0.5})
    assert rows[1] == pytest.approx({
        "optimizer": 1.0, "metrics": 0.0, "remat": 0.0, "backward": 0.0,
        "forward": 0.0, "unscoped": 0.0})
    assert sum(rows[0].values()) == pytest.approx(
        xplane.total(xplane.clip(xplane.busy(ops), 0.0, 8.0)))
    assert spans.instruction_name(ops[0][0]) == "while.3"
    assert spans.op_name_of(ops[1][0]).endswith("jvp()/mul")


def test_idle_goes_to_the_innermost_program_span():
    host = serving_trace()
    idle = [(1.0105, 1.0125),     # inside serve_decode_args
            (1.0131, 1.0133),     # under a runtime event in dispatch
            (1.0492, 1.0498),     # in `serve` itself, after serve_post
            (0.96, 0.98),         # between steps: outside every span
            # one gap across post (1 ms), the step's tail (1 ms), the next
            # step's schedule (1 ms) and half its admit: cut at each edge
            (1.098, 1.102)]
    by = spans.idle_by_span(host, idle)
    assert by == pytest.approx({
        "serve_decode_args": 0.002, "serve_decode_dispatch": 0.0002,
        "serve": 0.0006 + 0.001, "outside": 0.02, "serve_post": 0.001,
        "serve_schedule": 0.001, "serve_admit": 0.001})
    assert sum(by.values()) == pytest.approx(sum(b - a for a, b in idle))
    cover = spans.innermost_cover(host)
    assert all(a[1] <= b[0] for a, b in zip(cover, cover[1:]))
    ops = [("%fusion.1 = f32[4] fusion()", 1.0, 1.4),
           ("%fusion.2 = f32[4] fusion()", 1.6, 2.0)]
    assert spans.device_idle(ops, (1.0, 2.0)) == pytest.approx([(1.4, 1.6)])


# ------------------------------------------------------- the reader files

def ctx_for(host, monkeypatch, cell="serve_decode_heavy", ops=(), modules=()):
    trace = spans.SpanTrace(list(host), list(ops), list(modules))
    monkeypatch.setattr(spans, "for_context", lambda ctx: trace)
    monkeypatch.setattr(spans, "_SCOPE_ROWS", {})     # memo of a run
    m = Manifest(ROOT)
    entry = m.workload(cell)
    return types.SimpleNamespace(
        cell=entry, config=m.config(entry["config"]), trace=object(),
        trace_window=(0.0, 100.0), programs={"train_step": "jit__train_step"})


def test_serving_readers_on_the_synthetic_trace(monkeypatch):
    m = Manifest(ROOT)
    ctx = ctx_for(serving_trace(), monkeypatch)
    got = {name: m.layer_metric(name).read(ctx) for name in NEW[:7]}
    assert got["request_queue_wait_ms"] == pytest.approx(102.0)
    assert got["prefill_service_ms"] == pytest.approx(7.5)
    assert got["schedule_host_ms"] == pytest.approx(3.0)
    assert got["decode_args_host_ms"] == pytest.approx(3.0)
    # steps of 50 ms less the 30 ms decode fetch, less 4 ms where a final
    # chunk's logits were fetched too: 20, 16, 16
    assert got["engine_host_self_ms"] == pytest.approx(16.0)
    assert got["kv_read_amplification"] == pytest.approx(4.0)
    assert got["kv_read_mib_per_token"] == pytest.approx(
        400 * 65536 / 2 / 2 ** 20)


def test_readers_return_none_where_the_program_has_no_such_span(monkeypatch):
    """The parent's trace: `serve`, `serve_decode`, `serve_prefill_chunk`
    and `train` with no arguments and no children; and no trace at all."""
    m = Manifest(ROOT)
    parent = spans._sorted([
        sp("serve", 0.0, 0.05, step_num=1), sp("serve_decode", 0.01, 0.04),
        sp("serve_prefill_chunk", 0.002, 0.004), sp("train", 1.0, 1.2)])
    ctx = ctx_for(parent, monkeypatch, ops=[
        ("%fusion.1 = f32[4] fusion()", 1.0, 1.1)],
        modules=[("jit__train_step(123)", 1.0, 1.1)])
    import dla_tpu.telemetry.xla_introspect as xi
    monkeypatch.setattr(xi, "compiled_scopes", lambda pattern: {})
    for name in NEW:
        assert m.layer_metric(name).read(ctx) is None, name
    monkeypatch.undo()
    bare = types.SimpleNamespace(trace=None, cell={"name": "serve_decode_heavy"})
    for name in NEW:
        assert m.layer_metric(name).read(bare) is None, name


def test_training_readers_and_scopes_from_the_event_text(monkeypatch):
    m = Manifest(ROOT)

    def op(name, scope, s, e):
        return (f'%{name} = f32[4] fusion(), metadata={{op_name="{scope}"}}',
                s, e)
    host, ops, modules = [], [], []
    for k in range(3):
        t = 10.0 + k
        host += [sp("train_data_wait", t - 0.02, t - 0.019),
                 sp("train_h2d", t - 0.015, t - 0.010),
                 sp("train", t, t + 0.8, step_num=k, host_ns=0),
                 sp("train_dispatch", t, t + 0.01),
                 sp("train_loss_fetch", t + 0.01, t + 0.8),
                 sp("train_metrics_fetch", t + 0.8, t + 0.81)]
        modules.append(("jit__train_step(99)", t + 0.01, t + 0.79))
        ops += [op("f.1", "jit(_train_step)/jvp()/dot", t + 0.01, t + 0.21),
                op("f.2", "jit(_train_step)/transpose(jvp())/checkpoint/"
                   "rematted_computation/dot", t + 0.21, t + 0.36),
                op("f.3", "jit(_train_step)/transpose(jvp())/dot",
                   t + 0.36, t + 0.71),
                op("f.4", "jit(_train_step)/optimizer/add", t + 0.71,
                   t + 0.76),
                op("f.5", "jit(_train_step)/step_metrics/reduce_sum",
                   t + 0.76, t + 0.78)]
    ctx = ctx_for(spans._sorted(host), monkeypatch, cell="sft_packed_1chip",
                  ops=spans._sorted(ops), modules=modules)
    got = {name: m.layer_metric(name).read(ctx) for name in NEW[7:]}
    assert got == pytest.approx({
        "metrics_fetch_ms": 10.0, "batch_put_ms": 5.0,
        "train_fwd_device_ms": 200.0, "train_remat_device_ms": 150.0,
        "train_bwd_device_ms": 350.0, "train_opt_device_ms": 50.0,
        "train_metrics_device_ms": 20.0})


def test_manifest_lists_the_new_readers_in_their_cells():
    m = Manifest(ROOT)
    names = [e["name"] for e in m.data["per_layer"]]
    assert names[-len(NEW):] == list(NEW)      # appended, in this order
    for cell, mine in (("serve_decode_heavy", NEW[:7]),
                       ("serve_prefill_heavy", NEW[:7]),
                       ("sft_packed_1chip", NEW[7:]),
                       ("sft_packed_4chip", NEW[7:])):
        got = {e["name"] for e in m.metrics_for("per_layer", cell)}
        assert set(mine) <= got and not (set(NEW) - set(mine)) & got


def test_spans_report_on_a_hand_made_trace(monkeypatch, tmp_path, capsys):
    from perfbench import spans_report
    ops = [("%fusion.1 = f32[4] fusion()", 1.0, 1.048),
           ("%fusion.1 = f32[4] fusion()", 1.06, 1.098),
           ("%fusion.1 = f32[4] fusion()", 1.11, 1.149)]
    trace = spans.SpanTrace(serving_trace(), ops, [])
    fake = tmp_path / "t.xplane.pb"
    fake.write_bytes(b"x" * 2048)
    monkeypatch.setattr(xplane, "find_xplane", lambda d: str(fake))
    monkeypatch.setattr(spans, "load", lambda path: trace)
    out = spans_report.report("serve_decode_heavy")
    assert out["steps"] == 3 and out["step_span"] == "serve"
    assert out["step_ms_median"] == pytest.approx(50.0)
    assert out["self_time_per_step"]["serve_decode_args"]["mean_ms"] == \
        pytest.approx(3.0)
    assert out["period_ms_median"] == pytest.approx(50.0)
    assert out["loop_cover_pct"] == pytest.approx(100.0)
    assert out["queue_wait"]["n"] == 1 and out["prefill_service"]["n"] == 2
    assert out["kv_read_amplification"] == pytest.approx(4.0)
    assert out["idle_s"] == pytest.approx(0.024)
    assert out["idle_outside_pct"] == pytest.approx(0.0)
    assert spans_report.main(["serve_decode_heavy"]) == 0
    text = capsys.readouterr().out
    assert "serve_decode_fetch" in text and "amplification 4.000" in text
