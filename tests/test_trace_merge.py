"""Distributed-tracing tests (telemetry/trace_context.py +
tools/trace_merge.py): traceparent round-trips, the zero-work-when-
disabled pin extended to the span spool, torn-spool tolerance, clock
alignment edge cases (known skew recovered from beat pairs, single-beat
one-way peers, wall-anchor fallback, beats beating contradictory wall
clocks, causal clamping), the SamplerFleet chaos timeline (reassignment
is a CHILD of the dispatch it replaced), and the cross-process
acceptance: two subprocess gateway fleets behind a FederatedRouter with
a mid-stream migration merge into ONE valid Chrome trace whose span
trees cross process boundaries with correct parent links."""
import json
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from dla_tpu.telemetry.trace import Tracer, get_tracer, install_tracer
from dla_tpu.telemetry.trace_context import (
    TRACEPARENT_HEADER,
    SpanSpool,
    TraceContext,
    open_spool,
    read_spool,
    spool_paths,
)
from tools.trace_merge import (
    MergeError,
    align,
    load_dir,
    merge_dir,
    self_check,
    span_trees,
    validate,
)

REPO_ROOT = Path(__file__).resolve().parent.parent

T1 = "0af7651916cd43dd8448eb211c80319c"          # fixture-style ids
S1, S2, S3 = "b7ad6b7169203331", "00f067aa0ba902a1", "53ce929d0e0e4736"


# ---------------------------------------------------------------------------
# TraceContext
# ---------------------------------------------------------------------------

def test_traceparent_mint_child_header_roundtrip():
    root = TraceContext.mint()
    assert len(root.trace_id) == 32 and len(root.span_id) == 16
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.span_id != root.span_id
    back = TraceContext.from_header(root.to_header())
    assert back == root
    assert root.to_header().startswith("00-")
    # tags carry (trace, span) and the parent link when known
    tags = child.tags(root)
    assert tags == {"trace": root.trace_id, "span": child.span_id,
                    "parent": root.span_id}
    assert "parent" not in root.tags()
    # dict round-trip (the MigrationTicket / TrajectoryGroup carrier)
    assert TraceContext.from_dict(root.to_dict()) == root


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-b7ad6b7169203331-01",
    f"00-{T1}-tooshort-01", f"00-{T1}-{S1}", f"00-{'z' * 32}-{S1}-01",
])
def test_traceparent_malformed_header_is_untraced_not_error(bad):
    assert TraceContext.from_header(bad) is None
    assert TraceContext.from_dict({"trace_id": 7}) is None


# ---------------------------------------------------------------------------
# the zero-work pin extends to the spool
# ---------------------------------------------------------------------------

class _RaisingSpool(SpanSpool):
    """Every record write raises — a disabled tracer must never get
    here (trace.py's zero-producer-work contract, spool edition)."""

    def __init__(self):
        super().__init__("/nonexistent/never-opened.jsonl", "raising")
        self.anchored = 0

    def anchor(self, t0):         # attach-time anchor is allowed
        self.anchored += 1

    def write(self, rec):
        raise AssertionError("disabled tracer reached the spool")


def test_disabled_tracer_never_reaches_spool():
    tr = Tracer(enabled=False)
    tr.attach_spool(_RaisingSpool())
    t = tr.now()
    with tr.span("s", "cat"):
        pass
    tr.complete("c", t, tr.now(), cat="cat", args={"x": 1})
    tr.instant("i")
    tr.async_begin("cat", "a", 1)
    tr.async_end("cat", "a", 1)
    assert tr.emitted == 0 and tr.spooled == 0 and tr.spool_errors == 0
    # flipping enabled on proves the spool WOULD have been reached
    tr.enabled = True
    with pytest.raises(AssertionError):
        tr.complete("c", t, tr.now())


def test_spool_write_failures_counted_never_raised(tmp_path):
    sp = open_spool(str(tmp_path), "proc/with:odd chars")
    assert "spans_" in sp.path.name and "/" not in sp.path.name
    sp.write({"k": "span", "bad": float("nan")})    # not strict JSON
    assert sp.errors == 1 and sp.written == 0
    sp.event({"name": "ok", "ph": "X", "ts": 0.0, "dur": 1.0})
    assert sp.written == 1
    sp.close()
    assert spool_paths(str(tmp_path)) == [sp.path]


# ---------------------------------------------------------------------------
# synthetic spools: alignment edge cases
# ---------------------------------------------------------------------------

def _ev(name, ts_us, trace=None, span=None, parent=None, dur=50.0):
    ev = {"name": name, "ph": "X", "ts": float(ts_us),
          "dur": float(dur), "tid": 0}
    if trace is not None:
        args = {"trace": trace, "span": span}
        if parent is not None:
            args["parent"] = parent
        ev["args"] = args
    return ev


def _write_spool(dirpath, proc, pid, mono, wall, events,
                 beats_sent=(), beats_seen=(), torn=False):
    """Hand-author one spool file. ``mono``/``wall`` anchor the process
    clocks with perf == t0 == 0, so an event's monotonic time is simply
    ``mono + ts/1e6``."""
    lines = [json.dumps({"k": "clock", "proc": proc, "pid": pid,
                         "perf": 0.0, "mono": mono, "wall": wall,
                         "t0": 0.0})]
    for ev in events:
        lines.append(json.dumps({"k": "span", "proc": proc, "ev": ev}))
    for peer, seq, m in beats_sent:
        lines.append(json.dumps({"k": "beat_sent", "proc": proc,
                                 "peer": peer, "seq": seq, "mono": m}))
    for peer, seq, m in beats_seen:
        lines.append(json.dumps({"k": "beat_seen", "proc": proc,
                                 "peer": peer, "seq": seq, "mono": m}))
    text = "\n".join(lines) + "\n"
    if torn:
        text += '{"k": "span", "proc": "' + proc + '", "ev": {"na'
    path = Path(dirpath) / f"spans_{proc}_{pid}.jsonl"
    path.write_text(text)
    return path


def test_known_skew_recovered_from_paired_beats(tmp_path):
    """Two procs, true monotonic offset 4900 s, bidirectional beats with
    asymmetric lags (20 ms / 10 ms): the paired (NTP-midpoint) estimate
    must land within the lag bound, and the contradictory wall clocks
    (which agree exactly — implying offset ~0) must NOT win."""
    # A is the busier proc -> reference
    _write_spool(
        tmp_path, "A", 1, mono=100.0, wall=1000.0,
        events=[_ev("root", 0.0, T1, S1),
                _ev("left", 10.0, T1, S3, parent=S1),
                _ev("pad", 20.0)],
        beats_sent=[("A", 1, 100.0), ("A", 2, 100.2)],
        beats_seen=[("B", 1, 100.51)])
    _write_spool(
        tmp_path, "B", 2, mono=5000.0, wall=1000.0,
        events=[_ev("remote", 30.0, T1, S2, parent=S1)],
        beats_sent=[("B", 1, 5000.5)],
        beats_seen=[("A", 1, 5000.02), ("A", 2, 5000.21)])
    procs = load_dir(str(tmp_path))["procs"]
    off = align(procs)
    assert off["A"]["method"] == "reference"
    assert off["B"]["method"] == "paired"
    # true offset is -4900 (B's monotonic reads 4900 ahead of A's);
    # estimate must sit inside the [10 ms, 20 ms] lag bracket
    assert abs(off["B"]["offset"] + 4900.0) < 0.02
    doc = merge_dir(str(tmp_path))
    assert validate(doc) == []
    trees = span_trees(doc)
    assert len(trees[T1]["procs"]) == 2         # one tree, two pids
    assert trees[T1]["unresolved"] == []


def test_single_beat_peer_aligns_one_way(tmp_path):
    _write_spool(tmp_path, "A", 1, mono=0.0, wall=500.0,
                 events=[_ev("a", 0.0, T1, S1), _ev("pad", 5.0)],
                 beats_sent=[("A", 7, 1.0)])
    _write_spool(tmp_path, "B", 2, mono=300.0, wall=999.0,
                 events=[_ev("b", 0.0, T1, S2, parent=S1)],
                 beats_seen=[("A", 7, 301.015)])
    off = align(load_dir(str(tmp_path))["procs"])
    assert off["B"]["method"] == "one_way"
    # the single one-sided bound IS the estimate: -300.015
    assert abs(off["B"]["offset"] + 300.015) < 1e-9
    assert validate(merge_dir(str(tmp_path))) == []


def test_beatless_peer_falls_back_to_wall_anchor(tmp_path):
    _write_spool(tmp_path, "A", 1, mono=100.0, wall=1000.0,
                 events=[_ev("a", 0.0, T1, S1), _ev("pad", 5.0)])
    _write_spool(tmp_path, "B", 2, mono=5000.0, wall=1000.5,
                 events=[_ev("b", 0.0, T1, S2, parent=S1)])
    off = align(load_dir(str(tmp_path))["procs"])
    assert off["B"]["method"] == "wall"
    # wall anchors say B's event happened 0.5 s after A's
    assert abs(off["B"]["offset"] + 4899.5) < 1e-6
    doc = merge_dir(str(tmp_path))
    assert doc["otherData"]["procs"]["B"]["method"] == "wall"
    assert validate(doc) == []


def test_torn_trailing_record_skipped_not_crashed(tmp_path):
    p = _write_spool(tmp_path, "A", 1, mono=0.0, wall=0.0,
                     events=[_ev("a", 0.0, T1, S1)], torn=True)
    recs, skipped = read_spool(str(p))
    assert skipped == 1 and len(recs) == 2      # clock + span survive
    doc = merge_dir(str(tmp_path))
    assert doc["otherData"]["skipped_lines"] == 1
    assert validate(doc) == []


def test_causal_clamp_child_never_starts_before_parent(tmp_path):
    """A one-way peer's residual lag can place a child hop BEFORE its
    parent; the merger must clamp it (monotone parent links) and emit
    cross-process flow arrows for the stitched link."""
    _write_spool(tmp_path, "A", 1, mono=0.0, wall=0.0,
                 events=[_ev("parent", 1000.0, T1, S1), _ev("pad", 5.0)],
                 beats_sent=[("A", 1, 0.0)])
    # aligned naively, the child lands at ts 0 — 1 ms before its parent
    _write_spool(tmp_path, "B", 2, mono=50.0, wall=0.0,
                 events=[_ev("child", 0.0, T1, S2, parent=S1)],
                 beats_seen=[("A", 1, 50.0)])
    doc = merge_dir(str(tmp_path))
    assert validate(doc) == []
    assert doc["otherData"]["clamped"] >= 1
    flows = [e for e in doc["traceEvents"]
             if e.get("cat") == "traceflow"]
    assert {e["ph"] for e in flows} == {"s", "f"}
    tree = span_trees(doc)[T1]
    assert tree["spans"][S2]["ts"] >= tree["spans"][S1]["ts"]


def test_empty_dir_raises_merge_error(tmp_path):
    with pytest.raises(MergeError):
        merge_dir(str(tmp_path))


def test_self_check_fixture_green():
    assert self_check() == 0


# ---------------------------------------------------------------------------
# SamplerFleet chaos: reassignment is a child of the original dispatch
# ---------------------------------------------------------------------------

def test_fleet_reassign_span_children_of_original_dispatch():
    import jax
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.sampling import derive_rollout_seeds
    from dla_tpu.rollout import SamplerFleet, SamplerFleetConfig
    from dla_tpu.serving.server import ServingConfig

    cfg = get_model_config("tiny")
    model = Transformer(cfg)
    params = model.init(jax.random.key(7))
    rs = np.random.RandomState(3)
    prompts = [list(rs.randint(3, 500, (n,))) for n in (6, 4, 9, 5)]
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    gen = GenerationConfig(max_new_tokens=5, do_sample=True,
                           temperature=0.9, top_p=0.9, top_k=8,
                           eos_token_id=2, pad_token_id=0)
    seeds = derive_rollout_seeds(123, len(ids))

    prev = get_tracer()
    tracer = Tracer(enabled=True, capacity=1 << 16)
    install_tracer(tracer)
    fleet = SamplerFleet(
        model, params, gen,
        ServingConfig(page_size=4, num_pages=64, num_slots=3,
                      max_model_len=32,
                      fault_plan="sampler=1:rollout_step=0:lost"),
        SamplerFleetConfig(samplers=2, lease_ttl_s=0.3))
    try:
        fleet.generate(ids, mask, seeds)
        assert fleet.fleet_metrics.snapshot()[
            "rollout/fleet/reassigned_rollouts"] >= 1
    finally:
        fleet.close()
        install_tracer(prev)

    evs = [e for e in tracer.export()["traceEvents"]
           if e.get("ph") == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e.get("args") or {})
    roots = by_name.get("fleet_rollout", [])
    dispatches = by_name.get("sampler_dispatch", [])
    reassigns = by_name.get("sampler_reassign_dispatch", [])
    drives = by_name.get("sampler_drive", [])
    assert roots and dispatches and reassigns and drives
    trace_id = roots[0]["trace"]
    # one shared trace id across every hop of the rollout
    assert all(a["trace"] == trace_id
               for a in dispatches + reassigns + drives)
    # initial dispatches parent under the rollout root...
    assert {a["parent"] for a in dispatches} == {roots[0]["span"]}
    # ...and EVERY reassignment parents under an ORIGINAL dispatch span
    # (the acceptance bar: the merged timeline shows reassignment as a
    # child of the dispatch it replaced, not a fresh root)
    dispatch_spans = {a["span"] for a in dispatches}
    for a in reassigns:
        assert a["parent"] in dispatch_spans
    # each drive parents under ITS dispatch (initial or reassign)
    all_dispatch_spans = dispatch_spans | {a["span"] for a in reassigns}
    for a in drives:
        assert a["parent"] in all_dispatch_spans


# ---------------------------------------------------------------------------
# cross-process acceptance: two fleets + router + mid-stream migration
# ---------------------------------------------------------------------------

def test_cross_process_merge_with_midstream_migration(tmp_path):
    """Two SUBPROCESS gateway-fronted fleets behind a FederatedRouter,
    every process spooling spans into one shared dir; a request is
    caught mid-stream on the slow peer and migrated. The merged doc
    must be ONE valid Chrome trace where every federated request's span
    tree crosses the router AND a worker process with resolved parent
    links, the migrated request's tree touches all three processes, and
    no process fell back to wall-clock alignment."""
    sys.path.insert(0, str(REPO_ROOT))
    from _cpuhost import cpu_child_env
    from dla_tpu.serving import FederatedRouter, FederationConfig

    gossip = tmp_path / "gossip"
    spool = tmp_path / "spool"
    gossip.mkdir()
    spool.mkdir()
    env = cpu_child_env(1, str(REPO_ROOT))
    rs = np.random.RandomState(11)
    prompts = [[int(t) for t in rs.randint(3, 500, (6,))]
               for _ in range(4)]

    prev = get_tracer()
    install_tracer(Tracer.from_config(
        {"enabled": True, "capacity": 1 << 17,
         "spool_dir": str(spool), "proc": "router"}))
    procs = {}
    fed = FederatedRouter(gossip, FederationConfig())
    try:
        for name, slow_ms in (("a", "25"), ("b", "0")):
            procs[name] = subprocess.Popen(
                [sys.executable,
                 str(REPO_ROOT / "tests" / "_gateway_worker.py"),
                 str(gossip), name, slow_ms, str(spool)],
                env=env, cwd=str(REPO_ROOT),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True)
        deadline = time.monotonic() + 600
        while len(fed.live_peers()) < 2:
            assert time.monotonic() < deadline, "peers never came up"
            time.sleep(0.05)

        fids = [fed.submit(p, 6) for p in prompts]
        fed.results(timeout_s=600)

        # catch one request mid-stream on the slow peer, then move it
        moved = None
        for _ in range(6):
            f = fed.submit(prompts[0], 8)
            fr = fed._requests[f]
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if fr.peer == "a" and fr.remote_rid is not None \
                        and len(fr.tokens) >= 2 \
                        and fr.state == "pending":
                    moved = f
                    break
                if fr.state != "pending":
                    break
                time.sleep(0.01)
            if moved is not None:
                break
            fed.results(timeout_s=300)
        assert moved is not None, "never caught a mid-stream request"
        fed.migrate(moved, "b")
        out = fed.results(timeout_s=600)
        assert out[moved].state == "finished"
        migrated_trace = fed._requests[moved].trace.trace_id
        traces = {f: fed._requests[f].trace.trace_id
                  for f in fids + [moved]}
    finally:
        for p in procs.values():
            p.send_signal(signal.SIGTERM)
        for p in procs.values():
            try:
                p.communicate(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
        tr = get_tracer()
        assert tr.dropped == 0 and tr.spool_errors == 0
        tr.detach_spool()
        install_tracer(prev)

    assert len(spool_paths(str(spool))) == 3    # router + two workers
    doc = merge_dir(str(spool))
    assert validate(doc) == []
    other = doc["otherData"]
    assert set(other["procs"]) == {"router", "a", "b"}
    # beats flow worker->router; nobody may need wall clocks
    assert all(p["method"] in ("reference", "paired", "one_way")
               for p in other["procs"].values())
    trees = span_trees(doc)
    for f, tid in traces.items():
        tree = trees.get(tid)
        assert tree is not None, f"request {f}: no spans merged"
        assert tree["unresolved"] == []
        assert len(tree["procs"]) >= 2, \
            f"request {f}'s span tree never crossed a process boundary"
    # the migrated request's tree touches router + source + target
    assert len(trees[migrated_trace]["procs"]) == 3
