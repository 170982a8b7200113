"""The program's spans on the profiler's clock (utils/profiling.SPANS): the
engine and the trainer emit exactly what the table lists, with exactly its
arguments, nested and ordered as PERF.md section 3 says; a real profiler
trace carries the arguments; StepClock's accounting covers the metrics
fetch; the HLO scope map and the classifier read a lowered train step."""
import dataclasses
import glob
import itertools
import time

import jax
import numpy as np
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.parallel.mesh import mesh_from_config
from dla_tpu.serving import ServingConfig, ServingEngine
from dla_tpu.telemetry import is_catalog_name
from dla_tpu.telemetry.stepclock import SEGMENTS, StepClock
from dla_tpu.telemetry.xla_introspect import compiled_scopes, hlo_scopes
from dla_tpu.training.train_sft import make_sft_loss
from dla_tpu.training.trainer import Trainer
from dla_tpu.utils import profiling
from dla_tpu.utils.profiling import SPANS

MAX_NEW = 8
#: the most spans and marks one engine step may emit: 1 step span, 2
#: schedule passes, admit, chunk + fetch + first token, decode and its 4
#: children, post; marks for a step's admissions, finishes, preemptions
SPAN_CAP = 13 + 3 * 4


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation`` (and the step
    variant): rows of [name, kwargs, enter tick, exit tick] on a logical
    clock, so nesting and order are exact."""

    def __init__(self):
        self.rows = []
        self.tick = itertools.count()
        rec = self

        class Annotation:
            def __init__(self, name, **kwargs):
                self.row = [name, kwargs, None, None]

            def __enter__(self):
                self.row[2] = next(rec.tick)
                rec.rows.append(self.row)
                return self

            def __exit__(self, *exc):
                self.row[3] = next(rec.tick)
                return False

        self.cls = Annotation

    def install(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", self.cls)
        monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", self.cls)
        return self

    def named(self, name):
        return [r for r in self.rows if r[0] == name]

    def children(self, row):
        """Rows directly inside ``row``."""
        inside = [r for r in self.rows
                  if r is not row and row[2] < r[2] and r[3] < row[3]]
        return [r for r in inside
                if not any(o is not r and o[2] < r[2] and r[3] < o[3]
                           for o in inside)]


@pytest.fixture(scope="module")
def model_and_params():
    model = Transformer(get_model_config("tiny"))
    return model, model.init(jax.random.key(7))


def _prompts(n=4, seed=3):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(3, 500, (length,)))
            for length in rs.randint(4, 10, (n,))]


ENGINES = {
    "chunked": dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32,
                    prefill_chunk=4),
    # prefill_chunk unset: one chunk as wide as the window
    "default_chunk": dict(page_size=4, num_pages=32, num_slots=2,
                          max_model_len=32),
    "speculative": dict(page_size=4, num_pages=32, num_slots=2,
                        max_model_len=32,
                        speculative={"enabled": True, "k": 3,
                                     "draft": "self"}),
    # capacity 7 pages: both prompts admit, cannot both grow to 12 tokens
    "preempting": dict(page_size=2, num_pages=8, num_slots=2,
                       max_model_len=12, prefill_chunk=2),
    # latent attention + routed experts (the tiny-mla-moe preset): the
    # decode phase also marks what its dropless routing did
    "experts": dict(page_size=4, num_pages=32, num_slots=2, max_model_len=32,
                    prefill_chunk=4),
    # layers of several kinds (the tiny-sambay preset): a window pool and
    # per-slot state beside the paged rows; two block-table rows a slot
    # in the one packed array
    "state_space": dict(page_size=4, num_pages=32, num_slots=2,
                        max_model_len=32, prefill_chunk=4),
    # state-space layers beside two plain multi-query attention layers
    # with rows of their own (the tiny-jamba preset): no window pool
    "ssm_attention": dict(page_size=4, num_pages=32, num_slots=2,
                          max_model_len=32, prefill_chunk=4),
    # Mamba-2 layers beside plain attention with routed experts in every
    # layer (the tiny-granite-h preset): state and the routing mark
    "ssm_experts": dict(page_size=4, num_pages=32, num_slots=2,
                        max_model_len=32, prefill_chunk=4),
}
PRESETS = {"experts": "tiny-mla-moe", "state_space": "tiny-sambay",
           "ssm_attention": "tiny-jamba", "ssm_experts": "tiny-granite-h"}
ROUTED = ("experts", "ssm_experts")


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_engine_emits_the_span_table(model_and_params, monkeypatch, kind):
    model, params = model_and_params
    if kind in PRESETS:
        model = Transformer(get_model_config(PRESETS[kind]))
        params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen, ServingConfig(**ENGINES[kind]))
    rec = Recorder().install(monkeypatch)
    prompts = ([[5, 6, 7, 8]] * 2 if kind == "preempting" else _prompts())
    geom = eng.cache.geom
    live = []                     # the host mirror's sum, per decode phase
    decode = (eng._spec_decode_step if kind == "speculative"
              else eng._decode_step)

    def watched():
        live.append(int(eng.cache.lengths[sorted(
            eng.scheduler.running)].sum()))
        return decode()
    monkeypatch.setattr(
        eng, "_spec_decode_step" if kind == "speculative"
        else "_decode_step", watched)
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    eng.run_until_drained(max_steps=500)
    eng.close()

    # every emitted name is in the table, with exactly its arguments
    for name, kwargs, _, _ in rec.rows:
        assert name in SPANS, f"{name} is not in profiling.SPANS"
        assert tuple(kwargs) == SPANS[name][1], (name, kwargs)
        assert all(isinstance(v, (int, str)) and not isinstance(v, bool)
                   for v in kwargs.values()), (name, kwargs)
    # every row of the table this path exercises is emitted
    want = {"serve", "serve_schedule", "serve_admit", "serve_first_token",
            "serve_decode", "serve_decode_args", "serve_decode_dispatch",
            "serve_decode_fetch", "serve_emit", "serve_post",
            "serve_req_submit", "serve_req_admit", "serve_req_first_token",
            "serve_req_finish", "serve_prefill_chunk", "serve_chunk_fetch"}
    if kind in ROUTED:
        want.add("serve_moe_route")
    else:
        assert not rec.named("serve_moe_route")     # no routed experts
    if kind == "preempting":
        want.add("serve_req_preempt")
        assert eng.metrics.preemptions.value == len(
            rec.named("serve_req_preempt")) >= 1
    assert want <= {r[0] for r in rec.rows}

    # children nest inside `serve`, in order, without overlap, and tile it
    steps = rec.named("serve")
    assert [s[1]["step_num"] for s in steps] == list(range(len(steps)))
    for step in steps:
        kids = rec.children(step)
        assert kids and kids[0][0] == "serve_schedule"
        assert kids[-1][0] == "serve_post"
        for a, b in zip(kids, kids[1:]):
            assert a[3] < b[2], (a[0], b[0])
        inside = [r for r in rec.rows if step[2] <= r[2] and r[3] <= step[3]]
        assert len(inside) <= SPAN_CAP, [r[0] for r in inside]
    # every span of a step is inside its step span (submit marks are the
    # caller's, between steps)
    for row in rec.rows:
        if row[0] not in ("serve", "serve_req_submit"):
            assert any(s[2] < row[2] and row[3] < s[3] for s in steps), row

    # the decode phase: its children, and the KV read it reports
    reads = (eng._spec_k + 1) * geom.num_slots * geom.slot_window
    phases = rec.named("serve_decode")
    assert len(phases) == len(live) > 0
    inside_decode = ["serve_decode_args", "serve_decode_dispatch",
                     "serve_decode_fetch", "serve_emit"]
    if kind in ROUTED:          # the mark follows the step's one fetch
        inside_decode.insert(3, "serve_moe_route")
    for phase, held in zip(phases, live):
        assert [k[0] for k in rec.children(phase)] == inside_decode
        assert phase[1]["read_tokens"] == reads
        assert phase[1]["live_tokens"] == held
        assert 1 <= phase[1]["slots"] <= geom.num_slots
        assert phase[1]["sampling_slots"] == 0      # greedy requests
        # constants of the geometry, 0 for a model with neither
        if kind == "state_space":
            assert phase[1]["state_slots"] == geom.num_slots
            assert phase[1]["window_read_tokens"] == (
                geom.num_slots * geom.window_gather_pages * geom.page_size)
            assert geom.window_gather_pages == 8 // 4 + 1
        elif kind in ("ssm_attention", "ssm_experts"):
            assert phase[1]["state_slots"] == geom.num_slots
            assert phase[1]["window_read_tokens"] == 0
        else:
            assert phase[1]["state_slots"] == 0
            assert phase[1]["window_read_tokens"] == 0
    assert eng.metrics.decode_steps_sampled.value == 0
    # what each dispatch sent to the device: one packed array, its bytes
    # fixed by the geometry (a speculative round's two programs share it)
    sent = {"serve_decode_args": eng._decode_layout.nbytes(geom.num_slots),
            "serve_prefill_chunk": eng._chunk_layout.nbytes()}
    rows = [r for r in rec.rows if r[0] in sent]
    assert all(r[1]["puts"] == 1 and r[1]["h2d_bytes"] == sent[r[0]]
               for r in rows)
    snap = eng.metrics.snapshot()
    assert snap["serving/step_arg_puts"] == len(rows)
    assert snap["serving/step_arg_bytes"] == sum(
        r[1]["h2d_bytes"] for r in rows)
    # a chunk's ``context`` is what the slot held when it started; the
    # scan counter is the chunks' real tokens x the state-space layers
    chunks = rec.named("serve_prefill_chunk")
    assert all(c[1]["context"] == c[1]["start"] for c in chunks)
    state_layers = {"state_space": 4, "ssm_attention": 10,
                    "ssm_experts": 9}.get(kind, 0)
    assert snap["serving/prefill/scan_tokens"] == state_layers * sum(
        c[1]["nvalid"] for c in chunks)
    assert snap["serving/kv_paged_layers"] == {
        "state_space": 1, "ssm_attention": 2, "experts": 2,
        "ssm_experts": 1}.get(kind, model.cfg.num_layers)
    if kind in ROUTED:
        cfg = model.cfg
        routes = [r[1] for r in rec.named("serve_moe_route")]
        assert len(routes) == len(phases)
        for route, phase in zip(routes, phases):
            assert route["slots"] == phase[1]["slots"]
            # every running row's choices land (all experts are held),
            # summed over layers; no expert is hit without a pair
            assert route["expert_assignments"] == (
                route["slots"] * cfg.num_experts_per_token * cfg.num_layers)
            assert 1 <= route["experts_hit"] <= route["expert_assignments"]
        snap = eng.metrics.snapshot()
        assert snap["serving/moe/experts_hit"] == sum(
            r["experts_hit"] for r in routes)
        assert snap["serving/moe/expert_assignments"] == sum(
            r["expert_assignments"] for r in routes)

    # each request: submit -> admit -> first_token -> finish, in time order
    for rid in rids:
        marks = [(r[0][len("serve_req_"):], r[2]) for r in rec.rows
                 if r[0].startswith("serve_req_") and r[1]["rid"] == rid]
        kinds = [k for k, _ in marks if k != "preempt"]
        assert kinds[0] == "submit" and kinds[1] == "admit"
        assert kinds[-1] == "finish" and kinds.count("first_token") == 1
        assert kinds.count("finish") == kinds.count("submit") == 1
        assert kinds.index("first_token") > kinds.index("admit")
        # a preempted request is admitted once more per preemption
        assert kinds.count("admit") == 1 + sum(
            1 for k, _ in marks if k == "preempt")
        done = rec.named("serve_req_finish")
        row = next(r for r in done if r[1]["rid"] == rid)
        assert row[1]["status"] == "length" and row[1]["tokens"] == MAX_NEW


def test_the_bucketed_prefill_left_no_span_and_no_knob():
    """One prefill lane: its span is the chunk's, and no field of either
    config selects or sizes another."""
    from dla_tpu.serving import SchedulerConfig
    assert "serve_prefill" not in SPANS and "serve_prefill_chunk" in SPANS
    for cfg in (ServingConfig, SchedulerConfig):
        names = {f.name for f in dataclasses.fields(cfg)}
        assert not names & {"max_prefill_batch", "lookahead"}, cfg
        assert "prefill_chunk" in names


def _tiny_trainer(out_dir):
    cfg = get_model_config("tiny", remat="full", max_seq_length=16)
    mesh = mesh_from_config({"mesh": {"data": 2, "fsdp": 2, "model": 2}})
    with jax.sharding.set_mesh(mesh):
        model = Transformer(cfg)
        trainer = Trainer(
            config={"experiment_name": "spans",
                    "optimization": {"total_batch_size": 4,
                                     "micro_batch_size": 1,
                                     "learning_rate": 1e-3,
                                     "max_train_steps": 100,
                                     "lr_scheduler": "constant"},
                    "logging": {"output_dir": str(out_dir), "log_dir": None,
                                "save_every_steps": 0},
                    "hardware": {"gradient_accumulation_steps": 1}},
            mesh=mesh, loss_fn=make_sft_loss(model),
            params=model.init(jax.random.key(0)),
            param_specs=model.partition_specs())
    batch = {"input_ids": np.ones((4, 16), np.int32),
             "labels": np.ones((4, 16), np.int32),
             "attention_mask": np.ones((4, 16), np.int32)}
    return mesh, trainer, batch


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """A tiny trainer after one (compiling) step, shared by the cases
    below; each takes further steps of its own."""
    mesh, trainer, batch = _tiny_trainer(tmp_path_factory.mktemp("spans"))
    with jax.sharding.set_mesh(mesh):
        trainer.step_on_batch(batch, jax.random.key(1))
    return mesh, trainer, batch


def test_trainer_emits_the_span_table(trained, monkeypatch):
    mesh, trainer, batch = trained
    rec = Recorder().install(monkeypatch)
    first = trainer.step
    with jax.sharding.set_mesh(mesh):
        for i in range(3):
            with trainer.clock.segment("data_wait"):
                pass
            trainer.step_on_batch(batch, jax.random.key(i))
    for name, kwargs, _, _ in rec.rows:
        assert name in SPANS, name
        assert tuple(kwargs) == SPANS[name][1], (name, kwargs)
    names = [r[0] for r in rec.rows]
    per_step = ["train_data_wait", "train_h2d", "train", "train_dispatch",
                "train_loss_fetch", "train_guard_fetch",
                "train_metrics_fetch"]
    assert names == per_step * 3
    assert [r[1]["step_num"] for r in rec.named("train")] == [
        first, first + 1, first + 2]
    for step in rec.named("train"):
        assert [k[0] for k in rec.children(step)] == [
            "train_dispatch", "train_loss_fetch"]
        assert abs(step[1]["host_ns"] - time.perf_counter_ns()) < 600e9
    # the segment spans are siblings of `train`, one after another
    top = [r for r in rec.rows
           if r[0] not in ("train_dispatch", "train_loss_fetch")]
    for a, b in zip(top, top[1:]):
        assert a[3] < b[2]
    assert trainer.train_step_compiles == 1     # spans add no retrace


def test_every_span_of_the_table_has_a_layer_and_plain_arguments():
    assert len(SPANS) >= 25
    for name, (layer, args) in SPANS.items():
        assert name.startswith(("serve", "train", "startup_", "xla_")), name
        assert layer and isinstance(args, tuple)
        # the start-up layer is the start-up spans, and only they
        assert (layer == "start-up") == name.startswith(
            ("startup_", "xla_")), name
    # every StepClock segment has its profiler span; compute is `train`
    for seg in SEGMENTS:
        assert ("train" if seg == "compute" else f"train_{seg}") in SPANS


# ------------------------------------------------------- start-up records

def _held_to_the_table(records):
    """Every start-up record is a row of SPANS, layer ``start-up``, with
    exactly the row's arguments, plain values, and a sane interval."""
    assert records
    for r in records:
        assert r["name"] in SPANS, r["name"]
        layer, args = SPANS[r["name"]]
        assert layer == "start-up" and tuple(r["args"]) == args, r
        assert all(isinstance(v, (int, str)) and not isinstance(v, bool)
                   for v in r["args"].values()), r
        assert 0 < r["start_ns"] <= r["end_ns"] and r["thread"]


def _inside(inner, outer):
    return (outer["start_ns"] <= inner["start_ns"]
            and inner["end_ns"] <= outer["end_ns"]
            and inner["thread"] == outer["thread"])


def _one(records, name, **args):
    rows = [r for r in records if r["name"] == name
            and all(r["args"].get(k) == v for k, v in args.items())]
    assert len(rows) == 1, (name, args, rows)
    return rows[0]


@pytest.mark.parametrize("preset", ["tiny", "tiny-jamba"])
def test_engine_startup_records_and_none_from_a_steady_step(
        monkeypatch, capsys, preset):
    profiling.reset_startup_spans()
    rec = Recorder().install(monkeypatch)
    model = Transformer(get_model_config(preset))
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen,
                        ServingConfig(**ENGINES["chunked"]))
    eng.submit([5, 6, 7, 8, 9, 10], MAX_NEW)
    assert capsys.readouterr().out == ""        # nothing said yet
    for _ in range(50):
        if eng.step():
            break
    records = profiling.startup_spans()
    _held_to_the_table(records)
    build = _one(records, "startup_model_build")
    assert build["args"] == {"layers": model.cfg.num_layers,
                             "kernel_imports": ""}     # no TPU here
    engine = _one(records, "startup_engine_build")
    assert engine["args"] == {"slots": 2, "pages": 32}
    pools = _one(records, "startup_pool_alloc")
    assert pools["args"]["arrays"] == len(eng.cache.spec)
    assert _inside(pools, engine) and not _inside(engine, build)
    for fn in ("decode", "prefill_chunk"):
        low = _one(records, "xla_lower", fn=fn)
        comp = _one(records, "xla_compile", fn=fn)
        assert low["end_ns"] <= comp["start_ns"]
        assert comp["args"]["cache_hit"] == -1  # the suite's cache is off
        assert comp["args"]["n_compiles"] == low["args"]["n_compiles"] == 1
        assert not _inside(low, engine)         # at the first dispatch
    # the profiler's events are the same rows (a late argument keeps
    # its placeholder there), and the compiles sit in their step span
    emitted = [r for r in rec.rows if r[0].startswith(("startup_", "xla_"))]
    assert sorted(r[0] for r in emitted) == sorted(
        r["name"] for r in records)
    for name, kwargs, _, _ in emitted:
        assert tuple(kwargs) == SPANS[name][1], (name, kwargs)
    steps = rec.named("serve")
    for row in rec.named("xla_lower") + rec.named("xla_compile"):
        assert any(s[2] < row[2] and row[3] < s[3] for s in steps)
    # the first token out: gauges on the engine's registry and one line
    snap = eng.metrics.registry.snapshot()
    summary = profiling.startup_summary()
    for name in ("startup_model_build", "startup_engine_build",
                 "startup_pool_alloc", "xla_lower", "xla_compile"):
        gauge = f"telemetry/xla/startup/{name}_s"
        assert is_catalog_name(gauge)
        assert snap[gauge] == pytest.approx(summary["spans_s"][name])
    assert snap["telemetry/xla/startup/background_import_s"] == 0.0
    assert snap["telemetry/xla/cache_misses"] == 0.0
    assert snap["telemetry/xla/lower_s"] > 0.0
    # self time: the engine's build does not count its pools twice
    assert summary["spans_s"]["startup_engine_build"] == pytest.approx(
        (engine["end_ns"] - engine["start_ns"]
         - pools["end_ns"] + pools["start_ns"]) * 1e-9)
    said = capsys.readouterr().out
    assert said.count("[dla_tpu] start-up (self s):") == 1
    assert "persistent cache 0 hits / 0 misses" in said
    # a steady-state step adds no record and says nothing
    for _ in range(3):
        assert eng.step()
    assert len(profiling.startup_spans()) == len(records)
    assert capsys.readouterr().out == ""
    assert eng.decode_compiles == eng.prefill_chunk_compiles == 1
    eng.close()


def test_trainer_startup_records_and_none_from_a_steady_step(
        tmp_path, capsys):
    profiling.reset_startup_spans()
    mesh, trainer, batch = _tiny_trainer(tmp_path / "startup")
    records = profiling.startup_spans()
    _held_to_the_table(records)
    build = _one(records, "startup_trainer_build")
    state = _one(records, "startup_state_init")
    assert _inside(state, build)
    assert not _inside(build, _one(records, "startup_model_build"))
    assert not any(r["name"].startswith("xla_") for r in records)
    with jax.sharding.set_mesh(mesh):
        trainer.step_on_batch(batch, jax.random.key(1))
    records = profiling.startup_spans()
    _held_to_the_table(records)
    low = _one(records, "xla_lower", fn="train_step")
    comp = _one(records, "xla_compile", fn="train_step")
    assert build["end_ns"] <= low["start_ns"] <= low["end_ns"] \
        <= comp["start_ns"]
    snap = trainer.registry.snapshot()
    for name in ("startup_trainer_build", "startup_state_init",
                 "xla_lower", "xla_compile"):
        assert snap[f"telemetry/xla/startup/{name}_s"] >= 0.0
    assert snap["telemetry/xla/train_step/lower_s"] == pytest.approx(
        (low["end_ns"] - low["start_ns"]) * 1e-9)
    assert capsys.readouterr().out.count("start-up (self s):") == 1
    with jax.sharding.set_mesh(mesh):
        for i in range(3):
            trainer.step_on_batch(batch, jax.random.key(i))
    assert len(profiling.startup_spans()) == len(records)
    assert "start-up" not in capsys.readouterr().out
    assert trainer.train_step_compiles == 1


def test_startup_records_are_capped_and_count_their_drops(monkeypatch):
    profiling.reset_startup_spans()
    monkeypatch.setattr(profiling, "STARTUP_SPAN_CAP", 4)
    for i in range(7):
        with profiling.startup_span("startup_kernel_import",
                                    module=f"m{i}"):
            pass
    records = profiling.startup_spans()
    assert [r["args"]["module"] for r in records] == ["m0", "m1", "m2", "m3"]
    assert profiling.startup_spans_dropped() == 3
    assert profiling.startup_summary()["records_dropped"] == 3
    # a copy: a reader cannot edit the process's list
    records[0]["args"]["module"] = "edited"
    assert profiling.startup_spans()[0]["args"]["module"] == "m0"
    profiling.reset_startup_spans()
    assert profiling.startup_spans() == []
    assert profiling.startup_spans_dropped() == 0


def test_background_import_carries_its_threads_name_and_stays_apart():
    """The constructor's import thread and the main thread's wait at
    trace time are told apart by the thread's name alone; the summary
    keeps the hidden seconds out of the foreground's."""
    import threading
    profiling.reset_startup_spans()
    gate = threading.Event()

    def background():
        with profiling.startup_span("startup_kernel_import",
                                    module="dla_tpu.ops.paged_attention"):
            gate.wait(5.0)

    thread = threading.Thread(target=background, daemon=True,
                              name="dla-paged-kernel-import")
    with profiling.startup_span("xla_lower", fn="decode", n_compiles=1):
        thread.start()
        with profiling.startup_span("startup_kernel_import",
                                    module="dla_tpu.ops.paged_attention"):
            time.sleep(0.02)
            gate.set()
            thread.join()
    records = profiling.startup_spans()
    by_thread = {r["thread"]: r for r in records
                 if r["name"] == "startup_kernel_import"}
    assert set(by_thread) == {"dla-paged-kernel-import", "MainThread"}
    lower = _one(records, "xla_lower")
    wait = by_thread["MainThread"]
    assert _inside(wait, lower)
    summary = profiling.startup_summary()
    hidden = by_thread["dla-paged-kernel-import"]
    assert summary["background_import_s"] == pytest.approx(
        (hidden["end_ns"] - hidden["start_ns"]) * 1e-9)
    assert summary["spans_s"]["startup_kernel_import"] == pytest.approx(
        (wait["end_ns"] - wait["start_ns"]) * 1e-9)
    # the lowering's self time leaves its nested wait out, and the other
    # thread's import, which overlaps it, is not subtracted
    assert summary["spans_s"]["xla_lower"] == pytest.approx(
        (lower["end_ns"] - lower["start_ns"]
         - wait["end_ns"] + wait["start_ns"]) * 1e-9)
    assert "background kernel imports" in profiling.startup_line(summary)


def test_a_late_argument_has_to_be_one_the_span_opened_with():
    profiling.reset_startup_spans()
    with profiling.startup_span("xla_compile", fn="f", n_compiles=1,
                                cache_hit=-1) as span:
        span.set(cache_hit=1)
        with pytest.raises(KeyError):
            span.set(hit=1)
    assert profiling.startup_spans()[0]["args"] == {
        "fn": "f", "n_compiles": 1, "cache_hit": 1}
    assert span.seconds >= 0.0


def test_weights_path_leaves_its_record():
    from dla_tpu.training.model_io import load_causal_lm
    profiling.reset_startup_spans()
    bundle = load_causal_lm("tiny", {"tokenizer": "byte"},
                            jax.random.key(0))
    records = profiling.startup_spans()
    _held_to_the_table(records)
    weights = _one(records, "startup_weights")
    assert weights["args"] == {"source": "preset"}
    assert all(_inside(r, weights) for r in records
               if r["name"] == "startup_model_build")
    assert bundle.params is not None


# ------------------------------------------------- a real profiler trace

def test_real_trace_carries_arguments_and_host_ns(model_and_params, trained,
                                                  tmp_path):
    from jax.profiler import ProfileData
    model, params = model_and_params
    gen = GenerationConfig(max_new_tokens=4, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    eng = ServingEngine(model, params, gen, ServingConfig(**ENGINES["chunked"]))
    rid = eng.submit([5, 6, 7, 8, 9, 10], 4)
    eng.step()                    # compiles outside the trace
    mesh, trainer, batch = trained
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    before = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            eng.step()
        with jax.sharding.set_mesh(mesh):
            for i in range(2):
                trainer.step_on_batch(batch, jax.random.key(i))
    finally:
        jax.profiler.stop_trace()
        after = time.perf_counter_ns()
        eng.close()
    path = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name in SPANS]
    by_name = {}
    for ev in events:
        by_name.setdefault(ev.name, []).append(ev)
    assert len(by_name["serve"]) == 3 and len(by_name["train"]) == 2
    chunk = dict(by_name["serve_prefill_chunk"][0].stats)
    assert chunk["rid"] == rid and chunk["nvalid"] >= 1
    assert chunk["puts"] == 1
    assert chunk["h2d_bytes"] == eng._chunk_layout.nbytes()
    sent = dict(by_name["serve_decode_args"][0].stats)
    assert sent["puts"] == 1 and sent["h2d_bytes"] == (
        eng._decode_layout.nbytes(eng.cache.geom.num_slots))
    # host_ns is perf_counter_ns at the span's start: the pairs
    # (host_ns, start_ns) of two spans differ by the same offset, which
    # is what lays a perf_counter reading over the xplane's clock
    steps = by_name["serve"] + by_name["train"]
    offsets = [dict(ev.stats)["host_ns"] - ev.start_ns for ev in steps]
    assert all(before <= dict(ev.stats)["host_ns"] <= after for ev in steps)
    assert max(offsets) - min(offsets) < 1e6        # within a millisecond
    for ev in steps:
        at = dict(ev.stats)["host_ns"] - min(offsets)
        assert ev.start_ns - 1e6 <= at <= ev.start_ns + ev.duration_ns
    assert dict(by_name["serve_decode"][0].stats)["read_tokens"] == (
        eng.cache.geom.num_slots * eng.cache.geom.slot_window)


# ----------------------------------------------------------- StepClock

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_metrics_fetch_is_inside_the_steps_wall():
    fc = FakeClock()
    seen = []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(self.name)

        def __exit__(self, *exc):
            return False

    clock = StepClock(now=fc, span=Span)
    with clock.segment("h2d"):
        fc.advance(0.002)
    with clock.segment("compute"):
        fc.advance(0.050)
    with clock.segment("metrics_fetch"):
        fc.advance(0.007)
    fc.advance(0.001)
    clock.end_step(ok=True)
    assert seen == ["h2d", "compute", "metrics_fetch"]
    assert clock.wall_total == pytest.approx(0.060)
    assert clock.seg_total["metrics_fetch"] == pytest.approx(0.007)
    assert sum(clock.seg_total.values()) + clock.other_total == \
        pytest.approx(clock.wall_total)
    out = clock.interval_metrics()
    assert out["telemetry/metrics_fetch_ms"] == pytest.approx(7.0)
    assert out["telemetry/other_ms"] == pytest.approx(1.0)


def test_step_on_batch_accounts_h2d_and_metrics_fetch(trained):
    mesh, trainer, batch = trained
    clock = trainer.clock
    before = dict(clock.seg_total), clock.wall_total, clock.other_total
    with jax.sharding.set_mesh(mesh):
        trainer.step_on_batch(batch, jax.random.key(5))
    for seg in ("h2d", "compute", "metrics_fetch"):
        assert clock.seg_total[seg] > before[0][seg]
    grown = sum(clock.seg_total.values()) - sum(before[0].values())
    assert grown + clock.other_total - before[2] == pytest.approx(
        clock.wall_total - before[1], rel=1e-6)
    assert clock.last_wall_ms * 1e-3 == pytest.approx(
        clock.wall_total - before[1], rel=1e-6)


def test_clock_rates_leave_the_first_step_out():
    fc = FakeClock()
    clock = StepClock(now=fc)
    assert clock.rates(4) == {"tokens_per_sec": 0.0, "ms_per_step": 0.0}
    clock.count_tokens(1000)          # the compiling step: starts the clock
    for _ in range(4):
        fc.advance(0.5)
        clock.count_tokens(1000)
    rates = clock.rates(4)
    assert rates["tokens_per_sec"] == pytest.approx(2000.0)
    assert rates["tokens_per_sec_per_chip"] == pytest.approx(500.0)
    assert rates["ms_per_step"] == pytest.approx(500.0)
    # counted with telemetry off too: the payload keys do not depend on it
    off = StepClock(enabled=False, now=fc)
    off.count_tokens(10)
    fc.advance(1.0)
    off.count_tokens(10)
    assert off.rates(1)["tokens_per_sec"] == pytest.approx(10.0)


def test_fit_payload_keeps_its_throughput_keys(tmp_path):
    mesh, trainer, batch = _tiny_trainer(tmp_path / "fit")
    trainer.max_steps, trainer.log_every = 4, 2
    rows = []
    trainer.logger.log = lambda payload, step: rows.append(dict(payload))

    def batches():
        while True:
            yield batch
    with jax.sharding.set_mesh(mesh):
        trainer.fit(batches(), rng=jax.random.key(0))
    assert len(rows) == 2
    for key in ("tokens_per_sec", "tokens_per_sec_per_chip", "ms_per_step"):
        assert rows[-1][key] > 0.0
    assert rows[-1]["tokens_per_sec"] == pytest.approx(
        rows[-1]["tokens_per_sec_per_chip"] * jax.device_count())
    assert "telemetry/metrics_fetch_ms" in rows[-1]


# ------------------------------------------------ device scopes in the HLO

def test_hlo_scopes_of_the_lowered_train_step(trained):
    from perfbench.lib.spans import classify
    scopes = compiled_scopes(r"jit__train_step")
    assert len(scopes) > 100
    assert compiled_scopes(r"no_such_module") == {}
    kinds = {}
    for op_name in scopes.values():
        kinds.setdefault(classify(op_name), []).append(op_name)
    # the trainer's scope, JAX's backward and remat frames, the model's
    assert {"optimizer", "metrics", "remat", "backward", "forward"} <= set(
        kinds)
    assert any("transpose(" in n for n in kinds["backward"])
    assert any("rematted_computation" in n for n in kinds["remat"])
    assert any("head_loss" in n for n in scopes.values())
    assert any("embed" in n for n in scopes.values())


def test_hlo_scopes_fusion_takes_its_roots_name():
    text = '''HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/optimizer/mul" stack_frame_id=3}
}

ENTRY %main.5 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  ROOT %add.2 = f32[4]{0} add(%fusion.7, %a), metadata={op_name="jit(f)/transpose(jvp())/add"}
}
'''
    assert hlo_scopes(text) == {
        "mul.1": "jit(f)/optimizer/mul",
        "fusion.7": "jit(f)/optimizer/mul",
        "add.2": "jit(f)/transpose(jvp())/add"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_train_step)/optimizer/mul", "optimizer"),
    ("jit(_train_step)/optimizer/jit(_where)/select_n", "optimizer"),
    ("jit(_train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/rematted_computation/dot_general", "remat"),
    ("jit(_train_step)/while/body/closed_call/transpose(jvp())/while/body/"
     "closed_call/checkpoint/dot_general", "backward"),
    ("jit(_train_step)/while/body/closed_call/transpose(jvp(head_loss))/"
     "dot_general", "backward"),
    ("jit(_train_step)/while/body/closed_call/jvp(head_loss)/slice",
     "forward"),
    ("jit(_train_step)/while/body/closed_call/jvp(embed)/jit(_take)",
     "forward"),
    ("jit(_train_step)/while/body/closed_call/jvp()/while/body/closed_call/"
     "dot_general", "forward"),
    ("jit(_train_step)/step_metrics/reduce_sum", "metrics"),
    ("jit(_train_step)/optimizer/step_metrics/mul", "optimizer"),
    ("jit(_train_step)/reduce_sum", "unscoped"),
    ("jit(_train_step)/my_optimizer_state/add", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_classifier_precedence(op_name, scope):
    from perfbench.lib.spans import classify
    assert classify(op_name) == scope
