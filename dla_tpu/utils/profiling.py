"""Tracing / profiling / numerics-debug subsystem.

The reference's only perf tooling is ``torch.cuda.synchronize`` +
``perf_counter`` around forward passes (reference src/eval/eval_latency.py:
45-53) and it has no sanitizers beyond seeding (reference
src/training/utils.py:24-29; SURVEY.md sec 5 rows "Tracing / profiling"
and "Race detection / sanitizers"). TPU-native replacement:

- **Trace capture**: ``ProfileWindow`` wraps ``jax.profiler.start_trace``
  / ``stop_trace`` around a configured step range, dumping an xplane
  trace viewable in TensorBoard/XProf/Perfetto. Config-gated::

      logging:
        profile:
          trace_dir: logs/trace      # where the xplane dump goes
          start_step: 10             # first profiled step
          num_steps: 3               # how many steps to capture

- **Spans on the profiler's clock**: ``annotate`` / ``step_annotation`` /
  ``mark`` emit ``jax.profiler.TraceAnnotation`` events with arguments,
  in the same xplane as the device ops. ``SPANS`` is the one table of
  what the engine and the trainer emit (docs/OBSERVABILITY.md).

- **Start-up spans kept in memory**: ``startup_span`` is an ``annotate``
  that also leaves one record in a bounded process-wide list
  (``startup_spans()``), because no profiler runs while a process
  starts: the constructors, the lazy kernel imports and every lowering
  and compile of an ``IntrospectedFunction``. ``startup_summary()``
  reduces them, with the compile accounting of
  ``telemetry.xla_introspect``, to the one line an operator reads.

- **Live profiler server**: ``hardware.profiler_port: 9999`` starts
  ``jax.profiler.start_server`` for on-demand capture from TensorBoard
  while a long run is in flight.

- **Numerics debugging** (the JAX analog of a sanitizer pass):
  ``hardware.debug_nans`` / ``hardware.debug_infs`` flip
  ``jax.config.jax_debug_nans`` / ``jax_debug_infs`` — every jitted step
  then re-runs op-by-op on a non-finite result and raises at the exact
  primitive. ``hardware.log_compiles`` surfaces recompilation storms.
  Data races are absent by construction (pure functional transforms),
  so these flags are the whole sanitizer surface.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from dla_tpu.telemetry import xla_introspect
from dla_tpu.telemetry.trace import get_tracer

_SERVER = None  # keep a ref so the profiler server outlives the call


def apply_debug_flags(hardware_cfg: Optional[Dict[str, Any]]) -> None:
    """Apply numerics/compile debug toggles from the ``hardware:`` block.

    Idempotent and cheap; called by the Trainer before the first compile so
    the flags affect the jitted step. Unknown keys are ignored (GPU-era
    keys like ``deepspeed_config`` pass through harmlessly, SURVEY.md
    sec 7 "tolerating the GPU-era keys").
    """
    cfg = hardware_cfg or {}
    if "debug_nans" in cfg:
        jax.config.update("jax_debug_nans", bool(cfg["debug_nans"]))
    if "debug_infs" in cfg:
        jax.config.update("jax_debug_infs", bool(cfg["debug_infs"]))
    if "log_compiles" in cfg:
        jax.config.update("jax_log_compiles", bool(cfg["log_compiles"]))
    port = cfg.get("profiler_port")
    if port:
        global _SERVER
        if _SERVER is None:
            _SERVER = jax.profiler.start_server(int(port))


class ProfileWindow:
    """Capture a jax.profiler trace over steps [start_step, start_step+num).

    Driven by the trainer loop: call ``on_step(step)`` before each step and
    ``close()`` when the loop ends (also stops a window that was cut short
    by max_steps). Only process 0 captures — one host's trace is
    representative under SPMD and multi-host writers would race on the
    same directory.
    """

    def __init__(self, profile_cfg: Optional[Dict[str, Any]]):
        cfg = profile_cfg or {}
        self.trace_dir = cfg.get("trace_dir")
        self.start_step = int(cfg.get("start_step", 1))
        # a non-positive window (config typo) would otherwise trace the
        # entire run: the stop check only fires after num_steps captures
        self.num_steps = max(1, int(cfg.get("num_steps", 3)))
        self.enabled = bool(self.trace_dir) and jax.process_index() == 0
        self._active = False
        self._done = False
        self._captured = 0

    def on_step(self, step: int) -> None:
        """Call before dispatching ``step``. `>=` (not `==`) so a run
        resumed past start_step still captures a window. Callers
        synchronize on each step's outputs (the trainer's ``float(loss)``)
        before the next ``on_step``, so captured steps are fully on-device
        by the time the window closes."""
        if not self.enabled or self._done:
            return
        if self._active:
            self._captured += 1
            if self._captured >= self.num_steps:
                self._stop()
        elif step >= self.start_step:
            jax.profiler.start_trace(self.trace_dir)
            self._active = True

    def arm(self, start_step: int, num_steps: Optional[int] = None) -> None:
        """Re-arm the one-shot window at runtime — the anomaly
        auto-capture path (telemetry.anomaly) points an already-spent
        window at the steps right after a detector trip. Resets the
        done latch; a window currently capturing is left alone (the
        open capture finishes first, exactly once)."""
        if self._active:
            return
        self.start_step = int(start_step)
        if num_steps is not None:
            self.num_steps = max(1, int(num_steps))
        self._done = False
        self._captured = 0

    def close(self) -> None:
        if self._active:
            self._stop()

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self._active = False
        self._done = True


#: Every span and mark the program emits on the profiler's clock:
#: name -> (layer as PERF.md section 3 names it, argument names). Spans
#: open through :class:`annotate` / :class:`step_annotation`, point events
#: through :func:`mark`; names starting ``serve_req_`` are marks. PERF.md
#: section 3 and docs/OBSERVABILITY.md mirror this table, and
#: tests/test_profiler_spans.py holds the engine and the trainer to it.
SPANS: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    # ---- serving: one engine step and its phases (serving/server.py)
    "serve": ("engine host loop", ("step_num", "host_ns")),
    "serve_schedule": ("scheduler", ()),
    "serve_admit": ("scheduler", ("queued",)),
    "serve_prefill_chunk": ("engine host loop",
                            ("rid", "slot", "start", "nvalid", "last",
                             "puts", "h2d_bytes", "context")),
    "serve_chunk_fetch": ("engine host loop", ("rid",)),
    "serve_first_token": ("engine host loop", ("rid",)),
    # ``state_slots`` / ``window_read_tokens``: the slots whose recurrent
    # state the step reads and writes, and the columns one window layer's
    # gather reads (0 for a model with neither)
    "serve_decode": ("KV pool", ("slots", "live_tokens", "read_tokens",
                                 "sampling_slots", "state_slots",
                                 "window_read_tokens")),
    # ``puts`` / ``h2d_bytes``: what the dispatch sent to the device
    "serve_decode_args": ("engine host loop", ("puts", "h2d_bytes")),
    "serve_decode_dispatch": ("engine host loop", ()),
    "serve_decode_fetch": ("engine host loop", ()),
    # mark after the fetch, models with routed experts only: what the
    # step's dropless routing did, summed over layers
    "serve_moe_route": ("experts", ("experts_hit", "expert_assignments",
                                    "slots")),
    "serve_emit": ("engine host loop", ("slots",)),
    "serve_post": ("engine host loop", ()),
    "serve_kv_export": ("KV pool", ("rid",)),
    "serve_kv_import": ("KV pool", ("rid",)),
    # ---- serving: the request lifecycle, one identifier (marks)
    "serve_req_submit": ("scheduler", ("rid", "prompt_len", "max_new")),
    "serve_req_admit": ("scheduler", ("rid", "slot", "cached_tokens")),
    "serve_req_first_token": ("scheduler", ("rid",)),
    "serve_req_finish": ("scheduler", ("rid", "status", "tokens")),
    "serve_req_preempt": ("scheduler", ("rid",)),
    # ---- training: the step span and one span per StepClock segment
    "train": ("trainer", ("step_num", "host_ns")),
    "train_dispatch": ("trainer", ()),
    "train_loss_fetch": ("trainer", ()),
    "train_guard_fetch": ("trainer", ()),
    "train_data_wait": ("data", ()),
    "train_h2d": ("trainer", ()),
    "train_metrics_fetch": ("trainer", ()),
    "train_logging": ("trainer", ()),
    "train_eval": ("trainer", ()),
    "train_checkpoint_stall": ("trainer", ()),
    # ---- start-up: once a process or once a compile, never inside a
    # steady-state step; each also leaves a record in ``startup_spans()``.
    # An argument whose value is known only when the span ends opens
    # with a placeholder ("" / -1) that ``startup_span.set`` overwrites
    # in the record (the profiler's event keeps the placeholder)
    "startup_model_build": ("start-up", ("layers", "kernel_imports")),
    "startup_kernel_import": ("start-up", ("module",)),
    "startup_weights": ("start-up", ("source",)),
    "startup_engine_build": ("start-up", ("slots", "pages")),
    "startup_pool_alloc": ("start-up", ("arrays",)),
    "startup_trainer_build": ("start-up", ()),
    "startup_state_init": ("start-up", ()),
    # the two halves of ``IntrospectedFunction._compile``: trace +
    # lowering (paid at every start, cache hit or not), then the backend
    # compile or its retrieval; ``cache_hit`` 1 / 0, -1 = the persistent
    # cache was not asked
    "xla_lower": ("start-up", ("fn", "n_compiles")),
    "xla_compile": ("start-up", ("fn", "n_compiles", "cache_hit")),
}


#: ``jax.named_scope`` frames the model puts around its mixers and steps,
#: read by the benchmark's scope readers from the compiled programs:
#: scope -> layer as PERF.md section 3 names it.
DEVICE_SCOPES: Dict[str, str] = {
    "embed": "model step", "optimizer": "trainer",
    "step_metrics": "trainer", "head_loss": "model step",
    "mla_attention": "model step", "moe_router": "experts",
    "moe_experts": "experts", "moe_shared": "experts",
    "ssm_mixer": "model step", "ssm_scan": "model step",
    "gmu": "model step",
    "swa_attention": "model step", "full_attention": "model step",
    "cross_attention": "model step",
}


class annotate:
    """Named host region on the profiler's clock (device ops inside still
    fuse), mirrored into the host tracer so it shows up both in the XLA
    profile and the Chrome-trace dump. ``args`` become the event's stats
    in the xplane: integers and short strings already in hand, nothing
    computed for the span's sake. Costs an atomic load and two Python
    calls when no trace is active."""
    __slots__ = ("_ann", "_span")

    def __init__(self, name: str, **args):
        self._ann = jax.profiler.TraceAnnotation(name, **args)
        self._span = get_tracer().span(name, cat="annotate", **args)

    def __enter__(self):
        self._ann.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


class step_annotation(annotate):
    """Per-step span. ``name`` distinguishes loops sharing a trace ("train"
    vs the serving engine's "serve"). ``host_ns`` is ``perf_counter_ns``
    at the span's start: the one stat that ties every ``perf_counter``
    reading (the host tracer's, ``StepClock``'s, a harness's token times)
    to the xplane's clock, by the nearest step's pair. The host tracer's
    mirror is the constant ``<name>_step`` (one Perfetto track row per
    loop) with the step number in args."""
    __slots__ = ()

    def __init__(self, step: int, name: str = "train", **args):
        self._ann = jax.profiler.StepTraceAnnotation(
            name, step_num=step, host_ns=time.perf_counter_ns(), **args)
        self._span = get_tracer().span(f"{name}_step", cat=name,
                                       step=int(step))


def mark(name: str, **args) -> None:
    """Point event on the profiler's clock: a zero-length annotation."""
    with jax.profiler.TraceAnnotation(name, **args):
        pass


# ------------------------------------------------------- start-up records

#: the most records the process keeps; later ones are counted, not kept
STARTUP_SPAN_CAP = 512
#: threads that exist to import a kernel's module beside the main thread
IMPORT_THREAD_SUFFIX = "-kernel-import"


class _StartupLog:
    """The process's start-up records, how many the cap turned away, and
    whether the one log line has been said."""
    records: List[Dict[str, Any]] = []
    dropped = 0
    reported = False


class startup_span(annotate):
    """An :class:`annotate` that ALWAYS leaves one record in the
    process-wide list :func:`startup_spans` returns: ``name``, ``thread``
    (its name), ``start_ns`` / ``end_ns`` from ``time.perf_counter_ns()``
    (the clock of every step span's ``host_ns``, so a start-up record
    and the device timeline of a traced window share one axis) and
    ``args``. The profiler sees set-up in no run (it is started after
    it), and the host tracer is off unless installed: the record is what
    says where a start went.

    For code that runs once a process or once a compile (a constructor,
    a lazy import, a lowering): NEVER inside a steady-state step. The
    list is capped at ``STARTUP_SPAN_CAP`` records (later ones only
    count as dropped), and ``tests/test_profiler_spans.py`` holds
    ``step()`` and ``step_on_batch`` to adding none. No lock: a record is
    built by its own thread and published by one ``list.append``."""
    __slots__ = ("_rec",)

    def __init__(self, name: str, **args):
        super().__init__(name, **args)
        self._rec = {"name": name,
                     "thread": threading.current_thread().name,
                     "start_ns": 0, "end_ns": 0, "args": args}

    def __enter__(self):
        super().__enter__()
        self._rec["start_ns"] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._rec["end_ns"] = time.perf_counter_ns()
        if len(_StartupLog.records) < STARTUP_SPAN_CAP:
            _StartupLog.records.append(self._rec)
        else:
            _StartupLog.dropped += 1
        return super().__exit__(*exc)

    def set(self, **args) -> None:
        """Overwrite arguments whose value the span's end decides (which
        imports a build started, whether a compile hit the cache): in the
        record only, and only names the span opened with."""
        unknown = set(args) - set(self._rec["args"])
        if unknown:
            raise KeyError(f"{self._rec['name']} opened without "
                           f"{sorted(unknown)}")
        self._rec["args"].update(args)

    @property
    def seconds(self) -> float:
        return (self._rec["end_ns"] - self._rec["start_ns"]) * 1e-9


def startup_spans() -> List[Dict[str, Any]]:
    """A copy of the records so far, in order of their ends."""
    return [dict(r, args=dict(r["args"]))
            for r in list(_StartupLog.records)]


def startup_spans_dropped() -> int:
    return _StartupLog.dropped


def reset_startup_spans() -> None:
    """Forget every record (tests: one pytest process builds hundreds of
    models and engines)."""
    del _StartupLog.records[:]
    _StartupLog.dropped = 0
    _StartupLog.reported = False


def self_seconds(records: List[Dict[str, Any]]) -> List[float]:
    """Each record's own seconds: its duration less what the records
    nested inside it ON ITS THREAD cover, in the order given."""
    own = [(r["end_ns"] - r["start_ns"]) * 1e-9 for r in records]
    by_thread: Dict[str, List[int]] = {}
    for i, r in enumerate(records):
        by_thread.setdefault(r["thread"], []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (records[i]["start_ns"],
                                -records[i]["end_ns"]))
        stack: List[int] = []
        for i in idx:
            while stack and records[stack[-1]]["end_ns"] <= \
                    records[i]["start_ns"]:
                stack.pop()
            if stack:
                own[stack[-1]] -= (records[i]["end_ns"]
                                   - records[i]["start_ns"]) * 1e-9
            stack.append(i)
    return [max(x, 0.0) for x in own]


def startup_summary() -> Dict[str, Any]:
    """Where this process's start went, from the records and the compile
    accounting: self seconds by span name on the threads that build and
    compile (``spans_s``), the kernel imports that ran beside them on a
    thread of their own (``background_import_s``: hidden unless the
    main thread then waited, which shows as its own
    ``startup_kernel_import``), the persistent cache's hits and misses,
    lowering and backend-compile seconds of every jitted function of the
    process, and the three costliest functions."""
    records = startup_spans()
    own = self_seconds(records)
    spans_s: Dict[str, float] = {}
    background = 0.0
    for rec, secs in zip(records, own):
        if rec["thread"].endswith(IMPORT_THREAD_SUFFIX):
            background += secs
        else:
            spans_s[rec["name"]] = spans_s.get(rec["name"], 0.0) + secs
    acct = xla_introspect.compile_accounting()
    by_fun: Dict[str, float] = {}
    for ev in xla_introspect.compile_events():
        if ev.fun_name:         # a cache hit or miss carries no name
            # JAX names a trace ``f`` and its lowering and compile ``jit(f)``
            name = ev.fun_name.removeprefix("jit(").removesuffix(")")
            by_fun[name] = by_fun.get(name, 0.0) + ev.seconds
    costliest = sorted(by_fun.items(), key=lambda kv: -kv[1])[:3]
    return {"spans_s": spans_s, "background_import_s": background,
            "cache_hits": acct["cache_hits"],
            "cache_misses": acct["cache_misses"],
            "lower_s": acct["lower_s"],
            "backend_compile_s": acct["backend_compile_s"],
            "costliest": costliest,
            "records_dropped": startup_spans_dropped()}


def startup_line(summary: Dict[str, Any]) -> str:
    """The one log line of :func:`startup_summary`."""
    spans = " ".join(f"{name} {secs:.2f}" for name, secs in sorted(
        summary["spans_s"].items(), key=lambda kv: -kv[1])
        if secs >= 0.005)
    funs = ", ".join(f"{name} {secs:.2f}"
                     for name, secs in summary["costliest"])
    return (f"start-up (self s): {spans}; background kernel imports "
            f"{summary['background_import_s']:.2f}; persistent cache "
            f"{summary['cache_hits']} hits / {summary['cache_misses']} "
            f"misses; all jitted functions: lowering "
            f"{summary['lower_s']:.2f}, backend compile "
            f"{summary['backend_compile_s']:.2f} (most: {funs})")


def report_startup(registry, log=print) -> Dict[str, Any]:
    """What an engine does after its first step that handed out a token
    and a trainer after its first completed step: set
    ``telemetry/xla/startup/<span name>_s`` (self seconds) and the
    process-wide compile gauges on ``registry``, and log the one line,
    the first time in this process (a fleet's members, a rebuilt engine
    and a trainer beside them share one start). Returns the summary."""
    summary = startup_summary()
    for name, secs in summary["spans_s"].items():
        xla_introspect.set_gauge(
            registry, f"telemetry/xla/startup/{name}_s", secs)
    xla_introspect.set_gauge(
        registry, "telemetry/xla/startup/background_import_s",
        summary["background_import_s"])
    xla_introspect.publish_compile_accounting(registry)
    if not _StartupLog.reported:
        _StartupLog.reported = True
        log("[dla_tpu] " + startup_line(summary))
    return summary
