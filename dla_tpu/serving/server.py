"""Continuous-batching serving engine: the host loop that drives jitted
prefill/decode steps over the block-paged KV pool.

Execution model — the three invariants everything else hangs off:

1. **Static decode shapes.** The decode step always runs the full
   ``num_slots``-row batch over the full per-slot page window. Requests
   entering and leaving only change the *data* (block tables, validity,
   the active mask) — never a shape — so XLA compiles the decode step
   exactly once per engine lifetime (asserted by test).
2. **Fixed-shape chunked prefill.** Every prompt prefills in chunks of
   ``prefill_chunk`` tokens, one request and one chunk per engine step
   beside the decode batch, so prefill compiles once per engine
   lifetime too, whatever the prompt lengths.
3. **Host-mirrored metadata.** Slot metadata (block tables, valid,
   lengths, last tokens) is authoritative on the host as numpy; each
   dispatch receives it packed into ONE int32 array, put once
   (serving/step_args.py; the window mask and the positions are derived
   in the program), and the host re-applies the deterministic updates
   itself instead of fetching arrays back. Only sampled tokens and
   prefill logits cross device->host per step.

Backpressure: admission needs every prompt page plus a decode reserve up
front; mid-decode page exhaustion preempts the youngest request (freed
pages go to older ones; the victim recomputes its prefix on
re-admission). The same engine is the intended async rollout backend for
PPO (docs/SERVING.md): rollouts are just requests whose consumer is the
trainer.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.generation.speculative import accept_prefix_len
from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.sampling import (SamplingParams, derive_request_seed,
                                  sample_token_block,
                                  sample_token_per_row)
from dla_tpu.resilience.faults import FaultPlan
from dla_tpu.serving.kv_blocks import (
    PagedKVCache,
    PageGeometry,
    PrefixCache,
)
from dla_tpu.serving.metrics import ServingMetrics
from dla_tpu.serving.migration import MigrationError, MigrationTicket
from dla_tpu.serving.resilience import (
    AdmissionController,
    DegradationLadder,
    DeviceStepError,
    NaNLogitsError,
    ShedConfig,
)
from dla_tpu.serving.scheduler import (
    TERMINAL_STATES,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
)
from dla_tpu.serving.step_args import PackedArgs
from dla_tpu.serving.tenancy import (
    AdapterStore,
    TenancyConfig,
    TenantPolicy,
)
from dla_tpu.telemetry.anomaly import AnomalyConfig, AnomalyMonitor
from dla_tpu.telemetry.exporter import MetricsHTTPServer, ReadinessProbe
from dla_tpu.telemetry.flight_recorder import FlightRecorder
from dla_tpu.telemetry.mfu import MFUCalculator
from dla_tpu.telemetry.slo import SLOWatch
from dla_tpu.telemetry.trace import (
    Tracer,
    get_tracer,
    install_tracer,
    register_trace_gauges,
)
from dla_tpu.telemetry.xla_introspect import (
    IntrospectedFunction,
    register_live_bytes_gauge,
)
from dla_tpu.utils.profiling import (
    ProfileWindow, annotate, mark, report_startup, startup_span,
    step_annotation)

#: ``_sample_host``'s sampler: one program over one logits row, shared by
#: every engine of the process (called eagerly, the sampler's ``lax.cond``
#: would compile anew at every call)
_sample_rows_jit = jax.jit(sample_token_per_row)

#: what ``step()`` raises, and the KV handoff refuses with, once a failed
#: dispatch has consumed the (donated) pools: see ``PagedKVCache.pools``
_POOL_CONSUMED = ("pool consumed by a failed dispatch: this engine's KV "
                  "is gone, rebuild it and replay")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Geometry + policy of one serving engine instance."""
    page_size: int = 16
    num_pages: int = 64          # pool size (page 0 reserved for trash)
    num_slots: int = 4           # static decode batch rows
    max_model_len: int = 128     # per-slot logical window (prompt + new)
    decode_reserve_pages: int = 1
    seed: int = 0
    # tokens per fixed-shape prefill chunk: a multiple of page_size, at
    # most max_model_len. Unset, the engine takes
    # min(max_model_len, 16 * page_size)
    prefill_chunk: Optional[int] = None
    # co-scheduling cap: a prefill chunk is deferred while the running
    # decode batch plus the chunk would exceed this many tokens per
    # engine step (0 = no cap; a chunk always runs when nothing decodes,
    # so the budget can't livelock prefill)
    prefill_token_budget: int = 0
    # share full pages of identical token prefixes across requests via
    # block-table aliasing (cache hits are chunk-granular, so the fixed
    # chunk schedule stays compile-stable)
    prefix_cache: bool = False
    # LRU cap on stored exact-full-prompt logits entries (each pins its
    # partial tail page in the cache)
    cached_logits_capacity: int = 128
    # same {trace_dir, start_step, num_steps} dict the trainer's
    # logging.profile takes: an xplane trace of a serving run is one
    # config flag away (windows count ENGINE steps, not tokens)
    profile: Optional[Dict] = None
    # Prometheus scrape endpoint (telemetry.exporter); 0 = ephemeral
    metrics_port: Optional[int] = None
    # host tracing (telemetry.trace): same {enabled, capacity, path}
    # block as the trainer's logging.telemetry.trace. When enabled the
    # engine emits one async span tree per request (enqueue -> admitted
    # -> first token -> per-decode instants -> finish), timestamped with
    # the engine's own clock so trace durations equal recorded TTFT/ITL.
    trace: Optional[Dict] = None
    # SLO watch (telemetry.slo): {objectives: [...], check_every: N}
    # evaluated against the metrics snapshot every N engine steps
    slo: Optional[Dict] = None
    # /healthz flips to 503 when no engine step completed for this long
    readiness_timeout_s: float = 600.0
    # admission control / load shedding / degradation ladder: the
    # serving.resilience ShedConfig fields as a dict (None or
    # {enabled: false} = no gate, PR-1 behavior)
    shed: Optional[Dict] = None
    # serving-scoped fault injection: an explicit plan spec string
    # ("engine_step=3:wedge;engine_step=6:nan_logits"); None falls back
    # to $DLA_FAULT_PLAN — only engine_step= entries fire here
    fault_plan: Optional[str] = None
    # flight-recorder postmortem directory (None = in-memory ring only)
    postmortem_dir: Optional[str] = None
    # XLA introspection (telemetry.xla_introspect): the three jitted
    # entry points dispatch through IntrospectedFunction for retrace
    # attribution + per-fn cost/memory/roofline gauges.
    # {enabled: bool (default true), max_entries: int}
    xla_introspect: Optional[Dict] = None
    # anomaly auto-triage (telemetry.anomaly.AnomalyConfig fields as a
    # dict) over inter-token latency and unattributed recompiles; the
    # capture dumps land in postmortem_dir. None = off.
    anomaly: Optional[Dict] = None
    # blockwise speculative decoding over the paged pool:
    # {enabled: bool (default true when the block is present),
    #  k: int draft tokens per round (default 4),
    #  draft: "int8" (weight-only int8 self-draft via quantize_weights)
    #         | "self" (full-precision self-draft — a correctness/bench
    #           reference with ~100% acceptance)}.
    # Greedy AND per-request-seeded sampled outputs stay bit-identical
    # to the non-speculative engine: the verify step samples the target
    # tokens itself at the request's fold_in(seed, k) stream positions
    # and accepts a draft token only when it EQUALS the target's sample.
    speculative: Optional[Dict] = None
    # multi-tenant LoRA serving (serving.tenancy TenancyConfig fields as
    # a dict): a device-resident pool of per-tenant adapters gathered
    # per-slot inside the ONE compiled decode step, plus per-tenant
    # quotas/SLOs/metrics (tenant KV is namespaced in the prefix cache
    # at chunk granularity).
    # None or {enabled: false} = single-tenant, PR-1 behavior.
    tenancy: Optional[Dict] = None
    # disaggregation role of this engine within a fleet:
    #   "mixed"   — prefill + decode co-scheduled (the default; a
    #               standalone engine is always mixed)
    #   "prefill" — runs chunked prefill only; the fleet ships each
    #               finished prefix to a decode engine as a
    #               MigrationTicket
    #   "decode"  — admission is handoff-only: submit() refuses, work
    #               arrives via import_request / restore
    role: str = "mixed"

    @property
    def pages_per_slot(self) -> int:
        return -(-self.max_model_len // self.page_size)


class ServingEngine:
    """Continuous-batching engine over one model + params.

    >>> eng = ServingEngine(model, params, GenerationConfig(...), cfg)
    >>> rid = eng.submit([1, 2, 3], max_new_tokens=16)
    >>> while eng.has_work():
    ...     for rid, tok in eng.step():
    ...         ...                      # stream tokens out per request
    >>> eng.result(rid).generated
    """

    def __init__(self, model: Transformer, params, gen: GenerationConfig,
                 cfg: ServingConfig,
                 now: Callable[[], float] = time.perf_counter):
        with startup_span("startup_engine_build", slots=int(cfg.num_slots),
                          pages=int(cfg.num_pages)):
            self._build(model, params, gen, cfg, now)

    def _build(self, model: Transformer, params, gen: GenerationConfig,
               cfg: ServingConfig, now: Callable[[], float]) -> None:
        if cfg.page_size < 1 or cfg.max_model_len % cfg.page_size:
            raise ValueError(
                f"max_model_len ({cfg.max_model_len}) must be a positive "
                f"multiple of page_size ({cfg.page_size})")
        if cfg.prefill_chunk is None:
            cfg = dataclasses.replace(cfg, prefill_chunk=min(
                cfg.max_model_len, 16 * cfg.page_size))
        if cfg.prefill_chunk <= 0:
            raise ValueError(
                f"prefill_chunk ({cfg.prefill_chunk}) must be positive: "
                "the monolithic bucketed prefill it used to select is "
                "gone, the engine prefills by chunks only")
        if cfg.prefill_chunk % cfg.page_size:
            raise ValueError(
                f"prefill_chunk ({cfg.prefill_chunk}) must be a "
                f"multiple of page_size ({cfg.page_size}): chunk "
                "boundaries must land on page boundaries so cached "
                "prefixes alias whole pages")
        if cfg.prefill_chunk > cfg.max_model_len:
            raise ValueError(
                f"prefill_chunk ({cfg.prefill_chunk}) exceeds "
                f"max_model_len ({cfg.max_model_len})")
        if cfg.role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'mixed', got "
                f"{cfg.role!r}")
        ten_cfg = TenancyConfig.from_config(cfg.tenancy)
        spec = dict(cfg.speculative or {})
        if spec and not spec.get("enabled", True):
            spec = {}
        if spec:
            unknown = set(spec) - {"enabled", "k", "draft"}
            if unknown:
                raise ValueError(
                    f"unknown speculative config keys: {sorted(unknown)}")
        self._spec_k = int(spec.get("k", 4)) if spec else 0
        self._spec_draft_kind = str(spec.get("draft", "int8"))
        if spec:
            if self._spec_k < 1:
                raise ValueError(
                    f"speculative.k must be >= 1, got {self._spec_k}")
            if self._spec_draft_kind not in ("int8", "self"):
                raise ValueError(
                    "speculative.draft must be 'int8' or 'self', got "
                    f"{self._spec_draft_kind!r}")
        # a model that keeps recurrent state a slot (its cache spec has
        # ``state`` arrays): what copies, shares or rolls back cached
        # rows would have to snapshot that state too, and nothing does
        # yet, so each such feature is refused here, by name
        self._stateful = any(a.kind == "state" for a in model.cache_spec())
        # whether the one-token step's program holds the paged attention
        # kernel; asked of the model at the first decode span
        self._kernel_decode: Optional[bool] = None
        self._kernel_scan: Optional[bool] = None
        # learned sparse attention: the columns a query reads are at most
        # index_topk of those its indexer scores (0: no indexer)
        self._dsa_topk = int(model.cfg.index_topk)
        self.dsa_last_selection = None
        if self._stateful:
            for on, what in (
                    (cfg.prefix_cache, "ServingConfig.prefix_cache (a "
                     "cached prefix is pages alone: the state at its end "
                     "is not kept)"),
                    (bool(spec), "speculative decoding (a rejected draft "
                     "token has already moved the state)"),
                    (cfg.role != "mixed", f"role={cfg.role!r} (KV export "
                     "/ import carries pages, not state)"),
                    (ten_cfg is not None, "tenancy (per-slot adapters)")):
                if on:
                    raise ValueError(
                        "this model keeps recurrent state a slot, and "
                        f"state snapshots are not built: no {what}")
        self.model = model
        self.params = params
        self.gen = gen
        self.cfg = cfg
        self.now = now
        geom = PageGeometry.for_model(
            model, page_size=cfg.page_size, num_pages=cfg.num_pages,
            num_slots=cfg.num_slots, pages_per_slot=cfg.pages_per_slot,
            prefill_chunk=cfg.prefill_chunk)
        self.cache = PagedKVCache(model, geom)
        self.prefix_cache: Optional[PrefixCache] = None
        if cfg.prefix_cache:
            self.prefix_cache = PrefixCache(
                self.cache.allocator, cfg.page_size,
                logits_capacity=cfg.cached_logits_capacity)
        self.scheduler = Scheduler(
            self.cache,
            SchedulerConfig(decode_reserve_pages=cfg.decode_reserve_pages,
                            prefill_chunk=cfg.prefill_chunk,
                            prefill_token_budget=cfg.prefill_token_budget),
            prefix_cache=self.prefix_cache)
        self.metrics = ServingMetrics()
        self.metrics.kv_bytes_per_token.set(self.cache.bytes_per_token)
        self.metrics.kv_paged_layers.set(self.cache.layers_of("paged"))
        self._state_layers = self.cache.layers_of("state")
        # (block columns, layers) of the chunk program's attention block
        # walk, (0, 0) where it gathers: the host's half of the counters
        self._attn_walk = (model.hybrid.chunk_attention_walk(
            geom.page_size, geom.pages_per_slot) if model.hybrid else (0, 0))
        self.metrics.window_bytes_per_token.set(
            self.cache.window_bytes_per_token)
        self.metrics.state_bytes_per_slot.set(
            self.cache.state_bytes_per_slot)
        self.metrics.kv_shared_readers.set(
            model.hybrid.shared_readers if model.hybrid else 1)
        self._window_released_mirrored = 0
        self._pc_mirrored = {"lookups": 0, "hit_tokens": 0,
                             "evictions": 0}
        # speculative round accounting lives in plain engine ints and is
        # delta-mirrored into the registry each step (same idiom as the
        # prefix-cache counters): a harness swapping in a fresh
        # ServingMetrics sees only post-swap activity, and the
        # Supervisor re-seeds cumulative totals across rebuilds
        self._spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0,
                            "rollbacks": 0}
        self._spec_mirrored = dict(self._spec_stats)
        # KV migration accounting: same delta-mirror idiom. Export
        # failures count on the source engine; imports, page counts and
        # host-bounce bytes on the target.
        self._mig_stats = {"migrations": 0, "migrated_pages": 0,
                           "host_bounce_bytes": 0, "failed_migrations": 0}
        self._mig_mirrored = dict(self._mig_stats)
        # the draft tree: int8 weight-only self-draft (quantize_weights
        # adds _wscale leaves, so this is a DIFFERENT treedef from the
        # target and rides the spec fns as its own jit argument) or the
        # target tree itself ("self")
        self.draft_params = (self._derive_draft(params)
                             if self._spec_k else None)
        self._results: Dict[int, Request] = {}
        # per-slot sampling state shipped into the jitted decode each
        # step ([num_slots] host mirrors, like the cache metadata): every
        # request carries its own traced (temperature, top_p, top_k,
        # seed), and gen_pos is the generated-token index keying the
        # per-request PRNG stream — fold_in(PRNGKey(seed), gen_pos).
        # There is NO sequential engine rng: sampling is a pure function
        # of (request seed, token index), so sampled requests replay
        # bit-identically after eviction or a supervisor restart.
        ns = cfg.num_slots
        self.samp_temp = np.zeros((ns,), np.float32)
        self.samp_top_p = np.ones((ns,), np.float32)
        self.samp_top_k = np.zeros((ns,), np.int32)
        self.samp_seed = np.zeros((ns,), np.uint32)
        self.gen_pos = np.zeros((ns,), np.int32)
        # per-slot adapter pool row ([num_slots] host mirror like the
        # sampling state): row 0 is the all-zeros base identity, so free
        # slots and base-model requests gather an exact +0.0 delta
        self.adapter_idx = np.zeros((ns,), np.int32)
        # what a dispatch sends: ONE int32 array (serving/step_args.py).
        # A decode step's is [num_slots, pages/slot + 8 or 9], a chunk's
        # [pages/slot + chunk + 2 or 3]; the adapter pool row rides only
        # with tenancy on. The window mask and positions are not in it:
        # the programs derive them from ``lengths`` / ``start``.
        # A model with a window pool sends a second block-table row a
        # slot (its ring of window pages), and one with per-slot state
        # the chunk's slot: still one array, one put.
        tenancy = ((("adapter", 1, np.int32),) if ten_cfg is not None
                   else ())
        window = ((("window_tables", geom.window_ring, np.int32),)
                  if geom.window_ring else ())
        self._decode_layout = PackedArgs(
            ("block_tables", geom.pages_per_slot, np.int32), *window,
            ("lengths", 1, np.int32), ("tokens", 1, np.int32),
            ("active", 1, np.bool_), ("top_k", 1, np.int32),
            ("seed", 1, np.uint32), ("gen_pos", 1, np.int32),
            ("temp", 1, np.float32), ("top_p", 1, np.float32), *tenancy)
        self._chunk_layout = PackedArgs(
            ("block_tables", geom.pages_per_slot, np.int32), *window,
            ("ids", cfg.prefill_chunk, np.int32),
            ("start", 1, np.int32), ("nvalid", 1, np.int32), *tenancy,
            *((("slot", 1, np.int32),) if self._stateful else ()))
        self._draining = False
        self._old_handlers: Optional[dict] = None
        # engine-step counter drives the profiling window (the serving
        # analog of the trainer's step number)
        self.engine_steps = 0
        self._startup_reported = False
        self.profile = ProfileWindow(cfg.profile)
        # host tracer: an engine-local one from cfg.trace (built on the
        # engine's OWN clock so request timestamps pass straight in and
        # trace durations equal recorded TTFT/ITL), installed process-
        # wide so annotate/step_annotation land on the same timeline;
        # otherwise whatever tracer is already installed (a co-located
        # trainer's) — or the disabled default, costing nothing.
        trace_cfg = dict(cfg.trace or {})
        self._installed_tracer = False
        if trace_cfg.get("enabled"):
            self.tracer = Tracer(
                enabled=True,
                capacity=int(trace_cfg.get("capacity", 65536)),
                now=now, path=trace_cfg.get("path"))
            install_tracer(self.tracer)
            self._installed_tracer = True
        else:
            self.tracer = get_tracer()
        # ring/spool accounting for THIS engine's tracer, mirrored into
        # the engine registry (the trainer tracer's contract — drops
        # are a /metrics number, not a silent eviction)
        register_trace_gauges(self.metrics.registry, self.tracer)
        # resilience surface: flight recorder for postmortems, the
        # admission gate + degradation ladder (both off unless cfg.shed
        # enables them), and the serving-scoped fault plan
        self.recorder = FlightRecorder(capacity=256,
                                       out_dir=cfg.postmortem_dir)
        shed_cfg = ShedConfig.from_config(cfg.shed)
        self.admission = (AdmissionController(shed_cfg)
                          if shed_cfg is not None else None)
        self.ladder = (DegradationLadder(shed_cfg, recorder=self.recorder)
                       if shed_cfg is not None else None)
        self._applied_level = 0
        self.faults = (FaultPlan.parse(cfg.fault_plan)
                       if cfg.fault_plan is not None
                       else FaultPlan.from_env())
        # armed by _poll_faults, consumed by the next decode dispatch
        self._fault_device_error = False
        self._fault_nan_logits = False
        # SLO watch over the serving snapshot (TTFT p95 etc.), checked
        # every `check_every` engine steps; /healthz readiness heartbeat
        self.slo = SLOWatch.from_config(cfg.slo,
                                        registry=self.metrics.registry,
                                        recorder=self.recorder)
        self._slo_every = max(1, int((cfg.slo or {}).get("check_every",
                                                         100)))
        # multi-tenant plane: the adapter pool the jitted steps gather
        # from, the per-tenant quota/SLO/metrics policy, and the
        # delta-mirror marks for the pool counters. The scheduler's
        # release hook pairs with _bind_adapter's acquire so adapter
        # refcounts track slot residency exactly (finish, evict, cancel
        # — every release path funnels through _release_resources).
        self.adapter_store: Optional[AdapterStore] = None
        self.tenants: Optional[TenantPolicy] = None
        self._bound_tenants: Dict[int, str] = {}    # rid -> acquired
        self._adapter_mirrored = {"publishes": 0, "loads": 0, "spills": 0}
        if ten_cfg is not None:
            self.adapter_store = AdapterStore(model, ten_cfg.adapter_pool)
            self.tenants = TenantPolicy(
                ten_cfg, registry=self.metrics.registry,
                recorder=self.recorder, now=now)
            self.scheduler.release_hook = self._release_adapter
        self.readiness = ReadinessProbe(
            threshold_s=float(cfg.readiness_timeout_s))
        self.metrics_server: Optional[MetricsHTTPServer] = None
        if cfg.metrics_port is not None:
            self.start_metrics_server(cfg.metrics_port)
        # trace-time counters: the function bodies run once per XLA
        # compile, so these ARE the compile counts the no-recompilation
        # test asserts on
        self.decode_compiles = 0
        self.prefill_chunk_compiles = 0
        self.spec_draft_compiles = 0
        self.spec_verify_compiles = 0
        self.export_compiles = 0
        self.import_compiles = 0
        # every program that returns the pools consumes the ones it was
        # given (donate_argnums names ``pools`` in the bound method's
        # signature), so XLA updates them in place and no pool-sized copy
        # leaves a step; each call site rebinds ``cache.pools`` in the same
        # statement. The export gather only reads: it does not donate.
        self._decode = jax.jit(self._decode_fn, donate_argnums=1)
        self._prefill_chunk = jax.jit(self._prefill_chunk_fn,
                                      donate_argnums=1)
        self._spec_draft = (jax.jit(self._spec_draft_fn, donate_argnums=1)
                            if self._spec_k else None)
        self._spec_verify = (jax.jit(self._spec_verify_fn, donate_argnums=1)
                             if self._spec_k else None)
        self._export_kv = jax.jit(self._export_kv_fn)
        self._import_kv = jax.jit(self._import_kv_fn, donate_argnums=0)
        # anomaly auto-triage over inter-token latency + unattributed
        # recompiles; captures land next to the other postmortems
        anomaly_cfg = AnomalyConfig.from_config(cfg.anomaly)
        self.anomaly = None
        if anomaly_cfg is not None:
            self.anomaly = AnomalyMonitor(
                anomaly_cfg, recorder=self.recorder, tracer=self.tracer,
                registry=self.metrics.registry, out_dir=cfg.postmortem_dir)
        # XLA introspection: the wrappers OWN dispatch via the AOT path,
        # so the trace-time counters above still tick exactly once per
        # compile (the serving compile-once pins are unchanged). Rooflines
        # use the 2N inference cost model. First compiles never reach
        # on_compile, so every event it forwards is a true recompile.
        xi_cfg = dict(cfg.xla_introspect or {})
        self.xla_introspect_enabled = bool(xi_cfg.get("enabled", True))
        if self.xla_introspect_enabled:
            n_params = sum(int(np.prod(x.shape))
                           for x in jax.tree_util.tree_leaves(params))
            dev = jax.devices()[0]
            self.mfu_calc = MFUCalculator(
                n_params, device_kind=getattr(dev, "device_kind", "cpu"),
                platform=dev.platform, training=False)
            register_live_bytes_gauge(self.metrics.registry)
            max_entries = int(xi_cfg.get("max_entries", 16))
            named = [("decode", self._decode),
                     ("prefill_chunk", self._prefill_chunk)]
            if self._spec_k:
                named += [("spec_draft", self._spec_draft),
                          ("spec_verify", self._spec_verify)]
            named += [("kv_export", self._export_kv),
                      ("kv_import", self._import_kv)]
            wrapped = [
                IntrospectedFunction(
                    name, fn, registry=self.metrics.registry,
                    recorder=self.recorder, mfu_calc=self.mfu_calc,
                    on_compile=self._on_recompile,
                    max_entries=max_entries)
                for name, fn in named]
            self._decode, self._prefill_chunk = wrapped[:2]
            if self._spec_k:
                self._spec_draft, self._spec_verify = wrapped[2:4]
            self._export_kv, self._import_kv = wrapped[-2:]
        else:
            self.mfu_calc = None

    def _derive_draft(self, params):
        """Build the draft tree from the (current) target tree. ``int8``
        re-quantizes (cheap relative to a refit's weight transfer);
        ``self`` aliases the target — zero extra memory, ~100%
        acceptance, the bench/correctness reference arm."""
        if self._spec_draft_kind == "self":
            return params
        return self.model.quantize_weights(params)

    def _on_recompile(self, event: Dict) -> None:
        """Recompile-event feed from the introspection wrappers: an
        UNattributed one (nothing in the fingerprint changed, yet XLA
        compiled) is an anomaly trigger after warmup."""
        if self.anomaly is not None:
            self.anomaly.note_recompile(
                int(event.get("step") or self.engine_steps), event["fn"],
                attributed=bool(event.get("attributed")))

    def _put_step_args(self, packed: np.ndarray) -> jnp.ndarray:
        """The one host-to-device put of a dispatch: ``packed`` is the
        step's host state as ``PackedArgs.pack`` laid it out.

        jnp.asarray on suitably-aligned host numpy memory may alias it
        zero-copy, and the engine mutates its mirrors in place right
        after a dispatch (mark_computed flips `valid` bits, advance_slot
        moves `lengths`) while the async computation may not have
        executed yet — an aliased mirror makes the jitted step read torn
        state. ``pack`` fills a NEW array at every dispatch and nothing
        writes it afterwards, which pins the dispatched values without a
        second copy."""
        self.metrics.step_arg_puts.inc()
        self.metrics.step_arg_bytes.inc(packed.nbytes)
        return jnp.asarray(packed)

    # -------------------------------------------------------- jitted steps

    def _prefill_chunk_fn(self, params, pools, packed, adapters=None):
        """One FIXED-SHAPE prefill chunk for a single slot: the model
        gathers the slot's pages (the already-computed prefix — cached
        hit pages and earlier chunks), runs the chunk forward and writes
        its C fresh rows into the pool at the (page, offset) computed
        here. ``packed`` is the dispatch's one host array
        (``_chunk_layout``): the slot's block-table row, the chunk's
        ``ids`` [C], and ``start`` / ``nvalid`` (chunk's absolute start
        column / real-token count), all traced, so every chunk of every
        request reuses ONE compile. The columns the chunk may attend are
        exactly those before it — ``PagedKVCache`` keeps a prefilling
        slot's ``valid`` a prefix of length ``start`` — so the mask and
        the positions are computed here, not sent. ``adapters`` are the
        stacked pools alone (device arrays already); the slot's pool row
        rides ``packed``. Returns (pools, logits [1, V]) — logits are the
        next-token distribution after the chunk's last real token,
        meaningful only on a request's final chunk (the only one whose
        logits the host fetches)."""
        self.prefill_chunk_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the serving compile-once tests
        ps = self.cache.geom.page_size
        c = self.cfg.prefill_chunk
        f = self._chunk_layout.unpack(packed)
        btab, start, nvalid = f["block_tables"][None], f["start"], f["nvalid"]
        if adapters is not None:
            adapters = {"idx": f["adapter"][None], **adapters}
        window = jnp.arange(self.cache.geom.slot_window, dtype=jnp.int32)
        real = jnp.arange(c) < nvalid
        # the chunk's columns land at their physical (page, offset); pad
        # columns (index >= nvalid) route to the trash page
        cols = start + jnp.arange(c, dtype=jnp.int32)
        view = {"pools": pools, "block_tables": btab,
                "valid": (window < start)[None], "pos": window[None],
                "real": real[None, :],
                "write_pages": jnp.where(real, btab[0, cols // ps], 0)[None],
                "write_offs": jnp.where(real, cols % ps, 0)[None]}
        if "window_tables" in f:
            view["window_tables"] = f["window_tables"][None]
        if "slot" in f:
            # the slot's recurrent state; a request's first chunk starts
            # from zeros whatever the slot's last request left there
            view["state_rows"] = f["slot"][None]
            view["fresh"] = (start == 0)[None]
        # absolute chunk schedule: positions are fixed by `start`, so a
        # cache hit changes WHICH chunks run, never the math inside one
        positions = cols[None, :]
        last_index = jnp.maximum(nvalid - 1, 0)[None]
        logits, pools, _ = self.model.prefill_step_paged(
            params, view, f["ids"][None], positions, last_index,
            adapters=adapters)
        return pools, logits

    def _export_kv_fn(self, pools, page_ids):
        """Gather one request's ordered pages out of the pool into a
        migration payload. ``page_ids`` [pages_per_slot] physical page
        ids with pad entries routed to trash page 0 — the shape is fixed
        by engine geometry, so every export of every request reuses ONE
        compile. Returns the payloads, one [L, pages_per_slot, page_size,
        heads, width] array per pool; they stay on device (the migrator
        decides whether they ever touch the host).
        """
        self.export_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the migration compile-once tests
        return tuple(p[:, page_ids] for p in pools)

    def _import_kv_fn(self, pools, payloads, page_ids):
        """Scatter a migration payload onto freshly allocated pages in
        ONE fixed-shape call — the install half of the KV handoff.
        ``page_ids`` [pages_per_slot] with pad entries routed to trash
        page 0 (pad payload rows carry the source's trash contents, so
        the duplicate page-0 writes are garbage-onto-garbage by the
        trash-page convention). Same one-compile-per-engine contract as
        the export gather."""
        self.import_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the migration compile-once tests
        return tuple(p.at[:, page_ids].set(x)
                     for p, x in zip(pools, payloads))

    def _unpack_decode(self, packed, adapters):
        """What the three decode programs do first: the fields of the
        dispatch's one host array (``_decode_layout``), the window mask
        and positions the host does not send, and the adapters argument
        with its per-slot rows. ``PagedKVCache`` keeps a running slot's
        ``valid`` mirror the prefix of length ``lengths`` and every
        column's position its index, so ``valid`` and ``pos`` are a
        compare on an iota here; a slot that is not running (free, or
        mid-prefill with ``lengths`` 0) attends nothing and its row is
        masked by ``active`` as before."""
        f = self._decode_layout.unpack(packed)
        window = jnp.arange(self.cache.geom.slot_window,
                            dtype=jnp.int32)[None, :]
        f["valid"] = window < f["lengths"][:, None]
        f["pos"] = jnp.broadcast_to(window, f["valid"].shape)
        if adapters is not None:
            adapters = {"idx": f["adapter"], **adapters}
        return f, adapters

    def _decode_fn(self, params, pools, packed, adapters=None):
        """One static-shape decode step over every slot: the model's
        paged step reads each slot's cached columns through its block
        table (the live pages by the paged kernel, or the whole [S]
        window gathered: ``Transformer._paged_layers``) and
        writes the fresh row at the (page, offset) computed here; the
        result is sampled PER-ROW (each slot's traced temperature/top_p/
        top_k/seed, keyed by the slot's generated-token index). Free
        slots compute garbage routed to the trash page. ``packed`` is the
        step's host state in one array (``_unpack_decode``). Returns the
        fresh pools plus a packed [4, B] int32
        array — row 0 the sampled tokens, row 1 their chosen-token
        logprobs bitcast to int32, rows 2 and 3 the step's expert
        counters broadcast (held experts that received a token, and
        (token, choice) pairs that landed here, summed over layers; 0
        without experts) — so the host still performs exactly
        ONE D2H fetch per decode step (the execution-model invariant).
        The pack is integer because a small token id viewed as f32 is a
        subnormal, and the TPU flushes those to zero."""
        self.decode_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the serving compile-once tests
        geom = self.cache.geom
        ps = geom.page_size
        b = geom.num_slots
        f, adapters = self._unpack_decode(packed, adapters)
        block_tables, lengths, active = (
            f["block_tables"], f["lengths"], f["active"])
        # this step's row: physical (page, offset) of each slot's write
        # column; inactive slots write the trash page
        page_ids = jnp.take_along_axis(
            block_tables, (lengths // ps)[:, None], axis=1)[:, 0]
        view = {"pools": pools, "block_tables": block_tables,
                "valid": f["valid"], "pos": f["pos"], "lengths": lengths,
                "real": active[:, None],
                "write_pages": jnp.where(active, page_ids, 0)[:, None],
                "write_offs": jnp.where(active, lengths % ps, 0)[:, None]}
        if "window_tables" in f:
            view["window_tables"] = f["window_tables"]
        # sparse attention also hands back the columns each slot's query
        # read, [L, B, 1, k]: kept on the device, unfetched
        logits, pools, routed, *selected = self.model.decode_step_paged(
            params, view, f["tokens"], adapters=adapters)
        # a free slot keeps its last request's temperature: zeroed, so
        # only running rows decide whether the step filters and draws
        new_tok, logp = sample_token_per_row(
            f["seed"], f["gen_pos"], logits,
            jnp.where(active, f["temp"], 0.0), f["top_p"], f["top_k"])
        new_tok = jnp.where(active, new_tok, 0)
        logp = jnp.where(active, logp, 0.0)
        packed = jnp.stack(
            [new_tok, jax.lax.bitcast_convert_type(logp, jnp.int32),
             jnp.broadcast_to(routed[0], (b,)),
             jnp.broadcast_to(routed[1], (b,))])
        return (pools, packed, *selected)

    def _spec_draft_fn(self, draft_params, pools, packed, adapters=None):
        """The speculative DRAFT phase: K sequential fixed-shape decode
        steps with the draft tree over the shared paged pool. Step i
        feeds the previous proposal (the pending token at i=0), writes
        its KV column at ``lengths + i``, marks it valid in the TRACED
        mask only (a column's position is its index, so ``pos`` needs no
        update; the host mirrors are authoritative and never see draft
        columns — that asymmetry is the free rollback), and
        samples proposal d_{i+1} on the request's own seeded stream at
        generated-token index ``gen_pos + i`` — so a perfect draft
        proposes exactly the tokens the target will sample, and the
        token-matching verify accepts the whole block. Columns beyond
        the slot window or the allocated pages route to the trash page.
        Returns (pools, proposals [B, K]); the proposals stay
        on device and flow straight into the verify dispatch — no D2H.
        """
        self.spec_draft_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the speculative compile-once tests
        geom = self.cache.geom
        ps = geom.page_size
        sw = geom.slot_window
        f, adapters = self._unpack_decode(packed, adapters)
        block_tables, lengths, active = (
            f["block_tables"], f["lengths"], f["active"])
        col_ids = jnp.arange(sw, dtype=jnp.int32)[None, :]
        temps = jnp.where(active, f["temp"], 0.0)   # as in _decode_fn

        def draft_step(carry, i):
            cur, valid_c, pools_c = carry
            col = lens_i = lengths + i
            in_win = (col < sw) & active
            page_ids = jnp.take_along_axis(
                block_tables,
                jnp.minimum(col // ps, geom.pages_per_slot - 1)[:, None],
                axis=1)[:, 0]
            view = {"pools": pools_c, "block_tables": block_tables,
                    "valid": valid_c, "pos": f["pos"], "lengths": lens_i,
                    "write_pages": jnp.where(in_win, page_ids, 0)[:, None],
                    "write_offs": jnp.where(in_win, col % ps, 0)[:, None]}
            logits, pools_c = self.model.decode_step_paged(
                draft_params, view, cur, adapters=adapters)[:2]
            nxt, _ = sample_token_per_row(
                f["seed"], f["gen_pos"] + i, logits, temps, f["top_p"],
                f["top_k"])
            nxt = jnp.where(active, nxt, 0)
            valid_c = valid_c | (
                (col_ids == col[:, None]) & in_win[:, None])
            return (nxt, valid_c, pools_c), nxt

        (_, _, pools), props = jax.lax.scan(
            draft_step, (f["tokens"], f["valid"], pools),
            jnp.arange(self._spec_k, dtype=jnp.int32))
        return pools, jnp.moveaxis(props, 0, 1)

    def _spec_verify_fn(self, params, pools, packed, proposals,
                        adapters=None):
        """The speculative VERIFY phase: one multi-token target forward
        over the block [pending, d_1 .. d_K] at columns
        ``lengths .. lengths + K``. ``valid`` is COMMITTED-ONLY, the
        columns below ``lengths`` as the host has them (``_unpack_decode``
        on the same ``packed`` the draft took) — the draft's columns must
        not be valid here, or the
        block attention would double-count keys its in-block causal term
        already supplies. The target then samples its OWN next token at
        every block position on the request's fold_in(seed, gen_pos + i)
        stream — these samples ARE the emitted tokens, which is why
        greedy and sampled outputs are bit-identical to the
        non-speculative engine — and draft token d_{i+1} is accepted iff
        it equals target sample s_i (so position i+1's KV was computed
        from the right input). All K+1 target KV columns scatter over
        the draft's (same pages, CO-written/private by
        ensure_decode_pages' span guard); the host commits only the
        accepted prefix, so rejected columns are never marked valid —
        rollback costs nothing and rejected tokens can never reach the
        PrefixCache index (only prefill registers pages). Returns a
        packed [3, B, K+1] int32 array — tokens / chosen-token logps
        bitcast / accept-count broadcast — ONE D2H per round."""
        self.spec_verify_compiles += 1  # dla: disable=trace-side-effect -- deliberate trace-time compile counter, pinned by the speculative compile-once tests
        geom = self.cache.geom
        ps = geom.page_size
        b = geom.num_slots
        sw = geom.slot_window
        g = self._spec_k + 1
        f, adapters = self._unpack_decode(packed, adapters)
        block_tables, lengths, active = (
            f["block_tables"], f["lengths"], f["active"])
        cols = lengths[:, None] + jnp.arange(g, dtype=jnp.int32)[None, :]
        in_win = (cols < sw) & active[:, None]
        page_ids = jnp.take_along_axis(
            block_tables,
            jnp.minimum(cols // ps, geom.pages_per_slot - 1), axis=1)
        view = {"pools": pools, "block_tables": block_tables,
                "valid": f["valid"], "pos": f["pos"], "lengths": lengths,
                "write_pages": jnp.where(in_win, page_ids, 0),
                "write_offs": jnp.where(in_win, cols % ps, 0)}
        block = jnp.concatenate([f["tokens"][:, None], proposals], axis=1)
        logits, pools, _ = self.model.decode_block_paged(
            params, view, block, adapters=adapters)
        toks, logps = sample_token_block(
            f["seed"], f["gen_pos"], logits,
            jnp.where(active, f["temp"], 0.0),   # as in _decode_fn
            f["top_p"], f["top_k"])
        toks = jnp.where(active[:, None], toks, 0)
        logps = jnp.where(active[:, None], logps, 0.0)
        accept = toks[:, :self._spec_k] == proposals
        acc = accept_prefix_len(accept)                    # [B] 0..K
        packed = jnp.stack([
            toks,
            jax.lax.bitcast_convert_type(logps, jnp.int32),
            jnp.broadcast_to(acc[:, None], (b, g))])
        return pools, packed

    # ------------------------------------------------------------- intake

    def submit(self, prompt_tokens: List[int], max_new_tokens: int,
               arrival_time: Optional[float] = None,
               deadline_s: Optional[float] = None,
               priority: int = 0,
               sampling: Optional[SamplingParams] = None,
               tenant: Optional[str] = None) -> int:
        """Queue a request; returns its id. Guards that the request can
        EVER fit: the pages its longest re-admission prefix takes (plus
        the decode reserve) within pool capacity.

        ``deadline_s`` is a per-request latency budget relative to
        arrival: past it the scheduler finishes the request with TIMEOUT
        status at the next engine step, whether it is still queued or
        mid-decode (generated-so-far tokens are kept).

        ``sampling`` overrides the engine-global ``gen.*`` knobs for this
        request (temperature/top_p/top_k/seed); None uses the engine
        defaults with a seed derived from (engine seed, rid). Either way
        the request's token stream is a pure function of its seed and
        token index — deterministic under eviction and supervisor
        replay. Per-token chosen-token logprobs accumulate on
        ``result(rid).generated_logprobs``.

        With admission control on (cfg.shed) the request may come back
        already terminal: SHED at the gate (bucket empty, or it is the
        worst of a full queue) — or it may displace a lower-priority
        queued request, which is shed instead. Check
        ``result(rid).state``.

        ``tenant`` (requires cfg.tenancy) runs the request under that
        tenant's published LoRA adapter, quota bucket, SLO accounting
        and prefix-cache namespace; None serves the base weights. A
        tenant whose own token bucket is empty has THIS request shed
        (``at="tenant_quota"``) before the shared gate is consulted —
        per-tenant isolation, other tenants unaffected."""
        if self._draining:
            raise RuntimeError(
                "engine is draining (SIGTERM received): admission closed")
        if self.cfg.role == "decode":
            raise RuntimeError(
                "engine role is 'decode': admission is handoff-only "
                "(import_request / restore)")
        if tenant is not None:
            self._check_tenant(tenant)
        geom = self.cache.geom
        req = Request(prompt_tokens=list(prompt_tokens),
                      max_new_tokens=int(max_new_tokens),
                      arrival_time=(self.now() if arrival_time is None
                                    else arrival_time),
                      priority=int(priority),
                      sampling=sampling,
                      tenant=tenant)
        if deadline_s is not None:
            req.deadline = req.arrival_time + float(deadline_s)
        worst = len(req.prompt_tokens) + req.max_new_tokens
        worst_pages = self.scheduler.admission_pages(
            min(worst, geom.slot_window))
        if worst_pages > self.cache.allocator.capacity:
            raise ValueError(
                f"request {req.rid} can never be served: needs up to "
                f"{worst_pages} pages, pool capacity is "
                f"{self.cache.allocator.capacity}")
        self.scheduler.submit(req)
        self._results[req.rid] = req
        self.metrics.requests_submitted.inc()
        mark("serve_req_submit", rid=req.rid,
             prompt_len=len(req.prompt_tokens), max_new=req.max_new_tokens)
        if self.tracer.enabled:
            # root of the request's async span tree, keyed by rid and
            # opened at the recorded arrival time — so the tree's span
            # durations are exactly the recorded latency metrics
            self.tracer.async_begin(
                "request", "request", req.rid, t=req.arrival_time,
                prompt_tokens=len(req.prompt_tokens),
                max_new_tokens=req.max_new_tokens)
        if tenant is not None and self.tenants is not None:
            self.tenants.on_submit(tenant)
            if not self.tenants.gate(tenant, req.arrival_time):
                # the tenant exhausted ITS OWN bucket: shed this arrival
                # and nothing else — the shared gate below never sees it
                self._shed(req, at="tenant_quota")
                return req.rid
        if self.admission is not None:
            _, victims = self.admission.on_submit(
                self.scheduler, req, req.arrival_time)
            for victim in victims:
                self._shed(victim, at="gate")
        return req.rid

    def result(self, rid: int) -> Request:
        return self._results[rid]

    def cancel(self, rid: int, reason: str = "cancelled") -> Request:
        """Client-initiated terminal cancellation — the gateway's
        broken-pipe-on-write path. Wherever the request currently lives
        (queued, prefilling, or mid-decode) its resources go back to
        the pool; generated-so-far tokens stay on the result. A no-op
        on already-terminal requests."""
        req = self._results[rid]
        if req.state in TERMINAL_STATES:
            return req
        self.scheduler.cancel(req, reason)
        self.metrics.requests_cancelled.inc()
        self.recorder.record("request_cancelled",
                             step=self.engine_steps, rid=rid,
                             reason=reason)
        self._req_end(req, "cancelled")
        return req

    def publish_params(self, new_params, donate: bool = False) -> None:
        """In-place weight refit: swap the param tree the jitted steps
        read. The new tree must match the old one's structure, shapes
        and dtypes exactly — same jit fingerprint, so the decode/prefill
        compile counters stay pinned (enforced here rather than
        discovered as a silent retrace). With ``donate=True`` the OLD
        tree's device buffers are freed eagerly (the rollout refitter's
        donation contract) — only safe when the caller owns the old tree
        exclusively; never donate params shared with a trainer.

        For an ADAPTER-ONLY change (one tenant's LoRA factors moved, the
        base weights didn't) use :meth:`publish_adapter` instead: it
        swaps just that tenant's pool row, never retransfers the base
        tree, and leaves every other tenant untouched."""
        old = self.params
        old_def = jax.tree_util.tree_structure(old)
        new_def = jax.tree_util.tree_structure(new_params)
        if old_def != new_def:
            raise ValueError(
                "refit params tree structure mismatch: "
                f"{new_def} vs engine {old_def} (an adapter-only tree "
                "belongs to publish_adapter, not a full-tree refit)")
        for o, n_ in zip(jax.tree_util.tree_leaves(old),
                         jax.tree_util.tree_leaves(new_params)):
            if o.shape != n_.shape or o.dtype != n_.dtype:
                raise ValueError(
                    "refit params leaf mismatch (would retrace): "
                    f"{n_.shape}/{n_.dtype} vs engine {o.shape}/{o.dtype}")
        self.params = new_params
        if self._spec_k:
            # draft refit rides the target refit: re-derive BEFORE any
            # donation frees the old leaves ("self" would otherwise
            # alias deleted buffers). Same structure in -> same
            # structure out, so the spec-fn jit fingerprints hold and
            # the draft/verify compile counters stay pinned.
            self.draft_params = self._derive_draft(new_params)
        if donate and old is not new_params:
            keep = {id(leaf) for leaf
                    in jax.tree_util.tree_leaves(new_params)}
            for leaf in jax.tree_util.tree_leaves(old):
                if id(leaf) not in keep and hasattr(leaf, "delete"):
                    try:
                        leaf.delete()
                    except Exception:
                        pass  # already deleted / externally owned

    def publish_adapter(self, tenant: str, tree, *,
                        alpha: Optional[float] = None,
                        rank: Optional[int] = None) -> None:
        """Install (or hot-swap) one tenant's LoRA adapter — the
        adapter-only sibling of :meth:`publish_params`. The tree is the
        adapter pytree ``init_lora`` produces for the pool's targets
        (treedef-validated the same way a refit is); a resident tenant's
        pool row is rewritten in place with identical shapes and dtypes,
        so the decode jit fingerprint — and the compile counters the
        compile-once tests pin — never move. Requests already decoding
        under this tenant pick the new factors up on their next step."""
        if self.adapter_store is None:
            raise RuntimeError(
                "publish_adapter requires cfg.tenancy (the engine was "
                "built without an adapter pool)")
        self.adapter_store.publish(tenant, tree, alpha=alpha, rank=rank)
        if self.tenants is not None:
            self.tenants.ensure(tenant)

    def _check_tenant(self, tenant: str) -> None:
        if self.adapter_store is None:
            raise ValueError(
                "tenant-scoped request requires cfg.tenancy")
        if not (self.adapter_store.has(tenant)
                or self.tenants.configured(tenant)):
            raise ValueError(
                f"unknown tenant {tenant!r}: publish_adapter first, or "
                "list it under tenancy.quotas for base-weight serving")

    def restore(self, prompt_tokens: List[int], max_new_tokens: int, *,
                generated: List[int], arrival_time: float,
                deadline: Optional[float] = None, priority: int = 0,
                rid: Optional[int] = None,
                sampling: Optional[SamplingParams] = None,
                generated_logprobs: Optional[List[float]] = None,
                tenant: Optional[str] = None
                ) -> Request:
        """Re-enter a journaled in-flight request after a supervisor
        rebuild: the eviction deterministic-recompute contract taken
        cross-engine. ``generated`` pre-seeds the tokens the client
        already streamed, so ``prefix_tokens`` is prompt + streamed —
        the engine re-prefills that prefix and continues from the next
        token. Nothing is re-emitted, and the continuation is
        bit-identical to the fault-free run — greedy AND sampled, since
        the sampling stream is keyed by (seed, token index) and the
        continuation resumes at index ``len(generated)``. ``rid`` (and
        ``sampling``) must be preserved for that determinism when the
        request used the rid-derived default seed. Bypasses the
        admission gate and the drain closure: replayed requests ARE the
        in-flight work a drain exists to finish.

        When the prefix cache already holds EVERY page of the committed
        prefix (the usual case on supervisor replay — the crashed
        engine's registrations are gone, but fleet rebalance hands the
        request to an engine that often served the same prompt), the
        request adopts those pages straight into a decode slot and
        resumes with ZERO prefill; otherwise it queues for the normal
        re-prefill."""
        if tenant is not None:
            # a rebuilt engine must have the adapter republished by its
            # factory before replay reaches it — fail loudly, not with
            # silently-base-weight decoding
            self._check_tenant(tenant)
        req = Request(prompt_tokens=list(prompt_tokens),
                      max_new_tokens=int(max_new_tokens),
                      arrival_time=arrival_time,
                      priority=int(priority),
                      sampling=sampling,
                      tenant=tenant)
        if rid is not None:
            req.rid = rid
        req.deadline = deadline
        req.generated = list(generated)
        req.generated_logprobs = (
            list(generated_logprobs) if generated_logprobs is not None
            else [0.0] * len(req.generated))
        if req.remaining_new_tokens <= 0:
            # every token already streamed before the failure: nothing
            # left to recompute
            self.scheduler.submit(req)
            self.scheduler.cancel(req, "length")
            self.metrics.requests_finished.inc()
        elif not self._try_adopt_cached(req):
            self.scheduler.submit(req)
        self._results[req.rid] = req
        return req

    def _try_adopt_cached(self, req: Request) -> bool:
        """Restore fast path: when the prefix cache holds every page of
        the request's COMMITTED prefix (``prefix_tokens[:-1]`` — the
        last generated token is the next decode input, its column not
        yet written), alias them into a free decode slot and resume
        decode directly, skipping prefill entirely. Only a page-aligned
        committed length qualifies: partial tail columns are never
        indexed, so an unaligned prefix always needs at least one chunk
        recomputed and takes the normal queue path. References taken
        here are unwound completely on any refusal — the fallback is
        indistinguishable from never having tried."""
        if self.prefix_cache is None or not req.generated:
            return False
        ps = self.cfg.page_size
        committed = len(req.prefix_tokens) - 1
        if committed < ps or committed % ps:
            return False
        geom = self.cache.geom
        if len(req.prompt_tokens) + req.max_new_tokens > geom.slot_window:
            return False     # let submit() raise its precise error
        if not self.scheduler.free_slots:
            return False
        if self.scheduler._admission_headroom() == 0:
            return False
        pages = self.prefix_cache.acquire_pages(
            req.prefix_tokens[:committed], namespace=req.tenant)
        if pages is None:
            return False
        n_extra = min(self.cfg.decode_reserve_pages,
                      geom.pages_per_slot - len(pages))
        extra = self.cache.allocator.alloc(n_extra) if n_extra > 0 else []
        if extra is None:
            for p in pages:
                self.cache.allocator.decref(p)
            return False
        self._adopt_committed(req, pages + extra, committed)
        self.metrics.prefill_tokens_saved.inc(committed)
        return True

    def _adopt_committed(self, req: Request, pages: List[int],
                         committed: int) -> None:
        """Shared tail of the two no-prefill entry paths (cache-alias
        restore and KV import): bind the request into a decode slot over
        ``pages`` whose first ``ceil(committed/ps)`` entries hold its
        committed KV, and enter the decode batch with the last generated
        token as the next input."""
        slot = self.scheduler.adopt(req, pages)
        self.cache.open_slot_prefill(slot, req.pages, committed)
        self.cache.begin_decode(slot, committed, req.generated[-1])
        self._bind_adapter(req)
        self._bind_slot_sampling(req)

    # ------------------------------------------------------- KV migration

    def export_request(self, rid: int) -> MigrationTicket:
        """Serialize a mid-decode request's committed state into a
        :class:`MigrationTicket` (the extract half of the KV handoff —
        usually reached via ``KVMigrator``). The request itself is NOT
        released: it keeps decoding here until ``release_migrated``,
        so a failed install downstream loses nothing.

        Refuses (``MigrationError``, counted on
        ``serving/migration/failed_migrations``) requests that are not
        resumable in place: unknown, queued/prefilling/terminal, or with
        an eviction hole — block-table pages no longer covering the
        committed columns."""
        self._refuse_stateful("KV export")
        req = self._results.get(rid)
        if req is None:
            return self._export_refuse(f"unknown rid {rid}")
        if self.cache.pools_dead:
            return self._export_refuse(f"request {rid}: {_POOL_CONSUMED}")
        if req.state is not RequestState.DECODE or req.slot is None \
                or self.scheduler.running.get(req.slot) is not req:
            return self._export_refuse(
                f"request {rid} is {req.state.value}, not mid-decode: "
                "only requests with committed KV in the pool can "
                "migrate (eviction hole — queued work just re-routes)")
        committed = len(req.prefix_tokens) - 1
        if committed < 1:
            return self._export_refuse(
                f"request {rid} has no committed columns yet")
        geom = self.cache.geom
        needed = geom.pages_for(committed)
        btab = self.cache.block_tables[req.slot]
        if len(req.pages) < needed or not all(
                int(btab[i]) == req.pages[i] and req.pages[i] != 0
                for i in range(needed)):
            return self._export_refuse(
                f"request {rid}: block table does not cover its "
                f"committed prefix (eviction hole)")
        if not bool(self.cache.valid[req.slot, :committed].all()):
            return self._export_refuse(
                f"request {rid}: uncomputed committed columns")
        ids = np.zeros((geom.pages_per_slot,), np.int32)
        ids[:needed] = req.pages[:needed]
        with annotate("serve_kv_export", rid=req.rid):
            payloads = self._export_kv(self.cache.pools, jnp.asarray(ids))
        return MigrationTicket(
            rid=req.rid,
            prompt_tokens=list(req.prompt_tokens),
            max_new_tokens=req.max_new_tokens,
            generated=list(req.generated),
            generated_logprobs=list(req.generated_logprobs),
            sampling=req.sampling,
            arrival_time=req.arrival_time,
            deadline=req.deadline,
            priority=req.priority,
            committed_len=committed,
            page_size=self.cfg.page_size,
            n_pages=needed,
            payloads=payloads,
            admitted_time=req.admitted_time,
            first_token_time=req.first_token_time,
            last_token_time=req.last_token_time,
            tenant=req.tenant)

    def _refuse_stateful(self, what: str) -> None:
        if self._stateful:
            raise ValueError(
                f"{what}: this model keeps recurrent state a slot, and a "
                "ticket carries pages, not state (state snapshots are not "
                "built)")

    def _export_refuse(self, msg: str):
        self._mig_stats["failed_migrations"] += 1
        raise MigrationError(msg)

    def import_request(self, ticket: MigrationTicket) -> Request:
        """Install a migrated request (the install half of the KV
        handoff): allocate pages, scatter the payload in ONE jitted
        fixed-shape call, register the committed FULL pages into the
        prefix cache (tail columns of a partial page stay private), and
        resume decode mid-stream — the request decodes on the very next
        engine step, bit-identically to never having moved.

        The source clocks ride the ticket, so TTFT is never re-recorded
        and the first post-handoff ITL sample honestly includes the
        handoff wait (also recorded on
        ``serving/migration/handoff_wait_ms``). Refuses geometry
        mismatches, window overflows, slot/page exhaustion
        (``MigrationError``, counted on failed_migrations) — the caller
        keeps the source copy running."""
        self._refuse_stateful("KV import")
        t_start = self.now()
        if self.cache.pools_dead:
            return self._import_refuse(
                f"ticket {ticket.rid}: {_POOL_CONSUMED}")
        if ticket.page_size != self.cfg.page_size:
            return self._import_refuse(
                f"page_size mismatch: ticket {ticket.page_size}, "
                f"engine {self.cfg.page_size}")
        if not ticket.generated:
            return self._import_refuse(
                f"ticket {ticket.rid} carries no generated tokens")
        committed = len(ticket.prompt_tokens) + len(ticket.generated) - 1
        if committed != ticket.committed_len:
            return self._import_refuse(
                f"ticket {ticket.rid}: committed_len "
                f"{ticket.committed_len} != prefix-1 ({committed})")
        geom = self.cache.geom
        needed = geom.pages_for(committed)
        if ticket.n_pages != needed:
            return self._import_refuse(
                f"ticket {ticket.rid}: n_pages {ticket.n_pages} != "
                f"{needed} for {committed} committed columns")
        shapes = [tuple(getattr(x, "shape", ())) for x in ticket.payloads]
        want = [p.shape[:1] + (geom.pages_per_slot,) + p.shape[2:]
                for p in self.cache.pools]
        if shapes != want:
            return self._import_refuse(
                f"ticket {ticket.rid}: payload geometry {shapes} does "
                f"not match this engine's pools {want}")
        if len(ticket.prompt_tokens) + ticket.max_new_tokens \
                > geom.slot_window:
            return self._import_refuse(
                f"ticket {ticket.rid} cannot fit the slot window "
                f"({geom.slot_window})")
        if not self.scheduler.free_slots \
                or self.scheduler._admission_headroom() == 0:
            return self._import_refuse(
                f"ticket {ticket.rid}: no free decode slot")
        if ticket.tenant is not None:
            try:
                self._check_tenant(ticket.tenant)
            except ValueError as e:
                # counted like any other refused install: the source
                # keeps the request, nothing decodes under wrong weights
                return self._import_refuse(
                    f"ticket {ticket.rid}: {e}")
        n_alloc = min(needed + self.cfg.decode_reserve_pages,
                      geom.pages_per_slot)
        pages = self.cache.allocator.alloc(n_alloc)
        if pages is None:
            return self._import_refuse(
                f"ticket {ticket.rid}: page pool cannot supply "
                f"{n_alloc} pages")
        ids = np.zeros((geom.pages_per_slot,), np.int32)
        ids[:needed] = pages[:needed]
        with annotate("serve_kv_import", rid=ticket.rid):
            self.cache.pools = self._import_kv(
                self.cache.pools, tuple(ticket.payloads),
                jnp.asarray(ids))
        req = Request(prompt_tokens=list(ticket.prompt_tokens),
                      max_new_tokens=int(ticket.max_new_tokens),
                      arrival_time=ticket.arrival_time,
                      priority=int(ticket.priority),
                      sampling=ticket.sampling,
                      tenant=ticket.tenant)
        req.rid = ticket.rid
        req.deadline = ticket.deadline
        req.generated = list(ticket.generated)
        req.generated_logprobs = list(ticket.generated_logprobs)
        req.admitted_time = ticket.admitted_time
        req.first_token_time = ticket.first_token_time
        req.last_token_time = ticket.last_token_time
        self._adopt_committed(req, pages, committed)
        if self.prefix_cache is not None:
            # index the committed FULL pages so later identical prompts
            # (and future migrations back) alias them; no logits entry —
            # the request resumes decode, there are no prefill logits
            self.prefix_cache.register(
                req.prefix_tokens[:committed], pages,
                namespace=req.tenant)
        self._results[req.rid] = req
        self._mig_stats["migrations"] += 1
        self._mig_stats["migrated_pages"] += needed
        if ticket.transport == "host":
            self._mig_stats["host_bounce_bytes"] += ticket.payload_bytes
        if ticket.last_token_time is not None:
            self.metrics.handoff_wait_ms.record(
                (t_start - ticket.last_token_time) * 1000.0)
        if self.tracer.enabled:
            self.tracer.async_begin(
                "request", "request", req.rid, t=req.arrival_time,
                prompt_tokens=len(req.prompt_tokens),
                max_new_tokens=req.max_new_tokens)
        return req

    def _import_refuse(self, msg: str):
        self._mig_stats["failed_migrations"] += 1
        raise MigrationError(msg)

    def release_migrated(self, rid: int) -> None:
        """Drop the SOURCE copy of a request that a target engine has
        successfully imported: free its slot and page references and
        forget it from the result surface (its live state — and final
        result — now belong to the target). Called only after the
        install committed, so the request exists on exactly one engine
        at every step boundary."""
        req = self._results.pop(rid, None)
        if req is None:
            return
        if req.state is RequestState.DECODE:
            self.scheduler.cancel(req, "migrated")
        self._req_end(req, "migrated")

    def _req_end(self, req: Request, status: str,
                 t: Optional[float] = None) -> None:
        """A request reached a terminal status: the lifecycle mark on the
        profiler's clock and the close of its async tree in the host
        tracer, side by side."""
        mark("serve_req_finish", rid=req.rid, status=status,
             tokens=len(req.generated))
        if self.tracer.enabled:
            self.tracer.async_end("request", "request", req.rid, t=t,
                                  status=status,
                                  tokens=len(req.generated))

    def has_work(self) -> bool:
        return bool(self.scheduler.queue or self.scheduler.running
                    or self.scheduler.prefilling)

    # --------------------------------------------------------- engine step

    def step(self) -> List[Tuple[int, int]]:
        """One engine iteration: ensure pages for running requests (may
        preempt) -> admit into leftovers -> decode. Page growth runs
        first so in-flight requests outrank new admissions for the pool;
        a fresh admission always carries its decode reserve, so it never
        needs a page in the same step. Returns the (rid, token) pairs
        emitted this step, in slot order — the streaming surface.

        Raises ``DeviceStepError`` on an engine whose pools a failed
        dispatch consumed (``PagedKVCache.pools``): its KV is gone, so
        the one way on is a rebuild and a replay, which is what a
        ``Supervisor`` does with every device fault."""
        if self.cache.pools_dead:
            raise DeviceStepError(_POOL_CONSUMED)
        self.profile.on_step(self.engine_steps)
        if self.xla_introspect_enabled:
            # stamp compile events from this step's dispatches
            self._decode.step = self.engine_steps
            self._prefill_chunk.step = self.engine_steps
            if self._spec_k:
                self._spec_draft.step = self.engine_steps
                self._spec_verify.step = self.engine_steps
            self._export_kv.step = self.engine_steps
            self._import_kv.step = self.engine_steps
        emitted: List[Tuple[int, int]] = []
        # a speculative round may COMMIT up to K+1 columns per slot, so
        # page headroom / copy-on-write cover the whole write span
        span = self._spec_k + 1
        with step_annotation(self.engine_steps, name="serve"):
            with annotate("serve_schedule"):
                self._poll_faults()
                self._expire(self.now())
                self._resilience_pass()
                self._ensure_decode_pages(span)
            with annotate("serve_admit",
                          queued=self.scheduler.queue_depth):
                self._admit(emitted)
            self._chunk_step(emitted)
            # second page-safety pass: requests admitted ABOVE (via cache
            # hit or final chunk) decode THIS step. Their first write may
            # land in a shared/indexed tail page — copy-on-write must run
            # before the decode, not next step — and the decode reserve
            # guarantees ONE column where a speculative round commits up
            # to span: grow (or preempt) before the round, or commits
            # could advance past allocated pages
            with annotate("serve_schedule"):
                self._ensure_decode_pages(span)
            if self.scheduler.running:
                emitted.extend(self._spec_decode_step() if self._spec_k
                               else self._decode_step())
            with annotate("serve_post"):
                self._post_step()
        if emitted and not self._startup_reported:
            # the first token out: this replica serves. Where the time
            # since the process started went, once (gauges and one line)
            self._startup_reported = True
            report_startup(self.metrics.registry)
        return emitted

    def _ensure_decode_pages(self, span: int) -> None:
        """One page-safety pass; every request it preempts is counted and
        marked on the profiler's clock."""
        for req in self.scheduler.ensure_decode_pages(span=span):
            self.metrics.preemptions.inc()
            mark("serve_req_preempt", rid=req.rid)

    def _post_step(self) -> None:
        """The tail of a step: counter mirrors, gauges, SLO and tenant
        passes (inside the step span as ``serve_post``)."""
        self.engine_steps += 1
        self.readiness.beat()
        if self.anomaly is not None:
            self.anomaly.on_step(self.engine_steps)
        self._mirror_cache_counters()
        self._mirror_spec_counters()
        self._mirror_migration_counters()
        self._mirror_adapter_counters()
        m = self.metrics
        m.queue_depth.set(self.scheduler.queue_depth)
        m.active_requests.set(self.scheduler.active_count)
        m.page_occupancy.set(self.cache.occupancy)
        walloc = self.cache.window_allocator
        if walloc is not None:
            m.window_page_occupancy.set(walloc.occupancy)
            m.window_pages_released.inc(
                self.cache.window_pages_released
                - self._window_released_mirrored)
            self._window_released_mirrored = self.cache.window_pages_released
        if self.slo is not None \
                and self.engine_steps % self._slo_every == 0:
            self.slo.observe(m.snapshot(), step=self.engine_steps)
        if self.tenants is not None \
                and self.engine_steps % self._slo_every == 0:
            # per-tenant burn over each tenant's OWN panel; any tenant
            # past the (opt-in) burn threshold sheds ONLY its own queue
            self.tenants.observe(step=self.engine_steps)
            for victim in self.tenants.shed_pass(self.scheduler):
                self._shed(victim, at="tenant_slo")

    def run_until_drained(self, max_steps: int = 100000,
                          on_cap: str = "raise") -> Dict[int, Request]:
        """Step until ``has_work()`` is false. Hitting ``max_steps`` with
        work still in flight is a wedge, and the two dispositions are
        both terminal — a drain NEVER silently returns live requests:

        - ``on_cap="raise"`` (default): RuntimeError, matching the
          Supervisor's run cap.
        - ``on_cap="shed"``: resolve every straggler as SHED with a
          ``drain_cap`` flight-recorder event and return normally — the
          fleet scale-down path, where the caller must reclaim the
          engine but may not leak a request without a terminal status.
        """
        try:
            for _ in range(max_steps):
                if not self.has_work():
                    return dict(self._results)
                self.step()
        finally:
            # an open trace window must flush even on an early exit
            self.profile.close()
        if on_cap == "shed":
            self._shed_stragglers()
            return dict(self._results)
        raise RuntimeError(f"serving loop did not drain in {max_steps} steps")

    def _shed_stragglers(self) -> None:
        """Terminal SHED for every request still queued or in flight —
        the drain-cap escape hatch. Running/prefilling work gives its
        slot and pages back through the scheduler's cancel path, so the
        engine is fully reclaimable afterwards."""
        stragglers = (list(self.scheduler.queue)
                      + list(self.scheduler.running.values())
                      + list(self.scheduler.prefilling.values()))
        self.recorder.record("drain_cap", step=self.engine_steps,
                             stragglers=len(stragglers))
        for req in stragglers:
            self.scheduler.cancel(req, "shed", RequestState.SHED)
            self.metrics.requests_shed.inc()
            self.recorder.record("request_shed", step=self.engine_steps,
                                 rid=req.rid, priority=req.priority,
                                 at="drain_cap")
            self._req_end(req, "shed")

    # -------------------------------------------------------- observability

    def start_metrics_server(self, port: int = 0) -> MetricsHTTPServer:
        """Expose this engine's registry at ``GET /metrics`` (Prometheus
        text format) on a background thread; idempotent. ``port=0``
        binds an ephemeral port — read it back from ``.port``."""
        if self.metrics_server is None:
            self.metrics_server = MetricsHTTPServer(
                self.metrics.registry, port=port,
                readiness=self.readiness)
        return self.metrics_server

    def close(self) -> None:
        """Release host-side resources (trace window, host tracer,
        metrics endpoint). Device state is dropped with the object as
        usual."""
        self.profile.close()
        if self.anomaly is not None:
            self.anomaly.close()
        if self._installed_tracer:
            self.tracer.dump()
            install_tracer(None)     # don't leak into the next engine
            self._installed_tracer = False
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None

    # ------------------------------------------------------ graceful drain

    def begin_drain(self) -> None:
        """Stop admission and shed work that never started: queued
        requests with no generated tokens are cancelled; evicted
        in-flight requests (they hold generated tokens and sunk compute)
        stay queued for re-admission, and running decodes run to
        completion. Safe to call from a signal handler's flag path —
        it only mutates host state."""
        if self._draining:
            return
        self._draining = True
        # /healthz answers 503 body "draining" from here on (load
        # balancers stop routing before admission starts rejecting);
        # the tripped-circuit-breaker path flips the same switch
        self.readiness.set_draining("draining")
        for req in [r for r in self.scheduler.queue if not r.generated]:
            self.scheduler.cancel(req, "cancelled")
            self.metrics.requests_cancelled.inc()
            self._req_end(req, "cancelled")

    @property
    def draining(self) -> bool:
        return self._draining

    def install_drain_handler(self) -> None:
        """SIGTERM -> begin_drain(): the serving analog of the trainer's
        preemption handling. The engine loop keeps stepping until
        ``has_work()`` is false, then the caller flushes metrics and
        exits — in-flight decodes finish, nothing is dropped mid-token."""
        from dla_tpu.resilience.preemption import install_sigterm_flag
        self._old_handlers = install_sigterm_flag(self.begin_drain)

    def drain(self, logger=None, max_steps: int = 100000,
              on_cap: str = "raise") -> Dict[int, Request]:
        """Begin (or continue) a drain, run it to empty, flush metrics.
        ``on_cap`` picks the straggler disposition at the step cap (see
        ``run_until_drained``); either way no request is left without a
        terminal status."""
        self.begin_drain()
        results = self.run_until_drained(max_steps, on_cap=on_cap)
        self.metrics.report(logger, self.metrics.decode_steps.value)
        return results

    def _expire(self, now: float) -> None:
        """Finish every queued or running request past its deadline with
        TIMEOUT status. Queued requests simply leave the queue; a running
        one gives its slot and pages back, so the timeout of a stuck-long
        request is itself a backpressure release valve."""
        for req in self.scheduler.expired(now):
            self.scheduler.cancel(req, "timeout", RequestState.TIMEOUT)
            self.metrics.requests_timed_out.inc()
            if req.admitted_time is None:
                # expired straight out of the queue, never admitted:
                # queue wait alone blew the deadline — the admission-
                # pressure signal, distinct from slow decode
                self.metrics.queue_timeouts.inc()
            self._req_end(req, "timeout", t=now)

    # ----------------------------------------------------------- resilience

    def _shed(self, req: Request, at: str = "queue") -> None:
        """Terminal SHED for one queued request: cancel out of the
        queue, count, record, close the trace span. Only never-started
        requests are ever shed (``sheddable_queued`` guarantees it), so
        beyond the scheduler's cancel path there is no slot or page
        state to unwind."""
        self.scheduler.cancel(req, "shed", RequestState.SHED)
        self.metrics.requests_shed.inc()
        if self.tenants is not None and req.tenant is not None:
            self.tenants.on_shed(req.tenant)
        self.recorder.record("request_shed", step=self.engine_steps,
                             rid=req.rid, priority=req.priority, at=at,
                             tenant=req.tenant)
        self._req_end(req, "shed")

    def _resilience_pass(self) -> None:
        """Once per step, after deadline expiry and before scheduling:
        feed the pressure signal (max of page occupancy and queue-depth
        fraction) to the degradation ladder, apply its rungs, and run
        the SLO-aware shed pass over the queue."""
        if self.admission is None:
            return
        shed_cfg = self.admission.cfg
        qfrac = self.scheduler.queue_depth / max(1,
                                                 shed_cfg.max_queue_depth)
        pressure = max(self.cache.allocator.occupancy, min(1.0, qfrac))
        prev = self._applied_level
        level = self.ladder.update(pressure, step=self.engine_steps)
        self.metrics.degradation_level.set(level)
        if level != prev:
            if prev == 0 and level >= 1 and self.prefix_cache is not None:
                # rung 1 entry: give cached-but-unreferenced prefix
                # pages back to the free pool (throughput optimization
                # goes first, requests go last)
                n_pages = self.cache.allocator.reclaim_cached()
                self.recorder.record("degradation_cache_flush",
                                     step=self.engine_steps,
                                     pages=n_pages)
            self._applied_level = level
        # rung 3: halve the concurrent-request ceiling so queue wait
        # trades against decode interference under pressure
        self.scheduler.max_active = (
            None if not self.ladder.shrink_batch
            else max(1, self.cfg.num_slots // 2))
        burn = 0.0
        if self.slo is not None:
            for objective in self.slo.slos:
                rate = self.slo.burn_rate(objective)
                if rate > burn:
                    burn = rate
        for victim in self.admission.shed_pass(self.scheduler, burn,
                                               level):
            self._shed(victim, at="slo" if burn else "ladder")

    def _poll_faults(self) -> None:
        """Fire any serving-scoped (``engine_step=``) fault-plan entries
        due this step. ``wedge`` sleeps right here — inside the step, so
        a supervising watchdog sees it; ``device_error``/``nan_logits``
        arm a flag the next decode dispatch consumes. ``burst`` is the
        Supervisor's to consume (it owns intake); the engine ignores
        it."""
        if not self.faults:
            return
        f = self.faults.take("wedge", self.engine_steps,
                             site="engine_step")
        if f is not None:
            self.recorder.record("fault_injected", step=self.engine_steps,
                                 fault="wedge")
            time.sleep(0.3 if f.arg is None else f.arg)
        f = self.faults.take("device_error", self.engine_steps,
                             site="engine_step")
        if f is not None:
            self.recorder.record("fault_injected", step=self.engine_steps,
                                 fault="device_error")
            self._fault_device_error = True
        f = self.faults.take("nan_logits", self.engine_steps,
                             site="engine_step")
        if f is not None:
            self.recorder.record("fault_injected", step=self.engine_steps,
                                 fault="nan_logits")
            self._fault_nan_logits = True

    # ------------------------------------------------------------ internals

    def _effective_sampling(self, req: Request) -> SamplingParams:
        """The request's sampling knobs: its explicit override, or the
        engine-global gen.* defaults with a (engine seed, rid)-derived
        seed — deterministic across restarts since restore() preserves
        rids."""
        if req.sampling is not None:
            return req.sampling
        return SamplingParams.from_gen(
            self.gen, derive_request_seed(self.cfg.seed, req.rid))

    def _bind_slot_sampling(self, req: Request) -> None:
        """Mirror the request's sampling knobs into its slot's row of the
        per-slot arrays the decode step ships to device."""
        sp = self._effective_sampling(req)
        s = req.slot
        self.samp_temp[s] = sp.effective_temperature
        self.samp_top_p[s] = sp.top_p
        self.samp_top_k[s] = sp.top_k
        self.samp_seed[s] = np.uint32(sp.seed & 0xFFFFFFFF)

    def _bind_adapter(self, req: Request) -> None:
        """Pin the request's tenant adapter for its freshly assigned
        slot and mirror the pool row into ``adapter_idx`` (row 0 — the
        zero identity — for base requests, and always rewritten so a
        reused slot never inherits the previous tenant's adapter).
        Called exactly once per slot assignment, BEFORE the slot's first
        dispatch; the paired release rides the scheduler's
        ``release_hook``, so every release path (finish, evict, cancel,
        shed, drain) unpins it. Load-on-admission lives here: acquire
        reloads a spilled adapter from its host copy."""
        if self.adapter_store is None or req.slot is None:
            return
        idx = 0
        if req.tenant is not None and self.adapter_store.has(req.tenant):
            idx = self.adapter_store.acquire(req.tenant)
            self._bound_tenants[req.rid] = req.tenant
        self.adapter_idx[req.slot] = idx

    def _release_adapter(self, req: Request) -> None:
        """Scheduler release hook: unpin whatever _bind_adapter acquired
        for this request (a no-op for base requests — the _bound_tenants
        record keeps acquire/release exactly paired even if an adapter
        appears for the tenant mid-flight)."""
        tenant = self._bound_tenants.pop(req.rid, None)
        if tenant is not None:
            self.adapter_store.release(tenant)

    def _adapters_args(self):
        """The adapter argument of a jitted dispatch: the stacked A/B
        pools, device arrays already (each slot's pool row rides the
        dispatch's packed array). None when tenancy is off — an empty
        pytree, so the dispatch signature and jit fingerprint are
        byte-identical to an adapter-free build."""
        if self.adapter_store is None:
            return None
        return self.adapter_store.pools

    def _mirror_adapter_counters(self) -> None:
        """Delta-mirror the AdapterStore's plain-int counters into the
        registry (the prefix-cache/speculative mirror contract: a fresh
        ServingMetrics swap sees only post-swap activity; the Supervisor
        re-seeds cumulative totals into rebuilt engines)."""
        st = self.adapter_store
        if st is None:
            return
        m, seen = self.metrics, self._adapter_mirrored
        m.adapter_publishes.inc(st.publishes - seen["publishes"])
        m.adapter_loads.inc(st.loads - seen["loads"])
        m.adapter_spills.inc(st.spills - seen["spills"])
        seen.update(publishes=st.publishes, loads=st.loads,
                    spills=st.spills)
        m.adapter_resident.set(st.resident_count)

    def _admit(self, emitted: List[Tuple[int, int]]) -> None:
        """Strict-FCFS admission. Exact-full-prompt cache hits
        skip prefill entirely (stored logits -> first token now) and
        keep admitting behind them; a partial admission occupies the
        single mid-prefill seat and stops the loop."""
        while True:
            req = self.scheduler.admit_chunk_prefill()
            if req is None:
                return
            # adapter rides every chunk of the prefill, so it binds at
            # slot assignment — before the first chunk dispatch, not at
            # activation (this is also where a cold adapter loads)
            self._bind_adapter(req)
            mark("serve_req_admit", rid=req.rid, slot=req.slot,
                 cached_tokens=req.prefill_pos)
            t = self.now()
            if req.admitted_time is None:
                req.admitted_time = t
                self.metrics.queue_wait_ms.record(
                    (t - req.arrival_time) * 1000.0)
                if self.tracer.enabled:
                    self.tracer.async_instant(
                        "request", "admitted", req.rid, t=t,
                        queue_wait_ms=(t - req.arrival_time) * 1000.0)
            n = len(req.prefix_tokens)
            # both at the prefix cache's lookup: the hit tokens a share
            # of the tokens looked up, in any window of steps
            self.metrics.prompt_tokens.inc(n)
            self.metrics.prefill_tokens_saved.inc(req.prefill_pos)
            if req.prefill_pos >= n:
                # full hit: every prompt page aliased, first-token
                # logits served from the cache — zero prefill FLOPs
                # dla: disable=host-sync-in-hot-loop -- cached_logits is already host numpy (stored by register); no device fetch happens
                logits_row = np.asarray(req.cached_logits)[None, :]
                req.cached_logits = None
                self._first_token(req, logits_row, t, emitted)

    def _chunk_step(self, emitted: List[Tuple[int, int]]) -> None:
        """Advance the (single) mid-prefill request by one fixed-shape
        chunk, co-scheduled with the running decode batch under the
        token budget. Only the FINAL chunk's logits cross device->host
        (the decode step's single-D2H discipline extends to prefill)."""
        sched = self.scheduler
        if not sched.prefilling:
            return
        if self.ladder is not None and self.ladder.no_coschedule \
                and sched.running:
            # degradation rung 2: never co-schedule a chunk with a live
            # decode batch. Same no-livelock shape as the budget below —
            # with nothing decoding the chunk always runs.
            return
        budget = self.cfg.prefill_token_budget
        if budget and sched.running and \
                len(sched.running) + self.cfg.prefill_chunk > budget:
            # decode batch fills the budget: the chunk waits a step.
            # With no running decodes the chunk ALWAYS runs, so an
            # undersized budget can't livelock prefill.
            return
        slot, req = next(iter(sched.prefilling.items()))
        prefix = req.prefix_tokens
        n = len(prefix)
        start = req.prefill_pos
        nvalid = min(self.cfg.prefill_chunk, n - start)
        ids = np.zeros((self.cfg.prefill_chunk,), np.int32)
        ids[:nvalid] = prefix[start:start + nvalid]
        c = self.cache
        # ``context``: tokens already cached for the slot when the chunk
        # starts (what its attention layers read beside the chunk)
        if self._kernel_scan is None:
            # asked once, here: the first dispatch, which traces the
            # program, follows in this same context
            self._kernel_scan = self.model.scan_chunk_kernel(
                self.cfg.prefill_chunk) is not None
        with annotate("serve_prefill_chunk", rid=req.rid, slot=slot,
                      start=start, nvalid=nvalid,
                      last=int(start + nvalid >= n), puts=1,
                      h2d_bytes=self._chunk_layout.nbytes(),
                      context=start):
            if c.window_allocator is not None:
                c.ensure_window(slot, start + nvalid - 1)
            packed = self._chunk_layout.pack(
                block_tables=c.block_tables[slot],
                window_tables=c.window_tables[slot], ids=ids,
                start=np.int32(start), nvalid=np.int32(nvalid),
                adapter=self.adapter_idx[slot], slot=np.int32(slot))
            c.pools, logits = self._prefill_chunk(
                self.params, c.pools, self._put_step_args(packed),
                self._adapters_args())
        self.metrics.prefill_chunks.inc()
        self.metrics.prefill_scan_tokens.inc(nvalid * self._state_layers)
        if self._kernel_scan:
            self.metrics.prefill_scan_kernel_chunks.inc()
        cols, layers = self._attn_walk
        if layers:
            self.metrics.prefill_attn_read_tokens.inc(
                -(-start // cols) * cols * layers)
            self.metrics.prefill_attn_window_tokens.inc(
                c.geom.slot_window * layers)
        if self._dsa_topk:
            self._count_dsa(start + 1 + np.arange(nvalid))
        c.mark_computed(slot, start, nvalid)
        req.prefill_pos = start + nvalid
        if req.prefill_pos < n:
            return
        with annotate("serve_chunk_fetch", rid=req.rid):
            # dla: disable=host-sync-in-hot-loop -- designed prefill D2H: one logits fetch per REQUEST (final chunk only), not per chunk
            logits_np = np.asarray(logits)
        self._first_token(req, logits_np, self.now(), emitted,
                          register=True)

    def _first_token(self, req: Request, logits_np: np.ndarray, t: float,
                     emitted: List[Tuple[int, int]],
                     register: bool = False) -> None:
        """A chunk-prefilled (or fully cache-hit) request's first token:
        host sampling from its one logits row, decode state, prefix-cache
        registration (``register``: the row was just computed, not served
        from the cache), activation."""
        with annotate("serve_first_token", rid=req.rid):
            tok, logp = self._sample_host(logits_np, req)
            self.cache.begin_decode(req.slot, len(req.prefix_tokens), tok)
            if register and self.prefix_cache is not None:
                # first-writer-wins: later identical prompts alias these
                # pages; the stored logits make the NEXT identical prompt
                # a zero-prefill full hit
                self.prefix_cache.register(
                    req.prefix_tokens, req.pages, logits_np[0],
                    namespace=req.tenant)
            self.scheduler.activate(req)
            self._bind_slot_sampling(req)
            self._emit(req, tok, t, emitted, first_of_prefill=True,
                       logp=logp)

    def _count_dsa(self, columns: np.ndarray) -> None:
        """Sparse attention's work, per query summed over the queries of a
        dispatch (not multiplied by layers): ``columns`` each query's
        indexer scores, of which it reads min(index_topk, columns)."""
        self.metrics.dsa_live_tokens.inc(int(columns.sum()))
        self.metrics.dsa_selected_tokens.inc(
            int(np.minimum(columns, self._dsa_topk).sum()))

    def _mirror_cache_counters(self) -> None:
        """Mirror the PrefixCache's plain-int counters into the metrics
        registry, delta-based with engine-side marks — so a harness that
        swaps in a fresh ServingMetrics (eval_latency does, to shed
        warmup) sees only post-swap activity."""
        pc = self.prefix_cache
        if pc is None:
            return
        m, seen = self.metrics, self._pc_mirrored
        m.prefix_lookups.inc(pc.lookups - seen["lookups"])
        m.prefix_hit_tokens.inc(pc.hit_tokens - seen["hit_tokens"])
        m.prefix_evictions.inc(pc.evictions - seen["evictions"])
        seen.update(lookups=pc.lookups, hit_tokens=pc.hit_tokens,
                    evictions=pc.evictions)

    def _sample_host(self, logits: np.ndarray, req: Request):
        """Sample the request's next token from its one prefill logits
        row ``logits`` [1, V] — the EXACT per-row rule the decode step
        runs (same fold_in(seed, token-index) keying, same filters), off
        the hot loop. The token index is len(generated), so an
        eviction/replay re-prefill resumes the same stream. Returns
        (token, logp)."""
        if np.isnan(logits).any():
            # real detection on the only logits the host ever sees: the
            # serving analog of the trainer's NaN guard. The supervisor
            # turns this into a rebuild-and-replay.
            raise NaNLogitsError("non-finite prefill logits")
        sp = self._effective_sampling(req)

        def row(value, dtype):
            # through numpy: ``jnp.asarray`` of a Python list takes
            # 0.14 ms longer, five times a first token (PERF.md, PR 31)
            # dla: disable=host-sync-in-hot-loop -- host scalar -> numpy marshalling, no device fetch
            return jnp.asarray(np.array([value], dtype))
        toks, lps = _sample_rows_jit(
            row(sp.seed & 0xFFFFFFFF, np.uint32),
            row(len(req.generated), np.int32), jnp.asarray(logits),
            row(sp.effective_temperature, np.float32),
            row(sp.top_p, np.float32), row(sp.top_k, np.int32))
        # fetch whole, index on the host: ``toks[0]`` on the device array
        # would run a slice and a squeeze program before each fetch
        # dla: disable=host-sync-in-hot-loop -- prefill sample fetch: one D2H pair per admitted request
        return int(np.asarray(toks)[0]), float(np.asarray(lps)[0])

    def _decode_span(self, active_slots: List[int],
                     sampling_slots: int) -> annotate:
        """The decode phase's span. Its arguments are the KV read as the
        host knows it on entry: running slots, the tokens they hold, and
        the columns the step's program reads; and how many of the
        running slots sample. ``read_tokens`` follows the program: where
        the one-token step holds the paged attention kernel
        (``Transformer.paged_decode_kernel``: dense rows, or a layer-spec
        model whose rows of its own are all plain attention, Jamba's and
        Granite 4.0-H's) it is the running slots' live pages x the page
        size, from the host mirror of the lengths; where it gathers
        (latent rows, SambaY's differential rows, a backend that is no
        TPU, and the speculative verify forward whatever the model) it
        is every slot's whole window, a constant of the geometry."""
        geom = self.cache.geom
        if self._kernel_decode is None:
            # asked once, here: the first dispatch, which traces the
            # program, follows in this same context
            self._kernel_decode = self.model.paged_decode_kernel() is not None
        # dla: disable=host-sync-in-hot-loop -- host numpy mirror of the slot lengths, no device fetch
        lengths = self.cache.lengths[active_slots]
        whole = geom.num_slots * geom.slot_window
        selected = 0
        if self._kernel_decode:
            # the one-token forwards (the step, or a round's K drafts)
            # read live pages; a round's verify forward gathers
            live_pages = int((-(-lengths // geom.page_size)).sum())
            read_tokens = max(self._spec_k, 1) * live_pages \
                * geom.page_size + (whole if self._spec_k else 0)
        elif self._dsa_topk and not self._spec_k:
            # sparse attention reads the latent rows its indexer selects
            # (the index keys of every window it scores are the
            # indexer's, not counted here)
            selected = int(np.minimum(lengths + 1, self._dsa_topk).sum())
            read_tokens = selected
        else:
            read_tokens = (self._spec_k + 1) * whole
        return annotate(
            "serve_decode", slots=len(active_slots),
            live_tokens=int(lengths.sum()),
            read_tokens=read_tokens,
            selected_tokens=selected,
            sampling_slots=sampling_slots,
            # what the step touches beside the paged rows, constants of
            # the geometry (0 for a model with neither): the slots whose
            # recurrent state it reads and writes, and the columns one
            # window layer's gather reads
            state_slots=geom.num_slots if self._stateful else 0,
            window_read_tokens=geom.num_slots * geom.window_gather_pages
            * geom.page_size)

    def _sampling_slots(self, active_slots: List[int]) -> int:
        """Running slots whose request samples (temperature > 0): with
        none, the decode program's sampler takes its arg-max branch."""
        # dla: disable=host-sync-in-hot-loop -- host numpy mirror of the slots' temperatures, no device fetch
        return int(np.count_nonzero(self.samp_temp[active_slots] > 0.0))

    def _decode_args(self, active_slots: List[int]) -> tuple:
        """Everything a decode dispatch takes after the pool: the step's
        host state packed into one array (``_decode_layout``), built and
        put on the device inside ``serve_decode_args``, and the adapter
        pools. The span says what went up: ``puts`` 1, ``h2d_bytes`` the
        array's."""
        c = self.cache
        with annotate("serve_decode_args", puts=1,
                      h2d_bytes=self._decode_layout.nbytes(
                          c.geom.num_slots)):
            active = np.zeros((c.geom.num_slots,), bool)
            active[active_slots] = True
            for slot in active_slots:
                # the PRNG position of the (first) token this step
                # samples: the request's generated-token index (re-binds
                # every step so evicted/re-admitted requests resume their
                # stream exactly; a speculative round advances it as
                # gen_pos + i in-graph)
                self.gen_pos[slot] = len(
                    self.scheduler.running[slot].generated)
            packed = self._decode_layout.pack(
                c.geom.num_slots, block_tables=c.block_tables,
                window_tables=c.window_tables, lengths=c.lengths, tokens=c.tokens, active=active,
                top_k=self.samp_top_k, seed=self.samp_seed,
                gen_pos=self.gen_pos, temp=self.samp_temp,
                top_p=self.samp_top_p, adapter=self.adapter_idx)
            return self._put_step_args(packed), self._adapters_args()

    def _decode_step(self) -> List[Tuple[int, int]]:
        c = self.cache
        active_slots = sorted(self.scheduler.running)
        sampling_slots = self._sampling_slots(active_slots)
        with self._decode_span(active_slots, sampling_slots):
            args = self._decode_args(active_slots)
            if self._fault_device_error:
                # injected BEFORE dispatch: no KV column was written, no
                # token sampled — exactly the state a real dispatch
                # failure leaves behind, so supervisor replay recomputes
                # cleanly
                self._fault_device_error = False
                raise DeviceStepError(
                    "injected device error (fault plan engine_step)")
            with annotate("serve_decode_dispatch"):
                out = self._decode(self.params, c.pools, *args)
                c.pools, packed = out[:2]
                if self._dsa_topk:
                    # (the slots that ran, their columns before the step,
                    # the columns each query read) of the newest step
                    self.dsa_last_selection = (
                        list(active_slots),
                        c.lengths[active_slots].copy(), out[2])
            with annotate("serve_decode_fetch"):
                # dla: disable=host-sync-in-hot-loop -- the designed single D2H per decode step (execution-model invariant)
                packed_np = np.asarray(packed)
            toks_np = packed_np[0]
            logps_np = packed_np[1].view(np.float32)
            if self.model.cfg.num_experts:
                # rode the same fetch: which of the held experts this
                # step's rows chose, summed over layers
                hit, landed = int(packed_np[2, 0]), int(packed_np[3, 0])
                mark("serve_moe_route", experts_hit=hit,
                     expert_assignments=landed, slots=len(active_slots))
                self.metrics.moe_experts_hit.inc(hit)
                self.metrics.moe_expert_assignments.inc(landed)
            if self._fault_nan_logits:
                # injected AFTER the fetch, where the real NaN guard below
                # (_sample_host) and a device-side check would trip: the
                # sampled tokens are garbage, so nothing is committed
                self._fault_nan_logits = False
                raise NaNLogitsError(
                    "injected non-finite logits (fault plan engine_step)")
            t_done = self.now()
            self.metrics.decode_steps.inc()
            if sampling_slots:
                self.metrics.decode_steps_sampled.inc()
            if self._kernel_decode:
                self.metrics.decode_steps_paged_kernel.inc()
            if self._dsa_topk:
                self._count_dsa(c.lengths[active_slots] + 1)
            emitted: List[Tuple[int, int]] = []
            with annotate("serve_emit", slots=len(active_slots)):
                for slot in active_slots:
                    req = self.scheduler.running[slot]
                    tok = int(toks_np[slot])
                    c.advance_slot(slot, tok)
                    self._emit(req, tok, t_done, emitted,
                               logp=float(logps_np[slot]))  # dla: disable=host-sync-in-hot-loop -- host numpy scalar; rode the packed decode fetch
        return emitted

    def _spec_decode_step(self) -> List[Tuple[int, int]]:
        """One speculative ROUND for the whole decode batch: draft
        dispatch -> verify dispatch -> one packed D2H -> per-slot
        variable commit. The host metadata (valid/pos/lengths/tokens
        mirrors) is authoritative and only ever advances by the ACCEPTED
        prefix — rejected draft columns exist solely in device pages
        that the next round's verify overwrites, so rollback is a no-op
        and an eviction/replay re-prefill never sees speculative
        residue. Both dispatches read the same host-metadata snapshot
        (one packed array, put once); the draft extends its own traced
        copy of ``valid`` while the verify attends committed-only (draft
        keys arrive via the in-block causal term instead)."""
        c = self.cache
        k = self._spec_k
        active_slots = sorted(self.scheduler.running)
        sampling_slots = self._sampling_slots(active_slots)
        with self._decode_span(active_slots, sampling_slots):
            packed_args, adapters = self._decode_args(active_slots)
            if self._fault_device_error:
                # injected BEFORE dispatch: no KV column written, no token
                # sampled — the state a real dispatch failure leaves behind
                self._fault_device_error = False
                raise DeviceStepError(
                    "injected device error (fault plan engine_step)")
            with annotate("serve_decode_dispatch"):
                # draft and verify share one adapter view: the draft
                # proposes under the SAME per-slot deltas the target
                # verifies with, so per-tenant acceptance stays high
                c.pools, proposals = self._spec_draft(
                    self.draft_params, c.pools, packed_args, adapters)
                c.pools, packed = self._spec_verify(
                    self.params, c.pools, packed_args, proposals, adapters)
            with annotate("serve_decode_fetch"):
                # dla: disable=host-sync-in-hot-loop -- the designed single D2H per speculative round (proposals never leave the device)
                packed_np = np.asarray(packed)
            toks_np = packed_np[0]                        # [B, K+1]
            logps_np = packed_np[1].view(np.float32)
            acc_np = packed_np[2][:, 0]                   # [B] accepts 0..K
            if self._fault_nan_logits:
                # injected AFTER the fetch, where a real device-side NaN
                # would surface: nothing was committed, replay is clean
                self._fault_nan_logits = False
                raise NaNLogitsError(
                    "injected non-finite logits (fault plan engine_step)")
            t_done = self.now()
            self.metrics.decode_steps.inc()
            if sampling_slots:
                self.metrics.decode_steps_sampled.inc()
            if self._kernel_decode:
                self.metrics.decode_steps_paged_kernel.inc()
            if self._dsa_topk:
                self._count_dsa(c.lengths[active_slots] + 1)
            emitted: List[Tuple[int, int]] = []
            with annotate("serve_emit", slots=len(active_slots)):
                for slot in active_slots:
                    req = self.scheduler.running[slot]
                    a = int(acc_np[slot])
                    self._spec_stats["rounds"] += 1
                    self._spec_stats["proposed"] += k
                    self._spec_stats["accepted"] += a
                    if a < k:
                        self._spec_stats["rollbacks"] += 1
                    # commit the accepted prefix: a+1 target samples
                    # (column lengths+j holds block token j's target KV;
                    # the emitted token becomes the next pending).
                    # EOS/length may finish the request mid-block — the
                    # tail accepts are dropped, exactly as the
                    # non-speculative engine would never have sampled
                    # past the terminal token.
                    for j in range(a + 1):
                        tok = int(toks_np[slot, j])
                        c.advance_slot(slot, tok)
                        self._emit(req, tok, t_done, emitted,
                                   logp=float(logps_np[slot, j]))  # dla: disable=host-sync-in-hot-loop -- host numpy scalar; rode the packed round fetch
                        if self.scheduler.running.get(slot) is not req:
                            break
        return emitted

    def _mirror_spec_counters(self) -> None:
        """Delta-mirror the speculative round stats into the registry
        (same contract as the prefix-cache mirror: a fresh
        ServingMetrics swap sees only post-swap activity; the Supervisor
        re-seeds cumulative totals into rebuilt engines)."""
        if not self._spec_k:
            return
        m, s, seen = self.metrics, self._spec_stats, self._spec_mirrored
        m.spec_rounds.inc(s["rounds"] - seen["rounds"])
        m.spec_proposed.inc(s["proposed"] - seen["proposed"])
        m.spec_accepted.inc(s["accepted"] - seen["accepted"])
        m.spec_rollbacks.inc(s["rollbacks"] - seen["rollbacks"])
        seen.update(s)
        if m.spec_proposed.value > 0:
            m.spec_acceptance_rate.set(
                m.spec_accepted.value / m.spec_proposed.value)

    def _mirror_migration_counters(self) -> None:
        """Delta-mirror the KV migration stats into the registry (the
        prefix-cache/speculative mirror contract: a fresh ServingMetrics
        swap sees only post-swap activity; the Supervisor re-seeds
        cumulative totals into rebuilt engines so the counters stay
        monotone across restarts)."""
        m, s, seen = self.metrics, self._mig_stats, self._mig_mirrored
        m.migrations.inc(s["migrations"] - seen["migrations"])
        m.migrated_pages.inc(
            s["migrated_pages"] - seen["migrated_pages"])
        m.host_bounce_bytes.inc(
            s["host_bounce_bytes"] - seen["host_bounce_bytes"])
        m.failed_migrations.inc(
            s["failed_migrations"] - seen["failed_migrations"])
        seen.update(s)

    def _emit(self, req: Request, tok: int, t: float,
              emitted: List[Tuple[int, int]],
              first_of_prefill: bool = False,
              logp: float = 0.0) -> None:
        """Record one generated token: stream it, time it, finish the
        request on EOS or length. ``logp`` is the token's chosen-token
        logprob (raw model distribution), kept parallel to
        ``generated`` on the request's result surface."""
        req.generated.append(tok)
        req.generated_logprobs.append(float(logp))  # dla: disable=host-sync-in-hot-loop -- float coercion of an already-host scalar
        emitted.append((req.rid, tok))
        self.metrics.tokens_generated.inc()
        # per-tenant panel: same samples as the engine-wide instruments,
        # attributed — the surface the tenant SLO watches burn against
        ten = (self.tenants if req.tenant is not None else None)
        if ten is not None:
            ten.on_token(req.tenant)
        traced = self.tracer.enabled
        if req.first_token_time is None:
            req.first_token_time = t
            self.metrics.ttft_ms.record((t - req.arrival_time) * 1000.0)
            if ten is not None:
                ten.on_ttft(req.tenant, (t - req.arrival_time) * 1000.0)
            mark("serve_req_first_token", rid=req.rid)
            if traced:
                self.tracer.async_instant(
                    "request", "first_token", req.rid, t=t,
                    ttft_ms=(t - req.arrival_time) * 1000.0)
        elif not first_of_prefill and req.last_token_time is not None:
            # inter-token latency only between consecutive decode steps
            # (a re-prefill after eviction restarts the clock)
            itl_ms = (t - req.last_token_time) * 1000.0
            self.metrics.itl_ms.record(itl_ms)
            if ten is not None:
                ten.on_itl(req.tenant, itl_ms)
            if self.anomaly is not None:
                self.anomaly.observe("itl_ms", itl_ms, self.engine_steps)
            if traced:
                self.tracer.async_instant(
                    "request", "decode", req.rid, t=t,
                    n=len(req.generated),
                    itl_ms=(t - req.last_token_time) * 1000.0)
        req.last_token_time = t
        eos = self.gen.eos_token_id
        status = None
        if eos is not None and eos >= 0 and tok == eos:
            self.scheduler.finish(req, "eos")
            self.metrics.requests_finished.inc()
            status = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            self.scheduler.finish(req, "length")
            self.metrics.requests_finished.inc()
            status = "length"
        if ten is not None and status is not None:
            ten.on_finish(req.tenant)
        if status is not None:
            self._req_end(req, status, t=t)
