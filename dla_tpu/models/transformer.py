"""Decoder-only transformer (Llama-2 family), pure JAX, TPU-first.

Replaces the reference's HF ``AutoModelForCausalLM`` wrapper
(src/models/base_model.py:17-42). Design points that matter on TPU:

- **scan-over-layers**: per-layer params are stacked with a leading [L]
  dim and the block is applied with ``lax.scan`` — compile time is O(1) in
  depth and XLA sees one block to optimize.
- **PartitionSpec-annotated params**: ``partition_specs()`` mirrors the
  param pytree. ZeRO-3-equivalent sharding = the ``fsdp`` axis on one dim
  of every matrix (GSPMD all-gathers per use, like DeepSpeed stage-3,
  config/deepspeed_zero3.json:6); tensor parallelism = the ``model`` axis
  on attention heads / MLP hidden (megatron layout, new capability —
  SURVEY.md sec 2.3).
- **remat**: ``jax.checkpoint`` around the block body replaces
  ``gradient_checkpointing_enable`` (base_model.py:36-37).
- **mixed precision**: bf16 activations, fp32 master params; params are
  cast to the activation dtype at use so the MXU runs bf16.
- **KV-cache decode**: ``prefill``/``decode_step`` give the jitted
  autoregressive path HF ``generate`` provided for the reference
  (train_rlhf.py:123-124).
"""
from __future__ import annotations

import importlib
import math
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dla_tpu.models.config import CacheArray, ModelConfig
from dla_tpu.models.hybrid import HybridStack
from dla_tpu.parallel.mesh import auto_axes
from dla_tpu.utils.compile_cache import cached_bytecode
from dla_tpu.utils.profiling import startup_span
from dla_tpu.ops.attention import (
    block_decode_attention,
    causal_attention,
    chunked_causal_attention,
    decode_attention,
)
from dla_tpu.ops import selective_scan_kernel
from dla_tpu.ops.norms import layer_norm, rms_norm
from dla_tpu.ops.rotary import (
    apply_rotary,
    position_query_scale,
    rotary_angles,
    yarn_mscale,
)

Params = Dict[str, Any]

# Activation sharding: batch over the two batch axes, sequence over the
# context-parallel axis, features replicated (TP slices live inside the block).
ACT_SPEC = P(("data", "fsdp"), "sequence", None)


# shapes for which the replicated-flash fallback was already reported —
# trace-time, so one line per compiled shape, not per step
_REPLICATED_FLASH_LOGGED: set = set()

def _flash_tileable(t: int) -> bool:
    """Whether the Pallas flash kernel may take sequence length T.

    On hardware, mosaic tiles 128-wide MXU blocks: require T % 128 == 0
    (VERDICT r2 weak-item 7 — ``t % min(128, t)`` was vacuously true for
    any T < 128, letting flash engage with degenerate blocks on TPU).
    CPU runs the kernel in interpret mode where any divisor-of-128 tile
    is fine — that keeps the small-shape parity tests cheap."""
    if jax.default_backend() == "cpu":
        return t % min(128, t) == 0
    return t >= 128 and t % 128 == 0


def _tpu_backend() -> bool:
    """Whether programs traced now are compiled for a TPU (the Pallas
    kernels that have no XLA twin on other backends key off this)."""
    return jax.default_backend() == "tpu"


def _import_paged_kernel():
    """The module that holds the paged decode kernel. It imports Pallas,
    so nothing imports it at module level; its bytecode (and Pallas's) is
    kept beside the compile cache: on the chip's host 0.4 s from there,
    1.2 s from source (PERF.md, PR 35)."""
    with startup_span("startup_kernel_import",
                      module="dla_tpu.ops.paged_attention"), \
            cached_bytecode():
        return importlib.import_module("dla_tpu.ops.paged_attention")


def _flash_mesh():
    """The ambient mesh when flash attention must be shard_map-wrapped:
    a pallas_call has no SPMD partitioning rule, so under a >1-device
    mesh GSPMD would otherwise fully replicate the attention inputs
    (observed: output sharding collapses to PartitionSpec()). Axes that
    an enclosing shard_map already made manual (the `stage` axis inside
    the pipeline schedule) don't count: the kernel nests as a
    partial-manual shard_map over the remaining auto axes. Returns None
    on single-device / no-mesh / all->1-axes-already-manual (plain
    pallas_call is fine)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return None
    n = 1
    for name in auto_axes(mesh):  # any >1 AUTO axis replicates
        n *= mesh.shape[name]
    return mesh if n > 1 else None


def _constrain(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # outside a mesh context (plain single-device use)


def _ambient_mesh():
    """The ambient mesh (``jax.sharding.set_mesh``), or None when none
    is set."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh


def _sequence_axis_size() -> int:
    """Size of the `sequence` axis of the ambient mesh (1 if no mesh)."""
    mesh = _ambient_mesh()
    return mesh.shape.get("sequence", 1) if mesh is not None else 1


def _stage_axis_size() -> int:
    """Size of the `stage` (pipeline) axis of the ambient mesh."""
    mesh = _ambient_mesh()
    return mesh.shape.get("stage", 1) if mesh is not None else 1


class Transformer:
    """Functional model: a namespace of pure functions bound to a config."""

    def __init__(self, cfg: ModelConfig):
        with startup_span("startup_model_build", layers=cfg.num_layers,
                          kernel_imports="") as span:
            self._build(cfg)
            span.set(kernel_imports=",".join(self._start_kernel_imports()))

    def _build(self, cfg: ModelConfig) -> None:
        self.cfg = cfg
        self.adtype = jnp.dtype(cfg.dtype)
        self.pdtype = jnp.dtype(cfg.param_dtype)
        if self._interleaved_storage and cfg.num_layers % (
                cfg.pipeline_stages * cfg.pipeline_interleave):
            raise ValueError(
                f"pipeline_stages={cfg.pipeline_stages} x "
                f"pipeline_interleave={cfg.pipeline_interleave} must divide "
                f"num_layers={cfg.num_layers}")
        # gemma-2 scales attention by query_pre_attn_scalar**-0.5 (which
        # differs from head_dim**-0.5 on the 27B); None = op default
        self._softmax_scale = (
            cfg.query_pre_attn_scalar ** -0.5
            if cfg.query_pre_attn_scalar else None)
        if cfg.latent_attention:
            # YaRN's temperature on a latent head (the DeepSeek-V2
            # lineage's convention): qk_head_dim**-0.5 * m**2 with
            # m = 0.1 * mscale_all_dim * ln(factor) + 1
            rs = cfg.rope_scaling or {}
            m = yarn_mscale(float(rs.get("factor") or 1.0),
                            float(rs.get("mscale_all_dim") or 0.0))
            self._softmax_scale = cfg.head_dim_ ** -0.5 * m * m
        # a model whose layers are of several kinds (cfg.layers): its
        # runs of layers live in models/hybrid.py. Its attention ops see
        # differential pairs, rows twice a head wide, so the scale is
        # stated, not read off the operands
        self.hybrid: Optional[HybridStack] = None
        if cfg.layers is not None:
            self.hybrid = HybridStack(self)
            self._softmax_scale = cfg.head_dim_ ** -0.5

    # ------------------------------------------------------- storage layout

    @property
    def _interleaved_storage(self) -> bool:
        """Whether stacked layer leaves are stored [V, S, c, ...] instead
        of [L, ...]. The circular/interleaved pipeline schedule assigns
        block b = p*S + s to stage s; with flat [L] storage sharded
        contiguously over `stage`, GSPMD must exchange ~(V-1)/V of every
        layer weight across the stage ring EVERY step (measured: one
        weight-shaped all-to-all per layer leaf per step, r5 HLO probe).
        Because block-major [V, S, c] is exactly the row-major reshape of
        the canonical [L] stack, storing that 3-D leading shape and
        sharding dim 1 over `stage` makes the round-robin ownership
        shard-local with ZERO data reordering — flattening back to [L]
        is a free reshape off-mesh. Enabled by cfg.pipeline_stages (set
        from hardware.mesh.stage by the config loader when
        pipeline_interleave > 1)."""
        return (self.cfg.pipeline_stages > 1
                and self.cfg.pipeline_interleave > 1)

    def _storage_lead(self) -> Tuple[int, int, int]:
        cfg = self.cfg
        v, s = cfg.pipeline_interleave, cfg.pipeline_stages
        return v, s, cfg.num_layers // (v * s)

    def _map_layer_stack(self, tree: Params, fn) -> Params:
        """Apply ``fn`` to every stacked leaf under tree["layers"]
        (shallow copy elsewhere). Trees without a "layers" key pass
        through unchanged."""
        if not isinstance(tree, dict) or "layers" not in tree:
            return tree
        return {**tree,
                "layers": {k: fn(v) for k, v in tree["layers"].items()}}

    def to_storage_layout(self, tree: Params) -> Params:
        """Canonical [L, ...] layer stacks -> the model's storage layout
        ([V, S, c, ...] when interleaved storage is on; identity
        otherwise). Idempotent: leaves already in storage shape pass
        through. Use after building canonical trees (HF import, external
        tools) before handing them to this model."""
        if not self._interleaved_storage:
            return tree
        v, s, c = self._storage_lead()

        def go(x):
            if x.shape[:3] == (v, s, c):
                return x
            return x.reshape((v, s, c) + x.shape[1:])
        return self._map_layer_stack(tree, go)

    def to_canonical_layout(self, tree: Params) -> Params:
        """Inverse of to_storage_layout (for export / plain-scan paths)."""
        if not self._interleaved_storage:
            return tree
        n = self.cfg.num_layers

        def go(x):
            if x.shape[0] == n:
                return x
            return x.reshape((n,) + x.shape[3:])
        return self._map_layer_stack(tree, go)

    def _flat_layers(self, layers: Params) -> Params:
        """Layer dict in canonical flat [L, ...] form for plain
        scan-over-layers paths (free reshape: block-major storage IS
        canonical row-major order)."""
        if not self._interleaved_storage:
            return layers
        n = self.cfg.num_layers
        return {k: (v.reshape((n,) + v.shape[3:])
                    if v.shape[0] != n else v)
                for k, v in layers.items()}

    def _storage_spec(self, spec: P) -> P:
        """Layer-stack PartitionSpec for the storage layout: the leading
        P("stage", *rest) becomes P(None, "stage", None, *rest) — the
        stage axis moves to the middle (block-index) dim."""
        if not self._interleaved_storage:
            return spec
        return P(None, "stage", None, *spec[1:])

    # ------------------------------------------------------------------ init

    def init(self, rng: jax.Array) -> Params:
        return self.to_storage_layout(self._init_canonical(rng))

    def _start_kernel_imports(self) -> List[str]:
        """If this model's paged decode step will run the Pallas kernel
        (``_paged_kernel_rows`` on a TPU backend), start importing the
        kernel's module on a daemon thread; returns the names of the
        threads started. The import is 0.4 s of Python
        from the bytecode cache (1.2 s from source: Pallas, most of it)
        that the first decode trace would otherwise wait for; every entry
        point builds the model before it makes the weights, so started
        here it is over before an engine exists. A thread hides Python
        only behind a wait (5.3 s for the 1.3 s import beside a main
        thread that runs Python): with the import at 1.2 s it cost the
        weights phase 0.7 to 1.0 s here and the end of ``init``'s trace
        was the better place; at 0.4 s it costs that phase nothing that
        shows and the set-up 0.35 s less than from there (PERF.md, PR 35:
        the phases of each build). The in-line import in
        ``paged_decode_kernel`` stays and is what correctness rests on:
        the per-module import lock makes it wait for this one, never
        race it."""
        started = []
        if self._paged_kernel_rows and _tpu_backend():
            threading.Thread(
                target=_import_paged_kernel,
                name="dla-paged-kernel-import", daemon=True).start()
            started.append("dla-paged-kernel-import")
        if self._scan_kernel_layers and _tpu_backend():
            # the same for the chunk's selective-scan kernel: its module
            # names Pallas inside its functions only, ``pallas()`` is
            # the import
            threading.Thread(
                target=selective_scan_kernel.pallas,
                name="dla-scan-kernel-import", daemon=True).start()
            started.append("dla-scan-kernel-import")
        return started

    def _init_canonical(self, rng: jax.Array) -> Params:
        cfg = self.cfg
        dh = cfg.head_dim_
        qdim, kvdim = cfg.num_heads * dh, cfg.num_kv_heads * dh
        keys = jax.random.split(rng, 8)
        std = 0.02
        out_std = std / (2 * cfg.num_layers) ** 0.5  # gpt-2-style depth scaling

        def mat(key, shape, scale):
            return (jax.random.normal(key, shape, jnp.float32) * scale
                    ).astype(self.pdtype)

        L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
        if self.hybrid is not None:
            params = {
                "embed": {"embedding": mat(keys[0], (cfg.vocab_size, D), std)},
                "layers": self.hybrid.init(keys[1]),
                "final_norm": jnp.ones((D,), self.pdtype),
            }
            if cfg.norm == "layer":
                params["final_norm_bias"] = jnp.zeros((D,), self.pdtype)
            if not cfg.tie_embeddings:
                params["lm_head"] = mat(
                    jax.random.fold_in(rng, 99), (D, cfg.vocab_size), std)
            return params
        if cfg.arch == "phi":
            # parallel-residual block: one shared input LayerNorm, biased
            # projections, non-gated GELU MLP (fc1/fc2)
            params = {
                "embed": {"embedding": mat(keys[0], (cfg.vocab_size, D), std)},
                "layers": {
                    "ln": jnp.ones((L, D), self.pdtype),
                    "ln_bias": jnp.zeros((L, D), self.pdtype),
                    "wq": mat(keys[1], (L, D, qdim), std),
                    "wq_bias": jnp.zeros((L, qdim), self.pdtype),
                    "wk": mat(keys[2], (L, D, kvdim), std),
                    "wk_bias": jnp.zeros((L, kvdim), self.pdtype),
                    "wv": mat(keys[3], (L, D, kvdim), std),
                    "wv_bias": jnp.zeros((L, kvdim), self.pdtype),
                    "wo": mat(keys[4], (L, qdim, D), out_std),
                    "wo_bias": jnp.zeros((L, D), self.pdtype),
                    "fc1": mat(keys[5], (L, D, F), std),
                    "fc1_bias": jnp.zeros((L, F), self.pdtype),
                    "fc2": mat(keys[6], (L, F, D), out_std),
                    "fc2_bias": jnp.zeros((L, D), self.pdtype),
                },
                "final_norm": jnp.ones((D,), self.pdtype),
                "final_norm_bias": jnp.zeros((D,), self.pdtype),
            }
            if not cfg.tie_embeddings:
                params["lm_head"] = mat(
                    jax.random.fold_in(rng, 99), (D, cfg.vocab_size), std)
                params["lm_head_bias"] = jnp.zeros(
                    (cfg.vocab_size,), self.pdtype)
            return params
        if cfg.num_experts > 0:
            # the router scores every expert; the weights are those of
            # the experts held here (all of them unless the config says)
            E, EH, F = cfg.num_experts, cfg.experts_held_, cfg.expert_width_
            mlp = {
                "router": mat(jax.random.fold_in(rng, 7), (L, D, E), std),
                "w_gate": mat(keys[5], (L, EH, D, F), std),
                "w_up": mat(keys[6], (L, EH, D, F), std),
                "w_down": mat(keys[7], (L, EH, F, D), out_std),
            }
            if cfg.num_shared_experts:
                FS = cfg.num_shared_experts * F
                mlp["ws_gate"] = mat(
                    jax.random.fold_in(rng, 11), (L, D, FS), std)
                mlp["ws_up"] = mat(
                    jax.random.fold_in(rng, 12), (L, D, FS), std)
                mlp["ws_down"] = mat(
                    jax.random.fold_in(rng, 13), (L, FS, D), out_std)
        else:
            mlp = {
                "w_gate": mat(keys[5], (L, D, F), std),
                "w_up": mat(keys[6], (L, D, F), std),
                "w_down": mat(keys[7], (L, F, D), out_std),
            }
        if cfg.latent_attention:
            H, r = cfg.num_heads, cfg.kv_lora_rank
            nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim)
            attn = {
                "wkv_a": mat(keys[2], (L, D, r + rope), std),
                "kv_norm": jnp.ones((L, r), self.pdtype),
                # per head [k_nope | v], heads outermost
                "wkv_b": mat(keys[3], (L, r, H * (nope + vd)), std),
                "wo": mat(keys[4], (L, H * vd, D), out_std),
            }
            if cfg.q_lora_rank:
                attn["wq_a"] = mat(keys[1], (L, D, cfg.q_lora_rank), std)
                attn["q_norm"] = jnp.ones((L, cfg.q_lora_rank), self.pdtype)
                attn["wq_b"] = mat(jax.random.fold_in(rng, 14),
                                   (L, cfg.q_lora_rank, qdim), std)
            else:
                attn["wq"] = mat(keys[1], (L, D, qdim), std)
        else:
            attn = {
                "wq": mat(keys[1], (L, D, qdim), std),
                "wk": mat(keys[2], (L, D, kvdim), std),
                "wv": mat(keys[3], (L, D, kvdim), std),
                "wo": mat(keys[4], (L, qdim, D), out_std),
            }
        params: Params = {
            "embed": {"embedding": mat(keys[0], (cfg.vocab_size, D), std)},
            "layers": {
                "attn_norm": jnp.ones((L, D), self.pdtype),
                **attn,
                "mlp_norm": jnp.ones((L, D), self.pdtype),
                **mlp,
            },
            "final_norm": jnp.ones((D,), self.pdtype),
        }
        if cfg.arch == "gemma2":  # post-attn / post-ffw norms (4 per block)
            params["layers"]["attn_post_norm"] = jnp.ones((L, D), self.pdtype)
            params["layers"]["mlp_post_norm"] = jnp.ones((L, D), self.pdtype)
        if cfg.attention_bias:  # qwen2-style q/k/v biases
            params["layers"]["wq_bias"] = jnp.zeros((L, qdim), self.pdtype)
            params["layers"]["wk_bias"] = jnp.zeros((L, kvdim), self.pdtype)
            params["layers"]["wv_bias"] = jnp.zeros((L, kvdim), self.pdtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = mat(
                jax.random.fold_in(rng, 99), (D, cfg.vocab_size), std)
        return params

    # ----------------------------------------------------------------- LoRA

    # target -> (in-dim key, out-dim key) of the base matrix [L, in, out]
    _LORA_SHAPES = {
        "wq": ("hidden", "q"), "wk": ("hidden", "kv"), "wv": ("hidden", "kv"),
        "wo": ("q", "hidden"), "w_gate": ("hidden", "ffn"),
        "w_up": ("hidden", "ffn"), "w_down": ("ffn", "hidden"),
        "fc1": ("hidden", "ffn"), "fc2": ("ffn", "hidden"),  # phi MLP
    }

    def _lora_dims(self):
        cfg = self.cfg
        dh = cfg.head_dim_
        return {"hidden": cfg.hidden_size, "q": cfg.num_heads * dh,
                "kv": cfg.num_kv_heads * dh, "ffn": cfg.intermediate_size}

    def init_lora(self, rng: jax.Array) -> Params:
        """Adapter pytree for cfg.lora_targets: per target, A [L, in, r]
        (gaussian) and B [L, r, out] (zeros) — the functional version of the
        reference's dead ``freeze_except_lora``/``model.lora`` surface
        (reference base_model.py:45-49, config/distill_config.yaml:10-14)."""
        cfg = self.cfg
        if cfg.lora_r <= 0:
            raise ValueError("init_lora requires lora_r > 0")
        dims = self._lora_dims()
        layers: Params = {}
        for i, t in enumerate(cfg.lora_targets):
            din, dout = (dims[k] for k in self._LORA_SHAPES[t])
            key = jax.random.fold_in(rng, i)
            layers[f"{t}_lora_a"] = (
                jax.random.normal(key, (cfg.num_layers, din, cfg.lora_r),
                                  jnp.float32) * 0.02).astype(self.pdtype)
            layers[f"{t}_lora_b"] = jnp.zeros(
                (cfg.num_layers, cfg.lora_r, dout), self.pdtype)
        return self.to_storage_layout({"layers": layers})

    def lora_partition_specs(self) -> Params:
        """A shards its input dim like the base matrix; B its output dim."""
        base = {
            "wq": P(None, "fsdp", "model"), "wk": P(None, "fsdp", "model"),
            "wv": P(None, "fsdp", "model"), "wo": P(None, "model", "fsdp"),
            "w_gate": P(None, "fsdp", "model"),
            "w_up": P(None, "fsdp", "model"),
            "w_down": P(None, "model", "fsdp"),
            "fc1": P(None, "fsdp", "model"),     # phi MLP
            "fc2": P(None, "model", "fsdp"),
        }
        layers: Params = {}
        for t in self.cfg.lora_targets:
            spec = base[t]
            layers[f"{t}_lora_a"] = self._storage_spec(
                P("stage", spec[1], None))
            layers[f"{t}_lora_b"] = self._storage_spec(
                P("stage", None, spec[2]))
        return {"layers": layers}

    def merge_lora(self, params: Params, lora: Params) -> Params:
        """Fold adapters into a standalone param tree (for decode/export:
        the KV-cache generation path runs merged weights)."""
        cfg = self.cfg
        scale = cfg.lora_alpha / cfg.lora_r
        out = jax.tree.map(lambda x: x, params)  # shallow-ish copy
        new_layers = dict(out["layers"])
        for t in cfg.lora_targets:
            a = lora["layers"][f"{t}_lora_a"].astype(jnp.float32)
            b = lora["layers"][f"{t}_lora_b"].astype(jnp.float32)
            # "..." leading dims: [L] canonical or [V, S, c] storage
            delta = jnp.einsum("...ir,...ro->...io", a, b) * scale
            new_layers[t] = (new_layers[t].astype(jnp.float32) + delta
                             ).astype(new_layers[t].dtype)
        out["layers"] = new_layers
        return out

    def _lora_proj(self, layer: Params, name: str, x: jnp.ndarray,
                   base_out: jnp.ndarray,
                   dropout_key: Optional[jax.Array]) -> jnp.ndarray:
        """base_out + scale * dropout(x) @ A @ B when adapters are present."""
        a = layer.get(f"{name}_lora_a")
        if a is None:
            return base_out
        cfg = self.cfg
        b_ = layer[f"{name}_lora_b"]
        z = x
        if dropout_key is not None and cfg.lora_dropout > 0:
            idx = list(cfg.lora_targets).index(name)
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_key, idx),
                1.0 - cfg.lora_dropout, z.shape)
            z = jnp.where(keep, z / (1.0 - cfg.lora_dropout), 0.0)
        scale = cfg.lora_alpha / cfg.lora_r
        return base_out + ((z @ a.astype(self.adtype))
                           @ b_.astype(self.adtype)) * scale

    def slot_lora_xs(self, adapters: Optional[Params]) -> Params:
        """Per-slot LoRA leaves for the paged decode scans: gather each
        batch row's adapter from the stacked ``[N, L, din, r]`` pools by
        ``adapters["idx"]`` ([B] int32) and move the layer axis leading
        ([L, B, din, r]) so the leaves ride the layer scan like
        ``swa_on``. Keys are renamed ``_lora_`` -> ``_slot_lora_`` so
        the training-path ``_lora_proj`` never sees them; pool B factors
        are expected pre-scaled by alpha/r (AdapterStore's publish
        contract), so the in-graph delta is a bare x@A@B. ``None``
        (tenancy off) contributes nothing — the decode graph is
        byte-identical to the adapter-free build."""
        if adapters is None:
            return {}
        idx = adapters["idx"]
        out: Params = {}
        for key, pool in adapters.items():
            if key == "idx":
                continue
            g = jnp.take(pool, idx, axis=0)        # [B, L, din, r]
            out[key.replace("_lora_", "_slot_lora_")] = \
                jnp.moveaxis(g, 0, 1)              # [L, B, din, r]
        return out

    # ------------------------------------------------------- partition specs

    def partition_specs(self) -> Params:
        specs = self._partition_specs_canonical()
        return self._map_layer_stack(
            specs, self._storage_spec) if self._interleaved_storage \
            else specs

    def _partition_specs_canonical(self) -> Params:
        """PartitionSpec pytree mirroring ``init``'s output.

        fsdp shards the embedding/hidden dim; model shards heads / MLP
        hidden / vocab (megatron). Stacked layer leaves lead with the
        ``stage`` axis — pipeline parallelism is "shard the layer stack":
        each stage owns a contiguous block of layers (no-op at stage=1,
        where the axis prunes away).

        The token-embedding table is deliberately NOT model-sharded: a
        gather whose operand is sharded on the indexed (vocab) dim forces
        the SPMD partitioner to rematerialize the full table on every
        forward ("involuntary full rematerialization"), paying a
        model-axis all-gather per step. P("fsdp", None) keeps the memory
        win (ZeRO-3 shard over fsdp, gathered at use like every other
        matrix) with zero TP-axis traffic on the embed path.
        """
        if self.hybrid is not None:
            specs = {"embed": {"embedding": P("fsdp", None)},
                     "layers": self.hybrid.partition_specs(),
                     "final_norm": P(None)}
            if self.cfg.norm == "layer":
                specs["final_norm_bias"] = P(None)
            if not self.cfg.tie_embeddings:
                specs["lm_head"] = P("fsdp", "model")
            return specs
        if self.cfg.arch == "phi":
            specs = {
                "embed": {"embedding": P("fsdp", None)},
                "layers": {
                    "ln": P("stage", None), "ln_bias": P("stage", None),
                    "wq": P("stage", "fsdp", "model"),
                    "wq_bias": P("stage", "model"),
                    "wk": P("stage", "fsdp", "model"),
                    "wk_bias": P("stage", "model"),
                    "wv": P("stage", "fsdp", "model"),
                    "wv_bias": P("stage", "model"),
                    "wo": P("stage", "model", "fsdp"),
                    "wo_bias": P("stage", None),
                    "fc1": P("stage", "fsdp", "model"),
                    "fc1_bias": P("stage", "model"),
                    "fc2": P("stage", "model", "fsdp"),
                    "fc2_bias": P("stage", None),
                },
                "final_norm": P(None),
                "final_norm_bias": P(None),
            }
            if not self.cfg.tie_embeddings:
                specs["lm_head"] = P("fsdp", "model")
                specs["lm_head_bias"] = P("model")
            return specs
        if self.cfg.num_experts > 0:
            mlp_specs = {
                "router": P("stage", "fsdp", None),
                "w_gate": P("stage", "expert", "fsdp", "model"),
                "w_up": P("stage", "expert", "fsdp", "model"),
                "w_down": P("stage", "expert", "model", "fsdp"),
            }
            if self.cfg.num_shared_experts:
                mlp_specs["ws_gate"] = P("stage", "fsdp", "model")
                mlp_specs["ws_up"] = P("stage", "fsdp", "model")
                mlp_specs["ws_down"] = P("stage", "model", "fsdp")
        else:
            mlp_specs = {
                "w_gate": P("stage", "fsdp", "model"),
                "w_up": P("stage", "fsdp", "model"),
                "w_down": P("stage", "model", "fsdp"),
            }
        if self.cfg.latent_attention:
            # the bottlenecks are replicated over `model`; the per-head
            # up-projections shard their head dim like wq / wo
            attn_specs = {
                "wkv_a": P("stage", "fsdp", None),
                "kv_norm": P("stage", None),
                "wkv_b": P("stage", None, "model"),
                "wo": P("stage", "model", "fsdp"),
            }
            if self.cfg.q_lora_rank:
                attn_specs["wq_a"] = P("stage", "fsdp", None)
                attn_specs["q_norm"] = P("stage", None)
                attn_specs["wq_b"] = P("stage", None, "model")
            else:
                attn_specs["wq"] = P("stage", "fsdp", "model")
        else:
            attn_specs = {
                "wq": P("stage", "fsdp", "model"),
                "wk": P("stage", "fsdp", "model"),
                "wv": P("stage", "fsdp", "model"),
                "wo": P("stage", "model", "fsdp"),
            }
        specs: Params = {
            "embed": {"embedding": P("fsdp", None)},
            "layers": {
                "attn_norm": P("stage", None),
                **attn_specs,
                "mlp_norm": P("stage", None),
                **mlp_specs,
            },
            "final_norm": P(None),
        }
        if self.cfg.arch == "gemma2":
            specs["layers"]["attn_post_norm"] = P("stage", None)
            specs["layers"]["mlp_post_norm"] = P("stage", None)
        if self.cfg.attention_bias:
            specs["layers"]["wq_bias"] = P("stage", "model")
            specs["layers"]["wk_bias"] = P("stage", "model")
            specs["layers"]["wv_bias"] = P("stage", "model")
        if not self.cfg.tie_embeddings:
            specs["lm_head"] = P("fsdp", "model")
        return specs

    # ---------------------------------------------------------------- block

    def _block(self, layer: Params, x: jnp.ndarray,
               cos: jnp.ndarray, sin: jnp.ndarray,
               kv_segment_mask: Optional[jnp.ndarray],
               q_positions: jnp.ndarray,
               kv_positions: jnp.ndarray,
               kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               allow_flash: bool = False,
               flash_segs: Optional[jnp.ndarray] = None,
               cp: Optional[Tuple] = None,
               dropout_key: Optional[jax.Array] = None,
               token_valid: Optional[jnp.ndarray] = None,  # [B, T] for MoE
               factored_mask: Optional[Tuple] = None,  # (valid, segments)
               dropless: bool = False,
               ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...], Any]:
        """One decoder block. Returns (output, cache rows, moe aux) — the
        rows are what a cache stores per token, before any override: (k, v)
        for dense attention, (latent row,) for latent attention.
        ``layer`` may carry LoRA leaves (merged upstream). ``dropless``:
        the serving entry points route experts with no capacity."""
        cfg = self.cfg
        dh = cfg.head_dim_
        rd = cfg.rotary_dim_
        b, t, d = x.shape

        def cast(w):
            return w.astype(self.adtype)

        def proj(name, inp):
            out = self._dense(layer, name, inp)
            bias = layer.get(f"{name}_bias")
            if bias is not None:
                out = out + cast(bias)
            return self._lora_proj(layer, name, inp, out, dropout_key)

        if cfg.arch == "phi":
            h = layer_norm(x, layer["ln"], layer["ln_bias"],
                           cfg.rms_norm_eps)
        else:
            h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        if cfg.latent_attention:
            with jax.named_scope("mla_attention"):
                q, k, v, row = self._latent_expanded(
                    layer, h, cos, sin, q_positions)
                q = _constrain(
                    q, P(("data", "fsdp"), "sequence", "model", None))
                k = _constrain(
                    k, P(("data", "fsdp"), "sequence", "model", None))
                attn = self._attention(
                    q, k, v, kv_segment_mask, q_positions, kv_positions,
                    allow_flash, cp, flash_segs=flash_segs,
                    factored_mask=factored_mask)
                attn_out = proj("wo", attn.reshape(b, t, -1))
            new_kv = (row,)
        else:
            q = proj("wq", h).reshape(b, t, cfg.num_heads, dh)
            k = proj("wk", h).reshape(b, t, cfg.num_kv_heads, dh)
            v = proj("wv", h).reshape(b, t, cfg.num_kv_heads, dh)
            q = _constrain(q, P(("data", "fsdp"), "sequence", "model", None))
            k = _constrain(k, P(("data", "fsdp"), "sequence", "model", None))
            q = apply_rotary(q, cos, sin, rotary_dim=rd)
            k = apply_rotary(k, cos, sin, rotary_dim=rd)
            new_kv = (k, v)
            if kv_override is not None:
                k, v = kv_override
            attn = self._attention(q, k, v, kv_segment_mask,
                                   q_positions, kv_positions, allow_flash,
                                   cp, flash_segs=flash_segs,
                                   window=self._layer_window(layer),
                                   factored_mask=factored_mask)
            attn = attn.reshape(b, t, cfg.num_heads * dh)

            if cfg.arch == "phi":
                # parallel residual: attention and MLP both read the
                # shared h
                attn_out = _constrain(proj("wo", attn), ACT_SPEC)
                ff = _constrain(
                    jax.nn.gelu(proj("fc1", h), approximate=True),
                    P(("data", "fsdp"), "sequence", "model"))
                mlp_out = _constrain(proj("fc2", ff), ACT_SPEC)
                return x + attn_out + mlp_out, new_kv, None

            attn_out = proj("wo", attn)
        if cfg.arch == "gemma2":  # post-attn norm BEFORE the residual add
            attn_out = rms_norm(attn_out, layer["attn_post_norm"],
                                cfg.rms_norm_eps)
        x = x + _constrain(attn_out, ACT_SPEC)
        h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        mlp_out, moe_aux = self._mlp(layer, h, proj, token_valid,
                                     dropless=dropless)
        if cfg.arch == "gemma2":
            mlp_out = rms_norm(mlp_out, layer["mlp_post_norm"],
                               cfg.rms_norm_eps)
        x = x + _constrain(mlp_out, ACT_SPEC)
        return x, new_kv, moe_aux

    # ----------------------------------------------------- latent attention

    def _latent_project(self, layer: Params, hn: jnp.ndarray, cos, sin,
                        q_positions: jnp.ndarray):
        """The projections both forms of latent attention share. hn
        [B, T, D] (post attn_norm) -> q_nope [B, T, H, nope], q_rope
        [B, T, H, rope] (rotated, and both carrying the position-dependent
        query scale), ckv [B, T, r] (normalised latent), kr [B, T, 1,
        rope] (the one rotated key all heads share). The cached row is
        [ckv | kr]."""
        cfg = self.cfg
        b, t, _ = hn.shape
        nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        if cfg.q_lora_rank:
            cq = rms_norm(self._dense(layer, "wq_a", hn), layer["q_norm"],
                          cfg.rms_norm_eps)
            q = self._dense(layer, "wq_b", cq)
        else:
            q = self._dense(layer, "wq", hn)
        q = q.reshape(b, t, cfg.num_heads, cfg.head_dim_)
        kv = self._dense(layer, "wkv_a", hn)                # [B, T, r+rope]
        ckv = rms_norm(kv[..., :r], layer["kv_norm"], cfg.rms_norm_eps)
        kr = apply_rotary(kv[:, :, None, r:], cos, sin,
                          interleave=cfg.rope_interleave)
        q_nope = q[..., :nope]
        q_rope = apply_rotary(q[..., nope:], cos, sin,
                              interleave=cfg.rope_interleave)
        q_scale = position_query_scale(q_positions, cfg.rope_scaling)
        if q_scale is not None:
            q_scale = q_scale[..., None, None].astype(q.dtype)
            q_nope, q_rope = q_nope * q_scale, q_rope * q_scale
        return q_nope, q_rope, ckv, kr

    def _latent_expanded(self, layer: Params, hn: jnp.ndarray, cos, sin,
                         q_positions: jnp.ndarray):
        """Expanded form (full sequences): every token's latent goes up
        through wkv_b to per-head keys and values. Returns q, k
        [B, T, H, nope+rope], v [B, T, H, v] and the cache row
        [B, T, 1, r+rope]."""
        cfg = self.cfg
        b, t, _ = hn.shape
        h_, nope = cfg.num_heads, cfg.qk_nope_head_dim
        q_nope, q_rope, ckv, kr = self._latent_project(
            layer, hn, cos, sin, q_positions)
        kvb = self._dense(layer, "wkv_b", ckv).reshape(
            b, t, h_, nope + cfg.v_head_dim)
        k = jnp.concatenate(
            [kvb[..., :nope],
             jnp.broadcast_to(kr, (b, t, h_, cfg.qk_rope_head_dim))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        return q, k, kvb[..., nope:], self._latent_row(ckv, kr)

    def _latent_row(self, ckv: jnp.ndarray, kr: jnp.ndarray,
                    heads: int = 1) -> jnp.ndarray:
        """[ckv | kr | zeros] along the last axis, ``heads`` in between:
        the cached row (heads = 1), and the absorbed query laid out like
        it, both ``cfg.latent_row_width_`` wide."""
        cfg = self.cfg
        parts = [ckv[:, :, None, :] if heads == 1 else ckv, kr]
        pad = cfg.latent_row_width_ - cfg.kv_lora_rank - cfg.qk_rope_head_dim
        if pad:
            parts.append(jnp.zeros(kr.shape[:-1] + (pad,), kr.dtype))
        return jnp.concatenate(parts, -1)

    def _latent_absorbed(self, layer: Params, hn: jnp.ndarray, cos, sin,
                         q_positions: jnp.ndarray, attend):
        """Absorbed form (decode against cached rows): wkv_b's key half
        moves onto the query and its value half onto the output, so the
        cache is read as it is stored — one [r+rope] row a token, shared
        by all heads (multi-query attention over the rows, the value
        being the row's first r numbers). Same numbers as the expanded
        form. ``attend(q, k_new, v_new) -> [B, T, H, r+rope]`` is the
        path's attention over (cached rows, new rows); rows and the
        absorbed query are ``latent_row_width_`` wide, zeros past r +
        rope. Returns ([B, T, H * v], row)."""
        cfg = self.cfg
        b, t, _ = hn.shape
        h_, nope, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q_nope, q_rope, ckv, kr = self._latent_project(
            layer, hn, cos, sin, q_positions)
        wkv_b = self._weight(layer, "wkv_b").reshape(
            r, h_, nope + cfg.v_head_dim)
        q_lat = self._latent_row(
            jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b[..., :nope]),
            q_rope, heads=h_)                            # [B, T, H, row]
        row = self._latent_row(ckv, kr)
        # the row is key and value at once: the weighted sum over whole
        # rows costs half more multiplies than over their first r numbers
        # and spares a sliced copy of the gathered window
        u = attend(q_lat, row, row)[..., :r]
        out = jnp.einsum("bthr,rhv->bthv", u, wkv_b[..., nope:])
        return out.reshape(b, t, h_ * cfg.v_head_dim), row

    def cache_rows(self) -> Tuple[Tuple[int, int], ...]:
        """What one token stores per layer, as (heads, width) per pool:
        keys and values of [KH, D] for dense attention, one lane-padded
        [1, r + rope] latent row for latent attention. A paged pool
        allocates one [L, pages, page_size, heads, width] array per
        entry; the paged steps take and return tuples in this order."""
        cfg = self.cfg
        if cfg.latent_attention:
            return ((1, cfg.latent_row_width_),)
        return ((cfg.num_kv_heads, cfg.head_dim_),) * 2

    @property
    def _paged_kernel_rows(self) -> bool:
        """Whether this model's cached rows are ones the paged decode
        kernel (ops/paged_attention.py) reads: the dense pair of keys
        and values ``[K, D]`` with ``D`` a multiple of 128 lanes, the
        kv heads of a token filling whole 32-bit words, a GQA group
        inside the kernel's 8 sublanes. Latent rows (one pool), a
        per-layer spec (models/hybrid.py's own gathers) and int8 pages
        are not: they keep the gather."""
        cfg = self.cfg
        return (not cfg.latent_attention and self.hybrid is None
                and not self._kv_int8
                and cfg.head_dim_ % 128 == 0
                and (cfg.num_kv_heads * self.adtype.itemsize) % 4 == 0
                and cfg.num_heads // cfg.num_kv_heads <= 8)

    def paged_decode_kernel(self):
        """The module of the paged attention kernel if a
        ``decode_step_paged`` traced now, under the ambient mesh, would
        run it, else None (the gather). What is observed, no knob: the
        rows (``_paged_kernel_rows``), a TPU backend (Mosaic compiles
        for nothing else), and no multi-device auto mesh (a
        ``pallas_call`` has no SPMD rule: GSPMD would replicate the
        pools). Imports the module the first time, unless the
        constructor's thread has by then."""
        if (self._paged_kernel_rows and _tpu_backend()
                and _flash_mesh() is None):
            return _import_paged_kernel()
        return None

    @property
    def _scan_kernel_layers(self) -> bool:
        """Whether this model has state-space layers of sizes the
        chunk's selective-scan kernel (ops/selective_scan_kernel.py)
        takes at some chunk length."""
        cfg = self.cfg
        return (self.hybrid is not None
                and any(s.mixer == "ssm" for s in self.hybrid.spec)
                and selective_scan_kernel.takes(
                    selective_scan_kernel.TOKEN_ALIGN, cfg.ssm_inner_,
                    cfg.ssm_state_size))

    def scan_chunk_kernel(self, tokens: int):
        """The selective-scan kernel's module, Pallas imported, if a
        paged chunk program of ``tokens`` tokens traced now, under the
        ambient mesh, would run it, else None (``selective_scan_chunk``,
        XLA's form). What is observed, no knob: state-space layers of
        sizes and a chunk of a length the kernel takes (``takes``), a
        TPU backend (Mosaic compiles for nothing else) and no
        multi-device auto mesh (a ``pallas_call`` has no SPMD rule).
        ``HybridStack.forward`` never asks: the kernel has no VJP."""
        cfg = self.cfg
        if (self._scan_kernel_layers
                and selective_scan_kernel.takes(
                    tokens, cfg.ssm_inner_, cfg.ssm_state_size)
                and _tpu_backend() and _flash_mesh() is None):
            selective_scan_kernel.pallas()
            return selective_scan_kernel
        return None

    def cache_spec(self) -> Tuple[CacheArray, ...]:
        """Every array a cache manager holds for this model, in the
        order the paged steps take and return them (``view["pools"]``).
        A model of one kind of layer: one ``paged`` array of every layer
        per ``cache_rows()`` entry. A model with a per-layer spec states
        pages of two lifetimes and per-slot state
        (``HybridStack.cache_spec``)."""
        if self.hybrid is not None:
            return self.hybrid.cache_spec()
        return tuple(
            CacheArray("paged", self.cfg.num_layers, row, self.adtype)
            for row in self.cache_rows())

    # ------------------------------------------------------------------ mlp

    def _mlp(self, layer: Params, h: jnp.ndarray, proj,
             token_valid: Optional[jnp.ndarray] = None,
             dropless: bool = False):
        """Dense gated-SiLU MLP, or the routed MoE variant when the layer
        carries a router (cfg.num_experts > 0), plus the shared experts
        where the layer has them. Returns (out, aux | None). Training
        (capacity dispatch): aux is the (load_balance, router_z,
        dropped_frac) triple from ops.moe for the trainer to weight in.
        ``dropless`` (every serving entry point): no capacity, aux is the
        int32 [2] (held experts hit, pairs landed) of this layer.
        ``token_valid`` keeps pad tokens from claiming expert capacity or
        skewing router stats."""
        if "router" in layer:
            from dla_tpu.ops.moe import moe_mlp, moe_mlp_dropless
            cfg = self.cfg
            kw = dict(k=cfg.num_experts_per_token, valid=token_valid,
                      first=cfg.moe_first_expert,
                      routed_scale=cfg.moe_routed_scale)
            if dropless:
                # a scan that keeps the experts' weights stacked hands
                # them over whole, with its block index (_paged_layers)
                stack = layer.get("expert_stack", layer)
                out, aux = moe_mlp_dropless(
                    h, layer["router"], stack["w_gate"], stack["w_up"],
                    stack["w_down"], layer=layer.get("layer_index"), **kw)
            else:
                out, aux = moe_mlp(
                    h, layer["router"], layer["w_gate"], layer["w_up"],
                    layer["w_down"],
                    capacity_factor=cfg.moe_capacity_factor,
                    group_size=cfg.moe_group_size, **kw)
            if "ws_gate" in layer:
                with jax.named_scope("moe_shared"):
                    ff = jax.nn.silu(proj("ws_gate", h)) * proj("ws_up", h)
                    out = out + proj("ws_down", _constrain(
                        ff, P(("data", "fsdp"), "sequence", "model")))
            return out, aux
        if self.cfg.arch in ("gemma", "gemma2"):
            gate = jax.nn.gelu(proj("w_gate", h), approximate=True)
        else:
            gate = jax.nn.silu(proj("w_gate", h))
        up = proj("w_up", h)
        ff = _constrain(gate * up, P(("data", "fsdp"), "sequence", "model"))
        return proj("w_down", ff), None

    def _flash_eligible(self, t: int) -> bool:
        """Whether the Pallas flash kernel may serve a full-sequence
        forward of length t for THIS config: the kernel speaks neither
        softcapping, per-layer windows, nor a non-default softmax scale
        (gemma-2) — those take the XLA path. The scale gate compares the
        EFFECTIVE scale, not the knob: query_pre_attn_scalar == head_dim
        (gemma2-2b/9b) yields exactly the kernel's default head_dim**-0.5
        and must not disqualify. One predicate shared by apply() and
        prefill() so the two gates cannot diverge."""
        cfg = self.cfg
        return (cfg.attention == "flash" and _flash_tileable(t)
                and not cfg.attn_logit_softcap
                and cfg.sliding_window_pattern == 1
                and (self._softmax_scale is None or math.isclose(
                    self._softmax_scale, cfg.head_dim_ ** -0.5))
                and (not cfg.latent_attention
                     or cfg.v_head_dim == cfg.head_dim_))

    def _with_layer_windows(self, layers: Params,
                            storage: bool = False) -> Params:
        """Inject the per-layer SWA flag into the scan stream for
        alternating-window archs (gemma-2: layer l slides iff
        (l+1) % pattern != 0, HF Gemma2's is_sliding). Not a param —
        rides the scan xs like the LoRA dropout keys. ``storage``:
        shape the flag [V, S, c] to match interleaved-storage leaves
        (canonical index semantics survive the row-major reshape)."""
        cfg = self.cfg
        if not (cfg.sliding_window and cfg.sliding_window_pattern > 1):
            return layers
        win = jnp.asarray([s.window is not None for s in cfg.layer_spec])
        if storage and self._interleaved_storage:
            win = win.reshape(self._storage_lead())
        return {**layers, "swa_on": win}

    def _weight(self, container: Params, name: str) -> jnp.ndarray:
        """The named weight matrix in activation dtype. int8 weight-only
        storage (``quantize_weights``) dequantizes on the fly via the
        ``<name>_wscale`` per-output-channel scales — XLA reads int8
        from HBM and fuses convert*scale into the consuming matmul, so
        the weight read traffic halves vs bf16 (the dominant bytes of
        the HBM-bound decode loop). Full-precision trees hit the plain
        astype path (dtype check is trace-time — zero runtime cost)."""
        w = container[name]
        if w.dtype == jnp.int8:
            # multiply in fp32, cast the PRODUCT: casting the scale to
            # bf16 first would add a correlated ~2^-9 relative error per
            # output channel on top of int8's inherent half-step error
            return (w.astype(jnp.float32)
                    * container[name + "_wscale"]).astype(self.adtype)
        return w.astype(self.adtype)

    def _dense(self, container: Params, name: str,
               inp: jnp.ndarray) -> jnp.ndarray:
        """``inp @ weight`` with int8 weight-only storage consumed through
        the fused Pallas kernel (ops.quant_matmul): the dequantization
        happens in VMEM, so HBM reads the int8 bytes and nothing else.
        The ``_weight`` convert*scale path relies on XLA fusing the
        dequant into the dot — measured on chip (r5 sweep_decode) it does
        NOT and materializes the bf16 matrix, making int8 rollout decode
        SLOWER than bf16 (b64 full stack 4.7x roofline). Under a >1-device
        auto mesh the kernel (no SPMD rule) would replicate the weight, so
        those contexts keep the XLA path — logged once per shape; the
        single-chip rollout/bench path is where the int8 bytes matter."""
        w = container[name]
        if w.dtype != jnp.int8:
            return inp @ w.astype(self.adtype)
        if _flash_mesh() is not None:
            key = ("int8_dense", name, inp.shape)
            if key not in _REPLICATED_FLASH_LOGGED and \
                    jax.process_index() == 0:
                _REPLICATED_FLASH_LOGGED.add(key)
                print(f"[dla_tpu][int8] {name} {inp.shape} consumed via "
                      "the XLA dequant path (multi-device auto mesh; the "
                      "fused kernel has no SPMD rule)",
                      file=sys.stderr, flush=True)
            return inp @ self._weight(container, name)
        from dla_tpu.ops.quant_matmul import int8_matmul
        return int8_matmul(inp, w, container[name + "_wscale"]
                           ).astype(self.adtype)

    _WEIGHT_ONLY_MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                         "w_down", "fc1", "fc2")

    def quantize_weights(self, params: Params) -> Params:
        """Weight-only int8 copy of a param tree for ROLLOUT decode
        (RLHF's hot loop): each dense [L, in, out] matrix stores int8
        with symmetric per-(layer, out-channel) fp32 scales
        (absmax/127 over the in dim). Embeddings, norms, biases, the
        tied unembedding, and MoE expert stacks stay full precision.
        The update/scoring paths keep using the original tree — only
        the sampled tokens see quantization."""
        out_layers: Params = {}
        # dense [L, in, out] canonical or [V, S, c, in, out] storage
        mat_ndim = 5 if self._interleaved_storage else 3
        for key, val in params["layers"].items():
            if (key in self._WEIGHT_ONLY_MATS and val.ndim == mat_ndim
                    and val.dtype != jnp.int8):  # idempotent: re-apply
                # of an already-quantized tree must not re-scale
                q, scale = self._symmetric_int8(val, axis=val.ndim - 2)
                out_layers[key] = q            # scale [..., 1, out]
                out_layers[key + "_wscale"] = scale
            else:
                out_layers[key] = val
        new = {**params, "layers": out_layers}
        lm = params.get("lm_head")
        if lm is not None and lm.dtype != jnp.int8:      # [D, V]
            q, scale = self._symmetric_int8(lm, axis=0)  # [1, V]
            new["lm_head"] = q
            new["lm_head_wscale"] = scale
        return new

    def _layer_window(self, layer: Params):
        """Effective window for a layer: the static config window, or —
        when the per-layer ``swa_on`` flag rides the scan (gemma-2
        alternating SWA) — a TRACED scalar that is the window on sliding
        layers and an unreachable bound on full-attention layers (one
        code path, no lax.cond in the scan body)."""
        cfg = self.cfg
        swa_on = layer.get("swa_on") if isinstance(layer, dict) else None
        if swa_on is None:
            return cfg.sliding_window or None
        return jnp.where(swa_on, jnp.int32(cfg.sliding_window),
                         jnp.int32(2 ** 30))

    def _attention(self, q, k, v, kv_segment_mask, q_positions, kv_positions,
                   allow_flash: bool = False, cp: Optional[Tuple] = None,
                   flash_segs: Optional[jnp.ndarray] = None,
                   window=None, factored_mask: Optional[Tuple] = None):
        """Pick the attention backend. The pallas flash kernel handles the
        full-sequence causal path on contiguous right-padded batches whose
        length tiles its blocks — including packed batches, whose segment
        ids fold into the kernel's mask (``flash_segs``). Everything else
        (decode against a cache, gapped masks, odd lengths) takes the XLA
        path. When ``cp`` is set — a (mode, kv_valid, segment_ids,
        gapped) 4-tuple, ``gapped`` meaning positions carry no physical
        -contiguity guarantee (gapped mask or caller-supplied) — the
        sequence dim is sharded over the mesh and attention runs ring /
        ulysses context-parallel, with the windowed ring's scan
        truncation disabled for gapped positions."""
        t, s = q.shape[1], k.shape[1]
        if cp is not None:
            mode, kv_valid, seg, gapped = cp
            if mode == "ulysses":
                from dla_tpu.ops.ulysses import ulysses_causal_attention
                # window/softcap/query-scale fold into the per-head-slice
                # attention: the all-to-all hands each device the FULL
                # sequence (global positions via gather), so the same
                # window semantics ring implements by rotating metadata
                # apply directly (ops/ulysses.py _ulysses_local)
                return ulysses_causal_attention(
                    q, k, v, q_positions=q_positions,
                    kv_positions=kv_positions, kv_valid=kv_valid,
                    segment_ids=seg,
                    window=window,
                    contiguous=not gapped,
                    softmax_scale=self._softmax_scale,
                    logit_softcap=self.cfg.attn_logit_softcap,
                    use_flash=(self.cfg.attention == "flash"
                               and _flash_tileable(t)),
                    flash_block_q=self.cfg.flash_block_q,
                    flash_block_k=self.cfg.flash_block_k)
            from dla_tpu.ops.ring_attention import ring_causal_attention
            # `window` comes from _layer_window: a static int (uniform
            # SWA — enables ring truncation), a traced per-layer scalar
            # (gemma-2 alternating SWA — mask-only), or None
            return ring_causal_attention(
                q, k, v, q_positions=q_positions, kv_positions=kv_positions,
                kv_valid=kv_valid, segment_ids=seg,
                window=window,
                window_truncate=not gapped,
                softmax_scale=self._softmax_scale,
                logit_softcap=self.cfg.attn_logit_softcap)
        if (self.cfg.attention == "flash" and allow_flash and t == s
                and _flash_tileable(t)):
            return self._flash(q, k, v, flash_segs)
        kw = dict(
            kv_segment_mask=kv_segment_mask,
            q_positions=q_positions, kv_positions=kv_positions,
            window=window if window is not None
            else (self.cfg.sliding_window or None),
            softmax_scale=self._softmax_scale,
            logit_softcap=self.cfg.attn_logit_softcap)
        from dla_tpu.ops.attention import DEFAULT_Q_CHUNK
        if t == s and t > DEFAULT_Q_CHUNK:
            # flash-ineligible long sequences (gemma-2 softcap/per-layer
            # window, gapped masks): query-chunked to keep live scores
            # O(T * chunk), forward AND backward (checkpointed scan).
            # With factored_mask set, each chunk builds its own [B,C,S]
            # mask slab from the 1-D metadata — no [B,T,T] anywhere.
            if factored_mask is not None:
                valid, segs = factored_mask
                return chunked_causal_attention(
                    q, k, v, kv_valid=valid,
                    q_segments=segs, kv_segments=segs, **kw)
            return chunked_causal_attention(q, k, v, **kw)
        if factored_mask is not None and kw["kv_segment_mask"] is None:
            # safety net (callers only set factored_mask on the long
            # path above): chunked's t <= q_chunk branch builds the slab
            valid, segs = factored_mask
            return chunked_causal_attention(
                q, k, v, kv_valid=valid,
                q_segments=segs, kv_segments=segs, **kw)
        return causal_attention(q, k, v, **kw)

    def _flash(self, q, k, v, segs: Optional[Tuple]):
        """Invoke the pallas flash kernel, shard_map-wrapped when the
        ambient mesh spans >1 device: the kernel has no SPMD rule, so a
        bare pallas_call under GSPMD silently replicates its operands.
        Per-shard the kernel sees the local batch slice and local head
        group; GQA grouping survives because the model axis divides
        num_kv_heads in any valid TP layout. ``segs`` is the
        pre-broadcast (qseg, kseg) pair from broadcast_segment_ids."""
        with startup_span("startup_kernel_import",
                          module="dla_tpu.ops.flash_attention"):
            from dla_tpu.ops.flash_attention import (
                DEFAULT_BLOCK_K,
                DEFAULT_BLOCK_Q,
                flash_causal_attention,
            )
        kw = dict(window=self.cfg.sliding_window or None,
                  block_q=self.cfg.flash_block_q or DEFAULT_BLOCK_Q,
                  block_k=self.cfg.flash_block_k or DEFAULT_BLOCK_K)
        mesh = _flash_mesh()
        if mesh is None:
            return flash_causal_attention(q, k, v, segs=segs, **kw)
        # shard over the batch/head axes that are still GSPMD-auto, and
        # make EVERY remaining auto axis manual: Mosaic refuses a kernel
        # under a partly-manual mesh ("cannot be automatically
        # partitioned"), even when the axes left auto have size 1. Axes
        # the specs do not name see replicated operands. Under the
        # pipeline's stage shard_map `stage` is already manual in the
        # enclosing scope and stays untouched.
        manual_axes = auto_axes(mesh)
        wrap_axes = {a for a in ("data", "fsdp", "model")
                     if a in manual_axes}
        model_size = mesh.shape.get("model", 1) if "model" in wrap_axes \
            else 1
        batch_shards = 1
        for a in ("data", "fsdp"):
            if a in wrap_axes:
                batch_shards *= mesh.shape[a]
        if (q.shape[0] % batch_shards or self.cfg.num_heads % model_size
                or self.cfg.num_kv_heads % model_size):
            # shard_map needs even divisibility; odd shapes (a last partial
            # eval batch, B < dp shards in a rollout) take the bare
            # pallas_call, which GSPMD runs replicated — correct, just not
            # partitioned. Training batches are always divisible. Logged
            # once per shape at trace time so a misconfigured run (e.g. a
            # rollout batch smaller than the dp shard count every step) is
            # diagnosable from its logs (VERDICT r3 weak-item 4).
            key = (q.shape, batch_shards, model_size)
            if key not in _REPLICATED_FLASH_LOGGED and \
                    jax.process_index() == 0:
                _REPLICATED_FLASH_LOGGED.add(key)
                print(f"[dla_tpu][flash] batch {q.shape[0]} x heads "
                      f"{self.cfg.num_heads}/{self.cfg.num_kv_heads} does "
                      f"not divide mesh (batch shards {batch_shards}, "
                      f"model {model_size}); attention runs REPLICATED "
                      "across the mesh for this shape",
                      file=sys.stderr, flush=True)
            return flash_causal_attention(q, k, v, segs=segs, **kw)
        batch_axes = tuple(a for a in ("data", "fsdp") if a in wrap_axes)
        head_axis = "model" if "model" in wrap_axes else None
        bspec = P(batch_axes or None, None, head_axis, None)
        if segs is None:
            fn = jax.shard_map(
                lambda a, b, c: flash_causal_attention(a, b, c, **kw),
                mesh=mesh, in_specs=(bspec, bspec, bspec),
                out_specs=bspec, axis_names=manual_axes, check_vma=False)
            return fn(q, k, v)
        sspec = P(batch_axes or None, None, None)
        fn = jax.shard_map(
            lambda a, b, c, s: flash_causal_attention(a, b, c, segs=s, **kw),
            mesh=mesh,
            in_specs=(bspec, bspec, bspec, (sspec, sspec)),
            out_specs=bspec, axis_names=manual_axes, check_vma=False)
        return fn(q, k, v, segs)

    def _maybe_remat(self, fn):
        if self.cfg.remat == "none":
            return fn
        if self.cfg.remat == "dots":
            # matmul outputs + the flash kernel's (out, lse) residuals:
            # saving the named flash outputs keeps the backward from
            # replaying the pallas forward (measured ~25% of the step at
            # T=2048); elementwise glue (norms, rotary, silu) is still
            # recomputed, which is the cheap part
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse"))
            return jax.checkpoint(fn, policy=policy)
        return jax.checkpoint(fn)  # "full"

    # -------------------------------------------------------------- forward

    def hidden_states(
        self,
        params: Params,
        input_ids: jnp.ndarray,                 # [B, T]
        attention_mask: Optional[jnp.ndarray] = None,   # [B, T] 1 = real
        segment_ids: Optional[jnp.ndarray] = None,      # [B, T] for packing
        positions: Optional[jnp.ndarray] = None,        # [B, T]
        gapped_mask: bool = False,
        lora: Optional[Params] = None,                  # adapter pytree
        dropout_rng: Optional[jax.Array] = None,        # enables lora dropout
    ) -> jnp.ndarray:
        """Full-sequence forward up to the final norm. [B, T, D].
        (Aux-discarding wrapper — MoE models training through a CE loss
        should use hidden_states_with_aux to keep the router's
        load-balance loss.)"""
        return self.hidden_states_with_aux(
            params, input_ids, attention_mask, segment_ids, positions,
            gapped_mask=gapped_mask, lora=lora, dropout_rng=dropout_rng)[0]

    def hidden_states_with_aux(
        self,
        params: Params,
        input_ids: jnp.ndarray,                 # [B, T]
        attention_mask: Optional[jnp.ndarray] = None,   # [B, T] 1 = real
        segment_ids: Optional[jnp.ndarray] = None,      # [B, T] for packing
        positions: Optional[jnp.ndarray] = None,        # [B, T]
        gapped_mask: bool = False,
        lora: Optional[Params] = None,                  # adapter pytree
        dropout_rng: Optional[jax.Array] = None,        # enables lora dropout
    ) -> Tuple[jnp.ndarray, Optional[Any]]:
        """Full-sequence forward up to the final norm. Returns
        ([B, T, D], moe_aux) where moe_aux is an ops.moe.MoEAux of
        layer-mean scalars when cfg.num_experts > 0, else None.

        ``gapped_mask``: declare that attention_mask may have internal
        zero gaps (not plain right-padding). Gapped masks are handled
        correctly by the XLA attention path (cumsum positions + explicit
        kv mask) but NOT by the flash kernel, so setting this disables
        flash. All internal callers produce right-padded or compacted
        (left_align-ed) batches and keep the default.
        """
        cfg = self.cfg
        b, t = input_ids.shape
        if self.hybrid is not None:
            return self._hybrid_hidden_states(
                params, input_ids, attention_mask, segment_ids, positions,
                gapped_mask, lora), None
        # caller-supplied positions carry no contiguity guarantee — the
        # windowed ring must treat them like gapped-mask positions and
        # skip its scan truncation
        custom_positions = positions is not None
        if positions is None:
            if segment_ids is None and attention_mask is not None:
                # position = index among *real* tokens, so sequences with
                # masked gaps (e.g. prompt pad + generated tail) see the
                # same rotary phases as their contiguous equivalents
                positions = jnp.maximum(
                    jnp.cumsum(attention_mask.astype(jnp.int32), axis=1) - 1, 0)
            elif segment_ids is not None:
                # restart positions at each packed segment boundary
                seg_start = jnp.concatenate(
                    [jnp.ones((b, 1), bool),
                     segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
                seg_idx = jnp.cumsum(seg_start.astype(jnp.int32), axis=1) - 1
                first_pos = jnp.where(
                    seg_start, jnp.arange(t)[None, :], 0)
                starts = jax.lax.cummax(first_pos, axis=1)
                positions = jnp.arange(t)[None, :] - starts
                del seg_idx
            else:
                positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

        # Context parallelism: when the ambient mesh shards `sequence`,
        # attention runs ring/ulysses from 1-D metadata. Ring stays
        # blockwise (no [B, T, T] mask); ulysses routes its per-shard
        # full-sequence attention through the flash kernel when the
        # backend is on (O(T) memory) and only its XLA fallback
        # materializes full-length scores (dla_tpu/ops/ulysses.py).
        cp = None
        if cfg.context_parallel != "none" and _sequence_axis_size() > 1:
            kv_valid = (attention_mask if attention_mask is not None
                        else jnp.ones((b, t), jnp.int32))
            seg = (segment_ids if segment_ids is not None
                   else jnp.zeros((b, t), jnp.int32))
            # gapped masks derive positions from cumsum(mask) and custom
            # positions are arbitrary, so physical chunk distance no
            # longer bounds position distance — the windowed ring must
            # not truncate its scan then
            cp = (cfg.context_parallel, kv_valid, seg,
                  gapped_mask or custom_positions)

        # Flash eligibility decided up front so the packed path skips the
        # [B, T, T] mask materialization entirely (round-2 verdict item 1:
        # packing + flash now compose — segment ids go to the kernel).
        # Right-padding alone needs no mask at all under flash: pad keys
        # sit above every real query's causal diagonal. Under pipeline
        # parallelism the kernel nests inside the stage shard_map as a
        # partial-manual shard_map over the still-auto batch/head axes
        # (round-3 verdict item 5 — PP no longer forces XLA attention).
        n_stages = _stage_axis_size()
        allow_flash = (not gapped_mask and cp is None
                       and self._flash_eligible(t))
        flash_segs = None
        if allow_flash and segment_ids is not None:
            # broadcast to the kernel's tileable layouts ONCE, outside the
            # scan-over-layers: inside the body the [B,T,block_k] expansion
            # would be rebuilt per layer (and re-rebuilt per layer in the
            # remat'd backward)
            with startup_span("startup_kernel_import",
                              module="dla_tpu.ops.flash_attention"):
                from dla_tpu.ops.flash_attention import (
                    DEFAULT_BLOCK_K,
                    broadcast_segment_ids,
                )
            flash_segs = broadcast_segment_ids(
                segment_ids,
                block_k=self.cfg.flash_block_k or DEFAULT_BLOCK_K)

        kv_mask = None
        factored = None
        if cp is None and not allow_flash:
            from dla_tpu.ops.attention import DEFAULT_Q_CHUNK
            if (t > DEFAULT_Q_CHUNK and n_stages == 1
                    and (attention_mask is not None
                         or segment_ids is not None)):
                # long flash-ineligible sequences route through the
                # query-chunked attention, which builds each chunk's
                # mask slab from this 1-D metadata — never materialize
                # the [B, T, T] mask here (at 32k that mask alone is
                # O(GB) before any score exists)
                factored = (attention_mask, segment_ids)
            else:
                if attention_mask is not None:
                    kv_mask = jnp.broadcast_to(
                        attention_mask[:, None, :].astype(bool), (b, t, t))
                if segment_ids is not None:
                    same_seg = (segment_ids[:, :, None]
                                == segment_ids[:, None, :])
                    kv_mask = (same_seg if kv_mask is None
                               else (kv_mask & same_seg))

        x = _constrain(self._embed(params, input_ids), ACT_SPEC)
        cos, sin = rotary_angles(positions, cfg.rotary_dim_, cfg.rope_theta,
                                 scaling=cfg.rope_scaling)

        layers = params["layers"]
        keys = None
        if lora is not None:
            layers = {**layers, **lora["layers"]}
            if dropout_rng is not None and cfg.lora_dropout > 0:
                keys = jax.random.split(dropout_rng, cfg.num_layers)
        # MoE routing must know which tokens are real: pads must not
        # claim expert capacity or skew the balance statistics (shared
        # by the pipeline and plain-scan paths)
        token_valid = None
        if cfg.num_experts > 0:
            if attention_mask is not None:
                token_valid = attention_mask
            elif segment_ids is not None:
                token_valid = (segment_ids > 0).astype(jnp.int32)

        # window flags join in the layout each path consumes: storage
        # shape under the pipeline (the [V,S,c] leaves go straight to the
        # stage schedule), flat [L] for the plain scan
        if n_stages > 1:
            layers = self._with_layer_windows(layers, storage=True)
        else:
            layers = self._with_layer_windows(self._flat_layers(layers))

        if n_stages > 1:
            # pipeline parallelism: layer stack sharded over `stage`,
            # GPipe microbatch schedule (ops.pipeline). LoRA leaves ride
            # in `layers` and reshape with everything else. Context
            # parallelism composes: the ring/ulysses shard_map nests
            # partial-manual over the still-auto `sequence` axis inside
            # the stage schedule (like _flash), with the CP metadata
            # (validity, segments) riding the aux shift register.
            if keys is not None:
                raise NotImplementedError(
                    "lora_dropout under pipeline parallelism is not "
                    "supported; set lora.dropout to 0")
            x, moe_aux = self._pipeline_forward(
                layers, x, cos, sin, kv_mask, positions, n_stages,
                allow_flash=allow_flash, flash_segs=flash_segs, cp=cp,
                token_valid=token_valid)
            return self._final_norm(params, x), moe_aux

        if keys is None:
            def body(carry, layer):
                h, _, aux = self._block(layer, carry, cos, sin, kv_mask,
                                        positions, positions,
                                        allow_flash=allow_flash,
                                        flash_segs=flash_segs, cp=cp,
                                        token_valid=token_valid,
                                        factored_mask=factored)
                return h, aux
        else:
            def body(carry, xs):
                layer, key = xs
                h, _, aux = self._block(layer, carry, cos, sin, kv_mask,
                                        positions, positions,
                                        allow_flash=allow_flash,
                                        flash_segs=flash_segs, cp=cp,
                                        dropout_key=key,
                                        token_valid=token_valid,
                                        factored_mask=factored)
                return h, aux
            layers = (layers, keys)

        x, auxs = jax.lax.scan(self._maybe_remat(body), x, layers)
        moe_aux = None
        if auxs is not None:
            moe_aux = type(auxs)(*(jnp.mean(a) for a in auxs))  # layer mean
        return self._final_norm(params, x), moe_aux

    def _hybrid_hidden_states(self, params, input_ids, attention_mask,
                              segment_ids, positions, gapped_mask, lora):
        """The full-sequence forward of a model with a per-layer spec:
        right-padded sequences from an empty state, on one device or a
        data / fsdp / model mesh. Packing, gapped masks and caller's
        positions would have to reset or skip the recurrent state, and
        context or pipeline parallelism to hand it on: none is built."""
        if (segment_ids is not None or positions is not None or gapped_mask
                or lora is not None or _sequence_axis_size() > 1
                or _stage_axis_size() > 1):
            raise NotImplementedError(
                "a model with state-space layers runs whole right-padded "
                "sequences: no packing (segment_ids), gapped masks, "
                "caller's positions, LoRA, context or pipeline parallelism")
        b, t = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        kv_mask, real = None, jnp.ones((b, t), bool)
        if attention_mask is not None:
            real = attention_mask.astype(bool)
            kv_mask = jnp.broadcast_to(real[:, None, :], (b, t, t))
        x = _constrain(self._embed(params, input_ids), ACT_SPEC)
        x = self.hybrid.forward(params["layers"], x, positions, kv_mask, real)
        return self._final_norm(params, x)

    def _pipeline_forward(self, layers: Params, x: jnp.ndarray,
                          cos: jnp.ndarray, sin: jnp.ndarray,
                          kv_mask: Optional[jnp.ndarray],
                          positions: jnp.ndarray,
                          n_stages: int, *,
                          allow_flash: bool = False,
                          flash_segs: Optional[Tuple] = None,
                          cp: Optional[Tuple] = None,
                          token_valid: Optional[jnp.ndarray] = None
                          ) -> Tuple[jnp.ndarray, Optional[Any]]:
        """GPipe over the `stage` mesh axis: reshape the [L, ...] layer
        stack to [S, L/S, ...] (shard-local — the stage axis owns
        contiguous layer blocks), microbatch the batch dim, and run the
        shift-register schedule from ops.pipeline. Flash attention stays
        engaged inside the stage shard_map: _flash nests partial-manual
        over the still-auto batch/head axes (`stage` stays manual in the
        enclosing scope), so the 70B PP path keeps the kernel that set
        the single-chip headline (round-3 verdict item 5)."""
        from dla_tpu.ops.pipeline import gpipe, microbatch, \
            resolve_microbatches
        cfg = self.cfg
        n_layers = cfg.num_layers
        v = max(1, cfg.pipeline_interleave)
        if n_layers % (n_stages * v):
            raise ValueError(
                f"pipeline needs num_layers ({n_layers}) divisible by "
                f"stage axis x interleave ({n_stages} x {v})")
        mesh = _ambient_mesh()
        dp_shards = 1
        if mesh is not None:
            for a in ("data", "fsdp"):
                if a in auto_axes(mesh):
                    dp_shards *= mesh.shape[a]
        if v > 1:
            # circular schedule: M pinned to the stage count; falls back
            # to plain GPipe when the batch can't split S ways. The
            # degradation announcements live in ops.pipeline, next to the
            # plain-path policy, so the two cannot drift.
            from dla_tpu.ops.pipeline import \
                resolve_interleaved_microbatches
            m, v = resolve_interleaved_microbatches(
                x.shape[0], n_stages, v, dp_shards,
                cfg.pipeline_microbatches)
        else:
            m = resolve_microbatches(x.shape[0], cfg.pipeline_microbatches,
                                     n_stages, dp_shards=dp_shards)
        # block b = p*S + s lives at stacked[s, p]: the schedule wants
        # [S, V, c] leaves with `stage` sharding dim 0.
        if self._interleaved_storage:
            if n_stages != cfg.pipeline_stages:
                raise ValueError(
                    f"model storage is laid out for pipeline_stages="
                    f"{cfg.pipeline_stages} but the mesh has a stage axis "
                    f"of {n_stages}; rebuild params via "
                    "to_canonical_layout/to_storage_layout")
            if v > 1:
                # storage leaves are already block-major [V, S, c, ...]
                # with `stage` sharding dim 1: the swap to [S, V, c] is a
                # shard-local transpose — NO cross-stage weight
                # collective per step (the (V-1)/V all-to-all reshard the
                # flat layout paid; docs/pp_bubble.md, r5)
                stage_layers = jax.tree.map(
                    lambda l: l.swapaxes(0, 1), layers)
            else:
                # degraded to plain GPipe (batch cannot split S ways —
                # already announced): contiguous stages need canonical
                # order, so this corner pays the reshard the main path
                # no longer does
                c = n_layers // n_stages
                stage_layers = jax.tree.map(
                    lambda l: l.reshape((n_layers,) + l.shape[3:]
                                        ).reshape((n_stages, 1, c)
                                                  + l.shape[3:]), layers)
        else:
            # flat [L] storage: [L] -> [V, S, c] (block-major) ->
            # transpose -> [S, V, c]. LAYOUT COST (v > 1 only): params
            # are stored contiguously over `stage` but the round-robin
            # schedule needs the strided blocks {p*S+s} — GSPMD inserts
            # a cross-stage reshard of ~(V-1)/V of the layer weights per
            # step. Set cfg.pipeline_stages (the config loader does it
            # from hardware.mesh.stage) to store block-major and make
            # the schedule shard-local.
            c = n_layers // (n_stages * v)
            stage_layers = jax.tree.map(
                lambda l: l.reshape((v, n_stages, c) + l.shape[1:]
                                    ).swapaxes(0, 1), layers)
        aux = {"cos": microbatch(cos, m), "sin": microbatch(sin, m),
               "positions": microbatch(positions, m)}
        if kv_mask is not None:
            aux["kv_mask"] = microbatch(kv_mask, m)
        if flash_segs is not None:
            aux["flash_segs"] = jax.tree.map(
                lambda a: microbatch(a, m), flash_segs)
        cp_mode = cp_gapped = None
        if cp is not None:
            # CP metadata microbatches with the activations; the static
            # parts (mode, gapped-positions flag) close over stage_fn
            cp_mode, cp_valid, cp_seg, cp_gapped = cp
            aux["cp_valid"] = microbatch(cp_valid, m)
            aux["cp_seg"] = microbatch(cp_seg, m)
        collect_aux = cfg.num_experts > 0
        if token_valid is not None:
            aux["token_valid"] = microbatch(token_valid, m)

        def stage_fn(stage_params, h, aux_t):
            cp_t = None
            if cp_mode is not None:
                cp_t = (cp_mode, aux_t["cp_valid"], aux_t["cp_seg"],
                        cp_gapped)

            def body(carry, layer):
                out, _, aux_l = self._block(
                    layer, carry, aux_t["cos"],
                    aux_t["sin"], aux_t.get("kv_mask"),
                    aux_t["positions"], aux_t["positions"],
                    allow_flash=allow_flash,
                    flash_segs=aux_t.get("flash_segs"), cp=cp_t,
                    token_valid=aux_t.get("token_valid"))
                return out, aux_l
            h, auxs = jax.lax.scan(self._maybe_remat(body), h,
                                   stage_params)
            if collect_aux:
                # sum this block's per-layer scalars; gpipe masks
                # garbage ticks, sums across ticks and psums across
                # stages — (1/(L*M))x that sum is the layer-and-
                # microbatch mean the plain scan path reports
                return h, jax.tree.map(
                    lambda a: jnp.sum(a.astype(jnp.float32), axis=0),
                    auxs)
            return h

        out = gpipe(stage_fn, stage_layers, microbatch(x, m), aux,
                    n_stages, passes=v, collect_aux=collect_aux)
        moe_aux = None
        if collect_aux:
            out, aux_sums = out
            moe_aux = type(aux_sums)(
                *(a / (n_layers * m) for a in aux_sums))
        return out.reshape(x.shape), moe_aux

    def _final_norm(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        if self.cfg.arch == "phi" or self.cfg.norm == "layer":
            return layer_norm(x, params["final_norm"],
                              params["final_norm_bias"],
                              self.cfg.rms_norm_eps)
        return rms_norm(x, params["final_norm"], self.cfg.rms_norm_eps)

    def _embed(self, params: Params, ids: jnp.ndarray) -> jnp.ndarray:
        """Token embedding read in the activation dtype. Gemma scales the
        input embedding by sqrt(hidden) (normalizer cast to the activation
        dtype, matching HF GemmaModel's bf16-rounded multiplier); the tied
        unembedding stays unscaled."""
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"]["embedding"], ids, axis=0
                         ).astype(self.adtype)
            if self.cfg.arch in ("gemma", "gemma2"):
                x = x * jnp.asarray(self.cfg.hidden_size ** 0.5, self.adtype)
        return x

    def unembed_params(self, params: Params
                       ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        """(w [D, V] in activation dtype, bias [V] or None) — the
        unembedding operands, for fused losses (ops.fused_ce) that
        contract hidden states against w chunk-by-chunk instead of
        materializing [B, T, V] logits."""
        if self.cfg.tie_embeddings:
            w = params["embed"]["embedding"].astype(self.adtype).T
        else:
            w = self._weight(params, "lm_head")
        bias = params.get("lm_head_bias")
        return w, None if bias is None else bias.astype(self.adtype)

    def unembed(self, params: Params, hidden: jnp.ndarray) -> jnp.ndarray:
        """[..., D] -> [..., V] logits (activation dtype; cast at the loss).
        gemma-2 softcaps final logits: cap * tanh(logits / cap) — applied
        here AND in the chunked fused-CE path (ops.fused_ce reads
        cfg.final_logit_softcap through model.cfg)."""
        lm = params.get("lm_head")
        if lm is not None and lm.dtype == jnp.int8:
            # quantized rollout tree: fused kernel path (the [D, V]
            # dequant would otherwise materialize 2x the int8 bytes
            # EVERY decode step)
            logits = self._dense(params, "lm_head", hidden)
            bias = params.get("lm_head_bias")
            bias = None if bias is None else bias.astype(logits.dtype)
        else:
            w, bias = self.unembed_params(params)
            logits = hidden @ w
        if bias is not None:
            logits = logits + bias
        cap = self.cfg.final_logit_softcap
        if cap:
            logits = (jnp.tanh(logits / jnp.asarray(cap, logits.dtype))
                      * jnp.asarray(cap, logits.dtype))
        return logits

    def apply(self, params: Params, input_ids: jnp.ndarray,
              attention_mask: Optional[jnp.ndarray] = None,
              segment_ids: Optional[jnp.ndarray] = None,
              positions: Optional[jnp.ndarray] = None,
              gapped_mask: bool = False,
              lora: Optional[Params] = None,
              dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
        """Logits forward: [B, T] -> [B, T, V]."""
        h = self.hidden_states(params, input_ids, attention_mask,
                               segment_ids, positions,
                               gapped_mask=gapped_mask, lora=lora,
                               dropout_rng=dropout_rng)
        return self.unembed(params, h)

    __call__ = apply

    # ------------------------------------------------------------- KV cache

    @property
    def _kv_int8(self) -> bool:
        return self.cfg.kv_cache_dtype == "int8"

    @staticmethod
    def _symmetric_int8(x: jnp.ndarray, axis: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Symmetric int8 quantization along ``axis``: (int8 values,
        fp32 scale with keepdims). The one recipe shared by the KV cache
        and weight-only paths (absmax/127, round, clip)."""
        absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                         keepdims=True)
        scale = absmax / 127.0 + 1e-12
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        return q, scale

    def _quantize_kv(self, x: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """[..., D] -> (int8 values, fp32 scale [...]): symmetric
        per-position per-head quantization (scale = absmax/127 along the
        head dim). Dequantization (q * scale) fuses into the attention
        einsum, so the cache's HBM read traffic halves on the
        bandwidth-bound decode loop."""
        q, scale = self._symmetric_int8(x, axis=-1)
        return q, scale[..., 0]

    def _dequantize_kv(self, q: jnp.ndarray, scale: jnp.ndarray
                       ) -> jnp.ndarray:
        # fp32 multiply, cast the product (see _weight: a bf16-cast
        # scale would shift whole per-position head vectors coherently)
        return (q.astype(jnp.float32) * scale[..., None]
                ).astype(self.adtype)

    def refuse_contiguous_cache(self) -> None:
        """Raise for a model the contiguous cache cannot hold."""
        if self.hybrid is not None:
            raise ValueError(
                "a model with a per-layer spec decodes against the paged "
                "cache manager (dla_tpu.serving.ServingEngine): the "
                "contiguous cache of init_cache / prefill / decode_step "
                "(GenerationEngine) has one geometry for every layer and "
                "no recurrent state")

    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        self.refuse_contiguous_cache()
        if cfg.latent_attention:
            raise NotImplementedError(
                "latent attention decodes against the paged pool "
                "(dla_tpu.serving.ServingEngine); the contiguous "
                "decode_step cache stores per-head keys and values")
        shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim_)
        kv_dtype = jnp.int8 if self._kv_int8 else self.adtype
        cache = {
            "k": jnp.zeros(shape, kv_dtype),
            "v": jnp.zeros(shape, kv_dtype),
            "valid": jnp.zeros((batch, max_len), bool),
            "lengths": jnp.zeros((batch,), jnp.int32),  # next position per seq
            "step": jnp.zeros((), jnp.int32),           # decode steps taken
        }
        if self._kv_int8:
            # scales are stored K-MAJOR [L, B, K, S] — the layout the
            # Pallas decode kernel consumes — so no [B, S, K] transpose
            # rides the per-layer decode hot loop (r5 review finding)
            sshape = (shape[0], batch, cfg.num_kv_heads, max_len)
            cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
            cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return cache

    def cache_partition_specs(self) -> Params:
        specs = {
            "k": P(None, ("data", "fsdp"), None, "model", None),
            "v": P(None, ("data", "fsdp"), None, "model", None),
            "valid": P(("data", "fsdp"), None),
            "lengths": P(("data", "fsdp")),
            "step": P(),
        }
        if self._kv_int8:
            specs["k_scale"] = P(None, ("data", "fsdp"), "model", None)
            specs["v_scale"] = P(None, ("data", "fsdp"), "model", None)
        return specs

    def prefill_external(self, params: Params, input_ids: jnp.ndarray,
                         attention_mask: jnp.ndarray,
                         ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
        """The cache-layout-agnostic half of prefill: run the prompt
        forward and hand back the raw cache rows instead of writing any
        particular cache. Returns (last-real-token logits [B, V], rows):
        one [L, B, T, heads, width] array per ``cache_rows()`` entry
        (keys and values; or the latent rows) in activation dtype.
        Experts route droplessly: this is a serving entry point.

        ``prefill`` packs these into the contiguous cache (the paged
        serving engine prefills through ``prefill_step_paged``)."""
        cfg = self.cfg
        self.refuse_contiguous_cache()
        b, t = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        flash_ok = self._flash_eligible(t)
        from dla_tpu.ops.attention import DEFAULT_Q_CHUNK
        kv_mask = None
        pre_factored = None
        if not flash_ok:
            if t > DEFAULT_Q_CHUNK:
                # long flash-ineligible prefill (gemma-2 32k rollouts):
                # factored validity through the chunked path, no [B,T,T]
                pre_factored = (attention_mask, None)
            else:
                kv_mask = jnp.broadcast_to(
                    attention_mask[:, None, :].astype(bool), (b, t, t))
        x = self._embed(params, input_ids)
        cos, sin = rotary_angles(positions, cfg.rotary_dim_, cfg.rope_theta,
                                 scaling=cfg.rope_scaling)

        def body(carry, layer):
            h, kv, _ = self._block(layer, carry, cos, sin, kv_mask,
                                   positions, positions,
                                   allow_flash=flash_ok,
                                   token_valid=attention_mask,
                                   factored_mask=pre_factored,
                                   dropless=True)
            return h, kv

        x, rows = jax.lax.scan(
            body, x,
            self._with_layer_windows(self._flat_layers(params["layers"])))
        h = self._final_norm(params, x)

        lengths = attention_mask.astype(jnp.int32).sum(axis=1)
        last_idx = jnp.maximum(lengths - 1, 0)
        last_h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]
        logits = self.unembed(params, last_h)
        return logits, rows

    def prefill(self, params: Params, cache: Params,
                input_ids: jnp.ndarray, attention_mask: jnp.ndarray,
                ) -> Tuple[jnp.ndarray, Params]:
        """Run the prompt through the model, writing the cache at [0, T).

        Prompts are right-padded to T; pad positions are marked invalid in
        the cache and the returned logits come from the last *real* token.
        Returns (last-real-token logits [B, V], cache).

        When the flash backend is on and T tiles its blocks, prefill runs
        the blockwise kernel with NO [B, T, T] mask materialization —
        right padding makes the causal structure sufficient: every pad key
        sits above the causal diagonal of every real query, and pad-query
        rows are garbage nothing consumes (VERDICT round-1 item 6; the 32k
        long-context rollout path stays O(T) HBM like training).
        """
        b, t = input_ids.shape
        logits, (ks, vs) = self.prefill_external(
            params, input_ids, attention_mask)
        lengths = attention_mask.astype(jnp.int32).sum(axis=1)
        max_len = cache["k"].shape[2]
        pad = max_len - t
        pad5 = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
        new_cache = {
            "valid": jnp.pad(attention_mask.astype(bool), ((0, 0), (0, pad))),
            "lengths": lengths,
            "step": jnp.zeros((), jnp.int32),
        }
        if self._kv_int8:
            kq, k_s = self._quantize_kv(ks)
            vq, v_s = self._quantize_kv(vs)
            new_cache["k"] = jnp.pad(kq, pad5)
            new_cache["v"] = jnp.pad(vq, pad5)
            # [L, B, T, K] -> K-major [L, B, K, S] (one transpose at
            # prefill; decode reads it transpose-free every step)
            pads = ((0, 0), (0, 0), (0, 0), (0, pad))
            new_cache["k_scale"] = jnp.pad(k_s.transpose(0, 1, 3, 2), pads)
            new_cache["v_scale"] = jnp.pad(v_s.transpose(0, 1, 3, 2), pads)
        else:
            new_cache["k"] = jnp.pad(ks, pad5)
            new_cache["v"] = jnp.pad(vs, pad5)
        return logits, new_cache

    def _unpack_decode_xs(self, xs, dequantize: bool):
        """Unstack one decode-scan slice: (layer, k_cache, v_cache,
        k_scale, v_scale); int8 caches optionally dequantized here (the
        XLA path — the Pallas kernel takes raw int8 + scales)."""
        k_s = v_s = None
        if self._kv_int8:
            layer, k_cache, v_cache, k_s, v_s = xs
            if dequantize:
                # K-major [B, K, S] storage -> positional [B, S, K]
                k_cache = self._dequantize_kv(
                    k_cache, k_s.transpose(0, 2, 1))
                v_cache = self._dequantize_kv(
                    v_cache, v_s.transpose(0, 2, 1))
        else:
            layer, k_cache, v_cache = xs
        return layer, k_cache, v_cache, k_s, v_s

    def _decode_layer(self, layer: Params, h_in: jnp.ndarray,
                      cos, sin, attend, q_positions=None,
                      token_valid=None):
        """The per-layer decode computation SHARED by decode_step (one
        token) and decode_block (G tokens): norms, projections, MLP,
        and every arch branch — only the attention backend differs, and
        ``attend(q, k, v) -> [B, T, H, D]`` supplies it. Keeping this
        single ensures a new arch branch lands in both paths (the
        'G == 1 is semantically decode_step' contract). Returns (output,
        (new cache rows, int32 [2] expert counters)): rows in
        ``cache_rows()`` order; the counters are (held experts hit, pairs
        landed) of this layer's dropless routing, zeros without experts.
        ``q_positions`` [B, T]: latent attention's position-dependent
        query scale reads them. ``token_valid`` [B, T]: rows that are
        real (a running slot, a chunk's real token); the others' routed
        pairs are neither computed nor counted."""
        cfg = self.cfg
        b, t, _ = h_in.shape
        dh = cfg.head_dim_

        def cast(w):
            return w.astype(self.adtype)

        def proj(name, inp):
            out = self._dense(layer, name, inp)
            sa = layer.get(f"{name}_slot_lora_a")
            if sa is not None:
                # per-slot low-rank delta around the (possibly int8)
                # base matmul: inp [B,T,din] x A [B,din,r] x B [B,r,out]
                # — B pre-scaled by alpha/r at publish, rank-padded with
                # zeros so every slot shares one static shape
                sb = layer[f"{name}_slot_lora_b"]
                z = jnp.einsum("btd,bdr->btr", inp, sa.astype(self.adtype))
                out = out + jnp.einsum("btr,bro->bto", z,
                                       sb.astype(self.adtype))
            bias = layer.get(f"{name}_bias")
            return out if bias is None else out + cast(bias)

        if cfg.arch == "phi":
            hn = layer_norm(h_in, layer["ln"], layer["ln_bias"],
                            cfg.rms_norm_eps)
        else:
            hn = rms_norm(h_in, layer["attn_norm"], cfg.rms_norm_eps)
        no_experts = jnp.zeros((2,), jnp.int32)
        if cfg.latent_attention:
            with jax.named_scope("mla_attention"):
                attn, row = self._latent_absorbed(
                    layer, hn, cos, sin, q_positions, attend)
                attn_out = proj("wo", attn)
            rows = (row,)
        else:
            q = proj("wq", hn).reshape(b, t, cfg.num_heads, dh)
            k = proj("wk", hn).reshape(b, t, cfg.num_kv_heads, dh)
            v = proj("wv", hn).reshape(b, t, cfg.num_kv_heads, dh)
            q = apply_rotary(q, cos, sin, rotary_dim=cfg.rotary_dim_)
            k = apply_rotary(k, cos, sin, rotary_dim=cfg.rotary_dim_)
            attn = attend(q, k, v).reshape(b, t, cfg.num_heads * dh)
            rows = (k, v)
            if cfg.arch == "phi":
                ff = jax.nn.gelu(proj("fc1", hn), approximate=True)
                return (h_in + proj("wo", attn) + proj("fc2", ff),
                        (rows, no_experts))
            attn_out = proj("wo", attn)
        if cfg.arch == "gemma2":
            attn_out = rms_norm(attn_out, layer["attn_post_norm"],
                                cfg.rms_norm_eps)
        x1 = h_in + attn_out
        hn2 = rms_norm(x1, layer["mlp_norm"], cfg.rms_norm_eps)
        mlp_out, routed = self._mlp(layer, hn2, proj, token_valid,
                                    dropless=True)
        if cfg.arch == "gemma2":
            mlp_out = rms_norm(mlp_out, layer["mlp_post_norm"],
                               cfg.rms_norm_eps)
        return x1 + mlp_out, (
            rows, no_experts if routed is None else routed)

    def decode_step(self, params: Params, cache: Params,
                    tokens: jnp.ndarray,  # [B] the tokens just sampled
                    ) -> Tuple[jnp.ndarray, Params]:
        """One decode step: write `tokens` at slot prompt_T + step, return
        logits for the next token. Static shapes; position per example is
        its true length (pads skipped via the cache valid mask)."""
        cfg = self.cfg
        b = tokens.shape[0]
        max_len = cache["k"].shape[2]
        if "prompt_width" not in cache:
            raise ValueError(
                "decode_step requires a cache produced by start_decode()")
        write_idx = cache["lengths"]                       # [B] logical position

        positions = write_idx[:, None]                     # [B, 1]
        x = self._embed(params, tokens[:, None])
        cos, sin = rotary_angles(positions, cfg.rotary_dim_, cfg.rope_theta,
                                 scaling=cfg.rope_scaling)

        # Physical write slot: prompts are right-padded to a uniform width T,
        # so every row writes decode step s at the same column T + s. Rotary
        # is applied with the *logical* position at write time, and
        # cache["pos"] records each column's logical position so the causal
        # mask stays correct even though pad columns sit mid-cache.
        col = cache["prompt_width"] + cache["step"]
        kv_pos = cache["pos"]

        # Attend over the UN-updated cache plus this token's fresh k/v via
        # decode_attention (score concatenation — no [B,S,K,D] copy inside
        # the layer loop); the scan emits only the new [B,1,K,D] columns,
        # written into the cache ONCE below. The round-3 path re-emitted
        # the full [L,B,S,K,D] cache through the scan each step, ~4x the
        # necessary HBM traffic on the decode hot loop (the PPO bottleneck,
        # reference src/training/train_rlhf.py:123-124).
        # int8 caches route through the Pallas decode kernel (dequant in
        # VMEM): the XLA `_dequantize_kv` path materializes a bf16 copy
        # of the cache per layer per step — measured on chip (r5
        # sweep_decode) that made int8 KV a REGRESSION vs bf16 (b64:
        # 3.77 vs 2.71 ms/token). Kernel gates: lane-aligned head_dim,
        # GQA group <= 8, and no >1-device auto mesh (pallas has no SPMD
        # rule; replicating the cache would be worse than the dequant
        # copy). Softcap is a static kernel param; gemma-2's alternating
        # per-layer windows become a two-bias select below.
        from dla_tpu.ops.decode_kernel import GP as _KGP
        kernel_eligible = (
            cfg.decode_kernel != "off"
            and cfg.head_dim_ % 128 == 0
            and cfg.num_heads // cfg.num_kv_heads <= _KGP
            and _flash_mesh() is None)
        # "auto": int8 caches only (in-VMEM dequant is the measured
        # win); "on": bf16 caches too (fill-bounded reads vs the XLA
        # einsum's full-S reads)
        use_decode_kernel = kernel_eligible and (
            self._kv_int8 or cfg.decode_kernel == "on")
        if cfg.decode_kernel == "on" and not kernel_eligible:
            # an EXPLICIT kernel request degrading to the XLA path must
            # not be silent: a sweep recording "kernel" numbers would
            # actually measure the einsum (same one-time-per-shape
            # discipline as the int8-dense fallback log above)
            key = ("decode_kernel_on", cfg.head_dim_, cfg.num_heads,
                   tokens.shape)
            if key not in _REPLICATED_FLASH_LOGGED and \
                    jax.process_index() == 0:
                _REPLICATED_FLASH_LOGGED.add(key)
                print("[dla_tpu][decode] decode_kernel: 'on' requested "
                      "but ineligible (head_dim % 128 != 0, GQA group "
                      f"> {_KGP}, or multi-device auto mesh) — decoding "
                      "via the XLA path", file=sys.stderr, flush=True)
        if (cfg.decode_kernel == "auto" and self._kv_int8
                and not kernel_eligible):
            # 'auto' + int8 KV exists to dequantize in VMEM; an
            # ineligible model silently pays the per-layer-per-step
            # bf16 materialization the kernel was chosen to avoid —
            # the exact regression the r5 sweep measured
            key = ("decode_kernel_auto_int8", cfg.head_dim_,
                   cfg.num_heads, tokens.shape)
            if key not in _REPLICATED_FLASH_LOGGED and \
                    jax.process_index() == 0:
                _REPLICATED_FLASH_LOGGED.add(key)
                print("[dla_tpu][decode] decode_kernel: 'auto' with an "
                      "int8 KV cache but the fused kernel is ineligible "
                      "(head_dim % 128 != 0, GQA group "
                      f"> {_KGP}, or multi-device auto mesh) — each "
                      "decode step dequantizes the full cache via XLA; "
                      "expect int8 KV to run SLOWER than bf16 here",
                      file=sys.stderr, flush=True)

        attn_bias = attn_bias_win = None
        if use_decode_kernel:
            # validity+causality(+window) as additive biases built ONCE
            # per step. Uniform-window models (mistral: pattern == 1)
            # fold the window into the single shared bias; alternating-
            # window models (gemma-2: pattern > 1) get BOTH biases, and
            # each layer's traced swa_on flag picks one inside the scan
            # (a [B, S] select per layer — nothing quadratic, no
            # re-derivation of the mask from positions).
            from dla_tpu.ops.decode_kernel import NEG_INF as _KNEG
            delta = positions - kv_pos                       # [B, S]
            bmask = cache["valid"] & (delta >= 0)
            if cfg.sliding_window:
                wmask = bmask & (delta < cfg.sliding_window)
                if cfg.sliding_window_pattern > 1:
                    attn_bias_win = jnp.where(
                        wmask, 0.0, _KNEG).astype(jnp.float32)
                else:
                    bmask = wmask
            attn_bias = jnp.where(bmask, 0.0, _KNEG).astype(jnp.float32)

        def body2(carry, xs):
            layer, k_cache, v_cache, k_s, v_s = self._unpack_decode_xs(
                xs, dequantize=not use_decode_kernel)

            def attend(q, k, v):
                if use_decode_kernel:
                    from dla_tpu.ops.decode_kernel import (
                        flash_decode_attention,
                    )
                    bias_l = attn_bias
                    if attn_bias_win is not None:
                        # gemma-2 alternating SWA: the layer's traced
                        # flag picks the windowed or full bias
                        bias_l = jnp.where(layer["swa_on"],
                                           attn_bias_win, attn_bias)
                    return flash_decode_attention(
                        q, k_cache, v_cache, k, v,
                        bias=bias_l, k_scale=k_s, v_scale=v_s,
                        kv_fill=col,  # no valid col at/after write slot
                        softmax_scale=self._softmax_scale,
                        logit_softcap=cfg.attn_logit_softcap)
                return decode_attention(
                    q, k_cache, v_cache, k, v,
                    kv_valid=cache["valid"],
                    q_positions=positions, kv_positions=kv_pos,
                    window=self._layer_window(layer),
                    softmax_scale=self._softmax_scale,
                    logit_softcap=cfg.attn_logit_softcap)

            return self._decode_layer(layer, carry, cos, sin, attend)

        xs = (self._with_layer_windows(self._flat_layers(params["layers"])),
              cache["k"], cache["v"])
        if self._kv_int8:
            xs = xs + (cache["k_scale"], cache["v_scale"])
        x, ((k_cols, v_cols), _) = jax.lax.scan(body2, x, xs)
        h = self._final_norm(params, x)
        logits = self.unembed(params, h[:, 0])

        # Single cache write for the whole step: the stacked [L,B,1,K,D]
        # new columns land at physical column `col`. Inside the decode
        # scan/while carry XLA aliases the cache buffers, so this is an
        # in-place column write, not a cache copy.
        zero = jnp.zeros((), jnp.int32)

        def write_col(buf, cols, rank5=True):
            # rank5: KV [L, B, S, K, D], column dim 2; rank4: K-major
            # scales [L, B, K, S], column is the LAST dim
            idx = (zero, zero, col, zero, zero) if rank5 else \
                (zero, zero, zero, col)
            return jax.lax.dynamic_update_slice(buf, cols, idx)

        # validity/positions after writing this token
        onehot_col = jax.nn.one_hot(col, max_len, dtype=jnp.int32)[None, :]
        valid_next = cache["valid"] | (onehot_col > 0)
        kv_pos_next = jnp.where(onehot_col > 0, write_idx[:, None], kv_pos)

        new_cache = {
            "valid": valid_next,
            "lengths": cache["lengths"] + 1,
            "step": cache["step"] + 1,
            "prompt_width": cache["prompt_width"],
            "pos": kv_pos_next,
        }
        if self._kv_int8:
            kq, k_s = self._quantize_kv(k_cols)
            vq, v_s = self._quantize_kv(v_cols)
            new_cache["k"] = write_col(cache["k"], kq)
            new_cache["v"] = write_col(cache["v"], vq)
            # K-major scale storage [L, B, K, S]: the new column
            # [L, B, 1, K] transposes to [L, B, K, 1], lands at col
            new_cache["k_scale"] = write_col(
                cache["k_scale"], k_s.transpose(0, 1, 3, 2), rank5=False)
            new_cache["v_scale"] = write_col(
                cache["v_scale"], v_s.transpose(0, 1, 3, 2), rank5=False)
        else:
            new_cache["k"] = write_col(cache["k"], k_cols)
            new_cache["v"] = write_col(cache["v"], v_cols)
        return logits, new_cache

    def _paged_layers(self, params: Params, view: Params, x: jnp.ndarray,
                      positions: jnp.ndarray, adapters: Optional[Params],
                      attention):
        """The layer scan the three paged steps share, over a block-paged
        pool (dla_tpu/serving/kv_blocks.py). ``x`` [B, T, D] embedded
        tokens at absolute ``positions`` [B, T]. Block l attends jointly
        over the row's cached columns in ITS layer of every pool and the
        tokens' own fresh rows, and writes the fresh rows into its layer
        at ``write_pages`` / ``write_offs``. The cached columns are read
        one of two ways:

        - the gather (every program but the one below): each row's pages
          are gathered into the row's whole [S] window, whatever its
          fill, and ``attention`` (``decode_attention`` for one token,
          ``block_decode_attention`` for several) masks it by
          ``view["valid"]`` / ``view["pos"]``;
        - the paged kernel (ops/paged_attention.py), when the step is one
          token a row (``attention is decode_attention``), the rows are
          ``_paged_kernel_rows``, programs compile for a TPU and no
          multi-device mesh is in force (``paged_decode_kernel``): it
          walks ``block_tables`` and reads pages ``0 .. ceil(lengths /
          page) - 1`` only. It takes the mask from ``view["lengths"]``
          alone: column c at position c, valid iff c < lengths, which is
          what ``PagedKVCache`` keeps for a running slot and what
          ``valid`` / ``pos`` say in every caller
          (``ServingEngine._unpack_decode``); those two are not read on
          this path.

        Gather and write live inside the scan, a layer at a time, with
        the pools riding the scan's carry (updated in place): gathering
        every layer's window up front makes XLA transpose the whole pool
        to pages-major and the gathered view back to layer-major, and a
        write with the layer axis in its window relays the pool out again
        (on a v5e, two thirds of a decode step and 3 GiB of temporaries);
        pools scanned as inputs and outputs are copied slab by slab,
        three times a layer (PERF.md, PR 29). The kernel takes the whole
        pools and the layer index for the same reason.

        Returns (hidden after the final norm [B, T, D], the pools with
        the fresh rows written, and int32 [2] = (held experts that
        received a token, (token, choice) pairs that landed here) summed
        over layers)."""
        cfg = self.cfg
        if self._kv_int8:
            raise NotImplementedError(
                "the paged steps serve activation-dtype pages; "
                "kv_cache_dtype=int8 is only wired into the contiguous "
                "decode_step path")
        if self.hybrid is not None:
            if adapters is not None:
                raise NotImplementedError(
                    "per-slot adapters on a model with a per-layer spec")
            x, pools = self.hybrid.paged(
                params["layers"], view, x, positions, attention,
                scan_kernel=self.scan_chunk_kernel(x.shape[1]))
            return (self._final_norm(params, x), pools,
                    jnp.zeros((2,), jnp.int32))
        cos, sin = rotary_angles(positions, cfg.rotary_dim_, cfg.rope_theta,
                                 scaling=cfg.rope_scaling)
        tables = view["block_tables"]                    # [B, pages/slot]
        b, window = view["valid"].shape

        layers = dict(self._with_layer_windows(
            self._flat_layers(params["layers"])))
        layers["layer_index"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        stack = None
        if "router" in layers:
            # the routed experts' weights stay stacked outside the scan
            # (ops.moe.moe_mlp_dropless, ``layer``): the block index
            # stands in for them
            stack = {k: layers.pop(k) for k in ("w_gate", "w_up", "w_down")}

        kernel = (self.paged_decode_kernel()
                  if attention is decode_attention else None)

        def body(carry, layer):
            h, pools = carry                  # pool [L, pages, page, h, w]
            l = layer["layer_index"]
            if stack is not None:
                layer = {**layer, "expert_stack": stack}
            if kernel is None:
                cached = [p[l, tables].reshape(b, window, *p.shape[3:])
                          for p in pools]

            def attend(q, k, v):
                if kernel is not None:
                    return kernel.paged_decode_attention(
                        q[:, 0], pools[0], pools[1], tables,
                        view["lengths"], k[:, 0], v[:, 0], layer=l,
                        window=self._layer_window(layer),
                        softmax_scale=self._softmax_scale,
                        logit_softcap=cfg.attn_logit_softcap)[:, None]
                # dense: (keys, values); latent: the one pool of rows is
                # both (Transformer._latent_absorbed)
                return attention(
                    q, cached[0], cached[-1], k, v,
                    kv_valid=view["valid"],
                    q_positions=positions, kv_positions=view["pos"],
                    window=self._layer_window(layer),
                    softmax_scale=self._softmax_scale,
                    logit_softcap=cfg.attn_logit_softcap)

            h, (rows, routed) = self._decode_layer(
                layer, h, cos, sin, attend, q_positions=positions,
                token_valid=view.get("real"))
            pools = tuple(
                p.at[l, view["write_pages"], view["write_offs"]].set(r)
                for p, r in zip(pools, rows))
            return (h, pools), routed

        (x, pools), routed = jax.lax.scan(
            body, (x, tuple(view["pools"])),
            {**layers, **self.slot_lora_xs(adapters)})
        return self._final_norm(params, x), pools, jnp.sum(routed, axis=0)

    def decode_step_paged(self, params: Params, view: Params,
                          tokens: jnp.ndarray,  # [B] the tokens just sampled
                          adapters: Optional[Params] = None,
                          ) -> Tuple[jnp.ndarray, Tuple, jnp.ndarray]:
        """One decode step against a block-paged pool — the sibling of
        ``decode_step`` for the serving engine. Each sequence's cached
        columns are read through its block table, a layer at a time: the
        live pages alone by the paged kernel where ``_paged_layers`` says
        it runs, else every page gathered into the [S] window; the
        step's fresh rows are written where the caller says.

        ``view``:
          pools         tuple, one [L, pages, page, heads, width] array
                        per ``cache_rows()`` entry (keys and values, or
                        the latent rows; activation dtype)
          block_tables  [B, pages/slot]  physical page ids per row
          valid         [B, S]           columns that may be attended:
                        column c iff c < lengths (the kernel path reads
                        ``lengths`` alone)
          pos           [B, S]           logical position per column = c
          lengths       [B]              true tokens so far = this query's pos
          write_pages, write_offs  [B, T]  physical (page, offset) each
                        fresh row is written to (the trash page for rows
                        the caller masks)
          real          [B, T] optional  rows that are real (a running slot;
                        a chunk's real token): routed experts skip the others

        Returns (logits [B, V], the pools with this step's rows written,
        and the step's int32 [2] expert counters, see ``_paged_layers``).
        Rows whose window is garbage (freed serving slots) compute garbage
        that the caller masks — static shapes, no recompilation as
        requests come and go. int8 KV paging is not plumbed yet: serving
        pages store the activation dtype."""
        h, pools, routed = self._paged_layers(
            params, view, self._embed(params, tokens[:, None]),
            view["lengths"][:, None], adapters, decode_attention)
        return self.unembed(params, h[:, 0]), pools, routed

    def prefill_step_paged(self, params: Params, view: Params,
                           tokens: jnp.ndarray,     # [B, C] chunk tokens
                           positions: jnp.ndarray,  # [B, C] absolute pos
                           last_index: jnp.ndarray,  # [B] last real token
                           adapters: Optional[Params] = None,
                           ) -> Tuple[jnp.ndarray, Tuple, jnp.ndarray]:
        """One fixed-width prefill CHUNK against the paged pool — the
        chunked-prefill sibling of ``decode_step_paged``. The chunk's C
        queries attend jointly over (a) the already-computed prefix held
        in the pool, with ``valid`` marking exactly the columns BEFORE
        this chunk, and (b) the chunk's own fresh keys, causally by
        absolute position (pad tokens carry later positions than every
        real query, so they mask themselves out). Returns
        (logits [B, V] — the next-token distribution after the token at
        ``last_index``, only meaningful on the FINAL chunk — the pools
        with the chunk's rows written (pad columns to the trash page, by
        the caller's ``write_pages``), and the expert counters)."""
        h, pools, routed = self._paged_layers(
            params, view, self._embed(params, tokens), positions, adapters,
            block_decode_attention)                         # [B, C, H]
        last = h[jnp.arange(tokens.shape[0]), last_index]   # [B, H]
        return self.unembed(params, last), pools, routed

    def decode_block_paged(self, params: Params, view: Params,
                           tokens: jnp.ndarray,  # [B, G] token block
                           adapters: Optional[Params] = None,
                           ) -> Tuple[jnp.ndarray, Tuple, jnp.ndarray]:
        """Verify a G-token block against the paged pool — the
        speculative-verify sibling of ``decode_step_paged``. Row b's
        block occupies absolute positions lengths[b]..lengths[b]+G-1;
        query g attends over (a) the committed prefix (``valid`` marks
        exactly the columns BEFORE the block — draft columns must NOT be
        valid, the in-block keys supply them fresh) and (b) the block's
        own keys, causally by position. Returns (logits [B, G, V] — one
        next-token distribution per block position — the pools with the
        block's rows written (rejected columns are the caller's rollback
        problem), and the expert counters)."""
        positions = view["lengths"][:, None] + \
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]  # [B, G]
        h, pools, routed = self._paged_layers(
            params, view, self._embed(params, tokens), positions, adapters,
            block_decode_attention)
        return self.unembed(params, h), pools, routed               # [B,G,V]

    def start_decode(self, params: Params, input_ids: jnp.ndarray,
                     attention_mask: jnp.ndarray, max_new_tokens: int,
                     ) -> Tuple[jnp.ndarray, Params]:
        """Prefill + set up decode bookkeeping. Returns (first logits, cache)."""
        b, t = input_ids.shape
        cache0 = self.init_cache(b, t + max_new_tokens)
        logits, cache = self.prefill(params, cache0, input_ids, attention_mask)
        max_len = t + max_new_tokens
        cache["prompt_width"] = jnp.asarray(t, jnp.int32)
        cache["pos"] = jnp.broadcast_to(
            jnp.arange(max_len)[None, :], (b, max_len)).astype(jnp.int32)
        return logits, cache

    def decode_block(self, params: Params, cache: Params,
                     tokens: jnp.ndarray,  # [B, G] a block of tokens
                     ) -> Tuple[jnp.ndarray, Params]:
        """Multi-token decode step: score a block of G tokens in ONE
        forward against the cache (intra-block causal via
        ops.attention.block_decode_attention), writing all G KV columns
        once. Returns (logits [B, G, V], cache) where logits[:, i] is
        the next-token distribution AFTER tokens[:, :i+1] — the
        verification forward of speculative decoding. The write is
        TENTATIVE: every new column is marked valid and lengths advance
        by G; a caller that rejects a per-row suffix retracts it with
        ``retract_block`` (columns invalidated, lengths corrected).
        G == 1 is semantically decode_step."""
        cfg = self.cfg
        b, g = tokens.shape
        if "prompt_width" not in cache:
            raise ValueError(
                "decode_block requires a cache produced by start_decode()")
        lengths0 = cache["lengths"]                        # [B]
        positions = lengths0[:, None] + jnp.arange(g)[None, :]  # [B, G]
        x = self._embed(params, tokens)
        cos, sin = rotary_angles(positions, cfg.rotary_dim_, cfg.rope_theta,
                                 scaling=cfg.rope_scaling)
        col0 = cache["prompt_width"] + cache["step"]
        kv_pos = cache["pos"]
        if self._kv_int8:
            # block verify dequantizes via the XLA path (the Pallas
            # decode kernel is single-token); speculative decoding with
            # an int8 cache pays the materialization decode_step's
            # kernel exists to avoid — say so once rather than letting
            # a benchmark silently measure the slow path
            key = ("decode_block_int8", tokens.shape)
            if key not in _REPLICATED_FLASH_LOGGED and \
                    jax.process_index() == 0:
                _REPLICATED_FLASH_LOGGED.add(key)
                print("[dla_tpu][decode] decode_block with an int8 KV "
                      "cache uses the XLA dequant path (the fused "
                      "kernel is single-token); prefer bf16 caches for "
                      "speculative decoding", file=sys.stderr, flush=True)

        def body(carry, xs):
            layer, k_cache, v_cache, _, _ = self._unpack_decode_xs(
                xs, dequantize=True)

            def attend(q, k, v):
                return block_decode_attention(
                    q, k_cache, v_cache, k, v,
                    kv_valid=cache["valid"],
                    q_positions=positions, kv_positions=kv_pos,
                    window=self._layer_window(layer),
                    softmax_scale=self._softmax_scale,
                    logit_softcap=cfg.attn_logit_softcap)

            return self._decode_layer(layer, carry, cos, sin, attend)

        xs = (self._with_layer_windows(self._flat_layers(params["layers"])),
              cache["k"], cache["v"])
        if self._kv_int8:
            xs = xs + (cache["k_scale"], cache["v_scale"])
        x, ((k_cols, v_cols), _) = jax.lax.scan(body, x, xs)
        h = self._final_norm(params, x)
        logits = self.unembed(params, h)                   # [B, G, V]

        zero = jnp.zeros((), jnp.int32)
        max_len = cache["k"].shape[2]

        def write_cols(buf, cols, rank5=True):
            idx = (zero, zero, col0, zero, zero) if rank5 else \
                (zero, zero, zero, col0)
            return jax.lax.dynamic_update_slice(buf, cols, idx)

        colmask = jax.nn.one_hot(  # [B?, S] no: [S] per col block
            col0 + jnp.arange(g), max_len, dtype=jnp.int32).sum(0)[None, :]
        valid_next = cache["valid"] | (colmask > 0)
        # logical position of physical col col0+i for row b is
        # lengths0[b] + i: scatter the block's positions in
        block_pos = jnp.zeros_like(kv_pos)
        block_pos = jax.lax.dynamic_update_slice(
            block_pos, positions, (zero, col0))
        kv_pos_next = jnp.where(colmask > 0, block_pos, kv_pos)

        new_cache = {
            "valid": valid_next,
            "lengths": lengths0 + g,
            "step": cache["step"] + g,
            "prompt_width": cache["prompt_width"],
            "pos": kv_pos_next,
        }
        if self._kv_int8:
            kq, k_s = self._quantize_kv(k_cols)
            vq, v_s = self._quantize_kv(v_cols)
            new_cache["k"] = write_cols(cache["k"], kq)
            new_cache["v"] = write_cols(cache["v"], vq)
            new_cache["k_scale"] = write_cols(
                cache["k_scale"], k_s.transpose(0, 1, 3, 2), rank5=False)
            new_cache["v_scale"] = write_cols(
                cache["v_scale"], v_s.transpose(0, 1, 3, 2), rank5=False)
        else:
            new_cache["k"] = write_cols(cache["k"], k_cols)
            new_cache["v"] = write_cols(cache["v"], v_cols)
        return logits, new_cache

    @staticmethod
    def retract_block(cache: Params, keep: jnp.ndarray,  # [B] 0..G
                      g: int) -> Params:
        """Undo the tentative acceptance of the LAST decode_block: per
        row, only the first ``keep[b]`` of its G columns stay valid;
        lengths roll back to pre-block + keep. The KV bytes of rejected
        columns stay in place (invalid, never attended) and are
        overwritten by... nothing — speculative decoding advances the
        physical cursor by G every round, trading cache columns for
        fewer serial steps."""
        col0 = cache["prompt_width"] + cache["step"] - g
        max_len = cache["valid"].shape[1]
        off = jnp.arange(max_len)[None, :] - col0          # [1, S]
        in_block = (off >= 0) & (off < g)
        keep_mask = off < keep[:, None]                    # [B, S]
        valid = jnp.where(in_block, cache["valid"] & keep_mask,
                          cache["valid"])
        return {**cache, "valid": valid,
                "lengths": cache["lengths"] - g + keep}
