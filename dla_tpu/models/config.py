"""Model hyperparameter schema + registry of presets.

Replaces the reference's reliance on HF ``AutoConfig``/``AutoModel``
(src/models/base_model.py:17-42): model architecture is explicit data here,
so the same transformer code serves Llama-2 7B/13B/70B, Mistral-7B, phi-2
-class students, and tiny test models.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple


class LayerSpec(NamedTuple):
    """What one layer is, as the model code and the cache manager read
    it. ``mixer`` is the token mixer in front of the block's MLP:

      attention            multi-head / grouped- / multi-query attention
                           over the layer's own keys and values; inside
                           ``ModelConfig.layers`` it is the plain form
                           (no rotary, bias, softcap or window)
      latent_attention     multi-head latent attention (one latent row;
                           the homogeneous stack's alone)
      ssm                  selective state-space layer (Mamba-1); its
                           scan output is also the memory the next
                           ``gmu`` layers gate
      ssm2                 Mamba-2 state-space layer (one scalar decay a
                           head, a state matrix a head, B and C shared by
                           a group, a gated RMSNorm before ``out_proj``)
      diff_attention       differential attention (arXiv:2410.05258)
                           over the layer's own keys and values
      gmu                  gated memory unit (arXiv:2507.06607): gates the
                           memory of the nearest ``ssm`` layer below
      cross_diff_attention differential attention whose keys and values
                           are those of the one ``paged``
                           ``diff_attention`` layer below

    ``cache`` is what the layer keeps for a sequence between steps:

      paged         rows a token, for as long as the request lives;
                    a model may have any number of such layers, each
                    with rows of its own
      paged_window  rows a token, dropped once behind every future
                    query's ``window``
      state         a fixed-size recurrent state a sequence
      shared        nothing of its own: it reads another layer's pages
      none          nothing

    ``window``: the layer attends keys in (pos - window, pos]; None =
    every earlier key."""
    mixer: str
    cache: str
    window: Optional[int] = None


class CacheArray(NamedTuple):
    """One device array a cache manager holds for a model
    (``Transformer.cache_spec()``). By ``kind``:

      paged         [layers, pages, page_size, *shape]: rows a token,
                    addressed through a slot's block table
      paged_window  the same over a pool of its own, whose pages go back
                    to the allocator once behind ``window``
      state         [layers, slots, *shape]: indexed by slot, not paged

    """
    kind: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Any
    window: Optional[int] = None


MIXERS = ("attention", "latent_attention", "ssm", "ssm2", "diff_attention",
          "gmu", "cross_diff_attention")
CACHE_KINDS = ("paged", "paged_window", "state", "shared", "none")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: Optional[int] = None      # defaults to hidden_size // num_heads
    rope_theta: float = 10000.0
    # HF ``rope_scaling`` dict for extended-context checkpoints:
    # {"rope_type": "llama3", factor, low_freq_factor, high_freq_factor,
    #  original_max_position_embeddings} (llama-3.1/3.2) or
    # {"rope_type": "linear", factor} — ops/rotary.py:_scale_inv_freq.
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_seq_length: int = 2048
    # architecture family:
    #   "llama"  pre-RMSNorm sequential block, gated-SiLU MLP, full RoPE
    #            (llama-2/-3, mistral)
    #   "phi"    parallel residual block (shared input LayerNorm feeding
    #            both attention and MLP), biased projections, GELU MLP,
    #            partial RoPE (phi-2 / phi-1.5)
    #   "gemma"  llama block shape with gated GELU-tanh MLP, embeddings
    #            scaled by sqrt(hidden) on read, tied unembedding, and
    #            (1+w) RMSNorm — the +1 folds into the stored weights at
    #            import/init so the norm path stays shared (gemma-1)
    #   "gemma2" gemma plus: post-attention and post-feedforward norms
    #            (four RMSNorms per block), attention-score and final
    #            -logit softcapping, query_pre_attn_scalar softmax scale,
    #            and alternating-layer sliding window (pattern 2)
    arch: str = "llama"
    # fraction of head_dim that rotates (phi-2: 0.4); 1.0 = full RoPE
    rotary_pct: float = 1.0
    # biases on the q/k/v projections within the llama block layout —
    # the qwen2 family (phi carries biases on every projection already)
    attention_bias: bool = False
    # mistral-style sliding-window attention (HF ``sliding_window``):
    # each token attends kv positions in (pos - window, pos]. None/0 =
    # full causal. Applies to every attention path: flash, xla, decode,
    # and ring context parallelism (absolute-position mask term rotates
    # with kv). Ulysses CP is the one refusal — Transformer.__init__
    # raises when both are set under an active sequence mesh (the mesh
    # isn't known here, and context_parallel is a harmless default
    # otherwise).
    sliding_window: Optional[int] = None
    # Alternating-layer SWA (gemma-2/-3): layer l uses the sliding
    # window iff (l + 1) % pattern != 0 — pattern 2 = every other layer
    # windowed starting at layer 0 (HF Gemma2's is_sliding), pattern 1 =
    # uniform (every layer windowed when sliding_window is set).
    sliding_window_pattern: int = 1
    # gemma-2 softcaps: scores <- cap * tanh(scores / cap) before the
    # softmax (attn) / at the unembedding (final). 0 = off.
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # gemma-2 attention scale: softmax scale = query_pre_attn_scalar
    # ** -0.5 (HF Gemma2Config; 27B uses hidden/num_heads != head_dim).
    # None = the usual head_dim ** -0.5.
    query_pre_attn_scalar: Optional[int] = None
    # numerics
    dtype: str = "bfloat16"             # activation dtype
    param_dtype: str = "float32"        # master param dtype
    # remat: "none" | "full" | "dots"  (jax.checkpoint policy per block)
    remat: str = "full"
    # attention backend: "xla" (fused einsum) | "flash" (pallas kernel,
    # used on the full-sequence path when shapes allow; decode/packed
    # paths always use xla)
    attention: str = "xla"
    # KV-cache storage dtype for autoregressive decode: "bfloat16"
    # (stores in the activation dtype) | "int8" (per-position per-head
    # symmetric quantization with fp scales — halves the cache's HBM
    # traffic on the bandwidth-bound decode loop; dequantize fuses into
    # the attention einsum). Training/prefill attention is unaffected.
    kv_cache_dtype: str = "bfloat16"
    # Pallas decode-attention kernel selection: "auto" engages it for
    # int8 caches (where in-VMEM dequant is the measured win); "on"
    # additionally routes bf16 caches through it (fill-bounded reads vs
    # the XLA einsum's full-S reads — sweepable per chip); "off" forces
    # the XLA decode_attention path everywhere.
    # Eligibility (transformer.decode_step): head_dim % 128 == 0 (lane
    # alignment), num_heads/num_kv_heads <= the kernel's GQA group cap,
    # and no multi-device auto mesh. An ineligible model falls back to
    # the XLA path — with int8 KV that path re-materializes a bf16 cache
    # copy per layer per step, so int8 + ineligible is SLOWER than bf16
    # (logged once per shape at decode time).
    decode_kernel: str = "auto"
    # flash kernel tile sizes (0 = the kernel's measured default, 512).
    # 512-wide blocks measured ~1.8x faster than 128 on v5e; exposed so
    # new chip generations / unusual shapes can retune without a fork.
    flash_block_q: int = 0
    flash_block_k: int = 0
    # context parallelism over the `sequence` mesh axis (long-context):
    # "ring" (ppermute KV rotation, any head count) | "ulysses" (head
    # all-to-all, needs kv_heads % seq_axis == 0). Active only when the
    # ambient mesh has sequence > 1; decode paths always run unsharded.
    context_parallel: str = "ring"
    # GPipe microbatch count when the mesh has stage > 1 (pipeline
    # parallelism). 0 = auto (targets 4x the stage count, see
    # ops.pipeline.resolve_microbatches). More microbatches shrink the
    # (S-1)/(M+S-1) bubble at the cost of smaller per-stage matmuls;
    # batch must be divisible by it.
    pipeline_microbatches: int = 0
    # Interleaved/circular pipeline (virtual stages): each physical
    # stage owns V round-robin layer blocks and microbatches traverse
    # the ring V times — bubble (S-1)/(V*S + S - 1) with only S
    # microbatches of activation in flight (vs needing M = V*S
    # microbatches for the same bubble under plain GPipe). Requires
    # num_layers % (stage * V) == 0; M is pinned to the stage count.
    pipeline_interleave: int = 1
    # storage hint for the interleaved schedule: when > 1 (and
    # pipeline_interleave > 1) the stacked layer dim of every layer/LoRA
    # leaf is stored block-major [V, S, L/(S*V), ...] — a row-major
    # reshape of the canonical [L] stack — so the circular schedule's
    # round-robin block ownership is stage-shard-local (no per-step
    # cross-stage weight reshard). The config loader sets this from
    # hardware.mesh.stage; couples param storage SHAPE (not order) to
    # the stage count — cross-topology moves are a free reshape via
    # Transformer.to_canonical_layout/to_storage_layout.
    pipeline_stages: int = 0
    # Mixture-of-Experts (beyond-reference capability; makes the
    # reserved `expert` mesh axis real — ops/moe.py). 0 = dense MLP.
    # llama arch only; top-k routing with GShard capacity dispatch.
    num_experts: int = 0
    num_experts_per_token: int = 2
    moe_capacity_factor: float = 1.25
    # GShard token-group size: capacity is enforced per group of this
    # many tokens, keeping dispatch memory/FLOPs O(T) at long context
    moe_group_size: int = 512
    moe_aux_weight: float = 0.01      # switch load-balance loss weight
    moe_z_weight: float = 0.001       # router z-loss weight
    # width of one routed (and one shared) expert where it differs from
    # the dense width (HF ``moe_intermediate_size``); 0 = intermediate_size
    moe_intermediate_size: int = 0
    # shared experts: a dense gated MLP of width num_shared_experts *
    # expert width that every token passes beside its routed experts
    num_shared_experts: int = 0
    # multiplies the renormalised top-k router weights (HF
    # ``routed_scaling_factor``)
    moe_routed_scale: float = 1.0
    # Expert parallelism as one chip sees it: this process holds the
    # routed experts [moe_first_expert, moe_first_expert +
    # moe_experts_held) of num_experts (0 held = all of them). The router
    # scores all num_experts; a choice that lands on an expert held
    # elsewhere adds nothing here (its owner adds it: the per-shard body
    # of the `expert` mesh axis, run without the exchange on one chip).
    moe_first_expert: int = 0
    moe_experts_held: int = 0
    # router scores: "softmax" (the chosen logits softmaxed) or "sigmoid"
    # (DeepSeek-V3's ``noaux_tc``: sigmoid of every logit, the top k of
    # score + a per-expert correction bias chosen, the chosen scores
    # without the bias renormalised, times moe_routed_scale)
    moe_scoring: str = "softmax"
    # leading dense layers (HF ``first_k_dense_replace``): the first this
    # many layers run a dense MLP of intermediate_size, the rest routed
    # experts; the two runs are scanned apart (params["dense_layers"])
    first_k_dense: int = 0
    # Multi-head latent attention (DeepSeek-V2 / mistral4): kv_lora_rank
    # > 0 makes the attention latent — queries through a q_lora_rank
    # bottleneck (0 = a plain wq), keys and values expanded from one
    # normalised kv_lora_rank latent per token, heads of qk_nope_head_dim
    # unrotated + qk_rope_head_dim rotated dims (one rotated key shared
    # by all heads) and v_head_dim values. The cached row is
    # [latent | rotated key]: kv_lora_rank + qk_rope_head_dim numbers a
    # token a layer. A property of the attention, not an `arch`: the
    # block stays llama-shaped.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotary pairing: False = split halves (x[i], x[i + d/2]), the
    # llama convention; True = adjacent dims (x[2i], x[2i+1]) (HF
    # ``rope_interleave``)
    rope_interleave: bool = False
    # learned sparse attention (DSA, DeepSeek-V3.2's indexer; GLM-5):
    # index_topk > 0 gives every latent-attention layer an indexer of
    # index_n_heads heads of index_head_dim (its first qk_rope_head_dim
    # dims rotated, adjacent pairs iff index_rope_interleave) whose key, a
    # LayerNorm'd index_head_dim row a token, the paged pool keeps beside
    # the latent row; each query's softmax runs over the index_topk
    # cached tokens the indexer scores highest. The paged serving steps
    # alone compute it
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_rope_interleave: bool = False
    # LoRA (the reference's model.lora block, advertised but never wired —
    # reference base_model.py:45-49 dead code, SURVEY.md sec 2.5; here it
    # is functional). lora_r == 0 disables. Adapters are a separate
    # trainable pytree (Transformer.init_lora); base params stay frozen.
    lora_r: int = 0
    lora_alpha: float = 32.0
    lora_dropout: float = 0.0
    lora_targets: tuple = ("wq", "wk", "wv", "wo")
    # Per-layer spec, one (mixer, cache, window) entry a layer (see
    # LayerSpec), for a model whose layers are not all of one kind. None
    # = derived from the fields above (``layer_spec``): one entry
    # repeated, or the alternating window of ``sliding_window_pattern``.
    # A model that sets it runs the sequential block x + mixer(norm(x)),
    # x + mlp(norm(x)) with a gated-SiLU MLP and no rotary embedding (its
    # state-space layers carry the order), whatever ``arch`` says.
    layers: Optional[Tuple[LayerSpec, ...]] = None
    # "rms" | "layer": LayerNorm with weight and bias, in the blocks of a
    # model with ``layers`` set and before the head
    norm: str = "rms"
    # selective state-space layers (Mamba-1): state size N, convolution
    # width, inner width = ssm_expand * hidden_size, and the rank of the
    # step-size projection (0 = ceil(hidden_size / 16))
    ssm_state_size: int = 16
    ssm_conv_width: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    # whether the mixer RMS-norms dt, B and C between the projection that
    # makes them and their use (HF JambaMambaMixer's dt_layernorm /
    # b_layernorm / c_layernorm, eps = rms_norm_eps)
    ssm_inner_norms: bool = False
    # Mamba-2 layers (mixer ``ssm2``, arXiv:2405.21060): the inner width
    # ssm_inner_ is ssm_heads heads of ssm_head_dim, each with one scalar
    # decay and a float32 state [ssm_head_dim, ssm_state_size]; B and C are
    # shared by ssm_groups groups of heads; ssm_chunk tokens are a block of
    # the chunked (SSD) form a prefill chunk runs
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # muP scalars (Granite 4.0): the embedding times embedding_multiplier,
    # each residual branch times residual_multiplier, attention scores
    # times attention_multiplier (0 = head_dim ** -0.5), the logits over
    # logits_scaling. At 1 (0 for the scale) nothing is multiplied: the
    # programs of every other model are the ones they were
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.layers is not None:
            object.__setattr__(self, "layers", tuple(
                LayerSpec(*e) for e in self.layers))
            self._check_layers()
        if self.norm not in ("rms", "layer") or (
                self.norm == "layer" and self.layers is None):
            raise ValueError(
                f"norm={self.norm!r}: 'layer' is implemented for models "
                "with a per-layer spec (`layers`); arch='phi' has its own")
        if self.latent_attention:
            if self.arch != "llama":
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) is implemented "
                    f"for the llama block only, not arch='{self.arch}'")
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim) <= 0 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs qk_nope_head_dim, v_head_dim "
                    "and an even qk_rope_head_dim")
            if (self.kv_cache_dtype != "bfloat16" or self.attention_bias
                    or self.sliding_window or self.lora_r > 0):
                raise ValueError(
                    "latent attention runs without int8 KV, projection "
                    "biases, a sliding window or LoRA adapters")
        if self.index_topk and not (
                self.latent_attention and self.index_n_heads > 0
                and self.index_head_dim >= self.qk_rope_head_dim
                and self.q_lora_rank > 0):
            raise ValueError(
                "the sparse-attention indexer (index_topk > 0) sits inside "
                "latent attention with a query bottleneck (q_lora_rank > "
                "0) and needs index_n_heads and an index_head_dim of at "
                "least qk_rope_head_dim")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', got "
                f"{self.moe_scoring!r}")
        if self.first_k_dense and not (
                self.num_experts > 0 and self.layers is None
                and 0 < self.first_k_dense < self.num_layers
                and self.pipeline_stages <= 1):
            raise ValueError(
                f"first_k_dense={self.first_k_dense}: leading dense layers "
                "come before routed-expert layers (num_experts > 0) of the "
                "homogeneous stack, fewer than num_layers, unpipelined")
        if self.kv_cache_dtype not in ("bfloat16", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'bfloat16' or 'int8', got "
                f"{self.kv_cache_dtype!r} — a typo here would silently "
                "run the full-precision cache")
        if self.decode_kernel not in ("auto", "on", "off"):
            raise ValueError(
                f"decode_kernel must be 'auto', 'on' or 'off', got "
                f"{self.decode_kernel!r} — a typo here would silently "
                "fall back to the XLA decode path")
        if self.num_experts > 0:
            if self.arch != "llama":
                raise ValueError(
                    f"MoE (num_experts={self.num_experts}) is implemented "
                    f"for the llama block only, not arch='{self.arch}'")
            if not (0 <= self.moe_first_expert and self.moe_first_expert
                    + self.experts_held_ <= self.num_experts):
                raise ValueError(
                    f"held experts [{self.moe_first_expert}, "
                    f"{self.moe_first_expert + self.experts_held_}) lie "
                    f"outside the {self.num_experts} the router scores")
            if self.lora_r > 0:
                ffn = {"w_gate", "w_up", "w_down", "fc1", "fc2"}
                bad = ffn & set(self.lora_targets)
                if bad:
                    raise ValueError(
                        f"LoRA targets {sorted(bad)} are dense-MLP "
                        f"matrices; with num_experts > 0 restrict "
                        f"lora_targets to attention projections")

    def _check_layers(self) -> None:
        if len(self.layers) != self.num_layers:
            raise ValueError(
                f"`layers` has {len(self.layers)} entries for "
                f"num_layers={self.num_layers}")
        mixers = {spec.mixer for spec in self.layers}
        paged = [l for l, spec in enumerate(self.layers)
                 if spec.cache == "paged"]
        memory = False
        for l, spec in enumerate(self.layers):
            if spec.mixer not in MIXERS or spec.cache not in CACHE_KINDS:
                raise ValueError(f"layer {l}: unknown spec {spec}")
            if spec.mixer == "latent_attention":
                raise ValueError(
                    f"layer {l}: mixer {spec.mixer!r} is run by the "
                    "homogeneous stack (leave `layers` unset)")
            want = {"ssm": ("state",), "ssm2": ("state",), "gmu": ("none",),
                    "attention": ("paged",),
                    "cross_diff_attention": ("shared",),
                    "diff_attention": ("paged", "paged_window")}[spec.mixer]
            if spec.cache not in want or (
                    (spec.cache == "paged_window") != bool(spec.window)):
                raise ValueError(
                    f"layer {l}: mixer {spec.mixer!r} with cache "
                    f"{spec.cache!r} and window {spec.window}")
            if spec.mixer == "gmu" and not memory:
                raise ValueError(f"layer {l}: gmu with no ssm layer below")
            if spec.mixer == "cross_diff_attention" and not (
                    len(paged) == 1 and paged[0] < l and
                    self.layers[paged[0]].mixer == "diff_attention"):
                raise ValueError(
                    f"layer {l}: cross attention reads the one paged "
                    "diff_attention layer below it; the spec has paged "
                    f"layers {paged}")
            memory = memory or spec.mixer == "ssm"
        if (self.kv_cache_dtype != "bfloat16" or self.lora_r > 0
                or self.latent_attention or self.sliding_window):
            raise ValueError(
                "a model with a per-layer spec runs without int8 KV (its "
                "pages and its recurrent state have no quantised form), "
                "LoRA adapters or latent attention, and states its windows "
                "in `layers`, not in sliding_window")
        # routed experts (num_experts > 0) sit in EVERY layer of a spec'd
        # model: the spec states no per-layer FFN kind, so experts in
        # every n-th layer beside dense MLPs have no form here
        if {"ssm", "ssm2"} <= mixers:
            raise ValueError(
                "Mamba-1 (ssm) and Mamba-2 (ssm2) layers in one spec: the "
                "cache states one shape of recurrent state")
        if "ssm2" in mixers and (
                self.ssm_heads <= 0
                or self.ssm_heads * self.ssm_head_dim != self.ssm_inner_
                or self.ssm_groups != 1 or "gmu" in mixers):
            raise ValueError(
                "an ssm2 layer needs ssm_heads x ssm_head_dim == "
                f"ssm_expand x hidden_size ({self.ssm_heads} x "
                f"{self.ssm_head_dim} vs {self.ssm_inner_}); ssm_groups > 1 "
                f"(here {self.ssm_groups}) and gated memory units over a "
                "Mamba-2 layer are not built")
        if "attention" in mixers and (
                self.attention_bias or self.attn_logit_softcap):
            raise ValueError(
                "mixer 'attention' inside `layers` is the plain form: no "
                "projection biases (attention_bias) and no "
                "attn_logit_softcap")
        if mixers & {"diff_attention", "cross_diff_attention"} and (
                self.num_heads % 2 or self.num_kv_heads % 2 or (
                    self.num_heads // 2) % (self.num_kv_heads // 2)):
            raise ValueError(
                "differential attention pairs heads: num_heads and "
                "num_kv_heads must be even, query pairs a multiple of "
                "key/value pairs")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} is no multiple of "
                f"num_kv_heads {self.num_kv_heads}")

    @property
    def layer_spec(self) -> Tuple[LayerSpec, ...]:
        """One LayerSpec a layer: ``layers`` where the model states it,
        else derived (one mixer, every layer paged, the window on the
        layers ``sliding_window_pattern`` says: layer l slides iff
        pattern is 1 or (l + 1) % pattern != 0, HF Gemma2's rule)."""
        if self.layers is not None:
            return self.layers
        mixer = "latent_attention" if self.latent_attention else "attention"
        pat = self.sliding_window_pattern
        return tuple(
            LayerSpec(mixer, "paged", self.sliding_window
                      if self.sliding_window and (pat == 1 or (l + 1) % pat)
                      else None)
            for l in range(self.num_layers))

    @property
    def ssm_inner_(self) -> int:
        return self.ssm_expand * self.hidden_size

    @property
    def ssm_dt_rank_(self) -> int:
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def head_dim_(self) -> int:
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def latent_row_width_(self) -> int:
        """Numbers one cached token stores per layer under latent
        attention: kv_lora_rank + qk_rope_head_dim, rounded up to whole
        128-lane rows once it fills one (320 -> 384; the pad lanes hold
        zeros). A row that is no multiple of the TPU's lane width gets a
        pages-minor default layout there, and every gather and write of
        a page then relays the pool out (PERF.md, PR 29)."""
        width = self.kv_lora_rank + self.qk_rope_head_dim
        return width if width < 128 else -(-width // 128) * 128

    @property
    def expert_layers_(self) -> int:
        """Layers with routed experts (0 for a dense model)."""
        return self.num_layers - self.first_k_dense if self.num_experts else 0

    @property
    def expert_width_(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def experts_held_(self) -> int:
        return self.moe_experts_held or self.num_experts

    @property
    def rotary_dim_(self) -> int:
        """Rotated slice of each head; even, as rotate_half requires."""
        if self.latent_attention:
            return self.qk_rope_head_dim
        rd = int(self.head_dim_ * self.rotary_pct)
        rd -= rd % 2
        if rd <= 0:
            raise ValueError(
                f"rotary_pct {self.rotary_pct} rotates {rd} of "
                f"{self.head_dim_} head dims; needs at least 2")
        return rd

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ModelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in fields}
        if "lora_targets" in d:
            d["lora_targets"] = tuple(d["lora_targets"])
        if d.get("layers") is not None:
            d["layers"] = tuple(
                LayerSpec(**e) if isinstance(e, dict) else LayerSpec(*e)
                for e in d["layers"])
        return cls(**d)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Registry: name -> ModelConfig. Names accepted anywhere the reference
# accepts an HF repo id (model_name_or_path config keys).
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, ModelConfig] = {}


def register_model(name: str, cfg: ModelConfig) -> None:
    _REGISTRY[name.lower()] = cfg


def get_model_config(name: str, **overrides: Any) -> ModelConfig:
    key = name.lower()
    if key not in _REGISTRY:
        raise KeyError(
            f"Unknown model preset '{name}'. Known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[key]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def known_models() -> Dict[str, ModelConfig]:
    return dict(_REGISTRY)


register_model("llama2-7b", ModelConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=11008,
    num_layers=32, num_heads=32, num_kv_heads=32, max_seq_length=4096))
register_model("llama2-13b", ModelConfig(
    vocab_size=32000, hidden_size=5120, intermediate_size=13824,
    num_layers=40, num_heads=40, num_kv_heads=40, max_seq_length=4096))
register_model("llama2-70b", ModelConfig(
    vocab_size=32000, hidden_size=8192, intermediate_size=28672,
    num_layers=80, num_heads=64, num_kv_heads=8, max_seq_length=4096))
register_model("mistral-7b", ModelConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, max_seq_length=8192,
    sliding_window=4096))  # HF config.json sliding_window (mistral v0.1)
register_model("gemma-2b", ModelConfig(
    vocab_size=256000, hidden_size=2048, intermediate_size=16384,
    num_layers=18, num_heads=8, num_kv_heads=1, head_dim=256,
    rms_norm_eps=1e-6, tie_embeddings=True, max_seq_length=8192,
    arch="gemma"))  # HF google/gemma-2b config.json (MQA)
register_model("gemma-7b", ModelConfig(
    vocab_size=256000, hidden_size=3072, intermediate_size=24576,
    num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
    rms_norm_eps=1e-6, tie_embeddings=True, max_seq_length=8192,
    arch="gemma"))
register_model("gemma2-2b", ModelConfig(
    vocab_size=256000, hidden_size=2304, intermediate_size=9216,
    num_layers=26, num_heads=8, num_kv_heads=4, head_dim=256,
    rms_norm_eps=1e-6, tie_embeddings=True, max_seq_length=8192,
    arch="gemma2", sliding_window=4096, sliding_window_pattern=2,
    attn_logit_softcap=50.0, final_logit_softcap=30.0,
    query_pre_attn_scalar=256))  # HF google/gemma-2-2b config.json
register_model("gemma2-9b", ModelConfig(
    vocab_size=256000, hidden_size=3584, intermediate_size=14336,
    num_layers=42, num_heads=16, num_kv_heads=8, head_dim=256,
    rms_norm_eps=1e-6, tie_embeddings=True, max_seq_length=8192,
    arch="gemma2", sliding_window=4096, sliding_window_pattern=2,
    attn_logit_softcap=50.0, final_logit_softcap=30.0,
    query_pre_attn_scalar=256))
register_model("llama3-8b", ModelConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
    max_seq_length=8192))  # HF meta-llama/Meta-Llama-3-8B config.json
register_model("llama3.1-8b", ModelConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
    max_seq_length=131072,
    rope_scaling={"rope_type": "llama3", "factor": 8.0,
                  "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                  "original_max_position_embeddings": 8192}))
register_model("llama3-70b", ModelConfig(
    vocab_size=128256, hidden_size=8192, intermediate_size=28672,
    num_layers=80, num_heads=64, num_kv_heads=8, rope_theta=500000.0,
    max_seq_length=8192))
register_model("llama3.2-1b", ModelConfig(
    vocab_size=128256, hidden_size=2048, intermediate_size=8192,
    num_layers=16, num_heads=32, num_kv_heads=8, rope_theta=500000.0,
    tie_embeddings=True, max_seq_length=131072,
    rope_scaling={"rope_type": "llama3", "factor": 32.0,
                  "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                  "original_max_position_embeddings": 8192}))
register_model("llama3.2-3b", ModelConfig(
    vocab_size=128256, hidden_size=3072, intermediate_size=8192,
    num_layers=28, num_heads=24, num_kv_heads=8, rope_theta=500000.0,
    tie_embeddings=True, max_seq_length=131072,
    rope_scaling={"rope_type": "llama3", "factor": 32.0,
                  "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                  "original_max_position_embeddings": 8192}))
register_model("phi3-mini", ModelConfig(
    vocab_size=32064, hidden_size=3072, intermediate_size=8192,
    num_layers=32, num_heads=32, num_kv_heads=32, rope_theta=10000.0,
    max_seq_length=4096, sliding_window=2047,
    # llama block shape: HF Phi3 fuses qkv/gate_up in storage only
    # (hf_import splits them); microsoft/Phi-3-mini-4k-instruct
    ))
register_model("qwen2-7b", ModelConfig(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944,
    num_layers=28, num_heads=28, num_kv_heads=4, rope_theta=1e6,
    rms_norm_eps=1e-6, max_seq_length=131072, attention_bias=True))
# phi-2 (2.7B): true architecture — parallel residual block, partial
# rotary (0.4), LayerNorm, biased projections, GELU MLP (HF
# microsoft/phi-2 config.json values; weight import in models/hf_import)
register_model("phi-2", ModelConfig(
    vocab_size=51200, hidden_size=2560, intermediate_size=10240,
    num_layers=32, num_heads=32, num_kv_heads=32, max_seq_length=2048,
    arch="phi", rotary_pct=0.4, rms_norm_eps=1e-5))
# mixtral 8x7B (MoE): 8 experts, top-2 routing — beyond-reference
# capability exercising the `expert` mesh axis. HF mixtral checkpoints
# import via models/hf_import (block_sparse_moe mapping,
# logits-parity-tested against transformers).
register_model("mixtral-8x7b", ModelConfig(
    vocab_size=32000, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, rope_theta=1e6,
    max_seq_length=32768, num_experts=8, num_experts_per_token=2))
# tiny models for tests / smoke runs
register_model("tiny", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=192,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_length=256,
    param_dtype="float32", dtype="float32", remat="none"))
register_model("tiny-gqa", ModelConfig(
    vocab_size=512, hidden_size=128, intermediate_size=384,
    num_layers=4, num_heads=8, num_kv_heads=4, max_seq_length=512,
    param_dtype="float32", dtype="float32", remat="none"))
register_model("tiny-moe", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_length=256,
    num_experts=4, num_experts_per_token=2,
    param_dtype="float32", dtype="float32", remat="none"))
# latent attention + routed experts top-2 of 8 + one shared expert, YaRN
# over a 16-token original context with the position-dependent query
# scale: the mistral4 block at toy widths. moe_capacity_factor = E / k
# gives every expert room for every token, so the capacity dispatch of
# the full-sequence path drops nothing and apply() can be held to the
# dropless reference.
register_model("tiny-mla-moe", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=192,
    num_layers=2, num_heads=4, num_kv_heads=4, max_seq_length=256,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
    rope_scaling={"rope_type": "yarn", "factor": 4.0, "beta_fast": 32,
                  "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16,
                  "llama_4_scaling_beta": 0.1},
    num_experts=8, num_experts_per_token=2, moe_intermediate_size=32,
    num_shared_experts=1, moe_capacity_factor=4.0,
    param_dtype="float32", dtype="float32", remat="none"))
# the same attention over a dense MLP: the latent pool without experts
register_model("tiny-mla", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=192,
    num_layers=2, num_heads=4, num_kv_heads=4, max_seq_length=256,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
    param_dtype="float32", dtype="float32", remat="none"))
# the GLM-5 block at toy widths: one leading dense layer, then two layers
# of top-2 of 8 sigmoid-routed experts with a correction bias beside a
# shared expert, every layer's latent attention restricted to the top 8
# of what a 2-head indexer of 16 (8 rotated) scores; contexts of tens of
# tokens make the selection prune
register_model("tiny-dsa", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=96,
    num_layers=3, num_heads=4, num_kv_heads=4, max_seq_length=256,
    rope_theta=1e6, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
    index_topk=8, index_n_heads=2, index_head_dim=16,
    index_rope_interleave=True,
    num_experts=8, num_experts_per_token=2, moe_intermediate_size=32,
    num_shared_experts=1, moe_routed_scale=2.5, moe_scoring="sigmoid",
    first_k_dense=1, moe_capacity_factor=4.0,
    param_dtype="float32", dtype="float32", remat="none"))


def sambay_layers(num_layers: int, window: int,
                  ssm_every: int = 2) -> Tuple[LayerSpec, ...]:
    """The SambaY decoder-hybrid-decoder layout (arXiv:2507.06607, figure
    1; HF ``phi4flash``'s ``mb_per_layer`` / ``yoco_mb`` / ``yoco_cross``):
    with n layers, a self-decoder of n/2 layers alternating a state-space
    mixer (every ``ssm_every``-th layer, from 0) with windowed
    differential attention; layer n/2 a state-space layer whose scan
    output is the cross-decoder's memory; layer n/2 + 1 full differential
    attention, the one cache the cross-decoder reads; then gated memory
    units (on the state-space positions) alternating with
    cross-attention."""
    half = num_layers // 2
    out = []
    for l in range(num_layers):
        ssm_pos = l % ssm_every == 0
        if l <= half:
            out.append(LayerSpec("ssm", "state") if ssm_pos else
                       LayerSpec("diff_attention", "paged_window", window))
        elif l == half + 1:
            out.append(LayerSpec("diff_attention", "paged"))
        else:
            out.append(LayerSpec("gmu", "none") if ssm_pos else
                       LayerSpec("cross_diff_attention", "shared"))
    return tuple(out)


# the SambaY layout at toy widths: (M, W) x 3, M (the memory), F,
# (G, X) x 2, so both repeating stretches run as scans; 4 query / 2
# key-value heads of 16 (2 and 1 differential pairs), state size 4,
# window 8
register_model("tiny-sambay", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=12, num_heads=4, num_kv_heads=2, max_seq_length=256,
    tie_embeddings=True, norm="layer", layers=sambay_layers(12, 8), ssm_state_size=4,
    param_dtype="float32", dtype="float32", remat="none"))


def jamba_layers(num_layers: int, period: int,
                 offset: int) -> Tuple[LayerSpec, ...]:
    """The Jamba layout (arXiv:2403.19887; HF
    ``JambaConfig.layers_block_type``): layer l is plain attention with
    rows of its own iff ``l % period == offset``, else a state-space
    layer."""
    return tuple(
        LayerSpec("attention", "paged") if l % period == offset
        else LayerSpec("ssm", "state") for l in range(num_layers))


# the Jamba layout at toy widths, one attention layer in 6 from layer 2:
# M x 2, A, M x 5, A, M x 3, five runs as the published 28 layers cut (a
# period over MAX_PERIOD is not looked for); 4 query heads over ONE key /
# value head of 16, state size 4, dt / B / C normed
register_model("tiny-jamba", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=12, num_heads=4, num_kv_heads=1, max_seq_length=256,
    tie_embeddings=True, rms_norm_eps=1e-6, layers=jamba_layers(12, 6, 2),
    ssm_state_size=4, ssm_inner_norms=True,
    param_dtype="float32", dtype="float32", remat="none"))


def granite_hybrid_layers(num_layers: int, attention: Tuple[int, ...]
                          ) -> Tuple[LayerSpec, ...]:
    """The Granite 4.0-H layout (HF ``layer_types``): plain attention at
    the layers in ``attention``, Mamba-2 everywhere else."""
    return tuple(LayerSpec("attention", "paged") if l in attention
                 else LayerSpec("ssm2", "state") for l in range(num_layers))


# the Granite 4.0-H period at toy widths: M x 5, A, M x 4 (three runs),
# 4 query / 2 key-value heads of 16 with a muP scale of its own, Mamba-2
# of 8 heads of 16 over a state of 8 in SSD blocks of 4, top-3 of 8
# routed experts of 16 beside a shared expert of 32, and the four muP
# scalars away from 1
register_model("tiny-granite-h", ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=16,
    num_layers=10, num_heads=4, num_kv_heads=2, max_seq_length=256,
    tie_embeddings=True, rms_norm_eps=1e-5,
    layers=granite_hybrid_layers(10, (5,)),
    ssm_state_size=8, ssm_heads=8, ssm_head_dim=16, ssm_chunk=4,
    num_experts=8, num_experts_per_token=3, moe_intermediate_size=16,
    num_shared_experts=2,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=1.0, logits_scaling=16.0,
    param_dtype="float32", dtype="float32", remat="none"))

# HF repo-id aliases so reference configs keep working verbatim
register_model("google/gemma-2b", _REGISTRY["gemma-2b"])
register_model("google/gemma-7b", _REGISTRY["gemma-7b"])
register_model("google/gemma-2-2b", _REGISTRY["gemma2-2b"])
register_model("google/gemma-2-9b", _REGISTRY["gemma2-9b"])
register_model("meta-llama/Meta-Llama-3-8B", _REGISTRY["llama3-8b"])
register_model("meta-llama/Llama-3.1-8B", _REGISTRY["llama3.1-8b"])
register_model("meta-llama/Meta-Llama-3-70B", _REGISTRY["llama3-70b"])
register_model("meta-llama/Llama-3.2-1B", _REGISTRY["llama3.2-1b"])
register_model("meta-llama/Llama-3.2-3B", _REGISTRY["llama3.2-3b"])
register_model("microsoft/Phi-3-mini-4k-instruct", _REGISTRY["phi3-mini"])
register_model("meta-llama/Llama-2-7b-hf", _REGISTRY["llama2-7b"])
register_model("meta-llama/Llama-2-13b-hf", _REGISTRY["llama2-13b"])
register_model("meta-llama/Llama-2-70b-hf", _REGISTRY["llama2-70b"])
register_model("mistralai/Mistral-7B-v0.1", _REGISTRY["mistral-7b"])
register_model("Qwen/Qwen2-7B", _REGISTRY["qwen2-7b"])
# qwen2.5 shares the qwen2 architecture and the 7B's exact dims
# (config.json differs only in sliding-window metadata, which HF
# defaults to off — hf_import handles real config.json files directly)
register_model("qwen2.5-7b", _REGISTRY["qwen2-7b"])
register_model("Qwen/Qwen2.5-7B", _REGISTRY["qwen2-7b"])
register_model("microsoft/phi-2", _REGISTRY["phi-2"])
register_model("mistralai/Mixtral-8x7B-v0.1", _REGISTRY["mixtral-8x7b"])
