"""dla_tpu — a TPU-native LLM alignment framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of
``nikhil-lalgudi/distributed-llm-alignment`` (the reference): the six-phase
alignment pipeline SFT -> Reward Model -> DPO / PPO-RLHF -> On-Policy
Distillation -> Evaluation, rebuilt for TPU:

- SPMD over a ``jax.sharding.Mesh`` with axes (data, fsdp, model, sequence)
  replaces the reference's Accelerate + DeepSpeed ZeRO-3 + NCCL stack
  (reference: src/training/utils.py:55-75, config/deepspeed_zero3.json).
- A pure-JAX decoder-only transformer with scan-over-layers and
  PartitionSpec-annotated parameters replaces HF ``AutoModelForCausalLM``
  (reference: src/models/base_model.py).
- A jitted prefill+decode generation engine with a preallocated KV cache
  replaces HF ``model.generate`` (reference: src/training/train_rlhf.py:123).

Package layout:
  parallel/    mesh construction, sharding helpers, multi-host init
  models/      transformer, reward model, configs/registry, HF weight import
  ops/         attention, norms, rotary, losses, sampling, pallas kernels
  data/        jsonl ingestion, templating/masking, padding, packing
  training/    config system, trainer core, per-phase entrypoints
  generation/  autoregressive decode engine
  checkpoint/  sharded save/restore with latest-pointer + retention
  eval/        alignment heuristics + latency/throughput harness
  utils/       logging, metrics, profiling
"""

__version__ = "0.1.0"

