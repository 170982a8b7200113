"""Scale-out memory dress rehearsal on a virtual CPU mesh — no chips.

AOT-compiles the REAL sharded SFT train step (Trainer._train_step:
fused-CE loss, in-step accumulation scan, AdamW/adafactor update) for a
scale config entirely from ShapeDtypeStructs — no 70B arrays ever exist,
on host or device — then reads ``compiled.memory_analysis()`` for the
PER-DEVICE argument/temp/peak bytes and checks them against the v5e HBM
budget. This is the measurement the r4 verdict asked for under item 8:
``docs/SCALING.md``'s 70B residency claims stop being paper claims and
become a compiled-program fact (modulo TPU tile padding, which XLA:CPU
does not model — dominant full matrices pad negligibly, so treat the
numbers as a tight lower bound).

    python tools/scale_rehearsal.py [config.yaml] [n_devices] [mesh_override]

      config.yaml    default config/sft_llama2_70b_v5e256_pp.yaml
      n_devices      default 256 (the config's native topology)
      mesh_override  e.g. "stage=4,fsdp=4,model=2" to rehearse the same
                     config scaled onto fewer virtual devices

Prints one JSON line per run:
  {"per_device": {"arguments_gb": ..., "temp_gb": ..., "peak_gb": ...,
                  "total_gb": ...}, "fits_v5e": true, ...}
"""
from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

V5E_HBM_GB = 15.75  # usable per-chip HBM, v5e (BASELINE.md)


def _parse_mesh(s: str):
    out = {}
    for part in s.split(","):
        k, v = part.split("=")
        out[k.strip()] = int(v)
    return out


def rehearse(config_path: str, n_devices: int,
             mesh_override=None, hbm_gb: float = V5E_HBM_GB) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.parallel.sharding import prune_spec_for_mesh, sharding_tree
    from dla_tpu.training.config import load_config
    from dla_tpu.training.model_io import _arch_overrides
    from dla_tpu.training.optim import build_optimizer
    from dla_tpu.training.trainer import Trainer, _match_opt_shardings

    cfg = load_config(config_path)  # injects model.pipeline_stages
    mesh_dict = mesh_override or cfg["hardware"]["mesh"]
    mesh_cfg = MeshConfig.from_dict(
        {k: v for k, v in mesh_dict.items() if k != "auto_initialize"})
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n_devices])
    sizes = dict(mesh.shape)
    print(f"[rehearsal] mesh {sizes} on {n_devices} virtual devices",
          file=sys.stderr)

    model_block = dict(cfg["model"])
    if mesh_override and "stage" in mesh_override:
        model_block["pipeline_stages"] = int(mesh_override["stage"])
    overrides = _arch_overrides(model_block)
    mcfg = get_model_config(model_block["model_name_or_path"], **overrides)
    model = Transformer(mcfg)

    opt_cfg = dict(cfg["optimization"])
    accum = int(cfg["hardware"].get("gradient_accumulation_steps", 1))
    opt_cfg.setdefault("gradient_accumulation_steps", accum)
    tx, _ = build_optimizer(opt_cfg)

    packing = bool(cfg.get("data", {}).get("packing"))

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch)
        return loss, {}

    # borrow the Trainer's REAL step so the rehearsal compiles exactly
    # what training runs (accumulation scan + optimizer.update + clip)
    class _Step:
        _train_step = Trainer._train_step
    stub = _Step()
    stub.loss_fn, stub.optimizer, stub.accum = loss_fn, tx, accum
    import jax.numpy as _jnp
    stub.grad_accum_dtype = _jnp.dtype(
        opt_cfg.get("grad_accum_dtype", "float32"))
    # what the step reads off the trainer besides the above: the
    # in-graph collector and non-finite guard at their config defaults
    from dla_tpu.resilience import GuardState, ResilienceConfig
    from dla_tpu.telemetry import CollectorConfig
    stub.train_step_compiles = 0
    stub.collector_cfg = CollectorConfig.from_config(
        (cfg.get("logging") or {}).get("telemetry") or {})
    stub.guard = GuardState(
        ResilienceConfig.from_config(cfg.get("resilience")).guard)

    with jax.sharding.set_mesh(mesh):
        specs = model.partition_specs()
        param_shapes = jax.eval_shape(model.init, jax.random.key(0))
        param_sh = sharding_tree(specs, mesh)
        params_abs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            param_shapes, param_sh)
        opt_sh = _match_opt_shardings(tx, params_abs, param_sh, mesh)
        opt_shapes = jax.eval_shape(tx.init, params_abs)
        opt_abs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            opt_shapes, opt_sh)

        dp = sizes.get("data", 1) * sizes.get("fsdp", 1)
        rows = int(opt_cfg["micro_batch_size"]) * dp
        seq = mcfg.max_seq_length
        b_sh = NamedSharding(
            mesh, prune_spec_for_mesh(P(None, ("data", "fsdp")), mesh))
        batch_keys = ["input_ids", "attention_mask", "labels"]
        if packing:
            batch_keys.append("segment_ids")
        batch_abs = {
            k: jax.ShapeDtypeStruct((accum, rows, seq), jnp.int32,
                                    sharding=b_sh)
            for k in batch_keys}

        # no donate_argnums: XLA:CPU check-fails inserting the aliasing
        # copies for this program ("Invalid binary instruction opcode
        # copy", r5); the donation effect is restored arithmetically
        # below — real training donates, so new params/opt REUSE the
        # argument buffers and the outputs cost nothing extra
        fn = jax.jit(
            _Step._train_step.__get__(stub),
            in_shardings=(param_sh, opt_sh, None, None, None, None, None),
            out_shardings=(param_sh, opt_sh,
                           NamedSharding(mesh, P()), None))
        print("[rehearsal] lowering...", file=sys.stderr)
        lowered = fn.lower(params_abs, opt_abs, None, batch_abs,
                           jax.random.key(0), jnp.float32(0.0),
                           jnp.float32(0.0))
        print("[rehearsal] compiling (SPMD partitioning + XLA:CPU)...",
              file=sys.stderr)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()

    gb = 1024 ** 3
    # donated params/opt alias their outputs, so chip residency per step
    # = arguments (params + opt + batch shard) + XLA temp (activations,
    # collective buffers) + non-aliased outputs
    args_gb = ma.argument_size_in_bytes / gb
    temp_gb = ma.temp_size_in_bytes / gb
    # the compiled-without-donation outputs double-count params + opt;
    # under donation (what training runs) they alias the arguments, so
    # chip residency = arguments + XLA temp
    total_gb = args_gb + temp_gb
    n_params = sum(
        int(np_prod(l.shape)) for l in jax.tree.leaves(param_shapes))
    result = {
        "config": os.path.basename(config_path),
        "n_devices": n_devices,
        "mesh": sizes,
        "params_b": round(n_params / 1e9, 2),
        "rows_per_step": rows,
        "seq": seq,
        "per_device": {
            "arguments_gb": round(args_gb, 3),
            "temp_gb": round(temp_gb, 3),
            "peak_reported_gb": round(ma.peak_memory_in_bytes / gb, 3),
            "total_gb": round(total_gb, 3),
        },
        "hbm_budget_gb": hbm_gb,
        "fits_v5e": bool(total_gb <= hbm_gb),
    }
    print(json.dumps(result), flush=True)
    return result


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def main() -> None:
    config = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        _REPO, "config", "sft_llama2_70b_v5e256_pp.yaml")
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    override = _parse_mesh(sys.argv[3]) if len(sys.argv) > 3 else None

    # XLA:CPU's AllReducePromotion pass check-fails on the pipeline
    # shard_map program ("Invalid binary instruction opcode copy",
    # bisected r5 — CPU-only pass; TPU never runs it). The rehearsal
    # only COMPILES, so the pass's numerics purpose is moot: disable it
    # before backend init so PP configs analyze in their real dtype.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_disable_hlo_passes=all-reduce-promotion")

    # compile-only analysis on the virtual CPU platform: forced before
    # the backend initializes, so this process never claims a chip and
    # starts no child
    from _cpuhost import force_cpu_platform
    if not force_cpu_platform(n):
        sys.exit(f"could not bring up {n} virtual CPU devices")
    rehearse(config, n, override)


if __name__ == "__main__":
    main()
