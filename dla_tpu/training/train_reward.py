"""Reward-model training (phase 2).

CLI parity: ``python -m dla_tpu.training.train_reward --config
config/reward_config.yaml`` (reference src/training/train_reward.py).
Behavior parity: Bradley-Terry pairwise loss over two backbone forwards
per batch (chosen, rejected; reference train_reward.py:140-148), eval
reports loss and preference accuracy (chosen > rejected,
train_reward.py:31-54).

TPU-native: both forwards live in one jitted SPMD step; the backbone and
scalar head are sharded over the (data, fsdp, model) mesh like every other
model here.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from dla_tpu.data.iterator import ShardedBatchIterator
from dla_tpu.data.loaders import build_preference_dataset
from dla_tpu.data.packing import pack_preference_splits
from dla_tpu.ops.fused_ce import weighted_moe_aux
from dla_tpu.ops.losses import masked_mean, pairwise_reward_loss
from dla_tpu.parallel.dist import initialize_distributed
from dla_tpu.parallel.mesh import mesh_from_config
from dla_tpu.training.config import config_from_args, make_arg_parser
from dla_tpu.training.model_io import (
    build_reward_model,
    init_lora_adapters,
    model_aux,
    save_merged_lora_final,
)
from dla_tpu.training.trainer import Trainer
from dla_tpu.utils.compile_cache import enable_compile_cache
from dla_tpu.utils.logging import log_rank_zero


def _side_kwargs(batch, side: str, n_segments: int):
    """Model.apply kwargs for one side of a (possibly packed) batch."""
    sub = batch[side]
    kw = {}
    if n_segments:
        kw = {"segment_ids": sub["segment_ids"], "n_segments": n_segments}
    return sub["input_ids"], sub["attention_mask"], kw


def make_reward_loss(model, lora: bool = False, n_segments: int = 0):
    """``n_segments > 0``: packed preference rows — rewards pool per
    segment ([B, n_segments]) and the pair mean is pair_mask-weighted
    (data/packing.py PackedPreferenceDataset)."""
    def loss_fn(params, frozen, batch, rng):
        if lora:
            # trainable = backbone adapters + the (tiny, full-rank)
            # scalar head; the frozen backbone rides in `frozen`
            full = {**frozen, "reward_head": params["reward_head"]}
            adapters = params["lora"]
        else:
            del frozen
            full, adapters = params, None
        drng = jax.random.split(rng, 2)
        ids_c, m_c, kw = _side_kwargs(batch, "chosen", n_segments)
        ids_r, m_r, kw_r = _side_kwargs(batch, "rejected", n_segments)
        chosen, aux_c = model.apply(full, ids_c, m_c, dropout_rng=drng[0],
                                    lora=adapters, with_aux=True, **kw)
        rejected, aux_r = model.apply(full, ids_r, m_r, dropout_rng=drng[1],
                                      lora=adapters, with_aux=True, **kw_r)
        pv = batch.get("pair_mask") if n_segments else None
        loss = pairwise_reward_loss(chosen, rejected, valid=pv)
        # MoE backbones: router regularization on both with-grad forwards
        loss = loss + weighted_moe_aux(model, aux_c, aux_r)
        return loss, {
            "acc": masked_mean((chosen > rejected).astype(jnp.float32), pv),
            "reward_margin": masked_mean(chosen - rejected, pv)}
    return loss_fn


def make_reward_eval(model, lora: bool = False, n_segments: int = 0):
    def eval_fn(params, frozen, batch, rng):
        del rng
        if lora:
            full = {**frozen, "reward_head": params["reward_head"]}
            adapters = params["lora"]
        else:
            del frozen
            full, adapters = params, None
        ids_c, m_c, kw = _side_kwargs(batch, "chosen", n_segments)
        ids_r, m_r, kw_r = _side_kwargs(batch, "rejected", n_segments)
        chosen = model.apply(full, ids_c, m_c, lora=adapters, **kw)
        rejected = model.apply(full, ids_r, m_r, lora=adapters, **kw_r)
        pv = batch.get("pair_mask") if n_segments else None
        loss = pairwise_reward_loss(chosen, rejected, valid=pv)
        return loss, {"acc": masked_mean(
            (chosen > rejected).astype(jnp.float32), pv)}
    return eval_fn


def main(argv=None) -> None:
    args = make_arg_parser("dla_tpu reward-model trainer").parse_args(argv)
    config = config_from_args(args)
    enable_compile_cache()
    initialize_distributed(config.get("hardware"))
    mesh = mesh_from_config(config.get("hardware"))
    from dla_tpu.training.utils import seed_everything
    rng = seed_everything(int(config.get("seed", 0)))

    packing = bool(config.get("data", {}).get("packing"))
    with jax.sharding.set_mesh(mesh):
        bundle = build_reward_model(config.get("model", {}), rng)

        data_cfg = {**config.get("data", {}),
                    "max_seq_length": bundle.config.max_seq_length}
        train_ds = build_preference_dataset(data_cfg, bundle.tokenizer, "train")
        has_eval = (data_cfg.get("eval_path")
                    if data_cfg.get("source", "local") == "local"
                    else data_cfg.get("eval_split"))
        eval_ds = (build_preference_dataset(data_cfg, bundle.tokenizer, "eval")
                   if has_eval else None)
        n_segments = 0
        if packing:
            train_ds, eval_ds, n_segments = pack_preference_splits(
                train_ds, eval_ds, bundle.config.max_seq_length)
            log_rank_zero(
                f"[dla_tpu] packing: {len(train_ds)} pair-rows, "
                f"{train_ds.packing_efficiency():.1%} token efficiency, "
                f"<= {n_segments} pairs/row")

        use_lora = bundle.config.lora_r > 0
        if use_lora:
            # adapters + scalar head train; backbone stays frozen (no
            # full Adam state at 7B+ backbone scale)
            head = bundle.params.pop("reward_head")
            head_spec = bundle.specs.pop("reward_head")
            adapters, lora_specs = init_lora_adapters(
                bundle, jax.random.fold_in(rng, 17))
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_reward_loss(bundle.model, lora=True,
                                         n_segments=n_segments),
                eval_fn=make_reward_eval(bundle.model, lora=True,
                                         n_segments=n_segments),
                params={"lora": adapters, "reward_head": head},
                param_specs={"lora": lora_specs, "reward_head": head_spec},
                frozen=bundle.params, frozen_specs=bundle.specs)
        else:
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_reward_loss(bundle.model,
                                         n_segments=n_segments),
                eval_fn=make_reward_eval(bundle.model,
                                         n_segments=n_segments),
                params=bundle.params, param_specs=bundle.specs)

        train_it = ShardedBatchIterator(
            train_ds, trainer.planned_global_batch(args.resume),
            seed=int(config.get("seed", 0)),
            process_index=jax.process_index(),
            process_count=jax.process_count())

        eval_iter_fn = None
        if eval_ds is not None:
            micro_global = trainer.micro * trainer.dp

            def eval_iter_fn():
                return iter(ShardedBatchIterator(
                    eval_ds, micro_global, shuffle=False,
                    process_index=jax.process_index(),
                    process_count=jax.process_count()))

        trainer.fit(
            train_it, rng=rng, eval_iter_fn=eval_iter_fn,
            data_state=train_it.state_dict, resume=args.resume,
            extra_aux=model_aux(bundle,
                                config.get("model", {}).get("tokenizer")))

        if use_lora:
            save_merged_lora_final(
                trainer, bundle, trainer.frozen,
                config.get("model", {}).get("tokenizer"))


if __name__ == "__main__":
    main()
