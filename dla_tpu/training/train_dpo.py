"""Direct Preference Optimization (phase 3a).

CLI parity: ``python -m dla_tpu.training.train_dpo --config
config/dpo_config.yaml`` (reference src/training/train_dpo.py).
Behavior parity: policy + frozen reference model; per-sequence
**length-normalized** mean-token logp (reference compute_logprobs,
train_dpo.py:31-39); loss -logsigmoid(beta * ((pi_c - pi_r) - (ref_c -
ref_r))) (train_dpo.py:42-44); logs preference_rate (margin > 0,
train_dpo.py:130-132).

TPU-native: all four transformer forwards run inside one jitted SPMD step;
per-token logp is gathered as logit[label] - logsumexp (no [B, T, V] fp32
log-softmax materialization, the reference's memory hot spot at
train_dpo.py:36); ``model.label_smoothing`` (a dead config key in the
reference, SURVEY.md sec 2.5) is wired for real as conservative DPO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dla_tpu.data.iterator import ShardedBatchIterator
from dla_tpu.data.loaders import build_preference_dataset
from dla_tpu.data.packing import pack_preference_splits
from dla_tpu.ops.fused_ce import (
    model_fused_segment_logprob,
    model_fused_sequence_logprob,
    weighted_moe_aux,
)
from dla_tpu.ops.losses import dpo_loss, masked_mean
from dla_tpu.parallel.dist import initialize_distributed
from dla_tpu.parallel.mesh import mesh_from_config
from dla_tpu.training.config import config_from_args, make_arg_parser
from dla_tpu.training.model_io import (
    init_lora_adapters,
    load_causal_lm,
    model_aux,
    save_merged_lora_final,
)
from dla_tpu.training.trainer import Trainer
from dla_tpu.training.utils import seed_everything
from dla_tpu.utils.compile_cache import enable_compile_cache
from dla_tpu.utils.logging import log_rank_zero


def make_dpo_loss(policy_model, ref_model, beta: float,
                  label_smoothing: float = 0.0, lora: bool = False,
                  train: bool = True, n_segments: int = 0):
    """``n_segments > 0`` selects the PACKED preference path
    (data.packing: true): per-(row, segment) logps [B, n_segments] with
    the batch's pair_mask weighting the pair mean — segment j of a
    chosen row is the partner of segment j of the rejected row by the
    joint placement in data/packing.py PackedPreferenceDataset."""
    def seq_logp(model, params, sub, adapters=None, rng=None,
                 with_aux=False):
        # fused hidden @ unembed + gather: no [B, T, V] materialization
        # in any of the four forwards (cf. reference train_dpo.py:36)
        if n_segments:
            return model_fused_segment_logprob(
                model, params, sub, n_segments,
                lora=adapters, dropout_rng=rng, with_aux=with_aux)
        return model_fused_sequence_logprob(
            model, params, sub["input_ids"], sub["attention_mask"],
            lora=adapters, dropout_rng=rng, with_aux=with_aux)

    def loss_fn(params, frozen, batch, rng):
        if lora:
            # trainable tree = adapters over a frozen base; the reference
            # model is the base itself (= the initial policy) unless a
            # separate ref was loaded — either way the policy base and
            # ref share storage instead of duplicating a full param tree
            base = frozen["base"]
            refp = frozen.get("ref", base)
            drop = rng if train else None
            pi_c, aux_c = seq_logp(policy_model, base, batch["chosen"],
                                   adapters=params, rng=drop,
                                   with_aux=True)
            pi_r, aux_r = seq_logp(policy_model, base, batch["rejected"],
                                   adapters=params, rng=drop,
                                   with_aux=True)
        else:
            del rng
            refp = frozen
            pi_c, aux_c = seq_logp(policy_model, params, batch["chosen"],
                                   with_aux=True)
            pi_r, aux_r = seq_logp(policy_model, params, batch["rejected"],
                                   with_aux=True)
        ref_c = jax.lax.stop_gradient(
            seq_logp(ref_model, refp, batch["chosen"]))
        ref_r = jax.lax.stop_gradient(
            seq_logp(ref_model, refp, batch["rejected"]))
        pv = batch.get("pair_mask") if n_segments else None
        loss, margin = dpo_loss(pi_c, pi_r, ref_c, ref_r,
                                beta, label_smoothing, valid=pv)
        # MoE policies: router balance/z regularization on the two
        # with-grad forwards (0.0 for dense models)
        loss = loss + weighted_moe_aux(policy_model, aux_c, aux_r)
        return loss, {
            "preference_rate": masked_mean(
                (margin > 0).astype(jnp.float32), pv),
            "margin": masked_mean(margin, pv),
            "policy_chosen_logp": masked_mean(pi_c, pv),
        }
    return loss_fn


def main(argv=None) -> None:
    args = make_arg_parser("dla_tpu DPO trainer").parse_args(argv)
    config = config_from_args(args)
    enable_compile_cache()
    initialize_distributed(config.get("hardware"))
    mesh = mesh_from_config(config.get("hardware"))
    rng = seed_everything(int(config.get("seed", 0)))

    model_cfg = config.get("model", {})
    beta = float(model_cfg.get("beta", 0.1))
    label_smoothing = float(model_cfg.get("label_smoothing", 0.0))
    packing = bool(config.get("data", {}).get("packing"))

    with jax.sharding.set_mesh(mesh):
        policy = load_causal_lm(
            model_cfg.get("policy_model_name_or_path",
                          model_cfg.get("model_name_or_path", "tiny")),
            model_cfg, rng)
        ref_name = model_cfg.get("reference_model_name_or_path")
        if ref_name:
            ref = load_causal_lm(ref_name, model_cfg, rng)
        else:
            ref = policy  # same weights as starting policy (frozen copy)

        data_cfg = {**config.get("data", {}),
                    "max_seq_length": policy.config.max_seq_length}
        train_ds = build_preference_dataset(data_cfg, policy.tokenizer, "train")
        has_eval = (data_cfg.get("eval_path")
                    if data_cfg.get("source", "local") == "local"
                    else data_cfg.get("eval_split"))
        eval_ds = (build_preference_dataset(data_cfg, policy.tokenizer, "eval")
                   if has_eval else None)
        n_segments = 0
        if packing:
            train_ds, eval_ds, n_segments = pack_preference_splits(
                train_ds, eval_ds, policy.config.max_seq_length)
            log_rank_zero(
                f"[dla_tpu] packing: {len(train_ds)} pair-rows, "
                f"{train_ds.packing_efficiency():.1%} token efficiency, "
                f"<= {n_segments} pairs/row")

        use_lora = policy.config.lora_r > 0
        if use_lora:
            # preference tuning without full fp32 Adam state (the blocker
            # the round-2 verdict named for 70B DPO): adapters train, the
            # base tree is frozen and doubles as the reference model
            adapters, lora_specs = init_lora_adapters(
                policy, jax.random.fold_in(rng, 17))
            frozen = {"base": policy.params}
            frozen_specs = {"base": policy.specs}
            if ref_name:
                frozen["ref"] = ref.params
                frozen_specs["ref"] = ref.specs
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_dpo_loss(policy.model, ref.model, beta,
                                      label_smoothing, lora=True,
                                      n_segments=n_segments),
                eval_fn=make_dpo_loss(policy.model, ref.model, beta,
                                      label_smoothing, lora=True,
                                      train=False, n_segments=n_segments),
                params=adapters, param_specs=lora_specs,
                frozen=frozen, frozen_specs=frozen_specs)
        else:
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_dpo_loss(policy.model, ref.model, beta,
                                      label_smoothing,
                                      n_segments=n_segments),
                params=policy.params, param_specs=policy.specs,
                frozen=ref.params, frozen_specs=ref.specs)

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
            extra_aux=model_aux(policy, model_cfg.get("tokenizer")))

        if use_lora:
            save_merged_lora_final(
                trainer, policy, trainer.frozen["base"],
                model_cfg.get("tokenizer"))


if __name__ == "__main__":
    main()
