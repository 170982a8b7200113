"""On-policy distillation (phase 4): student trained on teacher rollouts.

CLI parity: ``python -m dla_tpu.training.train_distill --config
config/distill_config.yaml`` (reference src/training/train_distill.py).
Behavior parity: two modes (reference train_distill.py:127-147):

- default: CE on teacher responses as labels (labels = input_ids, no
  prompt mask — TeacherRolloutDataset semantics);
- ``distill.use_kl && distill.on_policy``: forward KL(mean-of-teachers ||
  student), token-masked mean, with an optional teacher **ensemble**
  (teacher_model_names_or_paths, probs averaged — train_distill.py:135-139).

Per-sample ``reward`` is logged, not used to weight the loss (parity with
train_distill.py:125,160). ``optimization.temperature`` — a dead key in
the reference (SURVEY.md sec 2.5) — is wired into the KL for real; 1.0
reproduces reference behavior.

TPU-native: teacher forwards are frozen params on the same mesh inside the
one jitted step; the KL streams over sequence chunks (ops.fused_ce), so
no fp32 [B, T, V] tensor — student log-probs or any teacher's softmax —
is ever materialized at full sequence length.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from dla_tpu.data.iterator import ShardedBatchIterator
from dla_tpu.data.loaders import build_teacher_dataset
from dla_tpu.data.packing import PackedTeacherDataset
from dla_tpu.ops.fused_ce import (
    fused_cross_entropy_loss,
    fused_kl_distill_loss,
    weighted_moe_aux,
)
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


def make_distill_loss(student_model, teacher_models: List[Any],
                      use_kl: bool, temperature: float, lora: bool = False,
                      train: bool = True):
    # Both modes run through the chunked unembed fusions (ops.fused_ce):
    # neither the student's logits nor any teacher's probabilities are
    # materialized at [B, T, V].
    def loss_fn(params, frozen, batch, rng):
        seg = batch.get("segment_ids")   # packed rows (data.packing)
        if lora:
            base = frozen["student_base"]
            h, moe_aux = student_model.hidden_states_with_aux(
                base, batch["input_ids"],
                attention_mask=batch["attention_mask"], segment_ids=seg,
                lora=params, dropout_rng=rng if train else None)
        else:
            del rng
            base = params
            h, moe_aux = student_model.hidden_states_with_aux(
                params, batch["input_ids"],
                attention_mask=batch["attention_mask"], segment_ids=seg)
        sw, sbias = student_model.unembed_params(base)
        if seg is None:
            reward_mean = jnp.mean(batch["reward"])
        else:
            # packed rows carry token-weighted row means; re-weighting
            # by row fill makes this the corpus token-weighted mean —
            # exact under any packing (mean-of-row-means is not: FFD
            # leaves unevenly filled tail rows)
            w = jnp.sum(batch["attention_mask"], axis=1).astype(jnp.float32)
            reward_mean = jnp.sum(batch["reward"] * w) / (jnp.sum(w) + 1e-8)
        metrics = {"reward_mean": reward_mean}
        if use_kl and teacher_models:
            t_hiddens, t_ws, t_biases = [], [], []
            for i, tm in enumerate(teacher_models):
                tp = frozen[f"teacher_{i}"]
                t_hiddens.append(jax.lax.stop_gradient(tm.hidden_states(
                    tp, batch["input_ids"],
                    attention_mask=batch["attention_mask"],
                    segment_ids=seg)))
                tw, tb = tm.unembed_params(tp)
                t_ws.append(jax.lax.stop_gradient(tw))
                t_biases.append(None if tb is None
                                else jax.lax.stop_gradient(tb))
            kl_mask = batch["attention_mask"]
            if seg is not None:
                # a packed segment's FIRST token is the next-token
                # target of the previous segment's last position — the
                # same cross-segment pair the packer's label IGNORE
                # kills on the CE path (data/packing.py)
                start = jnp.concatenate(
                    [jnp.ones_like(seg[:, :1]),
                     (seg[:, 1:] != seg[:, :-1]).astype(seg.dtype)],
                    axis=1)
                kl_mask = kl_mask * (1 - start)
            loss = fused_kl_distill_loss(
                h, sw, t_hiddens, t_ws, kl_mask,
                temperature, student_bias=sbias, teacher_biases=t_biases,
                student_softcap=student_model.cfg.final_logit_softcap,
                teacher_softcaps=[tm.cfg.final_logit_softcap
                                  for tm in teacher_models])
            metrics["kl"] = loss
        else:
            loss, _ = fused_cross_entropy_loss(
                h, sw, batch["labels"], bias=sbias,
                softcap=student_model.cfg.final_logit_softcap)
            metrics["ce"] = loss
        # MoE students: router regularization on the with-grad forward
        loss = loss + weighted_moe_aux(student_model, moe_aux)
        return loss, metrics
    return loss_fn


def main(argv=None) -> None:
    args = make_arg_parser("dla_tpu distillation trainer").parse_args(argv)
    config = config_from_args(args)
    enable_compile_cache()
    initialize_distributed(config.get("hardware"))
    mesh = mesh_from_config(config.get("hardware"))
    rng = seed_everything(int(config.get("seed", 0)))

    model_cfg = config.get("model", {})
    distill_cfg: Dict[str, Any] = config.get("distill", {})
    use_kl = bool(distill_cfg.get("use_kl")) and bool(
        distill_cfg.get("on_policy"))
    temperature = float(config.get("optimization", {})
                        .get("temperature", 1.0))

    with jax.sharding.set_mesh(mesh):
        student = load_causal_lm(
            model_cfg.get("student_model_name_or_path", "tiny"),
            model_cfg, rng)

        teacher_models, frozen, frozen_specs = [], None, None
        if use_kl:
            names = (distill_cfg.get("teacher_model_names_or_paths")
                     or [distill_cfg.get("teacher_model_name_or_path",
                                         model_cfg.get("teacher_path"))])
            names = [n for n in names if n]
            frozen, frozen_specs = {}, {}
            for i, name in enumerate(names):
                tb = load_causal_lm(name, model_cfg, jax.random.fold_in(rng, i))
                if tb.config.vocab_size != student.config.vocab_size:
                    raise ValueError(
                        f"teacher '{name}' vocab {tb.config.vocab_size} != "
                        f"student vocab {student.config.vocab_size}; KL "
                        "distillation needs a shared vocabulary")
                teacher_models.append(tb.model)
                frozen[f"teacher_{i}"] = tb.params
                frozen_specs[f"teacher_{i}"] = tb.specs
            log_rank_zero(f"[dla_tpu] KL distillation from "
                          f"{len(teacher_models)} teacher(s), T={temperature}")

        use_lora = student.config.lora_r > 0
        if use_lora:
            adapters, lora_specs = init_lora_adapters(
                student, jax.random.fold_in(rng, 17))
            frozen = {**(frozen or {}), "student_base": student.params}
            frozen_specs = {**(frozen_specs or {}),
                            "student_base": student.specs}
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_distill_loss(student.model, teacher_models,
                                          use_kl, temperature, lora=True),
                eval_fn=make_distill_loss(student.model, teacher_models,
                                          use_kl, temperature, lora=True,
                                          train=False),
                params=adapters, param_specs=lora_specs,
                frozen=frozen, frozen_specs=frozen_specs)
        else:
            trainer = Trainer(
                config=config, mesh=mesh,
                loss_fn=make_distill_loss(student.model, teacher_models,
                                          use_kl, temperature),
                params=student.params, param_specs=student.specs,
                frozen=frozen, frozen_specs=frozen_specs)

        data_cfg = {**config.get("data", {}),
                    "max_seq_length": student.config.max_seq_length}
        train_ds = build_teacher_dataset(data_cfg, student.tokenizer)
        if data_cfg.get("packing"):
            train_ds = PackedTeacherDataset(
                train_ds, student.config.max_seq_length)
            log_rank_zero(
                f"[dla_tpu] packing: {len(train_ds)} rows, "
                f"{train_ds.packing_efficiency():.1%} token efficiency")
        train_it = ShardedBatchIterator(
            train_ds, trainer.planned_global_batch(args.resume),
            seed=int(config.get("seed", 0)),
            process_index=jax.process_index(),
            process_count=jax.process_count())

        trainer.fit(
            train_it, rng=rng,
            data_state=train_it.state_dict, resume=args.resume,
            extra_aux=model_aux(student, model_cfg.get("tokenizer")))

        if use_lora:
            save_merged_lora_final(
                trainer, student, trainer.frozen["student_base"],
                model_cfg.get("tokenizer"))


if __name__ == "__main__":
    main()
