"""Driver ``train_packed``: full fine-tuning on packed documents through
the program's normal path: ``make_sft_loss`` and ``Trainer`` as
``train_sft.build_trainer`` wires them, ``PackedInstructionDataset``, the
sharded and prefetching iterators, and the trainer's own jitted step
(``step_on_batch``, the loop an external driver is meant to use). The one
departure from ``build_trainer``: weights are made on the device in one
jitted, sharded call instead of leaf by leaf.
"""
from __future__ import annotations

import math
import time
from typing import Dict

import numpy as np

from perfbench.lib import stats
from perfbench.lib.traffic import SyntheticDocuments

ANNOTATIONS = ("train",)
PROGRAMS = {"train_step": r"jit__train_step"}

#: Tolerance of ``correct``: the program's loss on packed rows (bf16
#: activations, flash kernel, fused CE) against the float32 reference at
#: the same weights, in nats, where the loss is near ln(32000) = 10.4.
#: The mean over some thousand tokens averages bf16's rounding down: the
#: chip runs of this PR read at most 0.003. 0.02 passes another order of
#: summation and fails a wrong mask or position (tenths of a nat) or an
#: 8-bit path.
TOL_LOSS = 0.02


def run(bench) -> Dict:
    import jax
    from dla_tpu.data.iterator import ShardedBatchIterator
    from dla_tpu.data.packing import PackedInstructionDataset
    from dla_tpu.data.prefetch import PrefetchIterator
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.parallel.mesh import mesh_from_config
    from dla_tpu.parallel.sharding import sharding_tree
    from dla_tpu.training.train_sft import make_sft_loss
    from dla_tpu.training.trainer import Trainer

    from perfbench.lib import sut

    cfg, tr, mix = bench.config, bench.config["training"], bench.traffic
    seq = int(tr["max_seq_length"])
    mesh = mesh_from_config({"mesh": dict(tr["mesh"])})
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = int(tr["micro_batch_size"]) * dp
    config = {
        "experiment_name": f"perfbench_{bench.cell['name']}",
        "seed": 0,
        "optimization": {
            "total_batch_size": global_batch,
            "micro_batch_size": int(tr["micro_batch_size"]),
            "learning_rate": float(mix["learning_rate"][str(bench.cell["chips"])]),
            "warmup_steps": 0, "lr_scheduler": "constant",
            "max_train_steps": 10 ** 9, "max_grad_norm": 1.0,
            "adam_moment_dtype": tr["adam_moment_dtype"]},
        "logging": {"output_dir": str(bench.scratch / "ckpt"),
                    "log_dir": None, "save_every_steps": 0},
        "hardware": {"gradient_accumulation_steps": 1,
                     "mesh": dict(tr["mesh"])},
    }
    with jax.sharding.set_mesh(mesh):
        model = Transformer(sut.model_config(
            cfg, dtype=tr["dtype"], param_dtype=tr["param_dtype"],
            attention=tr["attention"], remat=tr["remat"],
            max_seq_length=seq))
        specs = model.partition_specs()
        params = sut.init_params(model, bench.seed,
                                 sharding_tree(specs, mesh))
        trainer = Trainer(config=config, mesh=mesh,
                          loss_fn=make_sft_loss(model),
                          params=params, param_specs=specs)
        del params      # the trainer holds the placed tree
        bench.say(f"trainer built: {trainer.n_params / 1e9:.2f}B "
                  f"parameters, mesh {dict(mesh.shape)}")

        docs = SyntheticDocuments(mix, bench.seed, int(cfg["vocab_size"]))
        rows = PackedInstructionDataset(docs, seq)
        source = ShardedBatchIterator(rows, global_batch, seed=bench.seed)
        batches = PrefetchIterator(source, int(mix["prefetch"]))
        rng = sut.seed_key(bench.seed)
        clock = trainer.clock
        log = []        # per step: (end time, document tokens, data_wait s)

        def step() -> float:
            waited = clock.seg_total["data_wait"]
            with clock.segment("data_wait"):
                batch = next(batches)
            loss, _ = trainer.step_on_batch(
                batch, jax.random.fold_in(rng, trainer.step))
            log.append((time.perf_counter(),
                        int((batch["segment_ids"] > 0).sum()),
                        clock.seg_total["data_wait"] - waited))
            return loss

        try:
            losses = [step() for _ in range(int(mix["warm_steps"]))]
            warm = len(log)
            t0, setup_s = bench.open_window()
            bench.say(f"window open after {warm} warm steps (set-up "
                      f"{setup_s:.1f}s), loss {losses[-1]:.3f}")
            while True:
                bench.tracer.tick(time.perf_counter() - t0)
                losses.append(step())
                if log[-1][0] - t0 >= bench.seconds:
                    break
            bench.close_window()
        finally:
            batches.close()
        t1 = log[-1][0]
        window_s = t1 - t0
        memory_peak = sut.memory_peak_bytes()
        steps = log[warm:]
        bench.say(f"window closed: {len(steps)} steps, last loss "
                  f"{losses[-1]:.3f}")

        ends = [t0] + [s[0] for s in steps]
        tokens = sum(s[1] for s in steps)
        chips = int(bench.cell["chips"])
        end_to_end = {
            "train_tok_s_chip": tokens / window_s / chips,
            "setup_s": setup_s,
        }
        counters = {
            "steps": len(steps), "tokens": tokens,
            "row_slots": len(steps) * global_batch * seq,
            "dataset_fill": rows.packing_efficiency(),
            "global_batch": global_batch, "warm_steps": warm,
            "train_step_compiles": trainer.train_step_compiles,
        }
        samples = {
            "step_wall_ms": [(b - a) * 1e3 for a, b in zip(ends, ends[1:])],
            "data_wait_ms": [s[2] * 1e3 for s in steps],
            "doc_lengths": [int(n) for n in docs.lengths],
        }

        # ---- correct: outside the window, against the plain reference
        bad = sum(1 for x in losses[warm:] if not math.isfinite(x))
        ok = bad == 0 and trainer.train_step_compiles == 1
        got, want = _loss_against_reference(bench, cfg, trainer, rows, mesh, dp)
        bench.say(f"reference: program loss {got:.5f}, float32 reference "
                  f"{want:.5f}, |diff| {abs(got - want):.5f} (tol {TOL_LOSS})")
        ok = ok and math.isfinite(got) and abs(got - want) <= TOL_LOSS
        counters["ref_loss_diff"] = abs(got - want)
    return {"correct": ok, "attempted": len(steps), "failed": bad,
            "end_to_end": end_to_end, "counters": counters,
            "samples": samples, "window_s": window_s,
            "memory_peak_bytes": memory_peak}


def _loss_against_reference(bench, cfg, trainer, rows, mesh, dp):
    """The program's loss (its own eval step: same loss function, flash
    kernel, fused CE, segment mask, restarted positions) on the first
    ``dp`` packed rows, and the plain reference's on the same rows at the
    same weights."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib import sut
    batch = rows.collate([rows[i] for i in range(dp)])
    loss, _ = trainer.compile_eval_step()(
        trainer.params, None, trainer.place_eval_batch(batch),
        jax.random.key(0))
    got = float(loss)

    ref = bench.manifest.reference(cfg["reference"])
    embedding, layer, final_norm, lm_head = sut.reference_weights(
        trainer.params, mesh if len(jax.devices()) > 1 else None)
    nll, count = 0.0, 0
    for r in range(dp):
        hidden = ref.hidden_states(
            batch["input_ids"][r], embedding, layer, final_norm, cfg,
            segments=batch["segment_ids"][r])
        s, n = ref.next_token_nll(hidden, lm_head, batch["labels"][r])
        nll, count = nll + float(s), count + int(n)
    return got, nll / max(count, 1)
