"""Shared training utilities: seeding, batch-identity checks."""
from __future__ import annotations

import random
from typing import Any, Dict

import jax
import numpy as np

from dla_tpu.utils.logging import log_rank_zero


def seed_everything(seed: int) -> jax.Array:
    """Seed host RNGs and return the root jax PRNG key
    (reference utils.py:24-29, minus the CUDA bits)."""
    random.seed(seed)
    np.random.seed(seed % (2 ** 32))
    return jax.random.key(seed)


def check_batch_identity(opt_cfg: Dict[str, Any], dp_size: int) -> int:
    """The reference's batch-size identity micro x world x accum = total
    (README troubleshooting; logged at train_sft.py:124-133). Returns the
    effective global batch; logs a warning on mismatch (like the reference,
    the identity is advisory, not enforced)."""
    micro = int(opt_cfg.get("micro_batch_size", 1))
    accum = int(opt_cfg.get("gradient_accumulation_steps",
                            opt_cfg.get("grad_accum", 1)))
    target = int(opt_cfg.get("total_batch_size", micro * accum * dp_size))
    effective = micro * accum * dp_size
    if effective != target:
        log_rank_zero(
            f"[dla_tpu] effective global batch {effective} "
            f"(micro {micro} x dp {dp_size} x accum {accum}) "
            f"!= configured total_batch_size {target}")
    return effective
