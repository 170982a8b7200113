"""MFU / throughput math and the per-chip peak tables.

Model FLOPs Utilization is the hardware-efficiency north star: the
fraction of a chip's peak bf16 FLOP/s the training loop actually
achieves, using the standard dense-transformer cost model

    train FLOPs/token ~= 6 * N        (fwd 2N + bwd 4N, N = params)
    MFU = tokens/sec/chip * 6N / peak_flops(chip)

This module is deliberately dependency-free (no jax import) so offline
report tooling can use the tables: the trainer, bench and the sweep
tools all read ONE set of peak numbers. A host CPU has no entry — a CPU
run reports no MFU and no roofline verdict — and an accelerator that is
not in the tables is an error, never another chip's figure.
"""
from __future__ import annotations

from typing import Dict, Optional

#: Documented tolerance for the XLA-vs-6N FLOPs cross-check
#: (``MFUCalculator.check_estimate``). 6N ignores attention's quadratic
#: term and counts fwd+bwd as exactly 3x forward, while XLA counts every
#: lowered op (2mnk per matmul, rematerialized fwd under checkpointing,
#: embedding gathers); on dense transformer steps the two land well
#: inside +-35% of each other, and a larger divergence means one of the
#: two numbers is wrong (docs/OBSERVABILITY.md "XLA introspection").
ESTIMATE_TOLERANCE = 0.35

#: Per-chip peak bf16 FLOP/s by device kind (substring match against
#: jax's ``device_kind``; Google Cloud TPU documentation).
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v4": 275e12, "v6": 918e12, "trillium": 918e12,
}

#: Per-chip HBM bandwidth, bytes/s (same substring match).
PEAK_HBM_BW = {
    "v5 lite": 819e9, "v5e": 819e9, "v5p": 2765e9,
    "v4": 1228e9, "v6": 1640e9, "trillium": 1640e9,
}


def _lookup(table: Dict[str, float], device_kind: str, platform: str,
            what: str) -> Optional[float]:
    if platform == "cpu":
        return None
    kind = (device_kind or "").lower()
    for key, val in table.items():
        if key in kind:
            return val
    raise ValueError(
        f"no {what} on record for device kind {device_kind!r} "
        f"(platform {platform!r}); add it to dla_tpu/telemetry/mfu.py "
        "with its source rather than assuming another chip's figure")


def peak_flops_for(device_kind: str, platform: str = "") -> Optional[float]:
    """Peak bf16 FLOP/s for a device kind string; None on the CPU
    platform. An accelerator that is not in the table is an error."""
    return _lookup(PEAK_BF16_FLOPS, device_kind, platform,
                   "peak bf16 FLOP/s")


def hbm_bw_for(device_kind: str, platform: str = "") -> Optional[float]:
    """Per-chip HBM bytes/s for a device kind string; None on the CPU
    platform, raises like :func:`peak_flops_for` on an unknown chip."""
    return _lookup(PEAK_HBM_BW, device_kind, platform, "HBM bandwidth")


def flops_per_token(n_params: int, training: bool = True) -> float:
    """Dense-transformer FLOPs per token: 6N training (fwd+bwd), 2N
    inference. The 6N approximation ignores attention's quadratic term,
    standard for MFU reporting (PaLM appendix B convention)."""
    return (6.0 if training else 2.0) * float(n_params)


class MFUCalculator:
    """Binds a model size to a chip so the hot loop computes MFU from
    the one number it already has (tokens/sec/chip).

    ``n_params`` should be the parameter count doing fwd+bwd work. For
    LoRA/adapter training the frozen base still does forward+activation
    -gradient work, so trainable-only counts UNDERSTATE true FLOPs; we
    use total touched params when the caller passes them, and document
    the caveat in docs/OBSERVABILITY.md.
    """

    def __init__(self, n_params: int, device_kind: str = "cpu",
                 platform: str = "cpu", training: bool = True):
        self.n_params = int(n_params)
        self.device_kind = device_kind
        self.platform = platform
        self.peak = peak_flops_for(device_kind, platform)
        self.hbm_bw = hbm_bw_for(device_kind, platform)
        self.flops_per_token = flops_per_token(self.n_params, training)

    def mfu(self, tokens_per_sec_per_chip: Optional[float]
            ) -> Optional[float]:
        """MFU in [0, ~1] from per-chip token throughput; 0.0 when the
        rate is unknown (no steps yet), None on a device with no peak on
        record (a CPU) — callers publish no gauge then."""
        if self.peak is None:
            return None
        if not tokens_per_sec_per_chip:
            return 0.0
        return tokens_per_sec_per_chip * self.flops_per_token / self.peak

    def roofline(self, flops: float, bytes_accessed: float
                 ) -> Dict[str, float]:
        """Analytic roofline verdict for one compiled function from its
        ``cost_analysis()`` FLOPs and bytes accessed.

        Arithmetic intensity (FLOPs per HBM byte) above the chip's ridge
        point (peak FLOP/s over peak HBM bytes/s) means the function is
        compute-bound; below it, bandwidth-bound. Values are plain
        floats so they publish directly as gauges: ``compute_bound``
        1.0/0.0. Empty on a device with no peaks on record."""
        if self.peak is None or self.hbm_bw is None:
            return {}
        intensity = (float(flops) / float(bytes_accessed)
                     if bytes_accessed > 0 else 0.0)
        ridge = self.peak / self.hbm_bw
        return {
            "intensity": intensity,
            "ridge": ridge,
            "compute_bound": 1.0 if intensity >= ridge else 0.0,
        }

    def check_estimate(self, xla_flops: float, tokens: float,
                       tolerance: float = ESTIMATE_TOLERANCE
                       ) -> Dict[str, float]:
        """Cross-check XLA's analytic FLOPs against the 6N estimate for
        a step over ``tokens`` tokens. ``ratio`` is XLA / 6N (1.0 =
        perfect agreement); ``within_tolerance`` is 0.0 when the
        divergence exceeds ``tolerance`` — the flagged condition the
        introspection layer publishes."""
        # dla: disable=host-sync-in-hot-loop -- plain python floats from cost_analysis, no device fetch; called at logging cadence
        estimate = self.flops_per_token * float(tokens)
        # dla: disable=host-sync-in-hot-loop -- plain python floats from cost_analysis, no device fetch; called at logging cadence
        ratio = float(xla_flops) / estimate if estimate > 0 else 0.0
        return {
            "estimate_flops": estimate,
            "ratio": ratio,
            "within_tolerance": (1.0 if abs(ratio - 1.0) <= tolerance
                                 else 0.0),
        }
