"""MFU accounting (SURVEY.md §4 "beyond reference": the rebuild adds MFU
tracking the reference never had).

Two FLOP sources: (a) XLA's own cost analysis on the compiled step — exact
for what was actually compiled; (b) analytic per-model formulas
(models.llama.flops_per_token) — stable across compiler versions. Peak chip
FLOPs tables cover the TPU generations this framework targets.
"""

from __future__ import annotations

import jax

# bf16 peak FLOP/s per chip, keyed by the exact `device_kind` JAX reports.
# (v5e's oft-quoted 394 TOPS is int8; bf16 is 197.)
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # trillium
}


def device_peak_flops(device: jax.Device | None = None) -> float:
    """Peak of the device a utilization is reported against. A device
    that is not in the table is an error: a utilization over a made-up
    peak (a CPU's, or a near-miss of the name) is not a measurement."""
    dev = device if device is not None else jax.devices()[0]
    try:
        return PEAK_FLOPS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {dev.device_kind!r} "
            f"(known: {sorted(PEAK_FLOPS)})") from None


def compiled_flops(compiled) -> float | None:
    """Total FLOPs of a jax compiled/lowered step via XLA cost analysis."""
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0]
        return float(analysis.get("flops", 0.0)) or None
    except Exception:
        return None


def mfu(flops_per_step: float, step_time_s: float, n_devices: int,
        peak_per_device: float | None = None) -> float:
    peak = peak_per_device if peak_per_device else device_peak_flops()
    if step_time_s <= 0:
        return 0.0
    return flops_per_step / (step_time_s * peak * n_devices)
