"""FLOPs accounting + MFU (model FLOPs utilization).

The reference publishes no performance numbers at all (BASELINE.md), so the
measurement harness is designed from scratch: per-step FLOPs come from XLA's
own cost model on the exact compiled executable (``compiled.cost_analysis()``
— counts every fused matmul/conv at 2*M*N*K, which is more faithful than
hand formulas), and MFU divides the achieved FLOP rate by the chip's peak
bf16 rate.  BASELINE.json's north star is >= 50% MFU for ResNet-50/ImageNet.
"""

from __future__ import annotations

from typing import Optional

import jax

# Per-chip peaks keyed by the EXACT ``jax.Device.device_kind`` string:
# (dense bf16 FLOP/s, HBM bytes/s), from the public Cloud TPU spec
# sheets.  Kinds are the strings jax itself matches on
# (jax/_src/pallas/mosaic/tpu_info.py).  "TPU v5 lite" is what the v5e
# this repo runs on reports (chip_smoke.py prints it); the other rows
# are spec-sheet values no run here has met.  A kind that is not in the
# table is an error where a peak is asked for — a substring guess once
# let "v5" fall through to the v5p number, and a silent ``None`` drops
# MFU from the artifact without anyone noticing.
CHIP_PEAKS = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),    # v5e
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),        # v5p
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1638e9),   # v6e / Trillium
    "TPU v6e": (918e12, 1638e9),
}


def _chip_peaks(device: Optional[jax.Device]) -> Optional[tuple]:
    """(bf16 FLOP/s, HBM bytes/s) of one chip; None off-TPU (a CPU has
    no chip peak to divide by); raises for a TPU kind not in the table."""
    d = device or jax.devices()[0]
    if d.platform != "tpu":
        return None
    if d.device_kind not in CHIP_PEAKS:
        raise ValueError(
            f"no peak FLOP/s / HBM bandwidth recorded for TPU device_kind "
            f"{d.device_kind!r}; add its spec-sheet row to "
            f"utils.flops.CHIP_PEAKS (known: {sorted(CHIP_PEAKS)})")
    return CHIP_PEAKS[d.device_kind]


def hbm_bytes_per_sec(device: Optional[jax.Device] = None) -> Optional[float]:
    """HBM bandwidth of one chip in bytes/s (None off-TPU)."""
    peaks = _chip_peaks(device)
    return peaks and peaks[1]


def peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Peak bf16 FLOP/s of one chip (None off-TPU)."""
    peaks = _chip_peaks(device)
    return peaks and peaks[0]


def compiled_flops(jitted, *args, **kwargs) -> Optional[float]:
    """FLOPs of one invocation of a jitted function, from XLA's cost model
    of the compiled executable (a lower/compile failure propagates).
    None when the backend's cost model reports no FLOPs."""
    analysis = jitted.lower(*args, **kwargs).compile().cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not analysis:
        return None
    flops = analysis.get("flops")
    return float(flops) if flops and flops > 0 else None


def mfu(flops_per_step: Optional[float], step_time_s: float,
        device: Optional[jax.Device] = None) -> Optional[float]:
    """Achieved fraction of peak: (FLOPs/step / step_time) / peak.
    None off-TPU or when the cost model reported no FLOPs; an unknown
    TPU ``device_kind`` raises (``CHIP_PEAKS``)."""
    peak = peak_flops(device)
    if not flops_per_step or not peak or step_time_s <= 0:
        return None
    return (flops_per_step / step_time_s) / peak
