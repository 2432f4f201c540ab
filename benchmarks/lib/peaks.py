"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX
reports.  A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s inter-chip
interconnect).  ``TPU v5 lite`` is what the v5e reports (PERF.md, PR 21).
Copied from the program's ``utils/flops.py::CHIP_PEAKS`` (PERF.md, Open
questions: the original can go once nothing else reads it); only the kind
this benchmark has met is kept.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30, "ici_bits_per_s": 1600e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peak recorded for device_kind {device_kind!r}; "
            f"add its row, with its source, to benchmarks/lib/peaks.py "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
