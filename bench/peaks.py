"""Per-chip peaks, keyed by the ``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip.  A device that is not in the table is an
error: no share of a peak is ever computed against a guessed one.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "int8_ops": 393e12,        # OP/s
        "hbm_bytes": 16e9,         # bytes
        "hbm_bw": 819e9,           # bytes/s
        "ici_bw": 1600e9 / 8,      # bytes/s per chip (1,600 Gbit/s)
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
