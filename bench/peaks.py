"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not in the table is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,     # FLOP/s
        "ops_int8": 393e12,       # OP/s
        "hbm_bytes": 16e9,        # B
        "hbm_bw": 819e9,          # B/s
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
