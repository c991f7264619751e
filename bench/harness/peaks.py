"""Published peaks of each chip, keyed by ``device_kind``.

A kind that is not here is an error, never a default. No fp32 matmul peak is
published for these chips, so fp32 work is counted against the bf16 peak: an
fp32 product at HIGHEST precision takes several bf16 passes, so such cells
cannot reach 100%. The EUP (exp) is a third resource with no published peak
and is not counted.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 MXU
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: 197 "
                  "TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"add them to bench/harness/peaks.py with their source")
    return PEAKS[device_kind]
