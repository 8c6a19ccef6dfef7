"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (Cloud TPU system
architecture): 197 TFLOP/s bfloat16 and 819 GB/s of HBM bandwidth per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
