"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports. A device that is not here is an error:
no share of a peak is ever computed against a guess."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16 and 819 GB/s of HBM per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
