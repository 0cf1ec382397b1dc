"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  A device that is not listed is an
error, never a default."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" system architecture page:
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a "
            f"row with its source to perfbench/pb/peaks.py") from None
