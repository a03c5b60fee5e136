"""Published peaks per chip, keyed by `jax.devices()[0].device_kind`.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of HBM at
819 GB/s. A device that is not in the table is an error, never a default: a
utilisation against a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"no published peak for device_kind {device_kind!r}; add it to "
            "benchmark/lib/peaks.py with its source")
    return PEAKS[device_kind]
