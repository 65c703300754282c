"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. One table, no override from the environment;
a device that is not in it is an error, never a default."""

from __future__ import annotations

#: source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s chip-to-chip
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
    },
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/peaks.py with its source") from None
