"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture — 197
TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of interchip interconnect. A device that is not in the table is an error,
not a default: a roofline share against a guessed peak is no measurement.
"""

from __future__ import annotations

DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def device_peaks(device_kind: str) -> dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them,"
            " with their source, to benchmarks/chip/peaks.py"
        ) from None
