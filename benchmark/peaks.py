"""Published peaks of the devices a cell may run on, keyed by
``device_kind`` as JAX reports it. A device that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600
Gbit/s inter-chip interconnect. Copied from ``bench.py::DEVICE_PEAKS``
(sound; the original is listed for deletion in PERF.md).
"""

from __future__ import annotations

DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


def device_peaks(device_kind: str) -> dict[str, float]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}"
        ) from None
