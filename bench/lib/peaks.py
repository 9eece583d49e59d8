"""Published peaks of the devices the benchmark has run on, by the
``device_kind`` JAX reports. A device without a row is an error, never a
default. (The benchmark's own copy of ``serving/profiling.py`` DEVICE_PEAKS.)"""

from __future__ import annotations

DEVICE_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "hbm_bytes_s": 819e9,
        "bf16_flops_s": 197e12,
        "int8_ops_s": 393e12,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e" system architecture',
    },
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    row = DEVICE_PEAKS.get(device_kind)
    if row is None:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}. Add a row with its source to "
            f"bench/lib/peaks.py"
        )
    return row
