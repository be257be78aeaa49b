"""Published peaks of the devices the benchmark knows, keyed by JAX's
``device_kind``.  A device that is not here is an error, never a
default."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s,
#: 197 TFLOP/s bf16, 393 TOP/s int8 per chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add it to benchmark/harness/"
                       "peaks.py with its source")
    return PEAKS[device_kind]
