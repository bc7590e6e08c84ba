"""Published peaks of the chips the benchmark may run on, keyed by
jax's `device_kind`.  A device that is not in the table is an error,
never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s,
#: 197 TFLOP/s bf16, 393 TOP/s int8, per chip.
_V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12, "int8_ops": 393e12,
        "hbm_bytes": 16e9, "source": "cloud.google.com/tpu/docs/v5e"}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add its "
            f"published numbers to benchmark/peaks.py, do not guess")
    return PEAKS[device_kind]
