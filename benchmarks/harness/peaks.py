"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s in bf16, 393 TOP/s
in int8, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in the benchmark's peak "
            f"table ({sorted(PEAKS)}); add its published peaks with their "
            "source to benchmarks/harness/peaks.py"
        ) from None


def dtype_bytes(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def least_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
