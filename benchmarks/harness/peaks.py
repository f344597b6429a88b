"""Published peaks of the chips the benchmark may run on, keyed by ``device_kind`` as JAX reports it.
A device that is not here is an error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8 a chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str, name: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: add it to benchmarks/harness/peaks.py "
                       "with its source")
    return PEAKS[device_kind][name]
