"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page): per
chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.  Copied
from ``bench.DEVICE_PEAKS``.  A device that is not listed is an error, never a
default.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind):
    if device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no peaks recorded for device_kind {device_kind!r}; add it to "
            f"benchmarks/harness/peaks.py with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]
