"""Published per-chip peaks, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
Copied from the repository's `bench.TPU_PEAKS`.  A device kind missing
here is an error, never a default.
"""

TPU_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in TPU_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to benchmark/peaks.py with their source")
    return TPU_PEAKS[device_kind]
