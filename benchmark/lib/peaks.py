"""Peak rates of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip). The
benchmark keeps its own table so that no change to the program can move a
utilisation; a device that is not in it is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}: add it to "
                       "benchmark/lib/peaks.py with its source") from None
