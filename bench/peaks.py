"""Published peaks of each accelerator the benchmark runs on, by device_kind.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip. A device whose kind is not listed here is
an error: the benchmark measures nothing on it.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
