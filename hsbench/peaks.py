"""Published peaks of the chips the benchmark has run on, keyed by
``jax.devices()[0].device_kind``. A device that is not here is an error, never
a default: add it with its source."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page: 197
    # TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture, per-chip)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "hsbench/peaks.py with their source"
        )
    return PEAKS[device_kind]
