"""Published chip-to-chip interconnect (ICI) peaks, keyed like ``peaks.py`` by
``jax.devices()[0].device_kind``. A device that is not here is an error, never
a default: add it with its source."""

ICI_PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page:
    # "Interchip Interconnect BW 1600 Gbps" per chip, all of its links together
    "TPU v5 lite": {
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture, per-chip: 1600 Gbps)",
    },
}


def ici_peaks(device_kind: str) -> dict:
    if device_kind not in ICI_PEAKS:
        raise KeyError(
            f"no published interconnect peak for device kind {device_kind!r}; add it to "
            "hsbench/peaks_ici.py with its source"
        )
    return ICI_PEAKS[device_kind]
