"""Published peaks of one chip, keyed by ``device_kind`` as jax reports it.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.  A kind that is not in the table
is an error, never a default (copied from ``bench.py:_PEAK_FLOPS``)."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it to "
            "benchmarks/harness/peaks.py with its source"
        )
    return PEAKS[device_kind]
