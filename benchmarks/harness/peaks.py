"""Published peaks of the devices this benchmark may run on, keyed by the
`device_kind` JAX reports.  A device that is not here is an error, not a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 16 GB of HBM2e at 819 GB/s and 197 TFLOP/s in bf16 (393 TOP/s int8).
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flop_per_s": 197e12,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/harness/"
            f"peaks.py (known: {sorted(PEAKS)}); add its published peaks "
            "with their source") from None
