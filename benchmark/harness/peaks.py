"""The benchmark's own table of published per-chip peaks.

Keyed by the exact ``device_kind`` JAX reports. A device that is not in
the table is an error, never a default: every roofline and MFU share is
a share of these numbers, and they live here so that no later PR can
move the yardstick by editing the program.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The row for ``device_kind``; KeyError names the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (benchmark/harness/peaks.py)") from None
