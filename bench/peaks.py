"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table of one chip kind; raises KeyError for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)} ({SOURCE})") from None
