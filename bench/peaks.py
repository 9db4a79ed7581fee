"""Peak rates of each chip the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s per
chip.  A roofline share is taken against the bf16 rate, the fastest
floating-point rate the chip has, so no precision choice of the program
can read above 100%.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
