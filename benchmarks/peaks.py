"""Published peaks of the chips this benchmark has run on, keyed by
``device_kind``.  The benchmark's own copy of the yardstick: a later PR
cannot move a share of a peak by editing the program's table.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect.  A kind that is not here is an error, never a
default: a share of a guessed peak is not a number.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add its row, "
            f"with its source, to benchmarks/peaks.py") from None
