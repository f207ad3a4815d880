"""Published peaks per device kind, the roofline's yardstick.

Copied from the program's ``launch/roofline.KERNEL_PEAKS`` (TPU row) so
that no later change to the program can move it.  Source: Google Cloud
documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s.  A kind not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    got = PEAKS.get(device_kind)
    if got is None:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them with their source")
    return got
