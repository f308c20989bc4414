"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (four
ICI links, 50 GB/s each).  A device kind missing from the table is an
error, never a default: a roofline against another chip's peaks is wrong
without saying so.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "hbm_bytes": 16e9, "ici_bw": 50e9},
}

# the chip the production meshes (launch/mesh.py) and the dry run target
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str = TARGET_KIND) -> dict:
    """Peak rates of one chip of ``device_kind`` (FLOP/s, B/s, bytes)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
