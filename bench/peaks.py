"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links.  A kind
that is not in the table is an error, never a default.
"""

from __future__ import annotations

__all__ = ["PEAKS", "chip_peaks"]

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def chip_peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
