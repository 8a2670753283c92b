"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite" (the device kind JAX reports for a TPU v5e chip):
Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.

A kind that is not in the table raises: no measurement is ever set
against another chip's peaks by default. The dry-run roofline analyses
its compiled artifacts against ``TARGET_KIND``, the chip this repository
is built for.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s, bf16 MXU
    int8_ops: float            # OP/s, int8 MXU
    hbm_bytes_per_s: float
    ici_link_bytes_per_s: float    # chip-to-chip interconnect, one link
    source: str


PEAKS = {
    # 1,600 Gbit/s per chip over the 4 links of its 2D torus: 50 GB/s a link
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes_per_s=819e9,
                         ici_link_bytes_per_s=1600e9 / 8 / 4,
                         source='Google Cloud documentation, "TPU v5e"'),
}

TARGET_KIND = "TPU v5 lite"


def peaks(kind: str) -> Peaks:
    """The published peaks of one chip of ``kind``; KeyError if unknown."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
