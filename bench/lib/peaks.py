"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite", the kind JAX reports for a TPU v5e chip: Google
Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
of HBM at 819 GB/s. A kind that is not in the table is an error, never a
default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
