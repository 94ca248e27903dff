"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 16 GB of HBM at
819 GB/s per chip (197 TFLOP/s in bf16, unused: the benchmark's
kernels are bound by memory).  A device whose kind is not here is an
error, never a default.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_s": 819e9,
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown
    device."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add "
            "them to bench/peaks.py with their source") from None
