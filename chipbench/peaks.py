"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s per chip.  The engine's
float64 work has no published peak; a roofline share takes the bf16 peak,
so its least time is bound by the bytes.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(device_kind: str, ops: float, nbytes: float) -> float:
    """The least time the chip could take: the larger of ops over peak
    FLOP/s and bytes over peak HBM bandwidth."""
    p = peak(device_kind)
    return max(ops / p["flops_per_s"], nbytes / p["hbm_bytes_per_s"])
