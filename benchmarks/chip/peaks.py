"""Published per-chip peaks, keyed by JAX's ``device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect.
Copied from ``benchmarks/roofline.py``'s ``PEAKS`` so that the yardstick
stays with the benchmark.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The row for ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
