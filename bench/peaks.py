"""Per-chip peaks, keyed by the ``device_kind`` the chip reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A kind that is not here is an error, never a default.
(A copy of ``benchmarks/roofline.py::PEAKS_BY_KIND``.)
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float      # bf16 FLOP/s
    hbm_bw: float     # bytes/s


PEAKS_BY_KIND: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS_BY_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no peak row for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS_BY_KIND)}") from None
