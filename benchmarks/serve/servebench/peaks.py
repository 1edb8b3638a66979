"""Published peaks of one accelerator chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s).  A device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(197e12, 819e9, 16e9, 'Google Cloud documentation, "TPU v5e"')

PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": _V5E,   # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
