"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

The benchmark keeps its own table so that a change to the program cannot
move the yardstick. A kind that is not listed is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float   # HBM bandwidth
    source: str


PEAKS = {
    # JAX reports a v5e chip as "TPU v5 lite".
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                             source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of a device kind; KeyError for a kind not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to PEAKS with its source"
                       ) from None
