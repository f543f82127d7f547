"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

The one table every roofline share in the repo divides by. A device whose
kind is not listed is an error, never a default: a share computed against
another chip's peaks is a wrong number that looks right. The host CPU has
no entry on purpose — ``peaks_for`` returns None there, and callers print
no roofline share for CPU rows.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s, dense bf16 matmul
    hbm_bytes_per_s: float   # HBM bandwidth
    ici_bytes_per_s: float   # chip-to-chip interconnect, all links together
    source: str


_V5E = ChipPeaks(
    flops_bf16=197e12, hbm_bytes_per_s=819e9,
    ici_bytes_per_s=1600e9 / 8,          # 1,600 Gbit/s
    source='Google Cloud documentation, "TPU v5e"')

# JAX reports a v5e chip as "TPU v5 lite".
PEAKS = {"TPU v5 lite": _V5E}

# The chip the production meshes and the dry-run roofline are sized for.
TARGET_KIND = "TPU v5 lite"


def peaks_for(device) -> ChipPeaks | None:
    """The peaks of ``device`` (a ``jax.Device``); None on the host CPU.
    Raises KeyError for an accelerator whose kind is not in ``PEAKS``."""
    if device.platform == "cpu":
        return None
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"({device.platform}); add it to repro.launch.peaks.PEAKS with "
            f"its source") from None
