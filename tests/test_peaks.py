"""The per-chip peaks table is keyed by device_kind and never defaults."""
from types import SimpleNamespace

import jax
import pytest

from repro.launch import peaks


def test_v5e_row_carries_the_published_numbers_and_source():
    p = peaks.peaks_for(SimpleNamespace(platform="tpu",
                                        device_kind="TPU v5 lite"))
    assert p.flops_bf16 == 197e12 and p.hbm_bytes_per_s == 819e9
    assert "TPU v5e" in p.source
    assert peaks.PEAKS[peaks.TARGET_KIND] is p


def test_host_cpu_has_no_roofline():
    assert peaks.peaks_for(jax.devices("cpu")[0]) is None


def test_unknown_accelerator_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        peaks.peaks_for(SimpleNamespace(platform="tpu",
                                        device_kind="TPU v99"))
