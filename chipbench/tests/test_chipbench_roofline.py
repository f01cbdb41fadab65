"""The histogram kernel's operation and byte count, and the peaks table."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, roofline

REPO = Path(__file__).resolve().parents[2]

PEAKS = json.loads((REPO / harness.PEAKS).read_text())


def test_latency_hist_cost_by_hand():
    # 72 lanes x 786,432 samples, 64 bins: 5 B a sample (f32 + bool flag),
    # 65 f32 edges and 64 int32 counts a lane, 7 comparisons a sample
    ops, bytes_ = roofline.latency_hist_cost(72, 786_432, 64)
    assert bytes_ == 72 * 786_432 * 5 + 72 * 65 * 4 + 72 * 64 * 4
    assert bytes_ == 283_152_672
    assert ops == 72 * 786_432 * 7


def test_share_names_its_bound():
    peak = PEAKS["devices"]["TPU v5 lite"]
    ops, bytes_ = roofline.latency_hist_cost(72, 786_432, 64)
    pct, bound = roofline.share(ops, bytes_, 0.05, peak)
    assert bound == "bytes"
    assert pct == pytest.approx(100 * 283_152_672 / 819e9 / 0.05)
    pct, bound = roofline.share(197e12, 1.0, 2.0, peak)
    assert (pct, bound) == (pytest.approx(50.0), "ops")


def test_peaks_hold_the_v5e():
    v5e = PEAKS["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert PEAKS["source"]


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.NoChip, match="TPU v99"):
        harness.devices_for(1, PEAKS, require_tpu=True)


def test_too_few_chips_is_an_error(monkeypatch):
    import jax
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.NoChip, match="4 chips"):
        harness.devices_for(4, PEAKS, require_tpu=True)
