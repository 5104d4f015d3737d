"""Interpolated percentiles; the FLOP function against a hand count; the
peaks table."""

import json
import statistics

import pytest

from lib import peaks, spec, stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([1.0, 2.0], 50) == pytest.approx(1.5)
    assert stats.percentile([7.0], 90) == 7.0
    # two clusters: nearest rank would jump from 1 to 100 between these
    low = stats.percentile([1.0] * 50 + [100.0] * 50, 49.9)
    high = stats.percentile([1.0] * 50 + [100.0] * 50, 50.1)
    assert 1.0 < low < high < 100.0
    sample = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.3]
    assert stats.percentile(sample, 50) == statistics.median(sample)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


def test_flops_match_a_hand_count_at_111m():
    cfg = json.loads((spec.BENCH_DIR / "configs/cerebras-gpt-111m.json").read_text())
    # per layer: q,k,v,o 4 x 768^2 = 2,359,296; MLP 2 x 768 x 3072 = 4,718,592
    per_layer = 2_359_296 + 4_718_592
    head = 50257 * 768  # 38,597,376, tied: counted once, as the output matmul
    assert peaks.matmul_params(cfg) == 10 * per_layer + head == 109_376_256
    # L = 2048: a token attends to 1024.5 positions; 12 x 768 x 1024.5 a layer
    attn = 10 * 12 * 768 * 1024.5
    assert peaks.train_flops_per_token(cfg, 2048) == 6 * 109_376_256 + attn
    assert peaks.train_flops_per_token(cfg, 2048) == pytest.approx(750.7e6, rel=1e-3)
    assert peaks.train_flops_per_token(cfg, 128) == pytest.approx(662.2e6, rel=1e-3)


def test_peaks_table_and_unknown_device():
    v5e = peaks.peak("TPU v5 lite")
    assert v5e == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("cpu")
