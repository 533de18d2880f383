"""The yardstick's arithmetic against hand counts: percentiles, spreads, the
cost functions, the table of peaks."""

import statistics

import numpy as np
import pytest

from benchmarks import costs, stats


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_is_numpys_linear(q):
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0, 5.0, 3.5, 8.9]
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_hand_counts():
    assert stats.percentile([10, 20, 30, 40, 50], 95) == pytest.approx(48.0)
    assert stats.percentile([7], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_interquartile_over_median():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 12.5)


def test_matmul_costs_by_hand():
    assert costs.matmul_flops(2, 3, 4) == 48.0
    assert costs.matmul_bytes(2, 3, 4, 4) == 4 * (6 + 12 + 8)


def test_matmul_least_seconds_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 2*4*4*4 = 128 operations x 3 passes / 100 = 3.84 s; 4*48 bytes / 10 = 19.2 s
    least = costs.matmul_least_seconds(4, 4, 4, 4, "high", 1, peaks)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(19.2)
    assert least["compute_s"] == pytest.approx(3.84)
    big = costs.matmul_least_seconds(1000, 1000, 1000, 4, "high", 4, peaks)
    assert big["bound"] == "compute"
    assert big["seconds"] == pytest.approx(3 * 2e9 / 4 / 100.0)
    assert costs.PASSES == {"default": 1, "high": 3, "highest": 6}


def test_paged_attention_cost_by_hand():
    c = costs.paged_attention_cost(batch=2, table_width=3, page_len=16,
                                   kv_heads=4, group=1, dh=8, itemsize=2)
    t = 2 * 3 * 4
    assert c["flops"] == 4.0 * t * 8 * 16
    assert c["bytes"] == 2.0 * t * 16 * 8 * 2 + 2.0 * 2 * 4 * 8 * 2


def test_decoder_sizes_of_the_serving_configuration():
    p = costs.decoder_param_count(4096, 8, 4, 50432)
    assert p["per_layer"] == 12 * 4096 * 4096
    assert p["total"] * 4 == pytest.approx(7.269e9, rel=1e-3)
    assert costs.kv_bytes_per_token(4096, 8, 2) == 131072


def test_peaks_are_published_and_an_unknown_kind_is_an_error():
    v5e = costs.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.load_peaks("cpu")
    with pytest.raises(KeyError):
        costs.load_peaks("_source")
