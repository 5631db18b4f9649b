"""The harness arithmetic, checked on hand-made numbers."""
import math
import statistics

import pytest

from chipbench import stats
from chipbench.stats import Span


def test_rate_is_all_work_over_all_time():
    # two passes of 8 cells, the second running past the window's end
    assert stats.rate(16, 32.6) == pytest.approx(16 / 32.6)
    with pytest.raises(ValueError):
        stats.rate(3, 0.0)


@pytest.mark.parametrize("q", [0, 25, 50, 80, 90, 100])
def test_percentile_matches_linear_interpolation(q):
    xs = [0.31, 0.12, 0.55, 0.4, 0.9, 0.21, 0.33, 0.47, 0.18, 0.66, 0.29]
    expected = statistics.quantiles(xs, n=100, method="inclusive")
    want = {0: min(xs), 100: max(xs)}.get(q) or expected[q - 1]
    assert stats.percentile(xs, q) == pytest.approx(want)


@pytest.mark.parametrize("n,q", [(114, 90), (100, 90), (99, 80), (76, 80),
                                 (50, 80), (49, 75), (38, 70), (20, 50),
                                 (19, None)])
def test_tail_percentile_keeps_ten_beyond(n, q):
    assert stats.tail_percentile(n) == q
    if q is not None:
        assert n * (100 - q) / 100 >= 10


def test_percentile_of_name():
    assert stats.percentile_of_name("race_p80_s") == 80
    assert stats.percentile_of_name("race_p70_s") == 70
    with pytest.raises(ValueError):
        stats.percentile_of_name("race_mean_s")


def _spans():
    return [Span(0.0, 0.002, 8, ((8, 128, 8),), nnz_min=300, cols_min=40),
            Span(0.010, 0.013, 6, ((8, 128, 16),), nnz_min=500, cols_min=60),
            Span(0.020, 0.021, 2, (), 0, 0)]


def test_span_ratios():
    sp = _spans()
    assert stats.lanes_per_round(sp) == pytest.approx(16 / 3)
    assert stats.alloc_share_pct(sp, 0.05) == pytest.approx(100 * 0.006 / 0.05)
    assert stats.alloc_round_ms(sp) == pytest.approx(2.0)
    assert stats.lanes_per_round([]) is None
    assert stats.alloc_share_pct([], 1.0) is None


def test_pad_fill_counts_padded_cells_of_min_batches():
    sp = _spans()
    cells = 8 * 128 * 8 + 8 * 128 * 16
    assert stats.pad_fill_pct(sp) == pytest.approx(100 * 800 / cells)
    assert stats.pad_fill_pct([Span(0, 1, 3, (), 0, 0)]) is None


def test_in_window_keeps_whole_spans():
    sp = _spans()
    assert stats.in_window(sp, 0.005, 0.025) == sp[1:]


def test_roofline_bytes_come_from_requests_not_padding():
    # 300 nonzeros and 40 columns: 8 bytes each, whatever the padding
    assert stats.solve_bytes(300, 40) == 8 * 340
    moved = stats.solve_bytes(300, 40)
    pct = stats.roofline_pct(moved, 819e9, 57e-6)
    assert pct == pytest.approx(100 * moved / 819e9 / 57e-6)
    assert 0 < pct < 100
    assert stats.roofline_pct(moved, 819e9, 0.0) is None
    with pytest.raises(ValueError):
        stats.roofline_pct(moved, 0.0, 1.0)
    assert not math.isnan(pct)
