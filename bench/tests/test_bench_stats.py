"""Percentile, rate, spread and interval arithmetic on fixed lists."""
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench.lib import stats  # noqa: E402


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (95, 9.55),
                                    (100, 10.0)])
def test_percentile_linear_between_ranks(q, want):
    assert stats.percentile(list(range(10, 0, -1)), q) == pytest.approx(want)


def test_percentile_edges():
    assert stats.percentile([], 95) is None
    assert stats.percentile([7.0], 95) == 7.0


def test_rate_counts_half_open_window():
    times = [0.0, 0.5, 1.0, 1.5, 2.0, 2.5]
    assert stats.count_in(times, 1.0, 2.0) == 2      # 1.0 and 1.5
    assert stats.rate(times, 0.0, 2.0) == pytest.approx(2.0)


def test_spread_is_python_quartiles_over_median():
    v = [10.0, 11.0, 12.0, 13.0, 20.0, 9.0]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


@pytest.mark.parametrize("iv,want", [
    ([], 0.0),
    ([(0, 1)], 1.0),
    ([(0, 2), (1, 3)], 3.0),                 # overlap
    ([(0, 1), (2, 3)], 2.0),                 # disjoint
    ([(0, 10), (2, 3), (4, 5)], 10.0),       # nested
    ([(5, 6), (0, 1), (1, 2)], 3.0),         # unsorted, touching
])
def test_union_length(iv, want):
    assert stats.union_length(iv) == want
