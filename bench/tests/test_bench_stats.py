"""Metric arithmetic: percentiles over every request and every gap, and
the tokens that reached the host inside a window."""
import pytest

from bench import stats


def test_percentiles_are_over_all_samples():
    vals = list(range(1, 101))                  # 1..100
    assert stats.percentile(vals, 50) == pytest.approx(50.5)
    assert stats.percentile(vals, 90) == pytest.approx(90.1)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_end_to_end_by_hand():
    times = [[0.1, 0.2, 0.4], [1.3, 1.4], [2.05, 2.5, 3.5, 3.6]]
    got = stats.end_to_end(times)
    gaps = [0.1, 0.2, 0.1, 0.45, 1.0, 0.1]
    assert got == {"itl_p90_ms": pytest.approx(
        1e3 * stats.percentile(gaps, 90))}
    # tokens before 3.0 s: 3 + 2 + 2
    assert stats.tokens_in_window(times, 0.0, 3.0) == 7


def test_gaps_and_window_counts():
    assert stats.inter_token_gaps([[0.0], [1.0, 1.5, 1.75]]) == \
        pytest.approx([0.5, 0.25])
    assert stats.tokens_in_window([[0.0, 0.99, 1.0]], 0.0, 1.0) == 2
