"""The sweep's knee: the highest rate sustained on every seed, with every
rate below it, where a slot-full clump on one seed fails its rate."""
from bench import sweep


def _row(rate, first, second, unfinished=0):
    return {"rate": rate, "ttft_p90_ms_first_half": first,
            "ttft_p90_ms_second_half": second,
            "unfinished_after_drain": unfinished}


def test_knee_needs_every_seed_and_every_lower_rate():
    rows = [_row(1.5, 61, 56), _row(1.5, 70, 80),
            _row(2.0, 76, 191), _row(2.0, 2100, 2400),   # one seed clumps
            _row(2.5, 90, 120), _row(2.5, 95, 110)]
    assert sweep.knee(rows, 500.0) == 1.5
    assert sweep.knee(rows, 5000.0) == 2.5
    assert sweep.knee([_row(1.0, 900, 50)], 500.0) is None


def test_unfinished_or_empty_half_is_not_sustained():
    assert not sweep.sustained(_row(1.0, 50, 50, unfinished=1), 500.0)
    assert not sweep.sustained(_row(1.0, 50, None), 500.0)
    assert sweep.sustained(_row(1.0, 50, 500.0), 500.0)
