"""The per-lane gap and the sample the check compares."""
import math

from bench import check

REF = {"avg_exec_us": 10.0, "total_energy_uj": 200.0, "events": 2000,
       "n_slow": 500, "n_tasks": 600}
PROG = {"avg_exec_us": 10.0, "total_energy_uj": 200.0, "n_iters": 2000,
        "n_slow": 500, "n_done": 600}


def test_lane_gap_is_the_worst_field():
    assert check.lane_gap(PROG, REF) == 0.0
    assert check.lane_gap(dict(PROG, avg_exec_us=10.1), REF) == \
        abs(10.1 - 10.0) / 10.0
    assert check.lane_gap(dict(PROG, n_iters=2010), REF) == 0.005
    assert check.lane_gap(dict(PROG, n_slow=506), REF) == 0.01


def test_unfinished_or_nan_lane_is_infinitely_off():
    assert check.lane_gap(dict(PROG, n_done=599), REF) == math.inf
    assert check.lane_gap(dict(PROG, avg_exec_us=float("nan")), REF) == \
        math.inf


def test_numbers_are_order_statistics():
    gaps = [0.0] * 29 + [math.inf] * 11
    nums = check.numbers(gaps)
    assert nums["lane_gap_median"] == 0.0
    assert nums["lane_gap_p75"] == math.inf


def test_sample_holds_the_longest_lane_and_is_seeded():
    a = check.draw(7, [560, 560], (1, 33), 40)
    assert a[0] == (1, 33) and len(a) == len(set(a)) == 40
    assert a == check.draw(7, [560, 560], (1, 33), 40)
    assert a != check.draw(8, [560, 560], (1, 33), 40)
