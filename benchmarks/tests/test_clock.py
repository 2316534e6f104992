"""The two-point clock finds a known slope and intercept."""

import time

from benchmarks.harness.clock import two_point


def test_two_point_recovers_slope_and_intercept():
    slope, fixed = 2e-4, 0.03

    def run_n(n):
        time.sleep(fixed + n * slope)

    c = two_point(run_n, target_secs=0.2, rounds=5)
    assert abs(c["iter_s"] - slope) / slope < 0.05
    assert abs(c["fixed_s"] - fixed) / fixed < 0.25
    assert c["n4"] == 4 * c["n"]
    # 4n repeats last about the target
    assert 0.1 < c["n4"] * slope < 0.4


def test_two_point_is_not_moved_by_the_fixed_cost():
    """The bias the program's own clock has at low floors: a large fixed
    cost per dispatch must not ride on the iteration time."""
    slope = 1e-4
    a = two_point(lambda n: time.sleep(0.001 + n * slope), 0.2)
    b = two_point(lambda n: time.sleep(0.05 + n * slope), 0.2)
    assert abs(a["iter_s"] - b["iter_s"]) / slope < 0.05
    assert b["fixed_s"] > 10 * a["fixed_s"]
