"""The benchmark's own clock for one schedule's iteration time.

``run_n(n)`` runs a schedule ``n`` times inside one fenced dispatch.  Its
wall time is ``fixed + n * iter``: the fixed part (dispatch, fence, whatever
the program does once per call) rode on the program's own low-floor readings
(PERF.md, PR 22: 123 ms at the search floor against 35 ms at 0.4 s).  Two
repeat counts ``n`` and ``4n`` separate the two: the slope is the iteration
time, the intercept the fixed cost per dispatch.  ``4n`` repeats last about
``target_secs``, so the difference of the two readings spans some 0.4 s of
device work, far above the host clock's half millisecond.
"""

from __future__ import annotations

import statistics
import time


def _timed(run_n, n: int, clock) -> float:
    t0 = clock()
    run_n(n)
    return clock() - t0


def two_point(run_n, target_secs: float = 0.5, rounds: int = 3,
              clock=time.perf_counter, max_n: int = 1 << 20) -> dict:
    """``{"iter_s", "fixed_s", "n", "n4", "slopes", "intercepts"}`` for an
    already-compiled ``run_n``: medians over ``rounds`` rounds.  A round
    takes the counts as n, 4n, 4n, n and uses the sums, so that it is the
    same whichever count comes after which (on the chip a call's time
    depends on the call before it: rounds that took n first read 10% more
    per iteration than rounds that took 4n first, PERF.md, PR 24)."""
    run_n(1)
    t1 = _timed(run_n, 1, clock)
    k = 4
    while True:
        tk = _timed(run_n, k, clock)
        if tk - t1 >= 0.05 or k >= max_n:
            break
        k *= 4
    rough = max((tk - t1) / (k - 1), 1e-8)
    n = max(2, min(max_n // 4, int(round(target_secs / rough / 4))))
    n4 = 4 * n
    slopes, intercepts = [], []
    for _ in range(rounds):
        t = {n: 0.0, n4: 0.0}
        for m in (n, n4, n4, n):
            t[m] += _timed(run_n, m, clock) / 2
        slope = (t[n4] - t[n]) / (n4 - n)
        slopes.append(slope)
        intercepts.append(t[n] - n * slope)
    return {"iter_s": statistics.median(slopes),
            "fixed_s": statistics.median(intercepts),
            "n": n, "n4": n4, "slopes": slopes, "intercepts": intercepts}
