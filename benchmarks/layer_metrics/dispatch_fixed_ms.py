"""Fixed cost of one fenced dispatch: the two-point clock's intercept,
median over the schedules the epilogue timed."""

import statistics


def read(record):
    clocks = record["epilogue"]["clocks"]
    if not clocks:
        return None
    return 1e3 * statistics.median(c["fixed_s"] for c in clocks)
