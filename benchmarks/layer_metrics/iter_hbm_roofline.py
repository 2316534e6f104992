"""Share of the HBM roofline the best finalist's iteration reaches: the
bytes one iteration must move (from shapes, ``harness/costs.py``) over the
chip's published HBM bandwidth (``harness/peaks.py``), over the device's own
time per iteration.  That time is the profiler's: the durations of the two
programs the epilogue ran at n and 4n repeats, differenced, so whatever a
dispatch does once is out of it.  Bound named: HBM."""


def read(record):
    t = record.get("trace")
    if not t or len(t.get("finalist_modules", [])) != 2 or not record["peaks"]:
        return None
    n, n4 = t["finalist_n"]
    (_, d_n), (_, d_n4) = t["finalist_modules"]
    device_iter_s = (d_n4 - d_n) / (n4 - n)
    if device_iter_s <= 0:
        return None
    least_s = record["cost"]["hbm_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_iter_s
