"""Naive's iteration time over the best finalist's, both by the benchmark's
two-point clock in the epilogue."""


def read(record):
    e = record["epilogue"]
    if e["best"]["iter_s"] <= 0:
        return None
    return e["naive"]["iter_s"] / e["best"]["iter_s"]
