"""Iteration time of the naive schedule (every kernel, one lane, no overlap)
by the benchmark's two-point clock."""


def read(record):
    return 1e3 * record["epilogue"]["naive"]["iter_s"]
