"""Seconds a measurement call of the traced slice kept the foreground thread
blocked on first calls: its ``pipeline.wait`` spans (a prefetch worker's
compile still running) and its own ``executor.first_call`` spans (nobody
compiled the program ahead), over its ``bench.benchmark`` and ``bench.batch``
spans."""

from benchmarks.harness.program_spans import (
    FIRST_CALL,
    MEASUREMENT_CALLS,
    foreground,
    named,
    seconds,
    window_spans,
)


def read(record):
    fg = foreground(window_spans(record))
    calls = named(fg, *MEASUREMENT_CALLS)
    if not calls:
        return None
    blocked = named(fg, "pipeline.wait", FIRST_CALL)
    return sum(seconds(s) for s in blocked) / len(calls)
