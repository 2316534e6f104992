"""Seconds of one program's first run, to the fetched fence: mean of the
program's ``executor.first_run`` spans in the traced slice.  A program the
foreground compiled itself runs first at the end of its first call; one a
prefetch worker compiled ahead (nothing runs there) runs first in the
foreground's warm dispatch, which loads it onto the device."""

from benchmarks.harness.program_spans import named, seconds, window_spans


def read(record):
    runs = named(window_spans(record), "executor.first_run")
    if not runs:
        return None
    return sum(seconds(s) for s in runs) / len(runs)
