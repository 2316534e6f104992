"""Share of the background compiles issued that a foreground measurement then
used: the program's counters ``pipeline.prefetch.hits`` over
``pipeline.prefetch.issued``.  Neither moves outside the window (set-up and
epilogue hint nothing).  What is missing from 100 was compiled for nothing or
was still in flight at the deadline."""

from benchmarks.harness.program_spans import counter


def read(record):
    issued = counter("pipeline.prefetch.issued")
    if not issued:
        return None
    return 100.0 * counter("pipeline.prefetch.hits") / issued
