"""Seconds of the backend's compile and load in one program's first call:
mean of the program's ``executor.xla_compile`` spans over the first calls the
traced slice holds whole, prefetch workers' among them.  Several compile at
once (two workers and the foreground), so this is a cost per program, not
time the search was blocked (``first_call_wait_s_per_eval``)."""

from benchmarks.harness.program_spans import part_seconds_per_program


def read(record):
    return part_seconds_per_program(record, "executor.xla_compile")
