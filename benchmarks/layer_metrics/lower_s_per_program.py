"""Seconds of Python tracing and lowering to StableHLO in one program's first
call: mean of the program's ``executor.lower`` spans (from the call until the
module is built) over the first calls the traced slice holds whole, prefetch
workers' among them."""

from benchmarks.harness.program_spans import part_seconds_per_program


def read(record):
    return part_seconds_per_program(record, "executor.lower")
