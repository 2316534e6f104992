"""Fenced dispatches a measurement call makes (warm call, every repeat of
every iteration, every growth of the repeat count): the traced slice's
``bench.dispatch`` spans over its ``bench.benchmark`` and ``bench.batch``
spans (the slice begins and ends between candidates, so it holds whole
calls).  Each costs ``dispatch_fixed_ms`` whatever it measures."""

from benchmarks.harness.program_spans import (
    MEASUREMENT_CALLS,
    named,
    window_spans,
)


def read(record):
    spans = window_spans(record)
    calls = named(spans, *MEASUREMENT_CALLS)
    if not calls:
        return None
    return len(named(spans, "bench.dispatch")) / len(calls)
