"""The part of a steady-state dispatch spent before the program is launched:
median of the program's ``executor.enqueue`` spans (the call of the compiled
program until it returns: arguments, staging buffers, launch) inside the
traced slice's ``bench.dispatch`` spans.  A dispatch that is a program's
first call has no such span.  The rest of a dispatch is
``executor.fence_wait``; ``dispatch_fixed_ms`` times both from outside."""

import statistics

from benchmarks.harness.program_spans import (
    children,
    named,
    seconds,
    window_spans,
)


def read(record):
    spans = window_spans(record)
    enq = children(spans, named(spans, "bench.dispatch"), "executor.enqueue")
    if not enq:
        return None
    return 1e3 * statistics.median(seconds(s) for s in enq)
