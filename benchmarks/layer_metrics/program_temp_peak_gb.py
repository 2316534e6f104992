"""GB of temporaries of the largest repeat-n program the run compiled: the
program's gauge ``executor.program_temp_bytes_max`` (``runtime/executor.py``
``_first_call``: ``memory_analysis().temp_size_in_bytes`` of every program
whose first call this process made, one device's, set-up's naive, the
window's candidates and the epilogue's finalists alike).  The runtime
reserves that memory beside a program's buffers, so ``memory_peak_bytes``
never held it, and a temporary of the loop is written and read through HBM
every iteration.  Nothing on a program without the gauge (an unset gauge
reads 0, which no repeat-n program's temporaries are)."""

GAUGE = "executor.program_temp_bytes_max"


def read(record):
    from tenzing_tpu.obs.metrics import get_metrics

    temp_bytes = get_metrics().gauge(GAUGE).value
    return temp_bytes / 1e9 if temp_bytes else None
