"""GB of temporaries of the largest repeat-n program a run of the packed
prefill step compiled: ``program_temp_peak_gb``'s reading (the program's
gauge ``executor.program_temp_bytes_max``) in this cell, by that reader.  The
step's ``xBC``, its convolved twin, ``z``, ``y``, ``out``, ``Q`` and ``O``
are buffers; what reads here is what XLA keeps beside them (a copy of every
written buffer in the loop's carry; a scan's chain in a candidate that
picked it: the ``(heads, chunks, 128, 128)`` decays; a copy a layout
forced).  Nothing on a program without the gauge."""

from benchmarks.layer_metrics.program_temp_peak_gb import read  # noqa: F401
