"""Share of the scans' chunks that a prompt starts inside of: 100 x
``ssd.boundary_chunks`` / ``ssd.chunks``, the program's counters
(``models/mamba2.py`` ``note_scan``: every traced scan of a layer adds its
chunks and those of them that hold a prompt's first token off the chunk's
edge, where the kernel drops the incoming state for the rows behind it and
masks the pairs across), differenced round the trace of the best finalist's
one-shot program alone (``builders/mixers_prefill.py`` ``Counted.check``
leaves ``{counter: gain}`` a schedule compared under
``cost["traced_counts"]``, naive first).  The packing of the step that was
timed; told, not steered: the traffic sets it (8 of 128 chunks at the
cell's twelve prompts).  Nothing on a program without the counters or a
builder without the table."""

from benchmarks.harness.mixers_costs import traced_counts


def read(record):
    got = traced_counts(record)
    if not got or not got.get("ssd.chunks"):
        return None
    return 100.0 * got["ssd.boundary_chunks"] / got["ssd.chunks"]
