"""Share of the router's picks that went to a zero-compute (identity)
expert: the program's counters ``moe.zero_picks`` over ``moe.zero_picks +
moe.routed_slots``, both set once by the set-up negotiation of every expert
block (``models/moe.py`` ``note_routing``).  Such a pick holds no slot,
crosses no chip and costs one multiply-add of the token itself; a balanced
router over 256 + 128 outputs sends a third of the picks there.  Nothing on
a program without the counter."""

from benchmarks.harness.program_spans import counter


def read(record):
    zero = counter("moe.zero_picks")
    if not zero:
        return None
    return 100.0 * zero / (zero + counter("moe.routed_slots"))
