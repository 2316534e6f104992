"""Share of the exchange's capacity slots that hold a token: the program's
counters ``moe.routed_slots`` over ``moe.capacity_slots``, both set once by
the set-up negotiation (``models/moe.py`` ``note_routing``).  What is
missing from 100 is padding: rows the all-to-alls carry and the experts
compute for nothing, the price of static shapes.  Nothing on a program
without the counters."""

from benchmarks.harness.program_spans import counter


def read(record):
    capacity = counter("moe.capacity_slots")
    if not capacity:
        return None
    return 100.0 * counter("moe.routed_slots") / capacity
