"""Share of the exchange's capacity slots that hold a token in the decode
step's expert blocks: the program's counters ``moe.routed_slots`` over
``moe.capacity_slots`` (``slot_fill_share``'s, which lists its own cell).
A decode batch sends 2 real picks a (source chip, expert) on average into 16
slots: what is missing from 100 is padding the all-to-alls carry and the
experts compute for nothing, the price of static shapes at a mean load of
2.  Nothing on a program without the zero experts' counter (the cells
before have none)."""

from benchmarks.harness.program_spans import counter


def read(record):
    capacity = counter("moe.capacity_slots")
    if not capacity or not counter("moe.zero_picks"):
        return None
    return 100.0 * counter("moe.routed_slots") / capacity
