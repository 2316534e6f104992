"""Share of the (query, key) pairs the best finalist's attention computes
that the packed causal mask then throws away: 100 x (1 - useful /
computed), the program's counters ``attn.pairs_useful`` and
``attn.pairs_computed`` (``models/ring_attention.py`` ``note_tiles``,
counting packed prompts: a pair is useful where the key is of the row's own
prompt and not beyond it; a kernel computes the tiles that hold such a
key, whole), differenced round the trace of that finalist's one-shot
program alone (``builders/mixers_prefill.py`` ``Counted.check`` leaves
``{counter: gain}`` a schedule compared under ``cost["traced_counts"]``,
naive first).  ``attn_masked_work_share``'s reading on this cell: a finer
tile at a diagonal or at a prompt's start lowers it.  Nothing on a program
without the counters or a builder without the table."""

from benchmarks.harness.mixers_costs import traced_counts


def read(record):
    got = traced_counts(record)
    if not got or not got.get("attn.pairs_computed"):
        return None
    return 100.0 * (1.0 - got["attn.pairs_useful"]
                    / got["attn.pairs_computed"])
