"""Share of the (query, key) pairs the best finalist's folds compute that
the mask then throws away: 100 x (1 - useful / computed), the program's
counters ``attn.pairs_useful`` and ``attn.pairs_computed``
(``models/ring_attention.py`` ``note_tiles``: every traced fold and fused
vertex adds to them at trace time; an XLA fold computes its whole block, a
kernel the tiles that hold a visible key), differenced round the trace of
that finalist's one-shot program alone (``builders/attn_period.py``
``counted_check`` leaves ``[useful, computed]`` a schedule compared under
``cost["traced_pairs"]``, naive first).  The padding of the program that was
timed: a finer tile at the mask's edge moves it.  Nothing on a program
without the counters or a builder without the table."""


def read(record):
    traced = (record.get("cost") or {}).get("traced_pairs") or []
    label = record["epilogue"]["best"].get("label", "")
    if not label.startswith("finalist"):
        return None
    at = 1 + int(label[len("finalist"):])
    if at >= len(traced) or not traced[at][1]:
        return None
    useful, computed = traced[at]
    return 100.0 * (1.0 - useful / computed)
