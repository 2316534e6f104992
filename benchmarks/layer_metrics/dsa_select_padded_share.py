"""Share of the candidates the best finalist's selections are handed that
are padding: 100 x (1 - visible / handed), the program's counters
``dsa.select_candidates`` (a group's visible keys) and
``dsa.select_candidates_padded`` (its rectangle of scores: every sequence
over the most pages one of the group has, and at least ``index_topk``
columns) (``models/sparse_attention.py`` ``DsaSelect``: every traced
selection adds to them at trace time), differenced round the trace of that
finalist's one-shot program alone (``builders/dsa_decode.py`` leaves
``[visible, handed]`` a schedule compared under
``cost["traced_candidates"]``, naive first).  Groups of more equal lengths
move it.  Nothing on a program without the counters or a builder without
the table."""


def read(record):
    traced = (record.get("cost") or {}).get("traced_candidates") or []
    label = record["epilogue"]["best"].get("label", "")
    if not label.startswith("finalist"):
        return None
    at = 1 + int(label[len("finalist"):])
    if at >= len(traced) or not traced[at][1]:
        return None
    visible, handed = traced[at]
    return 100.0 * (1.0 - visible / handed)
