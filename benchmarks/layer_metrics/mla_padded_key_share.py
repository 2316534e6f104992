"""Share of the keys the best finalist's cache reads compute that lie past a
sequence's length: 100 x (1 - useful / computed), the program's counters
``mla.keys_useful`` and ``mla.keys_computed``
(``models/latent_attention.py`` ``note_pages``: every traced kernel and XLA
fold adds to them at trace time; a kernel computes the tiles that hold a
visible key, whole), differenced round the trace of that finalist's
one-shot program alone (``builders/mla_decode.py`` leaves ``[useful,
computed]`` a schedule compared under ``cost["traced_keys"]``, naive first).
The padding of the program that was timed: a smaller page moves it.  Nothing
on a program without the counters or a builder without the table."""


def read(record):
    traced = (record.get("cost") or {}).get("traced_keys") or []
    label = record["epilogue"]["best"].get("label", "")
    if not label.startswith("finalist"):
        return None
    at = 1 + int(label[len("finalist"):])
    if at >= len(traced) or not traced[at][1]:
        return None
    useful, computed = traced[at]
    return 100.0 * (1.0 - useful / computed)
