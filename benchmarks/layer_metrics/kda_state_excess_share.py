"""Bytes of recurrent state the best finalist moves beyond the least: 100 x
(moved / least - 1), the program's counters ``kda.state_bytes`` (what the
traced KDA vertices pass through HBM of ``S``, ``Snew`` and the convolution
windows, from shapes and engine: two passes over the state for a fused
``kda_step``, four for the XLA chain) and ``kda.state_min_bytes`` (one read
and one write) (``models/delta_attention.py`` ``note_state``: every traced
vertex adds to them at trace time), differenced round the trace of that
finalist's one-shot program alone (``builders/kda_decode.py`` leaves
``[moved, least, ...]`` a schedule compared under ``cost["traced_kda"]``,
naive first).  0 where every (layer, group) is on the kernel, about 100 on the
chain.  Nothing on a program without the counters or a builder without the
table."""


def read(record):
    traced = (record.get("cost") or {}).get("traced_kda") or []
    label = record["epilogue"]["best"].get("label", "")
    if not label.startswith("finalist"):
        return None
    at = 1 + int(label[len("finalist"):])
    if at >= len(traced) or not traced[at][1]:
        return None
    moved, least = traced[at][:2]
    return 100.0 * (moved / least - 1.0)
