"""What the sparse-decode cell's device-trace readers share
(``layer_metrics/dsa_*``): the device's own seconds an iteration of the best
finalist, and the share of the traced slice's busy time that operations of
given names hold.  Kept beside ``harness/dsa_costs.py``; the readers of the
cells before (``mla_*``, ``attn_*``) spell the same out, each in its file."""

from __future__ import annotations


def finalist_iter_seconds(record):
    """The device's time per iteration of the best finalist: the durations of
    the two programs the epilogue ran at n and 4n repeats, differenced (what a
    dispatch does once is out of it).  ``None`` without such a trace."""
    t = record.get("trace")
    if not t or len(t.get("finalist_modules", [])) != 2:
        return None
    n, n4 = t["finalist_n"]
    (_, d_n), (_, d_n4) = t["finalist_modules"]
    iter_s = (d_n4 - d_n) / (n4 - n)
    return iter_s if iter_s > 0 else None


def busy_share(record, names):
    """Of the first device's busy seconds in the traced slice of the window,
    the share (0 to 1) of the operation kinds that start with one of
    ``names``, from the slice's ten longest kinds (``harness/trace.py``).
    ``None`` where the slice lists none of them."""
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    inside = [s for name, s in w["device_ops"] if name.startswith(names)]
    return sum(inside) / w["busy_s"] if inside else None
