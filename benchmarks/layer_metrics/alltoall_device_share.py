"""Share of the device's busy time, in the traced slice of the window, that
the first device spent in XLA's all-to-all operations: how much of an
iteration the dispatch and combine exchanges take on the device's own
timeline.  The profiler names a device operation by its HLO instruction,
which JAX's lowering calls ``all_to_all`` (the opcode is ``all-to-all``,
with ``-start`` / ``-done`` where the compiler makes the exchange
asynchronous): both spellings are taken.  Read as ``exchange_device_share`` is:
from the slice's ten longest operation kinds of the first device
(``harness/trace.py``), over the busy seconds, the mean of the chips'.
Nothing where the slice lists no all-to-all."""

TRANSFERS = ("all_to_all", "all-to-all")


def read(record):
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    moved = [s for name, s in w["device_ops"] if name.startswith(TRANSFERS)]
    if not moved:
        return None
    return 100.0 * sum(moved) / w["busy_s"]
