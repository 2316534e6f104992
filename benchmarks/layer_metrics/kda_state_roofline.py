"""Share of their roofline the KDA layers' vertices reach: the least seconds
an iteration's KDA layers can take (every sequence's state read once and
written once in float32, its convolution window in and out, its inputs and
its ``o``: ``harness/kda_costs.py`` ``kda_bytes``, over the chip's published
HBM bandwidth: bound named HBM) over their own device seconds an iteration.
Those are the best finalist's device time per iteration (the two programs
the epilogue ran at n and 4n repeats, differenced, as ``kda_step_roofline``)
times the share of the repeat-n loop's time that the KDA vertices' operations
hold in the traced slice of the window: ``kda_step`` and XLA's fusions over
every operation but what a dispatch does once (``harness/kda_shares.py``:
the carry's copies and the fence's reductions are not in the differenced
time either).  The slice holds the window's candidates, not the finalist
alone: a candidate that runs a group as the XLA chain spends *more* of its
time in the KDA vertices than the all-kernel finalist, so the share can only
come out too large and this reading too small; on an all-kernel slice it is
the kernel's own share of its roofline (PERF.md section 5 holds it against
the kernel timed alone).  The least bytes do not depend on the engine, so
the reading cannot pass 100 whatever implements a vertex.  Nothing where the
slice lists no ``kda_step`` or the cost counts no KDA bytes."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds
from benchmarks.harness.kda_shares import kda_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    got = kda_seconds(record)
    cost = record.get("cost") or {}
    if not device_iter_s or not got or not record["peaks"] or not cost.get(
            "kda_bytes"):
        return None
    kda_s, loop_s, _ = got
    least_s = cost["kda_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_iter_s * kda_s / loop_s)
