"""Share of its roofline the index kernel reaches: the least seconds an
iteration's index can take (every visible key's index row over the chip's
published HBM bandwidth, ``harness/dsa_costs.py`` ``index_bytes``: bound
named HBM) over the kernel's own device seconds an iteration.  Those are
the best finalist's device time per iteration (the two programs the
epilogue ran at n and 4n repeats, differenced, as ``dsa_step_roofline``)
times the share of the device's busy time that the operations named
``dsa_index`` hold in the traced slice of the window
(``dsa_index_device_share``).  The slice holds the window's candidates, not
the finalist alone; every candidate of the cell runs the same index kernels
over the same pages (no menu of the cell's graph touches the index), so
the share is the finalist's as far as the candidates' other parts take the
same time: they differ by a selection's reach and by order and lanes,
tenths of a millisecond in seven (PERF.md section 5 holds it against the
program's own reading by vertex).  Nothing where the slice lists no such
kernel or the cost counts no index bytes."""

from benchmarks.harness.dsa_shares import busy_share, finalist_iter_seconds

KERNEL = ("dsa_index",)


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    share = busy_share(record, KERNEL)
    cost = record.get("cost") or {}
    if not device_iter_s or not share or not record["peaks"] or not cost.get(
            "index_bytes"):
        return None
    least_s = cost["index_bytes"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (device_iter_s * share)
