"""Share of its roofline the ``ssd_scan`` kernel reaches: the least seconds
an iteration's Mamba-2 scans can take (``harness/mixers_costs.py``
``ssd_scan_cost``: the larger of their matrix-unit operations over the
chip's published bfloat16 peak and of the bytes of ``x``, ``dt``, ``B``,
``C``, ``y`` and the final states over its published HBM bandwidth) over
the kernel's own device seconds for them: ``ssd_scan``'s, by name, in the
one dispatch of the climb's start point that the builder profiles at set-up
(``mixers_costs.ssd_seconds``; every scan of the start point is the
kernel).  The kernel alone steers it: no candidate of the window, no choice
of the search and no copy of the repeat-n loop's carry is in the reading.
Bound named: HBM (1.1 GB beside 0.17 TFLOP at the cell's size: 1.34 ms
against 0.85).  Nothing where the builder left no such profile, it lists no
``ssd_scan`` or the cost counts no scan."""

from benchmarks.harness.mixers_costs import ssd_seconds


def read(record):
    got = ssd_seconds(record)
    cost = record.get("cost") or {}
    if not got or not record["peaks"] or not cost.get("ssd_bytes"):
        return None
    peaks = record["peaks"]
    least_s = max(cost["ssd_flops"] / peaks["bf16_flops"],
                  cost["ssd_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / got[0]
