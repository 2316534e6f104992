"""Share of the roofline the best finalist's whole decode step reaches on
one chip: the least seconds the step can take (``harness/scmoe_costs.py``
``scmoe_step_cost``: every weight the chip holds, its caches' visible rows
and the routed rows over the chip's published HBM bandwidth; or the step's
useful operations over its published bfloat16 peak, if that is larger) over
the first device's own time per iteration, taken as ``mla_step_roofline``
takes it: the durations of the two programs the epilogue ran at n and 4n
repeats, differenced.  Bound named: HBM (at LongCat-Flash-Lite's widths a
chip's step is 4.4 GB, 5.4 ms; its operations 0.7 ms).  The least work is
the same whatever order, lanes or engines a schedule picked, so the share
cannot pass 100.  Nothing where the configuration's cost counts no such
step."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    cost = record.get("cost") or {}
    if not device_iter_s or not record["peaks"] or not cost.get("scmoe_bytes"):
        return None
    peaks = record["peaks"]
    least_s = max(cost["scmoe_bytes"] / peaks["hbm_bytes_per_s"],
                  cost["flops"] / peaks["bf16_flops"])
    return 100.0 * least_s / device_iter_s
