"""Share of the roofline the best finalist's sparse decode step reaches: the
larger of (index-key, selected-latent and operand bytes over the chip's
published HBM bandwidth) and (useful operations over its published bfloat16
peak), both from lengths and widths alone (``harness/dsa_costs.py``: only
visible keys are indexed and only selected ones attended, a stored row's
padding, the scores' trip through HBM and the gathered tile are not counted,
so it cannot pass 100), over the device's own time per iteration, taken as
``mla_step_roofline`` takes it: the durations of the two programs the
epilogue ran at n and 4n repeats, differenced.  Bound named: HBM (at
DeepSeek-V3.2's widths the cell's step is 1.06 ms by bytes and 0.38 by the
MXU).  Nothing where the configuration's cost counts no indexed keys."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    cost = record.get("cost") or {}
    if not device_iter_s or not record["peaks"] or not cost.get(
            "keys_indexed"):
        return None
    peaks = record["peaks"]
    least_s = max(cost["hbm_bytes"] / peaks["hbm_bytes_per_s"],
                  cost["flops"] / peaks["bf16_flops"])
    return 100.0 * least_s / device_iter_s
