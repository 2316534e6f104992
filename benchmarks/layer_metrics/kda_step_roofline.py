"""Share of the roofline the best finalist's hybrid decode step reaches: the
least seconds the whole step can take (``harness/kda_costs.py``
``hybrid_decode_cost``: the KDA layers' state read once and written once,
their windows, inputs and outputs, and the latent layer's cache and
operands by ``harness/mla_costs.py``'s own count, over the chip's published
HBM bandwidth; or the latent layer's useful operations over its published
bfloat16 peak, if that is larger) over the device's own time per
iteration, taken as ``mla_step_roofline`` takes it: the durations of the
two programs the epilogue ran at n and 4n repeats, differenced.  The KDA
layers' operations are float32 sums on the vector unit and are held
against no peak.  Bound named: HBM (at Kimi-Linear's widths the cell's step
is 3.5 ms by bytes; its latent layer's 32 heads are 60 FLOP a byte, a
quarter of the chip's ridge).  The least work is the same whatever engine a
schedule picked, so the share cannot pass 100.  Nothing where the
configuration's cost counts no KDA bytes."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    cost = record.get("cost") or {}
    if not device_iter_s or not record["peaks"] or not cost.get("kda_bytes"):
        return None
    peaks = record["peaks"]
    least_s = max(cost["hbm_bytes"] / peaks["hbm_bytes_per_s"],
                  cost["mla_flops"] / peaks["bf16_flops"])
    return 100.0 * least_s / device_iter_s
