"""Share of its roofline the best finalist's whole packed prefill step
reaches: the least seconds the step can take, the sum over its layers of the
larger of a layer's two bounds (``harness/mixers_costs.py``
``mixers_prefill_cost`` ``layers``: a Mamba-2 mixer's matrix-unit operations
over the chip's published bfloat16 peak against the bytes of its inputs,
its ``out`` and what it leaves for a decode step over the published HBM
bandwidth; the attention's useful operations under the packed causal mask
against its Q, K, V and O), over the device's own time per iteration, taken
as ``iter_hbm_roofline`` takes it: the durations of the two programs the
epilogue ran at n and 4n repeats, differenced.  Bounds named: HBM for a
Mamba-2 mixer (0.5 GB beside 0.056 TFLOP a layer: 0.61 ms against 0.28), MXU
for the attention (0.36 TFLOP beside 0.28 GB: 1.83 ms against 0.35).  The
least work is the same whatever engine, order or lane a schedule picked, and
no intermediate (the convolved ``xBC``, ``y``, masked pairs) is counted, so
the share cannot pass 100.  Nothing where the configuration's cost lists no
layers."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    layers = (record.get("cost") or {}).get("layers")
    if not device_iter_s or not record["peaks"] or not layers:
        return None
    peaks = record["peaks"]
    least_s = sum(max(x["flops"] / peaks["bf16_flops"],
                      x["hbm_bytes"] / peaks["hbm_bytes_per_s"])
                  for x in layers)
    return 100.0 * least_s / device_iter_s
