"""Share of the MXU roofline the best finalist's iteration of the attention
layers reaches: the useful operations of one iteration (from shapes,
``harness/attn_costs.py``: pairs under the mask only, whatever a kernel
computes and then masks is not counted) over the chip's published bfloat16
peak (``harness/peaks.py``), over the device's own time per iteration, taken
as ``iter_mxu_roofline`` takes it: the durations of the two programs the
epilogue ran at n and 4n repeats, differenced.  Bound named: MXU.  Nothing
where the configuration's cost counts no visible pairs."""


def read(record):
    t = record.get("trace")
    if not t or len(t.get("finalist_modules", [])) != 2 or not record["peaks"]:
        return None
    flops = (record.get("cost") or {}).get("flops")
    if not flops:
        return None
    n, n4 = t["finalist_n"]
    (_, d_n), (_, d_n4) = t["finalist_modules"]
    device_iter_s = (d_n4 - d_n) / (n4 - n)
    if device_iter_s <= 0:
        return None
    return 100.0 * flops / record["peaks"]["bf16_flops"] / device_iter_s
