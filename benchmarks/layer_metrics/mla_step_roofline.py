"""Share of the roofline the best finalist's decode step reaches: the larger
of (cache and operand bytes over the chip's published HBM bandwidth) and
(useful operations over its published bfloat16 peak), both from lengths and
widths alone (``harness/mla_costs.py``: keys past a sequence's length are
not counted, so it cannot pass 100), over the device's own time per
iteration, taken as ``iter_mxu_roofline`` takes it: the durations of the two
programs the epilogue ran at n and 4n repeats, differenced.  Bound named:
whichever of the two is larger (at DeepSeek-V3's widths a key is 241.8 FLOP
a byte against the chip's 240.5; with the weights and inputs read once the
cell's step is 3.35 ms by HBM and 3.20 by the MXU: HBM, by 4%).  Nothing
where the configuration's cost counts no keys."""


def read(record):
    t = record.get("trace")
    if not t or len(t.get("finalist_modules", [])) != 2 or not record["peaks"]:
        return None
    cost = record.get("cost") or {}
    if not cost.get("keys"):
        return None
    n, n4 = t["finalist_n"]
    (_, d_n), (_, d_n4) = t["finalist_modules"]
    device_iter_s = (d_n4 - d_n) / (n4 - n)
    if device_iter_s <= 0:
        return None
    peaks = record["peaks"]
    least_s = max(cost["hbm_bytes"] / peaks["hbm_bytes_per_s"],
                  cost["flops"] / peaks["bf16_flops"])
    return 100.0 * least_s / device_iter_s
