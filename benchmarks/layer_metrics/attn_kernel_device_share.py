"""Share of the device's busy time, in the traced slice of the window, spent
in the attention kernels: the operations the program names ``attn_fold``
(one K/V block folded into a state in HBM) and ``attn_fused`` (a query
block's whole visible range, state in VMEM).  The rest is what XLA does
around them: slices of Q, K and V, the ordering tokens' adds, the
finalisers, the fence, and XLA's own folds where a schedule chose them.
Read as ``exchange_device_share`` is: from the slice's ten longest
operation kinds of the first device (``harness/trace.py``), over its busy
seconds.  Nothing where the slice lists no such kernel."""

KERNELS = ("attn_fold", "attn_fused")


def read(record):
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    inside = [s for name, s in w["device_ops"] if name.startswith(KERNELS)]
    if not inside:
        return None
    return 100.0 * sum(inside) / w["busy_s"]
