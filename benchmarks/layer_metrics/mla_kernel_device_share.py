"""Share of the device's busy time, in the traced slice of the window, spent
in the latent-attention kernels: the operations the program names
``mla_decode`` (a group's whole cache read, state in VMEM) and ``mla_fold``
(one link of a split-K chain, state through HBM).  What is missing is the
append, the absorb and up-project einsums, the chains' finalisers, the
ordering tokens, the fence and any copy of a pool.  Read as
``attn_kernel_device_share`` is: from the slice's ten longest operation
kinds of the first device (``harness/trace.py``), over its busy seconds.
Nothing where the slice lists no such kernel."""

KERNELS = ("mla_decode", "mla_fold")


def read(record):
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    inside = [s for name, s in w["device_ops"] if name.startswith(KERNELS)]
    if not inside:
        return None
    return 100.0 * sum(inside) / w["busy_s"]
