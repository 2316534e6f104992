"""Share of the device's busy time, in the traced slice of the window, that
the first device spent in transfer operations: XLA's collective-permute
(start and done), and the remote-DMA kernels (``rdma_shift_post``,
``rdma_shift_wait``, which also carry the loopback copy of a mesh axis of
size one).  Says whether the exchange or the packs and unpacks set the
iteration.  Read from the slice's ten longest operation kinds of the first
device (``harness/trace.py``), over the busy seconds, which are the mean of
the chips': all run one program.  Nothing where the slice lists no transfer."""

TRANSFERS = ("collective-permute", "rdma_")


def read(record):
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("busy_s"):
        return None
    moved = [s for name, s in w["device_ops"] if name.startswith(TRANSFERS)]
    if not moved:
        return None
    return 100.0 * sum(moved) / w["busy_s"]
