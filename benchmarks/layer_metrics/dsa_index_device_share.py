"""Share of the device's busy time, in the traced slice of the window, spent
in the lightning indexer's kernel: the operations the program names
``dsa_index`` (a group's scores over its pages of index keys).  Read as
``mla_kernel_device_share`` is: from the slice's ten longest operation kinds
of the first device (``harness/trace.py``), over its busy seconds.  Nothing
where the slice lists no such kernel."""

from benchmarks.harness.dsa_shares import busy_share

KERNELS = ("dsa_index",)


def read(record):
    share = busy_share(record, KERNELS)
    return None if share is None else 100.0 * share
