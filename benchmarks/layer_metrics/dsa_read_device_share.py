"""Share of the device's busy time, in the traced slice of the window, spent
attending over the selected tokens: the operations named ``mla_decode`` (the
dense cell's kernel, here over a group's gathered tiles, one open page of
``index_topk`` keys a sequence).  Read as ``mla_kernel_device_share`` is,
and from the same name: that metric lists its cells, and an accepted entry's
list is not this cell's PR's to edit (ISSUE 40: nothing that stood is
edited), so the cell has a reader of its own (PERF.md section 7: fold the
two in a ``benchmark`` PR).  Nothing where the slice lists no such
kernel."""

from benchmarks.harness.dsa_shares import busy_share

KERNELS = ("mla_decode",)


def read(record):
    share = busy_share(record, KERNELS)
    return None if share is None else 100.0 * share
