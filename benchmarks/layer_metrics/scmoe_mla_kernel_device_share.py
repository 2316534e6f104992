"""Share of the device's busy time, in the traced slice of the window, spent
in the latent-attention kernels (``mla_decode``, and ``mla_fold`` where a
candidate picked a chain), inside a whole decode step: the kernel the cell
shares with ``dsv3-mla-decode.climb`` and ``kimi-linear-kda-decode.climb``,
seen beside the projections, the dense FFNs and the expert blocks.  Read as
``mla_kernel_device_share`` is.  Nothing where the slice lists no such
kernel."""

from benchmarks.harness.dsa_shares import busy_share

KERNELS = ("mla_decode", "mla_fold")


def read(record):
    share = busy_share(record, KERNELS)
    return None if share is None else 100.0 * share
