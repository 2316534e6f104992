"""Share of the device's busy time, in the traced slice of the window, spent
in the attention kernels (``attn_fused``, and ``attn_fold`` where a
candidate picked a chain) inside the packed prefill step: the kernels the
cell shares with ``trinity-attn32k.climb``, here with a row's segment start
prefetched beside its position and a group of 16 query heads a K/V head,
seen beside the scans.  Told, not steered (ROADMAP.md W10 ii): the share
falls when the rest shrinks less.  ``attn_kernel_device_share``'s reading in
this cell, by that reader.  Nothing where the slice lists no such kernel."""

from benchmarks.layer_metrics.attn_kernel_device_share import read  # noqa: F401
