"""Share of the device's busy time, in the traced slice of the window, spent
in the KDA layers' vertices: the operations the program names ``kda_step``
(a (layer, group)'s convolution step, gates, state update and output norm in
one kernel) and, for a group a candidate runs as the XLA chain, XLA's
fusions (``harness/kda_shares.py``: in this cell's programs they are the
chain's).  The rest is the latent layer's ``mla_decode``, its appends, and
what a dispatch does once.  Read as ``dsa_index_device_share`` is: from the
slice's ten longest operation kinds of the first device
(``harness/trace.py``), over its busy seconds.  Nothing where the slice
lists no ``kda_step``."""

from benchmarks.harness.kda_shares import kda_seconds


def read(record):
    got = kda_seconds(record)
    return None if got is None else 100.0 * got[0] / got[2]
