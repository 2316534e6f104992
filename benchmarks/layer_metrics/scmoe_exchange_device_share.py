"""Share of the device's busy time, in the traced slice of the window, that
the first device spent in the expert blocks' exchange: XLA's all-to-all
(``all_to_all`` as JAX's lowering names the instruction, ``all-to-all`` the
opcode, with ``-start`` / ``-done`` where the compiler makes it
asynchronous) or, where a schedule picked the synthesized ring, its
collective-permutes.  How much of a step the dispatch and combine take on
the device's own timeline when nothing hides them; an exchange that runs
under a kernel still counts its own duration.  Read as
``alltoall_device_share`` is: from the slice's ten longest operation kinds
of the first device (``harness/trace.py``), over the busy seconds, the mean
of the chips'.  Nothing where the slice lists no such operation."""

from benchmarks.harness.dsa_shares import busy_share

TRANSFERS = ("all_to_all", "all-to-all", "collective-permute")


def read(record):
    share = busy_share(record, TRANSFERS)
    return None if share is None else 100.0 * share
