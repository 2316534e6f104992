"""Share of the best finalist's device time an iteration spent in the
``ssd_scan`` kernel (a Mamba-2 layer's whole chunked scan: decays, masks,
the three products a chunk and the state's walk).  The kernel's seconds are
its own, by name, from the dispatch of the climb's start point that the
builder profiles at set-up, by the calls the finalist's traced program makes
(``harness/mixers_costs.py`` ``ssd_seconds``); the iteration's are the
durations of the two programs the epilogue ran at n and 4n repeats,
differenced, as ``iter_hbm_roofline`` takes them.  The rest is the
convolutions and gated norms, the attention kernels and the executor's
copies of the loop's carry.  Told, not steered (ROADMAP.md W10 ii): the
share falls when the rest shrinks less than the kernel does, so a faster
kernel can lower it.  Nothing where the builder left no such profile, it
lists no ``ssd_scan``, or the best schedule is no finalist."""

from benchmarks.harness.dsa_shares import finalist_iter_seconds
from benchmarks.harness.mixers_costs import ssd_seconds


def read(record):
    device_iter_s = finalist_iter_seconds(record)
    got = ssd_seconds(record)
    if not device_iter_s or not got or got[1] is None:
        return None
    return 100.0 * got[1] / device_iter_s
