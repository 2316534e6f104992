"""What each entry of the one-chip halo's z menus costs behind the one
staging order (ISSUE 48, step 1), by vertex, on the chip:

    chiprun -- python experiments/halo_z_entries_on_chip.py [--seed N]

``benchmarks/tests/op_scopes_on_chip.py``'s table for ``halo512.climb``
(its own ``main``, every option of it), over the start point of the climb
walked with other z entries than ``halo_alias_prefer``'s: a z face crosses
its staging buffer turned (``models/halo_pipeline.py`` ``staged_sizes``),
so ``.xla``, ``.pallas`` and ``.pallasb`` reach it through a ``swapaxes``
and ``.window`` through a reshape.  These are the neighbours a climb's
kernel-flip moves visit.  Each schedule runs once first and is held to the
reference (``halo_mismatched_cells`` 0), whatever pair of entries it mixes.

``--rehearse-cpu`` walks it on the CPU at the toy size (nothing is timed).
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# label -> (the z packs' entry, the z unpacks' entry)
Z_ENTRIES = {
    "start": (".window", ".window"),
    "z.xla+pallasb": (".xla", ".pallasb"),  # the recipe up to PR 47
    "z.window+pallasb": (".window", ".pallasb"),
    "z.xla+window": (".xla", ".window"),
    "z.window+xla": (".window", ".xla"),
}


def z_prefer(pack: str, unpack: str):
    from tenzing_tpu.bench.workloads import halo_alias_prefer

    def prefer(op_name, choices):
        kind, _, d = op_name.partition("_")
        want = {"pack": pack, "unpack": unpack}.get(kind)
        if want and d.endswith("z"):
            return next(c for c in choices if c.endswith(want))
        return halo_alias_prefer(op_name, choices)

    return prefer


def schedules(built):
    from tenzing_tpu.solve.local import drive, phase_policy

    h = built.hints
    for label, (pack, unpack) in Z_ENTRIES.items():
        order = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], z_prefer(pack, unpack)))[0]
        out = built.executor.run(order)
        print(f"-- {label}: " + ", ".join(
            f"{c['name']} {c['value']} (limit {c['limit']})"
            for c in built.check(out)))
        del out
        yield label, order


def main() -> int:
    from benchmarks.tests import op_scopes_on_chip

    op_scopes_on_chip.schedules = schedules
    if "--workload" not in sys.argv:
        sys.argv[1:1] = ["--workload", "halo512.climb"]
    return op_scopes_on_chip.main()


if __name__ == "__main__":
    sys.exit(main())
