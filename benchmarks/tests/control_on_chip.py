"""Readings for the limits of ``correct``, at a cell's own size on the chip.

    python benchmarks/tests/control_on_chip.py --workload <cell> --seeds 1,2,3 [--sound-seeds ...]

For each seed of ``--seeds``: the plain reference computed one precision
down (its ``control``) is put in the program's place and compared as a run
compares (the control has to come out as not correct).  For each seed of
``--sound-seeds``: the program's naive schedule is built at the cell's size,
run once, and compared (a sound run's reading).  Prints one line per
reading and, last, the largest sound reading and the smallest control's for
each number compared.  One process; not part of a benchmark run.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--sound-seeds", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import cell as cell_mod

    cell = cell_mod.load_cell(args.workload)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    readings = {}

    def note(kind, seed, compared):
        for c in compared:
            bad = c["value"] > c["limit"]
            print(f"{kind} seed {seed}: {c['name']} = {c['value']!r} "
                  f"(limit {c['limit']!r}) -> "
                  f"{'not correct' if bad else 'correct'}", flush=True)
            readings.setdefault((c["name"], kind), []).append(c["value"])

    for seed in [int(s) for s in args.sound_seeds.split(",") if s]:
        built = builder.build(config, seed, devices, ref)
        out = built.executor.run(built.naive)
        note("sound", seed, built.check(out))
        del out, built
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        out = ref.control(config, seed)
        note("control", seed, ref.check(config, seed, out))
        del out
    summary = {}
    for (name, kind), vals in readings.items():
        summary.setdefault(name, {})[
            "largest_sound" if kind == "sound" else "smallest_control"] = (
                max(vals) if kind == "sound" else min(vals))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
