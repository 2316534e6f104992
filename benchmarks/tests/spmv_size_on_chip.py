"""How far a configuration's scale has to be cut: first-call seconds by size.

    python benchmarks/tests/spmv_size_on_chip.py --workload <cell> --key m --sizes 16384,32768,...

For each size the cell's configuration is built with ``shapes[key]`` set to
it (``band_width`` follows as a quarter of it, the source's ratio), and the
naive schedule's timed program is called for the first time with the
persistent compile cache off, as a window's candidates are: trace, compile,
load and first run, the cost a search pays for every candidate.  Then the
two-point clock reads its iteration.  With ``--window S`` a whole run of
the cell at that size follows (``S`` seconds of window), for the number of
candidates a window holds.  Prints one JSON line per size.  One process; not part of a benchmark run (PERF.md section 4: the rule by which
``m`` was chosen).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--key", default="m")
    ap.add_argument("--sizes", required=True)
    ap.add_argument("--seed", type=int, default=2147483801)
    ap.add_argument("--window", type=float, default=0.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod

    cell = cell_mod.load_cell(args.workload)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    ref = cell_mod.load_module("references", cell.config["reference"])
    builder = cell_mod.load_module("builders", cell.config["builder"])
    cell_mod.persistent_cache(False)
    for size in [int(s) for s in args.sizes.split(",") if s]:
        shapes = {**cell.config["shapes"], args.key: size,
                  "band_width": size // 4}
        config = {**cell.config, "shapes": shapes}
        t0 = time.perf_counter()
        built = builder.build(config, args.seed, devices, ref)
        t1 = time.perf_counter()
        ex = built.executor
        run_n = ex.prepare_n(built.naive)
        run_n(1)
        t2 = time.perf_counter()
        c = clock_mod.two_point(run_n, clock=time.perf_counter)
        print(json.dumps({
            args.key: size, "build_s": t1 - t0, "first_call_s": t2 - t1,
            "executor_compile_secs": ex.compile_secs,
            "iter_ms": c["iter_s"] * 1e3, "fixed_ms": c["fixed_s"] * 1e3,
            "memory_peak_bytes": cell_mod.memory_peak(devices)}),
            flush=True)
        del built, ex, run_n
        if args.window:
            sized = cell_mod.load_cell(args.workload)
            sized.config = config
            r = cell_mod.run_cell(args.workload, args.seed, args.window,
                                  False, time.perf_counter(),
                                  rehearse=args.rehearse_cpu,
                                  devices=devices, cell=sized)
            print(json.dumps({args.key: size, "window_s": args.window,
                              "result": r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
