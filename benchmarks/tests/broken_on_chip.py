"""A run at a cell's own size on the chip with the timed path broken
underneath: the repeat-n program never iterates (a step that returns its
state unchanged), while every one-shot program stays right.  ``correct`` has
to come out false, by ``timed_fence_gap`` alone.

    python benchmarks/tests/broken_on_chip.py --workload <cell> --seed 5 --seconds 10

Prints the run's lines and, last, ``broken run: correct=<bool>``; exits 0
when ``correct`` is false.  Not part of a benchmark run.
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from benchmarks.harness import cell as cell_mod
    from tenzing_tpu.runtime.executor import TraceExecutor

    sound = TraceExecutor._stepped_fn

    def never_iterates(self, ops):
        stepped = sound(self, ops)
        return lambda bufs, n: stepped(bufs, n * 0)

    TraceExecutor._stepped_fn = never_iterates
    r = cell_mod.run_cell(args.workload, args.seed, args.seconds, False,
                          time.perf_counter(), rehearse=args.rehearse_cpu)
    print(f"broken run: correct={r['correct']}", flush=True)
    return 0 if r["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
