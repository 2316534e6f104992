"""One run of one benchmark cell.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; its last key, and
the last lines of standard error, hold every number compared for ``correct``
beside its limit.  Everything else goes on earlier lines or under
``benchmarks/out/``.  ``--rehearse-cpu`` (private)
walks the same code at the configuration's toy shapes on ``JAX_PLATFORMS=cpu``
and never prints a result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up counts from here

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.harness import cell

    try:
        result = cell.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), T_PROCESS,
                               rehearse=args.rehearse_cpu)
    except cell.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    line = json.dumps(result)
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        print(f"rehearsal on the CPU, not a result: {line}")
        print("rehearsal done")
        return 0
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
