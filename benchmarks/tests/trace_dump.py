"""Look at a profiler trace by hand: planes, lines, and the first events of
each line.  ``python benchmarks/tests/trace_dump.py <trace dir> [--json OUT
--max-events N]`` also writes the neutral form (``harness/trace.py``) cut to
N events a line, which is how ``tests/data/*.json`` were recorded."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import trace as trace_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--json")
    ap.add_argument("--max-events", type=int, default=300)
    ap.add_argument("--from-ns", type=int, default=0)
    args = ap.parse_args()
    tr = trace_mod.load_xplane(args.trace_dir)
    for p in tr["planes"]:
        print("PLANE", p["name"])
        for ln in p["lines"]:
            evs = ln["events"]
            names = {}
            for n, a, b in evs:
                names[n] = names.get(n, 0) + (b - a)
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            print(f"  LINE {ln['name']!r}: {len(evs)} events; top by total ns: "
                  f"{[(n[:60], v) for n, v in top]}")
    if args.json:
        for p in tr["planes"]:
            for ln in p["lines"]:
                ln["events"] = [e for e in ln["events"]
                                if e[1] >= args.from_ns][:args.max_events]
            p["lines"] = [ln for ln in p["lines"] if ln["events"]]
        with open(args.json, "w") as f:
            json.dump(tr, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
