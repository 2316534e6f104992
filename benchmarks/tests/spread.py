"""Medians and spreads of the two sets ``sets_on_chip.sh`` wrote, as the
contract takes them (interquartile distance by ``statistics.quantiles(n=4)``
over the median), and what the driver's two tests would read.

    python benchmarks/tests/spread.py chiprun_out/<cell>.sets.jsonl
"""

import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return rest


def main(path):
    runs = [json.loads(ln) for ln in open(path) if ln.strip()]
    bad = [r for r in runs if r["rc"] != 0 or not r["result"]["correct"]]
    print(f"{len(runs)} runs, {len(bad)} failed or not correct; attempted "
          f"{[r['result']['attempted'] for r in runs]}, failed "
          f"{sum(r['result']['failed'] for r in runs)}")
    names = runs[0]["result"]["metrics"]
    for name in names:
        sets = {s: [r["result"]["metrics"][name]["value"] for r in runs
                    if r["set"] == s] for s in (1, 2)}
        both = sets[1] + sets[2]
        tight = statistics.mean(spread(without_farthest(v))
                                for v in sets.values())
        print(f"{name}: medians {statistics.median(sets[1]):.5g}, "
              f"{statistics.median(sets[2]):.5g} (second/first "
              f"{statistics.median(sets[2]) / statistics.median(sets[1]):.4f})"
              f"; spreads {100 * spread(sets[1]):.2f}%, "
              f"{100 * spread(sets[2]):.2f}%, all twelve "
              f"{100 * spread(both):.2f}%; without each set's farthest, mean "
              f"{100 * tight:.2f}% (a bound is too tight under "
              f"{200 * tight:.2f}%, too loose over "
              f"{800 * max(spread(both), spread(sets[1]), spread(sets[2])):.1f}%)"
              f"; range {min(both):.5g}-{max(both):.5g}")


if __name__ == "__main__":
    main(sys.argv[1])
