"""Set ``page_tokens``, ``groups`` and ``kda_groups`` of
``configs/kimi-linear-kda-decode.json`` from step 1's sweep
(``chiprun_out/kda_step1.*.json`` of ``tests/kda_runs_on_chip.sh sweep``),
and take the issue's pre-declared cut to 64 sequences if the peak inside
``correct`` at 128 leaves under 2 GB of the chip free.

    python benchmarks/tests/kda_choose.py <checkout> [<checkout> ...]

The page is the one whose start point read the fewest milliseconds an
iteration at 4 groups; the groups (the KDA layers' and the latent layer's
alike: the sweep moves them together) the count that read the fewest at the
configuration's page.  Writes what it chose, with every reading it chose
from, to ``chiprun_out/kda42/chosen.json`` and into ``shapes`` of the
configuration's file in each checkout named.
"""

import glob
import json
import os
import sys

NAME = "benchmarks/configs/kimi-linear-kda-decode.json"


def main(checkouts) -> int:
    root = checkouts[0]
    with open(os.path.join(root, NAME)) as f:
        shapes = json.load(f)["shapes"]
    page0, groups0 = shapes["page_tokens"], shapes["groups"]
    seen = {}
    for path in sorted(glob.glob(os.path.join(root, "chiprun_out",
                                              "kda_step1.*.json"))):
        with open(path) as f:
            r = json.load(f)
        start = next(iter(r["seeds"].values())).get("start", {})
        if "iter_ms" not in start:
            continue
        key = (r["page_tokens"], r["groups"], r["kda_groups"],
               r["sequences"])
        seen[key] = {"iter_ms": start["iter_ms"], "peak_gb": start["peak_gb"],
                     "free_gb": (r["bytes_limit"] - r["peak_bytes_in_use"])
                     / 1e9}
    n = len(shapes["lens"])
    pages = {k[0]: v["iter_ms"] for k, v in seen.items()
             if k[1:] == (groups0, shapes["kda_groups"], n)}
    groups = {k[1]: v["iter_ms"] for k, v in seen.items()
              if k[0] == page0 and k[1] == k[2] and k[3] == n}
    if page0 not in pages or groups0 not in groups:
        print(f"no reading at the configuration's own shapes: {seen}")
        return 1
    page = min(pages, key=pages.get)
    g = min(groups, key=groups.get)
    free = seen[(page0, groups0, shapes["kda_groups"], n)]["free_gb"]
    cut = free < 2.0
    chosen = {"page_tokens": page, "groups": g, "kda_groups": g,
              "fold_pages": max(1, shapes["fold_pages"] * page0 // page),
              "cut_to_64": cut, "free_gb_at_128": free,
              "start_iter_ms_by_page": pages,
              "start_iter_ms_by_groups": groups,
              "readings": {"p%d.g%d.k%d.s%d" % k: v for k, v in seen.items()}}
    out = os.path.join(root, "chiprun_out", "kda42")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chosen.json"), "w") as f:
        json.dump(chosen, f, indent=1)
    print("chosen: " + json.dumps({k: chosen[k] for k in (
        "page_tokens", "groups", "kda_groups", "fold_pages", "cut_to_64",
        "free_gb_at_128", "start_iter_ms_by_page",
        "start_iter_ms_by_groups")}))
    for checkout in checkouts:
        path = os.path.join(checkout, NAME)
        if not os.path.isfile(path):
            continue
        with open(path) as f:
            config = json.load(f)
        s = config["shapes"]
        s.update({k: chosen[k] for k in ("page_tokens", "groups",
                                         "kda_groups", "fold_pages")})
        if cut:
            s["lens"] = sorted(s["lens"])[::2]
        with open(path, "w") as f:
            json.dump(config, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["."]))
