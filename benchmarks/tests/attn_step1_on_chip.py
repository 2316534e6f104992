"""Step 1 of ISSUE 33, the go/no-go of the attention cell on the chip.

    python benchmarks/tests/attn_step1_on_chip.py --workload trinity-attn32k.climb --seeds a,b,c [--prompt 16384] [--control] [--quick]

For each seed the configuration is built as a run builds it, and for the
builder's naive (one lane, every query block a chain of ``attn_fold``
folds; before the review of PR 33 the chain of XLA folds, which is what the
readings at 32 768 in PERF.md are of) and the climb's start point (every query block on
the fused kernel, driven as ``hill_climb`` drives it):

* the first call of the repeat-n program, in seconds (what a candidate of
  the window costs before it is measured);
* the iteration time: by the benchmark's two-point clock, or with
  ``--quick`` from one call each at 1 and 3 repeats (the clock runs some 66
  iterations a schedule: at a size whose naive takes most of a second that
  is most of a minute, and that is the reading ``--quick`` is there to take);
* ``timed_fence_gap`` as ``harness/cell.py::compare`` takes it (has to be
  0.0: whether the chip's compiler lowers an exp between two products alike
  inside and outside the repeat loop);
* the one-shot program against the plain reference (``check``) and, with
  ``--control``, the reference's float8 control, each number beside its
  limit;
* the device's peak bytes after each step.

``--prompt`` overrides ``shapes.prompt_tokens`` (the issue's pre-declared
cut).  One process; not part of a benchmark run.  Writes
``chiprun_out/attn_step1.json``.
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
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--prompt", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.bench.compile_cache import enable_compile_cache
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.solve.local import drive, phase_policy

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    if args.prompt:
        config = {**config, "shapes": {**config["shapes"],
                                       "prompt_tokens": args.prompt}}
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(False)  # first calls as the window pays them
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"prompt_tokens": config["shapes"]["prompt_tokens"], "seeds": {}}

    def peak():
        return cell_mod.memory_peak(devices[:1]) / 1e9

    def wall(f, *a):
        t0 = time.perf_counter()
        f(*a)
        return time.perf_counter() - t0

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        h = built.hints
        start, _ = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak():.2f} GB, naive {len(built.naive.vector())} ops, "
              f"start point {len(start.vector())} ops", flush=True)
        rows = report["seeds"][str(seed)] = {}

        def one_schedule(order):
            t0 = time.perf_counter()
            run_n = ex.prepare_n(order)
            row = {"first_call_s": wall(run_n, 1)}
            if args.quick:
                t1, t3 = wall(run_n, 1), wall(run_n, 3)
                row.update(iter_ms=(t3 - t1) / 2 * 1e3, n=1)
            else:
                c = clock_mod.two_point(run_n)
                row.update(iter_ms=c["iter_s"] * 1e3,
                           fixed_ms=c["fixed_s"] * 1e3, n=c["n"])
            row["peak_after_timing_gb"] = peak()
            t1 = time.perf_counter()
            out = ex.run(order)
            jax.block_until_ready(out)
            row["one_shot_first_call_s"] = time.perf_counter() - t1
            row["compared"] = {x["name"]: [x["value"], x["limit"]]
                               for x in built.check(out)}
            del out
            row["timed_fence_gap"] = cell_mod.timed_fence_gap(
                ex, order, row["n"],
                cell_mod.probe_buffers(ex.init_bufs, seed))
            row["peak_gb"] = peak()
            row["seconds"] = time.perf_counter() - t0
            return row

        for label, order in (("start", start), ("naive", built.naive)):
            try:
                rows[label] = one_schedule(order)
            except Exception as e:  # out of memory at a size too large: read on
                rows[label] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            print(f"seed {seed} {label}: {json.dumps(rows[label])}",
                  flush=True)
        if args.control:
            out = ref.control(config, seed)
            rows["control"] = {x["name"]: [x["value"], x["limit"]]
                               for x in ref.check(config, seed, out)}
            print(f"seed {seed} control: {json.dumps(rows['control'])}",
                  flush=True)
            del out
        del built, ex
    reg = get_metrics()
    report["counters"] = {n: reg.counter(n).value for n in (
        "attn.tiles", "attn.tiles_skipped", "attn.tiles_edge",
        "attn.pairs_useful", "attn.pairs_computed")}
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = f"attn_step1.n{report['prompt_tokens']}.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r.get("timed_fence_gap", float("nan"))
            for rows in report["seeds"].values()
            for k, r in rows.items() if k != "control"]
    print(json.dumps({"largest_fence_gap": max(gaps),
                      "counters": report["counters"],
                      "peak_gb": (report["peak_bytes_in_use"] or 0) / 1e9,
                      "limit_gb": (report["bytes_limit"] or 0) / 1e9}))
    return 0 if max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
