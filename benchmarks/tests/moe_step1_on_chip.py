"""Step 1 of ISSUE 28, the go/no-go of the expert-parallel cell on the chip.

    python benchmarks/tests/moe_step1_on_chip.py --workload moonlight-ep4.mcts --seeds a,b,c [--ranks 1]

For each seed the configuration is built as a run builds it, and for naive
and the phase-ordered schedule (post every dispatch before awaiting any):

* ``timed_fence_gap`` as ``harness/cell.py::compare`` takes it (the timed
  program after n repeats against the one-shot program's outputs, on the
  probe; has to be 0.0: whether the chip's compiler lowers a silu between
  two matrix products alike inside and outside the repeat loop);
* the one-shot program against the plain reference (``check``), and the
  reference's control, each number beside its limit;
* the iteration time by the benchmark's two-point clock;
* with ``--async-a2a``, the same timed program compiled with the TPU
  compiler's ``xla_tpu_enable_async_all_to_all`` (off by default: by default
  every all-to-all is one synchronous operation), timed at two repeat
  counts;
* the first device's peak bytes.

``--ranks 1`` puts all the experts on one chip (the all-to-alls then cross
nothing): the same slots, products and sums a chip, at a quarter of the
chip time, to read the fence gap before four chips are spent.  One process;
not part of a benchmark run.  Writes ``chiprun_out/moe_step1.json``.
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
    ap.add_argument("--ranks", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--async-a2a", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.bench.compile_cache import enable_compile_cache
    from tenzing_tpu.models.moe import PHASES
    from tenzing_tpu.solve.greedy import greedy_phase_order

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    chips = cell.chips
    if args.ranks:
        s = config["shapes"]
        held = int(s["ranks"]) * int(s["experts_per_shard"]) // args.ranks
        config = {**config, "shapes": {**s, "ranks": args.ranks,
                                       "experts_per_shard": held}}
        chips = args.ranks
    devices = cell_mod.find_devices(chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(True)
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"ranks": chips, "seeds": {}}

    def peak():
        return cell_mod.memory_peak(devices[:1])

    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak() / 1e9:.2f} GB", flush=True)
        orders = {"naive": built.naive,
                  "phases": greedy_phase_order(
                      built.graph, built.hints["platform"], PHASES)}
        rows = report["seeds"][str(seed)] = {}
        for label, order in orders.items():
            t0 = time.perf_counter()
            run_n = ex.prepare_n(order)
            c = clock_mod.two_point(run_n)
            out = ex.run(order)
            compared = built.check(out)
            del out
            gap = cell_mod.timed_fence_gap(
                ex, order, c["n"], cell_mod.probe_buffers(ex.init_bufs, seed))
            row = rows[label] = {
                "iter_ms": c["iter_s"] * 1e3, "fixed_ms": c["fixed_s"] * 1e3,
                "n": c["n"], "timed_fence_gap": gap,
                "compared": {x["name"]: [x["value"], x["limit"]]
                             for x in compared},
                "peak_gb": peak() / 1e9,
                "seconds": time.perf_counter() - t0}
            print(f"seed {seed} {label}: {json.dumps(row)}", flush=True)
            if args.async_a2a and len(report["seeds"]) == 1:  # first seed
                f = jax.jit(ex._stepped_fn(order.vector())).lower(
                    ex.init_bufs, jnp.int32(1)).compile(
                        compiler_options={
                            "xla_tpu_enable_async_all_to_all": True})
                ts = {}
                for n in (2, 8, 8, 2, 2, 8):
                    t1 = time.perf_counter()
                    jax.device_get(f(ex.init_bufs, jnp.int32(n))[0])
                    ts.setdefault(n, []).append(time.perf_counter() - t1)
                row["async_a2a_iter_ms"] = (min(ts[8]) - min(ts[2])) / 6 * 1e3
                print(f"seed {seed} {label}: async all-to-all iter "
                      f"{row['async_a2a_iter_ms']:.3f} ms", flush=True)
                del f
        if args.control:
            out = ref.control(config, seed)
            rows["control"] = {x["name"]: [x["value"], x["limit"]]
                               for x in ref.check(config, seed, out)}
            print(f"seed {seed} control: {json.dumps(rows['control'])}",
                  flush=True)
            del out
        del built, ex, run_n
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_step1.json"), "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r["timed_fence_gap"] for rows in report["seeds"].values()
            for k, r in rows.items() if k != "control"]
    print(json.dumps({"largest_fence_gap": max(gaps),
                      "peak_gb": (report["peak_bytes_in_use"] or 0) / 1e9,
                      "limit_gb": (report["bytes_limit"] or 0) / 1e9}))
    return 0 if max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
