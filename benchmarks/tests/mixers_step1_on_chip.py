"""Step 1 of ISSUE 50, the go/no-go of the mixers-prefill cell on the chip.

    python benchmarks/tests/mixers_step1_on_chip.py --workload nemotron3-nano-mixers-prefill.climb --seeds a,b,c [--control] [--skip-naive] [--quick] [--blocks 4096x2048]

For each seed the configuration is built as a run builds it (``--blocks``:
with another ``q_block`` x ``kv_block``), and for the climb's start point
(every scan and query block on its fused kernel) and the builder's naive
(one lane, every scan the chain of four XLA vertices, every query block a
chain of ``attn_fold`` kernels):

* the first call of the repeat-n program, in seconds;
* the iteration time by the benchmark's two-point clock (``--quick``: from
  one call each at 1 and 5 repeats);
* for the first seed, one profiled dispatch of the repeat-n program at 1
  and at 5 repeats, differenced: the device's milliseconds an iteration by
  operation kind; and the program's counters for its traced body;
* the one-shot program against the plain reference (``check``, and the
  numbers a layer) and, with ``--control``, the reference's three controls
  (the state and decays carried in bfloat16; the boundaries ignored; K and
  V in float8) and its own sound outputs, each number beside its limit
  (what the limits were set from);
* ``timed_fence_gap`` of the timed program against the one-shot program on
  the harness's probe, which has to be 0;
* the device's peak bytes after each step, and what is left free.

One process; not part of a benchmark run.  Writes
``chiprun_out/mixers_step1.json``.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COUNTERS = ("ssd.chunks", "ssd.boundary_chunks", "ssd.prompts",
            "ssd.state_bytes_written", "ssd.fused_vertices",
            "ssd.chain_vertices", "attn.tiles", "attn.tiles_edge",
            "attn.tiles_skipped", "attn.pairs_useful", "attn.pairs_computed",
            "attn.fused_finishes", "attn.operands_in_place",
            "executor.value_tied_bytes", "executor.index_ties")
CONTROLS = ("control", "control_boundaries", "control_kv8", "sound")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-naive", action="store_true")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--out", default="mixers_step1")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from benchmarks.harness import trace as trace_mod
    from tenzing_tpu.bench.compile_cache import enable_compile_cache
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.solve.local import drive, phase_policy

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    if args.blocks:
        q, kv = (int(x) for x in args.blocks.split("x"))
        config = {**config,
                  "shapes": {**config["shapes"], "q_block": q, "kv_block": kv}}
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(False)  # first calls as the window pays them
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"blocks": args.blocks, "seeds": {}}
    reg = get_metrics()
    trace_dir = os.path.join(ROOT, "benchmarks", "out",
                             "mixers_step1_profile")

    def peak():
        return cell_mod.memory_peak(devices[:1]) / 1e9

    def wall(f, *a):
        t0 = time.perf_counter()
        f(*a)
        return time.perf_counter() - t0

    def counters():
        return {n: reg.counter(n).value for n in COUNTERS}

    def device_ops(run):
        shutil.rmtree(trace_dir, ignore_errors=True)
        cell_mod.start_trace(trace_dir)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        plane = trace_mod.device_planes(trace_mod.load_xplane(trace_dir))[0]
        ops = {}
        events = trace_mod._line(plane, trace_mod.OPS_LINE)["events"]
        for name, ns in trace_mod.self_times(events).items():
            kind = trace_mod.op_kind(name)
            ops[kind] = ops.get(kind, 0) + ns
        shutil.rmtree(trace_dir, ignore_errors=True)
        return ops

    def profiled(run_n, n):
        per = {reps: device_ops(lambda: run_n(reps)) for reps in (1, n)}
        ms = {k: (per[n].get(k, 0) - per[1].get(k, 0)) / (n - 1) / 1e6
              for k in per[n]}
        return dict(sorted(ms.items(), key=lambda kv: -kv[1])[:16])

    def compared(rows):
        return {x["name"]: [x["value"], x["limit"]] for x in rows}

    for at, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        h = built.hints
        start, _ = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))
        cost = {k: v for k, v in built.cost.items() if k != "traced_counts"}
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak():.2f} GB, naive {len(built.naive.vector())} ops, "
              f"start point {len(start.vector())} ops, cost "
              f"{json.dumps(cost)}", flush=True)
        rows = report["seeds"][str(seed)] = {}

        def one_schedule(order, profile):
            t0 = time.perf_counter()
            before = counters()
            run_n = ex.prepare_n(order)
            row = {"first_call_s": wall(run_n, 1)}
            row["traced_body"] = {k: v - before[k]
                                  for k, v in counters().items()
                                  if v != before[k]}
            if args.quick:
                t1, t5 = wall(run_n, 1), wall(run_n, 5)
                row.update(iter_ms=(t5 - t1) / 4 * 1e3, n=2)
            else:
                c = clock_mod.two_point(run_n)
                row.update(iter_ms=c["iter_s"] * 1e3,
                           fixed_ms=c["fixed_s"] * 1e3, n=c["n"])
            if profile and not args.rehearse_cpu:
                row["device_ms_an_iteration"] = profiled(run_n, 5)
            row["peak_after_timing_gb"] = peak()
            t1 = time.perf_counter()
            out = ex.run(order)
            jax.block_until_ready(out)
            row["one_shot_first_call_s"] = time.perf_counter() - t1
            t1 = time.perf_counter()
            row["compared"] = compared(built.check(out))
            row["check_s"] = time.perf_counter() - t1
            row["by_layer"] = ref.by_layer(config, seed, out)
            del out
            row["peak_after_check_gb"] = peak()
            row["timed_fence_gap"] = cell_mod.timed_fence_gap(
                ex, order, row["n"],
                cell_mod.probe_buffers(ex.init_bufs, seed))
            row["peak_gb"] = peak()
            row["seconds"] = time.perf_counter() - t0
            return row

        todo = [("start", start)] + (
            [] if args.skip_naive else [("naive", built.naive)])
        for label, order in todo:
            try:
                rows[label] = one_schedule(order, profile=at == 0)
            except Exception as e:  # out of memory at a size too large: read on
                rows[label] = {"error": f"{type(e).__name__}: {str(e)[:600]}"}
            print(f"seed {seed} {label}: {json.dumps(rows[label])}",
                  flush=True)
        if args.control:
            for name in CONTROLS:
                out = getattr(ref, name)(config, seed)
                rows[name] = compared(ref.check(config, seed, out))
                rows[name]["by_layer"] = ref.by_layer(config, seed, out)
                print(f"seed {seed} {name}: {json.dumps(rows[name])}",
                      flush=True)
                del out
        del built, ex
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"{args.out}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r.get("timed_fence_gap", float("nan"))
            for rows in report["seeds"].values()
            for k, r in rows.items() if k in ("start", "naive")]
    limit, top = report["bytes_limit"] or 0, report["peak_bytes_in_use"] or 0
    print(json.dumps({"largest_fence_gap": max(gaps) if gaps else None,
                      "peak_gb": top / 1e9, "limit_gb": limit / 1e9,
                      "free_gb": (limit - top) / 1e9}))
    return 0 if not gaps or max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
