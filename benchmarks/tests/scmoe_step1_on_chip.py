"""Step 1 of ISSUE 46, the go/no-go of the shortcut-connected decode cell on
the chip.

    python benchmarks/tests/scmoe_step1_on_chip.py --workload longcat-lite-scmoe-decode.climb --seeds a,b,c [--controls]

For each seed the configuration is built as a run builds it.  For the first
seed, for naive, the start point and the start point on the ring exchanges
(``--ring``; the configuration's ``synth``):

* the first call's seconds of the repeat-n program, the iteration time by
  the benchmark's two-point clock, and (start point only) the first
  device's milliseconds an iteration by operation kind, from a profile of
  two dispatches reduced as ``harness/trace.py`` reduces a window's slice;
* the one-shot program against the plain reference (``check``), each number
  beside its limit, and ``timed_fence_gap`` as ``harness/cell.py`` takes it;
* the buffers' bytes a chip, the fullest slot table of each expert block,
  and the first device's peak bytes (inside ``timed_fence_gap``: the run's
  data, the probe set and the one-shot program's outputs at once).

For every seed: naive's and the start point's ``check``, and with
``--controls`` the reference's three controls (slots as float8 with
bfloat16 scores; no zero term; caches as float8), which ``check`` has to
refuse.  ``--set page_tokens=1024,groups=4`` overrides shapes (a sweep's
point: only the start point is then clocked).  One process; not part of a
benchmark run.  Writes ``chiprun_out/scmoe_step1[.<tag>].json``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def ops_ms_per_iter(run_n, n: int, out_dir) -> list:
    """``[[kind, ms an iteration]]`` of the first device: a profile of one
    dispatch of ``n`` repeats, its operations' own times over ``n``."""
    import shutil

    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import trace as trace_mod

    cell_mod.start_trace(out_dir)
    try:
        run_n(n)
    finally:
        jax.profiler.stop_trace()
    planes = trace_mod.device_planes(trace_mod.load_xplane(out_dir))
    shutil.rmtree(out_dir, ignore_errors=True)
    if not planes:
        return []
    kinds = {}
    line = trace_mod._line(planes[0], trace_mod.OPS_LINE)
    for name, ns in trace_mod.self_times(line["events"]).items():
        k = trace_mod.op_kind(name)
        kinds[k] = kinds.get(k, 0) + ns
    return [[k, v / 1e6 / n] for k, v in
            sorted(kinds.items(), key=lambda kv: -kv[1])[:16]]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--controls", action="store_true")
    ap.add_argument("--ring", action="store_true")
    ap.add_argument("--set", default="")
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.bench.compile_cache import enable_compile_cache
    from tenzing_tpu.obs.metrics import get_metrics
    from tenzing_tpu.solve.local import drive, phase_policy

    cell = cell_mod.load_cell(args.workload)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    sweep = dict(kv.split("=") for kv in args.set.split(",") if kv)
    if sweep:
        config = {**config, "shapes": {**config["shapes"], **{
            k: type(config["shapes"][k])(v) for k, v in sweep.items()}}}
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(True)
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"set": sweep, "seeds": {}}

    def peak():
        return cell_mod.memory_peak(devices[:1])

    def named(compared):
        return {x["name"]: [x["value"], x["limit"]] for x in compared}

    gaps = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        chip_bytes = sum(
            s.data.nbytes for v in ex.init_bufs.values()
            for s in v.addressable_shards if s.device == devices[0])
        fullest = []
        for name, v in sorted(ex.init_bufs.items()):
            if name.endswith(".slot_tk_0"):
                held = np.asarray(jax.device_get(v)) >= 0
                n = held.shape[0]
                per = held.reshape(
                    n, n, int(config["shapes"]["experts_per_shard"]), -1)
                fullest.append(int(per.sum(-1).max()))
        rows = report["seeds"][str(seed)] = {
            "built_s": time.perf_counter() - t0,
            "buffers_gb_a_chip": chip_bytes / 1e9,
            "fullest_slot_table": fullest,
            "counters": {k: get_metrics().counter(k).value for k in (
                "moe.capacity_slots", "moe.routed_slots", "moe.zero_picks",
                "moe.dropped_slots", "scmoe.weight_bytes",
                "scmoe.cache_bytes")},
            "peak_gb_built": peak() / 1e9}
        print(f"seed {seed}: {json.dumps(rows)}", flush=True)
        h = built.hints
        orders = {"start": drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))[0]}
        if not sweep:
            orders = {"naive": built.naive, **orders}
        if args.ring and i == 0:
            ring = builder.prefer_of((".ring.c1",) + builder.START)
            orders["ring"] = drive(built.graph, h["platform"], phase_policy(
                h["platform"], h["phases"], ring))[0]
        for label, order in orders.items():
            t0 = time.perf_counter()
            run_n = ex.prepare_n(order)
            run_n(1)
            first_call_s = time.perf_counter() - t0
            row = rows[label] = {"first_call_s": first_call_s}
            if i == 0 or sweep:
                c = clock_mod.two_point(run_n)
                row.update(iter_ms=c["iter_s"] * 1e3,
                           fixed_ms=c["fixed_s"] * 1e3, n=c["n"])
                if label == "start" and not args.rehearse_cpu:
                    row["ops_ms"] = ops_ms_per_iter(
                        run_n, c["n"], os.path.join(
                            ROOT, "benchmarks", "out", "scmoe_step1_trace"))
            if not sweep:
                out = ex.run(order)
                row["compared"] = named(built.check(out))
                del out
                if i == 0:
                    row["timed_fence_gap"] = cell_mod.timed_fence_gap(
                        ex, order, row["n"],
                        cell_mod.probe_buffers(ex.init_bufs, seed))
                    gaps.append(row["timed_fence_gap"])
            row["peak_gb"] = peak() / 1e9
            row["seconds"] = time.perf_counter() - t0
            print(f"seed {seed} {label}: {json.dumps(row)}", flush=True)
        if args.controls:
            for kind in ("control", "zero_control", "cache_control"):
                out = getattr(ref, kind)(config, seed)
                rows[kind] = named(ref.check(config, seed, out))
                print(f"seed {seed} {kind}: {json.dumps(rows[kind])}",
                      flush=True)
                del out
        del built, ex, run_n
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = f".{args.tag}" if args.tag else ""
    with open(os.path.join(ROOT, "chiprun_out",
                           f"scmoe_step1{tag}.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({"largest_fence_gap": max(gaps, default=None),
                      "peak_gb": (report["peak_bytes_in_use"] or 0) / 1e9,
                      "limit_gb": (report["bytes_limit"] or 0) / 1e9}))
    return 0 if max(gaps, default=0.0) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
