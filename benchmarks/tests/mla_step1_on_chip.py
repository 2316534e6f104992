"""Step 1 of ISSUE 35, the go/no-go of the latent-decode cell on the chip.

    python benchmarks/tests/mla_step1_on_chip.py --workload dsv3-mla-decode.climb --seeds a,b,c [--page 1024] [--sequences 16] [--control] [--published] [--quick]

For each seed the configuration is built as a run builds it, and for the
builder's naive (one lane, every group a chain of ``mla_fold`` links) and
the climb's start point (every group on the fused ``mla_decode`` kernel,
driven as ``hill_climb`` drives it):

* the first call of the repeat-n program, in seconds (what a candidate of
  the window costs before it is measured);
* the iteration time by the benchmark's two-point clock (``--quick``: from
  one call each at 1 and 5 repeats);
* one profiled dispatch of the repeat-n program: the device's milliseconds
  an iteration by operation, and whether anything beside the kernels takes
  the time a pass over a pool would (**no operation may move a sealed
  pool**); the program's counters ``executor.value_tied_bytes`` and
  ``mla.*`` for its traced body;
* ``timed_fence_gap`` of the timed program against the one-shot program on
  the harness's probe (``harness/cell.py::timed_fence_gap``, as ``compare``
  calls it), which has to be 0;
* the one-shot program against the plain reference (``check``) and, with
  ``--control``, the reference's float8 control, each number beside its
  limit; with ``--published`` (first seed, first layer) the published form
  in blocks against the absorbed reference;
* the device's peak bytes after each step, and what is left free.

``--page`` overrides ``shapes.page_tokens`` (with ``fold_pages`` kept at 32k
keys a link), ``--sequences N`` takes every ``len / N``-th of the sorted
lengths (how the issue's pre-declared cut from 32 to the configuration's 16
was read).  One process; not part of a benchmark run.
Writes ``chiprun_out/mla_step1.p<page>.json``.
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

COUNTERS = ("mla.page_steps", "mla.page_steps_idle", "mla.keys_useful",
            "mla.keys_computed", "mla.appended_rows",
            "executor.value_tied_bytes", "executor.index_ties")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--page", type=int, default=None)
    ap.add_argument("--sequences", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--published", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-naive", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

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
    shapes = dict(config["shapes"])
    if args.page:
        keys = shapes["fold_pages"] * shapes["page_tokens"]
        shapes.update(page_tokens=args.page,
                      fold_pages=max(1, keys // args.page))
    if args.sequences:
        lens = sorted(shapes["lens"])
        shapes["lens"] = lens[::len(lens) // args.sequences]
    config = {**config, "shapes": shapes}
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(False)  # first calls as the window pays them
    ref = cell_mod.load_module("references", config["reference"])
    builder = cell_mod.load_module("builders", config["builder"])
    report = {"page_tokens": shapes["page_tokens"],
              "sequences": len(shapes["lens"]), "seeds": {}}
    reg = get_metrics()

    def peak():
        return cell_mod.memory_peak(devices[:1]) / 1e9

    def wall(f, *a):
        t0 = time.perf_counter()
        f(*a)
        return time.perf_counter() - t0

    def counters():
        return {n: reg.counter(n).value for n in COUNTERS}

    def profiled(run_n, n):
        """Device ms an iteration by operation, from one profiled dispatch
        at ``n`` repeats and one at 1 (differenced, so what a dispatch does
        once is out)."""
        out = os.path.join(ROOT, "benchmarks", "out", "mla_step1_profile")
        per = {}
        for reps in (1, n):
            shutil.rmtree(out, ignore_errors=True)
            cell_mod.start_trace(out)
            try:
                run_n(reps)
            finally:
                jax.profiler.stop_trace()
            plane = trace_mod.device_planes(trace_mod.load_xplane(out))[0]
            ops = {}
            events = trace_mod._line(plane, trace_mod.OPS_LINE)["events"]
            for name, ns in trace_mod.self_times(events).items():
                kind = trace_mod.op_kind(name)
                ops[kind] = ops.get(kind, 0) + ns
            per[reps] = ops
        shutil.rmtree(out, ignore_errors=True)
        ms = {k: (per[n].get(k, 0) - per[1].get(k, 0)) / (n - 1) / 1e6
              for k in per[n]}
        return dict(sorted(ms.items(), key=lambda kv: -kv[1])[:14])

    for at, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        jax.block_until_ready(ex.init_bufs)
        h = built.hints
        start, _ = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak():.2f} GB, naive {len(built.naive.vector())} ops, "
              f"start point {len(start.vector())} ops, cost "
              f"{json.dumps(built.cost)}", flush=True)
        rows = report["seeds"][str(seed)] = {}

        def one_schedule(order, profile):
            t0 = time.perf_counter()
            before = counters()
            run_n = ex.prepare_n(order)
            row = {"first_call_s": wall(run_n, 1)}
            row["traced_body"] = {k: v - before[k]
                                  for k, v in counters().items()}
            if args.quick:
                t1, t5 = wall(run_n, 1), wall(run_n, 5)
                row.update(iter_ms=(t5 - t1) / 4 * 1e3, n=2)
            else:
                c = clock_mod.two_point(run_n)
                row.update(iter_ms=c["iter_s"] * 1e3,
                           fixed_ms=c["fixed_s"] * 1e3, n=c["n"])
            if profile and not args.rehearse_cpu:
                row["device_ms_an_iteration"] = profiled(run_n, 9)
            row["peak_after_timing_gb"] = peak()
            t1 = time.perf_counter()
            out = ex.run(order)
            jax.block_until_ready(out)
            row["one_shot_first_call_s"] = time.perf_counter() - t1
            t1 = time.perf_counter()
            row["compared"] = {x["name"]: [x["value"], x["limit"]]
                               for x in built.check(out)}
            row["check_s"] = time.perf_counter() - t1
            del out
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
                rows[label] = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
            print(f"seed {seed} {label}: {json.dumps(rows[label])}",
                  flush=True)
        if args.control:
            out = ref.control(config, seed)
            rows["control"] = {x["name"]: [x["value"], x["limit"]]
                               for x in ref.check(config, seed, out)}
            print(f"seed {seed} control: {json.dumps(rows['control'])}",
                  flush=True)
            del out
        if args.published and at == 0:
            # the published form against the absorbed reference, one layer
            z = ref.sizes(config)
            t = ref.layer_tensors(ref.make_data(config, seed), 0)
            t1 = time.perf_counter()
            pub = jax.jit(lambda t: ref.published_layer(z, t))(t)
            absorbed = jax.jit(lambda t: ref.layer_reference(z, t))(t)
            err = jnp.linalg.norm(pub - absorbed, axis=2)
            norm = jnp.linalg.norm(pub, axis=2)
            rows["published_vs_absorbed"] = {
                "rms_gap": float(jnp.sqrt(jnp.sum(err ** 2)
                                          / jnp.sum(norm ** 2))),
                "widest_row_gap": float(jnp.max(
                    err / jnp.maximum(norm, jnp.median(norm)))),
                "seconds": time.perf_counter() - t1}
            print(f"seed {seed} published form, layer 0: "
                  f"{json.dumps(rows['published_vs_absorbed'])}", flush=True)
            del pub, absorbed, t
        del built, ex
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = f"mla_step1.p{report['page_tokens']}.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r.get("timed_fence_gap", float("nan"))
            for rows in report["seeds"].values()
            for k, r in rows.items() if k in ("start", "naive")]
    limit, top = report["bytes_limit"] or 0, report["peak_bytes_in_use"] or 0
    print(json.dumps({"largest_fence_gap": max(gaps),
                      "peak_gb": top / 1e9, "limit_gb": limit / 1e9,
                      "free_gb": (limit - top) / 1e9}))
    return 0 if max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
