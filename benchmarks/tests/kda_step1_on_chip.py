"""Step 1 of ISSUE 42, the go/no-go of the hybrid-decode cell on the chip.

    python benchmarks/tests/kda_step1_on_chip.py --workload kimi-linear-kda-decode.climb --seeds a,b,c [--page 1024] [--groups 4] [--kda-groups 4] [--sequences 64] [--control] [--parts] [--skip-naive] [--quick]

For each seed the configuration is built as a run builds it (``--page``,
``--groups``, ``--kda-groups`` and ``--sequences`` override its shapes:
how the page size, the groups and the pre-declared cut were read), and for
the builder's naive (one lane, every KDA group the chain of four XLA
vertices, every latent group a chain of ``mla_fold`` links) and the climb's
start point (every (layer, group) on its fused kernel):

* the first call of the repeat-n program, in seconds;
* the iteration time by the benchmark's two-point clock (``--quick``: from
  one call each at 1 and 5 repeats);
* for the first seed, one profiled dispatch of the repeat-n program: the
  device's milliseconds an iteration by operation (``kda_step``,
  ``mla_decode``, XLA's fusions); the program's counters ``kda.*`` and
  ``executor.value_tied_bytes`` for its traced body;
* ``timed_fence_gap`` of the timed program against the one-shot program on
  the harness's probe, which has to be 0;
* the one-shot program against the plain reference computed in blocks
  (``check``: the kernel's ``Snew``, ``Cvnew`` and ``o`` of every layer at
  the published widths) and, with ``--control``, the reference's control
  with the state carried in bfloat16 and its latent cache read as float8,
  each number beside its limit (what the limits were set from);
* the device's peak bytes after each step, and what is left free.

``--parts`` (first seed): the ``kda_step`` kernel alone on one layer's
buffers at head blocks of 32 and 16 and at 1, 2, 4 and 8 calls a layer,
and the XLA chain's four vertices alone, device milliseconds a call.  One
process; not part of a benchmark run.  Writes
``chiprun_out/kda_step1.<tag>.json``.
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

COUNTERS = ("kda.rows", "kda.state_bytes", "kda.state_min_bytes",
            "kda.fused_vertices", "kda.chain_vertices", "mla.page_steps",
            "mla.keys_useful", "mla.keys_computed",
            "executor.value_tied_bytes", "executor.index_ties")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="2147483659,2147483693,2147483713")
    ap.add_argument("--page", type=int, default=None)
    ap.add_argument("--groups", type=int, default=None)
    ap.add_argument("--kda-groups", type=int, default=None)
    ap.add_argument("--sequences", type=int, default=None)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--parts", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-naive", action="store_true")
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
    shapes = dict(config["shapes"])
    if args.page:
        keys = shapes["fold_pages"] * shapes["page_tokens"]
        shapes.update(page_tokens=args.page,
                      fold_pages=max(1, keys // args.page))
    if args.groups:
        shapes["groups"] = args.groups
    if args.kda_groups:
        shapes["kda_groups"] = args.kda_groups
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
    tag = (f"p{shapes['page_tokens']}.g{shapes['groups']}."
           f"k{shapes['kda_groups']}.s{len(shapes['lens'])}")
    report = {"page_tokens": shapes["page_tokens"],
              "groups": shapes["groups"], "kda_groups": shapes["kda_groups"],
              "sequences": len(shapes["lens"]), "seeds": {}}
    reg = get_metrics()
    trace_dir = os.path.join(ROOT, "benchmarks", "out", "kda_step1_profile")

    def peak():
        return cell_mod.memory_peak(devices[:1]) / 1e9

    def wall(f, *a):
        t0 = time.perf_counter()
        f(*a)
        return time.perf_counter() - t0

    def counters():
        return {n: reg.counter(n).value for n in COUNTERS}

    def device_ops(run):
        """Nanoseconds by operation kind of the first device while ``run()``
        is profiled."""
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
        """Device ms an iteration by operation, from one profiled dispatch
        at ``n`` repeats and one at 1 (differenced, so what a dispatch does
        once is out)."""
        per = {reps: device_ops(lambda: run_n(reps)) for reps in (1, n)}
        ms = {k: (per[n].get(k, 0) - per[1].get(k, 0)) / (n - 1) / 1e6
              for k in per[n]}
        return dict(sorted(ms.items(), key=lambda kv: -kv[1])[:14])

    def parts(bufs, kda):
        """The kernel alone and the chain's vertices alone on layer L0's
        buffers: device ms a call (a whole layer's sequences)."""
        from tenzing_tpu.models.delta_attention import KdaFused
        from tenzing_tpu.ops import kda_pallas

        operands = [bufs[f"{k}.L0"] for k in KdaFused.READS + KdaFused.WRITES]
        got = {}
        for hb in (32, 16):
            for calls in (1, 2, 4, 8):
                rows = kda.batch // calls

                @jax.jit
                def layer(ops):
                    s, c, o = ops[10:]
                    for i in range(calls):
                        s, c, o = kda_pallas.kda_step_pallas(
                            *ops[:10], s, c, o, lead0=i * rows, rows=rows,
                            head_block=hb, eps=kda.eps)
                    return s, c, o

                jax.block_until_ready(layer(operands))
                ops = device_ops(
                    lambda: jax.block_until_ready(layer(operands)))
                got[f"kda_step.hb{hb}.calls{calls}"] = sum(
                    ns for k, ns in ops.items()
                    if k.startswith("kda_step")) / 1e6
        n = {k: bufs[f"{k}.L0"] for k in KdaFused.READS}

        @jax.jit
        def chain(n):
            y, moved = kda_pallas.conv_step(n["x"], n["Cv"], n["Wc"])
            q, k, v, decay, beta = kda_pallas.gates(
                y, n["f"], n["dt_bias"], n["A_log"], n["b"])
            snew, o = kda_pallas.state_step(n["S"], q, k, v, decay, beta)
            return snew, moved, kda_pallas.out_norm(o, n["go"], n["w_norm"],
                                                    kda.eps)

        jax.block_until_ready(chain(n))
        ops = device_ops(lambda: jax.block_until_ready(chain(n)))
        got["xla_chain_layer_ms"] = sum(ops.values()) / 1e6
        got["xla_chain_ops"] = dict(sorted(
            ((k, v / 1e6) for k, v in ops.items()),
            key=lambda kv: -kv[1])[:6])
        return got

    for at, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        t0 = time.perf_counter()
        built = builder.build(config, seed, devices, ref)
        ex = built.executor
        ex.init_bufs = cell_mod.committed(ex.init_bufs)
        jax.block_until_ready(ex.init_bufs)
        h = built.hints
        start, _ = drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h["prefer"]))
        cost = {k: v for k, v in built.cost.items() if k != "traced_kda"}
        print(f"seed {seed}: built in {time.perf_counter() - t0:.1f} s, "
              f"peak {peak():.2f} GB, naive {len(built.naive.vector())} ops, "
              f"start point {len(start.vector())} ops, cost "
              f"{json.dumps(cost)}", flush=True)
        rows = report["seeds"][str(seed)] = {}
        if args.parts and at == 0 and not args.rehearse_cpu:
            from tenzing_tpu.models.delta_attention import DeltaDecodeArgs

            z = ref.sizes(config)
            rows["parts"] = parts(ex.init_bufs, DeltaDecodeArgs(
                batch=len(z["lens"]), heads=z["kda_heads"], d=z["d"],
                taps=z["taps"], groups=1, eps=z["eps"], dtype=z["dtype"]))
            print(f"seed {seed} parts: {json.dumps(rows['parts'])}",
                  flush=True)

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
                rows[label] = {"error": f"{type(e).__name__}: {str(e)[:400]}"}
            print(f"seed {seed} {label}: {json.dumps(rows[label])}",
                  flush=True)
        if args.control:
            for name, make in (("control", ref.control),
                               ("cache_control", ref.cache_control)):
                out = make(config, seed)
                rows[name] = {x["name"]: [x["value"], x["limit"]]
                              for x in ref.check(config, seed, out)}
                print(f"seed {seed} {name}: {json.dumps(rows[name])}",
                      flush=True)
                del out
        del built, ex
    stats = devices[0].memory_stats() or {}
    report["bytes_limit"] = stats.get("bytes_limit")
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"kda_step1.{tag}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    gaps = [r.get("timed_fence_gap", float("nan"))
            for rows in report["seeds"].values()
            for k, r in rows.items() if k in ("start", "naive")]
    limit, top = report["bytes_limit"] or 0, report["peak_bytes_in_use"] or 0
    print(json.dumps({"tag": tag, "largest_fence_gap": max(gaps),
                      "peak_gb": top / 1e9, "limit_gb": limit / 1e9,
                      "free_gb": (limit - top) / 1e9}))
    return 0 if max(gaps) == 0.0 else 1


if __name__ == "__main__":
    sys.exit(main())
