"""The program's own reduction of a profile beside the benchmark's, on the
chip, for one cell.

    chiprun -- python benchmarks/tests/program_spans_on_chip.py \
        --workload <cell> [--seed N] [--seconds 20] [--trim OUT.json]

Walks the cell's set-up as ``harness/cell.py`` does (through its functions),
then runs the mix's solver through the measurement stack for ``--seconds``
under ONE profiler session, keeps the trace, and prints two reductions of
it: ``harness/trace.py``'s (idle gaps by the harness's ``tzb:`` proxies) and
the program's (``tenzing_tpu/obs/attrib/xplane.py``: idle gaps by the
program's ``tz:`` spans, which the tracer mirrors into the session by
itself).  Busy and idle seconds of the two have to agree to 1%, and the idle
time the program's spans leave unnamed (``unattributed``, or the bare self
time of ``bench.benchmark`` / ``bench.batch``) has to stay under 10% of the
idle time: exit code 1 otherwise.  Then, from the tracer's ring, the sums
``PERF.md`` section 5 quotes: first calls and their parts, a dispatch's
enqueue and fence wait, dispatches a measurement call.  ``--trim`` writes a
cut of the trace in the neutral form, small enough for ``tests/data``.
"""

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

BARE = ("unattributed", "bench.benchmark", "bench.batch")


def profiled_window(workload: str, seed: int, seconds: float, out_dir: Path,
                    rehearse: bool = False):
    """Set-up as a run's, then the window under one profiler session."""
    import jax

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness.stack import Deadline, Spans, build_stack
    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.bench.compile_cache import enable_compile_cache

    cell = cell_mod.load_cell(workload)
    devices = cell_mod.find_devices(cell.chips, rehearse)
    enable_compile_cache(0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell_mod.persistent_cache(True)
    config, mix = cell.config, cell.mix
    if rehearse:
        config = cell_mod.toy_shapes(config)
    builder = cell_mod.load_module("builders", config["builder"])
    reference = (cell_mod.load_module("references", config["reference"])
                 if config.get("reference") else None)
    solver = cell_mod.load_module("solvers", mix["solver"])
    built = builder.build(config, seed, devices, reference)
    built.executor.init_bufs = cell_mod.committed(built.executor.init_bufs)
    spans = Spans(time.perf_counter)
    bench, verifier, prefetcher, resilient = build_stack(
        built.executor, built.graph, spans)
    try:
        resilient.benchmark(built.naive, BenchOpts(
            n_iters=1, max_retries=1, target_secs=1e-4))
        cell_mod.persistent_cache(False)
        ctx = SimpleNamespace(graph=built.graph, bench=bench,
                              verifier=verifier, prefetcher=prefetcher,
                              hints=built.hints, seed=seed)
        cell_mod.start_trace(out_dir)
        spans.annotate = True
        bench.open(seconds)
        try:
            solver.run(ctx, mix["params"])
        except Deadline:
            pass
        finally:
            bench.close()
            spans.annotate = False
            jax.profiler.stop_trace()
    finally:
        prefetcher.close()
    return bench


def ring_summary(t_open: float, t_close: float) -> dict:
    """What the per-layer readers read, over the whole profiled window."""
    from benchmarks.harness import program_spans as ps
    from tenzing_tpu.obs.tracer import get_tracer

    spans = sorted((s for s in get_tracer().spans()
                    if s.t1 is not None and t_open <= s.t0 <= t_close),
                   key=lambda s: s.t0)
    whole = ps.whole_first_calls(spans)
    out = {"spans": len(spans), "first_calls": len(ps.named(
        spans, ps.FIRST_CALL)), "first_calls_whole": len(whole)}
    if whole:
        mean = lambda xs: sum(xs) / len(xs)
        out["first_call_s"] = mean([ps.seconds(fc) for fc, _ in whole])
        for part in ("executor.lower", "executor.xla_compile",
                     "executor.first_run"):
            out[part + "_s_over_whole"] = sum(
                ps.seconds(p[part]) for _, p in whole if part in p) / len(
                    whole)
        out["parts_over_first_call"] = sum(
            ps.seconds(s) for _, p in whole for s in p.values()) / sum(
                ps.seconds(fc) for fc, _ in whole)
    runs = ps.named(spans, "executor.first_run")
    if runs:
        out["first_run_s_all"] = sum(map(ps.seconds, runs)) / len(runs)
    disp = ps.named(spans, "bench.dispatch")
    for name in ("executor.enqueue", "executor.fence_wait"):
        parts = ps.children(spans, disp, name)
        if parts:
            ms = sorted(1e3 * ps.seconds(s) for s in parts)
            out[name + "_ms"] = {
                "n": len(ms), "median": statistics.median(ms),
                "p10": ms[len(ms) // 10], "p90": ms[(9 * len(ms)) // 10]}
    steady = [s for s in disp if ps.children(spans, [s], "executor.enqueue")]
    if steady:
        out["steady_dispatch_ms_median"] = statistics.median(
            1e3 * ps.seconds(s) for s in steady)
    calls = ps.named(ps.foreground(spans), *ps.MEASUREMENT_CALLS)
    if calls:
        out["measurement_calls"] = len(calls)
        out["dispatches_per_call"] = len(disp) / len(calls)
        out["wait_s_per_call"] = sum(
            ps.seconds(s) for s in ps.named(
                ps.foreground(spans), "pipeline.wait", ps.FIRST_CALL)) / len(
                    calls)
    for name in ("pipeline.prefetch.issued", "pipeline.prefetch.hits",
                 "bench.dispatches"):
        out[name] = ps.counter(name)
    return out


def trimmed(trace: dict, seconds: float) -> dict:
    """``trace`` cut to the program's spans and the device's operations of
    ``seconds`` from the middle of the slice (what straddles an end is
    clipped to it), operation names cut to their kind: the neutral form at
    a size a repository can carry."""
    from tenzing_tpu.obs.attrib import xplane

    threads = xplane.program_threads(trace)
    lo = min(e[1] for evs in threads.values() for e in evs)
    hi = max(e[2] for evs in threads.values() for e in evs)
    a = lo + (hi - lo) // 2
    b = a + int(seconds * 1e9)
    planes = []
    for p in trace["planes"]:
        device = p["name"].startswith("/device:")
        lines = []
        for ln in p["lines"]:
            if device and ln["name"] != xplane.OPS_LINE:
                continue
            evs = [[xplane.op_kind(n) if device else n,
                    max(s, a) - a, min(e, b) - a]
                   for n, s, e in ln["events"] if e > a and s < b
                   and (device or n.startswith(xplane.SPAN_PREFIX))]
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 2525)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trim")
    ap.add_argument("--trim-seconds", type=float, default=2.0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    out_dir = ROOT / "benchmarks" / "out" / f"{args.workload}.program_spans"
    bench = profiled_window(args.workload, args.seed, args.seconds, out_dir,
                            rehearse=args.rehearse_cpu)

    from benchmarks.harness import trace as trace_mod
    from tenzing_tpu.obs.attrib import xplane

    trace = xplane.load_xplane(out_dir)
    theirs = trace_mod.reduce_window(trace)
    mine = xplane.reduce_trace(trace)
    print(f"== {args.workload}, seed {args.seed}: "
          f"{len(bench.in_window())} candidates in {args.seconds:g} s")
    print("-- harness/trace.py (tzb: proxies)")
    print(json.dumps(theirs, indent=1))
    print("-- tenzing_tpu.obs.attrib.xplane (tz: spans)")
    print(xplane.render(mine))
    print(json.dumps(mine["idle_by_span"]))
    print("-- the tracer's ring over the profiled window")
    print(json.dumps(ring_summary(bench.t_open, time.perf_counter()),
                     indent=1))
    if args.rehearse_cpu:
        print("rehearsal on the CPU: no device in the trace, nothing to "
              "compare")
        return 0
    their_idle = theirs["window_s"] - theirs["busy_s"]
    gap_busy = abs(mine["busy_s"] - theirs["busy_s"]) / theirs["busy_s"]
    gap_idle = abs(mine["idle_s"] - their_idle) / their_idle
    bare = sum(r["idle_s"] for r in mine["idle_by_span"]
               if r["span"] in BARE) / mine["idle_s"]
    print(f"-- busy {mine['busy_s']:.4f} against {theirs['busy_s']:.4f} s "
          f"({100 * gap_busy:.3f}% apart), idle {mine['idle_s']:.4f} against "
          f"{their_idle:.4f} s ({100 * gap_idle:.3f}% apart); idle left to "
          f"{', '.join(BARE)}: {100 * bare:.2f}%")
    if args.trim:
        os.makedirs(os.path.dirname(os.path.abspath(args.trim)),
                    exist_ok=True)
        with open(args.trim, "w") as f:
            json.dump(trimmed(trace, args.trim_seconds), f)
    ok = gap_busy <= 0.01 and gap_idle <= 0.01 and bare < 0.10
    print("agree" if ok else "DO NOT AGREE")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
