"""A cell's device time by the vertex of the schedule that made it, on the
chip.

    chiprun [--chips 4] -- python benchmarks/tests/op_scopes_on_chip.py \
        --workload <cell> [--seed N] [--stats] [--keep DIR]

Builds the cell's stack as a run does (``harness/cell.py``'s functions), on
an empty compile cache (the persistent cache stays off: a cached executable
carries the names of whoever compiled it first), and takes naive, the
hints' start point where the configuration has phases, and one
post-all-before-await-any schedule for each transfer engine where it has
engines.  Each is compiled ahead of time (its first call timed, its
temporaries read from the ``executor.first_call`` span), clocked by the
benchmark's two-point clock, and profiled for ONE dispatch at the clock's
repeat count.  Printed for each, from the program's own readers
(``tenzing_tpu/obs/attrib/xplane.py`` ``device_by_vertex``, ``hlo.py``
``loop_ops_by_scope``): per vertex and part the first device's ms an
iteration beside the MB the part's instructions write an iteration, the
executor's own and XLA's unscoped operations by kind, and their sum beside
the ``XLA Modules`` time of the dispatch.  ``--stats`` dumps, for one event
of each operation kind, every stat the profile holds (none carries the
scope: the events are named by instruction from the compiled text);
``--keep`` writes each schedule's compiled text and a cut of its
trace in the neutral form (``tests/data``).
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

PARTS = ("apply", "tie", "join")


def schedules(built):
    """``(label, schedule)``: naive, the start point, the engine overlaps."""
    from tenzing_tpu.solve.local import drive, phase_policy

    yield "naive", built.naive
    h = built.hints
    if h.get("phases"):
        yield "start", drive(built.graph, h["platform"], phase_policy(
            h["platform"], h["phases"], h.get("prefer")))[0]
    for engine in h.get("engines", []):
        from tenzing_tpu.models.halo import engine_overlap_order

        yield f"overlap.{engine}", engine_overlap_order(
            built.graph, h["platform"], engine)


def first_call_attrs(t0: float) -> dict:
    """The attrs of the newest ``executor.first_call`` span since ``t0``."""
    from tenzing_tpu.obs.tracer import get_tracer

    spans = [s for s in get_tracer().spans()
             if s.name == "executor.first_call" and s.t0 >= t0]
    return dict(spans[-1].attrs) if spans else {}


def written_mb(text: str):
    """``{(vertex, part): MB}`` and ``{kind: MB}`` (unscoped) a loop
    iteration's instructions write, and the fusions made across owners."""
    from tenzing_tpu.obs.attrib import hlo, xplane

    owned, unscoped, mixed = {}, {}, []
    for op in hlo.loop_ops_by_scope(text):
        if op.vertex == hlo.UNSCOPED:
            kind = xplane.op_kind(op.name)
            unscoped[kind] = unscoped.get(kind, 0.0) + op.bytes / 1e6
        else:
            key = (op.vertex, op.part)
            owned[key] = owned.get(key, 0.0) + op.bytes / 1e6
        if op.mixed:
            mixed.append(op)
    return owned, unscoped, mixed


def table(by: dict, n: int, text: str) -> list:
    owned, unscoped_mb, mixed = written_mb(text)
    ms = lambda s: 1e3 * s / n
    lines = [f"{'apply ms':>10} {'tie ms':>9} {'join ms':>9} | "
             f"{'apply MB':>10} {'tie MB':>9} {'join MB':>9}  vertex "
             "(MB: its instructions' results; an aliased result is whole)"]
    timed = dict(by["vertices"])
    for vertex in sorted(set(timed) | {v for v, _ in owned} - {"executor"}):
        parts = timed.get(vertex, {})
        lines.append(
            " ".join(f"{ms(parts.get(p, 0.0)):{w}.4f}"
                     for p, w in zip(PARTS, (10, 9, 9))) + " | "
            + " ".join(f"{owned.get((vertex, p), 0.0):{w}.3f}"
                       for p, w in zip(PARTS, (10, 9, 9))) + f"  {vertex}")
    lines.append("executor, ms an iteration: " + ", ".join(
        f"{kind} {ms(s):.4f}" for kind, s in by["executor"]))
    for kind, s in by["unscoped"]:
        lines.append(f"{ms(s):10.4f} ms {unscoped_mb.get(kind, 0.0):12.3f} "
                     f"MB  unscoped {kind}")
    for op in mixed[:12]:
        lines.append(f"mixed: {op.name} ({op.bytes / 1e6:.3f} MB) to "
                     f"{op.vertex}/{op.part}, also {', '.join(op.mixed)}")
    return lines


def stats_dump(trace_dir: Path) -> list:
    """Every stat of one event of each operation kind on the first device,
    and the device plane's line names (step 0 of ISSUE 38)."""
    import glob

    from jax.profiler import ProfileData

    from tenzing_tpu.obs.attrib import xplane

    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    plane = next((p for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/device:")), None)
    if plane is None:
        return ["no device plane in the profile"]
    lines = [f"lines of {plane.name}: "
             + ", ".join(f"{ln.name} ({len(list(ln.events))})"
                         for ln in plane.lines)]
    seen = set()
    for ln in plane.lines:
        if ln.name != xplane.OPS_LINE:
            continue
        for ev in ln.events:
            kind = xplane.op_kind(ev.name)
            if kind not in seen:
                seen.add(kind)
                lines.append(f"{kind}: " + json.dumps(
                    {k: str(v)[:160] for k, v in ev.stats}))
    return lines


def trimmed(trace: dict, events: list, keep: int = 400) -> dict:
    """The profiled dispatch's first ``keep`` device operations and the
    program's host spans, from the dispatch's start: a repository's size."""
    from tenzing_tpu.obs.attrib import xplane

    events = sorted(events, key=lambda e: e[1])[:keep]
    a, b = events[0][1], max(e[2] for e in events)
    planes = [{"name": xplane.device_planes(trace)[0]["name"], "lines": [
        {"name": xplane.OPS_LINE,
         "events": [[xplane.instruction_name(e[0]), e[1] - a,
                     min(e[2], b) - a, *e[3:]] for e in events]}]}]
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        lines = [{"name": ln["name"], "events": [
            [n, max(s, a) - a, min(e, b) - a] for n, s, e, *_ in ln["events"]
            if n.startswith(xplane.SPAN_PREFIX) and e > a and s < b]}
            for ln in p["lines"]]
        lines = [ln for ln in lines if ln["events"]]
        if lines:
            planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 3838)
    ap.add_argument("--only", help="one schedule's label")
    ap.add_argument("--stats", action="store_true")
    ap.add_argument("--keep")
    ap.add_argument("--first-calls", type=int, default=0,
                    help="only time that many first calls of each "
                         "schedule's program, each on an executor of its "
                         "own (runs on a checkout without the scopes too)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    import jax

    from benchmarks.harness import cell as cell_mod
    from tenzing_tpu.obs.tracer import configure

    configure(enabled=True)  # first calls outside a profiler session too
    cell = cell_mod.load_cell(args.workload)
    devices = cell_mod.find_devices(cell.chips, args.rehearse_cpu)
    cell_mod.persistent_cache(False)
    config = cell.config
    if args.rehearse_cpu:
        config = cell_mod.toy_shapes(config)
    builder = cell_mod.load_module("builders", config["builder"])
    reference = (cell_mod.load_module("references", config["reference"])
                 if config.get("reference") else None)
    built = builder.build(config, args.seed, devices, reference)
    ex = built.executor
    ex.init_bufs = cell_mod.committed(ex.init_bufs)
    if args.first_calls:
        from tenzing_tpu.obs.tracer import get_tracer

        for label, order in schedules(built):
            if args.only and label != args.only:
                continue
            rows = []
            for _ in range(args.first_calls):
                fresh = type(ex)(ex.platform, ex.init_bufs)
                t0 = time.perf_counter()
                fresh.precompile(order)
                whole = time.perf_counter() - t0
                parts = {s.name: s.t1 - s.t0 for s in get_tracer().spans()
                         if s.t0 >= t0 and s.t1 is not None}
                t1 = time.perf_counter()
                cell_mod.timed_program(fresh, order).memory_analysis()
                rows.append(f"{whole:.3f} (lower "
                            f"{parts.get('executor.lower', 0):.3f}, compile "
                            f"{parts.get('executor.xla_compile', 0):.3f}, "
                            f"sizes {time.perf_counter() - t1:.4f})")
            print(f"first calls {args.workload} {label} "
                  f"({len(order.vector())} ops): " + " ".join(rows))
        return 0

    from benchmarks.harness import clock as clock_mod
    from tenzing_tpu.obs.attrib import hlo, xplane
    from tenzing_tpu.obs.metrics import get_metrics

    out_dir = ROOT / "benchmarks" / "out" / f"{args.workload}.op_scopes"
    summary = {}
    if args.keep:
        Path(args.keep).mkdir(parents=True, exist_ok=True)
    for label, order in schedules(built):
        if args.only and label != args.only:
            continue
        t0 = time.perf_counter()
        ex.precompile(order)
        first_call_s = time.perf_counter() - t0
        sizes = first_call_attrs(t0)
        text = cell_mod.timed_program(ex, order).as_text()
        clock = clock_mod.two_point(ex.prepare_n(order))
        n = clock["n"]
        trace_dir = out_dir / label
        cell_mod.start_trace(trace_dir)
        try:
            ex.prepare_n(order)(n)
        finally:
            jax.profiler.stop_trace()
        print(f"== {args.workload} {label}, seed {args.seed}: "
              f"{len(order.vector())} ops, first call {first_call_s:.3f} s "
              f"(ahead of time, empty cache), temporaries "
              f"{sizes.get('temp_bytes', 0) / 1e9:.3f} GB, clock "
              f"{clock['iter_s'] * 1e3:.4f} ms an iteration (n={n})")
        if args.stats:
            print("\n".join(stats_dump(trace_dir)))
        trace = xplane.load_xplane(trace_dir, hlo.scopes_of_text(text))
        if args.rehearse_cpu or not xplane.device_planes(trace):
            print("no device in the trace (a rehearsal on the CPU): the "
                  "compiled text's owners alone")
            print("\n".join(table(xplane.device_by_vertex([]), 1, text)))
            continue
        module_s, events = xplane.dispatch_events(trace)
        by = xplane.device_by_vertex(events)
        total = by["apply_s"] + by["executor_s"] + by["unscoped_s"]
        print(f"{len(events)} operations in the dispatch")
        print("\n".join(table(by, n, text)))
        copies = xplane.device_by_vertex(
            e for e in events if xplane.op_kind(e[0]).startswith("copy"))
        print("copy operations' ms an iteration by owner: " + ", ".join(
            [f"{v} {1e3 * sum(p.values()) / n:.4f}"
             for v, p in copies["vertices"]]
            + [f"unscoped {k} {1e3 * s / n:.4f}"
               for k, s in copies["unscoped"]]))
        print(f"sum {1e3 * total / n:.4f} ms an iteration (apply "
              f"{1e3 * by['apply_s'] / n:.4f}, executor "
              f"{1e3 * by['executor_s'] / n:.4f}, unscoped "
              f"{1e3 * by['unscoped_s'] / n:.4f}) against XLA Modules "
              f"{1e3 * module_s / n:.4f} ({100 * total / module_s:.2f}%)")
        summary[label] = {
            "n": n, "iter_ms": clock["iter_s"] * 1e3,
            "first_call_s": first_call_s, "sizes": sizes,
            "module_ms_per_iter": 1e3 * module_s / n,
            "by_vertex_ms_per_iter": {
                v: {p: 1e3 * s / n for p, s in parts.items()}
                for v, parts in by["vertices"]},
            "executor_ms_per_iter": {k: 1e3 * s / n
                                     for k, s in by["executor"]},
            "unscoped_ms_per_iter": {k: 1e3 * s / n
                                     for k, s in by["unscoped"]}}
        if args.keep:
            keep = Path(args.keep)
            (keep / f"{args.workload}.{label}.hlo.txt").write_text(text)
            (keep / f"{args.workload}.{label}.trace.json").write_text(
                json.dumps(trimmed(trace, events)))
    gauge = get_metrics().gauge("executor.program_temp_bytes_max").value
    print(f"executor.program_temp_bytes_max {gauge / 1e9:.3f} GB")
    if args.keep:
        (Path(args.keep) / f"{args.workload}.summary.json").write_text(
            json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
