"""Reader of a ``jax.profiler`` trace: the device's idle time, given to the
program's own spans.

While a profiler session is active the tracer (obs/tracer.py) enters every
span it records as a ``jax.profiler.TraceAnnotation("tz:" + name)`` too, so
the xplane holds the program's spans on the device's clock, each on the line
of the thread that made it.  Profile a search (``jax.profiler.start_trace``
round it, or ``jax.profiler.trace``), then::

    python -m tenzing_tpu.obs.attrib.xplane <trace dir>

prints, for the slice the foreground thread's spans cover: device busy and
idle share; device seconds by operation kind, and by **the vertex of the
schedule that made each operation** (``device_by_vertex``: the executor's
``tz.<vertex>/{tie,apply,join}``, ``tz.fence`` and ``tz.sync.*`` scopes,
obs/scopes.py; what carries none is XLA's own, by kind); and **each idle gap of the
device given to the innermost ``tz:`` span the foreground thread was in**,
with, for every such span name, what the other threads' spans were in
meanwhile (a foreground ``pipeline.wait`` against a prefetch worker's
``executor.xla_compile``).  The foreground thread is the one that dispatches
(``bench.dispatch``): the measurement owner.

The reduction works on a neutral form, so it can be checked on a hand-made
trace and on a small one recorded on a chip (tests/data)::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, end_ns(, scope)], ...]}]}]}

``scope`` (optional, device events only) is the operation's name stack as
the program that ran carries it.  A TPU's ``XLA Ops`` events hold no stat
with it (device offset and duration alone; the device plane has no line
derived by name scope either: PERF.md, PR 38), so the scope is the
``op_name`` of the instruction the event is named after, read from the
compiled text of the program that ran (``--hlo <compiled text>``;
``hlo.scopes_of_text``).  Instruction names are a program's own, so the cut
by vertex is for a profile of ONE program's dispatches
(``timeline.traced_timeline``, ``benchmarks/tests/op_scopes_on_chip.py``).
The names are the executable's, never the schedule's: an executable read
from jax's persistent compile cache has the names of whoever compiled it
first (metadata is no part of the cache's key).
:func:`load_xplane` makes that form from the newest ``.xplane.pb`` under a
directory.  Device planes are named ``/device:<KIND>:<i>``; on a TPU their
line ``XLA Ops`` holds one event per executed operation (a ``while`` holds
its body's operations nested inside it).  The benchmark's own reduction
(``benchmarks/harness/trace.py``) is this algorithm over the harness's
``tzb:`` proxies; the program may not import it, so the two are kept in
step by ``benchmarks/tests/program_spans_on_chip.py``.
"""

from __future__ import annotations

import glob
import os
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence as Seq, Tuple

from tenzing_tpu.obs.scopes import EXECUTOR, owner_of
from tenzing_tpu.obs.tracer import SESSION_PREFIX as SPAN_PREFIX

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DISPATCH = "bench.dispatch"  # marks the foreground thread
UNATTRIBUTED = "unattributed"
TOP = 12                   # entries a printed list may have

Interval = Tuple[int, int, str]  # start_ns, end_ns, name


def instruction_name(event_name: str) -> str:
    """A device event's name as the compiled text names its instruction:
    ``%copy.106 = f32[...] copy(...)`` -> ``copy.106``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load_xplane(trace_dir, scopes: Optional[Dict[str, str]] = None) -> dict:
    """The neutral form of the newest ``.xplane.pb`` under ``trace_dir``.
    An ``XLA Ops`` event takes its scope from ``scopes`` (``{instruction
    name: op_name}``: ``hlo.scopes_of_text`` of the program that ran)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [[ev.name or "", int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns)] for ev in line.events]
            if scopes and line.name == OPS_LINE:
                for e in evs:
                    scope = scopes.get(instruction_name(e[0]))
                    if scope:
                        e.append(scope)
            lines.append({"name": line.name or "", "events": evs})
        planes.append({"name": plane.name or "", "lines": lines})
    return {"planes": planes}


def merge_intervals(ivs: Iterable[Seq[int]]) -> List[List[int]]:
    """Coalesce intervals so busy time and intersections count each
    nanosecond once."""
    out: List[List[int]] = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def dispatch_events(trace: dict) -> Tuple[float, List[list]]:
    """``(module seconds, its XLA Ops events)`` of the longest program the
    first device ran (the ``XLA Modules`` line): one profiled dispatch of a
    repeat-n program.  Without that line, every operation and their span."""
    lines = {ln["name"]: ln["events"]
             for ln in device_planes(trace)[0]["lines"]}
    events = lines[OPS_LINE]
    if not lines.get(MODULES_LINE):
        return (max(e[2] for e in events)
                - min(e[1] for e in events)) / 1e9, events
    _, a, b = max(lines[MODULES_LINE], key=lambda e: e[2] - e[1])[:3]
    return (b - a) / 1e9, [e for e in events if e[1] >= a and e[2] <= b]


def innermost(events: Iterable[Seq]) -> List[Interval]:
    """One line's nested events flattened to disjoint pieces, each named by
    the innermost event that covers it (a parent keeps only its self time),
    in order of start."""
    out: List[Interval] = []
    stack: List[list] = []  # [name, end, covered up to]

    def emit(name: str, a: int, b: int) -> None:
        if b > a:
            out.append((a, b, name))

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, at = stack.pop()
            emit(name, at, end)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, a, b in sorted(((e[0], e[1], e[2]) for e in events),
                             key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            b = min(b, stack[-1][1])  # a child cannot outlast its parent
            emit(stack[-1][0], stack[-1][2], a)
            stack[-1][2] = a
        stack.append([name, b, a])
    close(float("inf"))
    return sorted(out)


def op_kind(name: str) -> str:
    """A device operation's name cut to what is stable from program to
    program: ``%copy.106 = f32[...] copy(...)`` -> ``copy``."""
    head = instruction_name(name)
    return ".".join(p for p in head.split(".") if not p.isdigit()) or head


def device_by_vertex(events: Iterable[Seq]) -> dict:
    """One device's ``XLA Ops`` events (``[name, start, end(, scope)]``) cut
    by who made them: self seconds (nested operations taken out of their
    parents, as :func:`innermost` does) of each vertex of the schedule by
    part, then the executor's own (every vertex's ``tie`` and ``join``, the
    fence, the sync hooks) and what carries no ``tz.`` scope, by operation
    kind.  ``apply_s + executor_s + unscoped_s`` is the device's busy time
    over the events."""
    labelled = [((e[0], e[3] if len(e) > 3 else ""), e[1], e[2])
                for e in events]
    vertices: Dict[str, Dict[str, int]] = {}
    executor: Dict[str, int] = {}
    unscoped: Dict[str, int] = {}
    for a, b, (name, scope) in innermost(labelled):
        owner = owner_of(scope)
        if owner is None:
            kind = op_kind(name)
            unscoped[kind] = unscoped.get(kind, 0) + (b - a)
            continue
        vertex, part = owner
        if vertex != EXECUTOR:
            parts = vertices.setdefault(vertex, {})
            parts[part] = parts.get(part, 0) + (b - a)
        if vertex == EXECUTOR or part != "apply":
            executor[part] = executor.get(part, 0) + (b - a)
    apply_ns = sum(p.get("apply", 0) for p in vertices.values())
    return {
        "vertices": [[v, {k: ns / 1e9 for k, ns in sorted(p.items())}]
                     for v, p in sorted(vertices.items(),
                                        key=lambda kv: -sum(kv[1].values()))],
        "executor": _ranked(executor), "unscoped": _ranked(unscoped),
        "apply_s": apply_ns / 1e9,
        "executor_s": sum(executor.values()) / 1e9,
        "unscoped_s": sum(unscoped.values()) / 1e9}


def program_threads(trace: dict) -> Dict[Tuple[int, int], List[list]]:
    """``{(plane, line): [[span name, start_ns, end_ns], ...]}`` of the
    program's mirrored spans, one entry per host thread that made any (every
    Python thread's line has the same name, so a line goes by position)."""
    out: Dict[Tuple[int, int], List[list]] = {}
    for pi, p in enumerate(trace["planes"]):
        if p["name"].startswith("/device:"):
            continue
        for li, ln in enumerate(p["lines"]):
            evs = [[n[len(SPAN_PREFIX):], a, b] for n, a, b, *_ in ln["events"]
                   if n.startswith(SPAN_PREFIX) and b > a]
            if evs:
                out[(pi, li)] = evs
    return out


def overlaps(pieces: Seq[Interval], others: Seq[Interval]):
    """``(start, end, piece name, other name)`` for every overlap of two
    sorted lists of disjoint intervals."""
    k = 0
    for a, b, name in pieces:
        while k < len(others) and others[k][1] <= a:
            k += 1
        j = k
        while j < len(others) and others[j][0] < b:
            lo, hi = max(a, others[j][0]), min(b, others[j][1])
            if hi > lo:
                yield lo, hi, name, others[j][2]
            j += 1


def _ranked(d: Dict[str, float]) -> List[list]:
    return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]


def reduce_trace(trace: dict) -> dict:
    """Busy and idle time of the slice the foreground thread's spans cover
    (first start to last end: the profiler's own start-up and shutdown lie
    outside), device seconds by operation kind with nested operations taken
    out of their parents, and the idle gaps of the first device by the
    foreground's innermost span (module docstring).  ``{}`` where the trace
    holds no span of the program."""
    threads = program_threads(trace)
    if not threads:
        return {}
    fg = max(threads, key=lambda k: (
        sum(1 for e in threads[k] if e[0] == DISPATCH), len(threads[k])))
    w0 = min(e[1] for e in threads[fg])
    w1 = max(e[2] for e in threads[fg])
    out = {"slice_s": (w1 - w0) / 1e9, "n_threads": len(threads)}
    gaps: List[Interval] = [(w0, w1, "")]
    planes = device_planes(trace)
    if planes:
        busy, ops = [], {}
        for i, p in enumerate(planes):
            line = next(ln for ln in p["lines"] if ln["name"] == OPS_LINE)
            evs = [e for e in line["events"] if e[2] > w0 and e[1] < w1]
            merged = merge_intervals(
                [max(e[1], w0), min(e[2], w1)] for e in evs)
            busy.append(sum(b - a for a, b in merged))
            if i == 0:
                by_vertex = device_by_vertex(
                    [e[0], max(e[1], w0), min(e[2], w1), *e[3:]]
                    for e in evs)
                for a, b, name in innermost(evs):
                    kind = op_kind(name)
                    ops[kind] = ops.get(kind, 0) + (b - a)
                gaps, at = [], w0
                for a, b in merged:
                    if a > at:
                        gaps.append((at, a, ""))
                    at = max(at, b)
                if w1 > at:
                    gaps.append((at, w1, ""))
        out.update(busy_s=sum(busy) / len(busy) / 1e9, n_devices=len(planes),
                   device_ops=_ranked(ops), device_by_vertex=by_vertex)
    else:
        out.update(busy_s=0.0, n_devices=0, device_ops=[],
                   device_by_vertex=device_by_vertex([]))
    out["idle_s"] = out["slice_s"] - out["busy_s"]
    # the gaps, cut by the foreground's innermost spans
    idle: Dict[str, int] = {}
    named: List[Interval] = []
    for lo, hi, _, name in overlaps(gaps, innermost(threads[fg])):
        named.append((lo, hi, name))
        idle[name] = idle.get(name, 0) + (hi - lo)
    covered = sum(idle.values())
    total = sum(b - a for a, b, _ in gaps)
    if total > covered:
        idle[UNATTRIBUTED] = total - covered
    # what the other threads were in during each name's gaps
    meanwhile: Dict[str, Dict[str, int]] = {}
    for key, evs in threads.items():
        if key == fg:
            continue
        for lo, hi, name, other in overlaps(named, innermost(evs)):
            m = meanwhile.setdefault(name, {})
            m[other] = m.get(other, 0) + (hi - lo)
    out["idle_by_span"] = [
        {"span": name, "idle_s": s, "meanwhile": _ranked(
            meanwhile.get(name, {}))} for name, s in _ranked(idle)]
    return out


def render_by_vertex(by: dict, per: float = 1.0, unit: str = "s",
                     top: int = TOP) -> List[str]:
    """``device_by_vertex`` as lines, each number divided by ``per`` (a
    dispatch's repeat count gives time an iteration)."""
    if not (by["vertices"] or by["executor"]):
        return ["no tz. scope on the first device's operations: name them "
                "by instruction with --hlo <compiled text of the program "
                "that ran> (a program from before the scopes, or from the "
                "compile cache of one, has none to give)"]
    fmt = lambda s: f"{s / per:10.4f}"
    lines = [f"first device's {unit} by the schedule's vertex "
             "(apply | tie | join):"]
    for vertex, parts in by["vertices"][:top]:
        lines.append("  " + " ".join(
            fmt(parts.get(p, 0.0)) for p in ("apply", "tie", "join"))
            + f"  {vertex}")
    if len(by["vertices"]) > top:
        rest = by["vertices"][top:]
        lines.append("  " + " ".join(
            fmt(sum(p.get(part, 0.0) for _, p in rest))
            for part in ("apply", "tie", "join"))
            + f"  ({len(rest)} more vertices)")
    lines.append(f"  sums: apply {fmt(by['apply_s']).strip()}, executor "
                 f"{fmt(by['executor_s']).strip()} ("
                 + ", ".join(f"{k} {fmt(s).strip()}"
                             for k, s in by["executor"][:top])
                 + f"), unscoped {fmt(by['unscoped_s']).strip()}")
    lines += [f"  {fmt(s)}  unscoped {kind}"
              for kind, s in by["unscoped"][:top]]
    return lines


def render(red: dict) -> str:
    """The reduction as the text ``python -m`` prints."""
    if not red:
        return ("no tz: span in the trace: was the program run while the "
                "profiler session was active?")
    lines = [
        f"slice {red['slice_s']:.3f} s over {red['n_devices']} device(s), "
        f"{red['n_threads']} host thread(s) with spans: busy "
        f"{red['busy_s']:.3f} s, idle {red['idle_s']:.3f} s "
        f"({100 * red['idle_s'] / red['slice_s']:.1f}%)",
        "device seconds by operation kind:"]
    lines += [f"  {s:10.4f}  {kind}" for kind, s in red["device_ops"][:TOP]]
    lines += render_by_vertex(red["device_by_vertex"])
    lines.append("idle seconds by the foreground thread's innermost span "
                 "(other threads meanwhile, summed over threads):")
    for row in red["idle_by_span"][:TOP]:
        lines.append(f"  {row['idle_s']:10.4f}  {row['span']}")
        lines += [f"  {'':10}    {s:10.4f}  {other}"
                  for other, s in row["meanwhile"][:4]]
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    scopes = None
    if len(argv) == 3 and "--hlo" in argv[:2]:
        from tenzing_tpu.obs.attrib.hlo import scopes_of_text

        at = argv.index("--hlo")
        scopes = scopes_of_text(Path(argv.pop(at + 1)).read_text())
        argv.pop(at)
    if len(argv) != 1:
        sys.stderr.write(
            "usage: python -m tenzing_tpu.obs.attrib.xplane <trace dir> "
            "[--hlo <compiled text>]\n")
        return 2
    sys.stdout.write(
        render(reduce_trace(load_xplane(argv[0], scopes))) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
