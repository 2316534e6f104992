"""Reduction of a profiler trace to device busy time, device operations and
idle gaps named by what the host was doing.

Works on a neutral form, so that it can be checked on a small recorded trace
(``benchmarks/tests/data``)::

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, end_ns], ...]}]}]}

:func:`load_xplane` makes that form from the ``.xplane.pb`` the JAX profiler
writes.  Device planes are named ``/device:<KIND>:<i>``; on a TPU the line
``XLA Ops`` holds one event per executed operation (a ``while`` holds its
body's operations nested inside it) and ``XLA Modules`` one per executed
program.  Host annotations written by the benchmark (``tzb:<span>``) are on
the host plane, on the line of the thread that made them.
"""

from __future__ import annotations

import glob
import os
from pathlib import Path

SPAN_PREFIX = "tzb:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir) -> dict:
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
            lines.append({"name": line.name or "", "events": evs})
        planes.append({"name": plane.name or "", "lines": lines})
    return {"planes": planes}


def merge_intervals(ivs):
    """Coalesce intervals so that busy time counts each nanosecond once."""
    out = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def device_planes(trace: dict):
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def _line(plane: dict, name: str):
    return next((ln for ln in plane["lines"] if ln["name"] == name), None)


def self_times(events):
    """``{name: ns}`` of one line's events with nested children taken out of
    their parents (a ``while`` would otherwise own its whole body)."""
    out = {}
    stack = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return out


def host_spans(trace: dict):
    """``[(span name, start_ns, end_ns, line)]`` of the benchmark's own
    annotations, from every plane that is not a device's.  ``line`` is the
    line's position ``(plane, line)``: every Python thread's line has the
    same name."""
    out = []
    for pi, p in enumerate(trace["planes"]):
        if p["name"].startswith("/device:"):
            continue
        for li, ln in enumerate(p["lines"]):
            for name, a, b in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], a, b, (pi, li)))
    return out


def op_kind(name: str) -> str:
    """A device operation's name cut to what is stable from program to
    program: ``%copy.106 = f32[...] copy(...)`` -> ``copy``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return ".".join(p for p in head.split(".") if not p.isdigit()) or head


FOREGROUND = ("measure", "solver")  # the main thread is always in one
TOP = 10                            # entries a breakdown list may have


def reduce_window(trace: dict) -> dict:
    """Busy and idle time of the traced slice.

    The slice is what the benchmark's foreground spans cover (first start
    to last end): the profiler's own start-up and shutdown are outside it.
    ``busy_s`` is the union of device-operation intervals inside the slice,
    averaged over the device planes.  Each idle gap of the first device is
    given to the innermost benchmark span that the host's foreground thread
    was in at the time (``verify`` and ``first_call`` lie inside
    ``measure``), and ``unattributed`` where it was in none.
    """
    spans = host_spans(trace)
    fg = [s for s in spans if s[0] in FOREGROUND]
    planes = device_planes(trace)
    if not fg or not planes:
        return {}
    fg_line = max({s[3] for s in fg},
                  key=lambda ln: sum(1 for s in fg if s[3] == ln))
    fg_spans = [s for s in spans if s[3] == fg_line]
    w0 = min(s[1] for s in fg_spans if s[0] in FOREGROUND)
    w1 = max(s[2] for s in fg_spans if s[0] in FOREGROUND)
    busy = []
    merged_first = None
    ops = {}
    for p in planes:
        evs = [e for e in _line(p, OPS_LINE)["events"]
               if e[2] > w0 and e[1] < w1]
        clipped = [[max(a, w0), min(b, w1)] for _, a, b in evs]
        merged = merge_intervals(clipped)
        busy.append(sum(b - a for a, b in merged))
        if merged_first is None:
            merged_first = merged
            for name, ns in self_times(evs).items():
                ops[op_kind(name)] = ops.get(op_kind(name), 0) + ns
    # idle gaps of the first device
    gaps = []
    at = w0
    for a, b in merged_first:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if w1 > at:
        gaps.append((at, w1))
    # innermost span first: shortest span that covers the instant wins
    by_len = sorted(fg_spans, key=lambda s: s[2] - s[1])
    gap_ns = {}
    for ga, gb in gaps:
        cuts = sorted({ga, gb} | {t for s in fg_spans for t in (s[1], s[2])
                                  if ga < t < gb})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            name = next((s[0] for s in by_len if s[1] <= mid < s[2]),
                        "unattributed")
            gap_ns[name] = gap_ns.get(name, 0) + (b - a)
    rank = lambda d, k=TOP: [[name, v / 1e9] for name, v in
                             sorted(d.items(), key=lambda kv: -kv[1])[:k]]
    idle = rank(gap_ns)
    idle += [["host:" + n, v] for n, v in rank(
        runtime_in_gaps(trace, gaps), min(4, TOP - len(idle)))]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy) / len(busy) / 1e9,
            "n_devices": len(planes),
            "device_ops": rank(ops),
            "idle_gaps": idle}


def runtime_in_gaps(trace: dict, gaps) -> dict:
    """``{event name: ns}``: how long, inside the device's idle gaps, some
    thread of the runtime (host lines other than Python's) was in an event
    of that name.  Says what the host was doing below the benchmark's spans
    (a transfer being mapped, a compilation, a wait)."""
    by_name = {}
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            if ln["name"] == "python":
                continue
            for name, a, b in ln["events"]:
                if b > a and not name.startswith(SPAN_PREFIX):
                    by_name.setdefault(name, []).append((a, b))
    out = {}
    for name, ivs in by_name.items():
        total, gi = 0, 0
        for a, b in merge_intervals(ivs):
            while gi < len(gaps) and gaps[gi][1] <= a:
                gi += 1
            k = gi
            while k < len(gaps) and gaps[k][0] < b:
                total += min(b, gaps[k][1]) - max(a, gaps[k][0])
                k += 1
        if total:
            out[name] = total
    return out


def module_seconds(trace: dict, longest: int = None):
    """Durations, in order of start, of the programs the first device ran
    (the ``XLA Modules`` line): the device's own time for each dispatch.
    ``longest`` keeps only that many of the longest (a dispatch is preceded
    by tiny programs of its own, such as the conversion of its argument)."""
    planes = device_planes(trace)
    if not planes:
        return []
    line = _line(planes[0], MODULES_LINE)
    if line is None:
        return []
    evs = sorted(line["events"], key=lambda e: e[1])
    if longest is not None:
        keep = sorted(evs, key=lambda e: e[1] - e[2])[:longest]
        evs = [e for e in evs if e in keep]
    return [(name, (b - a) / 1e9) for name, a, b in evs]
