"""The program's own spans and counters, as the per-layer readers take them.

``harness/cell.py`` profiles a slice in mid-window, and the program's tracer
(``tenzing_tpu/obs/tracer.py``) records while a profiler session is active:
so after a ``--trace 1`` run the process-global tracer holds the slice's
spans, each with its start and end on ``time.perf_counter()``, the clock the
harness stamps its candidates on.  The traced finalist of the epilogue leaves
spans too, after the window: a reader takes those whose start lies inside the
window (``record["window"]``: from the last in-window completion less
``span_s`` to that completion).  On a program without such spans (the parent
of the PR that brought them, ``--trace 0``, a window too short for a slice)
there is nothing to read and every reader returns ``None``.
"""

from __future__ import annotations

import statistics

FIRST_CALL = "executor.first_call"
MEASUREMENT_CALLS = ("bench.benchmark", "bench.batch")


def window_bounds(record) -> tuple:
    w = record["window"]
    t_last = max(c["t1"] for c in w["candidates"] if not c.get("late"))
    return t_last - w["span_s"], t_last


def window_spans(record) -> list:
    """The program's finished spans that start inside the window, in order
    of start; ``[]`` where the tracer recorded none."""
    from tenzing_tpu.obs.tracer import get_tracer

    lo, hi = window_bounds(record)
    spans = [s for s in get_tracer().spans()
             if getattr(s, "t1", None) is not None and lo <= s.t0 <= hi]
    return sorted(spans, key=lambda s: s.t0)


def seconds(span) -> float:
    return span.t1 - span.t0


def named(spans, *names) -> list:
    return [s for s in spans if s.name in names]


def children(spans, parents, name) -> list:
    """The spans called ``name`` whose parent is one of ``parents``."""
    ids = {p.span_id for p in parents}
    return [s for s in spans if s.name == name and s.parent_id in ids]


def foreground(spans) -> list:
    """The spans of the thread that dispatches (``bench.dispatch``): the
    measurement owner.  Prefetch workers make spans of their own."""
    tids = [s.tid for s in spans if s.name == "bench.dispatch"]
    if not tids:
        return []
    fg = statistics.mode(tids)
    return [s for s in spans if s.tid == fg]


def whole_first_calls(spans) -> list:
    """``[(first call, {part name: span})]`` of the first calls recorded
    whole.  A prefetch worker's first call can straddle an end of the
    profiled slice: opened before it, the call itself is not recorded and
    its later parts have no parent here; closed after it, its later parts
    are missing.  Either would bend a mean of parts over calls."""
    out = []
    for fc in named(spans, FIRST_CALL):
        parts = {s.name: s for s in spans if s.parent_id == fc.span_id}
        need = {"executor.lower", "executor.xla_compile"}
        if not fc.attrs.get("aot"):
            need.add("executor.first_run")
        if need <= set(parts):
            out.append((fc, parts))
    return out


def part_seconds_per_program(record, part: str):
    """Mean seconds of ``part`` over the whole first calls."""
    whole = whole_first_calls(window_spans(record))
    if not whole:
        return None
    return sum(seconds(parts[part]) for _, parts in whole) / len(whole)


def counter(name: str) -> int:
    from tenzing_tpu.obs.metrics import get_metrics

    return get_metrics().counter(name).value
