"""Per-op timelines: the attribution profiler's measurement substrate.

An :class:`OpTimeline` is the per-op record set every attribution question
reduces to — (op, lane, start, duration) per timed unit — produced by the
**timed execution mode** (:func:`stepped_timeline` over
``TraceExecutor.op_stepped``): each op of a schedule runs as its own jitted
sub-program against the buffer state the previous steps produced, timed
with the same fetch-fenced discipline the benchmarker uses (median of
``repeats`` walls minus the calibrated trivial-fetch overhead).

What stepped durations mean — and what they do not:

* every step is **serial** (a step completes before the next starts), so
  the durations are overlap-free "sum of parts" components; the *starts*
  on the records are NOT measured — they are reconstructed by the analysis
  layer (analysis.py) from the happens-before relation, which is exactly
  what makes the critical-path / overlap-efficiency numbers attributable
  to schedule decisions rather than to measurement accidents;
* each step pays one dispatch + fence round trip, and its fence is a full
  reduction over the op's written buffers — both are part of the measured
  step cost.  The stepped sum therefore *over*-counts what the ops cost
  inside the one fused whole-schedule program, which is the point: the gap
  between the stepped sum and the measured whole-program time IS the
  dispatch overhead mega-kernelization removes (the MPK baseline number,
  ROADMAP "Mega-kernelize").
* sync ops are zero-duration records (token bookkeeping compiles to
  nothing timeable alone); split-kernel post→await groups are one record
  covering all member positions (the wait closure cannot cross a program
  boundary — see ``TraceExecutor.op_stepped``).

:func:`traced_timeline` is the other source, where the backend's profile
has an ``XLA Ops`` line (a TPU): ONE profiled dispatch of the *real*
repeat-n program, its device time cut by the executor's vertex scopes
(xplane.py ``device_by_vertex``) into the same records.  It times the
program that was measured, kernels fused and overlapped as XLA left them,
and runs on a mesh; the stepped mode stays for the backends without that
line (the CPU), where it is the only per-op clock.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer

# floor (µs) for a timed step the overhead subtraction pushed to <= 0: keeps
# every downstream ratio (overlap efficiency, per-lane shares) well-defined
# without inventing measurable time
MIN_DUR_US = 1e-3


@dataclass
class OpRecord:
    """One timed unit of a schedule: a single op, or a split-kernel
    post→await group (``positions`` then spans every member)."""

    name: str
    desc: str
    kind: str  # "device" | "host" | "sync"
    lane: Optional[int]  # lane id for device ops, None = host chain
    positions: Tuple[int, ...]
    dur_us: float = 0.0
    start_us: float = 0.0  # reconstructed by analysis.py, 0 until assigned

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "desc": self.desc,
            "kind": self.kind,
            "lane": self.lane,
            "positions": list(self.positions),
            "start_us": round(self.start_us, 4),
            "dur_us": round(self.dur_us, 4),
        }

    @classmethod
    def from_json(cls, j: Dict[str, Any]) -> "OpRecord":
        return cls(name=j["name"], desc=j.get("desc", j["name"]),
                   kind=j["kind"], lane=j.get("lane"),
                   positions=tuple(j["positions"]),
                   dur_us=float(j.get("dur_us", 0.0)),
                   start_us=float(j.get("start_us", 0.0)))


@dataclass
class OpTimeline:
    """The (op, lane, start, duration) record set for one schedule."""

    records: List[OpRecord] = field(default_factory=list)
    schedule: str = ""  # schedule_id digest (bench/benchmarker.py)
    source: str = "stepped"  # "stepped" | "traced" | "synthetic"
    n_ops: int = 0
    repeats: int = 0
    fetch_overhead_us: float = 0.0

    def timed(self) -> List[OpRecord]:
        """The non-sync records (the units that carry measured duration)."""
        return [r for r in self.records if r.kind != "sync"]

    def to_json(self) -> Dict[str, Any]:
        return {
            "schedule": self.schedule,
            "source": self.source,
            "n_ops": self.n_ops,
            "repeats": self.repeats,
            "fetch_overhead_us": round(self.fetch_overhead_us, 4),
            "records": [r.to_json() for r in self.records],
        }

    @classmethod
    def from_json(cls, j: Dict[str, Any]) -> "OpTimeline":
        return cls(records=[OpRecord.from_json(r) for r in j["records"]],
                   schedule=j.get("schedule", ""),
                   source=j.get("source", "stepped"),
                   n_ops=int(j.get("n_ops", 0)),
                   repeats=int(j.get("repeats", 0)),
                   fetch_overhead_us=float(j.get("fetch_overhead_us", 0.0)))


def _record_meta(ops, positions) -> Tuple[str, str, str, Optional[int]]:
    """(name, desc, kind, lane) of the unit covering ``positions``."""
    from tenzing_tpu.core.operation import BoundDeviceOp

    members = [ops[p] for p in positions]
    non_sync = [o for o in members
                if not getattr(o, "is_sync", lambda: False)()]
    if not non_sync:
        op = members[0]
        lanes = op.lanes() if hasattr(op, "lanes") else []
        return op.desc(), op.desc(), "sync", (lanes[0].id if lanes else None)
    name = "+".join(o.name() for o in non_sync)
    desc = non_sync[0].desc() if len(non_sync) == 1 else name
    dev = next((o for o in non_sync if isinstance(o, BoundDeviceOp)), None)
    if dev is not None:
        return name, desc, "device", dev.lane().id
    return name, desc, "host", None


def fetch_overhead_us() -> float:
    """Median wall of a trivial compiled fetch (dispatch + fetch round trip), in
    microseconds — the same calibration the EmpiricalBenchmarker subtracts
    per measurement, re-derived here so the profiler needs no benchmarker."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros(())
    jax.device_get(f(x))  # compile
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.device_get(f(x))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def stepped_timeline(executor, order, repeats: int = 7) -> OpTimeline:
    """Time every op of ``order`` through the executor's per-op stepped
    mode (``TraceExecutor.op_stepped``) and return the
    :class:`OpTimeline` (starts unassigned — run analysis.py over it).

    Each step is compiled+warmed once (excluded), then timed ``repeats``
    times against the SAME input buffers; the recorded duration is the
    median wall minus the calibrated fetch overhead, floored at
    ``MIN_DUR_US``.  Buffer state advances once per step, so later ops see
    exactly the values the schedule produces.
    """
    import jax

    from tenzing_tpu.bench.benchmarker import schedule_id

    tr = get_tracer()
    sid = schedule_id(order)
    with tr.span("attrib.profile", schedule=sid, repeats=repeats) as sp:
        steps = executor.op_stepped(order)
        ops = order.vector()
        overhead_us = fetch_overhead_us()
        bufs = executor.init_bufs
        records: List[OpRecord] = []
        n_timed = 0
        for positions, fn in steps:
            name, desc, kind, lane = _record_meta(ops, positions)
            if fn is None:
                records.append(OpRecord(name=name, desc=desc, kind=kind,
                                        lane=lane, positions=positions))
                continue

            def run(b=bufs, fn=fn):
                fence, out = fn(b)
                jax.device_get(fence)
                # host-space writes don't feed the fence; block on the rest
                jax.block_until_ready(out)
                return out

            with tr.span("attrib.step", unit=name):
                out = run()  # compile + warm, excluded from timing
                walls = []
                for _ in range(max(1, repeats)):
                    t0 = time.perf_counter()
                    run()
                    walls.append(time.perf_counter() - t0)
                walls.sort()
                dur_us = max(walls[len(walls) // 2] * 1e6 - overhead_us,
                             MIN_DUR_US)
            records.append(OpRecord(name=name, desc=desc, kind=kind,
                                    lane=lane, positions=positions,
                                    dur_us=dur_us))
            n_timed += 1
            bufs = out
        sp.set("n_timed", n_timed)
        get_metrics().counter("attrib.profiles").inc()
        get_metrics().counter("attrib.steps").inc(n_timed)
    return OpTimeline(records=records, schedule=sid, source="stepped",
                      n_ops=len(ops), repeats=repeats,
                      fetch_overhead_us=overhead_us)


def traced_timeline(executor, order, n: int = 8,
                    trace_dir: Optional[str] = None) -> OpTimeline:
    """One profiled dispatch of ``order``'s real repeat-n program (``n``
    iterations), the first device's time cut by the executor's vertex
    scopes into one :class:`OpRecord` a schedule position: a vertex's
    ``dur_us`` is its ``tie + apply + join`` self time an iteration (a sync
    op's is 0: what its hook emits is the executor's).  Starts unassigned —
    run analysis.py over it, as over a stepped timeline.

    The names come from the executable that ran (``compiled_n``): events
    with no scope stat of their own are named by their instruction.
    Raises ``RuntimeError`` where the profile has no ``XLA Ops`` line (the
    CPU backend): use :func:`stepped_timeline` there."""
    import tempfile

    import jax

    from tenzing_tpu.bench.benchmarker import schedule_id
    from tenzing_tpu.obs.attrib import hlo, xplane
    from tenzing_tpu.obs.scopes import SCOPE, vertex_scope

    tr = get_tracer()
    sid = schedule_id(order)
    ops = order.vector()
    with tr.span("attrib.profile", schedule=sid, traced_n=n) as sp:
        run_n = executor.prepare_n(order)
        run_n(1)  # its first call, and a warm dispatch path
        with tempfile.TemporaryDirectory() as tmp:
            out = trace_dir or tmp
            jax.profiler.start_trace(out)
            try:
                run_n(n)
            finally:
                jax.profiler.stop_trace()
            trace = xplane.load_xplane(out, hlo.scopes_of_text(
                executor.compiled_n(order).as_text()))
        if not xplane.device_planes(trace):
            raise RuntimeError(
                "traced_timeline: the profile has no XLA Ops line (backend "
                f"{jax.default_backend()!r}); use stepped_timeline")
        _, events = xplane.dispatch_events(trace)
        by_vertex = dict(xplane.device_by_vertex(events)["vertices"])
        records: List[OpRecord] = []
        for p in range(len(ops)):
            name, desc, kind, lane = _record_meta(ops, (p,))
            parts = by_vertex.get(vertex_scope(ops[p].name())[len(SCOPE):])
            dur_us = 0.0
            if kind != "sync":
                dur_us = max(sum((parts or {}).values()) / n * 1e6,
                             MIN_DUR_US)
            records.append(OpRecord(name=name, desc=desc, kind=kind,
                                    lane=lane, positions=(p,),
                                    dur_us=dur_us))
        n_timed = sum(r.kind != "sync" for r in records)
        sp.set("n_timed", n_timed)
        get_metrics().counter("attrib.profiles").inc()
        get_metrics().counter("attrib.steps").inc(n_timed)
    return OpTimeline(records=records, schedule=sid, source="traced",
                      n_ops=len(ops), repeats=n, fetch_overhead_us=0.0)
