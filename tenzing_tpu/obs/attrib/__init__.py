"""Schedule attribution profiler: per-op timelines, critical-path and
overlap analytics, winner-vs-naive explanation (ISSUE 6).

PR 1's telemetry answers "what happened when" at subsystem granularity;
this package answers **why a schedule is fast or slow, per op and per
decision**:

* :mod:`~tenzing_tpu.obs.attrib.timeline` — the timed execution mode:
  per-op stepped sub-programs over ``TraceExecutor.op_stepped`` produce an
  :class:`OpTimeline` of (op, lane, start, duration) records; where a
  profile has the device's own operations (a TPU), ``traced_timeline``
  makes the same records from one profiled dispatch of the real repeat-n
  program, cut by the executor's vertex scopes;
* :mod:`~tenzing_tpu.obs.attrib.analysis` — Gantt reconstruction on the
  verifier's happens-before relation, critical path, overlap efficiency,
  dispatch overhead (the MPK baseline number), roofline join;
* :mod:`~tenzing_tpu.obs.attrib.explain` — winner-vs-naive decision diff
  (lanes / reorder / sync removal / menu choices), the three-term timing
  decomposition, ``explain.json``, per-lane Perfetto tracks;
* :mod:`~tenzing_tpu.obs.attrib.xplane` — the reader of a jax.profiler
  trace: device busy and idle, device time by the schedule's vertex, and
  each idle gap given to the program's own span (``python -m tenzing_tpu.obs.attrib.xplane <trace dir>``; not
  imported here, so that ``-m`` runs it as a script of its own).

Driver surface: ``bench.py --profile-winner`` stamps the ``attrib`` block
into the driver JSON; ``python -m tenzing_tpu.obs.report`` mines corpora
and runs the regression check.  See docs/observability.md "Attribution".

Deliberately NOT imported from ``tenzing_tpu.obs`` eagerly: ``obs`` stays
stdlib-only importable; everything jax-touching here is lazy.
"""

from tenzing_tpu.obs.attrib.analysis import Attribution, analyze, lane_label
from tenzing_tpu.obs.attrib.explain import (
    diff_schedules,
    explain,
    timeline_trace_events,
    write_explain,
)
from tenzing_tpu.obs.attrib.timeline import (
    OpRecord,
    OpTimeline,
    fetch_overhead_us,
    stepped_timeline,
    traced_timeline,
)

__all__ = [
    "Attribution",
    "OpRecord",
    "OpTimeline",
    "analyze",
    "diff_schedules",
    "explain",
    "fetch_overhead_us",
    "lane_label",
    "stepped_timeline",
    "timeline_trace_events",
    "traced_timeline",
    "write_explain",
]
