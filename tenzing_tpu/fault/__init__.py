"""Fault tolerance for the search runtime (ISSUE 3, docs/robustness.md).

The paper's core loop — empirically benchmark thousands of candidate
schedules on real hardware across all ranks — is exactly the loop most
exposed to real-machine flakiness.  This package makes a multi-hour search
survive a flaky runtime, a hung compile, a broken candidate, a dead chip,
and a Ctrl-C without losing its corpus:

* :mod:`~tenzing_tpu.fault.errors` — the failure taxonomy (transient /
  deterministic / device-lost) and :func:`classify_error`.
* :mod:`~tenzing_tpu.fault.backoff` — the shared bounded-retry helper
  (exponential backoff + jitter, ``fault.retry`` telemetry).
* :mod:`~tenzing_tpu.fault.quarantine` — persistent per-schedule quarantine
  of deterministically-broken candidates.
* :mod:`~tenzing_tpu.fault.resilient` — :class:`ResilientBenchmarker`:
  watchdog timeout, classified retries, rank-coherent failure agreement,
  graceful degradation to a fallback benchmarker.
* :mod:`~tenzing_tpu.fault.checkpoint` — atomic checkpoint/resume: the
  measurement journal + solver cursors (``bench.py --checkpoint --resume``).
* :mod:`~tenzing_tpu.fault.inject` — seeded chaos:
  :class:`FaultInjectingBenchmarker` (``bench.py --inject-faults``).
"""

from tenzing_tpu.fault.backoff import BackoffPolicy, retry_call
from tenzing_tpu.fault.checkpoint import (
    CheckpointError,
    JournalingBenchmarker,
    SearchCheckpoint,
    atomic_write_json,
    read_checked_json,
)
from tenzing_tpu.fault.errors import (
    DeterministicScheduleError,
    DeviceLostError,
    FaultClass,
    MeasurementTimeout,
    QuarantinedScheduleError,
    StoreLockTimeout,
    TransientError,
    UnsoundScheduleError,
    classify_error,
    fault_code,
)
from tenzing_tpu.fault.inject import (
    FaultInjectingBenchmarker,
    InjectSpec,
    InjectedDeterministicError,
    InjectedTransientError,
    corrupt_schedule,
    parse_inject_specs,
)
from tenzing_tpu.fault.quarantine import Quarantine
from tenzing_tpu.fault.resilient import ResilientBenchmarker

__all__ = [
    "BackoffPolicy",
    "CheckpointError",
    "DeterministicScheduleError",
    "DeviceLostError",
    "FaultClass",
    "FaultInjectingBenchmarker",
    "InjectSpec",
    "InjectedDeterministicError",
    "InjectedTransientError",
    "JournalingBenchmarker",
    "MeasurementTimeout",
    "Quarantine",
    "QuarantinedScheduleError",
    "ResilientBenchmarker",
    "SearchCheckpoint",
    "StoreLockTimeout",
    "TransientError",
    "UnsoundScheduleError",
    "atomic_write_json",
    "classify_error",
    "corrupt_schedule",
    "fault_code",
    "parse_inject_specs",
    "read_checked_json",
    "retry_call",
]
