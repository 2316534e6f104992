"""Seeded fault injection: the chaos substrate for tests and CI.

:class:`FaultInjectingBenchmarker` wraps any benchmarker and injects
failures *deterministically* from seeded draws — the same seed replays the
same fault schedule, so a chaos run is a reproducible experiment, not a
flake generator.  Five kinds (``bench.py --inject-faults kind:rate:seed``,
comma-separated to compose):

* ``transient`` — raises :class:`InjectedTransientError` on a seeded
  per-attempt coin flip (classified transient → the resilient wrapper
  retries).
* ``hang`` — sleeps ``hang_secs`` before proceeding on a seeded per-attempt
  coin flip (the stalled-RPC simulation): with a watchdog shorter than the
  hang, the wrapper's :class:`MeasurementTimeout` path fires; without one,
  the call is merely slow — both are realistic device-runtime behaviors.
* ``deterministic`` — fails by *schedule identity* (a hash of the schedule
  id and the seed, not a per-attempt draw): the same ``rate`` fraction of
  candidates always fails, exactly like a candidate that genuinely cannot
  compile — the quarantine's target.
* ``device_lost`` — raises :class:`~tenzing_tpu.fault.errors.DeviceLostError`
  on a seeded per-attempt coin flip (the degradation drill).
* ``corrupt`` — **mutates the candidate schedule** (drops or reorders one
  of its sync ops, :func:`corrupt_schedule`) by schedule identity before
  passing it on: the simulation of a schedule-handling bug — exactly what
  the independent soundness verifier (tenzing_tpu/verify) exists to catch.
  A corrupt injector therefore belongs *outside* the
  :class:`~tenzing_tpu.fault.resilient.ResilientBenchmarker` whose
  ``verifier`` gate must see (and quarantine) the mutated schedule;
  ``bench.py`` splits the spec list accordingly.  Only mutations the
  configured ``unsound_check`` confirms detectable count as injected —
  dropping a genuinely redundant sync produces a still-correct schedule,
  which is no fault at all.

Injection draws are **rank-agreed by construction** (the multi-host chaos
item of ROADMAP.md): per-attempt kinds draw from a hash of (kind, seed,
schedule identity, per-schedule attempt counter) instead of per-process RNG
state.  Every rank benchmarks the same broadcast schedule sequence, so the
counters — and with them every draw — agree across hosts without
communication, and the rank-coherent ``agree_fault`` protocol
(fault/resilient.py) can be chaos-tested under a real control plane
(tests/test_multihost.py).  The counters also survive nothing: a restarted
process re-counts from zero, which is exactly what the deterministic
search's resume (re-executing the same query sequence) needs to replay the
same faults.
"""

from __future__ import annotations

import hashlib
import random as _random
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from tenzing_tpu.bench.benchmarker import BenchOpts, BenchResult, schedule_id
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.sync_ops import SyncOp
from tenzing_tpu.fault.errors import (
    DeterministicScheduleError,
    DeviceLostError,
    TransientError,
)
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer

KINDS = ("transient", "hang", "deterministic", "device_lost", "corrupt")


class InjectedTransientError(TransientError):
    """A seeded injected runtime flake."""


class InjectedDeterministicError(DeterministicScheduleError):
    """A seeded injected always-broken candidate."""


@dataclass(frozen=True)
class InjectSpec:
    """One injection channel: ``kind`` at probability ``rate`` from ``seed``."""

    kind: str
    rate: float
    seed: int


def parse_inject_specs(text: str) -> List[InjectSpec]:
    """Parse ``kind:rate:seed[,kind:rate:seed...]`` (the --inject-faults
    grammar).  Errors are loud: a typo'd chaos spec silently injecting
    nothing would make a green chaos run meaningless."""
    specs: List[InjectSpec] = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if len(fields) != 3:
            raise ValueError(
                f"--inject-faults spec {part!r}: want kind:rate:seed")
        kind, rate_s, seed_s = fields
        if kind not in KINDS:
            raise ValueError(
                f"--inject-faults kind {kind!r}: want one of {KINDS}")
        rate = float(rate_s)
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"--inject-faults rate {rate!r} not in [0, 1]")
        specs.append(InjectSpec(kind=kind, rate=rate, seed=int(seed_s)))
    if not specs:
        raise ValueError("--inject-faults: empty spec")
    return specs


def _hash_draw(material: str) -> float:
    """Uniform [0, 1) draw from a content hash — identical on every rank
    and across restarts for the same material."""
    h = hashlib.sha256(material.encode()).digest()
    return int.from_bytes(h[:8], "big") / float(1 << 64)


def _schedule_fails(sid: str, spec: InjectSpec) -> bool:
    """Deterministic by schedule identity: hash(sid, seed) under rate."""
    return _hash_draw(f"{sid}:{spec.seed}") < spec.rate


def _attempt_fires(sid: str, attempt: int, spec: InjectSpec) -> bool:
    """Per-attempt draw, rank-agreed: keyed on the schedule identity, the
    per-schedule attempt counter and the channel — not on process-local RNG
    state (see module docstring)."""
    return _hash_draw(f"{spec.kind}:{spec.seed}:{sid}:{attempt}") < spec.rate


# -- schedule corruption ---------------------------------------------------


def corrupt_schedule(
    order: Sequence,
    seed: int,
    unsound_check: Optional[Callable[[Sequence], bool]] = None,
) -> Optional[Sequence]:
    """A mutated copy of ``order`` with one sync op dropped or deferred
    (moved behind the rest of the schedule) — the two ways schedule-handling
    code plausibly mangles synchronization — or None when no mutation makes
    the schedule detectably unsound.

    Mutation candidates are tried in a ``seed``-deterministic shuffle;
    ``unsound_check(mutated) -> bool`` decides which mutations count (the
    chaos tests pass the EventSynchronizer-derived ground truth so the
    verifier under test is not consulted; ``bench.py`` passes the deployed
    verifier so a chaos run never silently injects a no-op).  Without a
    check, the first candidate mutation is returned blind."""
    ops = order.vector()
    sync_pos = [i for i, op in enumerate(ops) if isinstance(op, SyncOp)]
    if not sync_pos:
        return None
    cands = [("drop", i) for i in sync_pos]
    # defer: move the sync to the end of the schedule (past every op it was
    # protecting; a wait deferred past its dependents, a record past its
    # waiters — both reorderings real code could commit)
    cands += [("defer", i) for i in sync_pos if i != len(ops) - 1]
    rng = _random.Random(f"{seed}:{schedule_id(order)}")
    rng.shuffle(cands)
    for kind, i in cands:
        if kind == "drop":
            mut = ops[:i] + ops[i + 1:]
        else:
            mut = ops[:i] + ops[i + 1:] + [ops[i]]
        seq = Sequence(mut)
        if unsound_check is None or unsound_check(seq):
            return seq
    return None


class FaultInjectingBenchmarker:
    """Chaos wrapper (see module docstring).  ``injected`` counts injections
    per kind; ``calls`` counts benchmark queries — the chaos tests assert on
    both to prove the run actually exercised the fault paths.  ``corrupted``
    maps each mutated schedule's original id to the mutated id, so tests can
    hold the verifier to account for every mutation."""

    def __init__(self, inner, specs: List[InjectSpec],
                 hang_secs: float = 60.0, sleep=time.sleep,
                 unsound_check: Optional[Callable[[Sequence], bool]] = None,
                 exempt_ids: Optional[set] = None):
        self.inner = inner
        self.specs = list(specs)
        self.hang_secs = hang_secs
        self._sleep = sleep
        self._attempts: Dict[str, int] = {}  # sid -> benchmark-call count
        self.unsound_check = unsound_check
        # schedule ids exempt from the identity-keyed CANDIDATE-fault kinds
        # (deterministic, corrupt): bench.py registers its naive baseline —
        # an identity draw deterministically breaking the baseline would
        # kill every run under that seed before the search starts, which is
        # no chaos experiment at all.  Per-attempt device-fault kinds
        # (transient/hang/device_lost) still apply: baselines ride the same
        # flaky device as everything else and their failures retry.
        self.exempt_ids: set = set(exempt_ids) if exempt_ids else set()
        self.calls = 0
        self.injected: Dict[str, int] = {k: 0 for k in KINDS}
        self.corrupted: Dict[str, str] = {}  # original sid -> mutated sid
        # forwarded so a wrapped EmpiricalBenchmarker still offers the batch
        # protocol (injection applies per benchmark() query only: batches
        # are the final verdict path, which chaos leaves untouched)
        if hasattr(inner, "benchmark_batch_times"):
            self.benchmark_batch_times = inner.benchmark_batch_times
        # a corrupt injector stacked OUTSIDE the resilient wrapper must not
        # hide the inner stack's rank-coherence from the solvers
        self.rank_coherent = getattr(inner, "rank_coherent", False)

    def was_degraded(self, order) -> bool:
        """Degradation provenance passes through the injector — a corrupt
        injector stacked between JournalingBenchmarker and the resilient
        wrapper must not launder fallback answers into ``measured`` journal
        rows."""
        fn = getattr(self.inner, "was_degraded", None)
        return bool(fn(order)) if fn is not None else False

    def _record(self, kind: str, sid: str) -> None:
        self.injected[kind] += 1
        get_metrics().counter(f"fault.injected.{kind}").inc()
        tr = get_tracer()
        if tr.enabled:
            tr.event("fault.injected", kind=kind, schedule=sid)

    def benchmark(self, order, opts: Optional[BenchOpts] = None) -> BenchResult:
        self.calls += 1
        sid = schedule_id(order)
        attempt = self._attempts.get(sid, 0)
        self._attempts[sid] = attempt + 1
        for spec in self.specs:
            if spec.kind == "deterministic":
                if sid not in self.exempt_ids and _schedule_fails(sid, spec):
                    self._record("deterministic", sid)
                    raise InjectedDeterministicError(
                        f"injected deterministic failure (schedule {sid})")
            elif spec.kind == "corrupt":
                if (sid not in self.exempt_ids and _schedule_fails(sid, spec)
                        and isinstance(order, Sequence)):
                    mutated = corrupt_schedule(order, spec.seed,
                                               self.unsound_check)
                    if mutated is not None:
                        self._record("corrupt", sid)
                        self.corrupted[sid] = schedule_id(mutated)
                        order = mutated
            elif _attempt_fires(sid, attempt, spec):
                if spec.kind == "transient":
                    self._record("transient", sid)
                    raise InjectedTransientError(
                        f"injected transient failure (schedule {sid} "
                        f"attempt {attempt})")
                if spec.kind == "hang":
                    self._record("hang", sid)
                    self._sleep(self.hang_secs)
                elif spec.kind == "device_lost":
                    self._record("device_lost", sid)
                    raise DeviceLostError(
                        f"injected device loss (schedule {sid} "
                        f"attempt {attempt})")
        return self.inner.benchmark(order, opts)
