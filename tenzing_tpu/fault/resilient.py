"""ResilientBenchmarker: classified retries, watchdog, quarantine,
rank-coherent failure agreement, graceful degradation.

Wraps any benchmarker (the Benchmarker protocol: ``benchmark(order, opts)
-> BenchResult``; ``benchmark_batch_times`` forwarded when present) with the
fault policy of docs/robustness.md:

* **watchdog** — each attempt runs on a daemon worker thread bounded by a
  wall-clock ``timeout_secs``; a hung measurement (stuck collective, a
  runtime that stalls without erroring) surfaces as
  :class:`~tenzing_tpu.fault.errors.MeasurementTimeout` instead of blocking
  the search forever.  The timed-out worker is *abandoned* (Python cannot
  interrupt a thread blocked in C) — safe for a dead RPC, and the retry
  dispatches fresh.
* **classification** (fault/errors.py): transient → bounded retry with
  exponential backoff + jitter (the shared ``BackoffPolicy``); deterministic
  → persistent quarantine (fault/quarantine.py) + raise — the same broken
  candidate is never measured twice, even across restarts; device-lost →
  degrade or escalate.
* **rank-coherent agreement** — before each attempt and after it, every rank
  allreduce-maxes a fault code (``ControlPlane.agree_fault``).  A failure on
  one rank therefore becomes a failure on *all* ranks at the same attempt
  boundary: ranks retry together, quarantine together, and degrade
  together, instead of one rank raising while its peers deadlock in the
  next collective.  The watchdog is what guarantees a hung rank eventually
  *reaches* the agreement point.
* **soundness gate** — with a ``verifier`` configured (the independent
  happens-before checker, tenzing_tpu/verify), every schedule is verified
  *before* it is measured: an unsound schedule — a dropped or mis-ordered
  sync, whether from a synthesizer bug or injected corruption — is a
  deterministic fault discovered for free (no device time), quarantined
  with a ``verify.unsound`` obs event, and refused as
  :class:`~tenzing_tpu.fault.errors.UnsoundScheduleError`.  A
  fast-but-wrong schedule can therefore never produce a measurement.
* **graceful degradation** — on device loss with a ``fallback`` benchmarker
  configured (e.g. the PR 2 learned surrogate), the wrapper flips to
  answering every subsequent query from the fallback, records which
  schedules were answered that way (:meth:`was_degraded` — dump paths tag
  those rows ``fid=degraded`` so they never pass as measurements), and the
  search finishes instead of dying.  Without a fallback, device loss raises
  :class:`~tenzing_tpu.fault.errors.DeviceLostError`.

``rank_coherent = True`` advertises the agreement protocol to the solvers:
their reject paths may treat a benchmark failure as a dead-end candidate
even under a multi-host control plane (solve/mcts, solve/dfs, solve/local),
because every rank saw the same failure at the same point.
"""

from __future__ import annotations

import random as _random
import threading
import time
from typing import List, Optional

from tenzing_tpu.bench.benchmarker import BenchOpts, BenchResult, schedule_id
from tenzing_tpu.fault.backoff import BackoffPolicy
from tenzing_tpu.fault.errors import (
    DeviceLostError,
    FaultClass,
    MeasurementTimeout,
    QuarantinedScheduleError,
    UnsoundScheduleError,
    classify_error,
)
from tenzing_tpu.fault.quarantine import Quarantine
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.progress import get_reporter
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.parallel.control_plane import ControlPlane, default_control_plane


class ResilientBenchmarker:
    """Fault-policy wrapper around a benchmarker (see module docstring)."""

    rank_coherent = True

    def __init__(
        self,
        inner,
        control_plane: Optional[ControlPlane] = None,
        timeout_secs: Optional[float] = None,
        policy: Optional[BackoffPolicy] = None,
        quarantine: Optional[Quarantine] = None,
        fallback=None,
        sleep=time.sleep,
        seed: int = 0,
        verifier=None,
    ):
        self.inner = inner
        self.cp = control_plane if control_plane is not None else (
            default_control_plane())
        self.timeout_secs = timeout_secs
        self.policy = policy if policy is not None else BackoffPolicy()
        self.quarantine = quarantine if quarantine is not None else Quarantine()
        self.fallback = fallback
        # independent soundness gate (tenzing_tpu/verify.ScheduleVerifier):
        # an unsound schedule is a deterministic fault discovered WITHOUT
        # touching the device — quarantined and refused, never measured.
        # Verification is a pure function of the (broadcast-identical)
        # schedule, so every rank reaches the same verdict at the same
        # point: no agreement round needed, the protocol stays in lockstep.
        self.verifier = verifier
        self._sleep = sleep
        self._rng = _random.Random(seed)
        self.degraded = False
        self._degraded_keys: set = set()
        # the batch protocol is only offered when the wrapped benchmarker
        # has it — hill_climb's paired mode probes with getattr
        if hasattr(inner, "benchmark_batch_times"):
            self.benchmark_batch_times = self._batch_times

    # -- provenance --------------------------------------------------------
    def was_degraded(self, order) -> bool:
        """True if a query for ``order`` was answered by the fallback after
        device loss — dump paths tag such rows ``fid=degraded``."""
        return schedule_id(order) in self._degraded_keys

    # -- soundness gate ----------------------------------------------------
    def _check_sound(self, order) -> None:
        """Refuse an unsound schedule before it reaches the device: the
        independent verifier's rejection is classified deterministic (the
        schedule is wrong, not unlucky), quarantined, and raised as
        :class:`UnsoundScheduleError` with the minimal witness."""
        if self.verifier is None:
            return
        verdict = self.verifier(order)
        if verdict.ok:
            return
        from tenzing_tpu.verify.soundness import report_unsound

        report_unsound("resilient.benchmark", order, verdict)
        err = UnsoundScheduleError(
            f"schedule fails soundness verification: {verdict.witness()}")
        self.quarantine.add(order, err, FaultClass.DETERMINISTIC)
        raise err

    # -- watchdog ----------------------------------------------------------
    def _call_with_timeout(self, fn, *args, **kwargs):
        if self.timeout_secs is None:
            return fn(*args, **kwargs)
        out: dict = {}
        done = threading.Event()

        def work():  # pragma: no cover - trivial trampoline
            try:
                out["res"] = fn(*args, **kwargs)
            except BaseException as e:
                out["exc"] = e
            finally:
                done.set()

        t = threading.Thread(target=work, daemon=True, name="tz-measure")
        t.start()
        if not done.wait(self.timeout_secs):
            raise MeasurementTimeout(
                f"measurement exceeded {self.timeout_secs}s wall clock "
                "(watchdog)")
        if "exc" in out:
            raise out["exc"]
        return out["res"]

    # -- degradation -------------------------------------------------------
    def _degrade_or_raise(self, order, exc: Optional[BaseException]):
        if self.fallback is None:
            get_metrics().counter("fault.device_lost_fatal").inc()
            err = DeviceLostError(
                "device lost and no fallback benchmarker configured")
            if exc is not None:
                raise err from exc
            raise err
        if not self.degraded:
            self.degraded = True
            get_metrics().counter("fault.degraded").inc()
            tr = get_tracer()
            if tr.enabled:
                tr.event("fault.degraded",
                         error=type(exc).__name__ if exc else None,
                         message=str(exc)[:200] if exc else None)
            get_reporter().warn(
                "fault: device lost — degrading to fallback benchmarker; "
                "subsequent results carry fid=degraded provenance",
                error=type(exc).__name__ if exc else None,
            )

    def _answer_degraded(self, order, opts) -> BenchResult:
        res = self.fallback.benchmark(order, opts)
        self._degraded_keys.add(schedule_id(order))
        get_metrics().counter("fault.degraded_answers").inc()
        return res

    # -- the resilient measurement loop ------------------------------------
    def benchmark(self, order, opts: Optional[BenchOpts] = None) -> BenchResult:
        if self.degraded:
            # all ranks entered degradation together (the agreement below),
            # so the degraded path runs no collectives: the device — and
            # with it the cross-host barrier fabric — may be gone
            return self._answer_degraded(order, opts)
        rec = self.quarantine.check(order)
        if rec is not None:
            get_metrics().counter("fault.quarantine_hits").inc()
            tr = get_tracer()
            if tr.enabled:
                tr.event("fault.quarantine_hit",
                         schedule=self.quarantine.key(order),
                         error=rec.get("error"))
            raise QuarantinedScheduleError(
                f"schedule quarantined ({rec.get('error')}: "
                f"{rec.get('message', '')[:200]})")
        self._check_sound(order)
        tr = get_tracer()
        reg = get_metrics()
        attempts = self.policy.retries + 1
        for attempt in range(attempts):
            # pre-attempt agreement: aligns attempt generations — every rank
            # enters the measurement (or its failure handling) together
            self.cp.agree_fault(0)
            res: Optional[BenchResult] = None
            exc: Optional[BaseException] = None
            code = 0
            try:
                res = self._call_with_timeout(
                    self.inner.benchmark, order, opts)
            except (KeyboardInterrupt, SystemExit):
                raise  # an interrupt is for the trap layer, not the retrier
            except BaseException as e:
                exc = e
                code = FaultClass.CODES[classify_error(e)]
            # post-attempt agreement: the worst fault class on any rank wins
            agreed = int(self.cp.agree_fault(code))
            if agreed == FaultClass.CODES[FaultClass.OK]:
                return res  # type: ignore[return-value]
            cls = FaultClass.FROM_CODE.get(agreed, FaultClass.DETERMINISTIC)
            reg.counter(f"fault.errors.{cls}").inc()
            if tr.enabled:
                tr.event(
                    "fault.error", where="bench.benchmark",
                    schedule=schedule_id(order), attempt=attempt + 1,
                    error=type(exc).__name__ if exc else "peer-rank",
                    error_class=cls,
                    message=str(exc)[:200] if exc else None,
                )
            if cls == FaultClass.DEVICE_LOST:
                self._degrade_or_raise(order, exc)
                return self._answer_degraded(order, opts)
            if cls == FaultClass.DETERMINISTIC:
                self.quarantine.add(
                    order,
                    exc if exc is not None else RuntimeError("peer-rank failure"),
                    cls,
                )
                if exc is not None:
                    raise exc
                raise QuarantinedScheduleError(
                    "deterministic failure on a peer rank")
            # transient: bounded retry with backoff + jitter
            if attempt == attempts - 1:
                if exc is not None:
                    raise exc
                raise MeasurementTimeout(
                    "transient failure on a peer rank; retries exhausted")
            delay = self.policy.delay(attempt, self._rng)
            reg.counter("fault.retries").inc()
            if tr.enabled:
                tr.event("fault.retry", where="bench.benchmark",
                         schedule=schedule_id(order), attempt=attempt + 1,
                         error=type(exc).__name__ if exc else "peer-rank",
                         error_class=cls, delay_secs=round(delay, 4))
            if delay > 0.0:
                self._sleep(delay)
        raise AssertionError("unreachable")  # pragma: no cover

    # -- decorrelated batches ----------------------------------------------
    def _batch_times(
        self,
        orders: List,
        opts: Optional[BenchOpts] = None,
        seed: int = 0,
        times_out: Optional[List[List[float]]] = None,
        group_seeds=None,
    ) -> List[List[float]]:
        """``benchmark_batch_times`` with the watchdog (scaled: a batch is
        ``len(orders)`` measurement series) and transient-class retries.
        No quarantine — a batch mixes schedules, so a deterministic failure
        cannot be attributed to one candidate and simply raises.

        ``times_out`` handling depends on the watchdog.  Without one, the
        caller's lists are passed straight through (live partial data for
        the trap handler, the DFS partial-dump contract).  With a watchdog,
        a timed-out attempt abandons a worker thread that still holds
        references to whatever lists the inner call received — so each
        attempt gets FRESH private lists and the caller's are only
        clear()-ed + filled from a completed attempt's result: an abandoned
        worker can never interleave stale appends into the series the
        caller reads (iteration alignment is what paired comparisons trust).
        Trap dumps during a supervised batch then only see completed
        attempts, which is exactly the data that is actually valid."""
        if self.degraded:
            raise DeviceLostError(
                "batch benchmarking unavailable in degraded mode")
        # soundness-gate every member up front: unlike a runtime batch
        # failure, verification attributes the fault to ONE schedule, so
        # the unsound member is quarantined before anything is measured
        for order in orders:
            self._check_sound(order)
        timeout = (None if self.timeout_secs is None
                   else self.timeout_secs * max(1, len(orders)))
        tr = get_tracer()
        reg = get_metrics()
        attempts = self.policy.retries + 1
        for attempt in range(attempts):
            self.cp.agree_fault(0)
            exc = None
            code = 0
            out: Optional[List[List[float]]] = None
            inner_times = (times_out if timeout is None else
                           ([[] for _ in orders]
                            if times_out is not None else None))
            try:
                # inner benchmarkers that predate fused rounds keep their
                # old signature: forward group_seeds only when grouping
                gkw = {} if group_seeds is None else {
                    "group_seeds": group_seeds}
                out = self._call_with_timeout_scaled(
                    timeout, self.inner.benchmark_batch_times,
                    orders, opts, seed=seed, times_out=inner_times, **gkw)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                exc = e
                code = FaultClass.CODES[classify_error(e)]
            agreed = int(self.cp.agree_fault(code))
            if agreed == FaultClass.CODES[FaultClass.OK]:
                if timeout is not None and times_out is not None:
                    for dst, src in zip(times_out, out):
                        dst.clear()
                        dst.extend(src)
                    return times_out
                return out  # type: ignore[return-value]
            cls = FaultClass.FROM_CODE.get(agreed, FaultClass.DETERMINISTIC)
            reg.counter(f"fault.errors.{cls}").inc()
            if tr.enabled:
                tr.event("fault.error", where="bench.batch",
                         attempt=attempt + 1,
                         error=type(exc).__name__ if exc else "peer-rank",
                         error_class=cls,
                         message=str(exc)[:200] if exc else None)
            if cls != FaultClass.TRANSIENT or attempt == attempts - 1:
                if cls == FaultClass.DEVICE_LOST:
                    self._degrade_or_raise(None, exc)
                    raise DeviceLostError(
                        "device lost mid-batch; batch cannot degrade")
                if exc is not None:
                    raise exc
                raise MeasurementTimeout("peer-rank batch failure")
            if times_out is not None:
                for ts in times_out:
                    ts.clear()
            delay = self.policy.delay(attempt, self._rng)
            reg.counter("fault.retries").inc()
            if tr.enabled:
                tr.event("fault.retry", where="bench.batch",
                         attempt=attempt + 1, delay_secs=round(delay, 4),
                         error=type(exc).__name__ if exc else "peer-rank")
            if delay > 0.0:
                self._sleep(delay)
        raise AssertionError("unreachable")  # pragma: no cover

    def _call_with_timeout_scaled(self, timeout, fn, *args, **kwargs):
        saved, self.timeout_secs = self.timeout_secs, timeout
        try:
            return self._call_with_timeout(fn, *args, **kwargs)
        finally:
            self.timeout_secs = saved
