"""Async compile pipeline: overlap XLA compilation with device measurement.

The search wall of every bench run is dominated by *serialized* compiles
(~3.4 s per distinct schedule, 64 compiles inside a 147 s MCTS wall in the
r5 driver tail): ``TraceExecutor`` traces+compiles lazily on the first call
of the jitted program — i.e. inside the measurement path, while the device
sits idle.  But compilation is CPU-bound and GIL-releasing, measurement is
device-bound, and the solvers already know (or can cheaply guess) their next
candidates — the classic compile/execute pipelining MPK and TACCL lean on to
make schedule search affordable (PAPERS.md).

:class:`PrefetchingBenchmarker` wraps the *measurement* benchmarker (the
device stand-in at the bottom of the fault stack) and accepts **candidate
hints**: ``prefetch(orders)`` kicks off AOT compiles
(``TraceExecutor.precompile`` — ``jax.jit(...).lower(...).compile()`` into
the executor's schedule-JSON-keyed program cache) on a bounded background
thread pool while the foreground measurement runs.  An in-flight dedup map
guarantees each schedule compiles at most once; a foreground ``benchmark()``
for a schedule whose compile is still in flight joins it (paying only the
remainder) instead of compiling a duplicate.

Fault discipline — background threads NEVER touch the control plane:

* a background compile failure is recorded (classified via
  ``fault/errors.classify_error`` for telemetry) and **surfaced on the
  foreground ``benchmark()`` call** for that schedule: the stored exception
  is raised once on the caller's thread, where the
  :class:`~tenzing_tpu.fault.resilient.ResilientBenchmarker` above runs its
  normal classification, rank-coherent ``agree_fault`` agreement, and
  quarantine — exactly as if the compile had failed inline.  A transient
  verdict's retry passes through to a fresh foreground attempt (the stored
  failure is consumed by the raise).
* hints are *advisory*: they consume no solver RNG, touch no platform state
  (``provision_events`` is foreground-only bookkeeping), and a full queue
  drops excess hints rather than blocking — for DFS and the hill climb,
  prefetch disabled (or every hint dropped) is bit-identical to prefetch on.
  The tree search reads one thing off the prefetcher, its ``workers``: it
  draws that many rollouts ahead of the one it measures so as to have real
  hints to give (``solve/mcts/mcts.py``), so there the search depends on the
  pool's width and on nothing its threads do: compiles that do nothing,
  fail or finish at any other time give the same search.

Observability (docs/performance.md): ``pipeline.prefetch.issued`` /
``hits`` / ``wasted`` / ``failed`` / ``surfaced`` / ``dropped`` counters, a
``pipeline.queue_depth`` gauge, a ``pipeline.precompile`` span per
background compile (the executor's ``executor.first_call`` spans — ``aot:
true`` for background ones, with ``executor.lower`` and
``executor.xla_compile`` inside — give the compile wall; overlap fraction
falls out of comparing them against ``bench.benchmark`` spans on the main
thread), and a ``pipeline.wait`` span for the time the foreground is blocked
on a compile that is still running.

Shutdown: ``close()`` cancels pending compiles and joins the workers (no
leaked threads); a SIGINT/SIGABRT trap handler (utils/trap.py) only flips
the closed flag — it must not touch pool locks the interrupted thread may
hold — after which the signal's SIG_DFL re-raise tears the process down
(running compiles are abandoned like the resilient watchdog's workers;
Python cannot interrupt a thread blocked in C).
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor, wait
from typing import List, Optional

from tenzing_tpu.bench.benchmarker import BenchOpts, BenchResult, schedule_id
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.utils import trap


class PrefetchingBenchmarker:
    """Candidate-hint compile prefetcher (see module docstring).

    ``executor`` is anything with ``precompile(order) -> bool`` (and
    optionally ``is_compiled(order) -> bool``) — ``runtime.TraceExecutor``
    in production, a fake in tests.  ``workers`` bounds the pool;
    ``depth`` (default ``4 * workers``) bounds the in-flight queue — excess
    hints are dropped (re-hintable later), never queued unboundedly.
    ``rank`` (optional, e.g. the PR-2 ``SurrogateBenchmarker``) orders each
    hint batch most-promising-first by predicted time, so the compile budget
    lands on candidates most likely to be measured."""

    def __init__(self, inner, executor, workers: int = 2,
                 depth: Optional[int] = None, rank=None):
        self.inner = inner
        self.executor = executor
        self.workers = max(1, int(workers))
        self.depth = int(depth) if depth is not None else 4 * self.workers
        self.rank = rank
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="tz-prefetch")
        self._lock = threading.Lock()
        self._inflight: dict = {}   # schedule id -> Future
        self._failed: dict = {}     # schedule id -> background compile exc
        self._ready: set = set()    # precompiled, not yet consumed
        self._seen: set = set()     # ids ever submitted (dedup)
        self._closed = False
        # tallies mirrored into the metrics registry; read by the driver's
        # ``perf`` meta block (bench.py) and the pipeline tests
        self.issued = 0
        self.hits = 0
        self.failed = 0
        self.surfaced = 0
        self.dropped = 0
        # wrapper idiom of the fault stack: forward the batch protocol and
        # provenance probes only when the wrapped benchmarker offers them
        if hasattr(inner, "benchmark_batch_times"):
            self.benchmark_batch_times = self._batch_times
        self.rank_coherent = getattr(inner, "rank_coherent", False)
        self._wasted_counted = False
        self._trap_registered = True
        trap.register_handler(self._trap_cancel)

    # -- lifecycle ----------------------------------------------------------
    def __enter__(self) -> "PrefetchingBenchmarker":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _trap_cancel(self) -> None:
        """SIGINT/SIGABRT path: ONLY flip the closed flag — no pool calls.
        ``ThreadPoolExecutor.shutdown`` takes the same non-reentrant
        ``_shutdown_lock`` every ``submit()`` holds, and the trap runs on
        the interrupted thread (possibly mid-``prefetch``), so touching the
        pool here could deadlock the very dump path trap.py exists to
        protect.  The flag stops new work; the real signal path then
        re-raises via SIG_DFL (process dies, threads with it), and the
        test/cleanup path reaches :meth:`close`, which cancels + joins."""
        self._closed = True

    def close(self) -> None:
        """Cancel pending compiles and join the workers.  Idempotent (also
        after the trap handler already shut the pool down); after close
        every hint is a no-op and ``wasted()`` is final."""
        self._closed = True
        if self._trap_registered:
            self._trap_registered = False
            trap.unregister_handler(self._trap_cancel)
        # cancel_futures drops queued work; shutdown(wait=True) joins the
        # workers once their current compile returns (compiles finish — XLA
        # has no cancellation — so the join is bounded by one compile)
        self._pool.shutdown(wait=True, cancel_futures=True)
        if not self._wasted_counted:
            self._wasted_counted = True
            get_metrics().counter("pipeline.prefetch.wasted").inc(
                self.wasted())

    def wasted(self) -> int:
        """Background-compiled programs no foreground benchmark consumed
        (yet) — the cost of speculation, reported in the ``perf`` block."""
        with self._lock:
            return len(self._ready)

    def stats(self) -> dict:
        """The ``perf`` meta block's prefetch section."""
        return {
            "workers": self.workers,
            "issued": self.issued,
            "hits": self.hits,
            "wasted": self.wasted(),
            "failed": self.failed,
            "surfaced": self.surfaced,
            "dropped": self.dropped,
        }

    # -- hinting ------------------------------------------------------------
    def prefetch(self, orders) -> int:
        """Accept candidate hints; returns how many background compiles were
        actually issued.  Non-Sequence orders (CallableRunner names), dupes,
        already-compiled schedules, and hints beyond the queue bound are
        skipped — dropped hints may be re-hinted later (the DFS frontier
        window re-offers its slice every iteration)."""
        if self._closed:
            return 0
        cands: List[Sequence] = [o for o in orders
                                 if isinstance(o, Sequence)]
        # dedup BEFORE any ranking work: re-offered windows (the DFS
        # frontier slice arrives every iteration) must cost one memoized
        # schedule_id + set lookup per candidate, not a surrogate
        # featurization of schedules already submitted.  The live set is
        # read without the lock — _seen is mutated only by prefetch()
        # itself (one logical caller at a time), membership is GIL-atomic,
        # and the per-order re-check under the lock below is authoritative
        cands = [o for o in cands if schedule_id(o) not in self._seen]
        if not cands:
            return 0
        if self.rank is not None and len(cands) > 1:
            try:
                cands = sorted(cands,
                               key=lambda o: self.rank.predict(o)[0])
            except Exception:
                pass  # ranking is best-effort; hint order is advisory
        reg = get_metrics()
        is_compiled = getattr(self.executor, "is_compiled", None)
        issued = 0
        for order in cands:
            key = schedule_id(order)
            with self._lock:
                if self._closed or key in self._seen:
                    continue
                if len(self._inflight) >= self.depth:
                    self.dropped += 1
                    reg.counter("pipeline.prefetch.dropped").inc()
                    continue
                if is_compiled is not None and is_compiled(order):
                    self._seen.add(key)  # nothing to do, ever
                    continue
                self._seen.add(key)
                try:
                    fut = self._pool.submit(self._compile_one, key, order)
                except RuntimeError:  # pool shut down by the trap handler
                    self._seen.discard(key)
                    break
                self._inflight[key] = fut
                depth = len(self._inflight)
            issued += 1
            self.issued += 1
            reg.counter("pipeline.prefetch.issued").inc()
            reg.gauge("pipeline.queue_depth").set(depth)
        return issued

    def _compile_one(self, key: str, order: Sequence) -> None:
        """Worker body: AOT-compile one schedule, record success/failure.
        Runs off the control plane — errors are stored for the foreground,
        never raised into the pool."""
        reg = get_metrics()
        tr = get_tracer()
        try:
            with tr.span("pipeline.precompile", schedule=key):
                self.executor.precompile(order)
            with self._lock:
                self._ready.add(key)
        except BaseException as e:  # noqa: BLE001 — classified + surfaced
            from tenzing_tpu.fault.errors import classify_error

            reg.counter("pipeline.prefetch.failed").inc()
            if tr.enabled:
                tr.event("pipeline.precompile_failed", schedule=key,
                         error=type(e).__name__,
                         error_class=classify_error(e),
                         message=str(e)[:200])
            with self._lock:
                # under the lock: workers race each other on this tally
                # (every other tally is foreground-only)
                self.failed += 1
                self._failed[key] = e
        finally:
            with self._lock:
                self._inflight.pop(key, None)
                depth = len(self._inflight)
            reg.gauge("pipeline.queue_depth").set(depth)

    # -- foreground join ----------------------------------------------------
    def _join(self, order, cancel_queued: bool = True) -> None:
        """Settle any in-flight background compile for ``order``.

        A compile already RUNNING is waited on (the foreground pays only
        the remainder).  With ``cancel_queued``, a compile still queued
        BEHIND a backlog (more in flight than workers) is cancelled
        instead: compiling inline is faster than draining the queue, and a
        watchdog sized for one compile (``--measure-timeout``) must not
        fire on queue depth.  Without a backlog the future is about to run
        (or running) — waiting costs the inline compile at most, and a
        just-hinted schedule reliably lands as a prefetch hit."""
        with self._lock:
            fut = self._inflight.get(schedule_id(order))
            backlog = len(self._inflight) > self.workers
        if fut is None:
            return
        if cancel_queued and backlog and fut.cancel():
            # never started: _compile_one will not run, so drop the
            # in-flight entry here and let the foreground compile inline
            with self._lock:
                self._inflight.pop(schedule_id(order), None)
                depth = len(self._inflight)
            get_metrics().gauge("pipeline.queue_depth").set(depth)
            return
        if not fut.done():
            with get_tracer().span("pipeline.wait",
                                   schedule=schedule_id(order)):
                wait([fut])

    def _consume(self, order) -> None:
        """Account a prefetch hit and surface a stored background compile
        failure ON THE CALLER'S THREAD — the resilient layer above
        classifies, agrees rank-coherently, and quarantines exactly as for
        an inline compile failure.  The failure is consumed: a retry after
        a transient verdict reaches the real (foreground) attempt."""
        key = schedule_id(order)
        with self._lock:
            exc = self._failed.pop(key, None)
            hit = key in self._ready
            self._ready.discard(key)
        reg = get_metrics()
        if hit:
            self.hits += 1
            reg.counter("pipeline.prefetch.hits").inc()
        if exc is not None:
            self.surfaced += 1
            reg.counter("pipeline.prefetch.surfaced").inc()
            raise exc

    def benchmark(self, order, opts: Optional[BenchOpts] = None) -> BenchResult:
        if isinstance(order, Sequence):
            self._join(order)
            self._consume(order)
        return self.inner.benchmark(order, opts)

    def _batch_times(self, orders, opts: Optional[BenchOpts] = None,
                     seed: int = 0, times_out=None, group_seeds=None):
        """Batch members parallel-compile across the pool before the inner
        batch warms them (today: a serial compile per member); a stored
        background failure for any member surfaces here, like the inline
        warmup failure it replaces.  Members queued behind an unrelated
        backlog take the same cancel-and-compile-inline escape as the
        single path — the resilient batch watchdog scales with the batch
        size, not with speculative work hinted earlier."""
        self.prefetch(orders)
        for o in orders:
            if isinstance(o, Sequence):
                self._join(o)
                self._consume(o)
        # forward group_seeds only when grouping is requested, so inner
        # benchmarkers that predate fused rounds keep their old signature
        kw = {} if group_seeds is None else {"group_seeds": group_seeds}
        return self.inner.benchmark_batch_times(
            orders, opts, seed=seed, times_out=times_out, **kw)

    def was_degraded(self, order) -> bool:
        fn = getattr(self.inner, "was_degraded", None)
        return bool(fn(order)) if fn is not None else False
