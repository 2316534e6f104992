"""The measurement stack a search runs through, and the benchmark's own
wrapper round it.

The layers are the program's, assembled as ``bench/driver.py`` assembles them
(without injection, journal, surrogate or fleet)::

    DeadlineBenchmarker          the benchmark's: counts, times, keeps the
                                 best, stops the solver at the deadline
      CachingBenchmarker         equivalence-keyed cache
        ResilientBenchmarker     verifier gate, retries, quarantine
          PrefetchingBenchmarker background first calls (2 workers)
            EmpiricalBenchmarker fetch-fenced repeat-n measurement
              SpanExecutor       the benchmark's: names first calls
                TraceExecutor    one XLA program per schedule

Everything the benchmark adds is a proxy from this file: spans are taken
round calls into the program, never inside it.
"""

from __future__ import annotations

import contextlib
import statistics
import threading
import time


class Deadline(BaseException):
    """The window is over.  A ``BaseException`` because the solvers catch
    ``Exception`` as "this candidate failed"."""


class Spans:
    """Host spans on the benchmark's clock: ``(name, start, end, thread)``.
    With ``annotate`` on, each is also written into the profiler's trace
    (``tzb:<name>``), which puts it on the device's clock."""

    PREFIX = "tzb:"

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.items = []
        self.annotate = False
        self._lock = threading.Lock()

    def begin(self, name: str):
        """Open a span; :meth:`end` takes what this returns."""
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(self.PREFIX + name)
            ann.__enter__()
        return (name, self.clock(), ann)

    def end(self, token) -> None:
        name, t0, ann = token
        t1 = self.clock()
        if ann is not None:
            ann.__exit__(None, None, None)
        with self._lock:
            self.items.append((name, t0, t1, threading.get_ident()))

    @contextlib.contextmanager
    def span(self, name: str):
        token = self.begin(name)
        try:
            yield
        finally:
            self.end(token)


class TimedVerifier:
    """The program's verifier, each verdict inside a ``verify`` span, with
    the schedules it refused remembered by the caller's key."""

    def __init__(self, inner, spans: Spans, key):
        self.inner = inner
        self._spans = spans
        self._key = key
        self.refused = set()

    def __call__(self, order):
        with self._spans.span("verify"):
            verdict = self.inner(order)
        if not verdict.ok:
            self.refused.add(self._key(order))
        return verdict

    def __getattr__(self, name):
        return getattr(self.inner, name)


class SpanExecutor:
    """The program's executor, with the first call of each new program
    inside a ``first_call`` span (``first_call_bg`` on a prefetch worker)."""

    def __init__(self, inner, spans: Spans):
        self.inner = inner
        self._spans = spans

    def prepare_n(self, order):
        cold = not self.inner.is_compiled(order)
        run_n = self.inner.prepare_n(order)
        if not cold:
            return run_n
        state = {"cold": True}
        spans = self._spans

        def first_then_plain(n):
            if state["cold"]:
                state["cold"] = False
                with spans.span("first_call"):
                    return run_n(n)
            return run_n(n)

        return first_then_plain

    def precompile(self, order):
        with self._spans.span("first_call_bg"):
            return self.inner.precompile(order)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class DeadlineBenchmarker:
    """Outermost benchmarker.  Proxies the surface the solvers use
    (``benchmark``, ``benchmark_batch_times``, everything else by
    ``__getattr__``), stamps every call, keeps every result, and raises
    :class:`Deadline` once the window is over.

    One ``benchmark`` call that the cache did not answer is one candidate.
    One ``benchmark_batch_times`` call is one candidate too, its last order
    (a paired climb step measures incumbent and neighbour together; the
    neighbour is the candidate).  A cache in front has no batch method, so
    batches go to the layer beneath it, as a climb would send them.
    """

    def __init__(self, inner, spans: Spans, key, on_boundary=None):
        self.inner = inner
        self._spans = spans
        self._key = key
        self._on_boundary = on_boundary
        beneath = getattr(inner, "inner", inner)
        self._batch = (getattr(inner, "benchmark_batch_times", None)
                       or getattr(beneath, "benchmark_batch_times", None))
        if self._batch is not None:
            self.benchmark_batch_times = self._batch_times
        self.clock = spans.clock
        self.t_open = None
        self.deadline = None
        self.candidates = []      # one dict per candidate, in order
        self.readings = {}        # key -> [order, [pct50, ...]]
        self._solver = None       # the open ``solver`` span between calls

    # -- the window ---------------------------------------------------------
    def open(self, seconds: float) -> None:
        self.t_open = self.clock()
        self.deadline = self.t_open + seconds
        self._solver = self._spans.begin("solver")

    def close(self) -> None:
        """End the open ``solver`` span (the window is over)."""
        if self._solver is not None:
            self._spans.end(self._solver)
            self._solver = None

    def _gate(self) -> None:
        # the host was in the solver from the last call's end until now
        self.close()
        now = self.clock()
        if self._on_boundary is not None:
            self._on_boundary(now)
        if self.deadline is not None and now >= self.deadline:
            raise Deadline()

    def _remember(self, order, pct50: float) -> None:
        self.readings.setdefault(self._key(order), [order, []])[1].append(
            float(pct50))

    def _close_call(self, rec: dict) -> None:
        rec["t1"] = self.clock()
        if self.deadline is not None and rec["t1"] > self.deadline:
            rec["late"] = True
        self.candidates.append(rec)
        if rec.get("late"):
            raise Deadline()
        self._solver = self._spans.begin("solver")

    # -- the benchmarker surface -------------------------------------------
    def benchmark(self, order, opts=None):
        self._gate()
        hits0 = getattr(self.inner, "hits", None)
        rec = {"kind": "single", "key": self._key(order), "t0": self.clock()}
        try:
            with self._spans.span("measure"):
                res = self.inner.benchmark(order, opts)
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            self._close_call(rec)
            raise
        if hits0 is not None and self.inner.hits != hits0:
            self._solver = self._spans.begin("solver")
            return res  # answered by the cache: no candidate
        rec["pct50"] = float(res.pct50)
        self._close_call(rec)
        self._remember(order, res.pct50)
        return res

    def _batch_times(self, orders, opts=None, seed=0, **kw):
        self._gate()
        rec = {"kind": "batch", "key": self._key(orders[-1]),
               "t0": self.clock()}
        try:
            with self._spans.span("measure"):
                times = self._batch(orders, opts, seed=seed, **kw)
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {str(e)[:160]}"
            self._close_call(rec)
            raise
        rec["pct50"] = float(statistics.median(times[-1]))
        self._close_call(rec)
        for order, ts in zip(orders, times):
            self._remember(order, statistics.median(ts))
        return times

    def __getattr__(self, name):
        return getattr(self.inner, name)

    # -- what the window produced -------------------------------------------
    def in_window(self):
        return [c for c in self.candidates if not c.get("late")]

    def finalists(self, k: int = 2):
        """The schedules the epilogue times: the ``k`` best distinct ones by
        the search's own numbers (median of a schedule's readings), and the
        schedule the search measured most often, where it measured one more
        than once.  That one is a solver's incumbent (a paired climb measures
        it again with every neighbour), and its median is the only number
        here that is not a single draw: on the chip single readings of one
        schedule spread by 30% (PERF.md, PR 24), so the two best by single
        readings can both be lucky draws of worse schedules."""
        ranked = sorted(self.readings.values(),
                        key=lambda ov: statistics.median(ov[1]))
        picked = ranked[:k]
        most = max(self.readings.values(), key=lambda ov: len(ov[1]))
        if len(most[1]) > 1 and not any(most is p for p in picked):
            picked.append(most)
        return [(order, statistics.median(vals)) for order, vals in picked]


def build_stack(executor, graph, spans: Spans, on_boundary=None):
    """``(bench, verifier, prefetcher, resilient)`` — the stack of the
    module docstring over ``executor``; ``resilient`` is the layer beneath
    the cache (set-up sends naive through it, so the window's cache starts
    empty)."""
    from tenzing_tpu.bench.benchmarker import (
        CachingBenchmarker,
        EmpiricalBenchmarker,
    )
    from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker
    from tenzing_tpu.core.sequence import canonical_key
    from tenzing_tpu.fault import ResilientBenchmarker
    from tenzing_tpu.verify import ScheduleVerifier

    sx = SpanExecutor(executor, spans)
    verifier = TimedVerifier(ScheduleVerifier(graph), spans, canonical_key)
    prefetcher = PrefetchingBenchmarker(EmpiricalBenchmarker(sx),
                                        executor=sx, workers=2)
    resilient = ResilientBenchmarker(prefetcher, verifier=verifier)
    bench = DeadlineBenchmarker(CachingBenchmarker(resilient), spans,
                                canonical_key, on_boundary=on_boundary)
    return bench, verifier, prefetcher, resilient
