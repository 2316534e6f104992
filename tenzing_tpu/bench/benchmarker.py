"""Empirical and recorded benchmarking of candidate schedules.

Parity target: reference ``include/tenzing/benchmarker.hpp`` /
``src/benchmarker.cpp``:

* ``Benchmark.Result`` = percentiles 01/10/50/90/99 + stddev of per-iteration
  wall time (benchmarker.hpp:14-22).
* ``EmpiricalBenchmarker`` — adaptive inner loop grows samples-per-measurement
  until one measurement takes >= 10 ms (benchmarker.cpp:83-119); barrier before,
  wall-clock around the loop, **max across hosts** (benchmarker.cpp:101,145);
  nIters measurements; reject the whole set if the runs-test flags non-random
  structure and retry up to maxRetries (benchmarker.cpp:129-155).
* ``CsvBenchmarker`` — replays a recorded ``idx|pct...|stddev|json-op...`` CSV
  database, answering queries by bijection-equivalence matching of the query
  sequence against stored rows (benchmarker.cpp:169-223): search-algorithm
  experiments need no device at all.

TPU note (SURVEY.md §7.2 "Measurement fidelity"): the executor compiles a
schedule to one XLA program, and the sample loop runs *inside* that program
(``prepare_n``), fenced by a device->host fetch of one reduced scalar.  The
fetch is a fence on any backend — a ``device_get`` cannot return before the
value exists, where ``block_until_ready`` was once measured returning early
(timing flat in work size) — so each measurement is
``wall(run_n(n)) - fetch_overhead`` with the overhead calibrated per
benchmarker from trivial fetches — the per-measurement analog of the
reference's MPI_Barrier + MPI_Wtime bracketing.  Compile time is excluded: the
callable is built once per schedule before timing starts.
"""

from __future__ import annotations

import random as _random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Protocol, Tuple

from tenzing_tpu.bench.randomness import is_random
from tenzing_tpu.core.sequence import Sequence, canonical_key
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer, short_digest
from tenzing_tpu.parallel.control_plane import ControlPlane, default_control_plane
from tenzing_tpu.utils.numeric import percentile, stddev


def schedule_id(order) -> str:
    """Short stable id of a schedule for telemetry correlation:
    ``obs.tracer.short_digest`` of its serialized form (works for Sequence
    orders and the CallableRunner's plain string names alike).  Deterministic
    across processes — multi-host trace bundles and archived JSONL agree on
    ids without coordination.  Memoized on the sequence (``Sequence.cached``,
    invalidated on mutation): every benchmark/cache/verify/journal/injection
    layer derives the id of the same order, and each derivation used to
    re-serialize the whole schedule to JSON."""
    if isinstance(order, str):
        return order

    def derive() -> str:
        try:
            from tenzing_tpu.core.serdes import sequence_to_json_str

            payload = sequence_to_json_str(order)
        except Exception:
            payload = repr(order)
        return short_digest(payload)

    if isinstance(order, Sequence):
        return order.cached("schedule_id", derive)
    return derive()


def candidate_failed(where: str, order, exc: BaseException) -> None:
    """Structured record of a candidate schedule that failed to compile/run:
    a ``search.candidate_failed`` trace event carrying the schedule id, the
    exception class, and the fault taxonomy class (fault/errors.py —
    transient flake vs deterministic broken candidate vs device loss), plus
    a counter — failed candidates are attributable in the trace instead of
    vanishing into a stderr note.  Shared by every solver's reject path
    (hill-climb, MCTS rollout/confirm, DFS)."""
    # lazy import: fault.resilient imports this module, so a top-level
    # import here would cycle
    from tenzing_tpu.fault.errors import classify_error

    get_metrics().counter("search.candidate_failed").inc()
    tr = get_tracer()
    if tr.enabled:
        tr.event("search.candidate_failed", where=where,
                 schedule=schedule_id(order), error=type(exc).__name__,
                 error_class=classify_error(exc),
                 message=str(exc)[:200])


@dataclass
class BenchResult:
    """Percentile statistics of per-iteration wall time in seconds
    (reference Benchmark::Result, benchmarker.hpp:14-22)."""

    pct01: float = 0.0
    pct10: float = 0.0
    pct50: float = 0.0
    pct90: float = 0.0
    pct99: float = 0.0
    stddev: float = 0.0
    # provenance for offline re-derivation (ISSUE 1 satellite): the raw
    # per-sample series the percentiles were computed from, and the
    # calibrated fetch-overhead correction the empirical benchmarker
    # subtracted per measurement.  Excluded from equality/repr: two results
    # are "the same measurement" by their statistics, and replayed results
    # (CsvBenchmarker) legitimately carry no raw series.
    times: Optional[List[float]] = field(default=None, compare=False,
                                         repr=False)
    fetch_overhead: Optional[float] = field(default=None, compare=False,
                                            repr=False)

    @staticmethod
    def from_times(times: List[float]) -> "BenchResult":
        s = sorted(times)
        return BenchResult(
            pct01=percentile(s, 1),
            pct10=percentile(s, 10),
            pct50=percentile(s, 50),
            pct90=percentile(s, 90),
            pct99=percentile(s, 99),
            stddev=stddev(s),
            times=list(times),
        )

    def to_json(self) -> dict:
        out = {
            "pct01": self.pct01,
            "pct10": self.pct10,
            "pct50": self.pct50,
            "pct90": self.pct90,
            "pct99": self.pct99,
            "stddev": self.stddev,
        }
        if self.times is not None:
            out["times"] = list(self.times)
        if self.fetch_overhead is not None:
            out["fetch_overhead"] = self.fetch_overhead
        return out


@dataclass
class BenchOpts:
    """reference Benchmark::Opts (benchmarker.hpp:24-30)."""

    n_iters: int = 1000
    max_retries: int = 10
    target_secs: float = 0.01  # adaptive floor per measurement (benchmarker.cpp:85)


class ScheduleRunner(Protocol):
    """Anything that turns a schedule into a fenced run callable — provided by
    runtime.executor.  ``prepare_n`` (preferred) returns ``run_n(n)`` repeating
    the schedule n times inside one program; ``prepare`` a run-once callable."""

    def prepare(self, order: Sequence) -> Callable[[], None]: ...


class EmpiricalBenchmarker:
    """Times a schedule on the real device (reference EmpiricalBenchmarker)."""

    def __init__(
        self,
        runner: ScheduleRunner,
        control_plane: Optional[ControlPlane] = None,
    ):
        self.runner = runner
        self.cp = control_plane if control_plane is not None else default_control_plane()
        self._overhead: Optional[float] = None

    def _fetch_overhead(self) -> float:
        """Median wall time of a trivial compiled fetch: dispatch + fetch round trip.
        Subtracted from every measurement (each measurement is exactly one
        fetch-fenced call)."""
        if self._overhead is None:
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
            self._overhead = ts[len(ts) // 2]
        return self._overhead

    def _runner_for(self, order: Sequence) -> Tuple[Callable[[int], None], int]:
        """(run_n, fences_per_call_of_n): the prepare_n path fences once per
        measurement; the prepare() fallback fences once per sample, so the
        overhead subtraction must scale with n."""
        prep_n = getattr(self.runner, "prepare_n", None)
        if prep_n is not None:
            return prep_n(order), 0  # 0: one fence per run_n call, any n
        run_once = self.runner.prepare(order)

        def run_n(n: int) -> None:
            for _ in range(n):
                run_once()

        return run_n, 1  # 1: one fence per sample

    def _dispatch(self, run_n: Callable[[int], None], n: int) -> float:
        """One fenced ``run_n(n)`` and its wall seconds: a ``bench.dispatch``
        span (the executor's ``executor.enqueue`` / ``executor.fence_wait``, or
        a first call or first run, inside it) and one count of
        ``bench.dispatches``."""
        with get_tracer().span("bench.dispatch", n=n):
            t0 = time.perf_counter()
            run_n(n)
            wall = time.perf_counter() - t0
        get_metrics().counter("bench.dispatches").inc()
        return wall

    # reference measure(), benchmarker.cpp:83-119
    def _measure(
        self,
        run_n: Callable[[int], None],
        n_samples: int,
        opts: BenchOpts,
        fences_per_sample: int = 0,
    ) -> Tuple[float, int]:
        """One measurement: >= target_secs of device work past the fetch
        overhead; returns (secs-per-sample, possibly-grown n_samples)."""
        overhead = self._fetch_overhead()
        while True:
            self.cp.barrier()
            wall = self._dispatch(run_n, n_samples)
            cost = overhead * (fences_per_sample * n_samples if fences_per_sample else 1)
            elapsed = wall - cost
            elapsed = self.cp.allreduce_max(elapsed)
            if elapsed >= opts.target_secs:
                return elapsed / n_samples, n_samples
            # growth ratio from the raw wall time: overhead subtraction can
            # push elapsed to <= 0 at small n, and a ratio computed from a
            # near-zero denominator would jump n straight to the cap
            grow = max(
                n_samples * 2,
                int(n_samples * 1.5 * opts.target_secs / max(wall, 1e-9)),
            )
            if n_samples >= 1_000_000:
                # the cap is reached and elapsed still misses the floor: the
                # work is either folded away by the compiler or cheaper than
                # the fence overhead at any n.  Return the RAW wall time per
                # sample — an honest fence-dominated upper bound — rather
                # than the overhead-subtracted residual, which can be ~0 or
                # negative and would flow into paired ratios as a fabricated
                # astronomic speedup.  Max-reduced across hosts like every
                # other return from _measure (the benchmark() invariant).
                return self.cp.allreduce_max(wall) / n_samples, n_samples
            n_samples = min(grow, 1_000_000)

    # reference benchmark(), benchmarker.cpp:121-167
    def benchmark(self, order: Sequence, opts: Optional[BenchOpts] = None) -> BenchResult:
        opts = opts if opts is not None else BenchOpts()
        tr = get_tracer()
        sid = schedule_id(order) if tr.recording else None
        with tr.span("bench.benchmark", schedule=sid, n_iters=opts.n_iters,
                     target_secs=opts.target_secs) as sp:
            run_n, fences = self._runner_for(order)
            with tr.span("bench.warm", schedule=sid):
                # warmup: compile + first dispatch excluded
                self._dispatch(run_n, 1)
            n_samples = 1
            for attempt in range(opts.max_retries):
                times: List[float] = []
                for _ in range(opts.n_iters):
                    # _measure already max-reduces each elapsed across hosts
                    t, n_samples = self._measure(run_n, n_samples, opts, fences)
                    times.append(t)
                if is_random(times) or attempt == opts.max_retries - 1:
                    res = BenchResult.from_times(times)
                    res.fetch_overhead = self._overhead
                    sp.set("pct50", res.pct50)
                    sp.set("n_samples", n_samples)
                    sp.set("fetch_overhead", self._overhead)
                    sp.set("attempts", attempt + 1)
                    reg = get_metrics()
                    reg.counter("bench.benchmarks").inc()
                    reg.counter("bench.measurements").inc(len(times))
                    if attempt:
                        reg.counter("bench.runs_test_retries").inc(attempt)
                    return res
        raise AssertionError("unreachable")  # pragma: no cover

    # reference batch benchmark(), benchmarker.cpp:21-76: measure a SET of
    # schedules, visiting them in a fresh random permutation each iteration so
    # slow system drift decorrelates from schedule identity.
    def benchmark_batch_times(
        self,
        orders: List[Sequence],
        opts: Optional[BenchOpts] = None,
        seed: int = 0,
        times_out: Optional[List[List[float]]] = None,
        group_seeds: Optional[List[Tuple[int, int]]] = None,
    ) -> List[List[float]]:
        """Raw per-iteration times, aligned by iteration index: ``times[i][k]``
        is schedule i's secs-per-sample in iteration k, and iteration k visits
        every schedule once (shuffled) — so ``times[a][k] / times[b][k]`` is a
        *paired* comparison in which common-mode drift cancels (see
        utils.numeric.paired_speedup).

        ``times_out`` (a list of ``len(orders)`` empty lists) is filled in
        place as measurements land, so a signal handler can snapshot partial
        data from a long batch (the DFS partial-dump contract, trap.py).

        ``group_seeds`` — ``[(n_orders, seed), ...]`` partitioning ``orders``
        into consecutive groups, each shuffled by its OWN persistent
        ``Random(group_seed)``: a group's per-iteration visit order depends
        only on its own ``(group_orders, group_seed)``, bit-identical to a
        solo ``benchmark_batch_times(group_orders, seed=group_seed)`` call.
        This is how the search fleet's measurement owner fuses K candidate
        pairs from different worker processes into one device round without
        perturbing any worker's reproducibility (search/fleet.py) — the
        global permutation of the old single-seed path would entangle every
        group's visit order with its co-scheduled strangers.  ``None`` means
        one group ``(len(orders), seed)`` — exactly the historical
        behavior."""
        opts = opts if opts is not None else BenchOpts()
        groups = (list(group_seeds) if group_seeds is not None
                  else [(len(orders), seed)])
        if (any(n <= 0 for n, _ in groups)
                or sum(n for n, _ in groups) != len(orders)):
            raise ValueError(
                "group_seeds must partition orders into non-empty runs: "
                f"{groups} vs {len(orders)} orders")
        # one persistent RNG per group: reproducibility is per-group, never
        # a function of what else shares the device round
        group_rngs = [_random.Random(s) for _, s in groups]
        group_spans: List[range] = []
        at = 0
        for n, _ in groups:
            group_spans.append(range(at, at + n))
            at += n
        # validate before the (expensive) compile-all warmup; non-empty inner
        # lists would shift iteration indices and silently break the paired
        # -comparison alignment
        if times_out is not None and (
            len(times_out) != len(orders) or any(ts for ts in times_out)
        ):
            raise ValueError("times_out must have one EMPTY list per order")
        tr = get_tracer()
        with tr.span("bench.batch", n_orders=len(orders),
                     n_iters=opts.n_iters, seed=seed,
                     n_groups=len(groups)) as sp:
            runners = [self._runner_for(o) for o in orders]
            with tr.span("bench.batch_warm", n_orders=len(orders)):
                for r, _ in runners:
                    self._dispatch(r, 1)  # warmup/compile all before timing any
            n_samples = [1] * len(orders)
            times: List[List[float]] = (
                times_out if times_out is not None else [[] for _ in orders]
            )
            for _ in range(opts.n_iters):
                for span, rng in zip(group_spans, group_rngs):
                    perm = list(span)
                    rng.shuffle(perm)  # seeded: identical order on every host
                    for i in perm:
                        run_n, fences = runners[i]
                        t, n_samples[i] = self._measure(
                            run_n, n_samples[i], opts, fences)
                        times[i].append(t)
            sp.set("fetch_overhead", self._overhead)
            get_metrics().counter("bench.measurements").inc(
                opts.n_iters * len(orders))
        return times

    def benchmark_batch(
        self,
        orders: List[Sequence],
        opts: Optional[BenchOpts] = None,
        seed: int = 0,
    ) -> List[BenchResult]:
        return [
            BenchResult.from_times(ts)
            for ts in self.benchmark_batch_times(orders, opts, seed)
        ]


class CallableRunner:
    """ScheduleRunner over *named zero-arg callables* — external baselines
    (one fused ``jax.nn.dot_product_attention`` call, a single-jit XLA MoE)
    measured with the SAME protocol as searched schedules, including the
    decorrelated paired batch: the "order" is just the callable's name.  Each
    callable must be fully fenced (end with a ``jax.device_get``), mirroring
    the executor's fetch-fenced runners.

    CAUTION: one fence per *sample* — on a backend whose
    per-call round trip rivals the calibrated fetch overhead, the adaptive
    floor may never converge (elapsed-past-overhead stays ~0 while n_samples
    doubles).  Fast kernels on such a backend should use
    :class:`RepeatCallableRunner` instead."""

    def __init__(self, fns: Dict[str, Callable[[], None]]):
        self.fns = dict(fns)

    def prepare(self, name: str) -> Callable[[], None]:
        return self.fns[name]


class RepeatCallableRunner:
    """ScheduleRunner over named ``run_n(n)`` callables: each invocation runs
    n samples inside ONE fenced dispatch (the executor's ``prepare_n``
    discipline), so a measurement costs one fetch round trip regardless of
    n and the adaptive floor converges for arbitrarily fast kernels.  The
    callable must keep the n iterations live (loop-carried data dependence —
    e.g. ``runtime.executor.datatie`` — or XLA hoists the loop-invariant
    body and times one execution)."""

    def __init__(self, run_ns: Dict[str, Callable[[int], None]]):
        self.run_ns = dict(run_ns)

    def prepare_n(self, name: str) -> Callable[[int], None]:
        return self.run_ns[name]

    def prepare(self, name: str) -> Callable[[], None]:
        run_n = self.run_ns[name]
        return lambda: run_n(1)


class CachingBenchmarker:
    """Equivalence-keyed cache in front of any benchmarker: a schedule equal to
    an already-benchmarked one up to lane/event renaming reuses the recorded
    result instead of recompiling and re-timing (the CsvBenchmarker lookup,
    benchmarker.cpp:169-223, applied online; VERDICT r1 weak #5 — MCTS
    re-benchmarked identical rollouts).

    Lookup is an O(1) dict hit on (opts, ``canonical_key``) — the canonical
    form under lane/event renaming is equal exactly when the pairwise
    bijection check succeeds (core/sequence.py canonical_key) — and a result
    recorded under one BenchOpts is never returned for another."""

    def __init__(self, inner):
        self.inner = inner
        self._cache: dict = {}
        self.hits = 0
        self.misses = 0
        # a cache in front of a rank-coherent benchmarker is itself rank
        # -coherent: hits are local (identical on every rank — the broadcast
        # order and the restored journal agree rank-to-rank) and misses
        # inherit the inner agreement protocol (fault/resilient.py)
        self.rank_coherent = getattr(inner, "rank_coherent", False)

    @staticmethod
    def _key(order: Sequence, opts: Optional[BenchOpts]) -> Tuple:
        ok = (opts.n_iters, opts.max_retries, opts.target_secs) if opts else None
        return (ok, canonical_key(order))

    @property
    def hit_rate(self) -> float:
        """Fraction of queries answered from the cache (0.0 when unqueried)."""
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def has(self, order: Sequence, opts: Optional[BenchOpts] = None) -> bool:
        """Whether ``benchmark(order, opts)`` would be answered from the
        cache (a probe: it counts as neither hit nor miss)."""
        return self._key(order, opts) in self._cache

    def benchmark(self, order: Sequence, opts: Optional[BenchOpts] = None) -> BenchResult:
        key = self._key(order, opts)
        hit = key in self._cache
        if hit:
            self.hits += 1
            res = self._cache[key]
        else:
            res = self.inner.benchmark(order, opts)
            self._cache[key] = res
            self.misses += 1
        reg = get_metrics()
        reg.counter("bench.cache.hits" if hit else "bench.cache.misses").inc()
        reg.gauge("bench.cache.hit_rate").set(self.hit_rate)
        tr = get_tracer()
        if tr.enabled:
            tr.event("bench.cache", hit=hit, schedule=schedule_id(order),
                     pct50=res.pct50)
        return res


# -- recorded-timings replay (reference CsvBenchmarker, benchmarker.cpp:169-223) --

CSV_DELIM = "|"


def split_fidelity(cells: List[str]) -> Tuple[str, int]:
    """(fidelity, ops_start_index) of a split CSV row — THE parsing rule for
    the optional ``fid=<tag>`` cell between the stats and the ops (legacy
    rows have none and are "full").  Every reader of the dump format
    (CsvBenchmarker, postprocess, replay) must use this one definition so
    they cannot drift on which rows count as full-fidelity."""
    if len(cells) > 7 and cells[7].startswith("fid="):
        return cells[7][4:], 8
    return "full", 7


def result_row(idx: int, res: BenchResult, order: Sequence,
               fidelity: Optional[str] = None) -> str:
    """One CSV row: ``idx|pct01|pct10|pct50|pct90|pct99|stddev|op-json|...``
    (reference mcts.cpp:13-31 / dfs.cpp:84-105 dump format).  ``fidelity``
    (e.g. "screen" for a cheap multi-fidelity measurement) inserts a
    ``fid=<tag>`` cell before the ops — readable by CsvBenchmarker, invisible
    to rows that omit it, so legacy databases parse unchanged.  The tag has
    no escape mechanism, so one containing the cell delimiter would silently
    truncate and leave its tail masquerading as a malformed op cell —
    rejected here instead."""
    import json

    if fidelity is not None and CSV_DELIM in fidelity:
        raise ValueError(
            f"fidelity tag {fidelity!r} contains the CSV delimiter")

    cells = [
        str(idx),
        # float() first: a numpy scalar's repr ("np.float64(...)") would not
        # parse back, and CsvBenchmarker(strict=False) would silently skip
        # the row; plain-float repr round-trips exactly
        repr(float(res.pct01)),
        repr(float(res.pct10)),
        repr(float(res.pct50)),
        repr(float(res.pct90)),
        repr(float(res.pct99)),
        repr(float(res.stddev)),
    ] + ([f"fid={fidelity}"] if fidelity is not None else []) + [
        # '|' can only occur inside JSON strings; the \\u007c escape keeps the
        # cell valid JSON while making the row safely splittable on the delimiter
        json.dumps(op.to_json()).replace(CSV_DELIM, "\\u007c")
        for op in order
    ]
    return CSV_DELIM.join(cells)


class CsvBenchmarker:
    """Answers benchmark queries from a recorded database by equivalence-matching
    the query sequence against stored schedules — search experiments with no
    device in the loop (reference benchmarker.cpp:169-223).

    ``strict=False`` skips rows whose ops cannot be resolved against ``graph``
    (recorded against a different structural variant — e.g. a naive baseline
    dumped from the pre-choice graph); skipped row indices are kept in
    ``self.skipped`` so callers can see what the database did not cover.

    ``normalize=True`` matches queries modulo ``remove_redundant_syncs`` (both
    sides cleaned before the canonical-key lookup).  The peephole rules only delete
    sync ops with no execution effect, so normalized-equal schedules are the
    same program — this lets a database recorded by the DFS solver (raw
    terminal sequences) answer queries from the MCTS solver (which cleans
    every rollout before benchmarking), the offline replay-search workflow of
    the reference's mcts_csv drivers."""

    def __init__(self, rows: List[str], graph, strict: bool = True,
                 normalize: bool = False):
        from tenzing_tpu.core.serdes import op_from_json
        import json

        from tenzing_tpu.core.schedule import remove_redundant_syncs

        self._normalize = remove_redundant_syncs if normalize else (lambda s: s)
        self.entries: List[Tuple[Sequence, BenchResult]] = []
        self.fidelities: List[str] = []  # parallel to entries; "full" legacy
        self._by_canonical: dict = {}  # canonical(normalized seq) -> result
        self.skipped: List[int] = []
        for i, row in enumerate(rows):
            if not row.strip():
                continue
            cells = row.split(CSV_DELIM)
            try:
                res = BenchResult(
                    pct01=float(cells[1]),
                    pct10=float(cells[2]),
                    pct50=float(cells[3]),
                    pct90=float(cells[4]),
                    pct99=float(cells[5]),
                    stddev=float(cells[6]),
                )
                fid, ops_at = split_fidelity(cells)
                ops = [op_from_json(json.loads(c), graph) for c in cells[ops_at:]]
            except (KeyError, TypeError, ValueError, IndexError):
                # malformed row (e.g. dump truncated mid-write) or ops recorded
                # against a different structural variant
                if strict:
                    raise
                self.skipped.append(i)
                continue
            seq = Sequence(ops)
            self.entries.append((seq, res))
            self.fidelities.append(fid)
            # first FULL row wins for duplicate schedules (e.g. a search-time
            # row superseded by a final-batch row earlier in the file).
            # Screen-fidelity rows never answer benchmark queries: their
            # ~1 ms-floor numbers are bookkeeping, and letting one shadow a
            # full-floor twin would replay ~100x off-regime measurements.
            if fid == "full":
                self._by_canonical.setdefault(
                    canonical_key(self._normalize(seq)), res)

    @classmethod
    def from_file(cls, path: str, graph, strict: bool = True,
                  normalize: bool = False) -> "CsvBenchmarker":
        with open(path) as f:
            return cls(f.read().splitlines(), graph, strict=strict,
                       normalize=normalize)

    def benchmark(self, order: Sequence, opts: Optional[BenchOpts] = None) -> BenchResult:
        res = self._by_canonical.get(canonical_key(self._normalize(order)))
        if res is None:
            raise KeyError(
                f"no recorded schedule equivalent to: {order.desc()}"
            )
        return res
