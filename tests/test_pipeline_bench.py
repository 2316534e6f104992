"""Async compile pipeline (bench/pipeline.py, ISSUE 5).

Acceptance coverage:

* with prefetch enabled, a deterministic fake-runner harness produces
  results **bit-identical** to prefetch-off for all three solvers (MCTS,
  DFS, hill-climb) — hints consume no search RNG and touch no search state;
* a wall-clock test demonstrates real compile/measure overlap: total wall
  for a multi-candidate batch < serialized compile-time + measure-time;
* background compile failures surface on the foreground ``benchmark()``
  call, classified by the fault taxonomy, and deterministic ones quarantine
  exactly once — the resilient layer's protocol is unchanged;
* the pool leaks no threads: ``close()`` joins the workers, the SIGINT trap
  handler cancels pending compiles;
* the schedule-identity memo (``Sequence.cached``) serves stable values and
  invalidates on mutation.
"""

import threading
import time

import pytest

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    BenchResult,
    CachingBenchmarker,
    CsvBenchmarker,
    result_row,
    schedule_id,
)
from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.schedule import remove_redundant_syncs
from tenzing_tpu.core.sequence import canonical_key
from tenzing_tpu.models.spmv import SpMVCompound
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.obs.tracer import Tracer, set_tracer
from tenzing_tpu.solve.dfs import DfsOpts, enumerate_schedules
from tenzing_tpu.solve.dfs import explore as dfs_explore
from tenzing_tpu.solve.local import LocalOpts, hill_climb
from tenzing_tpu.solve.mcts import MctsOpts, explore
from tenzing_tpu.utils import trap


@pytest.fixture
def tracer():
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        yield reg
    finally:
        set_metrics(prev)


def _graph():
    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    return g


def _synth_result(seq) -> BenchResult:
    import hashlib

    key = canonical_key(remove_redundant_syncs(seq))
    h = hashlib.sha256(repr(key).encode()).digest()
    t = 1.0 + int.from_bytes(h[:8], "big") / float(1 << 64)
    return BenchResult.from_times([t, t, t])


@pytest.fixture(scope="module")
def corpus():
    """The full deduplicated 2-lane SpMV space as recorded CSV rows (the
    chaos-test corpus pattern: deterministic answers, no device)."""
    states = enumerate_schedules(_graph(), Platform.make_n_lanes(2),
                                 max_seqs=10_000)
    assert 3 <= len(states) < 10_000
    rows = [result_row(i, _synth_result(st.sequence), st.sequence)
            for i, st in enumerate(states)]
    return rows, [st.sequence for st in states]


def mk_db(rows):
    return CsvBenchmarker(rows, _graph(), normalize=True)


class FakeExecutor:
    """Compile stand-in: ``precompile``/``is_compiled`` against a set, with
    an optional per-compile sleep (the overlap test) and an optional
    failure oracle (the chaos tests)."""

    def __init__(self, compile_secs: float = 0.0, fail=None):
        self.compile_secs = compile_secs
        self.fail = fail
        self.compiled = set()
        self.precompiles = 0
        self._lock = threading.Lock()

    def is_compiled(self, order) -> bool:
        with self._lock:
            return schedule_id(order) in self.compiled

    def precompile(self, order) -> bool:
        if self.fail is not None:
            exc = self.fail(order)
            if exc is not None:
                raise exc
        if self.compile_secs:
            time.sleep(self.compile_secs)
        with self._lock:
            sid = schedule_id(order)
            if sid in self.compiled:
                return False
            self.compiled.add(sid)
            self.precompiles += 1
            return True


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("tz-prefetch") and t.is_alive()]


def _sims_key(sims):
    return [(canonical_key(remove_redundant_syncs(s.order)),
             s.result.pct50) for s in sims]


# -- accounting / fault surfacing -------------------------------------------


def test_prefetch_issue_hit_wasted_accounting(corpus, registry, tracer):
    rows, terminals = corpus
    ex = FakeExecutor()
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=2)
    try:
        issued = p.prefetch(terminals[:3])
        assert issued == 3
        # re-hinting is deduplicated, non-Sequence orders are skipped
        assert p.prefetch(terminals[:3] + ["not-a-sequence"]) == 0
        for o in terminals[:2]:
            p.benchmark(o, None)
        assert p.hits == 2
        # let the third (speculative) compile land before close(): on a
        # loaded host it can still be queued, and close() cancels queued
        # work — which would (correctly) report wasted()==0
        deadline = time.time() + 10.0
        while p.wasted() < 1 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        p.close()
    assert p.issued == 3 and p.wasted() == 1 and p.failed == 0
    assert registry.counter("pipeline.prefetch.issued").value == 3
    assert registry.counter("pipeline.prefetch.hits").value == 2
    assert registry.counter("pipeline.prefetch.wasted").value == 1
    # every background compile landed as a pipeline.precompile span
    spans = [s for s in tracer.spans() if s.name == "pipeline.precompile"]
    assert len(spans) == 3
    assert not _prefetch_threads()  # close() joined the workers
    # closed: hints are no-ops, benchmark still answers
    assert p.prefetch(terminals[3:4]) == 0
    assert p.benchmark(terminals[0], None) == mk_db(rows).benchmark(
        terminals[0], None)


def test_already_compiled_hints_are_skipped(corpus, registry):
    rows, terminals = corpus
    ex = FakeExecutor()
    ex.precompile(terminals[0])
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=1)
    try:
        assert p.prefetch(terminals[:1]) == 0  # is_compiled short-circuits
        assert ex.precompiles == 1
    finally:
        p.close()


def test_queue_bound_drops_excess_hints(corpus, registry):
    rows, terminals = corpus
    n = min(len(terminals), 8)
    ex = FakeExecutor(compile_secs=0.2)
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=1, depth=2)
    try:
        p.prefetch(terminals[:n])
        # worker=1, depth=2: at most 2 in flight; the rest dropped (and
        # re-hintable later), never queued unboundedly
        assert p.issued <= 2
        assert p.dropped >= n - 2
        assert registry.counter("pipeline.prefetch.dropped").value \
            == p.dropped
    finally:
        p.close()


def test_background_failure_surfaces_classified_and_quarantines_once(
        corpus, registry, tracer, tmp_path):
    """A background compile failure is recorded off the control plane and
    surfaced on the FOREGROUND benchmark() call, where the resilient layer
    classifies it (fault taxonomy), quarantines the deterministic candidate
    exactly once, and never measures it."""
    from collections import Counter

    from tenzing_tpu.fault import (
        BackoffPolicy,
        Quarantine,
        QuarantinedScheduleError,
        ResilientBenchmarker,
    )

    rows, terminals = corpus
    bad = terminals[0]
    bad_sid = schedule_id(bad)

    class CountingDb:
        def __init__(self, db):
            self.db = db
            self.by_sid = Counter()

        def benchmark(self, order, opts=None):
            self.by_sid[schedule_id(order)] += 1
            return self.db.benchmark(order, opts)

    ex = FakeExecutor(fail=lambda o: RuntimeError(
        "failed to compile: injected") if schedule_id(o) == bad_sid else None)
    counting = CountingDb(mk_db(rows))
    p = PrefetchingBenchmarker(counting, executor=ex, workers=1)
    rb = ResilientBenchmarker(
        p, quarantine=Quarantine(str(tmp_path / "q.json")),
        policy=BackoffPolicy(retries=2, base_secs=0.0, jitter=0.0),
        sleep=lambda s: None)
    try:
        assert p.prefetch([bad, terminals[1]]) == 2
        with pytest.raises(RuntimeError, match="failed to compile"):
            rb.benchmark(bad, None)
        # classified deterministic -> quarantined, never measured, and the
        # pipeline recorded the failure with its taxonomy class
        assert counting.by_sid[bad_sid] == 0
        assert p.failed == 1 and p.surfaced == 1
        evs = [e for e in tracer.events()
               if e.name == "pipeline.precompile_failed"]
        assert evs and evs[0].attrs["error_class"] == "deterministic"
        with pytest.raises(QuarantinedScheduleError):
            rb.benchmark(bad, None)
        assert counting.by_sid[bad_sid] == 0
        # the healthy hint still measures normally (and was a prefetch hit)
        rb.benchmark(terminals[1], None)
        assert counting.by_sid[schedule_id(terminals[1])] == 1
        assert p.hits == 1
    finally:
        p.close()
    assert not _prefetch_threads()


# -- fused batched rounds (the search fleet's measurement owner) ------------


class BatchDb:
    """CsvBenchmarker plus the fused-round batch protocol
    (``benchmark_batch_times``) — the shape the fleet's measurement owner
    drives (search/fleet.py): each member answered from the recorded corpus
    in one call, per-group seeds recorded for the passthrough assertion."""

    def __init__(self, db):
        self.db = db
        self.batch_calls = 0
        self.last_group_seeds = None

    def benchmark(self, order, opts=None):
        return self.db.benchmark(order, opts)

    def benchmark_batch_times(self, orders, opts=None, seed=0,
                              times_out=None, group_seeds=None):
        self.batch_calls += 1
        self.last_group_seeds = group_seeds
        out = []
        for o in orders:
            r = self.db.benchmark(o, opts)
            ts = list(r.times) if r.times else [r.pct50] * 3
            if times_out is not None:
                times_out[len(out)].extend(ts)
            out.append(ts)
        return out


def test_batched_round_full_queue_drops_hints_without_blocking(
        corpus, registry):
    """A fused measurement round over a saturated prefetch pipeline must
    DROP its members' hints and still run: the members are simply not
    prefetched (the inner batch warms them itself), the round never blocks
    behind speculative work hinted earlier, and the shed hints land on the
    ``dropped`` tally (re-hintable later)."""
    rows, terminals = corpus
    assert len(terminals) >= 6
    gate = threading.Event()

    class GatedExecutor(FakeExecutor):
        def precompile(self, order):
            gate.wait(30.0)
            return super().precompile(order)

    ex = GatedExecutor()
    inner = BatchDb(mk_db(rows))
    p = PrefetchingBenchmarker(inner, executor=ex, workers=1, depth=2)
    try:
        # saturate: 1 compile parked on the gate + 1 queued = depth
        p.prefetch(terminals[:4])
        assert p.issued == 2 and p.dropped == 2
        members = terminals[4:6]
        t0 = time.time()
        times = p.benchmark_batch_times(
            members, None, seed=3, group_seeds=[(1, 5), (1, 7)])
        wall = time.time() - t0
        # the round completed inline while the pool stayed parked
        assert wall < 5.0 and not gate.is_set()
        assert inner.batch_calls == 1
        assert inner.last_group_seeds == [(1, 5), (1, 7)]
        db = mk_db(rows)
        assert times == [[db.benchmark(o, None).pct50] * 3 for o in members]
        # both members' hints were shed, never queued behind the backlog
        assert p.dropped == 4
        assert registry.counter("pipeline.prefetch.dropped").value == 4
    finally:
        gate.set()
        p.close()
    assert not _prefetch_threads()


def test_batched_round_surfaces_stored_failure_exactly_once(
        corpus, registry):
    """A background compile failure stored for a batch member surfaces on
    the foreground join of the fused round — once.  The raise consumes the
    stored failure (the resilient layer's retry contract), so the next
    round over the same members reaches the inner batch instead of
    re-raising a stale exception."""
    rows, terminals = corpus
    bad, good = terminals[0], terminals[1]
    bad_sid = schedule_id(bad)
    ex = FakeExecutor(fail=lambda o: RuntimeError(
        "failed to compile: injected") if schedule_id(o) == bad_sid else None)
    inner = BatchDb(mk_db(rows))
    p = PrefetchingBenchmarker(inner, executor=ex, workers=1)
    try:
        assert p.prefetch([bad]) == 1
        deadline = time.time() + 10.0
        while p.failed < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert p.failed == 1
        with pytest.raises(RuntimeError, match="injected"):
            p.benchmark_batch_times([bad, good], None, seed=1)
        # surfaced before the inner batch ran, and exactly once
        assert p.surfaced == 1 and inner.batch_calls == 0
        assert registry.counter("pipeline.prefetch.surfaced").value == 1
        times = p.benchmark_batch_times([bad, good], None, seed=1)
        assert len(times) == 2 and inner.batch_calls == 1
        assert p.surfaced == 1  # consumed: no stale re-raise
        assert registry.counter("pipeline.prefetch.surfaced").value == 1
        assert p.hits == 1  # the healthy member's hint landed meanwhile
    finally:
        p.close()
    assert not _prefetch_threads()


def test_transient_background_failure_retries_through_to_real_attempt(
        corpus, registry):
    """A surfaced TRANSIENT background failure is consumed by the raise:
    the resilient retry reaches the real foreground attempt and succeeds."""
    from tenzing_tpu.fault import BackoffPolicy, ResilientBenchmarker
    from tenzing_tpu.fault.errors import TransientError

    rows, terminals = corpus
    flaky = {"armed": True}

    def fail(order):
        if flaky["armed"]:
            flaky["armed"] = False
            return TransientError("injected background flake")
        return None

    ex = FakeExecutor(fail=fail)
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=1)
    rb = ResilientBenchmarker(
        p, policy=BackoffPolicy(retries=2, base_secs=0.0, jitter=0.0),
        sleep=lambda s: None)
    try:
        p.prefetch(terminals[:1])
        res = rb.benchmark(terminals[0], None)  # surfaced, retried, answered
        assert res == _synth_result(terminals[0])
        assert p.surfaced == 1
    finally:
        p.close()


def test_trap_handler_cancels_pending_compiles(corpus):
    """The SIGINT path: the trap handler only closes the intake (it must
    not touch pool locks the interrupted thread may hold); close()
    afterwards cancels the still-queued compiles and joins cleanly."""
    rows, terminals = corpus
    n = min(len(terminals), 6)
    ex = FakeExecutor(compile_secs=0.3)
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=1,
                               depth=n)
    try:
        p.prefetch(terminals[:n])
        assert p.issued >= 2
        trap.run_callbacks()  # what the real SIGINT handler does
        assert p.prefetch(terminals[:n]) == 0  # closed to new work
    finally:
        p.close()
    # cancel_futures dropped the queued compiles: far fewer ran than issued
    assert ex.precompiles <= 2
    assert not _prefetch_threads()
    # close() unregistered the pipeline's trap handler
    assert p._trap_cancel not in trap.callbacks()


# -- bit-identical search behavior -------------------------------------------


class NullExecutor:
    """Compiles nothing and remembers nothing: the prefetcher it gives has a
    width (``workers``) and no effect."""

    def is_compiled(self, order) -> bool:
        return False

    def precompile(self, order) -> bool:
        return False


def test_solvers_bit_identical_prefetch_on_vs_off(corpus, registry):
    """The acceptance criterion.  DFS: measured results with prefetch
    enabled are bit-identical to prefetch-off over the deterministic
    corpus.  The tree search draws ahead by the prefetcher's ``workers``,
    so its "off" side is the same search against a prefetcher of that
    width whose executor compiles nothing: what the compile threads do
    never reaches the search."""
    rows, _ = corpus
    g = _graph()
    plat = Platform.make_n_lanes(2)

    def run_mcts(prefetcher):
        # through the prefetcher (over the same corpus), so that a hinted
        # rollout's compile is consumed: a hit
        return explore(g, plat, prefetcher,
                       MctsOpts(n_iters=24, seed=3, prefetch=prefetcher))

    def run_dfs(prefetcher):
        return dfs_explore(g, plat, mk_db(rows),
                           DfsOpts(max_seqs=10_000, prefetch=prefetcher))

    off_dfs = run_dfs(None)
    null = PrefetchingBenchmarker(mk_db(rows), executor=NullExecutor(),
                                  workers=2)
    try:
        off_mcts = run_mcts(null)
    finally:
        null.close()
    ex = FakeExecutor(compile_secs=0.002)
    p = PrefetchingBenchmarker(mk_db(rows), executor=ex, workers=2)
    try:
        on_mcts = run_mcts(p)
        # the rollouts measured were the rollouts hinted (the parent's
        # speculative completions were issued and never hit)
        assert p.hits > 0
        on_dfs = run_dfs(p)
        assert p.issued > 0  # the hints actually flowed
    finally:
        p.close()
    assert _sims_key(on_mcts.sims) == _sims_key(off_mcts.sims)
    assert on_mcts.tree_size == off_mcts.tree_size
    assert _sims_key(on_dfs.sims) == _sims_key(off_dfs.sims)
    assert not _prefetch_threads()


# what ``explore(..., MctsOpts(n_iters=24, seed=3))`` measured on the corpus
# at PR 43's parent commit, in order (``schedule_id`` of every sim), and the
# tree it left
PARENT_SIMS = (["e1067b0eecb0", "ce95dd513ed8", "588e350ccbd3", "5fbab9f8c71b",
                "e6ebdcb535a1", "f00e27cde505", "545d468c874e", "4fe6c3a3b6d7",
                "a3fec232ca6c", "be2440a2ba39", "2540d4c8ec85", "732fef6f0a15",
                "e6ebdcb535a1", "a1e315818944", "eef3f8becb09", "531c1d626e9e",
                "4875b1dfb836", "eeeae76a08d7", "e7e68b3df9d6", "a55e451e07ea",
                "b54353c03314", "eeede6e6ccf2", "f00e27cde505", "e0ea00bf0748"],
               30)


def test_mcts_without_prefetcher_is_the_parents_search(corpus, registry):
    """No prefetcher, no lookahead: rollout for rollout the search the
    parent commit ran, and the same tree."""
    rows, _ = corpus
    res = explore(_graph(), Platform.make_n_lanes(2), mk_db(rows),
                  MctsOpts(n_iters=24, seed=3))
    sims, tree_size = PARENT_SIMS
    assert [schedule_id(s.order) for s in res.sims] == sims
    assert res.tree_size == tree_size


def test_hill_climb_bit_identical_prefetch_on_vs_off():
    """Hill-climb neighbor batches are materialized before the measure loop
    either way (pure replay): the accepted chain and every measured
    neighbor are identical with and without prefetch."""
    from tests.test_local import PHASES, RiggedBenchmarker, mk

    def climb(prefetcher):
        g, plat, _ = mk()
        return hill_climb(
            g, plat, CachingBenchmarker(RiggedBenchmarker()), PHASES,
            opts=LocalOpts(budget=18, bench_opts=BenchOpts(n_iters=1),
                           seed=3, prefetch=prefetcher),
        )

    off = climb(None)
    ex = FakeExecutor()
    p = PrefetchingBenchmarker(None, executor=ex, workers=2)
    try:
        on = climb(p)
        assert p.issued > 0
    finally:
        p.close()
    key = lambda r: ([(canonical_key(s.order), s.result.pct50)
                      for s in r.sims],
                     canonical_key(r.final.order), r.final.result.pct50)
    assert key(on) == key(off)


# -- compile/measure overlap --------------------------------------------------


def test_wall_clock_overlap_beats_serialized_compile_plus_measure(corpus):
    """The headline: for a multi-candidate batch, pipelined wall <
    serialized compile + measure.  Compile is simulated at 80 ms (sleep —
    GIL-released, like XLA), measurement at 30 ms; with 4 workers the
    compiles hide almost entirely behind the measurements."""
    rows, terminals = corpus
    n = min(len(terminals), 6)
    cands = terminals[:n]
    compile_s, measure_s = 0.08, 0.03

    class SlowDeviceBench:
        """Device stand-in that compiles inline when the program cache
        misses — exactly the lazy TraceExecutor behavior."""

        def __init__(self, ex, db):
            self.ex = ex
            self.db = db

        def benchmark(self, order, opts=None):
            if not self.ex.is_compiled(order):
                self.ex.precompile(order)  # foreground (serialized) compile
            time.sleep(measure_s)
            return self.db.benchmark(order, opts)

    # serialized reference: compile + measure per candidate, no overlap
    ex_off = FakeExecutor(compile_secs=compile_s)
    bench_off = SlowDeviceBench(ex_off, mk_db(rows))
    t0 = time.perf_counter()
    for o in cands:
        bench_off.benchmark(o, None)
    serial_wall = time.perf_counter() - t0
    assert serial_wall >= n * (compile_s + measure_s) * 0.9

    # pipelined: hint the batch, then measure in the foreground
    ex_on = FakeExecutor(compile_secs=compile_s)
    p = PrefetchingBenchmarker(SlowDeviceBench(ex_on, mk_db(rows)),
                               executor=ex_on, workers=4, depth=n)
    try:
        t0 = time.perf_counter()
        p.prefetch(cands)
        for o in cands:
            p.benchmark(o, None)
        pipe_wall = time.perf_counter() - t0
    finally:
        p.close()
    assert p.hits == n  # every foreground call found its program ready
    # generous margin (CI scheduling noise): the pipeline must clearly beat
    # the serialized sum-of-parts
    assert pipe_wall < 0.75 * serial_wall, (pipe_wall, serial_wall)


# -- schedule-identity memoization (ISSUE 5 satellite) ------------------------


def test_sequence_memo_stable_and_invalidated_on_mutation(corpus):
    from tenzing_tpu.core.resources import Event
    from tenzing_tpu.core.serdes import sequence_to_json_str
    from tenzing_tpu.core.sync_ops import EventSync

    _, terminals = corpus
    seq = terminals[0][:]  # private copy (slice -> new Sequence)
    k1 = canonical_key(seq)
    assert canonical_key(seq) is k1  # memo serves the same object
    j1 = sequence_to_json_str(seq)
    assert sequence_to_json_str(seq) is j1
    s1 = schedule_id(seq)
    assert schedule_id(seq) is s1
    seq.push_back(EventSync(Event(0)))
    # mutation invalidates every derivation
    assert canonical_key(seq) != k1
    assert sequence_to_json_str(seq) != j1
    assert schedule_id(seq) != s1
    # and the recomputed values are the true ones
    assert canonical_key(seq) == canonical_key(
        type(seq)(seq.vector()))
    assert sequence_to_json_str(seq) == sequence_to_json_str(
        type(seq)(seq.vector()))
