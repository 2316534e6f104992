"""Chaos + checkpoint/resume acceptance (ISSUE 3).

A seeded MCTS + DFS search over a *recorded corpus* (the full deduplicated
2-lane SpMV space, rendered to CSV rows and replayed through CsvBenchmarker
— the reference's mcts_csv workflow, so no device is in the loop and every
measurement answer is deterministic) is run under seeded fault injection:
>= 20% transient failures, injected hangs caught by the watchdog, and
deterministic per-schedule failures.  The acceptance criteria:

* the chaos run crashes nowhere and finds the SAME best schedule as the
  clean run;
* every failure lands as a classified ``fault.*`` telemetry event;
* deterministic failures are quarantined — each broken candidate is
  measured at most once even across a kill + resume;
* a killed run (KeyboardInterrupt mid-measurement, the SIGINT path) leaves
  a complete, deadlock-free telemetry bundle with all in-flight spans
  closed, and ``--resume`` re-measures nothing already measured while
  reaching the same final best as an uninterrupted run.
"""

import hashlib
import json
from collections import Counter

import pytest

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    BenchResult,
    CachingBenchmarker,
    CsvBenchmarker,
    result_row,
)
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.schedule import remove_redundant_syncs
from tenzing_tpu.core.sequence import canonical_key
from tenzing_tpu.fault import (
    BackoffPolicy,
    FaultInjectingBenchmarker,
    InjectSpec,
    JournalingBenchmarker,
    Quarantine,
    ResilientBenchmarker,
    SearchCheckpoint,
)
from tenzing_tpu.fault.inject import _schedule_fails
from tenzing_tpu.models.spmv import SpMVCompound
from tenzing_tpu.obs.export import to_jsonl
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.obs.tracer import Tracer, get_tracer, set_tracer
from tenzing_tpu.solve.dfs import DfsOpts, enumerate_schedules
from tenzing_tpu.solve.dfs import explore as dfs_explore
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


def _key(order):
    return canonical_key(remove_redundant_syncs(order))


def _synth_result(seq) -> BenchResult:
    """Deterministic 'measurement' from the schedule's canonical identity:
    the corpus is a pure function of the search space, so clean and chaos
    runs are comparable bit-for-bit."""
    h = hashlib.sha256(repr(_key(seq)).encode()).digest()
    t = 1.0 + int.from_bytes(h[:8], "big") / float(1 << 64)
    return BenchResult.from_times([t, t, t])


@pytest.fixture(scope="module")
def corpus():
    """The full deduplicated 2-lane space as recorded CSV rows."""
    states = enumerate_schedules(_graph(), Platform.make_n_lanes(2),
                                 max_seqs=10_000)
    assert 3 <= len(states) < 10_000  # complete coverage
    rows = [result_row(i, _synth_result(st.sequence), st.sequence)
            for i, st in enumerate(states)]
    return rows, [st.sequence for st in states]


def mk_db(rows):
    return CsvBenchmarker(rows, _graph(), normalize=True)


class CountingInner:
    """Device stand-in instrumentation: counts attempts (calls in) and
    completions (calls that returned) per (canonical key, opts) and per
    telemetry schedule id; optionally simulates a SIGINT mid-measurement
    after N attempts (running the trap callbacks exactly like the signal
    handler would, then raising KeyboardInterrupt)."""

    def __init__(self, db, interrupt_after=None, on_interrupt=None):
        self.db = db
        self.attempts = Counter()
        self.completed = Counter()
        self.by_sid = Counter()
        self.orders = {}  # sid -> the order object, for targeted re-queries
        self.total = 0
        self.interrupt_after = interrupt_after
        self.on_interrupt = on_interrupt

    def _k(self, order, opts):
        ok = (opts.n_iters, opts.max_retries, opts.target_secs) if opts \
            else None
        return (_key(order), ok)

    def benchmark(self, order, opts=None):
        from tenzing_tpu.bench.benchmarker import schedule_id

        self.total += 1
        self.attempts[self._k(order, opts)] += 1
        sid = schedule_id(order)
        self.by_sid[sid] += 1
        self.orders[sid] = order
        # >= not ==: under the watchdog, an attempt can run on an abandoned
        # worker thread where a raised interrupt is swallowed with the
        # discarded result — every later attempt must keep "delivering the
        # signal" until one propagates from a live measurement
        if self.interrupt_after is not None and \
                self.total >= self.interrupt_after:
            if self.on_interrupt is not None:
                self.on_interrupt()
            trap.run_callbacks()  # what the real SIGINT handler does
            raise KeyboardInterrupt
        res = self.db.benchmark(order, opts)
        self.completed[self._k(order, opts)] += 1
        return res


def _fast_policy():
    return BackoffPolicy(retries=8, base_secs=0.0, jitter=0.0)


def _best(sims):
    s = min(sims, key=lambda s: s.result.pct50)
    return _key(s.order), s.result.pct50


def _validate_bundle(text):
    """Every span record's parent id resolves within the bundle, and the
    in-flight search spans were flushed closed."""
    recs = [json.loads(line) for line in text.splitlines()]
    spans = {r["id"]: r for r in recs if r["kind"] == "span"}
    for r in spans.values():
        assert r["dur_us"] >= 0
        if r["parent"] is not None:
            assert r["parent"] in spans, f"dangling parent in {r['name']}"
    flushed = {r["name"] for r in spans.values()
               if r["attrs"].get("flushed")}
    assert "mcts.explore" in flushed
    assert "mcts.iter" in flushed
    return recs


# deterministic-injection channel shared by the test and its precondition
DET_SPEC = InjectSpec("deterministic", 0.12, 5)
CHAOS_SPECS = [DET_SPEC,
               InjectSpec("transient", 0.25, 31),
               InjectSpec("hang", 0.05, 53)]


def _chaos_stack(rows, quarantine_path, ckpt=None, interrupt_after=None,
                 on_interrupt=None):
    # counting sits ABOVE injection: an attempt counts whether the flaky
    # "device" completed it or not — that is what "measured at most once"
    # must bound
    inject = FaultInjectingBenchmarker(mk_db(rows), CHAOS_SPECS,
                                       hang_secs=2.5)
    counting = CountingInner(inject, interrupt_after=interrupt_after,
                             on_interrupt=on_interrupt)
    resilient = ResilientBenchmarker(
        counting, timeout_secs=1.0, policy=_fast_policy(),
        quarantine=Quarantine(quarantine_path), sleep=lambda s: None)
    layer = JournalingBenchmarker(resilient, ckpt) if ckpt else resilient
    return CachingBenchmarker(layer), counting, inject, resilient


def test_chaos_search_finds_clean_best_with_kill_and_resume(
        tmp_path, tracer, registry, corpus):
    rows, terminals = corpus
    plat = Platform.make_n_lanes(2)
    n_iters = 30

    # -- clean reference: seeded MCTS + exhaustive DFS, no faults ----------
    mcts_clean = explore(_graph(), plat, mk_db(rows),
                         MctsOpts(n_iters=n_iters, seed=3))
    dfs_clean = dfs_explore(_graph(), plat, mk_db(rows),
                            DfsOpts(max_seqs=10_000))
    assert len(dfs_clean.sims) == len(terminals)
    clean_key, clean_pct50 = _best(mcts_clean.sims + dfs_clean.sims)

    # precondition of the equality criterion: the injection seed must not
    # deterministically break the best schedule itself (a quarantined best
    # is legitimately unfindable) — in either spelling the solvers query
    from tenzing_tpu.bench.benchmarker import schedule_id

    best_raw = min(terminals, key=lambda s: _synth_result(s).pct50)
    for spelling in (best_raw, remove_redundant_syncs(best_raw)):
        assert not _schedule_fails(schedule_id(spelling), DET_SPEC)

    # -- chaos phase A: injected faults, killed mid-measurement ------------
    ckdir = str(tmp_path / "ckpt")
    qpath = str(tmp_path / "ckpt" / "quarantine.json")
    ckpt = SearchCheckpoint(ckdir)
    bundles = []
    bench_a, count_a, inject_a, _ = _chaos_stack(
        rows, qpath, ckpt=ckpt, interrupt_after=16,
        on_interrupt=lambda: bundles.append(to_jsonl(get_tracer())))
    with pytest.raises(KeyboardInterrupt):
        explore(_graph(), plat, bench_a,
                MctsOpts(n_iters=n_iters, seed=3, checkpoint=ckpt,
                         dump_csv_path=str(tmp_path / "partial.csv")))
    # the simulated SIGINT produced a complete bundle with in-flight spans
    # closed, a partial CSV, and an interrupted-cursor snapshot
    _validate_bundle(bundles[0])
    assert (tmp_path / "partial.csv").exists()
    state = SearchCheckpoint(ckdir).load_state()
    assert state["mcts"]["interrupted"] is True

    # -- chaos phase B: resume — quarantine + journal carry over -----------
    ckpt2 = SearchCheckpoint(ckdir)
    bench_b, count_b, inject_b, _ = _chaos_stack(rows, qpath, ckpt=ckpt2)
    restored = ckpt2.restore_into(bench_b, _graph())
    assert restored > 0
    res_mcts = explore(_graph(), plat, bench_b,
                       MctsOpts(n_iters=n_iters, seed=3, checkpoint=ckpt2))
    res_dfs = dfs_explore(_graph(), plat, bench_b,
                          DfsOpts(max_seqs=10_000, checkpoint=ckpt2))

    # zero crashes, and the chaos search found the clean run's best
    chaos_key, chaos_pct50 = _best(res_mcts.sims + res_dfs.sims)
    assert chaos_key == clean_key
    assert chaos_pct50 == clean_pct50

    # schedules measured before the kill were not re-measured after it
    for key, n in count_a.completed.items():
        assert count_b.completed[key] == 0, \
            "resume re-measured an already-measured schedule"

    # the chaos actually happened: >=20% transient injection rate and >=2
    # hangs (seeded — these counts are deterministic for fixed seeds)
    calls = inject_a.calls + inject_b.calls
    transients = (inject_a.injected["transient"]
                  + inject_b.injected["transient"])
    hangs = inject_a.injected["hang"] + inject_b.injected["hang"]
    dets = (inject_a.injected["deterministic"]
            + inject_b.injected["deterministic"])
    assert calls > 50
    assert transients >= 0.2 * calls
    assert hangs >= 2
    assert dets >= 1

    # every failure is a classified fault.* event: one fault.error per
    # injected failure (hangs surface as watchdog MeasurementTimeouts),
    # each carrying a taxonomy class
    errs = [e for e in tracer.events() if e.name == "fault.error"]
    assert len(errs) == transients + hangs + dets
    assert all(e.attrs["error_class"] in
               ("transient", "deterministic", "device_lost") for e in errs)
    assert any(e.attrs["error"] == "MeasurementTimeout" for e in errs)
    retries = [e for e in tracer.events() if e.name == "fault.retry"]
    assert len(retries) >= transients  # each transient/hang was retried

    # deterministic failures are quarantined, persist across the restart,
    # and each broken candidate was attempted at most once overall
    quar = Quarantine(qpath)
    assert len(quar) >= 1
    for sid in quar.entries:
        assert count_a.by_sid[sid] + count_b.by_sid[sid] <= 1
    qevents = [e for e in tracer.events() if e.name == "fault.quarantine"]
    assert {e.attrs["schedule"] for e in qevents} == set(quar.entries)
    # a re-query of a quarantined candidate — as after yet another restart
    # — is refused by the persisted quarantine without touching the device
    from tenzing_tpu.fault import QuarantinedScheduleError

    sid = next(iter(quar.entries))
    order = {**count_a.orders, **count_b.orders}[sid]
    before = count_a.by_sid[sid] + count_b.by_sid[sid]
    with pytest.raises(QuarantinedScheduleError):
        bench_b.benchmark(order, None)
    assert count_a.by_sid[sid] + count_b.by_sid[sid] == before
    assert registry.counter("fault.quarantine_hits").value >= 1


def test_resume_after_interrupt_no_remeasure_and_same_best(
        tmp_path, tracer, corpus):
    """The pure resume criterion, no chaos: kill a clean search
    mid-measurement, resume from the checkpoint, verify nothing measured
    before the kill is measured again and the final best matches an
    uninterrupted run exactly."""
    rows, _ = corpus
    plat = Platform.make_n_lanes(2)
    opts = dict(n_iters=24, seed=3)

    # uninterrupted reference
    ref_inner = CountingInner(mk_db(rows))
    ref = explore(_graph(), plat,
                  CachingBenchmarker(ResilientBenchmarker(
                      ref_inner, policy=_fast_policy())),
                  MctsOpts(**opts))
    ref_key, ref_pct50 = _best(ref.sims)
    assert ref_inner.total > 10

    # interrupted run: journaling on, SIGINT simulated mid-measurement
    ckdir = str(tmp_path / "ckpt")
    ckpt = SearchCheckpoint(ckdir)
    bundles = []
    inner1 = CountingInner(
        mk_db(rows), interrupt_after=9,
        on_interrupt=lambda: bundles.append(to_jsonl(get_tracer())))
    bench1 = CachingBenchmarker(JournalingBenchmarker(
        ResilientBenchmarker(inner1, policy=_fast_policy()), ckpt))
    with pytest.raises(KeyboardInterrupt):
        explore(_graph(), plat, bench1, MctsOpts(**opts, checkpoint=ckpt))
    _validate_bundle(bundles[0])  # complete, deadlock-free, spans closed

    # resume: restore the journal, re-run the same seeded search
    ckpt2 = SearchCheckpoint(ckdir)
    inner2 = CountingInner(mk_db(rows))
    bench2 = CachingBenchmarker(JournalingBenchmarker(
        ResilientBenchmarker(inner2, policy=_fast_policy()), ckpt2))
    restored = ckpt2.restore_into(bench2, _graph())
    assert restored == sum(inner1.completed.values()) > 0
    res = explore(_graph(), plat, bench2, MctsOpts(**opts, checkpoint=ckpt2))

    # no already-measured schedule was re-measured...
    for key in inner1.completed:
        assert inner2.attempts[key] == 0
    # ... every (schedule, fidelity) hit the device at most once overall...
    combined = inner1.completed + inner2.completed
    assert combined and max(combined.values()) == 1
    # ... and the resumed search reconstructs the reference exactly
    got_key, got_pct50 = _best(res.sims)
    assert (got_key, got_pct50) == (ref_key, ref_pct50)
    assert len(res.sims) == len(ref.sims)
    assert [s.result.pct50 for s in res.sims] == \
        [s.result.pct50 for s in ref.sims]
    # the resumed checkpoint now carries the completed cursor
    assert SearchCheckpoint(ckdir).load_state()["mcts"]["it"] == \
        opts["n_iters"] - 1


CORRUPT_SPEC = InjectSpec("corrupt", 0.25, 7)


def test_corruption_chaos_every_mutation_caught(tmp_path, tracer, registry,
                                                corpus):
    """Corruption-chaos acceptance (ISSUE 4): a seeded MCTS + exhaustive
    DFS with >= 20% schedule corruption — sync ops dropped/reordered by the
    injector, with the ORIGINAL oracle (EventSynchronizer, via
    tests/test_verify.oracle_unsound_check) deciding which mutations count,
    so the verifier under test is never consulted to pick them — must:

    * have every mutated candidate caught by the independent verifier and
      quarantined: ZERO unsound schedules measured;
    * still find the clean run's best schedule (the corruption seed is
      precondition-checked not to hit the best candidate, the same pattern
      as DET_SPEC above);
    * emit a ``verify.unsound`` event per catch.
    """
    from tenzing_tpu.bench.benchmarker import schedule_id
    from tenzing_tpu.solve.dfs import expand_all
    from tenzing_tpu.verify import ScheduleVerifier, verify_schedule

    from tests.test_verify import oracle_unsound_check

    rows, terminals = corpus
    g = _graph()
    plat = Platform.make_n_lanes(2)

    # clean reference
    mcts_clean = explore(g, plat, mk_db(rows), MctsOpts(n_iters=30, seed=3))
    dfs_clean = dfs_explore(g, plat, mk_db(rows), DfsOpts(max_seqs=10_000))
    clean_key, clean_pct50 = _best(mcts_clean.sims + dfs_clean.sims)

    # precondition: the corruption seed must not hit the best schedule in
    # either spelling the solvers query (a corrupted best is legitimately
    # unfindable — the run would catch it, but could not measure it)
    best_raw = min(terminals, key=lambda s: _synth_result(s).pct50)
    for spelling in (best_raw, remove_redundant_syncs(best_raw)):
        assert not _schedule_fails(schedule_id(spelling), CORRUPT_SPEC)

    # chaos stack: the corrupt injector sits ABOVE the resilient layer so
    # the verifier gate sees the mutated schedule (the bench.py layering)
    qpath = str(tmp_path / "quarantine.json")
    verifier = ScheduleVerifier(g)
    counting = CountingInner(mk_db(rows))
    quar = Quarantine(qpath)
    resilient = ResilientBenchmarker(
        counting, policy=_fast_policy(), quarantine=quar,
        verifier=verifier, sleep=lambda s: None)
    inject = FaultInjectingBenchmarker(
        resilient, [CORRUPT_SPEC],
        unsound_check=oracle_unsound_check(expand_all(g.clone())))
    bench = CachingBenchmarker(inject)

    res_mcts = explore(g, plat, bench, MctsOpts(n_iters=30, seed=3))
    res_dfs = dfs_explore(g, plat, bench, DfsOpts(max_seqs=10_000))

    # the chaos actually happened: >= 20% of the distinct candidates were
    # mutated (seeded by schedule identity at rate 0.25)
    assert inject.injected["corrupt"] >= 1
    assert len(inject.corrupted) >= 0.15 * len(terminals)

    # every mutated schedule was caught and quarantined; none was measured
    measured_sids = set(counting.by_sid)
    for orig, mutated in inject.corrupted.items():
        assert mutated in quar.entries, "a corruption escaped the verifier"
        assert mutated not in measured_sids
    # zero unsound schedules measured, full stop: everything that reached
    # the inner "device" re-verifies clean
    for order in counting.orders.values():
        assert verify_schedule(order, g).ok
    unsound_events = [e for e in tracer.events()
                      if e.name == "verify.unsound"]
    assert len(unsound_events) >= len(inject.corrupted)
    assert registry.counter("verify.unsound").value >= len(inject.corrupted)

    # the clean-run best was still found, with the identical measurement
    chaos_key, chaos_pct50 = _best(res_mcts.sims + res_dfs.sims)
    assert (chaos_key, chaos_pct50) == (clean_key, clean_pct50)


# compile-failure channel for the prefetch chaos test: the same seeded
# subset of schedules fails to compile in the background (FakeExecutor) AND
# in the foreground (the CompileGate below) — what a genuinely uncompilable
# candidate does with and without the pipeline
COMPILE_FAIL_SPEC = InjectSpec("deterministic", 0.1, 77)


def test_chaos_with_prefetch_matches_prefetch_off(tmp_path, tracer,
                                                  registry, corpus):
    """ISSUE 5 chaos acceptance: seeded fault injection with the async
    compile pipeline enabled must (a) produce bit-identical search results
    to prefetch-off (for the tree search, which draws ahead by the
    prefetcher's ``workers``: to the same search against a prefetcher of
    that width whose executor compiles nothing, so that every compile
    failure is the foreground's), (b) classify background compile errors
    through the fault taxonomy and quarantine deterministic ones exactly
    once, and (c) leak no pipeline threads."""
    import threading

    from tenzing_tpu.bench.benchmarker import schedule_id
    from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker

    from tests.test_pipeline_bench import FakeExecutor, NullExecutor

    rows, terminals = corpus
    plat = Platform.make_n_lanes(2)

    def compile_fails(order) -> bool:
        return _schedule_fails(schedule_id(order), COMPILE_FAIL_SPEC)

    # precondition (the DET_SPEC pattern above): neither failure channel
    # may hit the best schedule in either spelling the solvers query
    best_raw = min(terminals, key=lambda s: _synth_result(s).pct50)
    for spelling in (best_raw, remove_redundant_syncs(best_raw)):
        assert not compile_fails(spelling)
        assert not _schedule_fails(schedule_id(spelling), DET_SPEC)
    fails = [s for s in terminals if compile_fails(s)]
    assert fails  # the compile-failure chaos actually has targets

    class CompileGate:
        """Foreground lazy-compile stand-in: the seeded subset fails before
        any measurement — above the device-fault injector (a compile never
        reaches the device), below the counting layer."""

        def __init__(self, inner):
            self.inner = inner

        def benchmark(self, order, opts=None):
            if compile_fails(order):
                raise RuntimeError(
                    f"failed to compile (chaos {schedule_id(order)})")
            return self.inner.benchmark(order, opts)

    def run_off():
        inject = FaultInjectingBenchmarker(mk_db(rows), CHAOS_SPECS,
                                           hang_secs=2.5)
        counting = CountingInner(CompileGate(inject))
        quar = Quarantine(str(tmp_path / "off" / "quarantine.json"))
        # the tree search's "off" is the lookahead's width and no compiles;
        # DFS's is no hints at all (unhinted, the layer passes through)
        null = PrefetchingBenchmarker(counting, executor=NullExecutor(),
                                      workers=2)
        try:
            bench = CachingBenchmarker(ResilientBenchmarker(
                null, timeout_secs=1.0, policy=_fast_policy(),
                quarantine=quar, sleep=lambda s: None))
            mcts = explore(_graph(), plat, bench,
                           MctsOpts(n_iters=30, seed=3, prefetch=null))
            dfs = dfs_explore(_graph(), plat, bench,
                              DfsOpts(max_seqs=10_000))
        finally:
            null.close()
        return mcts, dfs, counting, quar

    off_mcts, off_dfs, off_count, off_quar = run_off()

    ex = FakeExecutor(fail=lambda o: RuntimeError(
        f"failed to compile (chaos {schedule_id(o)})")
        if compile_fails(o) else None)
    inject_on = FaultInjectingBenchmarker(mk_db(rows), CHAOS_SPECS,
                                          hang_secs=2.5)
    count_on = CountingInner(CompileGate(inject_on))
    p = PrefetchingBenchmarker(count_on, executor=ex, workers=2)
    try:
        # a guaranteed background-compile failure (solver hints are
        # speculative; this pins the classified-surfacing assertion)
        p.prefetch([fails[0]])
        quar_on = Quarantine(str(tmp_path / "on" / "quarantine.json"))
        resilient_on = ResilientBenchmarker(
            p, timeout_secs=1.0, policy=_fast_policy(), quarantine=quar_on,
            sleep=lambda s: None)
        bench_on = CachingBenchmarker(resilient_on)
        on_mcts = explore(_graph(), plat, bench_on,
                          MctsOpts(n_iters=30, seed=3, prefetch=p))
        on_dfs = dfs_explore(_graph(), plat, bench_on,
                             DfsOpts(max_seqs=10_000, prefetch=p))
        assert p.issued > 0
    finally:
        p.close()

    # (a) bit-identical to prefetch-off, and both find the clean best
    sims_key = lambda res: [(_key(s.order), s.result.pct50)
                            for s in res.sims]
    assert sims_key(on_mcts) == sims_key(off_mcts)
    assert sims_key(on_dfs) == sims_key(off_dfs)
    assert _best(on_mcts.sims + on_dfs.sims) == \
        _best(off_mcts.sims + off_dfs.sims) == \
        (_key(best_raw), _synth_result(best_raw).pct50)

    # (b) background failures were classified + surfaced, and every
    # deterministic failure (compile chaos or injected) quarantined with
    # the candidate measured at most once overall
    assert p.failed >= 1 and p.surfaced >= 1
    pevs = [e for e in tracer.events()
            if e.name == "pipeline.precompile_failed"]
    assert pevs and all(
        e.attrs["error_class"] == "deterministic" for e in pevs)
    assert set(quar_on.entries) == set(off_quar.entries)
    for sid in quar_on.entries:
        assert count_on.by_sid[sid] + off_count.by_sid[sid] <= 2  # <=1 each
        assert count_on.by_sid[sid] <= 1

    # (c) no leaked pipeline threads
    assert not [t for t in threading.enumerate()
                if t.name.startswith("tz-prefetch") and t.is_alive()]


def test_device_lost_without_fallback_escalates_out_of_search(corpus):
    """Device loss is fatal, never a per-candidate verdict: with no
    degradation fallback the search must abort, not grind through every
    remaining candidate re-discovering the dead chip."""
    from tenzing_tpu.fault import DeviceLostError

    rows, _ = corpus
    plat = Platform.make_n_lanes(2)
    inject = FaultInjectingBenchmarker(
        mk_db(rows), [InjectSpec("device_lost", 1.0, 9)])
    rb = ResilientBenchmarker(inject, policy=_fast_policy(),
                              sleep=lambda s: None)
    with pytest.raises(DeviceLostError):
        explore(_graph(), plat, rb, MctsOpts(n_iters=5, seed=3))
    with pytest.raises(DeviceLostError):
        dfs_explore(_graph(), plat, rb, DfsOpts(max_seqs=10_000))


def test_device_lost_with_fallback_finishes_degraded(corpus, tracer):
    """Graceful degradation: with a fallback benchmarker the search
    completes, and every post-loss answer is attributable via
    was_degraded (the fid=degraded dump tag)."""
    rows, _ = corpus
    plat = Platform.make_n_lanes(2)

    class Fallback:
        def benchmark(self, order, opts=None):
            return _synth_result(order)

    # lose the device on the 4th measurement
    inner = CountingInner(mk_db(rows))
    calls = {"n": 0}

    class LoseAfter:
        def benchmark(self, order, opts=None):
            from tenzing_tpu.fault import DeviceLostError

            calls["n"] += 1
            if calls["n"] == 4:
                raise DeviceLostError("chip gone for good")
            return inner.benchmark(order, opts)

    rb = ResilientBenchmarker(LoseAfter(), policy=_fast_policy(),
                              fallback=Fallback(), sleep=lambda s: None)
    res = explore(_graph(), plat, CachingBenchmarker(rb),
                  MctsOpts(n_iters=12, seed=3))
    assert rb.degraded
    assert len(res.sims) == 12  # the search FINISHED
    degraded = [s for s in res.sims if rb.was_degraded(s.order)]
    assert degraded  # post-loss answers exist and are attributable
    assert any(e.name == "fault.degraded" for e in tracer.events())
