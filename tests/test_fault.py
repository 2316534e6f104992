"""Fault layer units (ISSUE 3): error taxonomy, the shared backoff helper,
persistent quarantine, ResilientBenchmarker (watchdog / classified retry /
rank agreement / degradation), and the seeded fault-injection harness."""

import json
import os
import threading
import time

import pytest

from tenzing_tpu.bench.benchmarker import BenchOpts, BenchResult, schedule_id
from tenzing_tpu.fault import (
    BackoffPolicy,
    DeviceLostError,
    FaultClass,
    FaultInjectingBenchmarker,
    InjectSpec,
    InjectedDeterministicError,
    InjectedTransientError,
    MeasurementTimeout,
    Quarantine,
    QuarantinedScheduleError,
    ResilientBenchmarker,
    TransientError,
    classify_error,
    fault_code,
    parse_inject_specs,
    retry_call,
)
from tenzing_tpu.fault.inject import _schedule_fails
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.obs.tracer import Tracer, set_tracer
from tenzing_tpu.parallel.control_plane import ControlPlane


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


def _ok(t=1.0):
    return BenchResult.from_times([t, t, t])


class ScriptedBench:
    """Pops one scripted behavior per call: an exception instance to raise,
    a float to sleep (then succeed), or None to succeed."""

    def __init__(self, script):
        self.script = list(script)
        self.calls = 0

    def benchmark(self, order, opts=None):
        self.calls += 1
        step = self.script.pop(0) if self.script else None
        if isinstance(step, BaseException):
            raise step
        if isinstance(step, float):
            time.sleep(step)
        return _ok()


# -- taxonomy ---------------------------------------------------------------

@pytest.mark.parametrize("exc,want", [
    (InjectedTransientError("x"), FaultClass.TRANSIENT),
    (MeasurementTimeout("x"), FaultClass.TRANSIENT),
    (TransientError("x"), FaultClass.TRANSIENT),
    (DeviceLostError("x"), FaultClass.DEVICE_LOST),
    (InjectedDeterministicError("x"), FaultClass.DETERMINISTIC),
    (TimeoutError("anything"), FaultClass.TRANSIENT),
    (ConnectionResetError("peer"), FaultClass.TRANSIENT),
    (RuntimeError("connection reset by peer"), FaultClass.TRANSIENT),
    (RuntimeError("UNAVAILABLE: runtime hiccup"), FaultClass.TRANSIENT),
    (RuntimeError("DEADLINE_EXCEEDED while fetching"), FaultClass.TRANSIENT),
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"),
     FaultClass.DETERMINISTIC),
    (RuntimeError("failed to compile HLO"), FaultClass.DETERMINISTIC),
    (ValueError("operand shape mismatch"), FaultClass.DETERMINISTIC),
    (RuntimeError("device lost: chip rebooted"), FaultClass.DEVICE_LOST),
    # unknown errors default to deterministic (see fault/errors.py rationale)
    (RuntimeError("mysterious"), FaultClass.DETERMINISTIC),
])
def test_classification(exc, want):
    assert classify_error(exc) == want


def test_fault_codes_are_severity_ordered():
    assert (fault_code(TransientError("x"))
            < fault_code(ValueError("shape"))
            < fault_code(DeviceLostError("x")))
    # the rank-agreement protocol allreduce-maxes these codes: the mapping
    # must be a bijection so the worst class round-trips
    assert FaultClass.FROM_CODE[FaultClass.CODES[FaultClass.TRANSIENT]] == \
        FaultClass.TRANSIENT


# -- backoff ----------------------------------------------------------------

def test_backoff_policy_growth_and_cap():
    p = BackoffPolicy(base_secs=1.0, factor=2.0, max_secs=5.0, jitter=0.0)
    assert [p.delay(k) for k in range(4)] == [1.0, 2.0, 4.0, 5.0]


def test_backoff_jitter_bounds():
    import random

    p = BackoffPolicy(base_secs=1.0, factor=1.0, jitter=0.5)
    rng = random.Random(0)
    ds = [p.delay(0, rng) for _ in range(100)]
    assert all(0.5 <= d <= 1.5 for d in ds)
    assert len(set(ds)) > 1  # actually jittered


def test_retry_call_retries_transient_then_succeeds(tracer, registry):
    sleeps = []
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("flaky")
        return "ok"

    out = retry_call(fn, policy=BackoffPolicy(retries=3, base_secs=0.25,
                                              factor=2.0, jitter=0.0),
                     where="test", sleep=sleeps.append)
    assert out == "ok" and calls["n"] == 3
    assert sleeps == [0.25, 0.5]
    retries = [e for e in tracer.events() if e.name == "fault.retry"]
    assert len(retries) == 2
    assert retries[0].attrs["where"] == "test"
    assert retries[0].attrs["error_class"] == FaultClass.TRANSIENT
    assert retries[0].attrs["attempt"] == 1
    assert registry.counter("fault.retries").value == 2


def test_retry_call_does_not_retry_deterministic(registry):
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        retry_call(fn, sleep=lambda s: None)
    assert calls["n"] == 1
    assert registry.counter("fault.retries").value == 0


def test_retry_call_exhausts_and_reraises():
    with pytest.raises(TransientError):
        retry_call(lambda: (_ for _ in ()).throw(TransientError("always")),
                   policy=BackoffPolicy(retries=2, base_secs=0.0),
                   sleep=lambda s: None)


def test_retry_call_on_retry_hook_runs_before_sleep():
    seen = []
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] == 1:
            raise TransientError("once")
        return 1

    retry_call(fn, policy=BackoffPolicy(retries=1, base_secs=0.1, jitter=0.0),
               on_retry=lambda e, a, d: seen.append((type(e).__name__, a, d)),
               sleep=lambda s: seen.append(("slept", s)))
    assert seen == [("TransientError", 0, 0.1), ("slept", 0.1)]


# -- quarantine -------------------------------------------------------------

def test_quarantine_persists_across_instances(tmp_path, registry):
    path = str(tmp_path / "q.json")
    q = Quarantine(path)
    sid = q.add("sched-a", ValueError("bad shape"), FaultClass.DETERMINISTIC)
    assert q.check("sched-a")["error"] == "ValueError"
    assert q.check("sched-b") is None
    # a fresh instance (a restarted process) still refuses the candidate
    q2 = Quarantine(path)
    assert len(q2) == 1
    assert q2.check("sched-a")["error_class"] == FaultClass.DETERMINISTIC
    assert q2.key("sched-a") == sid
    # no torn temp files left behind
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []


def test_quarantine_add_is_idempotent(tmp_path, registry):
    q = Quarantine(str(tmp_path / "q.json"))
    q.add("s", ValueError("x"), FaultClass.DETERMINISTIC)
    q.add("s", ValueError("y"), FaultClass.DETERMINISTIC)
    assert len(q) == 1
    assert q.check("s")["message"] == "x"  # first verdict wins
    assert registry.counter("fault.quarantined").value == 1


def test_quarantine_unreadable_file_is_empty_but_reported(tmp_path):
    path = tmp_path / "q.json"
    path.write_text("{ not json")
    notes = []
    q = Quarantine(str(path), log=notes.append)
    assert len(q) == 0
    assert notes and "unreadable" in notes[0]


def test_quarantine_version_mismatch_is_empty(tmp_path):
    path = tmp_path / "q.json"
    path.write_text(json.dumps({"version": 99, "entries": {"x": {}}}))
    notes = []
    q = Quarantine(str(path), log=notes.append)
    assert len(q) == 0 and notes


# -- ResilientBenchmarker ---------------------------------------------------

def _resilient(inner, **kw):
    kw.setdefault("policy", BackoffPolicy(retries=3, base_secs=0.0,
                                          jitter=0.0))
    kw.setdefault("sleep", lambda s: None)
    return ResilientBenchmarker(inner, **kw)


def test_resilient_retries_transient(tracer, registry):
    inner = ScriptedBench([TransientError("flake"), TransientError("flake")])
    rb = _resilient(inner)
    res = rb.benchmark("sched", BenchOpts())
    assert res.pct50 == 1.0 and inner.calls == 3
    errs = [e for e in tracer.events() if e.name == "fault.error"]
    assert len(errs) == 2
    assert all(e.attrs["error_class"] == FaultClass.TRANSIENT for e in errs)
    assert registry.counter(
        f"fault.errors.{FaultClass.TRANSIENT}").value == 2


def test_resilient_transient_exhaustion_reraises():
    inner = ScriptedBench([TransientError(f"flake {i}") for i in range(9)])
    rb = _resilient(inner, policy=BackoffPolicy(retries=2, base_secs=0.0))
    with pytest.raises(TransientError):
        rb.benchmark("sched")
    assert inner.calls == 3  # first + 2 retries, bounded


def test_resilient_quarantines_deterministic(tmp_path, tracer, registry):
    qpath = str(tmp_path / "q.json")
    inner = ScriptedBench([ValueError("bad shape forever")])
    rb = _resilient(inner, quarantine=Quarantine(qpath))
    with pytest.raises(ValueError):
        rb.benchmark("sched-broken")
    assert inner.calls == 1  # no retry for a deterministic failure
    # second query never reaches the device — quarantine answers
    with pytest.raises(QuarantinedScheduleError):
        rb.benchmark("sched-broken")
    assert inner.calls == 1
    # ... even in a fresh process (the persistent file)
    rb2 = _resilient(ScriptedBench([]), quarantine=Quarantine(qpath))
    with pytest.raises(QuarantinedScheduleError):
        rb2.benchmark("sched-broken")
    assert registry.counter("fault.quarantine_hits").value == 2
    assert [e.name for e in tracer.events()
            if e.name.startswith("fault.quarantine")] == [
        "fault.quarantine", "fault.quarantine_hit", "fault.quarantine_hit"]


def test_resilient_watchdog_times_out_hang_and_retries(tracer):
    inner = ScriptedBench([30.0])  # first call hangs "forever"
    rb = _resilient(inner, timeout_secs=0.1)
    res = rb.benchmark("sched")  # times out, retry succeeds
    assert res.pct50 == 1.0
    errs = [e for e in tracer.events() if e.name == "fault.error"]
    assert len(errs) == 1 and errs[0].attrs["error"] == "MeasurementTimeout"
    assert errs[0].attrs["error_class"] == FaultClass.TRANSIENT


def test_resilient_device_lost_without_fallback_is_fatal():
    inner = ScriptedBench([DeviceLostError("gone")])
    rb = _resilient(inner)
    with pytest.raises(DeviceLostError):
        rb.benchmark("sched")
    assert inner.calls == 1


def test_resilient_degrades_to_fallback(tracer, registry):
    class Fallback:
        def __init__(self):
            self.calls = 0

        def benchmark(self, order, opts=None):
            self.calls += 1
            return _ok(9.0)

    inner = ScriptedBench([DeviceLostError("gone")])
    fb = Fallback()
    rb = _resilient(inner, fallback=fb)
    res = rb.benchmark("sched-a")
    assert res.pct50 == 9.0 and rb.degraded
    assert rb.was_degraded("sched-a") and not rb.was_degraded("sched-b")
    # every subsequent query is answered by the fallback, device untouched
    rb.benchmark("sched-b")
    assert rb.was_degraded("sched-b")
    assert inner.calls == 1 and fb.calls == 2
    assert registry.counter("fault.degraded").value == 1
    assert any(e.name == "fault.degraded" for e in tracer.events())


class TwoRankCP(ControlPlane):
    """A control plane simulating a peer rank: ``agree_fault`` maxes the
    local code with a scripted peer code per call."""

    def __init__(self, peer_codes):
        self.peer_codes = list(peer_codes)
        self.seen = []

    def size(self):
        return 2

    def agree_fault(self, code):
        peer = self.peer_codes.pop(0) if self.peer_codes else 0
        self.seen.append(int(code))
        return max(int(code), peer)


def test_rank_agreement_peer_transient_forces_local_retry():
    """The local rank measured fine, but a peer reported a transient fault:
    the local rank must discard its result and retry in lockstep."""
    inner = ScriptedBench([])
    # agreement calls alternate pre/post per attempt: pre=0, post=peer-fault
    cp = TwoRankCP(peer_codes=[0, FaultClass.CODES[FaultClass.TRANSIENT],
                               0, 0])
    rb = _resilient(inner, control_plane=cp)
    res = rb.benchmark("sched")
    assert res.pct50 == 1.0
    assert inner.calls == 2  # re-measured after the peer's failure


def test_rank_agreement_peer_deterministic_quarantines_everywhere(tmp_path):
    inner = ScriptedBench([])
    cp = TwoRankCP(peer_codes=[0, FaultClass.CODES[FaultClass.DETERMINISTIC]])
    q = Quarantine(str(tmp_path / "q.json"))
    rb = _resilient(inner, control_plane=cp, quarantine=q)
    with pytest.raises(QuarantinedScheduleError):
        rb.benchmark("sched-peer-broken")
    # the local rank quarantined the candidate although IT measured fine —
    # rank-coherent: the peer's verdict is everyone's verdict
    assert q.check("sched-peer-broken") is not None


def test_resilient_is_rank_coherent_and_forwards_through_wrappers():
    from tenzing_tpu.bench.benchmarker import CachingBenchmarker

    rb = _resilient(ScriptedBench([]))
    assert rb.rank_coherent
    assert CachingBenchmarker(rb).rank_coherent
    assert not CachingBenchmarker(ScriptedBench([])).rank_coherent


def test_resilient_batch_retry_clears_partial_times_in_place():
    class Batchy:
        def __init__(self):
            self.calls = 0

        def benchmark(self, order, opts=None):
            return _ok()

        def benchmark_batch_times(self, orders, opts=None, seed=0,
                                  times_out=None):
            self.calls += 1
            if self.calls == 1:
                if times_out is not None:
                    times_out[0].append(0.5)  # partial data, then die
                raise TransientError("mid-batch flake")
            out = [[1.0], [2.0]]
            if times_out is not None:
                for t, o in zip(times_out, out):
                    t.extend(o)
                return times_out
            return out

    inner = Batchy()
    rb = _resilient(inner)
    t0, t1 = [], []
    times = rb.benchmark_batch_times(["a", "b"], BenchOpts(), seed=0,
                                     times_out=[t0, t1])
    assert inner.calls == 2
    # the caller's lists were cleared in place before the retry: no stale
    # partial measurement prefixes the aligned series
    assert t0 == [1.0] and t1 == [2.0]
    assert times[0] is t0


def test_keyboard_interrupt_passes_straight_through():
    inner = ScriptedBench([KeyboardInterrupt()])
    rb = _resilient(inner)
    with pytest.raises(KeyboardInterrupt):
        rb.benchmark("sched")
    assert inner.calls == 1  # never retried, never quarantined


# -- fault injection --------------------------------------------------------

def test_parse_inject_specs():
    specs = parse_inject_specs("transient:0.25:7,hang:0.02:11")
    assert specs == [InjectSpec("transient", 0.25, 7),
                     InjectSpec("hang", 0.02, 11)]
    for bad in ("transient", "transient:0.5", "bogus:0.5:1",
                "transient:1.5:1", ""):
        with pytest.raises(ValueError):
            parse_inject_specs(bad)


def test_injection_is_seed_deterministic(registry):
    def run(seed):
        inj = FaultInjectingBenchmarker(
            ScriptedBench([]), [InjectSpec("transient", 0.5, seed)])
        pattern = []
        for i in range(40):
            try:
                inj.benchmark(f"s{i}")
                pattern.append(0)
            except InjectedTransientError:
                pattern.append(1)
        return pattern, inj

    p1, inj1 = run(3)
    p2, _ = run(3)
    p3, _ = run(4)
    assert p1 == p2          # same seed, same fault schedule
    assert p1 != p3          # different seed, different schedule
    assert inj1.injected["transient"] == sum(p1) > 0
    assert inj1.calls == 40


def test_deterministic_injection_keyed_by_schedule_identity():
    spec = InjectSpec("deterministic", 0.5, 123)
    inj = FaultInjectingBenchmarker(ScriptedBench([]), [spec])
    # find one schedule that fails and one that passes under this seed
    fails = next(f"s{i}" for i in range(50)
                 if _schedule_fails(schedule_id(f"s{i}"), spec))
    passes = next(f"s{i}" for i in range(50)
                  if not _schedule_fails(schedule_id(f"s{i}"), spec))
    for _ in range(3):  # the SAME schedules fail/pass on every attempt
        with pytest.raises(InjectedDeterministicError):
            inj.benchmark(fails)
        inj.benchmark(passes)


def test_hang_injection_stalls_then_proceeds():
    naps = []
    inj = FaultInjectingBenchmarker(
        ScriptedBench([]), [InjectSpec("hang", 1.0, 5)],
        hang_secs=12.5, sleep=naps.append)
    res = inj.benchmark("s")
    assert res.pct50 == 1.0  # a hang is a stall, not an error
    assert naps == [12.5]


def test_device_lost_injection():
    inj = FaultInjectingBenchmarker(
        ScriptedBench([]), [InjectSpec("device_lost", 1.0, 5)])
    with pytest.raises(DeviceLostError):
        inj.benchmark("s")


def test_injected_hang_plus_watchdog_end_to_end(tracer):
    """The composition the chaos harness relies on: an injected hang makes
    the watchdog fire, the timeout classifies transient, the retry passes
    (rate keeps the second draw clean), and the whole failure is visible as
    classified fault.* telemetry."""
    from tenzing_tpu.fault.inject import _attempt_fires

    # a seed whose first draw injects the hang and whose second does not,
    # so the retry after the watchdog timeout recovers (draws are keyed on
    # schedule identity + attempt counter — rank-agreed by construction)
    rate = 0.6

    def draws(s):
        spec = InjectSpec("hang", rate, s)
        sid = schedule_id("sched")
        return (_attempt_fires(sid, 0, spec), _attempt_fires(sid, 1, spec))

    seed = next(s for s in range(1000)
                if draws(s)[0] and not draws(s)[1])
    inj = FaultInjectingBenchmarker(
        ScriptedBench([]), [InjectSpec("hang", rate, seed)],
        hang_secs=30.0)  # real sleep on a daemon thread, abandoned
    rb = _resilient(inj, timeout_secs=0.1)
    res = rb.benchmark("sched")
    assert res.pct50 == 1.0
    names = [e.name for e in tracer.events()]
    assert "fault.injected" in names
    assert "fault.error" in names and "fault.retry" in names


def test_resilient_batch_under_watchdog_isolates_caller_lists():
    """With the watchdog armed, a timed-out batch abandons a worker thread
    that still holds its list references — so each attempt must get fresh
    private lists, and the caller's only ever receive a COMPLETED
    attempt's aligned series (no stale interleaved appends)."""
    seen_lists = []

    class Batchy:
        def __init__(self):
            self.calls = 0

        def benchmark(self, order, opts=None):
            return _ok()

        def benchmark_batch_times(self, orders, opts=None, seed=0,
                                  times_out=None):
            self.calls += 1
            seen_lists.append(times_out)
            if self.calls == 1:
                times_out[0].append(99.0)  # partial garbage, then hang
                time.sleep(30.0)
            for t, v in zip(times_out, ([1.0], [2.0])):
                t.extend(v)
            return times_out

    inner = Batchy()
    rb = _resilient(inner, timeout_secs=0.05)
    t0, t1 = [], []
    rb.benchmark_batch_times(["a", "b"], BenchOpts(), times_out=[t0, t1])
    assert inner.calls == 2
    # the caller's lists were never handed to the supervised inner call...
    assert all(lst is not t0 and lst is not t1
               for attempt in seen_lists for lst in attempt)
    # ...and carry exactly the completed attempt's series, garbage-free
    assert t0 == [1.0] and t1 == [2.0]


def test_injection_draws_agree_across_instances():
    """The rank-agreement substrate (ROADMAP multi-host chaos item): draws
    are keyed on (kind, seed, schedule identity, per-schedule attempt
    counter) — two injector instances fed the same benchmark-call sequence
    (what the broadcast protocol guarantees every rank sees) make
    IDENTICAL draws, with no shared RNG state.  A restarted process
    re-counts attempts from zero, so a resumed run replays the same
    faults too."""
    specs = [InjectSpec("transient", 0.4, 3), InjectSpec("hang", 0.1, 5)]

    def run():
        naps = []
        inj = FaultInjectingBenchmarker(ScriptedBench([]), specs,
                                        hang_secs=1.0, sleep=naps.append)
        pattern = []
        # repeated queries of the same schedules: the attempt counter must
        # advance the draw (a retry is a fresh coin flip, same on all ranks)
        for i in [0, 1, 2, 0, 0, 1, 2, 2, 0, 1] * 4:
            try:
                inj.benchmark(f"s{i}")
                pattern.append(0)
            except InjectedTransientError:
                pattern.append(1)
        return pattern, len(naps), inj

    p1, n1, inj1 = run()
    p2, n2, _ = run()
    assert p1 == p2 and n1 == n2  # rank-agreed by construction
    assert sum(p1) > 0 and n1 > 0  # both channels actually fired
    # ...and the same schedule is NOT deterministically fated: different
    # attempts of one schedule draw independently
    by_attempt = [p1[i] for i, q in enumerate([0, 1, 2, 0, 0, 1, 2, 2, 0, 1]
                                              * 4) if q == 0]
    assert 0 < sum(by_attempt) < len(by_attempt)


def test_corrupt_injection_mutates_by_schedule_identity(registry):
    """corrupt: draws by schedule identity, mutates via corrupt_schedule,
    and records original -> mutated ids for accountability."""
    from tenzing_tpu.core.graph import Graph
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.fault import corrupt_schedule
    from tenzing_tpu.models.spmv import SpMVCompound
    from tenzing_tpu.solve.dfs import enumerate_schedules

    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    states = enumerate_schedules(g, Platform.make_n_lanes(2), max_seqs=40)
    spec = InjectSpec("corrupt", 0.5, 11)

    seen = {}

    class Recorder:
        def benchmark(self, order, opts=None):
            seen[schedule_id(order)] = order
            return BenchResult.from_times([1.0])

    inj = FaultInjectingBenchmarker(Recorder(), [spec])
    for st in states:
        inj.benchmark(st.sequence)
    assert inj.injected["corrupt"] > 0
    assert set(inj.corrupted) != set(inj.corrupted.values())
    for orig, mutated in inj.corrupted.items():
        assert mutated in seen  # the mutation went DOWN the stack
        assert orig != mutated
    # replay: identical mutations (content-keyed, no RNG state)
    inj2 = FaultInjectingBenchmarker(Recorder(), [spec])
    for st in states:
        inj2.benchmark(st.sequence)
    assert inj2.corrupted == inj.corrupted
    # corrupt_schedule without sync ops has nothing to mutate
    from tenzing_tpu.core.sequence import Sequence

    assert corrupt_schedule(Sequence([g.start(), g.finish()]), 1) is None


def test_injector_forwards_degraded_provenance():
    """A corrupt injector stacked between the journaling layer and the
    resilient wrapper must forward was_degraded — otherwise fallback
    answers would journal as provenance 'measured' and a resumed run
    would replay predictions as device measurements."""
    class DegradedInner:
        def was_degraded(self, order):
            return order == "degraded-one"

        def benchmark(self, order, opts=None):
            return BenchResult.from_times([1.0])

    inj = FaultInjectingBenchmarker(DegradedInner(),
                                    [InjectSpec("corrupt", 1.0, 1)])
    assert inj.was_degraded("degraded-one") is True
    assert inj.was_degraded("other") is False
    # ...and stays False-safe over an inner without the method
    assert FaultInjectingBenchmarker(
        ScriptedBench([]), [InjectSpec("corrupt", 1.0, 1)]
    ).was_degraded("x") is False


def test_exempt_ids_skip_identity_keyed_kinds_only():
    """bench.py registers its naive baseline here: identity-keyed
    candidate-fault kinds (deterministic/corrupt) skip exempt schedules —
    a seed deterministically breaking the BASELINE would kill every run —
    while per-attempt device-fault kinds still apply to them."""
    det = InjectSpec("deterministic", 0.5, 123)
    # a schedule this seed deterministically fails
    fails = next(f"s{i}" for i in range(50)
                 if _schedule_fails(schedule_id(f"s{i}"), det))
    inj = FaultInjectingBenchmarker(ScriptedBench([]), [det],
                                    exempt_ids={schedule_id(fails)})
    inj.benchmark(fails)  # exempt: no raise
    assert inj.injected["deterministic"] == 0
    # transient still fires on an exempt schedule (per-attempt kind)
    tr = InjectSpec("transient", 1.0, 1)
    inj2 = FaultInjectingBenchmarker(ScriptedBench([]), [tr],
                                     exempt_ids={schedule_id(fails)})
    with pytest.raises(InjectedTransientError):
        inj2.benchmark(fails)
