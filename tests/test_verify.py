"""Independent schedule-soundness verifier (ISSUE 4).

The acceptance gates:

* **soundness-of-the-synthesizer** (fuzz): every schedule the
  EventSynchronizer-driven construction emits — exhaustive DFS terminals,
  randomized rollouts, and their ``remove_redundant_syncs`` cleanups,
  across the model suite — passes the independent verifier: 0 false
  positives.
* **minimality-of-the-detector**: dropping any single *load-bearing* sync
  from a verified schedule is detected (100%), where load-bearing is
  decided by the ORIGINAL oracle (``EventSynchronizer.is_synced`` over the
  evolved graph) — two independently-implemented judgments must agree on
  every mutation, in both directions (a genuinely redundant drop must NOT
  be flagged).
"""

import random

import pytest

from tenzing_tpu.core.event_synchronizer import EventSynchronizer
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import NoOp
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.resources import Event, Lane
from tenzing_tpu.core.schedule import remove_redundant_syncs
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.state import State
from tenzing_tpu.core.sync_ops import (
    EventRecord,
    EventSync,
    LaneSync,
    SyncOp,
    WaitEvent,
)
from tenzing_tpu.fault.inject import corrupt_schedule
from tenzing_tpu.models.spmv import SpMVCompound
from tenzing_tpu.solve.dfs import enumerate_schedules, expand_all
from tenzing_tpu.verify import ScheduleVerifier, verify_schedule


def _spmv_graph():
    g = Graph()
    g.start_then(SpMVCompound())
    g.then_finish(SpMVCompound())
    return g


def _halo_graph():
    from tenzing_tpu.models.halo import HaloArgs
    from tenzing_tpu.models.halo_pipeline import build_graph

    return build_graph(HaloArgs(nq=2, lx=4, ly=4, lz=4, radius=1),
                       impl_choice=False, xfer_choice=False)


def _moe_graph():
    from tenzing_tpu.models.moe_pipeline import (
        MoEPipeArgs,
        build_graph,
        make_pipe_buffers,
    )

    margs = MoEPipeArgs(n_experts=4, tokens=32, d_model=8, d_ff=16,
                        n_chunks=2)
    _, _, cap = make_pipe_buffers(margs, seed=0, with_expected=False,
                                  staging="f32")
    return build_graph(margs, cap, impl_choice=False, staging="f32")


def synth_sound(graph, seq) -> bool:
    """The ORIGINAL oracle's judgment of a complete sequence: every
    non-sync op must be ``is_synced`` against the prefix that precedes it —
    exactly the incremental criterion the synthesizer enforced while
    building the schedule (core/event_synchronizer.py)."""
    ops = seq.vector()
    for i, op in enumerate(ops):
        if isinstance(op, SyncOp):
            continue
        if not EventSynchronizer.is_synced(graph, Sequence(ops[:i]), op):
            return False
    return True


def _random_rollouts(graph, platform, n, seed):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        st = State(graph)
        while not st.is_terminal():
            ds = st.get_decisions(platform)
            st = st.apply(ds[rng.randrange(len(ds))])
        out.append(st)
    return out


# -- soundness of the synthesizer (fuzz: 0 false positives) -----------------

def test_spmv_exhaustive_space_verifies_clean():
    g = _spmv_graph()
    states = enumerate_schedules(g, Platform.make_n_lanes(2), max_seqs=10_000)
    assert len(states) >= 3
    ver = ScheduleVerifier(g)
    for st in states:
        for seq in (st.sequence, remove_redundant_syncs(st.sequence)):
            v = ver(seq)
            assert v.ok, f"false positive: {v.witness()}\n{seq.desc()}"
    assert ver.unsound == 0


@pytest.mark.parametrize("mk_graph,n", [(_halo_graph, 10), (_moe_graph, 10),
                                        (_spmv_graph, 10)])
def test_randomized_rollouts_verify_clean(mk_graph, n):
    g = mk_graph()
    ver = ScheduleVerifier(g)
    for nl in (2, 3):
        for st in _random_rollouts(g, Platform.make_n_lanes(nl), n, seed=nl):
            for seq in (st.sequence, remove_redundant_syncs(st.sequence)):
                v = ver(seq)
                assert v.ok, f"false positive: {v.witness()}\n{seq.desc()}"
            # the fuzz is only meaningful if the oracle agrees the
            # schedules were legal in the first place
            assert synth_sound(st.graph, st.sequence)


# -- minimality of the detector (100% single-drop detection) ----------------

def test_every_single_dropped_sync_is_detected():
    """Both judges — the EventSynchronizer-derived oracle and the
    independent verifier — must agree on EVERY single-sync-drop mutation of
    every (cleaned) schedule in the exhaustive SpMV space: a load-bearing
    drop is detected, a redundant drop is not flagged."""
    g = _spmv_graph()
    states = enumerate_schedules(g, Platform.make_n_lanes(2), max_seqs=10_000)
    ver = ScheduleVerifier(g)
    n_mutations = n_detected = 0
    for st in states:
        for seq in (st.sequence, remove_redundant_syncs(st.sequence)):
            ops = seq.vector()
            for i, op in enumerate(ops):
                if not isinstance(op, SyncOp):
                    continue
                mut = Sequence(ops[:i] + ops[i + 1:])
                n_mutations += 1
                oracle_sound = synth_sound(st.graph, mut)
                got = ver(mut)
                assert got.ok == oracle_sound, (
                    f"judges disagree (oracle sound={oracle_sound}, "
                    f"verifier {got.witness()}) after dropping "
                    f"{op.desc()} from {seq.desc()}")
                if not oracle_sound:
                    n_detected += 1
                    assert any(v.kind in ("dep", "race:raw", "race:war",
                                          "race:waw")
                               for v in got.violations)
    assert n_mutations > 100
    assert n_detected > 50  # the space genuinely contains load-bearing syncs


def oracle_unsound_check(evolved_unbound):
    """``corrupt_schedule`` effectiveness check from the ORIGINAL oracle:
    bind the evolved graph with the lanes the order itself carries (the
    oracle skips unbound predecessors as free), then ask is_synced."""
    from tenzing_tpu.core.operation import BoundDeviceOp

    def check(seq) -> bool:
        assign = {op: op.lane() for op in seq
                  if isinstance(op, BoundDeviceOp)}
        bound = evolved_unbound.apply_lane_assignment(
            {v: assign[v] for v in evolved_unbound.vertices()
             if v in assign})
        return not synth_sound(bound, seq)

    return check


def test_corrupt_schedule_mutations_always_caught():
    """fault/inject.corrupt_schedule with the oracle as its effectiveness
    check only emits mutations the oracle deems unsound — and the
    independent verifier must catch every one (the chaos guarantee)."""
    g = _spmv_graph()
    check = oracle_unsound_check(expand_all(g.clone()))
    states = enumerate_schedules(g, Platform.make_n_lanes(2), max_seqs=10_000)
    ver = ScheduleVerifier(g)
    n = 0
    for st in states:
        seq = remove_redundant_syncs(st.sequence)
        for seed in (1, 2, 3):
            mut = corrupt_schedule(seq, seed, unsound_check=check)
            if mut is None:
                continue
            n += 1
            assert not ver(mut).ok, (
                f"verifier missed a corruption of {seq.desc()} -> "
                f"{mut.desc()}")
    assert n > 50


# -- targeted unit coverage --------------------------------------------------

def _two_lane_chain():
    """start -> a@lane0 -> b@lane1 -> finish with explicit syncs."""
    from tenzing_tpu.core.operation import DeviceOp

    class Dev(DeviceOp):
        def __init__(self, name, buf_in, buf_out):
            super().__init__(name)
            self._r, self._w = buf_in, buf_out

        def reads(self):
            return [self._r]

        def writes(self):
            return [self._w]

    g = Graph()
    a, b = Dev("a", "x", "y"), Dev("b", "y", "z")
    g.start_then(a)
    g.then(a, b)
    g.then_finish(b)
    a0, b1 = a.bind(Lane(0)), b.bind(Lane(1))
    e0, e1 = Event(0), Event(1)
    seq = Sequence([
        g.start(), a0, EventRecord(Lane(0), e0), WaitEvent(Lane(1), e0),
        b1, EventRecord(Lane(1), e1), EventSync(e1), g.finish(),
    ])
    return g, seq, a0, b1


def test_hand_built_schedule_verifies_and_labels_races():
    g, seq, a0, b1 = _two_lane_chain()
    assert verify_schedule(seq, g).ok
    ops = seq.vector()
    # drop the WaitEvent: a -> b is now unordered, and it conflicts on "y"
    # (a writes, b reads) -> race:raw with the buffer as the witness
    mut = Sequence([o for o in ops if not isinstance(o, WaitEvent)])
    v = verify_schedule(mut, g)
    assert not v.ok
    assert v.violations[0].kind == "race:raw"
    assert v.violations[0].resource == "y"
    assert "happens-before" in v.witness()
    # drop the EventSync: b -> finish unordered; finish reads/writes
    # nothing -> plain dep violation
    mut2 = Sequence([o for o in ops if not isinstance(o, EventSync)])
    v2 = verify_schedule(mut2, g)
    assert not v2.ok
    assert v2.violations[0].kind == "dep"
    # reorder: wait before its record observes nothing -> unordered + warned
    i_rec = next(i for i, o in enumerate(ops) if isinstance(o, EventRecord))
    i_wait = next(i for i, o in enumerate(ops) if isinstance(o, WaitEvent))
    swapped = list(ops)
    swapped[i_rec], swapped[i_wait] = swapped[i_wait], swapped[i_rec]
    v3 = verify_schedule(Sequence(swapped), g)
    assert not v3.ok
    assert any("dangling wait" in w for w in v3.warnings)


def test_structural_defects_flagged():
    g, seq, a0, b1 = _two_lane_chain()
    ops = seq.vector()
    # missing op
    v = verify_schedule(Sequence([o for o in ops if o is not b1]), g)
    assert not v.ok and any(x.kind == "missing_op" for x in v.violations)
    # duplicated op
    v = verify_schedule(Sequence(ops + [b1]), g)
    assert not v.ok and any(x.kind == "duplicate_op" for x in v.violations)
    # unbound device op
    from tenzing_tpu.core.operation import unbound

    ops2 = [unbound(o) if o is b1 else o for o in ops]
    v = verify_schedule(Sequence(ops2), g)
    assert not v.ok and any(x.kind == "unbound_op" for x in v.violations)


def test_dangling_record_warns_but_stays_sound():
    g, seq, a0, b1 = _two_lane_chain()
    ops = seq.vector()
    extra = Sequence(ops[:-1] + [EventRecord(Lane(1), Event(7)), ops[-1]])
    v = verify_schedule(extra, g)
    assert v.ok
    assert any("dangling record" in w for w in v.warnings)


def test_lane_sync_orders_device_then_host():
    g, seq, a0, b1 = _two_lane_chain()
    ops = seq.vector()
    # replace record+sync before finish with a LaneSync on lane 1
    pruned = [o for o in ops
              if not isinstance(o, EventSync)
              and not (isinstance(o, EventRecord) and o.lane() == Lane(1))]
    i_fin = len(pruned) - 1
    with_ls = pruned[:i_fin] + [LaneSync(Lane(1))] + pruned[i_fin:]
    assert verify_schedule(Sequence(with_ls), g).ok
    assert not verify_schedule(Sequence(pruned), g).ok


def test_verdict_json_and_verifier_cache():
    g, seq, _, _ = _two_lane_chain()
    ver = ScheduleVerifier(g)
    assert ver(seq).ok and ver(seq).ok
    assert ver.checked == 1  # second call answered from the verdict cache
    assert ver("not-a-sequence").ok  # non-Sequence orders are vacuous
    j = ver(seq).to_json()
    assert j["ok"] is True and j["violations"] == []
    ops = seq.vector()
    bad = ver(Sequence([o for o in ops if not isinstance(o, WaitEvent)]))
    j = bad.to_json()
    assert j["ok"] is False and j["violations"][0]["kind"] == "race:raw"


def test_host_ops_need_no_sync_among_themselves():
    g = Graph()
    a, b = NoOp("h1"), NoOp("h2")
    g.start_then(a)
    g.then(a, b)
    g.then_finish(b)
    assert verify_schedule(Sequence([g.start(), a, b, g.finish()]), g).ok
    # ...but reversing host program order breaks the dep
    assert not verify_schedule(Sequence([g.start(), b, a, g.finish()]), g).ok


# -- the measurement-stack guard ---------------------------------------------

def test_resilient_guard_quarantines_unsound_schedules():
    from tenzing_tpu.bench.benchmarker import BenchResult, schedule_id
    from tenzing_tpu.fault import (
        Quarantine,
        ResilientBenchmarker,
        UnsoundScheduleError,
    )
    from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics

    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        g, seq, _, _ = _two_lane_chain()
        ops = seq.vector()
        bad = Sequence([o for o in ops if not isinstance(o, WaitEvent)])

        class Inner:
            calls = 0

            def benchmark(self, order, opts=None):
                self.calls += 1
                return BenchResult.from_times([1.0])

        inner = Inner()
        quar = Quarantine()
        rb = ResilientBenchmarker(inner, quarantine=quar,
                                  verifier=ScheduleVerifier(g))
        assert rb.benchmark(seq).pct50 == 1.0  # sound passes through
        with pytest.raises(UnsoundScheduleError):
            rb.benchmark(bad)
        assert inner.calls == 1  # the unsound schedule was NEVER measured
        assert schedule_id(bad) in quar.entries
        assert reg.counter("verify.unsound").value == 1
    finally:
        set_metrics(prev)


def test_solver_accept_points_reject_unsound(tmp_path):
    """All three solvers refuse a candidate their ``verify`` gate rejects
    (here: a gate that rejects everything — so every accept point must
    fire) without crashing and without measuring anything."""
    from tenzing_tpu.bench.benchmarker import BenchResult
    from tenzing_tpu.solve.dfs import DfsOpts
    from tenzing_tpu.solve.dfs import explore as dfs_explore
    from tenzing_tpu.solve.local import LocalOpts, hill_climb
    from tenzing_tpu.solve.mcts import MctsOpts, explore

    class RejectAll:
        def __call__(self, order):
            from tenzing_tpu.verify.soundness import Soundness, Violation

            return Soundness(ok=False, violations=[
                Violation(kind="dep", a="x", b="y", a_pos=0, b_pos=1)])

    class Inner:
        calls = 0

        def benchmark(self, order, opts=None):
            self.calls += 1
            return BenchResult.from_times([1.0])

    g = _spmv_graph()
    plat = Platform.make_n_lanes(2)
    inner = Inner()
    res = explore(g, plat, inner, MctsOpts(n_iters=4, seed=1,
                                           verify=RejectAll()))
    assert res.sims == [] and inner.calls == 0
    res = dfs_explore(g, plat, inner, DfsOpts(max_seqs=5,
                                              verify=RejectAll()))
    assert res.sims == [] and inner.calls == 0
    with pytest.raises(RuntimeError, match="incumbent"):
        hill_climb(g, plat, inner, phases=("spmv",),
                   opts=LocalOpts(budget=2, verify=RejectAll()))
    assert inner.calls == 0


@pytest.mark.parametrize("workload", ["halo", "moe", "spmv", "attn"])
def test_driver_naive_baseline_verifies(workload):
    """The naive baseline the driver measures first — the denominator of
    every verdict — passes the verifier AS BUILT, for every workload, at
    smoke size and device-free.  The hand-listed halo/moe baselines carried
    no sync ops and killed ``bench.py`` at its first measurement (race:raw
    between a lane-bound pack and its host-side spill); nothing fed them to
    the verifier."""
    from tenzing_tpu.bench.driver import (
        BUILDERS,
        DriverRequest,
        graph_for,
        naive_schedule,
        workload_shape,
    )

    assert workload in BUILDERS
    req = DriverRequest(workload=workload, smoke=True)
    g, _ = graph_for(req)
    wargs = None
    if workload == "halo":
        from tenzing_tpu.models.halo import HaloArgs

        s = workload_shape(req)
        wargs = HaloArgs(nq=s["nq"], lx=s["n"], ly=s["n"], lz=s["n"],
                         radius=s["radius"])
    elif workload == "moe":
        from tenzing_tpu.models.moe_pipeline import (
            MoEPipeArgs,
            make_pipe_buffers,
        )

        margs = MoEPipeArgs(**workload_shape(req))
        _, _, cap = make_pipe_buffers(margs, seed=0, with_expected=False)
        wargs = (margs, cap)
    seq = naive_schedule(workload, g, wargs)
    v = ScheduleVerifier(g)(seq)
    assert v.ok, f"unsound baseline: {v.witness()}\n{seq.desc()}"
    # one lane, fully serialized
    lanes = {l.id for op in seq.vector()
             for l in getattr(op, "lanes", lambda: [])()}
    assert lanes == {0}
