"""Distributed search fleet (search/fleet.py, ISSUE 20).

Acceptance coverage:

* grouped permutation reproducibility: a fused K-candidate round with
  ``group_seeds`` visits (and times) each group **bit-identically** to that
  group's solo ``benchmark_batch_times`` call — the measurement owner can
  pack strangers from other workers into one device round without
  perturbing any worker's paired accept decisions;
* the file control plane's monotonic snapshot exchange and winner-takes-all
  claim registry, and ``SharedSearchState``'s improvement-only incumbent
  publishing over it;
* the worker<->owner file protocol: a fused round answers each request with
  its own slice, hints forward to the prefetcher, singles answer inline,
  and errors round-trip with their fault class (``DeviceLostError``
  survives the process boundary);
* rank-agreed MCTS subtree partitioning: disjoint, covering, never empty;
* ``run_serialized`` (the ``--search-workers 1 --measure-batch 1`` path) is
  bit-identical to the direct legacy ``hill_climb`` invocation;
* a real two-subprocess fleet over the device-free spmv graph: every job
  completes, fused rounds fire, incumbents and claims cross the fleet.
"""

import hashlib
import os

import pytest

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    BenchResult,
    CsvBenchmarker,
    EmpiricalBenchmarker,
    result_row,
    schedule_id,
)
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.schedule import remove_redundant_syncs
from tenzing_tpu.core.sequence import canonical_key
from tenzing_tpu.core.state import State
from tenzing_tpu.fault.errors import DeviceLostError
from tenzing_tpu.models.spmv import SpMVCompound
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.parallel.control_plane import FileControlPlane
from tenzing_tpu.search.fleet import (
    FleetBenchmarker,
    FleetJob,
    MeasureOwner,
    SharedSearchState,
    _opts_from_json,
    _opts_to_json,
    _result_from_json,
    _result_to_json,
    claim_key,
    resolve_prefer,
    run_fleet,
    run_serialized,
)
from tenzing_tpu.solve.dfs import enumerate_schedules
from tenzing_tpu.solve.local import LocalOpts, hill_climb
from tenzing_tpu.solve.mcts.mcts import Node, prune_to_subtree
from tenzing_tpu.solve.mcts.strategies import FastMin


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
    key = canonical_key(remove_redundant_syncs(seq))
    h = hashlib.sha256(repr(key).encode()).digest()
    t = 1.0 + int.from_bytes(h[:8], "big") / float(1 << 64)
    return BenchResult.from_times([t, t, t])


@pytest.fixture(scope="module")
def corpus():
    states = enumerate_schedules(_graph(), Platform.make_n_lanes(2),
                                 max_seqs=10_000)
    assert 4 <= len(states) < 10_000
    return [st.sequence for st in states]


# -- identity / serialization ------------------------------------------------


def test_claim_key_canonical_and_stable(corpus):
    a, b = corpus[0], corpus[1]
    assert claim_key(a) == claim_key(a)
    assert len(claim_key(a)) == 32
    assert int(claim_key(a), 16) >= 0  # hex digest
    assert claim_key(a) != claim_key(b)
    # canonical: redundant-sync removal does not change the claim
    assert claim_key(remove_redundant_syncs(a)) == claim_key(a)


def test_json_round_trips():
    j = FleetJob(index=3, budget=17, seed=9, lanes=6,
                 phases=("pack", "unpack"), prefer="recorded",
                 chosen={"xfer_a": "xfer_a.rdma"}, kind="mcts",
                 subtree=(1, 4))
    assert FleetJob.from_json(j.to_json()) == j
    assert FleetJob.from_json(FleetJob(index=0, budget=1,
                                       seed=2).to_json()).phases == ("",)
    opts = BenchOpts(n_iters=7, max_retries=3, target_secs=0.25)
    rt = _opts_from_json(_opts_to_json(opts))
    assert (rt.n_iters, rt.max_retries, rt.target_secs) == (7, 3, 0.25)
    res = BenchResult.from_times([0.5, 0.25, 0.75])
    assert _result_from_json(_result_to_json(res)) == res


def test_resolve_prefer_names_driver_policies():
    from tenzing_tpu.bench import driver

    assert resolve_prefer(FleetJob(0, 1, 2)) is driver.generic_xla_prefer
    assert resolve_prefer(
        FleetJob(0, 1, 2, prefer="halo_alias")) is driver.halo_alias_prefer
    assert resolve_prefer(
        FleetJob(0, 1, 2, prefer="moe_bf16")) is driver.moe_bf16_prefer
    rec = resolve_prefer(FleetJob(0, 1, 2, prefer="recorded",
                                  chosen={"op": "op.host"}))
    assert rec("op", ["op.xla", "op.host"]) == "op.host"
    assert rec("other", ["other.xla", "other.host"]) == "other.xla"


# -- control plane / shared state --------------------------------------------


def test_file_control_plane_snapshots_and_claims(tmp_path):
    root = str(tmp_path / "ctrl")
    cp0 = FileControlPlane(root, 0, 2)
    cp1 = FileControlPlane(root, 1, 2)
    cp0.publish("incumbent", {"cost_s": 2.0})
    cp1.publish("incumbent", {"cost_s": 1.0})
    cp0.publish("incumbent", {"cost_s": 1.5})  # replaces rank 0's snapshot
    snaps = cp1.gather("incumbent")
    assert snaps == {0: {"cost_s": 1.5}, 1: {"cost_s": 1.0}}
    assert cp1.gather("incumbent", include_self=False) == {0: {"cost_s": 1.5}}
    # winner-takes-all: first claimant owns the key, rivals lose
    assert cp0.claim("visited", "k1") is True
    assert cp1.claim("visited", "k1") is False
    assert cp1.claim("visited", "k2") is True
    assert cp0.claim_count("visited") == 2


def test_shared_search_state_claims_and_incumbents(tmp_path, registry,
                                                   corpus):
    root = str(tmp_path / "ctrl")
    s0 = SharedSearchState(FileControlPlane(root, 0, 2))
    s1 = SharedSearchState(FileControlPlane(root, 1, 2))
    assert s0.claim(corpus[0]) is True
    assert s1.claim(corpus[0]) is False  # rank 0 already paid for it
    assert s1.claim(corpus[1]) is True
    assert (s0.claimed, s0.claim_misses) == (1, 0)
    assert (s1.claimed, s1.claim_misses) == (1, 1)
    assert registry.counter("search.fleet.claim_misses").value == 1
    s0.note_incumbent(2.0, corpus[0])
    s1.note_incumbent(1.0, corpus[1])
    s0.note_incumbent(3.0, corpus[2])  # worse: not published
    assert s0.cp.gather("incumbent")[0]["cost_s"] == 2.0
    assert s0.global_best() == (1, 1.0)


# -- grouped permutation reproducibility (the fused-round contract) ----------


class VisitRecorder(EmpiricalBenchmarker):
    """EmpiricalBenchmarker with the device replaced by a deterministic
    visit log: ``_measure`` records which schedule ran when and answers a
    time that depends only on (schedule, its own visit count) — so two
    calls produce identical times iff they visit identically."""

    def __init__(self):  # no runner/control plane: both paths overridden
        self.visits = []
        self._counts = {}
        self._overhead = 0.0

    def _runner_for(self, order):
        key = schedule_id(order)

        def run_n(n):
            pass

        run_n.key = key
        return run_n, 0

    def _measure(self, run_n, n_samples, opts, fences_per_sample=0):
        k = run_n.key
        c = self._counts[k] = self._counts.get(k, 0) + 1
        self.visits.append(k)
        h = int(hashlib.sha256(k.encode()).hexdigest()[:12], 16)
        return (h % 9973 + c) / 1e6, n_samples


def test_fused_group_seeds_bit_identical_to_solo(corpus):
    """The satellite-2 contract: a group's per-iteration visit order (and
    therefore its times, accept decisions, everything downstream) depends
    only on its own ``(orders, seed)`` — never on the strangers sharing
    the fused round."""
    ga, gb = corpus[:2], corpus[2:4]
    opts = BenchOpts(n_iters=4, max_retries=1)
    fused = VisitRecorder()
    t_fused = fused.benchmark_batch_times(
        ga + gb, opts, seed=5, group_seeds=[(2, 5), (2, 9)])
    solo_a, solo_b = VisitRecorder(), VisitRecorder()
    t_a = solo_a.benchmark_batch_times(ga, opts, seed=5)
    t_b = solo_b.benchmark_batch_times(gb, opts, seed=9)
    assert t_fused[:2] == t_a and t_fused[2:] == t_b
    keys_a = {schedule_id(o) for o in ga}
    assert [k for k in fused.visits if k in keys_a] == solo_a.visits
    assert [k for k in fused.visits if k not in keys_a] == solo_b.visits


def test_bad_group_partition_rejected(corpus):
    with pytest.raises(ValueError, match="partition"):
        VisitRecorder().benchmark_batch_times(
            corpus[:3], BenchOpts(n_iters=1), group_seeds=[(2, 5)])
    with pytest.raises(ValueError, match="partition"):
        VisitRecorder().benchmark_batch_times(
            corpus[:2], BenchOpts(n_iters=1), group_seeds=[(2, 5), (0, 9)])


# -- worker<->owner file protocol --------------------------------------------


class SynthBench:
    """Owner-side benchmark stack stand-in: deterministic per-schedule
    answers (hash of the canonical form), batch protocol included."""

    def __init__(self, fail=None):
        self.fail = fail
        self.group_seeds_seen = []

    def benchmark(self, order, opts=None):
        if self.fail is not None:
            exc = self.fail(order)
            if exc is not None:
                raise exc
        return _synth_result(order)

    def benchmark_batch_times(self, orders, opts=None, seed=0,
                              times_out=None, group_seeds=None):
        self.group_seeds_seen.append(group_seeds)
        n = (opts or BenchOpts()).n_iters
        return [[_synth_result(o).pct50] * n for o in orders]


def _mk_fleet_dir(tmp_path):
    d = str(tmp_path / "fleet")
    for sub in ("jobs", "mq", "ctrl"):
        os.makedirs(os.path.join(d, sub))
    return d


def test_owner_answers_fused_round_per_request(tmp_path, registry, corpus):
    d = _mk_fleet_dir(tmp_path)
    g = _graph()
    bench = SynthBench()
    owner = MeasureOwner(d, g, bench, measure_batch=4)
    owner.heartbeat()
    p1 = FleetBenchmarker(d, 1, g, timeout_secs=5.0)
    p2 = FleetBenchmarker(d, 2, g, timeout_secs=5.0)
    opts = BenchOpts(n_iters=3, max_retries=1)
    r1 = p1._submit("batch", corpus[:2], opts, seed=5)
    r2 = p2._submit("batch", corpus[2:4], opts, seed=9)
    owner.drain(busy_workers=2)
    assert owner.rounds == 1 and owner.fused_orders == 4
    assert owner.occupancy() == 1.0
    assert bench.group_seeds_seen == [[(2, 5), (2, 9)]]
    assert registry.counter("search.fleet.rounds").value == 1
    assert registry.counter("search.fleet.fused_orders").value == 4
    t1 = [list(ts) for ts in p1._await(r1)["times"]]
    t2 = [list(ts) for ts in p2._await(r2)["times"]]
    assert t1 == [[_synth_result(o).pct50] * 3 for o in corpus[:2]]
    assert t2 == [[_synth_result(o).pct50] * 3 for o in corpus[2:4]]
    # the high-level proxy call fills the times_out contract too
    r3 = p1._submit("batch", corpus[:1], opts, seed=1)
    owner.drain(busy_workers=1)  # every busy worker pending -> fires at 1
    acc = [[]]
    out = p1._await(r3)
    assert [list(ts) for ts in out["times"]] == [
        [_synth_result(corpus[0]).pct50] * 3]
    assert owner.rounds == 2 and owner.occupancy() == 5 / 8
    del acc


def test_owner_forwards_hints_and_singles(tmp_path, registry, corpus):
    d = _mk_fleet_dir(tmp_path)
    g = _graph()

    class Prefetcher:
        def __init__(self):
            self.seen = []

        def prefetch(self, orders):
            self.seen.extend(orders)
            return len(orders)

    pf = Prefetcher()
    owner = MeasureOwner(d, g, SynthBench(), measure_batch=4, prefetcher=pf)
    owner.heartbeat()
    proxy = FleetBenchmarker(d, 0, g, timeout_secs=5.0)
    assert proxy.prefetch(corpus[:3]) == 3
    rid = proxy._submit("single", corpus[:1], BenchOpts(n_iters=2), 0)
    owner.drain(busy_workers=1)
    assert owner.hints == 3 and owner.singles == 1 and owner.rounds == 0
    assert [canonical_key(o) for o in pf.seen] == [
        canonical_key(o) for o in corpus[:3]]
    assert registry.counter("search.fleet.hints").value == 3
    assert registry.counter("search.fleet.singles").value == 1
    res = _result_from_json(proxy._await(rid)["result"])
    assert res == _synth_result(corpus[0])


def test_owner_error_round_trip_preserves_fault_class(tmp_path, registry,
                                                      corpus):
    d = _mk_fleet_dir(tmp_path)
    g = _graph()
    bench = SynthBench(fail=lambda o: ValueError("synthetic owner failure"))
    owner = MeasureOwner(d, g, bench, measure_batch=2)
    owner.heartbeat()
    proxy = FleetBenchmarker(d, 0, g, timeout_secs=5.0)
    rid = proxy._submit("single", corpus[:1], BenchOpts(n_iters=1), 0)
    owner.drain(busy_workers=1)
    with pytest.raises(RuntimeError, match=r"\[owner\] ValueError"):
        proxy._await(rid)
    # a device loss is fatal on BOTH sides: the owner re-raises after
    # answering, and the worker reconstructs the DeviceLostError type
    bench.fail = lambda o: DeviceLostError("chip dropped off the bus")
    rid = proxy._submit("single", corpus[:1], BenchOpts(n_iters=1), 0)
    with pytest.raises(DeviceLostError):
        owner.drain(busy_workers=1)
    with pytest.raises(DeviceLostError, match="chip dropped off the bus"):
        proxy._await(rid)


# -- subtree partitioning ----------------------------------------------------


def _first_branching_node(plat):
    """Walk the deterministic decision tree down to the first node with
    more than one child (the spmv root's only decision is the compound
    expansion) — ``prune_to_subtree`` works on any Node."""
    node = Node(State(_graph()), FastMin)
    node.ensure_children(plat)
    while len(node.children) == 1:
        node = node.children[0]
        node.ensure_children(plat)
    assert len(node.children) >= 2
    return node


def test_mcts_subtree_slices_disjoint_covering_nonempty():
    plat = Platform.make_n_lanes(2)
    all_keys = [c.decision.key()
                for c in _first_branching_node(plat).children]
    seen = []
    for k in range(2):
        node = _first_branching_node(plat)
        prune_to_subtree(node, plat, (k, 2))
        keys = [c.decision.key() for c in node.children]
        assert keys  # never empty
        seen.extend(keys)
    assert sorted(seen) == sorted(all_keys)  # disjoint AND covering
    # more ranks than children: the empty slice degrades to one child
    for k in range(len(all_keys) + 2):
        node = _first_branching_node(plat)
        prune_to_subtree(node, plat, (k, len(all_keys) + 2))
        assert len(node.children) >= 1


# -- backward-compat bit-identity --------------------------------------------


def test_run_serialized_bit_identical_to_legacy_climb(corpus):
    g = _graph()
    rows = [result_row(i, _synth_result(s), s)
            for i, s in enumerate(corpus)]
    opts = BenchOpts(n_iters=3, max_retries=1)
    jobs = [FleetJob(index=0, budget=5, seed=3, lanes=2),
            FleetJob(index=1, budget=4, seed=7, lanes=2)]
    fr = run_serialized(g, jobs, CsvBenchmarker(rows, g, normalize=True),
                        opts)
    assert fr.stats["workers"] == 1 and fr.stats["measure_batch"] == 1
    assert fr.stats["failed_jobs"] == 0
    assert fr.stats["distinct_candidates"] >= 1
    for j, jr in zip(jobs, fr.jobs):
        r = hill_climb(
            g, Platform.make_n_lanes(2),
            CsvBenchmarker(rows, g, normalize=True), j.phases,
            prefer=resolve_prefer(j),
            opts=LocalOpts(budget=j.budget, bench_opts=opts, seed=j.seed,
                           paired=True))
        assert [(canonical_key(s.order), s.result.pct50)
                for s in jr.sims] == [
            (canonical_key(s.order), s.result.pct50) for s in r.sims]
        assert canonical_key(jr.final.order) == canonical_key(r.final.order)
        assert jr.final.result == r.final.result


# -- the fleet end to end ----------------------------------------------------


def test_fleet_end_to_end_two_workers(tmp_path, registry):
    """Two real worker subprocesses over the device-free spmv smoke graph,
    this process as the measurement owner: every job completes, at least
    one fused round fires, incumbents and claims cross the fleet, and the
    ``perf.distributed`` stats block is fully populated."""
    from tenzing_tpu.bench.driver import DriverRequest, graph_for

    req = DriverRequest(workload="spmv", smoke=True)
    g, _ = graph_for(req)
    jobs = [FleetJob(index=0, budget=4, seed=2, lanes=2),
            FleetJob(index=1, budget=4, seed=3, lanes=2)]
    fr = run_fleet(g, req.to_json(), jobs, SynthBench(),
                   BenchOpts(n_iters=3, max_retries=1), n_workers=2,
                   measure_batch=4, verify=False,
                   fleet_dir=str(tmp_path / "fleet"), lease_ttl=5.0)
    st = fr.stats
    assert st["failed_jobs"] == 0 and len(fr.jobs) == 2
    for jr in fr.jobs:
        assert jr.final is not None and jr.sims
        assert jr.worker in ("worker-r0", "worker-r1")
    assert st["rounds"] >= 1
    assert 0.0 < st["batch_occupancy"] <= 1.0
    assert st["candidates"] == sum(len(jr.sims) for jr in fr.jobs)
    assert 1 <= st["distinct_candidates"] <= st["candidates"]
    assert st["best_cost_us"] == pytest.approx(
        min(s.result.pct50 for jr in fr.jobs for s in jr.sims) * 1e6,
        rel=1e-6)
    assert st["claimed_keys"] >= 1
    assert st["incumbent_costs_s"]  # at least one worker published
    assert st["worker_restarts"] == 0
    assert registry.counter("search.fleet.rounds").value == st["rounds"]
    # the fleet dir we own survives for inspection: done docs exist
    for j in jobs:
        assert os.path.exists(
            os.path.join(str(tmp_path / "fleet"), "jobs",
                         f"job-{j.index}.done.json"))
