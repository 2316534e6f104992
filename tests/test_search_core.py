"""The search core (``core/state.py`` decisions, ``solve/dfs.py`` enumeration,
``solve/mcts/node.py`` playouts) against things independent of it: the
event synchronizer's replay, the soundness verifier, the pairwise bijection
test, and counts and digests pinned from the commit before a second (C++)
core was removed, where the two cores agreed on every one of them.
"""

import ast
import hashlib
import random
from pathlib import Path

import pytest

from tenzing_tpu.core.event_synchronizer import EventSynchronizer
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import BoundDeviceOp, DeviceOp, NoOp
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.resources import Lane
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.sequence import get_equivalence as seq_equiv
from tenzing_tpu.core.serdes import sequence_to_json_str
from tenzing_tpu.core.state import State
from tenzing_tpu.models.spmv import SpMVCompound
from tenzing_tpu.solve.dfs import (
    _dedup_terminal_states,
    enumerate_schedules,
    expand_all,
    get_all_sequences,
    get_unique_sequences,
)
from tenzing_tpu.solve.mcts.node import Node
from tenzing_tpu.solve.mcts.strategies import FastMin
from tenzing_tpu.verify import ScheduleVerifier

PACKAGE = Path(__file__).resolve().parent.parent / "tenzing_tpu"


class Dev(DeviceOp):
    """Minimal device op (the test_gpu_graph.cu KernelOp analog)."""

    def apply(self, bufs, ctx):  # pragma: no cover - never traced here
        return {}


def host_chain_graph():
    g = Graph()
    a, b = NoOp("a"), NoOp("b")
    g.start_then(a)
    g.then(a, b)
    g.then_finish(b)
    return g


def device_diamond_graph():
    """start -> {da, db} -> dc -> finish, all device ops."""
    g = Graph()
    da, db, dc = Dev("da"), Dev("db"), Dev("dc")
    g.start_then(da)
    g.start_then(db)
    g.then(da, dc)
    g.then(db, dc)
    g.then_finish(dc)
    return g


def mixed_graph():
    """Device ops feeding a host op (device->host sync case)."""
    g = Graph()
    d, h = Dev("d"), NoOp("h")
    g.start_then(d)
    g.then(d, h)
    g.then_finish(h)
    return g


def spmv_graph():
    return SpMVCompound().graph()


GRAPHS = [host_chain_graph, device_diamond_graph, mixed_graph, spmv_graph]
SMALL = GRAPHS[:3]


def digest(seqs) -> str:
    """Order-sensitive digest of a list of schedules, ops and lanes and all."""
    h = hashlib.sha256()
    for s in seqs:
        h.update(sequence_to_json_str(s).encode() + b"\n")
    return h.hexdigest()[:16]


def _assert_legal_complete(graph, seq: Sequence):
    """Replay a schedule: every non-sync op must be synced at its position, and
    every graph vertex must execute exactly once."""
    bound = {}
    for op in seq:
        if isinstance(op, BoundDeviceOp):
            bound[op.unbound()] = op.lane()
    g = graph.apply_lane_assignment(bound) if bound else graph
    seen = []
    for op in seq:
        prefix = Sequence(seen)
        assert EventSynchronizer.is_synced(g, prefix, op), (
            f"op {op!r} unsynced at position {len(seen)}"
        )
        seen.append(op)
    executed_keys = {op.eq_key() for op in seq}
    for v in g.vertices():
        assert v.eq_key() in executed_keys


# -- decisions ---------------------------------------------------------------


@pytest.mark.parametrize("make", GRAPHS)
@pytest.mark.parametrize("n_lanes", [1, 2])
def test_decisions_along_random_walks(make, n_lanes):
    """At every state of a seeded walk the decisions are distinct and every
    one of them applies; where the walk ends, the synchronizer's replay and
    the verifier (which shares no code with the decision process) agree the
    schedule is legal and complete."""
    g = make()
    plat = Platform.make_n_lanes(n_lanes)
    verifier = ScheduleVerifier(g)
    for seed in range(5):
        rng = random.Random(seed)
        st = State(g)
        while not st.is_terminal():
            ds = st.get_decisions(plat)
            assert ds, "a state that is not terminal offers a decision"
            keys = [d.key() for d in ds]
            assert len(set(keys)) == len(keys)
            nexts = [st.apply(d) for d in ds]
            st = nexts[rng.randrange(len(ds))]
        _assert_legal_complete(g, st.sequence)
        verdict = verifier(st.sequence)
        assert verdict.ok, verdict.witness()


# -- enumeration -------------------------------------------------------------

# bijection-unique terminals of each small graph: what both cores counted at
# the commit before this file
N_UNIQUE = {
    ("host_chain_graph", 1): 1, ("host_chain_graph", 2): 1,
    ("device_diamond_graph", 1): 2, ("device_diamond_graph", 2): 8,
    ("mixed_graph", 1): 1, ("mixed_graph", 2): 1,
}


@pytest.mark.parametrize("make", SMALL)
@pytest.mark.parametrize("n_lanes", [1, 2])
def test_unique_sequences_are_the_dedup_of_all(make, n_lanes):
    """Dedup as the walk finds terminals == dedup after the whole walk,
    sequence for sequence."""
    g = make()
    plat = Platform.make_n_lanes(n_lanes)
    after = _dedup_terminal_states(get_all_sequences(g, plat, max_seqs=100000))
    during = get_unique_sequences(g, plat, max_seqs=100000)
    assert len(during) == N_UNIQUE[make.__name__, n_lanes]
    assert ([sequence_to_json_str(s.sequence) for s in during]
            == [sequence_to_json_str(s.sequence) for s in after])


# the capped lists of the commit before this file, two lanes (both cores)
CAPPED = {
    ("host_chain_graph", 1): (1, "5a1d3a65a49fd033"),
    ("host_chain_graph", 3): (1, "5a1d3a65a49fd033"),
    ("host_chain_graph", 7): (1, "5a1d3a65a49fd033"),
    ("device_diamond_graph", 1): (1, "c3fa0b6670a9671c"),
    ("device_diamond_graph", 3): (3, "65bfe7f027cb606c"),
    ("device_diamond_graph", 7): (7, "9c6249ea68fd8147"),
    ("mixed_graph", 1): (1, "b7e1c5932113ceac"),
    ("mixed_graph", 3): (1, "b7e1c5932113ceac"),
    ("mixed_graph", 7): (1, "b7e1c5932113ceac"),
}


@pytest.mark.parametrize("make", SMALL)
@pytest.mark.parametrize("cap", [1, 3, 7])
def test_capped_enumeration(make, cap):
    """The cap counts deduplicated terminals, and the same budget gives the
    same terminals in the same order as it always did."""
    got = get_unique_sequences(make(), Platform.make_n_lanes(2), max_seqs=cap)
    n, want = CAPPED[make.__name__, cap]
    assert len(got) == n <= cap
    assert digest(s.sequence for s in got) == want


N_SPMV_ONE_LANE = 4  # as both cores counted it


def test_enumeration_spmv_counts():
    """The SpMV inner DAG: its one-lane count, and on two lanes a
    bijection-unique set by the pairwise test (not by canonical key)."""
    g = spmv_graph()
    one = get_unique_sequences(g, Platform.make_n_lanes(1), max_seqs=100000)
    assert len(one) == N_SPMV_ONE_LANE
    two = get_unique_sequences(g, Platform.make_n_lanes(2), max_seqs=2000)
    assert len(two) >= 30
    for i in range(30):
        for j in range(i + 1, 30):
            assert not seq_equiv(two[i].sequence, two[j].sequence)


def test_enumerate_schedules_resolves_compounds():
    """enumerate_schedules pre-expands compound ops (structural closure) and
    must match the walk that explores ExpandOp as a decision."""
    g = Graph()
    c = SpMVCompound()
    g.start_then(c)
    g.then_finish(c)
    plat = Platform.make_n_lanes(1)
    walked = _dedup_terminal_states(get_all_sequences(g, plat, 100000))
    eager = enumerate_schedules(g, plat, 100000)
    assert len(eager) == len(walked)
    expanded = expand_all(g)
    for st in eager:
        _assert_legal_complete(expanded, st.sequence)
    # two lanes: full deduped space of the spmv DAG
    assert len(enumerate_schedules(g, Platform.make_n_lanes(2), 100000)) == 96


def test_enumerate_honors_pinned_lane_bindings():
    """A graph whose device ops were pre-bound by the caller keeps those
    lanes in every enumerated schedule."""
    g = device_diamond_graph()
    dops = g.device_vertices()
    pinned = g.apply_lane_assignment({dops[0]: Lane(1)})  # da pinned to lane 1
    plat = Platform.make_n_lanes(2)
    after = _dedup_terminal_states(
        get_all_sequences(pinned, plat, max_seqs=100000))
    got = enumerate_schedules(pinned, plat, max_seqs=100000)
    assert ([sequence_to_json_str(s.sequence) for s in got]
            == [sequence_to_json_str(s.sequence) for s in after])
    assert got
    for st in got:
        _assert_legal_complete(pinned, st.sequence)
        das = [op for op in st.sequence
               if isinstance(op, BoundDeviceOp) and op.name() == "da"]
        assert das and all(op.lane().id == 1 for op in das)


# -- playouts ----------------------------------------------------------------


def _uniform_rollout(graph, plat, seed) -> Sequence:
    root = Node(State(graph), FastMin)
    endpoint, seq = root.get_rollout(plat, random.Random(seed))
    assert endpoint is root
    return seq


@pytest.mark.parametrize("make", GRAPHS)
def test_rollout_produces_legal_schedules(make):
    g = make()
    plat = Platform.make_n_lanes(2)
    for seed in range(8):
        _assert_legal_complete(g, _uniform_rollout(g, plat, seed))


def test_rollout_varies_with_seed():
    g = spmv_graph()
    plat = Platform.make_n_lanes(2)
    seqs = {tuple(op.desc() for op in _uniform_rollout(g, plat, s))
            for s in range(16)}
    assert len(seqs) > 1


# sha256[:16] of the 45 schedules that the commit before this file handed its
# benchmarker with its C++ core switched off, and the tree they left
MESH_HALO_ROLLOUTS = ("6cf24de55584dd1e", 145)


def test_mesh_halo_search_is_the_search_it_was():
    """halo512-mesh4.mcts's graph (every exchange chooses its engine), two
    lanes, FastMin, uniform playouts with a random step at 0.15: the 45
    schedules of one seed are those of the commit before this file, whatever
    the process-wide generators hold (a playout draws from the search's own
    ``random.Random`` and from nothing else)."""
    import numpy as np

    from tenzing_tpu.bench.benchmarker import BenchOpts
    from tenzing_tpu.models.halo import HaloArgs, add_to_graph
    from tenzing_tpu.solve.mcts import MctsOpts, explore
    from tests.test_local import RiggedBenchmarker

    class Recording(RiggedBenchmarker):
        def __init__(self):
            super().__init__()
            self.orders = []

        def benchmark(self, order, opts=None):
            self.orders.append(order)
            return super().benchmark(order, opts)

    g = add_to_graph(Graph(), HaloArgs(nq=3, lx=512, ly=512, lz=512, radius=3),
                     xfer_choice=True)
    for ambient in (0, 1):
        random.seed(ambient)
        np.random.seed(ambient)
        bench = Recording()
        res = explore(g, Platform.make_n_lanes(2), bench,
                      MctsOpts(n_iters=45, rollout_eps=0.15, seed=7,
                               cache_benchmarks=False,
                               bench_opts=BenchOpts(n_iters=1)),
                      strategy=FastMin)
        assert len(bench.orders) == 45
        assert (digest(bench.orders), res.tree_size) == MESH_HALO_ROLLOUTS


# -- one core ----------------------------------------------------------------


def test_the_search_core_builds_and_loads_nothing():
    """The decision process is this package's Python and nothing else: no
    module of the solvers or the core can start a process or load a shared
    library, and nothing in the package reads the switch that once chose
    between two cores."""
    offenders = []
    for sub in ("solve", "core"):
        for path in sorted((PACKAGE / sub).rglob("*.py")):
            for node in ast.walk(ast.parse(path.read_text())):
                if isinstance(node, ast.Import):
                    names = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    names = [node.module or ""]
                else:
                    continue
                for name in names:
                    if name.split(".")[0] in ("subprocess", "ctypes"):
                        offenders.append(
                            f"{path.relative_to(PACKAGE)}: {name}")
    switch = "TENZING_TPU_" + "NATIVE"
    for path in sorted(PACKAGE.rglob("*")):
        if path.is_file() and path.suffix != ".pyc":
            if switch.encode() in path.read_bytes():
                offenders.append(f"{path.relative_to(PACKAGE)}: {switch}")
    assert not offenders, offenders
    assert not (PACKAGE / "native").exists()
    assert not (PACKAGE.parent / "native").exists()
