"""The tree search's lookahead (``solve/mcts``): given a compile prefetcher,
``explore`` draws the rollout it is about to measure and one more for every
worker, hints them at once, and measures in the order drawn; an unmeasured
rollout is a pending visit on every node of its path.  No device: schedules
are timed by a hash, compiles are a sleep.
"""

import hashlib
import random
import threading
import time

import pytest

from tenzing_tpu.bench.benchmarker import (
    BenchResult,
    CachingBenchmarker,
    schedule_id,
)
from tenzing_tpu.bench.pipeline import PrefetchingBenchmarker
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.sequence import canonical_key
from tenzing_tpu.fault import (
    JournalingBenchmarker,
    ResilientBenchmarker,
    SearchCheckpoint,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.obs.tracer import Tracer, set_tracer
from tenzing_tpu.core.state import State
from tenzing_tpu.solve.mcts import MctsOpts, explore
from tenzing_tpu.solve.mcts.node import Node
from tenzing_tpu.solve.mcts.strategies import AvgTime, FastMin, Unvisited
from tenzing_tpu.verify.soundness import Soundness, Violation

from tests.test_mcts import FakePlatform, two_indep_device_graph
from tests.test_pipeline_bench import FakeExecutor
from tests.test_pipeline_bench import _graph as spmv_graph


class Deadline(BaseException):
    """The benchmark harness's way out of a search: not an ``Exception``,
    which the solvers take for a candidate that failed."""


@pytest.fixture
def registry():
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        yield reg
    finally:
        set_metrics(prev)


@pytest.fixture
def tracer():
    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        yield tr
    finally:
        set_tracer(prev)


class Hints:
    """A prefetcher that is only its width: takes hints, compiles nothing."""

    def __init__(self, workers):
        self.workers = workers
        self.hinted = []  # schedule ids, in the order hinted

    def prefetch(self, orders):
        self.hinted.extend(schedule_id(o) for o in orders)
        return 0


class HashBench:
    """Times a schedule by a hash of it.  ``before(order)`` runs first in
    every call (the tests' window into the search mid-flight); ``stop_at``
    raises :class:`Deadline` in place of that call's answer; ``fails``
    names schedule ids that do not compile."""

    def __init__(self, before=None, stop_at=None, fails=(), secs=0.0):
        self.before = before
        self.stop_at = stop_at
        self.fails = set(fails)
        self.secs = secs
        self.measured = []  # schedule ids, in the order measured

    def benchmark(self, order, opts=None):
        if self.before is not None:
            self.before(order)
        if self.stop_at is not None and len(self.measured) >= self.stop_at:
            raise Deadline()
        sid = schedule_id(order)
        if sid in self.fails:
            raise RuntimeError(f"failed to compile ({sid})")
        if self.secs:
            time.sleep(self.secs)
        self.measured.append(sid)
        h = hashlib.sha256(repr(canonical_key(order)).encode()).digest()
        t = 1.0 + int.from_bytes(h[:8], "big") / float(1 << 64)
        return BenchResult.from_times([t, t, t])


def spy():
    """``(strategy, root)``: FastMin, and a getter of the tree's root of the
    search that last used it (``explore`` sets ``ctx.root``)."""
    made = []

    class Spy(FastMin):
        class Context(FastMin.Context):
            def __init__(self, seed=0):
                super().__init__(seed)
                made.append(self)

    return Spy, lambda: made[-1].root


def walk(node):
    yield node
    for c in node.children:
        yield from walk(c)


def shape(node):
    """The whole tree, comparable: decisions, counts, flags, children."""
    return (node.decision.key() if node.decision is not None else None,
            node.n_, node.pending_, node.fully_visited_, node.expanded_,
            [shape(c) for c in node.children])


def search(bench, n_iters, prefetch=None, seed=3, strategy=None, graph=None,
           plat=None, **kw):
    return explore(graph if graph is not None else spmv_graph(),
                   plat if plat is not None else Platform.make_n_lanes(2),
                   bench,
                   MctsOpts(n_iters=n_iters, seed=seed, prefetch=prefetch,
                            cache_benchmarks=False, **kw),
                   strategy=strategy)


# -- hints that hit -----------------------------------------------------------


def test_every_rollout_is_hinted_before_it_is_measured_and_hits(registry):
    """Over 24 iterations with two workers: a schedule's hint is out before
    its measurement begins, its first call runs on a worker, and the
    measurement uses it."""
    hinted = []
    unhinted = []

    def before(order):
        if schedule_id(order) not in hinted:
            unhinted.append(schedule_id(order))

    inner = HashBench(before=before, secs=0.005)
    p = PrefetchingBenchmarker(inner, executor=FakeExecutor(compile_secs=0.01),
                               workers=2)
    issue = p.prefetch

    def recording(orders):
        orders = list(orders)
        hinted.extend(schedule_id(o) for o in orders)
        return issue(orders)

    p.prefetch = recording
    try:
        res = search(p, 24, prefetch=p)
    finally:
        p.close()
    assert len(res.sims) == 24 and not unhinted
    assert hinted == inner.measured  # measured in the order drawn
    assert p.issued >= 20
    # what is missing is the first rollout's, where the foreground can reach
    # its measurement before a worker has picked the compile up
    assert p.hits / p.issued >= 0.9
    assert registry.counter("pipeline.prefetch.hits").value == p.hits
    # 23 of 24 were drawn while another was unmeasured; none was dropped
    assert registry.counter("mcts.lookahead.drawn").value == 23
    assert registry.counter("mcts.lookahead.dropped").value == 0


@pytest.mark.parametrize("workers", [1, 2, 3])
def test_queue_holds_the_rollout_in_hand_and_one_more_a_worker(registry,
                                                               workers):
    """At each measurement the queue is the rollout being measured and
    ``workers`` more (fewer only at the end of the budget), every one a
    pending visit at the root, and the gauge says so."""
    strategy, root = spy()
    depth, gauge = [], []

    def before(order):
        depth.append(root().pending_)
        gauge.append(registry.gauge("mcts.lookahead.pending").value)

    n = 12
    search(HashBench(before=before), n, prefetch=Hints(workers),
           strategy=strategy)
    assert depth == [min(workers + 1, n - i) for i in range(n)]
    assert gauge == [d - 1 for d in depth]
    assert registry.counter("mcts.lookahead.drawn").value == n - 1


def test_no_lookahead_without_a_width(registry):
    """No prefetcher, or one that has no ``workers``: one rollout drawn, one
    measured, as ever."""
    class NoWidth:
        def prefetch(self, orders):
            return 0

    for prefetch in (None, NoWidth()):
        strategy, root = spy()
        depth = []
        res = search(HashBench(before=lambda o: depth.append(root().pending_)),
                     8, prefetch=prefetch, strategy=strategy)
        assert depth == [1] * 8 and len(res.sims) == 8
    assert registry.counter("mcts.lookahead.drawn").value == 0


# -- virtual visits -----------------------------------------------------------


def test_queued_rollouts_take_different_unplayed_children(registry):
    """No two unmeasured rollouts start from the same unplayed child while
    a sibling of it is free, and they do spread: some node has had two of
    its children pending at once."""
    strategy, root = spy()
    spread = []

    def before(order):
        for node in walk(root()):
            fresh = [c for c in node.children if c.n_ == 0]
            if any(c.pending_ > 1 for c in fresh):
                assert all(c.visits() for c in node.children), \
                    "a rollout doubled up on a child beside a free one"
            spread.append(sum(1 for c in fresh if c.pending_))

    search(HashBench(before=before), 40, prefetch=Hints(3), strategy=strategy)
    assert max(spread) >= 2


def test_pending_visits_count_in_uct(registry):
    """Below the unplayed frontier the descent counts pending visits too:
    with every child of the root played, the queued rollouts do not all go
    down the one branch that UCT with measured counts alone would pick."""
    strategy, root = spy()
    branches = []

    def before(order):
        r = root()
        if all(c.n_ for c in r.children):
            branches.append(sum(1 for c in r.children if c.pending_))

    search(HashBench(before=before), 60, prefetch=Hints(3), strategy=strategy,
           graph=two_indep_device_graph(), plat=FakePlatform(2))
    assert branches and max(branches) >= 2


@pytest.mark.parametrize("strategy", [Unvisited, FastMin, AvgTime])
def test_a_pending_unmeasured_child_is_neutral_to_every_strategy(strategy):
    """A child that is drawn through and not yet measured has no times: a
    strategy that keys on that (``Unvisited``: +inf) is not asked, and the
    child scores the neutral 0.0, so the next draws are not all sent after
    it."""
    plat = FakePlatform(2)
    root = Node(State(two_indep_device_graph()), strategy)
    ctx = strategy.Context(seed=0)
    ctx.root = root
    root.ensure_children(plat)
    assert len(root.children) >= 2
    pending = root.children[0]
    pending.mark_pending()
    for i, c in enumerate(root.children[1:]):
        c.backprop(ctx, BenchResult.from_times([1.0 + i]))
    first_steps = set()
    for seed in range(32):
        node = root.select(ctx, plat, random.Random(seed))
        while node.parent is not root:
            node = node.parent
        first_steps.add(id(node))
    assert len(first_steps) >= 2 or id(pending) not in first_steps


def test_what_the_cache_answers_is_not_hinted(registry):
    """A drawn rollout whose result the caching layer holds (an equivalent
    schedule measured earlier, or restored from a journal) has no first
    call ahead of it: it is drawn and settled like any other and not
    hinted."""
    first = search(HashBench(), 24, prefetch=Hints(2))
    known = first.sims[::2]
    inner = HashBench()
    cache = CachingBenchmarker(inner)
    for s in known:
        cache._cache[cache._key(s.order, MctsOpts().bench_opts)] = s.result
    hints = Hints(2)
    res = explore(spmv_graph(), Platform.make_n_lanes(2), cache,
                  MctsOpts(n_iters=24, seed=3, prefetch=hints))
    assert [schedule_id(s.order) for s in res.sims] == \
        [schedule_id(s.order) for s in first.sims]
    held = {canonical_key(s.order) for s in known}
    assert held and not any(canonical_key(s.order) in held
                            for s in res.sims
                            if schedule_id(s.order) in hints.hinted)
    assert set(inner.measured) <= set(hints.hinted)


# -- the loop's ends ----------------------------------------------------------


def refusing(bad):
    """A verifier that refuses the schedule ids in ``bad``."""
    def verify(order):
        if schedule_id(order) in bad:
            return Soundness(ok=False, violations=[Violation(
                kind="missing_op", a="", b="refused by the test")])
        return Soundness(ok=True)

    return verify


@pytest.mark.parametrize("how", ["verifier", "compile"])
def test_a_rejected_rollout_settles_its_pending_visits(registry, how):
    """A rollout the verifier refuses, or whose program does not compile,
    is backpropagated as a penalty: its pending visits become real ones
    like any measured rollout's, and nothing is left pending."""
    n = 24
    clean = search(HashBench(), n, prefetch=Hints(2))
    bad = {schedule_id(s.order) for s in clean.sims[3::5]}
    strategy, root = spy()
    kw = ({"verify": refusing(bad)} if how == "verifier" else {})
    bench = HashBench(fails=bad if how == "compile" else ())
    res = search(bench, n, prefetch=Hints(2), strategy=strategy, **kw)
    rejected = n - len(res.sims)
    assert rejected >= 1
    assert not any(schedule_id(s.order) in bad for s in res.sims)
    r = root()
    assert all(node.pending_ == 0 for node in walk(r))
    assert r.n_ == n  # penalties are visits too
    assert res.tree_size == r.size()
    assert registry.counter("mcts.lookahead.dropped").value == 0


def test_a_normal_end_leaves_nothing_pending(registry):
    """The budget caps the draws as well: the last rollouts are measured
    with a shorter queue, and none is drawn to be thrown away."""
    strategy, root = spy()
    hints = Hints(2)
    bench = HashBench()
    res = search(bench, 16, prefetch=hints, strategy=strategy)
    assert hints.hinted == bench.measured and len(res.sims) == 16
    r = root()
    assert all(node.pending_ == 0 for node in walk(r))
    assert r.n_ == 16 and res.tree_size == r.size()
    assert registry.counter("mcts.lookahead.dropped").value == 0


@pytest.mark.parametrize("expand_rollout", [False, True])
def test_a_deadline_drops_the_queue_and_what_it_built(registry, tracer,
                                                      expand_rollout):
    """The harness's ``Deadline`` out of the tenth measurement: the three
    rollouts drawn and not measured go, with their pending visits and the
    nodes their draws created.  What is left is the tree of a search that
    had nine iterations to begin with."""
    strategy, root = spy()
    with pytest.raises(Deadline):
        search(HashBench(stop_at=9), 1000, prefetch=Hints(2),
               strategy=strategy, expand_rollout=expand_rollout)
    cut = root()
    assert registry.counter("mcts.lookahead.dropped").value == 3
    assert all(node.pending_ == 0 for node in walk(cut))
    assert cut.n_ == 9
    span = [s for s in tracer.spans() if s.name == "mcts.explore"][-1]
    assert span.attrs["n_sims"] == 9
    assert span.attrs["tree_size"] == cut.size()

    whole = search(HashBench(), 9, prefetch=Hints(2), strategy=strategy,
                   expand_rollout=expand_rollout)
    assert shape(cut) == shape(root())
    assert whole.tree_size == cut.size()


def test_a_draw_that_fails_leaves_no_nodes(registry):
    """An exception out of a draw itself (here the rollout policy, at its
    fortieth decision and so in the middle of a materialized playout): the
    half-drawn rollout's nodes go with those of the queue, and what is left
    is the tree of the rollouts that were measured."""
    calls = [0]

    def policy(state, decisions):
        calls[0] += 1
        if calls[0] == 40:
            raise Deadline()
        return decisions[0]

    strategy, root = spy()
    bench = HashBench()
    kw = dict(strategy=strategy, expand_rollout=True, rollout_eps=0.0)
    with pytest.raises(Deadline):
        search(bench, 1000, prefetch=Hints(2), rollout_policy=policy, **kw)
    cut = root()
    assert all(node.pending_ == 0 for node in walk(cut))
    assert cut.n_ == len(bench.measured) >= 1

    calls[0] = 40  # past the fault
    search(HashBench(), cut.n_, prefetch=Hints(2), rollout_policy=policy,
           **kw)
    assert shape(cut) == shape(root())


def test_the_checkpoint_cursor_counts_measured_rollouts_only(registry,
                                                             tmp_path):
    """The cursor saved after every iteration gives the tree without what
    the queued draws added: the size a search stopped there ends with."""
    sizes = []

    class Cursor:
        def save_state(self, **kw):
            sizes.append(kw["mcts"]["tree_size"])

    search(HashBench(), 12, prefetch=Hints(2), checkpoint=Cursor())
    assert len(sizes) == 12
    for n in (1, 5, 9, 12):
        assert search(HashBench(), n, prefetch=Hints(2)).tree_size == \
            sizes[n - 1]


@pytest.mark.parametrize("expand_rollout", [False, True])
@pytest.mark.parametrize("workers", [1, 3])
def test_a_small_space_is_exhausted_exactly(registry, workers,
                                            expand_rollout):
    """Two independent operations on two lanes: the search ends by itself,
    the root fully visited, with every node of the tree the endpoint of
    exactly one rollout (every terminal, where playouts build the tree),
    as without lookahead, and nothing pending or dropped."""
    def run(prefetch):
        strategy, root = spy()
        bench = HashBench()
        res = search(bench, 100_000, prefetch=prefetch, strategy=strategy,
                     graph=two_indep_device_graph(), plat=FakePlatform(2),
                     expand_rollout=expand_rollout)
        return res, root()

    plain, plain_root = run(None)
    res, r = run(Hints(workers))
    assert r.fully_visited_
    assert all(node.pending_ == 0 for node in walk(r))
    assert registry.counter("mcts.lookahead.dropped").value == 0
    terminals = [node for node in walk(r) if node.is_terminal()]
    assert all(node.n_ == 1 for node in terminals)
    assert len(res.sims) == len(plain.sims) and r.size() == plain_root.size()
    if expand_rollout:
        assert len(res.sims) == len(terminals)
        assert sorted(schedule_id(s.order) for s in res.sims) == \
            sorted(schedule_id(s.order) for s in plain.sims)
    else:
        assert len(res.sims) == r.size() - 1


# -- determinism --------------------------------------------------------------


def test_compile_threads_of_any_speed_give_the_same_search(registry):
    """Same seed, same ``workers``: slow compiles, fast ones and none at
    all give the same rollouts in the same order and the same tree.  Another
    width is another search."""
    def run(workers, compile_secs):
        inner = HashBench()
        if compile_secs is None:
            return search(inner, 24, prefetch=Hints(workers))
        p = PrefetchingBenchmarker(
            inner, executor=FakeExecutor(compile_secs=compile_secs),
            workers=workers)
        try:
            return search(p, 24, prefetch=p)
        finally:
            p.close()

    def key(res):
        return ([(schedule_id(s.order), s.result.pct50) for s in res.sims],
                res.tree_size)

    ref = key(run(2, None))
    assert key(run(2, 0.0)) == ref
    assert key(run(2, 0.004)) == ref
    assert key(run(2, 0.02)) == ref
    assert key(run(1, None)) != ref
    assert not [t for t in threading.enumerate()
                if t.name.startswith("tz-prefetch") and t.is_alive()]


def test_a_resumed_search_rebuilds_the_same_tree(registry, tmp_path):
    """Killed at its tenth measurement and resumed against the journal: the
    replay draws ahead as the first run did, every answer it had comes from
    the journal, and sims and tree are those of a run never interrupted."""
    opts = dict(n_iters=24, seed=3)
    strategy, root = spy()

    def stack(inner, ckpt):
        return CachingBenchmarker(JournalingBenchmarker(
            ResilientBenchmarker(inner), ckpt))

    ref = explore(spmv_graph(), Platform.make_n_lanes(2),
                  CachingBenchmarker(ResilientBenchmarker(HashBench())),
                  MctsOpts(**opts, prefetch=Hints(2)), strategy=strategy)
    ref_shape = shape(root())

    ckdir = str(tmp_path / "ckpt")
    ckpt = SearchCheckpoint(ckdir)
    first = HashBench(stop_at=9)
    with pytest.raises(Deadline):
        explore(spmv_graph(), Platform.make_n_lanes(2), stack(first, ckpt),
                MctsOpts(**opts, prefetch=Hints(2), checkpoint=ckpt),
                strategy=strategy)
    cursor = SearchCheckpoint(ckdir).load_state()["mcts"]
    # the sims it had when its tenth distinct schedule came up for measuring
    # (the cache answers a repeat, and a repeat is a sim)
    keys = [canonical_key(s.order) for s in ref.sims]
    had = next(i for i in range(len(keys)) if len(set(keys[:i + 1])) == 10)
    assert cursor["n_sims"] == had and cursor["tree_size"] == root().size()

    ckpt2 = SearchCheckpoint(ckdir)
    second = HashBench()
    bench2 = stack(second, ckpt2)
    assert ckpt2.restore_into(bench2, spmv_graph()) == 9
    res = explore(spmv_graph(), Platform.make_n_lanes(2), bench2,
                  MctsOpts(**opts, prefetch=Hints(2), checkpoint=ckpt2),
                  strategy=strategy)
    assert not set(first.measured) & set(second.measured)
    assert [(schedule_id(s.order), s.result.pct50) for s in res.sims] == \
        [(schedule_id(s.order), s.result.pct50) for s in ref.sims]
    assert shape(root()) == ref_shape and res.tree_size == ref.tree_size


# -- through the driver -------------------------------------------------------


@pytest.mark.needs_pinned_host
@pytest.mark.parametrize("killed_after", [None, 8])
def test_the_driver_resumes_a_search_at_the_width_it_had(
        tmp_path, monkeypatch, capfd, killed_after):
    """``bench.py --checkpoint D`` with its default ``--prefetch-compiles
    2``, run to its end or killed at its ninth measurement, then
    ``--resume``: the pipeline is off under resume, and the tree search
    still draws two ahead, as the checkpoint says the first run did.  So it
    draws the first run's rollouts again, the journal answers every one it
    holds, and the search is that of a run never interrupted."""
    import json
    import re

    from tenzing_tpu.bench.benchmarker import EmpiricalBenchmarker
    from tenzing_tpu.bench.driver import DriverRequest, run
    from tenzing_tpu.utils import trap

    timer = HashBench()
    measured = []  # (schedule, floor) of every call that reached the device
    kill_at = [None]

    def benchmark(self, order, opts=None):
        if len(measured) == kill_at[0]:
            trap.run_callbacks()  # what the real SIGINT handler does
            raise KeyboardInterrupt
        measured.append((schedule_id(order), opts.n_iters, opts.target_secs))
        return timer.benchmark(order, opts)

    monkeypatch.setattr(EmpiricalBenchmarker, "benchmark", benchmark)

    def drive(ckdir, resume=False):
        """The tree search's own lines of the driver's stderr: rollouts,
        tree and best, and the cache's hits and misses at its end."""
        run(DriverRequest(smoke=True, workload="spmv", mcts_iters=12,
                          checkpoint=str(ckdir), resume=resume))
        err = capfd.readouterr().err
        search = re.search(r"^mcts wall \d+s, (.*)$", err, re.M).group(1)
        cache = re.search(r"^bench cache: (\d+) hits / (\d+) misses; "
                          r"compiled programs: (\d+)", err, re.M)
        return (search,) + tuple(int(g) for g in cache.groups())

    ckdir = tmp_path / "ckpt"
    if killed_after is None:
        ref = drive(ckdir)
    else:
        ref = drive(tmp_path / "never_killed")
        del measured[:]
        kill_at[0] = killed_after
        with pytest.raises(KeyboardInterrupt):
            drive(ckdir)
        kill_at[0] = None
        assert len(measured) == killed_after
    with open(SearchCheckpoint(str(ckdir)).journal_path) as f:
        journaled = [json.loads(line) for line in f]
    assert SearchCheckpoint(str(ckdir)).load_state()["lookahead"] == 2
    first = set(measured)
    del measured[:]

    search, hits, misses, compiled = drive(ckdir, resume=True)
    assert search == ref[0]
    assert hits >= len(journaled) and hits + misses == ref[1] + ref[2]
    assert not first & set(measured)
    if not misses:
        assert compiled == 0
    assert SearchCheckpoint(str(ckdir)).load_state()["lookahead"] == 2
