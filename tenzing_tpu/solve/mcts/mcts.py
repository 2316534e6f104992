"""MCTS search driver.

Parity target: reference ``tenzing-mcts/include/tenzing/mcts/mcts.hpp``
``explore`` (mcts.hpp:154-327): per iteration — select (rank 0), expand, random
rollout to a complete schedule, ``remove_redundant_syncs``, broadcast the order
to all hosts, provision events, benchmark on every host, backprop (rank 0),
periodic graphviz tree dump with decaying cadence (mcts.hpp:52-127,302-309),
phase counters (counters.hpp), stop when the root is fully visited
(mcts.hpp:194-201) — broadcast via the control plane's stop protocol.

Beyond the reference: given a compile prefetcher (``MctsOpts.prefetch``),
rank 0 draws its next rollouts before the last one is measured — a short
queue of drawn, hinted, unmeasured schedules, each a pending visit on its
path (``node.py``) — so that their first calls run behind the measurement in
hand.  Rollouts are measured in the order drawn.
"""

from __future__ import annotations

import random as _random
import sys
from collections import deque
from dataclasses import dataclass, field
from typing import List, Optional, Tuple, Type

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    BenchResult,
    CachingBenchmarker,
    candidate_failed,
    result_row,
    schedule_id,
)
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.schedule import remove_redundant_syncs
from tenzing_tpu.core.sequence import Sequence, canonical_key
from tenzing_tpu.core.serdes import sequence_from_json, sequence_to_json
from tenzing_tpu.core.state import State
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.progress import get_reporter
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.parallel.control_plane import ControlPlane, default_control_plane
from tenzing_tpu.solve.mcts.node import Node
from tenzing_tpu.solve.mcts.strategies import FastMin
from tenzing_tpu.utils import trap
from tenzing_tpu.utils.counters import Counters


@dataclass
class MctsOpts:
    """reference mcts::Opts (mcts.hpp:42-50)."""

    n_iters: int = 300
    bench_opts: BenchOpts = field(default_factory=BenchOpts)
    # multi-fidelity split (reference Benchmark::Opts knob, benchmarker.hpp:
    # 24-30 — the knob existed, the policy didn't): when ``screen_opts`` is
    # set, every rollout is measured at that CHEAP floor (search-time numbers
    # only steer the tree), and after the loop the ``confirm_topk`` best
    # distinct schedules are re-measured at the full ``bench_opts`` floor —
    # so the solver's official output carries final-fidelity numbers while
    # the tree explores at a fraction of the measurement cost (VERDICT r4
    # item 2: 40 rollouts in 93 s was 99.8% BENCHMARK)
    screen_opts: Optional[BenchOpts] = None
    confirm_topk: int = 6
    # informed playouts (Node.get_rollout): complete each rollout with this
    # ``(state, decisions) -> decision`` policy instead of uniform random,
    # taking a random decision with probability ``rollout_eps`` per step.
    # None = the reference's uniform-random playout.
    rollout_policy: Optional[object] = None
    rollout_eps: float = 0.15
    expand_rollout: bool = False
    dump_tree: bool = False
    dump_tree_prefix: str = "mcts_tree"
    dump_csv_path: Optional[str] = None
    seed: int = 0
    # equivalence-keyed benchmark cache: different rollouts that reduce (after
    # remove_redundant_syncs) to already-timed schedules reuse the recorded
    # result instead of recompiling and re-running (VERDICT r1 weak #5)
    cache_benchmarks: bool = True
    # fault.checkpoint.SearchCheckpoint: when set, rank 0 snapshots the
    # solver cursor (iteration, sims, tree size) after every iteration and
    # the trap handler writes a final snapshot — resume re-executes the
    # deterministic search against the journal-restored benchmark cache,
    # reconstructing the tree exactly (docs/robustness.md)
    checkpoint: Optional[object] = None
    # independent soundness gate (verify.ScheduleVerifier): every rollout —
    # i.e. the output of EventSynchronizer-driven construction PLUS
    # remove_redundant_syncs — is verified before it is benchmarked; an
    # unsound schedule is rejected like a failed compile (penalty backprop,
    # negative-cached) and a ``verify.unsound`` event lands in the trace.
    # Deterministic and device-free, so identical on every rank.
    verify: Optional[object] = None
    # compile prefetcher (bench.pipeline.PrefetchingBenchmarker): the search
    # hints it the seed queue up front, every rollout as it is drawn, and
    # the confirm queue before the sequential confirm loop, and the hinted
    # first calls run in the background behind the measurement in hand.
    # With a prefetcher rank 0 draws ahead: before it measures a rollout it
    # has drawn (and hinted) that one and ``prefetch.workers`` more, each
    # drawn against the tree as it stands with the unmeasured ones counted
    # as pending visits (node.py).  So a prefetcher is NOT bit-identical to
    # prefetch-off here, as it is for DFS and the hill climb: the search is
    # bit-identical to the same search against a prefetcher of the same
    # ``workers`` whose compiles do nothing, or finish at any other time.
    # What the prefetcher does never reaches the search, only its width
    # does: a resumed search has to be given the width it had (the driver
    # records it in the checkpoint), and a drawn rollout that the caching
    # layer already answers is not hinted.  Every strategy supports it: a
    # pending, unmeasured child scores a neutral 0.0 (Node.select).  None
    # (the default), or a prefetcher without ``workers``, draws each
    # rollout after the last one's backprop: the reference's loop.
    prefetch: Optional[object] = None
    # disjoint fleet sharding ``(k, n)`` (search/fleet.py): restrict the
    # search to the k-th of n slices of the root's top-level children —
    # the enumeration is deterministic (Node.ensure_children sorts by
    # decision key), so n workers agree on the partition from their rank
    # alone, with no exchange.  An empty slice falls back to the single
    # child ``k % len`` so every worker always has a subtree.  None (the
    # default) searches the whole tree — bit-identical to pre-fleet.
    subtree: Optional[Tuple[int, int]] = None

    def to_json(self) -> dict:
        return {
            "n_iters": self.n_iters,
            "expand_rollout": self.expand_rollout,
            "seed": self.seed,
            "cache_benchmarks": self.cache_benchmarks,
        }


@dataclass
class SimResult:
    order: Sequence
    result: BenchResult
    # which measurement floor produced ``result``: "full" (bench_opts) or
    # "screen" (the cheap multi-fidelity floor) — recorded per CSV row so the
    # recorded-search databases stay honest about measurement regime
    fidelity: str = "full"


@dataclass
class MctsResult:
    sims: List[SimResult] = field(default_factory=list)
    tree_size: int = 0
    counters: Optional[Counters] = None

    def dump_csv(self, path: Optional[str] = None) -> str:
        rows = [
            # "full" rows keep the legacy 7+ops format; only screened rows
            # carry the explicit fidelity cell.  Numbered from 1: row 0 is
            # reserved for the naive-at-final-fidelity anchor (bench.py
            # --dump-csv), which a solver-internal dump does not have —
            # anchor readers then treat these files as anchorless
            result_row(i, s.result, s.order,
                       fidelity=None if s.fidelity == "full" else s.fidelity)
            for i, s in enumerate(self.sims, start=1)
        ]
        text = "\n".join(rows) + ("\n" if rows else "")
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def best(self) -> Optional[SimResult]:
        if not self.sims:
            return None
        return min(self.sims, key=lambda s: s.result.pct10)


@dataclass
class _Drawn:
    """A rollout drawn and not yet measured: an entry of rank 0's queue."""

    endpoint: Node
    order: Sequence
    grew: list  # ``(node, children it had)`` as drawn, for ``take_back``
    n_grown: int  # nodes it added to the tree
    seeded: bool
    selected: Optional[str]  # the expanded child's decision, for the trace


def _dump_cadence(it: int) -> bool:
    """Decaying dump cadence (reference mcts.hpp:302-309): every iteration up to
    10, then every 10th up to 100, then every 100th."""
    if it < 10:
        return True
    if it < 100:
        return it % 10 == 0
    return it % 100 == 0


def _materialize_seed(root: Node, path, grew: Optional[list] = None) -> tuple:
    """Walk ``path`` (a decision list from ``solve.local.drive``) down the
    tree, creating ONLY the matching child per step (siblings are left for
    ``ensure_children`` to fill lazily when UCT actually visits the node — a
    ~100-decision path with eager sibling expansion would allocate thousands
    of never-selected Node/State clones); returns (deepest matched node, the
    terminal state reached by applying the FULL path).  Decisions match by
    content key — the same mechanism the hill-climb's neighbor replay uses —
    so a path recorded on an independent State chain of the same graph lands
    on the same tree nodes.  ``grew`` as in ``Node.ensure_children``."""
    node, st = root, root.state
    matched = True
    for d in path:
        st = st.apply(d)
        if matched:
            nxt = next(
                (c for c in node.children
                 if c.decision is not None and c.decision.key() == d.key()),
                None,
            )
            if nxt is None and not node.expanded_ and not node.is_terminal():
                # pre-create just this child; expanded_ stays False so the
                # node's remaining decisions enumerate on first real visit
                nxt = Node(st, node.strategy, d, node)
                if grew is not None:
                    grew.append((node, len(node.children)))
                node.children.append(nxt)
            if nxt is None:
                matched = False
            else:
                node = nxt
    return node, st


def _seed_orders(graph: Graph, seeds, limit: int) -> list:
    """The terminal schedules of the first ``limit`` seed paths — known
    before the first iteration, so their compiles can prefetch while the
    incumbent measurements run.  Pure replay on fresh States (the same
    ``st.apply`` walk ``_materialize_seed`` performs): no tree, no RNG.
    ``limit`` (the prefetcher's queue bound) caps the replay work: hints
    beyond the queue would be dropped anyway, so materializing them is
    O(path_len) State.apply calls for nothing."""
    orders = []
    for path in seeds:
        if len(orders) >= limit:
            break
        st = State(graph)
        for d in path:
            st = st.apply(d)
        if st.is_terminal():
            orders.append(st.sequence)
    return orders


def prune_to_subtree(root: Node, platform, subtree: Tuple[int, int]) -> None:
    """Restrict ``root`` to the k-th of n rank-agreed top-level slices
    (``MctsOpts.subtree``): expand the root's children — a deterministic
    enumeration, identical in every process — and keep indices
    ``i % n == k % n``.  An empty slice degrades to the single child
    ``k % len(children)`` so a worker never ends up with nothing to
    search.  The kept children and everything below them are untouched:
    UCT statistics, seeds landing inside the slice, and the stop protocol
    all behave exactly as in a whole-tree search."""
    k, n = int(subtree[0]), max(1, int(subtree[1]))
    root.ensure_children(platform)
    kids = root.children
    if not kids:
        return
    keep = [c for i, c in enumerate(kids) if i % n == k % n]
    root.children = keep if keep else [kids[k % len(kids)]]


def explore(
    graph: Graph,
    platform,
    benchmarker,
    opts: Optional[MctsOpts] = None,
    strategy: Optional[Type] = None,
    control_plane: Optional[ControlPlane] = None,
    seeds=None,
) -> MctsResult:
    """Run the MCTS search (reference mcts::explore, mcts.hpp:154-327).

    ``seeds`` (optional): decision paths (e.g. recorded by
    ``solve.local.drive`` over heuristic incumbent policies) consumed as the
    FIRST iterations — each is materialized as a tree path, benchmarked like
    any rollout (usually a cache hit when the incumbent was pre-benchmarked),
    and backpropagated, warm-starting the selection statistics so UCT descends
    near known-good prefixes instead of re-discovering them from scratch
    (VERDICT r3 item 1).  Seeds ride the normal stop/schedule broadcast, so
    the multi-host protocol is unchanged."""
    opts = opts if opts is not None else MctsOpts()
    strategy = strategy if strategy is not None else FastMin
    cp = control_plane if control_plane is not None else default_control_plane()
    tr = get_tracer()
    tr.set_rank(cp.rank())
    reporter = get_reporter()
    rng = _random.Random(opts.seed)
    counters = Counters(prefix="mcts.phase")
    result = MctsResult(counters=counters)
    if opts.cache_benchmarks and not isinstance(benchmarker, CachingBenchmarker):
        # cache locally on every host: the broadcast order is identical on all
        # hosts, so hits/misses agree rank-to-rank (no divergent collectives)
        benchmarker = CachingBenchmarker(benchmarker)
    # a rank-coherent benchmarker (fault.resilient.ResilientBenchmarker, or
    # any wrapper forwarding its flag) guarantees every rank sees the same
    # failure at the same point, so the reject path is safe under a
    # multi-host control plane too — without it, a rank-local failure must
    # crash rather than desync the per-measurement barrier protocol
    reject_ok = cp.size() == 1 or getattr(benchmarker, "rank_coherent", False)

    def dump_partial():  # reference mcts.hpp:174-179
        if opts.dump_csv_path:
            result.dump_csv(opts.dump_csv_path)
        else:
            sys.stdout.write(result.dump_csv())
        if opts.checkpoint is not None and cp.rank() == 0:
            # the SIGINT final snapshot (ISSUE 3): the journal already holds
            # every completed measurement; this stamps the cursor so resume
            # tooling can report how far the interrupted run got
            opts.checkpoint.save_state(
                mcts={"n_sims": len(result.sims), "interrupted": True})

    reg = get_metrics()
    root: Optional[Node] = None
    # rank 0's lookahead: rollouts drawn, hinted and not yet measured, oldest
    # first, each a pending visit on its path.  Its width is the
    # prefetcher's: the rollout in hand and one more for every worker that
    # could be compiling behind it
    queue: deque = deque()
    ahead = (getattr(opts.prefetch, "workers", 0)
             if opts.prefetch is not None else 0)
    trap.register_handler(dump_partial)
    # manual enter/exit (not `with`): the finally below must set the
    # run-total attrs on every exit path, including the mid-block return
    explore_ctx = tr.span("mcts.explore", n_iters=opts.n_iters,
                          seed=opts.seed)
    explore_sp = explore_ctx.__enter__()
    try:
        ctx = strategy.Context(seed=opts.seed)
        root = Node(State(graph), strategy) if cp.rank() == 0 else None
        if root is not None:
            ctx.root = root
            if opts.subtree is not None:
                prune_to_subtree(root, platform, opts.subtree)
        seed_iter = iter(seeds if seeds is not None else ())
        if opts.prefetch is not None and cp.rank() == 0 and seeds:
            # the seed queue's terminal schedules are known now; compile
            # them in the background while the first iterations measure
            opts.prefetch.prefetch(_seed_orders(
                graph, seeds, getattr(opts.prefetch, "depth", 8)))
        failed_keys: set = set()  # negative cache for uncompilable schedules
        ropts = opts.screen_opts if opts.screen_opts is not None else (
            opts.bench_opts)

        def answered(order: Sequence) -> bool:
            """No first call lies ahead of ``order``: the caching layer (its
            journal-restored entries included) holds the result of an
            equivalent schedule.  Decides what is hinted, never what is
            drawn."""
            return (isinstance(benchmarker, CachingBenchmarker)
                    and benchmarker.has(order, ropts))

        def draw(grew: list) -> Optional[_Drawn]:
            """The next rollout against the tree as it stands, seed paths
            first; None when no further one can be drawn.  ``grew`` lists
            what it adds to the tree, also where it raises."""
            assert root is not None
            selected = None
            path = next(seed_iter, None)
            if path is not None:
                with counters.phase("SEED"):
                    endpoint, st = _materialize_seed(root, path, grew)
                    if not st.is_terminal():  # defensive: complete
                        _, order = endpoint.get_rollout(
                            platform, rng,
                            policy=opts.rollout_policy,
                            policy_eps=opts.rollout_eps,
                        )
                    else:
                        # benchmarked AS RECORDED (no redundant-sync
                        # cleanup): the cache key matches the incumbent's
                        # measurement exactly when the rollout opts do
                        # (with a multi-fidelity screen floor the seed is
                        # instead re-measured cheaply at that floor)
                        order = st.sequence
            elif root.closed():
                return None
            else:
                with counters.phase("SELECT"):
                    leaf = root.select(ctx, platform, rng, grew)
                with counters.phase("EXPAND"):
                    child = leaf.expand(platform, rng, grew)
                with counters.phase("ROLLOUT"):
                    endpoint, order = child.get_rollout(
                        platform, rng, opts.expand_rollout,
                        policy=opts.rollout_policy,
                        policy_eps=opts.rollout_eps, grew=grew,
                    )
                with counters.phase("REDUNDANT_SYNC"):
                    order = remove_redundant_syncs(order)
                if tr.enabled and child.decision is not None:
                    selected = child.decision.desc()
            endpoint.mark_pending()
            return _Drawn(endpoint, order, grew,
                          sum(len(n.children) - had for n, had in grew),
                          path is not None, selected)

        def settle(res: BenchResult) -> None:
            """Rank 0: the head of the queue is measured (or refused and
            given its penalty): its pending visits become real ones."""
            with counters.phase("BACKPROP"):
                queue.popleft().endpoint.backprop(ctx, res, pending=True)

        def measured_size() -> int:
            """The tree without what the queued draws added to it."""
            return root.size() - sum(d.n_grown for d in queue)

        for it in range(opts.n_iters):
            # per-iteration span (ISSUE 1): which node/path was selected,
            # the rolled-out schedule's hash, the measured time and the tree
            # size — the phase spans (mcts.phase.*) nest inside it
            with tr.span("mcts.iter", it=it) as it_sp:
                order: Optional[Sequence] = None
                if cp.rank() == 0:
                    fresh = []
                    while (len(queue) <= ahead
                           and it + len(queue) < opts.n_iters):
                        grew: list = []
                        try:
                            drawn = draw(grew)
                        except BaseException:
                            Node.ungrow(grew)  # a failed draw leaves no nodes
                            raise
                        if drawn is None:
                            break
                        if queue:
                            reg.counter("mcts.lookahead.drawn").inc()
                        queue.append(drawn)
                        if (opts.prefetch is not None
                                and not answered(drawn.order)):
                            fresh.append(drawn.order)
                    if fresh:
                        # their first calls start now, behind the
                        # measurements ahead of them in the queue
                        opts.prefetch.prefetch(fresh)
                    if queue:
                        head = queue[0]
                        order = head.order
                        reg.gauge("mcts.lookahead.pending").set(
                            len(queue) - 1)
                        if head.seeded:
                            it_sp.set("seeded", True)
                        if head.selected is not None:
                            it_sp.set("selected", head.selected)
                # stop-flag + schedule broadcast (mcts.hpp:129-152,244)
                with counters.phase("BCAST"):
                    # stop: the tree has no rollout left, drawn or to draw
                    if cp.bcast_json(cp.rank() == 0 and order is None):
                        break
                    payload = cp.bcast_json(
                        sequence_to_json(order) if cp.rank() == 0 else None
                    )
                    if cp.rank() != 0:
                        order = sequence_from_json(payload, graph)
                # event provisioning (reference mcts.hpp:247-270)
                events = []
                for op in order:
                    if hasattr(op, "events"):
                        events.extend(op.events())
                platform.provision_events(events)
                key = canonical_key(order)
                if tr.enabled:
                    it_sp.set("schedule", schedule_id(order))
                res: Optional[BenchResult] = None
                if key not in failed_keys and opts.verify is not None:
                    verdict = opts.verify(order)
                    if not verdict.ok:
                        from tenzing_tpu.verify.soundness import report_unsound

                        report_unsound("mcts.rollout", order, verdict)
                        reporter.warn(
                            "mcts: rollout rejected by the soundness "
                            f"verifier ({verdict.witness()})", it=it)
                        it_sp.set("unsound", True)
                        failed_keys.add(key)
                if key not in failed_keys:
                    with counters.phase("BENCHMARK"):
                        try:
                            res = benchmarker.benchmark(order, ropts)
                        except Exception as e:
                            # a rollout whose schedule cannot compile/run on
                            # the hardware (e.g. liveness exceeding device
                            # memory) is a legitimate dead end, not a search
                            # crash.  Safe single-host, and multi-host when
                            # the benchmarker is rank-coherent (its agreement
                            # protocol made every rank fail together);
                            # otherwise a rank-local failure would desync the
                            # per-measurement barrier/allreduce protocol, so
                            # there the error must propagate (a crash beats a
                            # collective deadlock).  Device loss is never a
                            # per-candidate verdict: without a degradation
                            # fallback it must escalate out of the search.
                            from tenzing_tpu.fault.errors import DeviceLostError

                            if not reject_ok or isinstance(e, DeviceLostError):
                                raise
                            candidate_failed("mcts.rollout", order, e)
                            reporter.warn(
                                "mcts: rollout rejected (failed to compile/"
                                f"run: {type(e).__name__}: {str(e)[:200]})",
                                it=it,
                            )
                            failed_keys.add(key)
                if res is None:
                    # negative-cached or fresh failure: backprop a penalty
                    # (2x the worst time seen) so the tree learns to avoid
                    # the region without re-paying the failing compile; no
                    # sim is recorded (no fake measurements in the result
                    # set)
                    it_sp.set("rejected", True)
                    worst = max(
                        (s.result.pct50 for s in result.sims), default=1.0
                    )
                    if cp.rank() == 0:
                        settle(BenchResult.from_times([2.0 * worst]))
                    continue
                fidelity = ("screen" if opts.screen_opts is not None
                            else "full")
                if tr.enabled:
                    it_sp.set("pct50", res.pct50)
                    it_sp.set("fidelity", fidelity)
                result.sims.append(SimResult(
                    order=order, result=res, fidelity=fidelity,
                ))
                if cp.rank() == 0:
                    settle(res)
                    if tr.enabled:
                        it_sp.set("tree_size", measured_size())
                    if opts.dump_tree and _dump_cadence(it):
                        path = f"{opts.dump_tree_prefix}_{it:06d}.dot"
                        with open(path, "w") as f:
                            f.write(root.dump_graphviz())
                    if opts.checkpoint is not None:
                        # cursor snapshot per completed iteration: the tree
                        # itself reconstructs on resume by re-executing the
                        # seeded search against the journal-restored cache
                        # (every answer identical, zero device time), so the
                        # checkpoint only needs the generative cursor
                        opts.checkpoint.save_state(
                            mcts={"it": it, "n_sims": len(result.sims),
                                  "tree_size": measured_size()})
        # multi-fidelity confirm: the top-k distinct screened schedules are
        # re-measured at the full bench_opts floor so the solver's official
        # output carries final-fidelity numbers (the CachingBenchmarker key
        # includes the opts, so this cannot be answered from the screen
        # cache).  Rides the same broadcast protocol as rollouts — every
        # rank benchmarks every finalist.
        if opts.screen_opts is not None and result.sims:
            finals: List[Sequence] = []
            if cp.rank() == 0:
                seen_keys: set = set()
                for s in sorted(result.sims, key=lambda s: s.result.pct50):
                    k = canonical_key(s.order)
                    if k in seen_keys:
                        continue
                    seen_keys.add(k)
                    finals.append(s.order)
                    if len(finals) >= opts.confirm_topk:
                        break
                if opts.prefetch is not None:
                    # confirm-queue lookahead: finalists usually hit the
                    # program cache (they were measured during the search),
                    # but a resumed run's journal-answered rollouts never
                    # compiled — prefetch covers exactly that gap
                    opts.prefetch.prefetch(finals)
            with counters.phase("BCAST"):
                n_finals = cp.bcast_json(
                    len(finals) if cp.rank() == 0 else None)
            for fi in range(n_finals):
                with counters.phase("BCAST"):
                    payload = cp.bcast_json(
                        sequence_to_json(finals[fi]) if cp.rank() == 0
                        else None)
                order = (finals[fi] if cp.rank() == 0
                         else sequence_from_json(payload, graph))
                events = []
                for op in order:
                    if hasattr(op, "events"):
                        events.extend(op.events())
                platform.provision_events(events)
                with counters.phase("CONFIRM"):
                    try:
                        res = benchmarker.benchmark(order, opts.bench_opts)
                    except Exception as e:
                        from tenzing_tpu.fault.errors import DeviceLostError

                        if not reject_ok or isinstance(e, DeviceLostError):
                            raise
                        candidate_failed("mcts.confirm", order, e)
                        reporter.warn(
                            "mcts: confirm rejected (failed to compile/run: "
                            f"{type(e).__name__}: {str(e)[:200]})",
                            finalist=fi,
                        )
                        continue
                result.sims.append(
                    SimResult(order=order, result=res, fidelity="full"))
        if opts.dump_csv_path and cp.rank() == 0:
            result.dump_csv(opts.dump_csv_path)
        return result
    finally:
        # whatever ended the loop (the iterations, a closed tree, the
        # harness's deadline or any other exception out of a measurement):
        # rollouts still queued were never measured, so their pending visits
        # and their nodes go, newest first, and the tree that is counted
        # describes measured rollouts alone
        reg.counter("mcts.lookahead.dropped").inc(len(queue))
        while queue:
            dropped = queue.pop()
            dropped.endpoint.take_back(dropped.grew)
        if root is not None:
            result.tree_size = root.size()
        explore_sp.set("n_sims", len(result.sims))
        explore_sp.set("tree_size", result.tree_size)
        explore_ctx.__exit__(None, None, None)
        trap.unregister_handler(dump_partial)
