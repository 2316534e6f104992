"""Exhaustive depth-first schedule enumeration + benchmarking.

Parity target: reference ``tenzing-dfs`` (dfs.hpp/dfs.cpp): ``get_all_sequences``
is a worklist DFS over ``State.frontier`` with equivalence-class dedup at each
expansion (dfs.cpp:16-82); ``explore`` enumerates on rank 0, dedups completed
sequences pairwise under resource bijection (dfs.hpp:88-113), broadcasts each
schedule to all hosts (stop-flag + schedule, dfs.hpp:50-70,145-167), benchmarks
it, and collects results; SIGINT dumps the partial CSV (dfs.hpp:118-122).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    BenchResult,
    result_row,
    schedule_id,
)
from tenzing_tpu.core import sequence as sequence_mod
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.serdes import sequence_from_json, sequence_to_json
from tenzing_tpu.core.state import State
from tenzing_tpu.obs.progress import get_reporter
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.parallel.control_plane import ControlPlane, default_control_plane
from tenzing_tpu.utils import trap
from tenzing_tpu.utils.counters import Counters


@dataclass
class DfsOpts:
    """reference dfs::Opts (dfs.hpp:30-40; maxSeqs cap from examples/spmv.cu:117).

    ``batch=True`` benchmarks the whole enumerated set through
    ``benchmark_batch_times`` — every schedule visited once per iteration in a
    fresh random order (reference batch benchmark, benchmarker.cpp:21-76) — so
    slow system drift decorrelates from schedule identity and cross-schedule
    comparisons in the dumped database are honest.  Falls back to one-at-a-time
    benchmarking when the benchmarker has no ``benchmark_batch_times`` (e.g.
    CSV replay) or under a multi-host control plane (the batch path is
    single-host).

    ``prescreen`` (a ``learn.surrogate.SurrogateBenchmarker``) with
    ``prescreen_keep > 0`` ranks the enumerated terminals by predicted time
    and benchmarks only the best ``prescreen_keep`` — exhaustive enumeration
    with learned triage of the measurement budget (the skipped count lands
    in the ``learn.prune.dfs_skipped`` counter and the explore span)."""

    max_seqs: int = 15000
    bench_opts: BenchOpts = field(default_factory=BenchOpts)
    dump_csv_path: Optional[str] = None
    batch: bool = False
    batch_seed: int = 0
    prescreen: Optional[object] = None  # learn SurrogateBenchmarker
    prescreen_keep: int = 0
    # fault.checkpoint.SearchCheckpoint: rank 0 snapshots the frontier
    # cursor (next un-benchmarked terminal index) per measurement; resume
    # re-enumerates (deterministic) and the journal-restored cache answers
    # every already-measured terminal instantly (docs/robustness.md)
    checkpoint: Optional[object] = None
    # independent soundness gate (verify.ScheduleVerifier): every
    # enumerated terminal is verified before it is benchmarked; unsound
    # terminals are rejected with a ``verify.unsound`` event instead of
    # being measured (docs/robustness.md, "Schedule soundness")
    verify: Optional[object] = None
    # compile prefetcher (bench.pipeline.PrefetchingBenchmarker): the next
    # ``prefetch_lookahead`` terminals of the enumerated frontier are hinted
    # each iteration, so terminal i+1 compiles in the background while
    # terminal i measures (the batch path needs no hint here — a prefetcher
    # in the benchmark stack prefetches the whole batch itself).  Hints are
    # advisory; None (the default) is bit-identical to today.
    prefetch: Optional[object] = None
    prefetch_lookahead: int = 4
    # disjoint fleet sharding ``(k, n)`` (search/fleet.py): after
    # enumeration (+ prescreen), keep only terminals ``k % n, k % n + n,
    # ...`` of the deterministic enumeration order — n workers agree on
    # the partition from their rank alone, and the union of all n slices
    # is exactly the un-sharded terminal set.  An empty slice degrades to
    # the single terminal ``k % len`` so a worker always measures
    # something.  None (the default) is bit-identical to pre-fleet.
    subtree: Optional[tuple] = None

    def to_json(self) -> dict:
        """Provenance stamp of the options (reference dfs.cpp:11-14)."""
        return {"max_seqs": self.max_seqs, "n_iters": self.bench_opts.n_iters,
                "batch": self.batch, "batch_seed": self.batch_seed}


@dataclass
class SimResult:
    """One benchmarked schedule (reference SimResult, dfs.hpp:20-28)."""

    order: Sequence
    result: BenchResult


@dataclass
class DfsResult:
    """reference dfs::Result (dfs.hpp:74-76, dump_csv dfs.cpp:84-105)."""

    sims: List[SimResult] = field(default_factory=list)
    # phase-timing attribution (SELECT / DEDUP / BENCHMARK / BCAST) — the
    # MCTS result has carried this since the seed; DFS search time was
    # unattributable (ISSUE 1 satellite)
    counters: Optional[Counters] = None

    def dump_csv(self, path: Optional[str] = None) -> str:
        # numbered from 1: row index 0 is reserved for "the naive schedule
        # at final fidelity" (the bench.py --dump-csv anchor invariant) and
        # a solver-internal dump has no naive anchor — starting at 1 makes
        # anchor readers (recorded.naive_anchor_of, learn/dataset.py) treat
        # these files as anchorless instead of silently anchoring every
        # in-file ratio to an arbitrary first-enumerated terminal
        rows = [result_row(i, s.result, s.order)
                for i, s in enumerate(self.sims, start=1)]
        text = "\n".join(rows) + ("\n" if rows else "")
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def best(self) -> Optional[SimResult]:
        if not self.sims:
            return None
        return min(self.sims, key=lambda s: s.result.pct10)


def _dfs_terminals(
    graph: Graph, platform, max_seqs: int, dedup_terminals: bool,
    counters: Optional[Counters] = None,
) -> List[State]:
    """Worklist DFS over ``State.frontier`` (reference get_all_sequences,
    dfs.cpp:16-82; the per-expansion dedup is dfs.cpp:46-58).  With
    ``dedup_terminals`` the cap counts bijection-unique terminals, recognized
    by O(1) ``canonical_key`` lookups (equivalent to the reference's pairwise
    bijection scan — canonical keys are equal iff a lane/event bijection
    exists; agreement is property-tested in tests/test_dedup_canonical.py).

    ``counters`` attributes the walk per node: frontier expansion under
    SELECT, canonical-key dedup under DEDUP (spanless — a tracer span per
    node would flood the trace; the aggregate lands in the metrics)."""
    c = counters if counters is not None else Counters(mirror_global=False)
    terminals: List[State] = []
    seen_keys: set = set()
    stack: List[State] = [State(graph)]
    while stack and len(terminals) < max_seqs:
        st = stack.pop()
        if st.is_terminal():
            if dedup_terminals:
                with c.phase("DEDUP", span=False):
                    key = sequence_mod.canonical_key(st.sequence)
                    dup = key in seen_keys
                    seen_keys.add(key)
                if dup:
                    continue
            terminals.append(st)
            continue
        with c.phase("SELECT", span=False):
            stack.extend(st.frontier(platform))
    return terminals


def get_all_sequences(
    graph: Graph, platform, max_seqs: int = 15000,
    counters: Optional[Counters] = None,
) -> List[State]:
    """All complete schedules reachable from the initial state (terminal
    duplicates across converging DFS paths included; ``max_seqs`` caps raw
    terminals)."""
    return _dfs_terminals(graph, platform, max_seqs, dedup_terminals=False,
                          counters=counters)


def get_unique_sequences(
    graph: Graph, platform, max_seqs: int = 15000,
    counters: Optional[Counters] = None,
) -> List[State]:
    """Like :func:`get_all_sequences`, but terminals are deduplicated under
    resource bijection *as they are found* and ``max_seqs`` counts unique
    terminals."""
    return _dfs_terminals(graph, platform, max_seqs, dedup_terminals=True,
                          counters=counters)


def expand_all(graph: Graph) -> Graph:
    """Inline every CompoundOp.  An ExpandOp is the only decision available for
    a frontier compound and commutes with execution order, so eager expansion
    preserves the terminal-schedule space (reference state.cpp:82-87)."""
    while True:
        comps = [v for v in graph.vertices() if isinstance(v, CompoundOp)]
        if not comps:
            return graph
        graph = graph.clone_but_expand(comps[0])


def structural_variants(graph: Graph) -> List[Graph]:
    """All graphs reachable by compound expansion and choice substitution —
    the structural (graph-surgery) half of the decision space, taken eagerly so
    ``enumerate_schedules`` can share its budget fairly between them."""
    graph = expand_all(graph)
    choices = [v for v in graph.vertices() if isinstance(v, ChoiceOp)]
    if not choices:
        return [graph]
    out: List[Graph] = []
    for c in choices[0].choices():
        out.extend(structural_variants(graph.clone_but_replace(c, choices[0])))
    return out


def enumerate_schedules(graph: Graph, platform, max_seqs: int = 15000,
                        counters: Optional[Counters] = None) -> List[State]:
    """Terminal states with both per-expansion and terminal dedup applied.

    Structural decisions (compound expansion, implementation choices) are
    resolved eagerly into graph variants; each variant's order x lane space is
    walked by :func:`get_unique_sequences`.  The ``max_seqs`` budget is
    fair-shared across variants (a huge first variant must not starve the
    others out of the search entirely); unused share flows to later variants.
    *Deduplicated* terminals count against the cap."""
    reporter = get_reporter()
    tr = get_tracer()
    variants = structural_variants(graph)
    out: List[State] = []
    for k, g in enumerate(variants):
        remaining = max_seqs - len(out)
        if remaining <= 0:
            reporter.warn(
                f"tenzing-tpu: dfs budget exhausted; {len(variants) - k} structural "
                "variant(s) not enumerated (raise max_seqs)",
                variants_left=len(variants) - k, max_seqs=max_seqs,
            )
            break
        share = -(-remaining // (len(variants) - k))  # ceil fair share
        with tr.span("dfs.enumerate_variant", variant=k, share=share) as sp:
            # the walk attributes itself per node: SELECT and DEDUP
            found = get_unique_sequences(g, platform, share,
                                         counters=counters)
            sp.set("n_terminals", len(found))
        truncated = len(found) >= share
        if truncated and k + 1 < len(variants):
            reporter.warn(
                f"tenzing-tpu: dfs variant {k} truncated at its fair share "
                f"({share} schedules)",
                variant=k, share=share,
            )
        out.extend(found)
    return out


def _dedup_terminal_states(states: List[State]) -> List[State]:
    """Dedup of completed schedules under resource bijection (reference
    dfs.hpp:88-113) — by O(1) ``canonical_key`` bucket instead of the
    reference's O(n^2) pairwise bijection scan (equivalent by the canonical-key
    theorem, core/sequence.py; property-tested in
    tests/test_dedup_canonical.py)."""
    uniq: List[State] = []
    seen: set = set()
    for s in states:
        key = sequence_mod.canonical_key(s.sequence)
        if key not in seen:
            seen.add(key)
            uniq.append(s)
    return uniq


def explore(
    graph: Graph,
    platform,
    benchmarker,
    opts: Optional[DfsOpts] = None,
    control_plane: Optional[ControlPlane] = None,
) -> DfsResult:
    """Enumerate, dedup, benchmark every schedule (reference dfs::explore,
    dfs.hpp:78-178)."""
    import sys

    opts = opts if opts is not None else DfsOpts()
    cp = control_plane if control_plane is not None else default_control_plane()
    tr = get_tracer()
    tr.set_rank(cp.rank())
    reporter = get_reporter()
    counters = Counters(prefix="dfs.phase")
    result = DfsResult(counters=counters)
    batch_partial: dict = {}  # orders + in-flight times for mid-batch dumps

    def dump_partial():  # reference dfs.hpp:118-122
        if not result.sims and batch_partial:
            # signal arrived mid-batch: synthesize results from the times
            # accumulated so far (benchmark_batch_times fills times_out in
            # place) so a wall-clock-limited batch run still emits data
            for order, ts in zip(batch_partial["orders"], batch_partial["times"]):
                if ts:
                    result.sims.append(
                        SimResult(order=order, result=BenchResult.from_times(ts))
                    )
        if opts.dump_csv_path:
            result.dump_csv(opts.dump_csv_path)
        else:
            sys.stdout.write(result.dump_csv())
        if opts.checkpoint is not None and cp.rank() == 0:
            opts.checkpoint.save_state(
                dfs={"n_sims": len(result.sims), "interrupted": True})

    trap.register_handler(dump_partial)
    try:
        with tr.span("dfs.explore", max_seqs=opts.max_seqs,
                     batch=opts.batch) as root_sp:
            if cp.rank() == 0:
                with tr.span("dfs.enumerate"):
                    states = enumerate_schedules(graph, platform,
                                                 opts.max_seqs,
                                                 counters=counters)
                if (opts.prescreen is not None and opts.prescreen_keep > 0
                        and len(states) > opts.prescreen_keep):
                    # learned triage: benchmark only the terminals the
                    # surrogate ranks in the money (stable sort keeps the
                    # enumeration order as the tiebreak, so equal
                    # predictions stay deterministic)
                    with tr.span("learn.prescreen", n_in=len(states),
                                 keep=opts.prescreen_keep):
                        ranked = sorted(
                            range(len(states)),
                            key=lambda i: opts.prescreen.predict(
                                states[i].sequence)[0],
                        )
                        skipped = len(states) - opts.prescreen_keep
                        states = [states[i]
                                  for i in ranked[:opts.prescreen_keep]]
                    from tenzing_tpu.obs.metrics import get_metrics

                    get_metrics().counter("learn.prune.dfs_skipped").inc(
                        skipped)
                    reporter.info(
                        f"tenzing-tpu: dfs prescreen kept "
                        f"{len(states)}/{len(states) + skipped} terminals",
                        kept=len(states), skipped=skipped,
                    )
                if opts.subtree is not None and states:
                    sk, sn = int(opts.subtree[0]), max(1, int(opts.subtree[1]))
                    sliced = states[sk % sn::sn]
                    states = sliced if sliced else [states[sk % len(states)]]
                n = len(states)
            else:
                states, n = [], 0
            with counters.phase("BCAST"):
                n = cp.bcast_json(n)  # stop-flag protocol (dfs.hpp:50-70)
            root_sp.set("n_schedules", n)
            batch_times_fn = getattr(benchmarker, "benchmark_batch_times", None)
            if opts.batch and (batch_times_fn is None or cp.size() != 1):
                if cp.rank() == 0:
                    why = (
                        "multi-host control plane"
                        if cp.size() != 1
                        else f"{type(benchmarker).__name__} has no benchmark_batch_times"
                    )
                    reporter.warn(
                        f"tenzing-tpu: dfs batch=True ignored ({why}); falling back "
                        "to one-at-a-time (correlated) benchmarking",
                        why=why,
                    )
            if opts.batch and batch_times_fn is not None and cp.size() == 1:
                orders = [st.sequence for st in states]
                if opts.verify is not None:
                    from tenzing_tpu.verify.soundness import report_unsound

                    kept = []
                    for o in orders:
                        verdict = opts.verify(o)
                        if verdict.ok:
                            kept.append(o)
                            continue
                        report_unsound("dfs.benchmark", o, verdict)
                        reporter.warn(
                            "tenzing-tpu: dfs terminal rejected by the "
                            f"soundness verifier ({verdict.witness()})")
                    orders = kept
                times: List[List[float]] = [[] for _ in orders]
                batch_partial.update(orders=orders, times=times)
                # no explicit hint here: a prefetcher sitting in the
                # benchmark stack already prefetches the whole batch as the
                # first statement of its benchmark_batch_times forward
                # (bench/pipeline.py) — a second hint would be dead weight
                with counters.phase("BENCHMARK"):
                    batch_times_fn(
                        orders, opts.bench_opts, seed=opts.batch_seed,
                        times_out=times
                    )
                for order, ts in zip(orders, times):
                    result.sims.append(
                        SimResult(order=order, result=BenchResult.from_times(ts))
                    )
                # only after the results are in result.sims: a signal landing
                # between clear() and the copy would otherwise dump an empty CSV
                # despite every measurement having completed (trap.py contract)
                batch_partial.clear()
                if opts.checkpoint is not None and cp.rank() == 0:
                    opts.checkpoint.save_state(
                        dfs={"batch_done": True, "n_sims": len(result.sims)})
            else:
                # reject policy mirrors MCTS: a terminal that fails to
                # compile/run is a dead end, not a search crash — safe
                # single-host, and multi-host when the benchmarker's
                # rank-coherent agreement made every rank fail together
                reject_ok = cp.size() == 1 or getattr(
                    benchmarker, "rank_coherent", False)
                for i in range(n):
                    with tr.span("dfs.iter", i=i) as sp:
                        if cp.rank() == 0:
                            st = states[i]
                            payload = sequence_to_json(st.sequence)
                            if opts.prefetch is not None:
                                # frontier slice: the next terminals are
                                # known — compile them while this one
                                # measures.  Re-offering the window each
                                # iteration is cheap (id dedup) and lets
                                # hints dropped at a full queue resubmit.
                                opts.prefetch.prefetch(
                                    [states[j].sequence for j in range(
                                        i + 1,
                                        min(n, i + 1 +
                                            opts.prefetch_lookahead))])
                        else:
                            st, payload = None, None
                        with counters.phase("BCAST"):
                            payload = cp.bcast_json(payload)
                        if cp.rank() == 0:
                            order = st.sequence
                        else:
                            order = sequence_from_json(payload, graph)
                        if opts.verify is not None:
                            verdict = opts.verify(order)
                            if not verdict.ok:
                                from tenzing_tpu.verify.soundness import (
                                    report_unsound,
                                )

                                # deterministic + device-free: every rank
                                # reaches the same verdict, so the coherent
                                # skip needs no agreement round
                                report_unsound("dfs.benchmark", order,
                                               verdict)
                                reporter.warn(
                                    "tenzing-tpu: dfs terminal rejected by "
                                    "the soundness verifier "
                                    f"({verdict.witness()})", i=i)
                                sp.set("unsound", True)
                                continue
                        with counters.phase("BENCHMARK"):
                            try:
                                res = benchmarker.benchmark(
                                    order, opts.bench_opts)
                            except Exception as e:
                                from tenzing_tpu.fault.errors import (
                                    DeviceLostError,
                                )

                                # device loss is fatal, not a candidate
                                # verdict (fault/resilient.py escalation)
                                if not reject_ok or isinstance(
                                        e, DeviceLostError):
                                    raise
                                from tenzing_tpu.bench.benchmarker import (
                                    candidate_failed,
                                )

                                candidate_failed("dfs.benchmark", order, e)
                                reporter.warn(
                                    "tenzing-tpu: dfs terminal rejected "
                                    f"(failed to compile/run: "
                                    f"{type(e).__name__}: {str(e)[:200]})",
                                    i=i,
                                )
                                sp.set("rejected", True)
                                continue
                        if tr.enabled:
                            sp.set("schedule", schedule_id(order))
                            sp.set("pct50", res.pct50)
                        result.sims.append(SimResult(order=order, result=res))
                    # throttled: the cursor is consistency metadata (resume
                    # reconstructs from the journal, which has its own
                    # per-measurement fsync) — an atomic rewrite per
                    # terminal would double the sync I/O of the hot loop
                    if opts.checkpoint is not None and cp.rank() == 0 and (
                            i % 25 == 0 or i == n - 1):
                        opts.checkpoint.save_state(
                            dfs={"i": i, "n": n,
                                 "n_sims": len(result.sims)})
            if opts.dump_csv_path and cp.rank() == 0:
                result.dump_csv(opts.dump_csv_path)
            return result
    finally:
        trap.unregister_handler(dump_partial)
