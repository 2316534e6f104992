"""Neighborhood search over schedules: hill-climbing in decision space.

Beyond the reference's two solvers (exhaustive DFS, MCTS): the measured
anytime driver showed hand-built greedy incumbents repeatedly winning the
paired final while MCTS rollouts — exploring the full space from scratch —
lagged.  This solver searches the *neighborhood of an incumbent* instead: a
schedule is represented by the decision list that builds it from
``State(graph)``; a neighbor substitutes ONE decision (a different lane
binding, implementation choice, or execution order pick) and completes the
rest by following the original plan where it still applies, falling back to
the phase policy where it does not.  First-improvement hill climbing under a
benchmark budget then refines the incumbent with measured steps — the classic
local-search complement to MCTS's global exploration, sharing the same SDP
machinery, benchmarkers, and caching as the other solvers.
"""

from __future__ import annotations

import random as _random
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence as Seq, Tuple

from tenzing_tpu.bench.benchmarker import (
    BenchOpts,
    candidate_failed,
    schedule_id,
)
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.state import (
    AssignLane,
    ChooseOp,
    Decision,
    ExecuteOp,
    ExpandOp,
    State,
)
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer


def phase_policy(platform, phases: Seq[str],
                 prefer: Optional[Callable[[str, List[str]], Optional[str]]] = None,
                 priority: Optional[Callable[[str], int]] = None):
    """A policy closure for :func:`drive`: expand compounds eagerly, resolve
    ChoiceOps via ``prefer(choice_op_name, choice_names) -> chosen name`` (or
    the first choice), round-robin lane bindings, and execute in ``phases``
    order with the sync-gating discipline of solve/greedy.py.

    ``priority`` (op name -> int) overrides the prefix-index phase of an op —
    finer-than-phase disciplines (e.g. the halo paired await/unpack interleave,
    models/halo_pipeline.paired_priority) express per-op orderings while
    reusing the same gating machinery."""
    from tenzing_tpu.core.sync_ops import SyncOp

    lane_rr = [0]

    def phase(op) -> int:
        name = op.name()
        if priority is not None:
            return priority(name)
        for i, p in enumerate(phases):
            if name.startswith(p):
                return i
        return 0

    def policy(st: State, ds: List[Decision]) -> Decision:
        expands = [d for d in ds if isinstance(d, ExpandOp)]
        if expands:
            return expands[0]
        chooses = [d for d in ds if isinstance(d, ChooseOp)]
        if chooses:
            grp = sorted(
                (d for d in chooses if d.op.name() == chooses[0].op.name()),
                key=lambda d: d.choice.name(),
            )
            if prefer is not None:
                want = prefer(grp[0].op.name(), [d.choice.name() for d in grp])
                pick = next((d for d in grp if d.choice.name() == want), None)
                if pick is not None:
                    return pick
            return grp[0]
        assigns = sorted(
            (d for d in ds if isinstance(d, AssignLane)), key=lambda d: d.op.name()
        )
        if assigns:
            opname = assigns[0].op.name()
            lane = platform.lanes[lane_rr[0] % len(platform.lanes)]
            lane_rr[0] += 1
            return next(
                (d for d in assigns if d.op.name() == opname and d.lane == lane),
                assigns[0],
            )
        execs = [d for d in ds if isinstance(d, ExecuteOp)]
        real = sorted(
            (d for d in execs if not isinstance(d.op, SyncOp)),
            key=lambda d: (phase(d.op), d.op.name()),
        )
        syncs = sorted(
            (d for d in execs if isinstance(d.op, SyncOp)), key=lambda d: d.op.desc()
        )
        done = {op.name() for op in st.sequence}
        pending_min = min(
            (phase(v) for v in st.graph.vertices() if v.name() not in done),
            default=99,
        )
        if real and (not syncs or phase(real[0].op) <= pending_min):
            return real[0]
        return syncs[0]

    return policy


def drive(graph: Graph, platform, policy) -> Tuple[Sequence, List[Decision]]:
    """Run ``policy`` to a terminal state, recording the decision list."""
    st = State(graph)
    decisions: List[Decision] = []
    while not st.is_terminal():
        ds = st.get_decisions(platform)
        d = policy(st, ds)
        decisions.append(d)
        st = st.apply(d)
    return st.sequence, decisions


def replay_with_substitution(
    graph: Graph, platform, decisions: List[Decision], i: int,
    alt: Decision, fallback,
) -> Tuple[Sequence, List[Decision]]:
    """The neighbor: apply ``decisions[:i]``, then ``alt`` instead of
    ``decisions[i]``, then complete by taking any still-offered decision from
    the original plan (earliest-planned first) and falling back to
    ``fallback`` when the plan no longer applies (e.g. after an
    implementation-choice flip invalidated downstream ops)."""
    st = State(graph)
    taken: List[Decision] = []
    for d in decisions[:i]:
        st = st.apply(d)
        taken.append(d)
    st = st.apply(alt)
    taken.append(alt)
    plan = list(decisions[i + 1:])
    while not st.is_terminal():
        ds = st.get_decisions(platform)
        offered = {d.key(): d for d in ds}
        pick = None
        for j, p in enumerate(plan):
            got = offered.get(p.key())
            if got is not None:
                pick = got
                del plan[j]
                break
        if pick is None:
            pick = fallback(st, ds)
        st = st.apply(pick)
        taken.append(pick)
    return st.sequence, taken


@dataclass
class LocalOpts:
    """``budget`` counts benchmarked DISTINCT schedules: canonical-key
    dedup skips no-op neighbors (a substitution that rebuilds the identical
    schedule) without charging the budget, and a neighbor already measured by
    an earlier solver through a shared ``CachingBenchmarker`` (cache hit —
    instant, no device time) is likewise free (ADVICE r3).

    ``prescreen`` (a ``learn.surrogate.SurrogateBenchmarker``) prunes
    neighbors before they are measured: a candidate whose optimistic
    prediction (``mu - prescreen_z * (sigma_cand + sigma_incumbent)``) is
    still worse than the incumbent's prediction is skipped without charging
    the budget — the learned model spends the measurement budget on
    neighbors it cannot rule out.

    ``paired=True`` makes each accept decision DRIFT-IMMUNE: the neighbor and
    the current incumbent are measured back-to-back as one decorrelated
    2-schedule batch and the move is taken only when the paired ratio's
    bootstrap CI clears 1.0.  Without it, first-improvement climbing under a
    drifting chip accepts moves because the *chip* sped up between the
    incumbent's old measurement and the neighbor's new one (observed in the
    r4 driver: a climb chain "improving" 142 -> 96 ms that ranked below its
    own seed in the paired screen).  Needs a benchmarker exposing
    ``benchmark_batch_times`` (EmpiricalBenchmarker, directly or as the
    ``.inner`` of a CachingBenchmarker)."""

    budget: int = 24
    bench_opts: BenchOpts = field(default_factory=BenchOpts)
    seed: int = 0
    max_alts_per_step: int = 3
    paired: bool = False
    prescreen: Optional[object] = None  # learn SurrogateBenchmarker
    prescreen_z: float = 2.0
    # fault.checkpoint.SearchCheckpoint: snapshots the climb cursor (budget
    # spent, accepted moves) per measured neighbor; resume re-executes the
    # seeded climb against the journal-restored cache (cache hits are free
    # — the budget is re-spent only on schedules never measured before), so
    # the accepted chain reconstructs deterministically
    checkpoint: Optional[object] = None
    # independent soundness gate (verify.ScheduleVerifier): the incumbent
    # and every neighbor are verified before they are measured; an unsound
    # neighbor is rejected like one that failed to compile
    verify: Optional[object] = None
    # compile prefetcher (bench.pipeline.PrefetchingBenchmarker): each
    # position's neighbor batch is built up front and hinted before the
    # sequential measure loop, so neighbor k+1's compile overlaps neighbor
    # k's measurement.  Building the batch early is pure replay (no RNG):
    # None (the default) is bit-identical to prefetch-off.
    prefetch: Optional[object] = None
    # cross-worker search exchange (search.fleet.SharedSearchState): a fleet
    # of climbs over different seeds shares (a) a winner-takes-all claim
    # registry of canonical schedule keys — ``claim(seq) -> False`` means
    # another worker already paid for this neighbor, skip it budget-free
    # like a local dedup hit — and (b) incumbent snapshots published on
    # every accepted move (``note_incumbent(cost_s, seq)``), the fleet's
    # "allreduce incumbents" half.  None = solo climb, bit-identical to the
    # pre-fleet behavior.
    shared: Optional[object] = None


@dataclass
class LocalResult:
    sims: List = field(default_factory=list)  # SimResult-compatible entries
    final: object = None  # the accepted chain tip (the climb's official output)

    def best(self):
        return min(self.sims, key=lambda s: s.result.pct50) if self.sims else None


def hill_climb(
    graph: Graph, platform, benchmarker, phases: Seq[str],
    prefer=None, opts: Optional[LocalOpts] = None, priority=None,
) -> LocalResult:
    """First-improvement hill climbing from the phase-policy incumbent."""
    from tenzing_tpu.solve.mcts.mcts import SimResult

    from tenzing_tpu.core.sequence import canonical_key

    opts = opts if opts is not None else LocalOpts()
    rng = _random.Random(opts.seed)
    # a FRESH policy per drive/replay: phase_policy carries a round-robin
    # lane counter, and sharing one closure would make the schedule a given
    # (position, alternative) neighbor maps to depend on how many fallback
    # assignments happened earlier in the run
    fresh = lambda: phase_policy(platform, phases, prefer, priority)
    result = LocalResult()

    def unsound(seq_, where):
        """True (and reported) when the soundness gate rejects ``seq_`` —
        the climb treats it exactly like a neighbor that failed to
        compile, without spending any device time."""
        if opts.verify is None:
            return False
        verdict = opts.verify(seq_)
        if verdict.ok:
            return False
        import sys

        from tenzing_tpu.verify.soundness import report_unsound

        report_unsound(where, seq_, verdict)
        sys.stderr.write(
            "hill-climb: schedule rejected by the soundness verifier "
            f"({verdict.witness()})\n")
        return True

    def measured(seq_):
        """Benchmark + record; returns (result | None, charge) where
        ``charge`` is False for a cache hit (instant, no device time) — the
        single free-cache-hit policy both the incumbent and the neighbor loop
        use.  ``None`` result = the schedule failed to compile/run (rejected,
        same policy as paired_step)."""
        if unsound(seq_, "local.measure"):
            return None, False
        pre_hits = getattr(benchmarker, "hits", None)
        try:
            res = benchmarker.benchmark(seq_, opts.bench_opts)
        except Exception as e:
            import sys

            from tenzing_tpu.fault.errors import DeviceLostError

            if isinstance(e, DeviceLostError):
                raise  # fatal escalation, never a neighbor verdict
            candidate_failed("local.measure", seq_, e)
            sys.stderr.write(
                "hill-climb: schedule rejected (failed to compile/run: "
                f"{type(e).__name__}: {str(e)[:200]})\n"
            )
            return None, True
        result.sims.append(SimResult(order=seq_, result=res))
        return res, pre_hits is None or benchmarker.hits == pre_hits

    batch_owner = benchmarker
    batcher = getattr(benchmarker, "benchmark_batch_times", None)
    if batcher is None:
        batch_owner = getattr(benchmarker, "inner", None)
        batcher = getattr(batch_owner, "benchmark_batch_times", None)
    use_paired = opts.paired and batcher is not None

    def paired_step(cur_seq, cand_seq):
        """(candidate BenchResult | None, accept, charge) from one
        decorrelated 2-schedule batch: accept only when the paired cur/cand
        ratio's CI clears 1.0; ``charge`` is False when the batch was
        answered from a journal replay (JournalingBenchmarker.batch_hits —
        the same free-cache-hit budget policy as ``measured``, so a resumed
        climb re-spends budget only on batches never run before).  A
        neighbor that fails to COMPILE (e.g. an ordering whose liveness
        needs more HBM than the chip has — observed on the halo flagship:
        several multi-GB grid versions kept alive at once) is a reject, not
        a crash: infeasible-on-hardware is a legitimate verdict for a
        schedule."""
        from tenzing_tpu.bench.benchmarker import BenchResult
        from tenzing_tpu.utils.numeric import paired_speedup

        pair_seed = rng.randrange(1 << 30)
        if unsound(cand_seq, "local.paired"):
            return None, False, False
        pre_hits = getattr(batch_owner, "batch_hits", None)
        try:
            times = batcher([cur_seq, cand_seq], opts.bench_opts, seed=pair_seed)
        except Exception as e:  # compile/runtime failure of the candidate
            import sys

            from tenzing_tpu.fault.errors import DeviceLostError

            if isinstance(e, DeviceLostError):
                raise  # fatal escalation, never a neighbor verdict
            candidate_failed("local.paired", cand_seq, e)
            sys.stderr.write(
                "hill-climb: neighbor rejected (failed to compile/run: "
                f"{type(e).__name__}: {str(e)[:200]})\n"
            )
            return None, False, True
        charge = pre_hits is None or batch_owner.batch_hits == pre_hits
        m, lo, _ = paired_speedup(times[0], times[1], seed=pair_seed + 1)
        res = BenchResult.from_times(times[1])
        result.sims.append(SimResult(order=cand_seq, result=res))
        return res, (m > 1.0 and lo > 1.0), charge

    seq, decisions = drive(graph, platform, fresh())
    cur, charge = measured(seq)
    if cur is None:
        raise RuntimeError(
            "hill-climb incumbent schedule failed to compile/run — nothing "
            "to climb from"
        )
    seen = {canonical_key(seq)}
    spent = 1 if charge else 0
    accepted = 0
    if opts.shared is not None:
        opts.shared.note_incumbent(cur.pct50, seq)

    def save_cursor():
        if opts.checkpoint is not None:
            opts.checkpoint.save_state(
                climb={"spent": spent, "accepted": accepted,
                       "n_sims": len(result.sims)})

    save_cursor()

    def sweep_order(decs):
        """Shuffled positions, structural decisions (implementation choices,
        lane bindings) first — they are sparse in the list but carry the
        biggest schedule differences."""
        struct = [i for i, d in enumerate(decs)
                  if isinstance(d, (ChooseOp, AssignLane))]
        struct_set = set(struct)
        rest = [i for i in range(len(decs)) if i not in struct_set]
        rng.shuffle(struct)
        rng.shuffle(rest)
        return struct + rest

    improved = True
    while spent < opts.budget and improved:
        improved = False
        for i in sweep_order(decisions):
            # re-derive the state at position i to enumerate alternatives
            st = State(graph)
            for d in decisions[:i]:
                st = st.apply(d)
            ds = st.get_decisions(platform)
            alts = [d for d in ds if d.key() != decisions[i].key()]
            rng.shuffle(alts)
            if opts.prefetch is not None:
                # the whole neighbor batch is materialized before the
                # measure loop: replay_with_substitution is deterministic
                # and RNG-free, so building candidate k+1 early changes
                # nothing — but it lets the prefetcher compile it while
                # candidate k measures
                neighbors = [
                    (alt, *replay_with_substitution(
                        graph, platform, decisions, i, alt, fresh()))
                    for alt in alts[: opts.max_alts_per_step]
                ]
                opts.prefetch.prefetch(
                    [cs for _, cs, _ in neighbors
                     if canonical_key(cs) not in seen])
            else:
                # prefetch off: replay lazily, exactly the pre-pipeline
                # cost model (a first-improvement break pays for no
                # neighbor it never visits)
                neighbors = (
                    (alt, *replay_with_substitution(
                        graph, platform, decisions, i, alt, fresh()))
                    for alt in alts[: opts.max_alts_per_step]
                )
            for alt, cand_seq, cand_dec in neighbors:
                key = canonical_key(cand_seq)
                if key in seen:
                    # a no-op neighbor (e.g. swapping which of two Expands
                    # goes first yields the identical schedule) — skip
                    # WITHOUT charging the budget
                    continue
                seen.add(key)
                if opts.shared is not None and not opts.shared.claim(cand_seq):
                    # another fleet worker already claimed this exact
                    # canonical schedule — the subtrees stay *dynamically*
                    # disjoint, and the skip is budget-free like a local
                    # dedup hit
                    continue
                if opts.prescreen is not None:
                    mu_c, s_c = opts.prescreen.predict(cand_seq)
                    mu_i, s_i = opts.prescreen.predict(seq)
                    if mu_c - opts.prescreen_z * (s_c + s_i) > mu_i:
                        # even the optimistic bound is worse than the
                        # incumbent's prediction: prune without measuring
                        get_metrics().counter(
                            "learn.prune.local_skipped").inc()
                        tr = get_tracer()
                        if tr.enabled:
                            tr.event("learn.prune", where="local",
                                     schedule=schedule_id(cand_seq))
                        continue
                with get_tracer().span("climb.iter", it=len(result.sims),
                                       pos=i):
                    if use_paired:
                        res, accept, charge = paired_step(seq, cand_seq)
                    else:
                        res, charge = measured(cand_seq)
                        accept = res is not None and res.pct50 < cur.pct50
                if charge:
                    spent += 1  # cache and journal hits are free
                if accept:  # first improvement: move
                    cur, seq, decisions = res, cand_seq, cand_dec
                    improved = True
                    accepted += 1
                    if opts.shared is not None:
                        opts.shared.note_incumbent(cur.pct50, seq)
                    save_cursor()  # accepted moves only: the cursor is
                    # consistency metadata (resume replays the journal), so
                    # a per-neighbor atomic rewrite would just double the
                    # measurement loop's sync I/O
                    break
                if spent >= opts.budget:
                    break
            if improved or spent >= opts.budget:
                break
    save_cursor()  # final spend/accept tallies
    result.final = SimResult(order=seq, result=cur)
    return result
