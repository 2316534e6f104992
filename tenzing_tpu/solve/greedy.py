"""Greedy phase-ordered incumbent schedules.

The reference hard-codes one overlap discipline into its halo graph —
every-post-before-any-wait edges (ops_halo_exchange.cu:249-256).  This
framework's graphs deliberately leave that order free for the solver, and
:func:`greedy_phase_order` reconstructs the discipline as a *schedule* instead
of a graph constraint: ops execute in phase order (all packs, then all posts,
then all awaits, ...), round-robined across lanes, with the SDP machinery
inserting exactly the sync ops the solver would.  Anytime searches
(bench.py) seed their incumbent set with it so the directed search starts
from the domain heuristic rather than from naive.
"""

from __future__ import annotations

from typing import Callable, Sequence as Seq

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.sequence import Sequence


def greedy_phase_order(graph: Graph, platform, phases: Seq[str]) -> Sequence:
    """A complete schedule of ``graph`` executing ops in ``phases`` order.

    ``phases`` is a tuple of op-name prefixes, earliest first (must cover
    every op in the graph, including "start"/"finish"); an op's phase is the
    first prefix its name starts with.  Device ops round-robin across
    ``platform.lanes``; a later-phase op never runs while an earlier-phase op
    anywhere in the graph is unexecuted (the required sync is placed
    instead), so every phase-``k`` op happens before any phase-``k+1`` op on
    *all* lanes.  One implementation of the discipline: this is
    ``solve.local.drive`` under ``solve.local.phase_policy`` (which also
    resolves ChoiceOps and expands compounds for choice graphs)."""
    from tenzing_tpu.solve.local import drive, phase_policy

    seq, _ = drive(graph, platform, phase_policy(platform, phases))
    return seq


def serialized_chain_order(graph: Graph, platform,
                           chain_rank: Callable[[str], int]) -> Sequence:
    """The fully-serialized baseline of a graph of independent chains: every
    device op on ``platform.lanes[0]``, each chain completed before the next
    starts.  ``chain_rank`` maps a work op's name to its chain's position
    (lower first; "start"/"finish" are handled here).  Derived through the
    same SDP drive as :func:`greedy_phase_order`, so the ``EventRecord``/
    ``EventSync`` pairs a lane-bound op needs before a host-side consumer are
    inserted exactly as a solver would insert them — a hand-listed op
    sequence without them is unsound (verify/)."""
    from tenzing_tpu.core.platform import Platform
    from tenzing_tpu.solve.local import drive, phase_policy

    def priority(name: str) -> int:
        if name == "start":
            return -1
        if name == "finish":
            return 1 << 30
        return chain_rank(name)

    one_lane = Platform(platform.lanes[:1])
    seq, _ = drive(graph, one_lane, phase_policy(one_lane, (), priority=priority))
    return seq
