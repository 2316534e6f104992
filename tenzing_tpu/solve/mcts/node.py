"""MCTS tree node.

Parity target: reference ``tenzing-mcts/include/tenzing/mcts/mcts_node.hpp``:
``Node<Strategy>`` holds parent/children, the decision that produced it, its own
graph snapshot (graph-mutating decisions change the graph down the subtree,
mcts_node.hpp:25-106), rollout count ``n_``, ``fullyVisited_``, and per-node
strategy state.  ``select`` is UCT descent with the strategy's exploitation term
(mcts_node.hpp:168-240); ``expand`` returns the first unplayed child
(mcts_node.hpp:352-369); ``get_rollout`` descends randomly to a terminal state
(mcts_node.hpp:371-446); ``backprop`` bumps counts, propagates fully-visited, and
calls the strategy up the chain (mcts_node.hpp:326-350).

Beyond the reference: a rollout that is drawn and not yet measured (the
search's lookahead, ``mcts.explore``) leaves a *pending* visit on every node
of its path (``mark_pending``).  ``select`` and ``expand`` count it as a visit,
so the next draw takes another unplayed child and UCT another branch;
``backprop(..., pending=True)`` turns it into a real one, ``take_back`` undoes
it together with the children the draw created, which the drawing methods
list for their caller (``grew``).  With nothing pending every method is the
reference's.

Simplification vs the reference: each node stores its full SDP ``State``
(graph + sequence) rather than reconstructing the state from the root path —
clone surgery shares op objects so snapshots are cheap.
"""

from __future__ import annotations

import math
import random
from typing import List, Optional, Tuple

from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.state import Decision, ExecuteOp, State


class Node:
    def __init__(
        self,
        state: State,
        strategy,
        decision: Optional[Decision] = None,
        parent: Optional["Node"] = None,
    ):
        self.state = state
        self.strategy = strategy
        self.decision = decision
        self.parent = parent
        self.children: List["Node"] = []
        self.n_ = 0  # rollouts through this node (reference n_)
        self.pending_ = 0  # rollouts drawn through this node, not yet measured
        self.fully_visited_ = False
        self.expanded_ = False
        self.strat_state = strategy.State()  # per-node observations

    # -- structure ---------------------------------------------------------
    def is_terminal(self) -> bool:
        return self.state.is_terminal()

    def label(self) -> str:
        return self.decision.desc() if self.decision is not None else "root"

    def ensure_children(self, platform, grew: Optional[list] = None) -> None:
        """Create one child per decision (reference create_children,
        mcts_node.hpp:514-552); Execute decisions become op nodes, graph-only
        decisions become decision nodes — both are plain children here.
        Children pre-created by seed materialization are kept, not
        duplicated (matched by decision key).  ``grew`` (here and in the
        drawing methods below) collects ``(node, children it had)`` for every
        node given children, for ``take_back``."""
        if self.expanded_ or self.is_terminal():
            self.expanded_ = True
            return
        have = {c.decision.key() for c in self.children if c.decision is not None}
        if grew is not None:
            grew.append((self, len(self.children)))
        for d in self.state.get_decisions(platform):
            if d.key() not in have:
                self.children.append(Node(self.state.apply(d), self.strategy, d, self))
        self.expanded_ = True
        if not self.children:
            self.fully_visited_ = True

    # -- visits, measured and pending --------------------------------------
    def visits(self) -> int:
        """Rollouts drawn through this node: measured and pending."""
        return self.n_ + self.pending_

    def closed(self) -> bool:
        """No further rollout can be drawn beneath this node: it is fully
        visited, or will be once its pending rollouts are measured.  Only
        nodes with a pending visit can differ from ``fully_visited_``, so
        the walk stays on the pending paths."""
        if self.fully_visited_ or not self.pending_:
            return self.fully_visited_
        if self.is_terminal():
            return True
        return (self.expanded_ and bool(self.children)
                and all(c.closed() for c in self.children))

    def mark_pending(self) -> None:
        """A rollout was drawn from here: one pending visit on every node up
        to the root, until ``backprop`` or ``take_back``."""
        node: Optional[Node] = self
        while node is not None:
            node.pending_ += 1
            node = node.parent

    def take_back(self, grew) -> None:
        """Undo a draw from here that will not be measured: its pending
        visits, and the children it created (``grew``, as the drawing methods
        listed them).  Draws are taken back newest first."""
        node: Optional[Node] = self
        while node is not None:
            assert node.pending_ > 0, "take_back of a rollout never marked"
            node.pending_ -= 1
            node = node.parent
        Node.ungrow(grew)

    @staticmethod
    def ungrow(grew) -> None:
        """Remove the children that one draw created."""
        for node, had in reversed(grew):
            del node.children[had:]
            node.expanded_ = False

    # -- selection (reference mcts_node.hpp:168-240) ------------------------
    def select(self, ctx, platform, rng: random.Random,
               grew: Optional[list] = None) -> "Node":
        """UCT descent: walk down while fully expanded, maximizing
        exploit + sqrt(2)*sqrt(ln n_parent / n_child), the counts being
        ``visits()``; closed children score -inf; ties break randomly.  A
        child that is pending and not yet measured has nothing for a strategy
        to judge: its exploit term is the neutral 0.0 under every strategy
        (``Unvisited`` would give it +inf, and send the next draw after it)."""
        node = self
        while True:
            node.ensure_children(platform, grew)
            if node.is_terminal() or not node.children:
                return node
            if not all(c.visits() for c in node.children):
                return node
            best_score = -math.inf
            best: List[Node] = []
            for c in node.children:
                if c.closed():
                    continue
                exploit = self.strategy.select(ctx, c) if c.n_ else 0.0
                explore = math.sqrt(2.0) * math.sqrt(
                    math.log(node.visits()) / c.visits())
                score = exploit + explore
                if score > best_score:
                    best_score, best = score, [c]
                elif score == best_score:
                    best.append(c)
            if not best:
                return node  # all children closed
            node = rng.choice(best)

    def expand(self, platform, rng: random.Random,
               grew: Optional[list] = None) -> "Node":
        """An unplayed child (no visit, measured or pending), or self when
        there is none (reference mcts_node.hpp:352-369)."""
        self.ensure_children(platform, grew)
        unplayed = [c for c in self.children if not c.visits()]
        if unplayed:
            return rng.choice(unplayed)
        return self

    # -- rollout (reference mcts_node.hpp:371-446) ---------------------------
    def get_rollout(
        self, platform, rng: random.Random, expand_rollout: bool = False,
        policy=None, policy_eps: float = 0.0, grew: Optional[list] = None,
    ) -> Tuple["Node", Sequence]:
        """Descent to a terminal state; returns (backprop endpoint, the
        complete schedule).  Without ``expand_rollout`` the playout runs on
        throwaway State objects and the endpoint is this node (reference
        mcts_node.hpp:371-446, backpropStart = this); with it, the visited path
        is materialized as tree nodes and the endpoint is the terminal node.

        ``policy`` (optional, ``(state, decisions) -> decision``): an informed
        rollout — each playout step takes the policy's pick instead of a
        uniform-random one, except with probability ``policy_eps`` per step
        (exploration noise so distinct leaves produce distinct completions).
        Uniform-random completion of a ~100-decision halo schedule almost
        never assembles a coherent discipline, which is why random-playout
        MCTS lagged the hill-climbs for four rounds (VERDICT r4 weak #2);
        the policy rollout scores each tree prefix by the best-known way of
        finishing it — the standard informed-playout MCTS improvement."""
        if expand_rollout:
            node: Node = self
            while not node.is_terminal():
                node.ensure_children(platform, grew)
                if not node.children:
                    break
                if policy is not None and rng.random() >= policy_eps:
                    # the policy picks a decision; take the matching child
                    pick = policy(node.state,
                                  [c.decision for c in node.children])
                    node = next(
                        (c for c in node.children
                         if c.decision.key() == pick.key()),
                        rng.choice(node.children),
                    )
                else:
                    node = rng.choice(node.children)
            return node, node.state.sequence
        if policy is None:
            # a uniform playout opens with one draw that nothing reads: it
            # is part of the stream a seed stands for, which journals replay
            # against and tests pin (tests/test_search_core.py)
            rng.getrandbits(63)
        state = self.state
        while not state.is_terminal():
            ds = state.get_decisions(platform)
            if not ds:
                break
            if policy is not None and rng.random() >= policy_eps:
                state = state.apply(policy(state, ds))
            else:
                state = state.apply(rng.choice(ds))
        return self, state.sequence

    # -- backprop (reference mcts_node.hpp:326-350) --------------------------
    def backprop(self, ctx, result, pending: bool = False) -> None:
        """One measured rollout from here up to the root.  ``pending``: the
        rollout was drawn ahead (``mark_pending``), and that visit becomes
        this one."""
        node: Optional[Node] = self
        while node is not None:
            node.n_ += 1
            if pending:
                assert node.pending_ > 0, "backprop of a rollout never marked"
                node.pending_ -= 1
            self.strategy.backprop(ctx, node, result)
            if node.is_terminal():
                node.fully_visited_ = True
            elif node.expanded_ and node.children and all(
                c.fully_visited_ for c in node.children
            ):
                node.fully_visited_ = True
            node = node.parent

    # -- introspection ------------------------------------------------------
    def size(self) -> int:
        return 1 + sum(c.size() for c in self.children)

    def dump_graphviz(self, max_nodes: int = 500) -> str:
        """Tree dump with rollout counts (reference dump_graphviz,
        mcts.hpp:52-127)."""
        lines = ["digraph mcts {"]
        count = [0]

        def walk(node: Node, nid: int) -> int:
            my = nid
            lines.append(
                f'  n{my} [label="{node.label()}\\nn={node.n_}'
                + ("\\nfull" if node.fully_visited_ else "")
                + '"];'
            )
            nxt = my + 1
            for c in node.children:
                if count[0] >= max_nodes:
                    break
                if c.n_ == 0:
                    continue
                count[0] += 1
                lines.append(f"  n{my} -> n{nxt};")
                nxt = walk(c, nxt)
            return nxt

        walk(self, 0)
        lines.append("}")
        return "\n".join(lines) + "\n"
