"""T3-style op chunking (ISSUE 10): protocol, roofline pruning goldens,
chunked-vs-unchunked numerics, verifier fuzz over chunked projections,
solver enumeration of chunk counts, and the directive feature markers.

The acceptance gates:

* **soundness**: every chunked schedule the synthesizer emits over the
  chunk-extended choice graphs passes the independent PR-4 verifier
  (0 false positives), and the original EventSynchronizer oracle agrees;
* **numerics**: ``chunks=1`` is the op itself (bit-identical by
  construction); ``chunks>1`` re-associates the accumulation across chunk
  boundaries and must be allclose to the unchunked evaluation — for the
  naive serialization AND randomized 2-lane schedules;
* **searchability**: MCTS, DFS and hill-climb all visit >= 2 distinct
  chunk counts with zero solver changes (chunked expansions are ordinary
  ChoiceOp alternatives);
* **pruning**: ``bench/roofline.py::prune_chunkings`` matches hand-computed
  goldens (traffic floor, dispatch+combine cost vs the hidden-comm bound).
"""

import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tenzing_tpu.bench import roofline
from tenzing_tpu.bench.benchmarker import BenchOpts, EmpiricalBenchmarker
from tenzing_tpu.core.chunking import (
    CHUNK_MARK,
    ChunkChoice,
    ChunkDirective,
    ChunkedOp,
    chunk_menus,
    chunk_variants,
    chunks_of,
    menu_info,
)
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.state import State
from tenzing_tpu.models.ring_attention import (
    BlockAttnStep,
    BlockedAttention,
    RingAttnArgs,
    fold_chunk_menu,
    make_blocked_buffers,
)
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.solve.dfs import enumerate_schedules
from tenzing_tpu.verify import ScheduleVerifier

ATTN = RingAttnArgs(n_devices=2, batch=1, seq_local=8, head_dim=4)


def _attn_graph(args=ATTN, impl_choice=False):
    g = Graph()
    op = BlockedAttention(args, impl_choice=impl_choice, chunk=True,
                          chunk_relax=True)
    g.start_then(op)
    g.then_finish(op)
    return g


def _drive(g, plat, want_suffix=None):
    """First-decision serialization, preferring choice alternatives whose
    name ends with ``want_suffix``."""
    st = State(g)
    while not st.is_terminal():
        ds = st.get_decisions(plat)
        pick = None
        if want_suffix is not None:
            pick = next(
                (d for d in ds
                 if getattr(d, "choice", None) is not None
                 and d.choice.name().endswith(want_suffix)), None)
        st = st.apply(pick or ds[0])
    return st.sequence


def _has_chunk(seq) -> bool:
    return bool(chunks_of(seq))


class TestProtocol:
    def test_chunk_counts_always_contain_one(self):
        step = BlockAttnStep("attn_0", 0, ATTN)
        counts = step.chunk_counts()
        assert 1 in counts and counts == sorted(counts)
        assert all(ATTN.seq_local % n == 0 for n in counts)

    def test_split_partials_chain_through_directive(self):
        step = BlockAttnStep("attn_0", 0, ATTN)
        v = ChunkedOp(step, 2)
        g = v.graph()
        names = [op.name() for op in g.vertices()]
        assert f"attn_0{CHUNK_MARK}2" in names
        assert "attn_0.c2p0" in names and "attn_0.c2p1" in names
        # serial chain: directive -> p0 -> p1 (the combine is the
        # accumulating state the partials thread through)
        by = {op.name(): op for op in g.vertices()}
        assert by["attn_0.c2p0"] in g.succs(by[f"attn_0{CHUNK_MARK}2"])
        assert by["attn_0.c2p1"] in g.succs(by["attn_0.c2p0"])

    def test_chunked_op_guards(self):
        step = BlockAttnStep("attn_0", 0, ATTN)
        with pytest.raises(ValueError):
            ChunkedOp(step, 1)  # 1 = the op itself, never an expansion
        from tenzing_tpu.models.ring_attention import BlockAttnStepPallas

        with pytest.raises(ValueError):
            ChunkedOp(BlockAttnStepPallas("attn_0.pallas", 0, ATTN), 2)
        with pytest.raises(ValueError):
            step.split(3)  # 8 columns do not split 3 ways
        # a partial never re-splits
        assert not step.split(2)[0].chunkable()

    def test_chunks_of_parses_directives(self):
        seq = [ChunkDirective("ffn_0.xla", 4), ChunkDirective("attn_1", 2)]
        assert chunks_of(seq) == {"ffn_0.xla": 4, "attn_1": 2}
        assert chunks_of([]) == {}

    def test_directive_serdes_roundtrip(self):
        from tenzing_tpu.core.serdes import (
            sequence_from_json,
            sequence_to_json,
        )

        g = _attn_graph()
        seq = Sequence([ChunkDirective("attn_0", 2)])
        back = sequence_from_json(sequence_to_json(seq), g)
        assert chunks_of(back) == {"attn_0": 2}

    def test_chunked_schedule_serdes_roundtrip(self):
        """An executed chunked schedule (directive + partials) re-anchors
        against the choice graph: partials resolve by name through the
        ChunkedOp alternative's sub-graph."""
        from tenzing_tpu.core.serdes import (
            sequence_from_json,
            sequence_to_json,
        )

        g = _attn_graph()
        plat = Platform.make_n_lanes(2)
        seq = _drive(g, plat, want_suffix=".chunked.c2")
        assert _has_chunk(seq)
        back = sequence_from_json(sequence_to_json(seq), g)
        assert [op.name() for op in back] == [op.name() for op in seq]
        assert chunks_of(back) == chunks_of(seq)

    def test_chunk_menus_collects_choice_metadata(self):
        menus = chunk_menus(_attn_graph())
        assert set(menus) == {f"attn_{s}" for s in range(ATTN.n_devices)}
        for m in menus.values():
            assert m["counts"] == [1, 2, 4]
        # kernel-menu variant (impl_choice) keys on the wrapped .xla name
        menus = chunk_menus(_attn_graph(impl_choice=True))
        assert set(menus) == {f"attn_{s}.xla" for s in range(ATTN.n_devices)}

    def test_menu_info_normalizes(self):
        m = menu_info("x", [4, 2, 2], {2: 10.0, 4: None})
        assert m["counts"] == [1, 2, 4]  # 1 injected, dedup, sorted
        assert m["est_hidden_us"] == {2: 10.0}  # None estimates dropped

    def test_chunk_variants_skips_degenerate_counts(self):
        step = BlockAttnStep("attn_0", 0, ATTN)
        vs = chunk_variants(step, [1, 2, 2, 4])
        assert [v.chunks() for v in vs] == [2, 4]

    def test_marker_strings_agree_across_modules(self):
        """learn/features.py duplicates the directive markers to stay
        import-light; the literals must agree or the surrogate silently
        zeroes chunked schedules."""
        from tenzing_tpu.learn import features
        from tenzing_tpu.runtime.fused import TILE_PREFIX

        assert features._CHUNK_MARK == CHUNK_MARK
        assert features._TILE_PREFIX == TILE_PREFIX


class TestPruneChunkings:
    def test_traffic_floor_golden(self):
        # 8 MiB of traffic, no comm model: n=2 leaves 4 MiB/chunk (fine at
        # the 1 MiB floor), n=16 leaves 0.5 MiB (all prologue: dropped)
        c = roofline.Cost(flops=0.0, hbm_bytes=8 * 2**20)
        assert roofline.prune_chunkings(c, [1, 2, 16]) == [1, 2]
        # 1 always survives, even alone
        assert roofline.prune_chunkings(
            roofline.Cost(0.0, 10.0), [1, 2, 4]) == [1]

    def test_hidden_comm_bound_golden(self):
        # an op whose analytic floor is exactly 1000 us
        c = roofline.Cost(flops=roofline.V5E_PEAK_BF16_FLOPS * 1e-3,
                          hbm_bytes=8 * 2**20)
        assert roofline.op_roofline_us(c) == pytest.approx(1000.0)
        assert roofline.hidden_comm_bound_us(c, 1, 500.0) == 0.0
        # n=2 exposes the tail half: min(comm, 500)
        assert roofline.hidden_comm_bound_us(c, 2, 300.0) == \
            pytest.approx(300.0)
        assert roofline.hidden_comm_bound_us(c, 2, 800.0) == \
            pytest.approx(500.0)
        # n=4 exposes 3/4 of the op
        assert roofline.hidden_comm_bound_us(c, 4, 1e9) == \
            pytest.approx(750.0)

    def test_comm_rule_golden(self):
        c = roofline.Cost(flops=roofline.V5E_PEAK_BF16_FLOPS * 1e-3,
                          hbm_bytes=8 * 2**20)
        # n=2 hides up to 500 us for one extra dispatch (25 us): survives
        assert roofline.prune_chunkings(c, [1, 2], comm_us=500.0) == [1, 2]
        # only 10 us of comm exists — under the dispatch floor: dropped
        assert roofline.prune_chunkings(c, [1, 2], comm_us=10.0) == [1]
        # a combine pass costing ~1000 us/partial swamps the 500 us bound
        combine = roofline.V5E_PEAK_HBM_BYTES * 1e-3
        assert roofline.prune_chunkings(
            c, [1, 2], comm_us=500.0, combine_bytes=combine) == [1]
        # no comm to hide prunes every n > 1 (the honest single-chip attn
        # answer fold_chunk_menu reports un-relaxed)
        assert roofline.prune_chunkings(c, [1, 2, 4], comm_us=0.0) == [1]

    def test_model_menus_relaxed_and_pruned(self):
        counts, est = fold_chunk_menu(ATTN, relax=True)
        assert counts == [1, 2, 4] and est == {}
        # full-size blocked attn has no neighboring transfer: all pruned
        counts, _ = fold_chunk_menu(
            RingAttnArgs(n_devices=8, batch=4, seq_local=1024, head_dim=128))
        assert counts == [1]
        # MoE pipe full-size: the combine-side DMA is real comm — the
        # roofline keeps at least one n>1 and prices its hidden bound
        from tenzing_tpu.models.moe_pipeline import (
            MoEPipeArgs,
            ffn_chunk_menu,
        )

        counts, est = ffn_chunk_menu(MoEPipeArgs(tokens=8192), cap=4096)
        assert any(n > 1 for n in counts)
        assert all(est[n] > 0 for n in counts if n > 1)


class TestChunkedNumerics:
    def test_naive_chunked_matches_unchunked_per_count(self):
        bufs, want = make_blocked_buffers(ATTN, seed=3)
        plat = Platform.make_n_lanes(1)
        g = _attn_graph()
        ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
        for n in (2, 4):
            seq = _drive(g, plat, want_suffix=f".chunked.c{n}")
            assert set(chunks_of(seq).values()) == {n}
            out = ex.run(seq)
            np.testing.assert_allclose(np.asarray(out["O"]), want,
                                       rtol=2e-4, atol=2e-5)

    def test_chunks_one_is_bit_identical(self):
        """The unchunked menu entry IS the original op: resolving the
        chunk choice to it produces the same program as the chunk-free
        graph, bit for bit."""
        bufs, _ = make_blocked_buffers(ATTN, seed=4)
        plat = Platform.make_n_lanes(1)
        jb = {k: jnp.asarray(v) for k, v in bufs.items()}
        ex = TraceExecutor(plat, jb)
        plain = Graph()
        op = BlockedAttention(ATTN)
        plain.start_then(op)
        plain.then_finish(op)
        out_plain = ex.run(_drive(plain, plat))
        seq1 = _drive(_attn_graph(), plat)  # first choice = the op itself
        assert not _has_chunk(seq1)
        out_c1 = TraceExecutor(plat, jb).run(seq1)
        assert np.array_equal(np.asarray(out_plain["O"]),
                              np.asarray(out_c1["O"]))

    def test_randomized_two_lane_chunked_schedules_match(self):
        bufs, want = make_blocked_buffers(ATTN, seed=5)
        plat = Platform.make_n_lanes(2)
        g = _attn_graph()
        seqs = [s.sequence for s in enumerate_schedules(g, plat,
                                                        max_seqs=64)]
        chunked = [s for s in seqs if _has_chunk(s)]
        assert len(chunked) >= 2  # the space genuinely contains them
        ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
        for s in chunked[:3]:
            out = ex.run(s)
            np.testing.assert_allclose(np.asarray(out["O"]), want,
                                       rtol=2e-4, atol=2e-5)

    @pytest.mark.needs_pinned_host
    def test_moe_pipe_chunked_matches_dense_routing(self):
        from tenzing_tpu.models.moe_pipeline import (
            MoEPipeArgs,
            build_graph,
            host_buffer_names,
            make_pipe_buffers,
        )

        margs = MoEPipeArgs(n_experts=4, tokens=32, d_model=8, d_ff=16,
                            n_chunks=2)
        bufs, want, cap = make_pipe_buffers(margs, seed=1)
        g = build_graph(margs, cap, chunk=True, chunk_relax=True)
        plat = Platform.make_n_lanes(2)
        jbufs = TraceExecutor.place_host_buffers(
            bufs, host_buffer_names(margs))
        ex = TraceExecutor(plat, jbufs)
        seq = _drive(g, plat, want_suffix=".chunked.c2")
        assert _has_chunk(seq)
        out = ex.run(seq)
        np.testing.assert_allclose(np.asarray(out["Y"]), want, rtol=2e-3,
                                   atol=2e-5)


class TestPartialFolds:
    """Direct-apply equality: the n partials' accumulating updates fold to
    the whole op's output on plain arrays (the multichip models' split
    protocol, testable without a mesh)."""

    def test_moe_expert_ffn_fold(self):
        from tenzing_tpu.models.moe import ExpertFFN, MoEArgs

        ma = MoEArgs(n_ep=4, tokens_per_shard=16, d_model=8, d_ff=16)
        rng = np.random.default_rng(0)
        bufs = {
            "recv_disp_0": jnp.asarray(
                rng.standard_normal((4, 4, 8)), jnp.float32),
            "W1": jnp.asarray(rng.standard_normal((1, 8, 16)), jnp.float32),
            "W2": jnp.asarray(rng.standard_normal((1, 16, 8)), jnp.float32),
            "ffn_out_0": jnp.zeros((4, 4, 8), jnp.float32),
        }
        op = ExpertFFN("ffn_0", 0, ma)
        want = op.apply(dict(bufs), None)["ffn_out_0"]
        for n in (2, 4):
            acc = dict(bufs)
            for p in op.split(n):
                acc.update(p.apply(acc, None))
            np.testing.assert_allclose(np.asarray(acc["ffn_out_0"]),
                                       np.asarray(want), rtol=1e-5)

    def test_pipeline_stage_fold(self):
        from tenzing_tpu.models.pipeline import StageCompute

        rng = np.random.default_rng(1)
        op = StageCompute("compute_0_0", 0, 0, mb_rows=4)
        bufs = {
            "act_0_0": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
            "W": jnp.asarray(rng.standard_normal((1, 8, 8)), jnp.float32),
            "out_0": jnp.zeros((8, 8), jnp.float32),
        }
        want = op.apply(dict(bufs), None)["out_0"]
        for n in (2, 4):
            acc = dict(bufs)
            for p in op.split(n):
                acc.update(p.apply(acc, None))
            np.testing.assert_allclose(np.asarray(acc["out_0"]),
                                       np.asarray(want), rtol=1e-5)

    def test_tp_mlp_fold(self):
        from tenzing_tpu.models.tp_mlp import TpLayerPartial

        rng = np.random.default_rng(2)
        op = TpLayerPartial("mlp_0_0", 0, 0, mb_rows=4)
        bufs = {
            "X_0": jnp.asarray(rng.standard_normal((4, 8)), jnp.float32),
            "W1": jnp.asarray(rng.standard_normal((2, 8, 8)), jnp.float32),
            "W2": jnp.asarray(rng.standard_normal((2, 8, 8)), jnp.float32),
            "part_0_0": jnp.zeros((4, 8), jnp.float32),
        }
        want = op.apply(dict(bufs), None)["part_0_0"]
        for n in (2, 4):
            acc = dict(bufs)
            for p in op.split(n):
                acc.update(p.apply(acc, None))
            np.testing.assert_allclose(np.asarray(acc["part_0_0"]),
                                       np.asarray(want), rtol=1e-5)

    def test_partials_reject_indivisible_runtime_rows(self):
        """Regression (review): chunk validity is checked against the
        build-time extent, but a sharded layout (e.g. tp_mlp's dp axis)
        can hand the partial FEWER runtime rows — rows=2 with n_parts=4
        used to slice 0 rows per partial and return an all-zero output
        silently.  The apply must fail at trace time instead."""
        from tenzing_tpu.models.pipeline import StageCompute
        from tenzing_tpu.models.tp_mlp import TpLayerPartial

        rng = np.random.default_rng(5)
        mlp = TpLayerPartial("mlp_0_0", 0, 0, mb_rows=4)
        bufs = {
            "X_0": jnp.asarray(rng.standard_normal((2, 8)), jnp.float32),
            "W1": jnp.asarray(rng.standard_normal((2, 8, 8)), jnp.float32),
            "W2": jnp.asarray(rng.standard_normal((2, 8, 8)), jnp.float32),
            "part_0_0": jnp.zeros((2, 8), jnp.float32),
        }
        [part] = [p for p in mlp.split(4) if p._part == 0][:1]
        with pytest.raises(ValueError, match="do not split"):
            part.apply(bufs, None)

        stage = StageCompute("compute_0_0", 0, 0, mb_rows=4)
        sbufs = {
            "act_0_0": jnp.asarray(rng.standard_normal((6, 8)), jnp.float32),
            "W": jnp.asarray(rng.standard_normal((1, 8, 8)), jnp.float32),
            "out_0": jnp.zeros((6, 8), jnp.float32),
        }
        with pytest.raises(ValueError, match="do not split"):
            stage.split(4)[0].apply(sbufs, None)

    def test_moe_pipe_expert_fold(self):
        from tenzing_tpu.models.moe_pipeline import (
            ExpertFFNPipe,
            MoEPipeArgs,
            make_pipe_buffers,
        )

        margs = MoEPipeArgs(n_experts=4, tokens=32, d_model=8, d_ff=16,
                            n_chunks=2)
        bufs, _, cap = make_pipe_buffers(margs, seed=3, with_expected=False)
        op = ExpertFFNPipe("ffn_0", 0, margs, cap)
        jb = {k: jnp.asarray(v) for k, v in bufs.items()}
        # the expert input: reuse the send staging buffer as the received
        # table (contents arbitrary for the fold identity)
        jb["recv_0"] = jnp.asarray(
            np.random.default_rng(4).standard_normal(
                jb["send_0"].shape), jnp.float32)
        want = op.apply(dict(jb), None)["out_0"]
        for n in (2, 4):
            acc = dict(jb)
            for p in op.split(n):
                acc.update(p.apply(acc, None))
            np.testing.assert_allclose(np.asarray(acc["out_0"]),
                                       np.asarray(want), rtol=1e-5)


class TestVerifierFuzz:
    """The PR-4 verifier certifies chunked projections as-is: every
    schedule the synthesizer emits over the chunk-extended graphs passes
    (0 false positives), and the original oracle agrees."""

    def _graphs(self):
        from tenzing_tpu.models.pipeline import Pipeline, PipelineArgs
        from tenzing_tpu.models.tp_mlp import TpMlp, TpMlpArgs

        def tp():
            g = Graph()
            op = TpMlp(TpMlpArgs(n_tp=2), chunk=True, chunk_relax=True)
            g.start_then(op)
            g.then_finish(op)
            return g

        def pp():
            g = Graph()
            op = Pipeline(PipelineArgs(n_pp=2, n_microbatches=2,
                                       n_chains=2),
                          chunk=True, chunk_relax=True)
            g.start_then(op)
            g.then_finish(op)
            return g

        return [_attn_graph(), _attn_graph(impl_choice=True), tp(), pp()]

    def test_randomized_chunked_rollouts_verify_clean(self):
        from tests.test_verify import synth_sound

        for gi, g in enumerate(self._graphs()):
            ver = ScheduleVerifier(g)
            rng = random.Random(100 + gi)
            n_chunked = 0
            for _ in range(8):
                st = State(g)
                while not st.is_terminal():
                    ds = st.get_decisions(Platform.make_n_lanes(2))
                    # bias toward chunked alternatives so the fuzz
                    # actually exercises chunked projections
                    pick = next(
                        (d for d in ds
                         if getattr(d, "choice", None) is not None
                         and ".chunked.c" in d.choice.name()
                         and rng.random() < 0.7), None)
                    st = st.apply(pick or ds[rng.randrange(len(ds))])
                v = ver(st.sequence)
                assert v.ok, f"false positive: {v.witness()}"
                assert synth_sound(st.graph, st.sequence)
                n_chunked += bool(_has_chunk(st.sequence))
            assert n_chunked >= 1  # the fuzz saw real chunked schedules
            assert ver.unsound == 0

    def test_projection_resolves_executed_count_not_first(self):
        """Regression (found by this fuzz): compound choice alternatives
        all share start/finish sentinel names, so the projection used to
        resolve every such choice to its FIRST compound alternative — a
        ``.chunked.c4`` schedule projected as the ``.c2`` expansion and
        verified ``missing_op``.  The sentinel-skipping resolution must
        project the executed count."""
        from tenzing_tpu.verify.soundness import project_graph

        g = _attn_graph()
        plat = Platform.make_n_lanes(1)
        seq = _drive(g, plat, want_suffix=".chunked.c4")
        assert set(chunks_of(seq).values()) == {4}
        names = frozenset(op.name() for op in seq)
        evolved, notes = project_graph(g, names)
        assert not notes
        vnames = {v.name() for v in evolved.vertices()}
        assert "attn_0.c4p0" in vnames and "attn_0.c2p0" not in vnames
        assert ScheduleVerifier(g)(seq).ok

    def test_projection_resolves_fused_engine_choice(self):
        """Same latent bug, pre-existing surface: the attn engine choice's
        first alternative is a compound (BlockChain) — a schedule
        executing the fused kernel must not project as the chain."""
        from tenzing_tpu.verify.soundness import project_graph

        g = Graph()
        op = BlockedAttention(ATTN, fused_choice=True)
        g.start_then(op)
        g.then_finish(op)
        plat = Platform.make_n_lanes(1)
        seq = _drive(g, plat, want_suffix=".fused_bf16")
        assert any(o.name().endswith(".fused_bf16") for o in seq)
        evolved, notes = project_graph(
            g, frozenset(o.name() for o in seq))
        assert not notes
        vnames = {v.name() for v in evolved.vertices()}
        assert "attn_blocks.fused_bf16" in vnames
        assert "attn_0" not in vnames
        assert ScheduleVerifier(g)(seq).ok

    def test_out_of_graph_tile1_directive_goes_after_start(self):
        """Regression (driver review): the driver completes out-of-graph
        sequences (naive baseline, greedy seeds, recorded rows) with a
        ``fuse_tile.t1`` directive when ``--fuse-search-tiles`` planted a
        tile choice.  The planted choice is a successor of the ``start``
        sentinel, so the directive must be inserted AFTER the leading
        start op — at position 0 it precedes its projected predecessor
        and the verifier rejects the schedule, demoting naive wins to
        ``verified: false`` and silently discarding warm starts."""
        from tenzing_tpu.runtime.fused import FuseTile, with_tile_menu

        def mk():
            g = Graph()
            op = BlockedAttention(ATTN)
            g.start_then(op)
            g.then_finish(op)
            return g

        plat = Platform.make_n_lanes(1)
        ops = list(_drive(mk(), plat).vector())
        assert ops[0].name() == "start"
        ver = ScheduleVerifier(with_tile_menu(mk(), [1, 2]))
        before = Sequence([FuseTile(1)] + ops)
        after = Sequence([ops[0], FuseTile(1)] + ops[1:])
        assert not ver(before).ok
        assert ver(after).ok

    def test_exhaustive_small_space_verifies_clean(self):
        g = _attn_graph()
        ver = ScheduleVerifier(g)
        states = enumerate_schedules(g, Platform.make_n_lanes(2),
                                     max_seqs=64)
        chunked = [s for s in states if _has_chunk(s.sequence)]
        assert chunked
        for st in states:
            v = ver(st.sequence)
            assert v.ok, f"false positive: {v.witness()}"
        assert ver.unsound == 0


class TestSolversSearchChunks:
    """Chunk counts are ordinary choice decisions: all three solvers visit
    >= 2 distinct counts with zero solver changes."""

    def _bench(self, plat, bufs):
        ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
        return EmpiricalBenchmarker(ex)

    def _seen_counts(self, sims):
        seen = set()
        for s in sims:
            cs = set(chunks_of(s.order).values())
            seen.update(cs or {1})
        return seen

    def test_dfs_enumerates_chunk_alternatives(self):
        from tenzing_tpu.solve.dfs import DfsOpts, explore

        bufs, _ = make_blocked_buffers(ATTN, seed=0)
        plat = Platform.make_n_lanes(1)
        res = explore(
            _attn_graph(), plat, self._bench(plat, bufs),
            DfsOpts(max_seqs=24, dump_csv_path="/dev/null",
                    bench_opts=BenchOpts(n_iters=2, target_secs=0.0002)))
        seen = self._seen_counts(res.sims)
        assert 1 in seen and len(seen) >= 2

    def test_hill_climb_searches_chunks(self):
        from tenzing_tpu.solve.local import LocalOpts, hill_climb

        bufs, _ = make_blocked_buffers(ATTN, seed=0)
        plat = Platform.make_n_lanes(1)

        def prefer(op_name, choices):
            # seed unchunked; flip moves must explore the chunk menu
            return next(
                (c for c in choices if not c.endswith((".c2", ".c4"))),
                None)

        res = hill_climb(
            _attn_graph(), plat, self._bench(plat, bufs),
            phases=("attn",), prefer=prefer,
            opts=LocalOpts(budget=6, seed=0,
                           bench_opts=BenchOpts(n_iters=2,
                                                target_secs=0.0002)))
        assert res.sims
        seen = self._seen_counts(res.sims)
        assert 1 in seen and len(seen) >= 2

    def test_mcts_searches_chunks(self):
        from tenzing_tpu.solve.mcts import MctsOpts, explore

        bufs, _ = make_blocked_buffers(ATTN, seed=0)
        plat = Platform.make_n_lanes(1)
        res = explore(
            _attn_graph(), plat, self._bench(plat, bufs),
            MctsOpts(n_iters=12, seed=3,
                     bench_opts=BenchOpts(n_iters=2, target_secs=0.0002),
                     screen_opts=BenchOpts(n_iters=2, target_secs=0.0002)))
        seen = self._seen_counts(res.sims)
        assert len(seen) >= 2


class TestFeatureMarkers:
    def test_directive_features_counted(self):
        from tenzing_tpu.learn.features import FEATURE_NAMES, featurize
        from tenzing_tpu.runtime.fused import FuseTile

        seq = Sequence([ChunkDirective("ffn_0", 2),
                        ChunkDirective("attn_1.xla", 4), FuseTile(8)])
        v = dict(zip(FEATURE_NAMES, featurize(seq)))
        assert v["n_chunk_dir"] == 2.0
        assert v["sum_chunk_counts"] == 6.0
        assert v["n_fuse_tile_dir"] == 1.0
        assert v["sum_fuse_tiles"] == 8.0

    def test_feature_names_append_only(self):
        """Directive coordinates sit at the END of the vector in append
        order (chunk/tile four, then the synth seven): every pre-existing
        coordinate keeps its position, so corpora featurized before an
        append stay consistent."""
        from tenzing_tpu.learn.features import FEATURE_NAMES

        assert FEATURE_NAMES[-11:-7] == ["n_chunk_dir", "sum_chunk_counts",
                                         "n_fuse_tile_dir", "sum_fuse_tiles"]
        assert FEATURE_NAMES[-7:] == ["n_synth_dir", "n_synth_ring",
                                      "n_synth_ringr", "n_synth_rhd",
                                      "n_synth_neighbor", "n_synth_pipe",
                                      "sum_synth_chunks"]
        assert FEATURE_NAMES.index("n_ops") == 0  # prefix unchanged

    def test_save_load_contract_rejects_pre_append_model(self, tmp_path):
        """A model saved under the pre-append name list fails the load
        contract loudly instead of silently mis-predicting with a
        truncated vector."""
        from tenzing_tpu.learn import RidgeEnsemble
        from tenzing_tpu.learn.features import FEATURE_NAMES, featurize

        rng = np.random.default_rng(0)
        old_names = list(FEATURE_NAMES[:-4])
        X = rng.random((8, len(old_names)))
        y = rng.random(8)
        old = RidgeEnsemble(feature_names=old_names).fit(X, y)
        path = str(tmp_path / "old.json")
        old.save(path)
        with pytest.raises(ValueError, match="contract"):
            RidgeEnsemble.load(path, expect_features=list(FEATURE_NAMES))
        # and the current featurizer round-trips
        Xn = np.asarray([featurize(Sequence([ChunkDirective("a", 2)]))])
        cur = RidgeEnsemble(feature_names=list(FEATURE_NAMES)).fit(
            np.repeat(Xn, 8, axis=0), y)
        path2 = str(tmp_path / "new.json")
        cur.save(path2)
        RidgeEnsemble.load(path2, expect_features=list(FEATURE_NAMES))


class TestHiddenCommMeasured:
    def test_overlap_accounting_on_synthetic_timeline(self):
        """hidden_comm_measured_us sums exactly the comm-interval overlap
        with partial intervals — hand-built Gantt, no device."""
        from tenzing_tpu.core.chunking import hidden_comm_measured_us
        from tenzing_tpu.obs.attrib.analysis import Attribution
        from tenzing_tpu.obs.attrib.timeline import OpRecord, OpTimeline

        class FakeXfer:
            KIND = "all_to_all_start"  # in bench/model.py ICI_KINDS

            def name(self):
                return "a2a_0"

        class FakeOp:
            KIND = "noop"

            def name(self):
                return "x"

        ops = [ChunkDirective("ffn_0", 2), FakeOp(), FakeXfer(), FakeOp()]
        recs = [
            OpRecord("ffn_0.chunk.c2", "", "host", None, (0,), 0.0, 0.0),
            OpRecord("ffn_0.c2p0", "", "device", 0, (1,), 100.0, 0.0),
            OpRecord("a2a_0", "", "device", 1, (2,), 80.0, 60.0),
            OpRecord("ffn_0.c2p1", "", "device", 0, (3,), 100.0, 100.0),
        ]
        at = Attribution(timeline=OpTimeline(records=recs))
        # comm [60, 140) overlaps p0 [0,100) by 40 and p1 [100,200) by 40
        assert hidden_comm_measured_us(ops, at) == pytest.approx(80.0)
        # unchunked schedule: nothing to attribute
        assert hidden_comm_measured_us([FakeXfer()], at) == 0.0
