"""Pallas kernel tests (interpret mode — runs anywhere; device execution of the
same kernels is exercised by the TPU bench) and the implementation-ChoiceOp
search path (reference ChoiceOp menu, operation.hpp:90-93 / state.cpp:61-65)."""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")


def _band(m, bw, nnz, seed=0):
    from tenzing_tpu.models.spmv import random_band_matrix

    return random_band_matrix(m, bw, nnz, seed=seed)


class TestEllSpmvPallas:
    def test_matches_reference_matvec(self):
        from tenzing_tpu.ops import ell_spmv_pallas

        a = _band(300, 40, 3000, seed=1)
        v, c = a.to_slab()
        x = np.random.default_rng(0).random(a.n, dtype=np.float32)
        got = ell_spmv_pallas(jnp.asarray(v.T), jnp.asarray(c.T), jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(got), a.matvec(x), rtol=2e-3)

    def test_wide_slab_and_row_padding(self):
        # slab wider than one vreg (w > 128) and m not a block multiple
        from tenzing_tpu.ops import ell_spmv_pallas

        a = _band(67, 300, 67 * 150, seed=2)
        v, c = a.to_slab()
        assert v.shape[1] > 128
        x = np.random.default_rng(1).random(a.n, dtype=np.float32)
        got = ell_spmv_pallas(jnp.asarray(v.T), jnp.asarray(c.T), jnp.asarray(x),
                              block_m=32)
        np.testing.assert_allclose(np.asarray(got), a.matvec(x), rtol=2e-3)

    def test_supports_gate(self):
        from tenzing_tpu.ops.spmv_pallas import LANES, MAX_X_BLOCKS, supports

        assert supports(LANES * MAX_X_BLOCKS)
        assert not supports(LANES * MAX_X_BLOCKS + 1)

    def test_pallas_op_fallback_large_x(self):
        # SpMVPallasOp guards on supports(): huge x silently takes the XLA path
        from tenzing_tpu.models.spmv import SpMVPallasOp
        from tenzing_tpu.ops.spmv_pallas import LANES, MAX_X_BLOCKS

        n = LANES * MAX_X_BLOCKS + LANES
        rng = np.random.default_rng(0)
        bufs = {
            "x": jnp.asarray(rng.random(n, dtype=np.float32)),
            # the workload's layout: (w, m), matrix rows along the lanes
            "vals": jnp.asarray(rng.random((3, 16), dtype=np.float32)),
            "cols": jnp.asarray(rng.integers(0, n, size=(3, 16)), jnp.int32),
            "rows": jnp.arange(16, dtype=jnp.int32),  # every row holds entries
            "y": jnp.zeros(16, jnp.float32),
        }
        out = SpMVPallasOp("k", "x", "y", "vals", "cols", "rows").apply(bufs, None)
        want = np.sum(np.asarray(bufs["vals"]) * np.asarray(bufs["x"])[np.asarray(bufs["cols"])], axis=0)
        np.testing.assert_allclose(np.asarray(out["y"]), want, rtol=1e-5)


class TestImplChoiceSearch:
    """The kernel menu is part of the searched space: a ChooseOp decision per
    implementation, and every completed schedule computes the right answer."""

    def _graph(self):
        from tenzing_tpu.core.graph import Graph
        from tenzing_tpu.models.spmv import SpMVCompound

        g = Graph()
        g.start_then(SpMVCompound(impl_choice=True))
        g.then_finish(SpMVCompound(impl_choice=True))
        return g

    def test_choice_decisions_enumerated(self):
        from tenzing_tpu.core.platform import Platform
        from tenzing_tpu.core.state import ChooseOp, ExpandOp, State

        plat = Platform.make_n_lanes(1)
        s = State(self._graph())
        (d,) = s.get_decisions(plat)
        assert isinstance(d, ExpandOp)
        s = s.apply(d)
        chooses = [d for d in s.get_decisions(plat) if isinstance(d, ChooseOp)]
        # spmv_local offers both kernels at the initial frontier
        descs = {d.choice.name() for d in chooses}
        assert "spmv_local.xla" in descs and "spmv_local.pallas" in descs

    def test_both_impls_compute_correctly(self):
        from tenzing_tpu.core.platform import Platform
        from tenzing_tpu.models.spmv import make_spmv_buffers
        from tenzing_tpu.runtime.executor import TraceExecutor
        from tenzing_tpu.solve.dfs import get_all_sequences

        bufs, want = make_spmv_buffers(m=96, nnz_per_row=4, bw=12, seed=3)
        plat = Platform.make_n_lanes(1)
        seqs = get_all_sequences(self._graph(), plat, max_seqs=40)
        names = [";".join(op.name() for op in s.sequence) for s in seqs]
        pallas_scheds = [
            s for s, n in zip(seqs, names) if ".pallas" in n
        ]
        xla_scheds = [s for s, n in zip(seqs, names) if ".pallas" not in n]
        assert pallas_scheds and xla_scheds
        ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
        for sched in (pallas_scheds[0], xla_scheds[0]):
            out = ex.run(sched.sequence)
            np.testing.assert_allclose(np.asarray(out["y"]), want, rtol=2e-3)


class TestFfnPallas:
    def test_single_matches_xla(self):
        import jax

        from tenzing_tpu.ops.ffn_pallas import ffn_pallas

        rng = np.random.default_rng(0)
        x = rng.standard_normal((37, 8)).astype(np.float32)  # ragged rows
        w1 = rng.standard_normal((8, 16)).astype(np.float32)
        w2 = rng.standard_normal((16, 8)).astype(np.float32)
        want = jax.nn.gelu(x @ w1) @ w2
        got = ffn_pallas(jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2),
                         interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_batched_tiles_hidden_dim(self):
        """Ragged rows AND a hidden dim that is not a multiple of the tile:
        the zero-padded hidden tiles must contribute exactly 0."""
        import jax

        from tenzing_tpu.ops.ffn_pallas import ffn_pallas_batched

        rng = np.random.default_rng(1)
        # dff=520 > the 512 hidden tile: two k-tiles, the second zero-padded
        # by 504 — exercises both the in-place accumulation and the padding
        e, c, d, dff = 2, 11, 8, 520
        x = rng.standard_normal((e, c, d)).astype(np.float32)
        w1 = rng.standard_normal((e, d, dff)).astype(np.float32)
        w2 = rng.standard_normal((e, dff, d)).astype(np.float32)
        want = np.stack([
            np.asarray(jax.nn.gelu(x[i] @ w1[i]) @ w2[i]) for i in range(e)
        ])
        got = ffn_pallas_batched(jnp.asarray(x), jnp.asarray(w1),
                                 jnp.asarray(w2), interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


# -- moved from the deleted version-compat suite: these test the kernels and
# the shared out_struct helper, now through the installed jax directly


def test_out_struct_shapes_and_dtype():
    from tenzing_tpu.ops.common import out_struct

    s = out_struct((3, 5), jnp.float32, jnp.zeros((3, 5)))
    assert tuple(s.shape) == (3, 5) and s.dtype == jnp.float32
    assert s.vma == frozenset()


def test_fused_attention_kernel_runs_with_compiler_params():
    """A kernel that passes pltpu.CompilerParams compiles and runs in
    interpret mode on the installed jax."""
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    b, n, d = 1, 8, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, d)), jnp.float32)
    acc = jnp.zeros((b, n, d))
    m = jnp.full((b, n, d), -1e30)
    l = jnp.zeros((b, n, d))
    acc2, m2, l2 = attn_fused_pallas(q, k, v, acc, m, l, 1.0, bkv=n)
    o = np.asarray(acc2 / l2)
    s = np.asarray(q) @ np.asarray(k).transpose(0, 2, 1)
    p = np.exp(s - s.max(axis=2, keepdims=True))
    p /= p.sum(axis=2, keepdims=True)
    np.testing.assert_allclose(o, p @ np.asarray(v), rtol=1e-5, atol=1e-5)


# (first row of the query block in O, its rows): whole tiles of 8 in place
# (at row 0, inside O, the short last block), then the two cases that land
# by dynamic_update_slice: a ragged count (its pad rows sliced off) and a
# block that starts inside a tile
FINISH_BLOCKS = {"at_row_0": (0, 16), "inside_o": (16, 16),
                 "last_short_tile": (36, 4), "ragged_rows": (8, 20),
                 "off_the_tile": (20, 16)}


@pytest.mark.parametrize("block", list(FINISH_BLOCKS))
@pytest.mark.parametrize("window", [None, 12], ids=["full", "window"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_finishing_kernel_is_the_state_then_the_division(
        dtype, window, block, monkeypatch):
    """``attn_fused_pallas(finish=True)`` writes, to the last bit, what the
    kernel that hands on its state followed by ``(acc / l).astype`` gives:
    as fresh rows, and into its own rows of an O whose other rows keep what
    they held.  Four query heads over two K/V heads."""
    import jax

    from tenzing_tpu.ops import attention_pallas
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    monkeypatch.setattr(attention_pallas, "Q_TILE", 8)
    q0, rows = FINISH_BLOCKS[block]
    n, d = 40, 8
    rng = np.random.default_rng(q0 + rows)
    big_q = jnp.asarray(rng.standard_normal((4, n, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((2, n, d)), dtype)
            for _ in range(2))
    q = big_q[:, q0:q0 + rows]
    mask = dict(bkv=4, q_pos=q0, causal=True, window=window)
    acc, m, l = attn_fused_pallas(q, k, v, None, None, None, 0.35, **mask)
    want = (acc / l).astype(dtype)
    fresh = attn_fused_pallas(q, k, v, None, None, None, 0.35, finish=True,
                              **mask)
    assert fresh.dtype == want.dtype and fresh.shape == want.shape
    assert np.array_equal(np.asarray(fresh, np.float32),
                          np.asarray(want, np.float32))
    o = jnp.full((4, n, d), 7.0, dtype)  # the sentinel
    got = attn_fused_pallas(q, k, v, None, None, None, 0.35, finish=True,
                            o=o, o_row0=q0, **mask)
    assert got.dtype == o.dtype
    assert np.array_equal(
        np.asarray(got, np.float32),
        np.asarray(o.at[:, q0:q0 + rows].set(want), np.float32))
    traced = str(jax.make_jaxpr(lambda o: attn_fused_pallas(
        q, k, v, None, None, None, 0.35, finish=True, o=o, o_row0=q0,
        **mask))(o))
    in_place = block in ("at_row_0", "inside_o", "last_short_tile")
    assert ("dynamic_update_slice" in traced) != in_place


@pytest.mark.parametrize("q_pos", [0, 4], ids=["at_0", "at_4"])
@pytest.mark.parametrize("window", [None, 12], ids=["full", "window"])
def test_a_query_tile_idles_first_and_ends_on_a_fold(window, q_pos):
    """``walk_step``: a query tile that sees fewer K/V tiles than the grid
    has steps sits on its first tile through the steps it has to spare,
    then folds ``first .. last`` in order, so its last step is a fold (the
    next tile's operands are fetched behind it) wherever it sees a key."""
    from tenzing_tpu.ops.attention_pallas import (
        _Plan,
        visible_tiles,
        walk_step,
    )

    bq, bkv, tiles = 8, 4, 12
    spans = [visible_tiles(_Plan(0.0, bq, bkv, tiles, 0, True, window, True),
                           q_pos + j * bq, 0, max, min) for j in range(3)]
    steps = max(last - first + 1 for first, last in spans)
    plan = _Plan(0.0, bq, bkv, tiles, steps, True, window, True)
    assert len({last - first for first, last in spans}) > 1  # some idle
    for first, last in spans:
        walk = [walk_step(plan, first, last, t) for t in range(steps)]
        spare = steps - (last - first + 1)
        assert [first + int(w) for w, _ in walk] == (
            [first] * spare + list(range(first, last + 1)))
        assert [bool(live) for _, live in walk] == (
            [False] * spare + [True] * (last - first + 1))
    # no mask: every tile, in order, every step a fold
    plain = _Plan(0.0, bq, bkv, tiles, tiles, False, None, True)
    assert [walk_step(plain, 0, tiles - 1, t) for t in range(tiles)] == [
        (t, True) for t in range(tiles)]


def test_finishing_kernel_refuses_a_state_it_would_not_hand_on():
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    q = jnp.zeros((1, 8, 8))
    with pytest.raises(ValueError, match="opens their state"):
        attn_fused_pallas(q, q, q, q, q, q, 1.0, finish=True)
    with pytest.raises(ValueError, match="finishes its rows"):
        attn_fused_pallas(q, q, q, None, None, None, 1.0, o=q)


def test_halo_and_rdma_modules_build_their_compiler_params():
    """Module-level pltpu.CompilerParams construction: every field the
    kernels pass is one the installed class knows (none is dropped)."""
    import dataclasses

    from jax.experimental.pallas import tpu as pltpu

    import tenzing_tpu.ops.halo_pallas as hp
    import tenzing_tpu.ops.rdma  # noqa: F401

    assert isinstance(hp._SEQUENTIAL_GRID, pltpu.CompilerParams)
    known = {f.name for f in dataclasses.fields(pltpu.CompilerParams)}
    assert {"dimension_semantics", "collective_id",
            "has_side_effects"} <= known


# -- the kernel body over a paged latent cache (ISSUE 35) ----------------------

PAGED_LENS = [5, 9, 16, 17, 30, 8, 3, 85]  # visible keys: a page is 8
#: (first sequence, sequences): the last two a group of one sequence and a
#: group whose lengths differ by more than ten times (1, 1 and 11 pages)
PAGED_GROUPS = {"first_three": (0, 3), "last_three": (3, 3), "all": (0, 8),
                "one": (4, 1), "tenfold": (5, 3)}


def _paged_case(seed=0, page=8, d=24, dv=16, n=8):
    """Pools with their pages as columns, a shuffled table, and per
    sequence the dense float64 answer."""
    rng = np.random.default_rng(seed)
    tiles = [-(-x // page) for x in PAGED_LENS]
    sealed = [t - 1 for t in tiles]
    pages = sum(sealed) + 2  # two pages no sequence owns
    perm = rng.permutation(pages)
    table = np.zeros((len(PAGED_LENS), max(tiles)), np.int32)
    at = 0
    for b, cnt in enumerate(sealed):
        table[b, :cnt] = perm[at:at + cnt]
        at += cnt
    pool = rng.standard_normal((pages, d, page)).astype(np.float32)
    opened = rng.standard_normal((len(PAGED_LENS), d, page)).astype(
        np.float32)
    q = rng.standard_normal((len(PAGED_LENS), n, d)).astype(np.float32)

    def want(b, scale):
        rows = [pool[table[b, j]].T for j in range(sealed[b])] + [opened[b].T]
        c = np.concatenate(rows)[:PAGED_LENS[b]].astype(np.float64)
        s = q[b].astype(np.float64) @ c.T * scale
        p = np.exp(s - s.max(1, keepdims=True))
        return (p / p.sum(1, keepdims=True)) @ c[:, :dv]

    arrays = tuple(jnp.asarray(x) for x in (
        q, pool, opened, np.asarray(PAGED_LENS, np.int32), table))
    return arrays, want, page, dv


@pytest.mark.parametrize("group", list(PAGED_GROUPS))
def test_paged_decode_kernel_reads_each_sequence_through_its_table_row(group):
    """``mla_decode``: V is K's first ``v_dim`` columns, every sequence
    stops at its own length, sealed pages come through the table and the
    last tile from the open pool; the rows of sequences outside the group
    keep what they held."""
    from tenzing_tpu.ops.attention_pallas import (
        mla_decode_pallas,
        paged_tiles,
    )

    arrays, want, page, dv = _paged_case()
    lead0, rows = PAGED_GROUPS[group]
    tiles = tuple(paged_tiles(PAGED_LENS[lead0:lead0 + rows], page))
    o = jnp.full((len(PAGED_LENS), 8, dv), 7.0, jnp.float32)
    got = np.asarray(mla_decode_pallas(
        *arrays, o, 0.3, v_dim=dv, lead0=lead0, tiles=tiles))
    for b in range(len(PAGED_LENS)):
        if lead0 <= b < lead0 + rows:
            np.testing.assert_allclose(got[b], want(b, 0.3), rtol=2e-5,
                                       atol=2e-6)
        else:
            assert (got[b] == 7.0).all()


@pytest.mark.parametrize("span", [1, 2, 4])
@pytest.mark.parametrize("group", list(PAGED_GROUPS))
def test_paged_fold_chain_is_the_fused_kernel(group, span):
    """``mla_fold`` links of ``span`` pages through the state in HBM, the
    first opening it: ``acc / l`` at the end is what ``mla_decode`` writes;
    a link past a sequence's last page leaves its state as it came."""
    from tenzing_tpu.ops.attention_pallas import (
        mla_decode_pallas,
        mla_fold_pallas,
        paged_tiles,
    )

    arrays, want, page, dv = _paged_case(seed=1)
    lead0, rows = PAGED_GROUPS[group]
    vis = PAGED_LENS[lead0:lead0 + rows]
    state = (None, None, None)
    for k_pos in range(0, max(vis), span * page):
        tiles = tuple(paged_tiles(vis, page, k_pos, span * page))
        before = state
        state = mla_fold_pallas(*arrays, *state, 0.3, v_dim=dv, lead0=lead0,
                                k_pos=k_pos, tiles=tiles)
        for i, n_vis in enumerate(vis):
            if k_pos >= n_vis and before[0] is not None:
                for new, old in zip(state, before):
                    assert np.array_equal(np.asarray(new[i]),
                                          np.asarray(old[i]))
    chain = np.asarray(state[0] / state[2])
    o = jnp.zeros((len(PAGED_LENS), 8, dv), jnp.float32)
    fused = np.asarray(mla_decode_pallas(
        *arrays, o, 0.3, v_dim=dv, lead0=lead0,
        tiles=tuple(paged_tiles(vis, page))))
    for i in range(rows):
        np.testing.assert_allclose(chain[i], want(lead0 + i, 0.3),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(chain[i], fused[lead0 + i], rtol=2e-6,
                                   atol=2e-7)


def test_paged_kernel_refusals_and_its_tile_count():
    from tenzing_tpu.ops.attention_pallas import (
        mla_fold_pallas,
        paged_tiles,
    )

    assert paged_tiles(PAGED_LENS, 8) == [1, 2, 2, 3, 4, 1, 1, 11]
    assert paged_tiles(PAGED_LENS, 8, 16, 16) == [0, 0, 0, 1, 2, 0, 0, 2]
    assert paged_tiles([8, 9], 8, 8) == [0, 1]
    arrays, _, _, dv = _paged_case()
    with pytest.raises(ValueError, match="starts at a page"):
        mla_fold_pallas(*arrays, None, None, None, 0.3, v_dim=dv, lead0=0,
                        k_pos=4, tiles=(1,) * 8)
    # a grid has a step, and a state that is opened is opened for every row
    state = (jnp.zeros((3, 8, dv)),) * 3
    with pytest.raises(ValueError, match="every call some sequence"):
        mla_fold_pallas(*arrays, *state, 0.3, v_dim=dv, lead0=0, k_pos=24,
                        tiles=(0, 0, 0))
    with pytest.raises(ValueError, match="opens the state"):
        mla_fold_pallas(*arrays, None, None, None, 0.3, v_dim=dv, lead0=2,
                        k_pos=16, tiles=(0, 1, 2))


# -- the paged walk without the softmax: a sparse selection's scores (ISSUE 40) --

@pytest.mark.parametrize("group", list(PAGED_GROUPS))
def test_index_kernel_scores_each_sequence_through_its_table_row(group):
    """``dsa_index``: ``sum_h w_h relu(q_h . k_j)`` for every visible key of
    the group's sequences, sealed pages through the table and the last from
    the open pool, ``NEG`` past a length inside its open page; pages past a
    sequence's last and the rows of sequences outside the group keep what
    they held."""
    from tenzing_tpu.ops.attention_pallas import (
        NEG,
        dsa_index_pallas,
        paged_tiles,
    )

    (q, pool, opened, lens, table), _, page, _ = _paged_case(seed=2)
    w = jnp.asarray(np.random.default_rng(3).standard_normal(
        q.shape[:2]).astype(np.float32))
    lead0, rows = PAGED_GROUPS[group]
    tiles = tuple(paged_tiles(PAGED_LENS[lead0:lead0 + rows], page))
    held = jnp.full((len(PAGED_LENS), 1, table.shape[1] * page), 7.0,
                    jnp.float32)
    got = np.asarray(dsa_index_pallas(q, w, pool, opened, lens, table, held,
                                      lead0=lead0, tiles=tiles))
    q, w, pool, opened, table = (np.asarray(x, np.float64) if x.dtype !=
                                 jnp.int32 else np.asarray(x)
                                 for x in (q, w, pool, opened, table))
    for b, n in enumerate(PAGED_LENS):
        if not lead0 <= b < lead0 + rows:
            assert (got[b] == 7.0).all()
            continue
        sealed = -(-n // page) - 1
        keys = np.concatenate([pool[table[b, j]].T for j in range(sealed)]
                              + [opened[b].T])
        want = (np.maximum(q[b] @ keys.T, 0.0) * w[b][:, None]).sum(0)
        np.testing.assert_allclose(got[b, 0, :n], want[:n], rtol=2e-5,
                                   atol=2e-6)
        assert (got[b, 0, n:(sealed + 1) * page] == NEG).all()
        assert (got[b, 0, (sealed + 1) * page:] == 7.0).all()


def test_index_kernel_refusals():
    from tenzing_tpu.ops.attention_pallas import dsa_index_pallas

    (q, pool, opened, lens, table), _, page, _ = _paged_case()
    w = jnp.ones(q.shape[:2], jnp.float32)
    scores = jnp.zeros((len(PAGED_LENS), 1, table.shape[1] * page))
    with pytest.raises(ValueError, match="a visible key, so a page"):
        dsa_index_pallas(q, w, pool, opened, lens, table, scores, lead0=0,
                         tiles=(1, 0, 2))
    with pytest.raises(ValueError, match="one row a sequence"):
        dsa_index_pallas(q, w, pool, opened, lens, table, scores[:, 0],
                         lead0=0, tiles=(1, 2, 2))


@pytest.mark.parametrize("case", ["cell_g0", "cell_g1", "cell_g2", "cell_g3",
                                  "tenfold", "a_link", "one"])
def test_paged_walk_is_the_pages_there_are(case):
    """The grid of a paged call, step for step: sequence after sequence,
    each on the tiles ``paged_tiles`` counts for it, ascending from the
    range's first; a sequence's first step and its count with every step of
    it; no step for a sequence with no tile in the range, and none that is
    not a tile's."""
    import json
    import os

    import jax

    from tenzing_tpu.ops.attention_pallas import paged_step, paged_tiles

    # ``dsv3-mla-decode.climb``'s cached lengths: its four groups are runs
    # of four of the sorted list, its pages 2048 keys
    with open(os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "benchmarks", "configs",
                           "dsv3-mla-decode.json")) as f:
        cell = sorted(json.load(f)["shapes"]["lens"])
    lens, page, k_pos, span, want = {
        "cell_g0": (cell[0:4], 2048, 0, None, (5, 5, 5, 6)),
        "cell_g1": (cell[4:8], 2048, 0, None, (6, 7, 8, 9)),
        "cell_g2": (cell[8:12], 2048, 0, None, (11, 14, 17, 21)),
        "cell_g3": (cell[12:16], 2048, 0, None, (27, 34, 45, 64)),
        "tenfold": ((700, 7001, 70001), 128, 0, None, (6, 55, 547)),
        # g3's third link of 16 pages: the shortest sequence ended before it
        "a_link": (cell[12:16], 2048, 32 * 2048, 16 * 2048,
                   (0, 2, 13, 16)),
        "one": ((30,), 8, 0, None, (4,)),
    }[case]
    tiles = paged_tiles([n + 1 for n in lens], page, k_pos, span)
    assert tuple(tiles) == want
    steps = [(i, t) for i, n in enumerate(tiles) for t in range(n)]
    assert len(steps) == sum(tiles)
    walked = np.asarray(jax.vmap(lambda s: jnp.stack(
        paged_step(tiles, s)))(jnp.arange(len(steps), dtype=jnp.int32)))
    starts = np.cumsum(tiles) - np.asarray(tiles)
    for s, (i, t) in enumerate(steps):
        assert tuple(walked[s]) == (i, starts[i], tiles[i])
        assert s - walked[s, 1] == t
    # an index map that wants the sequence alone gets the same one
    assert [int(paged_step(tiles, jnp.int32(s), 1)[0])
            for s in range(0, len(steps), 7)] == [
                i for i, _ in steps[::7]]
