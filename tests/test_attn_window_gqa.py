"""Blocked attention with grouped heads, a causal mask, a sliding window and
query blocks (models/ring_attention.py ``BlockedAttention``) against the
plain reference (models/attention_reference.py), at small sizes on the CPU
(Pallas in interpret mode).

The length, 40, is a multiple neither of the window (12) nor of the query
block (16): the last query block has 8 rows.  Exact counts: no visible pair
is left out and no masked pair let in.
"""

import hashlib
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.bench import roofline
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.state import ChooseOp, State
from tenzing_tpu.models.attention_reference import attention
from tenzing_tpu.models.ring_attention import (
    AttnEngineChoice,
    BlockAttnChoice,
    BlockedAttention,
    RingAttention,
    RingAttnArgs,
    make_blocked_buffers,
    period_graph,
    tile_plan,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.verify.soundness import ScheduleVerifier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 12
ARGS = RingAttnArgs(n_devices=5, seq_local=8, head_dim=8, heads=4,
                    kv_heads=2, causal=True, window=WINDOW, q_block=16)
KINDS = {"window": ARGS, "full": replace(ARGS, window=None)}
ENGINES = {"xla_chain": (".chain", ".xla"),
           "pallas_chain": (".chain", ".pallas"),
           "fused": (".fused",)}


def drive(graph, plat, want=()):
    """The schedule of taking, at every menu, the first entry that ends in
    one of ``want``, and else the first decision offered."""
    st = State(graph)
    while not st.is_terminal():
        ds = st.get_decisions(plat)
        pick = None
        for w in want:
            pick = pick or next(
                (d for d in ds if isinstance(d, ChooseOp)
                 and d.choice.name().endswith(w)), None)
        st = st.apply(pick or ds[0])
    return st.sequence


def layer(args, seed=3, **menus):
    """(graph, executor, numpy buffers) of one layer tagged ``L0``."""
    bufs, _ = make_blocked_buffers(args, seed=seed, layer="L0")
    g = period_graph([("L0", args)], **menus)
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, bufs


def dense_mask(args):
    i, j = np.arange(args.seq)[:, None], np.arange(args.seq)[None, :]
    seen = j <= i
    if args.window is not None:
        seen &= j > i - args.window
    return seen


@pytest.mark.parametrize("engine", list(ENGINES))
@pytest.mark.parametrize("kind", list(KINDS))
def test_system_matches_the_plain_reference(kind, engine):
    args = KINDS[kind]
    g, plat, ex, bufs = layer(args, impl_choice=True, fused_choice=True)
    seq = drive(g, plat, ENGINES[engine])
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    assert any(n.endswith(ENGINES[engine][-1]) for n in names)
    out = ex.run(seq)
    want = attention(bufs["Q.L0"], bufs["K.L0"], bufs["V.L0"], True,
                     args.window)
    np.testing.assert_allclose(np.asarray(out["O.L0"]), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("engine", list(ENGINES))
def test_two_iterations_leave_every_buffer_as_one_leaves_it(engine):
    g, plat, ex, _ = layer(ARGS, impl_choice=True, fused_choice=True)
    seq = drive(g, plat, ENGINES[engine])
    once = ex.run(seq)
    twice = ex.compile(seq)(once)
    for name in once:
        assert np.array_equal(np.asarray(once[name]),
                              np.asarray(twice[name])), name


@pytest.mark.parametrize("kind", list(KINDS))
def test_plan_counters_equal_a_count_made_from_the_mask(kind):
    args = KINDS[kind]
    seen = dense_mask(args)
    blk, qb = args.seq_local, args.q_block
    q_blocks = [range(q0, min(q0 + qb, args.seq))
                for q0 in range(0, args.seq, qb)]
    kv_blocks = [range(k0, k0 + blk) for k0 in range(0, args.seq, blk)]
    rects = [seen[np.ix_(list(q), list(k))]
             for q in q_blocks for k in kv_blocks]
    visible = [r for r in rects if r.any()]
    for engine in ENGINES:
        reg = MetricsRegistry()
        prev = set_metrics(reg)
        try:
            g, plat, ex, _ = layer(args, impl_choice=True, fused_choice=True)
            jax.make_jaxpr(ex.program(drive(g, plat, ENGINES[engine])))(
                ex.init_bufs)
        finally:
            set_metrics(prev)
        count = {n: reg.counter("attn." + n).value for n in (
            "tiles", "tiles_skipped", "tiles_edge", "pairs_useful",
            "pairs_computed")}
        assert count["tiles"] == len(visible)
        assert count["tiles_skipped"] == len(rects) - len(visible)
        assert count["tiles_edge"] == sum(not r.all() for r in visible)
        assert count["pairs_useful"] == args.heads * int(seen.sum())
        assert count["pairs_computed"] >= count["pairs_useful"]
        if engine == "xla_chain":  # whole blocks, the mask comes after
            assert count["pairs_computed"] == args.heads * sum(
                r.size for r in visible)


@pytest.mark.parametrize("state", ["opens", "carries"])
@pytest.mark.parametrize("window", [None, 5, 12])
def test_kernel_skips_tiles_and_masks_edges_as_the_einsum_does(
        window, state, monkeypatch):
    """Several query tiles a block and several K/V tiles a range: tiles with
    no visible key are skipped, edge tiles masked, rows that see nothing in
    the operand left as they came."""
    from tenzing_tpu.models.ring_attention import AttnStep
    from tenzing_tpu.ops import attention_pallas
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    # query tiles of 8 rows (no other test traces these shapes)
    monkeypatch.setattr(attention_pallas, "Q_TILE", 8)

    args = RingAttnArgs(n_devices=1, seq_local=32, head_dim=8, heads=4,
                        kv_heads=2, causal=True, window=window)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((4, 24, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 32, 8)), jnp.float32)
            for _ in range(2))
    st = None
    if state == "carries":
        st = tuple(jnp.asarray(a, jnp.float32) for a in (
            rng.standard_normal((4, 24, 8)),
            np.broadcast_to(rng.standard_normal((4, 24, 1)), (4, 24, 8)),
            np.broadcast_to(rng.random((4, 24, 1)) + 1.0, (4, 24, 8))))
    for q_pos, k_pos in ((16, 0), (16, 16), (40, 8), (0, 32)):
        want = AttnStep("x", 0, args)._update(q, k, v, st, q_pos, k_pos)
        got = attn_fused_pallas(
            q, k, v, *(st or (None,) * 3), args.scale, bkv=4,
            q_pos=q_pos, k_pos=k_pos, causal=True, window=window)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("kind,kinds", [("window", 4), ("full", 5)])
def test_folds_that_sit_alike_under_the_mask_are_one_traced_call(kind, kinds):
    """The kernel's static arguments are how a block sits under the mask
    (the positions' difference, or no mask where no edge crosses it), not
    where: a window layer's 11 folds are 4 kinds of call (a chain's first
    on or below the diagonal, a later one on it or above it), a full
    layer's 20 are 5 (unmasked first or later; on the diagonal first or
    later; above it)."""
    from tenzing_tpu.ops.attention_pallas import attn_block_pallas

    # widths no other test traces, so that the count is this program's
    args = RingAttnArgs(n_devices=8, seq_local=8, head_dim=24, heads=6,
                        kv_heads=3, causal=True, q_block=16,
                        window=8 if kind == "window" else None)
    g, plat, ex, _ = layer(args, impl_choice=True, fused_choice=True)
    seq = drive(g, plat, ENGINES["pallas_chain"])
    folds = sum(op.name().endswith(".pallas") for op in seq)
    assert folds == {"window": 11, "full": 20}[kind]
    jaxpr = jax.make_jaxpr(ex.program(seq))(ex.init_bufs)
    calls = [e.params["jaxpr"] for e in jaxpr.eqns
             if e.params.get("name") == attn_block_pallas.__name__]
    assert len(calls) == folds
    assert len({id(c) for c in calls}) == kinds


def drive_blocks(graph, plat, order, fused):
    """The schedule that takes the query blocks' vertices in ``order``,
    the blocks in ``fused`` on the fused kernel and the others on chains
    of XLA folds."""
    rank = {f".q{i}.": r for r, i in enumerate(order)}

    def key(d):
        name = d.op.name()
        r = next((r for tag, r in rank.items() if tag in name), -1)
        if isinstance(d, ChooseOp):
            block = next(i for i in order if f".q{i}." in name)
            want = ".fused" if block in fused else (".chain", ".xla")
            return (r, not d.choice.name().endswith(want))
        return (r, False)

    st = State(graph)
    while not st.is_terminal():
        st = st.apply(min(st.get_decisions(plat), key=key))
    return st.sequence


@pytest.mark.parametrize("fused", [{1, 2}, {0, 1, 2, 3}],
                         ids=["two_fused", "all_fused"])
def test_four_writers_of_one_o_give_the_reference_in_every_order(fused):
    """A layer's four vertices write one buffer, O, in disjoint rows, with
    no edge between them: two fused kernels that write their rows in place
    and two chains whose finalisers put theirs in (``all_fused``: the
    cell's start point, four kernels that read the layer's whole Q, K and
    V and share no slice).  Each order of the four is sound and gives the
    dense reference's O."""
    import itertools

    args = replace(ARGS, n_devices=8, q_block=16)  # 64 positions, 4 blocks
    g, plat, ex, bufs = layer(args, impl_choice=True, fused_choice=True)
    want = np.asarray(attention(bufs["Q.L0"], bufs["K.L0"], bufs["V.L0"],
                                True, args.window))
    verify = ScheduleVerifier(g)
    seen = set()
    for order in itertools.permutations(range(4)):
        seq = drive_blocks(g, plat, order, fused=fused)
        assert verify(seq).ok
        names = [op.name() for op in seq]
        writers = [n for n in names
                   if n.endswith((".fused", "attn_finalize"))]
        assert [int(n.split(".q")[1][0]) for n in writers] == list(order)
        seen.add(tuple(writers))
        out = ex.program(seq)(ex.init_bufs)  # op by op: no program compiled
        np.testing.assert_allclose(np.asarray(out["O.L0"]), want,
                                   rtol=2e-5, atol=2e-6)
    assert len(seen) == 24


def test_bf16_fused_vertex_finishes_its_rows_in_o_s_dtype():
    """A float32 layer's ``fused_bf16`` entry casts Q, K and V for the MXU;
    the rows it writes are O's own float32, a bfloat16 rounding of the
    operands away from the reference."""
    g, plat, ex, bufs = layer(ARGS, impl_choice=True, fused_choice=True)
    seq = drive(g, plat, (".fused_bf16",))
    assert sum(op.name().endswith(".fused_bf16") for op in seq) == 3
    out = ex.program(seq)(ex.init_bufs)
    assert out["O.L0"].dtype == jnp.float32
    want = attention(bufs["Q.L0"], bufs["K.L0"], bufs["V.L0"], True,
                     ARGS.window)
    gap = np.abs(np.asarray(out["O.L0"]) - np.asarray(want)).max()
    assert 1e-4 < gap < 3e-2


def period_of_four():
    """A period shaped as the benchmark cell's at toy widths: three window
    layers and a full one, four query blocks a layer."""
    full = RingAttnArgs(n_devices=8, seq_local=8, head_dim=8, heads=4,
                        kv_heads=2, causal=True, q_block=16)
    win = replace(full, window=WINDOW)
    layers = [("L0", win), ("L1", win), ("L2", win), ("L3", full)]
    bufs = {}
    for tag, a in layers:
        bufs.update(make_blocked_buffers(a, seed=1, layer=tag)[0])
    g = period_graph(layers, impl_choice=True, fused_choice=True)
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, [tag + "." for tag, _ in layers]


def cell_program(which):
    """(graph, executor, schedule) of the cell's two programs at toy widths:
    the climb's ``start`` point (every query block on the fused kernel) or
    ``naive`` (one lane, every block a chain of kernel folds)."""
    from tenzing_tpu.bench.workloads import attn_fused_prefer
    from tenzing_tpu.solve.local import drive as drive_policy, phase_policy

    g, plat, ex, phases = period_of_four()
    if which == "start":
        seq, _ = drive_policy(g, plat, phase_policy(plat, phases,
                                                    attn_fused_prefer))
    else:
        seq = drive(g, Platform.make_n_lanes(1), (".chain", ".pallas"))
    return g, ex, seq


@pytest.mark.parametrize("which,finalisers,finishes", [
    ("start", 0, 16), ("naive", 16, 0)])
def test_who_finishes_the_rows_of_the_cell_s_two_programs(
        which, finalisers, finishes):
    """The climb's start point (every query block on the fused kernel)
    holds no ``FinalizeAttn``, writes no state buffer and counts 16
    ``attn.fused_finishes`` a traced body; naive (every block a chain of
    kernel folds) holds four finalisers a layer and counts none."""
    from tenzing_tpu.core.operation import unbound
    from tenzing_tpu.models.ring_attention import FinalizeAttn

    g, ex, seq = cell_program(which)
    assert ScheduleVerifier(g)(seq).ok
    fins = [op for op in map(unbound, seq) if isinstance(op, FinalizeAttn)]
    assert len(fins) == finalisers
    assert not any(f.fusible() for f in fins)
    state = {n for n in ex.init_bufs if n.split(".")[0] in (
        "acc", "m_run", "l_run")}
    assert len(state) == 12
    written = {w for op in seq for w in op.writes()}
    assert bool(written & state) == (which == "naive")
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        jaxpr, out = jax.make_jaxpr(ex.program(seq), return_shape=True)(
            ex.init_bufs)
    finally:
        set_metrics(prev)
    assert reg.counter("attn.fused_finishes").value == finishes
    # a dict's leaves are in the order of its sorted keys: which buffers
    # leave the traced body as the very variable they entered it
    came = dict(zip(sorted(ex.init_bufs), jaxpr.jaxpr.invars))
    left = dict(zip(sorted(out), jaxpr.jaxpr.outvars))
    untouched = {n for n in left if left[n] is came[n]}
    assert {n for n in left if n.startswith("O.")}.isdisjoint(untouched)
    assert (state <= untouched) == (which == "start")


def _fused_call(args, qb, q, k, v, o, whole, bkv=None, **more):
    """``attn_fused_pallas`` as :class:`FusedBlockAttn` calls it for query
    block ``qb``, writing its rows of ``o``: handed the layer's Q, K and V
    with the block's rows and key range (``whole``), or the rows and keys
    sliced out first, as before ISSUE 37."""
    from tenzing_tpu.ops.attention_pallas import attn_fused_pallas

    blk = args.seq_local
    k0, keys = qb.blocks[0] * blk, len(qb.blocks) * blk
    if whole:
        at = dict(q_row0=qb.q0, rows=qb.rows, k_row0=k0, keys=keys)
    else:
        q = q[:, qb.q0:qb.q0 + qb.rows]
        k, v, at = k[:, k0:k0 + keys], v[:, k0:k0 + keys], {}
    return attn_fused_pallas(
        q, k, v, None, None, None, args.scale, bkv=bkv or blk,
        q_pos=qb.q0 - k0,
        causal=True, window=args.window, interpret=True, finish=True, o=o,
        o_row0=qb.q0, **at, **more)


@pytest.mark.parametrize("block", ["first", "middle", "last", "off_tile"])
@pytest.mark.parametrize("kind", list(KINDS))
def test_whole_operands_give_the_sliced_call_s_o_bit_for_bit(kind, block):
    """The fused call handed the layer's whole Q, K and V finds its rows
    and keys by index map (no ``dynamic_slice`` in what it traces) and
    writes, to the last bit, the O of the call handed slices, at the
    cell's rehearsal shape (40 positions, query blocks of 16, K/V blocks of
    8: the last block has 8 rows).  ``off_tile``: rows 4..20 are no tile
    of 16 rows of Q, keys 4..20 no tiles of 8 of K and V; the call slices
    all three out itself and still agrees."""
    from tenzing_tpu.models.ring_attention import QBlock

    args = KINDS[kind]
    plan = tile_plan(args)
    if block == "off_tile":
        # offsets inside a tile: a block of the plan moved by four rows
        args, qb = replace(args, seq_local=4), QBlock(None, 4, 16,
                                                      (1, 2, 3, 4), 0)
        bkv, slices = 8, 3  # Q, K and V: all fall back
    else:
        qb = {"first": plan[0], "middle": plan[1], "last": plan[-1]}[block]
        bkv, slices = None, 0
    bufs, _ = make_blocked_buffers(KINDS[kind], seed=5, layer="L0")
    q, k, v = (jnp.asarray(bufs[t + ".L0"]) for t in "QKV")
    o = jnp.asarray(np.random.default_rng(6).standard_normal(q.shape),
                    jnp.float32)
    tok = jnp.zeros((), jnp.int32)
    whole = _fused_call(args, qb, q, k, v, o, True, bkv, tok=tok)
    sliced = _fused_call(args, qb, q, k, v, o, False, bkv)
    traced = str(jax.make_jaxpr(lambda *a: _fused_call(
        args, qb, *a, True, bkv, tok=tok))(q, k, v, o))
    assert traced.count("dynamic_slice") == slices
    assert np.array_equal(np.asarray(whole), np.asarray(sliced))
    rows = slice(qb.q0, qb.q0 + qb.rows)
    assert not np.array_equal(np.asarray(whole)[:, rows],
                              np.asarray(o)[:, rows])
    untouched = np.ones(q.shape[1], bool)
    untouched[rows] = False
    assert np.array_equal(np.asarray(whole)[:, untouched],
                          np.asarray(o)[:, untouched])


@pytest.mark.parametrize("which,in_place", [("start", 16), ("naive", 0)])
def test_what_the_cell_s_two_programs_hand_their_kernels(which, in_place):
    """The climb's start point hands every fused kernel the layer's Q, K
    and V as they lie: its traced body holds no ``dynamic_slice``, no
    buffer takes an ordering token by value (the sixteen vertices take
    theirs by index, onto the positions the kernel prefetches), and
    ``attn.operands_in_place`` counts 16.  Naive's chains of folds slice
    as they did and count none."""
    _, ex, seq = cell_program(which)
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        text = str(jax.make_jaxpr(ex.program(seq))(ex.init_bufs))
    finally:
        set_metrics(prev)
    assert reg.counter("attn.operands_in_place").value == in_place
    assert reg.counter("executor.index_ties").value == in_place
    assert ("dynamic_slice" in text) == (which == "naive")
    tied = reg.counter("executor.value_tied_bytes").value
    assert (tied == 0) == (which == "start")


def test_defaults_trace_to_the_jaxpr_they_had():
    """``RingAttnArgs()``'s defaults (one head group, no mask, no query
    blocks) are the shape the module had before PR 33: the program of the
    first-decision schedule traces to the same jaxpr, to the letter (the
    digest is of the parent commit's)."""
    args = RingAttnArgs(n_devices=4)
    bufs, _ = make_blocked_buffers(args, seed=0)
    g = Graph()
    op = BlockedAttention(args, impl_choice=True, fused_choice=True)
    g.start_then(op)
    g.then_finish(op)
    plat = Platform.make_n_lanes(1)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    text = str(jax.make_jaxpr(ex.program(drive(g, plat)))(ex.init_bufs))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "3ffb66b59823239412fcce35811ad2549311b4ea009f0e67678625749d7b57a4")


def test_menus_drop_the_entries_that_coincide_by_dtype():
    def suffixes(choice):
        return [c.name().rsplit(".", 1)[1] for c in choice.choices()]

    for dtype, low in (("float32", True), ("bfloat16", False)):
        a = replace(ARGS, dtype=dtype)
        qb = tile_plan(a)[0]
        assert suffixes(AttnEngineChoice(a, True, qb=qb, layer="L0")) == (
            ["chain", "fused"] + ["fused_bf16"] * low)
        assert suffixes(BlockAttnChoice("s", 0, a, qb=qb)) == (
            ["xla", "pallas"] + ["pallas_bf16"] * low)


def test_plan_at_the_cell_s_blocks():
    """32k tokens in K/V blocks of 2048 and query blocks of 4096: a chain
    has 2 to 16 folds in the full layer and 2 or 3 in a window layer; a
    period is 32 vertices, each of which finishes its own rows of O (a
    fused vertex in the kernel, a chain with a finaliser of its own)."""
    full = RingAttnArgs(n_devices=16, seq_local=2048, heads=32, kv_heads=4,
                        causal=True, q_block=4096, dtype="bfloat16")
    win = replace(full, window=2048)
    assert [len(q.blocks) for q in tile_plan(full)] == list(range(2, 17, 2))
    assert [len(q.blocks) for q in tile_plan(win)] == [2] + [3] * 7
    assert sum(q.skipped for q in tile_plan(win)) == 8 * 16 - 23
    g = period_graph([("L0", win), ("L1", win), ("L2", win), ("L3", full)],
                     impl_choice=True, fused_choice=True)
    seq = drive(g, Platform.make_n_lanes(1), (".fused",))
    names = [op.name() for op in seq]
    assert sum(n.endswith(".fused") for n in names) == 32
    assert not any(n.endswith("attn_finalize") for n in names)
    chains = [op.name() for op in drive(g, Platform.make_n_lanes(1),
                                        (".chain", ".pallas"))]
    assert sum(n.endswith("attn_finalize") for n in chains) == 32
    assert "L3.q7.attn_finalize" in chains
    # the layers in the order of the residual stream: every vertex of a
    # layer, its blocks' finalisers included, before any of the next
    for order in (names, chains):
        at = [[i for i, n in enumerate(order) if n.startswith(f"L{l}.")]
              for l in range(4)]
        assert all(max(a) < min(b) for a, b in zip(at, at[1:]))


def test_attention_cost_counts_pairs_under_the_mask():
    for n, window in ((40, 12), (40, None), (7, 12), (16, 16)):
        seen = dense_mask(replace(ARGS, n_devices=n, seq_local=1,
                                  window=window))
        assert roofline.attention_pairs(n, True, window) == int(seen.sum())
    assert roofline.attention_pairs(40) == 1600
    c = roofline.attention_cost(1, 40, 8, 2, heads=4, kv_heads=2,
                                causal=True, window=12)
    assert c.flops == 4.0 * 4 * int(dense_mask(ARGS).sum()) * 8
    assert c.hbm_bytes == 2.0 * (4 + 2) * 40 * 8 * 2


def test_the_ring_refuses_a_mask_it_cannot_place():
    with pytest.raises(ValueError, match="positions"):
        RingAttention(replace(ARGS, q_block=None))
    with pytest.raises(ValueError, match="causal"):
        RingAttnArgs(n_devices=2, window=4)


def test_benchmark_reference_is_the_model_s_reference():
    """``benchmarks/references/attn_window_gqa.py`` imports nothing of the
    program: its layer, computed some rows at a time, is held here to the
    model's plain reference, and its control to what it says it is."""
    spec = importlib.util.spec_from_file_location(
        "attn_window_gqa", os.path.join(
            REPO, "benchmarks", "references", "attn_window_gqa.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((4, 40, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 40, 8)), jnp.float32)
            for _ in range(2))
    ref.ROWS = 16  # several chunks of rows, the last one padded
    for window in (None, 12):
        np.testing.assert_allclose(
            np.asarray(ref._layer(q, k, v, window)),
            np.asarray(attention(q, k, v, True, window)),
            rtol=1e-5, atol=1e-6)
    low = ref._layer(q, k, v, 12, ref.FLOAT8_E4M3)
    f8 = lambda t: jax.lax.reduce_precision(t, 4, 3)  # 3 bits of mantissa
    assert float(jnp.max(jnp.abs(f8(k) / k - 1))) > 0.03
    np.testing.assert_allclose(
        np.asarray(low), np.asarray(attention(q, f8(k), f8(v), True, 12)),
        rtol=1e-5, atol=1e-6)
