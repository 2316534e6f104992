"""One decode step of latent attention over a paged latent cache
(models/latent_attention.py) against the plain reference
(models/latent_attention_reference.py), at toy widths on the CPU (Pallas in
interpret mode).

Eight sequences of unequal length (one shorter than a page, one a page and
a key, none a multiple of the page) in four groups, pages of 8 tokens laid
out through a shuffled table, two layers.
"""

import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.core.platform import Platform
from tenzing_tpu.core.state import ChooseOp, State
from tenzing_tpu.models.latent_attention import (
    LatentDecodeArgs,
    MlaEngineChoice,
    block_table,
    buffer_shapes,
    decode_graph,
    decode_plan,
    dense_caches,
    make_decode_buffers,
)
from tenzing_tpu.models.latent_attention_reference import (
    absorbed,
    published,
    yarn_scale,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.ops.attention_pallas import (
    attn_block_pallas,
    attn_fused_pallas,
    mla_decode_pallas,
    mla_fold_pallas,
    paged_tiles,
)
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.verify.soundness import ScheduleVerifier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENS = (3, 9, 13, 17, 26, 31, 44, 61)
ARGS = LatentDecodeArgs(lens=LENS, heads=4, rank=16, rope=8, nope=8, v_dim=8,
                        scale=yarn_scale(8, 8), page=8, groups=4,
                        fold_pages=2, dtype="float32")
LAYERS = ("L0", "L1")
#: the step's shapes the engines are held to the reference on: the groups of
#: neighbours above; a group whose sequences differ by twenty times (1, 1, 1
#: and 8 pages: in the chain's second link three of four have no page); a
#: group a sequence
SHAPES = {
    "neighbours": ARGS,
    "tenfold": dataclasses.replace(
        ARGS, lens=(3, 5, 6, 61, 62, 63, 64, 125), groups=2, fold_pages=4),
    "singles": dataclasses.replace(ARGS, groups=8),
}
ENGINES = {"fused": (".fused",), "chain": (".chain", ".pallas"),
           "xla": (".chain", ".xla")}
#: the widest row's gap a fault must pass and a sound float32 run stay far
#: under (the benchmark's limit is for bfloat16 and lies higher)
ROW_LIMIT = 1e-3


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


def step(args=ARGS, seed=3, table_seed=11, lanes=2):
    bufs = make_decode_buffers(args, LAYERS, seed, table_seed)
    g = decode_graph(args, LAYERS, impl_choice=True)
    plat = Platform.make_n_lanes(lanes)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, bufs


def reference(args, bufs, layer, form=published, **change):
    """``o`` ``(batch, heads, v_dim)`` of one layer by the plain reference:
    every sequence's dense cache with its new row put last.  ``change``:
    ``extra_key`` lets every sequence see the key after its last,
    ``drop_new`` leaves the new row out."""
    out = []
    caches = dense_caches(args, bufs, layer)
    for b, cache in enumerate(caches):
        new = np.concatenate([bufs[f"c_new.{layer}"][b],
                              bufs[f"kr_new.{layer}"][b]])[None]
        rows = [cache] + ([] if change.get("drop_new") else [new])
        if change.get("extra_key"):
            col = args.lens[b] % args.page + 1
            if col < args.page:  # what the open page holds there
                rows.append(bufs[f"Copen.{layer}"][b][:, col][None])
        out.append(form(np.concatenate(rows), bufs[f"q_nope.{layer}"][b],
                        bufs[f"q_rope.{layer}"][b], bufs[f"W_UK.{layer}"],
                        bufs[f"W_UV.{layer}"], args.scale))
    return np.asarray(jnp.stack(out))


def widest_row_gap(o, ref):
    err = np.linalg.norm(o - ref, axis=-1)
    norm = np.linalg.norm(ref, axis=-1)
    return float((err / np.maximum(norm, np.median(norm))).max())


def test_the_scale_is_the_config_s():
    assert round(yarn_scale(), 6) == 0.135234
    assert abs(0.1 * np.log(40) + 1 - 1.36888) < 1e-5


@pytest.mark.parametrize("seed", [0, 1])
def test_absorbed_form_is_the_published_form(seed):
    """In float32 at ``highest`` the two orders of sums agree to rounding:
    relative 2e-5 of the largest entry (some 600 float32 sums a value)."""
    bufs = make_decode_buffers(ARGS, LAYERS, seed, 5)
    for layer in LAYERS:
        a = reference(ARGS, bufs, layer, absorbed)
        p = reference(ARGS, bufs, layer, published)
        assert np.abs(a - p).max() <= 2e-5 * np.abs(p).max()
        assert widest_row_gap(a, p) < 2e-5


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("engine", list(ENGINES))
def test_system_matches_the_plain_reference(engine, shape):
    args = SHAPES[shape]
    g, plat, ex, bufs = step(args)
    seq = drive(g, plat, ENGINES[engine])
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    assert any(n.endswith(ENGINES[engine][-1]) for n in names)
    out = ex.run(seq)
    for layer in LAYERS:
        want = reference(args, bufs, layer)
        np.testing.assert_allclose(np.asarray(out[f"o.{layer}"]), want,
                                   rtol=2e-4, atol=2e-5)
        assert widest_row_gap(np.asarray(out[f"o.{layer}"]), want) < 1e-4
        # the appended row, exact, and no other column touched
        opened = np.array(bufs[f"Copen.{layer}"])
        for b, n in enumerate(args.lens):
            opened[b, :, n % args.page] = np.concatenate(
                [bufs[f"c_new.{layer}"][b], bufs[f"kr_new.{layer}"][b]])
        assert np.array_equal(np.asarray(out[f"Copen.{layer}"]), opened)


@pytest.mark.parametrize("fault", ["extra_key", "drop_new", "swapped_row"])
def test_a_fault_moves_the_widest_row_gap_past_its_limit(fault):
    """One key past a length let in, the new row left out, two rows of the
    table swapped: each reads far over what a sound run reads."""
    g, plat, ex, bufs = step()
    seq = drive(g, plat, (".fused",))
    if fault == "swapped_row":
        table = np.array(bufs["table"])
        table[[5, 7]] = table[[7, 5]]
        out = ex.compile(seq)({**ex.init_bufs, "table": jnp.asarray(table)})
        want = reference(ARGS, bufs, "L0")
    else:
        out = ex.run(seq)
        want = reference(ARGS, bufs, "L0", **{fault: True})
    sound = widest_row_gap(np.asarray(ex.run(seq)["o.L0"]),
                           reference(ARGS, bufs, "L0"))
    assert sound < ROW_LIMIT / 10
    assert widest_row_gap(np.asarray(out["o.L0"]), want) > 30 * ROW_LIMIT


@pytest.mark.parametrize("engine", list(ENGINES))
def test_two_iterations_leave_every_buffer_as_one_leaves_it(engine):
    g, plat, ex, _ = step()
    seq = drive(g, plat, ENGINES[engine])
    once = ex.run(seq)
    twice = ex.compile(seq)(once)
    for name in once:
        assert np.array_equal(np.asarray(once[name]),
                              np.asarray(twice[name])), name


@pytest.mark.parametrize("engine", list(ENGINES))
def test_counters_equal_a_count_from_the_lengths(engine):
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        g, plat, ex, _ = step()
        jax.make_jaxpr(ex.program(drive(g, plat, ENGINES[engine])))(
            ex.init_bufs)
        plan = decode_plan(ARGS)
    finally:
        set_metrics(prev)
    count = {n: reg.counter("mla." + n).value for n in (
        "page_steps", "page_steps_idle", "keys_useful", "keys_computed",
        "appended_rows")}
    layers, page = len(LAYERS), ARGS.page
    tiles = [n // page + 1 for n in LENS]  # sealed pages and the open one
    assert count["appended_rows"] == layers * len(LENS)
    assert count["keys_useful"] == layers * sum(n + 1 for n in LENS)
    assert count["page_steps"] == layers * sum(tiles)
    # a kernel's grid is the pages there are: only the XLA fold computes a
    # link's rectangle, every sequence over the most pages one has there
    assert sum(grp.steps for grp in plan) == sum(tiles)
    assert all(sum(sum(t) for _, t in grp.links) == grp.steps
               for grp in plan)
    grid = sum(tiles) if engine != "xla" else sum(
        grp.rows * max(t) for grp in plan for _, t in grp.links)
    assert count["page_steps_idle"] == layers * (grid - sum(tiles))
    assert count["page_steps_idle"] == {"xla": layers * 4}.get(engine, 0)
    assert count["keys_computed"] == layers * grid * page


def test_the_grouping_is_one_span_of_the_program_s_tracing():
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        decode_plan(ARGS)
    finally:
        set_tracer(prev)
    (span,) = [s for s in tr.spans() if s.name == "mla.plan"]
    assert span.attrs == {"groups": 4, "page_tokens": 8, "rows": 2}


def test_plan_groups_neighbours_and_cuts_chains_by_pages():
    plan = decode_plan(ARGS)
    assert [(g.lead0, g.rows) for g in plan] == [(0, 2), (2, 2), (4, 2),
                                                 (6, 2)]
    # a group's grid: the pages its sequences have, summed (not the
    # longest's by the rows: 4, 6, 8 and 16)
    assert [g.tiles for g in plan] == [(1, 2), (2, 3), (4, 4), (6, 8)]
    assert [g.steps for g in plan] == [3, 5, 8, 14]
    # links of two pages, each with the pages a sequence has in it: the
    # longest group has four, and its shorter sequence none in the last
    assert plan[3].links == ((0, (2, 2)), (16, (2, 2)), (32, (2, 2)),
                             (48, (0, 2)))
    assert plan[0].links == ((0, (1, 2)),)
    assert paged_tiles([62, 45], 8, 48, 16) == [2, 0]
    with pytest.raises(ValueError, match="sorted"):
        LatentDecodeArgs(lens=(9, 3), groups=1)
    menu = MlaEngineChoice(ARGS, plan[1], "L0", True)
    assert [c.name().rsplit(".", 1)[1] for c in menu.choices()] == [
        "chain", "fused"]


def test_table_is_a_permutation_of_the_pool_and_the_graph_s_size():
    table = block_table(ARGS, 11)
    used = np.concatenate([table[b, :n] for b, n in enumerate(ARGS.sealed)])
    assert sorted(used) == list(range(ARGS.pool_pages))
    assert not np.array_equal(used, np.arange(ARGS.pool_pages))
    shapes = buffer_shapes(ARGS, LAYERS)
    assert shapes["C.L0"] == ((ARGS.pool_pages, 24, 8), "float32")
    assert shapes["Copen.L1"] == ((8, 24, 8), "float32")
    g, plat, _, _ = step()
    start = drive(g, plat, (".fused",))
    # append, absorb, four groups and the up-projection a layer
    names = [op.name() for op in start]
    assert sum(n.startswith(("L0.", "L1.")) for n in names) == 2 * 7
    assert sum(n.endswith(".mla_read.fused") for n in names) == 2 * 4


# -- what the shared kernel body traces for the prefill's entry points ---------

PINNED = {
    "fold_state":
        "e3d1bf79917b51ef93843bbb9d99e864b5e621b851482c7fea35f5ba1ee5311f",
    "fold_init_masked":
        "69e779b1b129128fc211b53d08ad8c6dfe3016a6d22ebd9dfecd636790702315",
    "fused_state":
        "4e7811d9e5df514a77e8958ef37f3a2e0af8733f0450b4ce6d0e42d308347de0",
    "fused_finish_in_place":
        "708cd4a19ccf362ea5265d7ca77732e667f6dc8565af9797f29ec9de5792e924",
    "fused_finish_fresh":
        "ce8b40bf3950e1bed2ccf68354349dc4ddeea9627464d54ba06764882404ffc5",
    # the paged two, pinned at PR 40 from PR 39's tree, before the walk's
    # index maps were shared with ``dsa_index``
    "paged_decode":
        "3dcbd4bec305eedb541e64013e342a41468700659488dbde91511626bb6e7e23",
    "paged_fold_state":
        "6a0ff4948724a4d22aea3ae3f4d2f4e59efe2f80ee3790151295c6b4b6eecbec",
}


def _prefill_calls():
    f32 = jnp.float32
    q, k = jnp.zeros((4, 16, 8), f32), jnp.zeros((2, 32, 8), f32)
    st = tuple(jnp.zeros((4, 16, 8), f32) for _ in range(3))
    o = jnp.zeros((4, 64, 8), f32)
    pq = jnp.zeros((4, 4, 24), f32)
    pool, opened = jnp.zeros((6, 24, 8), f32), jnp.zeros((4, 24, 8), f32)
    lens = jnp.asarray([4, 10, 18, 27], jnp.int32)
    table = jnp.zeros((4, 4), jnp.int32)
    po = jnp.zeros((4, 4, 16), f32)
    pst = tuple(jnp.zeros((2, 4, 16), f32) for _ in range(3))
    return {
        "paged_decode": (lambda *a: mla_decode_pallas(
            *a, 0.5, v_dim=16, lead0=2, tiles=(3, 4), interpret=True),
            (pq, pool, opened, lens, table, po)),
        "paged_fold_state": (lambda *a: mla_fold_pallas(
            *a, 0.5, v_dim=16, lead0=2, k_pos=16, tiles=(1, 2),
            interpret=True), (pq, pool, opened, lens, table) + pst),
        "fold_state": (lambda *a: attn_block_pallas(
            *a, 0.5, bkv=16, interpret=True), (q, k, k) + st),
        "fold_init_masked": (lambda q, k, v: attn_block_pallas(
            q, k, v, None, None, None, 0.5, bkv=16, q_pos=16, causal=True,
            window=12, interpret=True), (q, k, k)),
        "fused_state": (lambda *a: attn_fused_pallas(
            *a, 0.5, 16, interpret=True), (q, k, k) + st),
        "fused_finish_in_place": (lambda q, k, v, o: attn_fused_pallas(
            q, k, v, None, None, None, 0.5, 16, q_pos=16, causal=True,
            interpret=True, finish=True, o=o, o_row0=16), (q, k, k, o)),
        "fused_finish_fresh": (lambda q, k, v: attn_fused_pallas(
            q, k, v, None, None, None, 0.5, 16, q_pos=16, causal=True,
            window=12, interpret=True, finish=True), (q, k, k)),
    }


@pytest.mark.parametrize("call", list(PINNED))
def test_prefill_entry_points_trace_what_they_traced(call):
    """``attn_fold`` and ``attn_fused`` share the kernel body the decode
    step extended, ``mla_decode`` and ``mla_fold`` the paged walk the
    sparse step's ``dsa_index`` took over: their jaxprs, kernel body
    included, are pinned.  The two
    unmasked calls are PR 34's to the letter; the three masked ones were
    pinned again at PR 37, which moved a query tile's idle K/V steps in
    front of its folds (``walk_step``: same folds, same order, O to the
    last bit) and changed nothing else they trace."""
    f, operands = _prefill_calls()[call]
    text = str(jax.make_jaxpr(f)(*operands))
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[call]


def test_benchmark_reference_is_the_model_s_reference():
    """``benchmarks/references/mla_paged_decode.py`` imports nothing of the
    program and computes the absorbed form a block of keys at a time
    through its own reading of the table: held here to the published form
    of the model's plain reference on the same data."""
    spec = importlib.util.spec_from_file_location(
        "mla_paged_decode", os.path.join(
            REPO, "benchmarks", "references", "mla_paged_decode.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    bufs = make_decode_buffers(ARGS, LAYERS, 4, 9)
    z = {"lens": LENS, "heads": 4, "rank": 16, "rope": 8, "nope": 8,
         "v_dim": 8, "page": 8, "scale": ARGS.scale}
    for layer in LAYERS:
        got = ref.layer_reference(z, {
            k.split(".")[0]: jnp.asarray(v) for k, v in bufs.items()
            if k.endswith("." + layer) or "." not in k})
        want = reference(ARGS, bufs, layer)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                   atol=2e-5)
