"""One decode step of learned sparse attention over two paged caches
(models/sparse_attention.py) against the plain reference
(models/latent_attention_reference.py), at toy widths on the CPU (Pallas in
interpret mode).

Eight sequences of unequal length in four groups, pages of 8 tokens laid out
through a shuffled table, 16 keys selected (three sequences see fewer, five
more), two layers.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_mla_decode import drive, widest_row_gap

from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models import sparse_attention as sa
from tenzing_tpu.models.latent_attention import (
    LatentDecodeArgs,
    decode_graph,
    make_decode_buffers,
)
from tenzing_tpu.models.latent_attention_reference import (
    index_scores,
    published,
    select,
    sparse_published,
    yarn_scale,
)
from tenzing_tpu.models.sparse_attention import (
    SparseDecodeArgs,
    SparseReadsChoice,
    buffer_shapes,
    candidates,
    dense_caches,
    dsa_graph,
    dsa_plan,
    make_dsa_buffers,
    running_count,
    select_chunks,
    whole_batch,
)
from tenzing_tpu.obs.metrics import MetricsRegistry, set_metrics
from tenzing_tpu.ops import attention_pallas
from tenzing_tpu.runtime.executor import TraceExecutor
from tenzing_tpu.verify.soundness import ScheduleVerifier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENS = (3, 9, 13, 17, 26, 31, 44, 61)
LATENT = LatentDecodeArgs(lens=LENS, heads=4, rank=16, rope=8, nope=8,
                          v_dim=8, scale=yarn_scale(8, 8), page=8, groups=4,
                          dtype="float32")
ARGS = SparseDecodeArgs(LATENT, index_heads=4, index_dim=8, topk=16)
LAYERS = ("L0", "L1")
#: the step's shapes the menus are held to the reference on: the groups of
#: neighbours above (sequences on either side of ``topk``); a group whose
#: sequences differ by twenty times; a selection wider than a page and no
#: multiple of it; a group a sequence
SHAPES = {
    "neighbours": ARGS,
    "tenfold": dataclasses.replace(ARGS, latent=dataclasses.replace(
        LATENT, lens=(3, 5, 6, 61, 62, 63, 64, 125), groups=2)),
    "topk20": dataclasses.replace(ARGS, topk=20),
    "singles": dataclasses.replace(ARGS, latent=dataclasses.replace(
        LATENT, groups=8)),
}
MENUS = {"kernel, by group": (".pallas", ".by_group"),
         "kernel, by layer": (".pallas", ".by_layer"),
         "xla, by group": (".xla", ".by_group"),
         "xla, by layer": (".xla", ".by_layer")}
ROW_LIMIT = 1e-3  # far over a sound float32 run, far under a fault


def step(args=ARGS, seed=3, table_seed=11, lanes=2, bufs=None):
    bufs = bufs or make_dsa_buffers(args, LAYERS, seed, table_seed)
    g = dsa_graph(args, LAYERS, impl_choice=True)
    plat = Platform.make_n_lanes(lanes)
    ex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in bufs.items()})
    return g, plat, ex, bufs


def caches(args, bufs, layer, **change):
    """Per sequence ``(latent cache, index keys)`` dense, each with its new
    row put last.  ``change``: ``extra_key`` lets every sequence see the
    key after its last, ``drop_new`` leaves the new rows out."""
    a = args.latent
    out = []
    for b, (lat, keys) in enumerate(dense_caches(args, bufs, layer)):
        new = (np.concatenate([bufs[f"c_new.{layer}"][b],
                               bufs[f"kr_new.{layer}"][b]])[None],
               bufs[f"kI_new.{layer}"][b][None])
        lat, keys = [lat], [keys]
        if not change.get("drop_new"):
            lat.append(new[0])
            keys.append(new[1])
        col = a.lens[b] % a.page + 1
        if change.get("extra_key") and col < a.page:
            # what the open pages hold there
            lat.append(bufs[f"Copen.{layer}"][b][col, :a.width][None])
            keys.append(bufs[f"KIopen.{layer}"][b][:, col][None])
        out.append((np.concatenate(lat), np.concatenate(keys)))
    return out


def reference(args, bufs, layer, sel=None, **change):
    """``(o, selections)`` of one layer by the plain reference: the
    published form, dense, masked by the reference's own selection (or by
    ``sel``, one array of positions a sequence)."""
    out, picked = [], []
    for b, (cache, keys) in enumerate(caches(args, bufs, layer, **change)):
        s = select(index_scores(keys, bufs[f"qI.{layer}"][b],
                                bufs[f"wI.{layer}"][b]),
                   args.topk) if sel is None else sel[b]
        picked.append(np.asarray(s))
        out.append(sparse_published(
            cache, s, bufs[f"q_nope.{layer}"][b], bufs[f"q_rope.{layer}"][b],
            bufs[f"W_UK.{layer}"], bufs[f"W_UV.{layer}"],
            args.latent.scale))
    return np.asarray(jnp.stack(out)), picked


def selected(args, out, layer):
    """The program's selection, a sorted array a sequence (the slots that
    count)."""
    sel = np.asarray(out[f"sel.{layer}"])
    return [np.sort(sel[b, :n]) for b, n in enumerate(args.picked)]


# -- the selection ---------------------------------------------------------------------

@pytest.mark.parametrize("rows,n,k", [(3, 40, 16), (2, 300, 16),
                                      (4, 20000, 2048), (2, 32773, 100),
                                      (1, 2048, 2048), (16, 16384, 2048),
                                      (5, 129, 128), (2, 16385, 1)])
def test_running_count_and_the_selection(rows, n, k):
    """Equal scores go to the lower position: held to numpy's stable sort
    (what ``lax.top_k`` and the reference's ``select`` give) on scores a
    third of which are rounded to a tenth (many equal) and half of one row
    ``NEG``."""
    rng = np.random.default_rng(n)
    x = rng.standard_normal((rows, n)).astype(np.float32)
    x[:, ::3] = np.round(x[:, ::3], 1)
    x[0, n // 2:] = sa.NEG
    marks = x > 0.3
    assert np.array_equal(np.asarray(running_count(jnp.asarray(marks))),
                          np.cumsum(marks, axis=1))
    want = np.sort(np.argsort(-x, axis=1, kind="stable")[:, :k], axis=1)
    # the positions ascending, as a gather reads them
    assert np.array_equal(np.asarray(select_chunks(jnp.asarray(x), k)), want)
    assert np.array_equal(
        np.sort(np.asarray(jax.lax.top_k(jnp.asarray(x), k)[1]), axis=1),
        want)
    for row, ref in zip(x, want):
        assert np.array_equal(np.sort(np.asarray(select(row, k))), ref)


@pytest.mark.parametrize("value", [0.25, 0.0, -1.5, sa.NEG])
def test_all_scores_equal_selects_the_first_positions(value):
    x = jnp.full((2, 64), value, jnp.float32)
    assert np.array_equal(np.asarray(select_chunks(x, 16)),
                          np.tile(np.arange(16), (2, 1)))


# -- the system against the published form -------------------------------------------

#: every menu choice on every shape
CASES = [(menu, shape) for shape in SHAPES for menu in MENUS]


@pytest.mark.parametrize("menu,shape", CASES)
def test_system_matches_the_plain_reference(menu, shape):
    args = SHAPES[shape]
    g, plat, ex, bufs = step(args)
    seq = drive(g, plat, MENUS[menu])
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    assert any(n.endswith(MENUS[menu][0]) for n in names)
    assert any(".by_layer." in n for n in names) == menu.endswith("layer")
    out = ex.run(seq)
    a = args.latent
    for layer in LAYERS:
        want, picked = reference(args, bufs, layer)
        for b, (got, ref) in enumerate(zip(selected(args, out, layer),
                                           picked)):
            assert np.array_equal(got, ref), (layer, b)
        np.testing.assert_allclose(np.asarray(out[f"o.{layer}"]), want,
                                   rtol=2e-4, atol=2e-5)
        assert widest_row_gap(np.asarray(out[f"o.{layer}"]), want) < 1e-4
        # both appended rows, exact, and nothing else touched
        opened = np.array(bufs[f"Copen.{layer}"])
        keys = np.array(bufs[f"KIopen.{layer}"])
        for b, n in enumerate(a.lens):
            opened[b, n % a.page, :a.width] = np.concatenate(
                [bufs[f"c_new.{layer}"][b], bufs[f"kr_new.{layer}"][b]])
            keys[b, :, n % a.page] = bufs[f"kI_new.{layer}"][b]
        assert np.array_equal(np.asarray(out[f"Copen.{layer}"]), opened)
        assert np.array_equal(np.asarray(out[f"KIopen.{layer}"]), keys)


def test_a_selection_of_every_key_is_dense_latent_attention():
    """``topk >= L_b + 1`` for every sequence: the sparse step gives what
    the dense step (``decode_graph``, the benchmark's ``dsv3-mla-decode``)
    gives on the same caches, to the dense reference's tolerance."""
    args = dataclasses.replace(ARGS, topk=64)
    assert max(args.latent.visible) <= args.topk
    g, plat, ex, bufs = step(args)
    out = ex.run(drive(g, plat, (".pallas", ".by_group")))
    # the same caches in the dense step's layout: a page's keys as columns
    a = args.latent
    dense = make_decode_buffers(a, LAYERS, 0, 11)
    for name in dense:
        kind = name.split(".")[0]
        if kind in ("C", "Copen"):
            dense[name] = np.swapaxes(bufs[name][:, :, :a.width], 1, 2)
        elif name in bufs and kind not in ("qt", "o_lat", "o"):
            dense[name] = bufs[name]
    dg = decode_graph(a, LAYERS)
    dex = TraceExecutor(plat, {k: jnp.asarray(v) for k, v in dense.items()})
    dout = dex.run(drive(dg, plat, (".fused",)))
    for layer in LAYERS:
        want = np.asarray(jnp.stack([published(
            cache, bufs[f"q_nope.{layer}"][b], bufs[f"q_rope.{layer}"][b],
            bufs[f"W_UK.{layer}"], bufs[f"W_UV.{layer}"], a.scale)
            for b, (cache, _) in enumerate(caches(args, bufs, layer))]))
        for got in (out, dout):
            np.testing.assert_allclose(np.asarray(got[f"o.{layer}"]), want,
                                       rtol=2e-4, atol=2e-5)
        for b, got in enumerate(selected(args, out, layer)):
            assert np.array_equal(got, np.arange(a.visible[b]))


def test_equal_scores_go_to_the_lower_position_through_the_program():
    """Every index key of a sequence the same: all its scores are equal, and
    it selects its first ``topk`` positions, whichever way the layer's
    selections are cut."""
    bufs = make_dsa_buffers(ARGS, LAYERS, 5, 11)
    for layer in LAYERS:
        key = bufs[f"KI.{layer}"][0, :, 0].copy()
        for name in (f"KI.{layer}", f"KIopen.{layer}"):
            bufs[name][:] = key[None, :, None]
        bufs[f"kI_new.{layer}"][:] = key
    for how in (".by_group", ".by_layer"):
        g, plat, ex, _ = step(bufs=bufs)
        out = ex.run(drive(g, plat, (".pallas", how)))
        for layer in LAYERS:
            for b, got in enumerate(selected(ARGS, out, layer)):
                assert np.array_equal(got, np.arange(ARGS.picked[b])), how


@pytest.mark.parametrize("fault", ["extra_key", "drop_new", "swapped_row",
                                   "gather_off_by_one"])
def test_a_fault_moves_the_widest_row_gap_past_its_limit(fault, monkeypatch):
    """One key past a length let in, the new rows left out, two rows of the
    table swapped, every row gathered from the position after the one
    selected: each reads far over what a sound run reads against the
    reference over the program's own selection."""
    g, plat, ex, bufs = step()
    seq = drive(g, plat, (".pallas", ".by_group"))
    sound_out = ex.run(seq)
    own = selected(ARGS, sound_out, "L0")
    sound = widest_row_gap(np.asarray(sound_out["o.L0"]),
                           reference(ARGS, bufs, "L0", sel=own)[0])
    assert sound < ROW_LIMIT / 10
    if fault == "swapped_row":
        table = np.array(bufs["table"])
        table[[5, 7]] = table[[7, 5]]
        out = ex.compile(seq)({**ex.init_bufs, "table": jnp.asarray(table)})
        want, ref_sel = reference(ARGS, bufs, "L0")
    elif fault == "gather_off_by_one":
        # where the read's vertex hands the kernel its rows and positions
        real, kernel = sa.sealed_rows, attention_pallas.mla_decode_rows_pallas
        monkeypatch.setattr(sa, "sealed_rows", lambda pool, table, sel,
                            page: real(pool, table, sel + 1, page))
        monkeypatch.setattr(
            attention_pallas, "mla_decode_rows_pallas",
            lambda q, rows, opened, sel, *rest, **kw: kernel(
                q, rows, opened, sel + 1, *rest, **kw))
        _, _, shifted, _ = step()
        out = shifted.run(seq)
        want, ref_sel = reference(ARGS, bufs, "L0",
                                  sel=selected(ARGS, out, "L0"))
    else:
        out = sound_out
        want, ref_sel = reference(ARGS, bufs, "L0", **{fault: True})
    assert widest_row_gap(np.asarray(out["o.L0"]), want) > 30 * ROW_LIMIT
    if fault in ("extra_key", "drop_new"):
        # and the selection is another: a sequence under ``topk`` keys has
        # one selected key more or less than the faulty reading has
        got = selected(ARGS, out, "L0")
        assert any(len(a) != len(b) or not np.array_equal(a, b)
                   for a, b in zip(got, ref_sel))


@pytest.mark.parametrize("menu", list(MENUS))
def test_two_iterations_leave_every_buffer_as_one_leaves_it(menu):
    g, plat, ex, _ = step()
    seq = drive(g, plat, MENUS[menu])
    once = ex.run(seq)
    twice = ex.compile(seq)(once)
    for name in once:
        assert np.array_equal(np.asarray(once[name]),
                              np.asarray(twice[name])), name


# -- the read's kernel against the plain statement ----------------------------------

def selection(args, where: str, rng):
    """``(B, topk)`` positions, ascending a sequence, the visible ones
    first and then positions past the length: ``mixed`` any of a sequence's
    visible keys, ``open`` as many of its open page's as there are and the
    rest from the last sealed pages, ``sealed`` none of its open page's
    (a sequence with fewer sealed keys than it picks takes its first)."""
    a = args.latent
    sel = np.zeros((a.batch, args.topk), np.int32)
    for b, (vis, n) in enumerate(zip(a.visible, args.picked)):
        base = (vis - 1) // a.page * a.page
        if where == "mixed":
            own = rng.choice(vis, n, replace=False)
        elif where == "open":
            own = np.arange(vis)[-n:]
        else:
            own = rng.choice(base, n, replace=False) if base >= n else (
                np.arange(vis)[:n])
        sel[b, :n] = np.sort(own)
        sel[b, n:] = vis + np.arange(args.topk - n)
    return sel


def plain_read(args, bufs, sel, grp, tile, o_lat, layer="L0"):
    """The parent's two vertices: :func:`gather_rows` into the column tile
    ``G`` and ``mla_decode`` over it as one open page a sequence."""
    a = args.latent
    rows = slice(grp.lead0, grp.lead0 + grp.rows)
    cols = sa.gather_rows(bufs[f"C.{layer}"], bufs[f"Copen.{layer}"][rows],
                          bufs["table"][rows], bufs["lens"][rows], sel[rows],
                          a.page, a.width)
    g = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros((a.batch, a.width, args.topk), cols.dtype), cols,
        grp.lead0, 0)
    return attention_pallas.mla_decode_pallas(
        bufs[f"qt.{layer}"], g, g, bufs["picked"],
        jnp.zeros((a.batch, 1), jnp.int32), o_lat, a.scale, v_dim=a.rank,
        lead0=tile.lead0, tiles=tile.tiles)


def rows_read(args, bufs, sel, grp, o_lat, layer="L0"):
    a = args.latent
    rows = slice(grp.lead0, grp.lead0 + grp.rows)
    return attention_pallas.mla_decode_rows_pallas(
        bufs[f"qt.{layer}"],
        sa.sealed_rows(bufs[f"C.{layer}"], bufs["table"][rows], sel[rows],
                       a.page),
        bufs[f"Copen.{layer}"], sel, bufs["lens"], bufs["picked"], o_lat,
        a.scale, v_dim=a.rank, lead0=grp.lead0)


#: dtype x where the selection lies x the table: a permuted table and the
#: identity (``table_seed`` None: page j of the pool is the j-th sealed page)
READS = [(dtype, where, table_seed)
         for dtype in ("float32", "bfloat16")
         for where in ("mixed", "open", "sealed")
         for table_seed in (11, None)]


@pytest.mark.parametrize("dtype,where,table_seed", READS)
def test_rows_kernel_reads_what_gather_and_mla_decode_read(dtype, where,
                                                           table_seed):
    """``mla_decode_rows`` over :func:`sealed_rows` against
    :func:`gather_rows` + ``mla_decode`` over the tile, at the rehearse
    sizes, group by group (three of the four do not start at sequence 0;
    three sequences see fewer keys than ``topk``, and their slots past
    their length, which ``picked`` leaves out, read some row of the pool
    through a clipped table slot in both).  The arithmetic is
    ``mla_decode``'s at ``mla_decode``'s places: the rows written are
    equal to the last bit, and every other row of ``o_lat`` stays."""
    args = dataclasses.replace(ARGS, latent=dataclasses.replace(
        LATENT, dtype=dtype))
    a = args.latent
    rng = np.random.default_rng(7)
    bufs = make_dsa_buffers(args, LAYERS, 3, table_seed or 0)
    if table_seed is None:
        table = np.zeros_like(bufs["table"])
        at = 0
        for b, n in enumerate(a.sealed):
            table[b, :n] = at + np.arange(n)
            at += n
        bufs["table"] = table
    bufs = {k: jnp.asarray(v) for k, v in bufs.items()}
    dt = jnp.dtype(dtype)
    bufs["qt.L0"] = jnp.asarray(
        rng.standard_normal((a.batch, a.heads, a.width)), dt)
    sel = jnp.asarray(selection(args, where, rng))
    in_open = np.asarray(sel) // a.page == (
        (np.asarray(a.visible) - 1) // a.page)[:, None]
    seen = np.arange(args.topk)[None, :] < np.asarray(args.picked)[:, None]
    if where == "open":
        assert (in_open & seen).sum(axis=1).tolist() == [
            min(n, (v - 1) % a.page + 1)
            for n, v in zip(args.picked, a.visible)]
    if where == "sealed":
        assert not (in_open & seen)[3:].any()  # those with 16 sealed keys
    before = jnp.asarray(rng.standard_normal((a.batch, a.heads, a.rank)), dt)
    for grp, tile in dsa_plan(args):
        want = plain_read(args, bufs, sel, grp, tile, before)
        got = rows_read(args, bufs, sel, grp, before)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32)), grp.index
        rows = slice(grp.lead0, grp.lead0 + grp.rows)
        assert not np.array_equal(np.asarray(got[rows], np.float32),
                                  np.asarray(before[rows], np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_kernel_takes_a_selection_in_any_order(dtype):
    """``open_span`` is the run of slots that holds every open-page slot,
    whatever the order: a shuffled selection reads the same set of rows,
    so the same O to the tolerance of another order of float32 sums."""
    args = dataclasses.replace(ARGS, latent=dataclasses.replace(
        LATENT, dtype=dtype))
    a = args.latent
    rng = np.random.default_rng(8)
    bufs = {k: jnp.asarray(v) for k, v in make_dsa_buffers(
        args, LAYERS, 3, 11).items()}
    bufs["qt.L0"] = jnp.asarray(
        rng.standard_normal((a.batch, a.heads, a.width)), jnp.dtype(dtype))
    sel = selection(args, "mixed", rng)
    mixed = sel.copy()
    for b, n in enumerate(args.picked):
        mixed[b, :n] = rng.permutation(sel[b, :n])
    span = np.asarray(attention_pallas.open_span(
        jnp.asarray(mixed), bufs["lens"], a.page))
    in_open = mixed // a.page == ((np.asarray(a.visible) - 1) // a.page)[
        :, None]
    for b in range(a.batch):
        at = np.flatnonzero(in_open[b])
        assert (span[0, b], span[1, b]) == (
            (at[0], at[-1] + 1) if len(at) else (0, 0))
    zero = jnp.zeros((a.batch, a.heads, a.rank), jnp.dtype(dtype))
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" else dict(
        rtol=2e-5, atol=2e-6)
    for grp, _ in dsa_plan(args):
        np.testing.assert_allclose(
            np.asarray(rows_read(args, bufs, jnp.asarray(mixed), grp, zero),
                       np.float32),
            np.asarray(rows_read(args, bufs, jnp.asarray(sel), grp, zero),
                       np.float32), **tol)


#: the read kernel's call, kernel body included, as PR 41 traced it (beside
#: ``test_mla_decode.PINNED``, which holds the kernels it stands next to):
#: rows of 32 bits, moved as they are, and of 16, moved as halves of words
PINNED_ROWS = {
    "float32":
        "5a02d9a2d25f4df3e8be322e3083d69f50784bf59ab4b03d3e4a8f40fb4f2e1e",
    "bfloat16":
        "bafa5c39553d1dbb57e8cc4a841793ae051799f9042015123520742675f2566f",
}


@pytest.mark.parametrize("dtype", list(PINNED_ROWS))
def test_rows_kernel_traces_what_it_traced(dtype):
    import hashlib

    dt = jnp.dtype(dtype)
    operands = (jnp.zeros((4, 4, 24), dt), jnp.zeros((2, 16, 128), dt),
                jnp.zeros((4, 8, 128), dt), jnp.zeros((4, 16), jnp.int32),
                jnp.asarray([4, 10, 18, 27], jnp.int32),
                jnp.asarray([4, 10, 16, 16], jnp.int32),
                jnp.zeros((4, 4, 16), dt))
    text = str(jax.make_jaxpr(
        lambda *a: attention_pallas.mla_decode_rows_pallas(
            *a, 0.5, v_dim=16, lead0=2, interpret=True))(*operands))
    assert "mla_decode_rows" in text
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_ROWS[dtype]


# -- tracing ---------------------------------------------------------------------------

@pytest.mark.parametrize("menu", list(MENUS))
def test_counters_equal_a_count_from_the_lengths(menu):
    reg = MetricsRegistry()
    prev = set_metrics(reg)
    try:
        g, plat, ex, _ = step()
        jax.make_jaxpr(ex.program(drive(g, plat, MENUS[menu])))(ex.init_bufs)
        plan = dsa_plan(ARGS)
    finally:
        set_metrics(prev)
    count = {n: reg.counter("dsa." + n).value for n in (
        "keys_indexed", "keys_indexed_computed", "select_candidates",
        "select_candidates_padded", "rows_gathered", "appended_rows",
        "row_dmas", "tile_bytes_via_hbm")}
    layers, page, k = len(LAYERS), LATENT.page, ARGS.topk
    visible = sum(n + 1 for n in LENS)
    tiles = [n // page + 1 for n in LENS]
    assert count["appended_rows"] == layers * 2 * len(LENS)
    assert count["keys_indexed"] == layers * visible
    # the kernel scores the pages there are, whole; XLA a group's rectangle
    rect = sum(grp.rows * max(grp.tiles) for grp, _ in plan)
    assert count["keys_indexed_computed"] == layers * page * (
        rect if menu.startswith("xla") else sum(tiles))
    assert count["select_candidates"] == layers * visible
    # a selection is handed its rectangle: a group's, or the layer's
    handed = [whole_batch(plan)] if menu.endswith("layer") else [
        grp for grp, _ in plan]
    assert count["select_candidates_padded"] == layers * sum(
        grp.rows * max(max(grp.tiles) * page, k) for grp in handed)
    assert sum(grp.rows * candidates(ARGS, grp) for grp in handed) == (
        512 if menu.endswith("layer") else 272)
    assert count["rows_gathered"] == layers * len(LENS) * k
    # how the rows reach the read: by XLA's one gather, through HBM once as
    # whole rows (float32 here), and by no DMA of the kernel's own
    assert count["row_dmas"] == 0
    assert count["tile_bytes_via_hbm"] == count["rows_gathered"] * 128 * 4
    # the read is no paged walk: none of the dense step's counters moves
    for name in ("page_steps", "page_steps_idle", "keys_useful",
                 "keys_computed"):
        assert reg.counter("mla." + name).value == 0


def test_the_plan_is_one_span_of_the_program_s_tracing():
    from tenzing_tpu.obs.tracer import Tracer, set_tracer

    tr = Tracer(enabled=True)
    prev = set_tracer(tr)
    try:
        plan = dsa_plan(ARGS)
    finally:
        set_tracer(prev)
    (span,) = [s for s in tr.spans() if s.name == "dsa.plan"]
    assert span.attrs == {"groups": 4, "page_tokens": 8, "topk": 16,
                          "rows": 2}
    # a group over the caches and over the gathered tiles: the same rows,
    # the pages there are against one step a sequence
    assert [(c.lead0, c.tiles, t.lead0, t.tiles) for c, t in plan] == [
        (0, (1, 2), 0, (1, 1)), (2, (2, 3), 2, (1, 1)),
        (4, (4, 4), 4, (1, 1)), (6, (6, 8), 6, (1, 1))]
    assert ARGS.picked == (4, 10, 14, 16, 16, 16, 16, 16)
    assert ARGS.tile.visible == ARGS.picked and ARGS.tile.page == 16


def test_buffers_and_the_graph_s_size():
    shapes = buffer_shapes(ARGS, LAYERS)
    pages = LATENT.pool_pages
    # a latent token is a row of whole lanes, an index page holds columns
    assert ARGS.row == 128 and SparseDecodeArgs(
        LatentDecodeArgs(lens=(1,), groups=1)).row == 640
    assert shapes["C.L0"] == ((pages, 8, 128), "float32")
    assert shapes["Copen.L1"] == ((8, 8, 128), "float32")
    assert shapes["KI.L0"] == ((pages, 8, 8), "float32")
    assert shapes["KIopen.L1"] == ((8, 8, 8), "float32")
    assert shapes["sel.L0"] == ((8, 16), "int32")
    assert shapes["I"] == ((8, 1, 64), "float32")
    assert "G" not in shapes and "tile_table" not in shapes
    assert shapes["picked"] == ((8,), "int32")
    assert shapes["wI.L0"] == ((8, 4), "float32")
    bufs = make_dsa_buffers(ARGS, LAYERS, 0, 11)
    assert not bufs["C.L0"][..., 24:].any() and bufs["C.L0"][..., :24].all()
    assert list(bufs["picked"]) == list(ARGS.picked)
    g, plat, _, _ = step()
    names = [op.name() for op in drive(g, plat, (".pallas", ".by_group"))]
    # two appends, absorb, four chains of three and the up-projection a layer
    assert sum(n.startswith(("L0.", "L1.")) for n in names) == 2 * 16
    for kind, n in (("dsa_index.pallas", 8), ("dsa_select", 8),
                    ("dsa_gather", 0), ("dsa_read", 8), ("index_append", 2)):
        assert sum(x.endswith(kind) for x in names) == n, kind
    # one selection a layer: it waits for every group's index, and every
    # read for it
    seq = drive(g, plat, (".pallas", ".by_layer"))
    assert ScheduleVerifier(g)(seq).ok
    names = [op.name() for op in seq]
    assert sum(n.startswith(("L0.", "L1.")) for n in names) == 2 * 13
    assert [n for n in names if n.endswith("dsa_select")] == [
        "L0.by_layer.dsa_select", "L1.by_layer.dsa_select"]
    at = names.index("L0.by_layer.dsa_select")
    assert all(names.index(f"L0.by_layer.g{i}.dsa_index.pallas") < at
               < names.index(f"L0.by_layer.g{i}.dsa_read")
               for i in range(4))
    plan = dsa_plan(ARGS)
    menu = SparseReadsChoice("L0.dsa_reads", ARGS, plan, "L0")
    assert [c.name().rsplit(".", 1)[1] for c in menu.choices()] == [
        "by_group", "by_layer"]
    assert whole_batch(plan).tiles == (1, 2, 2, 3, 4, 4, 6, 8)
    # without the index's menu the kernel stands
    plain = [op.name() for op in drive(dsa_graph(ARGS, LAYERS), plat)]
    assert sum(n.endswith(".dsa_index") for n in plain) == 8
    assert sum(n.endswith(".dsa_select") for n in plain) == 8


# -- the benchmark's reference ----------------------------------------------------------

def test_benchmark_reference_is_the_model_s_reference():
    """``benchmarks/references/dsa_paged_decode.py`` imports nothing of the
    program, scores a page of keys at a time through its own reading of the
    table and attends in the absorbed order over the gathered rows: held
    here to the published form of the model's plain reference (dense,
    masked by the selection) on the same data."""
    spec = importlib.util.spec_from_file_location(
        "dsa_paged_decode", os.path.join(
            REPO, "benchmarks", "references", "dsa_paged_decode.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    bufs = make_dsa_buffers(ARGS, LAYERS, 4, 9)
    z = {"lens": LENS, "heads": 4, "rank": 16, "rope": 8, "nope": 8,
         "v_dim": 8, "page": 8, "scale": LATENT.scale, "index_heads": 4,
         "index_dim": 8, "topk": 16}
    assert ref.row_width(z) == ARGS.row
    assert tuple(ref.picked(z)) == ARGS.picked
    for layer in LAYERS:
        t = {k.split(".")[0]: jnp.asarray(v) for k, v in bufs.items()
             if k.endswith("." + layer) or "." not in k}
        o, sel, scores = ref.layer_reference(z, t)
        want, picked = reference(ARGS, bufs, layer)
        np.testing.assert_allclose(np.asarray(o), want, rtol=2e-4,
                                   atol=2e-5)
        for b, n in enumerate(ARGS.picked):
            assert np.array_equal(np.sort(np.asarray(sel)[b, :n]), picked[b])
            keys = caches(ARGS, bufs, layer)[b][1]
            np.testing.assert_allclose(
                np.asarray(scores)[b, :LENS[b] + 1], np.asarray(index_scores(
                    keys, bufs[f"qI.{layer}"][b], bufs[f"wI.{layer}"][b])),
                rtol=1e-5, atol=1e-6)
            assert (np.asarray(scores)[b, LENS[b] + 1:] == ref.NEG).all()
