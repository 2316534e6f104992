"""The mesh halo's y and z faces leave the grid through a window kernel
(ISSUE 44).

``models/halo.py`` ``Pack`` was one ``lax.dynamic_slice`` whatever the face.
XLA has no instruction for a thin slice: it fused the z packs' strided reads
into the exchanges' value ties (4.1 of naive's 9.44 ms an iteration on four
v5e chips at 448^3 a shard) and relayouted the whole grid to feed the fused
packs of an overlap schedule (PERF.md, PR 44).  A face whose thin axis is the
grid's sublane (y) or lane (z) axis now goes through ``ops/halo_pallas.py``
``pack_face_window`` on the shard's own unpadded grid, its ordering token a
scalar-prefetch operand; a lane-thin face leaves the kernel transposed.  The
mirror of tests/test_halo_window_unpack.py: the kernel, the op, the one-chip
twin.  CPU, the Pallas interpreter, toy shards: what is checked is values
and the traced program, never a time.

Since ISSUE 48 the one-chip twin's z faces have the kernel on their menu
(``pack_<d>.window``) and cross their staging buffer turned, whichever
entry packed them; its x and y packs trace as they did.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.bench.driver import naive_schedule
from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import (
    DIRECTIONS,
    HaloArgs,
    Pack,
    _face_slices,
    add_to_graph,
    dir_name,
    engine_overlap_order,
    make_halo_buffers,
)
from tenzing_tpu.models.halo_pipeline import (
    PackFlat,
    _flat_rows,
    _padded_shape,
    flatten_face,
    stage_face,
    staged_sizes,
    unstage_face,
)
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.ops import halo_pallas
from tenzing_tpu.ops.halo_pallas import pack_face_window
from tenzing_tpu.runtime.executor import TraceExecutor

# 454-like: no extent a multiple of its tile, and the high y edge (rows
# 19..21 of 25) sits in another sublane tile than the low one
UNALIGNED = HaloArgs(nq=2, lx=5, ly=19, lz=136, radius=3)
THIN = [d for d in DIRECTIONS if d[0] == 0]  # y and z faces, low and high
THIN_IDS = [dir_name(d) for d in THIN]
DIR_IDS = [dir_name(d) for d in DIRECTIONS]


def _zero():
    return jnp.zeros((), jnp.int32)


@pytest.mark.parametrize("grid", ["unpadded", "tile-padded"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", THIN, ids=THIN_IDS)
def test_window_pack_is_dynamic_slice_to_the_bit(d, dtype, grid):
    """Low and high side, y and z, a 4-byte and a 2-byte grid, the shard's
    own extents and the one-chip twin's tile-padded ones: the kernel's face
    is ``lax.dynamic_slice``'s bit for bit, in the builder's shape (the
    lane-thin face's transposition stays inside ``pack_face_window``)."""
    rng = np.random.default_rng(13)
    shape = UNALIGNED.local_shape()
    if grid == "tile-padded":
        shape = _padded_shape(shape, jnp.dtype(dtype).itemsize)
    u = jnp.asarray(rng.random(shape, dtype=np.float32)).astype(dtype)
    starts, sizes = _face_slices(UNALIGNED, d, "pack")
    got = pack_face_window(u, tuple(starts), tuple(sizes), _zero(),
                           interpret=True)
    want = jax.lax.dynamic_slice(u, starts, sizes)
    assert got.dtype == u.dtype and got.shape == tuple(sizes)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_pack_adapts_to_the_thin_axis(d, monkeypatch):
    """x faces keep ``dynamic_slice``; y and z faces count as Pallas ops and
    call the kernel with the token's zero.  Every pack takes its token by
    index, and the face is the same slice either way."""
    op = Pack(UNALIGNED, d)
    windowed = d[0] == 0
    assert op.INDEX_TIE is True
    assert op.uses_pallas() is windowed
    calls = []
    real = halo_pallas.pack_face_window
    monkeypatch.setattr(
        halo_pallas, "pack_face_window",
        lambda *a, **k: calls.append(a[3]) or real(*a, **k))
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.random(UNALIGNED.local_shape(), dtype=np.float32))
    starts, sizes = _face_slices(UNALIGNED, d, "pack")
    zero = _zero()
    before = get_metrics().counter("halo.window_packs").value
    face = op.apply({"U": u}, SimpleNamespace(tok_index_zero=zero))[
        f"buf_{dir_name(d)}"]
    np.testing.assert_array_equal(
        np.asarray(face), np.asarray(jax.lax.dynamic_slice(u, starts, sizes)))
    assert len(calls) == int(windowed)
    assert all(z is zero for z in calls)
    assert (get_metrics().counter("halo.window_packs").value - before
            == int(windowed))


@pytest.mark.parametrize("d", THIN, ids=THIN_IDS)
def test_window_pack_traced_outside_the_contract_raises(d):
    """No ``tok_index_zero``, no happens-before edge: fail loudly, before
    any kernel is built (the x faces': tests/test_halo_index_tie.py)."""
    u = jnp.zeros(UNALIGNED.local_shape(), jnp.float32)
    with pytest.raises(RuntimeError, match="tok_index_zero"):
        Pack(UNALIGNED, d).apply({"U": u},
                                 SimpleNamespace(tok_index_zero=None))


# -- the mesh program ---------------------------------------------------------

ARGS = HaloArgs(nq=2, lx=8, ly=6, lz=4, radius=2)
MESH = (2, 2, 1)
X_FACE_BYTES = sum(int(np.prod(_face_slices(ARGS, d, "pack")[1])) * 4
                   for d in DIRECTIONS if d[0] != 0)
SCHEDULES = ["naive", "xla", "rdma"]


def _mesh(which: str):
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(MESH), ("x", "y", "z"))
    bufs, specs, want = make_halo_buffers(MESH, ARGS, seed=3)
    plat = Platform.make_n_lanes(2, mesh=mesh, specs=specs)
    g = add_to_graph(Graph(), ARGS, xfer_choice=True)
    seq = (naive_schedule("halo_mesh", g, None) if which == "naive"
           else engine_overlap_order(g, plat, which))
    ex = TraceExecutor(plat, {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in bufs.items()})
    return ex, seq, want


def _counters():
    reg = get_metrics()
    return tuple(reg.counter(name).value for name in (
        "halo.window_packs", "halo.window_unpacks", "executor.index_ties",
        "executor.value_tied_bytes"))


@pytest.mark.needs_shard_map
@pytest.mark.parametrize("which", SCHEDULES)
def test_mesh_body_counts_four_window_packs(which):
    """One traced body of the mesh halo: four faces leave through the
    window kernel and four arrive through its mirror, the packs' and
    unpacks' ten index ties are the ten they were (a pack took its token by
    index before it was a kernel; the six remote-DMA posts of the ``rdma``
    schedule take theirs so too now), and the counted value-tied reads are
    what they were, the two received x faces, never the grid.  The one-shot
    result is the expected grid cell for cell."""
    ex, seq, want = _mesh(which)
    before = _counters()
    jax.jit(ex._stepped_fn(seq.vector())).lower(ex.init_bufs, jnp.int32(1))
    packs, unpacks, ties, tied_bytes = (
        b - a for a, b in zip(before, _counters()))
    assert (packs, unpacks) == (4, 4)
    assert ties == (16 if which == "rdma" else 10)
    assert tied_bytes == X_FACE_BYTES
    np.testing.assert_array_equal(np.asarray(ex.run(seq)["U"]), want)


@pytest.mark.needs_shard_map
def test_pack_token_edge_is_the_kernels_first_operand():
    """The traced repeat-n program hands every window pack a scalar
    prefetch operand that is a value of the program and that the token
    reaches: were it a literal, every order of the packs would trace to the
    same unordered kernel calls.  (That the compiled TPU program keeps it:
    tests/test_tpu_compile.py, ``mesh_halo_loop``.)"""
    from jax.extend import core as jcore

    def subjaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(x, jcore.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jcore.Jaxpr):
                    yield x

    ex, seq, _ = _mesh("xla")
    closed = jax.make_jaxpr(ex._stepped_fn(seq.vector()))(
        ex.init_bufs, jnp.int32(1))
    found = []

    def token(v):
        # the tokens are the program's only float32 scalars
        return (not isinstance(v, jcore.Literal) and v.aval.shape == ()
                and v.aval.dtype == jnp.float32)

    def walk(jaxpr, tokened_in=None):
        tokened = {v: bool(tokened_in and tokened_in[i])
                   for i, v in enumerate(jaxpr.invars)}
        for eqn in jaxpr.eqns:
            ins = [not isinstance(x, jcore.Literal)
                   and (token(x) or tokened.get(x, False))
                   for x in eqn.invars]
            if eqn.primitive.name == "pallas_call" and "halo_window_pack" \
                    in str(eqn.params.get("name", "")) + str(
                        eqn.params.get("name_and_src_info", "")):
                found.append(ins[0])
            for sub in subjaxprs(eqn.params):
                # a call hands its operands on in order; a loop's carry
                # starts over (its tokens are float32 scalars there too)
                walk(sub, ins if len(sub.invars) == len(ins) else None)
            for o in eqn.outvars:
                tokened[o] = any(ins)

    walk(closed.jaxpr)
    assert found == [True] * 4


# -- the one-chip twin: naive never reaches it, the start point does -----------

# the parent's (74bc99f) value-tied bytes for this body, read before the edit
ONE_CHIP_VALUE_TIED_BYTES = 3072


def _one_chip(args, menus: bool):
    from tenzing_tpu.models.halo_pipeline import (
        build_graph,
        host_buffer_names,
        make_pipeline_buffers,
    )

    bufs, want = make_pipeline_buffers(args, seed=0)
    ex = TraceExecutor(Platform.make_n_lanes(6), TraceExecutor.place_host_buffers(
        bufs, host_buffer_names()))
    return ex, build_graph(args, impl_choice=menus, xfer_choice=menus), want


def _body_counts(ex, seq):
    reg = get_metrics()
    names = ("halo.window_packs", "halo.window_unpacks",
             "halo.window_unpacks_turned", "executor.index_ties",
             "executor.value_tied_bytes")
    before = [reg.counter(n).value for n in names]
    jax.jit(ex._stepped_fn(seq.vector())).lower(ex.init_bufs, jnp.int32(1))
    return tuple(reg.counter(n).value - b for n, b in zip(names, before))


@pytest.mark.needs_pinned_host
def test_one_chip_body_counts_no_window_pack():
    """Naive of ``halo512.climb``'s graph (``halo_pipeline``: ``PackFlat``,
    ``UnpackRecv``, no menu) keeps the XLA slice on its tile-padded grid:
    no window pack and no window unpack, the six packs' index ties, and the
    value-tied bytes the parent read."""
    from tenzing_tpu.models.halo_pipeline import naive_order

    args = HaloArgs(nq=2, lx=4, ly=4, lz=4, radius=1)
    ex, _, _ = _one_chip(args, menus=False)
    assert _body_counts(ex, naive_order(args, Platform.make_n_lanes(1))) == (
        0, 0, 0, 6, ONE_CHIP_VALUE_TIED_BYTES)


# the start point's value-tied reads: the x and y unpacks' ``recv`` buffers
# (rows of 128 float32), never the grid and no z face
START_ARGS = HaloArgs(nq=2, lx=8, ly=16, lz=128, radius=2)
START_VALUE_TIED_BYTES = sum(
    _flat_rows(_face_slices(START_ARGS, d, "pack")[1]) * 128 * 4
    for d in DIRECTIONS if d[2] == 0)


@pytest.mark.needs_pinned_host
def test_one_chip_start_point_counts_the_window_pair():
    """The climb's start point (``halo_alias_prefer`` on the menu graph): the
    two z faces leave through ``pack_<d>.window`` and arrive through
    ``unpack_<d>.window``, turned; those four take their token by index
    beside the four other packs, and what is tied by value is the four x
    and y unpacks' received buffers.  One run of it is the expected grid
    cell for cell."""
    from tenzing_tpu.bench.workloads import halo_alias_prefer
    from tenzing_tpu.models.halo_pipeline import HALO_PHASES
    from tenzing_tpu.solve.local import drive, phase_policy

    ex, graph, want = _one_chip(START_ARGS, menus=True)
    seq, _ = drive(graph, ex.platform, phase_policy(
        ex.platform, HALO_PHASES, halo_alias_prefer))
    names = [op.name() for op in seq.vector()]
    assert [n for n in names if n.endswith(".window")] == [
        "pack_mz.window", "pack_pz.window", "unpack_mz.window",
        "unpack_pz.window"]
    assert _body_counts(ex, seq) == (2, 2, 2, 8, START_VALUE_TIED_BYTES)
    np.testing.assert_array_equal(np.asarray(ex.run(seq)["U"]), want)


# -- the one-chip twin's packs: x and y as before, z staged turned -------------


def _slice_up_to_pr43(args, d, bufs, ctx):
    """``Pack``'s slice at the token's zero, as ``PackFlat.apply`` took it
    up to PR 43 (and takes it)."""
    starts, sizes = _face_slices(args, d, "pack")
    z = ctx.tok_index_zero
    axis = 1 + [i for i, v in enumerate(d) if v != 0][0]
    starts = tuple(s + z if i == axis else s for i, s in enumerate(starts))
    return jax.lax.dynamic_slice(bufs["U"], starts, sizes)


def _staged(face, d):
    """The staging order spelled out: a z face turned, then flattened as
    every face was up to PR 47."""
    if d[2]:
        face = jnp.swapaxes(face, 2, 3)
    return flatten_face(face, face.shape)


def _packflat_up_to_pr43(args, d, bufs, ctx):
    return {f"buf_{dir_name(d)}": _staged(
        _slice_up_to_pr43(args, d, bufs, ctx), d)}


def _kernel_up_to_pr43(kernel, flat: bool):
    def then(args, d, bufs, ctx):
        starts, sizes = _face_slices(args, d, "pack")
        out = kernel(bufs["U"], tuple(starts), tuple(sizes), interpret=True)
        return {f"buf_{dir_name(d)}": out if flat else _staged(out, d)}

    return then


# lz a multiple of 128, so the flat kernel is on the y faces' menu
MENU_ARGS = HaloArgs(nq=2, lx=8, ly=8, lz=128, radius=2)
SUBCLASSES = [
    ("PackFlat", lambda: PackFlat, _packflat_up_to_pr43, False, True),
    ("PackXla", lambda: halo_pallas.PackXla, _packflat_up_to_pr43, False,
     True),
    ("PackPallas", lambda: halo_pallas.PackPallas,
     _kernel_up_to_pr43(halo_pallas.pack_face_pallas, False), True, False),
    ("PackPallasB", lambda: halo_pallas.PackPallasB,
     _kernel_up_to_pr43(halo_pallas.pack_face_pallas_batched, False), True,
     False),
    ("PackPallasF", lambda: halo_pallas.PackPallasF,
     _kernel_up_to_pr43(halo_pallas.pack_face_flat_pallas, True), True,
     False),
]


# a y and a z face each; the flat kernel is not on a z face's menu
ON_FACES = [(s, d) for s in SUBCLASSES for d in [(0, 1, 0), (0, 0, -1)]
            if not (s[0] == "PackPallasF" and d[2])]


@pytest.mark.parametrize(
    "sub,d", ON_FACES, ids=[f"{s[0]}-{dir_name(d)}" for s, d in ON_FACES])
def test_one_chip_packs_trace_as_before(sub, d):
    """The one-chip flagship's older packs (``halo512.climb``'s menus hold
    them) are the programs they were, equation for equation, with
    ``uses_pallas`` and the token's way in as they were: a y face to the
    letter, a z face with one ``swapaxes`` between the read it always was
    and the flatten it always was (the staging order, ISSUE 48)."""
    _, cls, then, pallas, index_tie = sub
    op = cls()(MENU_ARGS, d)
    assert bool(op.INDEX_TIE) is index_tie
    assert op.uses_pallas() is pallas
    u = jnp.zeros(_padded_shape(MENU_ARGS.local_shape(), 4), jnp.float32)
    z = jnp.zeros((), jnp.int32)

    def now(u, z):
        return op.apply({"U": u}, SimpleNamespace(tok_index_zero=z))

    def before(u, z):
        return then(MENU_ARGS, d, {"U": u},
                    SimpleNamespace(tok_index_zero=z))

    text = str(jax.make_jaxpr(now)(u, z))
    assert text == str(jax.make_jaxpr(before)(u, z))
    assert text.count(" transpose[") == (1 if d[2] else 0)


# -- the window pair on the one-chip menu (ISSUE 48) ----------------------------

Z_FACES = [d for d in DIRECTIONS if d[2] != 0]
Z_IDS = [dir_name(d) for d in Z_FACES]
# a padded grid no extent of which is a multiple of its tile before padding
PADDED = HaloArgs(nq=2, lx=5, ly=19, lz=136, radius=3)


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_only_a_lane_thin_z_face_is_staged_turned(d):
    """The one staging order of a direction: ``(nq, sx, sz, sy)`` for a z
    face thinner than y is long, the face's own extents otherwise (every x
    and y face, and a z face that is not lane-thin), and ``stage_face`` /
    ``unstage_face`` are each other's inverse either way."""
    rng = np.random.default_rng(5)
    for args in (PADDED, HaloArgs(nq=2, lx=4, ly=2, lz=8, radius=3)):
        _, sizes = _face_slices(args, d, "pack")
        nq, sx, sy, sz = sizes
        turned = d[2] != 0 and sz < sy
        assert turned is (d[2] != 0 and args is PADDED)
        assert staged_sizes(d, sizes) == (
            (nq, sx, sz, sy) if turned else (nq, sx, sy, sz))
        face = jnp.asarray(rng.random(sizes, dtype=np.float32))
        flat = stage_face(face, d)
        assert flat.shape == (_flat_rows(sizes), 128)
        n = int(np.prod(sizes))
        np.testing.assert_array_equal(
            np.asarray(flat).reshape(-1)[:n],
            np.asarray(jnp.swapaxes(face, 2, 3) if turned else face
                       ).reshape(-1))
        np.testing.assert_array_equal(
            np.asarray(unstage_face(flat, d, sizes)), np.asarray(face))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", Z_FACES, ids=Z_IDS)
def test_window_pair_is_slice_then_update_to_the_bit(d, dtype):
    """``PackWindow`` then ``UnpackWindow`` of a z direction on a
    tile-padded grid, through the ``(rows, 128)`` buffer: the grid is
    ``dynamic_slice`` -> ``dynamic_update_slice``'s bit for bit, a 4-byte
    and a 2-byte grid; both take their token by index, both count as Pallas
    ops, and the counters the mesh path has count them."""
    args = HaloArgs(nq=PADDED.nq, lx=PADDED.lx, ly=PADDED.ly, lz=PADDED.lz,
                    radius=PADDED.radius, dtype=dtype)
    pack = halo_pallas.PackWindow(args, d)
    unpack = halo_pallas.UnpackWindow(args, d)
    name = dir_name(d)
    assert (pack.name(), unpack.name()) == (
        f"pack_{name}.window", f"unpack_{name}.window")
    assert pack.INDEX_TIE is True and unpack.INDEX_TIE is True
    assert pack.uses_pallas() and unpack.uses_pallas()
    rng = np.random.default_rng(17)
    shape = _padded_shape(args.local_shape(), jnp.dtype(dtype).itemsize)
    u = jnp.asarray(rng.random(shape, dtype=np.float32)).astype(dtype)
    ctx = SimpleNamespace(tok_index_zero=_zero())
    reg = get_metrics()
    names = ("halo.window_packs", "halo.window_unpacks",
             "halo.window_unpacks_turned")
    before = [reg.counter(n).value for n in names]
    buf = pack.apply({"U": u}, ctx)[f"buf_{name}"]
    ps, sizes = _face_slices(args, d, "pack")
    assert buf.shape == (_flat_rows(sizes), 128) and buf.dtype == u.dtype
    got = unpack.apply({"U": u, f"recv_{name}": buf}, ctx)["U"]
    us, _ = _face_slices(args, d, "unpack")
    want = jax.lax.dynamic_update_slice(
        u, jax.lax.dynamic_slice(u, ps, sizes), us)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert [reg.counter(n).value - b for n, b in zip(names, before)] == [
        1, 1, 1]


@pytest.mark.parametrize("d", Z_FACES, ids=Z_IDS)
def test_window_entries_stage_what_the_kernels_hold(d):
    """Between the two kernels a z face is never the shell's own shape: the
    pack's traced program holds no ``transpose`` outside its kernel (the
    kernel's turned result is reshaped into the buffer) and neither does
    the unpack's (the buffer is reshaped into the kernel's turned
    operand)."""
    name = dir_name(d)
    u = jnp.zeros(_padded_shape(PADDED.local_shape(), 4), jnp.float32)
    _, sizes = _face_slices(PADDED, d, "pack")
    recv = jnp.zeros((_flat_rows(sizes), 128), jnp.float32)
    ctx = lambda z: SimpleNamespace(tok_index_zero=z)

    def outside_kernels(jaxpr):
        return [e.primitive.name for e in jaxpr.eqns
                if e.primitive.name not in ("pjit", "jit")] + [
            n for e in jaxpr.eqns if e.primitive.name in ("pjit", "jit")
            for n in outside_kernels(e.params["jaxpr"].jaxpr)]

    packed = jax.make_jaxpr(lambda u, z: halo_pallas.PackWindow(
        PADDED, d).apply({"U": u}, ctx(z)))(u, _zero())
    unpacked = jax.make_jaxpr(lambda u, r, z: halo_pallas.UnpackWindow(
        PADDED, d).apply({"U": u, f"recv_{name}": r}, ctx(z)))(
            u, recv, _zero())
    for closed in (packed, unpacked):
        prims = outside_kernels(closed.jaxpr)
        assert prims.count("pallas_call") == 1
        assert "transpose" not in prims


@pytest.mark.parametrize("d", Z_FACES, ids=Z_IDS)
def test_window_entries_traced_outside_the_contract_raise(d):
    """No ``tok_index_zero``, no happens-before edge: both menu entries fail
    loudly, as ``Pack`` and ``Unpack`` do."""
    name = dir_name(d)
    u = jnp.zeros(_padded_shape(PADDED.local_shape(), 4), jnp.float32)
    _, sizes = _face_slices(PADDED, d, "pack")
    recv = jnp.zeros((_flat_rows(sizes), 128), jnp.float32)
    none = SimpleNamespace(tok_index_zero=None)
    with pytest.raises(RuntimeError, match="tok_index_zero"):
        halo_pallas.PackWindow(PADDED, d).apply({"U": u}, none)
    with pytest.raises(RuntimeError, match="tok_index_zero"):
        halo_pallas.UnpackWindow(PADDED, d).apply(
            {"U": u, f"recv_{name}": recv}, none)


def _entries(choice, d):
    return choice(PADDED, d).choices()


PAIRS = [(d, p.name(), q.name())
         for d in Z_FACES
         for p in _entries(halo_pallas.PackChoice, d)
         for q in _entries(halo_pallas.UnpackChoice, d)]


@pytest.mark.parametrize("d,pack,unpack", PAIRS,
                         ids=[f"{p}-{q}" for _, p, q in PAIRS])
def test_every_z_pack_meets_every_z_unpack(d, pack, unpack):
    """The search picks a direction's pack and unpack independently: every
    pack entry of a z direction's menu, through the ``(rows, 128)`` buffer,
    into every unpack entry of it, is ``dynamic_slice`` ->
    ``dynamic_update_slice`` to the bit.  No neighbour of the climb can be
    wrong by its staging order."""
    name = dir_name(d)
    p = next(c for c in _entries(halo_pallas.PackChoice, d)
             if c.name() == pack)
    q = next(c for c in _entries(halo_pallas.UnpackChoice, d)
             if c.name() == unpack)
    rng = np.random.default_rng(23)
    u = jnp.asarray(rng.random(_padded_shape(PADDED.local_shape(), 4),
                               dtype=np.float32))
    ctx = SimpleNamespace(tok_index_zero=_zero())
    buf = p.apply({"U": u}, ctx)[f"buf_{name}"]
    got = q.apply({"U": u, f"recv_{name}": buf}, ctx)["U"]
    ps, sizes = _face_slices(PADDED, d, "pack")
    us, _ = _face_slices(PADDED, d, "unpack")
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.lax.dynamic_update_slice(
            u, jax.lax.dynamic_slice(u, ps, sizes), us)))


def test_the_z_menus_end_in_the_window_pair():
    """Appended behind the entries that were there, so every recorded choice
    index stands; on a z face alone, and only where it is lane-thin (a z
    face thicker than y is long has neither entry and is staged as it is)."""
    thick = HaloArgs(nq=2, lx=4, ly=2, lz=8, radius=3)
    for d in DIRECTIONS:
        for choice in (halo_pallas.PackChoice, halo_pallas.UnpackChoice):
            names = [c.name().rsplit(".", 1)[1]
                     for c in choice(PADDED, d).choices()]
            assert names[:2] == ["xla", "pallas"]
            assert ("window" in names) is (d[2] != 0)
            assert "window" not in names[:-1]
            assert "window" not in [
                c.name().rsplit(".", 1)[1]
                for c in choice(thick, d).choices()]
