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
twin that must not change.  CPU, the Pallas interpreter, toy shards: what is
checked is values and the traced program, never a time.
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
    _padded_shape,
    flatten_face,
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


# -- the one-chip twin never reaches it ---------------------------------------

# the parent's (74bc99f) value-tied bytes for this body, read before the edit
ONE_CHIP_VALUE_TIED_BYTES = 3072


@pytest.mark.needs_pinned_host
def test_one_chip_body_counts_no_window_pack():
    """``halo512.climb``'s graph (``halo_pipeline``: ``PackFlat`` and the
    kernel menu) keeps the XLA slice on its tile-padded grid: no window
    pack and no window unpack, the six packs' index ties, and the
    value-tied bytes the parent read."""
    from tenzing_tpu.models.halo_pipeline import (
        host_buffer_names,
        make_pipeline_buffers,
        naive_order,
    )

    args = HaloArgs(nq=2, lx=4, ly=4, lz=4, radius=1)
    bufs, _ = make_pipeline_buffers(args, seed=0, with_expected=False)
    plat = Platform.make_n_lanes(2)
    ex = TraceExecutor(plat, TraceExecutor.place_host_buffers(
        bufs, host_buffer_names()))
    seq = naive_order(args, Platform.make_n_lanes(1))
    before = _counters()
    jax.jit(ex._stepped_fn(seq.vector())).lower(ex.init_bufs, jnp.int32(1))
    assert tuple(b - a for a, b in zip(before, _counters())) == (
        0, 0, 6, ONE_CHIP_VALUE_TIED_BYTES)


# -- the one-chip twin's packs trace as before ---------------------------------


def _packflat_up_to_pr43(args, d, bufs, ctx):
    """``PackFlat.apply`` as it stood up to PR 43: ``Pack``'s slice at the
    token's zero, flattened."""
    starts, sizes = _face_slices(args, d, "pack")
    z = ctx.tok_index_zero
    axis = 1 + [i for i, v in enumerate(d) if v != 0][0]
    starts = tuple(s + z if i == axis else s for i, s in enumerate(starts))
    sl = jax.lax.dynamic_slice(bufs["U"], starts, sizes)
    return {f"buf_{dir_name(d)}": flatten_face(sl, sizes)}


def _kernel_up_to_pr43(kernel, flat: bool):
    def then(args, d, bufs, ctx):
        starts, sizes = _face_slices(args, d, "pack")
        out = kernel(bufs["U"], tuple(starts), tuple(sizes), interpret=True)
        return {f"buf_{dir_name(d)}":
                out if flat else flatten_face(out, sizes)}

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
    """The one-chip flagship's packs (``halo512.climb`` runs them) are the
    programs they were: the same jaxpr, equation for equation, on a y and a
    z face (which ``Pack`` itself now hands to the window kernel), with
    ``uses_pallas`` and the token's way in as they were."""
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

    assert str(jax.make_jaxpr(now)(u, z)) == str(
        jax.make_jaxpr(before)(u, z))
