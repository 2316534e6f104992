"""The mesh halo writes its y and z ghost shells with an aliased window kernel
(ISSUE 32).

``models/halo.py`` ``Unpack`` was one ``lax.dynamic_update_slice`` whatever
the face; for a face whose thin axis is the grid's sublane (y) or lane (z)
axis XLA does that write in place but 3 cells of a tile at a time (10.7 ms
of a 20.8 ms iteration on four v5e chips at 448^3 a shard: PERF.md, PR 32).
Such a face now goes through ``ops/halo_pallas.py`` ``unpack_face_window``
on the shard's own unpadded grid, its ordering token a scalar-prefetch
operand.  Here the kernel and the op; the mesh program end to end, its
counters and its token edges are in tests/test_halo_index_tie.py.  CPU, the
Pallas interpreter, toy shards: what is checked is values and the traced
program, never a time.

Since ISSUE 47 a z face reaches the kernel TURNED, ``(nq, sx, sz, sy)``, the
form ``pack_face_window`` emits, and the body turns it in VMEM: the padded
``(nq, sx, sy, 3)`` operand (308 MB for 7 MB of cells at the cell's size) is
no operand of the kernel any more.

Since ISSUE 48 the one-chip twin's z faces have the kernel on their menu
(``unpack_<d>.window``, on the turned face its staging buffer holds:
tests/test_halo_window_pack.py has the pair, the menus and the start
point's counters); its older unpacks read a z face through one ``swapaxes``.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tenzing_tpu.core.platform import Platform
from tenzing_tpu.models.halo import (
    DIRECTIONS,
    HaloArgs,
    Unpack,
    _face_slices,
    dir_name,
)
from tenzing_tpu.models.halo_pipeline import UnpackRecv, unflatten_face
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.ops import halo_pallas
from tenzing_tpu.ops.halo_pallas import _shell_block, unpack_face_window
from tenzing_tpu.runtime.executor import TraceExecutor

# 454-like: no extent a multiple of its tile, and the high y shell (rows
# 22..24 of 25) straddles two sublane tiles of 8
UNALIGNED = HaloArgs(nq=2, lx=5, ly=19, lz=136, radius=3)
THIN = [d for d in DIRECTIONS if d[0] == 0]  # y and z faces, low and high
THIN_IDS = [dir_name(d) for d in THIN]
DIR_IDS = [dir_name(d) for d in DIRECTIONS]


def _zero():
    return jnp.zeros((), jnp.int32)


@pytest.mark.parametrize("a0,n,extent,tile,want", [
    (0, 3, 454, 8, (8, 0, 0)),        # the cell's low y shell: one sublane tile
    (451, 3, 454, 8, (8, 56, 3)),     # its high one: [448, 456) of 454
    (0, 3, 454, 128, (128, 0, 0)),    # low z shell: one lane tile
    (451, 3, 454, 128, (128, 3, 67)),  # high: lanes [384, 512) of 454
    (3, 448, 454, 8, (454, 0, 3)),    # a face's long side: the whole axis
    (22, 3, 25, 8, (16, 1, 6)),       # a shell across two tiles: doubled
    (6, 4, 12, 8, (12, 0, 6)),        # nothing smaller holds it: the axis
], ids=["y-low", "y-high", "z-low", "z-high", "long-side", "straddle",
        "whole-axis"])
def test_shell_block(a0, n, extent, tile, want):
    """The block a ``BlockSpec`` addresses holds the whole cut, is a
    multiple of the tile (or the axis), and says where the cut sits."""
    w, b, off = _shell_block(a0, n, extent, tile)
    assert (w, b, off) == want
    assert b * w + off == a0 and off + n <= w
    assert w == extent or w % tile == 0


# every thin face as the shell's own shape, and the z faces turned as well
FORMS = [(d, False) for d in THIN] + [(d, True) for d in THIN if d[2] != 0]
FORM_IDS = [dir_name(d) + ("-turned" if t else "") for d, t in FORMS]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d,turned", FORMS, ids=FORM_IDS)
def test_window_unpack_is_dynamic_update_slice_to_the_bit(d, turned, dtype):
    """Low and high side, y and z, a z face as the shell's shape and turned
    (``(nq, sx, sz, sy)``, the form ``Unpack`` hands over), a 4-byte and a
    2-byte grid, no extent aligned: the kernel's grid is
    ``lax.dynamic_update_slice``'s bit for bit, and every cell outside the
    face is the cell that went in."""
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.random(UNALIGNED.local_shape(), dtype=np.float32)
                    ).astype(dtype)
    starts, sizes = _face_slices(UNALIGNED, d, "unpack")
    face = (jnp.asarray(rng.random(sizes, dtype=np.float32)) + 2.0
            ).astype(dtype)
    got = unpack_face_window(
        u, jnp.swapaxes(face, 2, 3) if turned else face, tuple(starts),
        _zero(), turned=turned, interpret=True)
    want = jax.lax.dynamic_update_slice(u, face, starts)
    assert got.dtype == u.dtype and got.shape == u.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    cut = tuple(slice(s, s + n) for s, n in zip(starts, sizes))
    outside = np.ones(u.shape, bool)
    outside[cut] = False
    np.testing.assert_array_equal(np.asarray(got, np.float32)[outside],
                                  np.asarray(u, np.float32)[outside])
    np.testing.assert_array_equal(np.asarray(got, np.float32)[cut],
                                  np.asarray(face, np.float32))


@pytest.mark.parametrize("d,turned", FORMS, ids=FORM_IDS)
def test_only_a_turned_face_is_turned(d, turned):
    """The kernel's body by what it traces to: a face in the shell's own
    shape (every y face; the parent's jaxpr, sha for sha, at the cell's
    size) is merged by one store, nothing is turned and the kernel has no
    scratch; a turned z face costs one ``transpose`` a q, out of the one
    VMEM scratch its rows are laid into."""
    starts, sizes = _face_slices(UNALIGNED, d, "unpack")
    if turned:
        sizes = (sizes[0], sizes[1], sizes[3], sizes[2])
    text = str(jax.make_jaxpr(
        lambda u, f, z: unpack_face_window(u, f, tuple(starts), z,
                                           turned=turned, interpret=False)
    )(jnp.zeros(UNALIGNED.local_shape(), jnp.float32),
      jnp.zeros(sizes, jnp.float32), _zero()))
    assert (text.count(" transpose["), text.count("Ref<vmem>")) == (
        (UNALIGNED.nq, 1) if turned else (0, 0))


@pytest.mark.parametrize("d", DIRECTIONS, ids=DIR_IDS)
def test_unpack_adapts_to_the_thin_axis(d, monkeypatch):
    """x faces keep ``dynamic_update_slice`` on a value-tied read; y and z
    faces declare an index tie, count as Pallas ops and call the kernel
    with the token's zero: a y face as it is, a z face turned (``(nq, sx,
    sz, sy)``, ``turned=True``), which ``halo.window_unpacks_turned``
    counts.  The result is the same grid either way."""
    op = Unpack(UNALIGNED, d)
    windowed = d[0] == 0
    turned = d[2] != 0
    assert bool(op.INDEX_TIE) is windowed
    assert op.uses_pallas() is windowed
    calls = []
    real = halo_pallas.unpack_face_window
    monkeypatch.setattr(
        halo_pallas, "unpack_face_window",
        lambda *a, **k: calls.append((a[1].shape, a[3], k["turned"]))
        or real(*a, **k))
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.random(UNALIGNED.local_shape(), dtype=np.float32))
    starts, sizes = _face_slices(UNALIGNED, d, "unpack")
    face = jnp.asarray(rng.random(sizes, dtype=np.float32))
    zero = _zero()
    ctx = SimpleNamespace(tok_index_zero=zero if windowed else None)
    reg = get_metrics()
    before = (reg.counter("halo.window_unpacks").value,
              reg.counter("halo.window_unpacks_turned").value)
    out = op.apply({"U": u, f"recv_{dir_name(d)}": face}, ctx)["U"]
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(jax.lax.dynamic_update_slice(u, face, starts)))
    nq, sx, sy, sz = sizes
    assert calls == ([((nq, sx, sz, sy) if turned else (nq, sx, sy, sz),
                       zero, turned)] if windowed else [])
    assert all(z is zero for _, z, _ in calls)
    assert (reg.counter("halo.window_unpacks").value - before[0],
            reg.counter("halo.window_unpacks_turned").value - before[1]
            ) == (int(windowed), int(turned))


@pytest.mark.parametrize("d", THIN, ids=THIN_IDS)
def test_window_unpack_traced_outside_the_contract_raises(d):
    """No ``tok_index_zero``, no happens-before edge: fail loudly (as
    ``Pack``: tests/test_halo_index_tie.py)."""
    u = jnp.zeros(UNALIGNED.local_shape(), jnp.float32)
    face = jnp.zeros(_face_slices(UNALIGNED, d, "unpack")[1], jnp.float32)
    with pytest.raises(RuntimeError, match="tok_index_zero"):
        Unpack(UNALIGNED, d).apply(
            {"U": u, f"recv_{dir_name(d)}": face},
            SimpleNamespace(tok_index_zero=None))


# -- the one-chip twin never reaches it ---------------------------------------


def _counters():
    reg = get_metrics()
    return tuple(reg.counter(name).value for name in (
        "halo.window_unpacks", "halo.window_unpacks_turned",
        "executor.index_ties", "executor.value_tied_bytes"))


# the parent's (89ae733) value-tied bytes for this body, read before the edit
ONE_CHIP_VALUE_TIED_BYTES = 3072


@pytest.mark.needs_pinned_host
def test_one_chip_body_counts_no_window_unpack():
    """Naive of ``halo512.climb``'s graph (``halo_pipeline``: ``UnpackRecv``,
    no menu) never reaches ``Unpack.apply``: no window unpack, turned or
    not, the six packs' index ties, and the value-tied bytes the parent
    read.  (The menu's start point counts two, turned:
    tests/test_halo_window_pack.py.)"""
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


# -- subclasses with a write of their own trace as before ----------------------


def _unstaged(recv, d, sizes):
    """The staging order spelled out: every face unflattened as it was up
    to PR 47, a z face from its turned extents and turned back."""
    if not d[2]:
        return unflatten_face(recv, sizes)
    nq, sx, sy, sz = sizes
    return jnp.swapaxes(unflatten_face(recv, (nq, sx, sz, sy)), 2, 3)


def _unpackrecv_up_to_pr31(args, d, bufs):
    """``UnpackRecv.apply`` as it stands since PR 9: unflatten, one update."""
    starts, _ = _face_slices(args, d, "unpack")
    _, sizes = _face_slices(args, d, "pack")
    face = _unstaged(bufs[f"recv_{dir_name(d)}"], d, sizes)
    return {"U": jax.lax.dynamic_update_slice(bufs["U"], face, starts)}


def _kernel_up_to_pr31(kernel, flat: bool):
    def then(args, d, bufs):
        starts, _ = _face_slices(args, d, "unpack")
        _, sizes = _face_slices(args, d, "pack")
        recv = bufs[f"recv_{dir_name(d)}"]
        if flat:
            return {"U": kernel(bufs["U"], recv, tuple(starts), tuple(sizes),
                                interpret=True)}
        return {"U": kernel(bufs["U"], _unstaged(recv, d, sizes),
                            tuple(starts), interpret=True)}

    return then


# lz a multiple of 128, so the flat kernels are on the y faces' menu
MENU_ARGS = HaloArgs(nq=2, lx=8, ly=8, lz=128, radius=2)
SUBCLASSES = [
    ("UnpackRecv", lambda: UnpackRecv, _unpackrecv_up_to_pr31, False),
    ("UnpackXla", lambda: halo_pallas.UnpackXla, _unpackrecv_up_to_pr31,
     False),
    ("UnpackPallas", lambda: halo_pallas.UnpackPallas,
     _kernel_up_to_pr31(halo_pallas.unpack_face_pallas, False), True),
    ("UnpackPallasB", lambda: halo_pallas.UnpackPallasB,
     _kernel_up_to_pr31(halo_pallas.unpack_face_pallas_batched, False), True),
    ("UnpackPallasF", lambda: halo_pallas.UnpackPallasF,
     _kernel_up_to_pr31(halo_pallas.unpack_face_flat_pallas, True), True),
]


# a y face each, and a z face where the entry is on its menu
ON_FACES = [(s, d) for s in SUBCLASSES for d in [(0, 1, 0), (0, 0, -1)]
            if not (s[0] == "UnpackPallasF" and d[2])]


@pytest.mark.parametrize(
    "sub,d", ON_FACES, ids=[
        s[0] + ("" if d[1] else "-" + dir_name(d)) for s, d in ON_FACES])
def test_subclasses_with_their_own_write_trace_as_before(sub, d):
    """The one-chip flagship's older unpacks (``halo512.climb``'s menus hold
    them) are the programs they were: the same jaxpr, equation for
    equation, on a y face (which ``Unpack`` itself now hands to the window
    kernel), on the executor's value-tied read and with ``uses_pallas`` as
    it was; on a z face with the staging order's one ``swapaxes`` between
    the unflatten it always was and the write it always was (ISSUE 48)."""
    from tenzing_tpu.models.halo_pipeline import _flat_rows, _padded_shape

    name, cls, then, pallas = sub
    op = cls()(MENU_ARGS, d)
    assert not op.INDEX_TIE
    assert op.uses_pallas() is pallas
    _, sizes = _face_slices(MENU_ARGS, d, "pack")
    u = jnp.zeros(_padded_shape(MENU_ARGS.local_shape(), 4), jnp.float32)
    recv = jnp.zeros((_flat_rows(sizes), 128), jnp.float32)
    ctx = SimpleNamespace(tok_index_zero=None)

    def now(u, recv):
        return op.apply({"U": u, f"recv_{dir_name(d)}": recv}, ctx)

    def before(u, recv):
        return then(MENU_ARGS, d, {"U": u, f"recv_{dir_name(d)}": recv})

    text = str(jax.make_jaxpr(now)(u, recv))
    assert text == str(jax.make_jaxpr(before)(u, recv))
    assert text.count(" transpose[") == (1 if d[2] else 0)
