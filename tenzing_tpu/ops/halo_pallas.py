"""Pallas pack/unpack kernels for the halo faces — the kernel menu.

Parity target: the reference ships TWO CUDA kernel families for halo
pack/unpack, selected by storage order (``pack_kernel_qxyz`` warp-per-gridpoint
vs ``pack_kernel_xyzq`` thread-per-gridpoint, ops_halo_exchange.cu:519-573 and
the mirror unpack kernels :611-699, launch-config selection in Pack::run /
Unpack::run) — a per-workload implementation choice the search explores.

TPU-native menu: the XLA path (``models/halo.Pack``/``Unpack``) lowers the face
slice to XLA's fusion machinery; this module is the alternative — an explicit
**window-DMA kernel**: per (q, face-row) grid step the tile-aligned BOUNDING
WINDOW of the face cut (``_tile_window``) is DMA'd between HBM and VMEM with
``pltpu.make_async_copy`` and the ragged face cut is extracted (pack) or
merged (unpack read-modify-write, input/output-aliased: guaranteed in place)
in registers.  Mosaic requires HBM DMA slices tile-aligned (probed on v5e:
"Slice shape along dimension 3 must be aligned to tiling (128)"), so the
window is the aligned superset of the cut — a few extra aligned bytes for
aligned DMA, vs the XLA path's fused narrow copy whose in-place lowering
depends on XLA's liveness analysis.  Which wins per face shape (x-faces are
lane-contiguous, z-faces are 3-element strided in the lane dim) is exactly the
storage-order question the reference's two kernel families answer — so it is
exposed as a ChoiceOp and searched (SpMV's kernel menu precedent,
models/spmv.py SpMVImplChoice).

MEASURED (r5): the menu's value on the flagship is NOT kernel speed —
isolated and composed per-op costs differ 10-100x in both directions
(experiments/HALO_INCONTEXT.json vs MENU_INCUMBENT.json) because XLA
fuses/aliases across the whole program.  The load-bearing property is the
ALIASING GUARANTEE: at nq=3, 512^3 f32 the grid is 2.07 GB, a non-in-place
ghost-shell write costs a ~5 ms full-U copy, and the measured winners pick
exactly the aliased kernels per face (x .pallas, y .pallasf, z .pallasb —
experiments/MENU_INCUMBENT2.json: 2.94x vs the XLA-unpack recipe's 2.51x in
the same paired batch).

The kernels above want a TILE-PADDED grid (``halo_pipeline._padded_shape``:
the one-chip flagship's ``(3, 518, 520, 640)``).  The mesh exchange
(``models/halo.py``, the cell ``halo512-mesh4.mcts``) keeps each shard at its
own ``(3, 454, 454, 454)``, where Mosaic refuses every manual window; its
``Unpack`` writes the y and z ghost shells with a fifth kernel,
:func:`unpack_face_window`, which pipelines by ``BlockSpec`` and takes its
ordering token as a scalar-prefetch operand, and its ``Pack`` reads the y
and z edges with that kernel's mirror, :func:`pack_face_window`.
``unpack_face_window``'s docstring is the one account of the unpadded-grid
window and the scalar-prefetch tie, ``pack_face_window``'s of why a
lane-thin (z) face leaves its kernel transposed; since PR 47 it enters
``unpack_face_window`` the same way and is turned in VMEM, so between the
two kernels a z face is 11 MB whatever XLA does with it, never the 308 MB
of the shell's own shape in the default layout.  On the mesh ``Unpack`` and
``Pack`` pick them by the face's thin axis.  On the one-chip menu they are
the z faces' ``.window`` entries (``PackWindow``, ``UnpackWindow``, PR 48):
a z face crosses its flat staging buffer turned, whichever entry wrote it
(``halo_pipeline.staged_sizes``), so the pair hands it from kernel to
kernel through two reshapes and the older entries through a ``swapaxes``.

Off-TPU the kernels run in the Pallas interpreter (``interpret=True``), same
code path as the repo's other Pallas kernels.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from tenzing_tpu.core.operation import ChoiceOp, OpBase
from tenzing_tpu.models.halo import (
    HaloArgs,
    _face_slices,
    _index_zero,
    dir_name,
    sublane_tile,
)
from tenzing_tpu.models.halo_pipeline import (
    PackFlat,
    UnpackRecv,
    flatten_face,
    stage_face,
    staged_sizes,
    unflatten_face,
)
from tenzing_tpu.obs.metrics import get_metrics


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# The two-slot rotating-DMA kernels assume the grid executes strictly
# sequentially in linear order t = q*nb + b.  That is Pallas TPU's default
# today, but nothing else pins it — "arbitrary" makes the requirement
# explicit so a future parallel/megacore grid default can't silently race
# the rotating slots.
_SEQUENTIAL_GRID = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary")
)


def _two_slot_fetch(t, total, src_slice, slots, sems, emit):
    """The read-side two-slot choreography shared by both batched pack
    kernels: bootstrap the t==0 fetch, await this step's window, prefetch
    t+1 into the other slot, then run ``emit(window)`` on the landed rows.
    One definition so a fix lands in every user (ADVICE r4: the pattern was
    hand-duplicated across four kernels)."""

    def body(wa, sa, wb, sb):
        @pl.when(t == 0)
        def _():
            pltpu.make_async_copy(src_slice(t), wa, sa).start()

        pltpu.make_async_copy(src_slice(t), wa, sa).wait()

        @pl.when(t + 1 < total)
        def _():
            pltpu.make_async_copy(src_slice(t + 1), wb, sb).start()

        emit(wa)

    @pl.when(t % 2 == 0)
    def _():
        body(slots[0], sems[0], slots[1], sems[1])

    @pl.when(t % 2 == 1)
    def _():
        body(slots[1], sems[1], slots[0], sems[0])


def _two_slot_rmw(t, total, in_slice, out_slice, slots, in_sems, out_sems,
                  merge):
    """The read-modify-write two-slot choreography shared by both batched
    unpack kernels: fetch the step-t window (bootstrapped at t==0), drain the
    other slot's t-1 write-back before reusing it for the t+1 prefetch (the
    fetch reads disjoint rows, so the two DMAs fly together), run
    ``merge(window)``, post the write-back, and drain BOTH slots on the
    final step (the last write-back is never waited by a next prefetch)."""

    def body(wa, sai, sao, wb, sbi, sbo):
        @pl.when(t == 0)
        def _():
            pltpu.make_async_copy(in_slice(t), wa, sai).start()

        pltpu.make_async_copy(in_slice(t), wa, sai).wait()

        @pl.when(t + 1 < total)
        def _():
            @pl.when(t >= 1)
            def _():
                pltpu.make_async_copy(wb, out_slice(t - 1), sbo).wait()

            pltpu.make_async_copy(in_slice(t + 1), wb, sbi).start()

        merge(wa)
        pltpu.make_async_copy(wa, out_slice(t), sao).start()

        @pl.when(t == total - 1)
        def _():
            @pl.when(t >= 1)
            def _():
                pltpu.make_async_copy(wb, out_slice(t - 1), sbo).wait()

            pltpu.make_async_copy(wa, out_slice(t), sao).wait()

    @pl.when(t % 2 == 0)
    def _():
        body(slots[0], in_sems[0], out_sems[0], slots[1], in_sems[1],
             out_sems[1])

    @pl.when(t % 2 == 1)
    def _():
        body(slots[1], in_sems[1], out_sems[1], slots[0], in_sems[0],
             out_sems[0])


def _tile_window(y0: int, sy: int, z0: int, sz: int,
                 Y: int, Z: int, itemsize: int = 4) -> Tuple[int, int, int, int]:
    """(wy0, WH, wz0, WW): the tile-aligned bounding window of the face cut,
    clamped to the plane extents — Mosaic requires HBM DMA slices
    tile-aligned (probed on v5e; flagship grids are tile-padded by
    ``halo_pipeline._padded_shape`` so the clamp is inert there), and DMAing
    only the window instead of the full plane cuts the moved bytes up to 30x
    for sublane-thin faces (y-faces: one sublane-tile stripe) and 5x for
    lane-thin faces (z-faces: a (Y, 128) stripe).  The sublane tile scales
    with dtype width (8 for 4-byte, 16 for 2-byte, 32 for 1-byte)."""
    st = sublane_tile(itemsize)
    wy0 = (y0 // st) * st
    wy1 = min(-(-(y0 + sy) // st) * st, Y)
    wz0 = (z0 // 128) * 128
    wz1 = min(-(-(z0 + sz) // 128) * 128, Z)
    return wy0, wy1 - wy0, wz0, wz1 - wz0


def _batch_rows(sx: int, row_bytes: int, cap: int = 2_500_000) -> int:
    """Rows DMA'd per grid step: the largest divisor of ``sx`` whose window
    fits the per-slot VMEM budget (two slots + the block-pipelined face
    buffers must stay well under the ~16 MB core VMEM).  1 means the batched
    kernel degenerates to the per-row kernel."""
    best = 1
    for b in range(1, sx + 1):
        if sx % b == 0 and b * row_bytes <= cap:
            best = b
    return best


@functools.partial(
    jax.jit, static_argnames=("starts", "sizes", "interpret")
)
def pack_face_pallas(
    u: jax.Array, starts: Tuple[int, ...], sizes: Tuple[int, ...], interpret: bool = False
) -> jax.Array:
    """out[q, i, :, :] = u[q, x0+i, y0:y0+sy, z0:z0+sz]: aligned bounding
    -window DMA in, ragged face cut extracted in VMEM."""
    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)

    def kernel(u_ref, o_ref, win, sem):
        q = pl.program_id(0)
        i = pl.program_id(1)
        cp = pltpu.make_async_copy(
            u_ref.at[q, x0 + i, pl.ds(wy0, WH), pl.ds(wz0, WW)], win, sem
        )
        cp.start()
        cp.wait()
        o_ref[0, 0] = win[y0 - wy0 : y0 - wy0 + sy, z0 - wz0 : z0 - wz0 + sz]

    return pl.pallas_call(
        kernel,
        grid=(nq, sx),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, sy, sz), lambda q, i: (q, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, sx, sy, sz), u.dtype),
        scratch_shapes=[pltpu.VMEM((WH, WW), u.dtype), pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(u)


@functools.partial(jax.jit, static_argnames=("starts", "interpret"))
def unpack_face_pallas(
    u: jax.Array, face: jax.Array, starts: Tuple[int, ...], interpret: bool = False
) -> jax.Array:
    """u[q, x0+i, y0:y0+sy, z0:z0+sz] = face[q, i, :, :], in place (aliased —
    GUARANTEED, unlike a dynamic-update-slice whose in-place lowering depends
    on XLA's liveness analysis of the surrounding schedule): read-modify
    -write of each touched aligned bounding window through VMEM."""
    nq, sx, sy, sz = face.shape
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)

    def kernel(u_ref, f_ref, o_ref, win, sem):
        q = pl.program_id(0)
        i = pl.program_id(1)
        cp_in = pltpu.make_async_copy(
            u_ref.at[q, x0 + i, pl.ds(wy0, WH), pl.ds(wz0, WW)], win, sem
        )
        cp_in.start()
        cp_in.wait()
        win[y0 - wy0 : y0 - wy0 + sy, z0 - wz0 : z0 - wz0 + sz] = f_ref[0, 0]
        cp_out = pltpu.make_async_copy(
            win, o_ref.at[q, x0 + i, pl.ds(wy0, WH), pl.ds(wz0, WW)], sem
        )
        cp_out.start()
        cp_out.wait()

    return pl.pallas_call(
        kernel,
        grid=(nq, sx),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, 1, sy, sz), lambda q, i: (q, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[pltpu.VMEM((WH, WW), u.dtype), pltpu.SemaphoreType.DMA],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(u, face)


@functools.partial(
    jax.jit, static_argnames=("starts", "sizes", "interpret")
)
def pack_face_pallas_batched(
    u: jax.Array, starts: Tuple[int, ...], sizes: Tuple[int, ...],
    interpret: bool = False
) -> jax.Array:
    """Batched-row pack: one aligned window DMA moves ``BX`` face rows
    ((BX, WH, WW) per step instead of (WH, WW)), and the NEXT step's window
    DMA is prefetched into the other of two rotating VMEM slots while the
    current rows are extracted — MB-scale DMAs instead of the per-row
    kernel's 1536 serial ~20-266 KB transfers at the flagship config, which
    are DMA-latency-bound, not bandwidth-bound (measured: the per-row y-face
    kernels spend ~4 us/step on ~25 us of face bytes)."""
    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)
    BX = _batch_rows(sx, WH * WW * u.dtype.itemsize)
    nb = sx // BX
    total = nq * nb
    yl, zl = y0 - wy0, z0 - wz0

    def kernel(u_ref, o_ref, win0, win1, s0, s1):
        t = pl.program_id(0) * nb + pl.program_id(1)

        def u_slice(tt):
            qq = tt // nb
            bb = tt - qq * nb
            return u_ref.at[
                qq, pl.ds(x0 + bb * BX, BX), pl.ds(wy0, WH), pl.ds(wz0, WW)
            ]

        def emit(wa):
            o_ref[0] = wa[:, yl : yl + sy, zl : zl + sz]

        _two_slot_fetch(t, total, u_slice, (win0, win1), (s0, s1), emit)

    return pl.pallas_call(
        kernel,
        grid=(nq, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, BX, sy, sz), lambda q, b: (q, b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, sx, sy, sz), u.dtype),
        scratch_shapes=[
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_SEQUENTIAL_GRID,
        interpret=interpret,
    )(u)


@functools.partial(jax.jit, static_argnames=("starts", "interpret"))
def unpack_face_pallas_batched(
    u: jax.Array, face: jax.Array, starts: Tuple[int, ...],
    interpret: bool = False
) -> jax.Array:
    """Batched-row unpack with software-pipelined in/out DMAs: two rotating
    (BX, WH, WW) VMEM slots; at step t the slot-t window (started at t-1)
    is awaited, the face rows are merged, its write-back DMA is posted, and
    the t+1 window fetch is posted into the other slot — so the write-back
    of step t rides concurrently with the fetch of step t+1 (disjoint row
    ranges of the aliased grid).  In place like the per-row kernel
    (input/output-aliased)."""
    nq, sx, sy, sz = face.shape
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)
    BX = _batch_rows(sx, WH * WW * u.dtype.itemsize)
    nb = sx // BX
    total = nq * nb
    yl, zl = y0 - wy0, z0 - wz0

    def kernel(u_ref, f_ref, o_ref, win0, win1, s0i, s1i, s0o, s1o):
        t = pl.program_id(0) * nb + pl.program_id(1)

        def slice_of(ref):
            def at(tt):
                qq = tt // nb
                bb = tt - qq * nb
                return ref.at[
                    qq, pl.ds(x0 + bb * BX, BX), pl.ds(wy0, WH),
                    pl.ds(wz0, WW)
                ]

            return at

        def merge(wa):
            wa[:, yl : yl + sy, zl : zl + sz] = f_ref[0]

        _two_slot_rmw(t, total, slice_of(u_ref), slice_of(o_ref),
                      (win0, win1), (s0i, s1i), (s0o, s1o), merge)

    return pl.pallas_call(
        kernel,
        grid=(nq, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, BX, sy, sz), lambda q, b: (q, b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={0: 0},
        compiler_params=_SEQUENTIAL_GRID,
        interpret=interpret,
    )(u, face)


@functools.partial(
    jax.jit, static_argnames=("starts", "sizes", "interpret")
)
def pack_face_flat_pallas(
    u: jax.Array, starts: Tuple[int, ...], sizes: Tuple[int, ...],
    interpret: bool = False
) -> jax.Array:
    """Batched-row pack emitting the dense (rows, 128) STAGING layout
    directly: the face rows are extracted from the aligned window in VMEM and
    relaid to the flat layout with an in-kernel reshape (vreg shuffles at
    VMEM bandwidth), so the separate XLA flatten pass — measured at
    ~10 ms/iter of chunked HBM relayout copies across the winner's schedule
    (experiments/PROFILE_WINNER.json) — disappears, while the staging buffer
    stays dense (the 4D-staging A/B showed tile-padded staging pays 2.7x+
    DMA bytes).  Requires sz % 128 == 0 (the ``_flat_ok`` gate): that keeps
    every (BX, sy, sz) block row-aligned in the flat buffer AND the relayout
    a sublane merge Mosaic can lower — z-faces (sz = radius) fail the Mosaic
    relayout pass, probed on v5e.  The two-slot DMA choreography is the
    shared ``_two_slot_fetch`` — one definition for both pack kernels."""
    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    assert sz % 128 == 0, (sy, sz)  # _flat_ok gate
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)
    BX = _batch_rows(sx, WH * WW * u.dtype.itemsize)
    nb = sx // BX
    total = nq * nb
    br = (BX * sy * sz) // 128  # flat rows per block
    yl, zl = y0 - wy0, z0 - wz0

    def kernel(u_ref, o_ref, win0, win1, s0, s1):
        t = pl.program_id(0) * nb + pl.program_id(1)

        def u_slice(tt):
            qq = tt // nb
            bb = tt - qq * nb
            return u_ref.at[
                qq, pl.ds(x0 + bb * BX, BX), pl.ds(wy0, WH), pl.ds(wz0, WW)
            ]

        def emit(wa):
            o_ref[...] = wa[:, yl : yl + sy, zl : zl + sz].reshape(br, 128)

        _two_slot_fetch(t, total, u_slice, (win0, win1), (s0, s1), emit)

    return pl.pallas_call(
        kernel,
        grid=(nq, nb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((br, 128), lambda q, b: (q * nb + b, 0)),
        out_shape=jax.ShapeDtypeStruct((nq * sx * sy * sz // 128, 128),
                                       u.dtype),
        scratch_shapes=[
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=_SEQUENTIAL_GRID,
        interpret=interpret,
    )(u)


@functools.partial(jax.jit, static_argnames=("starts", "sizes", "interpret"))
def unpack_face_flat_pallas(
    u: jax.Array, flat: jax.Array, starts: Tuple[int, ...],
    sizes: Tuple[int, ...], interpret: bool = False
) -> jax.Array:
    """Batched-row unpack consuming the dense (rows, 128) staging buffer
    directly (inverse of :func:`pack_face_flat_pallas`): each flat block is
    relaid to face rows in VMEM and merged into the aligned window, with the
    same two-slot fetch/write-back pipeline and final drain as the batched
    window kernel.  Aliased in place."""
    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    assert sz % 128 == 0, (sy, sz)  # _flat_ok gate
    wy0, WH, wz0, WW = _tile_window(y0, sy, z0, sz, Y, Z, u.dtype.itemsize)
    BX = _batch_rows(sx, WH * WW * u.dtype.itemsize)
    nb = sx // BX
    total = nq * nb
    br = (BX * sy * sz) // 128
    yl, zl = y0 - wy0, z0 - wz0

    def kernel(u_ref, f_ref, o_ref, win0, win1, s0i, s1i, s0o, s1o):
        t = pl.program_id(0) * nb + pl.program_id(1)

        def slice_of(ref):
            def at(tt):
                qq = tt // nb
                bb = tt - qq * nb
                return ref.at[
                    qq, pl.ds(x0 + bb * BX, BX), pl.ds(wy0, WH),
                    pl.ds(wz0, WW)
                ]

            return at

        def merge(wa):
            wa[:, yl : yl + sy, zl : zl + sz] = f_ref[...].reshape(BX, sy, sz)

        _two_slot_rmw(t, total, slice_of(u_ref), slice_of(o_ref),
                      (win0, win1), (s0i, s1i), (s0o, s1o), merge)

    return pl.pallas_call(
        kernel,
        grid=(nq, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((br, 128), lambda q, b: (q * nb + b, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.VMEM((BX, WH, WW), u.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        input_output_aliases={0: 0},
        compiler_params=_SEQUENTIAL_GRID,
        interpret=interpret,
    )(u, flat)


# -- the window write and read on an unpadded grid (models/halo.py) ------------


def _shell_block(a0: int, n: int, extent: int, tile: int) -> Tuple[int, int, int]:
    """(block extent, block index, offset of the cut in the block) of the
    smallest tile-aligned BLOCK of one axis that holds the cut
    ``[a0, a0 + n)``: blocks of ``tile``, doubled while the cut straddles two
    of them, the whole axis where nothing smaller holds it (a cut over most
    of the axis, as a face's long sides).  A block, not ``_tile_window``'s
    window: a ``BlockSpec`` addresses whole blocks, and a block that runs
    past the axis's end (lanes ``[384, 512)`` of 454) is legal there."""
    w = tile
    while w < extent and a0 // w != (a0 + n - 1) // w:
        w *= 2
    return (extent, 0, a0) if w >= extent else (w, a0 // w, a0 % w)


@functools.partial(
    jax.jit, static_argnames=("starts", "turned", "interpret"))
def unpack_face_window(
    u: jax.Array, face: jax.Array, starts: Tuple[int, ...],
    tok_zero: jax.Array, turned: bool = False, interpret: bool = False
) -> jax.Array:
    """u[:, x0+i, y0:y0+sy, z0:z0+sz] = face[:, i], in place, on a grid that
    is NOT tile-padded (the mesh cell's ``(3, 454, 454, 454)`` a shard): the
    ghost-shell write of a face whose thin axis is the grid's sublane (y) or
    lane (z) axis.

    Why a kernel: XLA's ``dynamic-update-slice`` does this write in place
    but slowly, 3 cells of 128 lanes at a time from an update it first
    relayouts (measured on four v5e chips at 448^3 a shard: 5.11 + 3.93 ms
    the two z faces, 0.84 + 0.84 the y faces, of a 20.8 ms iteration:
    PERF.md, PR 29).  What such a write has to touch is the tile column (z:
    every y, one 128-lane tile) or tile row (y: one sublane tile, every z)
    that holds the shell, once in and once out.

    Why not the window kernels above: they DMA ``_tile_window``'s window by
    hand, and on an unpadded grid Mosaic refuses every such slice, a whole
    axis included ("Slice shape along dimension 2 must be aligned to tiling
    (8), but is 454": it sees the buffer at its physical 456 x 512).  So
    this one pipelines by ``BlockSpec``: per grid step (one x row, every q)
    Pallas brings the block of :func:`_shell_block` in, the body copies it
    and merges the face row, Pallas writes it back.  The block at the high
    end runs past the axis (sublanes ``[448, 456)``, lanes ``[384, 512)`` of
    454) and Pallas masks what is not there.  Input/output-aliased, so what
    no block visits is never touched and every other cell of a visited
    block, ghost edges and corners included, goes back as it came.

    The ordering token: ``tok_zero`` (``ctx.tok_index_zero``, an int32 zero
    that depends on the op's token) is a scalar-prefetch operand that the
    index map adds to the x block index.  The kernel cannot start before it
    is there, and no buffer gets a value-preserving add: on the received
    face that add would be a Pallas consumer's operand, materialised in the
    padded default layout (0.6 GB of traffic for a 7 MB z face).

    A lane-thin (z) face comes TURNED (``turned``: ``face`` is ``(nq, sx,
    sz, sy)``, the form :func:`pack_face_window` emits, which says why): as
    this kernel's operand the shell's own ``(nq, sx, sy, 3)`` is pinned to
    the default layout, 3 -> 128 lanes, 308 MB that XLA writes once (the
    relayout from the collective-permute's thin-major result) for the
    kernel to read once.  The turned face is 11 MB.  The body lays each q's
    ``(sz, sy)`` row into rows ``[zl, zl + sz)`` of a ``(WW, sy)`` scratch,
    turns that once on the XLU and selects its lanes ``[zl, zl + sz)`` into
    the window block; what else the scratch holds is never selected.  A
    sublane-thin (y) face, and a z face handed over as the shell's shape,
    take the plain merge."""
    nq, sx, sy, sz = face.shape
    if turned:
        sy, sz = sz, sy
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    WH, by, yl = _shell_block(y0, sy, Y, sublane_tile(u.dtype.itemsize))
    WW, bz, zl = _shell_block(z0, sz, Z, 128)

    def kernel(tok_ref, u_ref, f_ref, o_ref, *scratch):
        o_ref[...] = u_ref[...]
        if not turned:
            o_ref[:, yl : yl + sy, zl : zl + sz] = f_ref[...]
            return
        (rows,) = scratch
        lane = jax.lax.broadcasted_iota(jnp.int32, (sy, WW), 1)
        shell = (lane >= zl) & (lane < zl + sz)
        for q in range(nq):
            rows[zl : zl + sz, :] = f_ref[q]
            o_ref[q, yl : yl + sy, :] = jnp.where(
                shell, rows[...].T, u_ref[q, yl : yl + sy, :])

    def window(i, tok_ref):
        return (0, x0 + i + tok_ref[0], by, bz)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sx,),
            in_specs=[
                pl.BlockSpec((nq, None, WH, WW), window),
                pl.BlockSpec((nq, None) + face.shape[2:],
                             lambda i, tok_ref: (0, i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((nq, None, WH, WW), window),
            scratch_shapes=(
                [pltpu.VMEM((WW, sy), u.dtype)] if turned else []),
        ),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        input_output_aliases={1: 0},  # operand 0 is the token's zero
        name="halo_window_unpack",
        interpret=interpret,
    )(tok_zero.reshape(1), u, face)


@functools.partial(
    jax.jit, static_argnames=("starts", "sizes", "turned", "interpret")
)
def pack_face_window(
    u: jax.Array, starts: Tuple[int, ...], sizes: Tuple[int, ...],
    tok_zero: jax.Array, turned: bool = False, interpret: bool = False
) -> jax.Array:
    """face[:, i] = u[:, x0+i, y0:y0+sy, z0:z0+sz] on a grid that is NOT
    tile-padded: the mirror of :func:`unpack_face_window`, built from the
    same parts (``_shell_block``'s block of each thin axis, one x row and
    every q a grid step, the token's zero by scalar prefetch on the x block
    index; the grid is only read, so no alias).

    Why a kernel: XLA has no instruction for a thin slice.  It fuses the
    strided read into whatever consumes the face, the exchange's value tie
    (1.8-2.3 ms a z face at 448^3 a shard, 3.35 for the pair: PERF.md
    section 5, PR 38), and where several packs fuse it relayouts the whole
    1.27 GB grid to feed them.  What a pack has to read is the tile column
    (z) or tile row (y) that holds the edge; a Pallas consumer also pins
    the grid's default layout.

    A lane-thin (z) face leaves the kernel TRANSPOSED, ``(nq, sx, sz,
    sy)``, and is handed on through ``swapaxes``: in the default layout a
    ``(nq, sx, sy, 3)`` float32 face is padded 3 -> 128 lanes (308 MB for
    7 MB of cells), and every pass over it (the write, the exchange's value
    tie, the relayout to the collective-permute's thin-major layout) costs
    0.4-0.8 ms.  The transposed face is 11 MB, the ``swapaxes`` a bitcast
    to a layout of XLA's choosing, and tie and relayout run on that.  The
    body turns each q's ``(sy, 128)`` block on the XLU and keeps the ``sz``
    rows that are the edge.  With ``turned`` the lane-thin face is handed
    on as the kernel wrote it, ``(nq, sx, sz, sy)``, the operand
    ``unpack_face_window(..., turned=True)`` takes (the one-chip menu's
    ``PackWindow`` stages it so)."""
    nq, sx, sy, sz = sizes
    _, x0, y0, z0 = starts
    _, _, Y, Z = u.shape
    WH, by, yl = _shell_block(y0, sy, Y, sublane_tile(u.dtype.itemsize))
    WW, bz, zl = _shell_block(z0, sz, Z, 128)
    lane_thin = sz < sy

    def kernel(tok_ref, u_ref, f_ref):
        if lane_thin:
            for q in range(nq):
                f_ref[q] = u_ref[q, yl : yl + sy, :].T[zl : zl + sz, :]
        else:
            f_ref[...] = u_ref[:, yl : yl + sy, zl : zl + sz]

    row = (sz, sy) if lane_thin else (sy, sz)
    face = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sx,),
            in_specs=[pl.BlockSpec(
                (nq, None, WH, WW),
                lambda i, tok_ref: (0, x0 + i + tok_ref[0], by, bz))],
            out_specs=pl.BlockSpec(
                (nq, None) + row, lambda i, tok_ref: (0, i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nq, sx) + row, u.dtype),
        name="halo_window_pack",
        interpret=interpret,
    )(tok_zero.reshape(1), u)
    return jnp.swapaxes(face, 2, 3) if lane_thin and not turned else face


# -- ops + choice menu ------------------------------------------------------------


class PackPallas(PackFlat):
    """Pack via the plane-DMA kernel, then flatten to the (rows, 128) staging
    layout (menu alternative to the XLA slice).

    INDEX_TIE stays OFF: the Pallas grid needs static start indices, so this
    variant keeps the value-tied read (the executor's default)."""

    INDEX_TIE = False

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"pack_{dir_name(d)}.pallas"

    def apply(self, bufs, ctx):
        starts, sizes = _face_slices(self._args, self._d, "pack")
        out = pack_face_pallas(
            bufs["U"], tuple(starts), tuple(sizes), interpret=_interpret()
        )
        return {f"buf_{dir_name(self._d)}": stage_face(out, self._d)}

    def uses_pallas(self) -> bool:
        return True


class PackXla(PackFlat):
    """The XLA-slice pack under a menu-distinct name."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"pack_{dir_name(d)}.xla"


def _face_bx(args: HaloArgs, d, which: str = "pack") -> int:
    """The batched kernels' rows-per-DMA for this face (1 means the batched
    variant degenerates to the per-row kernel and is left off the menu).
    ``which`` picks the window the kernel will actually DMA — the pack reads
    the interior edge, the unpack RMWs the ghost shell, and the two can span
    a different number of sublane tiles.  The itemsize comes from the grid
    dtype in ``args`` so the gate agrees with the BX the kernels compute from
    ``u.dtype.itemsize`` (a 2-byte grid halves the sublane tile)."""
    from tenzing_tpu.models.halo_pipeline import _padded_shape

    itemsize = args.itemsize()
    starts, sizes = _face_slices(args, d, "pack")
    if which == "unpack":
        starts, _ = _face_slices(args, d, "unpack")
    _, sx, sy, sz = sizes
    _, _, y0, z0 = starts
    _, _, Y, Z = _padded_shape(args.local_shape(), itemsize)
    _, WH, _, WW = _tile_window(y0, sy, z0, sz, Y, Z, itemsize)
    return _batch_rows(sx, WH * WW * itemsize)


class PackPallasB(PackFlat):
    """Pack via the batched-row prefetching window kernel."""

    INDEX_TIE = False

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"pack_{dir_name(d)}.pallasb"

    def apply(self, bufs, ctx):
        starts, sizes = _face_slices(self._args, self._d, "pack")
        out = pack_face_pallas_batched(
            bufs["U"], tuple(starts), tuple(sizes), interpret=_interpret()
        )
        return {f"buf_{dir_name(self._d)}": stage_face(out, self._d)}

    def uses_pallas(self) -> bool:
        return True


class UnpackPallas(UnpackRecv):
    """Unpack via the aliased plane-DMA kernel."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"unpack_{dir_name(d)}.pallas"

    def apply(self, bufs, ctx):
        starts, face = self._face(bufs)
        out = unpack_face_pallas(
            bufs["U"], face, tuple(starts), interpret=_interpret()
        )
        return {"U": out}

    def uses_pallas(self) -> bool:
        return True


def _window_ok(args: HaloArgs, d) -> bool:
    """Whether the ``.window`` pair is on direction ``d``'s menus: where the
    face is staged turned (``halo_pipeline.staged_sizes``: a lane-thin z
    face), the one form both window kernels share."""
    _, sizes = _face_slices(args, d, "pack")
    return staged_sizes(d, sizes) != tuple(sizes)


def _flat_ok(args: HaloArgs, d) -> bool:
    """Whether the direct-flat kernels apply: the face's trailing dim must be
    lane-aligned (sz % 128 == 0) — that makes every block row-aligned in the
    (rows, 128) staging buffer AND keeps the in-kernel relayout a
    sublane-merge Mosaic can lower (probed on v5e: a 3-wide trailing dim —
    z-faces — fails in the Mosaic relayout pass).  The flat kernels write
    and read the face's own order, so a face that is staged turned (a z
    face a whole lane tile thick and thinner than y, which nobody runs) is
    not theirs either."""
    _, sizes = _face_slices(args, d, "pack")
    return sizes[3] % 128 == 0 and not _window_ok(args, d)


class PackPallasF(PackFlat):
    """Pack via the direct-flat kernel: dense staging emitted straight from
    the grid window, relayout in VMEM (no separate XLA flatten pass)."""

    INDEX_TIE = False

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"pack_{dir_name(d)}.pallasf"

    def apply(self, bufs, ctx):
        starts, sizes = _face_slices(self._args, self._d, "pack")
        out = pack_face_flat_pallas(
            bufs["U"], tuple(starts), tuple(sizes), interpret=_interpret()
        )
        return {f"buf_{dir_name(self._d)}": out}

    def uses_pallas(self) -> bool:
        return True


class UnpackXla(UnpackRecv):
    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"unpack_{dir_name(d)}.xla"


class UnpackPallasF(UnpackRecv):
    """Unpack via the direct-flat kernel (consumes the dense staging buffer
    with no separate XLA unflatten pass; aliased in place)."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"unpack_{dir_name(d)}.pallasf"

    def apply(self, bufs, ctx):
        starts, _ = _face_slices(self._args, self._d, "unpack")
        _, sizes = _face_slices(self._args, self._d, "pack")
        out = unpack_face_flat_pallas(
            bufs["U"], bufs[f"recv_{dir_name(self._d)}"], tuple(starts),
            tuple(sizes), interpret=_interpret()
        )
        return {"U": out}

    def uses_pallas(self) -> bool:
        return True


class UnpackPallasB(UnpackRecv):
    """Unpack via the batched-row in/out-pipelined aliased window kernel."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"unpack_{dir_name(d)}.pallasb"

    def apply(self, bufs, ctx):
        starts, face = self._face(bufs)
        out = unpack_face_pallas_batched(
            bufs["U"], face, tuple(starts), interpret=_interpret()
        )
        return {"U": out}

    def uses_pallas(self) -> bool:
        return True


class PackWindow(PackFlat):
    """Pack a lane-thin (z) face with the mesh halo's window kernel
    (:func:`pack_face_window`: one 128-lane tile column of the grid read
    once) and stage the TURNED face the kernel wrote: ``(nq, sx, sz, sy)``
    is whole rows of 128 lanes at the flagship's size, so the staging buffer
    is a reshape of it.  ``INDEX_TIE`` stays on (inherited): the token is
    the kernel's scalar-prefetch operand and the 2.07 GB grid is only read,
    which is what sets this entry apart from the value-tied Pallas packs
    above (a full pass over the grid and a new version of it a pack)."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"pack_{dir_name(d)}.window"

    def apply(self, bufs, ctx):
        starts, sizes = _face_slices(self._args, self._d, "pack")
        z = _index_zero(self, ctx)
        get_metrics().counter("halo.window_packs").inc()
        out = pack_face_window(
            bufs["U"], tuple(starts), tuple(sizes), z, turned=True,
            interpret=_interpret())
        return {f"buf_{dir_name(self._d)}": flatten_face(out, out.shape)}

    def uses_pallas(self) -> bool:
        return True


class UnpackWindow(UnpackRecv):
    """Unpack a lane-thin (z) face with the mesh halo's aliased window
    kernel (:func:`unpack_face_window`, ``turned=True``: the tile column
    that holds the shell read and written once, the face turned in VMEM).
    The staged face is the kernel's operand as it is, a reshape of
    ``recv_<d>``; the token goes in by index, the kernel's scalar-prefetch
    operand, so the received face gets no value-add."""

    INDEX_TIE = True

    def __init__(self, args: HaloArgs, d):
        super().__init__(args, d)
        self._name = f"unpack_{dir_name(d)}.window"

    def apply(self, bufs, ctx):
        starts, _ = _face_slices(self._args, self._d, "unpack")
        _, sizes = _face_slices(self._args, self._d, "pack")
        z = _index_zero(self, ctx)
        reg = get_metrics()
        reg.counter("halo.window_unpacks").inc()
        reg.counter("halo.window_unpacks_turned").inc()
        face = unflatten_face(bufs[f"recv_{dir_name(self._d)}"],
                              staged_sizes(self._d, sizes))
        out = unpack_face_window(
            bufs["U"], face, tuple(starts), z, turned=True,
            interpret=_interpret())
        return {"U": out}

    def uses_pallas(self) -> bool:
        return True


class PackChoice(ChoiceOp):
    """XLA slice vs Pallas DMA kernel for one direction's pack (the reference's
    storage-order kernel-family selection as a searched ChoiceOp)."""

    def __init__(self, args: HaloArgs, d):
        super().__init__(f"pack_{dir_name(d)}")
        self._args, self._d = args, tuple(d)

    def choices(self) -> List[OpBase]:
        menu: List[OpBase] = [
            PackXla(self._args, self._d), PackPallas(self._args, self._d)
        ]
        if _face_bx(self._args, self._d) > 1:
            menu.append(PackPallasB(self._args, self._d))
        if _flat_ok(self._args, self._d):
            menu.append(PackPallasF(self._args, self._d))
        if _window_ok(self._args, self._d):
            menu.append(PackWindow(self._args, self._d))
        return menu


class UnpackChoice(ChoiceOp):
    def __init__(self, args: HaloArgs, d):
        super().__init__(f"unpack_{dir_name(d)}")
        self._args, self._d = args, tuple(d)

    def choices(self) -> List[OpBase]:
        menu: List[OpBase] = [
            UnpackXla(self._args, self._d), UnpackPallas(self._args, self._d)
        ]
        if _face_bx(self._args, self._d, which="unpack") > 1:
            menu.append(UnpackPallasB(self._args, self._d))
        if _flat_ok(self._args, self._d):
            menu.append(UnpackPallasF(self._args, self._d))
        if _window_ok(self._args, self._d):
            menu.append(UnpackWindow(self._args, self._d))
        return menu
