"""Pallas remote-DMA comm ops: the direct Isend/Irecv/Wait analog.

Parity target: reference ``include/tenzing/mpi/ops_mpi.hpp:17-146`` — the
nonblocking Isend/Irecv post whose completion a separate ``Wait`` op observes.
SURVEY.md §7.0 names ``pltpu.make_async_remote_copy`` + semaphores as the
TPU-native realization: the post/wait split *is* the overlap opportunity the
search exists to exploit, and on TPU the DMA engines move the bytes while the
TensorCore keeps executing kernels.

Two ops, two dispatch regimes:

* :class:`RdmaCopyStart` — device->device copy through the chip's RDMA engine
  addressed to the device itself (the single-chip realization of a
  device-resident transfer; the "CUDA-aware MPI" analog of SURVEY §7.0's
  translation table — device buffers addressed by ICI DMA, no host staging —
  vs the host-staged round trip of ``HostSpillStart``/``HostFetchStart``,
  the non-GPU-aware staging analog).  On a real TPU the post and the wait are
  **separate Pallas kernels** passing DMA semaphores between them
  (semaphores-in-out_shape): the start kernel issues ``rdma.start()`` and
  returns immediately, the schedule runs whatever it placed between post and
  await on the TensorCore, and ``AwaitTransfer`` runs the wait kernel that
  blocks on the semaphores — exactly MPI_Isend/MPI_Wait.  Under the Pallas
  interpreter (CPU tests) semaphore outputs are unsupported, so the op
  degrades to one fused local-DMA copy kernel (on one chip the loopback
  remote copy is the same data movement) — numerically identical, the
  overlap being a hardware property anyway.

* :class:`RdmaShiftStart` — neighbor shift over a mesh axis, each shard
  DMA-writing its block into the next shard's output buffer
  (``make_async_remote_copy`` with MESH device ids) after a neighbor barrier
  (``get_barrier_semaphore``) — the per-neighbor computed-offset DMA that is
  the TPU analog of the reference's negotiated per-rank exchange
  (``row_part_spmv.cuh:259-423``).  A searchable ChoiceOp alternative to
  ``PermuteStart`` (XLA collective-permute) in the halo and irregular-SpMV
  menus.  On TPU the post and the wait are separate kernels
  (``rdma_shift_post`` barriers + ``rdma.start()`` and returns semaphores;
  ``rdma_shift_wait`` blocks on them from the AwaitTransfer), so the searched
  post/wait placement is physical overlap freedom exactly as for the loopback
  copy.  Under the interpreter the op degrades to the fused start+wait kernel
  (semaphore outputs unsupported — probed).  When the axis has size 1 the
  shift degenerates to the loopback copy (no barrier — Mosaic rejects
  ``collective_id`` when no custom barrier is used, probed on v5e).

Validated on hardware: the split start/wait loopback copy round-trips 64 MB
correctly on TPU v5e (allclose), and in interpret mode on an 8-device CPU mesh
the shift matches ``jnp.roll`` along 1-D and 3-D meshes (tests/test_rdma.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tenzing_tpu.core.operation import register_kind
from tenzing_tpu.ops.comm_ops import CommStart


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mesh_ids(axes: Tuple[str, ...], axis: Optional[str], shift: int):
    """(device_id fwd, device_id bwd, device_id_type, axis size) for the
    shifted neighbor pair on the current mesh."""
    if axis is None or not axes:
        return 0, 0, pltpu.DeviceIdType.LOGICAL, 1
    n = jax.lax.axis_size(axis)
    me = {a: jax.lax.axis_index(a) for a in axes}
    fwd = dict(me)
    fwd[axis] = (me[axis] + shift) % n
    bwd = dict(me)
    bwd[axis] = (me[axis] - shift) % n
    fwd_id = tuple(fwd[a] for a in axes)
    bwd_id = tuple(bwd[a] for a in axes)
    return fwd_id, bwd_id, pltpu.DeviceIdType.MESH, n


def _shift_fused_kernel(axes, axis, shift, x_ref, y_ref, send_sem, recv_sem):
    fwd, bwd, id_type, n = _mesh_ids(axes, axis, shift)
    if n > 1:
        # both neighbors must have entered the kernel before either side's
        # buffers are written remotely (standard RDMA ring discipline)
        barrier = pltpu.get_barrier_semaphore()
        for nb in (fwd, bwd):
            pltpu.semaphore_signal(barrier, inc=1, device_id=nb, device_id_type=id_type)
        pltpu.semaphore_wait(barrier, 2)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=y_ref, send_sem=send_sem, recv_sem=recv_sem,
        device_id=fwd, device_id_type=id_type,
    )
    rdma.start()
    rdma.wait()


def rdma_shift_fused(
    x: jax.Array,
    axes: Tuple[str, ...],
    axis: Optional[str],
    shift: int,
    collective_id: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused (start+wait) remote-DMA shift of ``x`` to the ``+shift`` neighbor
    along ``axis``; the output holds the block received from ``-shift``."""
    if interpret is None:
        interpret = _interpret()
    kern = functools.partial(_shift_fused_kernel, tuple(axes), axis, shift)
    needs_barrier = axis is not None and axes and jax.lax.axis_size(axis) > 1
    params = (
        pltpu.CompilerParams(collective_id=collective_id, has_side_effects=True)
        if needs_barrier
        else pltpu.CompilerParams(has_side_effects=True)
    )
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        compiler_params=params,
        interpret=pltpu.InterpretParams() if interpret else False,
        name="rdma_shift_fused",
    )(x)


def _loop_local_kernel(x_ref, y_ref, sem):
    cp = pltpu.make_async_copy(x_ref, y_ref, sem)
    cp.start()
    cp.wait()


def rdma_copy_fused_local(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """Fused device->device DMA copy via the *local* async-copy engine — the
    interpret-mode stand-in for the loopback remote copy (on one chip the two
    are the same data movement; the boolean Pallas interpreter supports
    ``make_async_copy`` but not remote descriptors, and the TPU-interpret
    machinery (`InterpretParams`) cannot coexist with pinned-host program
    outputs — probed: mlir memory-kind propagation length mismatch)."""
    if interpret is None:
        interpret = _interpret()
    return pl.pallas_call(
        _loop_local_kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
        name="rdma_copy_fused_local",
    )(x)


# -- split start/wait (TPU hardware): semaphores as kernel outputs ----------


def _shift_post_kernel(axes, axis, shift, tok_ref, x_ref, send_ref, recv_ref,
                       y_ref):
    """Post half of the mesh neighbor shift: neighbor barrier, then
    ``rdma.start()`` — returns with the DMA in flight (MPI_Isend).
    ``tok_ref`` is the ordering token's zero: an operand the call waits for
    and the body never reads."""
    fwd, bwd, id_type, n = _mesh_ids(axes, axis, shift)
    if n > 1:
        barrier = pltpu.get_barrier_semaphore()
        for nb in (fwd, bwd):
            pltpu.semaphore_signal(barrier, inc=1, device_id=nb, device_id_type=id_type)
        pltpu.semaphore_wait(barrier, 2)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=y_ref, send_sem=send_ref, recv_sem=recv_ref,
        device_id=fwd, device_id_type=id_type,
    )
    rdma.start()


def _shift_wait_kernel(axes, axis, shift, x_ref, send_ref, recv_ref, y_in_ref, y_ref):
    """Wait half: block on the posted shift's send+recv semaphores
    (MPI_Wait); the destination passes through aliased."""
    fwd, _, id_type, _ = _mesh_ids(axes, axis, shift)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=y_in_ref, send_sem=send_ref, recv_sem=recv_ref,
        device_id=fwd, device_id_type=id_type,
    )
    rdma.wait()


def rdma_shift_post(
    x: jax.Array,
    axes: Tuple[str, ...],
    axis: Optional[str],
    shift: int,
    collective_id: int = 0,
    tok_zero: Optional[jax.Array] = None,
):
    """Post the mesh neighbor shift; returns (send_sem, recv_sem, y) with the
    remote DMA in flight — the MPI_Isend half of the reference's split
    (ops_mpi.hpp:17-146).  TPU only: the interpreter cannot materialize
    semaphore outputs (probed on v5e; see module docstring).

    ``tok_zero`` (``ctx.tok_index_zero``) is the ordering token by index,
    as ``ops/halo_pallas.py`` ``unpack_face_window`` takes it: a scalar
    operand in SMEM that the kernel call cannot start without, so ``x``
    gets no value-preserving add.  ``x`` is a Pallas operand in the padded
    default layout, and on a lane-thin halo face that add was a pass of its
    own over 0.6 GB (0.92 ms a z face at 448^3 a shard: PERF.md, PR 44)."""
    if tok_zero is None:
        tok_zero = jnp.zeros((), jnp.int32)
    kern = functools.partial(_shift_post_kernel, tuple(axes), axis, shift)
    needs_barrier = axis is not None and axes and jax.lax.axis_size(axis) > 1
    params = (
        pltpu.CompilerParams(collective_id=collective_id, has_side_effects=True)
        if needs_barrier
        else pltpu.CompilerParams(has_side_effects=True)
    )
    return pl.pallas_call(
        kern,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.SEMAPHORE),
            pl.BlockSpec(memory_space=pltpu.SEMAPHORE),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        out_shape=(
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        ),
        compiler_params=params,
        name="rdma_shift_post",
    )(tok_zero.reshape(1), x)


def rdma_shift_wait(
    x: jax.Array, send, recv, y: jax.Array,
    axes: Tuple[str, ...], axis: Optional[str], shift: int,
) -> jax.Array:
    """Block on the in-flight shift's semaphores and return the completed
    destination (aliased, no extra copy) — the MPI_Wait half."""
    kern = functools.partial(_shift_wait_kernel, tuple(axes), axis, shift)
    return pl.pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SEMAPHORE),
            pl.BlockSpec(memory_space=pltpu.SEMAPHORE),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        name="rdma_shift_wait",
    )(x, send, recv, y)


def rdma_start_loopback(x: jax.Array):
    """Post a device->device RDMA copy of ``x``; returns (send_sem, recv_sem,
    y) with the DMA in flight — the MPI_Isend half.  TPU only (the interpreter
    cannot materialize semaphore outputs; probed).  The degenerate no-axis
    shift: ``_mesh_ids`` yields the LOGICAL self-descriptor and no barrier."""
    return rdma_shift_post(x, (), None, 1)


def rdma_wait_loopback(x: jax.Array, send, recv, y: jax.Array) -> jax.Array:
    """Block on the in-flight copy's semaphores and return the completed
    destination (aliased, no extra copy) — the MPI_Wait half."""
    return rdma_shift_wait(x, send, recv, y, (), None, 1)


# -- schedulable ops --------------------------------------------------------


@register_kind("rdma_copy_start")
class RdmaCopyStart(CommStart):
    """Post a device-resident RDMA copy ``src -> dst`` (loopback on one chip).

    The searchable alternative to the host-staged round trip
    (``HostSpillStart`` + ``HostFetchStart``) in the transfer-engine menu:
    device buffers addressed by the DMA engine, no PCIe/host hop — the
    CUDA-aware-MPI analog (SURVEY §7.0).  On TPU the post stashes a wait
    closure for ``AwaitTransfer`` (split kernels, true Isend/Wait); under the
    interpreter it degrades to the fused kernel."""

    def apply(self, bufs: Dict[str, Any], ctx) -> Dict[str, Any]:
        x = bufs[self._src]
        if _interpret():
            return {self._dst: rdma_copy_fused_local(x)}
        send, recv, y = rdma_start_loopback(x)
        inflight = getattr(ctx, "inflight", None)
        if inflight is not None:
            inflight[self._dst] = functools.partial(
                rdma_wait_loopback, x, send, recv
            )
        return {self._dst: y}

    def uses_pallas(self) -> bool:
        return True


@register_kind("rdma_shift_start")
class RdmaShiftStart(CommStart):
    """Post a neighbor shift of ``src`` over mesh axis ``axis`` into ``dst``
    via per-neighbor remote DMA — the menu alternative to :class:`PermuteStart`
    (XLA collective-permute).  ``collective_id`` must be unique among RDMA
    ops with barriers in one schedule (barrier semaphores are shared by id).

    On TPU the post and the wait are SEPARATE Pallas kernels passing DMA
    semaphores between them (``rdma_shift_post``/``rdma_shift_wait``): this op
    issues the barrier + ``rdma.start()`` and stashes the wait closure for
    ``AwaitTransfer`` — the true MPI_Isend/MPI_Wait split the reference models
    (ops_mpi.hpp:17-146), so the searched post/wait placement is a physical
    overlap freedom on the mesh, not just a graph position (VERDICT r3 item 2).
    Under the Pallas interpreter (CPU tests/dryrun) semaphore outputs are
    unsupported, so the op degrades to the fused start+wait kernel and the
    await falls back to the ordinary data dependency."""

    # the post takes its token by index (``rdma_shift_post``'s ``tok_zero``)
    INDEX_TIE = True

    def __init__(self, name: str, src: str, dst: str, axis: str,
                 shift: int = 1, collective_id: int = 0):
        super().__init__(name, src, dst)
        self._axis = axis
        self._shift = shift
        self._cid = collective_id

    def apply(self, bufs: Dict[str, Any], ctx) -> Dict[str, Any]:
        axes = tuple(getattr(ctx, "axis_names", ()) or ())
        x = bufs[self._src]
        axis = self._axis if axes else None
        z = ctx.tok_index_zero
        if z is None:  # as models/halo.py Pack: no zero, no ordering edge
            raise RuntimeError(
                f"{self.desc()}: INDEX_TIE op traced without tok_index_zero "
                "(executor contract violated — the post would have no "
                "happens-before edge)"
            )
        if _interpret():
            # the interpreter drops an operand its body never reads: there
            # the token stays a value-preserving add on the source
            return {
                self._dst: rdma_shift_fused(
                    x + z.astype(x.dtype), axes, axis, self._shift,
                    collective_id=self._cid,
                )
            }
        send, recv, y = rdma_shift_post(
            x, axes, axis, self._shift, collective_id=self._cid, tok_zero=z
        )
        inflight = getattr(ctx, "inflight", None)
        if inflight is not None:
            inflight[self._dst] = functools.partial(
                rdma_shift_wait, x, send, recv,
                axes=axes, axis=axis, shift=self._shift,
            )
        return {self._dst: y}

    def uses_pallas(self) -> bool:
        return True

    def to_json(self) -> Dict[str, Any]:
        j = super().to_json()
        j.update(axis=self._axis, shift=self._shift, collective_id=self._cid)
        return j
