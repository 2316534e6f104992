"""Async communication ops: the post/wait split, as schedulable vertices.

Parity target: reference ``include/tenzing/mpi/ops_mpi.hpp`` (Isend / Irecv /
Ialltoallv / Wait / OwningWaitall / MultiWait, :17-146) and the SpMV batch comm
ops (``ops_spmv.cuh:217-304`` PostRecv/WaitRecv/PostSend/WaitSend).  The split
between *posting* a transfer and *waiting* for it IS the overlap opportunity
the search exists to exploit (SURVEY.md §7.0) — collapsing an exchange into one
synchronous op (round 1) removed the schedule freedom the solver is supposed to
explore.

TPU-native semantics.  The reference's Isend/Irecv are *host-posted* ops: the
network DMA proceeds asynchronously off-stream, and ``Wait`` (a CpuOp) blocks
the host chain (EventSynchronizer's CPU case table, event_synchronizer.hpp).
The analog here:

* a **start op** contributes the transfer to the traced program: its *inputs*
  are tied to the host chain at the post point (a transfer cannot begin before
  its source is produced and the host program reaches the post), but its
  *completion* is NOT joined into any chain — the in-flight value simply sits
  in the buffer dict, and XLA lowers it as an async pair (copy-start/copy-done
  for host transfers, collective-permute-start/done for ICI permutes) whose
  done is placed as late as data dependencies allow;
* an **AwaitTransfer** joins the in-flight value's completion into the host
  chain (reference ``Wait``): every op scheduled after it — on any lane —
  observes the transfer as finished; ops scheduled between the start and the
  await overlap the DMA.  ``MultiAwait`` waits a set (reference MultiWait).

Transfers available:

* :class:`HostSpillStart` / :class:`HostFetchStart` — device->host-pinned and
  host->device copies (the single-chip async DMA; PCIe on real hardware).  The
  TPU analog of ``cudaMemcpyAsync`` staging, and the measured substrate of the
  lane-overlap proof (runtime/executor.py docstring: 20.8 ms serialized vs
  14.0 ms overlapped on v5e).
* :class:`PermuteStart` — ``lax.ppermute`` over a mesh axis (ICI neighbor
  exchange; reference Isend+Irecv pair to a neighbor rank).  XLA lowers it to
  collective-permute-start/done; the await placement decides how much compute
  hides the ICI hop.

These are plain named graph vertices: serdes re-anchors them by name
(core/serdes.py), and they need no lane-assignment decision (host-posted, like
the reference's CpuOp comm ops) — the searched freedom is their *position* in
the order, exactly the reference's post/wait placement freedom.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence as Seq

from tenzing_tpu.core.operation import CpuOp, register_kind


def _to_memory_kind(x, kind: str):
    """``x`` moved to the "pinned_host" or "device" memory of whatever device
    the surrounding program runs on.  A memory-space transfer, not a sharding
    on ``jax.devices()[0]``: it lowers to the same ``annotate_device_placement``
    but names no device, so the program also traces for a device that is
    described and not attached (tests/test_tpu_compile.py) and under a mesh."""
    import jax

    space = {"pinned_host": jax.memory.Space.Host,
             "device": jax.memory.Space.Device}[kind]
    return jax.device_put(x, space)


class CommStart(CpuOp):
    """Base: a host-posted async transfer (reference Isend/Irecv shape).

    Subclasses implement ``apply`` (the transfer's dataflow) and declare
    ``DST_SPACE`` ("host" or "device") — the executor tracks which buffers are
    host-resident because host-space tensors admit only pure copies (no
    tie arithmetic; measured TPU toolchain limitation).  Tracing ties the
    *device-side* end of the transfer to the host chain at the post point
    (source for spills/permutes, destination for fetches) but does NOT join
    completion into any chain — that is AwaitTransfer's job.
    """

    DST_SPACE = "device"
    # True: the op takes the post point's token by index, a kernel operand
    # (``ctx.tok_index_zero``: ops/rdma.py RdmaShiftStart), and its reads
    # get no value-preserving add
    INDEX_TIE = False

    def __init__(self, name: str, src: str, dst: str):
        super().__init__(name)
        self._src = src
        self._dst = dst

    def src(self) -> str:
        return self._src

    def dst(self) -> str:
        return self._dst

    def reads(self) -> List[str]:
        return [self._src]

    def writes(self) -> List[str]:
        return [self._dst]

    def apply(self, bufs: Dict[str, Any], ctx) -> Dict[str, Any]:
        raise NotImplementedError

    def trace(self, tc) -> None:
        view = dict(tc.bufs)
        if self.INDEX_TIE:
            tc.tie_by_index(tc._host_tok)
        else:
            for name in self.reads():
                # host-space reads skip the tie inside tie_named; their post
                # ordering then rests on the destination-side tie below
                view[name] = tc.tie_named(name, view[name], tc._host_tok)
        out = self.apply(view, tc)
        for name, val in out.items():
            if name not in tc.bufs:
                raise KeyError(
                    f"comm op {self.desc()!r} writes undeclared buffer {name!r}"
                )
            if self.DST_SPACE == "host":
                tc.host_space.add(name)
            else:
                tc.host_space.discard(name)
                if self._src in tc.host_space:
                    # fetch from host: the source tie was skipped, so anchor
                    # the post point on the device result instead
                    val = tc._tie(val, tc._host_tok)
            tc.bufs[name] = val
        # deliberately NO chain advance: the transfer is in flight

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.KIND, "name": self.name(), "src": self._src, "dst": self._dst}


@register_kind("host_spill_start")
class HostSpillStart(CommStart):
    """Post an async device->host copy of ``src`` into host buffer ``dst``."""

    DST_SPACE = "host"

    def apply(self, bufs, ctx):
        return {self._dst: _to_memory_kind(bufs[self._src], "pinned_host")}


@register_kind("host_fetch_start")
class HostFetchStart(CommStart):
    """Post an async host->device copy of ``src`` into device buffer ``dst``."""

    def apply(self, bufs, ctx):
        return {self._dst: _to_memory_kind(bufs[self._src], "device")}


@register_kind("permute_start")
class PermuteStart(CommStart):
    """Post a neighbor shift of ``src`` over mesh axis ``axis`` into ``dst``
    (ICI hop; XLA lowers to collective-permute-start/done)."""

    def __init__(self, name: str, src: str, dst: str, axis: str, shift: int = 1):
        super().__init__(name, src, dst)
        self._axis = axis
        self._shift = shift

    def apply(self, bufs, ctx):
        import jax

        n = jax.lax.axis_size(self._axis)
        s = self._shift % n
        perm = [(i, (i + s) % n) for i in range(n)]
        return {self._dst: jax.lax.ppermute(bufs[self._src], self._axis, perm)}

    def to_json(self) -> Dict[str, Any]:
        j = super().to_json()
        j.update(axis=self._axis, shift=self._shift)
        return j


@register_kind("all_to_all_start")
class AllToAllStart(CommStart):
    """Post a width-padded all-to-all over mesh axis ``axis`` — the reference
    ``Ialltoallv`` (ops_mpi.hpp:82-119), with raggedness handled by padding
    each pairwise segment to the common width (there is no ragged all-to-all
    on ICI).  ``src``/``dst`` are (batch, n, w)-per-shard buffers whose
    ``split_axis`` indexes the peer shard: out[:, q, :] is what shard q sent
    here."""

    def __init__(self, name: str, src: str, dst: str, axis: str,
                 split_axis: int = 1):
        super().__init__(name, src, dst)
        self._axis = axis
        self._split = split_axis

    def apply(self, bufs, ctx):
        import jax

        return {
            self._dst: jax.lax.all_to_all(
                bufs[self._src], self._axis, self._split, self._split
            )
        }

    def to_json(self) -> Dict[str, Any]:
        j = super().to_json()
        j.update(axis=self._axis, split_axis=self._split)
        return j


@register_kind("psum_start")
class PsumStart(CommStart):
    """Post an all-reduce (sum) of ``src`` over mesh axis ``axis`` into
    ``dst`` — the collective analog of the reference's nonblocking collective
    (Ialltoallv, ops_mpi.hpp:82-119) for the tensor-parallel pattern: XLA
    lowers it to all-reduce-start/done, and the await placement decides how
    much compute hides the reduction."""

    def __init__(self, name: str, src: str, dst: str, axis: str):
        super().__init__(name, src, dst)
        self._axis = axis

    def apply(self, bufs, ctx):
        import jax

        return {self._dst: jax.lax.psum(bufs[self._src], self._axis)}

    def to_json(self) -> Dict[str, Any]:
        j = super().to_json()
        j.update(axis=self._axis)
        return j


def _settle_inflight(tc, name: str) -> None:
    """If ``name`` has an explicit in-flight completion handle (split-kernel
    RDMA, ops/rdma.py), run its wait kernel now: the buffer value becomes the
    *completed* destination and downstream consumers (and the host-chain join)
    depend on the semaphore wait, not merely on the post."""
    pending = getattr(tc, "inflight", {}).pop(name, None)
    if pending is not None:
        tc.bufs[name] = pending(tc.bufs[name])


@register_kind("await_transfer")
class AwaitTransfer(CpuOp):
    """Wait for an in-flight buffer: joins its completion into the host chain
    (reference Wait, ops_mpi.hpp:121-131).  Ops ordered after this observe the
    transfer as done; ops between the start and this op overlap the DMA."""

    def __init__(self, name: str, buf: str):
        super().__init__(name)
        self._buf = buf

    def buf(self) -> str:
        return self._buf

    def reads(self) -> List[str]:
        return [self._buf]

    def trace(self, tc) -> None:
        from tenzing_tpu.runtime.executor import _clean, _scalarize

        if self._buf in tc.host_space:
            # a spilled (host-resident) buffer exposes no device-readable
            # completion handle; with SSA buffers a spill needs no wait for
            # source reuse anyway — await the round-trip's fetch result instead
            return
        _settle_inflight(tc, self._buf)
        tc._host_tok = tc._join(tc._host_tok, _clean(_scalarize(tc.bufs[self._buf])))

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.KIND, "name": self.name(), "buf": self._buf}


@register_kind("multi_await")
class MultiAwait(CpuOp):
    """Wait for a set of in-flight buffers (reference MultiWait/OwningWaitall,
    ops_mpi.hpp:133-146): one schedulable op for the wait-all discipline."""

    def __init__(self, name: str, bufs: Seq[str]):
        super().__init__(name)
        self._bufs = list(bufs)

    def bufs(self) -> List[str]:
        return list(self._bufs)

    def reads(self) -> List[str]:
        return list(self._bufs)

    def trace(self, tc) -> None:
        from tenzing_tpu.runtime.executor import _clean, _scalarize

        for b in self._bufs:
            if b not in tc.host_space:
                _settle_inflight(tc, b)
        toks = [
            _clean(_scalarize(tc.bufs[b])) for b in self._bufs if b not in tc.host_space
        ]
        tc._host_tok = tc._join(tc._host_tok, *toks)

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.KIND, "name": self.name(), "bufs": list(self._bufs)}
