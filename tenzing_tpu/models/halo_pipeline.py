"""Single-chip 3D halo-exchange pipeline: the north-star benchmark workload.

Parity target: the reference's halo-exchange benchmark graph
(``HaloExchange::add_to_graph``, src/halo_exchange/ops_halo_exchange.cu:33-257)
— per face direction ``Pack(GpuOp) -> OwningIsend -> MultiWait`` and
``OwningIrecv -> Wait -> Unpack(GpuOp)``, searched over order x stream
assignment with config nQ=3, 512^3 cells, radius 3
(halo_run_strategy.hpp:42-49; BASELINE.md).

TPU-native single-chip realization.  The environment benches on ONE chip, so
the network hop is realized as the chip's asynchronous host round-trip DMA
(``HostSpillStart`` -> ``HostFetchStart``, the measured overlap substrate of
experiments/lane_overlap.py) — each direction's face travels
device -> pinned-host -> device, the single-chip analog of the reference's
staging through MPI.  Numerically this is the periodic 1x1x1-shard case: every
ghost shell receives the shard's own opposite interior face (the same result
``models/halo.py`` computes on an ``mx=my=mz=1`` mesh).

Per direction ``d`` the DAG is::

    pack_d (DeviceOp, lane-searched)      # slice interior face -> buf_d
      -> spill_d (HostSpillStart)         # post async device->host DMA
      -> fetch_d (HostFetchStart)         # post async host->device DMA
      -> await_d (AwaitTransfer)          # the reference's Wait
      -> unpack_d (DeviceOp, lane-searched)  # write ghost shell

The six chains are independent: the searched freedom is exactly the
reference's — how the six posts, waits, packs and unpacks interleave across
lanes, with the naive baseline (``naive_order``) the fully-synchronous
serialization that finishes each direction before starting the next (post
immediately awaited: MPI_Send-like blocking semantics).

Send-side completion note: the reference wires every ``OwningIsend`` into one
``MultiWait("he_wait_sends")`` because MPI requests must be waited.  Here the
spill's completion handle is the host buffer itself, which the fetch consumes
as a data dependency, so a separate send-side wait op would be a no-op by
construction (comm_ops.AwaitTransfer skips host-space buffers); the
post/await split on the receive side carries the whole overlap freedom.

With ``impl_choice=True`` pack/unpack become ChoiceOps over an XLA-slice vs
Pallas-kernel menu (ops/halo_pallas.py) — the analog of the reference's two
storage-order CUDA kernel families (ops_halo_exchange.cu:519-699).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.models.halo import (
    DIRECTIONS,
    HaloArgs,
    Pack,
    Unpack,
    _face_axis,
    _face_slices,
    dir_name,
    sublane_tile,
)
from tenzing_tpu.ops.comm_ops import AwaitTransfer, HostFetchStart, HostSpillStart


def _flat_rows(sizes) -> int:
    """Rows of the (rows, 128) staging layout for a face of ``sizes``."""
    n = int(np.prod(sizes))
    return -(-n // 128)


def flatten_face(face, sizes):
    """Face tensor -> (rows, 128) staging layout (shared by the XLA and Pallas
    pack variants; the inverse of :func:`unflatten_face`)."""
    import jax.numpy as jnp

    n = int(np.prod(sizes))
    flat = jnp.pad(face.reshape(-1), (0, _flat_rows(sizes) * 128 - n))
    return flat.reshape(-1, 128)


def unflatten_face(flat, sizes):
    """(rows, 128) staging layout -> face tensor of ``sizes``."""
    n = int(np.prod(sizes))
    return flat.reshape(-1)[:n].reshape(tuple(sizes))


def staged_sizes(d, sizes) -> Tuple[int, ...]:
    """The extents a halo face of ``sizes`` (the shell's own ``(nq, sx, sy,
    sz)``) has in its staging buffer, the ONE order every pack entry of
    direction ``d`` writes and every unpack entry of it reads (the search
    picks the two independently).  A lane-thin face, a z face with ``sz <
    sy`` (the rule ``Pack`` and ``Unpack`` tell one by), is staged TURNED,
    ``(nq, sx, sz, sy)``: the form ``pack_face_window`` writes and
    ``unpack_face_window`` reads (ops/halo_pallas.py), so between the menu's
    ``.window`` entries no XLA fusion ever sees a ``(.., sy, 3)`` face, which
    the default layout pads 3 -> 128 lanes.  Every other face is staged as
    it is."""
    nq, sx, sy, sz = sizes
    turned = _face_axis(d) == 3 and sz < sy
    return (nq, sx, sz, sy) if turned else (nq, sx, sy, sz)


def stage_face(face, d):
    """A halo face in the shell's own shape -> direction ``d``'s (rows, 128)
    staging buffer, in :func:`staged_sizes`' order."""
    import jax.numpy as jnp

    if staged_sizes(d, face.shape) != tuple(face.shape):
        face = jnp.swapaxes(face, 2, 3)
    return flatten_face(face, face.shape)


def unstage_face(flat, d, sizes):
    """Direction ``d``'s staging buffer -> the face in the shell's own
    ``sizes`` (the inverse of :func:`stage_face`)."""
    import jax.numpy as jnp

    staged = staged_sizes(d, sizes)
    face = unflatten_face(flat, staged)
    return face if staged == tuple(sizes) else jnp.swapaxes(face, 2, 3)


class PackFlat(Pack):
    """Pack that emits the face as a 128-lane-flattened (rows, 128) staging
    buffer.  Probed on both the CPU backend and TPU v5e: spilling a 4D face
    with a tiny trailing dim (z-faces are (nq, lx, ly, r)) through
    pinned-host memory corrupts the round-trip (XLA copies only a partial
    stripe — a layout bug in mixed-memory copies of oddly-shaped tensors), so
    every staged transfer uses the 2D tiled layout the host-offload path is
    reliable for — which is also what the reference does with its staging
    buffers (contiguous pack buffers, ops_halo_exchange.hpp:97-186).
    The slice itself, and how it takes its ordering token (INDEX_TIE), are
    the base class's ``_xla_slice``, for every face; the order the face has
    in the buffer is :func:`stage_face`'s, a z face turned.  This is naive's
    pack and the menu's ``.xla`` entry; the menu's ``.window`` entry
    (ops/halo_pallas.py ``PackWindow``) reads a z face with the mesh halo's
    window kernel and stages what the kernel wrote.  See
    :class:`tenzing_tpu.models.halo.Pack`."""

    def uses_pallas(self) -> bool:
        return False

    def apply(self, bufs, ctx):
        face = self._xla_slice(bufs, ctx)
        return {f"buf_{dir_name(self._d)}": stage_face(face, self._d)}


class UnpackRecv(Unpack):
    """Unpack reading the fetched (round-tripped) flat staging buffer:
    :func:`unstage_face` back to the face extents, then one
    ``dynamic_update_slice`` into the ghost shell whatever the face, on the
    executor's value-tied read.  This is naive's unpack and the menu's
    ``.xla`` entry; the kernels are the menu's (ops/halo_pallas.py
    ``UnpackChoice``: the aliased window-DMA kernels on the tile-padded
    grid and, for a z face, ``UnpackWindow``, which hands the staged face
    to the mesh halo's window kernel as it is)."""

    INDEX_TIE = False

    def uses_pallas(self) -> bool:
        return False

    def _face(self, bufs):
        """(ghost-shell starts, the received face in the shell's shape)."""
        starts, _ = _face_slices(self._args, self._d, "unpack")
        _, sizes = _face_slices(self._args, self._d, "pack")
        return starts, unstage_face(
            bufs[f"recv_{dir_name(self._d)}"], self._d, sizes)

    def apply(self, bufs, ctx):
        import jax.lax as lax

        starts, face = self._face(bufs)
        return {"U": lax.dynamic_update_slice(bufs["U"], face, starts)}


class HostRoundTrip(CompoundOp):
    """The host-staged transfer as one expandable vertex: post the
    device->host spill, then the host->device fetch — the non-GPU-aware-MPI
    staging analog, packaged so it can sit in a ChoiceOp next to the
    device-resident RDMA alternative."""

    def __init__(self, name: str, dname: str, buf: str, host: str, recv: str):
        super().__init__(name)
        self._dname = dname
        self._buf, self._host, self._recv = buf, host, recv

    def graph(self) -> Graph:
        g = Graph()
        spill = HostSpillStart(f"spill_{self._dname}", self._buf, self._host)
        fetch = HostFetchStart(f"fetch_{self._dname}", self._host, self._recv)
        g.start_then(spill)
        g.then(spill, fetch)
        g.then_finish(fetch)
        return g


class TransferChoice(ChoiceOp):
    """The transfer-engine menu for one direction's network hop: the
    host-staged round trip (PCIe + host memory, the non-CUDA-aware staging
    analog) vs a device-resident RDMA copy (the chip's DMA engine, the
    CUDA-aware analog — SURVEY §7.0's 'device buffers addressed by ICI DMA').
    Which engine, like which kernel, is the solver's question."""

    def __init__(self, d: Tuple[int, int, int]):
        name = dir_name(d)
        super().__init__(f"xfer_{name}")
        self._d = tuple(d)

    def choices(self) -> List:
        from tenzing_tpu.ops.rdma import RdmaCopyStart

        name = dir_name(self._d)
        return [
            HostRoundTrip(
                f"xfer_{name}.host", name, f"buf_{name}", f"host_{name}",
                f"recv_{name}"
            ),
            RdmaCopyStart(f"xfer_{name}.rdma", f"buf_{name}", f"recv_{name}"),
        ]


def direction_ops(args: HaloArgs, d: Tuple[int, int, int], impl_choice: bool = False,
                  xfer_choice: bool = False, engine: str = "host"):
    """The op chain for one face direction: (pack, transfer ops, await,
    unpack).  ``impl_choice`` turns pack/unpack into the kernel menu;
    ``xfer_choice`` turns the transfer into the engine menu; ``engine``
    ("host" | "rdma" | "mixed") wires one engine directly when no menu is
    wanted (the heuristic incumbents pick an engine up front —
    greedy_phase_order makes no ChooseOp decisions); "mixed" alternates
    engines across directions so both physical transfer paths run
    concurrently (the flagship 1.337x incumbent)."""
    if engine not in ("host", "rdma", "mixed"):
        raise ValueError(f"unknown transfer engine {engine!r}")
    name = dir_name(d)
    if impl_choice:
        from tenzing_tpu.ops.halo_pallas import PackChoice, UnpackChoice

        pack = PackChoice(args, d)
        unpack = UnpackChoice(args, d)
    else:
        pack = PackFlat(args, d)
        unpack = UnpackRecv(args, d)
    if engine == "mixed":
        # alternate engines across directions: the host path (PCIe + host
        # memory) and the on-device DMA engine are DIFFERENT physical
        # transfer resources, so a mixed assignment moves faces over both
        # concurrently — a point the per-direction ChoiceOp space contains
        # and this incumbent seeds directly
        engine = "rdma" if DIRECTIONS.index(tuple(d)) % 2 == 0 else "host"
    if xfer_choice:
        xfer: Tuple = (TransferChoice(d),)
    elif engine == "rdma":
        from tenzing_tpu.ops.rdma import RdmaCopyStart

        xfer = (RdmaCopyStart(f"xfer_{name}.rdma", f"buf_{name}", f"recv_{name}"),)
    else:
        xfer = (
            HostSpillStart(f"spill_{name}", f"buf_{name}", f"host_{name}"),
            HostFetchStart(f"fetch_{name}", f"host_{name}", f"recv_{name}"),
        )
    await_ = AwaitTransfer(f"await_{name}", f"recv_{name}")
    return (pack,) + xfer + (await_, unpack)


def add_to_graph(
    g: Graph,
    args: HaloArgs,
    preds: Optional[List] = None,
    succs: Optional[List] = None,
    impl_choice: bool = False,
    xfer_choice: bool = False,
    engine: str = "host",
) -> Graph:
    """Six independent pack -> transfer -> await -> unpack chains
    (reference HaloExchange::add_to_graph shape, ops_halo_exchange.cu:33-257)."""
    preds = preds if preds is not None else [g.start()]
    succs = succs if succs is not None else [g.finish()]
    for d in DIRECTIONS:
        ops = direction_ops(args, d, impl_choice, xfer_choice, engine)
        pack, unpack = ops[0], ops[-1]
        for p in preds:
            g.then(p, pack)
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        for s in succs:
            g.then(unpack, s)
    return g


def build_graph(args: HaloArgs, impl_choice: bool = False,
                xfer_choice: bool = False, engine: str = "host") -> Graph:
    return add_to_graph(Graph(), args, impl_choice=impl_choice,
                        xfer_choice=xfer_choice, engine=engine)


# phase order of the pipeline's op-name prefixes (greedy incumbents and the
# hill-climb policy share it; covers both transfer engines)
HALO_PHASES = ("start", "pack", "spill", "fetch", "xfer", "await", "unpack",
               "finish")


def naive_order(args: HaloArgs, platform) -> Sequence:
    """The naive sequential baseline: one lane, each direction's chain completed
    (post immediately awaited) before the next starts — the fully-synchronous
    program the search must beat (BASELINE.md north star).  Derived through
    the SDP machinery (solve/greedy.py) so the schedule carries the sync ops
    the soundness verifier requires between a lane-bound pack and its
    host-side spill."""
    from tenzing_tpu.solve.greedy import serialized_chain_order

    rank = {dir_name(d): i for i, d in enumerate(DIRECTIONS)}
    return serialized_chain_order(
        build_graph(args), platform,
        lambda name: rank[name.split("_", 1)[1]])


def greedy_overlap_order(args: HaloArgs, platform, engine: str = "host") -> Sequence:
    """The post-all-before-await-any heuristic schedule, derived through the
    SDP machinery so the required sync ops are inserted exactly as the solver
    would.  This is the discipline the *reference's* halo graph hard-codes
    with its every-post-before-any-wait edges (ops_halo_exchange.cu:249-256);
    here the graph leaves the order free and this incumbent seeds the anytime
    search with it: packs round-robin across lanes, every transfer posted
    before any await, unpacks last (solve/greedy.py)."""
    from tenzing_tpu.solve.greedy import greedy_phase_order

    return greedy_phase_order(build_graph(args, engine=engine), platform,
                              HALO_PHASES)


def paired_priority(engine: str = "mixed"):
    """Per-op priority for the PAIRED overlap discipline: all packs, all
    posts, then per-direction ``await_d -> unpack_d`` pairs — each face is
    unpacked as soon as ITS transfer lands instead of after ALL transfers
    land (the phase discipline's all-awaits barrier).  Directions are visited
    fastest-engine-first: with ``engine='mixed'`` the on-chip DMA dirs
    (even DIRECTIONS indices) complete in microseconds and their unpacks run
    while the host round trips are still in flight — exactly the overlap the
    post/wait split exists to expose (reference Wait placement freedom,
    ops_mpi.hpp:121-131).  For phase_policy(priority=...) and the climb."""
    order = sorted(range(len(DIRECTIONS)),
                   key=lambda i: (i % 2 if engine == "mixed" else 0, i))
    rank = {dir_name(DIRECTIONS[i]): r for r, i in enumerate(order)}

    def priority(name: str) -> int:
        if name.startswith(("start",)):
            return 0
        if name.startswith("pack"):
            return 1
        if name.startswith(("spill", "fetch", "xfer")):
            return 2
        if name.startswith(("await", "unpack")):
            d = name.split("_", 1)[1].split(".", 1)[0]
            return 10 + 2 * rank[d] + (0 if name.startswith("await") else 1)
        return 99  # finish

    return priority


def paired_overlap_order(args: HaloArgs, platform, engine: str = "mixed") -> Sequence:
    """The paired await/unpack incumbent schedule (see :func:`paired_priority`),
    derived through the SDP machinery like the greedy incumbents."""
    from tenzing_tpu.solve.local import drive, phase_policy

    seq, _ = drive(
        build_graph(args, engine=engine), platform,
        phase_policy(platform, HALO_PHASES, priority=paired_priority(engine)),
    )
    return seq


def _padded_shape(shape: Tuple[int, int, int, int],
                  itemsize: int = 4) -> Tuple[int, int, int, int]:
    """U allocated with trailing dims padded to TPU tiling (sublane tile x
    128 lanes; the sublane tile scales with dtype width — 8 for 4-byte, 16
    for 2-byte, 32 for 1-byte): Mosaic requires HBM plane DMAs tile-aligned
    (ops/halo_pallas.py), and the padding is invisible to the XLA slice path
    (all face slices are interior)."""
    nq, x, y, z = shape
    st = sublane_tile(itemsize)
    return (nq, x, -(-y // st) * st, -(-z // 128) * 128)


def make_pipeline_buffers(
    args: HaloArgs, seed: int = 0, with_expected: bool = True
) -> Tuple[Dict[str, np.ndarray], Optional[np.ndarray]]:
    """(buffers, expected U): ghost shells filled with the shard's own opposite
    interior faces (periodic 1-shard domain).  ``with_expected=False`` skips
    the expected-U copy (a ~2 GB allocation at the reference bench config).
    The grid dtype is ``args.dtype`` — one source of truth shared with the
    Pallas menu gate (ops/halo_pallas.py ``_face_bx``)."""
    r = args.radius
    dtype = np.dtype(args.dtype)
    rng = np.random.default_rng(seed)
    U = np.zeros(_padded_shape(args.local_shape(), dtype.itemsize),
                 dtype=dtype)
    U[:, r : r + args.lx, r : r + args.ly, r : r + args.lz] = rng.random(
        (args.nq, args.lx, args.ly, args.lz), dtype=np.float32
    ).astype(dtype, copy=False)
    want = None
    if with_expected:
        want = U.copy()
        for d in DIRECTIONS:
            ps, sz = _face_slices(args, d, "pack")
            us, _ = _face_slices(args, d, "unpack")
            face = U[
                :, ps[1] : ps[1] + sz[1], ps[2] : ps[2] + sz[2], ps[3] : ps[3] + sz[3]
            ]
            want[
                :, us[1] : us[1] + sz[1], us[2] : us[2] + sz[2], us[3] : us[3] + sz[3]
            ] = face
    bufs: Dict[str, np.ndarray] = {"U": U}
    for d in DIRECTIONS:
        name = dir_name(d)
        _, sz = _face_slices(args, d, "pack")
        flat = np.zeros((_flat_rows(sz), 128), dtype=dtype)
        bufs[f"buf_{name}"] = flat
        bufs[f"host_{name}"] = flat.copy()  # placed in pinned_host by the caller
        bufs[f"recv_{name}"] = flat.copy()
    return bufs, want


def host_buffer_names() -> List[str]:
    """Buffers that must be device_put into pinned_host before execution (the
    executor detects host residency from the array's sharding memory_kind)."""
    return [f"host_{dir_name(d)}" for d in DIRECTIONS]
