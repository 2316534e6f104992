"""3D halo exchange over a device mesh: the spatial-decomposition workload.

Parity target: reference ``src/halo_exchange`` + ``include/tenzing/halo_exchange``
(C11 in SURVEY.md §2): a ``nX x nY x nZ x nQ`` grid with ghost radius ``r`` is
decomposed over ranks; per face direction the DAG is
Pack(GpuOp) -> Isend -> wait, Irecv -> Wait -> Unpack(GpuOp)
(``HaloExchange::add_to_graph``, ops_halo_exchange.cu:33-257), with pack/unpack
CUDA kernels per storage order (ops_halo_exchange.cu:519-699) and periodic
rank-coordinate wrap (halo_run_strategy.hpp:80-98).

TPU-native redesign: the grid (with ghost shells) is sharded over a 3D device
mesh ``("x", "y", "z")``; per direction the DAG is
Pack(slice of the interior edge) -> post (host-posted transfer along the
face's mesh axis, periodic: ``PermuteStart`` ICI collective-permute or
``RdmaShiftStart`` per-neighbor remote DMA) -> AwaitTransfer (the reference's
Wait) -> Unpack(the ghost-shell write).  The x faces' packs and unpacks are
XLA slice ops (contiguous copies the compiler fuses; the reference needs
hand-written CUDA kernels for exactly this); the y and z faces, thin along
the grid's sublane and lane axes, leave and enter the grid through a pair of
window kernels (:class:`Pack`, :class:`Unpack`).  The six directions are
independent in the graph and the post and wait are separate vertices, so the
solver searches how exchanges overlap each other and how much work hides
between each post and its wait — the reference's post-all-before-wait-any
discipline becomes one more region of the schedule space rather than a
hard-coded edge set.

SSA note: the six Unpacks all write ``U``, so within one schedule they chain
through the buffer's SSA versions in sequence order (disjoint ghost regions, so
any order is numerically identical); pack/exchange stages overlap freely.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp

# the six face directions (reference loops dx,dy,dz with exactly_one,
# ops_halo_exchange.cu:29-31,57-144)
DIRECTIONS: List[Tuple[int, int, int]] = [
    (1, 0, 0),
    (-1, 0, 0),
    (0, 1, 0),
    (0, -1, 0),
    (0, 0, 1),
    (0, 0, -1),
]

_AXIS_NAMES = ("x", "y", "z")


def dir_name(d: Tuple[int, int, int]) -> str:
    """'px'/'mx'/'py'/... (the reference's dir_to_tag analog,
    ops_halo_exchange.cu:16-27)."""
    for i, v in enumerate(d):
        if v != 0:
            return ("p" if v > 0 else "m") + _AXIS_NAMES[i]
    raise ValueError(d)


@dataclass(frozen=True)
class HaloArgs:
    """Per-shard grid extents (reference HaloExchange::Args,
    ops_halo_exchange.hpp:33-55; rank coords come from the mesh, not lambdas)."""

    nq: int = 3
    lx: int = 64
    ly: int = 64
    lz: int = 64
    radius: int = 3
    # grid element dtype, as a string so the dataclass stays hashable (the
    # sublane tile — and with it the Pallas menu gating — depends on itemsize)
    dtype: str = "float32"

    def local_shape(self) -> Tuple[int, int, int, int]:
        r = self.radius
        return (self.nq, self.lx + 2 * r, self.ly + 2 * r, self.lz + 2 * r)

    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize


def _face_axis(d: Tuple[int, int, int]) -> int:
    """The grid axis (of ``(q, x, y, z)``) along which direction ``d``'s face
    is thin: 1, 2 or 3."""
    return 1 + [i for i, v in enumerate(d) if v != 0][0]


def sublane_tile(itemsize: int) -> int:
    """TPU sublane tile for an element width (8 for 4-byte, 16 for 2-byte,
    32 for 1-byte) — the ONE definition shared by the grid padding
    (halo_pipeline._padded_shape) and the Pallas window/menu gating
    (ops/halo_pallas._tile_window): the two must agree or the kernels'
    tile-aligned HBM DMA windows fall outside the allocated padding."""
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def _face_slices(args: HaloArgs, d: Tuple[int, int, int], which: str):
    """Start indices + sizes of the face region along direction ``d``:
    ``which`` = 'pack' (interior edge) or 'unpack' (ghost shell)."""
    r = args.radius
    ext = [args.lx, args.ly, args.lz]
    starts = [0, r, r, r]
    sizes = [args.nq, ext[0], ext[1], ext[2]]
    for i, v in enumerate(d):
        if v == 0:
            continue
        sizes[1 + i] = r
        if which == "pack":
            # the interior edge facing the neighbor
            starts[1 + i] = ext[i] if v > 0 else r
        else:
            # the ghost shell on the OPPOSITE side (data arrives from -d)
            starts[1 + i] = 0 if v > 0 else ext[i] + r
    return starts, sizes


def _index_zero(op, ctx):
    """``ctx.tok_index_zero`` for an INDEX_TIE op.  It MUST come from the
    executor contract: a missing/None value means the op would trace with
    no ordering edge at all, so fail loudly."""
    z = ctx.tok_index_zero
    if z is None:
        raise RuntimeError(
            f"{op.desc()}: INDEX_TIE op traced without tok_index_zero "
            "(executor contract violated — the op would have no "
            "happens-before edge)"
        )
    return z


class Pack(DeviceOp):
    """Cut the interior edge for one direction (reference Pack,
    ops_halo_exchange.hpp:97-141, kernels ops_halo_exchange.cu:519-573).

    Which read depends on what the op can see, the face's thin axis in the
    grid ``(q, x, y, z)``, as for :class:`Unpack`:

    * x (axis 1, a leading dimension): ``lax.dynamic_slice``, whole tiles,
      which XLA fuses into the exchange that reads the face (0.03-0.05 ms);
    * y or z (axis 2 or 3, the grid's sublane or lane axis): the window
      kernel ``ops/halo_pallas.py`` ``pack_face_window``, which says why
      (XLA's strided z slices were 4.1 of the mesh cell's 9.44 ms, and its
      fused y slices the reason the ``xla`` overlap program relayouted the
      whole grid) and why a z face leaves it transposed.  The op then
      counts as a Pallas op (``uses_pallas``), and the program's counter
      ``halo.window_packs`` says, per traced body, how many faces went this
      way.

    INDEX_TIE, either way: the op takes its ordering token into the read's
    START index (``ctx.tok_index_zero``, an int32 zero that depends on the
    token; for the kernel a scalar-prefetch operand on its x block index),
    not as a value-preserving add on its read.  The happens-before is the
    same (the read cannot start before the token) and the face bit-identical,
    but the read is the whole grid and six packs read it: a value-add makes
    six live versions of the grid, each a full pass over it.  Measured on a
    v5e: the one-chip flagship (2.07 GB grid) paid 21 ms an iteration in
    fused full-grid adds and 13 ms in dynamic-update-slices that could no
    longer be done in place; the mesh cell ``halo512-mesh4.mcts`` (1.27 GB a
    shard), value-tied until PR 29, read 36.5 ms an iteration and 10.2 GB of
    temporaries for its overlap schedule, 20.8 ms and 5.1 GB since (its
    updates were in place before and after: PERF.md, PR 29).  In the slice
    the zero goes onto the DIRECTION axis, where ``start < dim - size``
    keeps the dynamic-slice clamp non-degenerate: on a full-extent axis the
    clamp is provably 0 and XLA folds the edge away (probed: the compiled
    program had static slices and no token edge).

    ``halo_pipeline.PackFlat`` (the one-chip twin: a tile-padded grid and a
    dense flat staging buffer for a host round trip; naive's pack and the
    menu's ``.xla`` entry) keeps :meth:`_xla_slice` for every face.  Of its
    subclasses, the menu of ops/halo_pallas.py, ``PackWindow`` reads a z
    face with the same window kernel as this class and takes its token the
    same way (PR 48: XLA's z slices were 4.1 of that cell's 10 ms); those
    that need static starts (the window-DMA kernels) set ``INDEX_TIE =
    False`` and get the executor's value-tied read."""

    INDEX_TIE = True

    def __init__(self, args: HaloArgs, d: Tuple[int, int, int]):
        super().__init__(f"pack_{dir_name(d)}")
        self._args, self._d = args, d

    def reads(self):
        return ["U"]

    def writes(self):
        return [f"buf_{dir_name(self._d)}"]

    def uses_pallas(self) -> bool:
        return _face_axis(self._d) >= 2

    def _xla_slice(self, bufs, ctx):
        """The face as one ``lax.dynamic_slice``, the token's zero on the
        direction axis' start."""
        import jax.lax as lax

        starts, sizes = _face_slices(self._args, self._d, "pack")
        z = _index_zero(self, ctx)
        axis = _face_axis(self._d)
        starts = tuple(
            s + z if i == axis else s for i, s in enumerate(starts)
        )
        return lax.dynamic_slice(bufs["U"], starts, sizes)

    def apply(self, bufs, ctx):
        name = f"buf_{dir_name(self._d)}"
        if not self.uses_pallas():
            return {name: self._xla_slice(bufs, ctx)}
        from tenzing_tpu.obs.metrics import get_metrics
        from tenzing_tpu.ops.halo_pallas import _interpret, pack_face_window

        starts, sizes = _face_slices(self._args, self._d, "pack")
        z = _index_zero(self, ctx)
        get_metrics().counter("halo.window_packs").inc()
        return {name: pack_face_window(
            bufs["U"], tuple(starts), tuple(sizes), z,
            interpret=_interpret())}


def _dir_axis_sign(d: Tuple[int, int, int]) -> Tuple[str, int]:
    """(mesh axis name, ±1) of a face direction."""
    return _AXIS_NAMES[_face_axis(d) - 1], (1 if sum(d) > 0 else -1)


def exchange_post(d: Tuple[int, int, int], engine: str = "xla"):
    """The host-posted exchange op for one direction: ``engine='xla'`` is a
    ``PermuteStart`` (ICI collective-permute, XLA-scheduled), ``'rdma'`` a
    ``RdmaShiftStart`` (per-neighbor Pallas remote DMA with a neighbor
    barrier — on TPU a true split post whose wait kernel runs at the matching
    AwaitTransfer; ops/rdma.py).  Both post the transfer and return with it in
    flight — the reference's Isend (ops_mpi.hpp:17-146); the separate await is
    wired by :func:`add_to_graph`."""
    from tenzing_tpu.ops.comm_ops import PermuteStart
    from tenzing_tpu.ops.rdma import RdmaShiftStart

    name = dir_name(d)
    axis, sign = _dir_axis_sign(d)
    if engine == "xla":
        return PermuteStart(
            f"exchange_{name}.xla", f"buf_{name}", f"recv_{name}",
            axis=axis, shift=sign,
        )
    if engine == "rdma":
        return RdmaShiftStart(
            f"exchange_{name}.rdma", f"buf_{name}", f"recv_{name}",
            axis=axis, shift=sign,
            # barrier semaphores are shared by collective id: one id per
            # direction keeps six concurrent exchanges from cross-talking
            collective_id=DIRECTIONS.index(tuple(d)),
        )
    raise ValueError(f"unknown exchange engine {engine!r}")


# -- synthesized exchange (collectives/synth.py) ----------------------------


def halo_synth_counts(args: HaloArgs) -> List[int]:
    """Chunk counts splitting a face's ``nq`` quantities: {1, 2} filtered
    by divisibility — pure routing, bit-identical for any count."""
    return [k for k in (1, 2) if 1 <= k <= args.nq and args.nq % k == 0]


def halo_synth_plans(args: HaloArgs, d: Tuple[int, int, int]):
    """Chunked neighbor-exchange instantiations for one face direction:
    the face payload splits along ``nq`` into k single-hop permutes whose
    awaits interleave (collectives/synth.py::plan_neighbor_shift)."""
    from tenzing_tpu.collectives.synth import plan_neighbor_shift

    name = dir_name(d)
    axis, sign = _dir_axis_sign(d)
    _, sizes = _face_slices(args, d, "pack")
    return [
        plan_neighbor_shift(f"exchange_{name}", f"buf_{name}", f"recv_{name}",
                            axis, sign, tuple(sizes), k,
                            itemsize=args.itemsize())
        for k in halo_synth_counts(args)
    ]


class ExchangeChoice(ChoiceOp):
    """XLA collective-permute vs Pallas remote-DMA for one direction's
    neighbor exchange — the transfer-engine half of the searched menu (the
    kernel half is ops/halo_pallas.py's pack/unpack choice).  Either way the
    chosen op only POSTS the transfer; the graph's AwaitTransfer is the
    separate wait, so the solver places post and wait independently
    (VERDICT r3 item 2).

    With ``synth=True`` the menu additionally offers synthesized
    chunk-routed decompositions of the shift (:func:`halo_synth_plans`,
    priced and pruned per collectives/synth.py) — the engine menu and the
    synthesized menu compete in ONE ChooseOp, so the solvers weigh
    "which engine" and "which decomposition" as a single decision."""

    def __init__(self, d: Tuple[int, int, int], args: Optional[HaloArgs] = None,
                 synth: bool = False, synth_relax: bool = False):
        super().__init__(f"exchange_{dir_name(d)}")
        self._d = tuple(d)
        self._variants: List = []
        if synth:
            if args is None:
                raise ValueError("ExchangeChoice(synth=True) needs HaloArgs")
            from tenzing_tpu.collectives.synth import sketch_menu
            from tenzing_tpu.collectives.topology import mesh_topology

            axis, _ = _dir_axis_sign(self._d)
            _, sizes = _face_slices(args, self._d, "pack")
            face_bytes = float(np.prod(sizes)) * args.itemsize()
            # a single-hop shift's per-link cost is extent-independent, so
            # a 2-ring prices it without knowing the mesh shape
            self._variants, self.synth_menu = sketch_menu(
                halo_synth_plans(args, self._d),
                mesh_topology({axis: 2}, host=False),
                fixed_bytes=face_bytes, relax=synth_relax,
                collective="shift")

    def choices(self):
        return ([exchange_post(self._d, "xla"), exchange_post(self._d, "rdma")]
                + list(self._variants))


class Unpack(DeviceOp):
    """Write the received face into the ghost shell (reference Unpack,
    ops_halo_exchange.hpp:143-186, kernels ops_halo_exchange.cu:611-699 — and
    without the stray device-sync defect noted in SURVEY.md §7.3).

    Which write depends on what the op can see, the face's thin axis in the
    grid ``(q, x, y, z)``:

    * x (axis 1, a leading dimension): ``lax.dynamic_update_slice``, whole
      tiles, 0.086 ms a face at 448^3 a shard (PERF.md, PR 29); the op takes
      its ordering token by value, an add on the received face (7 MB);
    * y or z (axis 2 or 3, the grid's sublane or lane axis): the aliased
      window kernel ``ops/halo_pallas.py`` ``unpack_face_window``, which
      also says why and how it takes the token (``INDEX_TIE``, into a block
      index by scalar prefetch).  The op then counts as a Pallas op
      (``uses_pallas``: ``shard_map`` without ``check_vma``, left out of
      fused regions), and the program's counter ``halo.window_unpacks``
      says, per traced body, how many faces went this way;
    * z (the lane axis, ``sz < sy`` as ``Pack`` tells a lane-thin face): the
      face goes to that kernel TURNED, ``swapaxes(face, 2, 3)``, the form
      ``Pack`` emits.  The buffer ``recv_<d>`` keeps the builder's shape;
      the ``swapaxes`` is a bitcast to a layout of XLA's choosing, so the
      collective-permute's thin-major result is relayouted to an 11 MB
      operand and not to the shell's own ``(nq, sx, sy, 3)``, which the
      default layout pads to 308 MB (0.47 ms of relayout a face and 0.43 of
      the kernel's 1.40 at 448^3 a shard: PERF.md, PR 47).  A remote DMA
      delivers its face padded; XLA then reads it into the turned form
      (0.41 ms) and the face costs what it did, 2.41 -> 2.37 ms from the
      wait into the shell, so no schedule keeps the padded operand.
      ``halo.window_unpacks_turned`` counts these (2 a mesh body).

    Subclasses with a write of their own (``halo_pipeline.UnpackRecv`` and
    the kernel menu of ops/halo_pallas.py) override ``apply`` and declare
    ``INDEX_TIE = False``: they keep the executor's value-tied read.  The
    menu's ``UnpackWindow`` (PR 48) is the exception: the same kernel as
    here on the turned face its staging buffer holds, the token by index."""

    def __init__(self, args: HaloArgs, d: Tuple[int, int, int]):
        super().__init__(f"unpack_{dir_name(d)}")
        self._args, self._d = args, d

    def reads(self):
        return ["U", f"recv_{dir_name(self._d)}"]

    def writes(self):
        return ["U"]

    @property
    def INDEX_TIE(self) -> bool:
        return _face_axis(self._d) >= 2

    def uses_pallas(self) -> bool:
        return self.INDEX_TIE

    def apply(self, bufs, ctx):
        import jax.lax as lax
        import jax.numpy as jnp

        starts, _ = _face_slices(self._args, self._d, "unpack")
        face = bufs[f"recv_{dir_name(self._d)}"]
        if not self.INDEX_TIE:
            return {"U": lax.dynamic_update_slice(bufs["U"], face, starts)}
        from tenzing_tpu.obs.metrics import get_metrics
        from tenzing_tpu.ops.halo_pallas import _interpret, unpack_face_window

        z = _index_zero(self, ctx)
        get_metrics().counter("halo.window_unpacks").inc()
        turned = face.shape[3] < face.shape[2]  # lane-thin, as Pack's rule
        if turned:
            get_metrics().counter("halo.window_unpacks_turned").inc()
            face = jnp.swapaxes(face, 2, 3)
        return {"U": unpack_face_window(
            bufs["U"], face, tuple(starts), z, turned=turned,
            interpret=_interpret())}


class HaloExchange(CompoundOp):
    """The whole 6-direction exchange as one compound op."""

    def __init__(self, args: HaloArgs, name: str = "halo_exchange"):
        super().__init__(name)
        self._args = args

    def graph(self) -> Graph:
        return add_to_graph(Graph(), self._args)

    def args(self) -> HaloArgs:
        return self._args


def add_to_graph(
    g: Graph,
    args: HaloArgs,
    preds: Optional[List] = None,
    succs: Optional[List] = None,
    xfer_choice: bool = False,
    synth: bool = False,
    synth_relax: bool = False,
) -> Graph:
    """Build the per-direction pack -> post -> await -> unpack chains
    (reference HaloExchange::add_to_graph, ops_halo_exchange.cu:33-257: the
    Isend and the Wait are SEPARATE vertices, and their relative placement is
    the searched overlap freedom).  With ``xfer_choice`` each post is a
    ChoiceOp over the transfer-engine menu (XLA collective-permute vs Pallas
    remote DMA) — same flag name as the pipelined halo's transfer menu
    (halo_pipeline.add_to_graph).  ``synth=True`` (implies the choice node)
    appends synthesized chunk-routed decompositions to each direction's
    menu; ``synth_relax`` keeps analytically-losing instantiations
    searchable."""
    from tenzing_tpu.ops.comm_ops import AwaitTransfer

    preds = preds if preds is not None else [g.start()]
    succs = succs if succs is not None else [g.finish()]
    for d in DIRECTIONS:
        name = dir_name(d)
        if synth:
            exch = ExchangeChoice(d, args=args, synth=True,
                                  synth_relax=synth_relax)
        elif xfer_choice:
            exch = ExchangeChoice(d)
        else:
            exch = exchange_post(d, "xla")
        await_ = AwaitTransfer(f"await_{name}", f"recv_{name}")
        pack, unpack = Pack(args, d), Unpack(args, d)
        for p in preds:
            g.then(p, pack)
        g.then(pack, exch)
        g.then(exch, await_)
        g.then(await_, unpack)
        for s in succs:
            g.then(unpack, s)
    return g


def engine_overlap_order(graph: Graph, platform, engine: str):
    """The post-all-before-await-any schedule of an ``xfer_choice`` mesh graph
    (:func:`add_to_graph`) with EVERY exchange on one transfer ``engine``
    ("xla" | "rdma") — the deterministic way to put a named engine on the
    mesh, next to whatever a search happens to explore."""
    from tenzing_tpu.solve.local import drive, phase_policy

    seq, _ = drive(graph, platform, phase_policy(
        platform, ("start", "pack", "exchange", "await", "unpack", "finish"),
        prefer=lambda op, choices: next(
            c for c in choices if c.endswith("." + engine))))
    return seq


def make_halo_buffers(
    mesh_shape: Tuple[int, int, int], args: HaloArgs, seed: int = 0,
    synth: bool = False
) -> Tuple[Dict[str, np.ndarray], Dict[str, object], np.ndarray]:
    """(buffers, partition specs, expected U after one exchange).

    The global interior grid is periodic; the expected array has every shard's
    ghost faces filled from its periodic neighbors (edges/corners of the shells
    stay untouched — the reference exchanges faces only)."""
    from jax.sharding import PartitionSpec as P

    mx, my, mz = mesh_shape
    r, nq = args.radius, args.nq
    rng = np.random.default_rng(seed)
    # global interior
    G = rng.random((nq, mx * args.lx, my * args.ly, mz * args.lz), dtype=np.float32)

    def shard_block(i, j, k, arr=None):
        a = G if arr is None else arr
        return a[
            :,
            i * args.lx : (i + 1) * args.lx,
            j * args.ly : (j + 1) * args.ly,
            k * args.lz : (k + 1) * args.lz,
        ]

    # per-shard local arrays with ghost shells, interiors filled
    locs = np.zeros((mx, my, mz) + args.local_shape(), dtype=np.float32)
    want = np.zeros_like(locs)
    for i in range(mx):
        for j in range(my):
            for k in range(mz):
                locs[i, j, k][:, r : r + args.lx, r : r + args.ly, r : r + args.lz] = (
                    shard_block(i, j, k)
                )
    want[:] = locs
    # expected ghosts: periodic neighbor interior edges
    for i in range(mx):
        for j in range(my):
            for k in range(mz):
                w = want[i, j, k]
                for d in DIRECTIONS:
                    ni = ((i - d[0]) % mx, (j - d[1]) % my, (k - d[2]) % mz)
                    nb = locs[ni]  # the shard the face arrives FROM
                    ps, sz = _face_slices(args, d, "pack")
                    us, _ = _face_slices(args, d, "unpack")
                    face = nb[
                        :,
                        ps[1] : ps[1] + sz[1],
                        ps[2] : ps[2] + sz[2],
                        ps[3] : ps[3] + sz[3],
                    ]
                    w[
                        :,
                        us[1] : us[1] + sz[1],
                        us[2] : us[2] + sz[2],
                        us[3] : us[3] + sz[3],
                    ] = face

    def assemble(blocks):
        """(mx,my,mz, nq, X,Y,Z) -> global (nq, mx*X, my*Y, mz*Z) layout."""
        return np.concatenate(
            [
                np.concatenate(
                    [np.concatenate(list(blocks[i, j]), axis=3) for j in range(my)],
                    axis=2,
                )
                for i in range(mx)
            ],
            axis=1,
        )

    U = assemble(locs)
    want_g = assemble(want)
    bufs = {"U": U}
    specs = {"U": P(None, "x", "y", "z")}
    for d in DIRECTIONS:
        _, sz = _face_slices(args, d, "pack")
        buf = np.zeros((sz[0], mx * sz[1], my * sz[2], mz * sz[3]), dtype=np.float32)
        bufs[f"buf_{dir_name(d)}"] = buf
        bufs[f"recv_{dir_name(d)}"] = buf.copy()
        specs[f"buf_{dir_name(d)}"] = P(None, "x", "y", "z")
        specs[f"recv_{dir_name(d)}"] = P(None, "x", "y", "z")
        if synth:
            # staging decls of the synthesized shift: plans carry per-device
            # face-chunk shapes; globals tile them over the spatial mesh
            # exactly like the face buffers they slice
            for plan in halo_synth_plans(args, d):
                for decl in plan.buffers:
                    s = decl.shape
                    bufs[decl.name] = np.zeros(
                        (s[0], mx * s[1], my * s[2], mz * s[3]),
                        dtype=np.float32)
                    specs[decl.name] = P(None, "x", "y", "z")
    return bufs, specs, want_g
