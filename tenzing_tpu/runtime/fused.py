"""Megakernel fusion backend: lower a searched schedule into fused Pallas
regions with searchable tiling.

The searched win has been bounded by per-op dispatch: ``runtime/executor.py``
traces each op separately and serializes them with ordering tokens, and the
attribution profiler measures exactly what that costs
(``dispatch_overhead_us = sum_of_parts - measured`` — the MPK baseline
number, obs/attrib/analysis.py).  MPK (PAPERS.md) shows that lowering a
*complete* schedule into one megakernel, and T3 that tiling ops so a
transfer overlaps its producer/consumer, moves the optimization *inside*
the fused program.  This module is that lowering:

* :func:`partition_regions` cuts a complete schedule into **fusible
  regions**: maximal runs of fusible device ops between comm/host/sync
  boundaries.  A comm or host op splits (transfers and collectives cannot
  live inside a Pallas kernel body); a cross-lane sync splits (an incoming
  wait means a member would have to observe non-member progress
  mid-region); an ``EventRecord`` interleaved inside a region is deferred
  to just after the fused op (the snapshot then covers MORE work —
  strictly conservative, downstream waits over-wait, never under-wait).
  A pure single-lane compute schedule therefore fuses to ONE region.
  Within a region, ops on different lanes are data-independent **by
  soundness**: a cross-lane data dependency in a sound schedule always
  carries a record/wait pair, and that pair would have split the region —
  so executing the members in the chosen total order inside one kernel
  preserves every happens-before edge trivially.

* :class:`FusedRegionOp` lowers one region into a single ``pallas_call``
  specialized to the chosen total order: the kernel body re-applies the
  member ops' ``apply`` functions over in-kernel values, so intermediate
  buffers live in VMEM/registers instead of round-tripping HBM between
  separately-dispatched programs.  Only ops that declare
  ``DeviceOp.fusible()`` are ever fused (opt-in audit, core/operation.py);
  ``uses_pallas`` ops are excluded (no nested kernels).  When traced into
  the remainder program the fused op joins and advances EVERY member lane
  (``TraceContext.trace_fused``) — a conservative barrier, sound by
  construction.

* **Searchable tiling**: the kernel grid is ``(tiles,)`` over the region's
  declared row decomposition (``DeviceOp.fuse_tiling`` — per-buffer
  independence axes; lane placement already decided the region boundaries
  the grid specializes).  Tile counts are exposed as **decision nodes in
  the choice graph**: :func:`with_tile_menu` plants a
  :class:`FuseTileChoice` between Start and the first real ops, the
  solvers resolve it through the ordinary ``ChooseOp`` machinery (MCTS /
  DFS / hill-climb all search it with zero solver changes), the executed
  :class:`FuseTile` directive rides the schedule, and
  :class:`FusedExecutor` reads it back when lowering.
  ``bench/roofline.py::prune_tilings`` prunes counts that cannot help
  (per-tile traffic under the grid-overhead floor, or a working set that
  cannot fit VMEM).

* :class:`FusedExecutor` wraps a :class:`TraceExecutor` behind the same
  ``ScheduleRunner`` protocol the benchmarkers consume: ``prepare_n`` /
  ``prepare`` / ``run`` / ``compile`` lower through the fusion plan and
  delegate to the inner executor's program cache (plans are cached per
  schedule x tiles).  Kernels run in the Pallas interpreter off-TPU, like
  every kernel in ops/.

Integrity: the fused path is opt-in (``bench.py --fuse-winner``) and the
driver gates fused outputs through the PR-4 result-integrity machinery —
fused-program outputs must be allclose to the stepped program's, and the
schedule is re-verified — before stamping the ``perf.fused`` provenance
block.  Intra-region summation order is unchanged at ``tiles=1`` (the
kernel applies the same jax ops to the same full blocks — bit-identical in
practice); ``tiles>1`` re-associates across tile boundaries and is held to
the allclose gate.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence as Seq, Tuple

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import (
    BoundDeviceOp,
    ChoiceOp,
    CpuOp,
    DeviceOp,
    OpBase,
    register_kind,
    unbound,
)
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.core.sync_ops import EventRecord, SyncOp
from tenzing_tpu.obs import scopes
from tenzing_tpu.obs.metrics import get_metrics
from tenzing_tpu.obs.tracer import get_tracer
from tenzing_tpu.runtime.executor import TraceExecutor, evolve_host_space


# -- tile decision nodes (the choice-graph surface) --------------------------

TILE_PREFIX = "fuse_tile.t"


@register_kind("fuse_tile")
class FuseTile(CpuOp):
    """The executed tile directive: a no-op host op named
    ``fuse_tile.t<N>`` whose only effect is to ride the schedule so the
    fusion backend (and the recorded-schedule corpus) can read the
    searched tile count back out.  A CpuOp so it costs nothing in the
    traced program and never lands inside a region."""

    def __init__(self, tiles: int):
        super().__init__(f"{TILE_PREFIX}{int(tiles)}")
        self._tiles = int(tiles)

    def tiles(self) -> int:
        return self._tiles

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.KIND, "name": self.name(), "tiles": self._tiles}

    @classmethod
    def from_json(cls, j: Dict[str, Any]) -> "FuseTile":
        return cls(int(j["tiles"]))


class FuseTileChoice(ChoiceOp):
    """The tile-count menu as an ordinary ChoiceOp: the solvers resolve it
    through the same ChooseOp decision they use for kernel/engine menus, so
    tile/lane co-placement is searched *inside* the fused program by MCTS,
    DFS and hill-climb alike with zero solver changes."""

    def __init__(self, tile_counts: Seq[int], name: str = "fuse_tile"):
        super().__init__(name)
        self._tiles = [int(t) for t in tile_counts]
        if not self._tiles:
            raise ValueError("FuseTileChoice needs at least one tile count")

    def tile_counts(self) -> List[int]:
        return list(self._tiles)

    def choices(self) -> List[OpBase]:
        return [FuseTile(t) for t in self._tiles]


def with_tile_menu(graph: Graph, tile_counts: Seq[int]) -> Graph:
    """Clone ``graph`` with a :class:`FuseTileChoice` planted between Start
    and the original entry ops: the directive therefore always executes
    before any device op (it can never split a region mid-schedule), and
    every complete schedule carries exactly one tile directive."""
    g = graph.clone()
    choice = FuseTileChoice(tile_counts)
    entries = [s for s in list(g.succs(g.start())) if s != g.finish()]
    g.then(g.start(), choice)
    for e in entries:
        g.then(choice, e)
    if not entries:  # degenerate start->finish graph: keep choice reachable
        g.then(choice, g.finish())
    return g


def tiles_of(order) -> int:
    """The tile count a schedule's :class:`FuseTile` directive requests
    (1 when the schedule carries none)."""
    for op in order:
        name = op.name() if hasattr(op, "name") else ""
        if name.startswith(TILE_PREFIX):
            try:
                return max(1, int(name[len(TILE_PREFIX):]))
            except ValueError:
                continue
    return 1


# -- region model ------------------------------------------------------------


@dataclass
class Region:
    """One fusible region: the member ops in schedule order, plus the
    EventRecords deferred past the fused op (module docstring)."""

    members: List[BoundDeviceOp] = field(default_factory=list)
    deferred: List[OpBase] = field(default_factory=list)
    positions: List[int] = field(default_factory=list)

    def lanes(self) -> List:
        seen, out = set(), []
        for op in self.members:
            l = op.lane()
            if l.id not in seen:
                seen.add(l.id)
                out.append(l)
        return out

    def reads_external(self) -> List[str]:
        """Buffers the region reads from outside (first touch is a read)."""
        written: set = set()
        out: List[str] = []
        for op in self.members:
            for n in op.reads():
                if n not in written and n not in out:
                    out.append(n)
            written.update(op.writes())
        return out

    def writes(self) -> List[str]:
        out: List[str] = []
        for op in self.members:
            for n in op.writes():
                if n not in out:
                    out.append(n)
        return out


def _op_fusible(op: OpBase, host_space: set) -> bool:
    """Region membership test: an opt-in fusible BoundDeviceOp that emits no
    nested Pallas kernel, moves nothing between memory spaces, and touches
    no host-resident buffer at this point of the schedule."""
    if not isinstance(op, BoundDeviceOp):
        return False
    if op.uses_pallas() or not op.fusible():
        return False
    if getattr(unbound(op), "DST_SPACE", None) is not None:
        return False
    if not op.writes():
        return False
    if any(n in host_space for n in list(op.reads()) + list(op.writes())):
        return False
    return True


def partition_regions(ops: List[OpBase],
                      host_space: Optional[set] = None,
                      min_ops: int = 1) -> List[Tuple[str, Any]]:
    """Cut a complete schedule into segments: ``("region", Region)`` for
    each fusible run of at least ``min_ops`` member ops, ``("op", op)``
    for everything else, preserving schedule order (deferred EventRecords
    are re-emitted immediately after their region).  ``host_space`` is the
    set of buffer names fusion must treat as host-resident at schedule
    start — :meth:`FusedExecutor._host_space0` passes only the EXPLICITLY
    pinned-host names (see its docstring for why that is deliberately
    narrower than the executor's ``_initial_host_space`` probe) — evolved
    across transfer ops via the executor's shared
    :func:`~tenzing_tpu.runtime.executor.evolve_host_space` rule."""
    host = set(host_space) if host_space else set()
    segments: List[Tuple[str, Any]] = []
    cur: List[Tuple[int, OpBase, bool]] = []  # (pos, op, is_member)

    def flush() -> None:
        if not cur:
            return
        members = [(p, op) for p, op, m in cur if m]
        if len(members) >= max(1, min_ops):
            region = Region(
                members=[op for _, op in members],
                deferred=[op for _, op, m in cur if not m],
                positions=[p for p, _ in members],
            )
            segments.append(("region", region))
            for op in region.deferred:
                segments.append(("op", op))
        else:
            for _, op, _m in cur:  # replay in exact original order
                segments.append(("op", op))
        cur.clear()

    for pos, op in enumerate(ops):
        if isinstance(op, SyncOp):
            if isinstance(op, EventRecord) and any(m for _, _, m in cur):
                # outgoing snapshot: defer past the fused op (conservative)
                cur.append((pos, op, False))
                continue
            flush()
            segments.append(("op", op))
            continue
        if _op_fusible(op, host):
            cur.append((pos, op, True))
            continue
        flush()
        segments.append(("op", op))
        evolve_host_space(host, op)
    flush()
    return segments


# -- tiling ------------------------------------------------------------------


def region_axes(region: Region) -> Optional[Dict[str, Optional[int]]]:
    """The region's common row decomposition: per buffer, the independence
    axis every touching member agrees on (``None`` = full view).  Returns
    ``None`` — no tiling, single-tile kernel only — when any member is
    untileable, members disagree on a buffer's axis, or a written buffer
    would need a full (non-tiled) view (a full-block write from every grid
    step cannot be row-decomposed)."""
    axes: Dict[str, Optional[int]] = {}
    for op in region.members:
        t = op.fuse_tiling()
        if t is None:
            return None
        for n in set(op.reads()) | set(op.writes()):
            a = t.get(n)
            if n in axes and axes[n] != a:
                return None
            axes[n] = a
    for op in region.members:
        for n in op.writes():
            if axes.get(n) is None:
                return None
    return axes


def region_tile_counts(region: Region, shapes: Dict[str, Tuple[int, ...]],
                       max_tiles: int = 64) -> List[int]:
    """Structurally valid tile counts for a region: powers of two dividing
    every tiled buffer's extent along its declared axis.  ``[1]`` when the
    region admits no decomposition.  Roofline pruning
    (bench/roofline.prune_tilings) is applied by the caller — validity and
    profitability are different questions."""
    axes = region_axes(region)
    if axes is None:
        return [1]
    tiled = [(n, a) for n, a in axes.items() if a is not None]
    if not tiled:
        return [1]
    for n, a in tiled:
        if n not in shapes or a >= len(shapes[n]):
            return [1]
    out = [1]
    t = 2
    while t <= max_tiles:
        if all(shapes[n][a] % t == 0 and shapes[n][a] >= t
               for n, a in tiled):
            out.append(t)
        t *= 2
    return out


def region_bytes(region: Region, nbytes: Dict[str, int]) -> int:
    """The region's aggregate traffic (external reads + writes), for the
    roofline pruning join."""
    names = set(region.reads_external()) | set(region.writes())
    return sum(int(nbytes.get(n, 0)) for n in names)


# -- kernel lowering ---------------------------------------------------------


class _FusedCtx:
    """The minimal apply-context inside a fused kernel body: fusible ops
    are pure buffer->buffer functions, but the executor contract passes a
    ctx — give INDEX_TIE consumers a plain zero (tokens do not exist
    inside the kernel; ordering is the total order of the body itself)."""

    axis_names: Tuple[str, ...] = ()

    def __init__(self):
        import jax.numpy as jnp

        self.tok_index_zero = jnp.zeros((), jnp.int32)
        self.inflight: Dict[str, Any] = {}


def _region_call(members: List[BoundDeviceOp], in_names: List[str],
                 out_names: List[str], shapes: Dict[str, Tuple[int, ...]],
                 dtypes: Dict[str, Any],
                 axes: Optional[Dict[str, Optional[int]]],
                 tiles: int) -> Callable[[Dict[str, Any]], Dict[str, Any]]:
    """Build ``call(bufs) -> {written buffers}``: ONE ``pallas_call`` whose
    body applies the member ops in the chosen total order over in-kernel
    values.  ``tiles > 1`` blocks every buffer along its declared axis
    (grid ``(tiles,)``); full-view buffers are re-presented whole to every
    grid step."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from tenzing_tpu.ops.common import out_struct

    def block_shape(n: str) -> Tuple[int, ...]:
        shp = list(shapes[n])
        a = axes.get(n) if (axes and tiles > 1) else None
        if a is not None:
            shp[a] = shp[a] // tiles
        return tuple(shp)

    def index_map(n: str):
        rank = len(shapes[n])
        a = axes.get(n) if (axes and tiles > 1) else None
        if a is None:
            return lambda i, rank=rank: (0,) * rank
        return lambda i, a=a, rank=rank: tuple(
            i if k == a else 0 for k in range(rank))

    in_specs = [pl.BlockSpec(block_shape(n), index_map(n)) for n in in_names]
    out_specs = [pl.BlockSpec(block_shape(n), index_map(n))
                 for n in out_names]
    n_in = len(in_names)

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:]
        vals = {n: r[...] for n, r in zip(in_names, ins)}
        ctx = _FusedCtx()
        for op in members:
            # the members' names nest under the region's own (obs/scopes.py)
            with jax.named_scope(scopes.vertex_scope(op.name())):
                vals.update(op.apply(vals, ctx))
        for n, r in zip(out_names, outs):
            r[...] = jnp.asarray(vals[n]).astype(r.dtype)

    def call(bufs: Dict[str, Any]) -> Dict[str, Any]:
        operands = [bufs[n] for n in in_names]
        outs = pl.pallas_call(
            kernel,
            grid=(tiles,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=[out_struct(shapes[n], dtypes[n], *operands)
                       for n in out_names],
            interpret=jax.default_backend() != "tpu",
        )(*operands)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return dict(zip(out_names, outs))

    return call


class FusedRegionKernel(DeviceOp):
    """The unbound fused-region computation: reads the region's external
    inputs, writes its outputs, ``apply`` runs the single Pallas kernel."""

    KIND = "fused_region"

    def __init__(self, name: str, members: List[BoundDeviceOp],
                 in_names: List[str], out_names: List[str],
                 call: Callable, tiles: int):
        super().__init__(name)
        self._members = list(members)
        self._in = list(in_names)
        self._out = list(out_names)
        self._call = call
        self._tiles = int(tiles)

    def members(self) -> List[BoundDeviceOp]:
        return list(self._members)

    def tiles(self) -> int:
        return self._tiles

    def reads(self) -> List[str]:
        return list(self._in)

    def writes(self) -> List[str]:
        return list(self._out)

    def apply(self, bufs: Dict[str, Any], ctx) -> Dict[str, Any]:
        return self._call(bufs)

    def uses_pallas(self) -> bool:
        return True

    def desc(self) -> str:
        return (f"{self.name()}({'+'.join(m.name() for m in self._members)})")

    def to_json(self) -> Dict[str, Any]:
        return {"kind": self.KIND, "name": self.name(),
                "members": [m.name() for m in self._members],
                "tiles": self._tiles}


class FusedRegionOp(BoundDeviceOp):
    """The bound fused region: owns EVERY member lane (its trace joins and
    advances all of them — ``TraceContext.trace_fused`` — so replacing the
    members can only add happens-before edges, never drop one)."""

    def __init__(self, kernel: FusedRegionKernel, lanes: List):
        super().__init__(kernel, lanes[0])
        self._all_lanes = list(lanes)

    def lanes(self) -> List:
        return list(self._all_lanes)

    def trace(self, tc) -> None:
        tc.trace_fused(self)

    def to_json(self) -> Dict[str, Any]:
        j = self.unbound().to_json()
        j["lane"] = self.lane().id
        j["lanes"] = [l.id for l in self._all_lanes]
        return j


# -- the fusion plan + executor ----------------------------------------------


@dataclass
class RegionInfo:
    """Provenance for one lowered region (the ``perf.fused`` block)."""

    n_ops: int
    members: List[str]
    lanes: List[int]
    tiles: int
    valid_tiles: List[int]
    pruned_tiles: List[int]

    def to_json(self) -> Dict[str, Any]:
        return {"n_ops": self.n_ops, "members": list(self.members),
                "lanes": list(self.lanes), "tiles": self.tiles,
                "valid_tiles": list(self.valid_tiles),
                "pruned_tiles": list(self.pruned_tiles)}


@dataclass
class FusionPlan:
    """What :meth:`FusedExecutor.plan` decided for one schedule: the fused
    order (regions replaced by :class:`FusedRegionOp`) plus provenance."""

    fused_order: Sequence
    regions: List[RegionInfo]
    tiles_requested: int
    n_ops_total: int
    n_ops_fused: int

    @property
    def tile_menu(self) -> List[int]:
        """Tile counts worth searching: valid-and-unpruned for at least
        one region (always contains 1)."""
        menu = {1}
        for r in self.regions:
            menu.update(r.pruned_tiles)
        return sorted(menu)

    def to_json(self) -> Dict[str, Any]:
        return {
            "regions": len(self.regions),
            "region_sizes": [r.n_ops for r in self.regions],
            "tiles_requested": self.tiles_requested,
            "tile_menu": self.tile_menu,
            "n_ops_total": self.n_ops_total,
            "n_ops_fused": self.n_ops_fused,
            "region_detail": [r.to_json() for r in self.regions],
        }


class FusedExecutor:
    """The opt-in fusion path behind the ``ScheduleRunner`` protocol: every
    ``prepare/prepare_n/run/compile`` lowers the schedule through the
    fusion plan and delegates to the wrapped :class:`TraceExecutor` (whose
    program cache keys on the FUSED sequence's JSON, so fused and stepped
    programs of the same schedule coexist).

    ``tiles=None`` reads the schedule's :class:`FuseTile` directive (the
    searched decision); an explicit ``tiles`` overrides it (the driver's
    tile-menu sweep).  A requested count invalid for some region falls
    back to that region's best valid divisor of the request — regions
    independently keep the largest decomposition the request admits.

    ``min_tile_bytes``/``vmem_bytes`` parameterize the roofline pruning
    (bench/roofline.prune_tilings); tests shrink them to exercise the
    menu on toy buffers."""

    def __init__(self, inner: TraceExecutor, tiles: Optional[int] = None,
                 min_ops: int = 1,
                 min_tile_bytes: Optional[int] = None,
                 vmem_bytes: Optional[int] = None):
        self.inner = inner
        self.tiles = tiles
        self.min_ops = min_ops
        self.min_tile_bytes = min_tile_bytes
        self.vmem_bytes = vmem_bytes
        self._plans: Dict[Tuple, FusionPlan] = {}

    # -- delegated surface --------------------------------------------------
    @property
    def platform(self):
        return self.inner.platform

    @property
    def init_bufs(self):
        return self.inner.init_bufs

    @property
    def compile_count(self) -> int:
        return self.inner.compile_count

    @property
    def compile_secs(self) -> float:
        return self.inner.compile_secs

    # -- planning -----------------------------------------------------------
    def _host_space0(self) -> set:
        """Buffers whose arrays are EXPLICITLY pinned to host memory (the
        ``place_host_buffers`` staging buffers).  Deliberately narrower
        than the executor's ``_initial_host_space`` substring probe: the
        CPU backend reports ``unpinned_host`` for EVERY array (it is host
        memory), which would classify the whole buffer dict host-resident
        and leave nothing fusible — but only ``pinned_host`` tensors carry
        the no-arithmetic restriction fusion must respect."""
        names = set()
        for k, v in self.inner.init_bufs.items():
            mk = getattr(getattr(v, "sharding", None), "memory_kind", None)
            if mk is not None and str(mk) == "pinned_host":
                names.add(k)
        return names

    def _shapes_dtypes(self):
        shapes = {k: tuple(getattr(v, "shape", ()))
                  for k, v in self.inner.init_bufs.items()}
        dtypes = {k: getattr(v, "dtype", None)
                  for k, v in self.inner.init_bufs.items()}
        nbytes = {k: int(getattr(v, "nbytes", 0))
                  for k, v in self.inner.init_bufs.items()}
        return shapes, dtypes, nbytes

    def _pruned_tiles(self, region: Region, valid: List[int],
                      nbytes: Dict[str, int]) -> List[int]:
        from tenzing_tpu.bench import roofline

        cost = roofline.Cost(flops=0.0,
                             hbm_bytes=float(region_bytes(region, nbytes)))
        # full-view buffers (declared axis None) are re-presented whole to
        # every grid step: their bytes do not shrink with the tile count
        axes = region_axes(region) or {}
        touched = set(region.reads_external()) | set(region.writes())
        full = float(sum(int(nbytes.get(n, 0)) for n in touched
                         if axes.get(n) is None))
        kw: Dict[str, Any] = {"full_bytes": full}
        if self.min_tile_bytes is not None:
            kw["min_tile_bytes"] = self.min_tile_bytes
        if self.vmem_bytes is not None:
            kw["vmem_bytes"] = self.vmem_bytes
        return roofline.prune_tilings(cost, valid, **kw)

    def plan(self, order: Sequence) -> FusionPlan:
        """The fusion plan for ``order`` (cached per schedule x tiles)."""
        from tenzing_tpu.core.serdes import sequence_to_json_str

        tiles_req = self.tiles if self.tiles is not None else tiles_of(order)
        key = (sequence_to_json_str(order), int(tiles_req), self.min_ops,
               self.min_tile_bytes, self.vmem_bytes)
        hit = self._plans.get(key)
        if hit is not None:
            return hit
        ops = order.vector()
        shapes, dtypes, nbytes = self._shapes_dtypes()
        segments = partition_regions(
            ops, host_space=self._host_space0(), min_ops=self.min_ops)
        fused_ops: List[OpBase] = []
        infos: List[RegionInfo] = []
        n_fused = 0
        with get_tracer().span("fused.plan", n_ops=len(ops),
                               tiles=int(tiles_req)):
            for kind, seg in segments:
                if kind == "op":
                    fused_ops.append(seg)
                    continue
                region: Region = seg
                valid = region_tile_counts(region, shapes)
                pruned = self._pruned_tiles(region, valid, nbytes)
                t = _best_divisor(int(tiles_req), pruned)
                in_names = region.reads_external()
                out_names = region.writes()
                axes = region_axes(region)
                call = _region_call(region.members, in_names, out_names,
                                    shapes, dtypes, axes, t)
                idx = len(infos)
                kernel = FusedRegionKernel(
                    f"fused{idx}.t{t}", region.members, in_names, out_names,
                    call, t)
                fused_ops.append(FusedRegionOp(kernel, region.lanes()))
                infos.append(RegionInfo(
                    n_ops=len(region.members),
                    members=[m.name() for m in region.members],
                    lanes=[l.id for l in region.lanes()],
                    tiles=t, valid_tiles=valid, pruned_tiles=pruned))
                n_fused += len(region.members)
        plan = FusionPlan(fused_order=Sequence(fused_ops), regions=infos,
                          tiles_requested=int(tiles_req),
                          n_ops_total=len(ops), n_ops_fused=n_fused)
        get_metrics().counter("fused.plans").inc()
        get_metrics().counter("fused.regions").inc(len(infos))
        self._plans[key] = plan
        return plan

    def fused_order(self, order: Sequence) -> Sequence:
        return self.plan(order).fused_order

    # -- ScheduleRunner protocol --------------------------------------------
    def precompile(self, order: Sequence) -> bool:
        """AOT-compile the FUSED program for ``order`` (the prefetch
        pipeline's background-worker entry): lowering first keeps the
        cache key the fused sequence's JSON, so the foreground
        ``prepare_n`` of the same schedule hits."""
        return self.inner.precompile(self.fused_order(order))

    def prepare(self, order: Sequence):
        return self.inner.prepare(self.fused_order(order))

    def prepare_n(self, order: Sequence):
        return self.inner.prepare_n(self.fused_order(order))

    def run(self, order: Sequence) -> Dict[str, Any]:
        return self.inner.run(self.fused_order(order))

    def compile(self, order: Sequence):
        return self.inner.compile(self.fused_order(order))


def _best_divisor(want: int, menu: List[int]) -> int:
    """The largest menu entry dividing ``want`` (1 is always a divisor and
    always on the menu) — a region keeps the biggest decomposition the
    requested tile count admits."""
    best = 1
    for t in menu:
        if t <= want and want % t == 0 and t > best:
            best = t
    return best


def fused_summary(plan: FusionPlan) -> str:
    """One human line for stderr provenance."""
    return (f"{len(plan.regions)} region(s) over {plan.n_ops_fused}/"
            f"{plan.n_ops_total} ops, sizes "
            f"{[r.n_ops for r in plan.regions]}, tiles "
            f"{[r.tiles for r in plan.regions]}")


__all__ = [
    "FuseTile", "FuseTileChoice", "with_tile_menu", "tiles_of",
    "Region", "partition_regions", "region_axes", "region_tile_counts",
    "region_bytes", "FusedRegionKernel", "FusedRegionOp",
    "RegionInfo", "FusionPlan", "FusedExecutor", "fused_summary",
]
