"""Single-chip MoE dispatch/combine pipeline: a benchmark workload.

The multi-chip MoE layer (models/moe.py) moves routed tokens between expert
shards with all-to-alls.  The environment benches on ONE chip, so — exactly
like the halo pipeline (models/halo_pipeline.py) — the network hop is realized
as the chip's asynchronous host round-trip DMA (``HostSpillStart`` ->
``HostFetchStart``): routed tokens travel device -> pinned-host -> device to
the resident experts and their outputs travel back the same way, the
single-chip analog of an expert-parallel deployment's dispatch and combine
transfers.  Numerically this is the 1-shard degenerate case: all experts are
resident, so Y must equal the dense routed evaluation regardless of schedule.

Per microbatch chunk ``c`` the DAG is::

    pack_c (DeviceOp, lane-searched)   # gather routed tokens into slot table
      -> spilld_c -> fetchd_c -> awaitd_c   # dispatch round trip (post/wait)
      -> ffn_c (DeviceOp / ChoiceOp)        # per-expert gelu MLP (MXU)
      -> spillc_c -> fetchc_c -> awaitc_c   # combine round trip (post/wait)
      -> combine_c (DeviceOp, lane-searched)  # weighted scatter-add
    all combine_c -> concat -> finish

Round 3 adds the transfer-ENGINE dimension: each chunk chain's dispatch
and combine hops can run as the host-staged round trip (spill+fetch, the
non-GPU-aware-MPI staging analog) or as a device-resident remote-DMA copy
(ops/rdma.py, the CUDA-aware analog) — ``engine="rdma"`` wires the latter,
``staging="choice"`` searches the full precision x engine menu.

The ``n_chunks`` chains are independent: the searched freedom is how chunk
A's DMAs hide behind chunk B's expert compute and how the two DMA directions
pipeline — the schedule MoE systems hand-tune.  The routing is host-side
setup (top-1 gating into capacity-padded slot tables, the negotiation analog
of models/moe.py), and staged transfers use the (rows, 128) flat layout the
host-offload path is reliable for (see models/halo_pipeline.PackFlat).

With ``impl_choice=True`` the expert MLP becomes a ChoiceOp over XLA einsums
vs the Pallas per-expert kernel (ops/ffn_pallas.py ffn_pallas_batched).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import (
    ChoiceOp,
    CompoundOp,
    DeviceOp,
    OpBase,
)
from tenzing_tpu.core.sequence import Sequence
from tenzing_tpu.models.halo_pipeline import flatten_face, unflatten_face
from tenzing_tpu.ops.comm_ops import AwaitTransfer, HostFetchStart, HostSpillStart
from tenzing_tpu.utils.numeric import gelu_tanh


@dataclass(frozen=True)
class MoEPipeArgs:
    n_experts: int = 8
    tokens: int = 8192  # total tokens on the chip
    d_model: int = 512
    d_ff: int = 2048
    n_chunks: int = 4  # independent dispatch->expert->combine chains
    dtype: str = "float32"

    @property
    def chunk_tokens(self) -> int:
        assert self.tokens % self.n_chunks == 0
        return self.tokens // self.n_chunks


def _slot_shape(args: MoEPipeArgs, cap: int) -> Tuple[int, int, int]:
    return (args.n_experts, cap, args.d_model)


class DispatchPackPipe(DeviceOp):
    """Gather chunk ``c``'s routed tokens into the capacity-padded slot table
    and emit it in the (rows, 128) staging layout the host round trip needs.
    With ``prec="bf16"`` the staging buffer is bfloat16 — half the DMA bytes,
    and numerically free on this platform: the expert matmuls truncate their
    operands to bf16 on the MXU regardless (xla_allow_excess_precision,
    experiments/device_numerics.py)."""

    def __init__(self, name: str, c: int, args: MoEPipeArgs, cap: int,
                 prec: str = "f32"):
        super().__init__(name)
        self._c, self._args, self._cap = c, args, cap
        self._sfx = "16" if prec == "bf16" else ""

    def reads(self):
        return ["X", f"idx_{self._c}"]

    def writes(self):
        return [f"send{self._sfx}_{self._c}"]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        a, tc_ = self._args, self._args.chunk_tokens
        xc = bufs["X"][self._c * tc_ : (self._c + 1) * tc_]  # (Tc, d)
        slots = xc[bufs[f"idx_{self._c}"]]  # (E, C, d)
        if self._sfx:
            slots = slots.astype(jnp.bfloat16)
        return {f"send{self._sfx}_{self._c}": flatten_face(slots, _slot_shape(a, self._cap))}


class ExpertFFNPipe(DeviceOp):
    """Run every resident expert's gelu MLP over its received slots (the MXU
    compute the DMAs hide behind)."""

    def __init__(self, name: str, c: int, args: MoEPipeArgs, cap: int,
                 prec: str = "f32"):
        super().__init__(name)
        self._c, self._args, self._cap = c, args, cap
        self._sfx = "16" if prec == "bf16" else ""

    def reads(self):
        return [f"recv{self._sfx}_{self._c}", "W1", "W2"]

    def writes(self):
        return [f"out{self._sfx}_{self._c}"]

    def _mlp(self, x3, w1, w2):
        import jax
        import jax.numpy as jnp

        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", x3, w1, preferred_element_type=jnp.float32)
        )
        return jnp.einsum(
            "ecf,efd->ecd", h.astype(x3.dtype), w2,
            preferred_element_type=jnp.float32,
        )

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        shape = _slot_shape(self._args, self._cap)
        raw = unflatten_face(bufs[f"recv{self._sfx}_{self._c}"], shape)
        x3 = raw.astype(jnp.float32) if self._sfx else raw
        y = self._mlp(x3, bufs["W1"], bufs["W2"])
        y = y.astype(jnp.bfloat16 if self._sfx else x3.dtype)
        return {f"out{self._sfx}_{self._c}": flatten_face(y, shape)}


    # -- op-chunking protocol (core/chunking.py, T3): the expert MLP splits
    # over the expert axis into n partial FFNs, each updating its expert
    # slice of the output slot table — so the combine-side DMA (or another
    # chunk's transfer) can interleave with the tail partials instead of
    # waiting for every expert.  XLA variant only: the Pallas kernel owns
    # its internal blocking.
    def chunkable(self) -> bool:
        return True

    def chunk_counts(self) -> List[int]:
        from tenzing_tpu.core.chunking import pow2_counts

        return pow2_counts(self._args.n_experts)

    def split(self, n: int) -> List["ExpertFFNPipePartial"]:
        e = self._args.n_experts
        if n < 1 or e % n:
            raise ValueError(f"{e} experts do not split {n} ways")
        return [
            ExpertFFNPipePartial(f"{self.name()}.c{n}p{j}", self._c,
                                 self._args, self._cap, j, n,
                                 "bf16" if self._sfx else "f32")
            for j in range(n)
        ]


class ExpertFFNPipePartial(ExpertFFNPipe):
    """Partial ``j`` of an ``n``-way expert split: run the MLP over its
    expert-row slice of the received slot table and fold the result into
    the output buffer (read-modify-write — the combine is the accumulating
    slice update, so the partials chain serially through the buffer
    version and the schedule interleaves OTHER ops between them)."""

    def __init__(self, name: str, c: int, args: MoEPipeArgs, cap: int,
                 part: int, n_parts: int, prec: str = "f32"):
        super().__init__(name, c, args, cap, prec)
        self._part, self._n_parts = part, n_parts

    def chunkable(self) -> bool:
        return False  # a partial never re-splits

    def reads(self):
        return super().reads() + [f"out{self._sfx}_{self._c}"]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp
        from jax import lax

        shape = _slot_shape(self._args, self._cap)
        lo = self._part * (shape[0] // self._n_parts)
        hi = lo + shape[0] // self._n_parts
        raw = unflatten_face(bufs[f"recv{self._sfx}_{self._c}"], shape)
        x3 = raw.astype(jnp.float32) if self._sfx else raw
        y = self._mlp(x3[lo:hi], bufs["W1"][lo:hi], bufs["W2"][lo:hi])
        y = y.astype(jnp.bfloat16 if self._sfx else x3.dtype)
        cur = unflatten_face(bufs[f"out{self._sfx}_{self._c}"], shape)
        upd = lax.dynamic_update_slice_in_dim(cur, y.astype(cur.dtype), lo, 0)
        return {f"out{self._sfx}_{self._c}": flatten_face(upd, shape)}


class ExpertFFNPipePallas(ExpertFFNPipe):
    """Same per-expert MLP through the Pallas kernel (one expert's weight pair
    + one row tile per program in VMEM)."""

    def _mlp(self, x3, w1, w2):
        from tenzing_tpu.ops.ffn_pallas import ffn_pallas_batched

        return ffn_pallas_batched(x3, w1, w2)

    def uses_pallas(self) -> bool:
        return True

    def chunkable(self) -> bool:
        return False  # the kernel owns its internal blocking


def ffn_chunk_menu(args: MoEPipeArgs, cap: int, relax: bool = False):
    """(pruned counts, {count: est hidden µs}) for one chunk's expert FFN —
    the roofline sketch constraint (bench/roofline.py::prune_chunkings).
    The neighboring transfer is the combine-side staging DMA of the output
    slot table; ``relax=True`` (CPU smoke / library tests) keeps every
    structurally-valid count so toy shapes stay searchable."""
    from tenzing_tpu.bench import roofline

    bpe = np.dtype(args.dtype).itemsize
    e, d, dff = args.n_experts, args.d_model, args.d_ff
    slots = float(e * cap)
    table = slots * d * bpe  # one slot-table pass
    cost = roofline.Cost(
        flops=4.0 * slots * d * dff,
        hbm_bytes=2.0 * table + float(e * 2 * d * dff * bpe))
    # combine cost: every extra partial re-presents the output table
    # (read + write of the RMW slice update)
    return roofline.chunk_menu(
        ExpertFFNPipe("probe", 0, args, cap).chunk_counts(), cost,
        comm_us=table / (roofline.V5E_XFER_GBS * 1e9) * 1e6,
        combine_bytes=2.0 * table, relax=relax)


class ExpertFFNPipeChoice(ChoiceOp):
    def __init__(self, name: str, c: int, args: MoEPipeArgs, cap: int,
                 prec: str = "f32", chunk_counts=(), chunk_est=None):
        super().__init__(name)
        self._c, self._args, self._cap, self._prec = c, args, cap, prec
        self._chunks = tuple(int(n) for n in chunk_counts if int(n) > 1)
        self._chunk_est = dict(chunk_est or {})
        if chunk_counts:
            from tenzing_tpu.core.chunking import menu_info

            self.chunk_menu = menu_info(name + ".xla", chunk_counts,
                                        self._chunk_est)

    def choices(self) -> List[OpBase]:
        from tenzing_tpu.core.chunking import ChunkedOp

        out: List[OpBase] = [
            ExpertFFNPipe(self.name() + ".xla", self._c, self._args, self._cap,
                          self._prec),
            ExpertFFNPipePallas(
                self.name() + ".pallas", self._c, self._args, self._cap,
                self._prec
            ),
        ]
        # chunked alternatives of the XLA expert MLP: ordinary menu entries
        # the solvers pick like any kernel (core/chunking.py)
        out += [
            ChunkedOp(ExpertFFNPipe(self.name() + ".xla", self._c,
                                    self._args, self._cap, self._prec),
                      n, est_hidden_us=self._chunk_est.get(n))
            for n in self._chunks
        ]
        return out


class CombinePipe(DeviceOp):
    """Scatter-add the returned expert outputs into token order scaled by the
    gate weights (padding slots carry weight 0)."""

    def __init__(self, name: str, c: int, args: MoEPipeArgs, cap: int,
                 prec: str = "f32"):
        super().__init__(name)
        self._c, self._args, self._cap = c, args, cap
        self._sfx = "16" if prec == "bf16" else ""

    def reads(self):
        return [f"ret{self._sfx}_{self._c}", f"idx_{self._c}", f"w_{self._c}"]

    def writes(self):
        return [f"Y_{self._c}"]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        a = self._args
        vals = unflatten_face(bufs[f"ret{self._sfx}_{self._c}"],
                              _slot_shape(a, self._cap))
        vals = vals.astype(jnp.float32)
        idx = bufs[f"idx_{self._c}"].reshape(-1)
        w = bufs[f"w_{self._c}"].reshape(-1, 1)
        y = jnp.zeros((a.chunk_tokens, a.d_model), vals.dtype)
        return {f"Y_{self._c}": y.at[idx].add(w * vals.reshape(-1, a.d_model))}


class ConcatPipe(DeviceOp):
    def __init__(self, name: str, args: MoEPipeArgs):
        super().__init__(name)
        self._args = args

    def reads(self):
        return [f"Y_{c}" for c in range(self._args.n_chunks)]

    def writes(self):
        return ["Y"]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        return {
            "Y": jnp.concatenate(
                [bufs[f"Y_{c}"] for c in range(self._args.n_chunks)], axis=0
            )
        }


def chunk_ops(args: MoEPipeArgs, c: int, cap: int, impl_choice: bool = False,
              prec: str = "f32", engine: str = "host",
              op_chunk_counts=(), op_chunk_est=None):
    """The op chain for one microbatch chunk.  ``prec="bf16"`` routes the
    staged transfers through the half-width bfloat16 buffer set (op and
    buffer names carry a ``16`` suffix so both variants can coexist in one
    choice graph); ``engine="rdma"`` replaces each host round trip with a
    device-resident remote-DMA copy (ops/rdma.py — the CUDA-aware-MPI
    analog; the host buffers stay declared but untouched).
    ``op_chunk_counts``/``op_chunk_est`` add T3-style chunked expert-FFN
    alternatives to the menus (core/chunking.py; :func:`ffn_chunk_menu`)."""
    if engine not in ("host", "rdma"):
        raise ValueError(f"unknown transfer engine {engine!r}")
    s = "16" if prec == "bf16" else ""
    counts = tuple(n for n in (op_chunk_counts or ()) if int(n) > 1)
    if impl_choice:
        mk = lambda name, c_, a_, cap_, p_: ExpertFFNPipeChoice(
            name, c_, a_, cap_, p_, chunk_counts=op_chunk_counts,
            chunk_est=op_chunk_est)
    elif counts:
        from tenzing_tpu.core.chunking import ChunkChoice, chunk_variants

        def mk(name, c_, a_, cap_, p_):
            op = ExpertFFNPipe(name, c_, a_, cap_, p_)
            return ChunkChoice(op, chunk_variants(op, counts, op_chunk_est))
    else:
        mk = ExpertFFNPipe
    pack = DispatchPackPipe(f"pack{s}_{c}", c, args, cap, prec)
    if engine == "rdma":
        from tenzing_tpu.ops.rdma import RdmaCopyStart

        xfer_d = (RdmaCopyStart(f"xferd{s}_{c}.rdma", f"send{s}_{c}",
                                f"recv{s}_{c}"),)
        xfer_c = (RdmaCopyStart(f"xferc{s}_{c}.rdma", f"out{s}_{c}",
                                f"ret{s}_{c}"),)
    else:
        xfer_d = (
            HostSpillStart(f"spilld{s}_{c}", f"send{s}_{c}", f"hdisp{s}_{c}"),
            HostFetchStart(f"fetchd{s}_{c}", f"hdisp{s}_{c}", f"recv{s}_{c}"),
        )
        xfer_c = (
            HostSpillStart(f"spillc{s}_{c}", f"out{s}_{c}", f"hcomb{s}_{c}"),
            HostFetchStart(f"fetchc{s}_{c}", f"hcomb{s}_{c}", f"ret{s}_{c}"),
        )
    awaitd = AwaitTransfer(f"awaitd{s}_{c}", f"recv{s}_{c}")
    ffn = mk(f"ffn{s}_{c}", c, args, cap, prec)
    awaitc = AwaitTransfer(f"awaitc{s}_{c}", f"ret{s}_{c}")
    comb = CombinePipe(f"combine{s}_{c}", c, args, cap, prec)
    return (pack,) + xfer_d + (awaitd, ffn) + xfer_c + (awaitc, comb)


class ChunkChain(CompoundOp):
    """One chunk's whole dispatch->expert->combine chain as a compound, at a
    fixed staging precision — the unit the staging ChoiceOp selects."""

    def __init__(self, c: int, args: MoEPipeArgs, cap: int,
                 impl_choice: bool, prec: str, engine: str = "host",
                 op_chunk_counts=(), op_chunk_est=None):
        super().__init__(f"chain_{c}.{prec}-{engine}")
        self._c, self._args, self._cap = c, args, cap
        self._impl_choice, self._prec = impl_choice, prec
        self._engine = engine
        self._op_chunk_counts = tuple(op_chunk_counts)
        self._op_chunk_est = dict(op_chunk_est or {})

    def graph(self) -> Graph:
        g = Graph()
        ops = chunk_ops(self._args, self._c, self._cap, self._impl_choice,
                        self._prec, self._engine,
                        self._op_chunk_counts, self._op_chunk_est)
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then_finish(ops[-1])
        return g


class StagingChoice(ChoiceOp):
    """The staging-precision menu for one chunk: f32 transfers vs half-width
    bf16 transfers.  On this platform bf16 staging is numerically free on the
    dispatch side (the expert matmuls truncate operands to bf16 regardless —
    xla_allow_excess_precision, experiments/device_numerics.py) and rounds
    the combine-side outputs to bf16; whether the halved DMA bytes win is the
    solver's question."""

    def __init__(self, c: int, args: MoEPipeArgs, cap: int, impl_choice: bool,
                 op_chunk_counts=(), op_chunk_est=None):
        super().__init__(f"chain_{c}")
        self._c, self._args, self._cap = c, args, cap
        self._impl_choice = impl_choice
        self._op_chunk_counts = tuple(op_chunk_counts)
        self._op_chunk_est = dict(op_chunk_est or {})

    def choices(self) -> List[OpBase]:
        return [
            ChunkChain(self._c, self._args, self._cap, self._impl_choice,
                       prec, engine, self._op_chunk_counts,
                       self._op_chunk_est)
            for prec in ("f32", "bf16")
            for engine in ("host", "rdma")
        ]


PHASES = ("start", "pack", "spilld", "fetchd", "xferd", "awaitd", "ffn",
          "spillc", "fetchc", "xferc", "awaitc", "combine", "concat", "finish")


def build_graph(args: MoEPipeArgs, cap: int, impl_choice: bool = False,
                staging: str = "f32", engine: str = "host",
                chunk: bool = False, chunk_relax: bool = False) -> Graph:
    """``n_chunks`` independent chains joined by the final concat (the
    multi-chip MoELayer's shape with the all-to-alls replaced by host round
    trips).  ``staging``: "f32" or "bf16" wires that variant directly;
    "choice" wraps each chunk's chain in a :class:`StagingChoice` so the
    solver also searches the transfer precision (buffers must come from
    ``make_pipe_buffers(..., staging="choice")``).

    ``chunk=True`` adds T3-style chunked expert-FFN alternatives to each
    chunk chain's menus (core/chunking.py; :func:`ffn_chunk_menu` prunes
    the counts through the roofline — ``chunk_relax`` skips the pruning,
    the CPU-smoke/tests mode)."""
    counts, est = ((), None)
    if chunk:
        counts, est = ffn_chunk_menu(args, cap, relax=chunk_relax)
    g = Graph()
    cat = ConcatPipe("concat", args)
    for c in range(args.n_chunks):
        if staging == "choice":
            chain = StagingChoice(c, args, cap, impl_choice, counts, est)
            g.start_then(chain)
            g.then(chain, cat)
            continue
        ops = chunk_ops(args, c, cap, impl_choice, prec=staging,
                        engine=engine, op_chunk_counts=counts,
                        op_chunk_est=est)
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then(ops[-1], cat)
    g.then_finish(cat)
    return g


def naive_order(args: MoEPipeArgs, cap: int, platform) -> Sequence:
    """The naive sequential baseline: one lane, each chunk's chain completed
    (posts immediately awaited) before the next starts, then the concat.
    Derived through the SDP machinery (solve/greedy.py) so the schedule
    carries the sync ops the soundness verifier requires."""
    from tenzing_tpu.solve.greedy import serialized_chain_order

    return serialized_chain_order(
        build_graph(args, cap), platform,
        lambda name: (args.n_chunks if name == "concat"
                      else int(name.rsplit("_", 1)[1])))


def greedy_overlap_order(args: MoEPipeArgs, cap: int, platform,
                         staging: str = "f32", engine: str = "host") -> Sequence:
    """Phase-ordered incumbent: all packs, all dispatch posts, ... — the
    software-pipelined discipline, via the shared greedy (solve/greedy.py).
    ``staging="bf16"`` yields the half-width-transfer incumbent;
    ``engine="rdma"`` the device-resident-transfer incumbent."""
    from tenzing_tpu.solve.greedy import greedy_phase_order

    return greedy_phase_order(
        build_graph(args, cap, staging=staging, engine=engine),
        platform, PHASES)


def route_tokens(
    x: np.ndarray, wg: np.ndarray, args: MoEPipeArgs
) -> Tuple[int, Dict[str, np.ndarray]]:
    """Host-side top-1 routing into per-chunk capacity-padded slot tables
    (idx_{c} (E, C) int32, w_{c} (E, C) float32) — the setup-negotiation
    analog (models/moe.py, reference row_part_spmv.cuh:259-423).  Returns
    (capacity, tables); the (expert, gate) assignment comes from the shared
    :func:`~tenzing_tpu.models.moe.top1_route` rule."""
    from tenzing_tpu.models.moe import top1_route

    e_, tc_ = args.n_experts, args.chunk_tokens
    expert, gate = top1_route(x, wg)

    cap = 1
    for c in range(args.n_chunks):
        e_blk = expert[c * tc_ : (c + 1) * tc_]
        cap = max(cap, int(np.bincount(e_blk, minlength=e_).max()))
    tables: Dict[str, np.ndarray] = {}
    for c in range(args.n_chunks):
        idx = np.zeros((e_, cap), dtype=np.int32)
        w = np.zeros((e_, cap), dtype=np.dtype(args.dtype))
        fill = [0] * e_
        for j in range(tc_):
            e = int(expert[c * tc_ + j])
            idx[e, fill[e]] = j
            w[e, fill[e]] = gate[c * tc_ + j]
            fill[e] += 1
        tables[f"idx_{c}"] = idx
        tables[f"w_{c}"] = w
    return cap, tables


def make_pipe_buffers(
    args: MoEPipeArgs, seed: int = 0, with_expected: bool = True,
    staging: str = "f32"
) -> Tuple[Dict[str, np.ndarray], Optional[np.ndarray], int]:
    """(buffers, expected Y or None, capacity).  Routing runs here on the
    host; the expected Y is the dense routed evaluation in float64.
    ``staging`` declares the transfer buffer set(s) to match ``build_graph``:
    "f32", "bf16", or "choice" (both sets — either chain variant may
    execute)."""
    rng = np.random.default_rng(seed)
    e_, t, d, dff = args.n_experts, args.tokens, args.d_model, args.d_ff
    dt = np.dtype(args.dtype)
    x = rng.standard_normal((t, d)).astype(dt)
    wg = rng.standard_normal((d, e_)).astype(dt)
    w1 = (rng.standard_normal((e_, d, dff)) / np.sqrt(d)).astype(dt)
    w2 = (rng.standard_normal((e_, dff, d)) / np.sqrt(dff)).astype(dt)
    cap, tables = route_tokens(x, wg, args)

    bufs: Dict[str, np.ndarray] = {"X": x, "W1": w1, "W2": w2,
                                   "Y": np.zeros((t, d), dt)}
    bufs.update(tables)
    rows = -(-int(np.prod(_slot_shape(args, cap))) // 128)
    flat = np.zeros((rows, 128), dt)
    import ml_dtypes  # ships with jax

    flat16 = np.zeros((rows, 128), ml_dtypes.bfloat16)
    suffixes = {"f32": ("",), "bf16": ("16",), "choice": ("", "16")}[staging]
    for c in range(args.n_chunks):
        for s in suffixes:
            proto = flat16 if s else flat
            for nm in (f"send{s}_{c}", f"hdisp{s}_{c}", f"recv{s}_{c}",
                       f"out{s}_{c}", f"hcomb{s}_{c}", f"ret{s}_{c}"):
                bufs[nm] = proto.copy()
        bufs[f"Y_{c}"] = np.zeros((args.chunk_tokens, d), dt)

    want = None
    if with_expected:
        from tenzing_tpu.models.moe import top1_route

        expert, gate = top1_route(x, wg)
        want64 = np.zeros((t, d), np.float64)
        for e in range(e_):
            sel = expert == e
            h = gelu_tanh(x[sel].astype(np.float64) @ w1[e].astype(np.float64))
            want64[sel] = gate[sel, None] * (h @ w2[e].astype(np.float64))
        want = want64.astype(dt)  # workload dtype (ADVICE r2)
    return bufs, want, cap


def host_buffer_names(args: MoEPipeArgs, staging: str = "f32") -> List[str]:
    """Buffers the caller must device_put into pinned_host."""
    suffixes = {"f32": ("",), "bf16": ("16",), "choice": ("", "16")}[staging]
    return [f"hdisp{s}_{c}" for c in range(args.n_chunks) for s in suffixes] + [
        f"hcomb{s}_{c}" for c in range(args.n_chunks) for s in suffixes
    ]
