"""Ring attention: long-context sequence parallelism as a searchable op DAG.

The reference has no attention (SURVEY.md §2.5: TP/PP/ring-attention absent; the
op-DAG must nonetheless *express* such programs — "a compound op whose subgraph
is a ring of permute+compute steps is exactly ring-attention-shaped").  This
model is that compound: the structural sibling of the halo exchange
(models/halo.py — neighbor ppermute + pack/unpack) and of the SpMV remote
exchange, with the same searchable comm/compute-overlap shape as the
reference's pack->Isend->compute pipelines (ops_halo_exchange.cu:33-257).

Design (blockwise ring attention, double-buffered):

* the sequence axis is sharded over mesh axis ``"sp"``: each device holds local
  queries Q and one K/V block; K/V blocks rotate around the ring via
  ``lax.ppermute`` while flash-style online-softmax state (acc, m, l) folds in
  one block per step;
* K/V are **double-buffered** (kv0/kv1 ping-pong): ``rotate_s`` reads the
  current buffer and writes the other, so ``attn_s`` and ``rotate_s`` are
  independent in the DAG — computing block s can overlap rotating block s+1.
  How aggressively they overlap (lane assignment, ordering, sync placement) is
  the solver's schedule space, exactly the reference's premise;
* the WAR edge ``attn_{s-1} -> rotate_s`` keeps the buffer being overwritten
  free (its reader has executed) so every topological order is correct under
  the executor's SSA buffer semantics;
* m and l are carried broadcast to Q's (b, n, d) shape so the Pallas kernel
  works on uniform tiles (ops/attention_pallas.py).

The per-step block update has an implementation ChoiceOp: plain XLA einsums vs
the Pallas MXU kernel.

:class:`BlockedAttention` is the one-chip workload (``bench.py --workload
attn``, and the benchmark's ``trinity-attn32k``): blockwise attention over
K/V resident in HBM, with query heads grouped over key/value heads
(``heads``, ``kv_heads``), a causal mask and a sliding window (``causal``,
``window``), and query blocking (``q_block``).  With query blocks a layer is
one chain per query block over the K/V blocks that block can see
(:func:`tile_plan`): blocks no query of the block sees are not in the graph.
Each chain competes with one fused kernel over the visible range
(:class:`AttnEngineChoice`), and its first fold writes the softmax state
instead of reading it, so an iteration is idempotent: n repeats leave every
buffer as one leaves it.  Every query block finishes its own rows of the
layer's O: the fused kernel divides in VMEM and writes them in place, with
no state through HBM; a chain ends in a finaliser of its block.  A layer
has no finaliser of its own then, and ends when its last block has.  The
defaults of :class:`RingAttnArgs` (one head
group, no mask, ``q_block=None``: every query in one chain whose state comes
from the buffers, as the ring's does) are the shape this module had before
it met a model, on the same code path: there the state is handed on, and
one :class:`FinalizeAttn` ends the layer.

Packed prompts (``segments``: the first row of each prompt of a packed
batch, ascending from 0; needs ``causal``): a row sees the keys of its own
prompt at or before it.  :func:`visible_pairs`, :func:`mask_crosses` and so
:func:`tile_plan` count it: a K/V block that lies wholly in other prompts
than a query block's is not in the graph, and a block a prompt's start
crosses is masked.  The kernels take the starts beside the positions
(scalar-prefetched; ops/attention_pallas.py).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase

AXIS = "sp"


@dataclass(frozen=True)
class RingAttnArgs:
    n_devices: int  # ring size (mesh axis "sp" extent)
    batch: int = 1
    seq_local: int = 128  # queries per device
    head_dim: int = 128
    dtype: str = "float32"
    heads: int = 1  # query heads; K/V head g serves heads g*group..(g+1)*group-1
    kv_heads: int = 1
    causal: bool = False  # key position <= query position
    window: Optional[int] = None  # keys back from the query's own, it included
    # rows of a query block (BlockedAttention); None: every query in one chain
    # whose state is read from the buffers
    q_block: Optional[int] = None
    # the first row of each prompt of a packed batch (ascending, the first
    # 0); None: one prompt
    segments: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.segments is not None:
            starts = tuple(int(s) for s in self.segments)
            if not self.causal or not starts or starts[0] != 0 or any(
                    b <= a for a, b in zip(starts, starts[1:])):
                raise ValueError(f"segments {starts}: ascending first rows "
                                 "from 0, under causal=True")
            object.__setattr__(self, "segments", starts)
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not group over "
                             f"{self.kv_heads} key/value heads")
        if self.window is not None and not self.causal:
            raise ValueError("a window is counted back from the query's own "
                             "position: it needs causal=True")

    @property
    def scale(self) -> float:
        return 1.0 / float(np.sqrt(self.head_dim))

    @property
    def seq(self) -> int:
        """Positions of a sequence (BlockedAttention: all resident)."""
        return self.n_devices * self.seq_local

    @property
    def low_precision_menu(self) -> bool:
        """Whether casting Q/K/V to bfloat16 is another computation than the
        stated one: with bfloat16 operands the ``*_bf16`` menu entries
        coincide with their plain twins and the menus drop them."""
        import jax.numpy as jnp

        return jnp.dtype(self.dtype) != jnp.bfloat16


NEG = -1e30  # the empty row maximum (ops/attention_pallas.py)


def _kv(s: int) -> Tuple[str, str]:
    """Buffer names holding the K/V block consumed at ring step ``s``."""
    return f"K{s % 2}", f"V{s % 2}"


class AttnStep(DeviceOp):
    """Fold ring step ``s``'s K/V block into the online-softmax state via XLA
    einsums (the reference-shape 'plain' implementation)."""

    def __init__(self, name: str, s: int, args: RingAttnArgs):
        super().__init__(name)
        self._s = s
        self._args = args

    def reads(self):
        k, v = _kv(self._s)
        return ["Q", k, v, "acc", "m_run", "l_run"]

    def writes(self):
        return ["acc", "m_run", "l_run"]

    def _update(self, q, k, v, state, q_pos=0, k_pos=0):
        """``(acc', m', l')`` of folding ``k``/``v`` (first row at position
        ``k_pos``) into ``state`` (``None``: the empty state) for the queries
        ``q`` (first row at ``q_pos``).  A K/V head's group of query heads
        is folded into the row axis, which is no operation for one group."""
        import jax.numpy as jnp

        a = self._args
        h, n, d = q.shape
        rows = (k.shape[0], (h // k.shape[0]) * n, d)
        if state is None:
            acc, m, l = (jnp.full(rows, c, jnp.float32) for c in (0., NEG, 0.))
        else:
            acc, m, l = (t.reshape(rows) for t in state)
        s_ = jnp.einsum("bqd,bkd->bqk", q.reshape(rows), k,
                        preferred_element_type=jnp.float32)
        s_ = s_ * a.scale
        edge = a.causal and mask_crosses(a, q_pos, n, k_pos, k.shape[1])
        if edge:
            qpos = q_pos + jnp.arange(rows[1])[:, None] % n
            kpos = k_pos + jnp.arange(k.shape[1])[None, :]
            seen = kpos <= qpos
            if a.window is not None:
                seen = seen & (kpos > qpos - a.window)
            if a.segments:
                from tenzing_tpu.ops.attention_pallas import segment_start

                seen = seen & (kpos >= segment_start(a.segments, qpos))
            s_ = jnp.where(seen, s_, NEG)
        m_blk = jnp.max(s_, axis=2, keepdims=True)  # (b, n, 1)
        m_new = jnp.maximum(m, jnp.broadcast_to(m_blk, m.shape))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_ - m_new[..., :1])
        if edge:
            p = jnp.where(seen, p, 0.0)
        l_new = l * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=2, keepdims=True), l.shape
        )
        acc_new = acc * alpha + jnp.einsum(
            "bqk,bkd->bqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
        ).astype(acc.dtype)
        return tuple(t.reshape(h, n, d) for t in (acc_new, m_new, l_new))

    def apply(self, bufs, ctx):
        k, v = _kv(self._s)
        acc, m, l = self._update(
            bufs["Q"], bufs[k], bufs[v],
            (bufs["acc"], bufs["m_run"], bufs["l_run"])
        )
        return {"acc": acc, "m_run": m, "l_run": l}

    # megakernel fusion (runtime/fused.py): the online-softmax update is
    # row-independent along the query axis (axis 1 of the (b, n, d) state);
    # the K/V block being folded must stay whole.  The Pallas subclasses
    # inherit this but are excluded by the partitioner's uses_pallas test
    # (no nested kernels).
    def fusible(self) -> bool:
        return True

    def fuse_tiling(self):
        t = {"Q": 1, "acc": 1, "m_run": 1, "l_run": 1}
        for n in self.reads():
            t.setdefault(n, None)  # the K/V pair, whatever its names
        return t


class AttnStepPallas(AttnStep):
    """Same update via the Pallas MXU kernel (ops/attention_pallas.py)."""

    def _update(self, q, k, v, state, q_pos=0, k_pos=0):
        from tenzing_tpu.ops.attention_pallas import attn_block_pallas

        a = self._args
        # the kernel's static arguments say how the block sits under the
        # mask and no more: the positions' difference, and no mask at all
        # where no edge crosses the block.  Folds that sit alike are then
        # one traced call, however many a chain has (a 16k prompt's chains
        # have 53 folds of 9 kinds: PERF.md, PR 33)
        masked = a.causal and mask_crosses(a, q_pos, q.shape[1], k_pos,
                                           k.shape[1])
        packed = {"segments": tuple(s - k_pos for s in a.segments)} if (
            masked and a.segments) else {}
        return attn_block_pallas(
            q, k, v, *(state or (None,) * 3), a.scale,
            q_pos=q_pos - k_pos if masked else 0, causal=masked,
            window=a.window if masked else None, **packed)

    def uses_pallas(self) -> bool:
        return True


class AttnStepPallasBf16(AttnStep):
    """Pallas kernel with Q/K/V cast to bfloat16 for the MXU matmuls (double
    the systolic-array throughput; softmax state and accumulation stay
    float32 via preferred_element_type inside the kernel)."""

    def _update(self, q, k, v, state, q_pos=0, k_pos=0):
        import jax.numpy as jnp

        bf = jnp.bfloat16
        return AttnStepPallas._update(
            self, q.astype(bf), k.astype(bf), v.astype(bf), state, q_pos,
            k_pos)

    def uses_pallas(self) -> bool:
        return True


class AttnStepChoice(ChoiceOp):
    """Implementation menu for one ring step: XLA einsums vs Pallas kernel
    (float32 and bfloat16-input variants)."""

    def __init__(self, name: str, s: int, args: RingAttnArgs):
        super().__init__(name)
        self._s = s
        self._args = args

    def choices(self) -> List[OpBase]:
        out = [
            AttnStep(self.name() + ".xla", self._s, self._args),
            AttnStepPallas(self.name() + ".pallas", self._s, self._args),
        ]
        if self._args.low_precision_menu:
            out.append(AttnStepPallasBf16(self.name() + ".pallas_bf16",
                                          self._s, self._args))
        return out


class RotateKV(DeviceOp):
    """Send the step-``s`` K/V block one hop around the ring into the *other*
    buffer pair (double-buffering: the write never clobbers what step ``s``
    reads).  The ICI analog of the halo Exchange op (models/halo.py) and of the
    reference's Isend/Irecv pairs (ops_mpi.hpp:17-146)."""

    def __init__(self, name: str, s: int):
        super().__init__(name)
        self._s = s

    def reads(self):
        return list(_kv(self._s))

    def writes(self):
        return list(_kv(self._s + 1))

    def apply(self, bufs, ctx):
        import jax

        n = jax.lax.axis_size(AXIS)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_in, v_in = _kv(self._s)
        k_out, v_out = _kv(self._s + 1)
        return {
            k_out: jax.lax.ppermute(bufs[k_in], AXIS, perm),
            v_out: jax.lax.ppermute(bufs[v_in], AXIS, perm),
        }


class RingAttention(CompoundOp):
    """The whole ring as one compound op: n_devices attn steps chained through
    the softmax state, n_devices-1 rotates chained through the kv buffers, WAR
    edges attn_{s-1} -> rotate_s, finalize at the end."""

    def __init__(self, args: RingAttnArgs, name: str = "ring_attention",
                 impl_choice: bool = False):
        super().__init__(name)
        if args.causal or args.heads != args.kv_heads:
            raise ValueError("the ring folds one head group with no mask: a "
                             "shard does not know its positions")
        self._args = args
        self._impl_choice = impl_choice

    def args(self) -> RingAttnArgs:
        return self._args

    def graph(self) -> Graph:
        g = Graph()
        n = self._args.n_devices
        mk = AttnStepChoice if self._impl_choice else AttnStep
        attns = [mk(f"attn_{s}", s, self._args) for s in range(n)]
        rots = [RotateKV(f"rotate_{s}", s) for s in range(n - 1)]
        g.start_then(attns[0])
        for s in range(1, n):
            g.then(attns[s - 1], attns[s])
            g.then(rots[s - 1], attns[s])
        for s in range(1, n - 1):
            g.then(rots[s - 1], rots[s])
        if rots:
            g.start_then(rots[0])
        for s in range(1, n - 1):
            # WAR: rotate_s overwrites the buffer attn_{s-1} reads
            g.then(attns[s - 1], rots[s])
        fin = FinalizeAttn()
        g.then(attns[-1], fin)
        g.then_finish(fin)
        return g


# -- the tile plan of a blocked layer -------------------------------------------


@dataclass(frozen=True)
class QBlock:
    """One query block of a layer and the K/V blocks it can see: a
    contiguous run, since the visible keys of a row are."""

    index: Optional[int]  # None: the one block of a layer without q_block
    q0: int
    rows: int
    blocks: Tuple[int, ...]  # K/V blocks that hold a key some row sees
    skipped: int             # K/V blocks no row of the block can see


def mask_crosses(args: RingAttnArgs, q0: int, rows: int, k0: int,
                 keys: int) -> bool:
    """Whether some (query, key) pair of the rectangle is masked: the
    diagonal or the window's far edge crosses it."""
    if not args.causal:
        return False
    if k0 + keys - 1 > q0:
        return True
    if args.segments and k0 < _segment_starts(args, q0 + rows - 1):
        return True  # some row's prompt starts beyond the first key
    return args.window is not None and k0 <= q0 + rows - 1 - args.window


def _segment_starts(args: RingAttnArgs, rows):
    """The first row of the prompt of each of ``rows`` (ints or an array)."""
    from tenzing_tpu.ops.attention_pallas import segment_start

    return segment_start(args.segments, np.asarray(rows), np.where)


def visible_pairs(args: RingAttnArgs, q0: int, rows: int, k0: int,
                  keys: int) -> int:
    """(query, key) pairs of the rectangle the mask lets through, one head."""
    i = np.arange(q0, q0 + rows, dtype=np.int64)
    hi = np.minimum(i, k0 + keys - 1) if args.causal else k0 + keys - 1
    lo = k0 if args.window is None else np.maximum(i - args.window + 1, k0)
    if args.segments:
        lo = np.maximum(lo, _segment_starts(args, i))
    return int(np.maximum(hi - lo + 1, 0).sum())


def tile_plan(args: RingAttnArgs) -> List[QBlock]:
    """The query blocks of a layer, each with the K/V blocks in its graph."""
    from tenzing_tpu.obs.tracer import get_tracer

    blk, n = args.seq_local, args.seq
    qb = args.q_block or n
    with get_tracer().span("attn.plan", q_block=qb, kv_block=blk,
                           causal=args.causal, window=args.window):
        plan = []
        for i, q0 in enumerate(range(0, n, qb)):
            rows = min(qb, n - q0)
            seen = tuple(s for s in range(args.n_devices)
                         if visible_pairs(args, q0, rows, s * blk, blk))
            plan.append(QBlock(
                index=i if args.q_block else None, q0=q0, rows=rows,
                blocks=seen, skipped=args.n_devices - len(seen)))
    return plan


def note_tiles(args: RingAttnArgs, qb: QBlock, blocks, computed: int,
               opens: bool) -> None:
    """The program's counters for one traced fold or fused vertex of
    ``qb`` over the K/V ``blocks`` (at trace time, once per traced body, as
    ``halo.window_unpacks``): ``attn.tiles`` (folds in the graph, a fused
    vertex counting the blocks it covers), ``attn.tiles_edge`` (those a mask
    edge crosses), ``attn.tiles_skipped`` (blocks no query of the block
    sees, counted where its chain opens), ``attn.pairs_useful`` (pairs under
    the mask) and ``attn.pairs_computed`` (pairs the implementation
    computes, masked ones included), over all heads."""
    from tenzing_tpu.obs.metrics import get_metrics

    reg, blk = get_metrics(), args.seq_local
    hb = args.batch * args.heads
    reg.counter("attn.tiles").inc(len(blocks))
    reg.counter("attn.tiles_edge").inc(sum(
        mask_crosses(args, qb.q0, qb.rows, s * blk, blk) for s in blocks))
    if opens:
        reg.counter("attn.tiles_skipped").inc(qb.skipped)
    reg.counter("attn.pairs_useful").inc(hb * sum(
        visible_pairs(args, qb.q0, qb.rows, s * blk, blk) for s in blocks))
    reg.counter("attn.pairs_computed").inc(hb * computed)


def note_finish() -> None:
    """``attn.fused_finishes``: one fused vertex wrote its rows of O itself
    (at trace time, once per traced body, as :func:`note_tiles`)."""
    from tenzing_tpu.obs.metrics import get_metrics

    get_metrics().counter("attn.fused_finishes").inc()


def note_in_place() -> None:
    """``attn.operands_in_place``: one fused vertex handed the kernel the
    layer's Q, K and V as they lie, no row or key sliced out of them first
    (at trace time, once per traced body, as :func:`note_tiles`)."""
    from tenzing_tpu.obs.metrics import get_metrics

    get_metrics().counter("attn.operands_in_place").inc()


def _all_rows(args: RingAttnArgs) -> QBlock:
    """The one query block of a layer without ``q_block``: every row."""
    return tile_plan(replace(args, q_block=None))[0]


def _names(layer: str, qb: "QBlock") -> Dict[str, str]:
    """Buffer names of a vertex: Q/K/V/O carry the layer's tag, the state
    the query block's (the layers of a period run one after another and
    share the state buffers)."""
    tag = f".{layer}" if layer else ""
    q = "" if qb.index is None else f".q{qb.index}"
    return {"Q": "Q" + tag, "K": "K" + tag, "V": "V" + tag, "O": "O" + tag,
            "acc": "acc" + q, "m_run": "m_run" + q, "l_run": "l_run" + q}


def _prefix(layer: str, qb: Optional["QBlock"] = None) -> str:
    """Op-name prefix of a layer's (query block's) vertices."""
    out = f"{layer}." if layer else ""
    if qb is not None and qb.index is not None:
        out += f"q{qb.index}."
    return out


STATE = ("acc", "m_run", "l_run")


def _rows_of(x, q0: int, rows: int):
    """Rows ``q0 .. q0+rows`` of axis 1, or ``x`` where that is all of it."""
    import jax.lax as lax

    if rows == x.shape[1]:
        return x
    return lax.dynamic_slice_in_dim(x, q0, rows, 1)


class BlockAttnStep(AttnStep):
    """Single-device variant: fold K/V block ``s`` *sliced from the resident
    K/V* into the state (blockwise/flash attention without the ring — the
    1-device degenerate case of sequence parallelism, long context in HBM).

    ``qb`` names the query block whose rows fold (default: every row, the
    state under its plain names); ``first`` makes the fold write the state
    from the empty one instead of reading it."""

    def __init__(self, name: str, s: int, args: RingAttnArgs,
                 qb: Optional[QBlock] = None, layer: str = "",
                 first: bool = False):
        super().__init__(name, s, args)
        self._qb = qb if qb is not None else _all_rows(args)
        self._layer = layer
        self._first = first
        self._n = _names(layer, self._qb)

    def reads(self):
        n = self._n
        return [n["Q"], n["K"], n["V"]] + (
            [] if self._first else [n[t] for t in STATE])

    def writes(self):
        return [self._n[t] for t in STATE]

    def _computed_pairs(self, k0: int, keys: int) -> int:
        return self._qb.rows * keys  # the einsum is dense; the mask comes after

    def apply(self, bufs, ctx):
        import jax.lax as lax

        a, n, qb = self._args, self._n, self._qb
        blk = a.seq_local
        k = lax.dynamic_slice_in_dim(bufs[n["K"]], self._s * blk, blk, 1)
        v = lax.dynamic_slice_in_dim(bufs[n["V"]], self._s * blk, blk, 1)
        # without query blocks every row the op is handed folds (a fused
        # region hands it a tile of them)
        q = bufs[n["Q"]] if qb.index is None else _rows_of(
            bufs[n["Q"]], qb.q0, qb.rows)
        state = None if self._first else tuple(bufs[n[t]] for t in STATE)
        out = self._update(q, k, v, state, qb.q0, self._s * blk)
        note_tiles(a, qb, [self._s], self._computed_pairs(self._s * blk, blk),
                   self._first)
        return dict(zip((n[t] for t in STATE), out))

    # fusion (runtime/fused.py) tiles the query rows: a tile no longer knows
    # its positions, so a masked fold stays out, as does a grouped one (the
    # group is folded into the rows)
    def fusible(self) -> bool:
        a = self._args
        return not a.causal and a.heads == a.kv_heads

    def fuse_tiling(self):
        n = self._n
        return {n["Q"]: 1, n["K"]: None, n["V"]: None,
                **{n[t]: 1 for t in STATE}}

    # -- op-chunking protocol (core/chunking.py, T3): the fold splits over
    # the K/V block axis into n sub-folds of seq_local/n columns each —
    # a sub-fold IS a finer BlockAttnStep (the online-softmax state chain
    # is the combine), so a neighboring op can interleave with the tail
    # sub-folds instead of waiting for the whole block.  XLA fold only:
    # the Pallas kernels own their internal blocking (and the partitioner
    # excludes nested kernels anyway).
    def chunkable(self) -> bool:
        return True

    def chunk_counts(self) -> List[int]:
        from tenzing_tpu.core.chunking import pow2_counts

        return pow2_counts(self._args.seq_local)

    def split(self, n: int) -> List["BlockAttnStep"]:
        blk = self._args.seq_local
        if n < 1 or blk % n:
            raise ValueError(f"{blk} K/V columns do not split {n} ways")
        sub = replace(self._args, seq_local=blk // n,
                      n_devices=self._args.n_devices * n)
        # sub-fold j of block s slices K/V at s*blk + j*(blk//n): the same
        # dynamic_slice arithmetic, one power of two finer
        return [BlockAttnSubFold(f"{self.name()}.c{n}p{j}", self._s * n + j,
                                 sub, self._qb, self._layer,
                                 self._first and j == 0)
                for j in range(n)]


class BlockAttnSubFold(BlockAttnStep):
    """A :meth:`BlockAttnStep.split` sub-fold: the same op one power of
    two finer (the online-softmax state chain is the combine), except it
    never re-splits — partials are leaves of the chunking protocol."""

    def chunkable(self) -> bool:
        return False


class BlockAttnStepPallas(BlockAttnStep):
    """Blocked step with the Pallas MXU kernel update."""

    _update = AttnStepPallas._update

    def _computed_pairs(self, k0: int, keys: int) -> int:
        from tenzing_tpu.ops.attention_pallas import computed_pairs

        a, qb = self._args, self._qb
        return computed_pairs(qb.rows, keys, qb.q0, k0, a.causal, a.window,
                              segments=a.segments)

    def uses_pallas(self) -> bool:
        return True

    def chunkable(self) -> bool:
        return False  # the kernel owns its internal blocking


class BlockAttnStepPallasBf16(BlockAttnStepPallas):
    """Blocked step with the bfloat16-input Pallas kernel update."""

    _update = AttnStepPallasBf16._update


def fold_chunk_menu(args: RingAttnArgs, relax: bool = False):
    """(pruned counts, {count: est hidden µs}) for one block fold — the
    roofline sketch constraint (bench/roofline.py::prune_chunkings).  The
    single-chip blocked fold has NO neighboring transfer to hide
    (``comm_us=0``), so the honest full-size menu prunes every n>1 and the
    driver's ``perf.chunked`` block says so; ``relax=True`` (the CPU smoke
    and the library tests — the ``min_tile_bytes=0`` convention of
    tests/test_fused.py) keeps every structurally-valid count so the
    machinery is searchable on toy shapes."""
    import jax.numpy as jnp

    from tenzing_tpu.bench import roofline

    bpe = jnp.dtype(args.dtype).itemsize
    b, d, blk = args.batch, args.head_dim, args.seq_local
    plan = tile_plan(args)
    folds = sum(len(qb.blocks) for qb in plan)
    # a fold's queries: a query block's rows, every head (without q_block
    # all queries fold against each block)
    nq = args.heads * max(qb.rows for qb in plan)
    state = 6.0 * b * nq * d * 4  # read+write acc/m_run/l_run, float32
    # the layer's operations under the mask, shared evenly among its folds
    whole = roofline.attention_cost(
        b, args.seq, d, bpe, heads=args.heads, kv_heads=args.kv_heads,
        causal=args.causal, window=args.window)
    cost = roofline.Cost(flops=whole.flops / folds,
                         hbm_bytes=state + 2.0 * b * args.kv_heads * blk * d
                         * bpe)
    # combine cost: every extra sub-fold re-presents the full softmax
    # state (the accumulating RMW is the combine)
    return roofline.chunk_menu(
        BlockAttnStep("probe", 0, args).chunk_counts(), cost,
        comm_us=0.0, combine_bytes=state, relax=relax)


class BlockAttnChoice(ChoiceOp):
    def __init__(self, name: str, s: int, args: RingAttnArgs,
                 chunk_counts=(), chunk_est=None,
                 qb: Optional[QBlock] = None, layer: str = "",
                 first: bool = False):
        super().__init__(name)
        self._s = s
        self._args = args
        self._where = (qb, layer, first)
        self._chunks = tuple(int(c) for c in chunk_counts if int(c) > 1)
        self._chunk_est = dict(chunk_est or {})
        if chunk_counts:
            from tenzing_tpu.core.chunking import menu_info

            self.chunk_menu = menu_info(name + ".xla", chunk_counts,
                                        self._chunk_est)

    def choices(self) -> List[OpBase]:
        from tenzing_tpu.core.chunking import ChunkedOp

        def mk(cls, suffix):
            return cls(self.name() + suffix, self._s, self._args,
                       *self._where)

        out: List[OpBase] = [mk(BlockAttnStep, ".xla"),
                             mk(BlockAttnStepPallas, ".pallas")]
        if self._args.low_precision_menu:
            out.append(mk(BlockAttnStepPallasBf16, ".pallas_bf16"))
        # chunked alternatives of the XLA fold: ordinary menu entries the
        # solvers pick like any kernel (core/chunking.py)
        out += [
            ChunkedOp(mk(BlockAttnStep, ".xla"), n,
                      est_hidden_us=self._chunk_est.get(n))
            for n in self._chunks
        ]
        return out


class FusedBlockAttn(DeviceOp):
    """ALL K/V blocks a query block sees folded in one fused Pallas flash
    kernel (ops/attention_pallas.attn_fused_pallas): the online-softmax
    state lives in VMEM scratch across the kv grid dimension instead of
    round-tripping HBM between per-block ops.

    The kernel is handed the layer's whole Q, K and V with the vertex's rows
    (``q0``, ``rows``) and key range (``k0``, ``keys``): where those are
    whole tiles of the buffers, as a layer with query blocks cuts them, its
    index maps find them and nothing is sliced out in HBM first
    (``attn.operands_in_place``; 1.5 ms of XLA slices an iteration at
    ``trinity-attn32k``, 0.54 GB of Q read and written: PERF.md, PR 37);
    where they are not, the call slices.  The vertex takes its ordering
    token by index (``INDEX_TIE``): the token's zero goes onto the
    positions the kernel scalar-prefetches, as the halo's window unpack
    takes its own, so no buffer gets a value-preserving add (on a K that
    nothing slices any more that add would write a fresh K a vertex).  The
    ``bf16`` entry casts, which is a copy anyway: of its rows and keys
    alone, sliced first.

    With query blocks (``first``: the vertex opens its own state and covers
    the whole visible range of its rows) the kernel also finishes them: it
    divides ``acc`` by ``l`` at a query tile's last step and writes rows
    ``q0 .. q0+rows`` of the layer's O in place.  The vertex then reads Q,
    K, V and O, writes O, and touches no state buffer.  What that saves, at
    the benchmark's ``trinity-attn32k`` (16 384 tokens, 32 heads of 128,
    query blocks of 4096; PERF.md, PR 34): a vertex that hands on its state
    writes three float32 ``(32, 4096, 128)`` tensors, 201 MB, and the
    layer's finaliser reads 537 MB of them and writes 67 MB of O: 3.2 GB
    of state written and 2.4 GB moved by four XLA fusions an iteration,
    beside the 0.6 GB of Q, K, V and O a layer has to move.

    The four vertices of a layer write one buffer, O, in disjoint rows.
    The graph has no edge between them and needs none: the executor's trace
    is SSA (each vertex takes the O the vertex traced before it left and
    returns it with its own rows written), so every order the search tries
    gives the same O (tests/test_attn_window_gqa.py pins it).

    Without query blocks (the state is handed on, as the ring's) nothing
    finishes here: state in, state out, the layer's one finaliser."""

    BF16 = False
    INDEX_TIE = True

    def __init__(self, name: str, args: RingAttnArgs,
                 qb: Optional[QBlock] = None, layer: str = "",
                 first: bool = False):
        super().__init__(name)
        self._args = args
        self._qb = qb if qb is not None else _all_rows(args)
        self._first = first
        self._n = _names(layer, self._qb)

    def reads(self):
        if self._first:
            return [self._n[t] for t in ("Q", "K", "V", "O")]
        return BlockAttnStep.reads(self)

    def writes(self):
        if self._first:
            return [self._n["O"]]
        return BlockAttnStep.writes(self)

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        from tenzing_tpu.ops.attention_pallas import (
            KV_TILE,
            Q_TILE,
            attn_fused_pallas,
            computed_pairs,
            taken_whole,
        )

        a, n, qb = self._args, self._n, self._qb
        blk = a.seq_local
        k0, keys = qb.blocks[0] * blk, len(qb.blocks) * blk
        tok = ctx.tok_index_zero
        if tok is None:  # as the halo's Pack: no zero, no happens-before edge
            raise RuntimeError(
                f"{self.desc()}: INDEX_TIE op traced without tok_index_zero "
                "(executor contract violated: the kernel would have no "
                "happens-before edge)")
        # one K/V block a grid step where the mask skips nothing; under a
        # mask the kernel's own tile, so that less of an edge is computed
        bkv = min(blk, KV_TILE) if a.causal else blk
        q, k, v = (bufs[n[t]] for t in ("Q", "K", "V"))
        if self.BF16:
            # a cast is a copy anyway: of the vertex's rows and keys alone
            q = _rows_of(q, qb.q0, qb.rows).astype(jnp.bfloat16)
            k, v = (_rows_of(t, k0, keys).astype(jnp.bfloat16)
                    for t in (k, v))
            at = {}
        else:
            at = dict(q_row0=qb.q0, rows=qb.rows, k_row0=k0, keys=keys)
            if taken_whole(q.shape[1], qb.q0, qb.rows, Q_TILE) and (
                    taken_whole(k.shape[1], k0, keys, bkv)):
                note_in_place()
        # the positions enter as their difference (AttnStepPallas._update)
        mask = dict(bkv=bkv, q_pos=qb.q0 - k0, causal=a.causal,
                    window=a.window, tok=tok, **at)
        if a.segments:  # in the positions' coordinates
            mask["segments"] = tuple(s - k0 for s in a.segments)
        note_tiles(a, qb, qb.blocks,
                   computed_pairs(qb.rows, keys, qb.q0, k0, a.causal,
                                  a.window, bkv=bkv, segments=a.segments),
                   self._first)
        if self._first:
            note_finish()
            return {n["O"]: attn_fused_pallas(
                q, k, v, None, None, None, a.scale, finish=True,
                o=bufs[n["O"]], o_row0=qb.q0, **mask)}
        out = attn_fused_pallas(q, k, v, *(bufs[n[t]] for t in STATE),
                                a.scale, **mask)
        return dict(zip((n[t] for t in STATE), out))

    def uses_pallas(self) -> bool:
        return True


class FusedBlockAttnBf16(FusedBlockAttn):
    BF16 = True


def _mk_block_step(name: str, s: int, args: RingAttnArgs, impl_choice: bool,
                   chunk_counts, chunk_est, *where) -> OpBase:
    """One block fold vertex: the kernel ChoiceOp (optionally extended
    with chunked alternatives), a bare step wrapped in a
    :class:`~tenzing_tpu.core.chunking.ChunkChoice` when only chunking is
    searched, or the plain step.  ``where``: query block, layer, first."""
    if impl_choice:
        return BlockAttnChoice(name, s, args, chunk_counts, chunk_est, *where)
    step = BlockAttnStep(name, s, args, *where)
    counts = [c for c in (chunk_counts or ()) if int(c) > 1]
    if counts:
        from tenzing_tpu.core.chunking import ChunkChoice, chunk_variants

        return ChunkChoice(step, chunk_variants(step, counts, chunk_est))
    return step


def _chain(g: Graph, args: RingAttnArgs, impl_choice: bool, chunk_counts,
           chunk_est, qb: QBlock, layer: str) -> Tuple[OpBase, OpBase]:
    """The per-block folds of ``qb`` linked through the state into ``g``;
    returns the chain's two ends.  With query blocks the first fold opens
    the state and the chain ends in the finaliser of its own rows of O (as
    a fused vertex finishes its own: :class:`FusedBlockAttn`)."""
    pre = _prefix(layer, qb)
    attns = [_mk_block_step(f"{pre}attn_{s}", s, args, impl_choice,
                            chunk_counts, chunk_est, qb, layer,
                            args.q_block is not None and s == qb.blocks[0])
             for s in qb.blocks]
    if args.q_block is not None:
        attns.append(FinalizeAttn(pre + "attn_finalize", [qb], layer,
                                  args.dtype, q0=qb.q0))
    for a, b in zip(attns, attns[1:]):
        g.then(a, b)
    return attns[0], attns[-1]


class BlockChain(CompoundOp):
    """The per-block fold chain as one expandable vertex — the staged
    alternative the fused kernel competes with inside
    :class:`AttnEngineChoice` (the HostRoundTrip-in-TransferChoice
    precedent, models/halo_pipeline.py)."""

    def __init__(self, name: str, args: RingAttnArgs, impl_choice: bool,
                 chunk_counts=(), chunk_est=None,
                 qb: Optional[QBlock] = None, layer: str = ""):
        super().__init__(name)
        self._args = args
        self._impl_choice = impl_choice
        self._chunk_counts = tuple(chunk_counts)
        self._chunk_est = dict(chunk_est or {})
        self._qb = qb if qb is not None else _all_rows(args)
        self._layer = layer

    def graph(self) -> Graph:
        g = Graph()
        head, tail = _chain(g, self._args, self._impl_choice,
                            self._chunk_counts, self._chunk_est, self._qb,
                            self._layer)
        g.start_then(head)
        g.then_finish(tail)
        return g


class AttnEngineChoice(ChoiceOp):
    """Granularity menu for the blocked fold of one query block: the
    per-block chain (searchable order x lane x per-block kernel) vs the
    fused single-kernel flash over the visible range (f32 or bf16 MXU
    inputs) — kernel granularity is itself a scheduling decision the solver
    owns.  Chains of unequal length (2 to 16 folds in a causal layer) sit
    beside each other in one graph."""

    def __init__(self, args: RingAttnArgs, impl_choice: bool,
                 chunk_counts=(), chunk_est=None,
                 qb: Optional[QBlock] = None, layer: str = ""):
        super().__init__(_prefix(layer, qb) + "attn_blocks")
        self._args = args
        self._impl_choice = impl_choice
        self._chunk_counts = tuple(chunk_counts)
        self._chunk_est = dict(chunk_est or {})
        self._qb = qb
        self._layer = layer

    def choices(self) -> List[OpBase]:
        first = self._args.q_block is not None
        name = self.name()
        out = [
            BlockChain(name + ".chain", self._args, self._impl_choice,
                       self._chunk_counts, self._chunk_est, self._qb,
                       self._layer),
            FusedBlockAttn(name + ".fused", self._args, self._qb,
                           self._layer, first),
        ]
        if self._args.low_precision_menu:
            out.append(FusedBlockAttnBf16(name + ".fused_bf16", self._args,
                                          self._qb, self._layer, first))
        return out


class FinalizeAttn(DeviceOp):
    """O = acc / l (the denominator division deferred past the folds), the
    query blocks of ``plan`` side by side, in the layer's ``dtype``.

    ``q0``: the finaliser of one query block's chain in a layer with query
    blocks.  It writes that block's rows of the layer's O, from row ``q0``,
    and leaves the others as they are, as a fused vertex does
    (:class:`FusedBlockAttn`, where the order of a layer's writers is
    accounted for)."""

    def __init__(self, name: str = "attn_finalize",
                 plan: Optional[List[QBlock]] = None, layer: str = "",
                 dtype: str = "float32", q0: Optional[int] = None):
        super().__init__(name)
        self._dtype = dtype
        self._parts = [_names(layer, qb) for qb in plan] if plan else [
            {t: t for t in STATE + ("O",)}]
        self._q0 = q0

    def reads(self):
        return [n[t] for n in self._parts for t in ("acc", "l_run")] + (
            [] if self._q0 is None else [self._parts[0]["O"]])

    def writes(self):
        return [self._parts[0]["O"]]

    def apply(self, bufs, ctx):
        import jax.lax as lax
        import jax.numpy as jnp

        o = self._parts[0]["O"]
        rows = [bufs[n["acc"]] / bufs[n["l_run"]] for n in self._parts]
        whole = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
        whole = whole.astype(self._dtype)
        if self._q0 is not None:
            whole = lax.dynamic_update_slice_in_dim(bufs[o], whole,
                                                    self._q0, 1)
        return {o: whole}

    # fusion: elementwise over the (b, n, d) state; a finaliser of some rows
    # of O stays out (a fused region would tile O whole)
    def fusible(self) -> bool:
        return len(self._parts) == 1 and self._q0 is None

    def fuse_tiling(self):
        n = self._parts[0]
        return {n["acc"]: 1, n["l_run"]: 1, n["O"]: 1}


class BlockedAttention(CompoundOp):
    """Single-device blockwise attention of one layer over ``n_blocks`` K/V
    blocks: per query block (``args.q_block`` rows; one block of every row
    without it) the folds of the K/V blocks it can see chain through the
    softmax state; block loads overlap on lanes; the per-step kernel is a
    ChoiceOp when ``impl_choice``; with ``fused_choice`` each chain
    additionally competes with the fused single-kernel flash over its
    visible range (:class:`AttnEngineChoice`).  ``args.n_devices`` is reused
    as the block count (no mesh involved).  ``layer`` tags the op names and
    the Q/K/V/O buffers, so that the layers of a model sit in one graph
    (:func:`period_graph`).

    ``chunk=True`` adds chunked sub-fold alternatives of each block's XLA
    fold to the menus (core/chunking.py; :func:`fold_chunk_menu` prunes the
    counts through the roofline — ``chunk_relax`` skips the pruning, the
    CPU-smoke/tests mode)."""

    def __init__(self, args: RingAttnArgs, name: str = "blocked_attention",
                 impl_choice: bool = False, fused_choice: bool = False,
                 chunk: bool = False, chunk_relax: bool = False,
                 layer: str = ""):
        super().__init__(name)
        self._args = args
        self._impl_choice = impl_choice
        self._fused_choice = fused_choice
        self._chunk = chunk
        self._chunk_relax = chunk_relax
        self._layer = layer

    def args(self) -> RingAttnArgs:
        return self._args

    def graph(self) -> Graph:
        g = Graph()
        counts, est = ((), None)
        if self._chunk:
            counts, est = fold_chunk_menu(self._args,
                                          relax=self._chunk_relax)
        plan = tile_plan(self._args)
        # with query blocks every block finishes its own rows of O (the
        # fused kernel, or its chain's finaliser): the layer ends when the
        # last of them has.  Without, one finaliser over the handed-on state
        fin = None if self._args.q_block is not None else FinalizeAttn(
            _prefix(self._layer) + "attn_finalize", plan, self._layer,
            self._args.dtype)
        for qb in plan:
            if self._fused_choice:
                head = tail = AttnEngineChoice(
                    self._args, self._impl_choice, counts, est, qb,
                    self._layer)
            else:
                head, tail = _chain(g, self._args, self._impl_choice, counts,
                                    est, qb, self._layer)
            g.start_then(head)
            g.then(tail, g.finish() if fin is None else fin)
        if fin is not None:
            g.then_finish(fin)
        return g


def period_graph(layers, **menus) -> Graph:
    """The layers of a model one after another, as the residual stream
    orders them (layer l+1 starts when layer l's O is final): ``layers`` is
    ``[(tag, RingAttnArgs)]``, ``menus`` the switches of
    :class:`BlockedAttention`.  The search's freedom is inside a layer."""
    g = Graph()
    ops = [BlockedAttention(a, name=f"{tag}.blocked_attention", layer=tag,
                            **menus) for tag, a in layers]
    g.start_then(ops[0])
    for a, b in zip(ops, ops[1:]):
        g.then(a, b)
    g.then_finish(ops[-1])
    return g


def blocked_buffer_shapes(args: RingAttnArgs, layer: str = ""):
    """``{name: (shape, dtype)}`` of one layer's buffers: Q and O
    ``(batch*heads, n, d)``, K and V ``(batch*kv_heads, n, d)`` in
    ``args.dtype`` (O float32 for a float32 layer), and per query block the
    float32 state."""
    hq, hk = args.batch * args.heads, args.batch * args.kv_heads
    n, d = args.seq, args.head_dim
    out = {}
    for qb in tile_plan(args):
        names = _names(layer, qb)
        out.update({names[t]: ((hq, qb.rows, d), "float32") for t in STATE})
    out.update({names["Q"]: ((hq, n, d), args.dtype),
                names["K"]: ((hk, n, d), args.dtype),
                names["V"]: ((hk, n, d), args.dtype),
                names["O"]: ((hq, n, d), args.dtype)})
    return out


def make_blocked_buffers(
    args: RingAttnArgs, seed: int = 0, layer: str = ""
) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """(buffers, expected O) of one layer of single-device blockwise
    attention; ``args.n_devices`` K/V blocks of ``seq_local`` each, resident
    in HBM.  Expected O is dense softmax attention under the layer's mask,
    computed in float64 on the host (small shapes: tests and smoke)."""
    rng = np.random.default_rng(seed)
    shapes = blocked_buffer_shapes(args, layer)
    names = _names(layer, tile_plan(args)[0])
    dt = np.dtype(args.dtype)
    q, k, v = (rng.standard_normal(shapes[names[t]][0]).astype(dt)
               for t in ("Q", "K", "V"))
    group = args.heads // args.kv_heads
    k64, v64 = (np.repeat(t.astype(np.float64), group, axis=0)
                for t in (k, v))
    s_ = np.einsum("bqd,bkd->bqk", q.astype(np.float64), k64) * args.scale
    if args.causal:
        i, j = np.arange(args.seq)[:, None], np.arange(args.seq)[None, :]
        seen = j <= i
        if args.window is not None:
            seen &= j > i - args.window
        s_ = np.where(seen, s_, -np.inf)
    p = np.exp(s_ - s_.max(axis=2, keepdims=True))
    p /= p.sum(axis=2, keepdims=True)
    want = np.einsum("bqk,bkd->bqd", p, v64).astype(np.float32)
    fill = {"m_run": NEG}
    bufs = {name: np.full(shape, fill.get(name.split(".")[0], 0.0), dtype)
            for name, (shape, dtype) in shapes.items()}
    bufs.update({names["Q"]: q, names["K"]: k, names["V"]: v})
    return bufs, want


def make_ring_buffers(
    args: RingAttnArgs, seed: int = 0
) -> Tuple[Dict[str, np.ndarray], Dict[str, object], np.ndarray]:
    """(buffers, partition specs, expected O) for a ring over ``args.n_devices``
    shards.  Expected O is full (global) softmax attention computed densely on
    the host, laid out in the same sp-sharded order as the device buffers."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(seed)
    b, nl, d, nsp = args.batch, args.seq_local, args.head_dim, args.n_devices
    n = nl * nsp
    dt = np.dtype(args.dtype)
    q = rng.standard_normal((b, n, d)).astype(dt)
    k = rng.standard_normal((b, n, d)).astype(dt)
    v = rng.standard_normal((b, n, d)).astype(dt)
    # dense reference
    s_ = np.einsum("bqd,bkd->bqk", q.astype(np.float64), k.astype(np.float64))
    s_ *= args.scale
    p = np.exp(s_ - s_.max(axis=2, keepdims=True))
    p /= p.sum(axis=2, keepdims=True)
    want = np.einsum("bqk,bkd->bqd", p, v.astype(np.float64)).astype(np.float32)

    shape = (b, n, d)
    bufs = {
        "Q": q,
        "K0": k,
        "V0": v,
        "K1": np.zeros_like(k),
        "V1": np.zeros_like(v),
        "acc": np.zeros(shape, np.float32),
        "m_run": np.full(shape, -1e30, np.float32),
        "l_run": np.zeros(shape, np.float32),
        "O": np.zeros(shape, np.float32),
    }
    spec = P(None, AXIS, None)
    specs = {name: spec for name in bufs}
    return bufs, specs, want
