"""One decode step of learned sparse attention (DeepSeek-V3.2's DSA) over two
paged caches, as a searchable op DAG: :mod:`~tenzing_tpu.models.
latent_attention`'s step with the dense cache read taken away.  A lightning
indexer scores every cached token, an exact top-k picks ``topk`` of them, and
latent attention runs over the picked tokens alone.

The layer (the model's own ``Indexer.forward`` and ``MLA.forward``; sequence
b with ``L_b`` cached tokens; the inputs arrive projected, normed and
rotated, ``qI`` ``(B, index_heads, index_dim)``, ``kI_new`` ``(B,
index_dim)``, ``wI`` ``(B, index_heads)`` float32 beside the latent step's):

1. *Append*: row ``L_b`` of b's latent cache becomes ``[c_new ; k_rope_new]``
   and row ``L_b`` of its index-key cache ``kI_new[b]``.
2. *Index*: ``I[b, j] = sum_h wI[b, h] relu(qI[b, h] . KI[b, j])``, ``j = 0
   .. L_b``, products and sums in float32.
3. *Select*: ``S_b`` = the positions of the ``min(topk, L_b + 1)`` largest
   ``I[b, .]``, exact; equal scores go to the lower position.
4. *Absorb*, as the latent step's.
5. *Read*: softmax of ``scale qt[b, h] . C[b, j, :]`` over ``j in
   S_b`` only, ``o_lat = sum_{j in S_b} p C[b, j, :rank]``.
6. *Up-project*, as the latent step's.

**Two kinds of state in one allocator.**  Both caches are paged through the
one block table and the one vector of lengths, two pools each (sealed pages
read only, one open page a sequence: ``latent_attention``'s reasons):

* index keys ``KI.<l>`` ``(pages, index_dim, page)`` / ``KIopen.<l>`` ``(B,
  index_dim, page)``: keys as columns, what the paged walk's kernel reads
  (``dsa_index``: the walk of ``mla_decode`` with no softmax and no state);
* latents ``C.<l>`` ``(pages, page, row)`` / ``Copen.<l>`` ``(B, page,
  row)``: **a token is a row**, ``row`` = the latent's width rounded up to
  whole lanes (576 -> 640, the tail zero).  The dense step keeps a page as
  columns ``(576, page)`` because the runtime lays a 576-wide bfloat16 array
  out with its 128-multiple axis minor whatever its logical shape: there a
  token is a strided column, and XLA copies the pool whole before it
  gathers from it (0.63 GB a call at the benchmark's size, read off the
  compiled text).  A 640-wide row is the minor axis as it lies and a
  gather reads rows.  Nothing dense ever reads this pool, so the layout
  the dense read wanted has no reader here.

Per group of sequences (``decode_plan``'s groups) a layer runs the chain
``index -> select -> read``: the scores of the group's pages into its rows
of ``I`` (shared by the layers), the selection into its rows of ``sel.<l>``,
and the read (:class:`DsaRead`): the picked rows of the sealed pool by one
gather, **as rows**, and one ``mla_decode_rows`` kernel over them, a step a
sequence, which writes the group's rows of ``o_lat`` in place.  ``(keys,
row)`` is the layout attention wants for K anyway (Q K^T contracts the minor
axis of both, P V is a plain product), and the pool already holds it: the
``(width, topk)`` column tile ``mla_decode`` takes, its buffer ``G``, the
transposes into it and the second gather (a slot in the open page read from
``Copen`` too, and a ``where`` over both) were there only because the dense
kernel was reused, and are gone (PR 41).  The kernel is handed the open pages
and takes those slots' rows from them itself, a few a sequence.
:func:`gather_rows` stays as the plain statement of which row a position is:
the tests hold the read to it and to ``mla_decode`` over its tile.

Menus: how far a selection reaches (:class:`SparseReadsChoice`: one a
group, each a link of its group's chain, handed the group's rectangle of
scores; or one a layer, handed every sequence over the longest's pages,
which waits for every group's index) and, with ``impl_choice``, the index
(XLA's gather and einsum over the group's rectangle, or the kernel).  The
selection itself is one algorithm, exact (:func:`select_chunks`).  The
lengths do not advance: an iteration is the same step again.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, List, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase
from tenzing_tpu.models.latent_attention import (
    NEG,
    Absorb,
    Append,
    Group,
    LatentDecodeArgs,
    UpProject,
    _names,
    block_table,
    decode_plan,
)

LANES = 128  # a row of the latent cache is whole lanes wide


@dataclass(frozen=True)
class SparseDecodeArgs:
    latent: LatentDecodeArgs
    index_heads: int = 64
    index_dim: int = 128
    topk: int = 2048

    @property
    def row(self) -> int:
        """Width of a stored latent row: whole lanes."""
        return -(-self.latent.width // LANES) * LANES

    @property
    def picked(self) -> Tuple[int, ...]:
        """Keys a sequence attends over: ``min(topk, L_b + 1)``."""
        return tuple(min(self.topk, n) for n in self.latent.visible)

    @property
    def tile(self) -> LatentDecodeArgs:
        """The selected tokens as a cache of their own: every sequence one
        open page of ``topk`` keys, ``picked`` of them visible (what
        ``mla_decode`` over :func:`gather_rows`' tile walks: the plain
        statement the read is held to)."""
        return replace(self.latent, lens=tuple(n - 1 for n in self.picked),
                       page=self.topk, fold_pages=1)


def dsa_plan(args: SparseDecodeArgs) -> List[Tuple[Group, Group]]:
    """Per group of a step ``(cache, tile)``: the group over the paged
    caches (the index walks its pages, the selection is handed its
    rectangle) and over its selected tokens as :attr:`SparseDecodeArgs.tile`
    (one step a sequence)."""
    from tenzing_tpu.obs.tracer import get_tracer

    a = args.latent
    with get_tracer().span("dsa.plan", groups=a.groups, page_tokens=a.page,
                           topk=args.topk, rows=a.batch // a.groups):
        return list(zip(decode_plan(a), decode_plan(args.tile)))


def _count(name: str, by: int) -> None:
    from tenzing_tpu.obs.metrics import get_metrics

    get_metrics().counter("dsa." + name).inc(by)


def _visible(args: SparseDecodeArgs, grp: Group) -> Tuple[int, ...]:
    return args.latent.visible[grp.lead0:grp.lead0 + grp.rows]


def candidates(args: SparseDecodeArgs, grp: Group) -> int:
    """Columns of the rectangle a group's selection is handed: the most
    pages one of its sequences has, whole, and at least ``topk``."""
    return max(max(grp.tiles) * args.latent.page, args.topk)


# -- the selection: exact, equal scores to the lower position --------------------

def chunk_counts(marked):
    """``(inside, starts)`` float32 of ``marked`` ``(rows, n)`` bool, a row
    as chunks of 128: ``inside`` ``(rows, chunks, 128)`` the inclusive
    running count inside each chunk, ``starts`` ``(rows, chunks)`` the marks
    before each chunk.  Counted by products with a triangle of ones (sums
    of ones and of whole numbers up to 128: exact), first inside the chunks
    and then over the chunks' totals, and a masked sum over the few totals
    of 128 chunks: ``jnp.cumsum`` gives the same numbers, and the TPU's
    compiler takes seconds over each of its instances."""
    import jax.numpy as jnp
    from jax import lax

    rows, n = marked.shape
    chunks = -(-n // (LANES * LANES)) * LANES  # a multiple of 128 of them
    x = jnp.pad(marked.astype(jnp.float32),
                ((0, 0), (0, chunks * LANES - n))).reshape(rows, -1, LANES)
    upto = (jnp.arange(LANES)[:, None] <= jnp.arange(LANES)[None, :]).astype(
        jnp.float32)

    def inside(v):
        return jnp.einsum("rcl,lm->rcm", v, upto,
                          precision=lax.Precision.HIGHEST)

    in_chunk = inside(x)
    totals = in_chunk[:, :, -1].reshape(rows, -1, LANES)
    over_chunks = inside(totals)
    blocks = over_chunks[:, :, -1]
    before = jnp.arange(blocks.shape[1])
    earlier = jnp.sum(jnp.where(before[:, None] < before[None, :],
                                blocks[:, :, None], 0.0), axis=1)
    return in_chunk, (over_chunks - totals + earlier[:, :, None]).reshape(
        rows, -1)


def running_count(marked):
    """Inclusive running count along the rows of ``marked`` ``(rows, n)``
    bool, int32 (:func:`chunk_counts`)."""
    import jax.numpy as jnp

    rows, n = marked.shape
    in_chunk, starts = chunk_counts(marked)
    return (in_chunk + starts[:, :, None]).reshape(rows, -1)[:, :n].astype(
        jnp.int32)


def mark_largest(scores, k: int):
    """``(rows, n)`` bool: each row's ``k`` largest of ``scores``, of equal
    scores the lower positions, with no sort: the ``k``-th largest by
    bisection on the bit patterns (32 counts over the row), the scores
    above it and the first of those equal to it."""
    import jax.numpy as jnp
    from jax import lax

    rows, _ = scores.shape
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    # keys that order as the scores do: a negative score's bits turned over
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def one_bit(i, kth):
        higher = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= higher[:, None], axis=1) >= k
        return jnp.where(enough, higher, kth)

    kth = lax.fori_loop(0, 32, one_bit, jnp.zeros((rows,), jnp.uint32))
    above, equal = keys > kth[:, None], keys == kth[:, None]
    wanted = k - jnp.sum(above, axis=1)
    return above | (equal & (running_count(equal) <= wanted[:, None]))


def select_chunks(scores, k: int):
    """``(rows, k)`` int32 positions, ascending, of each row's ``k`` largest
    of ``scores`` ``(rows, n)``, of equal scores the lower positions
    (:func:`mark_largest`), compacted with no sort, gather or scatter, on
    the MXU and the VPU alone: a slot's chunk of 128 candidates is the number of chunks that
    end at or before it (a comparison with the chunks' running totals), the
    marks before that chunk the largest such total, the chunk's own running
    counts a product of the chunk's one-hot row with :func:`chunk_counts`'
    (whole numbers up to 128: exact in bfloat16), and the lane inside the
    chunk the number of those counts at or under the slot's rank there."""
    import jax.numpy as jnp

    in_chunk, starts = chunk_counts(mark_largest(scores, k))
    ends = starts + in_chunk[:, :, -1]
    slots = jnp.arange(k, dtype=jnp.float32)
    past = ends[:, None, :] <= slots[None, :, None]  # (rows, k, chunks)
    chunk = jnp.sum(past, axis=2)
    start = jnp.max(jnp.where(past, ends[:, None, :], 0.0), axis=2)
    own = chunk[:, :, None] == jnp.arange(ends.shape[1])[None, None, :]
    counts = jnp.einsum("rkc,rcl->rkl", own.astype(jnp.bfloat16),
                        in_chunk.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    lane = jnp.sum(counts <= (slots[None, :] - start)[:, :, None], axis=2)
    return (chunk * LANES + lane).astype(jnp.int32)


def gather_rows(pool, opened, table, lens, sel, page: int, width: int):
    """``(rows, width, k)``: the latent rows at positions ``sel`` ``(rows,
    k)`` of each sequence, as columns (what ``mla_decode`` takes for an open
    page).  ``pool`` ``(pages, page, row)`` sealed pages, ``opened``
    ``(rows, page, row)`` the sequences' open ones, ``table`` ``(rows,
    max_pages)``, ``lens`` ``(rows,)`` visible keys.  A position past a
    sequence's last page reads through a clipped table slot: some row of the
    pool, which the reader's key limit leaves out."""
    import jax.numpy as jnp

    slot, at = sel // page, sel % page
    page_id = jnp.take_along_axis(
        table, jnp.clip(slot, 0, table.shape[1] - 1), axis=1)
    sealed = pool.reshape(-1, pool.shape[2])[page_id * page + at]
    in_open = jnp.take_along_axis(opened, at[:, :, None], axis=1)
    is_open = slot == ((lens - 1) // page)[:, None]
    got = jnp.where(is_open[:, :, None], in_open, sealed)[:, :, :width]
    return jnp.swapaxes(got, 1, 2)


def sealed_rows(pool, table, sel, page: int):
    """``(rows, k, row)``: for every position of ``sel`` ``(rows, k)`` the
    sealed pool's row, whole and as a row: one gather.  A position in a
    sequence's open page or past its last page reads through its table
    slot all the same (clipped to the table): some row of the pool, which
    the reader replaces from the open page or leaves out."""
    import jax.numpy as jnp

    page_id = jnp.take_along_axis(
        table, jnp.clip(sel // page, 0, table.shape[1] - 1), axis=1)
    return pool.reshape(-1, pool.shape[2])[page_id * page + sel % page]


# -- the vertices of a group's chain ---------------------------------------------

_INDEX = ("qI", "wI", "KI", "KIopen", "lens", "table")


class DsaIndex(DeviceOp):
    """A group's index scores into its rows of ``I`` (XLA: the group's pages
    gathered through the table, the whole rectangle computed, every
    sequence over the most pages one has)."""

    WHOLE = True

    def __init__(self, name: str, args: SparseDecodeArgs, grp: Group,
                 layer: str = ""):
        super().__init__(name)
        self._args, self._grp = args, grp
        self._n = {**_names(layer), "I": "I"}

    def reads(self):
        return [self._n[k] for k in _INDEX + ("I",)]

    def writes(self):
        return [self._n["I"]]

    def _scores(self, q, w, pool, opened, lens, table, scores):
        import jax.numpy as jnp
        from jax import lax

        a, g = self._args.latent, self._grp
        n_t = max(g.tiles)
        rows = slice(g.lead0, g.lead0 + g.rows)
        vis = lens[rows]
        tiles = jnp.arange(n_t)
        kt = pool[table[rows, :n_t]]  # (rows, tiles, dim, page)
        is_open = tiles[None, :] == ((vis - 1) // a.page)[:, None]
        kt = jnp.where(is_open[:, :, None, None], opened[rows][:, None], kt)
        s = jnp.einsum("rhd,rtdk->rhtk", q[rows], kt,
                       preferred_element_type=jnp.float32)
        # the heads' weighted sum on the VPU: float32 to the last bit
        got = jnp.sum(jnp.maximum(s, 0.0) * w[rows][:, :, None, None],
                      axis=1).reshape(g.rows, n_t * a.page)
        seen = jnp.arange(n_t * a.page)[None, :] < vis[:, None]
        got = jnp.where(seen, got, NEG)
        return lax.dynamic_update_slice(scores, got[:, None, :],
                                        (g.lead0, 0, 0))

    def apply(self, bufs, ctx):
        a, g, n = self._args.latent, self._grp, self._n
        live = sum(g.tiles)
        grid = g.rows * max(g.tiles) if self.WHOLE else live
        _count("keys_indexed", sum(_visible(self._args, g)))
        _count("keys_indexed_computed", grid * a.page)
        return {n["I"]: self._scores(*(bufs[n[k]] for k in _INDEX),
                                     bufs[n["I"]])}


class DsaIndexPallas(DsaIndex):
    """The same as one ``dsa_index`` kernel: a step a page there is."""

    WHOLE = False

    def _scores(self, q, w, pool, opened, lens, table, scores):
        from tenzing_tpu.ops.attention_pallas import dsa_index_pallas

        g = self._grp
        return dsa_index_pallas(q, w, pool, opened, lens, table, scores,
                                lead0=g.lead0, tiles=g.tiles)

    def uses_pallas(self) -> bool:
        return True


class DsaIndexChoice(ChoiceOp):
    """Implementation menu of a group's index: XLA or the kernel."""

    def __init__(self, name: str, *where):
        super().__init__(name)
        self._where = where

    def choices(self) -> List[OpBase]:
        return [DsaIndex(self.name() + ".xla", *self._where),
                DsaIndexPallas(self.name() + ".pallas", *self._where)]


class DsaSelect(DeviceOp):
    """A group's selection (:func:`select_chunks`): of each sequence's
    visible scores the ``topk`` largest, their positions into its row of
    ``sel`` (the visible ones first; a sequence with fewer visible keys than
    ``topk`` fills its row with positions past its length, which its
    reader's limit leaves out).  It is handed the group's rectangle of ``I``
    and masks by the lengths itself: a page the index never visited holds
    anything.  ``grp`` may be a whole layer's sequences
    (:func:`whole_batch`)."""

    def __init__(self, name: str, args: SparseDecodeArgs, grp: Group,
                 layer: str = ""):
        super().__init__(name)
        self._args, self._grp = args, grp
        self._n = {**_names(layer), "I": "I"}

    def reads(self):
        return [self._n[k] for k in ("I", "lens", "sel")]

    def writes(self):
        return [self._n["sel"]]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp
        from jax import lax

        args, g, n = self._args, self._grp, self._n
        have = max(g.tiles) * args.latent.page
        cols = candidates(args, g)
        rows = slice(g.lead0, g.lead0 + g.rows)
        rect = bufs[n["I"]][rows, 0, :have]
        seen = jnp.arange(have)[None, :] < bufs[n["lens"]][rows][:, None]
        rect = jnp.pad(jnp.where(seen, rect, NEG),
                       ((0, 0), (0, cols - have)), constant_values=NEG)
        _count("select_candidates", sum(_visible(args, g)))
        _count("select_candidates_padded", g.rows * cols)
        return {n["sel"]: lax.dynamic_update_slice_in_dim(
            bufs[n["sel"]], select_chunks(rect, args.topk), g.lead0, 0)}


class DsaRead(DeviceOp):
    """A group's attention over its selected tokens, into its rows of
    ``o_lat`` in place: the positions' rows of the sealed pool by one gather
    (:func:`sealed_rows`), as rows, and one ``mla_decode_rows`` kernel over
    them, which takes the rows of the slots in a sequence's open page from
    ``Copen`` itself and leaves the slots from ``picked`` on out."""

    _READS = ("qt", "sel", "C", "Copen", "lens", "table", "picked", "o_lat")

    def __init__(self, name: str, args: SparseDecodeArgs, grp: Group,
                 layer: str = ""):
        super().__init__(name)
        self._args, self._grp = args, grp
        self._n = {**_names(layer), "picked": "picked"}

    def reads(self):
        return [self._n[k] for k in self._READS]

    def writes(self):
        return [self._n["o_lat"]]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        from tenzing_tpu.ops.attention_pallas import mla_decode_rows_pallas

        args, a, g, n = self._args, self._args.latent, self._grp, self._n
        rows = slice(g.lead0, g.lead0 + g.rows)
        sel = bufs[n["sel"]]
        got = sealed_rows(bufs[n["C"]], bufs[n["table"]][rows], sel[rows],
                          a.page)
        _count("rows_gathered", g.rows * args.topk)
        # the kernel issues no DMA of its own (Mosaic takes no slice of the
        # pool finer than its tile of 8 rows): the rows come by XLA's
        # gather, through HBM once, whole lanes wide
        _count("row_dmas", 0)
        _count("tile_bytes_via_hbm", g.rows * args.topk * args.row
               * jnp.dtype(a.dtype).itemsize)
        return {n["o_lat"]: mla_decode_rows_pallas(
            bufs[n["qt"]], got, bufs[n["Copen"]], sel, bufs[n["lens"]],
            bufs[n["picked"]], bufs[n["o_lat"]], a.scale, v_dim=a.rank,
            lead0=g.lead0)}

    def uses_pallas(self) -> bool:
        return True


def whole_batch(plan) -> Group:
    """Every sequence of a step as one group: what a layer's one selection
    is handed."""
    return Group(0, 0, sum((grp.tiles for grp, _ in plan), ()), ())


class SparseReads(CompoundOp):
    """A layer's sparse reads as one expandable vertex: a group's ``index ->
    select -> read`` chains side by side, or (``by_layer``) the groups'
    indexes, one selection over the layer's every sequence, then the
    groups' reads."""

    def __init__(self, name: str, args: SparseDecodeArgs, plan, layer: str,
                 impl_choice: bool, by_layer: bool):
        super().__init__(name)
        self._args, self._plan, self._layer = args, plan, layer
        self._impl_choice, self._by_layer = impl_choice, by_layer

    def graph(self) -> Graph:
        args, tag = self._args, self._layer
        # a menu's alternatives are told apart by their vertices' names
        pre = (f"{tag}." if tag else "") + (
            "by_layer." if self._by_layer else "")
        g = Graph()
        over_all = self._by_layer and DsaSelect(
            pre + "dsa_select", args, whole_batch(self._plan), tag)
        for grp, _ in self._plan:
            at = f"{pre}g{grp.index}."
            where = (args, grp, tag)
            chain = [
                (DsaIndexChoice if self._impl_choice else DsaIndexPallas)(
                    at + "dsa_index", *where),
                over_all or DsaSelect(at + "dsa_select", *where),
                DsaRead(at + "dsa_read", *where)]
            g.start_then(chain[0])
            for x, y in zip(chain, chain[1:]):
                g.then(x, y)
            g.then_finish(chain[-1])
        return g


class SparseReadsChoice(ChoiceOp):
    """Granularity menu of a layer's selections (``MlaEngineChoice``'s
    pattern): one a group, which leaves a group's index free to run under
    another's selection and hands a selection its group's pages alone, or
    one a layer, one operation for the groups' several over every sequence
    by the longest's pages."""

    def __init__(self, name: str, args: SparseDecodeArgs, plan,
                 layer: str = "", impl_choice: bool = False):
        super().__init__(name)
        self._where = (args, plan, layer, impl_choice)

    def choices(self) -> List[OpBase]:
        return [SparseReads(self.name() + ".by_group", *self._where, False),
                SparseReads(self.name() + ".by_layer", *self._where, True)]


def dsa_graph(args: SparseDecodeArgs, layers, impl_choice: bool = False
              ) -> Graph:
    """The step's layers one after another, as the residual stream orders
    them.  In a layer the two appends and the absorb come first, side by
    side, then the sparse reads (:class:`SparseReadsChoice`: the groups'
    ``index -> select -> read`` chains side by side, or one selection for
    the layer between the indexes and the reads), then the
    up-projection.  ``impl_choice``: a menu of the index's implementations
    too."""
    plan = dsa_plan(args)
    a = args.latent
    g = Graph()
    last = None
    for tag in layers:
        pre = f"{tag}." if tag else ""
        heads = [Append(pre + "append", a, tag, as_rows=True,
                        counter="dsa.appended_rows"),
                 Append(pre + "index_append", a, tag, src=("kI_new",),
                        dst="KIopen", counter="dsa.appended_rows"),
                 Absorb(pre + "absorb", a, tag)]
        reads = SparseReadsChoice(pre + "dsa_reads", args, plan, tag,
                                  impl_choice)
        up = UpProject(pre + "up_project", a, tag)
        for h in heads:
            if last is None:
                g.start_then(h)
            else:
                g.then(last, h)
            g.then(h, reads)
        g.then(reads, up)
        last = up
    g.then_finish(last)
    return g


def buffer_shapes(args: SparseDecodeArgs, layers) -> Dict[str, tuple]:
    """``{name: (shape, dtype)}`` of the step's buffers."""
    a, dt = args.latent, args.latent.dtype
    b, h, w = a.batch, a.heads, a.width
    out = {"lens": ((b,), "int32"), "table": ((b, a.max_pages), "int32"),
           "picked": ((b,), "int32"),
           "I": ((b, 1, a.max_pages * a.page), "float32")}
    for tag in layers:
        n = _names(tag)
        out.update({
            n["C"]: ((a.pool_pages, a.page, args.row), dt),
            n["Copen"]: ((b, a.page, args.row), dt),
            n["KI"]: ((a.pool_pages, args.index_dim, a.page), dt),
            n["KIopen"]: ((b, args.index_dim, a.page), dt),
            n["c_new"]: ((b, a.rank), dt), n["kr_new"]: ((b, a.rope), dt),
            n["kI_new"]: ((b, args.index_dim), dt),
            n["qI"]: ((b, args.index_heads, args.index_dim), dt),
            n["wI"]: ((b, args.index_heads), "float32"),
            n["q_nope"]: ((b, h, a.nope), dt),
            n["q_rope"]: ((b, h, a.rope), dt),
            n["W_UK"]: ((h, a.nope, a.rank), dt),
            n["W_UV"]: ((h, a.rank, a.v_dim), dt),
            n["qt"]: ((b, h, w), dt), n["o_lat"]: ((b, h, a.rank), dt),
            n["o"]: ((b, h, a.v_dim), dt),
            n["sel"]: ((b, args.topk), "int32")})
    return out


def drawn_scales(args: SparseDecodeArgs) -> Dict[str, float]:
    """Standard deviations of the drawn inputs that are not 1 (``wI`` is
    ``weights_proj(x) . index_heads^-1/2 . index_dim^-1/2``)."""
    a = args.latent
    return {"W_UK": a.nope ** -0.5, "W_UV": a.rank ** -0.5,
            "wI": (args.index_heads * args.index_dim) ** -0.5}


DRAWN = ("C", "Copen", "KI", "KIopen", "c_new", "kr_new", "kI_new", "qI",
         "wI", "q_nope", "q_rope", "W_UK", "W_UV")


def make_dsa_buffers(args: SparseDecodeArgs, layers, seed: int = 0,
                     table_seed: int = 0) -> Dict[str, np.ndarray]:
    """Host buffers of a step at a small size (tests and smoke): the inputs
    normal (:func:`drawn_scales`), a latent row's tail zero, everything
    else zero."""
    import jax.numpy as jnp

    a = args.latent
    rng = np.random.default_rng(seed)
    scaled = drawn_scales(args)
    bufs = {}
    for name, (shape, dtype) in buffer_shapes(args, layers).items():
        kind = name.split(".")[0]
        if kind in DRAWN:
            x = rng.standard_normal(shape) * scaled.get(kind, 1.0)
            if kind in ("C", "Copen"):
                x[..., a.width:] = 0.0
        else:
            x = np.zeros(shape)
        bufs[name] = x.astype(jnp.dtype(dtype))
    bufs["lens"] = np.asarray(a.visible, np.int32)
    bufs["picked"] = np.asarray(args.picked, np.int32)
    bufs["table"] = block_table(a, table_seed)
    return bufs


def dense_caches(args: SparseDecodeArgs, bufs, layer: str = ""):
    """Per sequence ``(latent rows (L_b, width), index keys (L_b,
    index_dim))`` read through the table (host arrays; the plain
    reference's input: it knows no pages)."""
    a, n = args.latent, _names(layer)
    pool, opened = np.asarray(bufs[n["C"]]), np.asarray(bufs[n["Copen"]])
    keys, keys_open = (np.asarray(bufs[n["KI"]]),
                       np.asarray(bufs[n["KIopen"]]))
    table = np.asarray(bufs["table"])
    out = []
    for b, (length, sealed) in enumerate(zip(a.lens, a.sealed)):
        lat = [pool[table[b, j]] for j in range(sealed)] + [opened[b]]
        idx = [keys[table[b, j]].T for j in range(sealed)] + [keys_open[b].T]
        out.append((np.concatenate(lat)[:length, :a.width],
                    np.concatenate(idx)[:length]))
    return out
