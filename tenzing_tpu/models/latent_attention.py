"""One decode step of latent attention (MLA) over a paged latent cache, as a
searchable op DAG: the sibling of :class:`~tenzing_tpu.models.ring_attention.
BlockedAttention` for a batch of sequences of unequal length, one query
position each, against a cache that lives on the chip.

The layer (DeepSeek-V3's attention in its absorbed form, the order in which
the model's own inference code takes the sums; per sequence b with ``L_b``
cached tokens, head h):

1. *Append*: row ``L_b`` of b's cache becomes ``[c_new ; k_rope_new]``; keys
   ``0 .. L_b`` are then visible.
2. *Absorb*: ``qt[b,h] = [q_nope[b,h] . W_UK[h] ; q_rope[b,h]]``, ``rank +
   rope`` wide.
3. ``s[b,h,j] = scale . qt[b,h] . C[b,j,:]``; ``p = softmax_j`` over ``j <=
   L_b`` in float32; ``o_lat[b,h] = sum_j p . C[b,j,:rank]`` (V is a view of
   the cache's first ``rank`` columns: one read of the cache serves both
   products).
4. *Up-project*: ``o[b,h] = o_lat[b,h] . W_UV[h]``.

The cache is **two pools** (``runtime/executor.py``'s buffer semantics
decide it: a buffer an iteration writes is copied into the repeat-n loop's
carry once a dispatch, and the measurement stack holds the run's data, a
probe set and a one-shot program's outputs at once): *sealed* pages, read
only, reached through a block table (``C.<layer>``: ``(pages, width, page)``),
and one *open* page a sequence (``Copen.<layer>``: ``(batch, width, page)``),
which is the only page an append ever writes.  A page holds its keys as
**columns** (``(width, page)``: K^T as it lies).  That is the layout the
TPU's runtime gives a bfloat16 array whose last axis is 576 wide anyway (it
makes the 128-multiple axis minor), and a kernel operand in another layout
is copied whole on every call (0.6 to 1.2 GB a layer: read off the compiled text
before the first chip run).

Sequences are sorted by length and cut into ``groups`` contiguous groups;
each group is one :class:`MlaEngineChoice`: one ``mla_decode`` kernel over
the group's whole range (state in VMEM, writes its rows of ``o_lat`` in
place) **or** a chain of ``mla_fold`` links over ranges of ``fold_pages``
pages (state through HBM) that ends in a finaliser of the group's rows.  A
kernel's grid is the pages its sequences have in its range, one step each
(:func:`decode_plan` counts them from the static lengths), so a group costs
what it holds however unequal its sequences are.  The lengths do not
advance: an iteration is the same step again, so n repeats leave every
buffer as one leaves it.

**On a mesh** (``shards`` > 1: :func:`buffer_shapes`, :func:`mesh_specs`,
:func:`block_table`, :func:`make_decode_buffers`) the step is data-parallel
by sequence: every shard holds ``args.lens`` sequences of its own (the same
list of lengths on each), its own pools, and a block table into its own
sealed pool; every buffer but the two absorbed weight matrices is cut along
its leading axis, and the vertices above, traced under ``shard_map``, see a
shard's part at exactly the one-chip shapes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.core.operation import ChoiceOp, CompoundOp, DeviceOp, OpBase

NEG = -1e30  # the empty row maximum (ops/attention_pallas.py)
STATE = ("acc", "m_run", "l_run")


@dataclass(frozen=True)
class LatentDecodeArgs:
    lens: Tuple[int, ...]  # cached tokens a sequence, ascending
    heads: int = 128
    rank: int = 512        # kv_lora_rank: the latent, and V's width
    rope: int = 64         # qk_rope_head_dim
    nope: int = 128        # qk_nope_head_dim
    v_dim: int = 128       # v_head_dim
    scale: float = 192 ** -0.5
    page: int = 1024       # tokens a page
    groups: int = 4
    fold_pages: int = 32   # pages a link of a split-K chain covers
    dtype: str = "bfloat16"

    def __post_init__(self):
        if list(self.lens) != sorted(self.lens):
            raise ValueError("sequences come sorted by length: a group is a "
                             "run of neighbours")
        if len(self.lens) % self.groups:
            raise ValueError(f"{len(self.lens)} sequences in {self.groups} "
                             "groups")

    @property
    def batch(self) -> int:
        return len(self.lens)

    @property
    def width(self) -> int:
        return self.rank + self.rope

    @property
    def visible(self) -> Tuple[int, ...]:
        """Keys a sequence sees once its new row is in: ``L_b + 1``."""
        return tuple(n + 1 for n in self.lens)

    @property
    def sealed(self) -> Tuple[int, ...]:
        """Whole pages a sequence has behind its open one."""
        return tuple(n // self.page for n in self.lens)

    @property
    def max_pages(self) -> int:
        return max(self.sealed) + 1

    @property
    def pool_pages(self) -> int:
        return max(1, sum(self.sealed))


@dataclass(frozen=True)
class Group:
    """A run of sequences one engine vertex covers, and the links of its
    split-K chain: ``(k_pos, tiles)`` each, the link's first key and the
    pages each sequence has in its range (0 for one that ends before it)."""

    index: int
    lead0: int
    tiles: Tuple[int, ...]  # pages a sequence: the fused kernel walks them
    links: Tuple[Tuple[int, Tuple[int, ...]], ...]

    @property
    def rows(self) -> int:
        return len(self.tiles)

    @property
    def steps(self) -> int:
        """Grid steps of the fused kernel: the pages there are, summed."""
        return sum(self.tiles)


def decode_plan(args: LatentDecodeArgs) -> List[Group]:
    """The groups of a step (the same for every layer): contiguous runs of
    the sorted sequences, each with the pages its sequences have
    (``paged_tiles`` of the static lengths), whole and per link of
    ``fold_pages`` pages.  A kernel's grid is the sum of the pages it is
    handed, so a group costs what its sequences hold whatever their
    lengths' spread; only the XLA fold still computes a link's rectangle,
    sequences by the longest's pages."""
    from tenzing_tpu.obs.tracer import get_tracer
    from tenzing_tpu.ops.attention_pallas import paged_tiles

    rows = args.batch // args.groups
    with get_tracer().span("mla.plan", groups=args.groups,
                           page_tokens=args.page, rows=rows):
        plan = []
        span = args.fold_pages * args.page
        for g in range(args.groups):
            vis = args.visible[g * rows:(g + 1) * rows]
            tiles = tuple(paged_tiles(vis, args.page))
            links = tuple(
                (k_pos, tuple(paged_tiles(vis, args.page, k_pos, span)))
                for k_pos in range(0, max(tiles) * args.page, span))
            plan.append(Group(g, g * rows, tiles, links))
    return plan


def note_pages(args: LatentDecodeArgs, grp: Group, k_pos: int,
               tiles: Tuple[int, ...], whole: bool) -> None:
    """The program's counters for one traced kernel of ``grp`` over
    ``tiles`` pages a sequence from key ``k_pos``, or one XLA fold
    (``whole``: it computes the rectangle, every sequence over the most
    pages one has), at trace time, once per traced body, as ``attn.*``:
    ``mla.page_steps`` (steps that fold a page), ``mla.page_steps_idle``
    (steps of a rectangle past a sequence's last page: 0 for a kernel,
    whose grid is the pages there are), ``mla.keys_useful`` (visible keys of
    the range) and ``mla.keys_computed`` (keys of the pages computed,
    whole)."""
    from tenzing_tpu.obs.metrics import get_metrics

    reg = get_metrics()
    vis = args.visible[grp.lead0:grp.lead0 + grp.rows]
    live = sum(tiles)
    grid = grp.rows * max(tiles) if whole else live
    end = k_pos + max(tiles) * args.page
    reg.counter("mla.page_steps").inc(live)
    reg.counter("mla.page_steps_idle").inc(grid - live)
    reg.counter("mla.keys_useful").inc(
        sum(min(n, end) - min(n, k_pos) for n in vis))
    reg.counter("mla.keys_computed").inc(grid * args.page)


def _names(layer: str, grp: Optional[Group] = None) -> Dict[str, str]:
    """Buffer names of a vertex: the layer's tensors carry its tag; the
    limits, the table and a group's split-K state are shared by the layers
    (they run one after another)."""
    t = f".{layer}" if layer else ""
    n = {k: k + t for k in ("C", "Copen", "c_new", "kr_new", "q_nope",
                            "q_rope", "W_UK", "W_UV", "qt", "o_lat", "o",
                            # a sparse step's (models/sparse_attention.py)
                            "KI", "KIopen", "kI_new", "qI", "wI", "sel")}
    n.update(lens="lens", table="table")
    if grp is not None:
        n.update({s: f"{s}.g{grp.index}" for s in STATE})
    return n


class Append(DeviceOp):
    """Row ``L_b`` of every sequence's cache: column ``L_b % page`` of its
    open page becomes ``[c_new ; k_rope_new]`` (step 1).  ``src`` and
    ``dst`` name another cache's new row and open pages (a sparse step's
    index keys); ``as_rows``: an open page holds its keys as rows, ``(page,
    row width)``, the new row zero past its own width; ``counter``: the
    program's counter the rows are added to."""

    def __init__(self, name: str, args: LatentDecodeArgs, layer: str = "",
                 src=("c_new", "kr_new"), dst: str = "Copen",
                 as_rows: bool = False, counter: str = "mla.appended_rows"):
        super().__init__(name)
        self._args = args
        self._n = _names(layer)
        self._src, self._dst = tuple(src), dst
        self._as_rows, self._counter = as_rows, counter

    def reads(self):
        return [self._n[k] for k in self._src + (self._dst,)]

    def writes(self):
        return [self._n[self._dst]]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp
        from jax import lax

        from tenzing_tpu.obs.metrics import get_metrics

        a, n = self._args, self._n
        opened = bufs[n[self._dst]]
        new = jnp.concatenate([bufs[n[k]] for k in self._src],
                              axis=1).astype(opened.dtype)
        get_metrics().counter(self._counter).inc(a.batch)
        if self._as_rows:
            new = jnp.pad(new, ((0, 0), (0, opened.shape[2] - new.shape[1])))
        # one update in place a sequence, at a column the lengths fix: a
        # scatter makes the compiler lay the open pages out for the scatter
        # and copy them whole for the kernel, every iteration
        for b, length in enumerate(a.lens):
            at = length % a.page
            opened = lax.dynamic_update_slice(
                opened, new[b][None, None, :] if self._as_rows
                else new[b][None, :, None],
                (b, at, 0) if self._as_rows else (b, 0, at))
        return {n[self._dst]: opened}


class Absorb(DeviceOp):
    """``qt = [q_nope . W_UK ; q_rope]`` (step 2): float32 accumulation,
    stored in the layer's dtype."""

    def __init__(self, name: str, args: LatentDecodeArgs, layer: str = ""):
        super().__init__(name)
        self._n = _names(layer)

    def reads(self):
        return [self._n[k] for k in ("q_nope", "q_rope", "W_UK")]

    def writes(self):
        return [self._n["qt"]]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        n = self._n
        lat = jnp.einsum("bhd,hdc->bhc", bufs[n["q_nope"]], bufs[n["W_UK"]],
                         preferred_element_type=jnp.float32)
        qt = bufs[n["qt"]]
        return {n["qt"]: jnp.concatenate(
            [lat.astype(qt.dtype), bufs[n["q_rope"]].astype(qt.dtype)],
            axis=2)}


class UpProject(DeviceOp):
    """``o = o_lat . W_UV`` per head (step 4)."""

    def __init__(self, name: str, args: LatentDecodeArgs, layer: str = ""):
        super().__init__(name)
        self._n = _names(layer)

    def reads(self):
        return [self._n[k] for k in ("o_lat", "W_UV")]

    def writes(self):
        return [self._n["o"]]

    def apply(self, bufs, ctx):
        import jax.numpy as jnp

        n = self._n
        o = jnp.einsum("bhc,hcd->bhd", bufs[n["o_lat"]], bufs[n["W_UV"]],
                       preferred_element_type=jnp.float32)
        return {n["o"]: o.astype(bufs[n["o"]].dtype)}


def _prefix(layer: str, grp: Group) -> str:
    """Op-name prefix of a group's vertices in a layer."""
    return (f"{layer}." if layer else "") + f"g{grp.index}."


_CACHE = ("qt", "C", "Copen", "lens", "table")


class MlaDecode(DeviceOp):
    """A group's whole cache read in one ``mla_decode`` kernel: softmax
    state in VMEM, the group's rows of ``o_lat`` written in place, no state
    buffer touched (``FusedBlockAttn``'s finishing form)."""

    def __init__(self, name: str, args: LatentDecodeArgs, grp: Group,
                 layer: str = ""):
        super().__init__(name)
        self._args, self._grp = args, grp
        self._n = _names(layer, grp)

    def reads(self):
        return [self._n[k] for k in _CACHE + ("o_lat",)]

    def writes(self):
        return [self._n["o_lat"]]

    def apply(self, bufs, ctx):
        from tenzing_tpu.ops.attention_pallas import mla_decode_pallas

        a, g, n = self._args, self._grp, self._n
        note_pages(a, g, 0, g.tiles, whole=False)
        return {n["o_lat"]: mla_decode_pallas(
            *(bufs[n[k]] for k in _CACHE), bufs[n["o_lat"]], a.scale,
            v_dim=a.rank, lead0=g.lead0, tiles=g.tiles)}

    def uses_pallas(self) -> bool:
        return True


class MlaFold(DeviceOp):
    """One link of a group's split-K chain: the pages from key ``k_pos``
    that the link covers folded into the group's softmax state (XLA: the
    pages gathered through the table, the whole rectangle computed, every
    sequence over the most pages one has there).  ``first`` opens the state
    instead of reading it."""

    WHOLE = True  # computes its rectangle whole (the counters' keys_computed)

    def __init__(self, name: str, args: LatentDecodeArgs, grp: Group,
                 link: int, layer: str = "", first: bool = False):
        super().__init__(name)
        self._args, self._grp, self._first = args, grp, first
        self._k_pos, self._tiles = grp.links[link]
        self._n = _names(layer, grp)

    def reads(self):
        n = self._n
        return [n[k] for k in _CACHE] + (
            [] if self._first else [n[s] for s in STATE])

    def writes(self):
        return [self._n[s] for s in STATE]

    def _update(self, q, pool, opened, lens, table, state):
        import jax.numpy as jnp

        a, g = self._args, self._grp
        first, n_t = self._k_pos // a.page, max(self._tiles)
        rows = slice(g.lead0, g.lead0 + g.rows)
        vis = lens[rows]
        tiles = first + jnp.arange(n_t)
        idx = jnp.take_along_axis(
            table[rows], jnp.broadcast_to(
                jnp.clip(tiles, 0, table.shape[1] - 1), (g.rows, n_t)),
            axis=1)
        kt = pool[idx]  # (rows, tiles, width, page)
        is_open = tiles[None, :] == ((vis - 1) // a.page)[:, None]
        kt = jnp.where(is_open[:, :, None, None], opened[rows][:, None], kt)
        kt = jnp.moveaxis(kt, 1, 2).reshape(g.rows, a.width, n_t * a.page)
        seen = (first * a.page + jnp.arange(n_t * a.page))[None, :] \
            < vis[:, None]
        shape = (g.rows, a.heads, a.rank)
        if state is None:
            acc, m, l = (jnp.full(shape, c, jnp.float32)
                         for c in (0., NEG, 0.))
        else:
            acc, m, l = state
        s = jnp.einsum("rhd,rdk->rhk", q[rows], kt,
                       preferred_element_type=jnp.float32) * a.scale
        s = jnp.where(seen[:, None, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen[:, None, :], jnp.exp(s - m_new[..., :1]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "rhk,rck->rhc", p.astype(kt.dtype), kt[:, :a.rank],
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    def apply(self, bufs, ctx):
        n = self._n
        state = None if self._first else tuple(bufs[n[s]] for s in STATE)
        note_pages(self._args, self._grp, self._k_pos, self._tiles,
                   whole=self.WHOLE)
        out = self._update(*(bufs[n[k]] for k in _CACHE), state)
        return dict(zip((n[s] for s in STATE), out))


class MlaFoldPallas(MlaFold):
    """The link as one ``mla_fold`` kernel: a step a page, and none for a
    sequence that ends before the link (its state stays where it is)."""

    WHOLE = False

    def _update(self, q, pool, opened, lens, table, state):
        from tenzing_tpu.ops.attention_pallas import mla_fold_pallas

        a, g = self._args, self._grp
        return mla_fold_pallas(
            q, pool, opened, lens, table, *(state or (None,) * 3), a.scale,
            v_dim=a.rank, lead0=g.lead0, k_pos=self._k_pos,
            tiles=self._tiles)

    def uses_pallas(self) -> bool:
        return True


class MlaFoldChoice(ChoiceOp):
    """Implementation menu of one link: XLA gather and einsums, or the
    kernel."""

    def __init__(self, name: str, *where):
        super().__init__(name)
        self._where = where

    def choices(self) -> List[OpBase]:
        return [MlaFold(self.name() + ".xla", *self._where),
                MlaFoldPallas(self.name() + ".pallas", *self._where)]


class FinalizeLatent(DeviceOp):
    """``o_lat = acc / l`` for a group's rows, written into the layer's
    ``o_lat`` in place of what was there: the end of a split-K chain."""

    def __init__(self, name: str, args: LatentDecodeArgs, grp: Group,
                 layer: str = ""):
        super().__init__(name)
        self._grp = grp
        self._n = _names(layer, grp)

    def reads(self):
        return [self._n[k] for k in ("acc", "l_run", "o_lat")]

    def writes(self):
        return [self._n["o_lat"]]

    def apply(self, bufs, ctx):
        import jax.lax as lax

        n = self._n
        o_lat = bufs[n["o_lat"]]
        rows = (bufs[n["acc"]] / bufs[n["l_run"]]).astype(o_lat.dtype)
        return {n["o_lat"]: lax.dynamic_update_slice_in_dim(
            o_lat, rows, self._grp.lead0, 0)}


class FoldChain(CompoundOp):
    """A group's split-K chain as one expandable vertex (``BlockChain``'s
    pattern): its links in key order through the state, then the
    finaliser."""

    def __init__(self, name: str, args: LatentDecodeArgs, grp: Group,
                 layer: str = "", impl_choice: bool = False):
        super().__init__(name)
        self._args, self._grp, self._layer = args, grp, layer
        self._impl_choice = impl_choice

    def graph(self) -> Graph:
        g = Graph()
        pre = _prefix(self._layer, self._grp)
        mk = MlaFoldChoice if self._impl_choice else MlaFoldPallas
        ops = [mk(f"{pre}mla_fold_{i}", self._args, self._grp, i,
                  self._layer, i == 0)
               for i in range(len(self._grp.links))]
        ops.append(FinalizeLatent(pre + "mla_finalize", self._args,
                                  self._grp, self._layer))
        g.start_then(ops[0])
        for a, b in zip(ops, ops[1:]):
            g.then(a, b)
        g.then_finish(ops[-1])
        return g


class MlaEngineChoice(ChoiceOp):
    """Granularity menu of one group's cache read (``AttnEngineChoice``'s
    pattern): the split-K chain or the one fused kernel."""

    def __init__(self, args: LatentDecodeArgs, grp: Group, layer: str = "",
                 impl_choice: bool = False):
        super().__init__(_prefix(layer, grp) + "mla_read")
        self._where = (args, grp, layer)
        self._impl_choice = impl_choice

    def choices(self) -> List[OpBase]:
        return [FoldChain(self.name() + ".chain", *self._where,
                          self._impl_choice),
                MlaDecode(self.name() + ".fused", *self._where)]


def add_layer(g: Graph, args: LatentDecodeArgs, plan: List[Group], tag: str,
              after=(), impl_choice: bool = False) -> OpBase:
    """One layer's vertices: the append and the absorb first, side by side
    (each behind every vertex of ``after``; none: behind the graph's start),
    then the groups' engine menus, side by side, then the up-projection,
    which is returned.  ``after`` may be a mapping ``{"append": [...],
    "absorb": [...]}`` where vertices of the graph produce the new row and
    the query (``models/shortcut_moe.py``): each head then waits for its
    own producer only."""
    pre = f"{tag}." if tag else ""
    heads = [Append(pre + "append", args, tag),
             Absorb(pre + "absorb", args, tag)]
    up = UpProject(pre + "up_project", args, tag)
    for h, kind in zip(heads, ("append", "absorb")):
        before = after.get(kind, ()) if isinstance(after, dict) else after
        if not before:
            g.start_then(h)
        for prev in before:
            g.then(prev, h)
    for grp in plan:
        read = MlaEngineChoice(args, grp, tag, impl_choice)
        for h in heads:
            g.then(h, read)
        g.then(read, up)
    return up


def decode_graph(args: LatentDecodeArgs, layers, impl_choice: bool = False
                 ) -> Graph:
    """The step's layers one after another, as the residual stream orders
    them (layer l+1 starts when layer l's ``o`` is final)."""
    plan = decode_plan(args)
    g = Graph()
    last = None
    for tag in layers:
        last = add_layer(g, args, plan, tag, [last] if last else (),
                         impl_choice)
    g.then_finish(last)
    return g


_EVERYWHERE = ("W_UK", "W_UV")  # what a mesh holds whole on every shard


def mesh_specs(args: LatentDecodeArgs, layers, axis: str) -> Dict[str, object]:
    """Partition spec of every buffer of :func:`buffer_shapes` on a mesh
    whose ``axis`` cuts the sequences: the leading axis of each, but the two
    absorbed weight matrices, which every shard holds whole."""
    from jax.sharding import PartitionSpec as P

    whole = {_names(tag)[k] for tag in layers for k in _EVERYWHERE}
    return {name: P() if name in whole
            else P(axis, *([None] * (len(shape) - 1)))
            for name, (shape, _) in buffer_shapes(args, layers).items()}


def buffer_shapes(args: LatentDecodeArgs, layers, shards: int = 1
                  ) -> Dict[str, tuple]:
    """``{name: (shape, dtype)}`` of the step's buffers; with ``shards`` the
    global shapes on a mesh (:func:`mesh_specs`): ``shards`` times the
    sequences, pools and tables, one shard's after another."""
    if shards > 1:
        whole = {_names(tag)[k] for tag in layers for k in _EVERYWHERE}
        return {name: (shape if name in whole
                       else (shards * shape[0],) + tuple(shape[1:]), dtype)
                for name, (shape, dtype) in
                buffer_shapes(args, layers).items()}
    a, dt = args, args.dtype
    b, h, w = a.batch, a.heads, a.width
    out = {"lens": ((b,), "int32"), "table": ((b, a.max_pages), "int32")}
    for tag in layers:
        n = _names(tag)
        out.update({
            n["C"]: ((a.pool_pages, w, a.page), dt),
            n["Copen"]: ((b, w, a.page), dt),
            n["c_new"]: ((b, a.rank), dt), n["kr_new"]: ((b, a.rope), dt),
            n["q_nope"]: ((b, h, a.nope), dt),
            n["q_rope"]: ((b, h, a.rope), dt),
            n["W_UK"]: ((h, a.nope, a.rank), dt),
            n["W_UV"]: ((h, a.rank, a.v_dim), dt),
            n["qt"]: ((b, h, w), dt), n["o_lat"]: ((b, h, a.rank), dt),
            n["o"]: ((b, h, a.v_dim), dt)})
    for grp in decode_plan(args):
        n = _names("", grp)
        out.update({n[s]: ((grp.rows, h, a.rank), "float32") for s in STATE})
    return out


def block_table(args: LatentDecodeArgs, seed: int, shards: int = 1
                ) -> np.ndarray:
    """``(batch, max_pages)``: sequence b's j-th sealed page is page
    ``table[b, j]`` of the pool, the sealed pages of all sequences laid out
    as a random permutation of the pool (a cache after many allocations);
    slots past a sequence's sealed pages hold 0.  With ``shards``: one such
    table a shard, each into its own pool (another permutation each), one
    after another."""
    if shards > 1:
        return np.concatenate([block_table(args, seed + i)
                               for i in range(shards)])
    perm = np.random.default_rng(seed).permutation(args.pool_pages)
    table = np.zeros((args.batch, args.max_pages), np.int32)
    at = 0
    for b, n in enumerate(args.sealed):
        table[b, :n] = perm[at:at + n]
        at += n
    return table


def make_decode_buffers(args: LatentDecodeArgs, layers, seed: int = 0,
                        table_seed: int = 0, shards: int = 1
                        ) -> Dict[str, np.ndarray]:
    """Host buffers of a step at a small size (tests and smoke): the inputs
    standard normal (``W_UK`` over ``sqrt(nope)``, ``W_UV`` over
    ``sqrt(rank)``), the outputs and the state zero.  With ``shards`` the
    global arrays of a mesh (:func:`buffer_shapes`)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    scaled = {"W_UK": args.nope ** -0.5, "W_UV": args.rank ** -0.5}
    drawn = ("C", "Copen", "c_new", "kr_new", "q_nope", "q_rope",
             "W_UK", "W_UV")
    bufs = {}
    for name, (shape, dtype) in buffer_shapes(args, layers, shards).items():
        kind = name.split(".")[0]
        if kind in drawn:
            x = rng.standard_normal(shape) * scaled.get(kind, 1.0)
        else:
            x = np.full(shape, NEG if kind == "m_run" else 0.0)
        bufs[name] = x.astype(jnp.dtype(dtype))
    bufs["lens"] = np.tile(np.asarray(args.visible, np.int32), shards)
    bufs["table"] = block_table(args, table_seed, shards)
    return bufs


def dense_caches(args: LatentDecodeArgs, bufs, layer: str = ""):
    """Per sequence its cached rows ``(L_b, width)`` read through the table
    (host arrays; the plain reference's input: it knows no pages)."""
    n = _names(layer)
    pool, opened = np.asarray(bufs[n["C"]]), np.asarray(bufs[n["Copen"]])
    table = np.asarray(bufs["table"])
    out = []
    for b, (length, sealed) in enumerate(zip(args.lens, args.sealed)):
        pages = [pool[table[b, j]].T for j in range(sealed)] + [opened[b].T]
        out.append(np.concatenate(pages)[:length])
    return out
