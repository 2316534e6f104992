"""Pallas flash-attention kernels: online-softmax folds on the MXU.

The MXU workhorse of the attention workloads (models/ring_attention.py):
given queries Q and a stretch of K/V, fold it into the running (acc, m, l)
online-softmax state:

    s     = Q K^T * scale          (MXU)
    m'    = max(m, rowmax(s))
    alpha = exp(m - m')
    p     = exp(s - m')
    l'    = l * alpha + rowsum(p)
    acc'  = acc * alpha + p V      (MXU)

One kernel body, four entry points that differ in what they are handed and in
the name the device trace shows:

* :func:`attn_block_pallas` (``attn_fold``): one K/V block, the state read
  from HBM and written back — one link of a per-block chain;
* :func:`attn_fused_pallas` (``attn_fused``): a whole K/V range, the state
  in VMEM scratch across it;
* :func:`mla_decode_pallas` (``mla_decode``) and :func:`mla_fold_pallas`
  (``mla_fold``): the same two over a **paged latent cache** (one decode
  step of latent attention, models/latent_attention.py), see below.

(Two more kernels live here with bodies of their own: ``dsa_index`` on the
paged walk and ``mla_decode_rows``, both models/sparse_attention.py's.)

and three shapes of output:

* state in, state out (``acc``, ``m``, ``l`` handed): three float32
  ``(h, n, d)`` tensors read and three written;
* state out only (the three handed as ``None``): the first fold of a chain;
* O out (``attn_fused_pallas(..., finish=True)``, legal only with no state
  handed): a call that opens its rows' state and sees their whole visible
  range holds the finished ``acc`` and ``l`` in VMEM at a query tile's last
  K/V step, so it divides there (the float32 division a finaliser would
  do, rounded once) and writes O alone: no state leaves the chip.  Handed
  the layer's O (``o``, ``o_row0``) it writes its rows of it in place:
  O is an aliased operand that is never fetched, the output's index map
  walks tiles ``o_row0 // bq + j``, and every other row stays as it was.
  Rows that are not whole tiles of O (a ragged count, a block that starts
  inside a tile) come out fresh and are put in by
  ``dynamic_update_slice_in_dim``.

and what it reads, the same way (``attn_fused_pallas(..., q_row0, rows,
k_row0, keys)``): handed a layer's whole Q ``(h, N, d)`` and K, V ``(hkv,
Nk, d)`` with the call's row range and key range, it takes them as they lie
where those are whole tiles of the buffers (:func:`taken_whole`): the Q
index map walks tiles ``q_row0 // bq + j``, the K/V index map adds ``k_row0
// bkv`` to the visible tile it picks (the tile count a query tile's range
is clamped to stays the range's), and nothing is sliced out in HBM first.
A range that is no tile multiple is sliced out by the call.  ``tok``, the
caller's ordering token as an int32 zero, is added onto the scalar-
prefetched positions: the kernel waits for its scalars, so no operand needs
a value-preserving add to carry the token.

State tensors m and l are carried broadcast to (h, n, d) — same shape/layout
as acc — so every in-kernel operand is a clean 2D (n, d) or (n, nkv) tile (no
lane<->sublane transposes, no last-dim-1 blocks; see ops/spmv_pallas.py for
the Mosaic layout constraints that motivate this).  They stay float32
whatever Q/K/V are.

Heads: the leading axis of Q and the state is ``batch * heads``, that of K/V
``batch * kv_heads``; query head ``i`` reads K/V head ``i // (heads //
kv_heads)`` (the K/V index map), so grouped-query attention costs no copy.

Mask: ``causal`` (key position <= query position) and ``window`` (key
position > query position - window) are decided from positions.  The
positions of the operands' first rows (``q_pos``, ``k_pos``) reach the
kernel as one scalar-prefetch operand, so one kernel body serves every
block of a layer.  Only their difference matters; the callers
(models/ring_attention.py) hand ``q_pos - k_pos`` and 0, so that blocks
that sit alike under the mask are one traced call.  Per query tile only the K/V tiles that hold a visible
key are visited (the K/V index map walks that range, the steps left over
skip their compute and fetch nothing new); of those, only the tiles the
diagonal or the window's edge crosses build the iota comparison.  A row
whose every key in a tile is masked stays finite: ``m`` starts at -1e30,
not -inf, and a masked ``p`` is set to zero, not to ``exp(0)``.

Packed prompts (``segments``, with ``causal``): the rows are several
prompts one after another, and a row sees the keys of its own prompt alone,
from the prompt's first row (its *segment start*) to its own.  The starts
are scalar-prefetched behind the two positions, in their coordinates; a
row's start is the largest that is not beyond it (a few compares and
selects, on the scalar core for a tile's first and last row, on a column of
the tile for the mask).  A query tile walks from the tile that holds its
first row's start, and a tile is an edge tile too where some row's start
lies beyond its first key.

``acc``/``m``/``l`` handed as ``None`` starts from the empty
state instead of reading one: the first fold of a chain, which makes an
iteration leave the state one iteration leaves.

Paged (:func:`_flash_paged`; what the operands handed decide, no switch):

* **V is a view of K**: no V operand; the second product runs over K's
  first ``v_dim`` columns (a latent cache row is ``[c ; k_rope]`` and V is
  ``c``), so the cache is read once.  The state and O are ``v_dim`` wide,
  Q and K ``d``.
* **A key limit per leading index**: the leading index is a sequence, its
  rows (heads) all sit at one position, and ``lens[b]`` keys of it are
  visible (a scalar-prefetched vector).  The causal comparison by row does
  not apply; the tile that holds the last visible key masks by column.
* **K through a block table** (``table[b, j]``, scalar-prefetched): tile j
  of sequence b is ``pool[table[b, j]]`` while it is sealed (whole, every
  key visible: no mask built), and ``k_open[b]``, a second K operand, where
  the last visible key lies (the one page an append writes).
* **The grid is the pages there are**: one axis of (sequence, page) steps,
  sequence after sequence and a sequence's pages ascending, as many as the
  call's sequences have pages in its key range (``tiles``, static:
  :func:`paged_tiles` of the lengths the caller sizes the call by).  Not
  sequences by the longest's pages: every step folds a page, and the next
  sequence's Q, open page and first page are always fetched behind a fold.
  A step finds its sequence and its page by comparing its index with the
  sequences' first steps, which are immediates of the kernel and of its
  index maps (:func:`paged_step`): the walk is no operand and no operation
  of the program around the kernel.  A sequence's state opens at its first
  step of the call and leaves at its last; a sequence with no page in a
  link's range has no step, and keeps its state because the handed state
  *is* the output (aliased, as O is).
* **A page holds its keys as columns**, ``(d, page)``: K^T as it lies.  The
  TPU runtime lays a bfloat16 array out with its 128-multiple axis minor,
  so a ``(page, 576)`` page would reach the kernel through a copy of the
  whole pool on every call; ``(576, page)`` arrives as it is.  The first
  product is then plain and the second contracts the keys of both operands.
* The walk's sequences count from ``lead0`` of Q, ``lens``, ``table``,
  ``k_open`` and O, which are the whole batch's: no slice of them is made
  for a group.

The paged walk also serves a kernel with another body
(:func:`dsa_index_pallas`, ``dsa_index``; models/sparse_attention.py): the
scores a sparse selection is made from, ``sum_h w_h relu(q_h . k_j)`` over a
paged cache of index keys.  The same grid of the pages there are, the same
scalar operands and index maps (:func:`_paged_maps`), the open page as a
second K operand, no softmax and no state: a step writes its page of one
row of scores a sequence, ``NEG`` past the sequence's limit.

A sparse step's attention over its selected tokens is a kernel of its own
(:func:`mla_decode_rows_pallas`, ``mla_decode_rows``) and shares nothing
with the walk: the dense read wants whole pages as columns through an index
map and a running softmax over many pages; the sparse read wants
token-granular rows and one softmax over a resident tile.  Its keys stay
rows, ``(keys, row)``, as the sparse step's pool holds them, and arrive
gathered; the kernel fetches none of the pool itself, because Mosaic takes
no slice of a tiled HBM operand finer than its tile (8 rows of the pool's
``(8,128)(2,1)`` bfloat16 layout: a one-row DMA does not compile;
``tests/test_tpu_compile.py``).

``interpret=True`` (automatic off-TPU) runs the same kernel in the Pallas
interpreter for CPU tests.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tenzing_tpu.ops.common import out_struct

NEG = -1e30  # the empty row maximum: finite, so exp(NEG - NEG) is no NaN
Q_TILE = 512
KV_TILE = 1024


@dataclass(frozen=True)
class _Plan:
    """What the kernel body is specialised on (all static)."""

    scale: float
    bq: int
    bkv: int
    kv_tiles: int  # K/V tiles in the operand
    steps: int     # grid extent over K/V: the most tiles a query tile sees
                   # (paged: the whole grid, the pages its sequences have)
    causal: bool   # the mask: a window implies it
    window: Optional[int]
    init: bool
    finish: bool = False  # write O = acc / l and no state (needs init)
    # a paged latent cache (``_flash_paged``): V is K's first ``dv`` columns
    # (no V operand), the leading index is a sequence with its own key limit,
    # K tiles come through a block table, and the grid walks ``tiles[i]``
    # tiles from ``tile0`` of sequence ``lead0 + i`` of q, the limits, the
    # table and O, one sequence after another (``steps`` in all)
    dv: Optional[int] = None
    paged: bool = False
    lead0: int = 0
    tiles: Tuple[int, ...] = ()
    tile0: int = 0
    segs: int = 0  # segment starts prefetched behind the two positions


def segment_start(starts, pos, pick=jnp.where):
    """The largest of the ascending ``starts`` that is not beyond ``pos``
    (the first where none is): the first row of ``pos``'s prompt.  On
    traced values, or on Python ints with ``pick=pick_int``."""
    out = starts[0]
    for s in starts[1:]:
        out = pick(pos >= s, s, out)
    return out


def pick_int(cond, a, b):
    return a if cond else b


def visible_tiles(plan: _Plan, q_lo, k_pos, larger=jnp.maximum,
                  smaller=jnp.minimum, starts=(), pick=jnp.where):
    """``(first, last)`` K/V tile of the operand that holds a key visible to
    the query tile whose first row is at position ``q_lo`` (``last < first``
    where there is none).  On traced scalars inside the kernel and its index
    maps; on Python ints (``larger=max, smaller=min``) where the wrapper
    sizes the grid."""
    first, last = 0, plan.kv_tiles - 1
    if plan.window is not None:
        first = larger(q_lo - plan.window + 1 - k_pos, 0) // plan.bkv
    if len(starts):
        # nothing before the prompt of the tile's first row
        first = larger(first, larger(
            segment_start(starts, q_lo, pick) - k_pos, 0) // plan.bkv)
    if plan.causal:
        last = smaller((q_lo + plan.bq - 1 - k_pos) // plan.bkv, last)
    return first, last


def walk_step(plan: _Plan, first, last, t):
    """``(walked, live)``: at K/V step ``t`` the query tile that sees tiles
    ``first .. last`` is on tile ``first + walked``, and folds it if ``live``.  It
    walks them in its *last* steps: a tile that sees fewer than
    ``plan.steps`` idles first, on ``first`` (nothing folded, nothing
    fetched anew).  So a query tile's last step is a fold, and the next
    tile's Q and first K/V tile, fetched one step ahead, arrive behind a
    fold and not behind an idle step a sixth as long (PERF.md, PR 37).  The
    folds keep their order.  No mask: every tile, in order."""
    if not plan.causal:
        return t, True
    idle = plan.steps - (last - first + 1)
    return jnp.maximum(t - idle, 0), t >= idle


def computed_pairs(rows: int, keys: int, q_pos: int, k_pos: int,
                   causal: bool, window: Optional[int], bq: int = Q_TILE,
                   bkv: int = KV_TILE, segments=None) -> int:
    """(query, key) pairs a call of the kernel computes for one head, masked
    ones included: its (query tile, K/V tile) steps that hold a visible key,
    whole (the program's ``attn.pairs_computed``)."""
    bq, bkv = min(rows, bq), min(bkv, keys)
    plan = _Plan(0.0, bq, bkv, keys // bkv, 0, causal, window, False)
    starts = tuple(segments or ())
    tiles = 0
    for j in range(-(-rows // bq)):
        first, last = visible_tiles(plan, q_pos + j * bq, k_pos, max, min,
                                    starts, pick_int)
        tiles += max(0, last - first + 1)
    return tiles * bq * bkv


def _flash_kernel(plan: _Plan, offs, *refs):
    """One (head, q-tile, kv-step) grid step: state lives in VMEM scratch
    across the kv dimension (innermost, strictly sequential), so acc/m/l
    touch HBM once in (not at all with ``init``) and once out per q-tile
    (not at all with ``finish``: the tile's last step divides and writes its
    rows of O).  Paged: one (sequence, page) step of a one-axis grid; no
    positions, the scalar operands are the key limits and the block table,
    and K comes as a tile of the sealed pool and the sequence's open
    page."""
    if plan.paged:
        lens, (table, q_ref, k_ref, ko_ref, *refs) = offs, refs
        v_ref = None
    else:
        q_ref, k_ref, v_ref, *refs = refs
    if plan.finish:
        # an aliased O comes first among the refs, unfetched: never read
        o_out, acc_s, m_s, l_s = refs[-4:]
    elif plan.init:
        acc_out, m_out, l_out, acc_s, m_s, l_s = refs
    else:
        acc_in, m_in, l_in, acc_out, m_out, l_out, acc_s, m_s, l_s = refs
    if plan.paged:
        step = pl.program_id(0)
        seq, start, count = paged_step(plan.tiles, step)
        opens = step == start
    else:
        j, t = pl.program_id(1), pl.program_id(2)
        opens = t == 0

    @pl.when(opens)
    def _():
        if plan.init:
            acc_s[...] = jnp.zeros_like(acc_s)
            m_s[...] = jnp.full_like(m_s, NEG)
            l_s[...] = jnp.zeros_like(l_s)
        else:
            acc_s[...] = acc_in[0]
            m_s[...] = m_in[0]
            l_s[...] = l_in[0]

    if plan.paged:
        # the keys sequence b sees, and the tile of this step: the tile
        # that holds the last visible key is the open page, those before
        # it are sealed (whole, every key visible)
        limit = lens[plan.lead0 + seq]
        tile = plan.tile0 + (step - start)
        k_lo = tile * plan.bkv
    else:
        q_lo = offs[0] + j * plan.bq
        starts = [offs[2 + i] for i in range(plan.segs)]
        first, last = visible_tiles(plan, q_lo, offs[1], starts=starts)
        walked, live = walk_step(plan, first, last, t)
        k_lo = offs[1] + (first + walked) * plan.bkv

    def fold(edge: bool, k_ref=k_ref):
        if plan.paged:
            # a page holds its keys as columns, (d, bkv): K^T as it lies,
            # and V^T its first dv rows
            q, k, v = q_ref[0], k_ref[0], k_ref[0, :plan.dv, :]
        else:
            q, k, v = q_ref[0], k_ref[0], v_ref[0]
        m_old, l_old, acc_old = m_s[...], l_s[...], acc_s[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (0 if plan.paged else 1,)), ((), ())),
            preferred_element_type=jnp.float32
        ) * plan.scale  # (bq, bkv)
        if edge and plan.paged:
            # every row of a sequence sits at one position: a key limit
            seen = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) < limit
            s = jnp.where(seen, s, NEG)
        elif edge:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = kpos <= qpos
            if plan.window is not None:
                seen = seen & (kpos > qpos - plan.window)
            if plan.segs:
                # no key before the row's own prompt
                rows_at = q_lo + jax.lax.broadcasted_iota(
                    jnp.int32, (s.shape[0], 1), 0)
                seen = seen & (kpos >= segment_start(starts, rows_at))
            s = jnp.where(seen, s, NEG)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_old, jnp.broadcast_to(m_blk, m_old.shape))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[:, :1])
        if edge:
            p = jnp.where(seen, p, 0.0)
        l_s[...] = l_old * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_old.shape
        )
        acc_new = acc_old * alpha
        if plan.paged:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc_s[...] = acc_new + pv
        m_s[...] = m_new

    if plan.paged:
        open_tile = (limit - 1) // plan.bkv
        pl.when(tile < open_tile)(lambda: fold(False))
        pl.when(tile == open_tile)(lambda: fold(True, ko_ref))
    elif plan.causal:
        edge = k_lo + plan.bkv - 1 > q_lo
        if plan.window is not None:
            edge = edge | (k_lo <= q_lo + plan.bq - 1 - plan.window)
        if plan.segs:
            edge = edge | (k_lo < segment_start(starts,
                                                q_lo + plan.bq - 1))
        pl.when(live & edge)(lambda: fold(True))
        pl.when(live & jnp.logical_not(edge))(lambda: fold(False))
    else:
        fold(False)

    @pl.when(step == start + count - 1 if plan.paged
             else t == plan.steps - 1)
    def _():
        if plan.finish:
            # FinalizeAttn's own float32 division, rounded once
            o_out[0] = (acc_s[...] / l_s[...]).astype(o_out.dtype)
        else:
            acc_out[0] = acc_s[...].astype(acc_out.dtype)
            m_out[0] = m_s[...]
            l_out[0] = l_s[...]


def taken_whole(extent: int, row0: int, rows: int, tile: int) -> bool:
    """Whether a call that reads rows ``row0 .. row0 + rows`` of an operand
    of ``extent`` rows takes the operand as it lies: the rows are all of
    it, or whole tiles of it (of ``tile`` rows, or of ``rows`` where that
    is fewer), which a block index can address."""
    if (row0, rows) == (0, extent):
        return True
    tile = min(rows, tile)
    return row0 % tile == 0 and rows % tile == 0


def _rows_at(x, row0: int, rows: Optional[int], tile: int):
    """``(operand, first row, rows)`` of a call that reads rows ``row0 ..
    row0 + rows`` of ``x``'s axis 1 (to the end without ``rows``): ``x``
    as it lies where :func:`taken_whole`, else the rows sliced out, from
    row 0."""
    rows = x.shape[1] - row0 if rows is None else rows
    if taken_whole(x.shape[1], row0, rows, tile):
        return x, row0, rows
    return jax.lax.dynamic_slice_in_dim(x, row0, rows, 1), 0, rows


def _flash(name, q, k, v, acc, m, l, scale, bq, bkv, q_pos, k_pos, causal,
           window, interpret, finish=False, o=None, o_row0=0, q_row0=0,
           rows=None, k_row0=0, keys=None, tok=None, segments=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window is not None and not causal:
        raise ValueError("a window is counted back from the query's own "
                         "position: it needs causal=True")
    segments = tuple(int(s) for s in segments or ())
    if segments and not causal:
        raise ValueError("packed prompts are masked by position: "
                         "segments need causal=True")
    q, q_row0, n = _rows_at(q, q_row0, rows, bq)
    v = _rows_at(v, k_row0, keys, bkv)[0]
    k, k_row0, nkv = _rows_at(k, k_row0, keys, bkv)
    h, d = q.shape[0], q.shape[2]
    hkv = k.shape[0]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key/value heads")
    group = h // hkv
    bkv = min(bkv, nkv)
    if nkv % bkv:
        raise ValueError(f"{nkv} K/V rows in tiles of {bkv}")
    init = acc is None
    if finish and not init:
        raise ValueError("a call that finishes its rows opens their state: "
                         "it is handed no acc, m and l")
    if o is not None and not finish:
        raise ValueError("O is written by a call that finishes its rows")
    # tile the (row-independent) update over query tiles so VMEM holds one
    # q/state tile + one K/V tile, never all n queries at once; ragged n is
    # padded up to the tile (rows are independent, pad rows stay finite:
    # zero q/m give s=0, alpha=1 — no NaN/inf to leak) and sliced back off
    bq = min(n, bq)
    pad = (-n) % bq
    np_ = n + pad
    padw = ((0, 0), (0, pad), (0, 0))
    state = () if init else (acc, m, l)
    if pad:
        q = jnp.pad(q, padw)
        state = tuple(jnp.pad(t, padw) for t in state)
    plan = _Plan(float(scale), bq, bkv, nkv // bkv, nkv // bkv, bool(causal),
                 None if window is None else int(window), init, bool(finish),
                 segs=len(segments))
    if plan.causal:
        spans = [visible_tiles(plan, q_pos + j * bq, k_pos, max, min,
                               segments, pick_int)
                 for j in range(np_ // bq)]
        plan = replace(plan, steps=max(1, max(b - a + 1 for a, b in spans)))

    # the first tile of the call's rows in Q and of its keys in K and V; an
    # index map adds it only where it is not 0, so that a call that names
    # no offset traces what it traced (the pinned jaxprs)
    q_tile0, k_tile0 = q_row0 // bq, k_row0 // bkv

    def kv_tile(j, t, offs):
        tile = t
        if plan.causal:
            first, last = visible_tiles(
                plan, offs[0] + j * bq, offs[1],
                starts=[offs[2 + i] for i in range(plan.segs)])
            tile = jnp.clip(first + walk_step(plan, first, last, t)[0], 0,
                            plan.kv_tiles - 1)
        return k_tile0 + tile if k_tile0 else tile

    rowblk = pl.BlockSpec((1, bq, d), lambda i, j, t, offs: (i, j, 0))
    qblk = rowblk if not q_tile0 else pl.BlockSpec(
        (1, bq, d), lambda i, j, t, offs: (i, q_tile0 + j, 0))
    kvblk = pl.BlockSpec(
        (1, bkv, d), lambda i, j, t, offs: (i // group, kv_tile(j, t, offs), 0))
    operands = (q, k, v) + state
    in_specs = [qblk, kvblk, kvblk] + [rowblk] * len(state)
    aliases = {}
    # O in place where this call's query tiles are tiles of O: O is an
    # aliased operand nobody fetches (every tile written is written whole),
    # the output's index map walks the call's tiles of it, and the rows of
    # other calls are never touched
    in_place = o is not None and o_row0 % bq == 0 and not pad
    if in_place:
        tile0 = o_row0 // bq
        operands += (o,)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {len(operands): 0}  # operand 0 is the positions
        out_specs = [pl.BlockSpec(
            (1, bq, d), lambda i, j, t, offs: (i, tile0 + j, 0))]
        out_shape = [out_struct(o.shape, o.dtype, *operands)]
    elif finish:
        out_specs = [rowblk]
        out_shape = [out_struct(
            (h, np_, d), q.dtype if o is None else o.dtype, *operands)]
    else:
        out_specs = [rowblk] * 3
        out_shape = [out_struct((h, np_, d), jnp.float32, *operands)] * 3
    positions = jnp.asarray((q_pos, k_pos) + segments, jnp.int32)
    if tok is not None:
        # the caller's ordering token, an int32 zero: the kernel waits for
        # its scalars, so for the token, and no operand gets an add
        positions = positions + tok
    outs = pl.pallas_call(
        functools.partial(_flash_kernel, plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # kv innermost and strictly sequential: the VMEM scratch state
            # carries across the kv steps of one (head, q-tile)
            grid=(h, np_ // bq, plan.steps),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)] * 3,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        name=name,
        interpret=interpret,
    )(positions, *operands)
    if in_place:
        return outs[0]
    if pad:
        outs = [t[:, :n] for t in outs]
    if not finish:
        return tuple(outs)
    if o is None:
        return outs[0]
    return jax.lax.dynamic_update_slice_in_dim(o, outs[0], o_row0, 1)


_STATIC = ("scale", "bkv", "q_pos", "k_pos", "causal", "window", "interpret",
           "segments")


@functools.partial(jax.jit, static_argnames=_STATIC)
def attn_block_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    acc: Optional[jax.Array],
    m: Optional[jax.Array],
    l: Optional[jax.Array],
    scale: float,
    *,
    bkv: int = KV_TILE,
    q_pos: int = 0,
    k_pos: int = 0,
    causal: bool = False,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
    segments: Optional[Tuple[int, ...]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one K/V block into the online-softmax state; returns (acc', m', l').

    Shapes: q (h, n, d); k/v (hkv, nkv, d), h a multiple of hkv; acc/m/l
    (h, n, d) float32 with m/l broadcast along the last axis, or all three
    ``None`` to start from the empty state.  ``q_pos``/``k_pos``: the
    positions of q's and k's first rows, for the mask.  A block of more than
    ``bkv`` rows is walked ``bkv`` at a time.  ``segments``: the first rows
    of packed prompts, ascending, in the positions' coordinates.
    """
    return _flash("attn_fold", q, k, v, acc, m, l, scale, Q_TILE, bkv, q_pos,
                  k_pos, causal, window, interpret, segments=segments)


@functools.partial(jax.jit, static_argnames=_STATIC + (
    "finish", "o_row0", "q_row0", "rows", "k_row0", "keys"))
def attn_fused_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    acc: Optional[jax.Array],
    m: Optional[jax.Array],
    l: Optional[jax.Array],
    scale: float,
    bkv: int = KV_TILE,
    *,
    q_pos: int = 0,
    k_pos: int = 0,
    causal: bool = False,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
    finish: bool = False,
    o: Optional[jax.Array] = None,
    o_row0: int = 0,
    q_row0: int = 0,
    rows: Optional[int] = None,
    k_row0: int = 0,
    keys: Optional[int] = None,
    tok: Optional[jax.Array] = None,
    segments: Optional[Tuple[int, ...]] = None,
):
    """Fold a whole K/V range into the online-softmax state in ONE kernel —
    the fused alternative to chaining :func:`attn_block_pallas` per block.

    Why it exists (measured, r5): at b=4, n=8k, d=128 the chained version
    moves the (b, n, d) f32 state acc/m/l through HBM twice per block —
    8 blocks x 6 x 16.8 MB ~= 0.8 GB per iteration, ~1.2 ms at v5e peak —
    so the chain is HBM-state-bound at 66.5% MFU while the roofline says
    compute-bound.  Keeping the state in VMEM scratch across the kv grid
    dimension (strictly sequential, pinned "arbitrary") cuts state traffic
    to one read + one write per q-tile (one write with ``acc=None``, none
    with ``finish``).

    Shapes as :func:`attn_block_pallas`, with nkv % bkv == 0.  Under a mask
    each q-tile walks only the K/V tiles that hold a key it can see, in the
    last of the grid's K/V steps (:func:`walk_step`).

    ``q_row0``/``rows`` and ``k_row0``/``keys``: the call folds rows
    ``q_row0 .. q_row0 + rows`` of ``q`` against keys ``k_row0 .. k_row0 +
    keys`` of ``k``/``v`` (to the end without a count), read from the
    operands as they lie where the ranges are whole tiles of them and sliced
    out first where not (the module's head); ``n`` and ``nkv`` above are
    then the ranges', as are ``q_pos``/``k_pos`` the positions of their
    first rows, and a handed state has ``rows`` rows.  ``tok``: an int32
    zero that carries the caller's ordering token onto the prefetched
    positions.

    Returns ``(acc', m', l')``; with ``finish=True`` (and ``acc``, ``m``,
    ``l`` ``None``) O instead: ``(acc' / l')`` as fresh ``(h, n, d)`` rows
    in q's dtype, or, handed ``o`` ``(h, N, d)``, ``o`` with rows ``o_row0
    .. o_row0 + n`` written and every other row as it came (the module's
    head says how).  Every row has to see a key in the range.
    """
    return _flash("attn_fused", q, k, v, acc, m, l, scale, Q_TILE, bkv, q_pos,
                  k_pos, causal, window, interpret, finish, o, o_row0, q_row0,
                  rows, k_row0, keys, tok, segments)


# -- a paged latent cache: one decode step ---------------------------------------


def paged_tiles(lens, page: int, k_pos: int = 0, span: Optional[int] = None):
    """Per sequence, the tiles of the key range ``k_pos .. k_pos + span``
    (to the end without ``span``) that hold a visible key, ``lens`` visible
    keys each: what a call's grid walks (its extent is their sum) and what
    the program's ``mla.*`` counters count."""
    first = k_pos // page
    out = []
    for n in lens:
        last = -(-int(n) // page)  # one past the sequence's open tile
        if span is not None:
            last = min(last, first + span // page)
        out.append(max(0, last - first))
    return out


def paged_step(tiles, s, fields: int = 3):
    """``[sequence, its first step, its steps][:fields]`` of step ``s`` of
    the grid that walks ``tiles[i]`` (:func:`paged_tiles`) tiles of sequence
    i, sequence after sequence (a sequence with none has no step): the step
    is on tile ``s - first step`` of the sequence's in the range.  The
    sequences' first steps are immediates: a comparison and ``fields``
    selects a sequence after the first, on the scalar core, in the kernel
    and in each of its index maps."""
    rows = [(i, sum(tiles[:i]), n) for i, n in enumerate(tiles) if n]
    got = [np.int32(v) for v in rows[0][:fields]]
    for row in rows[1:]:
        past = jax.lax.ge(s, np.int32(row[1]))
        got = [jax.lax.select(past, np.int32(v), g)
               for v, g in zip(row, got)]
    return got


def _paged_maps(tiles, lead0: int, tile0: int, page: int, max_pages: int):
    """``(seq, sealed)``: the index maps of the paged walk's operands, for
    the step's sequence (Q, the open page, O, a state) and for the tile of
    the sealed pool it is on."""

    def seq(s, *_):
        return (lead0 + paged_step(tiles, s, 1)[0], 0, 0)

    def sealed(s, lens, table):
        # the sealed tile of this step, held at the sequence's last sealed
        # page on its open page's step (which fetches nothing new of the
        # pool: the next sequence's first page arrives behind that fold)
        i, start = paged_step(tiles, s, 2)
        b = lead0 + i
        last = (lens[b] - 1) // page - 1
        tile = jnp.clip(jnp.minimum(tile0 + (s - start), last), 0,
                        max_pages - 1)
        return (table[b, tile], 0, 0)

    return seq, sealed


def _flash_paged(name, q, pool, k_open, lens, table, state, scale, v_dim,
                 lead0, k_pos, tiles, o, interpret):
    """The kernel body over a paged cache.  ``q`` ``(B, n, d)``: the n rows
    of every sequence (heads: they sit at one position); ``pool`` ``(pages,
    d, page)`` sealed pages and ``k_open`` ``(B, d, page)`` each sequence's
    open page, their keys as columns; ``lens`` ``(B,)`` visible keys;
    ``table`` ``(B, max_pages)``: tile j of sequence b is ``pool[table[b,
    j]]`` while ``j < (lens[b] - 1) // page`` and ``k_open[b]`` at that
    tile, where the last visible key lies.  The call covers ``len(tiles)``
    sequences from ``lead0``, sequence i over its ``tiles[i]`` tiles from
    key ``k_pos`` (static: ``paged_tiles`` of the lengths ``lens`` holds),
    and its grid is those ``sum(tiles)`` steps in order (:func:`paged_step`):
    no step but folds a page.  A handed state is the output, aliased: a
    sequence with no tile here is never fetched and keeps it.  V^T is a
    tile's first ``v_dim`` rows."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n, d = q.shape
    pages, _, page = pool.shape
    if k_pos % page:
        raise ValueError(f"a key range starts at a page: {k_pos} % {page}")
    if n > Q_TILE:
        raise ValueError(f"{n} rows a sequence: one query tile holds {Q_TILE}")
    finish = o is not None
    init = state is None
    if finish and not init:
        raise ValueError("a call that finishes its rows opens their state: "
                         "it is handed no acc, m and l")
    if not any(tiles) or (init and not all(tiles)):
        raise ValueError(f"tiles {tiles} from key {k_pos}: a call that opens "
                         "the state gives every sequence a step, and every "
                         "call some sequence")
    rows, tile0 = len(tiles), k_pos // page
    plan = _Plan(float(scale), n, page, pages, sum(tiles), False, None,
                 init, finish, dv=int(v_dim), paged=True, lead0=int(lead0),
                 tiles=tiles, tile0=tile0)
    seq, sealed = _paged_maps(tiles, lead0, tile0, page, table.shape[1])

    stblk = pl.BlockSpec((1, n, v_dim),
                         lambda s, *_: (paged_step(tiles, s, 1)[0], 0, 0))
    operands = (q, pool, k_open) + (() if init else tuple(state))
    in_specs = [pl.BlockSpec((1, n, d), seq),
                pl.BlockSpec((1, d, page), sealed),
                pl.BlockSpec((1, d, page), seq)] + [stblk] * (
                    0 if init else 3)
    scalars = 2  # the limits and the table lead the operands
    if finish:
        # O in place, as attn_fused's: aliased, unfetched, the rows of the
        # other sequences never touched
        operands += (o,)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases = {scalars + len(operands) - 1: 0}
        out_specs = [pl.BlockSpec((1, n, v_dim), seq)]
        out_shape = [out_struct(o.shape, o.dtype, *operands)]
    else:
        # a handed state in place too: a sequence the walk never visits
        # (no tile in this range) keeps its rows, unread and unwritten
        aliases = {} if init else {scalars + 3 + i: i for i in range(3)}
        out_specs = [stblk] * 3
        out_shape = [out_struct((rows, n, v_dim), jnp.float32,
                                *operands)] * 3
    # two K operands double-buffered, the scores and P of a tile beside them
    tile_bytes = page * d * pool.dtype.itemsize
    vmem = min(100 << 20, max(32 << 20, 8 * tile_bytes))
    outs = pl.pallas_call(
        functools.partial(_flash_kernel, plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=scalars,
            # strictly sequential: the VMEM scratch state carries across a
            # sequence's steps
            grid=(plan.steps,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, v_dim), jnp.float32)] * 3,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem,
        ),
        name=name,
        interpret=interpret,
    )(lens.astype(jnp.int32), table.astype(jnp.int32), *operands)
    return outs[0] if finish else tuple(outs)


_PAGED_STATIC = ("scale", "v_dim", "lead0", "tiles", "interpret")


@functools.partial(jax.jit, static_argnames=_PAGED_STATIC)
def mla_decode_pallas(q, pool, k_open, lens, table, o, scale, *, v_dim: int,
                      lead0: int, tiles: Tuple[int, ...],
                      interpret: Optional[bool] = None):
    """One decode step's cache read for ``len(tiles)`` sequences from
    ``lead0``, each over its whole cache, in ONE kernel (``mla_decode``):
    state in VMEM, ``o`` ``(B, n, v_dim)`` returned with those sequences'
    rows written (float32 ``acc / l``, rounded once) and every other row as
    it came.  Operands as :func:`_flash_paged`; ``tiles`` the tiles each of
    the sequences has (:func:`paged_tiles` of its visible keys): the grid is
    their sum, one step a page."""
    return _flash_paged("mla_decode", q, pool, k_open, lens, table, None,
                        scale, v_dim, lead0, 0, tuple(tiles), o, interpret)


@functools.partial(jax.jit, static_argnames=_PAGED_STATIC + ("k_pos",))
def mla_fold_pallas(q, pool, k_open, lens, table, acc, m, l, scale, *,
                    v_dim: int, lead0: int, k_pos: int,
                    tiles: Tuple[int, ...],
                    interpret: Optional[bool] = None):
    """One link of a split-K chain (``mla_fold``): of each of ``len(tiles)``
    sequences from ``lead0`` the ``tiles[i]`` tiles from ``k_pos // page``
    (:func:`paged_tiles` of the link's key range) folded into its softmax
    state ``(len(tiles), n, v_dim)`` float32 through HBM (``None``: from the
    empty state, every sequence with a tile).  The grid is the sum of
    ``tiles``; a sequence with none has no step, and its rows of the handed
    state, which is the output in place, come back as they were.  Returns
    ``(acc', m', l')``."""
    state = None if acc is None else (acc, m, l)
    return _flash_paged("mla_fold", q, pool, k_open, lens, table, state,
                        scale, v_dim, lead0, k_pos, tuple(tiles), None,
                        interpret)


# -- a paged index-key cache: the scores a sparse selection is made from ----------


def _index_kernel(plan: _Plan, lens, table, q_ref, w_ref, k_ref, ko_ref, _,
                  o_ref):
    """One (sequence, page) step of the paged walk, with no softmax and no
    state: the page's index scores ``sum_h w_h relu(q_h . k_j)``, products
    and sums in float32, keys past the sequence's limit written as
    ``NEG``."""
    step = pl.program_id(0)
    seq, start = paged_step(plan.tiles, step, 2)
    limit = lens[plan.lead0 + seq]
    tile = plan.tile0 + (step - start)
    open_tile = (limit - 1) // plan.bkv

    def score(k_ref, edge: bool):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (heads, page)
        row = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0][:, :1], axis=0,
                      keepdims=True)
        if edge:
            seen = tile * plan.bkv + jax.lax.broadcasted_iota(
                jnp.int32, row.shape, 1) < limit
            row = jnp.where(seen, row, NEG)
        o_ref[0] = row

    pl.when(tile < open_tile)(lambda: score(k_ref, False))
    pl.when(tile == open_tile)(lambda: score(ko_ref, True))


@functools.partial(jax.jit, static_argnames=("lead0", "tiles", "interpret"))
def dsa_index_pallas(q, w, pool, k_open, lens, table, scores, *, lead0: int,
                     tiles: Tuple[int, ...],
                     interpret: Optional[bool] = None):
    """The lightning indexer's scores of ``len(tiles)`` sequences from
    ``lead0`` over their whole index-key cache, in ONE kernel
    (``dsa_index``) on the paged walk of :func:`_flash_paged`: ``scores[b,
    0, j] = sum_h w[b, h] relu(q[b, h] . KI[b, j])`` for the keys of the
    ``tiles[i]`` pages sequence ``lead0 + i`` has (:func:`paged_tiles` of
    its visible keys), ``NEG`` past its limit inside its open page.

    ``q`` ``(B, heads, d)``; ``w`` ``(B, heads)`` float32; ``pool``
    ``(pages, d, page)`` sealed pages of index keys and ``k_open`` ``(B, d,
    page)`` the open ones, keys as columns; ``lens`` ``(B,)`` visible keys,
    ``table`` ``(B, max_pages)``; ``scores`` ``(B, 1, max_pages * page)``
    float32, returned with those sequences' pages written and everything
    else as it came (aliased, never fetched: a page no step visits keeps
    what it held, so a reader masks by the lengths too).  The grid is the
    sum of ``tiles``: one step a page, no step that scores none."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tiles = tuple(tiles)
    _, heads, d = q.shape
    pages, _, page = pool.shape
    if not all(tiles):
        raise ValueError(f"tiles {tiles}: every sequence of the call has a "
                         "visible key, so a page")
    if scores.shape != (q.shape[0], 1, table.shape[1] * page):
        raise ValueError(f"scores {scores.shape}: one row a sequence over "
                         f"{table.shape[1]} pages of {page}")
    plan = _Plan(1.0, heads, page, pages, sum(tiles), False, None, True,
                 paged=True, lead0=int(lead0), tiles=tiles)
    seq, sealed = _paged_maps(tiles, lead0, 0, page, table.shape[1])

    def out(s, *_):
        i, start = paged_step(tiles, s, 2)
        return (lead0 + i, 0, s - start)

    # a head's weight along a row of lanes: the kernel takes its column
    wide = jnp.broadcast_to(w.astype(jnp.float32)[:, :, None],
                            w.shape + (128,))
    operands = (q, wide, pool, k_open, scores)
    scalars = 2
    return pl.pallas_call(
        functools.partial(_index_kernel, plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=scalars,
            grid=(plan.steps,),
            in_specs=[pl.BlockSpec((1, heads, d), seq),
                      pl.BlockSpec((1, heads, 128), seq),
                      pl.BlockSpec((1, d, page), sealed),
                      pl.BlockSpec((1, d, page), seq),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, page), out),
        ),
        out_shape=out_struct(scores.shape, scores.dtype, *operands),
        input_output_aliases={scalars + len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dsa_index",
        interpret=interpret,
    )(lens.astype(jnp.int32), table.astype(jnp.int32), *operands)


# -- selected latent rows: attention over a gathered tile of rows --------------------


def open_span(sel, lens, page: int):
    """``(2, rows)`` int32: per sequence the slots ``first .. last + 1`` of
    ``sel`` ``(rows, k)`` whose position lies in the sequence's open page
    (the page of its last visible key, ``lens`` visible keys; ``0, 0`` where
    none does).  An ascending selection's open-page slots are exactly that
    run; in any other order the run holds them all."""
    is_open = sel // page == ((lens - 1) // page)[:, None]
    slots = jnp.arange(sel.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(is_open, slots, sel.shape[1]), axis=1)
    last = jnp.max(jnp.where(is_open, slots + 1, 0), axis=1)
    return jnp.stack([jnp.minimum(first, last), last]).astype(jnp.int32)


def _rows_kernel(scale: float, dv: int, lead0: int, page: int, lens, picked,
                 span, sel, q_ref, rows_ref, ko_ref, _, o_ref, rows_s, q_s):
    """One sequence a step: its ``k`` gathered rows ``(k, row)`` into
    scratch, the slots whose position lies in its open page overwritten
    with that page's rows (the gather read the sealed pool for every
    slot), then one softmax over the ``picked`` first slots and ``O = P .
    rows[:, :dv]``: ``mla_decode``'s roundings at ``mla_decode``'s places
    (float32 scores and sums, P in the cache's dtype, ``acc / l`` rounded
    once), with no running state: every key is in VMEM at once."""
    i = pl.program_id(0)
    b = lead0 + i
    d = q_ref.shape[2]

    @pl.when(i == 0)
    def _():
        # Q padded to a row's width: the tail of a row is zero too
        q_s[...] = jnp.zeros_like(q_s)

    q_s[:, :d] = q_ref[0]
    # tokens a 32-bit sublane row holds: a 16-bit token is half of one, and
    # is moved as a word, by shifts (the scratch and the open page as words)
    packed = 4 // rows_ref.dtype.itemsize
    if packed == 1:
        rows_s[...], src = rows_ref[0], ko_ref
    else:
        rows_s[...] = pltpu.bitcast(rows_ref[0], jnp.uint32)
        src = ko_ref.bitcast(jnp.uint32)
        bits = 32 // packed
        ones = jnp.uint32((1 << bits) - 1)
    open_slot = (lens[b] - 1) // page

    def patch(j, carry):
        at = sel[b, j]

        @pl.when(at // page == open_slot)
        def _():
            r = at % page
            word = src[0, pl.ds(r // packed, 1), :]
            if packed > 1:
                half = (word >> (bits * (r % packed)).astype(jnp.uint32)
                        ) & ones
                up = (bits * (j % packed)).astype(jnp.uint32)
                word = (rows_s[pl.ds(j // packed, 1), :] & ~(ones << up)) | (
                    half << up)
            rows_s[pl.ds(j // packed, 1), :] = word

        return carry

    jax.lax.fori_loop(span[0, i], span[1, i], patch, 0)

    rows = rows_s[...]
    if packed > 1:
        rows = pltpu.bitcast(rows, rows_ref.dtype)
    s = jax.lax.dot_general(q_s[...], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    seen = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < picked[b]
    s = jnp.where(seen, s, NEG)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    acc = jnp.dot(p.astype(rows.dtype), rows[:, :dv],
                  preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "v_dim", "lead0",
                                             "interpret"))
def mla_decode_rows_pallas(q, rows, k_open, sel, lens, picked, o, scale, *,
                           v_dim: int, lead0: int,
                           interpret: Optional[bool] = None):
    """Latent attention of ``rows.shape[0]`` sequences from ``lead0`` over
    their selected tokens alone, in ONE kernel (``mla_decode_rows``): a
    step a sequence, its ``k`` rows resident, one softmax, ``o`` ``(B, n,
    v_dim)`` returned with those sequences' rows written and every other
    row as it came (aliased, never fetched).

    ``q`` ``(B, n, d)``; ``rows`` ``(R, k, row)``, ``row >= d`` whole lanes
    and a token's tail zero: slot j of sequence ``lead0 + i`` holds the
    sealed pool's row for position ``sel[lead0 + i, j]`` (read through the
    table, whatever the position: :func:`~tenzing_tpu.models.
    sparse_attention.sealed_rows`); ``k_open`` ``(B, page, row)`` the open
    pages, a token a row: a slot whose position lies in the sequence's open
    page (``lens`` ``(B,)`` visible keys) takes its row from there, inside
    the kernel, so the rows arrive by one gather and not two.  ``sel``
    ``(B, k)`` int32 reaches the scalar core whole; ``picked`` ``(B,)`` the
    key limits: slots from ``picked[b]`` on are left out.  Keys stay rows,
    ``(k, row)``: the first product contracts the minor axis of both
    operands, the second is plain, and nothing is transposed."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, n, d = q.shape
    count, k, row = rows.shape
    page = k_open.shape[1]
    if row < d or k_open.shape[2] != row:
        raise ValueError(f"rows of {row} and open pages of "
                         f"{k_open.shape[2]} for queries of {d}")
    packed = 4 // rows.dtype.itemsize
    if k % packed or page % packed:
        raise ValueError(f"{k} keys of pages of {page}: {packed} tokens to "
                         "a 32-bit row")
    at = slice(lead0, lead0 + count)
    span = open_span(sel[at], lens[at], page)

    def seq(i, *_):
        return (lead0 + i, 0, 0)

    operands = (q, rows, k_open, o)
    scalars = 4  # the limits, the open slots and the positions lead
    return pl.pallas_call(
        functools.partial(_rows_kernel, float(scale), int(v_dim), int(lead0),
                          page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=scalars,
            grid=(count,),
            in_specs=[pl.BlockSpec((1, n, d), seq),
                      pl.BlockSpec((1, k, row), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec((1, page, row), seq),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n, v_dim), seq),
            scratch_shapes=[
                pltpu.VMEM((k, row), rows.dtype) if packed == 1
                else pltpu.VMEM((k // packed, row), jnp.uint32),
                pltpu.VMEM((n, row), q.dtype)],
        ),
        out_shape=out_struct(o.shape, o.dtype, *operands),
        input_output_aliases={scalars + len(operands) - 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        name="mla_decode_rows",
        interpret=interpret,
    )(lens.astype(jnp.int32), picked.astype(jnp.int32), span,
      sel.astype(jnp.int32), *operands)
