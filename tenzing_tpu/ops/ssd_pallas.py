"""The chunked selective-state scan of Mamba-2 (state-space duality,
arXiv:2405.21060) over packed prompts: one Pallas kernel (``ssd_scan``) that
walks a head group's chunks in order with the running state in VMEM, and
the same four steps as plain ``jax.numpy`` for the XLA chain.

Per head ``h`` (``P`` channels, ``N`` state columns; group ``g = h // (H /
G)`` hands it ``B`` and ``C``), token ``t`` of a prompt::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,   S = 0 at the start
    y_t = S_t C_t + D x_t

In chunks of ``Q`` tokens, with ``a_i = dt_i A`` and ``cs`` its running sum
inside the chunk (across a prompt boundary too: only differences between
tokens of one prompt are ever used, and they do not see the boundary):

1. *diagonal blocks*: ``y_diag = ((C B^T) . L) (dt . x)``, ``L[i, j] =
   exp(cs_i - cs_j)`` where ``j <= i`` and both are of one prompt, else 0;
2. *chunk states*: ``S_loc = sum_j exp(cs_last - cs_j) dt_j x_j (outer)
   B_j`` over the tokens of the chunk's last prompt, and the chunk's decay
   ``exp(cs_last)`` where the prompt that came in is still the last (no
   boundary inside), else 0;
3. *the scan across chunks*: ``S_in[c+1] = decay[c] S_in[c] + S_loc[c]``;
4. *the states' part*: ``y_off_i = exp(cs_i) C_i S_in`` for the tokens of
   the prompt that came in with ``S_in``, else 0.

A prompt's final state is taken in the chunk that holds its last token
``e``: ``exp(cs_e) S_in`` (if it came in) ``+ sum_j exp(cs_e - cs_j) dt_j x_j
(outer) B_j`` over its tokens of the chunk.  Every exponent is a difference
of running sums and at most 0: no ratio of two exponentials.  Products are
in the operands' dtype with float32 accumulation; decays, masks and the
state float32.

**The kernel.**  Grid ``(groups, chunks)``, the chunk axis in order.  A step
holds the group's ``H / G`` heads: ``x`` ``(Q, H/G . P)``, ``B`` and ``C``
``(Q, N)`` fetched once for all of them (three index maps into the one
convolved ``[x | B | C]`` array: nothing is sliced out in HBM), the running
sums and ``dt`` as rows ``(H/G, Q)`` (one transpose a step gives them as
columns too, with the tokens' prompt ids: ``kda_step``'s trick).  The state
is kept transposed, ``(N, H/G . P)`` float32 in VMEM scratch, so that with
``B^T`` (one more transpose a step) every product is a plain one: ``C B^T``,
``(C B^T . L_h) (dt x)``, ``C S^T`` and ``B^T (w dt x)``.  Heads are walked
in lane tiles of ``128 / P`` heads (two at ``P`` = 64): ``x``, ``y`` and the
state are then whole 128-lane tiles, and a head's product over the tile is
taken whole and selected by lane (on a 128-wide matrix unit a 64-wide
product costs the same).  Prompt ids, the chunks' last ids and the prompts'
last tokens are operands, not immediates: another packing of the same token
count runs the same program.  The final states leave as one resident block a
group, ``(prompts, N, H/G . P)``, written by the steps that hold a prompt's
last token: 1.5 MB at six prompts; many more would want a smaller head
group.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tenzing_tpu.ops.common import out_struct

NEG = -1e30  # a masked exponent: exp gives 0, no inf times 0


def chunk_sums(dt, a, seg, ends, chunk: int):
    """What every form shares, from ``dt`` ``(T, H)`` float32 (after the
    softplus), ``a`` ``(H,)`` negative, ``seg`` ``(T,)`` prompt ids 0, 1, ...
    and ``ends`` ``(prompts,)`` last tokens: ``(dt, cs, seg, last, carry,
    end_chunk, end_off)`` with the tokens padded to whole chunks (``dt`` 0
    and the id ``prompts`` there): ``cs`` the running sum of ``dt a`` inside
    each chunk, ``last[c]`` the id of chunk c's last token and ``carry[c]``
    that of the token before its first (-1 for chunk 0)."""
    t, prompts = dt.shape[0], ends.shape[0]
    pad = -t % chunk
    dt = jnp.pad(dt.astype(jnp.float32), ((0, pad), (0, 0)))
    seg = jnp.pad(seg.astype(jnp.int32), (0, pad), constant_values=prompts)
    nc = (t + pad) // chunk
    cs = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(nc, chunk, -1),
                    axis=1).reshape(t + pad, -1)
    last = seg.reshape(nc, chunk)[:, -1]
    carry = jnp.concatenate([jnp.full((1,), -1, jnp.int32), last[:-1]])
    ends = ends.astype(jnp.int32)
    return dt, cs, seg, last, carry, ends // chunk, ends % chunk


def _split(xc, heads: int, head_dim: int, groups: int, state: int, nc: int,
           chunk: int):
    """``(x (nc, Q, H, P), B (nc, Q, G, N), C)`` of the convolved ``[x | B |
    C]`` rows ``(T, .)``, padded to whole chunks."""
    inner, gn = heads * head_dim, groups * state
    xc = jnp.pad(xc, ((0, nc * chunk - xc.shape[0]), (0, 0)))
    return (xc[:, :inner].reshape(nc, chunk, heads, head_dim),
            xc[:, inner:inner + gn].reshape(nc, chunk, groups, state),
            xc[:, inner + gn:].reshape(nc, chunk, groups, state))


def _by_chunk(v, nc: int, chunk: int):
    return v.reshape((nc, chunk) + v.shape[1:])


def ssd_diag(xc, dt, a, seg, ends, *, heads: int, head_dim: int, groups: int,
             state: int, chunk: int):
    """Step 1: ``y_diag`` ``(T padded, H P)`` float32.  The ``(H, chunks, Q,
    Q)`` decays are a product's operand: XLA passes them through HBM."""
    dt, cs, seg, *_ = chunk_sums(dt, a, seg, ends, chunk)
    nc = dt.shape[0] // chunk
    x, b, c = _split(xc, heads, head_dim, groups, state, nc, chunk)
    cs, dt, seg = (_by_chunk(v, nc, chunk) for v in (cs, dt, seg))
    i = jnp.arange(chunk)
    ok = (i[None, :] <= i[:, None])[None] & (
        seg[:, :, None] == seg[:, None, :])                     # (nc, Q, Q)
    decays = jnp.exp(jnp.where(
        ok[:, None], cs.transpose(0, 2, 1)[:, :, :, None]
        - cs.transpose(0, 2, 1)[:, :, None, :], NEG))          # (nc, H, Q, Q)
    cb = jnp.einsum("cign,cjgn->cgij", c, b,
                    preferred_element_type=jnp.float32)
    g = (jnp.repeat(cb, heads // groups, axis=1) * decays).astype(xc.dtype)
    dtx = (x.astype(jnp.float32) * dt[..., None]).astype(xc.dtype)
    y = jnp.einsum("chij,cjhp->cihp", g, dtx,
                   preferred_element_type=jnp.float32)
    return y.reshape(nc * chunk, heads * head_dim)


def _grown(x, b, dt, cs, at, mask, heads: int, groups: int):
    """``sum_j mask_j exp(at - cs_j) dt_j x_j (outer) B_j`` a chunk:
    ``(n, H, P, N)`` float32 for ``x`` ``(n, Q, H, P)``, ``b`` ``(n, Q, G,
    N)``, ``at`` ``(n, H)``, ``mask`` ``(n, Q)``."""
    w = jnp.exp(jnp.where(mask[:, :, None], at[:, None, :] - cs, NEG))
    wx = (x.astype(jnp.float32) * (w * dt)[..., None]).astype(x.dtype)
    n, q, _, p = x.shape
    wx = wx.reshape(n, q, groups, heads // groups, p)
    s = jnp.einsum("cjgrp,cjgn->cgrpn", wx, b,
                   preferred_element_type=jnp.float32)
    return s.reshape(n, heads, p, b.shape[-1])


def ssd_chunk_states(xc, dt, a, seg, ends, *, heads: int, head_dim: int,
                     groups: int, state: int, chunk: int):
    """Step 2: ``(S_loc (nc, H, P, N), decay (nc, H), fin_loc (prompts, H, P,
    N), fin_keep (prompts, H))``, all float32: what each chunk adds to the
    state of its last prompt and how much of the incoming state it keeps;
    the same for each prompt at its last token."""
    dt, cs, seg, last, carry, end_chunk, end_off = chunk_sums(
        dt, a, seg, ends, chunk)
    nc = dt.shape[0] // chunk
    x, b, _ = _split(xc, heads, head_dim, groups, state, nc, chunk)
    cs, dt, seg = (_by_chunk(v, nc, chunk) for v in (cs, dt, seg))
    cs_last = cs[:, -1]
    loc = _grown(x, b, dt, cs, cs_last, seg == last[:, None], heads, groups)
    decay = jnp.where((last == carry)[:, None], jnp.exp(cs_last), 0.0)
    prompts = jnp.arange(ends.shape[0], dtype=jnp.int32)
    cs_e = cs[end_chunk, end_off]                              # (prompts, H)
    fin_loc = _grown(x[end_chunk], b[end_chunk], dt[end_chunk], cs[end_chunk],
                     cs_e, seg[end_chunk] == prompts[:, None], heads, groups)
    fin_keep = jnp.where((carry[end_chunk] == prompts)[:, None],
                         jnp.exp(cs_e), 0.0)
    return loc, decay, fin_loc, fin_keep


def ssd_state_scan(loc, decay):
    """Step 3: the state that enters each chunk, ``(nc, H, P, N)``."""
    def step(s, row):
        loc_c, decay_c = row
        return decay_c[:, None, None] * s + loc_c, s

    return lax.scan(step, jnp.zeros_like(loc[0]), (loc, decay))[1]


def ssd_out(y_diag, s_in, fin_loc, fin_keep, xc, dt, a, d_skip, seg, ends, *,
            heads: int, head_dim: int, groups: int, state: int, chunk: int):
    """Step 4: ``(y (T, H P) in ``xc``'s dtype, S_final (prompts, H, P,
    N))``: the incoming states' part of the output added to the diagonal
    blocks' and the skip; each prompt's final state."""
    t = xc.shape[0]
    dt, cs, seg, _, carry, end_chunk, _ = chunk_sums(dt, a, seg, ends, chunk)
    nc = dt.shape[0] // chunk
    x, _, c = _split(xc, heads, head_dim, groups, state, nc, chunk)
    cs, seg = _by_chunk(cs, nc, chunk), _by_chunk(seg, nc, chunk)
    p = head_dim
    s = s_in.astype(xc.dtype).reshape(nc, groups, heads // groups, p, state)
    off = jnp.einsum("cign,cgrpn->cigrp", c, s,
                     preferred_element_type=jnp.float32).reshape(
                         nc, chunk, heads, p)
    came_in = (seg == carry[:, None])[:, :, None]
    off = off * jnp.where(came_in, jnp.exp(cs), 0.0)[..., None]
    y = (y_diag.reshape(nc, chunk, heads, p) + off
         + d_skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32))
    final = fin_keep[:, :, None, None] * s_in[end_chunk] + fin_loc
    return y.reshape(nc * chunk, heads * p)[:t].astype(xc.dtype), final


def ssd_chain(xc, dt, a, d_skip, seg, ends, **dims):
    """The four steps in a row (tests; the graph runs them as vertices)."""
    loc, decay, fin_loc, fin_keep = ssd_chunk_states(xc, dt, a, seg, ends,
                                                     **dims)
    return ssd_out(ssd_diag(xc, dt, a, seg, ends, **dims),
                   ssd_state_scan(loc, decay), fin_loc, fin_keep, xc, dt, a,
                   d_skip, seg, ends, **dims)


# -- the kernel -----------------------------------------------------------------


def _ssd_kernel(dims, last_ref, end_chunk_ref, end_off_ref, x_ref, b_ref,
                c_ref, cs_ref, dt_ref, seg_ref, d_ref, y_ref, fin_ref, st):
    q, p, hg, hp, prompts = dims
    f32 = jnp.float32
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    last = last_ref[c]
    carry = jnp.where(c > 0, last_ref[jnp.maximum(c - 1, 0)], -1)
    cs_rows, dt_rows = cs_ref[...], dt_ref[...]                  # (hg, Q)
    seg_row = seg_ref[...].astype(f32)                           # (1, Q)
    rows = jnp.concatenate([cs_rows, dt_rows, seg_row], axis=0)
    if rows.shape[0] < q:
        rows = jnp.concatenate(
            [rows, jnp.zeros((q - rows.shape[0], q), f32)], axis=0)
    cols = rows.T           # column h: cs of head h; hg + h: dt; 2 hg: ids
    seg_col = cols[:, 2 * hg:2 * hg + 1]                         # (Q, 1)
    i_ = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j_ = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    pair_ok = (j_ <= i_) & (seg_col == seg_row)
    came_in = seg_col == carry.astype(f32)
    of_last = seg_col == last.astype(f32)
    b, c_ = b_ref[...], c_ref[...]
    bt = b.astype(f32).T.astype(b.dtype)                         # (N, Q)
    cb = jnp.dot(c_, bt, preferred_element_type=f32)             # (Q, Q)
    width = hp * p
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    for k in range(hg // hp):
        lanes = slice(k * width, (k + 1) * width)
        heads = [k * hp + i for i in range(hp)]
        mine = [(lane >= i * p) & (lane < (i + 1) * p) for i in range(hp)]

        def spread(at):
            """Head i's column of ``cols`` over head i's lanes."""
            out = jnp.broadcast_to(cols[:, at + heads[0]:at + heads[0] + 1],
                                   (q, width))
            for i in range(1, hp):
                out = jnp.where(
                    mine[i], cols[:, at + heads[i]:at + heads[i] + 1], out)
            return out

        cs_t, dt_t = spread(0), spread(hg)
        xp = x_ref[:, lanes].astype(f32)
        dtx = xp * dt_t
        dtx_lo = dtx.astype(b.dtype)
        y = None
        for i, h in enumerate(heads):
            decays = jnp.exp(jnp.where(
                pair_ok, cols[:, h:h + 1] - cs_rows[h:h + 1, :], NEG))
            y_h = jnp.dot((cb * decays).astype(b.dtype), dtx_lo,
                          preferred_element_type=f32)
            y = y_h if y is None else jnp.where(mine[i], y_h, y)
        s_in = st[:, lanes]                                      # (N, width)
        y = y + jnp.where(came_in, jnp.exp(cs_t), 0.0) * jnp.dot(
            c_, s_in.astype(b.dtype), preferred_element_type=f32)
        y_ref[:, lanes] = (y + d_ref[:, lanes] * xp).astype(y_ref.dtype)

        def grown(at, mask):
            w = jnp.exp(jnp.where(mask, at - cs_t, NEG))
            return jnp.dot(bt, (w * dtx).astype(b.dtype),
                           preferred_element_type=f32)

        for n in range(prompts):
            @pl.when(end_chunk_ref[n] == c)
            def _(n=n):
                cs_e = jnp.sum(jnp.where(row == end_off_ref[n], cs_t, 0.0),
                               axis=0, keepdims=True)
                keep = jnp.where(carry == n, jnp.exp(cs_e), 0.0)
                fin_ref[n, :, lanes] = keep * s_in + grown(
                    cs_e, seg_col == float(n))

        cs_last = cs_t[q - 1:q, :]
        st[:, lanes] = jnp.where(last == carry, jnp.exp(cs_last),
                                 0.0) * s_in + grown(cs_last, of_last)


_STATIC = ("heads", "head_dim", "groups", "state", "chunk", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def ssd_chunk_scan(xc, dt, a, d_skip, seg, ends, *, heads: int, head_dim: int,
                   groups: int, state: int, chunk: int = 128,
                   interpret: Optional[bool] = None):
    """The four steps in ONE kernel (``ssd_scan``): ``(y (T, H P) in
    ``xc``'s dtype, S_final (prompts, H, P, N) float32)``.

    ``xc`` ``(T, H P + 2 G N)`` the convolved ``[x | B | C]`` rows, ``dt``
    ``(T, H)`` float32 after the softplus, ``a`` ``(H,)`` negative,
    ``d_skip`` ``(H,)``, ``seg`` ``(T,)`` int32 prompt ids 0, 1, ... in
    order, ``ends`` ``(prompts,)`` int32 each prompt's last token.  ``H P``
    is a multiple of ``N`` (the index maps of ``B`` and ``C`` count in
    blocks of ``N`` columns), and a head group's columns with ``dt`` and the
    ids fit one ``(chunk, chunk)`` transpose: ``2 H / G + 1 <= chunk``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, prompts = xc.shape[0], ends.shape[0]
    p, n, q = head_dim, state, chunk
    inner, hg = heads * p, heads // groups
    if heads % groups or inner % n or xc.shape[1] != inner + 2 * groups * n:
        raise ValueError(f"{heads} heads of {p}, {groups} groups of {n}: "
                         f"rows of {xc.shape[1]}")
    if 2 * hg + 1 > q:
        raise ValueError(f"{hg} heads a group in chunks of {q}: their "
                         "columns are made by one (chunk, chunk) transpose")
    hp = max(1, min(hg, 128 // p))  # heads a lane tile
    while hg % hp:
        hp -= 1
    dt, cs, seg, last, _, end_chunk, end_off = chunk_sums(dt, a, seg, ends, q)
    tp = dt.shape[0]
    nc = tp // q
    xc_p = jnp.pad(xc, ((0, tp - t), (0, 0)))
    d_row = jnp.repeat(d_skip.astype(jnp.float32), p)[None, :]
    operands = (xc_p, xc_p, xc_p, cs.T, dt.T, seg[None, :], d_row)
    gw = hg * p
    in_specs = [
        pl.BlockSpec((q, gw), lambda g, c, *_: (c, g)),
        pl.BlockSpec((q, n), lambda g, c, *_: (c, inner // n + g)),
        pl.BlockSpec((q, n), lambda g, c, *_: (c, (inner + groups * n) // n
                                               + g)),
        pl.BlockSpec((hg, q), lambda g, c, *_: (g, c)),
        pl.BlockSpec((hg, q), lambda g, c, *_: (g, c)),
        pl.BlockSpec((1, q), lambda g, c, *_: (0, c)),
        pl.BlockSpec((1, gw), lambda g, c, *_: (0, g)),
    ]
    y, fin = pl.pallas_call(
        functools.partial(_ssd_kernel, (q, p, hg, hp, prompts)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(groups, nc),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((q, gw), lambda g, c, *_: (c, g)),
                pl.BlockSpec((prompts, n, gw), lambda g, c, *_: (0, 0, g)),
            ],
            scratch_shapes=[pltpu.VMEM((n, gw), jnp.float32)],
        ),
        out_shape=[out_struct((tp, inner), xc.dtype, *operands),
                   out_struct((prompts, n, inner), jnp.float32, *operands)],
        compiler_params=pltpu.CompilerParams(
            # the chunk axis carries the state: in order, on one core
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20,
        ),
        name="ssd_scan",
        interpret=interpret,
    )(last, end_chunk, end_off, *operands)
    # the kernel keeps a state as (N, head . P): back to (head, P, N)
    fin = fin.reshape(prompts, n, heads, p).transpose(0, 2, 3, 1)
    return y[:t], fin
