"""Pallas flash-attention block kernel: one online-softmax update step.

The MXU workhorse of the ring-attention workload (models/ring_attention.py):
given local queries Q and one K/V block of the ring, fold the block into the
running (acc, m, l) online-softmax state:

    s     = Q K^T * scale          (MXU)
    m'    = max(m, rowmax(s))
    alpha = exp(m - m')
    p     = exp(s - m')
    l'    = l * alpha + rowsum(p)
    acc'  = acc * alpha + p V      (MXU)

State tensors m and l are carried broadcast to (b, n, d) — same shape/layout as
acc — so every in-kernel operand is a clean 2D (n, d) or (n, nkv) tile (no
lane<->sublane transposes, no last-dim-1 blocks; see ops/spmv_pallas.py for the
Mosaic layout constraints that motivate this).

The kernel grid runs over the batch dimension; one program folds one batch
element's whole block — Q/K/V blocks of ring attention are already VMEM-sized
by construction (n_local x d per step).

``interpret=True`` (automatic off-TPU) runs the same kernel in the Pallas
interpreter for CPU tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tenzing_tpu.ops.common import out_struct


def _attn_block_kernel(scale, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                       acc_out, m_out, l_out):
    q = q_ref[0]  # (n, d)
    k = k_ref[0]  # (nkv, d)
    v = v_ref[0]
    m_old = m_ref[0]  # (n, d) broadcast copies of the running row max
    l_old = l_ref[0]
    acc_old = acc_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (n, nkv)
    m_blk = jnp.max(s, axis=1, keepdims=True)  # (n, 1)
    m_new = jnp.maximum(m_old, jnp.broadcast_to(m_blk, m_old.shape))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, :1])  # (n, nkv)
    l_new = l_old * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_old.shape
    )
    acc_new = acc_old * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    acc_out[0] = acc_new.astype(acc_out.dtype)
    m_out[0] = m_new
    l_out[0] = l_new


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def attn_block_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    acc: jax.Array,
    m: jax.Array,
    l: jax.Array,
    scale: float,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold one K/V block into the online-softmax state; returns (acc', m', l').

    Shapes: q (b, n, d); k/v (b, nkv, d); acc/m/l (b, n, d) with m/l broadcast
    along the last axis.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, d = q.shape
    nkv = k.shape[1]
    # tile the (row-independent) update over query blocks so VMEM holds one
    # q/state tile + the whole K/V block, never all n queries at once; ragged n
    # is padded up to the tile (rows are independent, pad rows stay finite:
    # zero q/m give s=0, alpha=1 — no NaN/inf to leak) and sliced back off
    bq = min(n, 512)
    pad = (-n) % bq
    np_ = n + pad
    if pad:
        padw = ((0, 0), (0, pad), (0, 0))
        q, acc, m, l = (jnp.pad(t, padw) for t in (q, acc, m, l))
    qblk = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0))
    kvblk = pl.BlockSpec((1, nkv, d), lambda i, j: (i, 0, 0))
    specs_in = [qblk, kvblk, kvblk, qblk, qblk, qblk]
    operands = (q, k, v, acc, m, l)
    out_shape = [
        out_struct((b, np_, d), acc.dtype, *operands),
        out_struct((b, np_, d), m.dtype, *operands),
        out_struct((b, np_, d), l.dtype, *operands),
    ]
    specs_out = [qblk, qblk, qblk]
    kernel = functools.partial(_attn_block_kernel, float(scale))
    outs = pl.pallas_call(
        kernel,
        grid=(b, np_ // bq),
        in_specs=specs_in,
        out_specs=specs_out,
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v, acc, m, l)
    if pad:
        outs = [o[:, :n] for o in outs]
    return tuple(outs)


def _attn_fused_kernel(scale, nkv_steps, q_ref, k_ref, v_ref, acc_in, m_in,
                       l_in, acc_out, m_out, l_out, acc_s, m_s, l_s):
    """One (batch, q-tile, kv-block) grid step of the fused flash kernel:
    state lives in VMEM scratch across the kv dimension (innermost, strictly
    sequential), so acc/m/l touch HBM exactly twice per q-tile (initial read,
    final write) instead of twice per kv block."""
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _():
        acc_s[...] = acc_in[0]
        m_s[...] = m_in[0]
        l_s[...] = l_in[0]

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    m_old = m_s[...]
    l_old = l_s[...]
    acc_old = acc_s[...]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_old, jnp.broadcast_to(m_blk, m_old.shape))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = l_old * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_old.shape
    )
    acc_new = acc_old * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    acc_s[...] = acc_new
    m_s[...] = m_new
    l_s[...] = l_new

    @pl.when(kv == nkv_steps - 1)
    def _():
        acc_out[0] = acc_s[...].astype(acc_out.dtype)
        m_out[0] = m_s[...]
        l_out[0] = l_s[...]


@functools.partial(jax.jit, static_argnames=("scale", "bkv", "interpret"))
def attn_fused_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    acc: jax.Array,
    m: jax.Array,
    l: jax.Array,
    scale: float,
    bkv: int = 1024,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold the ENTIRE resident K/V into the online-softmax state in ONE
    kernel — the fused alternative to chaining :func:`attn_block_pallas`
    per block.

    Why it exists (measured, r5): at b=4, n=8k, d=128 the chained version
    moves the (b, n, d) f32 state acc/m/l through HBM twice per block —
    8 blocks x 6 x 16.8 MB ~= 0.8 GB per iteration, ~1.2 ms at v5e peak —
    so the chain is HBM-state-bound at 66.5% MFU while the roofline says
    compute-bound.  Keeping the state in VMEM scratch across the kv grid
    dimension (strictly sequential, pinned "arbitrary") cuts state traffic
    to one read + one write per q-tile.

    Shapes: q (b, n, d); k/v (b, nkv, d) with nkv % bkv == 0; acc/m/l
    (b, n, d) broadcast state as in :func:`attn_block_pallas`.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n, d = q.shape
    nkv = k.shape[1]
    bkv = min(bkv, nkv)
    assert nkv % bkv == 0, (nkv, bkv)
    nkv_steps = nkv // bkv
    bq = min(n, 512)
    pad = (-n) % bq
    np_ = n + pad
    if pad:
        padw = ((0, 0), (0, pad), (0, 0))
        q, acc, m, l = (jnp.pad(t, padw) for t in (q, acc, m, l))
    qblk = pl.BlockSpec((1, bq, d), lambda i, j, kv: (i, j, 0))
    kvblk = pl.BlockSpec((1, bkv, d), lambda i, j, kv: (i, kv, 0))
    operands = (q, k, v, acc, m, l)
    kernel = functools.partial(_attn_fused_kernel, float(scale), nkv_steps)
    from jax.experimental.pallas import tpu as pltpu

    outs = pl.pallas_call(
        kernel,
        # kv innermost and strictly sequential: the VMEM scratch state
        # carries across kv steps of one (batch, q-tile)
        grid=(b, np_ // bq, nkv_steps),
        in_specs=[qblk, kvblk, kvblk, qblk, qblk, qblk],
        out_specs=[qblk, qblk, qblk],
        out_shape=[
            out_struct((b, np_, d), acc.dtype, *operands),
            out_struct((b, np_, d), m.dtype, *operands),
            out_struct((b, np_, d), l.dtype, *operands),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, acc, m, l)
    if pad:
        outs = [o[:, :n] for o in outs]
    return tuple(outs)
