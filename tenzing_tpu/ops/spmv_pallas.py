"""Pallas ELL-slab SpMV kernel: y[i] = sum_j vals_t[j, i] * x[cols_t[j, i]].

TPU-native replacement for the reference's cuSPARSE ``cusparseSpMV`` call
(ops_spmv.cuh:61-163) and hand-rolled CUDA ``spmv`` kernel (ops_spmv.cuh:25-39),
operating on the ELL/band slab of the SpMV workload's buffers
(models/spmv.py ``make_spmv_buffers``).

Layout: the caller hands the slab as the workload stores it, transposed and
contiguous, ``(w, m)`` — the matrix's rows along the lanes, which is what the
XLA path (``SpMVOp``'s column sweep) wants.  The kernel itself works on
row-major ``(block_m, 128)`` tiles, so ``ell_spmv_pallas`` pads the slab to
``(w_pad, m_pad)`` (a lane multiple of slab rows, a block multiple of matrix
rows) and transposes it once, tile-aligned, before the ``pallas_call``.

Hardware note (probed on TPU v5e, jax 0.9 Mosaic): in-kernel dynamic gather
(``tpu.dynamic_gather``) requires operand/indices/output to share one 2D shape
with the gathered (lane) dimension exactly 128 — a within-vreg shuffle.  An
arbitrary-width gather therefore cannot live in the kernel; XLA's native gather
HLO is the hardware path for large x (models/spmv.py SpMVOp).  This kernel
instead decomposes x into 128-lane vregs and accumulates a masked within-vreg
gather per block:

    for b in blocks(x):   # unrolled, n/128 vregs
        g = dyn_gather(broadcast(x[b]), clip(cols - 128*b))   # lane shuffle
        acc += vals * g * (cols in block b)

Cost scales with ``n/128 * m * w`` lane-ops, so it wins only for *small* x —
exactly the renumbered remote-column vector of the distributed SpMV split
(reference split_mat.hpp:22-136: only needed x entries move).  Whether it beats
the XLA gather for a given matrix is an empirical question — so the workload
exposes the choice as a ChoiceOp and the solver searches it (the reference's
ChoiceOp menu, operation.hpp:90-93).

``interpret=True`` (automatic off-TPU) runs the same kernel in the Pallas
interpreter for CPU tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from tenzing_tpu.ops.common import out_struct

LANES = 128

# n/128 vregs above which the masked-gather sweep is clearly worse than the XLA
# gather path; callers use this to decide whether to even offer the choice
MAX_X_BLOCKS = 32


def supports(n: int, max_blocks: int = MAX_X_BLOCKS) -> bool:
    """Whether the kernel is sensible for an x vector of length ``n``."""
    return n <= LANES * max_blocks


def _ell_kernel(vals_ref, cols_ref, x_ref, o_ref):
    block_m, w = vals_ref.shape
    n_pad = x_ref.shape[1]
    cols = cols_ref[...]
    vals = vals_ref[...]
    acc = jnp.zeros((block_m, 1), vals.dtype)
    for b in range(n_pad // LANES):
        xb = jnp.broadcast_to(x_ref[:, b * LANES : (b + 1) * LANES], (block_m, LANES))
        rel = cols - b * LANES
        in_blk = (rel >= 0) & (rel < LANES)
        g = jnp.take_along_axis(
            xb,
            jnp.clip(rel, 0, LANES - 1),
            axis=1,
            mode="promise_in_bounds",
        )
        acc += jnp.sum(
            jnp.where(in_blk, vals * g, jnp.zeros_like(vals)),
            axis=1,
            keepdims=True,
        )
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def ell_spmv_pallas(
    vals_t: jax.Array,
    cols_t: jax.Array,
    x: jax.Array,
    *,
    block_m: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """y[i] = sum_j vals_t[j, i] * x[cols_t[j, i]] via the masked vreg-gather
    kernel, for the transposed ``(w, m)`` slab."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    w, m = vals_t.shape
    n = x.shape[0]
    # pad the slab width to a lane multiple and the rows to a block multiple
    # (cols 0 / vals 0: contributes 0), then hand the kernel row-major tiles
    w_pad = -(-w // LANES) * LANES
    n_pad = -(-n // LANES) * LANES
    xp = jnp.pad(x, (0, n_pad - n)) if n_pad != n else x
    block_m = min(block_m, max(8, m))
    m_pad = -(-m // block_m) * block_m
    pad = ((0, w_pad - w), (0, m_pad - m))
    vals = jnp.pad(vals_t, pad).T
    cols = jnp.pad(cols_t, pad).T
    y = pl.pallas_call(
        _ell_kernel,
        grid=(m_pad // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, w_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_m, w_pad), lambda i: (i, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        out_shape=out_struct((m_pad, 1), vals.dtype, vals, cols, xp),
        interpret=interpret,
    )(vals, cols, xp.reshape(1, n_pad))
    return y[:m, 0]
