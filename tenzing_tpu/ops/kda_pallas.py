"""One decode step of Kimi Delta Attention (KDA) on a recurrent state: the
gated delta rule with a decay a key channel, as one Pallas kernel
(``kda_step``) that reads a sequence's state once and writes it once.

Per sequence b and head h of ``H`` (``d`` channels a head; every sum
float32, no bfloat16 rounding of the state or of a product with it):

1. *Short convolution step.*  ``x[b]`` ``(3, H, d)`` is the new token's
   projected ``[q ; k ; v]`` row, ``Cv[b]`` ``(taps - 1, 3, H, d)`` the
   rows before it, ``Wc`` ``(taps, 3, H, d)`` the depthwise kernel: ``y =
   silu(sum_i Wc[i] . row_{t - taps + 1 + i})``; the window moves on by one
   (``Cvnew``).
2. *Gates.*  ``g = -exp(A_log[h]) softplus(f + dt_bias)`` (the log-decay a
   key channel), ``beta = sigmoid(b)``, ``q <- l2norm(q) d^(-1/2)``, ``k <-
   l2norm(k)``.
3. *State step.*  ``S' = S . exp(g)[:, None]``; ``u = beta (v - k^T S')``;
   ``Snew = S' + k (x) u``; ``o = q^T Snew``.  ``S`` is ``(d, d)``: key
   channels down the sublanes, value channels along the lanes.
4. *Output norm and gate.*  ``o <- RMSNorm_w(o) . sigmoid(go)`` a head.

:func:`conv_step`, :func:`gates`, :func:`state_step` and :func:`out_norm`
are those four as plain ``jax.numpy`` on arrays with any leading axes: the
kernel's body calls the first, second and fourth on its tiles, and the XLA
chain of ``models/delta_attention.py`` calls all four, so the two engines
take the same sums in steps 1, 2 and 4.  In step 3 the kernel walks its
heads: it needs ``k``, ``exp(g)`` and ``q`` as columns (a channel a
sublane), which one ``(128, 128)`` transpose a grid step gives it for all
the step's heads at once, and both contractions are sums over sublanes on
the VPU (a float32 product on the MXU would be rounded to bfloat16 or cost
six passes).

The grid is ``(sequences, head blocks)``: a step holds one sequence's
``head_block`` states in VMEM (2 MB in and 2 MB out at 32 heads of 128 x
128 float32, double-buffered), so a call moves ``S`` and ``Snew`` through
HBM once each, and the small operands beside them.  ``Snew``, ``Cvnew`` and
``o`` are handed in and aliased onto the outputs: a call writes the rows of
its own sequences and leaves the others as they came.
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

L2_EPS = 1e-6  # inside the root of q's and k's norm (the model's l2norm)
LANES = 128


def silu(y):
    return y * jax.nn.sigmoid(y)


def softplus(x):
    """``log(1 + exp(x))`` without overflow, of operations every lowering
    has."""
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def conv_step(x, cv, wc):
    """Step 1 for rows ``x`` ``(..., 3, H, d)`` with their windows ``cv``
    ``(..., taps - 1, 3, H, d)``: ``(y float32, the window moved on)``."""
    f32 = jnp.float32
    taps = wc.shape[0]
    y = wc[taps - 1].astype(f32) * x.astype(f32)
    for i in range(taps - 1):
        y = y + wc[i].astype(f32) * cv[..., i, :, :, :].astype(f32)
    moved = jnp.concatenate(
        [cv[..., 1:, :, :, :], x[..., None, :, :, :].astype(cv.dtype)],
        axis=-4)
    return silu(y), moved


def gates(y, f, dt_bias, a_log, b):
    """Step 2 from step 1's ``y`` ``(..., 3, H, d)``: ``(q, k, v, decay,
    beta)``, all float32; ``decay = exp(g)`` ``(..., H, d)``, ``beta``
    ``(..., H, 1)``."""
    f32 = jnp.float32
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]
    d = q.shape[-1]
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        * (d ** -0.5)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    g = -jnp.exp(a_log.astype(f32)) * softplus(
        f.astype(f32) + dt_bias.astype(f32))
    return q, k, v, jnp.exp(g), jax.nn.sigmoid(b.astype(f32))


def state_step(s, q, k, v, decay, beta):
    """Step 3 for states ``s`` ``(..., H, d, d)``: ``(Snew, o)``.  Products
    and sums on the VPU in float32 (an einsum would round to bfloat16 on
    the MXU)."""
    sd = s * decay[..., :, None]
    r = jnp.sum(sd * k[..., :, None], axis=-2)
    u = beta * (v - r)
    snew = sd + k[..., :, None] * u[..., None, :]
    return snew, jnp.sum(snew * q[..., :, None], axis=-2)


def out_norm(o, go, w_norm, eps: float):
    """Step 4 for ``o`` ``(..., H, d)`` float32."""
    f32 = jnp.float32
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * w_norm.astype(f32) * jax.nn.sigmoid(go.astype(f32))


def _kda_kernel(hb: int, eps: float, x_ref, cv_ref, wc_ref, f_ref, dtb_ref,
                alog_ref, b_ref, go_ref, wn_ref, s_ref, _snew, _cvnew, _o,
                snew_ref, cvnew_ref, o_ref, o_scr):
    y, moved = conv_step(x_ref[0], cv_ref[0], wc_ref[...])
    cvnew_ref[0] = moved
    q, k, v, decay, beta = gates(y, f_ref[0], dtb_ref[...], alog_ref[...],
                                 b_ref[0])
    # a channel a sublane, for every head of the step at once: column h is
    # head h's k, hb + h its decay, 2 hb + h its q
    rows = jnp.concatenate([k, decay, q], axis=0)
    pad = -rows.shape[0] % LANES
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)], axis=0)
    cols = rows.T
    for h in range(hb):
        k_c = cols[:, h:h + 1]
        sd = s_ref[0, h] * cols[:, hb + h:hb + h + 1]
        r = jnp.sum(sd * k_c, axis=0, keepdims=True)
        u = beta[h:h + 1, :] * (v[h:h + 1, :] - r)
        sn = sd + k_c * u
        snew_ref[0, h] = sn
        o_scr[h:h + 1, :] = jnp.sum(
            sn * cols[:, 2 * hb + h:2 * hb + h + 1], axis=0, keepdims=True)
    o_ref[0] = out_norm(o_scr[...], go_ref[0], wn_ref[...], eps).astype(
        o_ref.dtype)


_STATIC = ("lead0", "rows", "head_block", "eps", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def kda_step_pallas(x, cv, wc, f, dt_bias, a_log, b, go, w_norm, s, snew,
                    cvnew, o, *, lead0: int, rows: int,
                    head_block: Optional[int] = None, eps: float = 1e-5,
                    interpret: Optional[bool] = None):
    """Steps 1 to 4 for the ``rows`` sequences from ``lead0`` in ONE kernel
    (``kda_step``): ``(Snew, Cvnew, o)`` with those sequences' rows written
    and every other row as it came.

    ``x`` ``(B, 3, H, d)``, ``cv`` / ``cvnew`` ``(B, taps - 1, 3, H, d)``,
    ``wc`` ``(taps, 3, H, d)``, ``f`` / ``go`` / ``o`` ``(B, H, d)``,
    ``dt_bias`` ``(H, d)``, ``a_log`` ``(H, 1)``, ``b`` ``(B, H, 1)``,
    ``w_norm`` ``(1, d)``, ``s`` / ``snew`` ``(B, H, d, d)`` float32.
    ``head_block`` heads a grid step (all ``H`` by default; a smaller block
    of 16-bit operands is a multiple of 16)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    batch, _, heads, d = x.shape
    hb = heads if head_block is None else int(head_block)
    if heads % hb or (hb != heads and hb % 16):
        raise ValueError(f"{heads} heads in blocks of {hb}: a block divides "
                         "the heads and, short of all, is a multiple of 16")
    if s.dtype != jnp.float32 or snew.dtype != jnp.float32:
        raise ValueError("the state is float32")
    if not 0 <= lead0 <= lead0 + rows <= batch:
        raise ValueError(f"rows {lead0} .. {lead0 + rows} of {batch}")

    def spec(shape, head_axis, sequence=True):
        block = list(shape)
        block[head_axis] = hb
        if sequence:
            block[0] = 1

        def at(i, j):
            idx = [0] * len(shape)
            idx[head_axis] = j
            if sequence:
                idx[0] = lead0 + i
            return tuple(idx)

        return pl.BlockSpec(tuple(block), at)

    in_specs = [
        spec(x.shape, 2), spec(cv.shape, 3), spec(wc.shape, 2, False),
        spec(f.shape, 1), spec(dt_bias.shape, 0, False),
        spec(a_log.shape, 0, False), spec(b.shape, 1), spec(go.shape, 1),
        pl.BlockSpec(w_norm.shape, lambda i, j: (0, 0)),
        spec(s.shape, 1),
    ] + [pl.BlockSpec(memory_space=pl.ANY)] * 3
    operands = (x, cv, wc, f, dt_bias, a_log, b, go, w_norm, s, snew, cvnew,
                o)
    state_bytes = hb * d * d * 4
    return tuple(pl.pallas_call(
        functools.partial(_kda_kernel, hb, float(eps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(rows, heads // hb),
            in_specs=in_specs,
            out_specs=[spec(snew.shape, 1), spec(cvnew.shape, 3),
                       spec(o.shape, 1)],
            scratch_shapes=[pltpu.VMEM((hb, d), jnp.float32)],
        ),
        out_shape=[out_struct(a.shape, a.dtype, *operands)
                   for a in (snew, cvnew, o)],
        # in place: the rows of the other sequences are never touched
        input_output_aliases={10: 0, 11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state in and out, double-buffered, and as much again for
            # the walk's temporaries
            vmem_limit_bytes=min(100 << 20, max(32 << 20, 8 * state_bytes)),
        ),
        name="kda_step",
        interpret=interpret,
    )(*operands))
