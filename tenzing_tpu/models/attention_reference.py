"""Plain reference of one attention layer with grouped heads, a causal mask
and a sliding window: the equations of the model as written, to hold
``models/ring_attention.py`` ``BlockedAttention`` against.

For head h (its key/value head g = h // (heads // kv_heads)) and positions
i, j:

    S = Q_h K_g^T / sqrt(head_dim)
    visible(i, j) = j <= i                      (full layer)
                    i - window < j <= i         (window layer)
    P = softmax over visible j
    O_h = P V_g

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
a dense mask, no blocks, no online softmax: the whole ``(n, n)`` score
matrix of every head exists, so this is for the sizes of tests.  Departures
from the model's equations, all of them:

* the model multiplies P, rounded to bfloat16, by V with float32
  accumulation and stores O in bfloat16; here P and O stay float32 (the
  comparison's tolerance carries that rounding);
* Q, K and V are taken as handed (the projections, norms and rotary
  embedding that produce them, and any gate on O, are not part of the
  layer as this repository runs it).
"""

from __future__ import annotations

from typing import Optional


def attention(q, k, v, causal: bool = True, window: Optional[int] = None):
    """O ``(heads, n, d)`` float32 of q ``(heads, n, d)``, k and v
    ``(kv_heads, n, d)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    h, n, d = q.shape
    group = h // k.shape[0]
    with jax.default_matmul_precision("highest"):
        kh = jnp.repeat(k.astype(f32), group, axis=0)
        vh = jnp.repeat(v.astype(f32), group, axis=0)
        s = jnp.einsum("hid,hjd->hij", q.astype(f32), kh) / jnp.sqrt(f32(d))
        i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
        visible = jnp.ones((n, n), bool)
        if causal:
            visible = j <= i
        if window is not None:
            visible = visible & (j > i - window)
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,hjd->hid", p, vh)
