"""Plain reference of the deepseek_v3 expert layer (``models/moe.py`` with
``scoring="sigmoid"``, ``gated``): ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no slots, no chunks, no
capacity, no sharding — a loop over the experts with a mask.

For tokens ``x`` (T, d), as the model's ``MoEGate`` / ``DeepseekV3MoE``
compute it (``model_type: deepseek_v3``, ``scoring_func: sigmoid``,
``topk_method: noaux_tc`` with one group, ``norm_topk_prob``):

* scores ``s = sigmoid(x W_g)``; selected: the ``top_k`` largest of
  ``s + b`` (``e_score_correction_bias``; it moves the selection only);
* weights ``w_i = scale * s_i / (sum of the selected s + 1e-20)``;
* expert ``E_i(x) = (silu(x W1_i) * (x W3_i)) W2_i``, the shared expert
  ``S(x)`` the same form with its own width;
* ``y = S(x) + sum over the selected of w_i E_i(x)``.

Departures from the published layer: none in the equations.  The data are
taken as they come (bfloat16 weights are read as the float32 numbers they
are); every product and sum is float32.  :func:`moe_layer` takes an
``experts`` range so that one shard's share of the result can be computed
alone (``tests/test_moe_topk.py``: the shares, the shared expert counted
once, add up to the whole).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gate(x, wg, bias, top_k: int, scale: float):
    """``(selected (T, k) int32, weights (T, k) float32, scores (T, E))``."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                   wg.astype(jnp.float32)))
    _, sel = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], top_k)
    picked = jnp.take_along_axis(s, sel, axis=1)
    w = scale * picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w, s


def gated_mlp(x, w1, w3, w2):
    """``(silu(x w1) * (x w3)) w2`` in float32."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(jnp.dot(x, w1.astype(f32))) * jnp.dot(
            x, w3.astype(f32))
        return jnp.dot(h, w2.astype(f32))


def moe_layer(x, wg, bias, w1, w3, w2, shared, top_k: int, scale: float,
              experts=None, with_shared: bool = True):
    """``y`` (T, d) float32.  ``w1``/``w3`` (E, d, f), ``w2`` (E, f, d);
    ``shared`` is ``(ws1, ws3, ws2)`` or ``None``.  ``experts`` (a range of
    expert ids; default all) restricts the sum to what those experts give."""
    x = x.astype(jnp.float32)
    sel, w, _ = gate(x, wg, bias, top_k, scale)
    y = jnp.zeros(x.shape, jnp.float32)
    if with_shared and shared is not None:
        y = y + gated_mlp(x, *shared)
    for e in (range(w1.shape[0]) if experts is None else experts):
        # this expert's weight for every token: 0 where it is not selected
        we = jnp.sum(jnp.where(sel == e, w, 0.0), axis=1, keepdims=True)
        y = y + we * gated_mlp(x, w1[e], w3[e], w2[e])
    return y
