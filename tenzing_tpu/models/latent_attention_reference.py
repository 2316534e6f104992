"""Plain reference of one decode step of latent attention (DeepSeek-V3's MLA),
to hold ``models/latent_attention.py`` against.

**The published form** (:func:`published`).  A sequence's cache holds, per
token j, the latent ``c_j`` (``kv_lora_rank`` wide, after its norm) and the
shared rotary key ``k_rope_j``.  For the one new query position of a
sequence with ``L`` cached tokens, head h:

    k_nope[j,h] = W_UK[h] c_j            (qk_nope_head_dim)
    v[j,h]      = c_j W_UV[h]            (v_head_dim)
    s[h,j]      = scale (q_nope[h] . k_nope[j,h] + q_rope[h] . k_rope_j)
    p[h,:]      = softmax over j = 0 .. L      (the new token's row included)
    o[h]        = sum_j p[h,j] v[j,h]

with ``scale = (qk_nope_head_dim + qk_rope_head_dim)^(-1/2) mscale^2`` and
``mscale = 0.1 mscale_all_dim ln(factor) + 1`` from the config's yarn keys.

**The absorbed form** (:func:`absorbed`): the same sums in another order,
which is what the system runs and what the model's own inference code does:
``qt[h] = [q_nope[h] W_UK[h] ; q_rope[h]]``, ``s[h,j] = scale qt[h] .
[c_j ; k_rope_j]``, ``o_lat[h] = sum_j p[h,j] c_j``, ``o[h] = o_lat[h]
W_UV[h]``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one sequence at a time, its cache dense ``(L + 1, rank + rope)``: no pages,
no table, no blocks, no online softmax (sizes of tests).  Departures from
the model's equations, all of them: the down-projections, the ``q_a`` /
``kv_a`` norms, the rotary embedding and ``o_proj`` lie before and after
what is computed here and are taken as done (the inputs are handed as they
would arrive); P and ``o_lat`` stay float32 where the system rounds P to
bfloat16 before the second product and stores ``qt``, ``o_lat`` and ``o``
in bfloat16 (the comparison's tolerance carries those roundings).

**Learned sparse attention** (DeepSeek-V3.2's DSA; :func:`index_scores`,
:func:`select`, :func:`sparse_published`; ``models/sparse_attention.py``).
Beside the latent cache a sequence holds an index key ``kI_j``
(``index_head_dim`` wide) per token.  For the new query position, with the
indexer's queries ``qI[h]`` and head weights ``wI[h]`` (``weights_proj(x) .
index_n_heads^-1/2 . index_head_dim^-1/2``):

    I[j] = sum_h wI[h] relu(qI[h] . kI_j)          j = 0 .. L
    S    = the positions of the min(index_topk, L + 1) largest I[j]
           (equal scores: the lower position)
    o    = the published form above with s[h,j] = -inf for j outside S

Departures: the model caches its index keys in float8 with a scale a token
and rotates ``qI`` and ``kI`` by a Hadamard matrix for that rounding's
sake; here the keys are what the cache holds (the system's is bfloat16) and
the rotation, orthogonal and so without effect on ``qI . kI``, lies before
the inputs with ``wq_b``, ``wk``, ``k_norm`` and the rotary embedding.
"""

from __future__ import annotations

import math


def yarn_scale(nope: int = 128, rope: int = 64, factor: float = 40.0,
               mscale_all_dim: float = 1.0) -> float:
    """The softmax scale of the config: 0.135234 for DeepSeek-V3."""
    mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0
    return (nope + rope) ** -0.5 * mscale * mscale


def _f32(*xs):
    import jax.numpy as jnp

    return [jnp.asarray(x, jnp.float32) for x in xs]


def published(cache, q_nope, q_rope, w_uk, w_uv, scale: float):
    """``o`` ``(heads, v_dim)`` float32 of one sequence: ``cache`` ``(L + 1,
    rank + rope)`` with the new token's row last, ``q_nope`` ``(heads,
    nope)``, ``q_rope`` ``(heads, rope)``, ``w_uk`` ``(heads, nope, rank)``,
    ``w_uv`` ``(heads, rank, v_dim)``."""
    import jax
    import jax.numpy as jnp

    cache, q_nope, q_rope, w_uk, w_uv = _f32(cache, q_nope, q_rope, w_uk,
                                             w_uv)
    rank = w_uk.shape[2]
    c, k_rope = cache[:, :rank], cache[:, rank:]
    with jax.default_matmul_precision("highest"):
        k_nope = jnp.einsum("hdc,jc->jhd", w_uk, c)
        v = jnp.einsum("jc,hcd->jhd", c, w_uv)
        s = scale * (jnp.einsum("hd,jhd->hj", q_nope, k_nope)
                     + jnp.einsum("hr,jr->hj", q_rope, k_rope))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hj,jhd->hd", p, v)


def absorbed(cache, q_nope, q_rope, w_uk, w_uv, scale: float):
    """The same ``o`` by the absorbed order of sums."""
    import jax
    import jax.numpy as jnp

    cache, q_nope, q_rope, w_uk, w_uv = _f32(cache, q_nope, q_rope, w_uk,
                                             w_uv)
    rank = w_uk.shape[2]
    with jax.default_matmul_precision("highest"):
        qt = jnp.concatenate(
            [jnp.einsum("hd,hdc->hc", q_nope, w_uk), q_rope], axis=1)
        p = jax.nn.softmax(scale * jnp.einsum("hw,jw->hj", qt, cache),
                           axis=-1)
        o_lat = jnp.einsum("hj,jc->hc", p, cache[:, :rank])
        return jnp.einsum("hc,hcd->hd", o_lat, w_uv)


def index_scores(keys, q_idx, w_idx):
    """``I`` ``(L + 1,)`` float32 of one sequence: ``keys`` ``(L + 1,
    index_dim)`` with the new token's row last, ``q_idx`` ``(index_heads,
    index_dim)``, ``w_idx`` ``(index_heads,)``."""
    import jax
    import jax.numpy as jnp

    keys, q_idx, w_idx = _f32(keys, q_idx, w_idx)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("hd,jd->hj", q_idx, keys)
    return jnp.sum(jnp.maximum(s, 0.0) * w_idx[:, None], axis=0)


def select(scores, topk: int):
    """Positions of the ``min(topk, len(scores))`` largest scores, equal
    scores to the lower position, ascending."""
    import jax.numpy as jnp

    order = jnp.argsort(-jnp.asarray(scores, jnp.float32), stable=True)
    return jnp.sort(order[:min(topk, order.shape[0])])


def sparse_published(cache, sel, q_nope, q_rope, w_uk, w_uv, scale: float):
    """:func:`published` with every key outside ``sel`` (positions into
    ``cache``) masked to ``-inf`` before the softmax: the dense scores,
    masked by the selection."""
    import jax
    import jax.numpy as jnp

    cache, q_nope, q_rope, w_uk, w_uv = _f32(cache, q_nope, q_rope, w_uk,
                                             w_uv)
    rank = w_uk.shape[2]
    c, k_rope = cache[:, :rank], cache[:, rank:]
    inside = jnp.zeros((cache.shape[0],), bool).at[jnp.asarray(sel)].set(True)
    with jax.default_matmul_precision("highest"):
        k_nope = jnp.einsum("hdc,jc->jhd", w_uk, c)
        v = jnp.einsum("jc,hcd->jhd", c, w_uv)
        s = scale * (jnp.einsum("hd,jhd->hj", q_nope, k_nope)
                     + jnp.einsum("hr,jr->hj", q_rope, k_rope))
        p = jax.nn.softmax(jnp.where(inside[None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("hj,jhd->hd", p, v)
