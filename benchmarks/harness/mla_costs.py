"""Operations and bytes one decode step of latent attention over a cache
must do, from lengths and widths alone, whatever implements them (kept
beside ``harness/costs.py``, which later PRs cannot edit either: a roofline
share divides the result by a measured device time, so these can only be
counted too high by changing this file)."""

from __future__ import annotations


def latent_decode_cost(lens, heads: int, rank: int, rope: int, nope: int,
                       v_dim: int, layers: int, bytes_per_el: int = 2) -> dict:
    """One new token for each of ``len(lens)`` sequences, sequence b with
    ``lens[b]`` cached tokens, through ``layers`` layers.

    FLOPs, useful ones only.  The cache read: a visible key (``L_b + 1`` a
    sequence: the cached ones and the new one) costs each head ``2 (rank +
    rope)`` for its score and ``2 rank`` for its share of ``o_lat``.  Keys
    of a tile past a sequence's length, the softmax's exponentials and the
    division are not counted.  The two projections: ``2 nope rank`` and ``2
    rank v_dim`` a (sequence, head).

    HBM bytes, a floor: every visible key's row read once (``rank + rope``
    wide: V is a view of it, not a second read), the appended rows written,
    the inputs ``q_nope``, ``q_rope``, ``c_new``, ``k_rope_new`` and the
    two weights read once, ``o`` written once.  ``qt`` and ``o_lat`` (a
    kernel may keep either on the chip) and the softmax state of a split-K
    chain are not counted.

    So a share of either peak made of these cannot pass 100."""
    batch, width = len(lens), rank + rope
    keys = sum(int(n) + 1 for n in lens)
    cache_flops = 2.0 * heads * keys * (width + rank)
    proj_flops = 2.0 * batch * heads * (nope * rank + rank * v_dim)
    cache_bytes = keys * width + batch * width
    operand_bytes = (batch * heads * (nope + rope) + batch * width
                     + heads * (nope * rank + rank * v_dim)
                     + batch * heads * v_dim)
    return {"flops": layers * (cache_flops + proj_flops),
            "hbm_bytes": float(layers * bytes_per_el
                               * (cache_bytes + operand_bytes)),
            "cache_share": cache_flops / (cache_flops + proj_flops),
            "keys": layers * keys}
