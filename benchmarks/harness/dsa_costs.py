"""Operations and bytes one decode step of learned sparse attention (an
indexer over a paged index-key cache, an exact top-k, latent attention over
the selected tokens) must do, from lengths and widths alone, whatever
implements them (kept beside ``harness/mla_costs.py``: a roofline share
divides the result by a measured device time, so these can only be counted
too high by changing this file)."""

from __future__ import annotations


def sparse_decode_cost(lens, heads: int, rank: int, rope: int, nope: int,
                       v_dim: int, index_heads: int, index_dim: int,
                       topk: int, layers: int, bytes_per_el: int = 2) -> dict:
    """One new token for each of ``len(lens)`` sequences, sequence b with
    ``lens[b]`` cached tokens, through ``layers`` layers.

    FLOPs, useful ones only.  The index: a visible key (``L_b + 1`` a
    sequence) costs each index head ``2 index_dim`` for its product (the
    ReLU, the weights and the heads' sum are not counted).  The attention:
    a selected key (``min(topk, L_b + 1)`` a sequence) costs each head ``2
    (rank + rope)`` for its score and ``2 rank`` for its share of
    ``o_lat``.  The two projections as the dense step's.  The selection
    itself is no floating-point work.

    HBM bytes, a floor: every visible key's index row read once, every
    selected key's latent row read once (``rank + rope`` wide, without a
    stored row's padding), both appended rows written, the inputs (``qI``,
    ``wI`` in float32, ``kI_new``, ``q_nope``, ``q_rope``, ``c_new``,
    ``k_rope_new``) and the two weights read once, ``o`` written once.  The
    scores' trip to the selection and back, the selection, the gathered
    tile, ``qt`` and ``o_lat`` are not counted: a program may keep any of
    them on the chip.

    So a share of either peak made of these cannot pass 100."""
    batch, width = len(lens), rank + rope
    keys = sum(int(n) + 1 for n in lens)
    selected = sum(min(topk, int(n) + 1) for n in lens)
    index_flops = 2.0 * index_heads * index_dim * keys
    attend_flops = 2.0 * heads * selected * (width + rank)
    proj_flops = 2.0 * batch * heads * (nope * rank + rank * v_dim)
    index_bytes = bytes_per_el * keys * index_dim
    cache_bytes = bytes_per_el * (selected * width + batch * width
                                  + batch * index_dim)
    operand_bytes = bytes_per_el * (
        batch * heads * (nope + rope) + batch * width
        + batch * index_heads * index_dim + batch * index_dim
        + heads * (nope * rank + rank * v_dim)
        + batch * heads * v_dim) + 4 * batch * index_heads
    return {"flops": layers * (index_flops + attend_flops + proj_flops),
            "hbm_bytes": float(layers * (index_bytes + cache_bytes
                                         + operand_bytes)),
            "index_bytes": float(layers * index_bytes),
            "keys_indexed": layers * keys,
            "keys_selected": layers * selected}
