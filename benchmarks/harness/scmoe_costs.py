"""Operations and bytes one decode step of shortcut-connected expert blocks
must do on one chip, from shapes alone, whatever implements them and
whatever schedule runs them (kept beside ``harness/mla_costs.py`` and
``harness/moe_costs.py``: a roofline share divides the result by a measured
device time, so these can only be counted too high by changing this file)."""

from __future__ import annotations

from benchmarks.harness.mla_costs import latent_decode_cost


def scmoe_step_cost(lens, blocks: int, d: int, ffn: int, expert_f: int,
                    experts_held: int, n_router: int, real_rows: float,
                    heads: int, rank: int, q_rank: int, rope: int, nope: int,
                    v_dim: int, bytes_per_el: int = 2) -> dict:
    """One new token for each of ``len(lens)`` sequences of one chip through
    ``blocks`` blocks: two latent-attention layers with their projections,
    two gated dense FFNs and the chip's ``experts_held`` experts a block.
    ``real_rows``: the (token, real expert) pairs a block that land on this
    chip where the load is balanced (a host's pairs over its chips).

    HBM bytes, a floor.  Every weight the chip holds read once: its experts
    (three matrices each), the dense FFNs, an attention layer's ``q_a``,
    ``q_b``, ``kv_a``, the two absorbed matrices and ``o_proj``, the
    float32 router.  The caches by ``harness/mla_costs.py``'s own count
    (every visible key's row once, the appended rows written, the layer's
    operands).  The routed rows: read into the send slots, written there,
    read by the experts, written, read by the combine (five passes over
    ``real_rows`` rows; padding, the path between the send and receive
    buffers, the hidden activations and every other pass over the 64 rows of
    the residual stream are not counted).

    FLOPs, useful ones only: the attention by ``mla_costs``; ``2 d
    q_rank``, ``2 q_rank heads (nope + rope)``, ``2 d (rank + rope)`` and
    ``2 heads v_dim d`` a token and layer for the projections; ``6 d ffn`` a
    token and dense FFN; ``6 d expert_f`` a real row; ``2 d n_router`` a
    token for the scores.  Padding slots are not counted.

    ``weight_bytes``, ``cache_bytes``: the parts of ``hbm_bytes``."""
    tokens = len(lens)
    layers = 2 * blocks
    mla = latent_decode_cost(lens, heads, rank, rope, nope, v_dim, layers,
                             bytes_per_el)
    attn_w = (d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope)
              + heads * v_dim * d)  # the absorbed pair is mla's count
    weights = bytes_per_el * (
        blocks * experts_held * 3 * d * expert_f + layers * 3 * d * ffn
        + layers * attn_w) + 4 * blocks * d * n_router
    rows = bytes_per_el * blocks * 5 * real_rows * d
    flops = (mla["flops"]
             + layers * tokens * 2.0 * (attn_w + 3 * d * ffn)
             + blocks * (6.0 * d * expert_f * real_rows
                         + 2.0 * d * n_router * tokens))
    return {"flops": float(flops),
            "hbm_bytes": float(weights + mla["hbm_bytes"] + rows),
            "scmoe_bytes": float(weights + mla["hbm_bytes"] + rows),
            "weight_bytes": float(weights), "cache_bytes": mla["hbm_bytes"],
            "mla_flops": mla["flops"], "keys": mla["keys"]}
