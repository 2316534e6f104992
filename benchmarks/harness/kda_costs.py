"""Operations and bytes one decode step of a period of KDA layers and a
latent-attention layer must do, from shapes alone, whatever implements them
and whatever engine a schedule picked (kept beside ``harness/mla_costs.py``:
a roofline share divides the result by a measured device time, so these can
only be counted too high by changing this file)."""

from __future__ import annotations

from benchmarks.harness.mla_costs import latent_decode_cost


def kda_decode_cost(batch: int, heads: int, d: int, taps: int, layers: int,
                    bytes_per_el: int = 2) -> dict:
    """One new token for each of ``batch`` sequences through ``layers`` KDA
    layers, ``heads`` heads of a ``(d, d)`` float32 state each.

    Operations, useful ones only, a (sequence, head): the decay ``d^2``,
    ``k^T S'`` ``2 d^2``, the rank-one update ``2 d^2``, the read-out ``2
    d^2``; the convolution step ``2 taps`` a channel of ``3 d``.  Norms,
    gates and their exponentials are not counted.  None of it is the matrix
    unit's work at float32: the bound a share of the bfloat16 peak would
    give is not this step's.

    HBM bytes, a floor: ``S`` read once and ``Snew`` written once (float32),
    the convolution window read and written once, the inputs (the new
    ``[q ; k ; v]`` row, the decay gate's and the output gate's rows, beta)
    read and ``o`` written once, the parameters once a layer.  A second or
    third pass over the state, an intermediate through HBM and the chain's
    ``y``, ``qkv``, ``decay`` are not counted: a program may keep any of
    them on the chip.

    ``state_bytes`` is the part of ``hbm_bytes`` that is ``S``, ``Snew``
    and the two windows: what the new kernel's own roofline is made of."""
    state = 2 * 4 * d * d + 2 * (taps - 1) * 3 * d * bytes_per_el
    rows = (3 * d + d + d + 1 + d) * bytes_per_el
    params = heads * (taps * 3 * d * bytes_per_el + 4 * d + 4) + 4 * d
    return {"flops": float(layers * batch * heads
                           * (7 * d * d + 2 * taps * 3 * d)),
            "hbm_bytes": float(layers * (batch * heads * (state + rows)
                                         + params)),
            "state_bytes": float(layers * batch * heads * state)}


def hybrid_decode_cost(lens, kda_layers: int, kda_heads: int, d: int,
                       taps: int, mla_layers: int, heads: int, rank: int,
                       rope: int, nope: int, v_dim: int,
                       bytes_per_el: int = 2) -> dict:
    """The period's step: :func:`kda_decode_cost` for its KDA layers and
    ``harness/mla_costs.py``'s own count for its latent-attention layers at
    this model's head count, summed.  ``kda_bytes`` and ``kda_state_bytes``
    are the KDA layers' parts, ``keys`` the latent layers' visible keys."""
    kda = kda_decode_cost(len(lens), kda_heads, d, taps, kda_layers,
                          bytes_per_el)
    mla = latent_decode_cost(lens, heads, rank, rope, nope, v_dim,
                             mla_layers, bytes_per_el)
    return {"flops": kda["flops"] + mla["flops"],
            "hbm_bytes": kda["hbm_bytes"] + mla["hbm_bytes"],
            "kda_bytes": kda["hbm_bytes"],
            "kda_state_bytes": kda["state_bytes"],
            "kda_flops": kda["flops"], "mla_flops": mla["flops"],
            "mla_bytes": mla["hbm_bytes"], "keys": mla["keys"]}
