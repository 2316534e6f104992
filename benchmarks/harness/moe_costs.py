"""Operations and bytes one iteration of an expert-parallel expert layer
must do on one chip, from shapes alone (kept beside ``harness/costs.py``,
which later PRs cannot edit either: a roofline share divides the result by a
measured device time, so these can only be counted too high by changing
this file)."""

from __future__ import annotations


def moe_layer_cost(tokens: int, d: int, f: int, top_k: int, shared_f: int,
                   n_experts: int, experts_held: int,
                   bytes_per_el: int = 2) -> dict:
    """One chip's share of a gated (SwiGLU) top-``top_k`` expert layer with
    a shared expert, ``tokens`` tokens a chip, balanced over the chips.

    FLOPs, useful ones only: each token through ``top_k`` routed experts and
    the shared one, three products each (``2 d f`` a product and token),
    and the score product over all ``n_experts``.  A chip's experts receive
    ``tokens * top_k`` tokens where the load is balanced over the chips;
    slots that pad an expert's table to its capacity are not counted.

    HBM bytes, a floor: the chip's weights read once (its routed experts,
    the shared expert, the router); the tokens read once, and once more row
    by row into the send slots; the slots written, read by the experts,
    their outputs written, and read by the combine (four passes over
    ``tokens * top_k`` rows); the output written once.  The hidden
    activations (a kernel may keep them on the chip), the padding and the
    path between the send and receive buffers (the all-to-all's) are not
    counted."""
    routed_rows = tokens * top_k
    flops = (6.0 * d * f * routed_rows + 6.0 * d * shared_f * tokens
             + 2.0 * d * n_experts * tokens)
    weights = 3 * d * (experts_held * f + shared_f) + d * n_experts
    rows = 2 * tokens + 5 * routed_rows
    return {"flops": flops,
            "hbm_bytes": float(bytes_per_el) * (weights + rows * d)}
