"""Operations and bytes one iteration of a run of masked attention layers
must do, from shapes alone, whatever implements them (kept beside
``harness/costs.py``, which later PRs cannot edit either: a roofline share
divides the result by a measured device time, so these can only be counted
too high by changing this file)."""

from __future__ import annotations


def visible_pairs(n: int, window=None) -> int:
    """(query, key) pairs of one head under the causal mask: key j is
    visible to query i where ``j <= i`` and, with a window, ``j > i -
    window``: the query's own position and the ``window - 1`` before it.
    ``n (n + 1) / 2`` without a window; with one, the first ``window`` rows
    see ``1 .. window`` keys and every later row ``window``."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return n * window - window * (window - 1) // 2


def attention_layers_cost(n: int, windows, heads: int, kv_heads: int,
                          head_dim: int, bytes_per_el: int = 2) -> dict:
    """One prompt of ``n`` positions through one layer per entry of
    ``windows`` (``None``: a full layer).

    FLOPs, useful ones only: ``Q K^T`` and ``P V`` are ``2 * head_dim`` each
    a visible pair and query head.  Pairs a kernel computes and then masks,
    the softmax's exponentials and the final division are not counted, so a
    share of the MXU's peak made of these cannot pass 100.

    HBM bytes, a floor: a layer's Q, K and V read once and its O written
    once.  The softmax state and K/V read again per query tile (a kernel
    may keep either on the chip) are not counted."""
    pairs = sum(visible_pairs(n, w) for w in windows)
    rows = len(windows) * n * (2 * heads + 2 * kv_heads)
    return {"flops": 4.0 * head_dim * heads * pairs,
            "hbm_bytes": float(bytes_per_el) * rows * head_dim}
