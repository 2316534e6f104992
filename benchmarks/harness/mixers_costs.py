"""Operations and bytes one packed prefill step through the mixers of a
Mamba-2 hybrid's period must do on one chip, from shapes alone, whatever
implements them and whatever engine a schedule picked (kept beside
``harness/kda_costs.py`` and ``harness/attn_costs.py``: a share of a peak
divides the result by a measured device time, so these can only be counted
too high by changing this file); and what the cell's readers of the scan
and of the program's counters share."""

from __future__ import annotations


def ssd_scan_cost(tokens: int, prompts: int, heads: int, head_dim: int,
                  groups: int, state: int, chunk: int,
                  bytes_per_el: int = 2) -> dict:
    """One Mamba-2 layer's chunked selective-state scan over ``tokens``
    packed tokens.

    Operations on the matrix unit, a token: ``C B^T`` a group (``2 Q N``),
    and a head the masked product (``2 Q P``), the incoming state's part
    (``2 N P``) and the state's update (``2 N P``): 3.4 MFLOP at the
    published widths.  The decays' exponentials, masks and the skip are the
    vector unit's and are not counted.

    HBM bytes, a floor: ``x``, ``B`` and ``C`` read once and ``y`` written
    once (``bytes_per_el``), ``dt`` read once (float32), every prompt's
    final state written once (float32).  The chain's decays, chunk states
    and diagonal part through HBM are not counted."""
    inner, gn = heads * head_dim, groups * state
    flops = tokens * (2.0 * chunk * state * groups
                      + heads * 2.0 * head_dim * (chunk + 2 * state))
    hbm = (tokens * ((2 * inner + 2 * gn) * bytes_per_el + heads * 4)
           + prompts * inner * state * 4)
    return {"flops": float(flops), "hbm_bytes": float(hbm)}


def packed_pairs(lens) -> int:
    """(query, key) pairs of one head under the packed causal mask: a row
    sees its own prompt's keys at or before it."""
    return sum(n * (n + 1) // 2 for n in lens)


def mixers_prefill_cost(lens, pattern: str, heads: int, head_dim: int,
                        groups: int, state: int, taps: int, chunk: int,
                        attn_heads: int, kv_heads: int, attn_head_dim: int,
                        bytes_per_el: int = 2) -> dict:
    """The step for prompts of ``lens`` packed into one batch, a layer a
    character of ``pattern`` (``M`` a Mamba-2 mixer, ``*`` the attention).

    A Mamba-2 mixer.  Operations: :func:`ssd_scan_cost`'s (the convolution
    and the norm are the vector unit's).  HBM bytes, a floor: ``z``, ``xBC``
    and ``dt`` read once, ``out`` written once, and what the layer leaves
    for a decode step (final states float32, convolution tails) written
    once.  The convolved ``xBC`` and ``y`` are not counted: a program may
    keep either on the chip; ``conv_bytes`` and ``norm_bytes`` say what the
    two XLA vertices move where it does not (``xBC`` in and out; ``y``,
    ``z`` in and ``out`` out).

    The attention.  Operations, useful ones only: ``Q K^T`` and ``P V`` are
    ``2 d`` each a visible pair and query head (:func:`packed_pairs`);
    masked pairs, the exponentials and the division are not counted.  HBM
    bytes: Q, K, V read once, O written once.

    ``layers``: ``[{"flops", "hbm_bytes"}]`` a layer, in order (a reader
    takes the larger of a layer's two bounds: ``mixers_step_roofline``);
    ``flops`` and ``hbm_bytes`` their sums; ``ssd_flops`` / ``ssd_bytes`` the
    scans' alone (all ``M`` layers)."""
    tokens, prompts = sum(lens), len(lens)
    inner = heads * head_dim
    conv = inner + 2 * groups * state
    scan = ssd_scan_cost(tokens, prompts, heads, head_dim, groups, state,
                         chunk, bytes_per_el)
    mixer = {"flops": scan["flops"], "hbm_bytes": float(
        tokens * ((2 * inner + conv) * bytes_per_el + heads * 4)
        + prompts * (inner * state * 4 + (taps - 1) * conv * bytes_per_el))}
    attn = {"flops": 4.0 * attn_head_dim * attn_heads * packed_pairs(lens),
            "hbm_bytes": float(bytes_per_el * tokens * attn_head_dim
                               * (2 * attn_heads + 2 * kv_heads))}
    layers = [dict(mixer if k == "M" else attn) for k in pattern]
    n_m = pattern.count("M")
    return {"flops": sum(x["flops"] for x in layers),
            "hbm_bytes": sum(x["hbm_bytes"] for x in layers),
            "layers": layers,
            "ssd_flops": n_m * scan["flops"],
            "ssd_bytes": n_m * scan["hbm_bytes"],
            "conv_bytes": float(n_m * 2 * tokens * conv * bytes_per_el),
            "norm_bytes": float(n_m * 3 * tokens * inner * bytes_per_el)}


KERNEL = "ssd_scan"


def ssd_seconds(record):
    """``(at the start point, at the best finalist)``: the device seconds an
    iteration spends in the ``ssd_scan`` kernel, by the kernel's own name
    (the trace keeps it; XLA's fusions lose theirs).  The first from the one
    dispatch of the climb's start point that the builder profiles at set-up
    (``builders/mixers_prefill.py`` ``start_point_check`` leaves its
    operation kinds under ``cost["start_point_ops"]``): every scan there is
    the kernel, so these are the seconds of all the step's scans.  The
    second is the first by calls: the finalist's traced ``ssd.fused_vertices``
    over the start point's (a finalist that runs a layer's scan as the XLA
    chain calls the kernel less); ``None`` where the best schedule is no
    finalist.  No candidate of the window and no copy of the repeat-n
    loop's carry is in either.  ``None`` where no such profile was left or
    it lists no ``ssd_scan``."""
    cost = record.get("cost") or {}
    at_start = sum(s for name, s in cost.get("start_point_ops") or []
                   if name.startswith(KERNEL))
    calls = (cost.get("start_point_counts") or {}).get("ssd.fused_vertices")
    if not at_start or not calls:
        return None
    best = traced_counts(record)
    return at_start, (at_start * best["ssd.fused_vertices"] / calls
                      if best else None)


def traced_counts(record):
    """The best finalist's own ``{counter: gain}`` of the program's counters
    (``builders/mixers_prefill.py`` ``Counted.check`` leaves one a schedule
    compared under ``cost["traced_counts"]``, naive first), or ``None``."""
    traced = (record.get("cost") or {}).get("traced_counts") or []
    label = record["epilogue"]["best"].get("label", "")
    if not label.startswith("finalist"):
        return None
    at = 1 + int(label[len("finalist"):])
    return traced[at] if at < len(traced) else None
