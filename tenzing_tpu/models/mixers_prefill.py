"""One packed prefill step through the mixers of a Mamba-2 hybrid's period as
a searchable op DAG: Mamba-2 mixers (``models/mamba2.py``: convolution,
chunked selective-state scan, gated norm) and grouped-query attention over
the packed prompts (``models/ring_attention.py`` ``BlockedAttention`` with
``segments``), of each layer the mixer alone, as NVIDIA-Nemotron-3-Nano's
``M`` and ``*`` blocks have them between their projections.

The pattern string is the model's ``hybrid_override_pattern``'s alphabet
with the expert layers left out: ``"MMM*"`` is the mixers of one period
``EMEMEM*``.  Layer ``l`` of kind ``M`` is tagged ``L<l>.M``, of kind ``*``
``L<l>.A``; vertices and buffers carry the tag (``L1.M.ssd``,
``L3.A.q0.attn_blocks``; ``xBC.L1.M``, ``Q.L3.A``).  Every layer reads
inputs of its own, drawn as its projection would deliver them, and layer
``l + 1`` starts when layer ``l``'s output is final (``period_graph``'s
rule): the search's freedom is the scan's engine a layer, the attention's
engine a query block, the order inside a layer and the lanes.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from tenzing_tpu.core.graph import Graph
from tenzing_tpu.models import mamba2
from tenzing_tpu.models.mamba2 import Mamba2Args
from tenzing_tpu.models.ring_attention import (
    BlockedAttention,
    RingAttnArgs,
    blocked_buffer_shapes,
)


def layer_tags(pattern: str) -> List[Tuple[str, str]]:
    """``[(kind, tag)]`` of a pattern of ``M`` and ``*``."""
    if not pattern or set(pattern) - set("M*"):
        raise ValueError(f"a pattern of M and *: {pattern!r}")
    return [(k, f"L{l}.{'A' if k == '*' else 'M'}")
            for l, k in enumerate(pattern)]


def _same_step(mamba: Mamba2Args, attn: RingAttnArgs) -> None:
    if attn.batch != 1 or attn.seq != mamba.tokens or (
            tuple(attn.segments or (0,)) != mamba.starts):
        raise ValueError(
            f"one packed step: {mamba.tokens} tokens from {mamba.starts}, "
            f"the attention's {attn.seq} from {attn.segments}")


def mixers_prefill_graph(mamba: Mamba2Args, attn: RingAttnArgs,
                         pattern: str) -> Graph:
    """The layers of ``pattern`` one after another, each scan with its engine
    menu, each query block with its engine and fold menus."""
    _same_step(mamba, attn)
    g = Graph()
    last = None
    for kind, tag in layer_tags(pattern):
        if kind == "M":
            last = mamba2.add_layer(g, mamba, tag, last)
            continue
        op = BlockedAttention(attn, name=f"{tag}.blocked_attention",
                              impl_choice=True, fused_choice=True,
                              layer=tag)
        if last is None:
            g.start_then(op)
        else:
            g.then(last, op)
        last = op
    g.then_finish(last)
    return g


def buffer_shapes(mamba: Mamba2Args, attn: RingAttnArgs,
                  pattern: str) -> Dict[str, tuple]:
    """``{name: (shape, dtype)}`` of the step's buffers."""
    _same_step(mamba, attn)
    kinds = layer_tags(pattern)
    out = mamba2.buffer_shapes(mamba, [t for k, t in kinds if k == "M"])
    for kind, tag in kinds:
        if kind == "*":
            out.update(blocked_buffer_shapes(attn, tag))
    return out


def state_fill(name: str) -> float:
    """What a buffer the iteration writes starts at: the softmax state's row
    maximum at the empty row's, everything else at zero."""
    return -1e30 if name.split(".")[0] == "m_run" else 0.0
