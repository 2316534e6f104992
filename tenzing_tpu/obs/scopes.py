"""The name a device operation carries: who in a schedule's program made it.

``runtime/executor.py`` traces every operation of a schedule's program
inside a ``jax.named_scope`` of this grammar, so the compiled text's
``metadata={op_name="..."}`` and a profile's ``XLA Ops`` events carry the
schedule's own names; ``obs/attrib/hlo.py`` (``loop_ops_by_scope``) and
``obs/attrib/xplane.py`` (``device_by_vertex``) read them back::

    tz.<vertex>/tie      the ordering token taken: the value-preserving add
                         onto the op's smallest read, or the index zero
    tz.<vertex>/apply    the op's own work (a Pallas kernel or XLA's)
    tz.<vertex>/join     the outputs scalarized into the lane's token
    tz.fence             the repeat-n program's full-reduction fence
    tz.sync.<kind>       what a sync op's hook emits (token adds)

``<vertex>`` is the op's name as the schedule's JSON has it, made safe for a
name stack.  Whatever a vertex emits outside ``tie`` and ``join`` (an op
with a ``trace`` of its own: ops/comm_ops.py) counts as its ``apply``.  An
operation with no ``tz.`` component is XLA's own: the loop's counter and
carry, a relayout it inserted.  The scopes are metadata written while a
program is traced: always on, nothing once it is compiled, no part of a
jaxpr's text, and no part of jax's compile-cache key, so an executable read
from the persistent cache carries the names of whoever compiled it first.

Stdlib only: the readers run where there is no jax.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

SCOPE = "tz."
PARTS = ("tie", "apply", "join")
FENCE = "fence"
SYNC = "sync."
EXECUTOR = "executor"  # the owner of fence, sync, ties and joins in a sum
_UNSAFE = re.compile(r"[^A-Za-z0-9_.+:@=,\-]")


def vertex_scope(name: str) -> str:
    """``tz.<name>`` (``/`` would part a name stack, a quote end its HLO
    string: whatever is not a letter, a digit or one of ``_.+:@=,-``
    becomes ``_``)."""
    return SCOPE + _UNSAFE.sub("_", name)


def sync_scope(kind: str) -> str:
    return SCOPE + SYNC + kind


def owner_of(op_name: str) -> Optional[Tuple[str, str]]:
    """``(vertex, part)`` of a name stack (an HLO ``op_name``, a trace
    event's scope), by its outermost ``tz.`` component: a fused region's
    members nest under the region's own.  ``("executor", "fence")`` and
    ``("executor", "sync.<kind>")`` for the executor's own; ``None`` where
    no component starts with ``tz.``."""
    parts = op_name.split("/")
    for i, comp in enumerate(parts):
        if comp.startswith(SCOPE):
            vertex = comp[len(SCOPE):]
            if vertex == FENCE or vertex.startswith(SYNC):
                return EXECUTOR, vertex
            nxt = parts[i + 1] if i + 1 < len(parts) else ""
            return vertex, nxt if nxt in PARTS else "apply"
    return None
