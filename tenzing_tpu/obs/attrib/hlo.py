"""What a compiled program's loop does to a buffer of a given shape.

``TraceExecutor``'s repeat-n program is one ``while`` over the schedule's
ops; a whole pass over a large buffer inside its body (an ordering token
added onto a grid, a relayout ``copy`` of it, a ``dynamic-update-slice``
that could not be done in place) costs every iteration and shows in a device
trace only as a fusion's name.  This reads the post-optimization HLO text
(``jax.jit(f).lower(...).compile().as_text()``) instead: nothing runs, so it
works on a program compiled for a described chip too (tests/test_tpu_compile.py).
"""

from __future__ import annotations

import re
from typing import Dict, List, NamedTuple

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
# name = type opcode(operands...: a tuple's type has spaces, none nests
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^()]*(?:\([^()]*\)[^()]*)*\)|\S+)"
    r"\s+([\w\-]+)\(")
# instructions that move no data
_FREE = ("get-tuple-element", "parameter", "tuple", "bitcast", "constant")


class LoopOp(NamedTuple):
    name: str
    opcode: str  # a fusion's is "fusion"; ``fused`` lists what it fuses
    result: str  # the result's type, layout included
    fused: tuple


def computations(text: str) -> Dict[str, List[str]]:
    """HLO text -> {computation name: its instruction lines}."""
    comps: Dict[str, List[str]] = {}
    name = None
    for line in text.splitlines():
        m = None if line.startswith(" ") else _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.rstrip() == "}":  # a custom call's payload may span lines
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def loop_ops_of_shape(text: str, shape: str) -> List[LoopOp]:
    """The instructions in the bodies of ``text``'s ``while`` loops whose
    result (or, for a multi-output fusion, one of whose results) has
    ``shape`` (as HLO prints it, without layout: ``f32[3,454,454,454]``),
    the free ones left out.  Whether a ``dynamic-update-slice`` is done in
    place is buffer assignment's to decide and not in the text: read that
    from the program's temporaries (``memory_analysis()``) or a trace."""
    comps = computations(text)
    out = []
    for body in re.findall(r"\swhile\(.*\bbody=%?([\w.\-]+)", text):
        lines = comps.get(body, [])
        for l in lines:
            m = _INSTRUCTION.match(l)
            if not m or m.group(3) in _FREE or shape not in m.group(2):
                continue
            name, result, opcode = m.groups()
            called = re.search(r"calls=%?([\w.\-]+)", l)
            fused = tuple(sorted({
                i.group(3) for i in map(_INSTRUCTION.match,
                                        comps.get(called.group(1), []))
                if i and i.group(3) not in _FREE})) if called else ()
            out.append(LoopOp(name, opcode, result, fused))
    return out
