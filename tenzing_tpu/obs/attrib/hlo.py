"""What a compiled program's loop does: to a buffer of a given shape, and on
whose account.

``TraceExecutor``'s repeat-n program is one ``while`` over the schedule's
ops; a whole pass over a large buffer inside its body (an ordering token
added onto a grid, a relayout ``copy`` of it, a ``dynamic-update-slice``
that could not be done in place) costs every iteration and shows in a device
trace only as a fusion's name.  This reads the post-optimization HLO text
(``jax.jit(f).lower(...).compile().as_text()``) instead: nothing runs, so it
works on a program compiled for a described chip too (tests/test_tpu_compile.py).

:func:`loop_ops_by_scope` reads the owner of every operation of the loop
from the ``metadata={op_name=...}`` the executor's scopes leave in that text
(obs/scopes.py): which vertex of the schedule, and which part of it, a
``copy`` or a fusion belongs to, and which ones are XLA's own.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

from tenzing_tpu.obs.scopes import owner_of

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
# name = type opcode(operands...: a tuple's type has spaces, none nests
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^()]*(?:\([^()]*\)[^()]*)*\)|\S+)"
    r"\s+([\w\-]+)\(")
# instructions that move no data
_FREE = ("get-tuple-element", "parameter", "tuple", "bitcast", "constant")


_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ARRAY = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_BITS = re.compile(r"[a-z]+?([0-9]+)")
# an instruction whose time is its called computations' own
_CALLERS = {"while": ("body", "condition"), "call": ("to_apply",),
            "conditional": ("true_computation", "false_computation",
                            "branch_computations")}
UNSCOPED = "unscoped"


class LoopOp(NamedTuple):
    name: str
    opcode: str  # a fusion's is "fusion"; ``fused`` lists what it fuses
    result: str  # the result's type, layout included
    fused: tuple


def computations(text: str) -> Dict[str, List[str]]:
    """HLO text -> {computation name: its instructions, one a line}.  A
    custom call's attributes may span lines (a kernel's
    ``frontend_attributes`` before its ``metadata``): what follows an
    instruction's first line is joined onto it."""
    comps: Dict[str, List[str]] = {}
    name = None
    for line in text.splitlines():
        m = None if line.startswith(" ") else _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.rstrip() == "}":
            name = None
        elif name is not None:
            if comps[name] and not _INSTRUCTION.match(line):
                comps[name][-1] += " " + line.strip()
            else:
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


class ScopedOp(NamedTuple):
    name: str
    opcode: str    # a fusion's is "fusion"
    result: str    # the result's type, layout included
    bytes: int     # of the result (a tuple's: of its elements)
    vertex: str    # "unscoped" where no tz. scope; "executor" for its own
    part: str      # tie | apply | join; "fence", "sync.<kind>"; "" unscoped
    mixed: tuple   # a fusion's other owners ("vertex/part"), its root's left out


def op_name_of(line: str) -> str:
    """An instruction line's ``metadata={op_name="..."}``, ``""`` without."""
    m = _OP_NAME.search(line)
    return m.group(1) if m else ""


def type_bytes(result: str) -> int:
    """Bytes of an HLO result type: ``bf16[8,128]{1,0}`` -> 2048; a tuple's
    is the sum over its arrays; a token or an opaque type holds none."""
    total = 0
    for dtype, dims in _ARRAY.findall(result):
        m = _BITS.match(dtype)
        if dtype != "pred" and not m:
            continue  # token[], opaque[]
        bits = 8 if dtype == "pred" else int(m.group(1))  # s4: half a byte
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += (n * bits + 7) // 8
    return total


def scopes_of_text(text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over the whole text: the join of a
    trace's event names against the executable that ran, where the events
    carry no scope of their own."""
    out = {}
    for lines in computations(text).values():
        for line in lines:
            m, op_name = _INSTRUCTION.match(line), op_name_of(line)
            if m and op_name:
                out[m.group(1)] = op_name
    return out


def _opcode(line: str) -> Optional[str]:
    m = _INSTRUCTION.match(line)
    return m.group(3) if m else None


def _called(line: str, keys) -> List[str]:
    names = []
    for key in keys:
        m = re.search(key + r"=\{?([^,}\s]+(?:,\s*[^,}\s]+)*)\}?", line)
        if m:
            names += [n.strip().lstrip("%") for n in m.group(1).split(",")]
    return names


def _loop_lines(comps: Dict[str, List[str]], comp: str, inside: bool,
                seen: set) -> Iterator[str]:
    """The instruction lines inside ``comp``'s ``while`` loops (of ``comp``
    itself once ``inside``), nested loops, calls and branches walked."""
    if comp in seen:
        return
    seen.add(comp)
    for line in comps.get(comp, []):
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        keys = _CALLERS.get(m.group(3))
        if keys:
            for c in _called(line, keys):
                yield from _loop_lines(
                    comps, c, inside or m.group(3) == "while", seen)
        elif inside and m.group(3) not in _FREE:
            yield line


def _entry(text: str) -> Optional[str]:
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = _COMPUTATION.match(line)
            return m.group(1) if m else None
    return None


def _label(owner: Optional[Tuple[str, str]]) -> str:
    return "/".join(owner) if owner else UNSCOPED


def loop_ops_by_scope(text: str) -> List[ScopedOp]:
    """Every instruction that moves data in the ``while`` loops of
    ``text``'s entry computation (the repeat-n loop; loops, calls and
    branches nested in it are walked, the free instructions left out), each
    with its owner by the executor's scopes.  A fusion XLA made across
    owners goes to its root's, with the others listed as ``mixed``; an
    instruction with no ``tz.`` scope is XLA's own (``vertex``
    ``"unscoped"``): the loop's counter and carry, a relayout ``copy``."""
    comps = computations(text)
    out = []
    for line in _loop_lines(comps, _entry(text), False, set()):
        name, result, opcode = _INSTRUCTION.match(line).groups()
        owner = owner_of(op_name_of(line))
        mixed: tuple = ()
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if opcode == "fusion" and called:
            inner = [
                (l.lstrip().startswith("ROOT "), owner_of(op_name_of(l)))
                for l in comps.get(called.group(1), [])
                if _opcode(l) not in (None,) + _FREE]
            root = next((o for is_root, o in inner if is_root and o), None)
            owner = root or owner
            mixed = tuple(sorted({_label(o) for _, o in inner
                                  if o and o != owner}))
        vertex, part = owner if owner else (UNSCOPED, "")
        out.append(ScopedOp(name, opcode, result, type_bytes(result),
                            vertex, part, mixed))
    return out
