"""Collective operations in a profiler trace, named from the HLO.

On a TPU each event of a device's ``XLA Ops`` line is named with its HLO
instruction, ``%psum.7 = f32[1024,4,3]{...} all-reduce(...), ...``: the
instruction's name follows the program (JAX names it after the
primitive), its opcode follows the compiler.  An operation is a
collective here when its opcode is one of :data:`OPCODES` or starts with
one followed by ``-`` (the ``-start`` / ``-done`` halves of an
asynchronous one).
"""
from __future__ import annotations

import functools
import re
from typing import Sequence

from chipbench import trace as _trace

#: the collectives the mesh engine's programs lower to: the draw's psum
#: and the degree ring's ppermute
OPCODES = ("all-reduce", "collective-permute")

# the opcode: the first word followed by "(" after the result shape
_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of an operation event's name, or '' without one."""
    m = _OPCODE.search(name)
    return m.group(1) if m else ""


@functools.lru_cache(maxsize=4096)
def is_collective(name: str) -> bool:
    """True when the event's HLO opcode is one of :data:`OPCODES` (kept
    per name: a window repeats a few hundred names millions of times)."""
    op = opcode(name)
    return any(op == c or op.startswith(c + "-") for c in OPCODES)


def time(events: Sequence[_trace.Event], lo: float, hi: float) -> float:
    """Time in [lo, hi] covered by the collective operations among one
    device's ``events``: the union of their intervals."""
    return _trace.covered(_trace.merge(
        [(s, e) for name, s, e in events if is_collective(name)]), lo, hi)
