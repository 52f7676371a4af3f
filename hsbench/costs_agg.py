"""Least bytes of one call of a scan-and-aggregate program, from the call's
own operand shapes: the resident columns it takes in, each read once. Kept
with the yardstick, like ``costs.py``, so that no PR which speeds a program up
can also change what it is measured against; it knows no program by name and
no column: whatever implements the aggregate, a call has to read every value
of every column that is handed to it, and the group table it writes is a few
hundred bytes and counts for nothing.

A call is given as the HLO instruction texts of the operations that ran
inside it (the events of the device plane's ``XLA Ops`` line, each
``%name = shape kind(shape %operand, ...)``). Its inputs are the operands no
operation of the call produced: not the result of an operation that ran, and
not named after an instruction that moves no data and so has no event
(``%get-tuple-element.7``, an output of a fusion that ran; ``%bitcast.2``):
what is left are the program's parameters, whatever the front end called
them. The resident columns among them are the one-dimensional ones of the
longest length (the scan's rows, padded as the program holds them); literals
and the row count are scalars. On the TPU a 64-bit column is first split
into two 32-bit halves by operations of the call (``X64SplitLow/High``):
the column, 8 bytes a row, is what the call takes in."""

import re

from hsbench.costs import _BYTES

#: instructions of a call that the device plane gives no event: their results
#: are the call's own, not its inputs
_NO_EVENT = ("get-tuple-element", "bitcast", "tuple", "constant")

_OPERAND = re.compile(
    r"\b(pred|[su](?:8|16|32|64)|f(?:16|32|64)|bf16)\[([0-9,]*)\](?:\{[^}]*\})?\s+(%[\w.\-]+)")


def call_inputs(op_texts) -> dict:
    """``{operand name: (dtype, dims)}`` of what the call's operations read
    and none of them wrote."""
    produced, read = set(), {}
    for text in op_texts:
        head, sep, rest = text.partition(" = ")
        if not sep:
            continue
        produced.add(head.strip())
        for dtype, dims, name in _OPERAND.findall(rest):
            read[name] = (dtype, tuple(int(d) for d in dims.split(",") if d))
    return {n: s for n, s in read.items()
            if n not in produced and n.lstrip("%").rsplit(".", 1)[0] not in _NO_EVENT}


def resident_columns(op_texts) -> list:
    """``[(dtype, rows)]`` of the call's column inputs."""
    vectors = [(dtype, dims[0]) for dtype, dims in call_inputs(op_texts).values() if len(dims) == 1]
    rows = max((n for _, n in vectors), default=0)
    return [(dtype, n) for dtype, n in vectors if n == rows and rows > 1]


def call_least_bytes(op_texts) -> int:
    """Rows times the item sizes of the resident columns the call takes."""
    return sum(_BYTES[dtype] * rows for dtype, rows in resident_columns(op_texts))
