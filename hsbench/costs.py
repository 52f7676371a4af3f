"""Least work of the kernels whose roofline share the benchmark reports,
computed from shapes. Kept with the yardstick so that no PR which speeds a
kernel up can also change what it is measured against."""

import re

_SHAPE = re.compile(r"\b([su](?:8|16|32|64)|f(?:16|32|64)|bf16|pred)\[([0-9,]*)\]")
_BYTES = {"s8": 1, "u8": 1, "pred": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def shapes_in(hlo_text: str) -> list:
    """``[(dtype, (dims...)), ...]`` in the order they appear in an HLO line."""
    out = []
    for dtype, dims in _SHAPE.findall(hlo_text):
        out.append((dtype, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _nbytes(dtype, dims) -> int:
    n = _BYTES[dtype]
    for d in dims:
        n *= d
    return n


def hist_least_bytes(hlo_text: str) -> int:
    """The bucket histogram reads every bucket id once and writes one count
    per bucket: bytes of the call's operand plus bytes of its result, both
    taken from the custom call's own HLO line (result first, operands after).
    It does no arithmetic worth counting, so HBM bytes bound it."""
    # the attributes after the target repeat the operand's shape
    shapes = shapes_in(hlo_text.split("custom_call_target")[0])
    if len(shapes) < 2:
        raise ValueError(f"no result and operand shapes in {hlo_text[:120]!r}")
    return sum(_nbytes(*s) for s in shapes)
