"""Share of the chips' busy time that operations of one kind took: seconds in
which an operation with the HLO opcode ``<op>`` ran (``all-to-all`` also finds
``all-to-all-start``/``-done``) over seconds in which any operation ran, both
as unions of the ``XLA Ops`` intervals and summed over the device planes.
Nothing from a trace with fewer than two device planes (a collective has no
meaning on one chip) or without such an operation. Percent."""

from hsbench import tracing
from hsbench.layers import plane_busy_spread


def is_op(name: str, op: str) -> bool:
    """``%all_to_all.3 = s32[...] all-to-all(s32[...] %copy.9), ...``: by the
    instruction's opcode, not by its name (the TPU compiler writes the name
    with underscores), and not a fusion that has the operation among its
    operands. A bare name with no ``=`` is matched with ``-`` and ``_`` alike."""
    head, _, rest = name.partition(" = ")
    if rest:
        opcode = tracing.op_label(name).rsplit(" ", 1)[-1]
    else:
        opcode = head.lstrip("%").split(".")[0].replace("_", "-")
    return opcode == op or opcode.startswith(op + "-")


def op_seconds_by_plane(planes: dict, op: str) -> dict:
    """``{device plane: (seconds of the operation, calls of it)}``, for every
    device plane that ran any operation."""
    out = {}
    for p in tracing.device_planes(planes):
        ops = planes[p].get(tracing.OPS_LINE, [])
        if ops:
            mine = [(s, s + d) for name, s, d in ops if is_op(name, op)]
            out[p] = (sum(e - s for s, e in tracing.union(mine)) / 1e9, len(mine))
    return out


def read(run, params):
    if run.planes is None:
        return None
    per_plane = op_seconds_by_plane(run.planes, params["op"])
    mine = sum(m for m, _ in per_plane.values())
    if len(per_plane) < 2 or not mine:
        return None
    return 100.0 * mine / sum(plane_busy_spread.plane_busy_seconds(run.planes).values())
