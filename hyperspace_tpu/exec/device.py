"""TPU device execution path.

The two accelerated physical patterns (the ones the optimizer rewrites plans
into — SURVEY.md §3.2):

  1. ``Filter`` over an ``IndexScan``/``FileScan`` — the predicate tree is
     compiled to a jitted jnp program evaluated over encoded device columns,
     sharded row-wise over the session mesh (replaces Spark's
     per-bucket-parquet-scan + codegen'd filter; ref:
     HS/index/covering/FilterIndexRule.scala:144-194).
  2. Bucketed equi-``Join`` of two compatible ``IndexScan``s — both sides are
     pre-bucketed and pre-sorted on the join keys, so the join runs per-bucket
     with **no collectives**: a shard_map over the bucket axis where each
     device merge-joins its co-located buckets via two vmapped searchsorted
     passes (replaces Spark's exchange-free sort-merge join; ref:
     HS/index/covering/JoinIndexRule.scala:604-618).

Strings are dictionary-encoded host-side (exec/batch.py docstring); predicate
literals are translated into code-space via the sorted dictionary, so <, <=,
=, >=, > on strings all lower to integer compares on device.

Anything the device path cannot express raises ``DeviceUnsupported`` and the
host executor (exec/executor.py) runs the plan instead — mirroring how
``ApplyHyperspace`` never fails a query (ref: HS/index/rules/ApplyHyperspace.scala:59-63).
"""

from __future__ import annotations

import contextlib
import os
from functools import lru_cache, partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

from hyperspace_tpu.utils.x64 import ensure_x64

import numpy as np

from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.file_identity import committed_keys, plan_identity
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import (
    BinaryOp,
    Case,
    Col,
    Expr,
    In,
    InputFileName,
    IsNull,
    Lit,
    Not,
    extract_equi_join_keys,
)


class DeviceUnsupported(Exception):
    """Raised when an expression/plan shape cannot run on the device path."""


class ResidentOverCap(DeviceUnsupported):
    """A scan's columns would not fit the device column cache's budget
    (conf ``hyperspace.tpu.query.deviceCacheBytes``): they could not stay
    resident, so the resident aggregate tier leaves the query to the tiers
    behind it (the out-of-core stream, the host)."""


class GroupCapacityExceeded(DeviceUnsupported):
    """Observed group cardinality exceeds conf ``hyperspace.exec.agg.maxGroups``
    — the caller spills to the host hash-combine path (the accumulated device
    partial stays valid; see ``GroupedAggStream.to_partial_frame``)."""


# --------------------------------------------------------------------------
# column encoding
# --------------------------------------------------------------------------


class ColumnCodec:
    """How one host column was encoded for the device.

    kind:
      - "numeric":  the device holds the column's values as int64/float64
                    (bool and narrower integers widen to int64)
      - "datetime": the device holds the int64 epoch view; ``unit`` remembers
                    the datetime64 unit for literal conversion
      - "string":   device array is int32 codes into ``uniques`` (sorted);
                    code -1 encodes null

    An 8-byte column is one device array where the devices compute 64-bit
    values natively, and its two 32-bit planes (:class:`ColumnPlanes`) where
    they compute them as pairs; a program sees the 64-bit values either way
    (:func:`join_columns`).

    ``dtype`` is the host column's dtype before encoding, and ``nulls`` says
    whether a string column holds a null code; both None where the encoder
    did not look. They let a program over a RESIDENT column (no host batch
    at hand) give its result the host path's dtype, and size a dictionary
    key's domain.
    """

    def __init__(self, kind: str, uniques: Optional[np.ndarray] = None, unit: Optional[str] = None,
                 dtype=None, nulls: Optional[bool] = None):
        self.kind = kind
        self.uniques = uniques
        self.unit = unit
        self.dtype = dtype
        self.nulls = nulls


def encode_column(arr: np.ndarray) -> Tuple[np.ndarray, ColumnCodec]:
    # already-device-dtype columns pass through uncopied (asarray/view, not
    # astype): the native decode fast path hands us prefix views of padded
    # buffers, and a copy here would break the zero-copy staging handoff
    kind = arr.dtype.kind
    if kind in ("i", "u", "b"):
        return np.asarray(arr, dtype=np.int64), ColumnCodec("numeric", dtype=arr.dtype)
    if kind == "f":
        return np.asarray(arr, dtype=np.float64), ColumnCodec("numeric", dtype=arr.dtype)
    if kind == "M":
        unit = np.datetime_data(arr.dtype)[0]
        return arr.view("int64"), ColumnCodec("datetime", unit=unit, dtype=arr.dtype)
    if kind in ("U", "S", "O"):
        from hyperspace_tpu.ops.encode import factorize_strings

        codes, uniques, _ = factorize_strings(arr)
        return codes.astype(np.int32), ColumnCodec(
            "string", uniques=uniques, dtype=arr.dtype, nulls=bool((codes < 0).any())
        )
    raise DeviceUnsupported(f"unsupported column dtype {arr.dtype}")


# --------------------------------------------------------------------------
# the resident form of an 8-byte column
#
# A TPU has no 64-bit arithmetic: its compiler rewrites a float64 into a pair
# of float32 (a head, and the tail the head's rounding left) and an int64 into
# its low and high 32 bits, and a program handed an 8-byte array begins by
# splitting it, two passes over HBM a column and a call. A resident column is
# an index file's, the same in every call, so it is split once, by the chip
# itself when it is uploaded (``split-planes``), and stays resident as the two
# planes; each program joins them at its entry (:func:`join_columns`), which
# the same rewrite reduces to a few 32-bit operations fused into whatever
# reads the value. Where the devices compute 64-bit values natively (the CPU)
# a column stays whole: an f32 pair holds 48 of a float64's 53 bits.
# --------------------------------------------------------------------------


class ColumnPlanes(NamedTuple):
    """An int64 or float64 column as two one-dimensional 32-bit device arrays
    of its (padded) row length, sharded as the column would be. float64:
    ``first`` is the float32 head, ``second`` the float32 tail, the value
    their sum. int64: ``first`` is the low 32 bits (uint32), ``second`` the
    high 32 (int32). ``shape`` and ``dtype`` are the column's own."""

    first: "jax.Array"
    second: "jax.Array"

    @property
    def shape(self):
        return self.first.shape

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(np.float64 if self.first.dtype == np.float32 else np.int64)


def computes_in_pairs(mesh) -> bool:
    """Whether ``mesh``'s devices compute 64-bit values as 32-bit pairs."""
    return mesh.devices.flat[0].platform == "tpu"


def split_planes(x) -> ColumnPlanes:
    """The planes of an int64 or float64 array; traced, the body of the
    ``split-planes`` program. A float's head is its float32 rounding and its
    tail what that left, so that on a device that computes in pairs they are
    the pair it already holds. A head that is not finite (NaN, an infinity,
    a value beyond float32's range) takes a zero tail where ``inf - inf``
    would make the sum NaN, and a zero head gives its tail its sign, so that
    -0.0 comes back."""
    import jax.numpy as jnp

    if jnp.issubdtype(x.dtype, jnp.floating):
        head = x.astype(jnp.float32)
        tail = (x - head.astype(jnp.float64)).astype(jnp.float32)
        tail = jnp.where(jnp.isfinite(head), jnp.where(head == 0, head, tail), jnp.float32(0))
        return ColumnPlanes(head, tail)
    return ColumnPlanes(x.astype(jnp.uint32), (x >> 32).astype(jnp.int32))


def join_planes(column):
    """The 64-bit values of a :class:`ColumnPlanes` (traced); any other
    device column as it is."""
    import jax.numpy as jnp

    if not isinstance(column, ColumnPlanes):
        return column
    first, second = column
    if first.dtype == jnp.float32:
        return first.astype(jnp.float64) + second.astype(jnp.float64)
    return (second.astype(jnp.int64) << 32) | first.astype(jnp.int64)


def join_columns(cols):
    """``cols`` (name -> device column) with every :class:`ColumnPlanes`
    joined: called at the entry of each program over resident columns, so
    that everything behind it sees 64-bit values whatever the resident form."""
    return {c: join_planes(v) for c, v in cols.items()}


def _literal_bounds(codec: ColumnCodec, value) -> Tuple[int, int]:
    """(lo, hi) code bounds of a literal in a string dictionary:
    col == lit ⇔ lo <= code < hi;  col < lit ⇔ code < lo;  col <= lit ⇔ code < hi."""
    lo = int(np.searchsorted(codec.uniques, str(value), side="left"))
    hi = int(np.searchsorted(codec.uniques, str(value), side="right"))
    return lo, hi


def _literal_numeric(codec: ColumnCodec, value):
    if codec.kind == "datetime":
        return int(np.datetime64(value, codec.unit).view("int64"))
    if isinstance(value, (bool, np.bool_)):
        return int(value)
    return value


# --------------------------------------------------------------------------
# predicate compiler: Expr tree -> jnp program over encoded columns
#
# Literal values (and string-dictionary code bounds, which change per batch)
# are *runtime arguments* of the compiled program, not trace-time constants,
# so two queries that differ only in their constants (or dictionaries) hit
# the same XLA executable. The jitted program is cached per predicate
# *skeleton* (structure + column kinds, no literal values).
# --------------------------------------------------------------------------

_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "=", "!=": "!="}


class _LitSlots:
    """Collects literal values during compilation; each gets a slot index in
    the ``lits`` tuple passed to the compiled program at call time."""

    def __init__(self, base: int = 0):
        self.base = base  # slots of ``lits`` another compile already took
        self.values: List = []

    def add(self, value) -> int:
        self.values.append(value)
        return self.base + len(self.values) - 1


def predicate_skeleton(expr: Expr, codecs: Dict[str, ColumnCodec]) -> str:
    """Canonical structure of ``expr`` with literal *values* erased — the
    cache key for the jitted program (literals are runtime args)."""

    def lit_tag(v) -> str:
        if isinstance(v, str):
            return "s"
        if isinstance(v, (bool, np.bool_)):
            return "b"
        if isinstance(v, (int, np.integer)):
            return "i"
        if isinstance(v, np.datetime64):
            return "d"
        return "f"

    def rec(e: Expr) -> str:
        if isinstance(e, Col):
            return f"c:{e.name}:{codecs[e.name].kind if e.name in codecs else '?'}"
        if isinstance(e, Lit):
            return f"l:{lit_tag(e.value)}"
        if isinstance(e, BinaryOp):
            return f"({rec(e.left)}{e.op}{rec(e.right)})"
        if isinstance(e, Not):
            return f"!({rec(e.child)})"
        if isinstance(e, IsNull):
            return f"isnull({rec(e.child)})"
        if isinstance(e, In):
            return f"in({rec(e.child)},[{','.join(rec(v) for v in e.values)}])"
        if isinstance(e, InputFileName):
            return "input_file_name()"
        return f"{type(e).__name__}({','.join(rec(c) for c in e.children())})"

    return rec(expr)


def _const_subtree(e: Expr) -> bool:
    if isinstance(e, Lit):
        return True
    if isinstance(e, BinaryOp) and e.op in ("+", "-", "*", "/", "%"):
        return _const_subtree(e.left) and _const_subtree(e.right)
    return False


def _fold_const(e: Expr) -> Expr:
    """Fold literal-only arithmetic on host: calendar-unit intervals
    (date '1994-01-01' + interval '1' year => timedelta64[M]) have no
    JAX dtype, but their folded result is a plain datetime scalar."""
    if isinstance(e, Lit) or not _const_subtree(e):
        return e
    v = e.eval({})
    arr = np.asarray(v)
    return Lit(arr.reshape(-1)[0] if arr.ndim else arr[()])


def in_device_language(e: Expr) -> bool:
    """Whether every node of ``e`` is one the compilers below know: columns,
    literals, arithmetic, compares, AND/OR/NOT, IS NULL, IN and CASE. What
    their types allow is the compilers' to say (DeviceUnsupported); this asks
    nothing of the data, so a tier can turn a ``LIKE``, a cast or a function
    away before it reads a file."""
    if not isinstance(e, (Col, Lit, BinaryOp, Not, IsNull, In, Case)):
        return False
    return all(in_device_language(c) for c in e.children())


def _build_num(e: Expr, codecs: Dict[str, ColumnCodec], slots: "_LitSlots", datetimes: bool = True):
    """Numeric-valued subexpression -> device fn ``f(cols, lits)``; its
    literals take slots of ``slots``. The one numeric expression compiler:
    predicates (``compile_predicate``) and computed aggregate inputs
    (``compile_computes``) build through it. ``datetimes``: whether a datetime
    column may stand as a value (its int64 epoch view): a compare's side may,
    a computed input may not (a per-column unit has no arithmetic).

    ``CASE WHEN c THEN v ... [ELSE d] END`` is a chain of selects, the first
    condition that is definitely true winning as on the host (``np.select``
    over ``as_bool_mask``): the conditions are boolean expressions of the
    predicate compiler, string equality over dictionary codes included, and
    may read datetimes whatever ``datetimes`` says of the values. Without an
    ELSE the default is NULL, a float NaN, which the sums and counts skip."""
    e = _fold_const(e)
    if isinstance(e, Col):
        codec = codecs[e.name]
        if codec.kind == "string":
            raise DeviceUnsupported("string column used in numeric context")
        if codec.kind == "datetime" and not datetimes:
            raise DeviceUnsupported(f"datetime column {e.name!r} used as a number")
        name = e.name
        return lambda cols, lits: cols[name]
    if isinstance(e, Lit):
        v = e.value
        if v is None:
            raise DeviceUnsupported("NULL literal in numeric context")
        if isinstance(v, str):
            raise DeviceUnsupported("string literal in numeric context")
        if isinstance(v, np.datetime64):
            v = int(v.view("int64"))
        i = slots.add(_as_lit_scalar(v))
        return lambda cols, lits: lits[i]
    if isinstance(e, BinaryOp) and e.op in ("+", "-", "*", "/", "%"):
        lf, rf = _build_num(e.left, codecs, slots, datetimes), _build_num(e.right, codecs, slots, datetimes)
        op = e.op
        def f(cols, lits):
            l, r = lf(cols, lits), rf(cols, lits)
            if op == "+":
                return l + r
            if op == "-":
                return l - r
            if op == "*":
                return l * r
            if op == "/":
                return l / r
            return l % r
        return f
    if isinstance(e, Case):
        import jax.numpy as jnp

        build_bool = _bool_builder(codecs, slots)
        branches = [(build_bool(c), _build_num(v, codecs, slots, datetimes)) for c, v in e.branches]
        otherwise = e.otherwise
        if isinstance(otherwise, Lit) and otherwise.value is None:
            otherwise = None  # ELSE NULL is no ELSE
        default = _build_num(otherwise, codecs, slots, datetimes) if otherwise is not None else None

        def case(cols, lits):
            out = default(cols, lits) if default is not None else jnp.float64(np.nan)
            for (value, unknown), then in reversed(branches):  # the first true condition wins
                out = jnp.where(value(cols, lits) & ~unknown(cols, lits), then(cols, lits), out)
            return out

        return case
    raise DeviceUnsupported(f"unsupported numeric expr {type(e).__name__}")


def compile_computes(computes, codecs: Dict[str, ColumnCodec], lit_base: int = 0):
    """Compile computed aggregate inputs, ``(name, expr)`` pairs of a
    ``Compute`` node between an ``Aggregate`` and its scan, into ``(f,
    lit_values, skeleton)``: ``f(cols, lits)`` returns ``cols`` with every
    computed column added, to run inside an aggregate program. Literal slots
    start at ``lit_base``, behind the predicate's, so one ``lits`` tuple
    serves both. Numeric values only: a datetime value has a per-column
    epoch unit and a string none (DeviceUnsupported); the conditions of a
    ``CASE`` may read both."""
    slots = _LitSlots(lit_base)
    codecs = dict(codecs)
    fns, parts = [], []
    for name, expr in computes:
        for r in expr.references():
            if r not in codecs:
                raise DeviceUnsupported(f"computed input {name!r} over unknown column {r!r}")
        fns.append((name, _build_num(expr, codecs, slots, datetimes=False)))
        parts.append(f"{name}={predicate_skeleton(expr, codecs)}")
        codecs[name] = ColumnCodec("numeric")

    def fn(cols, lits):
        out = dict(cols)
        for name, f in fns:
            out[name] = f(out, lits)
        return out

    return fn, tuple(slots.values), ";".join(parts)


def _bool_builder(codecs: Dict[str, ColumnCodec], slots: "_LitSlots"):
    """``build_bool`` of the predicate compiler over ``codecs``, its literals
    taking slots of ``slots``: ``build_bool(expr)`` gives the ``(value,
    unknown)`` Kleene pair of a boolean expression. ``compile_predicate``
    keeps the definitely-true rows of one; a ``CASE`` of ``_build_num`` asks
    it for its conditions."""
    import jax.numpy as jnp

    def is_string_col(e: Expr) -> bool:
        return isinstance(e, Col) and codecs[e.name].kind == "string"

    def _has_datetime(e: Expr) -> bool:
        if isinstance(e, Col):
            return codecs[e.name].kind == "datetime"
        if isinstance(e, Lit):
            return isinstance(e.value, (np.datetime64, np.timedelta64))
        return any(_has_datetime(c) for c in e.children())

    def build_num(e: Expr):
        return _build_num(e, codecs, slots)

    # Boolean subtrees compile to (value, unknown) Kleene pairs so NULL stays
    # three-valued on device exactly as on host (expr.NullableBool): a NULL
    # operand makes the comparison unknown — in particular NULL != x and
    # NOT(NULL = x) must not come out true. The top level keeps definite-TRUE
    # rows only (value & ~unknown).
    def string_compare(col: Col, op: str, lit_value):
        codec = codecs[col.name]
        if codec.kind != "string" or not isinstance(lit_value, str):
            # mixed-type compares have host-defined semantics; don't guess
            raise DeviceUnsupported("string compare requires string column and string literal")
        lo_v, hi_v = _literal_bounds(codec, lit_value)
        lo = slots.add(np.int32(lo_v))
        hi = slots.add(np.int32(hi_v))
        name = col.name
        unknown = lambda cols, lits: cols[name] < 0  # null code is -1
        if op == "=":
            return (lambda cols, lits: (cols[name] >= lits[lo]) & (cols[name] < lits[hi]), unknown)
        if op == "!=":
            return (lambda cols, lits: (cols[name] < lits[lo]) | (cols[name] >= lits[hi]), unknown)
        if op == "<":
            return (lambda cols, lits: cols[name] < lits[lo], unknown)
        if op == "<=":
            return (lambda cols, lits: cols[name] < lits[hi], unknown)
        if op == ">":
            return (lambda cols, lits: cols[name] >= lits[hi], unknown)
        if op == ">=":
            return (lambda cols, lits: cols[name] >= lits[lo], unknown)
        raise DeviceUnsupported(f"unsupported string compare {op}")

    def _num_unknown(x):
        return jnp.isnan(x) if jnp.issubdtype(x.dtype, jnp.floating) else jnp.zeros(jnp.shape(x), bool)

    _NAT = np.iinfo(np.int64).min  # NaT under the int64 epoch view

    def num_unknown_expr(e: Expr):
        """Missing-value mask of a numeric-valued subexpression: NaN for
        float columns, NaT (INT64_MIN epoch view) for datetime columns,
        propagated through arithmetic."""
        if isinstance(e, Col):
            codec = codecs[e.name]
            name = e.name
            if codec.kind == "datetime":
                return lambda cols, lits: cols[name] == _NAT
            return lambda cols, lits: _num_unknown(cols[name])
        if isinstance(e, BinaryOp) and e.op in ("+", "-", "*", "/", "%"):
            lu, ru = num_unknown_expr(e.left), num_unknown_expr(e.right)
            return lambda cols, lits: lu(cols, lits) | ru(cols, lits)
        return lambda cols, lits: jnp.zeros((), bool)

    def _compare(lf, rf, op: str, lu=None, ru=None):
        def value(cols, lits):
            l, r = lf(cols, lits), rf(cols, lits)
            if op == "=":
                return l == r
            if op == "!=":
                return l != r
            if op == "<":
                return l < r
            if op == "<=":
                return l <= r
            if op == ">":
                return l > r
            return l >= r

        def unknown(cols, lits):
            u = _num_unknown(lf(cols, lits)) | _num_unknown(rf(cols, lits))
            if lu is not None:
                u = u | lu(cols, lits)
            if ru is not None:
                u = u | ru(cols, lits)
            return u

        return value, unknown

    def build_bool(e: Expr):
        if isinstance(e, BinaryOp) and e.op in ("AND", "OR"):
            (lv, lu), (rv, ru) = build_bool(e.left), build_bool(e.right)
            if e.op == "AND":
                # unknown unless either side is definitely false
                return (
                    lambda cols, lits: lv(cols, lits) & rv(cols, lits),
                    lambda cols, lits: (lu(cols, lits) | ru(cols, lits))
                    & ~(~lv(cols, lits) & ~lu(cols, lits))
                    & ~(~rv(cols, lits) & ~ru(cols, lits)),
                )
            return (
                lambda cols, lits: (lv(cols, lits) & ~lu(cols, lits))
                | (rv(cols, lits) & ~ru(cols, lits)),
                lambda cols, lits: (lu(cols, lits) | ru(cols, lits))
                & ~(lv(cols, lits) & ~lu(cols, lits))
                & ~(rv(cols, lits) & ~ru(cols, lits)),
            )
        if isinstance(e, Not):
            cv, cu = build_bool(e.child)
            return (lambda cols, lits: ~cv(cols, lits), cu)
        if isinstance(e, IsNull):
            c = e.child
            if isinstance(c, Col):
                codec = codecs[c.name]
                name = c.name
                no_unknown = lambda cols, lits: jnp.zeros(cols[name].shape, bool)
                if codec.kind == "string":
                    return (lambda cols, lits: cols[name] < 0, no_unknown)
                if codec.kind == "numeric":
                    return (
                        lambda cols, lits: jnp.isnan(cols[name])
                        if cols[name].dtype == jnp.float64
                        else jnp.zeros(cols[name].shape, bool),
                        no_unknown,
                    )
                if codec.kind == "datetime":  # NaT under the int64 epoch view
                    nat = np.iinfo(np.int64).min
                    return (lambda cols, lits: cols[name] == nat, no_unknown)
                return (lambda cols, lits: jnp.zeros(cols[name].shape, bool), no_unknown)
            raise DeviceUnsupported("IS NULL on non-column")
        if isinstance(e, In):
            child = e.child
            if not isinstance(child, Col):
                raise DeviceUnsupported("IN on non-column")
            values = [v.value for v in e.values]
            if not values:
                raise DeviceUnsupported("empty IN list")
            if any(v is None or (isinstance(v, float) and v != v) for v in values):
                # NULL in the list makes non-matches unknown (host
                # _in_semantics); keep that shape host-side
                raise DeviceUnsupported("NULL literal in IN list")
            if is_string_col(child):
                if not all(isinstance(v, str) for v in values):
                    raise DeviceUnsupported("mixed-type IN on string column")
            elif any(isinstance(v, str) for v in values):
                raise DeviceUnsupported("string IN value on non-string column")
            terms = []
            for val in values:
                if is_string_col(child):
                    terms.append(string_compare(child, "=", val))
                else:
                    cf = build_num(child)
                    num = _literal_numeric(codecs[child.name], val)
                    i = slots.add(_as_lit_scalar(num))
                    terms.append(
                        _compare(cf, lambda cols, lits, i=i: lits[i], "=", lu=num_unknown_expr(child))
                    )

            def value(cols, lits):
                m = terms[0][0](cols, lits)
                for tv, _ in terms[1:]:
                    m = m | tv(cols, lits)
                return m

            return value, terms[0][1]  # all terms share the child's null mask
        if isinstance(e, BinaryOp) and e.op in ("=", "!=", "<", "<=", ">", ">="):
            left, right, op = e.left, e.right, e.op
            # fold literal-only sides FIRST so a folded datetime constant
            # takes the Col-vs-Lit path below, where _literal_numeric
            # converts it to the column codec's epoch unit
            left, right = _fold_const(left), _fold_const(right)
            # normalize: Col OP Lit
            if isinstance(right, Col) and isinstance(left, Lit):
                left, right, op = right, left, _FLIP[op]
            if isinstance(left, Col) and isinstance(right, Lit):
                codec = codecs[left.name]
                if codec.kind == "string" or isinstance(right.value, str):
                    if codec.kind != "string":
                        raise DeviceUnsupported("string literal vs non-string column")
                    return string_compare(left, op, right.value)
                lf = build_num(left)
                val = _literal_numeric(codec, right.value)
                i = slots.add(_as_lit_scalar(val))
                return _compare(lf, lambda cols, lits: lits[i], op, lu=num_unknown_expr(left))
            # general numeric compare (col-vs-col, arithmetic): two datetime
            # columns of one unit compare as their epoch views (NaT on either
            # side is unknown); any other datetime operand has a per-column
            # epoch unit the generic path cannot reconcile — reject rather
            # than compare mismatched units
            same_unit = (
                isinstance(left, Col) and isinstance(right, Col)
                and codecs[left.name].kind == codecs[right.name].kind == "datetime"
                and codecs[left.name].unit == codecs[right.name].unit
            )
            for side in (left, right):
                if _has_datetime(side) and not same_unit:
                    raise DeviceUnsupported("datetime arithmetic compare on device")
            return _compare(
                build_num(left), build_num(right), op,
                lu=num_unknown_expr(left), ru=num_unknown_expr(right),
            )
        if isinstance(e, InputFileName):
            raise DeviceUnsupported("input_file_name() is host-only")
        raise DeviceUnsupported(f"unsupported boolean expr {type(e).__name__}")

    return build_bool


def compile_predicate(expr: Expr, codecs: Dict[str, ColumnCodec], lit_base: int = 0):
    """Compile ``expr`` into ``(f, lit_values)`` where
    ``f(cols: dict[str, jnp.ndarray], lits: tuple) -> bool mask`` and
    ``lit_values`` is the concrete argument tuple for this query; its slots
    start at ``lit_base`` of the ``lits`` it is called with (a program with
    two predicates hands both one tuple).

    Raises DeviceUnsupported for shapes outside the device language (string
    arithmetic, input_file_name(), col-vs-col string compares, ...).
    """
    slots = _LitSlots(lit_base)
    vf, uf = _bool_builder(codecs, slots)(expr)

    def fn(cols, lits):
        return vf(cols, lits) & ~uf(cols, lits)

    return fn, tuple(slots.values)


def _as_lit_scalar(v):
    """Fix the dtype a literal is passed with (jit traces lits as 0-d arrays;
    a stable dtype per slot keeps the executable cache warm)."""
    if isinstance(v, (np.timedelta64, np.datetime64)):
        # calendar units have no JAX dtype; raise here (not deep inside jit
        # tracing, where the ValueError would escape the fallback machinery)
        raise DeviceUnsupported(f"literal dtype {type(v).__name__} not device-representable")
    if isinstance(v, np.generic):
        return v
    if isinstance(v, bool):
        return np.int64(v)
    if isinstance(v, int):
        return np.int64(v)
    if isinstance(v, (str, bytes)):
        raise DeviceUnsupported("string literal in numeric slot")
    return np.float64(v)


# --------------------------------------------------------------------------
# device filter
# --------------------------------------------------------------------------


def _pad_to_multiple(arr: np.ndarray, m: int, fill) -> np.ndarray:
    n = arr.shape[0]
    rem = (-n) % m
    if rem == 0:
        return arr
    pad = np.full((rem,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


# --------------------------------------------------------------------------
# geometric shape buckets: every jitted program here specializes per input
# SHAPE, so ad-hoc padding (next multiple of n_dev) turns a streamed scan's
# slightly-varying chunk sizes into one fresh XLA compile per chunk. Rounding
# shapes up to powers of sqrt(2) over a floor caps the distinct shapes any
# stream can produce at 2-3 (chunking targets equal byte sizes), at <= 41%
# memory overhead. Shared by the filter, aggregate, and bucketed-SMJ
# rectangle paths; hs_xla_compiles_total measures the effect.
# --------------------------------------------------------------------------

_BUCKET_FLOOR = 4096
_SQRT2 = 1.4142135623730951


def bucket_rows(n: int, floor: int = _BUCKET_FLOOR) -> int:
    """Smallest geometric shape bucket (powers of sqrt(2) over ``floor``)
    holding ``n`` rows."""
    b = floor
    while b < n:
        b = int(b * _SQRT2) + 1
    return b


def _pad_to_bucket(arr: np.ndarray, m: int, fill) -> np.ndarray:
    """Pad axis 0 to the shape bucket for len(arr), rounded up to a multiple
    of ``m`` (the device count) so sharding stays even.

    Zero-copy handoff: when ``arr`` is a prefix view of a buffer that is
    *already* exactly this padded shape — the native decode fast path
    (exec/io.py) allocates its per-column buffers that way — and the buffer's
    tail holds ``fill``, the base buffer is adopted as-is; ``device_put``
    then ships the very memory the C decoder wrote."""
    n = arr.shape[0]
    target = bucket_rows(n)
    target += (-target) % m
    if target == n:
        return arr
    base = arr.base
    if (
        arr.ndim == 1
        and isinstance(base, np.ndarray)
        and base.ndim == 1
        and base.shape[0] == target
        and base.dtype.itemsize == arr.dtype.itemsize
        and arr.__array_interface__["data"][0] == base.__array_interface__["data"][0]
    ):
        adopted = base if base.dtype == arr.dtype else base.view(arr.dtype)
        # the fast path pre-fills the tail, but a coincidentally-shaped slice
        # of someone else's buffer must not leak its tail garbage: verify
        tail = adopted[n:]
        ok = bool(np.isnan(tail).all()) if fill != fill else bool((tail == fill).all())
        if ok:
            return adopted
    pad = np.full((target - n,) + arr.shape[1:], fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


# own (program skeleton, input-shape signature) ledger: jax.jit compiles
# exactly once per such pair, so first-seen here == one XLA compilation.
# Survives clear_device_cache() because the jit caches do too.
import threading as _threading
import time as _ptime

from hyperspace_tpu.obs import spans as _obs_spans
from hyperspace_tpu.obs.metrics import REGISTRY as _REGISTRY

_COMPILE_SEEN: set = set()
_COMPILE_SEEN_LOCK = _threading.Lock()


def _note_compile(skeleton: str, sig) -> bool:
    """Record one (skeleton, signature) pair; True when first seen — i.e.
    the next invocation of the jitted program pays the XLA compile."""
    key = (skeleton, sig)
    with _COMPILE_SEEN_LOCK:
        if key in _COMPILE_SEEN:
            return False
        _COMPILE_SEEN.add(key)
    from hyperspace_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "hs_xla_compiles_total",
        "Distinct (device program skeleton, input shape) XLA compilations",
    ).inc()
    return True


def _observe_program(family: str, first_seen: bool, t0: float) -> None:
    """On a first-seen signature, the wall seconds since ``t0`` — which XLA
    compilation dominates — in ``hs_device_compile_seconds_total``. How long
    the host spent in the dispatch call is the ``device-launch`` span of
    :func:`launch`; how long the program RAN is the profiler's to say (module
    ``jit_hs_<family>``); how long the host waited for it is the
    ``device-wait`` span of :func:`fetch`."""
    if first_seen:
        from hyperspace_tpu.obs.metrics import REGISTRY

        REGISTRY.counter(
            "hs_device_compile_seconds_total",
            "cumulative wall seconds of first-seen (compiling) device "
            "program invocations, by program family",
            program=family,
        ).inc(max(0.0, _ptime.perf_counter() - t0))


# family -> (its hs_device_dispatches_total series, its executable's name on
# the profiler's device plane), held here so that a launch costs a dict read
# and one add, not a registry lookup
_LAUNCHES: dict = {}


def launch(program: str):
    """One dispatch of a jitted device program, round the jitted call:
    ``with launch(family): out = jitted(...)``. Counts it in
    ``hs_device_dispatches_total{program}``. Where a trace is current it is a
    ``device-launch`` span (cat ``device``, attr ``program``) on the tree of
    the request that dispatched — the host time of the dispatch call itself —
    and its profiler annotation names the executable as the device plane
    spells it and the request, ``hs:device:device-launch
    module=jit_hs_<family> request=<id>``: with :func:`fetch`'s annotation a
    reader of the trace gives every device program its request. With no trace
    current: the count, one contextvar read, the shared no-op manager."""
    entry = _LAUNCHES.get(program)
    if entry is None:
        entry = _LAUNCHES[program] = (
            _REGISTRY.counter(
                "hs_device_dispatches_total",
                "Jitted device-program dispatches, by program family",
                program=program,
            ),
            "jit_" + _hlo_lint.program_name(program),
        )
    entry[0].inc()
    return _obs_spans.request_span("device-launch", "device", entry[1], program=program)


def device_peak_bytes() -> Optional[int]:
    """The allocator's own high-water mark: the largest ``peak_bytes_in_use``
    of ``memory_stats()`` over this process's devices. None where the backend
    keeps no such statistic (the CPU backend), and in a process whose JAX
    backend is not up yet — reading a gauge must not be what claims the chip."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


_REGISTRY.gauge(
    "hs_device_peak_bytes",
    "High-water bytes in use on a device, from the allocator's memory_stats() "
    "at read time; absent where the backend reports none",
    fn=device_peak_bytes,
)


# -- the host-device link ----------------------------------------------------
# (direction, site) -> counter, held here so that a transfer costs a dict
# read and one add, not a registry lookup
_LINK_BYTES: dict = {}


def link_bytes(direction: str, site: str, nbytes: int) -> None:
    """Count ``nbytes`` crossing the host-device link at ``site``
    (``direction`` is ``"h2d"`` or ``"d2h"``): the ``nbytes`` of the array
    that crosses, padding included."""
    c = _LINK_BYTES.get((direction, site))
    if c is None:
        from hyperspace_tpu.obs.metrics import REGISTRY

        if direction == "h2d":
            c = REGISTRY.counter(
                "hs_h2d_bytes_total",
                "Bytes uploaded host to device (padding included), by call site",
                site=site,
            )
        else:
            c = REGISTRY.counter(
                "hs_d2h_bytes_total",
                "Bytes downloaded device to host (padding included), by call site",
                site=site,
            )
        _LINK_BYTES[(direction, site)] = c
    c.inc(nbytes)


def put(arr, site: str, sharding=None):
    """``jax.device_put`` with the upload counted under ``site``."""
    import jax

    dev = jax.device_put(arr) if sharding is None else jax.device_put(arr, sharding)
    link_bytes("h2d", site, int(arr.nbytes))
    return dev


def wait(dev, program: str):
    """Block on a device result that stays on the device: :func:`fetch`'s
    ``device-wait`` span without the download."""
    import jax

    with _obs_spans.request_span("device-wait", cat="device", program=program):
        jax.block_until_ready(dev)


def fetch(dev, site: str, program: str):
    """Block on a device result (an array, or a pytree of them) and download
    it: the wait is a ``device-wait`` span (attr ``program``: the family that
    produced ``dev``; its annotation names the request like :func:`launch`'s),
    the bytes count under ``site``."""
    import jax

    with _obs_spans.request_span("device-wait", cat="device", program=program):
        out = jax.device_get(dev)
    link_bytes("d2h", site, sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(out)))
    return out


# skeleton -> jitted predicate program; the jit object is reused across
# queries so only genuinely new predicate *structures* pay an XLA compile
from collections import OrderedDict as _OrderedDict

_PREDICATE_CACHE: "_OrderedDict[str, callable]" = _OrderedDict()
_PREDICATE_CACHE_MAX = 256

# (scan identity, column, mesh fingerprint) -> (device column, codec, n_rows):
# a row-sharded device array, or the ColumnPlanes of an 8-byte column where
# the devices compute in pairs; one form a platform, whichever tier uploaded.
# Index bucket files are immutable (versioned v__=N dirs), so predicate
# columns stay resident in HBM across queries — the survey's "index
# column-chunks resident in HBM" stance (SURVEY.md §3.2); only the first
# query on an index version pays the host->device transfer.
from hyperspace_tpu.utils.lru import BytesLRU

from hyperspace_tpu.config import DEFAULTS as _CONF_DEFAULTS, keys as _conf_keys

# until a session states its budget: the key's default (2 GiB, or what
# HS_DEVICE_CACHE_BYTES says)
_device_cache = BytesLRU(int(_CONF_DEFAULTS[_conf_keys.TPU_QUERY_DEVICE_CACHE_BYTES]))


def set_device_cache_bytes(n: int) -> None:
    """Record the conf-requested budget of the resident column cache
    (``hyperspace.tpu.query.deviceCacheBytes``; called on Session
    construction, most-recent-wins like the decode pool's width: the cache is
    the process's, as the device is). Entries beyond a smaller budget go with
    the next ``put``."""
    _device_cache.cap = int(n)


def device_cache_cap() -> int:
    """Bytes the resident column cache may hold."""
    return _device_cache.cap


def check_fits_device_cache(rows: int, n_columns: int) -> None:
    """Raises :class:`ResidentOverCap` when ``n_columns`` columns of ``rows``
    rows, at 8 bytes a value and padded to their shape bucket, outweigh the
    cache's budget: asked before a scan is read or uploaded to stay."""
    need = bucket_rows(rows) * 8 * n_columns
    if need > device_cache_cap():
        raise ResidentOverCap(
            f"{n_columns} columns of {rows} rows take {need} bytes, over the device cache's {device_cache_cap()}"
        )


_REGISTRY.gauge(
    "hs_device_cache_bytes",
    "Bytes the device-resident column cache holds (scan columns, join matrices)",
    fn=lambda: _device_cache.total_bytes,
)


def _device_cache_get(key):
    return _device_cache.get(key)


# "hit" / "miss" -> counter of resident-column lookups, held as _LINK_BYTES is
_COLUMN_LOOKUPS: dict = {}


def _count_lookups(result: str, n: int = 1) -> None:
    c = _COLUMN_LOOKUPS.get(result)
    if c is None:
        from hyperspace_tpu.obs.metrics import REGISTRY

        c = _COLUMN_LOOKUPS[result] = REGISTRY.counter(
            "hs_device_cache_lookups_total",
            "Lookups of a scan column in the device-resident column cache, by result",
            result=result,
        )
    c.inc(n)


# "planes" / "whole" -> counter of 64-bit columns handed to a program
_COLUMN_FORMS: dict = {}


def count_column_forms(cols) -> None:
    """Count the 64-bit columns among ``cols`` (the device columns one launch
    hands its program) in ``hs_device_program_columns_total{form}``:
    ``planes`` for a :class:`ColumnPlanes`, ``whole`` for an 8-byte array,
    which a device that computes in pairs splits again in every call. 4-byte
    dictionary codes are neither."""
    for v in cols:
        form = "planes" if isinstance(v, ColumnPlanes) else "whole" if v.dtype.itemsize == 8 else None
        if form is None:
            continue
        c = _COLUMN_FORMS.get(form)
        if c is None:
            c = _COLUMN_FORMS[form] = _REGISTRY.counter(
                "hs_device_program_columns_total",
                "64-bit resident columns handed to a device program, by the form they are resident in",
                form=form,
            )
        c.inc()


def resident_column(key, n: int):
    """The ``(device column, codec, rows)`` resident under column key ``key``
    (``(scan key, column, mesh fingerprint)``), or None when there is none
    or it holds other than ``n`` rows; None for a None key (a batch with no
    scan identity is never cached, and not counted). Every lookup counts in
    ``hs_device_cache_lookups_total{result}``: a miss is an upload, unless
    the predicate then proves to be outside the device language."""
    if key is None:
        return None
    cached = _device_cache.get(key)
    if cached is not None and cached[2] != n:
        cached = None
    _count_lookups("miss" if cached is None else "hit")
    return cached


def _device_cache_put(key, value, nbytes: int) -> None:
    # overwrite semantics matter: a stale same-key entry (e.g. rows changed)
    # must be replaced, not pinned. An evicted array is freed when the last
    # query that looked it up lets go of it, not under that query.
    cache = _device_cache
    before = cache.evictions
    cache.put(key, value, nbytes)
    if cache.evictions > before:
        _REGISTRY.counter(
            "hs_device_cache_evictions_total",
            "Entries the device-resident column cache evicted to stay inside its budget",
        ).inc(cache.evictions - before)


def clear_device_cache() -> None:
    _device_cache.clear()
    # the join rank cache short-circuits per-bucket key decodes, so it must
    # clear too or decode-count dispatch traces depend on run history
    _RANK_CACHE.clear()
    _REBUCKET_CACHE.clear()
    _CAP_HINT_MEMO.clear()


def purge_device_cache_files(paths) -> int:
    """Drop every resident device column whose scan covers any of ``paths``
    (data-version commit invalidation); returns entries removed.

    Cache keys are ``(scan_key, col, mesh_fp)`` where scan_key is a tuple of
    ``(path, size, mtime_ns)`` file triples (suffixed, where the read pruned,
    with the row groups it kept), so a purge scans those leading triples.
    """
    wanted = set(paths)
    if not wanted:
        return 0
    removed = 0
    for key in _device_cache.keys():
        scan_key = key[0]
        if not isinstance(scan_key, tuple):
            continue
        hit = any(
            isinstance(part, tuple) and part and part[0] in wanted
            for part in scan_key
        )
        if hit and _device_cache.discard(key):
            removed += 1
    return removed


def _cached_predicate_jit(skeleton: str, fn, family: str):
    """The jitted program of ``skeleton``, compiled once; ``family`` (a
    contract of check/hlo_lint.py) names the executable ``jit_hs_<family>``."""
    import jax

    jitted = _PREDICATE_CACHE.get(skeleton)
    if jitted is None:
        while len(_PREDICATE_CACHE) >= _PREDICATE_CACHE_MAX:
            _PREDICATE_CACHE.popitem(last=False)
        jitted = jax.jit(_hlo_lint.named(family, fn))
        _PREDICATE_CACHE[skeleton] = jitted
    else:
        _PREDICATE_CACHE.move_to_end(skeleton)
    return jitted


def _mesh_fp(mesh) -> str:
    from hyperspace_tpu.parallel.mesh import mesh_fingerprint

    return mesh_fingerprint(mesh)


def _program_key(skeleton: str, mesh, sharded: bool = False) -> str:
    """Program-cache key: (program skeleton, mesh fingerprint, execution
    mode). The shape bucket is the jit cache's own shape signature, so the
    full identity is (skeleton, shape bucket, mesh fingerprint) — one cache
    serves the single-device (GSPMD jit) and sharded (shard_map) paths
    without executables ever aliasing across meshes or modes."""
    mode = "shmap" if sharded else "spmd"
    return f"{skeleton}@{_mesh_fp(mesh)}/{mode}"


# --- declared HLO contracts (hyperspace_tpu/check/hlo_lint.py) -------------
# Each device-program family states its collective budget next to the code
# that builds it (and inherits the forbidden-op rules: no host callbacks, no
# f32->f64 array upcasts, no bounded-dynamic shapes). With
# hyperspace.check.hlo.enabled on, maybe_verify() checks every newly
# compiled executable at program-cache-fill time.
from hyperspace_tpu.check import hlo_lint as _hlo_lint

_ANY = (0, None)
_hlo_lint.register_contract(
    "fused-filter",
    collectives={},
    description="fused predicate mask: elementwise over resident shards, shuffle-free",
)
_hlo_lint.register_contract(
    "fused-agg",
    collectives={"all-reduce": _ANY},
    description="fused filter+aggregate: scalar reductions may all-reduce, never move rows",
)
_hlo_lint.register_contract(
    "grouped-agg-chunk",
    collectives={"all-gather": _ANY, "all-reduce": _ANY},
    description="GSPMD grouped-aggregate chunk: the partitioner may gather fixed-size partials, never rows",
)
_hlo_lint.register_contract(
    "sharded-grouped",
    collectives={"all-gather": (1, None), "all-reduce": _ANY},
    description="shard_map grouped chunk: all-gathers per-shard partial TABLES (>=1), never rows",
)
_hlo_lint.register_contract(
    "grouped-merge",
    collectives={},
    description="pairwise partial-aggregate merge: device-local, collective-free",
)
_hlo_lint.register_contract(
    "bucketed-smj-span",
    collectives={},
    description="bucketed sort-merge join span search: the shuffle-freedom claim itself",
)
_hlo_lint.register_contract(
    "join-pair-totals",
    collectives={"all-reduce": _ANY, "all-gather": _ANY},
    description="per-bucket matched-pair counts of a device-materialized join: (nb,) ints out",
)
_hlo_lint.register_contract(
    "join-expand-gather",
    collectives={"all-gather": _ANY, "all-reduce": _ANY},
    description="device-materialized inner join: pair expansion + numeric payload gathers, final columns out",
)
_hlo_lint.register_contract(
    "dict-expand",
    collectives={},
    description="on-device dictionary expansion: codes gather a replicated remap table, shuffle-free",
    single_fusion=True,
)
_hlo_lint.register_contract(
    "split-planes",
    collectives={},
    description="an uploaded 8-byte column into its two 32-bit planes, once a column: elementwise, shuffle-free",
    single_fusion=True,
)


def _dry_codecs(batch: B.Batch, refs) -> Dict[str, ColumnCodec]:
    """Dtype-kind-only codecs for the pre-transfer support check (string
    bounds resolve to 0; values are discarded)."""
    out: Dict[str, ColumnCodec] = {}
    for r in refs:
        kind = batch[r].dtype.kind
        if kind in ("U", "S", "O"):
            out[r] = ColumnCodec("string", uniques=np.empty(0, dtype=str))
        elif kind == "M":
            out[r] = ColumnCodec("datetime", unit=np.datetime_data(batch[r].dtype)[0])
        elif kind in ("i", "u", "b", "f"):
            out[r] = ColumnCodec("numeric")
        else:
            raise DeviceUnsupported(f"unsupported column dtype {batch[r].dtype}")
    return out


def _dict_expand_fn(codes, remap):
    import jax.numpy as jnp

    return jnp.where(codes >= 0, remap[jnp.maximum(codes, 0)], jnp.int32(-1))


def _upload_program(session, mesh, family: str, fn, signature, *args):
    """One run of ``family``, a program that puts a freshly uploaded column
    into the form the query programs want: once a column, never in a window.
    Waited for, so that what it read (the 8-byte array, the raw codes) is
    free again before the next column is uploaded, not beside it."""
    key = _program_key(family, mesh)
    jitted = _cached_predicate_jit(key, fn, family)
    first = _note_compile(key, signature)
    _hlo_lint.maybe_verify(session.conf, family, key, jitted, args)
    t0 = _ptime.perf_counter()
    with launch(family):
        out = jitted(*args)
    wait(out, family)
    _observe_program(family, first, t0)
    return out


def _put_encoded(session, mesh, sharding, n_dev, arr, site: str = "filter-cols", planes: Optional[bool] = None):
    """Encode + bucket-pad + ``device_put`` one column, the upload counted
    under ``site``; returns (device column, codec, staged bytes).

    An 8-byte column is uploaded whole and, where the mesh's devices compute
    64-bit values as pairs (``planes`` says so for them in a test), split by
    the ``split-planes`` program into the :class:`ColumnPlanes` that stay; the
    8-byte array goes with the call. The planes weigh what the column did.

    Dict-backed string columns (B.DictBackedArray, produced by the native
    decode fast path) skip host factorization entirely: the int32 codes ship
    as-is — bytes×rows becomes 4×rows over PCIe — plus a small replicated
    code→sorted-rank remap table, and the fused collective-free "dict-expand"
    gather rewrites codes into sorted-dictionary space on device. The result
    (array + ColumnCodec) is identical to the factorize_strings path, so
    _literal_bounds' searchsorted contract holds."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    codes = getattr(arr, "hs_dict_codes", None)
    uniques = getattr(arr, "hs_dict_uniques", None)
    if codes is not None and uniques is not None and codes.shape[0] == arr.shape[0]:
        order = np.argsort(uniques)
        su = uniques[order]
        k = int(order.shape[0])
        rank = np.empty(k, dtype=np.int32)
        rank[order] = np.arange(k, dtype=np.int32)
        cap = 1
        while cap < max(k, 1):
            cap *= 2  # power-of-two remap shapes cap distinct XLA signatures
        remap = np.zeros(cap, dtype=np.int32)
        remap[:k] = rank
        padded = _pad_to_bucket(codes, n_dev, 0)
        dev_codes = put(padded, site, sharding)
        dev_remap = put(remap, site, NamedSharding(mesh, P()))
        dev = _upload_program(session, mesh, "dict-expand", _dict_expand_fn, (padded.shape, remap.shape), dev_codes, dev_remap)
        codec = ColumnCodec("string", uniques=su, dtype=arr.dtype, nulls=bool((codes < 0).any()))
        return dev, codec, int(padded.nbytes + remap.nbytes)
    enc, codec = encode_column(arr)
    padded = _pad_to_bucket(enc, n_dev, 0 if enc.dtype != np.float64 else np.nan)
    dev = put(padded, site, sharding)
    if padded.dtype.itemsize == 8 and (computes_in_pairs(mesh) if planes is None else planes):
        dev = _upload_program(session, mesh, "split-planes", split_planes, (padded.shape, padded.dtype.str), dev)
    return dev, codec, int(padded.nbytes)


def device_filter_mask(session, batch: B.Batch, condition: Expr, scan_key=None, parallel=None) -> np.ndarray:
    """Evaluate ``condition`` on device over the referenced columns of
    ``batch``; returns the host bool mask. Raises DeviceUnsupported when the
    predicate is outside the device language.

    ``scan_key`` identifies an immutable file set (IndexScan bucket files);
    when given, encoded predicate columns are kept resident on device across
    queries. ``parallel`` (a ``ShardedExecutor``) switches compilation from
    GSPMD jit to an explicit shard_map over the executor's mesh; the device
    cache is shared between the two modes (same fingerprint, same layout)."""
    ensure_x64()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    refs = sorted(condition.references())
    for r in refs:
        if r not in batch:
            raise DeviceUnsupported(f"referenced column {r!r} missing from batch")
    n = B.num_rows(batch)
    if n == 0:
        return np.zeros(0, dtype=bool)

    mesh = parallel.mesh if parallel is not None else session.mesh
    n_dev = mesh.devices.size
    axis = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(axis))
    fp = _mesh_fp(mesh)  # device-cache key part shared by both modes

    dev_cols: Dict[str, "jax.Array"] = {}
    codecs: Dict[str, ColumnCodec] = {}
    missing: List[str] = []
    for r in refs:
        ckey = (scan_key, r, fp) if scan_key is not None else None
        cached = resident_column(ckey, n)
        if cached is not None:
            dev_cols[r], codecs[r] = cached[0], cached[1]
        else:
            missing.append(r)

    if missing:
        # reject unsupported predicates BEFORE encoding/transferring the
        # missing columns — an unsupported shape must not cost HBM space or
        # a wasted upload
        compile_predicate(condition, _dry_codecs(batch, refs))

        for r in missing:
            dev, codec, nbytes = _put_encoded(session, mesh, sharding, n_dev, batch[r])
            dev_cols[r] = dev
            codecs[r] = codec
            if scan_key is not None:
                _device_cache_put((scan_key, r, fp), (dev, codec, n), nbytes)

    pred_fn, lit_values = compile_predicate(condition, codecs)

    def fn(cols, lits):
        return pred_fn(join_columns(cols), lits)

    skeleton = predicate_skeleton(condition, codecs)
    if parallel is not None:
        from hyperspace_tpu.parallel import collectives as _collectives

        fn = _collectives.sharded_elementwise(mesh, axis, fn)
        parallel.note_op("filter")
    key = _program_key(skeleton, mesh, sharded=parallel is not None)
    jitted = _cached_predicate_jit(key, fn, "fused-filter")
    first = _note_compile(key, tuple(dev_cols[r].shape for r in sorted(dev_cols)))
    _hlo_lint.maybe_verify(session.conf, "fused-filter", key, jitted, (dev_cols, lit_values))
    t0 = _ptime.perf_counter()
    count_column_forms(dev_cols.values())
    with launch("fused-filter"):
        mask = jitted(dev_cols, lit_values)
    out = fetch(mask, "filter-mask", "fused-filter")[:n]
    _observe_program("fused-filter", first, t0)
    return out


def stage_filter_columns(session, batch: B.Batch, condition: Optional[Expr], scan_key, extra_columns=None, parallel=None) -> None:
    """H2D staging hook for the scan pipeline (stage 2 of 3): encode,
    bucket-pad and ``device_put`` ``condition``'s columns into the device
    cache on the prefetch thread, so the consumer's ``device_filter_mask``
    on this chunk is a pure cache hit and the transfer overlaps chunk k's
    compute. ``extra_columns`` (group keys / aggregate inputs for the fused
    grouped-aggregate path) stage alongside the predicate columns. Silently
    a no-op when the predicate is outside the device language or
    ``scan_key`` is None (nothing would be cached)."""
    if scan_key is None or (condition is None and not extra_columns):
        return
    n = B.num_rows(batch)
    if n == 0:
        return
    refs = sorted(condition.references()) if condition is not None else []
    if any(r not in batch for r in refs):
        return
    cols = list(dict.fromkeys(refs + [c for c in (extra_columns or []) if c in batch]))
    from hyperspace_tpu.obs import spans as obs_spans

    try:
        ensure_x64()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if condition is not None:
            compile_predicate(condition, _dry_codecs(batch, refs))
        mesh = parallel.mesh if parallel is not None else session.mesh
        n_dev = mesh.devices.size
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        fp = _mesh_fp(mesh)
        from hyperspace_tpu.reliability.faults import FAULTS

        if FAULTS.active:
            FAULTS.check("device.transfer")
        with obs_spans.span("h2d-stage", cat="pipeline", rows=n):
            for r in cols:
                ckey = (scan_key, r, fp)
                if resident_column(ckey, n) is not None:
                    continue
                dev, codec, nbytes = _put_encoded(session, mesh, sharding, n_dev, batch[r])
                _device_cache_put(ckey, (dev, codec, n), nbytes)
    except DeviceUnsupported:
        return  # the consumer's host fallback will handle this chunk


# --------------------------------------------------------------------------
# fused filter + global aggregate (only scalars leave the device)
# --------------------------------------------------------------------------

_AGG_FNS = ("count", "sum", "min", "max", "avg")


class ScanColumns:
    """The columns ``names`` of one whole scan, on the device.

    ``scan_key`` is the scan's identity (file_identity.scan_identity, branded
    with a pruned read's kept signature): columns resident under it are
    looked up without reading anything; ``on_device`` counts each column's
    lookup in ``hs_device_cache_lookups_total`` (a caller that turns away
    before it, below ``deviceMinRows`` say, asked nothing of the device).
    ``load()`` reads the scan's host
    batch; it is called at most once, and only when a column is missing or
    a caller asks for ``batch()``: a scan whose columns are all resident
    opens no file, decodes nothing and uploads nothing.

    The read and the uploads of missing columns are single-flight,
    process-wide: of the requests that miss the same columns at once (a
    server's warm-up) one reads the scan and uploads, and the others find
    the columns resident when the lock is theirs. Otherwise each would hold
    a copy of the scan on the host and on the device."""

    _load_lock = _threading.Lock()

    def __init__(self, session, scan_key, names, load):
        self.session = session
        self.scan_key = scan_key
        self.names = list(names)
        self._load = load
        self._batch: Optional[B.Batch] = None
        self.mesh = session.mesh
        self._fp = _mesh_fp(self.mesh)
        self._found: Dict[str, tuple] = {}
        self._look()
        #: every column was resident when the query asked
        self.resident = bool(self.names) and len(self._found) == len(self.names)
        self._asked = (len(self._found), len(self.names) - len(self._found))
        #: how many columns were not, and would be uploaded
        self.missing = self._asked[1]

    def _ckey(self, c):
        return (self.scan_key, c, self._fp) if self.scan_key is not None else None

    def _look(self) -> None:
        """Take what the cache holds of the missing columns (not counted)."""
        if self.scan_key is None:
            return
        for c in self.names:
            if c not in self._found:
                got = _device_cache.get(self._ckey(c))
                if got is not None:
                    self._found[c] = got
        if len({e[2] for e in self._found.values()}) > 1:
            self._found = {}  # cannot be under one key; treat as nothing there

    @property
    def loaded(self) -> Optional[B.Batch]:
        """The host batch if it was read, else None."""
        return self._batch

    def batch(self) -> B.Batch:
        if self._batch is None:
            self._batch = self._load()
        return self._batch

    @property
    def rows(self) -> int:
        for entry in self._found.values():
            return entry[2]
        return B.num_rows(self.batch())

    def _codecs_before_upload(self) -> Dict[str, ColumnCodec]:
        """Codecs that are enough to compile against before any upload: the
        resident columns' own, dtype-kind-only ones for the rest."""
        out = {c: e[1] for c, e in self._found.items()}
        missing = [c for c in self.names if c not in out]
        if missing:
            out.update(_dry_codecs(self.batch(), missing))
        return out

    def on_device(self, check=None, site: str = "agg-cols"):
        """``(device columns, codecs)``, reading the scan and uploading what
        is not resident. ``check(codecs)`` is asked before any upload, with
        dry codecs, and may raise DeviceUnsupported: an unsupported shape
        must not cost HBM space or a wasted upload."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if not self.resident:
            mesh = self.mesh
            sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
            with self._load_lock:
                self._look()  # a request ahead of this one in the lock may have put them
                missing = [c for c in self.names if c not in self._found]
                if missing:
                    if check is not None:
                        check(self._codecs_before_upload())
                    rows = self.rows
                    if rows == 0:
                        raise DeviceUnsupported("empty input stays host-side")
                    for c in missing:
                        dev, codec, nbytes = _put_encoded(
                            self.session, mesh, sharding, mesh.devices.size, self.batch()[c], site
                        )
                        self._found[c] = (dev, codec, rows)
                        if self.scan_key is not None:
                            _device_cache_put(self._ckey(c), self._found[c], nbytes)
        if self.scan_key is not None:  # what the query found when it asked
            _count_lookups("hit", self._asked[0])
            _count_lookups("miss", self._asked[1])
        return (
            {c: self._found[c][0] for c in self.names},
            {c: self._found[c][1] for c in self.names},
        )


def _compile_aggregate(codecs, condition, computes, aggs, group_keys):
    """``(predicate fn or None, computes fn or None, literal values, skeleton)``
    of an aggregate over a scan with ``codecs``. Everything that can say
    DeviceUnsupported is in here, so that it can be asked with dry codecs
    before an upload."""
    for r in sorted(condition.references()) if condition is not None else []:
        if r not in codecs:
            raise DeviceUnsupported(f"referenced column {r!r} missing from scan")
    if condition is not None:
        pred_fn, lits = compile_predicate(condition, codecs)
        skeleton = predicate_skeleton(condition, codecs)
    else:
        pred_fn, lits, skeleton = None, (), "<none>"
    comp_fn = None
    computed = set()
    if computes:
        comp_fn, comp_lits, comp_sk = compile_computes(computes, codecs, lit_base=len(lits))
        lits = tuple(lits) + comp_lits
        skeleton += "|c:" + comp_sk
        computed = {name for name, _ in computes}
    for _, _fn, c in aggs:
        if c is None or c in computed:
            continue
        if c not in codecs:
            raise DeviceUnsupported(f"column {c!r} missing from scan")
        # datetimes stay host-side: float64 reduction would lose ns precision
        if codecs[c].kind != "numeric":
            raise DeviceUnsupported(f"aggregate over non-numeric column {c!r}")
    for k in group_keys:
        if k not in codecs:
            raise DeviceUnsupported(f"group key {k!r} missing from scan")
    return pred_fn, comp_fn, lits, skeleton


def device_scan_aggregate(
    session,
    cols: ScanColumns,
    condition: Optional[Expr],
    computes,
    group_keys,
    aggs,
    *,
    max_groups: int = 0,
    cap_floor: int = 64,
) -> Optional[B.Batch]:
    """An aggregate over one whole scan, in ONE program over its resident
    columns: the predicate, the computed aggregate inputs (``computes``:
    ``(name, expr)`` pairs of the Compute node under the Aggregate) and the
    reductions run on the device, and only the result table comes back.
    Ungrouped aggregates reduce to scalars (``fused-agg``); grouped ones
    whose keys are all dictionary-coded with a small domain reduce into one
    slot per group, without a sort (``grouped-agg-dense``); every other key
    that is an integer, a date or a dictionary code goes through
    ``grouped-agg-keyed`` (skip, sort, scan: as many groups as ``max_groups``
    allows). Raises DeviceUnsupported for any other shape (a float key, keys
    spanning more than 32 bits together): the caller has the sort-based
    :class:`GroupedAggStream` and the host for those."""
    ensure_x64()
    for _, fn, _c in aggs:
        if fn not in (_GROUPED_AGG_FNS if group_keys else _AGG_FNS):
            raise DeviceUnsupported(f"unsupported aggregate fn {fn!r}")
    if not cols.names:
        # nothing to put on device (count(*) with no predicate): the program
        # would see an empty column dict — host handles it
        raise DeviceUnsupported("no device-resident columns involved")
    def check(dry):
        _compile_aggregate(dry, condition, computes, aggs, group_keys)
        if any(dry[k].kind != "string" for k in group_keys):  # not the dense program's keys: the keyed one's
            _keyed_key_plan(group_keys, aggs, dry)

    dev_cols, codecs = cols.on_device(check)
    pred_fn, comp_fn, lits, skeleton = _compile_aggregate(codecs, condition, computes, aggs, group_keys)
    if not group_keys:
        return _fused_aggregate(session, cols, dev_cols, pred_fn, comp_fn, lits, skeleton, list(aggs))
    args = (session, cols, dev_cols, codecs, pred_fn, comp_fn, lits, skeleton, list(group_keys), list(aggs), max_groups)
    try:
        _dense_key_plan(group_keys, codecs, max_groups)
    except DeviceUnsupported:
        computed = {name for name, _ in computes or ()}
        after = (
            set(group_keys)
            | {c for _, _, c in aggs if c is not None and c not in computed}
            | {r for _, e in computes or () for r in e.references()}
        )
        reads = (sorted(condition.references()) if condition is not None else [], sorted(after))
        return _keyed_grouped_aggregate(*args, cap_floor, reads)
    return _dense_grouped_aggregate(*args)


def _fused_reduce(cols, mask, agg_spec):
    """The traced reductions of ungrouped ``agg_spec`` (``(fn, column)``
    pairs) over the rows of ``cols`` that ``mask`` keeps: ``(values, counts of
    non-null matches)``, scalars all. The body ``fused-agg`` and
    ``join-agg-resident`` share."""
    import jax.numpy as jnp

    cnt = mask.sum()
    outs = []
    valids = []  # per-aggregate non-null match count (NaN-skipping)
    for fn, c in agg_spec:
        if fn == "count":
            if c is None or not jnp.issubdtype(cols[c].dtype, jnp.floating):
                outs.append(cnt.astype(jnp.int64))
            else:
                # count(col) skips nulls (NaN), like the host path
                outs.append((mask & ~jnp.isnan(cols[c])).sum().astype(jnp.int64))
            valids.append(cnt)
            continue
        x = cols[c]
        is_int = jnp.issubdtype(x.dtype, jnp.integer) or x.dtype == jnp.bool_
        # pandas semantics: NaNs are skipped, not propagated
        m = mask if is_int else (mask & ~jnp.isnan(x))
        valids.append(m.sum())
        if fn == "sum":
            # integer sums stay int64 (host-path parity; exact)
            z = x.astype(jnp.int64) if is_int else x.astype(jnp.float64)
            outs.append(jnp.where(m, z, z.dtype.type(0)).sum())
        elif fn == "avg":
            xf = x.astype(jnp.float64)
            outs.append(jnp.where(m, xf, 0.0).sum() / jnp.maximum(m.sum(), 1))
        elif fn == "min":
            if is_int:
                outs.append(jnp.where(m, x.astype(jnp.int64), jnp.iinfo(jnp.int64).max).min())
            else:
                outs.append(jnp.where(m, x.astype(jnp.float64), jnp.inf).min())
        else:  # max
            if is_int:
                outs.append(jnp.where(m, x.astype(jnp.int64), jnp.iinfo(jnp.int64).min).max())
            else:
                outs.append(jnp.where(m, x.astype(jnp.float64), -jnp.inf).max())
    return tuple(outs), tuple(valids)


def _fused_result(aggs, outs, valids) -> B.Batch:
    """The one-row answer of ungrouped ``aggs`` from what :func:`_fused_reduce`
    gave, with the host path's NULLs and dtypes."""
    result: Dict[str, np.ndarray] = {}
    for (name, fn, c), val, n_valid in zip(aggs, outs, (int(v) for v in valids)):
        if fn == "count":
            result[name] = np.asarray([int(val)])
        elif n_valid == 0:
            # no non-null matches: SQL yields NULL (sum included — SUM over
            # zero rows is NULL, not 0)
            result[name] = np.asarray([np.nan])
        elif fn != "avg" and np.asarray(val).dtype.kind in ("i", "u"):
            # an integer input (or integer-valued computed one) keeps int64
            result[name] = np.asarray([int(val)])
        else:
            result[name] = np.asarray([float(val)])
    return result


def _fused_aggregate(session, cols, dev_cols, pred_fn, comp_fn, lit_values, skeleton, aggs):
    import jax.numpy as jnp

    mesh = cols.mesh
    n = cols.rows
    agg_spec = tuple((fn, c) for _, fn, c in aggs)
    skeleton = "agg:" + skeleton + "|" + repr(agg_spec)

    def program(cols, lits, n_valid):
        cols = join_columns(cols)
        total = next(iter(cols.values())).shape[0]
        valid = jnp.arange(total) < n_valid
        mask = valid if pred_fn is None else (pred_fn(cols, lits) & valid)
        if comp_fn is not None:
            cols = comp_fn(cols, lits)
        return _fused_reduce(cols, mask, agg_spec)

    key = _program_key(skeleton, mesh)
    jitted = _cached_predicate_jit(key, program, "fused-agg")
    first = _note_compile(key, tuple(dev_cols[r].shape for r in sorted(dev_cols)))
    _hlo_lint.maybe_verify(session.conf, "fused-agg", key, jitted, (dev_cols, lit_values, np.int64(n)))
    t0 = _ptime.perf_counter()
    count_column_forms(dev_cols.values())
    with launch("fused-agg"):
        outs, valids = jitted(dev_cols, lit_values, np.int64(n))
    outs, valids = fetch((outs, valids), "agg-table", "fused-agg")
    _observe_program("fused-agg", first, t0)
    trace.agg_rows("device", n)
    _annotate_tier(program="fused-agg")
    return _fused_result(aggs, outs, valids)


# --------------------------------------------------------------------------
# fused filter + grouped aggregate: sort-based segment reduction
#
# One jitted program per (predicate skeleton, key/slot spec, shape bucket,
# capacity bucket): predicate mask, lexicographic rank-compression of the
# encoded group keys, and jax.ops.segment_sum/min/max reductions all run on
# device; only the per-group partial table (<= capacity rows) ever leaves.
# Streamed chunks each produce such a partial, merged chunk-to-chunk ON
# DEVICE by the same segment-reduction applied to the concatenated partials
# (avg/stddev decompose into sum/count/sumsq, so every state is mergeable).
# `num_segments` capacities grow geometrically (powers of sqrt(2) over a
# conf floor) so arbitrary group cardinalities land on a handful of cached
# executables; cardinalities beyond conf maxGroups spill to the host
# hash-combine path via DeviceUnsupported.
# --------------------------------------------------------------------------

_GROUPED_AGG_FNS = ("count", "sum", "min", "max", "avg", "stddev_samp")

_FS_SENTINEL = np.int64(np.iinfo(np.int64).max)


def group_capacity(n: int, floor: int) -> int:
    """Smallest geometric capacity bucket (powers of sqrt(2) over ``floor``)
    holding ``n`` groups — same geometry as the row-shape buckets, applied to
    ``num_segments`` so cardinality sweeps reuse executables."""
    return bucket_rows(max(1, int(n)), floor=max(1, int(floor)))


def topk_capacity(k: int, floor: int = 64) -> int:
    """Candidate-buffer capacity for a LIMIT ``k``: the same geometric
    buckets over a small floor, so nearby limits (10, 12, 100...) land on a
    handful of compiled top-k executables instead of one per distinct k."""
    return bucket_rows(max(1, int(k)), floor=max(1, int(floor)))


def _count_groups(program: str, n: int) -> None:
    """``n`` groups answered by ``program`` in ``hs_agg_groups_total{program}``."""
    _REGISTRY.counter(
        "hs_agg_groups_total", "Groups a device grouped aggregate answered, by program family", program=program
    ).inc(n)


def _annotate_tier(**attrs) -> None:
    """Say on the tier's span (``agg-device-grouped-scan``, the current one
    outside a launch or a wait) which program answered and what it found."""
    sp = _obs_spans.current_span()
    if sp is not None:
        sp.set(**attrs)


def _grouped_slots(aggs, is_int: Dict[str, bool]):
    """Decompose ``aggs`` into deduplicated mergeable state slots.

    Returns (slots, refs): ``slots`` is a list of (kind, col, int-valued)
    with kind in cntm/cnt/sum/sumsq/min/max (cntm = matched-row count for
    count(*)); ``refs[i]`` maps aggregate i to its slot indices."""
    slots: List[Tuple[str, Optional[str], bool]] = []
    index: Dict[Tuple[str, Optional[str], bool], int] = {}

    def slot(kind, col, isint):
        key = (kind, col, isint)
        got = index.get(key)
        if got is None:
            got = index[key] = len(slots)
            slots.append(key)
        return got

    refs: List[List[int]] = []
    for _, fn, c in aggs:
        if fn not in _GROUPED_AGG_FNS:
            raise DeviceUnsupported(f"unsupported grouped aggregate fn {fn!r}")
        if fn == "count" and c is None:
            refs.append([slot("cntm", None, True)])
            continue
        if c is None:
            raise DeviceUnsupported(f"aggregate {fn!r} without an input column")
        ii = bool(is_int[c])
        if fn == "count":
            refs.append([slot("cnt", c, ii)])
        elif fn == "sum":
            refs.append([slot("sum", c, ii), slot("cnt", c, ii)])
        elif fn == "min":
            refs.append([slot("min", c, ii), slot("cnt", c, ii)])
        elif fn == "max":
            refs.append([slot("max", c, ii), slot("cnt", c, ii)])
        elif fn == "avg":
            # float64 sum even for int inputs (the host streaming partial
            # does the same); exactness holds below 2^53
            refs.append([slot("sum", c, False), slot("cnt", c, ii)])
        else:  # stddev_samp
            refs.append([slot("cnt", c, ii), slot("sum", c, False), slot("sumsq", c, False)])
    return slots, refs


def _final_columns(aggs, refs, input_dtypes, slot_cols) -> B.Batch:
    """Per-group final values of ``aggs`` from their state slots, with
    host-path semantics: count -> int64, int sum -> int64 (exact), float
    sum/min/max -> NULL (NaN) when every matched row was NULL, int min/max
    keep the input dtype, avg/stddev from the decomposed states."""
    out: B.Batch = {}
    for (name, fn, c), ref in zip(aggs, refs):
        if fn == "count":
            out[name] = slot_cols[ref[0]].astype(np.int64)
            continue
        dt = input_dtypes[c]
        is_int = dt.kind in ("i", "u", "b")
        if fn == "sum":
            s, cnt = slot_cols[ref[0]], slot_cols[ref[1]]
            if is_int:
                out[name] = s.astype(np.int64)  # int inputs have no NULLs
            else:
                out[name] = np.where(cnt > 0, s.astype(np.float64), np.nan)
        elif fn in ("min", "max"):
            v, cnt = slot_cols[ref[0]], slot_cols[ref[1]]
            if is_int:
                out[name] = v.astype(dt if dt.kind != "u" else np.int64)
            else:
                out[name] = np.where(cnt > 0, v.astype(np.float64), np.nan)
        elif fn == "avg":
            s, cnt = slot_cols[ref[0]], slot_cols[ref[1]]
            with np.errstate(invalid="ignore", divide="ignore"):
                out[name] = np.where(cnt > 0, s / np.maximum(cnt, 1), np.nan)
        else:  # stddev_samp
            cnt, s, ss = (slot_cols[r] for r in ref)
            with np.errstate(invalid="ignore", divide="ignore"):
                m = cnt > 1
                var = np.where(
                    m,
                    (ss - (s * s) / np.maximum(cnt, 1)) / np.maximum(cnt - 1, 1),
                    np.nan,
                )
                out[name] = np.sqrt(np.clip(var, 0.0, None))
    return out


def _key_code(k, tag):
    """Grouping code of an encoded key column: int64, or for floats the
    canonical float64 (-0.0 -> +0.0, NaN -> one canonical NaN, so NaN keys
    form ONE group like pandas dropna=False). Codes that ``_codes_differ``
    calls equal are one group. Floats stay floats: the TPU compiler's 64-bit
    rewriting implements no bitcast-convert from f64 to s64."""
    import jax.numpy as jnp

    if tag == "f":
        kf = k.astype(jnp.float64)
        return jnp.where(jnp.isnan(kf), jnp.float64(np.nan), kf + 0.0)
    return k.astype(jnp.int64)


def _codes_differ(a, b):
    """Elementwise "different group" over two ``_key_code`` arrays."""
    import jax.numpy as jnp

    if jnp.issubdtype(a.dtype, jnp.floating):
        return (a != b) & ~(jnp.isnan(a) & jnp.isnan(b))
    return a != b


def _segment_ids(codes, mask, cap):
    """Sort rows so equal key tuples are adjacent (masked rows last), then
    rank-compress into segment ids. Returns (order, sorted-mask, n_groups,
    scatter ids) — scatter ids send masked rows to ``cap``, which
    segment_sum/min/max silently drop (out-of-range scatter)."""
    import jax
    import jax.numpy as jnp

    total = mask.shape[0]
    inv = (~mask).astype(jnp.int32)
    with jax.named_scope("sort"):
        order = jnp.lexsort(tuple(reversed(codes)) + (inv,))
    with jax.named_scope("segment-ids"):
        ms = mask[order]
        ch = jnp.zeros((total - 1,), dtype=bool)
        for c in codes:
            cs = c[order]
            ch = ch | _codes_differ(cs[1:], cs[:-1])
        ch = ch | (ms[1:] != ms[:-1])
        seg = jnp.concatenate([jnp.zeros((1,), jnp.int64), jnp.cumsum(ch.astype(jnp.int64))])
        n_groups = jnp.max(jnp.where(ms, seg, -1)) + 1
        segs = jnp.where(ms, seg, cap)
    return order, ms, n_groups, segs


def _segment_reduce_slots(cols_sorted, ms, segs, cap, slot_specs):
    """Per-slot segment reductions over the sorted rows. ``cols_sorted`` maps
    input column -> (sorted values, int-valued)."""
    import jax.numpy as jnp
    from jax import ops as jops

    out = []
    for kind, col, isint in slot_specs:
        if kind == "cntm":
            out.append(jops.segment_sum(ms.astype(jnp.int64), segs, num_segments=cap, indices_are_sorted=True))
            continue
        x = cols_sorted[col]
        nn = ms if isint else (ms & ~jnp.isnan(x))
        if kind == "cnt":
            out.append(jops.segment_sum(nn.astype(jnp.int64), segs, num_segments=cap, indices_are_sorted=True))
        elif kind == "sum":
            z = x.astype(jnp.int64) if isint else x.astype(jnp.float64)
            out.append(jops.segment_sum(jnp.where(nn, z, z.dtype.type(0)), segs, num_segments=cap, indices_are_sorted=True))
        elif kind == "sumsq":
            xf = x.astype(jnp.float64)
            out.append(jops.segment_sum(jnp.where(nn, xf * xf, 0.0), segs, num_segments=cap, indices_are_sorted=True))
        elif kind == "min":
            if isint:
                z = jnp.where(nn, x.astype(jnp.int64), jnp.iinfo(jnp.int64).max)
            else:
                z = jnp.where(nn, x.astype(jnp.float64), jnp.inf)
            out.append(jops.segment_min(z, segs, num_segments=cap, indices_are_sorted=True))
        else:  # max
            if isint:
                z = jnp.where(nn, x.astype(jnp.int64), jnp.iinfo(jnp.int64).min)
            else:
                z = jnp.where(nn, x.astype(jnp.float64), -jnp.inf)
            out.append(jops.segment_max(z, segs, num_segments=cap, indices_are_sorted=True))
    return tuple(out)


def _grouped_chunk_program(pred_fn, key_specs, slot_specs, cap):
    """Build the fused filter -> group-by -> segment-reduce device program.

    Returns n_groups, per-group first-seen global row index, per-group key
    representatives (gathered from the first-occurrence row, so -0.0/NaN
    payloads follow appearance order like pandas), and the state slots."""
    import jax
    import jax.numpy as jnp
    from jax import ops as jops

    def program(cols, lits, n_valid, row_base):
        cols = join_columns(cols)
        total = next(iter(cols.values())).shape[0]
        valid = jnp.arange(total) < n_valid
        with jax.named_scope("filter"):
            mask = valid if pred_fn is None else (pred_fn(cols, lits) & valid)
        with jax.named_scope("key-encode"):
            codes = [_key_code(cols[name], tag) for name, tag in key_specs]
        order, ms, n_groups, segs = _segment_ids(codes, mask, cap)
        with jax.named_scope("segment-reduce"):
            # first original row index per group == appearance order == the
            # representative row the key values gather from
            rep = jops.segment_min(
                jnp.where(ms, order.astype(jnp.int64), jnp.int64(total)),
                segs, num_segments=cap, indices_are_sorted=True,
            )
            repc = jnp.clip(rep, 0, total - 1)
            fs = jnp.where(rep < total, rep + row_base, _FS_SENTINEL)
            key_out = tuple(cols[name][repc] for name, _ in key_specs)
            cols_sorted = {c: cols[c][order] for _, c, _ in slot_specs if c is not None}
            slot_out = _segment_reduce_slots(cols_sorted, ms, segs, cap, slot_specs)
        return n_groups, fs, key_out, slot_out

    return program


def _merge_concat_parts(key_specs, slot_specs, cap_out, kcat, slots_cat, fs_cat, mask):
    """Merge CONCATENATED partial-aggregate parts on device — the core shared
    by the pairwise chunk merge (``_grouped_merge_program``) and the sharded
    all-gather merge (parallel/collectives.py): re-rank-compress the keys and
    segment-reduce the states with each slot's merge op (cnt/sum/sumsq add,
    min/max fold).

    Contract: parts must be concatenated in ascending global-row-range order,
    so a group's minimum concat position is a row from the part where it first
    appeared — the key representatives gathered from it match what a single
    sequential pass would have produced."""
    import jax
    import jax.numpy as jnp
    from jax import ops as jops

    total = mask.shape[0]
    with jax.named_scope("merge"):
        codes = [_key_code(k, tag) for k, (_, tag) in zip(kcat, key_specs)]
        order, ms, n_groups, segs = _segment_ids(codes, mask, cap_out)
        rep = jops.segment_min(
            jnp.where(ms, order.astype(jnp.int64), jnp.int64(total)),
            segs, num_segments=cap_out, indices_are_sorted=True,
        )
        repc = jnp.clip(rep, 0, total - 1)
        key_out = tuple(k[repc] for k in kcat)
        # values fed to the segment ops must follow the SORTED row order that
        # ``segs`` is defined over (the keys above gather by concat position
        # instead, so they stay unsorted)
        fs = jops.segment_min(
            jnp.where(ms, fs_cat[order], _FS_SENTINEL), segs,
            num_segments=cap_out, indices_are_sorted=True,
        )
        slot_out = []
        for (kind, _, _), v in zip(slot_specs, slots_cat):
            v = v[order]
            if kind in ("cntm", "cnt", "sum", "sumsq"):
                slot_out.append(jops.segment_sum(jnp.where(ms, v, v.dtype.type(0)), segs, num_segments=cap_out, indices_are_sorted=True))
            elif kind == "min":
                big = jnp.iinfo(jnp.int64).max if jnp.issubdtype(v.dtype, jnp.integer) else jnp.inf
                slot_out.append(jops.segment_min(jnp.where(ms, v, big), segs, num_segments=cap_out, indices_are_sorted=True))
            else:  # max
                low = jnp.iinfo(jnp.int64).min if jnp.issubdtype(v.dtype, jnp.integer) else -jnp.inf
                slot_out.append(jops.segment_max(jnp.where(ms, v, low), segs, num_segments=cap_out, indices_are_sorted=True))
        return n_groups, fs, key_out, tuple(slot_out)


def _grouped_merge_program(key_specs, slot_specs, cap_in, cap_out):
    """Merge two partial-aggregate tables (each padded to ``cap_in`` rows) on
    device. The running partial occupies the first concat half and its groups
    were first seen no later than the incoming chunk's (row bases ascend), so
    the concat satisfies ``_merge_concat_parts``'s ordering contract."""
    import jax.numpy as jnp

    def program(keys_a, keys_b, slots_a, slots_b, fs_a, fs_b, n_a, n_b):
        idx = jnp.arange(cap_in)
        mask = jnp.concatenate([idx < n_a, idx < n_b])
        kcat = tuple(jnp.concatenate([a, b]) for a, b in zip(keys_a, keys_b))
        slots_cat = tuple(jnp.concatenate([va, vb]) for va, vb in zip(slots_a, slots_b))
        fs_cat = jnp.concatenate([fs_a, fs_b])
        return _merge_concat_parts(key_specs, slot_specs, cap_out, kcat, slots_cat, fs_cat, mask)

    return program


def _dev_pad(arr, target, fill):
    """Pad a (small, per-group) device array up to ``target`` rows."""
    import jax.numpy as jnp

    n = arr.shape[0]
    if n == target:
        return arr
    return jnp.concatenate([arr, jnp.full((target - n,), fill, arr.dtype)])


class GroupedAggStream:
    """Streaming grouped aggregation with device-resident partials.

    ``update(batch, condition)`` fuses the scan predicate with the grouped
    segment reduction over one chunk and merges the resulting partial table
    into the running device partial; ``finalize()`` pulls only the per-group
    table back and reconstructs exact host-path semantics (NULL sums,
    NaN-skipping counts, dtype-preserving min/max, appearance-ordered rows).

    String group keys are grouped per-chunk in their chunk-local dictionary
    codes, then the <= cardinality per-group codes are remapped into one
    growing global dictionary between chunk and merge — O(groups) host
    traffic, never O(rows).

    Raises DeviceUnsupported whenever the shape, a dtype, or the observed
    group cardinality (> ``max_groups``) leaves the device language; callers
    fall back (or spill) to the host hash-combine path.
    """

    def __init__(
        self, session, group_keys, aggs, *, max_groups: int, cap_floor: int, hint_key=None,
        parallel=None,
    ):
        if not group_keys:
            raise DeviceUnsupported("global aggregates take the fused-scalar path")
        self.session = session
        # a ShardedExecutor switches the chunk program from GSPMD jit to an
        # explicit shard_map whose per-shard partials merge on-device via
        # all-gather (parallel/collectives.py) instead of the host loop
        self._parallel = parallel
        self.group_keys = list(group_keys)
        self.aggs = [(name, fn, c) for name, fn, c in aggs]
        self.max_groups = int(max_groups)
        self.cap_floor = max(1, int(cap_floor))
        self._schema = None  # per-key (tag, dtype, unit) + per-input dtype
        self._slots = None
        self._refs = None
        self._partial = None  # dict(cap, n, fs, keys, slots) — device arrays
        self._family = "grouped-agg-chunk"  # of the program that last wrote _partial
        self._row_base = 0
        # seed capacity from the last observed cardinality of the same query
        # shape over the same scan: a fresh stream otherwise starts at the
        # floor and pays a right-sizing re-run on EVERY repeated (warm) query
        self._hint_key = (
            (hint_key, tuple(self.group_keys), tuple((fn, c) for _, fn, c in self.aggs))
            if hint_key is not None
            else None
        )
        self._cap_hint = _CAP_HINT_MEMO.get(self._hint_key, 1)
        self._strmaps: Dict[str, Dict[str, int]] = {}
        self._struniq: Dict[str, List] = {}

    # -- schema ---------------------------------------------------------------

    def _key_tag(self, arr: np.ndarray) -> str:
        kind = arr.dtype.kind
        if kind in ("i", "u", "b"):
            return "i"
        if kind == "f":
            return "f"
        if kind == "M":
            return "d"
        if kind in ("U", "S", "O"):
            return "s"
        raise DeviceUnsupported(f"unsupported group-key dtype {arr.dtype}")

    def _check_schema(self, batch: B.Batch):
        keys_schema = []
        for k in self.group_keys:
            arr = batch[k]
            tag = self._key_tag(arr)
            unit = np.datetime_data(arr.dtype)[0] if tag == "d" else None
            keys_schema.append((tag, arr.dtype, unit))
        inputs = {}
        for _, fn, c in self.aggs:
            if c is None:
                continue
            kind = batch[c].dtype.kind
            if kind not in ("i", "u", "b", "f"):
                raise DeviceUnsupported(f"grouped aggregate over non-numeric column {c!r}")
            inputs[c] = batch[c].dtype
        if self._schema is None:
            self._schema = (keys_schema, inputs)
            self._slots, self._refs = _grouped_slots(
                self.aggs, {c: dt.kind in ("i", "u", "b") for c, dt in inputs.items()}
            )
        else:
            prev_keys, prev_inputs = self._schema
            if [s[:1] + (s[2],) for s in prev_keys] != [s[:1] + (s[2],) for s in keys_schema] or {
                c: dt.kind in ("i", "u", "b") for c, dt in prev_inputs.items()
            } != {c: dt.kind in ("i", "u", "b") for c, dt in inputs.items()}:
                raise DeviceUnsupported("chunk schema drift under grouped aggregate")

    # -- chunk update ---------------------------------------------------------

    @property
    def has_data(self) -> bool:
        return self._partial is not None

    def update(self, batch: B.Batch, condition: Optional[Expr] = None, scan_key=None) -> None:
        with _obs_spans.span("agg-device-fold", cat="exec"):
            self._update(batch, condition, scan_key)

    def _update(self, batch: B.Batch, condition: Optional[Expr], scan_key) -> None:
        ensure_x64()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = B.num_rows(batch)
        if n == 0:
            return
        refs = sorted(condition.references()) if condition is not None else []
        agg_inputs = sorted({c for _, _, c in self.aggs if c is not None})
        for col in refs + agg_inputs + self.group_keys:
            if col not in batch:
                raise DeviceUnsupported(f"column {col!r} missing from batch")
        self._check_schema(batch)
        keys_schema, input_dtypes = self._schema
        if condition is not None:
            compile_predicate(condition, _dry_codecs(batch, refs))

        mesh = self._parallel.mesh if self._parallel is not None else self.session.mesh
        n_dev = mesh.devices.size
        sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
        fp = _mesh_fp(mesh)
        dev_cols: Dict[str, "jax.Array"] = {}
        codecs: Dict[str, ColumnCodec] = {}
        for col in sorted(set(refs) | set(agg_inputs) | set(self.group_keys)):
            ckey = (scan_key, col, fp) if scan_key is not None else None
            cached = resident_column(ckey, n)
            if cached is not None:
                dev_cols[col], codecs[col] = cached[0], cached[1]
                continue
            if col in agg_inputs and batch[col].dtype.kind in ("U", "S", "O"):
                raise DeviceUnsupported("string aggregate inputs stay host-side")
            dev, codec, nbytes = _put_encoded(
                self.session, mesh, sharding, n_dev, batch[col]
            )
            dev_cols[col] = dev
            codecs[col] = codec
            if ckey is not None:
                _device_cache_put(ckey, (dev, codec, n), nbytes)
        for col in agg_inputs:
            if codecs[col].kind == "string":
                raise DeviceUnsupported("string aggregate inputs stay host-side")

        if condition is not None:
            pred_fn, lit_values = compile_predicate(condition, codecs)
            pred_sk = predicate_skeleton(condition, codecs)
        else:
            pred_fn, lit_values = None, ()
            pred_sk = "<none>"
        key_specs = tuple(
            (name, "f" if tag == "f" else "i")
            for name, (tag, _, _) in zip(self.group_keys, keys_schema)
        )
        base_sk = (
            f"{pred_sk}|k:{','.join(f'{n_}:{t}' for n_, t in key_specs)}"
            f"|s:{','.join(f'{k}:{c}:{int(i)}' for k, c, i in self._slots)}"
        )

        cap = group_capacity(max(self._cap_hint, 1), self.cap_floor)
        shapes = tuple(dev_cols[r].shape for r in sorted(dev_cols))
        sharded = self._parallel is not None
        while True:
            if sharded:
                from hyperspace_tpu.parallel import collectives as _collectives

                program = _collectives.sharded_grouped_chunk_program(
                    mesh, mesh.axis_names[0], pred_fn, key_specs, self._slots, cap
                )
            else:
                program = _grouped_chunk_program(pred_fn, key_specs, self._slots, cap)
            family = self._family = "sharded-grouped" if sharded else "grouped-agg-chunk"
            key = _program_key(f"gagg[{cap}]:{base_sk}", mesh, sharded=sharded)
            jitted = _cached_predicate_jit(key, program, family)
            first = _note_compile(key, shapes)
            _hlo_lint.maybe_verify(
                self.session.conf,
                family,
                key, jitted,
                (dev_cols, lit_values, np.int64(n), np.int64(self._row_base)),
            )
            t0 = _ptime.perf_counter()
            count_column_forms(dev_cols.values())
            if sharded:
                n_g_dev, fs, key_out, slot_out = self._parallel.timed_call(
                    "grouped-agg", family, jitted,
                    dev_cols, lit_values, np.int64(n), np.int64(self._row_base),
                )
            else:
                with launch(family):
                    n_g_dev, fs, key_out, slot_out = jitted(
                        dev_cols, lit_values, np.int64(n), np.int64(self._row_base)
                    )
            n_g = int(fetch(n_g_dev, "agg-table", family))
            _observe_program(family, first, t0)
            if n_g > self.max_groups:
                exc = GroupCapacityExceeded(
                    f"group cardinality {n_g} exceeds maxGroups {self.max_groups}"
                )
                exc.folded = False  # this chunk is NOT in the running partial
                raise exc
            if n_g <= cap:
                break
            cap = group_capacity(n_g, self.cap_floor)  # one re-run, right-sized
        self._cap_hint = max(self._cap_hint, n_g)
        trace.agg_rows("device", n)

        key_out = list(key_out)
        for i, (name, (tag, _, _)) in enumerate(zip(self.group_keys, keys_schema)):
            if tag == "s":
                key_out[i] = self._remap_string_key(name, key_out[i], codecs[name], n_g, cap)
        new = {"cap": cap, "n": n_g, "fs": fs, "keys": key_out, "slots": list(slot_out)}
        self._row_base += n
        if self._partial is None:
            self._partial = new
        else:
            self._merge(new)

    def _remap_string_key(self, name, dev_codes, codec: ColumnCodec, n_g: int, cap: int):
        """Chunk-local dictionary codes -> global int64 codes (host remap of
        only the per-group representatives; -1 null stays -1)."""
        import jax

        local = fetch(dev_codes, "agg-table", self._family)[:n_g]
        mapping = self._strmaps.setdefault(name, {})
        uniq = self._struniq.setdefault(name, [])
        out = np.full(cap, -1, dtype=np.int64)
        for j, code in enumerate(local):
            if code < 0:
                continue
            val = codec.uniques[int(code)]
            got = mapping.get(val)
            if got is None:
                got = mapping[val] = len(uniq)
                uniq.append(val)
            out[j] = got
        return put(out, "agg-table")

    def _merge(self, new) -> None:
        import jax
        import time as _time
        from hyperspace_tpu.obs import spans as obs_spans
        from hyperspace_tpu.obs.metrics import REGISTRY

        a, b = self._partial, new
        keys_schema, _ = self._schema
        key_specs = tuple(
            (name, "f" if tag == "f" else "i")
            for name, (tag, _, _) in zip(self.group_keys, keys_schema)
        )
        cap_in = max(a["cap"], b["cap"])
        for part in (a, b):
            if part["cap"] != cap_in:
                part["fs"] = _dev_pad(part["fs"], cap_in, _FS_SENTINEL)
                part["keys"] = [_dev_pad(k, cap_in, 0 if k.dtype != np.float64 else np.nan) for k in part["keys"]]
                part["slots"] = [_dev_pad(s, cap_in, 0) for s in part["slots"]]
        cap_out = group_capacity(a["n"] + b["n"], self.cap_floor)
        mesh = self._parallel.mesh if self._parallel is not None else self.session.mesh
        skeleton = (
            f"gaggmerge[{cap_in}->{cap_out}]:k:{','.join(t for _, t in key_specs)}"
            f"|s:{','.join(f'{k}:{int(i)}' for k, _, i in self._slots)}"
        )
        key = _program_key(skeleton, mesh)
        program = _grouped_merge_program(key_specs, self._slots, cap_in, cap_out)
        jitted = _cached_predicate_jit(key, program, "grouped-merge")
        first = _note_compile(key, (cap_in, cap_out))
        _hlo_lint.maybe_verify(
            self.session.conf, "grouped-merge", key, jitted,
            (tuple(a["keys"]), tuple(b["keys"]), tuple(a["slots"]), tuple(b["slots"]),
             a["fs"], b["fs"], np.int64(a["n"]), np.int64(b["n"])),
        )
        t0 = _time.perf_counter()
        with obs_spans.span("agg-merge", cat="groupagg", groups_in=a["n"] + b["n"]):
            with launch("grouped-merge"):
                n_g_dev, fs, key_out, slot_out = jitted(
                    tuple(a["keys"]), tuple(b["keys"]),
                    tuple(a["slots"]), tuple(b["slots"]),
                    a["fs"], b["fs"], np.int64(a["n"]), np.int64(b["n"]),
                )
            n_g = int(fetch(n_g_dev, "agg-table", "grouped-merge"))
        _observe_program("grouped-merge", first, t0)
        REGISTRY.counter(
            "hs_agg_merge_seconds_total",
            "Cumulative device partial-aggregate merge time (seconds)",
        ).inc(_time.perf_counter() - t0)
        self._partial = {
            "cap": cap_out, "n": n_g, "fs": fs,
            "keys": list(key_out), "slots": list(slot_out),
        }
        self._cap_hint = max(self._cap_hint, n_g)
        if n_g > self.max_groups:
            # the merged partial is still VALID (capacity covered it) — keep
            # it so the caller can convert to a host partial before spilling
            exc = GroupCapacityExceeded(
                f"group cardinality {n_g} exceeds maxGroups {self.max_groups}"
            )
            exc.folded = True  # the triggering chunk IS in the stored partial
            raise exc

    # -- finalization ---------------------------------------------------------

    def _host_table(self):
        """Pull the per-group table to host, appearance-ordered: decoded key
        arrays + raw slot arrays."""
        p = self._partial
        if p is None:
            raise DeviceUnsupported("no device partial to finalize")
        n = p["n"]
        keys_schema, input_dtypes = self._schema
        fs, keys, slots = fetch(
            (p["fs"], tuple(p["keys"]), tuple(p["slots"])), "agg-table", self._family
        )
        order = np.argsort(fs[:n], kind="stable")
        key_cols = {}
        for name, (tag, dtype, unit), dev in zip(self.group_keys, keys_schema, keys):
            vals = dev[:n][order]
            if tag == "s":
                uniq = self._struniq.get(name, [])
                out = np.full(n, np.nan, dtype=object)
                pos = vals >= 0
                if pos.any():
                    lut = np.asarray(uniq, dtype=object)
                    out[pos] = lut[vals[pos].astype(np.int64)]
                key_cols[name] = out
            elif tag == "d":
                key_cols[name] = vals.astype(np.int64).view(f"M8[{unit}]")
            elif tag == "f":
                key_cols[name] = vals.astype(dtype)
            else:
                key_cols[name] = vals.astype(dtype)
        slot_cols = [s[:n][order] for s in slots]
        return n, key_cols, slot_cols

    def finalize(self) -> B.Batch:
        """Per-group final values with host-path semantics: count -> int64,
        int sum -> int64 (exact), float sum/min/max -> NULL (NaN) when every
        matched row was NULL, int min/max keep the input dtype, avg/stddev
        from the decomposed states. Rows in first-appearance order, exactly
        like pandas groupby(sort=False)."""
        with _obs_spans.span("agg-finalize", cat="exec"):
            return self._finalize()

    def _finalize(self) -> B.Batch:
        from hyperspace_tpu.obs.metrics import REGISTRY

        if self._hint_key is not None:
            _remember_capacity(self._hint_key, self._cap_hint)
        n, key_cols, slot_cols = self._host_table()
        _, input_dtypes = self._schema
        out: B.Batch = dict(key_cols)
        out.update(_final_columns(self.aggs, self._refs, input_dtypes, slot_cols))
        _count_groups(self._family, n)
        return out

    def to_partial_frame(self, plain):
        """The running device partial as ONE host partial frame in the
        streaming-aggregate merge format (``__p{i}`` columns per plan-agg
        index) — the spill path hands accumulated device state to the host
        hash-combine without recomputing any chunk."""
        import pandas as pd

        n, key_cols, slot_cols = self._host_table()
        _, input_dtypes = self._schema
        frame = dict(key_cols)
        by_name = {name: (fn, c) for name, fn, c in self.aggs}
        refs_by_name = {name: ref for (name, _, _), ref in zip(self.aggs, self._refs)}
        for i, name, fn, c in plain:
            ref = refs_by_name[name]
            p = f"__p{i}"
            if fn == "count":
                frame[p] = slot_cols[ref[0]].astype(np.int64)
            elif fn in ("sum", "min", "max"):
                v, cnt = slot_cols[ref[0]], slot_cols[ref[1]]
                dt = input_dtypes[c]
                if dt.kind in ("i", "u", "b"):
                    if fn == "sum":
                        frame[p] = v.astype(np.int64)
                    else:
                        frame[p] = v.astype(dt if dt.kind != "u" else np.int64)
                else:
                    frame[p] = np.where(cnt > 0, v.astype(np.float64), np.nan)
            elif fn == "avg":
                s, cnt = slot_cols[ref[0]], slot_cols[ref[1]]
                frame[p + "_s"] = np.where(cnt > 0, s.astype(np.float64), np.nan)
                frame[p + "_c"] = cnt.astype(np.int64)
            else:  # stddev_samp
                cnt, s, ss = (slot_cols[r] for r in ref)
                frame[p + "_n"] = cnt.astype(np.int64)
                frame[p + "_s"] = np.where(cnt > 0, s.astype(np.float64), np.nan)
                frame[p + "_ss"] = ss.astype(np.float64)
        return pd.DataFrame(frame)


# what a query shape over a scan's files was seen to need: groups (the chunk
# family's and the keyed program's table), and a predicate's selected rows and
# blocks (``_keyed_selected``)
_CAP_HINT_MEMO: Dict[tuple, object] = {}


def _remember_capacity(key, value) -> None:
    if len(_CAP_HINT_MEMO) >= 4096:  # bound pathological key churn
        _CAP_HINT_MEMO.clear()
    _CAP_HINT_MEMO[key] = value


def device_grouped_aggregate(
    session,
    batch: B.Batch,
    condition: Optional[Expr],
    group_keys,
    aggs,
    scan_key=None,
    *,
    max_groups: int,
    cap_floor: int,
    parallel=None,
) -> B.Batch:
    """One-shot fused filter -> grouped aggregate over a materialized scan
    batch (the non-streamed `_exec_aggregate` path). Raises DeviceUnsupported
    outside the device language or beyond ``max_groups`` cardinality."""
    if B.num_rows(batch) == 0:
        raise DeviceUnsupported("empty input stays host-side")
    stream = GroupedAggStream(
        session,
        group_keys,
        aggs,
        max_groups=max_groups,
        cap_floor=cap_floor,
        hint_key=scan_key,
        parallel=parallel,
    )
    stream.update(batch, condition, scan_key=scan_key)
    return stream.finalize()


# --------------------------------------------------------------------------
# grouped aggregate over dictionary keys: one slot per group, no sort
#
# A group key that is a dictionary-coded string has a domain the codec
# states: the dictionary's size. When the product of the keys' domains is
# small, a row's group is the mixed-radix number of its codes, and the state
# slots reduce into ``groups`` masked sums/mins/maxes — no sort, no gather,
# one program a query shape. The sort-based engine above stays for every
# other key (numbers, dates, wide dictionaries), whose domain nothing states.
#
# The program is ONE reduction over the rows: every slot's operand (the
# selected values, the non-NULL flags, the row index) goes into one variadic
# ``lax.reduce`` whose combiner adds, or takes the min or max, slot by slot,
# so a further slot costs accumulators, not a pass over the columns. What it
# carries through that pass is as narrow as the answer allows: float sums
# are float64 and integer sums int64 (the answer's own arithmetic; on the
# TPU a pair of f32 and a pair of u32, so each is several operations a row
# and group), but the bookkeeping (the row index, ``n_valid``, the ``cnt``
# and ``cntm`` counts, the first-row min) is int32 wherever the padded row
# count is below 2^31: none of them can pass the row count, and an emulated
# 64-bit add or compare costs a pass of its own worth of arithmetic. At 2^31
# rows and above they are int64 as the result table is; the shape decides,
# there is no key. The group table comes back int64 either way.
# --------------------------------------------------------------------------

#: most groups the direct-addressed program reduces into; the work of a pass
#: grows with it
_DENSE_MAX_GROUPS = 64

_hlo_lint.register_contract(
    "grouped-agg-dense",
    collectives={"all-gather": _ANY, "all-reduce": _ANY},
    description="direct-addressed grouped aggregate over dictionary keys: per-group reductions, only the group table leaves",
)


def _dense_key_plan(group_keys, codecs, max_groups: int):
    """``[(key, domain size, code offset)]`` and the number of groups, for
    keys that are all dictionary-coded; DeviceUnsupported otherwise. A null
    code (-1) takes a slot of its own unless the codec saw none."""
    plan, groups = [], 1
    for k in group_keys:
        codec = codecs[k]
        if codec.kind != "string":
            raise DeviceUnsupported(f"group key {k!r} is not dictionary-coded")
        off = 0 if codec.nulls is False else 1
        size = len(codec.uniques) + off
        plan.append((k, size, off))
        groups *= max(size, 1)
    limit = min(_DENSE_MAX_GROUPS, max_groups) if max_groups else _DENSE_MAX_GROUPS
    if groups > limit:
        raise DeviceUnsupported(f"{groups} dictionary groups exceed the direct-addressed limit {limit}")
    return plan, groups


def _dense_slots(aggs, comp_fn, dev_cols, codecs, lit_values):
    """``(input dtypes, slots, refs, index of the matched-row count)`` of a
    dense grouped aggregate: :func:`_resident_slots`, and a ``cntm`` slot
    (which groups hold a row at all) where no aggregate asked for one."""
    input_dtypes, slots, refs = _resident_slots(aggs, comp_fn, dev_cols, codecs, lit_values)
    if not any(kind == "cntm" for kind, _, _ in slots):
        slots = slots + [("cntm", None, True)]
    cntm_at = next(i for i, (kind, _, _) in enumerate(slots) if kind == "cntm")
    return input_dtypes, slots, refs, cntm_at


def _dense_reduce(cols, mask, rows, plan, groups: int, slots):
    """The traced group table of the direct-addressed program: every state of
    ``slots`` for each of ``groups`` mixed-radix groups of ``plan``'s
    dictionary codes, over the rows of ``cols`` that ``mask`` keeps, in ONE
    variadic reduction. ``rows`` numbers the rows (its dtype is the
    bookkeeping's: int32 below 2^31 rows); the first of a group orders the
    answer. ``(first rows, the slots' tables)``, int64 and float64. The body
    ``grouped-agg-dense`` and ``join-agg-resident`` share."""
    import jax
    import jax.numpy as jnp

    idx = rows.dtype
    total = rows.shape[0]
    with jax.named_scope("key-encode"):
        gid = jnp.zeros((total,), jnp.int32)
        for name, size, off in plan:
            gid = gid * size + (cols[name].astype(jnp.int32) + off)
        # (groups, rows): a row reduction over the minor axis per group
        member = (jnp.arange(groups, dtype=jnp.int32)[:, None] == gid[None, :]) & mask[None, :]

    def operand(kind, col, isint):
        """``(values[groups, rows], identity, combiner)`` of one state."""
        if col is None:
            return member.astype(idx), 0, jnp.add
        x = cols[col]
        nn = member if isint else (member & ~jnp.isnan(x)[None, :])
        if kind == "cnt":
            return nn.astype(idx), 0, jnp.add
        # integer sums stay int64 (exact), every float state is float64
        z = x.astype(jnp.int64) if isint else x.astype(jnp.float64)
        if kind in ("sum", "sumsq"):
            fill, fold = 0, jnp.add
            z = z * z if kind == "sumsq" else z
        elif kind == "min":
            fill, fold = (jnp.iinfo(jnp.int64).max if isint else jnp.inf), jnp.minimum
        else:  # max
            fill, fold = (jnp.iinfo(jnp.int64).min if isint else -jnp.inf), jnp.maximum
        return jnp.where(nn, z[None, :], z.dtype.type(fill)), fill, fold

    with jax.named_scope("group-reduce"):
        last = jnp.iinfo(idx).max
        operands = {"fs": (jnp.where(member, rows[None, :], last), last, jnp.minimum)}
        at = []
        for kind, col, isint in slots:
            if kind == "cntm" or (kind == "cnt" and isint):
                # every member row of an int column counts: one operand
                kind, col = "cnt", None
            key = (kind, col, isint)
            if key not in operands:
                operands[key] = operand(kind, col, isint)
            at.append(key)
        values, fills, folds = zip(*operands.values())
        reduced = jax.lax.reduce(
            values,
            tuple(v.dtype.type(f) for v, f in zip(values, fills)),
            lambda acc, x: tuple(fold(a, b) for fold, a, b in zip(folds, acc, x)),
            (1,),
        )
        table = {
            key: r.astype(jnp.int64) if jnp.issubdtype(r.dtype, jnp.integer) else r
            for key, r in zip(operands, reduced)
        }
    return table["fs"], tuple(table[key] for key in at)


def _dense_result(plan, codecs, group_keys, aggs, refs, input_dtypes, fs, slot_out, cntm_at) -> B.Batch:
    """The answer of a dense grouped aggregate from its group table: the
    groups that hold a row, in first-appearance order (pandas sort=False),
    their keys decoded from the mixed-radix group number."""
    live = np.flatnonzero(slot_out[cntm_at] > 0)
    live = live[np.argsort(fs[live], kind="stable")]
    result: B.Batch = {}
    radix = live.copy()
    for name, size, off in reversed(plan):
        codes = radix % size - off
        radix = radix // size
        vals = np.full(len(live), np.nan, dtype=object)
        pos = codes >= 0
        if pos.any():
            vals[pos] = np.asarray(codecs[name].uniques, dtype=object)[codes[pos]]
        result[name] = vals
    result = {k: result[k] for k in group_keys}
    result.update(_final_columns(aggs, refs, input_dtypes, [s[live] for s in slot_out]))
    return result


def _dense_grouped_aggregate(
    session, cols, dev_cols, codecs, pred_fn, comp_fn, lit_values, skeleton,
    group_keys, aggs, max_groups,
) -> B.Batch:
    import jax
    import jax.numpy as jnp

    mesh = cols.mesh
    n = cols.rows
    plan, groups = _dense_key_plan(group_keys, codecs, max_groups)
    input_dtypes, slots, refs, cntm_at = _dense_slots(aggs, comp_fn, dev_cols, codecs, lit_values)

    def program(cols, lits, n_valid):
        cols = join_columns(cols)
        total = next(iter(cols.values())).shape[0]
        # bookkeeping counts rows, so the row count bounds it (see above)
        idx = jnp.int32 if total < 2**31 else jnp.int64
        rows = jnp.arange(total, dtype=idx)
        with jax.named_scope("filter"):
            mask = rows < n_valid.astype(idx)
            if pred_fn is not None:
                mask = pred_fn(cols, lits) & mask
        if comp_fn is not None:
            cols = comp_fn(cols, lits)
        return _dense_reduce(cols, mask, rows, plan, groups, slots)

    skeleton = (
        f"gdense[{groups}]:{skeleton}|k:{','.join(f'{k}:{size}:{off}' for k, size, off in plan)}"
        f"|s:{','.join(f'{k}:{c}:{int(i)}' for k, c, i in slots)}"
    )
    key = _program_key(skeleton, mesh)
    jitted = _cached_predicate_jit(key, program, "grouped-agg-dense")
    first = _note_compile(key, tuple(dev_cols[r].shape for r in sorted(dev_cols)))
    _hlo_lint.maybe_verify(
        session.conf, "grouped-agg-dense", key, jitted, (dev_cols, lit_values, np.int64(n))
    )
    t0 = _ptime.perf_counter()
    count_column_forms(dev_cols.values())
    with launch("grouped-agg-dense"):
        out = jitted(dev_cols, lit_values, np.int64(n))
    fs, slot_out = fetch(out, "agg-table", "grouped-agg-dense")
    _observe_program("grouped-agg-dense", first, t0)
    trace.agg_rows("device", n)
    result = _dense_result(plan, codecs, group_keys, aggs, refs, input_dtypes, fs, slot_out, cntm_at)
    n_groups = len(next(iter(result.values())))
    _count_groups("grouped-agg-dense", n_groups)
    _annotate_tier(program="grouped-agg-dense", groups=n_groups)
    return result


# --------------------------------------------------------------------------
# grouped aggregate over integer and date keys: skip, sort, scan
#
# A key whose domain no codec states (an int64, a date, a wide dictionary's
# codes) has as many groups as the data says: TPC-H Q15's revenue by supplier
# is 100,000 groups of about 25 rows. Neither form above fits: the dense
# program costs by groups x rows, and the sort-based chunk family takes a host
# batch, no computed input, and minutes of compile. This program answers it
# from the resident columns in one module, ``jit_hs_grouped_agg_keyed``
# (and a probe before it, the first time a predicate is seen):
#
# 1. *skip*: one pass over the predicate's columns gives the mask; rows are
#    taken in blocks of ``_KEYED_BLOCK_ROWS``, and only the blocks that hold a
#    selected row go on: their numbers sorted to the front, their rows copied
#    out of every plane the query reads, the predicate's among them (the mask
#    of the rows that went on is asked of those again: a pred array is packed
#    four to a word and would have to be widened to be copied). On one device
#    the copy is a kernel (``ops/kernels.copy_blocks``: one launch, the
#    numbers prefetched, one DMA a block and a plane from where the plane
#    lies, whole and one-dimensional; nothing else of it is read). XLA's own
#    forms all read or write whole planes, or cost by the block: at the cell's
#    shapes (925 of 16,388 blocks of a 67 M-row plane, v5e, PERF.md PR 41's
#    ``micro.json``) a ``while`` of ``dynamic_slice`` took 6.0 ms a plane, a
#    ``gather`` of blocks 6.1, and rows of the plane laid out in blocks 2.4, of
#    which 1.6 are the slice to whole blocks and the layout itself, 268 MB
#    copied twice to keep 15. That last form stays on a mesh (``layout``),
#    where the planes are sharded by rows and the partitioner has to see the
#    skip. An index file is sorted by its key and a bucket holds one file a
#    build chunk, so a range on that key selects one run a file: about 2,500
#    runs of 1,000 rows for a quarter of Q15 at SF 10, 2.5 M of 67 M rows. The
#    blocks that go on hold a run and its two edges, so the block is as small
#    as a copy can be, one tile: at 4,096 rows those runs lay in 15.2 M rows,
#    at 1,024 in 5.2 M. A predicate that selects rows everywhere keeps
#    every block (``whole``), and the steps below then walk the whole scan:
#    slower, the same answer.
# 2. *sort*: the keys of a row become one 32-bit code, mixed-radix over each
#    key's offset from its least selected value (a NULL date takes the slot
#    past the greatest), and ``(code, position, inputs)`` is sorted, unstable,
#    with one key operand. That is what this chip's compiler builds in
#    seconds: a sort's compile time grows steeply with its operands (one key
#    3 s, key and payload 12 s, key and three payloads 25 s, four keys and
#    three payloads 1,000 s at 3 M rows on a v5e, PERF.md). Keys that span
#    more than 32 bits together are refused (``DeviceUnsupported``): the
#    program says so from what it saw, nothing is configured.
# 3. *scan*: the aggregate inputs ride through the sort as payload (at most
#    ``_KEYED_MAX_INPUTS`` of them: an aggregate over more is refused), and
#    every state slot is one segmented inclusive scan, Hillis-Steele, a loop
#    of shifted combines that ends after log2 of the longest group: float
#    sums in float64, integer sums in int64, counts and the first position in
#    int32.
# 4. the last row of each segment holds its group: their positions are sorted
#    to the front (one operand) and the group table gathered from them, cut
#    to the capacity bucket. Only that table leaves the chip.
#
# Two capacities shape a program. The blocks that go on are the call's own:
# a small program, ``grouped-agg-keyed-probe`` (the mask over the predicate's
# columns, nothing else), counts the selected rows and the blocks that hold
# one, and what it says of a predicate's literals over a scan's files is
# remembered (it cannot change), so a query that comes again launches the one
# program. Every row that goes on is sorted, scanned and gathered from, so
# the blocks have a ladder of their own (:func:`_keyed_block_capacity`: the
# count rounded up to four significant bits, an eighth of waste at most,
# where the shared sqrt(2) ladder took up to 41 % more): predicates of about
# the same size still share an executable. The groups that come back
# are on the geometric ladder, remembered for the query shape in
# ``_CAP_HINT_MEMO`` as the chunk family does; a shape seen for the first
# time is sized by its selected rows and then run once more at the bucket of
# the groups it found, so that the steady program is compiled by the first
# call and not by the second.
# --------------------------------------------------------------------------

_KEYED_BLOCK_ROWS = 1024  # one tile of a one-dimensional 32-bit plane: the least a DMA can take
_KEYED_MAX_INPUTS = 2  # two 32-bit sort operands an input: what compiles in seconds
_KEYED_NO_CODE = np.uint32(0xFFFFFFFF)

_hlo_lint.register_contract(
    "grouped-agg-keyed",
    collectives={"all-gather": _ANY, "all-reduce": _ANY, "all-to-all": _ANY, "collective-permute": _ANY},
    description="grouped aggregate over integer and date keys of resident columns: block skip, code sort, segmented scan; only the group table leaves",
)
_hlo_lint.register_contract(
    "grouped-agg-keyed-probe",
    collectives={"all-gather": _ANY, "all-reduce": _ANY, "all-to-all": _ANY, "collective-permute": _ANY},
    description="the keyed grouped aggregate's predicate alone: selected rows and the blocks that hold one, two scalars back",
)


def _keyed_key_plan(group_keys, aggs, codecs, dev_cols=None):
    """``[(key, nullable)]`` for keys this program can code: integers, dates
    (``nullable``: NaT, the int64 minimum, is a group of its own) and
    dictionary codes (the null code -1 is a value like any other). A float
    key raises DeviceUnsupported, and so do more aggregate inputs than ride
    through the sort; dry codecs tell no float from an integer, so that is
    asked again of the device columns."""
    inputs = {c for _, _, c in aggs if c is not None}
    if len(inputs) > _KEYED_MAX_INPUTS:
        raise DeviceUnsupported(f"{len(inputs)} aggregate inputs, the keyed program carries {_KEYED_MAX_INPUTS}")
    plan = []
    for k in group_keys:
        codec = codecs[k]
        dtype = codec.dtype if dev_cols is None else dev_cols[k].dtype
        if codec.kind == "numeric" and dtype is not None and np.dtype(dtype).kind == "f":
            raise DeviceUnsupported(f"float group key {k!r}")
        plan.append((k, codec.kind == "datetime"))
    return plan


def _shifted(x, d, fill):
    """``x[i - d]`` for ``i >= d`` and ``fill`` before; ``d`` is traced."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    padded = jnp.concatenate([jnp.full((n,), fill, x.dtype), x])
    return jax.lax.dynamic_slice(padded, (n - d,), (n,))


def _segmented_scan(start, values, folds):
    """Inclusive scans of ``values`` that start again wherever ``start`` is
    set, each with its own combiner of ``folds``: Hillis-Steele, one loop
    body whatever the length. A row's flag is set once its segment's start
    lies within the distance covered, so the loop ends when every flag is:
    after log2 of the LONGEST segment, not of the rows (a row outside any
    segment has to be flagged a start of its own, or it never ends)."""
    import jax
    import jax.numpy as jnp

    steps = max(1, int(np.ceil(np.log2(max(2, start.shape[0])))))

    def more(carry):
        k, flag, _ = carry
        return (k < steps) & ~flag.all()

    def body(carry):
        k, flag, xs = carry
        d = jnp.int32(1) << k
        out = tuple(
            jnp.where(flag, x, fold(_shifted(x, d, x.dtype.type(0)), x)) for x, fold in zip(xs, folds)
        )
        return k + 1, flag | _shifted(flag, d, True), out

    return jax.lax.while_loop(more, body, (jnp.int32(0), start, tuple(values)))[2]


def _keyed_mask(pred_fn, pred_cols, total: int, cols, lits, n_valid):
    """``(selected rows' mask, which whole blocks hold one)``: the part the
    probe and the program share."""
    import jax
    import jax.numpy as jnp

    n_full = total // _KEYED_BLOCK_ROWS
    with jax.named_scope("filter"):
        mask = jnp.arange(total, dtype=jnp.int32) < n_valid.astype(jnp.int32)
        if pred_fn is not None:
            mask = pred_fn(join_columns({c: cols[c] for c in pred_cols}), lits) & mask
        hit = mask[: n_full * _KEYED_BLOCK_ROWS].reshape(n_full, _KEYED_BLOCK_ROWS).any(axis=1)
    return mask, hit


def _keyed_probe(pred_fn, pred_cols, total: int):
    """The traced body of ``grouped-agg-keyed-probe``: how many rows the
    predicate selects and how many whole blocks hold one of them."""
    import jax.numpy as jnp

    def program(cols, lits, n_valid):
        mask, hit = _keyed_mask(pred_fn, pred_cols, total, cols, lits, n_valid)
        return mask.sum(dtype=jnp.int32), hit.sum(dtype=jnp.int32)

    return program


def _keyed_block_capacity(n_hit: int) -> int:
    """The blocks a program is built for when ``n_hit`` hold a selected row:
    ``n_hit`` rounded up to four significant bits (5,000 -> 5,120), a step of
    an eighth at most. The ladder is this program's own: every row that goes on
    is sorted, so the sqrt(2) steps of :func:`group_capacity`, which the row
    shapes and group tables share, would sort up to 41 % more rows than hold
    a selected one for the sake of fewer executables."""
    n = max(1, int(n_hit))
    shift = max(0, n.bit_length() - 4)
    return -(-n >> shift) << shift


def _keyed_skip(total: int, cap_blocks: int, mesh) -> Tuple[str, int]:
    """``(the skip's form, rows that go on behind it)`` for a program built
    for ``cap_blocks`` of ``total`` padded rows' blocks: ``whole`` where every
    block goes on (nothing is skipped, the scan itself is sorted); else
    ``copy`` on one device (the blocks by DMA out of the planes as they lie)
    and ``layout`` on a mesh (the planes are sharded by rows there, and the
    partitioner has to see the skip: rows of the plane laid out in blocks)."""
    n_full, tail = divmod(total, _KEYED_BLOCK_ROWS)
    if cap_blocks >= n_full:
        return "whole", total
    return "copy" if mesh.devices.size == 1 else "layout", (cap_blocks + bool(tail)) * _KEYED_BLOCK_ROWS


def _keyed_program(pred_fn, comp_fn, key_plan, slots, after, total: int, cap_blocks: int, cap: int, mesh):
    """The traced body of ``grouped-agg-keyed`` for ``total`` padded rows,
    ``cap_blocks`` blocks going on (no fewer than hold a selected row: the
    probe counted them) and a group table of ``cap`` rows. ``after``: (the
    predicate's columns, the columns read behind the skip)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.kernels import copy_blocks

    block = _KEYED_BLOCK_ROWS
    n_full, tail = divmod(total, block)  # whole blocks, and the rows of the short last one
    form, rows_on = _keyed_skip(total, cap_blocks, mesh)
    i64 = jnp.iinfo(jnp.int64)
    pred_cols, after_cols = after
    input_cols = sorted({col for _, col, _ in slots if col is not None})

    def sort_front(values, keep: int):
        """The ``keep`` least of ``values`` (int32), ascending."""
        return jax.lax.sort((values,), num_keys=1, is_stable=False)[0][:keep]

    def program(cols, lits, n_valid):
        mask, hit = _keyed_mask(pred_fn, pred_cols, total, cols, lits, n_valid)
        if form == "whole":
            sub, on = join_columns({c: cols[c] for c in after_cols}), mask
        else:
            with jax.named_scope("skip"):
                numbers = sort_front(jnp.where(hit, jnp.arange(n_full, dtype=jnp.int32), jnp.int32(n_full)), cap_blocks)
                rows = jnp.minimum(numbers, n_full - 1)

                def take(planes):
                    """The blocks that go on of every plane, then its short
                    last block, padded. The two forms of the skip."""
                    if form == "copy":
                        out = copy_blocks(rows, planes, block)
                    else:
                        out = [p[: n_full * block].reshape(n_full, block)[rows].reshape(cap_blocks * block) for p in planes]
                    if tail:
                        out = [jnp.concatenate([o, jnp.pad(p[n_full * block:], (0, block - tail))]) for o, p in zip(out, planes)]
                    return out

                # the predicate's columns go with the rest: the mask is asked of them again
                planes, columns = jax.tree_util.tree_flatten({c: cols[c] for c in sorted({*pred_cols, *after_cols})})
                sub = join_columns(jax.tree_util.tree_unflatten(columns, take(planes)))
                at = (rows[:, None] * block + jnp.arange(block, dtype=jnp.int32)).reshape(cap_blocks * block)
                on = jnp.broadcast_to((numbers < n_full)[:, None], (cap_blocks, block)).reshape(cap_blocks * block)
                if tail:  # the padding of the last block lies past every valid row
                    at = jnp.concatenate([at, n_full * block + jnp.arange(block, dtype=jnp.int32)])
                    on = jnp.concatenate([on, jnp.ones((block,), bool)])
                on = on & (at < n_valid.astype(jnp.int32))
                if pred_fn is not None:
                    on = on & pred_fn({c: sub[c] for c in pred_cols}, lits)
                sub = {c: sub[c] for c in after_cols}
        if comp_fn is not None:
            sub = comp_fn(sub, lits)
        with jax.named_scope("key-encode"):
            code = jnp.zeros((rows_on,), jnp.uint32)
            span = jnp.float64(1.0)
            fits = jnp.bool_(True)
            least, widths = [], []
            for name, nullable in key_plan:
                k = sub[name].astype(jnp.int64)
                null = (k == i64.min) if nullable else jnp.zeros((rows_on,), bool)
                live = on & ~null
                lo = jnp.min(jnp.where(live, k, i64.max))
                hi = jnp.max(jnp.where(live, k, i64.min))
                lo, hi = jnp.where(hi >= lo, lo, 0), jnp.where(hi >= lo, hi, 0)
                reach = hi - lo  # wraps negative where the values span more than 63 bits
                fits = fits & (reach >= 0) & (reach < 2**32 - 2)
                width = reach + (2 if nullable else 1)
                off = jnp.where(null, reach + 1, k - lo).astype(jnp.uint32)
                code = code * width.astype(jnp.uint32) + off
                span = span * width.astype(jnp.float64)
                least.append(lo)
                widths.append(width)
            fits = fits & (span < 2.0**32 - 1)
            code = jnp.where(on, code, _KEYED_NO_CODE)
        with jax.named_scope("sort"):
            pos = jnp.arange(rows_on, dtype=jnp.int32)
            code, pos, *moved = jax.lax.sort(
                (code, pos) + tuple(sub[c] for c in input_cols), num_keys=1, is_stable=False
            )
            inputs = dict(zip(input_cols, moved))
        with jax.named_scope("segments"):
            on = code != _KEYED_NO_CODE
            start = on & jnp.concatenate([jnp.ones((1,), bool), code[1:] != code[:-1]])
            end = on & jnp.concatenate([code[1:] != code[:-1], jnp.ones((1,), bool)])
            n_groups = end.sum(dtype=jnp.int32)
        with jax.named_scope("group-scan"):
            operands = {"fs": (pos, jnp.minimum)}
            at = []
            for kind, col, isint in slots:
                if kind == "cntm" or (kind == "cnt" and isint):
                    kind, col = "cnt", None  # every selected row of an int column counts
                key = (kind, col, isint)
                if key not in operands:
                    if col is None:
                        operands[key] = (on.astype(jnp.int32), jnp.add)
                    else:
                        x = inputs[col]
                        nn = on if isint else (on & ~jnp.isnan(x))
                        z = x.astype(jnp.int64) if isint else x.astype(jnp.float64)
                        if kind == "cnt":
                            operands[key] = (nn.astype(jnp.int32), jnp.add)
                        elif kind in ("sum", "sumsq"):
                            z = z * z if kind == "sumsq" else z
                            operands[key] = (jnp.where(nn, z, z.dtype.type(0)), jnp.add)
                        elif kind == "min":
                            fill = i64.max if isint else jnp.inf
                            operands[key] = (jnp.where(nn, z, z.dtype.type(fill)), jnp.minimum)
                        else:  # max
                            fill = i64.min if isint else -jnp.inf
                            operands[key] = (jnp.where(nn, z, z.dtype.type(fill)), jnp.maximum)
                at.append(key)
            values, folds = zip(*operands.values())
            scanned = dict(zip(operands, _segmented_scan(start | ~on, values, folds)))
        with jax.named_scope("group-table"):
            last = sort_front(jnp.where(end, jnp.arange(rows_on, dtype=jnp.int32), jnp.int32(rows_on)), cap)
            last = jnp.minimum(last, rows_on - 1)
            if last.shape[0] < cap:
                last = jnp.pad(last, (0, cap - last.shape[0]))

            def table(x):
                x = x[last]
                return x.astype(jnp.int64) if jnp.issubdtype(x.dtype, jnp.integer) else x

        return (
            (n_groups, fits),
            (jnp.stack(least), jnp.stack(widths)),
            code[last],
            table(scanned["fs"]),
            tuple(table(scanned[key]) for key in at),
        )

    return program


def _count_keyed_rows(selected: int, rows_on: int) -> None:
    """One launch of ``grouped-agg-keyed`` in ``hs_keyed_rows_total{kind}``:
    ``selected``, the rows its predicate selects (the probe's count), and
    ``sorted``, the rows that went on behind the skip. Their ratio says how
    much of what is sorted, scanned and gathered the predicate asked for."""
    for kind, rows in (("selected", selected), ("sorted", rows_on)):
        _REGISTRY.counter(
            "hs_keyed_rows_total",
            "Rows of the keyed grouped aggregate's launches: selected by the predicate, sorted behind the skip",
            kind=kind,
        ).inc(rows)


def _keyed_selected(session, cols, dev_cols, pred_fn, pred_cols, lit_values, skeleton, total: int):
    """``(rows the predicate selects, whole blocks that hold one)``: what the
    keyed program's shape follows from. One launch of the probe the first
    time these literals are asked of these files; the answer cannot change,
    so it is kept with the other capacities and a query that comes again
    launches the one program."""
    n = cols.rows
    if pred_fn is None:
        return n, total // _KEYED_BLOCK_ROWS
    memo_key = None
    if cols.scan_key is not None:
        memo_key = (cols.scan_key, "keyed-selected", skeleton, tuple(np.asarray(v).tobytes() for v in lit_values))
        known = _CAP_HINT_MEMO.get(memo_key)
        if known is not None:
            return known
    taken = {c: dev_cols[c] for c in pred_cols}
    key = _program_key(f"gkeyed-probe[{total}]:{skeleton}", cols.mesh)
    jitted = _cached_predicate_jit(key, _keyed_probe(pred_fn, pred_cols, total), "grouped-agg-keyed-probe")
    first = _note_compile(key, tuple(taken[c].shape for c in sorted(taken)))
    _hlo_lint.maybe_verify(session.conf, "grouped-agg-keyed-probe", key, jitted, (taken, lit_values, np.int64(n)))
    t0 = _ptime.perf_counter()
    count_column_forms(taken.values())
    with launch("grouped-agg-keyed-probe"):
        out = jitted(taken, lit_values, np.int64(n))
    known = tuple(int(v) for v in fetch(out, "agg-table", "grouped-agg-keyed-probe"))
    _observe_program("grouped-agg-keyed-probe", first, t0)
    if memo_key is not None:
        _remember_capacity(memo_key, known)
    return known


def _keyed_grouped_aggregate(
    session, cols, dev_cols, codecs, pred_fn, comp_fn, lit_values, skeleton,
    group_keys, aggs, max_groups, cap_floor, after,
) -> B.Batch:
    mesh = cols.mesh
    n = cols.rows
    total = int(next(iter(dev_cols.values())).shape[0])
    if total >= 2**31:
        raise DeviceUnsupported("row positions past 32 bits")
    key_plan = _keyed_key_plan(group_keys, aggs, codecs, dev_cols)
    input_dtypes, slots, refs = _resident_slots(aggs, comp_fn, dev_cols, codecs, lit_values)
    max_groups = int(max_groups) if max_groups else 1 << 20
    cap_floor = max(1, int(cap_floor))
    n_blocks = total // _KEYED_BLOCK_ROWS  # whole blocks: the short last one always goes on

    n_selected, n_hit = _keyed_selected(session, cols, dev_cols, pred_fn, after[0], lit_values, skeleton, total)
    cap_blocks = min(n_blocks, _keyed_block_capacity(n_hit))
    skip, rows_on = _keyed_skip(total, cap_blocks, mesh)
    hint = (cols.scan_key, tuple(group_keys), tuple((fn, c) for _, fn, c in aggs))
    groups_hint = _CAP_HINT_MEMO.get(hint) if cols.scan_key is not None else None
    # a shape no call has answered yet is sized by its selected rows
    cap = group_capacity(groups_hint or min(n_selected, max_groups), cap_floor)
    base = (
        f"{skeleton}|k:{','.join(f'{k}:{int(nullable)}' for k, nullable in key_plan)}"
        f"|s:{','.join(f'{k}:{c}:{int(i)}' for k, c, i in slots)}"
    )
    shapes = tuple(dev_cols[r].shape for r in sorted(dev_cols))
    while True:
        cap = min(cap, group_capacity(rows_on, cap_floor))  # no more groups than rows going on
        program = _keyed_program(pred_fn, comp_fn, key_plan, slots, after, total, cap_blocks, cap, mesh)
        key = _program_key(f"gkeyed[{total},{cap_blocks},{cap}]:{base}", mesh)  # the body is built for its shapes
        jitted = _cached_predicate_jit(key, program, "grouped-agg-keyed")
        first = _note_compile(key, shapes)
        _hlo_lint.maybe_verify(
            session.conf, "grouped-agg-keyed", key, jitted, (dev_cols, lit_values, np.int64(n))
        )
        t0 = _ptime.perf_counter()
        count_column_forms(dev_cols.values())
        with launch("grouped-agg-keyed"):
            out = jitted(dev_cols, lit_values, np.int64(n))
        _count_keyed_rows(n_selected, rows_on)
        n_groups, fits = (int(v) for v in fetch(out[0], "agg-table", "grouped-agg-keyed"))
        _observe_program("grouped-agg-keyed", first, t0)
        if not fits:
            raise DeviceUnsupported("group keys span more than 32 bits together")
        if n_groups > max_groups:
            exc = GroupCapacityExceeded(f"group cardinality {n_groups} exceeds maxGroups {max_groups}")
            exc.folded = False
            raise exc
        steady = group_capacity(n_groups, cap_floor)
        if n_groups > cap or (first and groups_hint is None and steady < cap):
            # the table was too small; or it was sized by the selected rows, and
            # the program this shape settles on is compiled now, not by the next call
            cap, groups_hint = steady, n_groups
            continue
        break
    if cols.scan_key is not None:
        _remember_capacity(hint, max(groups_hint or 0, n_groups))
    (least, widths), codes, fs, slot_out = fetch(out[1:], "agg-table", "grouped-agg-keyed")
    trace.agg_rows("device", n)

    # groups in first-appearance order (pandas sort=False), keys decoded from the code
    order = np.argsort(fs[:n_groups], kind="stable")
    radix = codes[:n_groups].astype(np.int64)[order]
    result: B.Batch = {}
    for (name, nullable), lo, width in reversed(list(zip(key_plan, least.tolist(), widths.tolist()))):
        off = radix % width
        radix = radix // width
        codec = codecs[name]
        if codec.kind == "string":
            vals = np.full(n_groups, np.nan, dtype=object)
            code = off + lo
            pos = code >= 0
            if pos.any():
                vals[pos] = np.asarray(codec.uniques, dtype=object)[code[pos]]
            result[name] = vals
        elif nullable:
            days = np.where(off == width - 1, np.iinfo(np.int64).min, off + lo)
            result[name] = days.astype(np.int64).view(f"M8[{codec.unit}]")
        else:
            dtype = np.dtype(codec.dtype) if codec.dtype is not None else np.dtype(np.int64)
            result[name] = (off + lo).astype(dtype if dtype.kind in ("i", "u", "b") else np.int64)
    result = {k: result[k] for k in group_keys}
    result.update(_final_columns(aggs, refs, input_dtypes, [s[:n_groups][order] for s in slot_out]))
    _count_groups("grouped-agg-keyed", n_groups)
    _annotate_tier(
        program="grouped-agg-keyed", groups=n_groups, capacity=cap,
        selected_rows=n_selected, blocks=cap_blocks, rows_on=rows_on, skip=skip,
    )
    return result


def _resident_slots(aggs, comp_fn, dev_cols, codecs, lit_values):
    """``(input dtypes, slots, refs)`` of ``aggs`` over resident columns: an
    input's dtype is the host column's (the codec kept it) or what its
    computed expression evaluates to; :func:`_grouped_slots` over those."""
    computed = _computed_dtypes(comp_fn, dev_cols, lit_values)
    input_dtypes = {}
    for _, _fn, c in aggs:
        if c is not None:
            host_dtype = computed[c] if c in computed else codecs[c].dtype
            input_dtypes[c] = np.dtype(dev_cols[c].dtype if host_dtype is None else host_dtype)
    slots, refs = _grouped_slots(aggs, {c: dt.kind in ("i", "u", "b") for c, dt in input_dtypes.items()})
    return input_dtypes, slots, refs


def _computed_dtypes(comp_fn, dev_cols, lit_values) -> Dict[str, np.dtype]:
    """Dtype each computed input evaluates to, from the shapes alone: an
    integer-valued expression keeps the host path's exact int64 states."""
    if comp_fn is None:
        return {}
    import jax

    shapes = jax.eval_shape(lambda cols, lits: comp_fn(join_columns(cols), lits), dev_cols, lit_values)
    return {c: np.dtype(v.dtype) for c, v in shapes.items() if c not in dev_cols}


# --------------------------------------------------------------------------
# bucketed shuffle-free merge join
# --------------------------------------------------------------------------


def _strip_projects(plan: L.LogicalPlan) -> Tuple[L.LogicalPlan, Optional[List[str]]]:
    cols = None
    while isinstance(plan, L.Project):
        cols = list(plan.columns) if cols is None else cols
        plan = plan.child
    return plan, cols


def _side_bucket_spec(node: L.LogicalPlan) -> Optional[L.BucketSpec]:
    """The bucket layout a join side arrives in, looking through the
    layout-preserving wrappers (Project/Filter). Covers plain IndexScans AND
    hybrid-scan sides (BucketUnion of index minus deletes + re-bucketed
    appends — ref: CoveringIndexRuleUtils.scala:146-288)."""
    spec = getattr(node, "bucket_spec", None)
    if spec is not None:
        return spec
    if isinstance(node, (L.Project, L.Filter)):
        return _side_bucket_spec(node.child)
    return None


def join_sides_compatible(plan: L.Join) -> Optional[Tuple[L.LogicalPlan, L.LogicalPlan, List[str], List[str]]]:
    """If both join children arrive bucketed on exactly the join keys with
    equal bucket counts — index scans or hybrid-scan BucketUnions — return
    (left_side, right_side, lkeys, rkeys); else None (ref: JoinIndexRanker's
    equal-bucket preference, HS/index/covering/JoinIndexRanker.scala:52-92)."""
    if plan.residual is not None:
        return None  # non-equi ON residuals run on the host join path
    pairs = extract_equi_join_keys(plan.condition)
    if not pairs:
        return None
    lspec = _side_bucket_spec(plan.left)
    rspec = _side_bucket_spec(plan.right)
    if lspec is None or rspec is None or lspec.num_buckets != rspec.num_buckets:
        return None
    lcols = set(plan.left.output_columns)
    rcols = set(plan.right.output_columns)
    lkeys, rkeys = [], []
    for a, b in pairs:
        if a in lcols and b in rcols:
            lkeys.append(a)
            rkeys.append(b)
        elif b in lcols and a in rcols:
            lkeys.append(b)
            rkeys.append(a)
        else:
            return None
    from hyperspace_tpu.plan.expr import strip_nested_prefix

    def norm(cols):
        return [strip_nested_prefix(c).lower() for c in cols]

    if norm(lspec.bucket_columns) != norm(lkeys) or norm(rspec.bucket_columns) != norm(rkeys):
        return None
    return plan.left, plan.right, lkeys, rkeys


def _bucket_files(scan: L.IndexScan) -> Dict[int, List[str]]:
    """An IndexScan's files grouped per bucket id, in scan order (the file
    name carries the bucket; ref layout: part-<bucket>.parquet,
    indexes/covering.py)."""
    from hyperspace_tpu.indexes.covering import bucket_of_file

    per_bucket: Dict[int, List[str]] = {}
    for f in scan.files:
        b = bucket_of_file(f)
        if b is None:
            raise DeviceUnsupported(f"index file {f!r} has no bucket id")
        per_bucket.setdefault(b, []).append(f)
    return per_bucket


def _merged_bucket(
    files: List[str], file_cols: List[str], columns: List[str], sort_keys: List[str], committed
) -> B.Batch:
    """One bucket's files as one batch sorted on ``sort_keys``: the files'
    ``file_cols`` under the names ``columns`` (nested index columns live
    under flat __hs_nested. names in the files). A bucket holds one sorted
    file for every build chunk that had rows for it (any table larger than
    ``batchRows``) and every incremental refresh in merge mode
    (UpdateMode.Merge — ref: actions/RefreshIncrementalAction.scala:115-128),
    so its concatenation is only piecewise sorted: the stable re-sort here is
    the one place that merges the runs."""
    from hyperspace_tpu.exec.io import read_parquet_batch

    batch = read_parquet_batch(files, file_cols, committed=committed)
    if file_cols != list(columns):
        batch = {o: batch[fc] for o, fc in zip(columns, file_cols)}
    if sort_keys and len(files) > 1:
        batch = _sort_bucket(batch, sort_keys)
    return batch


class _JoinSide(NamedTuple):
    """One join side as contiguous columns: every column ONE array holding
    the buckets in ascending order, each bucket merged on the sort keys, and
    ``offsets`` (int64[num_buckets + 1]) delimiting them. ``buckets`` are the
    ids that have a file, in scan order: a bucket without one is absent from
    ``views()``, a bucket a filter emptied is present with no rows.

    ``narrow[column][bucket]`` keeps a bucket's own array where its dtype
    differs from the contiguous column's (a nullable int column decodes as
    float64, a nullable bool as object, only in files that hold nulls): the
    views hand out exactly the per-bucket dtypes a per-bucket read gives."""

    columns: B.Batch
    offsets: np.ndarray
    buckets: Tuple[int, ...]
    narrow: Dict[str, Dict[int, np.ndarray]]

    def filtered(self, condition: Expr, columns: List[str]) -> "_JoinSide":
        """``columns`` of the rows ``condition`` keeps: one evaluation over
        the whole side, one mask, and the kept rows' offsets read from the
        mask's running count (order preserving: every bucket stays sorted)."""
        from hyperspace_tpu.plan.expr import as_bool_mask

        if not self.buckets:
            return self
        mask = as_bool_mask(condition.eval(self.columns))
        if mask.ndim == 0:  # a scalar predicate applies uniformly (B.mask_rows)
            mask = np.broadcast_to(mask, (int(self.offsets[-1]),))
        kept = B.mask_rows({c: self.columns[c] for c in columns}, mask)
        running = np.zeros(mask.shape[0] + 1, dtype=np.int64)
        np.cumsum(mask, out=running[1:])
        narrow = {
            c: {
                b: arr[mask[self.offsets[b] : self.offsets[b + 1]]]
                for b, arr in self.narrow[c].items()
            }
            for c in columns
            if c in self.narrow
        }
        return _JoinSide(kept, running[self.offsets], self.buckets, narrow)

    def views(self) -> Dict[int, B.Batch]:
        """The per-bucket batches the join tiers consume, as slices."""
        offs = self.offsets.tolist()
        out: Dict[int, B.Batch] = {}
        for b in self.buckets:
            lo, hi = offs[b], offs[b + 1]
            out[b] = {
                c: self.narrow[c][b] if b in self.narrow.get(c, ()) else arr[lo:hi]
                for c, arr in self.columns.items()
            }
        return out


_JOIN_SIDE_COUNTERS: Dict[str, object] = {}


def _count_join_side(result: str) -> None:
    c = _JOIN_SIDE_COUNTERS.get(result)
    if c is None:
        from hyperspace_tpu.obs.metrics import REGISTRY

        c = _JOIN_SIDE_COUNTERS[result] = REGISTRY.counter(
            "hs_join_side_total",
            "Index scans read as a bucketed join side, by whether every merged "
            "column was resident in the host cache or some were decoded and merged now",
            result=result,
        )
    c.inc()


def _read_buckets(scan: L.IndexScan, columns: List[str], sort_keys: List[str]) -> _JoinSide:
    """An IndexScan as a join side: ``columns`` of its files, bucket by
    bucket, each bucket merged on ``sort_keys`` (``_merged_bucket``).

    The merged order is a function of the index version alone, so the side is
    merged once and kept: every column is one entry of the host cache
    (exec/io._io_cache), keyed on the files' identities from the log entry,
    the sort keys and the file column — never on who asks, so every query
    over the same index version shares it, whatever its literals — and dropped
    with the files by ``purge_io_cache``. A column that is not resident is
    decoded and merged now with the sort keys; the stable sort over the same
    files in the same order lays every column out alike."""
    trace.record("scan", "index-bucketed")
    from hyperspace_tpu.exec.file_identity import scan_identity
    from hyperspace_tpu.exec.io import _io_cache_get

    if not scan.files:
        empty = np.zeros(scan.bucket_spec.num_buckets + 1, dtype=np.int64)
        return _JoinSide({c: np.empty(0) for c in columns}, empty, (), {})
    file_cols = [scan.file_column_of(c) for c in columns]
    sort_cols = [scan.file_column_of(k) for k in sort_keys]
    identity = scan_identity(scan)
    base = None if identity is None else ("join-side", identity, tuple(sort_cols))
    entries = {}
    if base is not None:
        for fc in file_cols:
            got = _io_cache_get(base + (fc,))
            if got is not None:
                entries[fc] = got
    missing = [fc for fc in dict.fromkeys(file_cols) if fc not in entries]
    if missing:
        _count_join_side("built")
        entries.update(_merge_side_columns(scan, missing, sort_cols, identity, base))
    else:
        _count_join_side("resident")
        for _ in scan.files:  # the events of a read the per-file cache answers
            trace.record("decode", "cached")
    first = entries[file_cols[0]]
    cols: B.Batch = {}
    narrow: Dict[str, Dict[int, np.ndarray]] = {}
    for c, fc in zip(columns, file_cols):
        entry = entries[fc]
        cols[c] = entry["values"]
        own = {b: a for b, a in entry.items() if not isinstance(b, str)}
        if own:
            narrow[c] = own
    return _JoinSide(cols, first["offsets"], tuple(first["buckets"].tolist()), narrow)


def _merge_side_columns(scan: L.IndexScan, file_cols: List[str], sort_cols: List[str], identity, base):
    """Decode ``file_cols`` of every bucket of ``scan`` with the sort
    columns, merge each bucket, and lay each column out as one array in
    ascending bucket order. Returns {file column -> cache entry}: ``values``,
    ``offsets``, ``buckets`` (ids in scan order) and, under its id, the own
    array of every bucket whose dtype the concatenation promoted
    (``_JoinSide.narrow``). Entries are cached under ``base`` + the column
    where the scan's files have an identity."""
    from hyperspace_tpu.exec.io import _io_cache_put, discard_reads

    per_bucket = _bucket_files(scan)
    read_cols = list(dict.fromkeys(list(sort_cols) + list(file_cols)))
    committed = committed_keys(scan)
    order = sorted(per_bucket)
    parts = [
        _merged_bucket(per_bucket[b], read_cols, read_cols, sort_cols, committed) for b in order
    ]
    if base is not None:
        # the merged copy takes the place of what the reads above left in the
        # cache under this column set, which no other reader asks for
        by_path = {k[0]: k for k in identity}
        for b in order:
            discard_reads([by_path[f] for f in per_bucket[b]], read_cols)
    nb = max(scan.bucket_spec.num_buckets, order[-1] + 1)
    counts = np.zeros(nb, dtype=np.int64)
    counts[order] = [B.num_rows(p) for p in parts]
    offsets = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    buckets = np.asarray(list(per_bucket), dtype=np.int64)
    out = {}
    for fc in file_cols:
        arrs = [p[fc] for p in parts]
        values = np.concatenate(arrs)
        entry = {"values": values, "offsets": offsets, "buckets": buckets}
        entry.update({b: a for b, a in zip(order, arrs) if a.dtype != values.dtype})
        _io_cache_put(None if base is None else base + (fc,), entry)
        out[fc] = entry
    return out


def _order_key_array(arr: np.ndarray) -> np.ndarray:
    """An order-preserving int64 view of ``arr``, null-safe: strings
    factorize to codes (null -> -1, before everything), datetimes view their
    epoch, floats use the IEEE total-order encoding — the exact encoding the
    index build sorts by (ops/encode.sort_key_int64), so sortedness checks
    and rank comparisons are sound for NaN too (a raw float comparison is
    NaN-blind and a raw object comparison TypeErrors on None)."""
    from hyperspace_tpu.ops.encode import sort_key_int64

    return sort_key_int64(arr)


def _sort_bucket(batch: B.Batch, sort_keys: List[str]) -> B.Batch:
    cols = [_order_key_array(batch[k]) for k in sort_keys]
    if not cols or cols[0].size <= 1:
        return batch
    if len(cols) == 1:
        k = cols[0]
        if np.any(k[1:] < k[:-1]):
            return B.take(batch, np.argsort(k, kind="stable"))
        return batch
    return B.take(batch, np.lexsort(cols[::-1]))  # first key primary


def _composite_ranks(
    l_arrs: List[np.ndarray], r_arrs: List[np.ndarray]
) -> Tuple[np.ndarray, np.ndarray]:
    """Order-preserving dense int64 ranks of the composite key tuples, shared
    across both sides: equal tuples (across sides) get equal ranks, and rank
    order is the lexicographic tuple order. Lets multi-column and string join
    keys reuse the single-int64 span machinery (native merge walk /
    searchsorted) unchanged."""
    n = l_arrs[0].shape[0]
    # order-preserving int codes for strings: python-string comparisons
    # inside lexsort dominate otherwise
    cols = [_order_key_array(np.concatenate([la, ra])) for la, ra in zip(l_arrs, r_arrs)]
    order = np.lexsort(cols[::-1])
    change = np.zeros(order.shape[0], dtype=bool)
    for c in cols:
        cs = c[order]
        if cs.shape[0] > 1:
            change[1:] |= cs[1:] != cs[:-1]
    ranks_sorted = np.cumsum(change.astype(np.int64))
    ranks = np.empty(order.shape[0], dtype=np.int64)
    ranks[order] = ranks_sorted
    return ranks[:n], ranks[n:]


def _filter_input_columns(node: L.Filter, columns: List[str]) -> List[str]:
    """What a join side's Filter needs of its child: ``columns`` and what
    the condition reads."""
    from hyperspace_tpu.plan.expr import contains_input_file_name

    if contains_input_file_name(node.condition):
        raise DeviceUnsupported("input_file_name() predicate on a join side")
    return list(dict.fromkeys(list(columns) + list(node.condition.references())))


def _contiguous_side(node: L.LogicalPlan, columns: List[str], sort_keys: List[str]) -> Optional[_JoinSide]:
    """A join side that is an IndexScan leaf under layout-preserving
    Projects and Filters, as one ``_JoinSide``: the leaf's resident side, and
    each Filter evaluated once over it. None for the hybrid-scan shapes,
    which ``_side_buckets`` assembles bucket by bucket."""
    node, _proj = _strip_projects(node)
    if isinstance(node, L.IndexScan):
        return _read_buckets(node, columns, sort_keys)
    if isinstance(node, L.Filter):
        inner_cols = _filter_input_columns(node, columns)
        child = _contiguous_side(node.child, inner_cols, sort_keys)
        if child is not None:
            return child.filtered(node.condition, columns)
    return None


def _side_buckets(
    session, node: L.LogicalPlan, columns: List[str], sort_keys: List[str]
) -> Dict[int, B.Batch]:
    """Per-bucket batches of one join side, each sorted on ``sort_keys``.

    An IndexScan leaf under Projects and Filters is read as one contiguous
    side and handed out as slices (``_contiguous_side``). The hybrid-scan
    shapes are assembled per bucket: lineage NOT-IN Filters over them
    (evaluated per bucket — layout preserving), Repartition of appended
    files (host re-bucketing with the SAME hash as the index build, so rows
    land in their index bucket), and BucketUnion (per-bucket concat of sorted
    runs, re-sorted once)."""
    side = _contiguous_side(node, columns, sort_keys)
    if side is not None:
        return side.views()
    node, _proj = _strip_projects(node)
    if isinstance(node, L.Filter):
        from hyperspace_tpu.plan.expr import as_bool_mask

        inner_cols = _filter_input_columns(node, columns)
        buckets = _side_buckets(session, node.child, inner_cols, sort_keys)
        out: Dict[int, B.Batch] = {}
        for b, batch in buckets.items():
            mask = as_bool_mask(node.condition.eval(batch))
            kept = B.mask_rows(batch, mask)  # order-preserving: stays sorted
            out[b] = {c: kept[c] for c in columns}
        return out
    if isinstance(node, L.Repartition):
        from hyperspace_tpu.exec.executor import Executor
        from hyperspace_tpu.ops.encode import hash_input_uint32
        from hyperspace_tpu.ops.hashing import bucket_ids_np

        spec = node.bucket_spec
        # hybrid scan re-buckets the SAME appended files on every query
        # against the index (ref: CoveringIndexRuleUtils.scala:357-417 —
        # on-the-fly re-bucketing is supposed to be the cheap path); cache
        # the per-bucket result on the appended files' identity so repeat
        # executions skip the decode + hash + sort entirely. A new append
        # changes the file list/mtimes and naturally misses.
        cache_key = None
        ident = plan_identity(node.child, (L.FileScan, L.Scan))
        if ident:
            cache_key = (
                "rebucket", ident, spec.num_buckets,
                tuple(spec.bucket_columns), tuple(columns), tuple(sort_keys),
                node.child.pretty(),
            )
        if cache_key is not None:
            hit = _REBUCKET_CACHE.get(cache_key)
            if hit is not None:
                trace.record("rebucket", "cached")
                return {b: dict(v) for b, v in hit.items()}
        batch = Executor(session).execute(node.child, required_columns=list(columns))
        try:
            key_cols = [batch[c] for c in spec.bucket_columns]
        except KeyError as e:
            raise DeviceUnsupported(f"bucket column missing from appended side: {e}")
        nb = spec.num_buckets
        ids = bucket_ids_np([hash_input_uint32(c) for c in key_cols], nb)
        order = np.argsort(ids, kind="stable")
        sorted_ids = ids[order]
        bounds = np.searchsorted(sorted_ids, np.arange(nb + 1))
        out = {}
        for b in range(nb):
            lo, hi = int(bounds[b]), int(bounds[b + 1])
            if hi > lo:
                idx = order[lo:hi]
                out[b] = _sort_bucket({c: batch[c][idx] for c in columns}, sort_keys)
        if cache_key is not None:
            nbytes = sum(a.nbytes for v in out.values() for a in v.values()
                         if hasattr(a, "nbytes"))
            # retain COPIES of the per-bucket dicts: the caller gets `out`
            # and may add derived keys; both hit and miss paths must hand
            # out equivalently isolated objects
            _REBUCKET_CACHE.put(cache_key, {b: dict(v) for b, v in out.items()}, nbytes)
            trace.record("rebucket", "computed")
        return out
    if isinstance(node, L.BucketUnion):
        parts = [_side_buckets(session, c, columns, sort_keys) for c in node.children()]
        keys = set()
        for p in parts:
            keys |= set(p)
        out = {}
        for b in keys:
            batches = [p[b] for p in parts if b in p]
            merged = batches[0] if len(batches) == 1 else B.concat(batches)
            out[b] = _sort_bucket(merged, sort_keys) if len(batches) > 1 else merged
        return out
    raise DeviceUnsupported(f"join side {type(node).__name__} is not a bucketed shape")


def _side_bucket_readers(session, node: L.LogicalPlan, columns: List[str], sort_keys: List[str]):
    """Lazy per-bucket readers for one join side: ``{bucket -> thunk}`` where
    each thunk decodes (and sorts/filters) ONLY that bucket when called. The
    streaming join walks buckets one at a time through these, so peak memory
    is one bucket pair instead of both whole sides (``_side_buckets``
    materializes everything — fine below the streaming threshold).

    Shapes mirror ``_side_buckets``: IndexScan leaves, layout-preserving
    Filters, Repartition of appended files (appends are small by the hybrid
    scan ratio caps, so that child materializes once, lazily), BucketUnion.
    """
    node, _proj = _strip_projects(node)
    if isinstance(node, L.IndexScan):
        file_cols = [node.file_column_of(c) for c in columns]
        committed = committed_keys(node)

        def make(files):
            def read() -> B.Batch:
                return _merged_bucket(files, file_cols, columns, sort_keys, committed)

            return read

        return {b: make(fs) for b, fs in _bucket_files(node).items()}
    if isinstance(node, L.Filter):
        from hyperspace_tpu.plan.expr import as_bool_mask

        inner_cols = _filter_input_columns(node, columns)
        child = _side_bucket_readers(session, node.child, inner_cols, sort_keys)

        def wrap(thunk):
            def read() -> Optional[B.Batch]:
                batch = thunk()
                if batch is None:  # empty bucket from a Repartition/BucketUnion child
                    return None
                mask = as_bool_mask(node.condition.eval(batch))
                kept = B.mask_rows(batch, mask)  # order-preserving: stays sorted
                return {c: kept[c] for c in columns}

            return read

        return {b: wrap(t) for b, t in child.items()}
    if isinstance(node, L.Repartition):
        # appended-files side: bounded small by hybridScan.maxAppendedRatio,
        # so materializing it once (on first bucket access) keeps the
        # streaming walk's memory profile intact
        cell: Dict[str, Dict[int, B.Batch]] = {}

        def load() -> Dict[int, B.Batch]:
            if "b" not in cell:
                cell["b"] = _side_buckets(session, node, columns, sort_keys)
            return cell["b"]

        nb = node.bucket_spec.num_buckets

        def make_r(b):
            def read() -> Optional[B.Batch]:
                return load().get(b)

            return read

        return {b: make_r(b) for b in range(nb)}
    if isinstance(node, L.BucketUnion):
        parts = [_side_bucket_readers(session, c, columns, sort_keys) for c in node.children()]
        keys = set()
        for p in parts:
            keys |= set(p)

        def make_u(b):
            def read() -> Optional[B.Batch]:
                batches = []
                for p in parts:
                    t = p.get(b)
                    if t is None:
                        continue
                    got = t()
                    if got is not None and B.num_rows(got):
                        batches.append(got)
                if not batches:
                    return None
                if len(batches) == 1:
                    return batches[0]
                return _sort_bucket(B.concat(batches), sort_keys)

            return read

        return {b: make_u(b) for b in keys}
    raise DeviceUnsupported(f"join side {type(node).__name__} is not a bucketed shape")


def _stream_join_dtype_hints(
    plan: L.Join, lside, rside, lcols_needed, rcols_needed
) -> Dict[str, np.dtype]:
    """Footer-derived dtypes for the join's output columns: a bucket where
    one side is absent still needs that side's columns typed (the whole-side
    path reads them from other buckets; per-bucket streaming can't), and an
    EMPTY streamed result is constructed entirely from these."""
    import pyarrow.parquet as pq
    from hyperspace_tpu.sources import schema as schema_codec

    def side_dtypes(side, cols) -> Dict[str, np.dtype]:
        scans = L.collect(side, lambda x: isinstance(x, L.IndexScan))
        if not scans or not scans[0].files:
            return {}
        try:
            sch = pq.read_schema(scans[0].files[0])
        except OSError:
            return {}
        out: Dict[str, np.dtype] = {}
        for c in cols:
            fc = scans[0].file_column_of(c)
            if fc in sch.names:
                try:
                    out[c] = schema_codec.arrow_to_numpy_dtype(sch.field(fc).type)
                except Exception:
                    pass
        return out

    lmap = side_dtypes(lside, lcols_needed)
    rmap = side_dtypes(rside, rcols_needed)
    hints: Dict[str, np.dtype] = {}
    for name in plan.output_columns:
        try:
            is_left, col = _join_column_source(name, lcols_needed, rcols_needed)
        except DeviceUnsupported:
            # a column with no resolvable side keeps no hint: cross-bucket
            # dtype promotion for it then depends on which buckets hold rows.
            # Surface the decision instead of silently narrowing it away.
            trace.fallback("join", "dtype_hint")
            trace.record("join", f"dtype-hint-dropped({name})")
            continue
        dt = (lmap if is_left else rmap).get(col)
        if dt is not None:
            hints[name] = dt
    return hints


def _count_join_stream_chunk() -> None:
    from hyperspace_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "hs_join_stream_chunks_total",
        "Chunks yielded by the streaming join paths (bucketed SMJ buckets + broadcast probe chunks)",
    ).inc()


def _chunk_nbytes(batch: B.Batch) -> int:
    return sum(int(np.asarray(a).nbytes) for a in batch.values())


def stream_bucketed_join(session, plan: L.Join, _compat=None):
    """Yield the bucketed SMJ's output ONE BUCKET AT A TIME: per bucket, both
    sides decode, spans compute (native merge walk / searchsorted), pairs
    expand, and the chunk is yielded before the next bucket's expansion. No
    operator state spans buckets, so memory stays O(bucket pair + one output
    chunk) at any scale — the out-of-core discipline Spark's streaming
    executors give the reference for free (ref:
    HS/index/covering/JoinIndexRule.scala:604-705, valid at any SF).

    With ``hyperspace.exec.join.pipeline.enabled`` (and the pipeline master
    switch) on, bucket b+1's BOTH side decodes — plus their span-key
    encodings, the expensive host half of the bucket — run on the prefetch
    pipeline (exec/pipeline.py) while bucket b's spans compute on the
    consumer thread, double-buffered under the pipeline depth/byte budgets
    and cancel-safe on generator close. Off, the serial consumer-thread loop
    is preserved bit-for-bit.

    Used above conf ``hyperspace.exec.stream.joinMinBytes`` (estimated from
    file sizes) by ``dispatch_bucketed_join``, and by
    ``DataFrame.to_local_iterator`` for callers that drain results
    incrementally. Chunk dtypes may differ across buckets (a nullable int
    column is float64 only in chunks holding nulls); ``B.concat`` promotes.
    """
    ensure_x64()
    from hyperspace_tpu import native

    compat = _compat if _compat is not None else join_sides_compatible(plan)
    if compat is None:
        raise DeviceUnsupported("join sides are not compatible bucketed index scans")
    lside, rside, lkeys, rkeys = compat
    if plan.how not in ("inner", "left", "right", "outer"):
        raise DeviceUnsupported(f"unsupported join type {plan.how!r}")
    needed = set(plan.output_columns) | {
        n[:-2] for n in plan.output_columns if n.endswith("#r")
    }
    lcols_needed = [c for c in lside.output_columns if c in needed or c in lkeys]
    rcols_needed = [c for c in rside.output_columns if c in needed or c in rkeys]
    lread = _side_bucket_readers(session, lside, lcols_needed, lkeys)
    rread = _side_bucket_readers(session, rside, rcols_needed, rkeys)
    nb = _side_bucket_spec(lside).num_buckets
    keep_left = plan.how in ("left", "outer")
    keep_right = plan.how in ("right", "outer")

    hints = _stream_join_dtype_hints(plan, lside, rside, lcols_needed, rcols_needed)
    parts = [b for b in range(nb) if b in lread or b in rread]

    def decode_pair(b):
        """Producer half: both side decodes + span-key encoding (the
        rank/int64 encode is the bucket's dominant host cost after decode,
        so it prefetches too)."""
        from hyperspace_tpu.reliability.faults import FAULTS

        if FAULTS.active:
            FAULTS.check("join.task")
        lt, rt = lread.get(b), rread.get(b)
        lb = lt() if lt is not None else None
        rb = rt() if rt is not None else None
        if lb is not None and B.num_rows(lb) == 0:
            lb = None
        if rb is not None and B.num_rows(rb) == 0:
            rb = None
        lk = rk = None
        if lb is not None and rb is not None:
            if len(lkeys) == 1:
                try:
                    lk = _join_key_of(lb, lkeys[0])
                    rk = _join_key_of(rb, rkeys[0])
                except DeviceUnsupported:
                    lk = rk = None
            if lk is None:
                lk, rk = _composite_ranks(
                    [lb[k] for k in lkeys], [rb[k] for k in rkeys]
                )
        return lb, rb, lk, rk

    def expand(lb, rb, lk, rk):
        """Consumer half: span walk + pair expansion; None when the bucket
        contributes no output rows."""
        if lb is None and rb is None:
            return None
        if lb is None and not keep_right:
            return None
        if rb is None and not keep_left:
            return None
        span_of = None
        if lb is not None and rb is not None:

            def span_of(_b, lk=lk, rk=rk):
                try:
                    return native.merge_spans(lk, rk)
                except native.NativeUnsupported:
                    return (
                        np.searchsorted(rk, lk, side="left"),
                        np.searchsorted(rk, lk, side="right"),
                    )

        chunk = _expand_join_pairs(
            plan,
            {0: lb} if lb is not None else {},
            {0: rb} if rb is not None else {},
            1,
            lcols_needed,
            rcols_needed,
            span_of,
            dtype_fallback=hints,
        )
        return chunk if B.num_rows(chunk) else None

    conf = session.conf
    if conf.join_pipeline_enabled and conf.pipeline_enabled and len(parts) > 1:
        from hyperspace_tpu.exec.pipeline import ScanPipeline

        def weigh(res):
            lb, rb, _lk, _rk = res
            return sum(_chunk_nbytes(s) for s in (lb, rb) if s is not None)

        pipe = ScanPipeline(
            [lambda b=b: decode_pair(b) for b in parts],
            depth=conf.pipeline_depth,
            max_buffered_bytes=conf.pipeline_max_buffered_bytes,
            weigh=weigh,
        )
        try:
            for lb, rb, lk, rk in pipe:
                chunk = expand(lb, rb, lk, rk)
                if chunk is not None:
                    _count_join_stream_chunk()
                    yield chunk
        finally:
            # generator close mid-stream lands here: cancel queued bucket
            # decodes and wait out in-flight ones so neither side's readers
            # outlive the stream (the pipeline cancel-safety contract)
            pipe.close()
        return

    for b in parts:
        chunk = expand(*decode_pair(b))
        if chunk is not None:
            _count_join_stream_chunk()
            yield chunk


@lru_cache(maxsize=32)
def _bucketed_span_program(mesh, axis: str):
    """Jitted per-bucket match-span program, cached per mesh so repeated joins
    reuse one XLA executable (jit's own cache handles shape variation)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def spans(lm, rm):
        @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=(P(axis), P(axis)))
        def per_shard(lm_, rm_):
            with jax.named_scope("span-probe"):
                lo = jax.vmap(lambda lk, rk: jnp.searchsorted(rk, lk, side="left"))(lm_, rm_)
                hi = jax.vmap(lambda lk, rk: jnp.searchsorted(rk, lk, side="right"))(lm_, rm_)
            return lo, hi
        return per_shard(lm, rm)

    return jax.jit(_hlo_lint.named("bucketed-smj-span", spans))


def _join_key_of(batch: B.Batch, key: str) -> np.ndarray:
    """Encode a join-key column; only identity-ordered encodings are
    cross-side comparable."""
    arr = batch[key]
    if arr.dtype.kind in ("i", "u", "b"):
        return arr.astype(np.int64)
    if arr.dtype.kind == "M":
        return arr.view("int64").astype(np.int64)
    raise DeviceUnsupported(f"device join requires integer/datetime keys; got {arr.dtype}")


_FOOTER_ROWS_CACHE: Dict[Tuple[str, int, int], int] = {}


def _file_num_rows(key) -> int:
    """Row count from the parquet footer, memoized on the file's identity
    ``key`` (file_identity: path, size, mtime)."""
    import pyarrow.parquet as pq

    got = _FOOTER_ROWS_CACHE.get(key)
    if got is None:
        if len(_FOOTER_ROWS_CACHE) > 65536:
            _FOOTER_ROWS_CACHE.clear()
        got = pq.read_metadata(key[0]).num_rows
        _FOOTER_ROWS_CACHE[key] = got
    return got


def _side_identity(node: L.LogicalPlan):
    """Identity of every file a join side's index and file scans read
    (file_identity.plan_identity); None when one cannot be stat'ed."""
    return plan_identity(node, (L.IndexScan, L.FileScan))


# composite-key rank encodings keyed on both sides' full identity, byte-capped
# like every other cache (exec/io.py's _io_cache pattern)
from hyperspace_tpu.utils.lru import BytesLRU

_RANK_CACHE = BytesLRU(int(os.environ.get("HS_RANK_CACHE_BYTES", 1 << 29)))

# re-bucketed hybrid-scan appends, keyed on the appended files' identity
# (see the Repartition branch of _side_buckets)
_REBUCKET_CACHE = BytesLRU(int(os.environ.get("HS_REBUCKET_CACHE_BYTES", 1 << 28)))


def _rank_cache_key(lside, rside, lkeys: List[str], rkeys: List[str]):
    """Identity of a rank encoding: both sides' file identities, the key
    names, AND the sides' plan text — ranks are computed over rows that
    survive the sides' Filters (lineage NOT-IN, pushed predicates), so a
    changed filter over identical files must miss. None (= don't cache) when
    any file has no identity."""
    sides = (_side_identity(lside), _side_identity(rside))
    if None in sides:
        return None
    return (tuple(lkeys), tuple(rkeys), lside.pretty(), rside.pretty()) + sides


def _fold_streamed_join(session, plan: L.Join, compat) -> B.Batch:
    """The host-span-smj-stream tier of :func:`dispatch_bucketed_join`: walk
    buckets one at a time and fold the chunks into one batch."""
    lside, rside, lkeys, rkeys = compat
    # fold chunks incrementally instead of list()-ing the whole
    # stream: peak memory is O(merged result + one pending run), not
    # O(result x2), and the generator is closed on any exit so both
    # sides' bucket readers release mid-stream
    gen = stream_bucketed_join(session, plan, _compat=compat)
    merged = None
    merged_bytes = 0
    pending: List[B.Batch] = []
    pending_bytes = 0
    try:
        for chunk in gen:
            pending.append(chunk)
            pending_bytes += _chunk_nbytes(chunk)
            # geometric fold: concat once the pending run reaches the
            # merged size, so total copy work stays O(result) while
            # at most one merged copy + one run are ever alive
            if merged is None or pending_bytes >= merged_bytes:
                batches = ([merged] if merged is not None else []) + pending
                merged = batches[0] if len(batches) == 1 else B.concat(batches)
                merged_bytes = _chunk_nbytes(merged)
                pending, pending_bytes = [], 0
    finally:
        gen.close()
    if pending:
        batches = ([merged] if merged is not None else []) + pending
        merged = batches[0] if len(batches) == 1 else B.concat(batches)
    if merged is None:
        # an empty streamed result must NOT fall back to the generic
        # merge — that materializes both multi-GiB sides, the OOM
        # this path exists to prevent; type the empty batch from the
        # index footers instead
        needed = set(plan.output_columns) | {
            n[:-2] for n in plan.output_columns if n.endswith("#r")
        }
        lc = [c for c in lside.output_columns if c in needed or c in lkeys]
        rc = [c for c in rside.output_columns if c in needed or c in rkeys]
        hints = _stream_join_dtype_hints(plan, lside, rside, lc, rc)
        if all(n in hints for n in plan.output_columns):
            trace.record("join", "host-span-smj-stream")
            return {n: np.empty(0, dtype=hints[n]) for n in plan.output_columns}
        raise DeviceUnsupported("streamed join produced no rows")
    trace.record("join", "host-span-smj-stream")
    return merged


def dispatch_bucketed_join(session, plan: L.Join) -> B.Batch:
    """Single entry point for the bucketed-SMJ paths: one compatibility
    analysis, then device or host spans by the input-rows threshold. Every
    key shape rides the device span program — single int/date keys feed it
    directly, composite and string keys through the shared per-bucket rank
    encodings (_encoded_join_keys). Raises DeviceUnsupported when the join
    isn't a compatible bucketed pair (the executor then falls back to its
    generic merge join)."""
    ensure_x64()
    compat = join_sides_compatible(plan)
    if compat is None:
        raise DeviceUnsupported("join sides are not compatible bucketed index scans")
    lside, rside, lkeys, rkeys = compat
    # both gates below read one pass over the sides' file identities: the
    # log entry's for index files, so sizing the dispatch costs no syscall
    sides = (_side_identity(lside), _side_identity(rside))
    file_keys = [k for side in sides for k in side] if None not in sides else None
    total = 0  # a file without identity or an unreadable footer -> stay on host
    if file_keys is not None:
        try:
            total = sum(_file_num_rows(k) for k in file_keys)
        except OSError:
            pass
    # out-of-core gate: above the streaming threshold (estimated from file
    # sizes — no decode), walk buckets one at a time instead of decoding
    # both whole sides; peak memory drops to O(bucket pair + output)
    stream_min = session.conf.stream_join_min_bytes
    if stream_min and stream_min > 0:
        input_bytes = sum(k[1] for k in file_keys) if file_keys is not None else 0
        if input_bytes >= stream_min:
            with _obs_spans.span("join-host-span-smj-stream", cat="exec"):
                return _fold_streamed_join(session, plan, compat)
    with _obs_spans.span("join-bucket-setup", cat="exec"):
        setup = _bucketed_join_setup(session, plan, compat)
    # the device span program's round trip is EXACTLY computable here: the
    # buckets are already decoded, and the key matrices are rectangles of
    # nb_padded x (widest bucket) int64 — skewed buckets pad every other
    # row to the widest, so raw row counts would badly undercount. Keys go
    # up (both rectangles), [lo, hi) comes down (16B per left SLOT). Above
    # the budget the host span walk (zero transfer) wins — the same
    # cost-based stance as joinDeviceMaterializeMaxBytes one level down.
    lbuckets_, rbuckets_, _lk_, _rk_, nb_, _lc_, _rc_ = setup
    n_dev_ = session.mesh.devices.size
    nb_padded_ = nb_ + ((-nb_) % n_dev_)
    wl_ = max((B.num_rows(b) for b in lbuckets_.values()), default=1)
    wr_ = max((B.num_rows(b) for b in rbuckets_.values()), default=1)
    span_bytes = nb_padded_ * (wl_ + wr_) * 8
    # the [lo, hi) matrices (16B/left slot) only come down when the
    # device-materialize path won't consume them on device; a materialize
    # run that later overflows ITS budget falls back to the host gather and
    # does download them once — accepted imprecision, bounded by one rep
    if plan.how != "inner" or not session.conf.join_device_materialize:
        span_bytes += nb_padded_ * wl_ * 16
    if (
        total >= session.conf.device_exec_min_rows
        and span_bytes <= session.conf.join_device_span_max_bytes
    ):
        with _obs_spans.span("join-device-smj", cat="exec") as tier:
            try:
                out = device_bucketed_join(session, plan, _compat=compat, _setup=setup)
                trace.record("join", "device-smj")
                return out
            except DeviceUnsupported:
                # e.g. a decoded batch outside the device language
                tier.set(fallback="unsupported")
    with _obs_spans.span("join-host-span-smj", cat="exec"):
        out = host_bucketed_join(session, plan, _compat=compat, _setup=setup)
        trace.record("join", "host-span-smj")
        return out


def _bucketed_join_setup(session, plan: L.Join, compat=None, needed_override=None):
    """Shared validation + per-bucket decode for the bucketed SMJ paths.

    Returns (lbuckets, rbuckets, lkeys, rkeys, nb, lcols_needed,
    rcols_needed). ``needed_override`` = (left cols, right cols) replaces the
    join-output-derived column need (the fused aggregate reads only its
    inputs, not the join's full output).
    """
    if compat is None:
        compat = join_sides_compatible(plan)
    if compat is None:
        raise DeviceUnsupported("join sides are not compatible bucketed index scans")
    lside, rside, lkeys, rkeys = compat
    if plan.how not in ("inner", "left", "right", "outer"):
        raise DeviceUnsupported(f"unsupported join type {plan.how!r}")

    # decode only the columns the consumer (plus keys) needs
    if needed_override is not None:
        need_l, need_r = set(needed_override[0]), set(needed_override[1])
    else:
        needed = set(plan.output_columns) | {n[:-2] for n in plan.output_columns if n.endswith("#r")}
        need_l = need_r = needed
    lcols_needed = [c for c in lside.output_columns if c in need_l or c in lkeys]
    rcols_needed = [c for c in rside.output_columns if c in need_r or c in rkeys]
    lbuckets = _side_buckets(session, lside, lcols_needed, lkeys)
    rbuckets = _side_buckets(session, rside, rcols_needed, rkeys)
    nb = _side_bucket_spec(lside).num_buckets
    return lbuckets, rbuckets, lkeys, rkeys, nb, lcols_needed, rcols_needed


def _expand_join_pairs(
    plan: L.Join,
    lbuckets: Dict[int, B.Batch],
    rbuckets: Dict[int, B.Batch],
    nb: int,
    lcols_needed: List[str],
    rcols_needed: List[str],
    span_of,
    dtype_fallback=None,
) -> B.Batch:
    """Pair expansion (variable-size output) + column gather, shared by the
    device and host span backends. ``span_of(b)`` returns (lo, hi) arrays of
    length len(left bucket b) — the matching right-row span per left row.

    Two passes: spans/counts first, then gathers straight into preallocated
    output columns (a concat of per-bucket batches would copy the whole
    result a second time). Outer joins (left/right/outer) emit unmatched rows
    with the opposite side's columns null (index -1 in the gather arrays;
    ints promote to float64 NaN, matching the pandas-merge fallback)."""
    how = plan.how
    keep_left = how in ("left", "outer")
    keep_right = how in ("right", "outer")
    out_cols = plan.output_columns
    lout = list(lcols_needed)
    rout = list(rcols_needed)

    # pass 1: per-bucket gather index arrays; -1 marks a null (unmatched) row
    from hyperspace_tpu import native

    def expand_inner(lo_b, counts, chunk_total):
        try:
            # int64 hi: expand_pairs itself guards the int32 range and
            # rejects oversize buckets back to the numpy path
            return native.expand_pairs(lo_b, np.asarray(lo_b, dtype=np.int64) + counts, chunk_total)
        except native.NativeUnsupported:
            ll = counts.shape[0]
            lidx = np.repeat(np.arange(ll), counts)
            offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
            ridx = np.arange(chunk_total) - np.repeat(offsets, counts) + np.repeat(lo_b, counts)
            return lidx, ridx

    # pieces hold (bucket, row_count, maker) with maker() -> (lidx, ridx);
    # expansion is deferred to pass 2 so only ONE bucket's index arrays are
    # alive at a time (peak memory matters on large inner joins)
    def matched_maker(lo_b, counts, keep_left_):
        def make():
            if keep_left_:
                # unmatched left rows expand as one (i, lo[i]) pair via the
                # same native kernel, then get their right index nulled
                counts_eff = np.maximum(counts, 1)
                ct = int(counts_eff.sum())
                lidx, ridx = expand_inner(np.asarray(lo_b), counts_eff, ct)
                null_rows = np.repeat(counts == 0, counts_eff)
                if null_rows.any():
                    ridx = np.asarray(ridx, dtype=np.int64)
                    ridx[null_rows] = -1
                return lidx, ridx
            return expand_inner(np.asarray(lo_b), counts, int(counts.sum()))

        return make

    pieces = []  # (bucket, count, maker)
    total = 0
    has_null_left = has_null_right = False
    for b in range(nb):
        lb = lbuckets.get(b)
        rb = rbuckets.get(b)
        ll = B.num_rows(lb) if lb is not None else 0
        rr = B.num_rows(rb) if rb is not None else 0
        if ll and rr:
            lo_b, hi_b = span_of(b)
            counts = (hi_b - lo_b).astype(np.int64)
            if keep_left:
                ct = int(np.maximum(counts, 1).sum())
                if (counts == 0).any():
                    has_null_right = True
                pieces.append((b, ct, matched_maker(lo_b, counts, True)))
                total += ct
            else:
                ct = int(counts.sum())
                if ct:
                    pieces.append((b, ct, matched_maker(lo_b, counts, False)))
                    total += ct
            if keep_right:
                # right rows covered by no span are unmatched
                cover = np.zeros(rr + 1, dtype=np.int64)
                sel = counts > 0
                np.add.at(cover, np.asarray(lo_b)[sel], 1)
                np.add.at(cover, np.asarray(hi_b)[sel], -1)
                unmatched = np.nonzero(np.cumsum(cover[:-1]) == 0)[0]
                if unmatched.size:
                    pieces.append(
                        (b, unmatched.size,
                         lambda u=unmatched: (np.full(u.size, -1, dtype=np.int64), u))
                    )
                    total += unmatched.size
                    has_null_left = True
        elif ll and keep_left:
            pieces.append((b, ll, lambda n_=ll: (np.arange(n_), np.full(n_, -1, dtype=np.int64))))
            total += ll
            has_null_right = True
        elif rr and keep_right:
            pieces.append((b, rr, lambda n_=rr: (np.full(n_, -1, dtype=np.int64), np.arange(n_))))
            total += rr
            has_null_left = True

    sources = {name: _join_column_source(name, lout, rout) for name in out_cols}
    participating = sorted({p[0] for p in pieces})
    # USING-style joins coalesce the key (Spark's df.join(other, on="k")):
    # a right/outer join's unmatched rows show the RIGHT side's key under
    # the left name instead of NULL — map left key output -> right source col
    coalesce_from = {}
    if keep_right and plan.using_pairs:
        for lk, rk in plan.using_pairs:
            if lk in out_cols and rk in rout:
                coalesce_from[lk] = rk

    def out_dtype(name: str) -> np.dtype:
        is_left, col = sources[name]
        src = lbuckets if is_left else rbuckets
        # promote across participating buckets (a nullable int column decodes
        # as float64 only in buckets whose files hold nulls), matching what
        # np.concatenate of per-bucket results used to do
        part = participating or sorted(src)
        dt = _join_column_dtype(
            name, sources[name], lbuckets, rbuckets, part, fallback=dtype_fallback
        )
        nullable = (is_left and has_null_left) or (not is_left and has_null_right)
        if nullable and dt.kind == "b":
            return np.dtype(object)  # pandas merge: bool + NaN -> object
        if nullable and dt.kind in ("i", "u"):
            return np.dtype(np.float64)  # pandas-merge null promotion
        return dt

    out = {name: np.empty(total, dtype=out_dtype(name)) for name in out_cols}
    if total == 0:
        return out

    def null_value(dt: np.dtype):
        if dt.kind == "M":
            return np.datetime64("NaT")
        if dt.kind == "m":
            return np.timedelta64("NaT")
        return np.nan  # float holes; pandas merge also fills object with NaN

    # pass 2: gather into the preallocated columns, expanding one bucket's
    # index arrays at a time
    off = 0
    for b, ct, make in pieces:
        lidx, ridx = make()
        for name in out_cols:
            is_left, col = sources[name]
            src = lbuckets if is_left else rbuckets
            idx = lidx if is_left else ridx
            arr = src.get(b, {}).get(col)
            if arr is None or arr.shape[0] == 0:
                # side absent for this bucket (or filtered to zero rows):
                # every index here is -1 by construction
                out[name][off : off + ct] = null_value(out[name].dtype)
                nulls = np.ones(ct, dtype=bool)
            else:
                nulls = np.asarray(idx) < 0
                if nulls.any():
                    vals = out[name][off : off + ct]
                    vals[:] = arr[np.clip(idx, 0, arr.shape[0] - 1)].astype(
                        out[name].dtype, copy=False
                    )
                    vals[nulls] = null_value(out[name].dtype)
                else:
                    out[name][off : off + ct] = arr[idx]
            alt = coalesce_from.get(name) if is_left else None
            if alt is not None and nulls.any():
                # left-null rows came from right-unmatched emissions: their
                # ridx is valid, so the USING key takes the right side's value
                ralt = rbuckets.get(b, {}).get(alt)
                fill = np.asarray(ridx)[nulls]
                ok = fill >= 0
                if ralt is not None and ralt.shape[0] and ok.any():
                    vals = out[name][off : off + ct]
                    sel = np.nonzero(nulls)[0][ok]
                    vals[sel] = ralt[fill[ok]].astype(out[name].dtype, copy=False)
        off += ct
    return out


def device_bucketed_join(session, plan: L.Join, _compat=None, _setup=None) -> B.Batch:
    """Execute a compatible bucketed equi-join on device.

    Per-bucket sorted runs of both sides are padded to rectangles, sharded over
    the mesh's bucket axis, and each device computes, for every left row, the
    [lo, hi) span of matching right rows via two vmapped ``searchsorted``
    passes — no collective is emitted (the reference's no-exchange SMJ,
    HS/index/covering/JoinIndexRule.scala:604-618). Pair expansion and column
    gathering happen host-side (variable-size output).
    """
    ensure_x64()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    setup = _setup if _setup is not None else _bucketed_join_setup(session, plan, _compat)
    lbuckets, rbuckets, lkeys, rkeys, nb, lcols_needed, rcols_needed = setup

    SENTINEL = np.int64(2**62)
    mesh = session.mesh
    n_dev = mesh.devices.size
    axis = mesh.axis_names[0]
    nb_padded = nb + ((-nb) % n_dev)

    # index bucket files are immutable (versioned v__=N dirs), so the sharded
    # key matrices stay resident in HBM across queries — same stance as the
    # predicate-column cache above; only the first execution of a (sides,
    # keys) pair pays the host->device transfer
    compat = _compat or join_sides_compatible(plan)
    pair_key = _rank_cache_key(compat[0], compat[1], lkeys, rkeys)
    mesh_tag = (n_dev, axis, tuple(str(d) for d in mesh.devices.flat))
    dev_key = ("join-keymats", pair_key, mesh_tag) if pair_key is not None else None
    cached = _device_cache_get(dev_key) if dev_key is not None else None
    if cached is not None:
        lmat_dev, rmat_dev, llens, rlens = cached
    else:
        # shared per-bucket int64 encodings: identity for single int/date
        # keys, dense cross-side ranks for composite/string keys — so every
        # key shape rides the device span program
        lkeys_by_bucket, rkeys_by_bucket = _encoded_join_keys(
            plan, setup, compat, _pair_key=pair_key
        )

        def stack_side(buckets: Dict[int, B.Batch], keymap: Dict[int, np.ndarray]):
            lens = [B.num_rows(buckets[b]) if b in buckets else 0 for b in range(nb_padded)]
            # bucket the rectangle width so streamed chunks of slightly
            # varying bucket sizes reuse the span program's executable
            width = bucket_rows(max(max(lens), 1), floor=256)
            keys_mat = np.full((nb_padded, width), SENTINEL, dtype=np.int64)
            for b in range(nb_padded):
                enc = keymap.get(b)
                if enc is not None and enc.shape[0]:
                    keys_mat[b, : enc.shape[0]] = enc
            return keys_mat, np.asarray(lens, dtype=np.int64)

        lmat, llens = stack_side(lbuckets, lkeys_by_bucket)
        rmat, rlens = stack_side(rbuckets, rkeys_by_bucket)
        sharding = NamedSharding(mesh, P(axis))
        lmat_dev = put(lmat, "join-mats", sharding)
        rmat_dev = put(rmat, "join-mats", sharding)
        if dev_key is not None:
            _device_cache_put(
                dev_key, (lmat_dev, rmat_dev, llens, rlens), lmat.nbytes + rmat.nbytes
            )

    spans = _bucketed_span_program(mesh, axis)
    first = _note_compile("join-span", (tuple(lmat_dev.shape), tuple(rmat_dev.shape)))
    _hlo_lint.maybe_verify(
        session.conf, "bucketed-smj-span", _program_key("join-span", mesh),
        spans, (lmat_dev, rmat_dev),
    )
    t0 = _ptime.perf_counter()
    with launch("bucketed-smj-span"):
        lo, hi = spans(lmat_dev, rmat_dev)
    _observe_program("bucketed-smj-span", first, t0)

    if plan.how == "inner" and session.conf.join_device_materialize:
        try:
            return _device_materialize_inner(
                session, plan, lbuckets, rbuckets, lcols_needed, rcols_needed,
                lo, hi, llens, rlens, nb, nb_padded,
                _ident=(pair_key, mesh_tag) if pair_key is not None else None,
            )
        except DeviceUnsupported:
            pass  # e.g. typed-empty output or odd column shapes -> host gather

    lo, hi = fetch((lo, hi), "join-out", "bucketed-smj-span")

    def span_of(b: int):
        ll = int(llens[b])
        return lo[b, :ll], hi[b, :ll]

    return _expand_join_pairs(plan, lbuckets, rbuckets, nb, lcols_needed, rcols_needed, span_of)


def _encoded_join_keys(plan: L.Join, setup, compat, _pair_key=None):
    """Per-bucket int64 key arrays for both sides, order-preserving and
    cross-side comparable. Single int64-comparable keys pass through;
    composite and string keys encode per bucket into shared dense int64
    ranks, cached across queries on the sides' immutable file + filter
    identity. The SAME arrays feed the host merge walk and the device span
    program, so both backends cover every key shape. ``_pair_key`` lets a
    caller that already computed `_rank_cache_key` pass it through."""
    lbuckets, rbuckets, lkeys, rkeys, _nb, _lc, _rc = setup

    single_int = len(lkeys) == 1
    lkeys_by_bucket: Dict[int, np.ndarray] = {}
    rkeys_by_bucket: Dict[int, np.ndarray] = {}
    if single_int:
        try:
            for b, batch in lbuckets.items():
                lkeys_by_bucket[b] = _join_key_of(batch, lkeys[0])
            for b, batch in rbuckets.items():
                rkeys_by_bucket[b] = _join_key_of(batch, rkeys[0])
        except DeviceUnsupported:
            single_int = False
    if not single_int:
        lside, rside = (compat or join_sides_compatible(plan))[:2]
        cache_key = (
            _pair_key
            if _pair_key is not None
            else _rank_cache_key(lside, rside, lkeys, rkeys)
        )
        cached = _RANK_CACHE.get(cache_key) if cache_key is not None else None
        if cached is not None:
            lkeys_by_bucket, rkeys_by_bucket = cached
        else:
            lkeys_by_bucket.clear()
            rkeys_by_bucket.clear()
            for b in set(lbuckets) & set(rbuckets):
                lr, rr = _composite_ranks(
                    [lbuckets[b][k] for k in lkeys], [rbuckets[b][k] for k in rkeys]
                )
                lkeys_by_bucket[b] = lr
                rkeys_by_bucket[b] = rr
            if cache_key is not None:
                nbytes = sum(a.nbytes for d in (lkeys_by_bucket, rkeys_by_bucket) for a in d.values())
                _RANK_CACHE.put(cache_key, (lkeys_by_bucket, rkeys_by_bucket), nbytes)
    return lkeys_by_bucket, rkeys_by_bucket


def _join_column_source(name: str, lout, rout) -> Tuple[bool, str]:
    """(is_left, source column name) for one join output column; the join's
    '#r'-suffixed duplicates resolve to the right side (the single naming
    convention of plan/logical.join_output_names)."""
    if name in lout:
        return True, name
    if name.endswith("#r") and name[:-2] in rout:
        return False, name[:-2]
    if name in rout:
        return False, name
    raise DeviceUnsupported(f"join output column {name!r} not found on either side")


def _join_column_dtype(
    name: str, source, lbuckets, rbuckets, participating, fallback=None
) -> np.dtype:
    """Column dtype promoted across the participating buckets (a nullable int
    column decodes as float64 only in buckets whose files hold nulls).
    ``fallback`` maps column name -> dtype for columns with no decoded data
    in scope — the per-bucket streaming join types a missing side's columns
    from the index footers (the whole-side path always has other buckets)."""
    is_left, col = source
    src = lbuckets if is_left else rbuckets
    dtypes = [src[b][col].dtype for b in participating if col in src.get(b, {})]
    if not dtypes:
        if fallback is not None and name in fallback:
            return fallback[name]
        raise DeviceUnsupported(f"cannot determine dtype of empty join column {name!r}")
    if any(dt == object for dt in dtypes):
        return np.dtype(object)
    return np.result_type(*dtypes)


from functools import lru_cache


@lru_cache(maxsize=32)
def _expand_gather_program(n_pad: int):
    """Jitted inner-join materialization: expand every (left row, matching
    right row) pair AND gather the numeric payload columns in one device
    program — the host receives final columns only (SURVEY §2.9
    "device-local merge-join kernel"). One compile per output size class.

    Shapes: ``lo``/``hi``/``llens`` describe the span matrices ((nb, Wl) and
    (nb,)); ``lcols``/``rcols`` are tuples of (nb, Wl)/(nb, Wr) rectangles.
    Output slot ``t`` maps to its (bucket, left row, right row) via ONE
    global searchsorted over the flattened inclusive pair-count cumsum — no
    (n_pad, Wl) intermediates, so memory stays O(rows + pairs)."""
    import jax
    import jax.numpy as jnp

    def run(lo, hi, llens, rlens, lcols, rcols, total):
        nb, wl = lo.shape
        # clamp spans to the right side's REAL rows: a left-only bucket's
        # SENTINEL padding keys would otherwise "match" the right rectangle's
        # SENTINEL padding region
        lo = jnp.minimum(lo, rlens[:, None])
        hi = jnp.minimum(hi, rlens[:, None])
        col_idx = jnp.arange(wl)[None, :]
        counts = jnp.where(col_idx < llens[:, None], hi - lo, 0)
        flat_counts = counts.reshape(-1)
        g_incl = jnp.cumsum(flat_counts)
        g_excl = g_incl - flat_counts
        t = jnp.arange(n_pad, dtype=g_incl.dtype)
        f = jnp.clip(jnp.searchsorted(g_incl, t, side="right"), 0, flat_counts.shape[0] - 1)
        valid = t < total
        b = f // wl
        i = f % wl
        p = t - g_excl[f]
        j = jnp.clip(lo.reshape(-1)[f] + p, 0, None)
        with jax.named_scope("gather"):
            louts = tuple(c.reshape(-1)[f] for c in lcols)
            routs = tuple(c[b, jnp.clip(j, 0, c.shape[1] - 1)] for c in rcols)
        return louts, routs, b, i, j, valid

    return jax.jit(_hlo_lint.named("join-expand-gather", run))


@lru_cache(maxsize=1)
def _bucket_pair_totals_fn():
    """One jitted per-bucket matched-pair-count reduction shared by every
    device-materialized join (a fresh jit per call would recompile on the
    query hot path)."""
    import jax
    import jax.numpy as jnp

    def run(lo, hi, ll, rl):
        return jnp.sum(
            jnp.where(
                jnp.arange(lo.shape[1])[None, :] < ll[:, None],
                jnp.minimum(hi, rl[:, None]) - jnp.minimum(lo, rl[:, None]),
                0,
            ),
            axis=1,
        )

    return jax.jit(_hlo_lint.named("join-pair-totals", run))


def _bucket_pair_totals(lo, hi, ll, rl):
    with launch("join-pair-totals"):
        return _bucket_pair_totals_fn()(lo, hi, ll, rl)


def _device_materialize_inner(
    session, plan: L.Join, lbuckets, rbuckets, lcols_needed, rcols_needed,
    lo_dev, hi_dev, llens, rlens, nb, nb_padded, _ident=None,
) -> B.Batch:
    """Device-side materialization of a compatible bucketed INNER join: pair
    expansion and numeric column gathers run on device; only string/object
    columns gather host-side (by the downloaded index arrays)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.ops.sort import padded_size

    if plan.how != "inner":
        raise DeviceUnsupported("device materialization covers inner joins")
    out_cols = plan.output_columns

    participating = sorted(set(lbuckets) & set(rbuckets))
    if not participating:
        # no overlapping buckets: empty inner join; let the host path build
        # the typed empty columns it already knows how to produce
        raise DeviceUnsupported("no overlapping buckets")

    sources = {
        name: _join_column_source(name, lcols_needed, rcols_needed) for name in out_cols
    }
    dtypes = {
        name: _join_column_dtype(name, sources[name], lbuckets, rbuckets, participating)
        for name in out_cols
    }
    device_cols = [n for n in out_cols if dtypes[n].kind in ("i", "u", "f", "b", "M", "m")]
    host_cols = [n for n in out_cols if n not in device_cols]

    # pair totals size the static output; one tiny d2h (nb ints)
    wl = lo_dev.shape[1]
    llens_np = np.asarray(llens)
    rlens_np = np.asarray(rlens)
    bucket_totals = fetch(
        _bucket_pair_totals(lo_dev, hi_dev, jnp.asarray(llens_np), jnp.asarray(rlens_np)),
        "join-out", "join-pair-totals",
    )
    total = int(bucket_totals.sum())
    out: B.Batch = {}
    if total == 0:
        for name in out_cols:
            dt = dtypes[name]
            out[name] = np.empty(0, dtype=dt)
        return out
    # cost-based placement: a device-materialized join downloads its WHOLE
    # output, so above the configured byte budget the host expansion (native
    # C pair kernels, no device->host transfer) wins (the budget predates
    # the current installation: not measured on it).
    # Downloads happen at the PADDED size (next power of two), and a host
    # (string) gather additionally downloads the b/i/j index arrays.
    n_pad = padded_size(total)
    est_bytes = n_pad * max(1, len(device_cols)) * 8
    if host_cols:
        est_bytes += 3 * n_pad * 8
    if est_bytes > session.conf.join_device_materialize_max_bytes:
        raise DeviceUnsupported(
            f"materialized output ~{est_bytes >> 20} MiB exceeds "
            "joinDeviceMaterializeMaxBytes -> host expansion"
        )

    def rectangles(side_buckets, cols, width_of):
        """(name -> (nb_padded, W) device-feedable rectangle) per column."""
        mats = {}
        for name in cols:
            is_left, col = sources[name]
            dt = dtypes[name]
            view_int = dt.kind in ("M", "m")
            base = np.dtype(np.int64) if view_int else (dt if dt.kind != "b" else np.dtype(np.int64))
            width = max(width_of, 1)
            mat = np.zeros((nb_padded, width), dtype=base)
            for b in participating:
                arr = side_buckets[b].get(col)
                if arr is None:
                    raise DeviceUnsupported(f"column {col!r} absent in bucket {b}")
                v = arr.view("int64") if view_int else arr
                mat[b, : v.shape[0]] = v.astype(base, copy=False)
            mats[name] = mat
        return mats

    l_device = [n for n in device_cols if sources[n][0]]
    r_device = [n for n in device_cols if not sources[n][0]]
    # the payload rectangles are pure functions of the sides' immutable
    # decoded buckets, so they stay HBM-resident across queries like the key
    # matrices (only the first execution pays the host->device transfer)
    mats_key = (
        ("join-paymats", _ident, tuple(l_device), tuple(r_device))
        if _ident is not None
        else None
    )
    cached = _device_cache_get(mats_key) if mats_key is not None else None
    if cached is not None:
        llens_dev, rlens_dev, lmats_dev, rmats_dev = cached
    else:
        wr = bucket_rows(max((B.num_rows(rbuckets[b]) for b in participating), default=1), floor=256)
        lmats = rectangles(lbuckets, l_device, wl)
        rmats = rectangles(rbuckets, r_device, wr)
        llens_dev = put(llens_np, "join-mats")
        rlens_dev = put(rlens_np, "join-mats")
        lmats_dev = tuple(put(lmats[n], "join-mats") for n in l_device)
        rmats_dev = tuple(put(rmats[n], "join-mats") for n in r_device)
        if mats_key is not None:
            nbytes = sum(m.nbytes for m in (*lmats.values(), *rmats.values()))
            _device_cache_put(
                mats_key, (llens_dev, rlens_dev, lmats_dev, rmats_dev), nbytes
            )

    run = _expand_gather_program(n_pad)
    with launch("join-expand-gather"):
        louts, routs, b_idx, i_idx, j_idx, valid = run(
            lo_dev,
            hi_dev,
            llens_dev,
            rlens_dev,
            lmats_dev,
            rmats_dev,
            np.int64(total),
        )

    louts, routs = fetch((louts, routs), "join-out", "join-expand-gather")
    for name, arr in zip(l_device + r_device, louts + routs):
        v = arr[:total]
        dt = dtypes[name]
        out[name] = v.view(dt) if dt.kind in ("M", "m") else v.astype(dt, copy=False)

    if host_cols:
        # string/object columns: download the (bucket-ordered) index arrays
        # once and gather host-side, bucket by bucket
        i_np, j_np = (
            a[:total] for a in fetch((i_idx, j_idx), "join-out", "join-expand-gather")
        )
        offsets = np.concatenate([[0], np.cumsum(bucket_totals)])
        for name in host_cols:
            is_left, col = sources[name]
            src = lbuckets if is_left else rbuckets
            idx = i_np if is_left else j_np
            res = np.empty(total, dtype=object)
            for b in participating:
                s, e = int(offsets[b]), int(offsets[b + 1])
                if e > s:
                    res[s:e] = src[b][col][idx[s:e]]
            out[name] = res
    return {name: out[name] for name in out_cols}


def _make_host_span_of(session, plan: L.Join, setup, compat):
    """Build ``span_of(b) -> (lo, hi)`` over the pre-sorted per-bucket runs
    using the shared per-bucket key encodings."""
    lkeys_by_bucket, rkeys_by_bucket = _encoded_join_keys(plan, setup, compat)

    from hyperspace_tpu import native

    def span_of(b: int):
        lk = lkeys_by_bucket[b]
        rk = rkeys_by_bucket[b]
        try:
            # single O(n+m) merge walk in C over the pre-sorted runs
            spans = native.merge_spans(lk, rk)
            trace.record("spans", "native")
            return spans
        except native.NativeUnsupported:
            trace.record("spans", "searchsorted")
            return np.searchsorted(rk, lk, side="left"), np.searchsorted(rk, lk, side="right")

    return span_of


def host_bucketed_join(session, plan: L.Join, _compat=None, _setup=None) -> B.Batch:
    """The shuffle-free bucketed SMJ with spans computed host-side. Used
    below the device-dispatch row threshold and for every key shape the
    device program doesn't cover."""
    ensure_x64()
    setup = _setup if _setup is not None else _bucketed_join_setup(session, plan, _compat)
    lbuckets, rbuckets, lkeys, rkeys, nb, lcols_needed, rcols_needed = setup
    span_of = _make_host_span_of(session, plan, setup, _compat)
    return _expand_join_pairs(plan, lbuckets, rbuckets, nb, lcols_needed, rcols_needed, span_of)


def _agg_side_of(lcols, rcols, col_name: str):
    """Which join side an aggregate input column comes from (and its source
    name there); '#r'-suffixed duplicates resolve to the right side."""
    if col_name.endswith("#r") and col_name[:-2] in rcols:
        return "right", col_name[:-2]
    if col_name in lcols:
        return "left", col_name
    if col_name in rcols:
        return "right", col_name
    raise DeviceUnsupported(f"aggregate input {col_name!r} not on either join side")


def _agg_column_stats(arr: np.ndarray):
    """(values as int64/float64, non-null mask or None, is_int) for a fused
    aggregate input; rejects dtypes the exact paths can't represent."""
    if arr.dtype.kind == "u" and arr.dtype.itemsize == 8:
        # uint64 >= 2^63 would wrap negative under int64 — materialize
        raise DeviceUnsupported("uint64 aggregate input -> materialize")
    if arr.dtype.kind in ("i", "u", "b"):
        return arr.astype(np.int64, copy=False), None, True
    if arr.dtype.kind == "f":
        return arr, ~np.isnan(arr), False
    raise DeviceUnsupported(f"non-numeric aggregate input dtype {arr.dtype}")


def _int_magnitude(vals: np.ndarray) -> int:
    """Largest |value| as a Python int. np.abs(int64.min) wraps negative, so
    take abs() after widening to Python ints, keeping the overflow guards
    sound for columns containing int64.min."""
    return max(abs(int(vals.max())), abs(int(vals.min())))


def _check_agg_input_dtypes(lside, rside, need_l, need_r) -> None:
    """Footer-only eligibility check for fused-aggregate inputs: numeric or
    boolean parquet types only (and not uint64). Sides without an index leaf
    carrying the column are checked later, at decode."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    for side, cols in ((lside, need_l), (rside, need_r)):
        scans = L.collect(side, lambda x: isinstance(x, L.IndexScan))
        scan = scans[0] if scans else None
        if scan is None or not scan.files:
            continue
        try:
            schema = pq.read_schema(scan.files[0])
        except OSError:
            continue
        for c in cols:
            if c not in scan.columns or scan.file_column_of(c) not in schema.names:
                continue
            t = schema.field(scan.file_column_of(c)).type
            if pa.types.is_uint64(t) or not (
                pa.types.is_integer(t) or pa.types.is_floating(t) or pa.types.is_boolean(t)
            ):
                raise DeviceUnsupported(f"aggregate input {c!r} type {t} -> materialize")


def _computed_map(computes, lcols, rcols):
    """name -> (side, expr) for Compute nodes between Aggregate and Join:
    an expression whose references live wholly on one side evaluates per
    bucket on that side's decoded batch (anything cross-side
    materializes)."""
    out = {}
    for name, expr in computes or ():
        refs = set(expr.references())
        if not refs:
            out[name] = ("left", expr)  # constant: broadcasts on either side
        elif refs <= lcols:
            out[name] = ("left", expr)
        elif refs <= rcols:
            out[name] = ("right", expr)
        else:
            raise DeviceUnsupported(
                f"computed aggregate input {name!r} references both sides -> materialize"
            )
    return out


def aggregate_over_bucketed_join(
    session, agg: L.Aggregate, join: L.Join, computes=()
) -> B.Batch:
    """Global aggregates over a compatible bucketed inner join WITHOUT
    materializing the pair expansion: per bucket, the [lo, hi) match spans
    give each left row's multiplicity, so sums become weighted sums and
    right-side sums become prefix-sum differences — O(n+m) per bucket instead
    of O(pairs). Integer sums stay exact (per-bucket int64 dot products with
    overflow guards, accumulated in Python ints). GROUP BY over exactly the
    join keys fuses too (segment reductions over the sorted runs). Raises
    DeviceUnsupported for shapes it can't fuse (other group keys, outer
    joins, min/max of right-side columns, non-numeric inputs, overflow-risk
    int sums); the caller then materializes.

    This is TPU-framework-specific: the reference delegates aggregation to
    Spark above its rewritten scans."""
    ensure_x64()
    if join.how != "inner":
        raise DeviceUnsupported("fused join-aggregate covers inner joins")
    compat = join_sides_compatible(join)
    if compat is None:
        raise DeviceUnsupported("join sides are not compatible bucketed scans")
    lside, rside, lkeys, rkeys = compat
    if agg.keys:
        return _grouped_aggregate_over_join(session, agg, join, compat, computes=computes)

    # which side does each aggregate input column come from?
    lcols = set(lside.output_columns)
    rcols = set(rside.output_columns)
    computed = _computed_map(computes, lcols, rcols)

    plans = []  # (name, fn, side, src, expr|None)
    need_l, need_r = set(), set()
    for name, fn, col_name in agg.aggs:
        if fn not in _AGG_FNS:
            raise DeviceUnsupported(f"unsupported aggregate fn {fn!r} -> materialize")
        if fn == "count" and col_name is None:
            plans.append((name, "count*", None, None, None))
            continue
        if col_name in computed:
            side, expr = computed[col_name]
            src = col_name
            refs = set(expr.references())
        else:
            side, src = _agg_side_of(lcols, rcols, col_name)
            expr, refs = None, {src}
        if fn in ("min", "max") and side == "right":
            # would need segment min over covered spans; not worth it here
            raise DeviceUnsupported("min/max of a right-side column -> materialize")
        plans.append((name, fn, side, src, expr))
        (need_l if side == "left" else need_r).update(refs)

    # cheap footer-level dtype check BEFORE any decode: a string/binary
    # aggregate input must not cost a full read of both sides only to fall
    # back (the overflow guards still bail late — they need values)
    _check_agg_input_dtypes(lside, rside, need_l, need_r)

    # decode only keys + needed inputs
    setup = _bucketed_join_setup(
        session, join, compat, needed_override=(sorted(need_l), sorted(need_r))
    )
    lbuckets, rbuckets, _lk, _rk, nb, _lc, _rc = setup
    span_of = _make_host_span_of(session, join, setup, compat)

    INT_GUARD = 2 ** 62

    def declared_is_int(side: str, src: str, expr=None) -> bool:
        # dtype from ANY decoded bucket, so the output dtype is right even
        # when no bucket has matches (empty-join sum must stay float for
        # float inputs, matching the materialized path)
        for batch in (lbuckets if side == "left" else rbuckets).values():
            if expr is not None:
                _v, _ok, is_int = _agg_column_stats(np.asarray(expr.eval(batch)))
                return is_int
            if src in batch:
                _v, _ok, is_int = _agg_column_stats(batch[src])
                return is_int
        raise DeviceUnsupported(f"aggregate input {src!r} has no decoded bucket")

    total_pairs = 0
    acc = {name: {"sum": 0, "cnt": 0, "min": None, "max": None} for name, *_ in plans}
    is_int_out = {
        name: (declared_is_int(side, src, expr) if side is not None else True)
        for name, fn, side, src, expr in plans
    }
    for b in range(nb):
        lb, rb = lbuckets.get(b), rbuckets.get(b)
        if lb is None or rb is None:
            continue
        ll, rr = B.num_rows(lb), B.num_rows(rb)
        if ll == 0 or rr == 0:
            continue
        lo, hi = span_of(b)
        lo_i = np.asarray(lo, dtype=np.int64)
        hi_i = np.asarray(hi, dtype=np.int64)
        counts = hi_i - lo_i
        bucket_pairs = int(counts.sum())
        total_pairs += bucket_pairs
        if bucket_pairs == 0:
            continue

        # per-(side, column) encodings + prefix sums, shared by every
        # aggregate reading that column in this bucket
        col_cache: Dict[Tuple[str, str], tuple] = {}

        def col_info(side: str, src: str, expr=None):
            got = col_cache.get((side, src))
            if got is not None:
                return got
            batch_ = lb if side == "left" else rb
            if expr is not None:
                arr = np.asarray(expr.eval(batch_))
                if arr.ndim == 0:  # constant expression broadcasts per row
                    arr = np.broadcast_to(arr, (B.num_rows(batch_),))
            else:
                arr = batch_[src]
            vals, ok, is_int = _agg_column_stats(arr)
            pref = prefn = None
            if side == "right":
                if is_int:
                    if vals.size and _int_magnitude(vals) * vals.size >= INT_GUARD:
                        raise DeviceUnsupported("int sum overflow risk -> materialize")
                    pref = np.concatenate([[0], np.cumsum(vals)])
                else:
                    pref = np.concatenate([[0.0], np.cumsum(np.where(ok, vals, 0.0))])
                nn = np.ones(vals.shape[0], dtype=np.int64) if ok is None else ok.astype(np.int64)
                prefn = np.concatenate([[0], np.cumsum(nn)])
            got = (vals, ok, is_int, pref, prefn)
            col_cache[(side, src)] = got
            return got

        for name, fn, side, src, expr in plans:
            a = acc[name]
            if fn == "count*":
                continue
            vals, ok, is_int, pref, prefn = col_info(side, src, expr)
            if side == "left":
                w = counts if ok is None else counts * ok
                if fn in ("sum", "avg"):
                    if is_int:
                        if vals.size and _int_magnitude(vals) * bucket_pairs >= INT_GUARD:
                            raise DeviceUnsupported("int sum overflow risk -> materialize")
                        a["sum"] += int(np.dot(vals, counts))
                    else:
                        a["sum"] += float(np.dot(np.where(ok, vals, 0.0), counts))
                    a["cnt"] += int(w.sum())
                elif fn == "count":
                    a["cnt"] += int(w.sum())
                else:  # min/max over rows that matched at least once
                    sel = (counts > 0) if ok is None else (ok & (counts > 0))
                    if sel.any():
                        mn, mx = vals[sel].min(), vals[sel].max()
                        a["min"] = mn if a["min"] is None else min(a["min"], mn)
                        a["max"] = mx if a["max"] is None else max(a["max"], mx)
            else:
                if fn in ("sum", "avg"):
                    span_sum = (pref[hi_i] - pref[lo_i]).sum()
                    a["sum"] += int(span_sum) if is_int else float(span_sum)
                    a["cnt"] += int((prefn[hi_i] - prefn[lo_i]).sum())
                elif fn == "count":
                    a["cnt"] += int((prefn[hi_i] - prefn[lo_i]).sum())

    out: B.Batch = {}
    for name, fn, side, src, expr in plans:
        a = acc[name]
        if fn == "count*":
            out[name] = np.asarray([total_pairs])
        elif fn == "count":
            out[name] = np.asarray([a["cnt"]])
        elif fn == "sum" and a["cnt"] == 0:
            # SQL: SUM over zero (non-null) rows is NULL, not 0
            out[name] = np.asarray([np.nan])
        elif fn == "sum":
            # int inputs stay int (exact)
            if is_int_out[name] and abs(a["sum"]) >= 2 ** 63:
                # exact Python-int total exceeds int64 across buckets: the
                # materialized path defines the (wrapping/float) behavior
                raise DeviceUnsupported("int sum exceeds int64 -> materialize")
            out[name] = np.asarray([a["sum"]], dtype=np.int64 if is_int_out[name] else np.float64)
        elif fn == "avg":
            out[name] = np.asarray([a["sum"] / a["cnt"] if a["cnt"] else np.nan])
        elif fn == "min":
            v = a["min"]
            out[name] = np.asarray([np.nan if v is None else v])
        else:
            v = a["max"]
            out[name] = np.asarray([np.nan if v is None else v])
    return out


def _grouped_aggregate_over_join(
    session, agg: L.Aggregate, join: L.Join, compat, computes=()
) -> B.Batch:
    """Grouped aggregates over a compatible bucketed inner join WITHOUT
    materializing the pair expansion.

    Groups are discovered as SUB-SEGMENTS of each bucket's sorted left run:
    boundaries fall wherever any join key, any left-side group key, or any
    (per-left-row gathered) right-side group key changes. Per-segment pair
    totals are reduceat sums of span counts; sums reduce count-weighted
    left values or span prefix-sum differences (right). Because equal group
    tuples can recur non-contiguously (group keys need not include every
    join key, and extra keys are unsorted within runs), per-segment
    partials FINAL-MERGE through one output-sized pandas groupby — the
    partial/final split, applied to segments instead of chunks.

    Right-side group keys additionally require the right side to be UNIQUE
    per join key in every bucket (spans of width <= 1, checked per bucket):
    that is what makes the gathered per-left-row value well defined. This
    covers the TPC-H q3 class — GROUP BY l_orderkey, o_orderdate,
    o_shippriority over lineitem JOIN orders (o_orderkey is unique) with a
    computed revenue input — end to end without pair expansion.

    Raises DeviceUnsupported for shapes it can't fuse (outer joins,
    min/max, cross-side computed inputs, non-unique right side under
    right-side group keys); the caller then materializes."""
    lside, rside, lkeys, rkeys = compat
    lcols = set(lside.output_columns)
    rcols = set(rside.output_columns)
    computed = _computed_map(computes, lcols, rcols)

    def resolve(col):
        if col in computed:
            side, expr = computed[col]
            return side, col, expr, set(expr.references())
        side, src = _agg_side_of(lcols, rcols, col)
        return side, src, None, {src}

    for _, fn, _c in agg.aggs:
        if fn not in _AGG_FNS:
            raise DeviceUnsupported(f"unsupported aggregate fn {fn!r} -> materialize")

    # group-key plan: join keys canonicalize to the LEFT key column
    # (matched rows carry equal values); anything else is an "extra"
    key_plan = []  # (out_name, kind, src, expr) kind in jk/lx/rx
    need_l, need_r = set(lkeys), set(rkeys)
    has_right_extra = False
    for k in agg.keys:
        side, src, expr, refs = resolve(k)
        if expr is None and side == "left" and src in lkeys:
            key_plan.append((k, "jk", src, None))
        elif expr is None and side == "right" and src in rkeys:
            key_plan.append((k, "jk", lkeys[rkeys.index(src)], None))
        elif side == "left":
            key_plan.append((k, "lx", src, expr))
            need_l |= refs
        else:
            key_plan.append((k, "rx", src, expr))
            need_r |= refs
            has_right_extra = True

    plans = []  # (name, fn, side, src, expr)
    for name, fn, col_name in agg.aggs:
        if fn == "count" and col_name is None:
            plans.append((name, "count*", None, None, None))
            continue
        side, src, expr, refs = resolve(col_name)
        if fn in ("min", "max"):
            raise DeviceUnsupported("grouped min/max -> materialize")
        plans.append((name, fn, side, src, expr))
        (need_l if side == "left" else need_r).update(refs)

    # footer pre-check covers computed inputs via their REFERENCES, so a
    # string-referencing expression bails before decoding both whole sides
    check_l = {s for _, fn, sd, s, e in plans if sd == "left" and e is None}
    check_r = {s for _, fn, sd, s, e in plans if sd == "right" and e is None}
    for _, fn, sd, _s, e in plans:
        if e is not None:
            (check_l if sd == "left" else check_r).update(e.references())
    _check_agg_input_dtypes(lside, rside, check_l, check_r)
    setup = _bucketed_join_setup(
        session, join, compat, needed_override=(sorted(need_l), sorted(need_r))
    )
    lbuckets, rbuckets, _lk, _rk, nb, _lc, _rc = setup
    span_of = _make_host_span_of(session, join, setup, compat)

    INT_GUARD = 2 ** 62

    key_parts: Dict[str, List[np.ndarray]] = {k: [] for k, *_ in key_plan}
    # per-aggregate partial columns: sum+cnt for sum/avg, cnt for counts
    sum_parts: Dict[str, List[np.ndarray]] = {name: [] for name, *_ in plans}
    cnt_parts: Dict[str, List[np.ndarray]] = {name: [] for name, *_ in plans}
    int_sum = {name: True for name, *_ in plans}

    for b in range(nb):
        lb, rb = lbuckets.get(b), rbuckets.get(b)
        if lb is None or rb is None:
            continue
        ll, rr = B.num_rows(lb), B.num_rows(rb)
        if ll == 0 or rr == 0:
            continue
        lo, hi = span_of(b)
        lo_i = np.asarray(lo, dtype=np.int64)
        hi_i = np.asarray(hi, dtype=np.int64)
        counts = hi_i - lo_i
        if has_right_extra and counts.size and int(counts.max()) > 1:
            raise DeviceUnsupported(
                "right-side group key over a non-unique join side -> materialize"
            )

        def left_col(src, expr):
            if expr is not None:
                arr = np.asarray(expr.eval(lb))
                return (
                    np.broadcast_to(arr, (ll,)) if arr.ndim == 0 else arr
                )
            return lb[src]

        def right_gathered(src, expr):
            arr = np.asarray(expr.eval(rb)) if expr is not None else rb[src]
            if arr.ndim == 0:
                arr = np.broadcast_to(arr, (rr,))
            # valid where counts == 1; count-0 rows carry a neighbor's
            # value, which either forms an empty segment (dropped) or
            # harmlessly extends an equal-valued one
            return arr[np.clip(lo_i, 0, rr - 1)]

        # sub-segment boundaries: change in ANY join key or group extra
        key_arrays = {}  # out_name -> per-left-row values for output
        change = np.zeros(ll, dtype=bool)
        if ll:
            change[0] = True
        for kc in lkeys:
            kv = _order_key_array(lb[kc])
            change[1:] |= kv[1:] != kv[:-1]
        for k, kind, src, expr in key_plan:
            if kind == "jk":
                key_arrays[k] = lb[src]
                continue
            arr = left_col(src, expr) if kind == "lx" else right_gathered(src, expr)
            key_arrays[k] = arr
            kv = _order_key_array(arr)
            change[1:] |= kv[1:] != kv[:-1]
        starts = np.flatnonzero(change)
        run_pairs = np.add.reduceat(counts, starts) if starts.size else np.empty(0, np.int64)
        keep = run_pairs > 0  # inner join: unmatched segments drop out
        if not keep.any():
            continue

        for k, kind, src, expr in key_plan:
            key_parts[k].append(key_arrays[k][starts][keep])

        col_cache: Dict[Tuple[str, str], tuple] = {}

        def col_info(side, src, expr):
            got = col_cache.get((side, src))
            if got is not None:
                return got
            if side == "left":
                arr = left_col(src, expr)
            else:
                arr = np.asarray(expr.eval(rb)) if expr is not None else rb[src]
                if arr.ndim == 0:
                    arr = np.broadcast_to(arr, (rr,))
            vals, ok, is_int = _agg_column_stats(arr)
            if is_int and vals.size and _int_magnitude(vals) * max(int(counts.sum()), 1) >= INT_GUARD:
                raise DeviceUnsupported("int sum overflow risk -> materialize")
            pref = prefn = None
            if side == "right":
                if ok is None:
                    pref = np.concatenate([[0], np.cumsum(vals)])
                    nn = np.ones(vals.shape[0], dtype=np.int64)
                else:
                    pref = np.concatenate([[0.0], np.cumsum(np.where(ok, vals, 0.0))])
                    nn = ok.astype(np.int64)
                prefn = np.concatenate([[0], np.cumsum(nn)])
            got = (vals, ok, is_int, pref, prefn)
            col_cache[(side, src)] = got
            return got

        for name, fn, side, src, expr in plans:
            if fn == "count*":
                cnt_parts[name].append(run_pairs[keep])
                continue
            vals, ok, is_int, pref, prefn = col_info(side, src, expr)
            if not is_int:
                int_sum[name] = False
            if side == "left":
                w = counts if ok is None else counts * ok
                cnts = np.add.reduceat(w, starts)[keep]
                cnt_parts[name].append(cnts)
                if fn in ("sum", "avg"):
                    contrib = vals * counts if ok is None else np.where(ok, vals, 0) * counts
                    sum_parts[name].append(np.add.reduceat(contrib, starts)[keep])
            else:
                row_cnts = prefn[hi_i] - prefn[lo_i]
                cnt_parts[name].append(np.add.reduceat(row_cnts, starts)[keep])
                if fn in ("sum", "avg"):
                    row_sums = pref[hi_i] - pref[lo_i]
                    sum_parts[name].append(np.add.reduceat(row_sums, starts)[keep])

    def declared_dtype(side, src) -> np.dtype:
        for batch in (lbuckets if side == "left" else rbuckets).values():
            if src in batch:
                return batch[src].dtype
        raise DeviceUnsupported(f"aggregate input {src!r} has no decoded bucket")

    def declared_expr_dtype(side, expr) -> np.dtype:
        # a computed column's dtype comes from evaluating it over any
        # decoded bucket (empty-result outputs must still type like the
        # materialized path's)
        for batch in (lbuckets if side == "left" else rbuckets).values():
            arr = np.asarray(expr.eval(batch))
            return arr.dtype
        return np.dtype(np.float64)

    out: B.Batch = {}
    any_parts = any(key_parts[k] for k, *_ in key_plan) if key_plan else False
    if not any_parts:
        for k, kind, src, expr in key_plan:
            if expr is not None:
                out[k] = np.empty(
                    0, dtype=declared_expr_dtype("left" if kind != "rx" else "right", expr)
                )
            else:
                out[k] = np.empty(
                    0, dtype=declared_dtype("left" if kind != "rx" else "right", src)
                )
        for name, fn, side, src, expr in plans:
            if fn in ("count", "count*"):
                dt = np.dtype(np.int64)
            elif fn == "sum" and side is not None:
                _v, _ok, is_int = _agg_column_stats(
                    np.empty(0, dtype=declared_dtype(side, src))
                    if expr is None
                    else np.empty(0, dtype=declared_expr_dtype(side, expr))
                )
                dt = np.dtype(np.int64) if is_int else np.dtype(np.float64)
            else:
                dt = np.dtype(np.float64)
            out[name] = np.empty(0, dtype=dt)
        return out

    # FINAL MERGE: equal group tuples recur across segments (and, when the
    # group keys don't pin the join key, across buckets) — one
    # segment-count-sized pandas groupby folds the partials. Keys enter as
    # null-safe int64 ORDER CODES, never as raw values: strings would pay
    # pandas' Arrow conversion (the round-4 lesson) and datetimes would
    # round-trip to ns; a representative row index maps each group back to
    # its exact original values/dtypes.
    import pandas as pd

    key_arrays_out = {k: np.concatenate(key_parts[k]) for k, *_ in key_plan}
    frame = {
        f"__k{i}": _order_key_array(key_arrays_out[k])
        for i, (k, *_rest) in enumerate(key_plan)
    }
    gcols = list(frame)
    n_seg = len(next(iter(key_arrays_out.values()))) if key_arrays_out else 0
    frame["__pos"] = np.arange(n_seg, dtype=np.int64)
    for name, fn, side, src, expr in plans:
        frame[f"__c_{name}"] = np.concatenate(cnt_parts[name])
        if sum_parts[name]:
            s_part = np.concatenate(sum_parts[name])
            if int_sum[name] and s_part.dtype.kind != "f":
                # pandas sums int64 with wrapping arithmetic; cross-bucket
                # merges could exceed int64 even when every per-bucket
                # partial passed its own guard
                if float(np.abs(s_part.astype(np.float64)).sum()) >= float(INT_GUARD):
                    raise DeviceUnsupported("int sum overflow risk at merge -> materialize")
            frame[f"__s_{name}"] = s_part
    df = pd.DataFrame(frame)
    gb = df.groupby(gcols, dropna=False, sort=False)
    agg_spec = {c: "sum" for c in df.columns if c not in gcols and c != "__pos"}
    agg_spec["__pos"] = "first"
    res = gb.agg(agg_spec).reset_index()

    rep = res["__pos"].to_numpy()
    for k, *_rest in key_plan:
        out[k] = key_arrays_out[k][rep]
    for name, fn, side, src, expr in plans:
        c = res[f"__c_{name}"].to_numpy()
        if fn in ("count", "count*"):
            out[name] = c.astype(np.int64)
            continue
        s = res[f"__s_{name}"].to_numpy()
        if fn == "avg":
            out[name] = np.divide(
                s.astype(np.float64), c, out=np.full(s.shape, np.nan), where=c > 0
            )
            continue
        # sum: SQL NULL (NaN) for all-null groups; int sums stay int when
        # no group needs a NULL hole
        if (c > 0).all():
            out[name] = s.astype(np.int64) if int_sum[name] and s.dtype.kind != "f" else s
        else:
            sf = s.astype(np.float64)
            sf[c == 0] = np.nan
            out[name] = sf
    return out
