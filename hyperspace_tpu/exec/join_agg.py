"""An aggregate over a join of two resident index scans, in one device program.

JoinIndexRule's own shape, ``Aggregate`` over an inner equi-``Join`` of two
covering indexes bucketed on the join key (TPC-H Q12: ``orders`` and
``lineitem`` on the order key, a filter on one side, a ``CASE`` over the
other, a grouped count), answered from the columns of BOTH scans resident on
the device (``device.ScanColumns``, 8-byte columns as 32-bit planes), by the
program family ``join-agg-resident`` (executables ``jit_hs_join_agg*``):

1. *filter*: each side's predicate is a mask over its planes (the predicate
   compiler of ``device.py``); the larger scan is the **probe** side, the
   smaller the **build** side.
2. *compact*: the selected probe rows go on, not the side. No scatter, no
   ``nonzero``: the mask is packed ``_WORD`` strided rows to a word (a
   reduction over the major axis, lane-friendly), the numbers of the non-zero
   words are sorted to the front (one operand), their bits spread into
   candidate rows, and those sorted once more (one operand) for the positions
   of the selected rows, ascending. Two short sorts where one over the scan
   would be: at 67 M rows with half a million selected, 4.2 M and 8.4 M
   values. A selection too dense for that (or a small scan) sorts the scan's
   positions directly (``whole``).
3. *probe*: each selected row's key finds its build row through an artefact
   made ON THE DEVICE once an index version (:class:`BuildTable`), kept in
   the device cache under the build scan's identity (a refresh or optimize
   commits other files: another identity, another artefact; the cache's
   budget counts it). The form follows what the code observes of the build
   key when it is made, nothing is configured: its least and greatest value
   and its count give ``direct`` (one int32 row number a key of the range,
   where the range is within ``_DIRECT_RANGE_FACTOR`` times the rows) or
   ``sorted`` (the keys' offsets sorted once with their row numbers, asked by
   a binary search unrolled into gathers: no ``searchsorted``, whose default
   lowers to ``while`` loops); and whether the key REPEATS is observed too,
   never assumed from the schema: a build side with a repeated key is refused
   (``DeviceUnsupported``) before any column but its key is uploaded, and the
   query goes on to the tiers there are. A probe row without a match adds
   nothing (inner join); a NULL key matches nothing.
4. *gather and fold*: the columns the aggregate reads are gathered for the
   selected rows only, the probe side's by position, one word a row: what a
   selected probe row hands on (its key as the offset into the table's range,
   a dictionary code in the bits its dictionary needs) is packed into 32-bit
   **row words** beside the mask, where the planes are streamed anyway, and
   one gather a word fetches it (:func:`_row_layout`; a column whose codec
   does not bound its values keeps its planes and is gathered from them). The
   build side's are gathered by the match; computed inputs (``CASE``
   included) run over them, and the fold is
   the scan tiers' own: ``fused-agg``'s reductions for a global aggregate,
   ``grouped-agg-dense``'s one variadic reduction for group keys that are
   dictionary codes of either side (at most 64 combinations). Counts and
   integer sums are exact, float sums float64. Any other group key is refused.

Two small programs stand beside it, neither in a window whose queries the
warm-up asked: ``join-agg-probe`` counts a predicate's selected rows the first
time its literals are asked of a scan's files (the capacity the program is
built for; remembered, it cannot change), and ``join-agg-table`` makes the
artefact. On a mesh the partitioner lays the program out (correct, not
tuned); a session that shards its queries (``hyperspace.parallel.enabled``)
is refused: no sharded form is written.
"""

from __future__ import annotations

import functools
import threading
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.device import DeviceUnsupported
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import Expr

_hlo_lint = D._hlo_lint
_REGISTRY = D._REGISTRY

_WORD = 16  # probe rows packed to a word of the mask: two sorts of about equal length at a selection of 1 in 150
_DIRECT_RANGE_FACTOR = 4  # a direct-address table where the key's range is within this multiple of its rows
_NO_CODE = np.uint32(0xFFFFFFFF)
_I32_MAX = np.int32(np.iinfo(np.int32).max)

_ANY = (0, None)
_COLLECTIVES = {"all-gather": _ANY, "all-reduce": _ANY, "all-to-all": _ANY, "collective-permute": _ANY}
_hlo_lint.register_contract(
    "join-agg-resident",
    collectives=_COLLECTIVES,
    description="aggregate over a join of two resident scans: mask and the probe side's row words, compaction by two one-operand sorts, the selected probe rows fetched one word a row, probe of the build side's table, gather of the matched build rows, fold; only the group table leaves",
)
_hlo_lint.register_contract(
    "join-agg-probe",
    collectives=_COLLECTIVES,
    description="the resident join-aggregate's probe-side predicate alone: the selected rows' count, one scalar back",
)
_hlo_lint.register_contract(
    "join-agg-table",
    collectives=_COLLECTIVES,
    description="the build side's artefact, once an index version: the key's range and count, then a direct-address table or the sorted keys, and whether a key repeats",
)


# --------------------------------------------------------------------------
# the shape
# --------------------------------------------------------------------------


class JoinSide(NamedTuple):
    """One side of the join: an ``IndexScan`` under Projects and at most one
    ``Filter``."""

    scan: L.IndexScan
    condition: Optional[Expr]
    key: str
    columns: frozenset  # what the side hands the join


class JoinAggShape(NamedTuple):
    left: JoinSide
    right: JoinSide
    computes: list
    reads: frozenset  # the join's columns the fold reads (:func:`aggregate_reads`)


def _side_chain(node: L.LogicalPlan, key: str) -> Optional[JoinSide]:
    columns = frozenset(node.output_columns)
    condition = None
    while isinstance(node, (L.Project, L.Filter)):
        if isinstance(node, L.Filter):
            if condition is not None:
                return None
            condition = node.condition
        node = node.child
    if not isinstance(node, L.IndexScan) or node.pruned_buckets is not None:
        return None
    if key not in node.columns or (condition is not None and not set(condition.references()) <= set(node.columns)):
        return None
    return JoinSide(node, condition, key, columns)


def join_aggregate_shape(plan: L.Aggregate) -> Optional[JoinAggShape]:
    """``plan`` as the shape this tier answers, or None (another tier's):
    an ``Aggregate`` over Projects and at most one ``Compute`` over an inner
    equi-``Join`` on ONE key of two index-scan chains that
    ``device.join_sides_compatible`` accepts, every column it reads found on
    exactly one side, every expression inside the device language and of
    types the program takes (by the schemas the index builds recorded).
    Decided without opening a file."""
    node = plan.child
    computes: list = []
    seen_compute = False
    while isinstance(node, (L.Project, L.Compute)):
        if isinstance(node, L.Compute):
            if seen_compute:
                return None
            seen_compute, computes = True, list(node.exprs)
        node = node.child
    if not isinstance(node, L.Join) or node.how != "inner":
        return None
    compat = D.join_sides_compatible(node)
    if compat is None:
        return None
    lnode, rnode, lkeys, rkeys = compat
    if len(lkeys) != 1:
        return None
    left, right = _side_chain(lnode, lkeys[0]), _side_chain(rnode, rkeys[0])
    if left is None or right is None or left.columns & right.columns:
        return None  # a name on both sides comes out of the join renamed
    names = {name for name, _ in computes}
    if names & set(plan.keys) or any(names & set(e.references()) for _, e in computes):
        return None  # a computed group key, or one computed from another
    both = left.columns | right.columns
    if names & both:
        return None
    reads = frozenset(aggregate_reads(plan.keys, plan.aggs, computes))
    if not reads <= both:
        return None
    asked = [e for _, e in computes] + [s.condition for s in (left, right) if s.condition is not None]
    if not all(D.in_device_language(e) for e in asked):
        return None  # a LIKE, a cast, a function: known before a file is read
    # and so are the columns' types, from the schemas the index builds recorded: an
    # integer group key, a string aggregate input, a float key are another tier's
    catalog = [_catalog_codecs(s.scan, side_columns(s, reads)) for s in (left, right)]
    if None not in catalog:
        try:
            _compile((left, right), *catalog, computes, plan.aggs, plan.keys)
        except DeviceUnsupported:
            return None
    return JoinAggShape(left, right, computes, reads)


@functools.lru_cache(maxsize=256)
def _schema_dtypes(schema_json: str) -> dict:
    from hyperspace_tpu.sources import schema as schema_codec

    return {f.name: schema_codec.arrow_to_numpy_dtype(f.type) for f in schema_codec.schema_from_json(schema_json)}


def _catalog_codecs(scan: L.IndexScan, names) -> Optional[dict]:
    """Kind-only codecs of ``names`` from the schema ``scan``'s log entry
    recorded when the index was built: what the compilers need to say
    DeviceUnsupported, known without opening a file. None where the entry
    recorded none, or not these columns."""
    text = scan.entry.derived_dataset.properties.get("schemaJson", "")
    if not text:
        return None
    dtypes = _schema_dtypes(text)
    empty = {c: np.empty(0, dtype=dtypes.get(scan.file_column_of(c), np.dtype("V"))) for c in names}
    try:
        codecs = D._dry_codecs(empty, names)
    except DeviceUnsupported:
        return None
    for c, codec in codecs.items():
        codec.dtype = empty[c].dtype
    return codecs


def aggregate_reads(keys, aggs, computes) -> set:
    """The join's columns the fold reads: group keys, aggregate inputs that
    are not computed, and what the computed ones are computed from."""
    names = {name for name, _ in computes}
    return (
        set(keys)
        | {c for _, _, c in aggs if c is not None and c not in names}
        | {r for _, e in computes for r in e.references()}
    )


def side_columns(side: JoinSide, reads) -> List[str]:
    """The scan columns the tier keeps resident of ``side``: its key, its
    filter's, and what the fold reads of it."""
    cond = set(side.condition.references()) if side.condition is not None else set()
    return sorted({side.key} | cond | {c for c in reads if c in side.columns})


def table_budget_bytes(build_rows: int) -> int:
    """The most the build side's artefact weighs, known before it is made: a
    direct-address table over the widest range it is chosen for (the sorted
    form, eight bytes a row, weighs less)."""
    return D.bucket_rows(_DIRECT_RANGE_FACTOR * max(1, build_rows)) * 4


def check_fits(sides) -> None:
    """``ResidentOverCap`` where what is not resident yet of two scans and the
    build side's artefact outweigh the device cache's budget together:
    ``sides`` is ``[(rows, columns to upload, artefact bytes)]``."""
    need = sum(D.bucket_rows(rows) * 8 * n + table for rows, n, table in sides)
    if need > D.device_cache_cap():
        raise D.ResidentOverCap(
            f"the columns of two scans and the build table take {need} bytes, over the device cache's {D.device_cache_cap()}"
        )


# --------------------------------------------------------------------------
# the build side's artefact
# --------------------------------------------------------------------------


class BuildTable(NamedTuple):
    """How a probe key finds its build row. ``direct``: ``arrays`` is one
    int32 table, the row number of key ``lo + i`` at ``i`` (-1: none).
    ``sorted``: the keys' uint32 offsets from ``lo`` ascending (padding and
    NULLs last, as ``_NO_CODE``) and their row numbers."""

    form: str
    arrays: tuple
    lo: int
    rows: int
    nbytes: int


_TABLE_LOCK = threading.Lock()
_TABLE_COUNTERS: dict = {}


def _count_table(result: str) -> None:
    c = _TABLE_COUNTERS.get(result)
    if c is None:
        c = _TABLE_COUNTERS[result] = _REGISTRY.counter(
            "hs_join_build_table_total",
            "Asks of a join's build-side table by the resident join-aggregate tier, one a query: found resident, or built",
            result=result,
        )
    c.inc()


def _key_valid(key, codec, n_valid):
    """Rows of a key column that hold a key: inside the scan, not NaT."""
    import jax.numpy as jnp

    valid = jnp.arange(key.shape[0], dtype=jnp.int32) < n_valid.astype(jnp.int32)
    if codec.kind == "datetime":
        valid = valid & (key != np.iinfo(np.int64).min)
    return valid


def _check_key_codecs(probe: D.ColumnCodec, build: D.ColumnCodec) -> None:
    """Keys compare as 64-bit integers: integer columns, or datetimes of one
    unit. Dictionary codes are each side's own and floats have no exact
    offset: DeviceUnsupported."""
    for codec in (probe, build):
        if codec.kind == "string":
            raise DeviceUnsupported("string join key")
        if codec.kind == "numeric" and codec.dtype is not None and np.dtype(codec.dtype).kind == "f":
            raise DeviceUnsupported("float join key")
    if probe.kind != build.kind or (probe.kind == "datetime" and probe.unit != build.unit):
        raise DeviceUnsupported("join keys of two kinds")


def _table_cache_key(cols: D.ScanColumns, key: str):
    return (cols.scan_key, ("join-table", key), cols._fp) if cols.scan_key is not None else None


def resident_table(cols: D.ScanColumns, key: str) -> Optional[BuildTable]:
    """The artefact of ``key`` resident under ``cols``' scan identity, or None."""
    ckey = _table_cache_key(cols, key)
    return D._device_cache_get(ckey) if ckey is not None else None


def build_table(session, cols: D.ScanColumns, key: str) -> BuildTable:
    """The artefact of build key ``key`` of the scan ``cols`` reads: the
    resident one, or made now, single-flight, from the key column alone (no
    other column of the side is uploaded before it stands). Raises
    DeviceUnsupported for a key that repeats or that no form takes. One count
    a query in ``hs_join_build_table_total{result}``."""
    got = resident_table(cols, key)
    if got is not None:
        _count_table("hit")
        return got
    with _TABLE_LOCK:
        got = resident_table(cols, key)  # a request ahead of this one in the lock may have made it
        if got is None:
            got = _make_table(session, D.ScanColumns(session, cols.scan_key, [key], cols.batch), key)
            ckey = _table_cache_key(cols, key)
            if ckey is not None:
                D._device_cache_put(ckey, got, got.nbytes)
    _count_table("built")
    return got


def _make_table(session, cols: D.ScanColumns, key: str) -> BuildTable:
    import jax
    import jax.numpy as jnp

    dev_cols, codecs = cols.on_device()
    column, codec = dev_cols[key], codecs[key]
    _check_key_codecs(codec, codec)
    n, total, mesh = cols.rows, int(column.shape[0]), cols.mesh
    if total >= 2**30:
        raise DeviceUnsupported("build row numbers past 30 bits")
    i64 = np.iinfo(np.int64)

    def stats(column, n_valid):
        k = D.join_planes(column)
        valid = _key_valid(k, codec, n_valid)
        return (jnp.min(jnp.where(valid, k, i64.max)), jnp.max(jnp.where(valid, k, i64.min)),
                valid.sum(dtype=jnp.int32))

    lo, hi, n_keys = (int(v) for v in _run_table(session, mesh, f"stats[{total}]:{codec.kind}", stats, (column, np.int64(n))))
    if n_keys == 0:
        raise DeviceUnsupported("a build side without a key")
    span = hi - lo + 1
    if span <= _DIRECT_RANGE_FACTOR * n_keys and span < 2**31:
        form, size = "direct", D.bucket_rows(span)

        def make(column, n_valid, lo):
            k = D.join_planes(column)
            valid = _key_valid(k, codec, n_valid)
            at = jnp.where(valid, k - lo, size).astype(jnp.int32)  # past the end: dropped
            table = jnp.full((size,), -1, jnp.int32).at[at].set(jnp.arange(total, dtype=jnp.int32), mode="drop")
            return (table,), (table >= 0).sum(dtype=jnp.int32)

    elif span < 2**32 - 1:
        form, size = "sorted", total

        def make(column, n_valid, lo):
            k = D.join_planes(column)
            valid = _key_valid(k, codec, n_valid)
            code = jnp.where(valid, (k - lo).astype(jnp.uint32), _NO_CODE)
            code, row = jax.lax.sort((code, jnp.arange(total, dtype=jnp.int32)), num_keys=1, is_stable=False)
            repeats = ((code[1:] == code[:-1]) & (code[1:] != _NO_CODE)).sum(dtype=jnp.int32)
            return (code, row), valid.sum(dtype=jnp.int32) - repeats

    else:
        raise DeviceUnsupported("build keys span more than 32 bits")
    arrays, distinct = _run_table(
        session, mesh, f"{form}[{total},{size}]:{codec.kind}", make, (column, np.int64(n), np.int64(lo)), keep=1
    )
    if int(distinct) != n_keys:
        raise DeviceUnsupported(f"build key {key!r} repeats: {n_keys - int(distinct)} rows share a key with another")
    return BuildTable(form, tuple(arrays), lo, n, sum(int(a.nbytes) for a in arrays))


def _run_table(session, mesh, skeleton: str, fn, args, keep: int = 0):
    """One run of a ``join-agg-table`` program: its first ``keep`` outputs stay
    on the device, the rest come down."""
    key = D._program_key(f"jtable:{skeleton}", mesh)
    jitted = D._cached_predicate_jit(key, fn, "join-agg-table")
    first = D._note_compile(key, tuple(getattr(a, "shape", ()) for a in args))
    _hlo_lint.maybe_verify(session.conf, "join-agg-table", key, jitted, args)
    t0 = D._ptime.perf_counter()
    with D.launch("join-agg-table"):
        out = jitted(*args)
    down = D.fetch(out[keep:] if keep else out, "join-table", "join-agg-table")
    D._observe_program("join-agg-table", first, t0)
    return (*out[:keep], *down) if keep else down


# --------------------------------------------------------------------------
# the program
# --------------------------------------------------------------------------


_TILE = 1024  # rows of one tile of a one-dimensional 32-bit plane: a slice that starts on one is read where it lies


def _words(total: int) -> int:
    """Words the mask of ``total`` rows packs into: whole tiles of them, so
    that each of a word's ``_WORD`` strided rows starts on a tile; the rows
    past ``_WORD`` times that (under ``_WORD`` tiles) go on unpacked."""
    return total // (_WORD * _TILE) * _TILE


def _compaction(total: int, cap: int) -> str:
    """``words`` where packing the mask and sorting twice walks fewer values
    than the scan has rows, ``whole`` (the scan's positions sorted) else."""
    words = _words(total)
    return "words" if 0 < words and words + cap * _WORD + (total - words * _WORD) < total and cap <= words else "whole"


def _selected_positions(mask, total: int, cap: int, form: str):
    """The positions of ``mask``'s set rows, ascending, as ``cap`` int32
    values (``_I32_MAX`` behind the last): traced."""
    import jax
    import jax.numpy as jnp

    def front(values, keep):
        return jax.lax.sort((values,), num_keys=1, is_stable=False)[0][:keep]

    rows = jnp.arange(total, dtype=jnp.int32)
    if form == "whole":
        return front(jnp.where(mask, rows, _I32_MAX), cap)
    words = _words(total)
    with jax.named_scope("pack"):
        # bit k of word w is row k * words + w: _WORD slices of the mask, each read where it lies
        packed = jnp.zeros((words,), jnp.uint32)
        for k in range(_WORD):
            packed = packed | (mask[k * words:(k + 1) * words].astype(jnp.uint32) << k)
    with jax.named_scope("words"):
        number = front(jnp.where(packed != 0, jnp.arange(words, dtype=jnp.int32), jnp.int32(words)), cap)
        live = number < words
        number = jnp.minimum(number, words - 1)
        word = jnp.where(live, packed.at[number].get(mode="promise_in_bounds"), jnp.uint32(0))
    with jax.named_scope("rows"):
        bits = jnp.arange(_WORD, dtype=jnp.uint32)
        chosen = ((word[None, :] >> bits[:, None]) & 1) != 0
        position = jnp.arange(_WORD, dtype=jnp.int32)[:, None] * words + number[None, :]
        candidates = jnp.where(chosen, position, _I32_MAX).reshape(_WORD * cap)
        if words * _WORD < total:  # the rows past the last word
            candidates = jnp.concatenate([candidates, jnp.where(mask[words * _WORD:], rows[words * _WORD:], _I32_MAX)])
        return front(candidates, cap)


def _gather(columns: dict, at):
    """``columns`` (name -> device column) at rows ``at``, planes gathered
    one by one and joined: the 64-bit values of the selected rows only."""
    import jax

    planes, tree = jax.tree_util.tree_flatten(columns)
    taken = [p.at[at].get(mode="promise_in_bounds") for p in planes]
    return D.join_columns(jax.tree_util.tree_unflatten(tree, taken))


class _Field(NamedTuple):
    """``bits`` bits of row word ``word``, from bit ``shift`` up."""

    word: int
    shift: int
    bits: int


class RowLayout(NamedTuple):
    """Where each value a selected probe row hands on rides: the key's code
    of the build table and the dictionary columns in ``words`` row words, and
    the columns whose planes are gathered as they are."""

    key: _Field
    columns: tuple  # (name, _Field) of every dictionary column the fold reads of the probe side
    planes: tuple  # names of the columns the codec does not bound
    words: int

    def skeleton(self) -> str:
        return ",".join(f"{name}@{f.word}.{f.shift}+{f.bits}" for name, f in (("", self.key), *self.columns))

    def fields(self) -> dict:
        """How many fields ride in each form (``hs_join_row_fields_total``)."""
        return {"word": 1 + len(self.columns), "planes": len(self.planes)}


def _row_layout(table_form: str, size: int, take, codecs) -> RowLayout:
    """The layout of the probe side's row words, from what is observed and
    nothing configured: the key as its code of the build table (``direct``:
    the offset into ``size`` slots, ``size`` itself for "no build row";
    ``sorted``: the 32-bit code, ``_NO_CODE`` for none), each dictionary
    column of ``take`` as code + 1 (NULL is 0) in the bits its dictionary
    needs. Fields go, in that order, into the first 32-bit word with room. A
    column the codec does not bound stays planes."""
    used: List[int] = []

    def place(bits: int) -> _Field:
        for word, taken in enumerate(used):
            if taken + bits <= 32:
                used[word] += bits
                return _Field(word, taken, bits)
        used.append(bits)
        return _Field(len(used) - 1, 0, bits)

    key = place(size.bit_length() if table_form == "direct" else 32)
    packed = [c for c in take if codecs[c].kind == "string"]
    columns = tuple((c, place(len(codecs[c].uniques).bit_length())) for c in packed)
    return RowLayout(key, columns, tuple(c for c in take if c not in packed), len(used))


def _row_words(pcols, layout: RowLayout, key_codec, key: str, none: int, lo, n_valid):
    """The probe side's row words (``layout.words`` uint32 arrays of its
    padded length): traced, element-wise over the planes the mask streams.
    The key's code is its offset from ``lo`` where that is under ``none``,
    and ``none`` where it has no build row: under ``lo``, past the table's
    range, NULL, or a row outside the scan."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("row-words"):
        k = D.join_planes(pcols[key])
        off = k - lo
        inside = _key_valid(k, key_codec, n_valid) & (off >= 0) & (off < none)
        values = [(layout.key, jnp.where(inside, off, none))] + [(f, pcols[c] + 1) for c, f in layout.columns]
        words = [jnp.uint32(0)] * layout.words
        for f, v in values:
            words[f.word] = words[f.word] | (v.astype(jnp.uint32) << f.shift)
        return tuple(words)


def _field(words, f: _Field):
    """Field ``f`` of the gathered row words, as uint32."""
    return (words[f.word] >> f.shift) & np.uint32((1 << f.bits) - 1)


def _no_code(table_form: str, arrays) -> int:
    """The key code that stands for "no build row", one past the codes a
    table of ``arrays`` can hold."""
    return arrays[0].shape[0] if table_form == "direct" else int(_NO_CODE)


def _lookup(table_form: str, arrays, code, on):
    """``(build row, matched)`` of each selected probe row's key code (its
    row word's: :func:`_row_words`): traced."""
    import jax.numpy as jnp

    on = on & (code != _no_code(table_form, arrays))
    if table_form == "direct":
        (table,) = arrays
        row = table.at[jnp.minimum(code, table.shape[0] - 1).astype(jnp.int32)].get(mode="promise_in_bounds")
        return jnp.maximum(row, 0), on & (row >= 0)
    codes, rows = arrays
    n = codes.shape[0]
    below = jnp.zeros(code.shape, jnp.int32)  # how many codes lie under the probe's: a lower bound, bit by bit
    for bit in reversed(range(n.bit_length())):
        step = below + jnp.int32(1 << bit)
        under = (step <= n) & (codes.at[jnp.minimum(step, n) - 1].get(mode="promise_in_bounds") < code)
        below = jnp.where(under, step, below)
    at = jnp.minimum(below, n - 1)
    found = on & (below < n) & (codes.at[at].get(mode="promise_in_bounds") == code)
    return rows.at[at].get(mode="promise_in_bounds"), found


class _Compiled(NamedTuple):
    probe_pred: object
    build_pred: object
    comp_fn: object
    lits: tuple
    probe_lits: int  # the probe predicate's are the first of ``lits``
    skeletons: tuple  # the probe predicate's, the build predicate's, the computes'


def _compile(shape_sides, codecs_p, codecs_b, computes, aggs, group_keys) -> _Compiled:
    """Everything that can say DeviceUnsupported of the query's expressions,
    so that it can be asked with dry codecs before an upload. One ``lits``
    tuple: the probe predicate's slots, the build predicate's, the computes'."""
    probe, build = shape_sides
    merged = {**codecs_b, **codecs_p}
    lits: tuple = ()

    def predicate(side, codecs):
        nonlocal lits
        if side.condition is None:
            return None, "<none>"
        fn, values = D.compile_predicate(side.condition, codecs, lit_base=len(lits))
        lits += tuple(values)
        return fn, D.predicate_skeleton(side.condition, codecs)

    probe_pred, probe_sk = predicate(probe, codecs_p)
    probe_lits = len(lits)
    build_pred, build_sk = predicate(build, codecs_b)
    comp_fn, comp_sk, computed = None, "", set()
    if computes:
        comp_fn, comp_lits, comp_sk = D.compile_computes(computes, merged, lit_base=len(lits))
        lits += tuple(comp_lits)
        computed = {name for name, _ in computes}
    for _, fn, c in aggs:
        if fn not in (D._GROUPED_AGG_FNS if group_keys else D._AGG_FNS):
            raise DeviceUnsupported(f"unsupported aggregate fn {fn!r}")
        if c is not None and c not in computed and merged[c].kind != "numeric":
            raise DeviceUnsupported(f"aggregate over non-numeric column {c!r}")
    for k in group_keys:
        if merged[k].kind != "string":
            raise DeviceUnsupported(f"group key {k!r} is not dictionary-coded")
    _check_key_codecs(codecs_p[probe.key], codecs_b[build.key])
    return _Compiled(probe_pred, build_pred, comp_fn, lits, probe_lits, (probe_sk, build_sk, "c:" + comp_sk))


_PROBE_ROWS: dict = {}


def _count_probe_rows(selected: int, matched: int) -> None:
    """One launch of ``join-agg-resident`` in ``hs_join_probe_rows_total{kind}``:
    ``selected``, the probe rows its predicate kept, and ``matched``, those of
    them whose key the build side holds."""
    for kind, rows in (("selected", selected), ("matched", matched)):
        c = _PROBE_ROWS.get(kind)
        if c is None:
            c = _PROBE_ROWS[kind] = _REGISTRY.counter(
                "hs_join_probe_rows_total",
                "Probe rows of the resident join-aggregate's launches: selected by the probe side's predicate, matched on the build side",
                kind=kind,
            )
        c.inc(rows)


def _count_row_fields(layout: RowLayout) -> None:
    """One launch of ``join-agg-resident`` in ``hs_join_row_fields_total{form}``:
    the fields a selected probe row hands on (its key and each column the
    fold reads of it), ``word`` where one rides in a row word, ``planes``
    where its planes are gathered."""
    for form, fields in layout.fields().items():
        _REGISTRY.counter(
            "hs_join_row_fields_total",
            "Fields a selected probe row of the resident join-aggregate hands on past the compaction, a launch: packed into a 32-bit row word, or gathered from the column's planes",
            form=form,
        ).inc(fields)


def _selected_rows(session, cols: D.ScanColumns, dev_cols, codec_key, key: str, pred_fn, pred_cols, lits, skeleton: str, total: int) -> int:
    """How many probe rows the predicate keeps (a NULL key is not kept): the
    capacity the program is built for. One launch of ``join-agg-probe`` the
    first time these literals are asked of these files; remembered with the
    other capacities, since it cannot change."""
    import jax.numpy as jnp

    n = cols.rows
    if pred_fn is None and codec_key.kind != "datetime":
        return n
    memo_key = None
    if cols.scan_key is not None:
        memo_key = (cols.scan_key, "join-selected", key, skeleton, tuple(np.asarray(v).tobytes() for v in lits))
        known = D._CAP_HINT_MEMO.get(memo_key)
        if known is not None:
            return known
    taken = {c: dev_cols[c] for c in sorted({*pred_cols, key})}

    def program(cols, lits, n_valid):
        return _probe_mask(cols, codec_key, key, pred_fn, pred_cols, lits, n_valid).sum(dtype=jnp.int32)

    pkey = D._program_key(f"jprobe[{total}]:{key}:{skeleton}", cols.mesh)
    jitted = D._cached_predicate_jit(pkey, program, "join-agg-probe")
    first = D._note_compile(pkey, tuple(taken[c].shape for c in sorted(taken)))
    _hlo_lint.maybe_verify(session.conf, "join-agg-probe", pkey, jitted, (taken, lits, np.int64(n)))
    t0 = D._ptime.perf_counter()
    D.count_column_forms(taken.values())
    with D.launch("join-agg-probe"):
        out = jitted(taken, lits, np.int64(n))
    known = int(D.fetch(out, "agg-table", "join-agg-probe"))
    D._observe_program("join-agg-probe", first, t0)
    if memo_key is not None:
        D._remember_capacity(memo_key, known)
    return known


def _probe_mask(cols, codec_key, key: str, pred_fn, pred_cols, lits, n_valid):
    """The probe rows that go on: inside the scan, kept by the predicate,
    holding a key. Traced; the probe and the program share it."""
    import jax

    with jax.named_scope("filter"):
        mask = _key_valid(D.join_planes(cols[key]), codec_key, n_valid)  # an integer key is not read for it
        if pred_fn is not None:
            mask = pred_fn(D.join_columns({c: cols[c] for c in pred_cols}), lits) & mask
    return mask


def _join_program(probe_key: str, key_codec, compiled: _Compiled, preds, take_b, totals, cap: int, form: str,
                  table_form: str, layout: RowLayout, fold):
    """The traced body of ``join-agg-resident`` for ``totals`` padded rows
    (probe, build), ``cap`` selected probe rows going on (no fewer than the
    predicate keeps: the probe counted them) compacted by ``form``, a build
    table of ``table_form``. ``preds``: each side's predicate columns;
    ``take_b``: the build side's columns gathered by the match; ``layout``:
    how a selected probe row is fetched, one word a row; ``fold``: ``("dense",
    plan, groups, slots)`` of a grouped fold, or ``("fused", (fn, column)
    pairs)`` of a global one."""
    import jax
    import jax.numpy as jnp

    (pred_p, pred_b), (total_p, total_b) = preds, totals

    def program(pcols, bcols, arrays, lo, lits, n_probe, n_build):
        mask = _probe_mask(pcols, key_codec, probe_key, compiled.probe_pred, pred_p, lits, n_probe)
        words = _row_words(pcols, layout, key_codec, probe_key, _no_code(table_form, arrays), lo, n_probe)
        with jax.named_scope("compact"):
            at = _selected_positions(mask, total_p, cap, form)
            on = at < total_p
            at = jnp.minimum(at, total_p - 1)
        with jax.named_scope("probe"):
            taken = [w.at[at].get(mode="promise_in_bounds") for w in words]
            row, matched = _lookup(table_form, arrays, _field(taken, layout.key), on)
            counts = (on.sum(dtype=jnp.int32), matched.sum(dtype=jnp.int32))
            if compiled.build_pred is not None:
                keep = compiled.build_pred(D.join_columns({c: bcols[c] for c in pred_b}), lits)
                keep = keep & (jnp.arange(total_b, dtype=jnp.int32) < n_build.astype(jnp.int32))
                matched = matched & keep.at[row].get(mode="promise_in_bounds")
        with jax.named_scope("gather"):
            cols = {**_gather({c: bcols[c] for c in take_b}, row), **_gather({c: pcols[c] for c in layout.planes}, at),
                    **{c: _field(taken, f).astype(jnp.int32) - 1 for c, f in layout.columns}}
        if compiled.comp_fn is not None:
            cols = compiled.comp_fn(cols, lits)
        if fold[0] == "fused":
            return counts, D._fused_reduce(cols, matched, fold[1])
        _, plan, groups, slots = fold
        return counts, D._dense_reduce(cols, matched, jnp.arange(cap, dtype=jnp.int32), plan, groups, slots)

    return program


def device_join_aggregate(
    session, probe: JoinSide, build: JoinSide, cols_p: D.ScanColumns, cols_b: D.ScanColumns,
    computes, group_keys, aggs, *, max_groups: int = 0,
) -> Tuple[B.Batch, dict]:
    """The answer of ``aggs`` (grouped by ``group_keys``) over the inner join
    of ``probe`` and ``build``, from their columns on the device (``cols_p``,
    ``cols_b``: what :func:`side_columns` names) and the build side's table,
    in one run of ``join-agg-resident``; and what it found, for the tier's
    span. What can refuse is asked before an upload: the expressions and the
    columns' types by :func:`join_aggregate_shape` (from the index builds'
    recorded schemas, before a file is read), the build key when its table is
    made, from the key column alone. The reads and uploads of a cold start
    are single-flight (``ScanColumns``, the table's lock): of the requests a
    server warms up with at once, one reads each scan."""
    D.ensure_x64()
    import jax
    import jax.numpy as jnp

    mesh = cols_p.mesh
    sides = (probe, build)
    aggs, group_keys = list(aggs), list(group_keys)
    table = build_table(session, cols_b, build.key)
    dev_b, codecs_b = cols_b.on_device()
    dev_p, codecs_p = cols_p.on_device()
    compiled = _compile(sides, codecs_p, codecs_b, computes, aggs, group_keys)
    codecs = {**codecs_b, **codecs_p}
    n_p, n_b = cols_p.rows, cols_b.rows
    total_p, total_b = int(dev_p[probe.key].shape[0]), int(dev_b[build.key].shape[0])
    if total_p >= 2**31:
        raise DeviceUnsupported("probe row positions past 32 bits")
    agg_spec = tuple((fn, c) for _, fn, c in aggs)
    key_codec = codecs_p[probe.key]

    # what is gathered of each side: what the fold reads, nothing of the filters
    reads = aggregate_reads(group_keys, aggs, computes)
    take_p = sorted(c for c in reads if c in dev_p)
    take_b = sorted(c for c in reads if c in dev_b)
    pred_p = sorted(probe.condition.references()) if probe.condition is not None else []
    pred_b = sorted(build.condition.references()) if build.condition is not None else []

    # the fold is the scan tiers' own; its dtypes follow from the columns' alone
    if group_keys:
        plan, groups = D._dense_key_plan(group_keys, codecs, max_groups)
        dtypes = {c: jax.ShapeDtypeStruct((8,), v.dtype) for c, v in {**dev_b, **dev_p}.items()}
        input_dtypes, slots, refs, cntm_at = D._dense_slots(aggs, compiled.comp_fn, dtypes, codecs, compiled.lits)
        fold = (f"gdense[{groups}]|k:{','.join(f'{k}:{size}:{off}' for k, size, off in plan)}"
                f"|s:{','.join(f'{k}:{c}:{int(i)}' for k, c, i in slots)}")
    else:
        fold = "agg|" + repr(agg_spec)

    selected = _selected_rows(session, cols_p, dev_p, key_codec, probe.key, compiled.probe_pred, pred_p,
                              compiled.lits[: compiled.probe_lits], compiled.skeletons[0], total_p)
    cap = min(total_p, D._keyed_block_capacity(selected))
    form = _compaction(total_p, cap)
    layout = _row_layout(table.form, int(table.arrays[0].shape[0]), take_p, codecs_p)

    fold_args = ("dense", plan, groups, slots) if group_keys else ("fused", agg_spec)
    program = _join_program(probe.key, key_codec, compiled, (pred_p, pred_b), take_b,
                            (total_p, total_b), cap, form, table.form, layout, fold_args)
    skeleton = (f"jagg[{total_p},{total_b},{cap},{form},{table.form}]:{probe.key}={build.key}|{'|'.join(compiled.skeletons)}"
                f"|{fold}|p:{','.join(take_p)}|b:{','.join(take_b)}|w:{layout.skeleton()}")
    key = D._program_key(skeleton, mesh)
    jitted = D._cached_predicate_jit(key, program, "join-agg-resident")
    args = (dev_p, dev_b, table.arrays, np.int64(table.lo), compiled.lits, np.int64(n_p), np.int64(n_b))
    first = D._note_compile(key, tuple(v.shape for d in (dev_p, dev_b) for _, v in sorted(d.items())) + tuple(a.shape for a in table.arrays))
    _hlo_lint.maybe_verify(session.conf, "join-agg-resident", key, jitted, args)
    t0 = D._ptime.perf_counter()
    D.count_column_forms([*dev_p.values(), *dev_b.values()])
    with D.launch("join-agg-resident"):
        out = jitted(*args)
    (n_selected, n_matched), folded = D.fetch(out, "agg-table", "join-agg-resident")
    D._observe_program("join-agg-resident", first, t0)
    n_selected, n_matched = int(n_selected), int(n_matched)
    _count_probe_rows(n_selected, n_matched)
    _count_row_fields(layout)
    trace.agg_rows("device", n_p)
    if group_keys:
        result = D._dense_result(plan, codecs, group_keys, aggs, refs, input_dtypes, *folded, cntm_at)
        n_groups = len(next(iter(result.values())))
        D._count_groups("join-agg-resident", n_groups)
    else:
        result, n_groups = D._fused_result(aggs, *folded), 1
    found = dict(program="join-agg-resident", probe_rows=n_p, build_rows=n_b, selected=n_selected,
                 matched=n_matched, groups=n_groups, table=table.form, capacity=cap, compaction=form,
                 row_words=layout.words, **{f"fields_{form}": n for form, n in layout.fields().items()})
    return result, found
