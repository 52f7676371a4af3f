"""The shape of the dense grouped program (``exec/device.py``,
``jit_hs_grouped_agg_dense``) and of the fused one (``jit_hs_fused_agg``),
read from what the compilers make of them. No chip is used and nothing runs
at the real size.

TPC-H Q1 and Q6 are asked once at toy size and the programs the tier built
for them are taken as they were handed to ``jit``. Then:

- each is compiled at the benchmark's size (67,126,100 padded rows: 60M
  ``lineitem`` rows of SF 10) for a *described* TPU v5e, its 8-byte columns
  handed over as the chip holds them resident, two 32-bit planes each
  (``ColumnPlanes``). Q1's optimized HLO has to show one pass: at most three
  fusions that take a row-length input to a group-length output (the
  slot-by-slot body had twelve), no 64-bit integer pair for a count or for
  the first row, no loop over the rows. In both, every plane and every code
  column is a one-dimensional parameter that a fusion of the entry
  computation reads, and no ``X64SplitLow/High`` takes a row-length operand
  (a whole 8-byte column would be split so in every call): what the
  benchmark's roofline reader (``hsbench/costs_agg.py``) counts as Q1's 48
  and Q6's 32 bytes a row;
- Q1's is lowered (shapes only) on either side of 2^31 rows: below, the row
  index, the counts and the first-row min are 32-bit; from 2^31 on they are
  64-bit. Integer sums are int64 and float sums float64 on both sides.

The topology is described inside a module-scoped fixture, never at import
(one process at a time may load the TPU's library; every xdist worker
imports this file), and the tests that need it skip where it cannot be
described. This is the only test file that describes one, so the keyed
program's copy kernel (``ops/kernels.copy_blocks``) is compiled here too: at
the benchmark's planes, Mosaic takes it (a block starts and ends on a tile of
a one-dimensional plane), and the module it makes reads no plane whole. And
so is the resident join-aggregate (``exec/join_agg.py``) at ``sf10-join``'s
shapes: one operation gathers out of a probe-length operand, the row word,
which a fusion of its own writes (fused into the gather as its operand's
producer it would be three random reads a row again).
"""

import re

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.check import hlo_lint
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.parallel.mesh import make_mesh

import reference_report as ref

SF10_PADDED_ROWS = 67_126_100  # bucket_rows(59,986,052): the rows ``sf10-report`` holds resident
GROUPS = 6  # l_returnflag (A, N, R) x l_linestatus (F, O)
BYTES_A_ROW = {"q1": 48, "q6": 32}  # five 8-byte columns and two int32 codes; four 8-byte columns
FAMILY = {"q1": "grouped-agg-dense", "q6": "fused-agg"}
# the planes of three float64 and two int64 columns and two codes; of two and two
PLANES = {"q1": ["f32"] * 6 + ["s32"] * 4 + ["u32"] * 2, "q6": ["f32"] * 4 + ["s32"] * 2 + ["u32"] * 2}


@pytest.fixture(scope="module")
def programs(tmp_path_factory):
    """``{query: (program, (columns, literals, n_valid))}`` of Q1's dense and
    Q6's fused program, as the tier handed them to ``jit`` for a 6,000-row
    index on one device."""
    root = tmp_path_factory.mktemp("q1-program")
    frame = ref.lineitem(6000, seed=38)
    (root / "lineitem").mkdir()
    pq.write_table(pa.Table.from_pandas(frame, preserve_index=False), str(root / "lineitem" / "part-00000.parquet"))
    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: str(root / "indexes"), hst.keys.NUM_BUCKETS: 4,
                             hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0})
    sess.set_mesh(make_mesh(1))
    hst.set_session(sess)
    df = sess.read_parquet(str(root / "lineitem"))
    df.create_or_replace_temp_view("lineitem")
    included = ["l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus"]
    hst.Hyperspace(sess).create_index(df, hst.CoveringIndexConfig("li_sd_rep", ["l_shipdate"], included))
    sess.enable_hyperspace()

    seen = {}
    cached_jit = D._cached_predicate_jit

    def spy(key, fn, family):
        jitted = cached_jit(key, fn, family)
        if family not in FAMILY.values():
            return jitted

        def call(*args):
            seen[family] = fn, args
            return jitted(*args)

        call.lower = jitted.lower  # hlo_lint.maybe_verify
        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(D, "_cached_predicate_jit", spy)
    try:
        got = {q: sess.sql(ref.SQL[q].format(**ref.PARAMS[q])).collect() for q in FAMILY}
    finally:
        mp.undo()
        hst.set_session(None)
    for q, family in FAMILY.items():
        ref.compare(got[q], ref.answer(q, frame), ref.ORDERED[q])
        assert family in seen, f"{q} did not take the {family} program"
    return {q: seen[family] for q, family in FAMILY.items()}


@pytest.fixture(scope="module")
def q1(programs):
    return programs["q1"]


def _shapes(args, rows: int, sharding=None, planes: bool = False):
    """The call's arguments as shapes, its columns ``rows`` long; with
    ``planes``, every 8-byte column as the ``ColumnPlanes`` of its split."""
    import jax

    def shape(x):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        whole = jax.ShapeDtypeStruct((rows,) if len(x.shape) == 1 else x.shape, x.dtype, sharding=sharding)
        if not (planes and len(x.shape) == 1 and x.dtype.itemsize == 8):
            return whole
        return D.ColumnPlanes(*(jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=sharding)
                                for p in jax.eval_shape(D.split_planes, whole)))

    return jax.tree.map(shape, args)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(programs, one_chip):
    """``{query: (module name, entry computation's instructions, whole text)}``
    of each program compiled for one described v5e chip at the benchmark's
    size, over planes."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    out = {}
    try:
        for q, (program, args) in programs.items():
            lowered = jax.jit(hlo_lint.named(FAMILY[q], program)).lower(
                *_shapes(args, SF10_PADDED_ROWS, one_chip, planes=True))
            text = lowered.compile().as_text()
            name = re.search(r"^HloModule (\S+?),", text, re.M).group(1)
            entry = text[text.index("\nENTRY "):]
            entry = entry[:entry.index("\n}")]
            out[q] = name, [l.strip() for l in entry.splitlines() if " = " in l and not l.startswith("ENTRY")], text
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return out


@pytest.fixture(scope="module")
def optimized(compiled):
    return compiled["q1"]


def _split(instruction: str):
    """``(name, result type, opcode and the rest)`` of one HLO instruction."""
    name, rest = instruction.removeprefix("ROOT ").split(" = ", 1)
    if rest.startswith("("):  # a tuple: "(s32[6]{0}, f32[6]{0}) fusion(...)"
        depth, at = 0, 0
        for at, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        return name, rest[:at + 1], rest[at + 2:]
    result, _, tail = rest.partition(" ")
    return name, result, tail


def _operands(tail: str):
    """Names of an instruction's operands."""
    return re.findall(r"%[\w.\-]+", tail[tail.index("("):].split(")", 1)[0])


def _reductions(entry):
    """``[(instruction, result type)]`` of the entry's fusions that take a
    row-length input to a group-length output."""
    types = {name: result for name, result, _ in map(_split, entry)}
    out = []
    for line in entry:
        _, result, tail = _split(line)
        if not tail.startswith("fusion("):
            continue
        if f"[{GROUPS}]" in result and any(f"[{SF10_PADDED_ROWS}]" in types.get(n, "") for n in _operands(tail)):
            out.append((line, result))
    return out


@pytest.mark.parametrize("q", list(FAMILY))
def test_the_program_keeps_its_name(compiled, q):
    name, _, _ = compiled[q]
    assert name == "jit_" + hlo_lint.program_name(FAMILY[q])


def test_q1_at_sf10_is_one_pass_over_the_rows(optimized):
    _, entry, text = optimized
    reductions = _reductions(entry)
    assert 1 <= len(reductions) <= 3, [line[:120] for line, _ in reductions]
    assert " while(" not in text, "a loop over the rows hides the columns from the roofline reader"


def test_no_count_and_no_first_row_is_a_64_bit_pair(optimized):
    _, entry, _ = optimized
    results = " ".join(result for _, result in _reductions(entry))
    # Q1's states: the first row and five distinct counts (cntm and the cnt of
    # the int column are one) in 32 bits; five float64 sums, each a pair of
    # f32; one exact int64 sum (l_quantity), the only pair of u32
    assert len(re.findall(rf"\bs32\[{GROUPS}\]", results)) == 6, results
    assert len(re.findall(rf"\bf32\[{GROUPS}\]", results)) == 10, results
    assert len(re.findall(rf"\bu32\[{GROUPS}\]", results)) == 2, results
    assert not re.findall(rf"\b[su]64\[{GROUPS}\]", results), results


@pytest.mark.parametrize("q", list(FAMILY))
def test_every_plane_is_read_by_a_fusion_and_nothing_row_long_is_split(compiled, q):
    _, entry, text = compiled[q]
    columns = {}
    for line in entry:
        m = re.match(rf"(%[\w.\-]+) = (\w+)\[{SF10_PADDED_ROWS}\]\S* parameter\(", line)
        if m:
            columns[m.group(1)] = m.group(2)
    assert sorted(columns.values()) == PLANES[q], columns
    readers = [l for l in entry if " fusion(" in l]
    for name in columns:
        assert any(re.search(re.escape(name) + r"\b", l.split(" = ", 1)[1]) for l in readers), \
            f"{name} is read by no fusion of the entry computation"
    splits = [l for l in text.splitlines() if re.search(r'custom_call_target="X64Split(Low|High)"', l)]
    assert splits, "the literals and the row count are 64-bit scalars, split in the call"
    assert not [l[:160] for l in splits if f"[{SF10_PADDED_ROWS}]" in l], "a pass over a column, every call"


def test_a_whole_column_would_be_split_in_every_call(programs, one_chip):
    """The form this one replaced, and what the reader above must not see."""
    import jax

    program, args = programs["q6"]
    text = jax.jit(hlo_lint.named("fused-agg", program)).lower(*_shapes(args, 4096, one_chip)).compile().as_text()
    assert len(re.findall(r'\[4096\]\S* custom-call\(\S+\), custom_call_target="X64Split(?:Low|High)"', text)) == 8


@pytest.mark.parametrize("q", list(FAMILY))
def test_the_benchmarks_reader_counts_the_planes_as_the_columns_bytes(compiled, q):
    costs_agg = pytest.importorskip("hsbench.costs_agg")
    _, entry, _ = compiled[q]
    # a trace names an operation by its long text, operands with their types;
    # the operations with an event are all but the parameters
    types = {name: result for name, result, _ in map(_split, entry)}
    ran = []
    for line in entry:
        name, result, tail = _split(line)
        if tail.startswith("parameter("):
            continue
        opcode, _, rest = tail.partition("(")
        after = rest.partition(")")[2]
        typed = ", ".join(f"{types[n]} {n}" for n in _operands(tail) if not types[n].startswith("("))
        ran.append(f"{name} = {result} {opcode}({typed}){after}")
    assert costs_agg.call_least_bytes(ran) == BYTES_A_ROW[q] * SF10_PADDED_ROWS


@pytest.mark.parametrize("rows, width", [(SF10_PADDED_ROWS, 32), (2**31 - 1, 32), (2**31, 64), (2**32 + 8, 64)])
def test_the_row_count_chooses_the_width_of_the_bookkeeping(q1, rows, width):
    import jax

    program, args = q1
    text = jax.jit(program).lower(*_shapes(args, rows)).as_text()
    iotas = set(re.findall(rf"stablehlo\.iota .*tensor<{rows}xi(\d+)>", text))
    assert iotas == {str(width)}, iotas
    reduces = [l for l in text.splitlines() if "stablehlo.reduce" in l and f"tensor<{GROUPS}x{rows}x" in l]
    assert len(reduces) == 1, reduces
    operands = re.findall(rf"tensor<{GROUPS}x{rows}x(\w+)>", reduces[0].split("->")[0])
    # the first row and five counts in the chosen width; the int64 sum wide
    # at any size; five float64 sums
    assert operands.count("f64") == 5, operands
    if width == 32:
        assert operands.count("i32") == 6 and operands.count("i64") == 1, operands
    else:
        assert operands.count("i64") == 7 and "i32" not in operands, operands


@pytest.mark.parametrize("block, n_planes", [(D._KEYED_BLOCK_ROWS, 8), (4096, 8), (D._KEYED_BLOCK_ROWS, 40)])
def test_the_copy_kernel_compiles_for_the_chip_and_reads_no_plane_whole(one_chip, monkeypatch, block, n_planes):
    """Eight planes as ``sf10-rollup`` holds them (and forty: fewer copies in
    flight, for the chip's semaphores), 768 blocks out of each: one Mosaic
    call, and nothing else of the module has a row-length operand."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from hyperspace_tpu.ops import kernels

    monkeypatch.setattr(kernels, "_use_interpret", lambda: False)
    dtypes = ([jnp.float32] * 4 + [jnp.uint32, jnp.int32] * 2) * (n_planes // 8)
    planes = [jax.ShapeDtypeStruct((SF10_PADDED_ROWS,), dt, sharding=one_chip) for dt in dtypes]
    numbers = jax.ShapeDtypeStruct((768,), jnp.int32, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda n, p: kernels.copy_blocks(n, p, block)).lower(numbers, planes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    entry = text[text.index("\nENTRY "):]
    entry = [l.strip() for l in entry[:entry.index("\n}")].splitlines() if " = " in l]
    calls = [l for l in entry if "custom-call(" in l and "tpu_custom_call" in l]
    assert len(calls) == 1 and calls[0].count(f"[{768 * block}]") == len(planes), calls
    others = [l for l in entry if l not in calls and " parameter(" not in l and f"[{SF10_PADDED_ROWS}]" in l]
    assert not others, others


def test_the_join_program_fetches_a_selected_probe_row_once(one_chip):
    """TPC-H Q12 as ``sf10-join`` asks it: 67,126,100 padded ``lineitem`` rows
    (nine planes), 16,781,524 ``orders`` rows and as many slots of the direct
    table, 557,056 selected rows going on. The key's offset (25 bits) and the
    mode's code (3) ride in one row word."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from hyperspace_tpu.exec import join_agg as JA
    from hyperspace_tpu.plan.expr import Case, col, lit

    D.ensure_x64()
    build_rows, cap = 16_781_524, 557_056
    dates = ("l_shipdate", "l_commitdate", "l_receiptdate")
    strings = lambda values: D.ColumnCodec("string", uniques=np.array(values, dtype=object), dtype=np.dtype(object), nulls=False)
    codecs_p = {"l_orderkey": D.ColumnCodec("numeric", dtype=np.dtype("int64")),
                "l_shipmode": strings(["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]),
                **{c: D.ColumnCodec("datetime", unit="D", dtype=np.dtype("M8[D]")) for c in dates}}
    codecs_b = {"o_orderkey": D.ColumnCodec("numeric", dtype=np.dtype("int64")),
                "o_orderpriority": strings(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"])}
    day = lambda text: lit(np.datetime64(text))
    cond = ((col("l_shipmode").isin("MAIL", "SHIP")) & (col("l_commitdate") < col("l_receiptdate")) & (col("l_shipdate") < col("l_commitdate"))
            & (col("l_receiptdate") >= day("1994-01-01")) & (col("l_receiptdate") < day("1995-01-01")))
    high = (col("o_orderpriority") == lit("1-URGENT")) | (col("o_orderpriority") == lit("2-HIGH"))
    low = (col("o_orderpriority") != lit("1-URGENT")) & (col("o_orderpriority") != lit("2-HIGH"))
    computes = [("hi", Case([(high, lit(1))], lit(0))), ("lo", Case([(low, lit(1))], lit(0)))]
    aggs, keys = [("high_line_count", "sum", "hi"), ("low_line_count", "sum", "lo")], ["l_shipmode"]
    sides = (JA.JoinSide(None, cond, "l_orderkey", frozenset(codecs_p)), JA.JoinSide(None, None, "o_orderkey", frozenset(codecs_b)))
    made = JA._compile(sides, codecs_p, codecs_b, computes, aggs, keys)
    codecs = {**codecs_b, **codecs_p}
    plan, groups = D._dense_key_plan(keys, codecs, 0)
    dtypes = {c: jax.ShapeDtypeStruct((8,), np.dtype("int32" if codecs[c].kind == "string" else "int64")) for c in codecs}
    _, slots, _, _ = D._dense_slots(aggs, made.comp_fn, dtypes, codecs, made.lits)
    layout = JA._row_layout("direct", build_rows, ["l_shipmode"], codecs_p)
    assert layout.skeleton() == "@0.0+25,l_shipmode@0.25+3" and (layout.words, layout.planes) == (1, ())
    program = JA._join_program("l_orderkey", codecs_p["l_orderkey"], made, (sorted(cond.references()), []), ["o_orderpriority"],
                               (SF10_PADDED_ROWS, build_rows), cap, JA._compaction(SF10_PADDED_ROWS, cap), "direct", layout, ("dense", plan, groups, slots))

    plane = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    planes = lambda n: D.ColumnPlanes(plane(n, jnp.uint32), plane(n, jnp.int32))
    scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
    pcols = {"l_orderkey": planes(SF10_PADDED_ROWS), "l_shipmode": plane(SF10_PADDED_ROWS, jnp.int32), **{c: planes(SF10_PADDED_ROWS) for c in dates}}
    bcols = {"o_orderkey": planes(build_rows), "o_orderpriority": plane(build_rows, jnp.int32)}
    args = (pcols, bcols, (plane(build_rows, jnp.int32),), scalar(np.int64), tuple(scalar(np.asarray(v).dtype) for v in made.lits), scalar(np.int64), scalar(np.int64))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(hlo_lint.named("join-agg-resident", program)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    entry = text[text.index("\nENTRY "):]
    entry = [l.strip() for l in entry[:entry.index("\n}")].splitlines() if " = " in l and not l.startswith("ENTRY")]
    split = {name: (result, tail) for name, result, tail in map(_split, entry)}
    long = lambda name: f"[{SF10_PADDED_ROWS}]" in split.get(name, ("",))[0]
    # what takes a probe-length operand and hands on the selected rows' length gathers out of it
    gathers = [(name, [n for n in _operands(tail) if long(n)]) for name, (result, tail) in split.items()
               if f"[{cap}]" in result and any(long(n) for n in _operands(tail))]
    assert len(gathers) == 1 and len(gathers[0][1]) == 1, gathers
    word, (result, tail) = gathers[0][1][0], split[gathers[0][1][0]]
    assert result.startswith("u32[") and tail.startswith("fusion("), (word, result, tail[:80])
    # written by a fusion of its own or by the mask's, whichever the compiler makes: either way it streams what the word is made of
    assert {n for n in _operands(tail) if long(n)} >= {n for n in split if re.match(r"%pcols__l_(orderkey|shipmode)", n)}, "the word's pass reads the key's planes and the mode's codes"
    parameters = [n for n, (result, tail) in split.items() if tail.startswith("parameter(") and long(n)]
    assert len(parameters) == 9, parameters
    readers = [tail for _, tail in split.values() if tail.startswith("fusion(")]
    assert all(any(n in _operands(tail) for tail in readers) for n in parameters), "every plane is streamed by a fusion"
    assert not [n for n, (result, tail) in split.items() if tail.startswith(("sort(", "while(")) and long(n)]
