"""The shape of the dense grouped program (``exec/device.py``,
``jit_hs_grouped_agg_dense``), read from what the compilers make of it. No
chip is used and nothing runs at the real size.

TPC-H Q1 is asked once at toy size and the program the tier built for it is
taken as it was handed to ``jit``. Then:

- it is compiled at the benchmark's size (67,126,100 padded rows: 60M
  ``lineitem`` rows of SF 10) for a *described* TPU v5e, and the optimized HLO
  has to show one pass: at most three fusions that take a row-length input to
  a group-length output (the slot-by-slot body had twelve), no 64-bit
  integer pair for a count or for the first row, no loop over the rows, and
  every resident column read, as a one-dimensional parameter, by a fusion or
  a 64-bit split of the entry computation: what the benchmark's roofline
  reader (``hsbench/costs_agg.py``) counts as the call's 48 bytes a row;
- it is lowered (shapes only) on either side of 2^31 rows: below, the row
  index, the counts and the first-row min are 32-bit; from 2^31 on they are
  64-bit. Integer sums are int64 and float sums float64 on both sides.

The topology is described inside a module-scoped fixture, never at import
(one process at a time may load the TPU's library; every xdist worker
imports this file), and the tests that need it skip where it cannot be
described. This is the only test file that describes one.
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
Q1_BYTES_A_ROW = 48  # five 8-byte columns and two int32 codes


@pytest.fixture(scope="module")
def q1(tmp_path_factory):
    """``(program, (columns, literals, n_valid))`` of Q1's dense program, as
    the tier handed them to ``jit`` for a 6,000-row index on one device."""
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
        if family != "grouped-agg-dense":
            return jitted

        def call(*args):
            seen["program"], seen["args"] = fn, args
            return jitted(*args)

        call.lower = jitted.lower  # hlo_lint.maybe_verify
        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(D, "_cached_predicate_jit", spy)
    try:
        got = sess.sql(ref.SQL["q1"].format(**ref.PARAMS["q1"])).collect()
    finally:
        mp.undo()
        hst.set_session(None)
    ref.compare(got, ref.answer("q1", frame), ref.ORDERED["q1"])
    assert seen, "Q1 did not take the dense grouped program"
    return seen["program"], seen["args"]


def _shapes(args, rows: int, sharding=None):
    """The call's arguments as shapes, its columns ``rows`` long."""
    import jax

    def shape(x):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct((rows,) if len(x.shape) == 1 else x.shape, x.dtype, sharding=sharding)

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
def optimized(q1, one_chip):
    """``(module name, entry computation's instructions, whole text)`` of
    Q1's program compiled for one described v5e chip at the benchmark's size."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    program, args = q1
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = jax.jit(hlo_lint.named("grouped-agg-dense", program)).lower(
            *_shapes(args, SF10_PADDED_ROWS, one_chip))
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    name = re.search(r"^HloModule (\S+?),", text, re.M).group(1)
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    return name, [line.strip() for line in entry.splitlines() if " = " in line and not line.startswith("ENTRY")], text


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


def test_the_program_keeps_its_name(optimized):
    name, _, _ = optimized
    assert name == "jit_hs_grouped_agg_dense"


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


def test_every_resident_column_is_read_by_a_fusion_or_a_split(optimized):
    _, entry, _ = optimized
    columns = {}
    for line in entry:
        m = re.match(rf"(%[\w.\-]+) = (\w+)\[{SF10_PADDED_ROWS}\]\S* parameter\(", line)
        if m:
            columns[m.group(1)] = m.group(2)
    assert sorted(columns.values()) == ["f64", "f64", "f64", "s32", "s32", "s64", "s64"], columns
    readers = [l for l in entry if " fusion(" in l or re.search(r'custom_call_target="X64Split(Low|High)"', l)]
    for name in columns:
        assert any(re.search(re.escape(name) + r"\b", l.split(" = ", 1)[1]) for l in readers), \
            f"{name} is read by no fusion and no 64-bit split of the entry computation"


def test_the_benchmarks_reader_counts_48_bytes_a_row(optimized):
    costs_agg = pytest.importorskip("hsbench.costs_agg")
    _, entry, _ = optimized
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
    assert costs_agg.call_least_bytes(ran) == Q1_BYTES_A_ROW * SF10_PADDED_ROWS


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
