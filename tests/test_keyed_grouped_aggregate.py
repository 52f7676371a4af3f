"""The keyed grouped aggregate (``exec/device.py``, ``grouped-agg-keyed``)
against plain pandas.

``device_scan_aggregate`` is handed a host batch (nothing resident, no index),
so every case is the program over that batch's columns: group keys that are
integers, dates or dictionary codes past the dense program's 64 groups, a
filter, computed inputs. The reference is ``groupby(sort=False, dropna=False)``
over the filtered frame: the same groups in the same (first appearance) order,
keys, counts and integer sums exact, floats to 1e-12. The cases:

- int64 and date keys, two keys together, a date key with NULLs (NaT is a
  group of its own), a float input with NULLs, dictionary codes with a null;
- 1, 300 and 100,000 groups;
- ``sum``, ``count``, ``avg``, ``min``, ``max``, ``stddev_samp`` over a float
  and an int column, an int sum past 2^53, a computed input ``a * (1 - b)``;
- a predicate selecting none, about 4 % (runs of a sorted column, so that
  blocks are skipped) and all of the rows; no predicate at all;
- more than two inputs are ``DeviceUnsupported`` before anything is uploaded;
- the capacity ladder crossing a bucket between two calls over one scan, and
  ``GroupCapacityExceeded`` past ``maxGroups``; keys that span more than 32
  bits and a float key are ``DeviceUnsupported``;
- the blocks that go on are each call's own: the probe runs once for a
  predicate's literals over a scan, and a call that selects every block
  leaves the shape of a narrow one as it was;
- whole columns and 32-bit planes give the same answer;
- the same on one device and on a mesh of four virtual devices;
- the skip: runs that start and end inside a block, end in the short last
  block, lie in adjacent blocks, hit one block or every block, with NULL
  dates and NaN inputs in the blocks taken; one device copies the blocks out
  of the columns as they lie (``copy``, the Pallas kernel, interpreted here),
  a mesh lays the columns out in blocks (``layout``), read from the tier
  span; the blocks' own ladder wastes an eighth at most and leaves the shared
  ladders alone; no operation of the copy form's program outside the filter
  takes a whole column and gives more than a block.
"""

import numpy as np
import pandas as pd
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.obs import spans
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.parallel.mesh import make_mesh

FLOAT_RTOL = 1e-12
DAY0 = np.datetime64("1992-01-01")


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device", "mesh-of-four"])
def sess(request, tmp_path_factory):
    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path_factory.mktemp("keyed") / "indexes")})
    s.set_mesh(make_mesh(request.param))
    hst.set_session(s)
    yield s
    hst.set_session(None)


def _batch(rows: int, groups: int, seed: int = 41) -> dict:
    """``d`` ascending (an index's sort column: a range selects runs), ``k``
    an int64 key with ``groups`` values, ``day`` a date key, ``s`` strings."""
    rng = np.random.default_rng(seed)
    x = rng.normal(100.0, 30.0, rows)
    x[rng.random(rows) < 0.15] = np.nan
    return {
        "d": np.sort(rng.integers(0, 100, rows)).astype(np.int64),
        "k": (rng.integers(0, groups, rows) * 7 - 1000).astype(np.int64),
        "day": DAY0 + rng.integers(0, min(groups, 2400), rows).astype("timedelta64[D]"),
        "s": np.asarray([f"g{i:03d}" for i in range(70)], dtype=object)[rng.integers(0, 70, rows)],
        "x": x,
        "i": rng.integers(-50, 51, rows).astype(np.int64),
        "big": rng.integers(2**51, 2**52, rows).astype(np.int64),
        "a": np.round(rng.uniform(900.0, 105000.0, rows), 2),
        "b": rng.integers(0, 11, rows) / 100.0,
    }


SLOT_AGGS = [
    ("n", "count", None), ("n_x", "count", "x"), ("sum_x", "sum", "x"), ("avg_x", "avg", "x"), ("min_x", "min", "x"),
    ("max_x", "max", "x"), ("sd_x", "stddev_samp", "x"), ("n_i", "count", "i"), ("sum_i", "sum", "i"),
    ("avg_i", "avg", "i"), ("min_i", "min", "i"), ("max_i", "max", "i"),
]
REVENUE = [("rev", hst.col("a") * (hst.lit(1) - hst.col("b")))]
REVENUE_AGGS = [("total", "sum", "rev"), ("rows", "count", None)]
_PANDAS = {"sum": lambda s: s.sum(min_count=1), "avg": "mean", "min": "min", "max": "max", "stddev_samp": "std",
           "count": "count"}


def _reference(frame: pd.DataFrame, keys, aggs) -> pd.DataFrame:
    grouped = frame.groupby(list(keys), sort=False, dropna=False)
    out = grouped.size().rename("__size").reset_index()
    for name, fn, c in aggs:
        out[name] = out["__size"].to_numpy() if c is None else grouped[c].agg(_PANDAS[fn]).to_numpy()
    return out.drop(columns="__size")


def _groups_counted() -> float:
    return REGISTRY.counter("hs_agg_groups_total", "", program="grouped-agg-keyed").value


def _run(sess, batch, condition, computes, keys, aggs, max_groups=1 << 20, cap_floor=64, scan_key=None):
    cols = D.ScanColumns(sess, scan_key, sorted(batch), lambda: batch)
    before = _groups_counted()
    got = D.device_scan_aggregate(sess, cols, condition, computes, list(keys), list(aggs),
                                  max_groups=max_groups, cap_floor=cap_floor)
    return got, _groups_counted() - before


def _traced_run(sess, batch, condition, computes, keys, aggs):
    """``(answer, what the program said of itself on the tier's span)``."""
    with spans.trace("keyed") as root:
        got, _ = _run(sess, batch, condition, computes, keys, aggs)
    return got, dict(root.attrs)


def _same(got: dict, want: pd.DataFrame, keys, aggs, int_inputs=("i", "big")) -> None:
    assert list(got) == list(keys) + [name for name, _, _ in aggs]
    for k in keys:
        g, w = np.asarray(got[k]), want[k].to_numpy()
        assert len(g) == len(w), (k, len(g), len(w))
        if w.dtype.kind == "M":
            assert g.dtype.kind == "M"
            assert np.array_equal(g.astype("M8[ns]").view(np.int64), w.astype("M8[ns]").view(np.int64)), k
        elif w.dtype.kind == "O":
            assert all((a == b) or (pd.isna(a) and pd.isna(b)) for a, b in zip(g, w)), k
        else:
            assert g.dtype == w.dtype and np.array_equal(g, w), (k, g[:5], w[:5])
    for name, fn, c in aggs:
        g, w = np.asarray(got[name]), want[name].to_numpy()
        if fn == "count" or (c in int_inputs and fn in ("sum", "min", "max")):
            assert g.dtype == np.int64, (name, g.dtype)
            assert np.array_equal(g, w.astype(np.int64)), (name, g, w)
        else:
            w = w.astype(np.float64)
            assert np.array_equal(np.isnan(g), np.isnan(w)), (name, g, w)
            np.testing.assert_allclose(g, w, rtol=FLOAT_RTOL, atol=0, err_msg=name)


def _nat_days(b):
    day = b["day"].copy()
    day[::17] = np.datetime64("NaT")
    return dict(b, day=day)


def _null_strings(b):
    s = b["s"].copy()
    s[::11] = None
    return dict(b, s=s)


CASES = {
    # name: (rows, groups, keys, condition or None, computes, aggs, patch(batch) or None)
    "int-key-every-slot-kind": (20000, 300, ["k"], hst.col("d") <= 70, [], SLOT_AGGS, None),
    "date-key": (20000, 300, ["day"], hst.col("d") <= 70, [], SLOT_AGGS[:7], None),
    "date-key-with-nulls": (20000, 300, ["day"], hst.col("d") <= 70, [], SLOT_AGGS[:7], _nat_days),
    "int-and-date-key": (20000, 20, ["k", "day"], hst.col("d") <= 70, [], SLOT_AGGS[:7], None),
    "seventy-dictionary-codes-with-a-null": (12000, 70, ["s"], hst.col("d") <= 80, [], SLOT_AGGS[:7], _null_strings),
    "one-group": (9000, 1, ["k"], hst.col("d") <= 50, [], SLOT_AGGS[:7], None),
    "a-hundred-thousand-groups": (400000, 100000, ["k"], hst.col("d") <= 97, REVENUE, REVENUE_AGGS, None),
    "int-sum-past-2-53": (20000, 300, ["k"], hst.col("d") <= 94, [],
                          [("s", "sum", "big"), ("mn", "min", "big"), ("mx", "max", "big"), ("n", "count", "big")], None),
    "a-computed-input": (30000, 300, ["k"], hst.col("d") <= 70, REVENUE,
                         [("s", "sum", "rev"), ("m", "avg", "rev"), ("lo", "min", "rev"), ("hi", "max", "rev"),
                          ("n", "count", "rev"), ("rows", "count", None)], None),
    "a-predicate-that-selects-none": (20000, 300, ["k"], hst.col("d") > 1000, REVENUE, REVENUE_AGGS, None),
    "a-predicate-that-selects-four-percent": (60000, 300, ["k"], (hst.col("d") >= 40) & (hst.col("d") < 44),
                                              REVENUE, REVENUE_AGGS, None),
    "a-predicate-that-selects-all": (20000, 300, ["k"], hst.col("d") >= 0, REVENUE, REVENUE_AGGS, None),
    "no-predicate": (20000, 300, ["k"], None, REVENUE, REVENUE_AGGS, None),
    "rows-fill-the-padded-length": (D.bucket_rows(20000), 300, ["k"], hst.col("d") <= 70, [], SLOT_AGGS[:7], None),
}


def _matched(frame: pd.DataFrame, condition) -> pd.DataFrame:
    if condition is None:
        return frame
    return frame[np.asarray(condition.eval({c: frame[c].to_numpy() for c in frame.columns}), dtype=bool)]


@pytest.mark.parametrize("case", list(CASES))
def test_the_keyed_program_answers_as_pandas_does(sess, case):
    rows, groups, keys, condition, computes, aggs, patch = CASES[case]
    batch = _batch(rows, groups)
    if patch is not None:
        batch = patch(batch)
    frame = pd.DataFrame(batch)
    got, counted = _run(sess, batch, condition, computes, keys, aggs)
    matched = _matched(frame, condition)
    if computes:
        matched = matched.assign(rev=matched.a * (1 - matched.b))
    want = _reference(matched, keys, aggs)
    _same(got, want, keys, aggs)
    assert counted == len(want)
    if case == "a-hundred-thousand-groups":
        assert len(want) > 95000
    if case == "a-predicate-that-selects-none":
        assert len(want) == 0 and got["total"].dtype == np.float64 and got["k"].dtype == np.int64
    if case == "a-predicate-that-selects-four-percent":
        assert 0.02 < len(matched) / len(frame) < 0.06
        assert len(frame) > 8 * D._KEYED_BLOCK_ROWS, "several blocks, most of them skipped"
    if case == "date-key-with-nulls":
        assert np.isnat(got["day"]).sum() == 1
    if case == "one-group":
        assert len(want) == 1


def test_the_capacity_ladder_is_climbed_and_remembered(sess):
    """Two calls over one resident scan: the second selects more groups than the
    first one's bucket holds, is run again at the next bucket, and a third call
    starts there (one run, no first-seen program)."""
    batch = _batch(40000, 3000)
    key = (("ladder", int(sess.mesh.devices.size)),)
    compiles = REGISTRY.counter("hs_xla_compiles_total", "")
    few, _ = _run(sess, batch, hst.col("d") < 1, REVENUE, ["k"], REVENUE_AGGS, scan_key=key)
    hint = (key, ("k",), (("sum", "rev"), ("count", None)))
    small = D._CAP_HINT_MEMO[hint]
    assert small == len(few["k"]) and D.group_capacity(small, 64) < D.group_capacity(3000, 64)
    many, _ = _run(sess, batch, hst.col("d") < 90, REVENUE, ["k"], REVENUE_AGGS, scan_key=key)
    assert len(many["k"]) == 3000 and D._CAP_HINT_MEMO[hint] == 3000
    frame = pd.DataFrame(batch)
    frame = frame[frame.d < 90].assign(rev=lambda f: f.a * (1 - f.b))
    _same(many, _reference(frame, ["k"], REVENUE_AGGS), ["k"], REVENUE_AGGS)
    before = compiles.value
    dispatches = REGISTRY.counter("hs_device_dispatches_total", "", program="grouped-agg-keyed")
    ran = dispatches.value
    again, _ = _run(sess, batch, hst.col("d") < 90, REVENUE, ["k"], REVENUE_AGGS, scan_key=key)
    assert compiles.value == before and dispatches.value == ran + 1
    assert all(np.array_equal(again[c], many[c]) for c in many)
    D.clear_device_cache()


def _keyed_programs() -> set:
    return {k for k in D._PREDICATE_CACHE if k.startswith("gkeyed[")}


def _shape(program_key: str):
    """(padded rows, blocks going on, group table rows) a program was built for."""
    return tuple(int(v) for v in program_key[len("gkeyed["):program_key.index("]")].split(","))


def test_a_call_over_every_block_leaves_a_narrow_calls_shape_as_it_was(sess):
    """The blocks that go on follow from what a call's own predicate selects:
    the probe counts them once for a predicate's literals over a scan's files,
    and a query over the whole scan in between changes nothing for a narrow
    one (same program, no compile, no second probe)."""
    batch = _batch(D.bucket_rows(60000), 300)  # no padded block without a row: the fine ladder would leave it out
    aggs = [("total", "sum", "rev"), ("best", "max", "rev")]  # a shape no other test compiles
    key = (("blocks", int(sess.mesh.devices.size)),)
    narrow = (hst.col("d") >= 40) & (hst.col("d") < 44)
    probes = REGISTRY.counter("hs_device_dispatches_total", "", program="grouped-agg-keyed-probe")
    runs = REGISTRY.counter("hs_device_dispatches_total", "", program="grouped-agg-keyed")
    compiles = REGISTRY.counter("hs_xla_compiles_total", "")
    try:
        asked, others = probes.value, _keyed_programs()
        first, _ = _run(sess, batch, narrow, REVENUE, ["k"], aggs, scan_key=key)
        assert probes.value == asked + 1
        narrow_programs = _keyed_programs() - others
        _run(sess, batch, hst.col("d") >= 0, REVENUE, ["k"], aggs, scan_key=key)  # every block holds a row
        assert probes.value == asked + 2, "other literals: asked once"
        whole_programs = _keyed_programs() - narrow_programs - others
        assert narrow_programs and all(b < t // D._KEYED_BLOCK_ROWS for t, b, _ in map(_shape, narrow_programs))
        assert whole_programs and all(b == t // D._KEYED_BLOCK_ROWS for t, b, _ in map(_shape, whole_programs))
        before = (compiles.value, runs.value, probes.value)
        again, _ = _run(sess, batch, narrow, REVENUE, ["k"], aggs, scan_key=key)
        assert (compiles.value, runs.value, probes.value) == (before[0], before[1] + 1, before[2])
        assert _keyed_programs() == narrow_programs | whole_programs | others
        assert all(np.array_equal(again[c], first[c]) for c in first)
    finally:
        D.clear_device_cache()


def test_more_than_two_inputs_are_refused_before_anything_is_uploaded(sess):
    batch = _batch(20000, 300)
    uploads = REGISTRY.counter("hs_h2d_bytes_total", "", site="agg-cols")
    before = uploads.value
    with pytest.raises(D.DeviceUnsupported, match="3 aggregate inputs"):
        _run(sess, batch, hst.col("d") <= 70, REVENUE, ["k"], [("s", "sum", "rev"), ("sx", "sum", "x"), ("si", "sum", "i")])
    assert uploads.value == before


def test_more_groups_than_max_groups_is_group_capacity_exceeded(sess):
    batch = _batch(20000, 300)
    with pytest.raises(D.GroupCapacityExceeded, match="exceeds maxGroups 100"):
        _run(sess, batch, hst.col("d") <= 70, [], ["k"], SLOT_AGGS[:3], max_groups=100)


def test_keys_past_32_bits_and_float_keys_are_not_this_programs(sess):
    batch = _batch(20000, 300)
    wide = dict(batch, k=np.where(np.arange(20000) % 2 == 0, batch["k"], batch["k"] + 2**40))
    with pytest.raises(D.DeviceUnsupported, match="more than 32 bits"):
        _run(sess, wide, hst.col("d") <= 70, [], ["k"], SLOT_AGGS[:3])
    with pytest.raises(D.DeviceUnsupported, match="more than 32 bits"):
        _run(sess, dict(batch, k2=batch["big"]), hst.col("d") <= 70, [], ["k", "k2"], SLOT_AGGS[:3])
    with pytest.raises(D.DeviceUnsupported, match="float group key"):
        _run(sess, batch, hst.col("d") <= 70, [], ["a"], SLOT_AGGS[:3])


@pytest.mark.parametrize("keys", [["k"], ["day"]], ids=["int-key", "date-key"])
def test_planes_and_whole_columns_give_the_same_answer(sess, keys, monkeypatch):
    """An f32 pair holds 48 bits of a float64: float sums to 1e-13, the rest exactly."""
    batch = _batch(30000, 300)
    whole, _ = _run(sess, batch, hst.col("d") <= 70, REVENUE, keys, REVENUE_AGGS + [("mx", "max", "i")])
    monkeypatch.setattr(D, "computes_in_pairs", lambda mesh: True)
    counted = REGISTRY.counter("hs_device_program_columns_total", "", form="planes")
    before = counted.value
    planes, said = _traced_run(sess, batch, hst.col("d") <= 70, REVENUE, keys, REVENUE_AGGS + [("mx", "max", "i")])
    assert counted.value > before, "the program was handed planes"
    assert said["skip"] == ("copy" if sess.mesh.devices.size == 1 else "layout"), "some blocks were skipped"
    assert list(planes) == list(whole)
    for c in whole:
        if np.asarray(whole[c]).dtype.kind == "f":
            np.testing.assert_allclose(planes[c], whole[c], rtol=1e-13, atol=0, err_msg=c)
        else:
            assert np.array_equal(planes[c], whole[c]), c


# ---------------------------------------------------------------------------
# the skip: which blocks go on, and how they are taken
# ---------------------------------------------------------------------------

BLOCK = D._KEYED_BLOCK_ROWS
SKIP_ROWS = D.bucket_rows(10 * BLOCK)  # fills its padded length: ten whole blocks and more, and a short last one


def _row_numbers(b):
    """``d`` is the row's number: a range on it selects exactly those rows."""
    return dict(b, d=np.arange(len(b["d"]), dtype=np.int64))


def _between(lo, hi):
    return (hst.col("d") >= lo) & (hst.col("d") < hi)


# no stddev: a group of a handful of rows cancels to 1e-12 of its sum of squares, which is the tolerance
SKIP_AGGS = [a for a in SLOT_AGGS if a[1] != "stddev_samp"]
SKIP_CASES = {
    # name: (condition, keys, aggs, patch, blocks that hold a selected row; None: every one)
    "a-run-that-starts-and-ends-inside-a-block": (_between(BLOCK + 100, 3 * BLOCK + 900), ["k"], SKIP_AGGS, None, 3),
    "a-run-that-ends-in-the-short-last-block": (hst.col("d") >= SKIP_ROWS // BLOCK * BLOCK - 500, ["k"], SKIP_AGGS, None, 1),
    "two-runs-in-adjacent-blocks": (_between(2 * BLOCK - 100, 2 * BLOCK) | _between(2 * BLOCK + 10, 2 * BLOCK + 500),
                                    ["k"], SKIP_AGGS, None, 2),
    "a-single-block": (_between(3 * BLOCK + 5, 3 * BLOCK + 50), ["k"], SKIP_AGGS, None, 1),
    "the-first-and-the-last-whole-block": (_between(0, 10) | _between(SKIP_ROWS // BLOCK * BLOCK - 10, SKIP_ROWS // BLOCK * BLOCK),
                                           ["k"], SKIP_AGGS, None, 2),
    "every-block": (hst.col("d") >= 0, ["k"], SKIP_AGGS, None, None),
    "null-dates-and-nan-inputs-in-the-blocks-taken": (_between(2 * BLOCK - 700, 5 * BLOCK + 3), ["day"], SKIP_AGGS[:6], _nat_days, 5),
}


@pytest.mark.parametrize("case", list(SKIP_CASES))
def test_the_skip_takes_the_blocks_that_hold_a_selected_row(sess, case):
    condition, keys, aggs, patch, n_hit = SKIP_CASES[case]
    batch = _row_numbers(_batch(SKIP_ROWS, 300))
    if patch is not None:
        batch = patch(batch)
    assert SKIP_ROWS % BLOCK and D.bucket_rows(SKIP_ROWS) == SKIP_ROWS, "a short last block, all of it rows"
    selected = REGISTRY.counter("hs_keyed_rows_total", "", kind="selected")
    sorted_ = REGISTRY.counter("hs_keyed_rows_total", "", kind="sorted")
    runs = REGISTRY.counter("hs_device_dispatches_total", "", program="grouped-agg-keyed")
    before = (selected.value, sorted_.value, runs.value)
    got, said = _traced_run(sess, batch, condition, [], keys, aggs)
    matched = _matched(pd.DataFrame(batch), condition)
    _same(got, _reference(matched, keys, aggs), keys, aggs)
    launches = runs.value - before[2]
    one_device = sess.mesh.devices.size == 1
    if n_hit is None:
        padded = SKIP_ROWS + (-SKIP_ROWS) % sess.mesh.devices.size
        assert said["skip"] == "whole" and said["rows_on"] == padded and said["blocks"] == padded // BLOCK
    else:
        assert said["skip"] == ("copy" if one_device else "layout")
        assert said["blocks"] == n_hit, "under sixteen blocks the ladder is exact"
        assert said["rows_on"] == (n_hit + 1) * BLOCK, "and the short last block"
    assert said["selected_rows"] == len(matched)
    assert selected.value - before[0] == launches * len(matched)
    assert sorted_.value - before[1] == launches * said["rows_on"]


def test_the_blocks_ladder_wastes_an_eighth_at_most_and_leaves_the_shared_ladders_alone():
    counts = np.arange(1, 20001)
    caps = np.asarray([D._keyed_block_capacity(int(n)) for n in counts])
    assert (caps >= counts).all() and (caps <= counts * 9 / 8 + 1).all()
    assert (caps[:16] == counts[:16]).all() and D._keyed_block_capacity(705) == 768 and D._keyed_block_capacity(0) == 1
    assert (np.diff(caps) >= 0).all()
    # two counts within 3 % of each other share an executable more often than not
    near = [(int(n), int(m)) for n in range(100, 20001, 7) for m in (int(n * 1.03),)]
    shared = sum(D._keyed_block_capacity(n) == D._keyed_block_capacity(m) for n, m in near)
    assert shared > len(near) / 2, (shared, len(near))
    # the ladders every other shape shares are what they were
    assert [D.group_capacity(n, 4) for n in (1, 4, 5, 705, 100000)] == [4, 4, 6, 925, 118642]
    assert [D.group_capacity(n, 64) for n in (1, 100, 3000, 100000)] == [64, 129, 4170, 133494]
    assert [D.bucket_rows(n) for n in (1, 4096, 4097, 20000, 60_000_000)] == [4096, 4096, 5793, 23175, 67126100]


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs it calls, but a kernel's
    own body (its operands are references, not values)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.fixture
def one_device(tmp_path):
    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "indexes")})
    s.set_mesh(make_mesh(1))
    hst.set_session(s)
    yield s
    hst.set_session(None)


def test_the_copy_form_lays_no_whole_column_out(one_device, monkeypatch):
    """The whole-column copies cannot come back unnoticed: in the narrow form
    on one device, outside the filter (the mask and the blocks it hits), no
    ``slice``, ``reshape``, ``gather`` or ``dynamic_slice`` takes an operand
    of the scan's padded length and gives more than a block of it (the short
    last block's rows are sliced out: less than a block)."""
    import jax

    sess = one_device
    batch = _row_numbers(_batch(SKIP_ROWS, 300))
    traced = {}
    inner = D._cached_predicate_jit

    def keep(key, program, family):
        jitted = inner(key, program, family)
        if family == "grouped-agg-keyed":
            traced["program"] = program
        return jitted

    monkeypatch.setattr(D, "_cached_predicate_jit", keep)
    monkeypatch.setattr(D, "computes_in_pairs", lambda mesh: True)  # planes, as the chip holds them
    cols = D.ScanColumns(sess, None, sorted(batch), lambda: batch)
    dev_cols, _ = cols.on_device()
    _, said = _traced_run(sess, batch, _between(BLOCK + 100, 3 * BLOCK + 900), REVENUE, ["k"], REVENUE_AGGS)
    assert said["skip"] == "copy"
    taken = {c: dev_cols[c] for c in ("a", "b", "d", "k")}
    assert all(isinstance(v, D.ColumnPlanes) for v in taken.values())
    lits = [np.int64(0)] * 8
    closed = jax.make_jaxpr(traced["program"])(taken, lits, np.int64(SKIP_ROWS))
    kinds, whole = set(), []
    for eqn in _equations(closed.jaxpr):
        kinds.add(eqn.primitive.name)
        if eqn.primitive.name not in ("slice", "reshape", "gather", "dynamic_slice"):
            continue
        if "filter" in str(eqn.source_info.name_stack):
            continue
        if any(getattr(v.aval, "shape", ()) == (SKIP_ROWS,) for v in eqn.invars) and \
                max(int(np.prod(v.aval.shape)) for v in eqn.outvars) > BLOCK:
            whole.append(str(eqn)[:200])
    assert "pallas_call" in kinds and "sort" in kinds
    assert not whole, whole
