"""The dense grouped aggregate (``exec/device.py``, ``grouped-agg-dense``)
against plain pandas.

``device_scan_aggregate`` is handed a host batch (nothing resident, no
index), so every case is one program over that batch's columns: group keys
that are dictionary-coded strings, a filter, computed inputs. The reference
is ``groupby(sort=False, dropna=False)`` over the filtered frame: the same
groups in the same (first appearance) order, counts and integer sums exact,
floats to 1e-12. The cases:

- every state slot kind: ``count(*)``, ``count(col)``, ``sum``, ``avg``,
  ``min``, ``max``, ``stddev_samp``, over a float and over an int column;
- a float input with NULLs in some rows (``cnt`` != ``cntm``) and a group whose
  rows are all NULL (its sum, min, max and avg are NULL, its count 0);
- an int input whose sum passes 2^53 and must stay exact;
- a key with NULLs (the null code takes a slot of its own);
- 1, 6 and 64 groups, and a 65th that must raise ``DeviceUnsupported``;
- a group that no row matches after the filter (absent from the answer);
- ``n_valid`` below the padded length, and equal to it;
- a computed input ``a * (1 - b)``;
- the same on one device and on a mesh of four virtual devices.

The structure of the compiled program (one pass, 32-bit bookkeeping) is held
in ``test_dense_grouped_program.py``.
"""

import numpy as np
import pandas as pd
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.parallel.mesh import make_mesh

FLOAT_RTOL = 1e-12


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device", "mesh-of-four"])
def sess(request, tmp_path_factory):
    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path_factory.mktemp("dense") / "indexes")})
    s.set_mesh(make_mesh(request.param))
    hst.set_session(s)
    yield s
    hst.set_session(None)


def _batch(rows: int, keys: dict, seed: int = 38) -> dict:
    """A host batch as the readers give it (a NULL string is ``None``).
    ``keys``: name -> (labels, share of NULL cells)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(100.0, 30.0, rows)
    x[rng.random(rows) < 0.15] = np.nan
    frame = {
        "d": rng.integers(0, 100, rows).astype(np.int64),
        "x": x,
        "i": rng.integers(-50, 51, rows).astype(np.int64),
        "big": rng.integers(2**51, 2**52, rows).astype(np.int64),
        "a": np.round(rng.uniform(900.0, 105000.0, rows), 2),
        "b": rng.integers(0, 11, rows) / 100.0,
    }
    for name, (labels, nulls) in keys.items():
        cells = np.asarray(labels, dtype=object)[rng.integers(0, len(labels), rows)]
        cells[rng.random(rows) < nulls] = None
        frame[name] = cells
    return frame


SLOT_AGGS = [
    ("n", "count", None), ("n_x", "count", "x"), ("sum_x", "sum", "x"), ("avg_x", "avg", "x"), ("min_x", "min", "x"),
    ("max_x", "max", "x"), ("sd_x", "stddev_samp", "x"), ("n_i", "count", "i"), ("sum_i", "sum", "i"),
    ("avg_i", "avg", "i"), ("min_i", "min", "i"), ("max_i", "max", "i"), ("sd_i", "stddev_samp", "i"),
]
_PANDAS = {"sum": lambda s: s.sum(min_count=1), "avg": "mean", "min": "min", "max": "max", "stddev_samp": "std",
           "count": "count"}


def _reference(frame: pd.DataFrame, keys, aggs) -> pd.DataFrame:
    grouped = frame.groupby(list(keys), sort=False, dropna=False)
    out = grouped.size().rename("__size").reset_index()
    for name, fn, c in aggs:
        if c is None:
            out[name] = out["__size"].to_numpy()
        else:
            out[name] = grouped[c].agg(_PANDAS[fn]).to_numpy()
    return out.drop(columns="__size")


def _run(sess, batch, condition, computes, keys, aggs, program="grouped-agg-dense"):
    cols = D.ScanColumns(sess, None, sorted(batch), lambda: batch)
    counter = REGISTRY.counter("hs_agg_groups_total", "", program=program)
    before = counter.value
    got = D.device_scan_aggregate(sess, cols, condition, computes, list(keys), list(aggs), max_groups=0)
    return got, counter.value - before


def _same(got: dict, want: pd.DataFrame, keys, aggs, int_inputs=("i", "big")) -> None:
    assert list(got) == list(keys) + [name for name, _, _ in aggs]
    for k in keys:
        g, w = pd.Series(got[k], dtype=object), want[k].astype(object)
        assert len(g) == len(w)
        assert all((a == b) or (pd.isna(a) and pd.isna(b)) for a, b in zip(g, w)), (k, list(g), list(w))
    for name, fn, c in aggs:
        g, w = np.asarray(got[name]), want[name].to_numpy()
        if fn == "count" or (c in int_inputs and fn in ("sum", "min", "max")):
            assert g.dtype == np.int64, (name, g.dtype)
            assert np.array_equal(g, w.astype(np.int64)), (name, g, w)
        else:
            w = w.astype(np.float64)
            assert np.array_equal(np.isnan(g), np.isnan(w)), (name, g, w)
            np.testing.assert_allclose(g, w, rtol=FLOAT_RTOL, atol=0, err_msg=name)


def _labels(n: int):
    return [f"g{i:02d}" for i in range(n)]


CASES = {
    # name: (rows, keys, keep d <= this or None, computes, aggs, patch(batch) or None)
    "every-slot-kind": (5000, {"k1": (["A", "N", "R"], 0.0), "k2": (["F", "O"], 0.0)}, 70, [],
                        SLOT_AGGS, None),
    "an-all-null-group": (4000, {"k1": (["A", "N", "R"], 0.0)}, 70, [], SLOT_AGGS,
                          lambda b: dict(b, x=np.where(b["k1"] == "N", np.nan, b["x"]))),
    "int-sum-past-2-53": (6000, {"k1": (["A", "N"], 0.0)}, 94, [],
                          [("s", "sum", "big"), ("mn", "min", "big"), ("mx", "max", "big"), ("n", "count", "big")], None),
    "a-key-with-nulls": (5000, {"k1": (["A", "N", "R"], 0.2), "k2": (["F", "O"], 0.1)}, 70, [],
                         SLOT_AGGS[:7], None),
    "one-group": (3000, {"k1": (["only"], 0.0)}, 50, [], SLOT_AGGS[:7], None),
    "six-groups": (5000, {"k1": (_labels(3), 0.0), "k2": (["F", "O"], 0.0)}, None, [], SLOT_AGGS[:7], None),
    "sixty-four-groups": (9000, {"k1": (_labels(8), 0.0), "k2": (_labels(8), 0.0)}, 80, [],
                          SLOT_AGGS[:7], None),
    "a-group-the-filter-empties": (4000, {"k1": (["A", "N", "R"], 0.0)}, 70, [], SLOT_AGGS[:7],
                                   lambda b: dict(b, d=np.where(b["k1"] == "R", 99, b["d"]))),
    "rows-fill-the-padded-length": (D.bucket_rows(3000), {"k1": (["A", "N", "R"], 0.0)}, 70, [],
                                    SLOT_AGGS[:7], None),
    "a-computed-input": (5000, {"k1": (["A", "N", "R"], 0.0), "k2": (["F", "O"], 0.0)}, 70,
                         [("disc", hst.col("a") * (hst.lit(1) - hst.col("b")))],
                         [("s", "sum", "disc"), ("m", "avg", "disc"), ("lo", "min", "disc"), ("n", "count", "disc"),
                          ("rows", "count", None)], None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_one_pass_program_answers_as_pandas_does(sess, case):
    rows, keys, at_most, computes, aggs, patch = CASES[case]
    condition = None if at_most is None else hst.col("d") <= at_most
    batch = _batch(rows, keys)
    if patch is not None:
        batch = patch(batch)
    frame = pd.DataFrame(batch)
    if case == "rows-fill-the-padded-length":
        assert D.bucket_rows(len(frame)) == len(frame)
    got, counted = _run(sess, batch, condition, computes, keys, aggs)
    matched = frame if at_most is None else frame[frame.d <= at_most]
    if computes:
        matched = matched.assign(disc=matched.a * (1 - matched.b))
    want = _reference(matched, keys, aggs)
    _same(got, want, keys, aggs)
    assert counted == len(want)
    if case == "an-all-null-group":
        at = list(got["k1"]).index("N")
        assert got["n_x"][at] == 0 and got["n"][at] > 0
        assert all(np.isnan(got[c][at]) for c in ("sum_x", "avg_x", "min_x", "max_x", "sd_x"))
    if case == "int-sum-past-2-53":
        assert (np.abs(got["s"]) > 2**53).all()
    if case == "a-group-the-filter-empties":
        assert "R" not in list(got["k1"]) and "R" in set(frame.k1)
    if case == "sixty-four-groups":
        assert len(want) == 64


def test_a_sixty_fifth_group_is_not_the_dense_programs(sess):
    """Past 64 groups the dictionary codes are keys like any integer: the
    keyed program (``grouped-agg-keyed``) answers, the dense one counts none."""
    batch = _batch(9000, {"k1": (_labels(13), 0.0), "k2": (_labels(5), 0.0)})
    with pytest.raises(D.DeviceUnsupported, match="65 dictionary groups"):
        D._dense_key_plan(["k1", "k2"], {k: D.encode_column(batch[k])[1] for k in ("k1", "k2")}, 0)
    dense = REGISTRY.counter("hs_agg_groups_total", "", program="grouped-agg-dense")
    before = dense.value
    got, counted = _run(sess, batch, hst.col("d") <= 80, [], ["k1", "k2"], SLOT_AGGS[:3], program="grouped-agg-keyed")
    frame = pd.DataFrame(batch)
    want = _reference(frame[frame.d <= 80], ["k1", "k2"], SLOT_AGGS[:3])
    _same(got, want, ["k1", "k2"], SLOT_AGGS[:3])
    assert counted == len(want) == 65 and dense.value == before
