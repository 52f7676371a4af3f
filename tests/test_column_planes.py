"""The resident form of an 8-byte column (``exec/device.py``): whole where
the devices compute 64-bit values natively, two 32-bit planes
(``ColumnPlanes``) where they compute them as pairs.

The tests run on the CPU, whose form is the whole column, so the plane form
is asked for through ``_put_encoded``'s own ``planes`` argument (and, where a
whole tier has to upload by itself, by telling ``computes_in_pairs`` that the
mesh does). An f32 pair holds 48 bits of a float64, so float answers over
planes are compared to 1e-13, and predicates on floats keep their literals
away from the data's values; integers are exact.

- ``split_planes`` / ``join_planes`` round trips: float64 zeros of both signs,
  NaN, the infinities, the edges of float32's range, values that need the
  tail; int64 extremes, negatives, values past 2^53;
- ``fused-filter``, ``fused-agg``, ``grouped-agg-dense`` and
  ``grouped-agg-chunk`` over the same data resident whole and as planes, on
  one device and on a mesh of four (where the filter and the chunk program
  also run in their ``shard_map`` forms): equal answers;
- a column uploaded by one tier is found and used by the other, in one form;
- ``hs_device_program_columns_total{form}`` counts both forms.
"""

import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.parallel.executor import ShardedExecutor
from hyperspace_tpu.parallel.mesh import make_mesh

FLOAT_RTOL = 1e-13
F32_MAX = float(np.finfo(np.float32).max)


@pytest.fixture(autouse=True)
def _x64():
    D.ensure_x64()


def _round_trip(values):
    import jax

    planes = jax.jit(D.split_planes)(values)
    assert isinstance(planes, D.ColumnPlanes)
    assert planes.first.shape == planes.second.shape == values.shape == planes.shape
    assert planes.dtype == values.dtype
    return planes, np.asarray(jax.jit(D.join_planes)(planes))


@pytest.mark.parametrize("value", [
    0.0, -0.0, np.nan, np.inf, -np.inf, F32_MAX, -F32_MAX, float(np.finfo(np.float32).tiny), 1.0, -2.5, 2.0 ** 100,
], ids=repr)
def test_a_float_that_float32_holds_comes_back_bit_for_bit(value):
    x = np.array([value, 1.0, value], dtype=np.float64)
    planes, back = _round_trip(x)
    assert planes.first.dtype == planes.second.dtype == np.float32
    assert np.array_equal(back.view(np.int64), x.view(np.int64)), (back, x)
    assert not np.asarray(planes.second)[[0, 2]].any(), "the head holds all of it: the tail is zero"


@pytest.mark.parametrize("value", [0.1, 1 / 3, 123456789.123456789, -59986052.07, 1e-20, 104949.5 * 0.93], ids=repr)
def test_a_float_that_needs_the_tail_comes_back_to_48_bits(value):
    x = np.full(5, value, dtype=np.float64)
    planes, back = _round_trip(x)
    assert np.asarray(planes.second).all(), "float32 alone does not hold it"
    head_only = np.asarray(planes.first).astype(np.float64)
    assert abs(head_only[0] - value) > abs(back[0] - value)
    np.testing.assert_allclose(back, x, rtol=2.0 ** -47, atol=0)


def test_a_float_beyond_float32_s_range_is_the_pair_s_infinity():
    # the pair's exponent is float32's: what the chip's float64 holds there
    _, back = _round_trip(np.array([1e300, -1e300, 2 * F32_MAX], dtype=np.float64))
    assert np.array_equal(back, [np.inf, -np.inf, np.inf])


@pytest.mark.parametrize("value", [
    np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0, 1, 2**53 + 1, -(2**53 + 1), 2**32, -(2**32), 2**31,
    -(2**31), 2**32 - 1, 9862 * 86_400_000_000_000,
], ids=str)
def test_an_int64_comes_back_exactly(value):
    x = np.array([value, 0, value], dtype=np.int64)
    planes, back = _round_trip(x)
    assert (planes.first.dtype, planes.second.dtype) == (np.uint32, np.int32)
    assert back.dtype == np.int64 and np.array_equal(back, x)


def test_join_leaves_any_other_column_as_it_is():
    codes = np.arange(7, dtype=np.int32)
    assert D.join_planes(codes) is codes
    assert D.join_columns({"c": codes})["c"] is codes


# -- the four programs, whole against planes --------------------------------

ROWS = 6000


@pytest.fixture(scope="module", params=[1, 4], ids=["one-device", "mesh-of-four"])
def sess(request, tmp_path_factory):
    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path_factory.mktemp("planes") / "indexes")})
    s.set_mesh(make_mesh(request.param))
    hst.set_session(s)
    yield s
    hst.set_session(None)
    D.clear_device_cache()


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(40)
    x = np.round(rng.uniform(900.0, 105000.0, ROWS), 2)
    x[rng.random(ROWS) < 0.1] = np.nan
    return {
        "day": (np.datetime64("1992-01-01") + rng.integers(0, 2500, ROWS).astype("timedelta64[D]")).astype("datetime64[ns]"),
        "q": rng.integers(1, 51, ROWS).astype(np.int64),
        "big": rng.integers(2**51, 2**52, ROWS).astype(np.int64),
        "x": x,
        "disc": rng.integers(0, 11, ROWS) / 100.0,
        "flag": np.asarray(["A", "N", "R"], dtype=object)[rng.integers(0, 3, ROWS)],
        "g": rng.integers(-20, 20, ROWS).astype(np.int64),
    }


def _resident(sess, batch, form: str, names):
    """``names`` of ``batch`` made resident in ``form`` under a scan key of
    their own, as a tier's upload would leave them."""
    mesh = sess.mesh
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    scan_key = ((f"/planes-test/{form}/{mesh.devices.size}", ROWS, 40),)
    fp = D._mesh_fp(mesh)
    for c in names:
        dev, codec, nbytes = D._put_encoded(sess, mesh, sharding, mesh.devices.size, batch[c], planes=form == "planes")
        wide = batch[c].dtype.kind in "iufM"
        assert isinstance(dev, D.ColumnPlanes) == (wide and form == "planes"), (c, type(dev))
        if wide:
            assert nbytes == 8 * dev.shape[0], "a column weighs the same in either form"
        D._device_cache_put((scan_key, c, fp), (dev, codec, ROWS), nbytes)
    return scan_key


# a float literal between the data's values (two decimals): an f32 pair's
# 0.05 is not float64's 0.05, as the chip's is not
CONDITION = (
    (hst.col("day") >= hst.lit(np.datetime64("1994-01-01"))) & (hst.col("day") < hst.lit(np.datetime64("1997-01-01")))
    & (hst.col("disc") > hst.lit(0.045)) & (hst.col("disc") < hst.lit(0.075)) & (hst.col("q") < hst.lit(24))
)
COMPUTES = [("rev", hst.col("x") * (hst.lit(1) - hst.col("disc")))]
AGGS = [("n", "count", None), ("n_x", "count", "x"), ("sum_rev", "sum", "rev"), ("avg_x", "avg", "x"),
        ("min_x", "min", "x"), ("max_x", "max", "x"), ("sum_q", "sum", "q"), ("sum_big", "sum", "big"),
        ("min_big", "min", "big"), ("max_q", "max", "q")]
CHUNK_AGGS = [a for a in AGGS if a[2] != "rev"] + [("sd_x", "stddev_samp", "x")]


def _filter(sess, batch, scan_key, parallel):
    return {"mask": D.device_filter_mask(sess, batch, CONDITION, scan_key=scan_key, parallel=parallel)}


def _scan_aggregate(keys):
    def run(sess, batch, scan_key, parallel):
        names = sorted({"day", "disc", "q", "x", "big"} | set(keys))
        cols = D.ScanColumns(sess, scan_key, names, lambda: pytest.fail("every column is resident"))
        assert cols.resident
        return D.device_scan_aggregate(sess, cols, CONDITION, COMPUTES, list(keys), AGGS, max_groups=0)

    return run


def _chunk(sess, batch, scan_key, parallel):
    return D.device_grouped_aggregate(sess, batch, CONDITION, ["g"], CHUNK_AGGS, scan_key=scan_key,
                                      max_groups=1000, cap_floor=16, parallel=parallel)


PROGRAMS = {
    # family: (run, the columns it reads, whether it has a shard_map form)
    "fused-filter": (_filter, ["day", "disc", "q"], True),
    "fused-agg": (_scan_aggregate([]), ["day", "disc", "q", "x", "big"], False),
    "grouped-agg-dense": (_scan_aggregate(["flag"]), ["day", "disc", "q", "x", "big", "flag"], False),
    "grouped-agg-chunk": (_chunk, ["day", "disc", "q", "x", "big", "g"], True),
}


def _forms() -> dict:
    return {form: REGISTRY.counter("hs_device_program_columns_total", "", form=form).value for form in ("planes", "whole")}


@pytest.mark.parametrize("family, sharded", [
    (family, sharded) for family, (_, _, has_sharded_form) in PROGRAMS.items() for sharded in (False, True)
    if has_sharded_form or not sharded
], ids=lambda v: v if isinstance(v, str) else ("shard-map" if v else "jit"))
def test_a_program_answers_the_same_over_planes_as_over_whole_columns(sess, batch, family, sharded):
    run, names, _ = PROGRAMS[family]
    if sharded and sess.mesh.devices.size == 1:
        pytest.skip("a shard_map form wants a mesh of several devices")
    parallel = ShardedExecutor(sess) if sharded else None
    wide = sum(batch[c].dtype.kind in "iufM" for c in names)
    answers = {}
    for form in ("whole", "planes"):
        scan_key = _resident(sess, batch, form, names)
        before = _forms()
        answers[form] = run(sess, batch, scan_key, parallel)
        grew = {f: v - before[f] for f, v in _forms().items()}
        # one count a launch and 64-bit column handed over, under its form (the
        # chunk program runs again when it has to grow its capacity)
        other = "planes" if form == "whole" else "whole"
        assert grew[other] == 0 and grew[form] in ((wide, 2 * wide) if family == "grouped-agg-chunk" else (wide,)), (form, grew)
    whole, planes = answers["whole"], answers["planes"]
    assert list(whole) == list(planes) and len(whole)
    for name in whole:
        w, p = np.asarray(whole[name]), np.asarray(planes[name])
        assert w.dtype == p.dtype and w.shape == p.shape, name
        if w.dtype.kind == "f":
            assert np.array_equal(np.isnan(w), np.isnan(p)), name
            np.testing.assert_allclose(p, w, rtol=FLOAT_RTOL, atol=0, err_msg=name)
        else:
            assert np.array_equal(w, p), (name, w, p)
    if family == "fused-filter":
        assert 0 < whole["mask"].sum() < ROWS


# -- one form under one key, whichever tier uploaded -------------------------


@pytest.mark.parametrize("form", ["whole", "planes"])
@pytest.mark.parametrize("first", ["filter-stages", "aggregate-uploads"])
def test_a_column_one_tier_uploaded_is_used_by_the_other(sess, batch, form, first, monkeypatch):
    monkeypatch.setattr(D, "computes_in_pairs", lambda mesh: form == "planes")
    scan_key = ((f"/planes-test/shared/{form}/{first}/{sess.mesh.devices.size}", ROWS, 40),)
    names = ["day", "disc", "q", "x", "big"]
    reads = []

    def load():
        reads.append(1)
        return batch

    def lookups():
        return {r: REGISTRY.counter("hs_device_cache_lookups_total", "", result=r).value for r in ("hit", "miss")}

    def aggregate():
        cols = D.ScanColumns(sess, scan_key, names, load)
        return cols, D.device_scan_aggregate(sess, cols, CONDITION, COMPUTES, [], AGGS, max_groups=0)

    if first == "filter-stages":
        D.stage_filter_columns(sess, batch, CONDITION, scan_key, extra_columns=["x", "big"])
        before = lookups()
        cols, got = aggregate()
        assert cols.resident and not reads, "the aggregate tier found what the filter tier staged"
    else:
        _, got = aggregate()
        assert reads == [1]
        before = lookups()
        mask = D.device_filter_mask(sess, batch, CONDITION, scan_key=scan_key)
        assert 0 < mask.sum() < ROWS
    grew = {r: v - before[r] for r, v in lookups().items()}
    assert grew["miss"] == 0 and grew["hit"] >= 3, grew
    fp = D._mesh_fp(sess.mesh)
    for c in names:
        dev = D._device_cache_get((scan_key, c, fp))[0]
        assert isinstance(dev, D.ColumnPlanes) == (form == "planes"), c
    assert int(got["n"][0]) > 0


def test_the_counter_has_two_labels_and_skips_dictionary_codes(sess, batch):
    mesh = sess.mesh
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    put = lambda c, planes: D._put_encoded(sess, mesh, sharding, mesh.devices.size, batch[c], planes=planes)[0]
    before = _forms()
    D.count_column_forms([put("x", True), put("q", True), put("day", False), put("flag", True)])
    assert {f: v - before[f] for f, v in _forms().items()} == {"planes": 2, "whole": 1}


@pytest.mark.parametrize("family", ["fused-filter", "fused-agg", "grouped-agg-dense"])
def test_with_the_lint_on_a_program_over_planes_holds_its_contract(batch, family, tmp_path):
    """``hyperspace.check.hlo.enabled``: the split and the program over its
    planes are verified as they compile, and the join is no ``f64-upcast``."""
    from hyperspace_tpu.check import hlo_lint

    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"), "hyperspace.check.hlo.enabled": True})
    s.set_mesh(make_mesh(1))
    hlo_lint.reset_runtime_state()
    verified = lambda f: REGISTRY.counter("hs_check_programs_verified_total", "", program=f).value
    before = {f: verified(f) for f in ("split-planes", family)}
    try:
        run, names, _ = PROGRAMS[family]
        answer = run(s, batch, _resident(s, batch, "planes", names), None)
        assert len(answer)
        assert all(verified(f) > n for f, n in before.items()), "both were compiled and verified"
        assert not hlo_lint.runtime_violations(), [f.render() for f in hlo_lint.runtime_violations()]
    finally:
        hlo_lint.reset_runtime_state()
        D.clear_device_cache()
