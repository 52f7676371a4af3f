"""The resident join-aggregate program (``exec/join_agg.py``) against the
plain reference (``tests/reference_join.py``) over random columns.

The program is driven as the executor drives it: two ``ScanColumns`` under
made-up scan identities (a version number stands for an index version), the
build side's table made by the tier itself. Held here:

- a unique build key with a dense range (``direct``) and a sparse one
  (``sorted``), on one device and on the session's mesh of eight virtual ones
  (the partitioner's layout), grouped and global;
- both compactions: the packed mask and two short sorts (``words``), and the
  scan's positions sorted (``whole``);
- probe keys without a match, NULL keys on both sides, NULL string codes as a
  group and inside ``CASE``, an empty selection, a filter on the build side, a
  compare of two date columns, inputs and group keys from either side;
- a build key that repeats is refused before any other column of the side is
  uploaded; keys no form takes are refused;
- the table is made once an index version and again for another;
- the probe side's row words: a layout of one word and of two, a column that
  stays planes beside a packed key, NULL keys and codes, keys outside the
  table's range, the widths' edges, both compactions, columns resident as
  planes; what ``hs_join_row_fields_total`` and the tier's span say of each.
"""

import numpy as np
import pandas as pd
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import join_agg as JA
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.parallel.mesh import make_mesh
from hyperspace_tpu.plan.expr import Case, col, lit

import reference_join as R

MODES = ["AIR", "FOB", "MAIL", "RAIL", "SHIP"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
DAY0 = np.datetime64("1994-01-01", "ns")


def _frames(seed: int, probe_rows: int = 40000, build_rows: int = 6000, key_step: int = 1, orphans: float = 0.0):
    """``lineitem``-like probe rows and ``orders``-like build rows: the build
    key is unique, ``key_step`` apart (1: a dense range; 1000: a sparse one),
    its rows in another order than its keys; ``orphans`` of the probe keys
    have no build row."""
    rng = np.random.default_rng(seed)
    keys = np.arange(build_rows, dtype=np.int64) * key_step + 17
    build = pd.DataFrame({
        "o_key": rng.permutation(keys),
        "o_priority": rng.choice(np.array(PRIORITIES + [None], dtype=object), build_rows, p=[0.19] * 5 + [0.05]),
        "o_total": np.round(rng.uniform(10.0, 5000.0, build_rows), 2),
        "o_flag": rng.integers(0, 3, build_rows).astype(np.int64),
    })
    pk = rng.choice(keys, probe_rows)
    if orphans:
        lost = rng.random(probe_rows) < orphans
        pk[lost] = pk[lost] + (key_step // 2 if key_step > 1 else build_rows * 2)
    ship = DAY0 + rng.integers(0, 400, probe_rows).astype("timedelta64[D]")
    commit = ship + rng.integers(-20, 40, probe_rows).astype("timedelta64[D]")
    probe = pd.DataFrame({
        "l_key": pk.astype(np.int64),
        "l_mode": rng.choice(np.array(MODES + [None], dtype=object), probe_rows, p=[0.19] * 5 + [0.05]),
        "l_ship": ship,
        "l_commit": commit,
        "l_qty": rng.integers(1, 51, probe_rows).astype(np.int64),
        "l_price": np.where(rng.random(probe_rows) < 0.03, np.nan, np.round(rng.uniform(1.0, 900.0, probe_rows), 2)),
    })
    return probe, build


def _batch(frame: pd.DataFrame) -> dict:
    """The frame as a scan hands it over: strings as objects, NULL as None."""
    out = {}
    for c in frame.columns:
        values = frame[c].to_numpy()
        if values.dtype.kind in "OUT" or str(frame[c].dtype) in ("str", "string"):
            values = np.asarray([None if v is None or v != v else v for v in frame[c].tolist()], dtype=object)
        out[c] = values
    return out


@pytest.fixture()
def sess(tmp_path):
    s = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "indexes")})
    hst.set_session(s)
    D.clear_device_cache()
    yield s
    hst.set_session(None)
    D.clear_device_cache()


def _ask(sess, probe, build, *, pcond=None, bcond=None, computes=(), keys=(), aggs=(), version=1, on=("l_key", "o_key")):
    """The tier's answer and what it found, from frames under scan identities
    of ``version``."""
    reads = JA.aggregate_reads(keys, aggs, computes)
    pside = JA.JoinSide(None, pcond, on[0], frozenset(probe.columns))
    bside = JA.JoinSide(None, bcond, on[1], frozenset(build.columns))
    cols_p = D.ScanColumns(sess, (("mem://probe", version, len(probe)),), JA.side_columns(pside, reads), lambda: _batch(probe))
    cols_b = D.ScanColumns(sess, (("mem://build", version, len(build)),), JA.side_columns(bside, reads), lambda: _batch(build))
    return JA.device_join_aggregate(sess, pside, bside, cols_p, cols_b, list(computes), list(keys), list(aggs))


def _same(got: dict, want: dict, keys=()) -> None:
    """``got`` (groups in any order) equals ``want`` (sorted by keys, NULL last)."""
    assert list(got) == list(want)
    if keys:
        norm = lambda v: None if v is None or v != v else v
        rows = sorted(range(len(got[keys[0]])), key=lambda i: tuple((norm(got[k][i]) is None, norm(got[k][i]) or "") for k in keys))
        got = {c: np.asarray(v)[rows] for c, v in got.items()}
        for k in keys:
            assert [norm(v) for v in got[k]] == [norm(v) for v in want[k]], k
    for c in want:
        if c in keys:
            continue
        g, w = np.asarray(got[c]), np.asarray(want[c])
        assert len(g) == len(w), c
        if w.dtype.kind in "iub":
            assert g.dtype.kind in "iu" and list(g) == list(w), c  # counts and integer sums: exact
        else:
            np.testing.assert_allclose(g.astype(np.float64), w.astype(np.float64), rtol=1e-12, atol=0, equal_nan=True, err_msg=c)


HIGH = (col("o_priority") == lit("1-URGENT")) | (col("o_priority") == lit("2-HIGH"))
LOW = (col("o_priority") != lit("1-URGENT")) & (col("o_priority") != lit("2-HIGH"))
Q12_COMPUTES = [("hi", Case([(HIGH, lit(1))], lit(0))), ("lo", Case([(LOW, lit(1))], lit(0)))]
Q12_AGGS = [("high", "sum", "hi"), ("low", "sum", "lo")]
Q12_FILTER = (col("l_mode").isin("MAIL", "SHIP")) & (col("l_ship") < col("l_commit")) & (col("l_qty") < lit(20))


def _q12_reference(probe, build, keys=("l_mode",)):
    return R.join_aggregate(
        probe, build, ("l_key", "o_key"),
        left_filter=lambda f: f.l_mode.isin(["MAIL", "SHIP"]).to_numpy() & (f.l_ship < f.l_commit).to_numpy() & (f.l_qty < 20).to_numpy(),
        computes={
            "hi": lambda j: R.case(len(j), [(R.eq(j.o_priority, "1-URGENT") | R.eq(j.o_priority, "2-HIGH"), 1)], 0),
            "lo": lambda j: R.case(len(j), [(R.ne(j.o_priority, "1-URGENT") & R.ne(j.o_priority, "2-HIGH"), 1)], 0),
        },
        keys=keys, aggs=Q12_AGGS)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("key_step, table", [(1, "direct"), (1000, "sorted")])
@pytest.mark.parametrize("grouped", [True, False])
def test_q12s_shape_over_random_columns(sess, devices, key_step, table, grouped):
    """A filter with ``IN`` and a date-column compare on the probe side, two
    ``CASE`` counts over the build side's dictionary codes (``=``/``OR``,
    ``!=``/``AND``; a NULL priority counts in neither), grouped by the probe
    side's mode or global: both forms of the table, both meshes."""
    sess.set_mesh(make_mesh(devices))
    probe, build = _frames(seed=11 + key_step, key_step=key_step, orphans=0.1)
    keys = ["l_mode"] if grouped else []
    got, found = _ask(sess, probe, build, pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=keys, aggs=Q12_AGGS)
    _same(got, _q12_reference(probe, build, keys), keys)
    assert found["table"] == table and found["probe_rows"] == len(probe) and found["build_rows"] == len(build)
    assert 0 < found["matched"] < found["selected"] < len(probe), "a tenth of the probe keys have no build row"
    assert found["groups"] == (2 if grouped else 1)


@pytest.mark.parametrize("rows, compaction", [(40000, "words"), (3000, "whole")])
def test_both_compactions_select_the_same_rows(sess, rows, compaction):
    sess.set_mesh(make_mesh(1))
    probe, build = _frames(seed=5, probe_rows=rows, build_rows=800)
    cond = (col("l_qty") == lit(7)) & (col("l_mode") == lit("AIR"))
    aggs = [("n", "count", None), ("q", "sum", "l_qty"), ("t", "sum", "o_total")]
    got, found = _ask(sess, probe, build, pcond=cond, aggs=aggs)
    want = R.join_aggregate(probe, build, ("l_key", "o_key"), left_filter=lambda f: (f.l_qty == 7).to_numpy() & R.eq(f.l_mode, "AIR"), aggs=aggs)
    _same(got, want)
    assert found["compaction"] == compaction and found["selected"] == found["matched"] == int(want["n"][0])
    assert found["capacity"] >= found["selected"]


def test_a_dense_selection_sorts_the_scan_itself(sess):
    """No filter at all: every probe row goes on, and the answer is the
    reference's (slower, the same)."""
    sess.set_mesh(make_mesh(1))
    probe, build = _frames(seed=6, probe_rows=20000, build_rows=500)
    aggs = [("n", "count", None), ("p", "avg", "l_price"), ("lo", "min", "o_total"), ("hi", "max", "l_qty")]
    got, found = _ask(sess, probe, build, keys=["o_priority"], aggs=aggs)
    _same(got, R.join_aggregate(probe, build, ("l_key", "o_key"), keys=["o_priority"], aggs=aggs), ["o_priority"])
    assert found["compaction"] == "whole" and found["selected"] == len(probe)
    assert found["groups"] == 6, "a NULL priority is a group of its own"


def test_keys_and_inputs_from_either_side_and_a_filter_on_the_build_side(sess):
    """Grouped by one column of each side; sums of a probe column, a build
    column and a ``CASE`` that reads both sides; the build side filtered."""
    probe, build = _frames(seed=7)
    computes = [("mixed", Case([((col("l_mode") == lit("RAIL")) & (col("o_flag") == lit(1)), col("l_qty") * lit(2))], col("o_flag")))]
    aggs = [("q", "sum", "l_qty"), ("t", "sum", "o_total"), ("m", "sum", "mixed"), ("n", "count", None), ("np", "count", "l_price")]
    got, found = _ask(sess, probe, build, pcond=col("l_qty") > lit(40), bcond=col("o_total") < lit(2500.0),
                      computes=computes, keys=["o_priority", "l_mode"], aggs=aggs)
    want = R.join_aggregate(
        probe, build, ("l_key", "o_key"), left_filter=lambda f: (f.l_qty > 40).to_numpy(), right_filter=lambda f: (f.o_total < 2500.0).to_numpy(),
        computes={"mixed": lambda j: R.case(len(j), [(R.eq(j.l_mode, "RAIL") & (j.o_flag == 1).to_numpy(), j.l_qty.to_numpy() * 2)], j.o_flag.to_numpy())},
        keys=["o_priority", "l_mode"], aggs=aggs)
    _same(got, want, ["o_priority", "l_mode"])
    assert found["groups"] == 36 and found["matched"] == found["selected"], "the build filter is applied behind the match"


def test_a_case_without_else_is_null_and_the_sums_skip_it(sess):
    probe, build = _frames(seed=8, probe_rows=8000, build_rows=300)
    computes = [("only_mail", Case([(col("l_mode") == lit("MAIL"), col("l_qty"))], None))]
    aggs = [("s", "sum", "only_mail"), ("c", "count", "only_mail"), ("n", "count", None)]
    got, _ = _ask(sess, probe, build, computes=computes, keys=["o_priority"], aggs=aggs)
    want = R.join_aggregate(probe, build, ("l_key", "o_key"),
                            computes={"only_mail": lambda j: R.case(len(j), [(R.eq(j.l_mode, "MAIL"), j.l_qty.to_numpy())])},
                            keys=["o_priority"], aggs=aggs)
    _same(got, want, ["o_priority"])
    assert np.asarray(got["s"]).dtype.kind == "f", "NULL is a NaN: the sum is a float's"


def test_an_empty_selection(sess):
    probe, build = _frames(seed=9, probe_rows=5000, build_rows=300)
    grouped, found = _ask(sess, probe, build, pcond=col("l_qty") > lit(1000), computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS)
    assert list(grouped) == ["l_mode", "high", "low"] and all(len(v) == 0 for v in grouped.values())
    assert found["selected"] == found["matched"] == found["groups"] == 0
    glob, _ = _ask(sess, probe, build, pcond=col("l_qty") > lit(1000), aggs=[("n", "count", None), ("q", "sum", "l_qty")])
    assert list(glob["n"]) == [0] and np.isnan(glob["q"][0]), "SUM over no row is NULL"


def test_date_keys_and_a_null_key_matches_nothing(sess):
    """Both keys are dates with NaT among them: a NULL key is no key, on
    either side (the reference drops them; pandas would pair NaT with NaT)."""
    rng = np.random.default_rng(10)
    days = DAY0 + np.arange(500).astype("timedelta64[D]")
    bkeys = rng.permutation(days).astype("datetime64[s]")  # 500 days are 43 M seconds: a sparse range
    build = pd.DataFrame({"o_day": np.concatenate([bkeys, [np.datetime64("NaT")] * 3]),
                          "o_priority": rng.choice(np.array(PRIORITIES, dtype=object), 503)})
    pk = rng.choice(days, 9000).astype("datetime64[s]")
    pk[rng.random(9000) < 0.05] = np.datetime64("NaT")
    probe = pd.DataFrame({"l_day": pk, "l_qty": rng.integers(1, 51, 9000).astype(np.int64)})
    aggs = [("n", "count", None), ("q", "sum", "l_qty")]
    got, found = _ask(sess, probe, build, keys=["o_priority"], aggs=aggs, on=("l_day", "o_day"))
    _same(got, R.join_aggregate(probe, build, ("l_day", "o_day"), keys=["o_priority"], aggs=aggs), ["o_priority"])
    assert found["selected"] == int((~pd.isna(probe.l_day)).sum()) == found["matched"] and found["table"] == "sorted"


def _uploads() -> float:
    entry = REGISTRY.snapshot().get("hs_h2d_bytes_total", {"series": []})
    return sum(float(s.get("value", 0.0)) for s in entry["series"])


@pytest.mark.parametrize("key_step", [1, 1000])
def test_a_build_key_that_repeats_is_refused_before_another_column_is_uploaded(sess, key_step):
    probe, build = _frames(seed=12, probe_rows=6000, build_rows=400, key_step=key_step)
    build.loc[7, "o_key"] = build.loc[300, "o_key"]  # one key twice: the schema would not say
    before = _uploads()
    with pytest.raises(D.DeviceUnsupported, match="repeats"):
        _ask(sess, probe, build, pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS)
    key_bytes = D.bucket_rows(len(build)) * 8
    assert 0 < _uploads() - before <= key_bytes + 8 * 8, "the build key went up (padded to the mesh), and nothing else of either side"
    resident = {k[1] for k in D._device_cache.keys()}
    assert resident == {"o_key"}, resident


@pytest.mark.parametrize("case", ["string-key", "float-key", "keys-of-two-kinds", "numeric-group-key", "span-over-32-bits", "datetime-input"])
def test_what_no_form_takes_is_refused(sess, case):
    probe, build = _frames(seed=13, probe_rows=3000, build_rows=200)
    kwargs = dict(aggs=[("n", "count", None)])
    if case == "string-key":
        kwargs["on"] = ("l_mode", "o_priority")
    elif case == "float-key":
        build["o_key"] = build.o_key.astype(np.float64)
        probe["l_key"] = probe.l_key.astype(np.float64)
    elif case == "keys-of-two-kinds":
        kwargs["on"] = ("l_ship", "o_key")
    elif case == "numeric-group-key":
        kwargs["keys"] = ["l_qty"]
    elif case == "span-over-32-bits":
        build.loc[0, "o_key"] = 2**40
    else:
        kwargs["aggs"] = [("d", "max", "l_ship")]
    with pytest.raises(D.DeviceUnsupported):
        _ask(sess, probe, build, **kwargs)


def _tables(result: str) -> float:
    return REGISTRY.counter("hs_join_build_table_total", "", result=result).value


def _probe_rows(kind: str) -> float:
    return REGISTRY.counter("hs_join_probe_rows_total", "", kind=kind).value


def test_the_table_is_made_once_an_index_version_and_again_for_another(sess):
    probe, build = _frames(seed=14, probe_rows=6000, build_rows=400)
    ask = lambda frame, version: _ask(sess, probe, frame, pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS, version=version)
    hit, built, selected, matched = _tables("hit"), _tables("built"), _probe_rows("selected"), _probe_rows("matched")
    first, found = ask(build, 1)
    assert (_tables("built") - built, _tables("hit") - hit) == (1, 0)
    again, _ = ask(build, 1)
    assert (_tables("built") - built, _tables("hit") - hit) == (1, 1), "the second ask finds the table resident"
    assert _probe_rows("selected") - selected == 2 * found["selected"] and _probe_rows("matched") - matched == 2 * found["matched"]
    for c in first:
        assert list(first[c]) == list(again[c])
    # another version of the build side: other files, another identity, another table
    other = build.assign(o_priority=build.o_priority.map(lambda p: "1-URGENT" if p == "5-LOW" else p))
    refreshed, _ = ask(other, 2)
    assert (_tables("built") - built, _tables("hit") - hit) == (2, 1)
    _same(refreshed, _q12_reference(probe, other), ["l_mode"])
    assert list(refreshed["high"]) != list(first["high"])
    tables = [k for k in D._device_cache.keys() if k[1] == ("join-table", "o_key")]
    assert len(tables) == 2 and D._device_cache.total_bytes >= sum(D._device_cache_get(k).nbytes for k in tables), "the budget counts them"


# -- the probe side's row words -------------------------------------------------


def _row_fields(form: str) -> float:
    return REGISTRY.counter("hs_join_row_fields_total", "", form=form).value


def _modes(probe: pd.DataFrame, values, seed: int) -> pd.DataFrame:
    return probe.assign(l_mode=np.random.default_rng(seed).choice(np.array(values, dtype=object), len(probe)))


COUNTS = [("n", "count", None), ("q", "sum", "l_qty")]
# case -> (frames' arguments, what is asked, (row_words, fields_word, fields_planes), table, compaction)
ROW_WORD_CASES = {
    # the key's offset into a direct table and a dictionary code: 13 + 3 bits
    "one-word": (dict(seed=21, orphans=0.1), dict(pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS), (1, 2, 0), "direct", "whole"),
    # a sorted table's 32-bit code fills a word: the mode's code goes into a second
    "two-words": (dict(seed=22, key_step=1000, orphans=0.1), dict(pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS), (2, 2, 0), "sorted", "whole"),
    # a float64 and an int64 the codec does not bound: gathered from their own columns, the key packed alone
    "unbounded-columns-stay-planes": (dict(seed=23), dict(pcond=col("l_qty") == lit(7), keys=["o_priority"], aggs=COUNTS + [("p", "sum", "l_price")]), (1, 1, 2), "direct", "words"),
    "null-code": (dict(seed=24), dict(pcond=col("l_qty") == lit(7), keys=["l_mode"], aggs=COUNTS), (1, 2, 1), "direct", "words"),
    "keys-outside-the-range": (dict(seed=25), dict(pcond=col("l_qty") == lit(7), keys=["l_mode"], aggs=COUNTS), (1, 2, 1), "direct", "words"),
    "dictionary-of-one": (dict(seed=26), dict(pcond=col("l_qty") == lit(7), keys=["l_mode", "o_priority"], aggs=COUNTS), (1, 2, 1), "direct", "words"),
    "dictionary-of-eight": (dict(seed=27), dict(pcond=col("l_qty") == lit(7), keys=["l_mode"], aggs=COUNTS), (1, 2, 1), "direct", "words"),
    "whole": (dict(seed=28, probe_rows=3000, build_rows=300), dict(pcond=col("l_qty") == lit(7), keys=["l_mode"], aggs=COUNTS), (1, 2, 1), "direct", "whole"),
    "two-words-sparse": (dict(seed=29, key_step=1000, orphans=0.2), dict(pcond=col("l_qty") == lit(7), keys=["l_mode"], aggs=COUNTS), (2, 2, 1), "sorted", "words"),
    "columns-as-planes": (dict(seed=30, orphans=0.1), dict(pcond=Q12_FILTER, computes=Q12_COMPUTES, keys=["l_mode"], aggs=Q12_AGGS), (1, 2, 0), "direct", "whole"),
}


@pytest.mark.parametrize("case", list(ROW_WORD_CASES))
def test_a_selected_probe_row_is_fetched_as_row_words(sess, monkeypatch, case):
    """Every value a selected probe row hands on rides in a 32-bit row word
    where the code knows its bounds, and the answer is the reference's."""
    sess.set_mesh(make_mesh(1))
    frames, asked, (row_words, fields_word, fields_planes), table, compaction = ROW_WORD_CASES[case]
    probe, build = _frames(**frames)
    if case == "keys-outside-the-range":  # under ``lo`` (17), past the table's slots, past 32 bits of offset, negative
        probe.loc[probe.index[::7], "l_key"] = np.resize(np.array([3, 16, 17 + 10**6, 2**40, -5, 2**62], dtype=np.int64), len(probe.index[::7]))
    elif case == "dictionary-of-one":  # one bit: NULL or the value
        probe = _modes(probe, ["AIR", None], 1)
    elif case == "dictionary-of-eight":  # 2^3 values and NULL: code + 1 needs a fourth bit
        probe = _modes(probe, [f"M{i}" for i in range(8)] + [None], 2)
    elif case == "columns-as-planes":  # the chip's resident form: every 8-byte column as two 32-bit planes
        monkeypatch.setattr(D, "computes_in_pairs", lambda mesh: True)
    before = {form: _row_fields(form) for form in ("word", "planes")}
    got, found = _ask(sess, probe, build, **asked)
    if case == "columns-as-planes":
        assert all(isinstance(D._device_cache_get(k)[0], D.ColumnPlanes) for k in D._device_cache.keys() if k[1] in ("l_key", "l_qty", "l_ship", "o_key"))
    keys = asked["keys"]
    if "computes" in asked:
        want = _q12_reference(probe, build, keys)
    else:
        cond = asked.get("pcond")
        want = R.join_aggregate(probe, build, ("l_key", "o_key"), left_filter=(lambda f: (f.l_qty == 7).to_numpy()) if cond is not None else None, keys=keys, aggs=asked["aggs"])
    _same(got, want, keys)
    assert (found["row_words"], found["fields_word"], found["fields_planes"]) == (row_words, fields_word, fields_planes)
    assert {form: _row_fields(form) - before[form] for form in before} == {"word": fields_word, "planes": fields_planes}
    assert (found["table"], found["compaction"]) == (table, compaction)
    if case in ("one-word", "two-words", "keys-outside-the-range", "two-words-sparse", "columns-as-planes"):
        assert 0 < found["matched"] < found["selected"], "a probe key without a build row adds nothing"
    else:
        assert found["matched"] == found["selected"] > 0
    if case == "null-code":
        assert found["groups"] == 6 and sum(v is None or v != v for v in got["l_mode"]) == 1, "the NULL mode is a group of its own"


def test_a_null_key_in_a_row_word_matches_nothing(sess):
    """Timestamp keys a second apart (a direct table) with NaT on both sides:
    the row word of a NULL key holds no offset."""
    rng = np.random.default_rng(31)
    days = DAY0.astype("datetime64[s]") + np.arange(500).astype("timedelta64[s]")
    build = pd.DataFrame({"o_day": np.concatenate([rng.permutation(days), [np.datetime64("NaT")] * 3]),
                          "o_priority": rng.choice(np.array(PRIORITIES, dtype=object), 503)})
    pk = rng.choice(days, 9000)
    pk[rng.random(9000) < 0.05] = np.datetime64("NaT")
    probe = pd.DataFrame({"l_day": pk, "l_mode": rng.choice(np.array(MODES + [None], dtype=object), 9000)})
    aggs = [("n", "count", None)]
    got, found = _ask(sess, probe, build, keys=["l_mode", "o_priority"], aggs=aggs, on=("l_day", "o_day"))
    _same(got, R.join_aggregate(probe, build, ("l_day", "o_day"), keys=["l_mode", "o_priority"], aggs=aggs), ["l_mode", "o_priority"])
    assert found["table"] == "direct" and found["selected"] == int((~pd.isna(probe.l_day)).sum()) == found["matched"]
    assert (found["row_words"], found["fields_word"], found["fields_planes"]) == (1, 2, 0)


@pytest.mark.parametrize("table, size, uniques, want", [
    ("direct", 16_781_524, [7], "@0.0+25,a@0.25+3"),  # tpch-sf10-join: one word
    ("direct", 16_781_524, [1, 8, 63], "@0.0+25,a@0.25+1,b@0.26+4,c@1.0+6"),  # 2^k values need k + 1 bits; over 32: the next word
    ("direct", 2**24, [127, 3], "@0.0+25,a@0.25+7,b@1.0+2"),  # a table of 2^24 slots: ``size`` itself is a code
    ("direct", 2**24 - 1, [127, 3, 1], "@0.0+24,a@0.24+7,b@1.0+2,c@0.31+1"),  # a later field takes the room an earlier word has left
    ("sorted", 600, [5], "@0.0+32,a@1.0+3"),
    ("sorted", 600, [], "@0.0+32"),
])
def test_the_row_words_layout_follows_what_is_observed(table, size, uniques, want):
    names = "abc"[: len(uniques)]
    codecs = {c: D.ColumnCodec("string", uniques=np.array([f"v{i}" for i in range(n)], dtype=object)) for c, n in zip(names, uniques)}
    codecs["x"] = D.ColumnCodec("numeric", dtype=np.dtype("float64"))
    layout = JA._row_layout(table, size, sorted([*names, "x"]), codecs)
    assert layout.skeleton() == want and layout.planes == ("x",)
    assert layout.words == 1 + max(f.word for f in [layout.key, *(f for _, f in layout.columns)])
