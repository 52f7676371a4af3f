"""The comparison that decides ``correct``, and its controls at a size a test
run can hold. The chip runs of the same controls at the cells' own sizes are
in PERF.md."""

import numpy as np
import pytest

from hsbench import check, control, run


def test_answers_compare_exactly_but_for_floats():
    want = {"k": np.array([1, 2, 3]), "s": np.array(["a", "b", "c"], dtype=object),
            "d": np.array(["1994-01-01", "1994-01-02", "1994-01-03"], dtype="datetime64[s]"),
            "f": np.array([1.0, 2.0, 3.0])}
    got = {"k": np.array([3, 1, 2]), "s": np.array(["c", "a", "b"]),
           "d": np.array(["1994-01-03", "1994-01-01", "1994-01-02"], dtype="datetime64[D]"),
           "f": np.array([3.0, 1.0, 2.0 * (1 + 1e-12)])}
    wrong, gap = check.compare_answer(got, want, ordered=False)
    assert wrong == 0 and gap == pytest.approx(1e-12, rel=1e-3)
    assert check.compare_answer(got, want, ordered=True)[0] > 0       # the order of ORDER BY counts
    assert check.compare_answer({**got, "k": np.array([3, 1, 9])}, want, False)[0] == 1
    assert check.compare_answer({c: v[:2] for c, v in got.items()}, want, False)[0] == 1  # a row lost
    assert check.compare_answer({"k": got["k"]}, want, False)[0] == 1  # a column lost
    assert check.compare_answer({}, {}, False) == (0, 0.0)


def test_host_bucket_is_the_programs_host_hash():
    from hyperspace_tpu.ops import encode, hashing

    rng = np.random.default_rng(3)
    ints = rng.integers(-2**40, 2**40, 5000)
    days = np.datetime64("1992-01-01") + rng.integers(0, 3000, 5000).astype("timedelta64[D]")
    for col in (ints, days):
        want = hashing.bucket_ids_np([encode.hash_input_uint32(col)], 200)
        assert (check.host_bucket(col, 200) == want).all()
    with pytest.raises(TypeError):
        check.host_bucket(np.array([1.5]), 200)


def _args(cell, seed, seconds, **kw):
    a = run.parse(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                   "--rehearse-on-cpu", "--rehearse-sf", "0.01"])
    for k, v in kw.items():
        setattr(a, k, v)
    return a


@pytest.mark.parametrize("cell", ["sf1-lookup", "sf1-analytic"])
def test_float32_aggregates_come_out_not_correct(cell):
    result, _ = run.execute(_args(cell, 2400000900, 3, control=True))
    assert result["correct"] is True and result["failed"] == 0
    c = result["control"]["float32_aggregates"]
    assert c["correct"] is False and c["numbers"]["answer.float_rel_gap"] > 1e-9


def test_a_broken_index_comes_out_not_correct():
    result, _ = run.execute(_args("sf10-build", 2400000901, 3, control=True))
    assert result["correct"] is True
    c = result["control"]
    assert set(c) == {"row_dropped", "payloads_swapped", "row_in_wrong_bucket"}
    assert not any(v["correct"] for v in c.values())
    assert "index.rows_off" in c["row_dropped"]["failed"]
    assert c["payloads_swapped"]["failed"] == ["index.checksum_differs"]
    assert "index.rows_in_wrong_bucket" in c["row_in_wrong_bucket"]["failed"]


def test_the_control_command_exits_zero_only_when_controls_fail(capsys):
    assert control.main(["--workload", "sf1-lookup", "--seed", "5", "--seconds", "2",
                         "--rehearse-on-cpu"]) == 0
    assert "every control comes out not correct" in capsys.readouterr().out
