"""TPC-H Q1 and Q6 in plain pandas over frames: the reference the resident
aggregate tier is held to in ``test_report_reference.py``. Nothing here
imports ``hyperspace_tpu.exec``."""

import numpy as np
import pandas as pd

Q1 = """select l_returnflag, l_linestatus,
  sum(l_quantity) as sum_qty,
  sum(l_extendedprice) as sum_base_price,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
  avg(l_quantity) as avg_qty,
  avg(l_extendedprice) as avg_price,
  avg(l_discount) as avg_disc,
  count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '{delta}' day (3)
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""

Q6 = """select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '{date}'
  and l_shipdate < date '{date}' + interval '1' year
  and l_discount between {disc_lo} and {disc_hi}
  and l_quantity < {quantity}"""

PARAMS = {"q1": {"delta": 90}, "q6": {"date": "1994-01-01", "disc_lo": "0.05", "disc_hi": "0.07", "quantity": 24}}
SQL = {"q1": Q1, "q6": Q6}
ORDERED = {"q1": True, "q6": False}


def lineitem(rows: int, seed: int) -> pd.DataFrame:
    """A lineitem-shaped frame with the benchmark generator's domains."""
    rng = np.random.default_rng(seed)
    ship = np.datetime64("1992-01-01") + rng.integers(366, 2526, rows).astype("timedelta64[D]")
    return pd.DataFrame({
        "l_shipdate": ship,
        "l_quantity": rng.integers(1, 51, rows).astype(np.int64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, rows), 2),
        "l_discount": np.round(rng.integers(0, 11, rows) / 100.0, 2),
        "l_tax": np.round(rng.integers(0, 9, rows) / 100.0, 2),
        "l_returnflag": np.array(["A", "N", "R"], dtype=object)[rng.integers(0, 3, rows)],
        "l_linestatus": np.array(["F", "O"], dtype=object)[rng.integers(0, 2, rows)],
    })


def q1(li: pd.DataFrame, delta: int, dtype=np.float64) -> pd.DataFrame:
    """``dtype`` float32 folds the aggregates in the nearest precision below
    the engine's: the control that has to fail the comparison."""
    m = li[li.l_shipdate <= np.datetime64("1998-12-01") - np.timedelta64(int(delta), "D")]
    price, disc, tax = (m[c].to_numpy().astype(dtype) for c in ("l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (1 - disc)
    f = pd.DataFrame({
        "l_returnflag": m.l_returnflag.to_numpy(), "l_linestatus": m.l_linestatus.to_numpy(),
        "qty": m.l_quantity.to_numpy(), "price": price, "disc": disc,
        "disc_price": disc_price, "charge": disc_price * (1 + tax),
    })
    g = f.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("qty", "sum"), sum_base_price=("price", "sum"), sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"), avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), count_order=("qty", "size"))
    return g.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)


def q6(li: pd.DataFrame, date: str, disc_lo, disc_hi, quantity, dtype=np.float64) -> pd.DataFrame:
    lo = np.datetime64(date, "D")
    hi = (np.datetime64(date, "M") + 12).astype("datetime64[D]")
    m = li[(li.l_shipdate >= lo) & (li.l_shipdate < hi) & (li.l_discount >= float(disc_lo))
           & (li.l_discount <= float(disc_hi)) & (li.l_quantity < quantity)]
    revenue = (m.l_extendedprice.to_numpy().astype(dtype) * m.l_discount.to_numpy().astype(dtype)).sum()
    return pd.DataFrame({"revenue": [revenue]})


def answer(name: str, li: pd.DataFrame, dtype=np.float64) -> pd.DataFrame:
    return {"q1": q1, "q6": q6}[name](li, dtype=dtype, **PARAMS[name])


def compare(got: dict, want: pd.DataFrame, ordered: bool, rtol: float = 1e-9) -> float:
    """Asserts ``got`` (the engine's batch) equals ``want``: columns, rows,
    keys and counts exactly (row order too where ``ordered``), floats within
    ``rtol``; returns the widest relative gap of a float."""
    assert sorted(got) == sorted(want.columns)
    frame = pd.DataFrame({c: np.asarray(got[c]) for c in want.columns})
    assert len(frame) == len(want)
    exact = [c for c in want.columns if want[c].dtype.kind != "f"]
    if not ordered and exact:
        frame = frame.sort_values(exact).reset_index(drop=True)
        want = want.sort_values(exact).reset_index(drop=True)
    gap = 0.0
    for c in want.columns:
        g, w = frame[c].to_numpy(), want[c].to_numpy()
        if w.dtype.kind == "f":
            rel = np.abs(g.astype(np.float64) - w.astype(np.float64)) / np.maximum(np.abs(w.astype(np.float64)), 1e-300)
            gap = max(gap, float(rel.max()) if len(rel) else 0.0)
        else:
            assert g.dtype.kind == w.dtype.kind or {g.dtype.kind, w.dtype.kind} <= {"O", "U"}, c
            np.testing.assert_array_equal(g, w, err_msg=c)
    assert gap <= rtol, f"float gap {gap} above {rtol}"
    return gap
