import numpy as np
import pandas as pd

from hsbench.oracles import columns, day, plus_months

COLUMNS = {"lineitem": ["l_shipdate", "l_suppkey", "l_extendedprice", "l_discount"],
           "supplier": ["s_suppkey", "s_name", "s_address", "s_phone"]}


def answer(t, p):
    """TPC-H Q15: ``revenue0`` (revenue by supplier over a quarter) computed
    once, the suppliers whose revenue equals its greatest, by supplier key."""
    li, s = t["lineitem"], t["supplier"]
    ship = li.l_shipdate.to_numpy()
    m = (ship >= day(p["date"])) & (ship < plus_months(p["date"], 3))
    revenue = li.l_extendedprice.to_numpy()[m] * (1 - li.l_discount.to_numpy()[m])
    revenue0 = pd.DataFrame({"supplier_no": li.l_suppkey.to_numpy()[m], "total_revenue": revenue}).groupby(
        "supplier_no", as_index=False).total_revenue.sum()
    top = revenue0[revenue0.total_revenue == revenue0.total_revenue.max()]
    out = s.merge(top, left_on="s_suppkey", right_on="supplier_no").sort_values("s_suppkey")
    return columns(out, ["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"])
