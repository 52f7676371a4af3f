import numpy as np

from hsbench.oracles import columns, day, plus_months

COLUMNS = {
    "orders": ["o_orderkey", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_shipmode", "l_commitdate", "l_receiptdate", "l_shipdate"],
}


def answer(t, p):
    o, li = t["orders"], t["lineitem"]
    li = li[li.l_shipmode.isin([p["mode1"], p["mode2"]])
            & (li.l_commitdate < li.l_receiptdate) & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= day(p["date"])) & (li.l_receiptdate < plus_months(p["date"], 12))]
    m = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    high = m.o_orderpriority.isin(["1-URGENT", "2-HIGH"]).astype(np.int64)
    g = m.assign(high_line_count=high, low_line_count=1 - high).groupby(
        "l_shipmode", as_index=False)[["high_line_count", "low_line_count"]].sum()
    return columns(g.sort_values("l_shipmode"), ["l_shipmode", "high_line_count", "low_line_count"])
