from hsbench.oracles import columns, day, plus_months

COLUMNS = {
    "orders": ["o_orderkey", "o_orderdate", "o_orderpriority"],
    "lineitem": ["l_orderkey", "l_commitdate", "l_receiptdate"],
}


def answer(t, p):
    o, li = t["orders"], t["lineitem"]
    win = o[(o.o_orderdate >= day(p["date"])) & (o.o_orderdate < plus_months(p["date"], 3))]
    late = li.l_orderkey[li.l_commitdate < li.l_receiptdate]
    m = win[win.o_orderkey.isin(late)]
    g = m.groupby("o_orderpriority", as_index=False).size().rename(columns={"size": "order_count"})
    return columns(g.sort_values("o_orderpriority"), ["o_orderpriority", "order_count"])
