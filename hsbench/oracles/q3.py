from hsbench.oracles import columns, day

COLUMNS = {
    "customer": ["c_custkey", "c_mktsegment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice", "l_discount"],
}


def answer(t, p):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    d = day(p["date"])
    custs = c.c_custkey[c.c_mktsegment == p["segment"]]
    o = o[(o.o_orderdate < d) & o.o_custkey.isin(custs)]
    li = li[(li.l_shipdate > d) & li.l_orderkey.isin(o.o_orderkey)]
    m = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    g = m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False).revenue.sum()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True], kind="stable").head(10)
    return columns(g, ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"])
