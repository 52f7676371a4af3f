from hsbench.oracles import columns, day, plus_months

COLUMNS = {
    "customer": ["c_custkey", "c_name", "c_acctbal", "c_phone", "c_address", "c_comment", "c_nationkey"],
    "nation": ["n_nationkey", "n_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_returnflag", "l_extendedprice", "l_discount"],
}


def answer(t, p):
    c, n, o, li = t["customer"], t["nation"], t["orders"], t["lineitem"]
    o = o[(o.o_orderdate >= day(p["date"])) & (o.o_orderdate < plus_months(p["date"], 3))]
    li = li[(li.l_returnflag == "R") & li.l_orderkey.isin(o.o_orderkey)]
    m = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    g = m.groupby("o_custkey", as_index=False).revenue.sum()
    g = (g.merge(c, left_on="o_custkey", right_on="c_custkey")
          .merge(n, left_on="c_nationkey", right_on="n_nationkey"))
    g = g.sort_values("revenue", ascending=False, kind="stable").head(20)
    return columns(g, ["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
                       "c_address", "c_phone", "c_comment"])
