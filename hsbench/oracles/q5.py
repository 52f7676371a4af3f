from hsbench.oracles import columns, day, plus_months

COLUMNS = {
    "region": ["r_regionkey", "r_name"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "customer": ["c_custkey", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
}


def answer(t, p):
    r, n = t["region"], t["nation"]
    n = n[n.n_regionkey.isin(r.r_regionkey[r.r_name == p["region"]])]
    c = t["customer"]
    c = c[c.c_nationkey.isin(n.n_nationkey)]
    s = t["supplier"]
    s = s[s.s_nationkey.isin(n.n_nationkey)]
    o = t["orders"]
    o = o[(o.o_orderdate >= day(p["date"])) & (o.o_orderdate < plus_months(p["date"], 12))
          & o.o_custkey.isin(c.c_custkey)]
    li = t["lineitem"]
    li = li[li.l_orderkey.isin(o.o_orderkey) & li.l_suppkey.isin(s.s_suppkey)]
    m = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey")
           .merge(s, left_on="l_suppkey", right_on="s_suppkey"))
    m = m[m.c_nationkey == m.s_nationkey].merge(n, left_on="s_nationkey", right_on="n_nationkey")
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    g = m.groupby("n_name", as_index=False).revenue.sum()
    return columns(g.sort_values("revenue", ascending=False, kind="stable"), ["n_name", "revenue"])
