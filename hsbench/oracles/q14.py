import numpy as np

from hsbench.oracles import day, plus_months

COLUMNS = {
    "part": ["p_partkey", "p_type"],
    "lineitem": ["l_partkey", "l_shipdate", "l_extendedprice", "l_discount"],
}


def answer(t, p):
    li, part = t["lineitem"], t["part"]
    li = li[(li.l_shipdate >= day(p["date"])) & (li.l_shipdate < plus_months(p["date"], 1))]
    m = li.merge(part, left_on="l_partkey", right_on="p_partkey")
    rev = (m.l_extendedprice * (1 - m.l_discount)).to_numpy()
    promo = rev[m.p_type.astype(str).str.startswith("PROMO").to_numpy()].sum()
    return {"promo_revenue": np.array([100.0 * promo / rev.sum()])}
