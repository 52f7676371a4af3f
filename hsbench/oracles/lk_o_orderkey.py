from hsbench.oracles import columns

COLUMNS = {"orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice", "o_orderstatus"]}


def answer(t, p):
    o = t["orders"]
    return columns(o[o.o_orderkey.to_numpy() == p["key"]],
                   ["o_custkey", "o_orderdate", "o_totalprice", "o_orderstatus"])
