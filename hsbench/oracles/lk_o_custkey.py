from hsbench.oracles import columns

COLUMNS = {"orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"]}


def answer(t, p):
    o = t["orders"]
    return columns(o[o.o_custkey.to_numpy() == p["key"]],
                   ["o_orderkey", "o_orderdate", "o_totalprice"])
