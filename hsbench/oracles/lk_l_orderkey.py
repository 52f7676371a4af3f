from hsbench.oracles import columns

COLUMNS = {"lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_quantity", "l_shipdate"]}


def answer(t, p):
    li = t["lineitem"]
    return columns(li[li.l_orderkey.to_numpy() == p["key"]],
                   ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate"])
