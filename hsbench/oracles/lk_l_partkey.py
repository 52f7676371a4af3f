from hsbench.oracles import columns

COLUMNS = {"lineitem": ["l_partkey", "l_extendedprice", "l_quantity", "l_shipdate", "l_shipmode"]}


def answer(t, p):
    li = t["lineitem"]
    return columns(li[li.l_partkey.to_numpy() == p["key"]],
                   ["l_extendedprice", "l_quantity", "l_shipdate", "l_shipmode"])
