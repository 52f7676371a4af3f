import numpy as np

from hsbench.oracles import day

COLUMNS = {"lineitem": ["l_shipdate", "l_extendedprice", "l_discount"]}


def answer(t, p):
    li = t["lineitem"]
    ship = li.l_shipdate.to_numpy()
    m = (ship >= day(p["lo"])) & (ship < day(p["hi"]))
    revenue = (li.l_extendedprice.to_numpy()[m] * li.l_discount.to_numpy()[m]).sum()
    return {"revenue": np.array([revenue]), "n": np.array([int(m.sum())])}
