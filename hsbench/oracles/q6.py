import numpy as np

from hsbench.oracles import day, plus_months

COLUMNS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}


def answer(t, p):
    li = t["lineitem"]
    ship, disc = li.l_shipdate.to_numpy(), li.l_discount.to_numpy()
    m = ((ship >= day(p["date"])) & (ship < plus_months(p["date"], 12))
         & (disc >= float(p["disc_lo"])) & (disc <= float(p["disc_hi"]))
         & (li.l_quantity.to_numpy() < p["quantity"]))
    return {"revenue": np.array([(li.l_extendedprice.to_numpy()[m] * disc[m]).sum()])}
