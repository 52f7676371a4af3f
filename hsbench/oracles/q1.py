import numpy as np
import pandas as pd

from hsbench.oracles import columns, day

COLUMNS = {"lineitem": ["l_shipdate", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                        "l_returnflag", "l_linestatus"]}


def answer(t, p):
    li = t["lineitem"]
    for c in ("l_returnflag", "l_linestatus"):
        if li[c].dtype == object:  # once a frame: later answers group by codes, not by Python strings
            li[c] = li[c].astype("category")
    m = li.l_shipdate.to_numpy() <= day("1998-12-01") - np.timedelta64(int(p["delta"]), "D")
    price, disc = li.l_extendedprice.to_numpy()[m], li.l_discount.to_numpy()[m]
    disc_price = price * (1 - disc)
    f = pd.DataFrame({
        "l_returnflag": li.l_returnflag.array[m], "l_linestatus": li.l_linestatus.array[m],
        "qty": li.l_quantity.to_numpy()[m], "price": price, "disc": disc,
        "disc_price": disc_price, "charge": disc_price * (1 + li.l_tax.to_numpy()[m]),
    })
    g = f.groupby(["l_returnflag", "l_linestatus"], as_index=False, observed=True).agg(
        sum_qty=("qty", "sum"), sum_base_price=("price", "sum"), sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"), avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), count_order=("qty", "size"))
    g = g.astype({"l_returnflag": object, "l_linestatus": object})
    return columns(g.sort_values(["l_returnflag", "l_linestatus"]), list(g.columns))
