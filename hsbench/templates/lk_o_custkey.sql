SELECT o_orderkey, o_orderdate, o_totalprice
FROM orders WHERE o_custkey = {key}
