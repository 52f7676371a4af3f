SELECT o_custkey, o_orderdate, o_totalprice, o_orderstatus
FROM orders WHERE o_orderkey = {key}
