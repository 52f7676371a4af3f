SELECT l_extendedprice, l_discount, l_quantity, l_shipdate
FROM lineitem WHERE l_orderkey = {key}
