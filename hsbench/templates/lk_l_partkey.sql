SELECT l_extendedprice, l_quantity, l_shipdate, l_shipmode
FROM lineitem WHERE l_partkey = {key}
