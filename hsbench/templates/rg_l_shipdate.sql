SELECT SUM(l_extendedprice * l_discount) AS revenue, COUNT(*) AS n
FROM lineitem
WHERE l_shipdate >= date '{lo}' AND l_shipdate < date '{hi}'
