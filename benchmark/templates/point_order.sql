select o_orderkey, o_custkey, o_totalprice, o_orderdate from orders where o_orderkey = ?
