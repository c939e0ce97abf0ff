insert into memory.default.bench_orders
select o_orderpriority, o_totalprice, o_orderkey, o_orderdate
from orders where o_orderdate = date '{DAY}'
