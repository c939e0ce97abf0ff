select o_orderpriority, count(*) as n, sum(o_totalprice) as total
from memory.default.bench_orders
group by o_orderpriority
order by o_orderpriority
