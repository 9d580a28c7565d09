"""The flagship queries as DATA: pure IR, no hand-written lowering.

``q6_plan``/``q95_plan`` are the IR spellings of the hand-fused
``_q6_step``/``_q95_step`` pipelines in ``__graft_entry__.py`` — the
compiler's lowering rules reproduce those paths exactly, and
tests/test_plan.py gates the outputs bit-identical on plain AND
encoded inputs under both engine knob settings.  ``q9_plan`` is the
proof that new queries are now data, not code: a q9-shaped pipeline
(multi-join + conditional aggregate) that exists ONLY as IR — there is
no hand-fused ``_q9_step`` anywhere.  ``tpch_q1_plan`` is TPC-H Q1 as
Spark SQL types it: expressions in a Project, two low-cardinality keys,
decimal sums and averages.  ``tpch_q3_plan`` is TPC-H Q3 in the physical
shape Spark gives it: two filtered joins, one feeding the other's build
side, a three-key group-by and an ordered limit.  ``tpch_q18_plan`` is
TPC-H Q18: a group-by of one group an order under a ``HAVING``, the ``IN``
subquery as a semi-join, two more joins, a second group-by and a top-100.
``tpch_q13_plan`` is TPC-H Q13: a ``NOT LIKE`` over the order comments, an
outer join whose unmatched customers count zero, and a group-by over a
group-by.
"""

from __future__ import annotations

from .ir import (Agg, Aggregate, Col, DateLit, Desc, Exchange, Filter, Join,
                 Lit, Project, Scan, Sort, TopK)

# the q9 conditional: high-value orders only (the WHEN net > threshold
# arm of q9's conditional aggregate, expressed as filter -> row_valid)
Q9_V_THRESHOLD = 250


def q6_plan() -> Aggregate:
    """q6: filter (price < 50) -> group by k: sum(v), count(*),
    avg(price).  One plan serves the int-keyed, string-keyed AND
    dictionary-encoded batches: the domain/onehot hints only engage for
    a plain int key, exactly like the hand paths (``_q6_step`` vs
    ``_q6str_step``)."""
    return Aggregate(
        Filter(Scan("batch"), "price", "<", 50.0),
        keys=("k",),
        aggs=(Agg("sum", "v", "sum_v"),
              Agg("count", None, "cnt"),
              Agg("mean", "price", "avg_price")),
        domain=100, onehot=True)


def q95_plan() -> Aggregate:
    """q95: exchange -> join dim1 -> exchange -> join dim2 -> exchange
    -> group by seg.  The trailing Exchange+Aggregate pair is what the
    compiler fuses (sort engine: secondary operands; scatter/auto or
    encoded: elision) — the IR says WHAT Spark's plan says
    (exchange-before-HashAggregate), the compiler decides the fused
    physical form."""
    from __graft_entry__ import Q95_SEG

    j1 = Join(Exchange(Scan("fact"), "k"), Scan("dim1"), "k", "k",
              dense_domain="build")
    j2 = Join(Exchange(j1, "wh"), Scan("dim2"), "wh", "wh",
              dense_domain="build")
    return Aggregate(
        Exchange(j2, "seg"),
        keys=("seg",),
        aggs=(Agg("count", None, "orders"), Agg("sum", "v", "net")),
        domain=Q95_SEG)


def q9_plan() -> Aggregate:
    """q9 shape, IR-only: fact joins both dims (adaptive strategy — the
    dims are small, so the plan-time decision goes broadcast under the
    default ``broadcast_threshold_rows``), then a conditional aggregate
    (only orders with v >= threshold count) grouped by segment."""
    from __graft_entry__ import Q95_SEG

    j1 = Join(Scan("fact"), Scan("dim1"), "k", "k",
              dense_domain="build", strategy="auto")
    j2 = Join(j1, Scan("dim2"), "wh", "wh",
              dense_domain="build", strategy="auto")
    return Aggregate(
        Filter(j2, "v", ">=", Q9_V_THRESHOLD),
        keys=("seg",),
        aggs=(Agg("sum", "v", "net_hi"),
              Agg("count", None, "orders_hi"),
              Agg("mean", "v", "avg_hi")),
        domain=Q95_SEG)


# TPC-H Q1's keys as a dictionary-encoded scan hands them over:
# l_returnflag A/N/R -> 0/1/2, l_linestatus F/O -> 0/1
TPCH_Q1_DOMAINS = (3, 2)


def tpch_q1_plan(delta_days: int = 90) -> Sort:
    """TPC-H Q1, the pricing summary report (specification clause 2.4.1,
    validation parameter DELTA = 90)::

        select l_returnflag, l_linestatus, sum(l_quantity),
               sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
               sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
               avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
        from lineitem
        where l_shipdate <= date '1998-12-01' - interval '90' day
        group by l_returnflag, l_linestatus
        order by l_returnflag, l_linestatus

    over ``decimal(12,2)`` measures, a ``DATE`` and two int32 dictionary
    codes.  Spark types ``disc_price`` ``decimal(26,4)`` and ``charge``
    ``decimal(38,6)`` (raw ``(40,6)``, adjusted with the scale kept); the
    sums come out ``decimal(22,2)``, ``(22,2)``, ``(36,4)``, ``(38,6)``,
    the averages ``decimal(16,6)``."""
    disc_price = Col("l_extendedprice") * (1 - Col("l_discount"))
    lineitem = Project(
        Filter(Scan("lineitem"), "l_shipdate", "<=",
               DateLit("1998-12-01", -int(delta_days))),
        ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount",
         ("disc_price", disc_price),
         ("charge", disc_price * (1 + Col("l_tax")))))
    keys = ("l_returnflag", "l_linestatus")
    return Sort(Aggregate(
        lineitem, keys=keys,
        aggs=(Agg("sum", "l_quantity", "sum_qty"),
              Agg("sum", "l_extendedprice", "sum_base_price"),
              Agg("sum", "disc_price", "sum_disc_price"),
              Agg("sum", "charge", "sum_charge"),
              Agg("mean", "l_quantity", "avg_qty"),
              Agg("mean", "l_extendedprice", "avg_price"),
              Agg("mean", "l_discount", "avg_disc"),
              Agg("count", None, "count_order")),
        domain=TPCH_Q1_DOMAINS, onehot=True), keys)


# TPC-H key ranges at scale factor 1: C_CUSTKEY 1..150,000 dense, and
# O_ORDERKEY sparse (dbgen keeps 8 of every 32 values: 1,500,000 keys
# up to 6,000,000)
TPCH_SF1_CUSTKEY_DOMAIN = 150_001
TPCH_SF1_ORDERKEY_DOMAIN = 6_000_001


def tpch_q3_plan(segment_code: int = 1, date_iso: str = "1995-03-15",
                 custkey_domain: int = TPCH_SF1_CUSTKEY_DOMAIN,
                 orderkey_domain: int = TPCH_SF1_ORDERKEY_DOMAIN,
                 limit: int = 10) -> TopK:
    """TPC-H Q3, the shipping priority query (specification clause 2.4.3,
    validation parameters SEGMENT = BUILDING, DATE = 1995-03-15)::

        select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
               o_orderdate, o_shippriority
        from customer, orders, lineitem
        where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
          and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
          and l_shipdate > date '1995-03-15'
        group by l_orderkey, o_orderdate, o_shippriority
        order by revenue desc, o_orderdate limit 10

    in the physical shape Spark plans it at scale factor 1 under its
    defaults.  CUSTOMER filtered on its segment (``segment_code``: the
    dictionary code of ``c_mktsegment``, alphabetical, BUILDING = 1) and
    pruned to its key is small enough to broadcast: it joins the filtered
    ORDERS with no exchange on either side.  That join's rows, pruned to
    the three columns still read, are the build side, behind an exchange
    on ``o_orderkey``, of the join with LINEITEM, itself filtered, pruned
    and exchanged on ``l_orderkey``.  The rows are then partitioned by the
    first group key, so the aggregate follows with no further exchange,
    and ``TakeOrderedAndProject`` takes the ten.  The domains are hints
    the program checks: customer keys are dense, order keys sparse (a
    quarter of the range is used).  Spark types ``revenue_term``
    ``decimal(26,4)`` and its sum ``decimal(36,4)``."""
    date = DateLit(date_iso)
    customer = Project(
        Filter(Scan("customer"), "c_mktsegment", "==", int(segment_code)),
        ("c_custkey",))
    orders = Project(
        Join(Filter(Scan("orders"), "o_orderdate", "<", date), customer,
             "o_custkey", "c_custkey", dense_domain=int(custkey_domain)),
        ("o_orderkey", "o_orderdate", "o_shippriority"))
    lineitem = Project(
        Filter(Scan("lineitem"), "l_shipdate", ">", date),
        ("l_orderkey", "l_extendedprice", "l_discount"))
    joined = Join(Exchange(lineitem, "l_orderkey"),
                  Exchange(orders, "o_orderkey"),
                  "l_orderkey", "o_orderkey",
                  dense_domain=int(orderkey_domain))
    terms = Project(joined, (
        "l_orderkey", "o_orderdate", "o_shippriority",
        ("revenue_term", Col("l_extendedprice") * (1 - Col("l_discount")))))
    revenue = Aggregate(
        terms, keys=("l_orderkey", "o_orderdate", "o_shippriority"),
        aggs=(Agg("sum", "revenue_term", "revenue"),))
    return TopK(
        Project(revenue, ("l_orderkey", "revenue", "o_orderdate",
                          "o_shippriority")),
        (Desc("revenue"), "o_orderdate"), int(limit))


def tpch_q18_plan(quantity: int = 300,
                  custkey_domain: int = TPCH_SF1_CUSTKEY_DOMAIN,
                  orderkey_domain: int = TPCH_SF1_ORDERKEY_DOMAIN,
                  limit: int = 100) -> TopK:
    """TPC-H Q18, the large volume customer query (specification clause
    2.4.18, validation parameter QUANTITY = 300)::

        select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
               sum(l_quantity)
        from customer, orders, lineitem
        where o_orderkey in (select l_orderkey from lineitem
                             group by l_orderkey
                             having sum(l_quantity) > 300)
          and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        order by o_totalprice desc, o_orderdate limit 100

    in the physical shape Spark plans it at scale factor 1.  The subquery
    is an aggregate of one group an order (1,500,000 at SF1) behind an
    exchange on its key, the ``HAVING`` a Filter above it (the sum is
    ``decimal(22,2)``; Spark casts the literal to it), and the ``IN`` a
    left semi join of ORDERS with its keys over the sparse order-key
    domain.  The orders that pass join CUSTOMER (pruned to its key) on the
    dense customer-key domain; those rows, pruned and exchanged on
    ``o_orderkey``, are the build side of the join with LINEITEM, itself
    pruned and exchanged on ``l_orderkey``.  The rows are then partitioned
    by a group key, so the second aggregate follows with no further
    exchange, and ``TakeOrderedAndProject`` takes the hundred.  The
    subquery is lowered once and used once: the semi join that Spark also
    infers on LINEITEM, over a reused exchange, is left out.

    ``c_name`` is the text ``Customer#`` and ``c_custkey`` in nine digits
    (clause 4.2.3), so ``c_custkey`` determines it: the plan groups on
    ``c_custkey`` and whoever presents the hundred rows writes the name
    from the key.  The build side's ``c_custkey`` is ``o_custkey`` under
    the join's other name, and the group key ``o_orderkey`` the probe
    side's ``l_orderkey``: an equality join keeps one of each pair."""
    lines = Project(Scan("lineitem"), ("l_orderkey", "l_quantity"))
    per_order = Aggregate(Exchange(lines, "l_orderkey"),
                          keys=("l_orderkey",),
                          aggs=(Agg("sum", "l_quantity", "sum_qty"),))
    large = Project(Filter(per_order, "sum_qty", ">", Lit(int(quantity))),
                    ("l_orderkey",))
    orders = Join(Scan("orders"), large, "o_orderkey", "l_orderkey",
                  how="semi", dense_domain=int(orderkey_domain))
    customers = Join(orders, Project(Scan("customer"), ("c_custkey",)),
                     "o_custkey", "c_custkey",
                     dense_domain=int(custkey_domain))
    build = Project(customers, ("o_orderkey", ("c_custkey", Col("o_custkey")),
                                "o_orderdate", "o_totalprice"))
    joined = Join(Exchange(lines, "l_orderkey"),
                  Exchange(build, "o_orderkey"),
                  "l_orderkey", "o_orderkey",
                  dense_domain=int(orderkey_domain))
    keys = ("c_custkey", "o_orderkey", "o_orderdate", "o_totalprice")
    volume = Aggregate(
        Project(joined, ("c_custkey", ("o_orderkey", Col("l_orderkey")),
                         "o_orderdate", "o_totalprice", "l_quantity")),
        keys=keys, aggs=(Agg("sum", "l_quantity", "sum_qty"),))
    return TopK(Project(volume, keys + ("sum_qty",)),
                (Desc("o_totalprice"), "o_orderdate"), int(limit))


# TPC-H C_CUSTKEY at scale factor 10: 1..1,500,000, dense
TPCH_SF10_CUSTKEY_DOMAIN = 1_500_001


def tpch_q13_plan(word1: str = "special", word2: str = "requests",
                  custkey_domain: int = TPCH_SF10_CUSTKEY_DOMAIN) -> Sort:
    """TPC-H Q13, the customer distribution query (specification clause
    2.4.13, validation parameters WORD1 = special, WORD2 = requests)::

        select c_count, count(*) as custdist
        from (select c_custkey, count(o_orderkey) as c_count
              from customer left outer join orders
                on c_custkey = o_custkey
                and o_comment not like '%special%requests%'
              group by c_custkey) as c_orders
        group by c_count
        order by custdist desc, c_count desc

    in the physical shape Spark plans it at scale factor 10: CUSTOMER
    (1,500,000 keys, 12 MB) is over the broadcast threshold and the
    preserved side of an outer join cannot be broadcast, so both sides are
    exchanged on the customer key and sort-merge joined.  ORDERS is
    filtered on its comment (the ``NOT LIKE`` belongs to the join's
    condition, on the side that is not preserved, so it filters ORDERS
    before the join) and pruned to its two keys; the outer join keeps
    every customer, written from the side whose keys are unique
    (``how='right'``: ORDERS probes CUSTOMER over the dense domain of its
    keys).  ``count(o_orderkey)`` counts the matched orders, none for a
    customer with no order, whose ``o_orderkey`` is null.  The rows are
    then partitioned by the customer key, so the first aggregate follows
    with no exchange; its counts are exchanged and aggregated again, and
    sorted."""
    orders = Project(
        Filter(Scan("orders"), "o_comment", "not_like",
               f"%{word1}%{word2}%"),
        ("o_orderkey", "o_custkey"))
    customer = Project(Scan("customer"), ("c_custkey",))
    joined = Join(Exchange(orders, "o_custkey"),
                  Exchange(customer, "c_custkey"),
                  "o_custkey", "c_custkey", how="right",
                  dense_domain=int(custkey_domain))
    per_customer = Aggregate(joined, keys=("c_custkey",),
                             aggs=(Agg("count", "o_orderkey", "c_count"),))
    dist = Aggregate(Exchange(Project(per_customer, ("c_count",)), "c_count"),
                     keys=("c_count",),
                     aggs=(Agg("count", None, "custdist"),))
    return Sort(dist, (Desc("custdist"), Desc("c_count")))
