"""TPC-H Q18 (specification clause 2.4.18) as Spark SQL answers it, one row at
a time, in Python ints, dicts and ``sorted``: the plain reference of
``plan.queries.tpch_q18_plan``.  Nothing of the package is imported, and no
numpy: every rule is written out where it applies.

    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice,
           sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                         having sum(l_quantity) > QUANTITY)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100

Tables arrive as name -> list, a decimal as its unscaled int at scale 2
(``decimal(12,2)``), a date as days since 1970-01-01; ``None`` is a null.
``c_name`` is not a column here: by clause 4.2.3 it is the text ``Customer#``
and ``c_custkey`` in nine digits, and is written from the key.  Spark's rules,
as used:

* ``sum(decimal(12,2))`` is ``decimal(22,2)``, nulls skipped, null over no
  value; ``HAVING`` compares it with QUANTITY cast to that type, a comparison
  with a null is null, and the group goes.
* ``x IN (subquery)`` in a ``WHERE`` is a left semi join: a null on either
  side matches nothing, a key twice in the subquery keeps the row once.
* a null key joins nothing; duplicate keys on either side of a join multiply.
* ``GROUP BY`` puts nulls of a key in one group.
* ``ORDER BY o_totalprice desc, o_orderdate``: descending puts nulls last,
  ascending first.  SQL leaves rows equal in both keys unordered, so the
  answer lists, after the rows that are surely in, EVERY row equal in both
  keys to the one at rank ``limit``: any of them may make the cut.

The result: name -> list (``c_name``, ``c_custkey``, ``o_orderkey``,
``o_orderdate``, ``o_totalprice`` unscaled at scale 2, ``sum_qty`` unscaled at
scale 2), in order, ``limit`` rows or, with ties at the cut, more.
"""

RESULT_TYPES = {"c_name": "string", "c_custkey": "int64",
                "o_orderkey": "int64", "o_orderdate": "date",
                "o_totalprice": "decimal(12,2)", "sum_qty": "decimal(22,2)"}
COLUMNS = tuple(RESULT_TYPES)
PRICE, DATE = COLUMNS.index("o_totalprice"), COLUMNS.index("o_orderdate")


def customer_name(custkey):
    return None if custkey is None else "Customer#%09d" % custkey


def sort_key(totalprice, orderdate):
    """Ascending order of this is ``o_totalprice desc`` (nulls last) then
    ``o_orderdate`` (nulls first)."""
    return ((totalprice is None, -(totalprice or 0)),
            (orderdate is not None, orderdate or 0))


def _sum(values):
    """``sum`` of a decimal column: nulls skipped, null over no value."""
    live = [v for v in values if v is not None]
    return sum(live) if live else None


def tpch_q18_reference(customer, orders, lineitem, quantity=300, limit=100,
                       having_or_equal=False):
    """``having_or_equal=True`` is the control: ``HAVING sum(l_quantity) >=
    QUANTITY``, which lets in the orders that sum to exactly QUANTITY."""
    threshold = quantity * 100                    # cast to decimal(22,2)
    per_order = {}
    for okey, qty in zip(lineitem["l_orderkey"], lineitem["l_quantity"]):
        per_order.setdefault(okey, []).append(qty)
    large = set()
    for okey, qtys in per_order.items():
        total = _sum(qtys)
        if okey is None or total is None:
            continue
        if total > threshold or (having_or_equal and total == threshold):
            large.add(okey)
    customers = {}
    for ckey in customer["c_custkey"]:
        if ckey is not None:
            customers[ckey] = customers.get(ckey, 0) + 1
    groups = {}
    for okey, ckey, odate, price in zip(
            orders["o_orderkey"], orders["o_custkey"], orders["o_orderdate"],
            orders["o_totalprice"]):
        if okey is None or okey not in large or ckey is None:
            continue
        for _ in range(customers.get(ckey, 0)):
            groups.setdefault((ckey, okey, odate, price), []).extend(
                per_order[okey])
    rows = [(customer_name(k[0]), *k, _sum(qtys))
            for k, qtys in groups.items()]
    rows.sort(key=lambda r: (sort_key(r[PRICE], r[DATE]), r[1], r[2]))
    if len(rows) > limit:
        cut = sort_key(rows[limit - 1][PRICE], rows[limit - 1][DATE])
        rows = [r for i, r in enumerate(rows)
                if i < limit or sort_key(r[PRICE], r[DATE]) == cut]
    return {name: [r[i] for r in rows] for i, name in enumerate(COLUMNS)}


def wrong_values(got, want, limit=100):
    """Values of an answer (name -> sequence, rows in its own order) that
    the reference's ``want`` does not allow.  Row ``i`` must carry the sort
    keys of the reference's row ``i`` (rows equal in both keys share them)
    and be one of the reference's rows with those keys, each at most once; a
    row too many or too few counts as six."""
    g = list(zip(*(got[c] for c in COLUMNS)))
    w = list(zip(*(want[c] for c in COLUMNS)))
    expect = min(limit, len(w))
    wrong = len(COLUMNS) * abs(len(g) - expect)
    free = {}
    for r in w:
        free.setdefault((r[PRICE], r[DATE]), []).append(r)
    for i in range(min(len(g), expect)):
        allowed = free.get((w[i][PRICE], w[i][DATE]), [])
        if g[i] in allowed:
            allowed.remove(g[i])
        else:
            wrong += max(1, sum(a != b for a, b in zip(g[i], w[i])))
    return int(wrong)
