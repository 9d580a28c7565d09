"""TPC-H Q13 (specification clause 2.4.13) as Spark SQL answers it, one row at
a time, in Python ints, strings, dicts and ``sorted``: the plain reference of
``plan.queries.tpch_q13_plan``.  Nothing of the package is imported, and no
numpy: every rule is written out where it applies.

    select c_count, count(*) as custdist
    from (select c_custkey, count(o_orderkey) as c_count
          from customer left outer join orders
            on c_custkey = o_custkey and o_comment not like PATTERN
          group by c_custkey) as c_orders
    group by c_count
    order by custdist desc, c_count desc

Tables arrive as name -> list; ``None`` is a null; ``o_comment`` is a Python
``str``.  Spark's rules, as used:

* ``LIKE``: ``%`` is any run of characters, ``_`` one character, every other
  character itself; the whole string must match.  A null string gives null,
  and ``NOT LIKE`` of null is null: the condition is not true and the order
  joins nothing.
* a left outer join keeps every customer: those that no order joins appear
  once, their ``o_orderkey`` null; a null key joins nothing (a customer with
  a null key is kept, unmatched).
* ``count(o_orderkey)`` counts the non-null values: 0 for an unmatched
  customer; ``count(*)`` counts rows.
* ``GROUP BY`` puts nulls of a key in one group.
* ``ORDER BY custdist desc, c_count desc``: neither is ever null here, and no
  two rows share a ``c_count``, so the order is total.

The result: ``{"c_count": [...], "custdist": [...]}`` in order.
"""

import re

COLUMNS = ("c_count", "custdist")


def like(text, pattern):
    """``text LIKE pattern``: True, False, or None for a null ``text``."""
    if text is None:
        return None
    rx = "".join(".*" if c == "%" else "." if c == "_" else re.escape(c)
                 for c in pattern)
    return re.fullmatch(rx, text, re.DOTALL) is not None


def tpch_q13_reference(customer, orders, pattern="%special%requests%"):
    kept = []   # (o_custkey, o_orderkey) of each order the join may take
    for okey, ckey, comment in zip(orders["o_orderkey"], orders["o_custkey"],
                                   orders["o_comment"]):
        hit = like(comment, pattern)
        if hit is None or hit:   # NOT LIKE is not true: the order joins none
            continue
        if ckey is not None:
            kept.append((ckey, okey))
    by_key = {}
    for ckey, okey in kept:
        by_key.setdefault(ckey, []).append(okey)
    rows = []   # (c_custkey, c_count) of each customer, one a customer row
    for ckey in customer["c_custkey"]:
        matches = by_key.get(ckey, []) if ckey is not None else []
        rows.append((ckey, sum(1 for o in matches if o is not None)))
    # the first group-by: one group a customer key, nulls one group
    counts = {}
    for ckey, n in rows:
        counts[ckey] = counts.get(ckey, 0) + n
    # the second: customers by their count
    dist = {}
    for c in counts.values():
        dist[c] = dist.get(c, 0) + 1
    order = sorted(dist.items(), key=lambda kv: (-kv[1], -kv[0]))
    return {"c_count": [c for c, _n in order],
            "custdist": [n for _c, n in order]}


def wrong_values(got, want):
    """Values of ``got`` (name -> list) that differ from ``want``'s, row for
    row; a row missing or extra counts each of its values."""
    wrong = 0
    for c in COLUMNS:
        g, w = list(got[c]), list(want[c])
        wrong += sum(1 for a, b in zip(g, w) if a != b) + abs(len(g) - len(w))
    return wrong
