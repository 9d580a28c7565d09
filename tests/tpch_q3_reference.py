"""TPC-H Q3 (specification clause 2.4.3) as Spark SQL answers it, one row at a
time, in Python ints, dicts and ``sorted``: the plain reference of
``plan.queries.tpch_q3_plan``.  Nothing of the package is imported, and no
numpy: every rule is written out where it applies.

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = SEGMENT and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < DATE and l_shipdate > DATE
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10

Tables arrive as name -> list, a decimal as its unscaled int at scale 2
(``decimal(12,2)``), a date as days since 1970-01-01, ``c_mktsegment`` as its
dictionary code; ``None`` is a null.  Spark's rules, as used:

* a comparison with a null is null, and ``WHERE`` drops the row; a null key
  joins nothing; duplicate keys on either side of a join multiply.
* ``1 - l_discount`` is ``decimal(13,2)``, the product ``decimal(26,4)``
  (p1+p2+1, s1+s2: no digit is rounded away), null if an operand is.
* ``GROUP BY`` puts nulls of a key in one group; ``sum(decimal(26,4))`` is
  ``decimal(36,4)``, nulls skipped, null over no value or past 36 digits.
* ``ORDER BY revenue desc, o_orderdate``: descending puts nulls last,
  ascending first.  SQL leaves rows equal in both keys unordered, so the
  answer lists, after the rows that are surely in, EVERY row equal in both
  keys to the one at rank ``limit``: any of them may make the cut.

The result: name -> list (``l_orderkey``, ``revenue`` unscaled at scale 4,
``o_orderdate``, ``o_shippriority``), in order, ``limit`` rows or, with ties
at the cut, more.
"""

import datetime

RESULT_TYPES = {"l_orderkey": "int64", "revenue": "decimal(36,4)",
                "o_orderdate": "date", "o_shippriority": "int32"}


def days(iso):
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def sort_key(revenue, orderdate):
    """Ascending order of this is ``revenue desc`` (nulls last) then
    ``o_orderdate`` (nulls first)."""
    return ((revenue is None, -(revenue or 0)),
            (orderdate is not None, orderdate or 0))


def round_half_up(unscaled, digits):
    """``unscaled`` with ``digits`` fewer decimal digits, HALF_UP."""
    q, r = divmod(abs(unscaled), 10 ** digits)
    q += 2 * r >= 10 ** digits
    return -q if unscaled < 0 else q


def tpch_q3_reference(customer, orders, lineitem, segment_code=1,
                      date_iso="1995-03-15", limit=10, term_scale=4):
    """``term_scale=2`` is the control: each ``revenue_term`` rounded HALF_UP
    to scale 2 before the sum (what typing the product ``decimal(12,2)``
    would give), the sum handed back at scale 4."""
    date = days(date_iso)
    building = {}
    for key, seg in zip(customer["c_custkey"], customer["c_mktsegment"]):
        if seg is not None and seg == segment_code and key is not None:
            building[key] = building.get(key, 0) + 1
    by_orderkey = {}
    for okey, ckey, odate, prio in zip(
            orders["o_orderkey"], orders["o_custkey"], orders["o_orderdate"],
            orders["o_shippriority"]):
        if odate is None or not odate < date or okey is None:
            continue
        for _ in range(building.get(ckey, 0) if ckey is not None else 0):
            by_orderkey.setdefault(okey, []).append((odate, prio))
    groups = {}
    for okey, ext, disc, ship in zip(
            lineitem["l_orderkey"], lineitem["l_extendedprice"],
            lineitem["l_discount"], lineitem["l_shipdate"]):
        if ship is None or not ship > date or okey is None:
            continue
        term = None
        if ext is not None and disc is not None:
            term = ext * (100 - disc)           # decimal(26,4), exact
            if abs(term) >= 10 ** 26:
                term = None
            elif term_scale != 4:
                term = round_half_up(term, 4 - term_scale) \
                    * 10 ** (4 - term_scale)
        for odate, prio in by_orderkey.get(okey, ()):
            groups.setdefault((okey, odate, prio), []).append(term)
    rows = []
    for (okey, odate, prio), terms in groups.items():
        live = [t for t in terms if t is not None]
        revenue = sum(live) if live else None
        if revenue is not None and abs(revenue) >= 10 ** 36:
            revenue = None
        rows.append((okey, revenue, odate, prio))
    # a total order under the SQL one, so that the answer is one answer
    rows.sort(key=lambda r: (sort_key(r[1], r[2]), r[0],
                             (r[3] is not None, r[3] or 0)))
    if len(rows) > limit:
        cut = sort_key(rows[limit - 1][1], rows[limit - 1][2])
        rows = [r for i, r in enumerate(rows)
                if i < limit or sort_key(r[1], r[2]) == cut]
    return {name: [r[i] for r in rows]
            for i, name in enumerate(RESULT_TYPES)}


def wrong_values(got, want, limit=10):
    """Values of an answer (name -> list, rows in its own order) that the
    reference's ``want`` does not allow.  Row ``i`` must carry the sort keys
    of the reference's row ``i`` (rows equal in both keys share them, so the
    sequence of keys is one sequence whatever the order among ties) and be
    one of the reference's rows with those keys, each at most once; a row
    too many or too few counts as four."""
    names = list(RESULT_TYPES)
    g = list(zip(*(got[c] for c in names)))
    w = list(zip(*(want[c] for c in names)))
    expect = min(limit, len(w))
    wrong = len(names) * abs(len(g) - expect)
    free = {}
    for r in w:
        free.setdefault(sort_key(r[1], r[2]), []).append(r)
    for i in range(min(len(g), expect)):
        allowed = free.get(sort_key(w[i][1], w[i][2]), [])
        if g[i] in allowed:
            allowed.remove(g[i])
        else:
            wrong += max(1, sum(a != b for a, b in zip(g[i], w[i])))
    return wrong
