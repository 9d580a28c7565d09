"""Plain numpy reference of TPC-H Q18 (specification clause 2.4.18) as Spark
SQL answers it, over the columns the query reads, none of them null:

    customer  c_custkey int64
    orders    o_orderkey, o_custkey int64, o_orderdate days since
              1970-01-01, o_totalprice decimal(12,2) as unscaled int64
    lineitem  l_orderkey int64, l_quantity decimal(12,2) as unscaled int64

Exact throughout, in int64: a quantity is under 10^12 hundredths by its type
and the sums are checked against 2^62 before they are taken (an order of
dbgen's has at most 7 lines of at most 50.00).  The per-order sums are
``np.add.at`` over the order keys' ranks; the ``IN`` and the joins are sorted
lookups that let keys repeat on either side (dbgen's do not).

The answer: name -> list, rows in ``ORDER BY o_totalprice desc, o_orderdate``
order, ``c_name`` the text ``Customer#`` and the key in nine digits (clause
4.2.3), ``o_totalprice`` and ``sum_qty`` as unscaled ints at scale 2.  SQL
leaves rows equal in both sort keys unordered, so after the rows that are
surely in the answer lists EVERY row equal in both keys to the one at rank
``limit``: any of them may make the cut, and ``wrong_values`` accepts any.

``tpch_q18_control`` breaks one guarantee: ``HAVING sum(l_quantity) >=
QUANTITY`` in the place of ``>``, which lets in the orders whose lines sum to
exactly QUANTITY.
"""

import numpy as np

from benchmark.reference.tpch_q3 import days  # noqa: F401  (the recipe's)

COLUMNS = ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
           "o_totalprice", "sum_qty")
PRICE, DATE = COLUMNS.index("o_totalprice"), COLUMNS.index("o_orderdate")


def _count_in(keys, sorted_build):
    """How often each of ``keys`` is in ``sorted_build``."""
    return (np.searchsorted(sorted_build, keys, side="right")
            - np.searchsorted(sorted_build, keys, side="left"))


def tpch_q18_reference(c_custkey, o_orderkey, o_custkey, o_orderdate,
                       o_totalprice, l_orderkey, l_quantity, quantity=300,
                       limit=100, having_or_equal=False):
    qty = l_quantity.astype(np.int64)
    if int(np.abs(qty).max(initial=0)) * max(len(qty), 1) >= 1 << 62:
        raise OverflowError("the quantities' sums may pass int64")
    # the subquery: one group an order key, its sum, the HAVING
    keys, rank = np.unique(l_orderkey, return_inverse=True)
    sums = np.zeros(len(keys), np.int64)
    np.add.at(sums, rank.reshape(-1), qty)
    threshold = int(quantity) * 100                 # decimal(22,2)
    large = keys[(sums >= threshold) if having_or_equal
                 else (sums > threshold)]           # sorted, unique
    # orders whose key is IN it (a semi join: once, however often it is
    # there), joined with customer (every match of the key)
    orders = np.flatnonzero(_count_in(o_orderkey, large) > 0)
    orders = np.repeat(orders, _count_in(o_custkey[orders],
                                         np.sort(c_custkey)))
    # ... and with lineitem: each such row of orders with every line of its
    # key; then group by (c_custkey, o_orderkey, o_orderdate, o_totalprice)
    groups, inverse, times = np.unique(
        np.stack([o_custkey[orders].astype(np.int64),
                  o_orderkey[orders].astype(np.int64),
                  o_orderdate[orders].astype(np.int64),
                  o_totalprice[orders].astype(np.int64)], axis=1),
        axis=0, return_inverse=True, return_counts=True)
    # a group's lines are its key's lines, once for each row of the group
    per_key = sums[np.searchsorted(keys, groups[:, 1])]
    if int(np.abs(per_key).max(initial=0)) * int(times.max(initial=0)) \
            >= 1 << 62:
        raise OverflowError("a group's sum may pass int64")
    sum_qty = per_key * times
    if np.any(np.abs(sum_qty) >= 10 ** 22):
        raise OverflowError("sum_qty passes its type: Spark's is null")
    rows = sorted(
        (("Customer#%09d" % int(k[0]), int(k[0]), int(k[1]), int(k[2]),
          int(k[3]), int(s)) for k, s in zip(groups, sum_qty)),
        key=lambda r: (-r[PRICE], r[DATE], r[1], r[2]))
    if len(rows) > limit:
        cut = rows[limit - 1]
        rows = [r for i, r in enumerate(rows)
                if i < limit or (r[PRICE], r[DATE]) == (cut[PRICE], cut[DATE])]
    return {name: [r[i] for r in rows] for i, name in enumerate(COLUMNS)}


def tpch_q18_control(*columns, **params):
    return tpch_q18_reference(*columns, having_or_equal=True, **params)


def wrong_values(got, want, limit=100):
    """Values of an answer (name -> sequence, rows in its own order) that
    the reference's ``want`` does not allow.  Row ``i`` must carry the sort
    keys of the reference's row ``i`` (rows equal in both keys share them,
    so the keys' sequence is one sequence whatever the order among ties) and
    be one of the reference's rows with those keys, each at most once; a row
    too many or too few counts as six."""
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
