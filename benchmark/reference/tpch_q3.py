"""Plain numpy reference of TPC-H Q3 (specification clause 2.4.3) as Spark SQL
answers it, over the columns the query reads, none of them null:

    customer  c_custkey int64, c_mktsegment int32 dictionary code
    orders    o_orderkey, o_custkey int64, o_orderdate days since
              1970-01-01, o_shippriority int32
    lineitem  l_orderkey int64, l_extendedprice, l_discount decimal(12,2)
              as unscaled int64, l_shipdate days

Exact throughout.  The joins are sorted lookups that let keys repeat on either
side (dbgen's do not).  A line's ``revenue_term`` (``decimal(26,4)``) is an
int64 product and a group's sum an int64 sum; the bounds that make both safe
are checked, not assumed (dbgen's values give a term under 1.05e9, and a
group has at most a few lines), and past them the sums are taken as Python
ints.

The answer: name -> list, rows in ``ORDER BY revenue desc, o_orderdate``
order, ``revenue`` as unscaled Python ints at scale 4.  SQL leaves rows equal
in both sort keys unordered, so after the rows that are surely in the answer
lists EVERY row equal in both keys to the one at rank ``limit``: any of them
may make the cut, and ``wrong_values`` accepts any.

``tpch_q3_control`` breaks one guarantee the way a narrower type would: each
``revenue_term`` is rounded HALF_UP to scale 2 before the sum, which is what
typing the product ``decimal(12,2)`` would give.
"""

import datetime

import numpy as np

COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")


def days(iso):
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def _matches(keys, sorted_build):
    """For each of ``keys`` its range of equal entries in ``sorted_build``."""
    lo = np.searchsorted(sorted_build, keys, side="left")
    return lo, np.searchsorted(sorted_build, keys, side="right") - lo


def _expand(lo, count):
    """Row ids of the probe side, each repeated once for each of its
    matches, and beside each the position of that match in the build."""
    probe = np.repeat(np.arange(len(count)), count)
    first = np.cumsum(count) - count
    return probe, np.repeat(lo, count) \
        + (np.arange(len(probe)) - np.repeat(first, count))


def tpch_q3_reference(c_custkey, c_mktsegment, o_orderkey, o_custkey,
                      o_orderdate, o_shippriority, l_orderkey,
                      l_extendedprice, l_discount, l_shipdate,
                      segment_code=1, date_iso="1995-03-15", limit=10,
                      term_scale=4):
    date = days(date_iso)
    # customer (filtered) joins orders (filtered)
    building = np.sort(c_custkey[c_mktsegment == segment_code])
    orders = np.flatnonzero(o_orderdate < date)
    _lo, count = _matches(o_custkey[orders], building)
    orders = np.repeat(orders, count)
    # ... and that, sorted on its key, is the build side of the join with
    # lineitem (filtered)
    orders = orders[np.argsort(o_orderkey[orders], kind="stable")]
    lines = np.flatnonzero(l_shipdate > date)
    lo, count = _matches(l_orderkey[lines], o_orderkey[orders])
    probe, build = _expand(lo, count)
    lines, orders = lines[probe], orders[build]
    ext = l_extendedprice[lines].astype(np.int64)
    factor = 100 - l_discount[lines].astype(np.int64)
    if int(np.abs(ext).max(initial=0)) * int(np.abs(factor).max(initial=0)) \
            >= 10 ** 18:
        raise OverflowError("revenue_term does not fit int64 a row")
    term = ext * factor                                 # decimal(26,4)
    if term_scale != 4:                                 # the control
        unit = 10 ** (4 - term_scale)
        q, r = np.divmod(np.abs(term), unit)
        term = np.sign(term) * (q + (2 * r >= unit)) * unit
    # group by (l_orderkey, o_orderdate, o_shippriority)
    keys = np.stack([l_orderkey[lines].astype(np.int64),
                     o_orderdate[orders].astype(np.int64),
                     o_shippriority[orders].astype(np.int64)], axis=1)
    groups, inverse = np.unique(keys, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    peak = int(np.abs(term).max(initial=0))
    most = int(np.bincount(inverse).max(initial=0))
    if peak * most < 1 << 62:
        revenue = np.zeros(len(groups), np.int64)
        np.add.at(revenue, inverse, term)
        revenue = [int(x) for x in revenue]
    else:
        revenue = [0] * len(groups)
        for g, t in zip(inverse.tolist(), term.tolist()):
            revenue[g] += t
    if any(abs(x) >= 10 ** 36 for x in revenue):
        raise OverflowError("revenue passes its type: Spark's is null")
    rows = sorted(
        ((int(k[0]), rev, int(k[1]), int(k[2]))
         for k, rev in zip(groups, revenue)),
        key=lambda r: (-r[1], r[2], r[0], r[3]))
    if len(rows) > limit:
        cut = rows[limit - 1]
        rows = [r for i, r in enumerate(rows)
                if i < limit or (r[1], r[2]) == (cut[1], cut[2])]
    return {name: [r[i] for r in rows] for i, name in enumerate(COLUMNS)}


def tpch_q3_control(*columns, **params):
    return tpch_q3_reference(*columns, term_scale=2, **params)


def wrong_values(got, want, limit=10):
    """Values of an answer (name -> sequence, rows in its own order) that
    the reference's ``want`` does not allow.  Row ``i`` must carry the sort
    keys of the reference's row ``i`` (rows equal in both keys share them,
    so the keys' sequence is one sequence whatever the order among ties) and
    be one of the reference's rows with those keys, each at most once; a row
    too many or too few counts as four."""
    g = list(zip(*(got[c] for c in COLUMNS)))
    w = list(zip(*(want[c] for c in COLUMNS)))
    expect = min(limit, len(w))
    wrong = len(COLUMNS) * abs(len(g) - expect)
    free = {}
    for r in w:
        free.setdefault((r[1], r[2]), []).append(r)
    for i in range(min(len(g), expect)):
        allowed = free.get((w[i][1], w[i][2]), [])
        if g[i] in allowed:
            allowed.remove(g[i])
        else:
            wrong += max(1, sum(a != b for a, b in zip(g[i], w[i])))
    return int(wrong)
