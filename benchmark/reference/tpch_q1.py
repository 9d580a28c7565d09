"""Plain numpy reference of TPC-H Q1 (specification clause 2.4.1) as Spark SQL
answers it, over the seven columns the query reads, none of them null:

    l_returnflag, l_linestatus   int32 dictionary codes
    l_quantity, l_extendedprice,
    l_discount, l_tax            decimal(12,2) as unscaled int64
    l_shipdate                   days since 1970-01-01

Exact throughout.  A row's ``disc_price`` (scale 4) and ``charge`` (scale 6)
are int64 products; the bound that makes that safe is checked, not assumed
(dbgen's values give a ``charge`` under 1.14e11 a row).  A group's sum is
taken in int64 over at most ``CHUNK`` rows at a time, where ``CHUNK`` times
the column's largest magnitude stays under 2^63, and the chunks' partial sums
are added as Python ints.  An average is the sum over the count at four more
digits of scale, HALF_UP, in Python ints.

The answer: name -> list in ``ORDER BY l_returnflag, l_linestatus`` order,
decimals as unscaled Python ints (``sum_qty``, ``sum_base_price`` scale 2,
``sum_disc_price`` 4, ``sum_charge`` 6, the averages 6).

``tpch_q1_control`` breaks one guarantee the way a lower precision would: the
four sums are taken in float64 and cast back.  At the published size a group's
``sum_charge`` is 5e17 units, past float64's 2^53.  A rehearsal's table is too
small for float64 to lose a unit, so below 2^20 rows the control takes float32,
the next precision down: the test there is that the comparison can fail.
"""

import datetime

import numpy as np

CHUNK = 1 << 25
COLUMNS = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
           "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
           "count_order")
SUM_PRECISION = {"sum_qty": 22, "sum_base_price": 22, "sum_disc_price": 36,
                 "sum_charge": 38}


def cutoff_days(delta_days=90):
    day = datetime.date(1998, 12, 1) - datetime.timedelta(days=delta_days)
    return (day - datetime.date(1970, 1, 1)).days


def _exact_sum(values):
    """The sum of an int64 array as a Python int."""
    if not len(values):
        return 0
    peak = max(int(values.max()), -int(values.min()), 1)
    step = max(1, min(CHUNK, ((1 << 63) - 1) // peak))
    return sum(int(values[lo:lo + step].sum(dtype=np.int64))
               for lo in range(0, len(values), step))


def _float_sum(values, dtype=np.float64):
    return int(values.astype(dtype).sum(dtype=dtype))


def _half_up(num, den):
    """``num / den`` rounded HALF_UP, in ints."""
    q, r = divmod(abs(num), den)
    q += 2 * r >= den
    return -q if num < 0 else q


def _product(a, b, what):
    """``a * b`` in int64, the bound that makes it exact checked first."""
    if int(np.abs(a).max(initial=0)) * int(np.abs(b).max(initial=0)) \
            >= 1 << 63:
        raise OverflowError(f"{what} does not fit int64 a row")
    return a * b


def _measures(ext, disc, tax):
    disc_price = _product(ext, 100 - disc, "disc_price")   # decimal(26,4)
    # raw decimal(40,6), adjusted to (38,6): the scale is kept
    return disc_price, _product(disc_price, 100 + tax, "charge")


def tpch_q1_reference(returnflag, linestatus, quantity, extendedprice,
                      discount, tax, shipdate, delta_days=90,
                      sum_of=_exact_sum):
    keep = shipdate <= cutoff_days(delta_days)   # a mask: compresses fast
    qty, ext, disc = (np.asarray(c[keep], np.int64)
                      for c in (quantity, extendedprice, discount))
    disc_price, charge = _measures(ext, disc, np.asarray(tax[keep], np.int64))
    span = int(linestatus.max(initial=0)) + 1
    group = returnflag[keep].astype(np.int32) * span + linestatus[keep]
    out = {c: [] for c in COLUMNS}
    # the groups there are, ascending: ORDER BY both keys
    for g in np.flatnonzero(np.bincount(group)):
        rows = group == g
        n = int(rows.sum())
        q, e, d = qty[rows], ext[rows], disc[rows]
        out["l_returnflag"].append(int(g) // span)
        out["l_linestatus"].append(int(g) % span)
        for name, values in (("sum_qty", q), ("sum_base_price", e),
                             ("sum_disc_price", disc_price[rows]),
                             ("sum_charge", charge[rows])):
            v = sum_of(values)
            if abs(v) >= 10 ** SUM_PRECISION[name]:
                raise OverflowError(f"{name} passes its type: Spark's is null")
            out[name].append(v)
        # Spark's Average: the exact sum over the count, decimal(16,6),
        # HALF_UP (the control breaks the four sums only)
        for name, values in (("avg_qty", q), ("avg_price", e),
                             ("avg_disc", d)):
            out[name].append(_half_up(_exact_sum(values) * 10**4, n))
        out["count_order"].append(n)
    return out


def tpch_q1_control(*columns, delta_days=90):
    dtype = np.float64 if len(columns[0]) >= 1 << 20 else np.float32
    return tpch_q1_reference(
        *columns, delta_days=delta_days,
        sum_of=lambda values: _float_sum(values, dtype))
