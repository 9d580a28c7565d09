"""TPC-H Q1 (specification clause 2.4.1) as Spark SQL answers it, one row at a
time, in Python ints and the ``decimal`` module: the plain reference of
``plan.queries.tpch_q1_plan``.  Nothing of the package is imported, and no
numpy: every rule is written out where it applies.

Columns arrive as Python lists, a decimal as its unscaled int at scale 2
(``decimal(12,2)``), ``l_shipdate`` as days since 1970-01-01, the keys as ints;
``None`` is a null.  The result is the query's rows in ``ORDER BY`` order
(ascending, nulls first), name -> list, decimals again as unscaled ints:

    l_returnflag, l_linestatus   int32
    sum_qty, sum_base_price      decimal(22,2)
    sum_disc_price               decimal(36,4)
    sum_charge                   decimal(38,6)
    avg_qty, avg_price, avg_disc decimal(16,6)
    count_order                  int64

Spark's rules, as used:

* ``1 - l_discount``: the literal is ``decimal(1,0)``, the difference
  ``decimal(13,2)`` (scale max(s1,s2), precision max(p1-s1,p2-s2)+scale+1).
* ``l_extendedprice * (1 - l_discount)``: ``decimal(26,4)`` (p1+p2+1, s1+s2).
* ``... * (1 + l_tax)``: raw ``decimal(40,6)``, adjusted to ``decimal(38,6)``
  (the integral digits kept, the scale 6 is the minimum and stays): no digit
  is rounded away, and a value of 10^38 or more is null (non-ANSI).
* ``sum(decimal(p,s))`` is ``decimal(min(38,p+10),s)``, nulls skipped, null
  over an empty input or past the type's precision.
* ``avg(decimal(p,s))`` is ``decimal(p+4,s+4)``: the sum over the count,
  rounded HALF_UP at the result's scale.
"""

import datetime
import decimal

RESULT_TYPES = {
    "l_returnflag": "int32", "l_linestatus": "int32",
    "sum_qty": "decimal(22,2)", "sum_base_price": "decimal(22,2)",
    "sum_disc_price": "decimal(36,4)", "sum_charge": "decimal(38,6)",
    "avg_qty": "decimal(16,6)", "avg_price": "decimal(16,6)",
    "avg_disc": "decimal(16,6)", "count_order": "int64",
}


def cutoff_days(delta_days=90):
    """``date '1998-12-01' - interval 'delta' day`` in days since the epoch."""
    day = datetime.date(1998, 12, 1) - datetime.timedelta(days=delta_days)
    return (day - datetime.date(1970, 1, 1)).days


def _fits(unscaled, precision):
    """A value past its type's precision is null (non-ANSI CheckOverflow)."""
    return unscaled if unscaled is not None and abs(unscaled) < 10**precision \
        else None


def _sum(values, precision):
    live = [v for v in values if v is not None]
    return _fits(sum(live), precision) if live else None


def _avg(values, scale, precision):
    """``avg`` of ``decimal(p, scale)`` values as ``decimal(precision,
    scale+4)``, unscaled."""
    live = [v for v in values if v is not None]
    if not live:
        return None
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        mean = (decimal.Decimal(sum(live)).scaleb(-scale)
                / decimal.Decimal(len(live)))
        q = mean.quantize(decimal.Decimal(1).scaleb(-(scale + 4)),
                          rounding=decimal.ROUND_HALF_UP)
        return _fits(int(q.scaleb(scale + 4)), precision)


def tpch_q1_reference(l_returnflag, l_linestatus, l_quantity,
                      l_extendedprice, l_discount, l_tax, l_shipdate,
                      delta_days=90):
    cutoff = cutoff_days(delta_days)
    groups = {}
    for rf, ls, qty, ext, disc, tax, ship in zip(
            l_returnflag, l_linestatus, l_quantity, l_extendedprice,
            l_discount, l_tax, l_shipdate):
        if ship is None or not ship <= cutoff:
            continue
        one_minus = None if disc is None else _fits(100 - disc, 13)
        one_plus = None if tax is None else _fits(100 + tax, 13)
        disc_price = None if ext is None or one_minus is None \
            else _fits(ext * one_minus, 26)
        charge = None if disc_price is None or one_plus is None \
            else _fits(disc_price * one_plus, 38)
        g = groups.setdefault((rf, ls), {k: [] for k in (
            "qty", "ext", "disc", "disc_price", "charge")})
        for k, v in (("qty", qty), ("ext", ext), ("disc", disc),
                     ("disc_price", disc_price), ("charge", charge)):
            g[k].append(v)

    def order(key):   # ascending, nulls first
        return tuple((k is not None, k if k is not None else 0) for k in key)

    out = {name: [] for name in RESULT_TYPES}
    for key in sorted(groups, key=order):
        g = groups[key]
        out["l_returnflag"].append(key[0])
        out["l_linestatus"].append(key[1])
        out["sum_qty"].append(_sum(g["qty"], 22))
        out["sum_base_price"].append(_sum(g["ext"], 22))
        out["sum_disc_price"].append(_sum(g["disc_price"], 36))
        out["sum_charge"].append(_sum(g["charge"], 38))
        out["avg_qty"].append(_avg(g["qty"], 2, 16))
        out["avg_price"].append(_avg(g["ext"], 2, 16))
        out["avg_disc"].append(_avg(g["disc"], 2, 16))
        out["count_order"].append(len(g["qty"]))
    return out
