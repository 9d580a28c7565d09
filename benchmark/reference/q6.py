"""Plain numpy reference of the program's ``q6_plan``: filter price < 50,
group by k: sum(v), count(*), avg(price) over doubles (a float64 sum over the
float64 prices, divided by the count, as Spark's ``avg`` of a DoubleType
column).  Copied from chip_smoke.py (``_q6_reference``) so that a later change
there does not move the yardstick."""

import numpy as np


def q6_reference(k, v, price, domain=100):
    m = price < 50.0
    ks = k[m].astype(np.int64)
    cnt = np.bincount(ks, minlength=domain)
    sums = np.zeros(domain, np.int64)
    np.add.at(sums, ks, v[m])
    psum = np.bincount(ks, weights=price[m], minlength=domain)
    avg = psum / np.maximum(cnt, 1).astype(np.float64)
    live = cnt > 0
    return {"k": np.flatnonzero(live).astype(np.int64), "sum_v": sums[live],
            "cnt": cnt[live].astype(np.int64), "avg_price": avg[live]}
