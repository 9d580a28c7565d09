"""Plain numpy reference of the q95 operator core: fact inner-join dim1 on k,
inner-join dim2 on wh (both dims carry unique keys, so each join keeps the
fact rows whose key the dim holds), group by seg: count(*), sum(v).  Copied
from chip_smoke.py (``_q95_reference``)."""

import numpy as np


def q95_reference(k, wh, seg, v, dim1_k, dim2_wh):
    keep = np.isin(k, dim1_k) & np.isin(wh, dim2_wh)
    seg, v = seg[keep].astype(np.int64), v[keep]
    orders = np.bincount(seg)
    net = np.zeros(orders.shape[0], np.int64)
    np.add.at(net, seg, v)
    live = orders > 0
    return {"seg": np.flatnonzero(live).astype(np.int64),
            "orders": orders[live].astype(np.int64), "net": net[live]}
