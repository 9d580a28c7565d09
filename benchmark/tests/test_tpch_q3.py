"""The two references of TPC-H Q3 held equal: the numpy form the cell compares
against (``benchmark/reference/tpch_q3.py``) and the row-at-a-time one in
plain Python (``tests/tpch_q3_reference.py``), over the configuration's own
generated tables and over tables whose join keys repeat; the control and the
comparison's handling of ties; and what the generator promises.

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import lib  # noqa: E402
from benchmark.reference import tpch_q3 as ref  # noqa: E402

import tpch_q3_reference as plain  # noqa: E402

TABLES = {"customer": ("c_custkey", "c_mktsegment"),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"),
          "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate")}


@pytest.fixture(scope="module")
def generated():
    """The configuration's databases at 2^13 LINEITEM rows, as numpy."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q3", 13)
    state = mod.build(cfg, mod, 2147483659, jax.devices()[:1])
    out = [(cfg, mod, state.host_tables(p)) for p in range(state.partitions)]
    plan.reset_plan_cache()
    return out


def _both(tables, **params):
    cols = [np.asarray(tables[f"{t}.{c}"]) for t, cs in TABLES.items()
            for c in cs]
    lists = {t: {c: [int(x) for x in tables[f"{t}.{c}"]] for c in cs}
             for t, cs in TABLES.items()}
    return (ref.tpch_q3_reference(*cols, **params),
            plain.tpch_q3_reference(lists["customer"], lists["orders"],
                                    lists["lineitem"], **params))


def test_the_generator_keeps_dbgens_promises(generated):
    for cfg, mod, t in generated:
        n = mod.table_rows(cfg)
        assert len(t["lineitem.l_orderkey"]) == n["lineitem"] == 1 << 13
        okey = t["orders.o_orderkey"]
        assert len(okey) == n["orders"] and len(set(okey)) == len(okey)
        i = np.arange(1, len(okey) + 1)
        assert np.array_equal(okey, ((i >> 3) << 5) | (i & 7))
        assert okey.max() + 1 == mod.plan(cfg).child.child.child.child \
            .dense_domain
        # every order has 1..7 lines, LINEITEM in order-key order
        lines = np.bincount(np.searchsorted(okey, t["lineitem.l_orderkey"]),
                            minlength=len(okey))
        assert lines.min() >= 1 and lines.max() <= 7
        assert (np.diff(t["lineitem.l_orderkey"]) >= 0).all()
        ckey = t["orders.o_custkey"]
        assert (ckey % 3 != 0).all() and ckey.min() >= 1 \
            and ckey.max() <= n["customer"]
        assert np.array_equal(t["customer.c_custkey"],
                              np.arange(1, n["customer"] + 1))
        assert set(t["customer.c_mktsegment"]) <= set(range(5))
        ship = t["lineitem.l_shipdate"] - t["orders.o_orderdate"][
            np.searchsorted(okey, t["lineitem.l_orderkey"])]
        assert ship.min() >= 1 and ship.max() <= 121
        assert (t["lineitem.l_discount"] >= 0).all() \
            and (t["lineitem.l_discount"] <= 10).all()
        assert mod.query_bytes(cfg) == sum(
            len(t[f"{tb}.{cs[0]}"]) * cfg["row_bytes"][tb]
            for tb, cs in TABLES.items())


def test_the_two_references_agree_on_generated_tables(generated):
    seen = 0
    for _cfg, _mod, t in generated:
        for params in ({}, {"limit": 3}, {"date_iso": "1996-01-01"},
                       {"segment_code": 4, "limit": 1000}):
            a, b = _both(t, **params)
            assert a == b
            seen += len(a["l_orderkey"])
    assert seen > 40


def test_the_two_references_agree_where_keys_repeat(generated):
    _cfg, _mod, t = generated[0]
    t = {k: np.array(v) for k, v in t.items()}
    # every seventh order twice under another date, every fifth customer
    # twice, a line's key moved onto another order's
    for name, step in (("orders", 7), ("customer", 5)):
        for c in TABLES[name]:
            col = t[f"{name}.{c}"]
            t[f"{name}.{c}"] = np.concatenate(
                [col, col[::step] - (c == "o_orderdate")])
    a, b = _both(t, limit=50)
    assert a == b and len(a["l_orderkey"]) >= 20
    assert len(set(zip(a["l_orderkey"], a["o_orderdate"]))) \
        > len(set(a["l_orderkey"]))


def test_the_control_differs_and_ties_are_accepted(generated):
    cfg, mod, t = generated[0]
    want = mod.reference(cfg, t)
    ctl = mod.control(cfg, t)
    assert ref.wrong_values(want, want) == 0
    assert ref.wrong_values(want, ctl) > 0
    cols = [np.asarray(t[f"{tb}.{c}"]) for tb, cs in TABLES.items()
            for c in cs]
    lists = {tb: {c: [int(x) for x in t[f"{tb}.{c}"]] for c in cs}
             for tb, cs in TABLES.items()}
    assert ctl == plain.tpch_q3_reference(
        lists["customer"], lists["orders"], lists["lineitem"], term_scale=2)
    assert ctl == ref.tpch_q3_control(*cols)
    # both comparisons count alike: rows tied in both keys in any order
    # and any of them at the cut, nothing else
    tied = {"l_orderkey": [7, 1, 2, 3, 4], "revenue": [90, 50, 50, 50, 50],
            "o_orderdate": [5, 6, 6, 6, 6], "o_shippriority": [0] * 5}
    for got, wrong in (
            ({c: [v[0], v[3], v[1]] for c, v in tied.items()}, 0),
            ({c: [v[0], v[4], v[2]] for c, v in tied.items()}, 0),
            ({c: [v[0], v[1], v[1]] for c, v in tied.items()}, 1),
            ({c: [v[1], v[0], v[2]] for c, v in tied.items()}, 6),
            ({c: [v[0], v[1]] for c, v in tied.items()}, 4)):
        assert ref.wrong_values(got, tied, limit=3) == wrong \
            == plain.wrong_values(got, tied, limit=3)
