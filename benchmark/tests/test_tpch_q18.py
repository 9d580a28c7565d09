"""The two references of TPC-H Q18 held equal: the numpy form the cell
compares against (``benchmark/reference/tpch_q18.py``) and the row-at-a-time
one in plain Python (``tests/tpch_q18_reference.py``), over the
configuration's own generated tables and over tables whose join keys repeat;
the control; what the generator promises; the name's way over the wire; and
the ``agg_input_slots`` reader.  (``test_correct.py`` takes the cell from
``BENCHMARK.json`` by itself: the rehearsal correct, its control and both
faults not.)

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
from benchmark.reference import tpch_q18 as ref  # noqa: E402

import tpch_q18_reference as plain  # noqa: E402

TABLES = {"customer": ("c_custkey",),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"),
          "lineitem": ("l_orderkey", "l_quantity")}
read = lib.load_module("metrics", "agg_input_slots").read


@pytest.fixture(scope="module")
def generated():
    """The configuration's databases at 2^13 LINEITEM rows, as numpy, and
    the program's counters after set-up's one query."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q18", 13)
    state = mod.build(cfg, mod, 2147483659, jax.devices()[:1])
    counters = {"plan_cache": plan.plan_cache_metrics()}
    out = [(cfg, mod, state.host_tables(p)) for p in range(state.partitions)]
    plan.reset_plan_cache()
    return out, counters


def _both(tables, **params):
    cols = [np.asarray(tables[f"{t}.{c}"]) for t, cs in TABLES.items()
            for c in cs]
    lists = {t: {c: [int(x) for x in tables[f"{t}.{c}"]] for c in cs}
             for t, cs in TABLES.items()}
    return (ref.tpch_q18_reference(*cols, **params),
            plain.tpch_q18_reference(lists["customer"], lists["orders"],
                                     lists["lineitem"], **params))


def test_the_generator_keeps_dbgens_promises(generated):
    for cfg, mod, t in generated[0]:
        n = mod.table_rows(cfg)
        assert len(t["lineitem.l_orderkey"]) == n["lineitem"] == 1 << 13
        okey = t["orders.o_orderkey"]
        assert len(okey) == n["orders"] and len(set(okey)) == len(okey)
        i = np.arange(1, len(okey) + 1)
        assert np.array_equal(okey, ((i >> 3) << 5) | (i & 7))
        the_plan = mod.plan(cfg)
        joins = [nd for nd in the_plan.walk()
                 if type(nd).__name__ == "Join"]
        assert [j.dense_domain for j in joins] == [
            okey.max() + 1, n["customer"] + 1, okey.max() + 1]
        # every order has 1..7 lines, LINEITEM in order-key order
        of_order = np.searchsorted(okey, t["lineitem.l_orderkey"])
        lines = np.bincount(of_order, minlength=len(okey))
        assert lines.min() >= 1 and lines.max() <= 7
        assert (np.diff(t["lineitem.l_orderkey"]) >= 0).all()
        ckey = t["orders.o_custkey"]
        assert (ckey % 3 != 0).all() and ckey.min() >= 1 \
            and ckey.max() <= n["customer"]
        assert np.array_equal(t["customer.c_custkey"],
                              np.arange(1, n["customer"] + 1))
        qty = t["lineitem.l_quantity"]
        assert (qty % 100 == 0).all() and qty.min() >= 100 \
            and qty.max() <= 5000
        # o_totalprice: each line between the cheapest and the dearest
        # part at its quantity, discount and tax at their ends
        lo = np.zeros(len(okey), np.int64)
        np.add.at(lo, of_order, (qty // 100) * 90000 * 90 // 100)
        hi = np.zeros(len(okey), np.int64)
        np.add.at(hi, of_order, (qty // 100) * 209900 * 108 // 100)
        price = t["orders.o_totalprice"]
        assert (lo <= price).all() and (price <= hi).all()
        assert price.max() < 10 ** 12
        assert mod.query_bytes(cfg) == sum(
            len(t[f"{tb}.{cs[0]}"]) * cfg["row_bytes"][tb]
            for tb, cs in TABLES.items())
        # a rehearsal lowers QUANTITY so that some orders pass
        assert mod.quantity(cfg) == cfg["rehearsal_quantity"] == 200
        assert 10 < len(mod.reference(cfg, t)["o_orderkey"]) <= 100
    cfg, mod = lib.load_config("tpch-q18")
    assert mod.quantity(cfg) == 300 and mod.rows_per_query(cfg) == 6001215
    assert mod.query_bytes(cfg) == 157_371_870


def test_the_two_references_agree_on_generated_tables(generated):
    seen = 0
    for _cfg, _mod, t in generated[0]:
        for params in ({"quantity": 200}, {"quantity": 200, "limit": 3},
                       {"quantity": 150, "limit": 40}, {"quantity": 300},
                       {"quantity": 200, "having_or_equal": True}):
            a, b = _both(t, **params)
            assert a == b
            seen += len(a["o_orderkey"])
    assert seen > 300


def test_the_two_references_agree_where_keys_repeat(generated):
    _cfg, _mod, t = generated[0][0]
    t = {k: np.array(v) for k, v in t.items()}
    # every seventh order twice under another date, every fifth customer
    # twice: the joins multiply, and a group's lines count once a row
    for name, step in (("orders", 7), ("customer", 5)):
        for c in TABLES[name]:
            col = t[f"{name}.{c}"]
            t[f"{name}.{c}"] = np.concatenate(
                [col, col[::step] - (c == "o_orderdate")])
    a, b = _both(t, quantity=200, limit=1000)
    assert a == b and len(a["o_orderkey"]) >= 20
    assert len(set(zip(a["o_orderkey"], a["o_orderdate"]))) \
        > len(set(a["o_orderkey"]))
    per_key = {}
    for k, q in zip(t["lineitem.l_orderkey"], t["lineitem.l_quantity"]):
        per_key[int(k)] = per_key.get(int(k), 0) + int(q)
    assert any(s != per_key[k] for k, s in zip(a["o_orderkey"],
                                               a["sum_qty"]))


def test_the_control_differs_and_ties_are_accepted(generated):
    cfg, mod, t = generated[0][0]
    want = mod.reference(cfg, t)
    ctl = mod.control(cfg, t)
    assert ref.wrong_values(want, want) == 0
    assert ref.wrong_values(want, ctl) > 0
    full = dict(cfg, limit=10**6)
    assert len(mod.control(full, t)["o_orderkey"]) \
        > len(mod.reference(full, t)["o_orderkey"])
    # both comparisons count alike: rows tied in both keys in any order
    # and any of them at the cut, nothing else
    tied = {"c_name": ["n7", "n1", "n2", "n3", "n4"],
            "c_custkey": [7, 1, 2, 3, 4], "o_orderkey": [70, 10, 20, 30, 40],
            "o_orderdate": [5, 6, 6, 6, 6],
            "o_totalprice": [90, 50, 50, 50, 50],
            "sum_qty": [31000, 30100, 30200, 30300, 30400]}
    for got, wrong in (
            ({c: [v[0], v[3], v[1]] for c, v in tied.items()}, 0),
            ({c: [v[0], v[4], v[2]] for c, v in tied.items()}, 0),
            ({c: [v[0], v[1], v[1]] for c, v in tied.items()}, 4),
            ({c: [v[1], v[0], v[2]] for c, v in tied.items()}, 12),
            ({c: [v[0], v[1]] for c, v in tied.items()}, 6)):
        assert ref.wrong_values(got, tied, limit=3) == wrong \
            == plain.wrong_values(got, tied, limit=3)


def test_the_answer_over_the_wire_is_the_reference(generated):
    """``query``'s nine columns (the name as three words of its bytes, the
    sum as two limbs) joined again by ``compare``; an altered byte of the
    name, a key that does not fit its name and a row less are counted."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from benchmark import planrun
    from spark_rapids_jni_tpu import plan

    cfg, mod = lib.load_config("tpch-q18", 12)
    state = mod.build(cfg, mod, 11, jax.devices()[:1])
    try:
        want = mod.reference(cfg, state.host_tables(0))
        res = state.query(0, 0, lib.Spans())
        assert tuple(res) == mod.RESULT_COLUMNS
        got, nulls = planrun.plain(res)
        assert nulls == 0 and len(got["o_orderkey"]) > 10
        assert mod.compare(cfg, got, want) == {"wrong_exact_values": 0}
        assert mod.names_of([got[c] for c in mod.NAME_WORDS]) \
            == want["c_name"][:100]
        bad = dict(got, **{"c_name.w1": got["c_name.w1"] + (
            np.arange(len(got["c_name.w1"])) == 0)})
        assert mod.compare(cfg, bad, want) == {"wrong_exact_values": 1}
        bad = dict(got, c_custkey=got["c_custkey"] + 1)
        assert mod.compare(cfg, bad, want)["wrong_exact_values"] \
            >= len(got["c_custkey"])
        bad = {c: v[:-1] for c, v in got.items()}
        assert mod.compare(cfg, bad, want) == {"wrong_exact_values": 6}
    finally:
        plan.reset_plan_cache()
    assert mod.names_of(mod.name_words([]).T) == []
    assert mod.names_of(mod.name_words([7, 150000]).T) == [
        "Customer#000000007", "Customer#000150000"]


def test_agg_input_slots_reads_the_programs_counter(generated):
    counters = generated[1]
    # both aggregates take LINEITEM's slots; no join compacts
    assert read({"counters": counters}) == 2 * (1 << 13)
    assert counters["plan_cache"]["joins_compacted"] == 0
    assert counters["plan_cache"]["joins_masked"] == 3


def test_agg_input_slots_gives_nothing_without_the_counter():
    assert read({"counters": {"plan_cache": {"joins_compacted": 0}}}) is None
    assert read({"counters": {"plan_cache": None}}) is None
    assert read({"counters": {}}) is None


def test_the_metric_and_the_cell_are_declared():
    bj = lib.benchmark_json()
    (m,) = [m for m in bj["per_layer"] if m["name"] == "agg_input_slots"]
    assert m == {"name": "agg_input_slots", "unit": "count",
                 "better": "lower", "source": "program_counter",
                 "layer": "relational operators", "moves": "rows_per_s",
                 "workloads": ["tpch-q18.served"]}
    assert bj["per_layer"][-1] is m and bj["workloads"][-1]["name"] \
        == "tpch-q18.served" and bj["configs"][-1]["name"] == "tpch-q18"
    mine = {m["name"] for m in lib.metrics_of(bj, "tpch-q18.served",
                                              "per_layer")}
    assert mine == {"frontdoor_overhead_ms", "plan_lookup_ms",
                    "plan_retraces", "execute_ms", "device_roofline_share",
                    "device_idle_share", "joins_compacted",
                    "agg_rowwide_gathers", "agg_input_slots"}
    assert {m["name"] for m in lib.metrics_of(
        bj, "tpch-q18.served", "end_to_end")} == {
            "query_p50_ms", "rows_per_s", "setup_s"}
