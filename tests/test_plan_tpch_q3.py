"""TPC-H Q3 through the IR: the ordered limit and the directed sort, a join
that feeds another's build side as a row mask, the three-key group-by over a
computed ``decimal(26,4)`` column on both branches of the sort engine, and the
whole plan against the row-at-a-time reference (``tests/tpch_q3_reference.py``),
eager and jitted, in process and across the data plane."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, plan, profiler
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import (Column, ColumnBatch,
                                                  Decimal128Column)
from spark_rapids_jni_tpu.plan import ir, queries
from spark_rapids_jni_tpu.relational import aggregate as agg

from tpch_q3_reference import (RESULT_TYPES, days, sort_key,
                               tpch_q3_reference, wrong_values)

D12 = T.SparkType.decimal(12, 2)
DATE = "1995-03-15"
DAY = days(DATE)
TYPES = {"c_custkey": T.INT64, "c_mktsegment": T.INT32,
         "o_orderkey": T.INT64, "o_custkey": T.INT64, "o_orderdate": T.DATE,
         "o_shippriority": T.INT32, "l_orderkey": T.INT64,
         "l_extendedprice": D12, "l_discount": D12, "l_shipdate": T.DATE}
# the sort engine's head, cut down so that both of its branches are met
# by tables of a few thousand rows (as tests/test_sortscan_head.py does)
HEAD = 64


@pytest.fixture(autouse=True)
def _fresh():
    plan.reset_plan_cache()
    yield
    config.reset()
    plan.reset_plan_cache()


def _engines(engine):
    """``sort``: the engines every cell runs on the chip (``auto`` off the
    CPU); ``auto``: the CPU's scatter group-by and hash join."""
    if engine == "sort":
        config.set("groupby_engine", "sort")
        config.set("join_engine", "sort")


def sparse_key(i):
    return ((i >> 3) << 5) | (i & 7)


def make_tables(n_orders, seed, nulls=0.0, segments=2, spread=(-200, 50)):
    """Seeded CUSTOMER, ORDERS and LINEITEM by dbgen's rules (the
    benchmark's recipe, in numpy), the order dates drawn around DATE so that
    a fifth of the orders make a group; returns name -> {column: list}, a
    null as ``None``."""
    r = np.random.default_rng(seed)
    nc = max(n_orders // 10, 3)
    i = np.arange(1, n_orders + 1)
    okey = sparse_key(i)
    rr = r.integers(0, nc - nc // 3, n_orders)
    odate = DAY + r.integers(spread[0], spread[1], n_orders)
    oi = np.repeat(np.arange(n_orders), r.integers(1, 8, n_orders))
    nl = len(oi)
    qty, pk = r.integers(1, 51, nl), r.integers(1, 200_001, nl)
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    tables = {
        "customer": {"c_custkey": np.arange(1, nc + 1),
                     "c_mktsegment": r.integers(0, segments, nc)},
        "orders": {"o_orderkey": okey,
                   "o_custkey": 3 * (rr // 2) + 1 + rr % 2,
                   "o_orderdate": odate,
                   "o_shippriority": r.integers(0, 2, n_orders)},
        "lineitem": {"l_orderkey": okey[oi], "l_extendedprice": qty * retail,
                     "l_discount": r.integers(0, 11, nl),
                     "l_shipdate": odate[oi] + r.integers(1, 122, nl)}}
    return {t: {c: [int(x) if ok else None
                    for x, ok in zip(a, r.random(len(a)) >= nulls)]
                for c, a in cols.items()} for t, cols in tables.items()}


def to_batches(tables):
    out = {}
    for t, cols in tables.items():
        b = {}
        for c, vals in cols.items():
            dt = TYPES[c]
            data = np.asarray([0 if v is None else v for v in vals],
                              np.dtype(dt.jnp_dtype))
            b[c] = Column(jnp.asarray(data),
                          jnp.asarray([v is not None for v in vals]), dt)
        out[t] = ColumnBatch(b)
    return out


def domains(tables):
    def past(vals):
        return max(v for v in vals if v is not None) + 1

    return {"custkey_domain": past(tables["customer"]["c_custkey"]),
            "orderkey_domain": past(tables["orders"]["o_orderkey"])}


def _values(col, n):
    vals = col.to_unscaled_pylist() if isinstance(col, Decimal128Column) \
        else col.to_pylist()
    return vals[:n]


def run_plan(the_plan, inputs, mode="jit"):
    cp = plan.compile_plan(the_plan, inputs)
    if mode == "eager":
        with jax.disable_jit():
            res, n = cp(inputs)
    else:
        res, n = cp(inputs)
    n = int(n)
    assert res.num_rows == the_plan.n and n <= the_plan.n
    for c in res.columns:   # nothing lives past the count
        assert not np.asarray(c.validity)[n:].any()
    return {c: _values(res[c], n) for c in RESULT_TYPES}, res, cp


def check(tables, mode="jit", limit=10, date=DATE, **dom):
    the_plan = queries.tpch_q3_plan(1, date, limit=limit,
                                    **(dom or domains(tables)))
    got, res, cp = run_plan(the_plan, to_batches(tables), mode)
    want = tpch_q3_reference(tables["customer"], tables["orders"],
                             tables["lineitem"], 1, date, limit)
    assert wrong_values(got, want, limit) == 0, (got, want)
    assert list(res.names) == list(RESULT_TYPES)
    assert res["revenue"].dtype == T.SparkType.decimal(36, 4)
    assert isinstance(res["revenue"], Decimal128Column)
    assert res["l_orderkey"].dtype == T.INT64
    assert res["o_orderdate"].dtype == T.DATE
    return got, want, cp


def groups_of(tables, date=DATE):
    ref = tpch_q3_reference(tables["customer"], tables["orders"],
                            tables["lineitem"], 1, date, 10**9)
    return len(ref["l_orderkey"]), ref


# ---------------------------------------------------------------------------
# the IR: the ordered limit and per-key direction are part of the signature
# ---------------------------------------------------------------------------

def test_order_and_limit_are_part_of_the_signature():
    s = ir.Scan("t")
    assert ir.Sort(s, ["a", "b"]).signature() == ("Sort", s.signature(),
                                                  ("a", "b"))
    assert ir.Sort(s, ("a",)).signature() \
        != ir.Sort(s, (ir.Desc("a"),)).signature()
    assert ir.Sort(s, (ir.Desc("a"),)) == ir.Sort(s, [ir.SortOrder("a", False)])
    a = ir.TopK(s, (ir.Desc("a"), "b"), 10)
    assert a.signature() != ir.TopK(s, (ir.Desc("a"), "b"), 11).signature()
    assert a.signature() != ir.TopK(s, ("a", "b"), 10).signature()
    assert a.signature() != ir.TopK(
        s, (ir.Desc("a", nulls_first=True), "b"), 10).signature()
    assert [o.describe() for o in a.order()] == [
        "a desc nulls last", "b asc nulls first"]
    with pytest.raises(ValueError):
        ir.TopK(s, ("a",), 0)
    q3 = queries.tpch_q3_plan()
    assert isinstance(q3, ir.TopK) and q3.n == 10
    assert q3.signature() != queries.tpch_q3_plan(2).signature()
    assert q3.signature() != queries.tpch_q3_plan(
        date_iso="1995-03-16").signature()
    assert ir.scan_names(q3) == ("lineitem", "orders", "customer")
    joins = [n for n in q3.walk() if isinstance(n, ir.Join)]
    assert [j.dense_domain for j in joins] == [150_001, 6_000_001]
    assert len(config._REGISTRY) == 71   # no knob came with it


# ---------------------------------------------------------------------------
# the directed sort and the ordered limit against ``sorted``
# ---------------------------------------------------------------------------

def _order_case(n, seed):
    """int64, DATE and decimal128 columns with nulls and many ties."""
    r = np.random.default_rng(seed)
    cols = {"k": (r.integers(-3, 4, n) * (1 << 40), T.INT64),
            "d": (r.integers(9000, 9004, n), T.DATE),
            "w": (r.integers(-2, 3, n), T.SparkType.decimal(36, 4))}
    batch, host = {}, {}
    for name, (a, dt) in cols.items():
        ok = r.random(n) >= 0.2
        if name == "w":
            wide = [int(x) * 10**30 + 7 for x in a]
            batch[name] = Decimal128Column.from_unscaled(
                [w if o else None for w, o in zip(wide, ok)], 36, 4)
            host[name] = [w if o else None for w, o in zip(wide, ok)]
        else:
            batch[name] = Column(jnp.asarray(a.astype(dt.jnp_dtype)),
                                 jnp.asarray(ok), dt)
            host[name] = [int(x) if o else None for x, o in zip(a, ok)]
    batch["id"] = Column(jnp.arange(n, dtype=jnp.int32),
                         jnp.ones((n,), jnp.bool_), T.INT32)
    host["id"] = list(range(n))
    return ColumnBatch(batch), host


def _py_order(host, order):
    """Row ids in the order of ``order`` by ``sorted`` (stable)."""
    def key(i):
        out = []
        for o in order:
            v = host[o.name][i]
            first = o.resolved_nulls_first()
            rank = (0 if first else 1) if v is None else (1 if first else 0)
            out.append((rank, 0 if v is None
                        else (v if o.ascending else -v)))
        return tuple(out)

    return sorted(range(len(host["id"])), key=key)


ORDERS = {
    "desc_decimal": (ir.Desc("w"), "id"),
    "desc_decimal_nulls_first": (ir.Desc("w", nulls_first=True), "id"),
    "asc_decimal_nulls_last": (ir.SortOrder("w", True, False), "id"),
    "desc_int64_then_date": (ir.Desc("k"), "d", "id"),
    "date_desc_nulls_first_then_int64": (ir.Desc("d", True), "k", "id"),
    "three_keys_mixed": (ir.Desc("w"), "d", ir.Desc("k"), "id"),
}


@pytest.mark.parametrize("name", list(ORDERS))
def test_directed_sort_and_topk_are_sorted(name):
    batch, host = _order_case(257, 5)
    node = ir.Sort(ir.Scan("t"), ORDERS[name])
    want = _py_order(host, node.order())
    out = plan.execute(node, {"t": batch})
    assert out["id"].to_pylist() == want
    for k in (1, 10, 300):   # more than there are rows, too
        res, live = plan.execute(ir.TopK(ir.Scan("t"), ORDERS[name], k),
                                 {"t": batch})
        n = int(np.asarray(live).sum())
        assert n == min(k, 257) and res.num_rows == k
        assert res["id"].to_pylist()[:n] == want[:k]
        assert not np.asarray(res["id"].validity)[n:].any()


def test_topk_over_a_filter_selects_among_live_rows_only():
    batch, host = _order_case(300, 8)
    order = (ir.Desc("w"), "d", "id")
    node = ir.TopK(ir.Filter(ir.Scan("t"), "id", ">=", 120), order, 7)
    res, live = plan.execute(node, {"t": batch})
    want = [i for i in _py_order(host, node.order()) if i >= 120][:7]
    assert res["id"].to_pylist() == want and np.asarray(live).all()
    # no live row at all
    res, live = plan.execute(
        ir.TopK(ir.Filter(ir.Scan("t"), "id", ">=", 1000), order, 7),
        {"t": batch})
    assert not np.asarray(live).any()
    assert plan.plan_cache_metrics()["topk_sorted_rows"] == 300


def test_a_sort_on_other_keys_above_a_key_ordered_aggregate_is_not_elided():
    n = 64
    r = np.random.default_rng(2)
    ones = jnp.ones((n,), jnp.bool_)
    batch = ColumnBatch({
        "a": Column(jnp.asarray(r.integers(0, 3, n), jnp.int32), ones,
                    T.INT32),
        "b": Column(jnp.asarray(r.integers(0, 2, n), jnp.int32), ones,
                    T.INT32)})
    agg_node = ir.Aggregate(ir.Scan("t"), ("a", "b"),
                            (ir.Agg("count", None, "c"),), domain=(3, 2),
                            onehot=True)
    res, ng = plan.execute(ir.Sort(agg_node, (ir.Desc("a"), "b")),
                           {"t": batch})
    g = int(ng)
    keys = list(zip(res["a"].to_pylist()[:g], res["b"].to_pylist()[:g]))
    assert keys == sorted(keys, key=lambda k: (-k[0], k[1])) and g == 6


# ---------------------------------------------------------------------------
# the whole plan against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_q3_plan_is_the_reference_on_dbgen_tables(monkeypatch, engine, mode):
    """More groups than the head and more than ten: eagerly the 1024-slot
    branch of the ladder, jitted (the ladder cut to its head) the row-wide
    one; a third of the customers have no order, most orders no qualifying
    line."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    if mode == "jit":
        monkeypatch.setattr(agg, "_TIER_STEPS", 1)
    _engines(engine)
    tables = make_tables(520 if mode == "eager" else 1500, 3)
    g, _ref = groups_of(tables)
    assert HEAD < g <= 16 * HEAD < len(tables["lineitem"]["l_orderkey"])
    ordered = set(tables["orders"]["o_custkey"])
    assert any(c not in ordered for c in tables["customer"]["c_custkey"])
    got, _want, cp = check(tables, mode)
    assert len(got["l_orderkey"]) == 10
    d = cp.decisions
    assert d["join0:o_custkey"]["output"] == "mask"
    assert d["join1:l_orderkey"]["output"] == "mask"
    assert d["project3:revenue_term"] == {
        "type": "decimal(26,4)",
        "routes": ("add:int64:decimal(13,2)",
                   "mul_exact:limbs:decimal(26,4)")}
    assert d["topk0:revenue,o_orderdate"] == {
        "n": 10, "keys": ("revenue desc nulls last",
                          "o_orderdate asc nulls first"),
        "route": "selection"}
    assert d["aggregate0:l_orderkey,o_orderdate,o_shippriority"] == {
        "head": HEAD,
        "tiers": (HEAD,) if mode == "jit" else (HEAD, 16 * HEAD)}
    m = plan.plan_cache_metrics()
    assert m["joins_masked"] == 2 and m["joins_compacted"] == 0
    assert m["topk_sorted_rows"] == len(tables["lineitem"]["l_orderkey"])
    if engine == "sort":
        # the keys at their groups' first rows aside, one row-wide gather
        # of the measure's limbs and one of its validity
        assert m["agg_rowwide_gathers"] == 2


@pytest.mark.parametrize("engine", ["auto", "sort"])
@pytest.mark.parametrize("case", ["head", "fewer_than_ten", "exactly_ten",
                                  "none"])
def test_q3_plan_group_counts(monkeypatch, engine, case):
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    _engines(engine)
    date = DATE
    if case == "head":
        tables = make_tables(200, 4)
        g, _ = groups_of(tables)
        assert 10 < g <= HEAD
    elif case == "fewer_than_ten":
        tables = make_tables(40, 5)
        g, _ = groups_of(tables)
        assert 0 < g < 10
    elif case == "exactly_ten":
        tables = make_tables(200, 6)
        g, ref = groups_of(tables)
        drop = set(ref["l_orderkey"][3:g - 7])   # keep ten groups
        line = tables["lineitem"]
        keep = [i for i, k in enumerate(line["l_orderkey"]) if k not in drop]
        tables["lineitem"] = {c: [v[i] for i in keep]
                              for c, v in line.items()}
        g, _ = groups_of(tables)
        assert g == 10
    else:
        tables = make_tables(200, 7)
        date = "1990-01-01"                      # before every order
        g, _ = groups_of(tables, date)
        assert g == 0
    got, _want, _cp = check(tables, date=date)
    assert len(got["l_orderkey"]) == min(g, 10)


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_q3_plan_with_null_keys_and_null_measures(monkeypatch, engine, mode):
    """A null key joins nothing, a null date fails its filter, a null
    ``o_shippriority`` is a group key of its own, a null measure is skipped
    by the sum and a group of nothing else has a null revenue, which
    ``desc`` puts last."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    _engines(engine)
    tables = make_tables(400, 11, nulls=0.08)
    g, ref = groups_of(tables)
    assert g > 10 and None in ref["o_shippriority"]
    check(tables, mode)
    # few enough groups that null revenues make the cut: orders whose only
    # lines have a null price or a null discount
    tables = _tie_tables(dates=[1, 2, 3, 4, 5],
                         prices=[100_00, None, 300_00, 200_00, 50_00])
    tables["lineitem"]["l_discount"][4] = None
    got, _want, _cp = check(tables, mode)
    assert got["revenue"] == [300_00 * 100, 200_00 * 100, 100_00 * 100,
                              None, None]
    assert got["o_orderdate"][3:] == [DAY - 5, DAY - 2]


def _tie_tables(dates, prices, lines=1):
    """One BUILDING customer; order ``i`` has ``lines`` lines of
    ``prices[i]`` at no discount, all shipped after DATE."""
    n = len(dates)
    keys = [sparse_key(i + 1) for i in range(n)]
    return {
        "customer": {"c_custkey": [1, 2], "c_mktsegment": [1, 0]},
        "orders": {"o_orderkey": keys, "o_custkey": [1] * n,
                   "o_orderdate": [DAY - d for d in dates],
                   "o_shippriority": [0] * n},
        "lineitem": {"l_orderkey": [k for k in keys for _ in range(lines)],
                     "l_extendedprice": [p for p in prices
                                         for _ in range(lines)],
                     "l_discount": [0] * (n * lines),
                     "l_shipdate": [DAY + 1] * (n * lines)}}


@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_ties_in_revenue_are_broken_by_o_orderdate(engine):
    _engines(engine)
    # fourteen orders of one revenue, every date another: the ten earliest
    tables = _tie_tables(dates=[3, 9, 1, 14, 7, 2, 12, 5, 11, 4, 8, 13, 6, 10],
                         prices=[500_00] * 14, lines=2)
    got, want, _cp = check(tables)
    assert got["o_orderdate"] == sorted(got["o_orderdate"])
    assert got["o_orderdate"] == [DAY - d for d in range(14, 4, -1)]
    assert len(want["l_orderkey"]) == 10 and set(got["revenue"]) == {
        2 * 500_00 * 100}


@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_ties_in_both_keys_take_any_of_the_tied_rows(engine):
    _engines(engine)
    # four rows surely in, then nine rows equal in revenue AND date for six
    # places: the reference lists all nine, any six are an answer
    tables = _tie_tables(dates=[1, 2, 3, 4] + [5] * 9,
                         prices=[900_00, 800_00, 700_00, 600_00]
                         + [100_00] * 9)
    got, want, _cp = check(tables)
    assert len(want["l_orderkey"]) == 13 and len(got["l_orderkey"]) == 10
    assert len(set(got["l_orderkey"])) == 10
    # the comparison does refuse what is no answer: a tied row twice, a row
    # of the tie group before one surely in, a row that is not there
    bad = {c: list(v) for c, v in got.items()}
    bad["l_orderkey"][9] = bad["l_orderkey"][8]
    assert wrong_values(bad, want) > 0
    bad = {c: list(v) for c, v in got.items()}
    for c in bad:
        bad[c][3], bad[c][4] = bad[c][4], bad[c][3]
    assert wrong_values(bad, want) > 0
    bad = {c: list(v) for c, v in got.items()}
    bad["l_orderkey"][0] += 1
    assert wrong_values(bad, want) > 0
    assert wrong_values({c: v[:9] for c, v in got.items()}, want) == 4


@pytest.mark.parametrize("engine", ["auto", "sort"])
@pytest.mark.parametrize("case", ["outside_the_domain", "duplicated"])
def test_the_general_join_branch_gives_the_same_rows(monkeypatch, engine,
                                                     case):
    """Build keys the dense branch cannot take (past the stated domain, or
    twice) send both joins through the general engine inside the same
    program: the same rows."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    _engines(engine)
    tables = make_tables(300, 13)
    dom = domains(tables)
    if case == "outside_the_domain":
        dom = {k: v // 2 for k, v in dom.items()}
    else:
        # every twentieth order twice, under another date; a customer twice
        orders = tables["orders"]
        for i in range(0, 300, 20):
            for c in orders:
                orders[c].append(orders[c][i] - (c == "o_orderdate"))
        for c, v in tables["customer"].items():
            v.append(v[0])
        keys = orders["o_orderkey"]
        assert len(set(keys)) < len(keys)
    g, _ = groups_of(tables)
    assert g > 10
    check(tables, **dom)


def test_a_join_that_is_anothers_build_child_hands_on_a_mask():
    """With no exchange between them the first join's consumer is the second
    join's build side, which takes ``right_valid``: no compaction."""
    tables = make_tables(200, 17)
    dom = domains(tables)
    q3 = queries.tpch_q3_plan(1, DATE, **dom)
    orders = ir.Join(ir.Filter(ir.Scan("orders"), "o_orderdate", "<",
                               ir.DateLit(DATE)),
                     ir.Filter(ir.Scan("customer"), "c_mktsegment", "==", 1),
                     "o_custkey", "c_custkey",
                     dense_domain=dom["custkey_domain"])
    joined = ir.Join(ir.Filter(ir.Scan("lineitem"), "l_shipdate", ">",
                               ir.DateLit(DATE)), orders,
                     "l_orderkey", "o_orderkey",
                     dense_domain=dom["orderkey_domain"])
    terms = ir.Project(joined, (
        "l_orderkey", "o_orderdate", "o_shippriority",
        ("revenue_term", ir.Col("l_extendedprice")
         * (1 - ir.Col("l_discount")))))
    direct = ir.TopK(ir.Aggregate(
        terms, ("l_orderkey", "o_orderdate", "o_shippriority"),
        (ir.Agg("sum", "revenue_term", "revenue"),)),
        (ir.Desc("revenue"), "o_orderdate"), 10)
    inputs = to_batches(tables)
    got, _res, cp = run_plan(direct, inputs)
    assert cp.decisions["join0:o_custkey"]["output"] == "mask"
    assert cp.decisions["join1:l_orderkey"]["output"] == "mask"
    assert plan.plan_cache_metrics()["joins_compacted"] == 0
    want, _res, _cp = run_plan(q3, inputs)
    assert got == want


def test_q3_scopes_start_at_their_own_plan_node(monkeypatch):
    head = 8   # three widths of these few hundred rows: 8, 128, every row
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", head)
    _engines("sort")
    tables = make_tables(100, 19)
    inputs = to_batches(tables)
    cp = plan.compile_plan(queries.tpch_q3_plan(1, DATE, **domains(tables)),
                           inputs)
    text = cp.fn.lower({n: inputs[n] for n in cp.input_names},
                       ()).as_text(debug_info=True)
    paths = {profiler.scope_path(m)
             for m in re.findall(r'"(jit\(run\)[^"]*)"', text)}
    heads = {p.split("/")[0] for p in paths if p}
    assert heads == {
        "plan.filter.c_mktsegment", "plan.filter.o_orderdate",
        "plan.filter.l_shipdate", "plan.join.c_custkey",
        "plan.exchange.o_orderkey", "plan.exchange.l_orderkey",
        "plan.join.o_orderkey", "plan.project.revenue_term",
        "plan.aggregate.l_orderkey", "plan.topk"}
    assert {"plan.topk/topk.select", "plan.topk/topk.gather",
            "plan.project.revenue_term/expr.mul_exact",
            "plan.join.o_orderkey/join.gather_right",
            "plan.aggregate.l_orderkey/agg.sortscan_sort"} <= paths
    assert 16 * head < len(tables["lineitem"]["l_orderkey"]) < 256 * head
    for branch in ("agg.sortscan_head", f"agg.sortscan_tier.{16 * head}",
                   "agg.sortscan_full"):
        assert any(p.startswith("plan.aggregate.l_orderkey/"
                                f"agg.sortscan_reduce/{branch}")
                   for p in paths), branch


def test_q3_over_the_serving_runtime_and_the_data_plane():
    """The hop the served cell's limb hand-off skips: the plan runs as a
    session of a ``ServeRuntime``, its ten rows cross as the worker ships
    them (one Arrow IPC stream, chunk CRCs in a descriptor) and are decoded
    as the supervisor does; ``revenue`` arrives as Arrow ``decimal128`` with
    limbs, nulls and type intact."""
    from spark_rapids_jni_tpu import mem
    from spark_rapids_jni_tpu.columnar.arrow import batch_to_ipc, ipc_to_batch
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.serve import ServeRuntime
    from spark_rapids_jni_tpu.serve import data_plane as dp

    tables = make_tables(300, 23, nulls=0.05)
    inputs = to_batches(tables)
    the_plan = queries.tpch_q3_plan(1, DATE, **domains(tables))
    want = tpch_q3_reference(tables["customer"], tables["orders"],
                             tables["lineitem"])

    def query(ctx):
        res, n = plan.execute(the_plan, inputs, ctx=ctx)
        n = int(n)
        return jax.tree_util.tree_map(lambda a: a[:n], res)

    RmmSpark.set_event_handler(64 << 20, host_pool_bytes=8 << 20,
                               poll_ms=10.0)
    mem.install_spill_framework()
    try:
        rt = ServeRuntime(max_concurrent=2, task_id_base=63_000)
        try:
            sessions = [rt.submit(query, est_bytes=1 << 20, tenant=t)
                        for t in ("tenant-a", "tenant-b")]
            answers = [s.result(timeout=120.0) for s in sessions]
        finally:
            assert rt.shutdown()
    finally:
        mem.shutdown_spill_framework()
        RmmSpark.clear_event_handler()
    for batch in answers:
        buf, fp = batch_to_ipc(batch)
        desc = dp.build_descriptor("frames", "seg-q3", len(buf), fp,
                                   1 << 12, dp.chunk_crcs(buf, 1 << 12),
                                   epoch=3)
        dp.verify_epoch(desc, 3)
        dp.verify_chunks(buf, desc)
        back = ipc_to_batch(buf, expect_fingerprint=fp)
        assert dp.batch_digest(back) == dp.batch_digest(batch)
        assert back.num_rows == 10
        assert isinstance(back["revenue"], Decimal128Column)
        assert back["revenue"].dtype == T.SparkType.decimal(36, 4)
        assert np.array_equal(np.asarray(back["revenue"].limbs),
                              np.asarray(batch["revenue"].limbs))
        got = {c: _values(back[c], 10) for c in RESULT_TYPES}
        assert wrong_values(got, want) == 0
        keys = [sort_key(r, d) for r, d in zip(got["revenue"],
                                               got["o_orderdate"])]
        assert keys == sorted(keys)
