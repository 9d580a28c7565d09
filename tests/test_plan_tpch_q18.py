"""TPC-H Q18 through the IR: ``HAVING`` (a Filter above an Aggregate) over a
``decimal(22,2)`` sum, the ``IN`` subquery as a semi join on the dense path
and on the general one, an Aggregate's output as a build side, the four-key
group-by and the top-100, and the whole plan against the row-at-a-time
reference (``tests/tpch_q18_reference.py``), eager and jitted."""

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
from spark_rapids_jni_tpu.relational.join import (hash_join,
                                                  join_dense_or_hash)

from tpch_q18_reference import (COLUMNS, RESULT_TYPES, customer_name,
                                sort_key, tpch_q18_reference, wrong_values)

D12 = T.SparkType.decimal(12, 2)
TYPES = {"c_custkey": T.INT64, "o_orderkey": T.INT64, "o_custkey": T.INT64,
         "o_orderdate": T.DATE, "o_totalprice": D12, "l_orderkey": T.INT64,
         "l_quantity": D12}
# the sort engine's head, cut down so that the branches of its ladder are
# met by tables of a few thousand rows (as tests/test_sortscan_head.py does)
HEAD = 64
QUANTITY = 200   # one order in twenty of these small tables passes


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


def make_tables(n_orders, seed, nulls=0.0):
    """Seeded CUSTOMER, ORDERS and LINEITEM by dbgen's rules (the
    benchmark's recipe, in numpy; ``o_totalprice`` drawn, not added up);
    returns name -> {column: list}, a null as ``None``."""
    r = np.random.default_rng(seed)
    nc = max(n_orders // 10, 3)
    okey = sparse_key(np.arange(1, n_orders + 1))
    rr = r.integers(0, nc - nc // 3, n_orders)
    oi = np.repeat(np.arange(n_orders), r.integers(1, 8, n_orders))
    tables = {
        "customer": {"c_custkey": np.arange(1, nc + 1)},
        "orders": {"o_orderkey": okey,
                   "o_custkey": 3 * (rr // 2) + 1 + rr % 2,
                   "o_orderdate": r.integers(8036, 10441, n_orders),
                   "o_totalprice": r.integers(90000, 60000000, n_orders)},
        "lineitem": {"l_orderkey": okey[oi],
                     "l_quantity": 100 * r.integers(1, 51, len(oi))}}
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
    got = {c: _values(res[c], n) for c in COLUMNS if c != "c_name"}
    # c_name is the key's text: whoever presents the rows writes it
    got["c_name"] = [customer_name(k) for k in got["c_custkey"]]
    return got, res, cp


def check(tables, mode="jit", quantity=QUANTITY, limit=100, **dom):
    the_plan = queries.tpch_q18_plan(quantity, limit=limit,
                                     **(dom or domains(tables)))
    got, res, cp = run_plan(the_plan, to_batches(tables), mode)
    want = tpch_q18_reference(tables["customer"], tables["orders"],
                              tables["lineitem"], quantity, limit)
    assert wrong_values(got, want, limit) == 0, (got, want)
    assert list(res.names) == [c for c in RESULT_TYPES if c != "c_name"]
    assert isinstance(res["sum_qty"], Decimal128Column)
    assert res["sum_qty"].dtype == T.SparkType.decimal(22, 2)
    assert res["o_totalprice"].dtype == D12
    assert res["c_custkey"].dtype == res["o_orderkey"].dtype == T.INT64
    assert res["o_orderdate"].dtype == T.DATE
    keys = [sort_key(p, d) for p, d in zip(got["o_totalprice"],
                                           got["o_orderdate"])]
    assert keys == sorted(keys)
    return got, want, cp


def groups_of(tables, quantity=QUANTITY):
    ref = tpch_q18_reference(tables["customer"], tables["orders"],
                             tables["lineitem"], quantity, 10**9)
    return len(ref["o_orderkey"]), ref


# ---------------------------------------------------------------------------
# the whole plan against the plain reference
# ---------------------------------------------------------------------------

def test_the_plan_is_data_and_its_parameters_are_part_of_the_signature():
    q18 = queries.tpch_q18_plan()
    assert isinstance(q18, ir.TopK) and q18.n == 100
    assert q18.signature() != queries.tpch_q18_plan(301).signature()
    assert q18.signature() != queries.tpch_q18_plan(limit=99).signature()
    assert ir.scan_names(q18) == ("lineitem", "orders", "customer")
    joins = [n for n in q18.walk() if isinstance(n, ir.Join)]
    assert [(j.how, j.dense_domain) for j in joins] == [
        ("semi", 6_000_001), ("inner", 150_001), ("inner", 6_000_001)]
    having = [n for n in q18.walk() if isinstance(n, ir.Filter)]
    assert len(having) == 1 and isinstance(having[0].child, ir.Aggregate)
    assert having[0].value == ir.Lit(300) and having[0].op == ">"
    assert len(config._REGISTRY) == 71   # no knob came with it


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_q18_plan_is_the_reference_on_dbgen_tables(monkeypatch, engine, mode):
    """Every order a group of the first aggregate (more than any width of
    the ladder short of the rows: the row-wide fetch), a few dozen of the
    second; a third of the customers have no order."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    monkeypatch.setattr(agg, "_TIER_STEPS", 1)
    _engines(engine)
    tables = make_tables(200 if mode == "eager" else 900, 3)
    rows = len(tables["lineitem"]["l_orderkey"])
    g, _ref = groups_of(tables)
    assert 5 < g <= HEAD < len(tables["orders"]["o_orderkey"]) < rows
    got, _want, cp = check(tables, mode)
    assert len(got["o_orderkey"]) == g
    d = cp.decisions
    assert d["join0:o_orderkey"]["how"] == "semi"
    assert d["join0:o_orderkey"]["output"] == "mask"
    assert d["join1:o_custkey"] == {"strategy": "shuffled",
                                    "build_rows": None, "output": "mask"}
    assert d["join2:l_orderkey"]["output"] == "mask"
    assert d["topk0:o_totalprice,o_orderdate"] == {
        "n": 100, "keys": ("o_totalprice desc nulls last",
                           "o_orderdate asc nulls first"),
        "route": "selection"}
    # a semi join by mask hands on its left child's rows: both aggregates
    # know theirs and keep their ladders
    for name in ("aggregate0:l_orderkey",
                 "aggregate1:c_custkey,o_orderkey,o_orderdate,o_totalprice"):
        assert d[name] == {"head": HEAD, "tiers": (HEAD,)}
    m = plan.plan_cache_metrics()
    assert m["joins_masked"] == 3 and m["joins_compacted"] == 0
    assert m["agg_input_slots"] == 2 * rows
    assert m["topk_sorted_rows"] == rows
    if engine == "sort":
        # the first aggregate reads its grouped rows in place; the second
        # moves its measure's limbs and validity through its sort
        assert m["agg_rowwide_gathers"] == 2


@pytest.mark.parametrize("engine", ["auto", "sort"])
@pytest.mark.parametrize("case", ["exactly_quantity", "none", "cut",
                                  "ties_at_the_cut"])
def test_q18_plan_having_and_the_cut(engine, case):
    _engines(engine)
    n = 12
    keys = [sparse_key(i + 1) for i in range(n)]
    # order i has four lines; 0..5 sum past 200, 6 to exactly 200, the
    # rest under it
    qty = [[60, 60, 60, 21 + i] for i in range(6)] + [[50, 50, 50, 50]] \
        + [[10, 20, 30, 40]] * (n - 7)
    tables = {
        "customer": {"c_custkey": [1, 2, 3]},
        "orders": {"o_orderkey": keys, "o_custkey": [1 + i % 2
                                                     for i in range(n)],
                   "o_orderdate": [9000 + i for i in range(n)],
                   "o_totalprice": [1000_00 * (i + 1) for i in range(n)]},
        "lineitem": {"l_orderkey": [k for k in keys for _ in range(4)],
                     "l_quantity": [100 * q for qs in qty for q in qs]}}
    quantity, limit = 200, 100
    if case == "exactly_quantity":
        got, _want, _cp = check(tables, quantity=quantity)
        assert got["o_orderkey"] == keys[5::-1]      # the seventh stays out
        assert got["sum_qty"] == [100 * (201 + i) for i in range(5, -1, -1)]
        assert got["c_name"][0] == "Customer#000000002"
        # ... and with >= it would be in: the comparison refuses that
        ctl = tpch_q18_reference(tables["customer"], tables["orders"],
                                 tables["lineitem"], quantity, limit,
                                 having_or_equal=True)
        assert ctl["o_orderkey"] == keys[6::-1]
        assert wrong_values(got, ctl, limit) > 0
    elif case == "none":
        got, want, _cp = check(tables, quantity=300)
        assert got["o_orderkey"] == [] == want["o_orderkey"]
    elif case == "cut":
        got, _want, _cp = check(tables, quantity=quantity, limit=4)
        assert got["o_orderkey"] == keys[5:1:-1]
    else:
        # the last four of the six equal in price AND date: two places
        for i in (0, 1, 2, 3):
            tables["orders"]["o_totalprice"][i] = 1000_00
            tables["orders"]["o_orderdate"][i] = 9000
        got, want, _cp = check(tables, quantity=quantity, limit=4)
        assert len(want["o_orderkey"]) == 6 and len(got["o_orderkey"]) == 4
        assert got["o_orderkey"][:2] == keys[5:3:-1]
        assert set(got["o_orderkey"][2:]) < set(keys[:4])
        bad = {c: list(v) for c, v in got.items()}
        bad["o_orderkey"][3] = bad["o_orderkey"][2]   # a tied row twice
        assert wrong_values(bad, want, 4) > 0


@pytest.mark.parametrize("engine,mode", [("auto", "jit"), ("sort", "jit"),
                                         ("sort", "eager")])
def test_q18_plan_with_null_keys_and_null_measures(monkeypatch, engine, mode):
    """A null key joins nothing and is in no subquery's answer, a null
    quantity is skipped by both sums, a null date or price is a group key
    like any other and sorts where Spark puts it."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    _engines(engine)
    tables = make_tables(300, 11, nulls=0.06)
    g, ref = groups_of(tables, 150)
    assert g > 10 and None in ref["o_totalprice"] + ref["o_orderdate"]
    check(tables, mode, quantity=150)


@pytest.mark.parametrize("engine", ["auto", "sort"])
@pytest.mark.parametrize("case", ["outside_the_domain", "duplicated"])
def test_the_general_join_branches_give_the_same_rows(monkeypatch, engine,
                                                      case):
    """Build keys the dense branch cannot take send the joins through the
    general engine inside the same program (the semi join as
    ``hash_join(..., "semi")`` with a prefix ``live``): the same rows."""
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", HEAD)
    _engines(engine)
    tables = make_tables(250, 13)
    dom = domains(tables)
    want_dense, _res, _cp = run_plan(
        queries.tpch_q18_plan(QUANTITY, **dom), to_batches(tables))
    if case == "outside_the_domain":
        dom = {k: v // 2 for k, v in dom.items()}
    else:
        # every twentieth order twice, under another date; a customer twice
        orders = tables["orders"]
        for i in range(0, 250, 20):
            for c in orders:
                orders[c].append(orders[c][i] - (c == "o_orderdate"))
        for c, v in tables["customer"].items():
            v.append(v[0])
    g, _ = groups_of(tables)
    assert g > 10
    got, _want, _cp = check(tables, **dom)
    if case == "outside_the_domain":
        assert got == want_dense


def test_q18_scopes_and_counters(monkeypatch):
    monkeypatch.setattr(agg, "_DEFAULT_GROUP_SLOTS", 8)
    _engines("sort")
    tables = make_tables(100, 19)
    inputs = to_batches(tables)
    cp = plan.compile_plan(queries.tpch_q18_plan(QUANTITY, **domains(tables)),
                           inputs)
    text = cp.fn.lower({n: inputs[n] for n in cp.input_names},
                       ()).as_text(debug_info=True)
    paths = {profiler.scope_path(m)
             for m in re.findall(r'"(jit\(run\)[^"]*)"', text)}
    heads = {p.split("/")[0] for p in paths if p}
    assert heads == {
        "plan.aggregate.l_orderkey", "plan.filter.sum_qty",
        "plan.join.l_orderkey", "plan.join.c_custkey",
        "plan.exchange.o_orderkey", "plan.exchange.l_orderkey",
        "plan.join.o_orderkey", "plan.aggregate.c_custkey", "plan.topk"}
    assert {"plan.join.l_orderkey/join.dense_check",
            "plan.join.l_orderkey/join.dense_semi",
            "plan.join.c_custkey/join.dense_probe",
            "plan.join.o_orderkey/join.gather_right",
            "plan.aggregate.c_custkey/agg.sortscan_sort"} <= paths
    # the semi lookup builds no rowid table and fetches nothing
    assert not any(p.startswith("plan.join.l_orderkey/join.dense_build")
                   or p.startswith("plan.join.l_orderkey/join.dense_rowid")
                   or p.startswith("plan.join.l_orderkey/join.gather_right")
                   for p in paths)
    for node in ("plan.aggregate.l_orderkey", "plan.aggregate.c_custkey"):
        assert any(p.startswith(f"{node}/agg.sortscan_reduce/"
                                "agg.sortscan_full") for p in paths), node


# ---------------------------------------------------------------------------
# beside it: HAVING, semi and anti on the dense path, an aggregate as a
# build side, and what a group count may not feed
# ---------------------------------------------------------------------------

def _fact(n=300, seed=7, groups=12):
    r = np.random.default_rng(seed)
    ones = jnp.ones((n,), jnp.bool_)
    return ColumnBatch({
        "k": Column(jnp.asarray(r.integers(0, groups, n), jnp.int32), ones,
                    T.INT32),
        "v": Column(jnp.asarray(r.integers(-5, 50, n), jnp.int64),
                    jnp.asarray(r.random(n) > 0.1), T.INT64),
        "d": Column(jnp.asarray(50 * r.integers(-60, 61, n), jnp.int64),
                    jnp.asarray(r.random(n) > 0.1), D12)})


def _py_groups(batch):
    out = {}
    for k, v, d in zip(batch["k"].to_pylist(), batch["v"].to_pylist(),
                       batch["d"].to_pylist()):
        g = out.setdefault(k, {"v": [], "d": [], "n": 0})
        g["n"] += 1
        g["v"] += [] if v is None else [v]
        g["d"] += [] if d is None else [d]
    return out


_PY_OPS = {"<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
           ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
           "==": lambda a, b: a == b, "!=": lambda a, b: a != b}


@pytest.mark.parametrize("op", list(_PY_OPS))
@pytest.mark.parametrize("output", ["onehot", "sort", "fused_exchange"])
def test_a_filter_above_an_aggregate_is_having(op, output):
    """The group count in front becomes a row mask, and with the predicate
    a scattered one: on the one-hot engine's output, on the sort engine's
    and on that of an aggregate fused with its exchange; the next consumer
    (an ordered limit) sees the passing groups only."""
    config.set("groupby_engine", "sort")
    batch = _fact()
    child = ir.Scan("t")
    if output == "fused_exchange":
        child = ir.Exchange(child, "k")
    node = ir.Aggregate(child, ("k",), (ir.Agg("sum", "v", "s"),
                                         ir.Agg("count", None, "c")),
                        **({"domain": 12, "onehot": True}
                           if output == "onehot" else {}))
    sums = {k: sum(g["v"]) for k, g in _py_groups(batch).items()}
    bar = sorted(sums.values())[5]   # one group's own sum: == finds it
    having = ir.TopK(ir.Filter(node, "s", op, bar), ("k",), 16)
    res, live = plan.execute(having, {"t": batch})
    n = int(np.asarray(live).sum())
    want = sorted(k for k, s in sums.items() if _PY_OPS[op](s, bar))
    assert 0 < len(want) < 12
    assert res["k"].to_pylist()[:n] == want
    # at the root the mask itself comes out, over the aggregate's slots
    out, mask = plan.execute(ir.Filter(node, "s", op, bar), {"t": batch})
    mask = np.asarray(mask)
    assert mask.shape == (out.num_rows,) and mask.sum() == len(want)
    assert sorted(np.asarray(out["k"].data)[mask].tolist()) == want


@pytest.mark.parametrize("op", list(_PY_OPS))
@pytest.mark.parametrize("storage", ["decimal64", "decimal128"])
def test_a_decimal_column_compares_with_an_exact_literal(op, storage):
    """A scanned ``decimal(12,2)`` in 64-bit storage, and the
    ``decimal(22,2)`` sum of one (128-bit limbs, some sums negative),
    against literals of scale 0, 1 and 2: the literal is brought to the
    column's scale."""
    config.set("groupby_engine", "sort")
    batch = _fact()
    sums = sorted(sum(g["d"]) for g in _py_groups(batch).values())
    assert sums[2] < 0 < sums[8] and sums[2] % 10 == 0
    lits = ((ir.Lit(12), 1200), (ir.Lit(-25, 1), -250)) \
        if storage == "decimal64" else (
            (ir.Lit(sums[8], 2), sums[8]), (ir.Lit(sums[2] // 10, 1), sums[2]))
    for lit, unscaled in lits:
        if storage == "decimal64":
            _out, mask = plan.execute(ir.Filter(ir.Scan("t"), "d", op, lit),
                                      {"t": batch})
            want = [d is not None and _PY_OPS[op](d, unscaled)
                    for d in batch["d"].to_pylist()]
            assert np.asarray(mask).tolist() == want and any(want)
        else:
            node = ir.Aggregate(ir.Scan("t"), ("k",),
                                (ir.Agg("sum", "d", "s"),))
            out, mask = plan.execute(ir.Filter(node, "s", op, lit),
                                     {"t": batch})
            assert out["s"].dtype == T.SparkType.decimal(22, 2)
            want = sorted(k for k, g in _py_groups(batch).items()
                          if g["d"] and _PY_OPS[op](sum(g["d"]), unscaled))
            got = np.asarray(out["k"].data)[np.asarray(mask)].tolist()
            assert sorted(got) == want and want
    with pytest.raises(NotImplementedError):   # a finer literal
        plan.execute(ir.Filter(ir.Scan("t"), "d", op, ir.Lit(5, 3)),
                     {"t": batch})


def _join_sides(seed, domain=40):
    """Left and right with null keys, keys outside the domain on the left,
    repeated keys on the right, and dead rows on either side."""
    r = np.random.default_rng(seed)
    nl, nr = 120, 50

    def col(a, ok, t=T.INT64):
        return Column(jnp.asarray(a.astype(t.jnp_dtype)), jnp.asarray(ok), t)

    left = ColumnBatch({
        "k": col(r.integers(-3, domain + 5, nl), r.random(nl) > 0.1),
        "v": col(np.arange(nl), np.ones(nl, bool))})
    right = ColumnBatch({
        "k": col(r.integers(0, domain, nr), r.random(nr) > 0.1),
        "w": col(np.arange(nr), np.ones(nr, bool))})
    return left, right, jnp.asarray(r.random(nl) > 0.2), \
        jnp.asarray(r.random(nr) > 0.2)


@pytest.mark.parametrize("how", ["semi", "anti"])
@pytest.mark.parametrize("valid", ["both_dead_rows", "left_dead_rows",
                                   "right_dead_rows", "none_dead"])
@pytest.mark.parametrize("branch", ["dense", "general"])
def test_semi_and_anti_on_the_dense_path_are_hash_joins(how, valid, branch):
    left, right, lv, rv = _join_sides(5)
    lv = lv if valid in ("both_dead_rows", "left_dead_rows") else None
    rv = rv if valid in ("both_dead_rows", "right_dead_rows") else None
    # a build key outside the stated domain forces the general branch
    domain = 40 if branch == "dense" else 20
    want, nw = hash_join(left, right, ["k"], ["k"], how, left_valid=lv,
                         right_valid=rv)
    nw = int(nw)
    got, ng = jax.jit(lambda a, b, x, y: join_dense_or_hash(
        a, b, "k", "k", domain, how, left_valid=x, right_valid=y))(
            left, right, lv, rv)
    assert int(ng) == nw > 0 and got.names == want.names == ("k", "v")
    for c in got.names:   # compacted: bit-identical, the null tail too
        assert got[c].to_pylist() == want[c].to_pylist()
    out, live = jax.jit(lambda a, b, x, y: join_dense_or_hash(
        a, b, "k", "k", domain, how, left_valid=x, right_valid=y,
        compact=False))(left, right, lv, rv)
    live = np.asarray(live)
    assert out.names == ("k", "v") and live.sum() == nw
    assert sorted(np.asarray(out["v"].data)[live].tolist()) \
        == sorted(want["v"].to_pylist()[:nw])
    if branch == "dense":   # the left rows where they were
        assert np.asarray(out["v"].data).tolist() == list(range(120))
        if how == "anti":   # a live null key matches nothing and stays
            keep = np.asarray(~left["k"].validity) \
                & (np.ones(120, bool) if lv is None else np.asarray(lv))
            assert keep.any() and live[keep].all()


@pytest.mark.parametrize("how", ["inner", "semi", "anti"])
def test_an_aggregates_output_is_a_build_side(how):
    """The build side's ``right_valid`` is the group count as a mask: the
    null slots past the groups match nothing."""
    config.set("groupby_engine", "sort")
    batch = _fact(groups=12)
    r = np.random.default_rng(3)
    probe = ColumnBatch({
        "pk": Column(jnp.asarray(r.integers(0, 20, 64), jnp.int32),
                     jnp.asarray(r.random(64) > 0.1), T.INT32),
        "id": Column(jnp.arange(64, dtype=jnp.int32),
                     jnp.ones((64,), jnp.bool_), T.INT32)})
    sums = ir.Aggregate(ir.Scan("t"), ("k",), (ir.Agg("sum", "v", "s"),))
    for build in (sums, ir.Filter(sums, "s", ">", 500)):
        node = ir.Sort(ir.Join(ir.Scan("p"), build, "pk", "k", how=how,
                               dense_domain=12), ("id",))
        out, live = plan.execute(node, {"t": batch, "p": probe})
        n = int(np.asarray(live).sum())
        groups = {k: sum(g["v"]) for k, g in _py_groups(batch).items()}
        if build is not sums:
            groups = {k: s for k, s in groups.items() if s > 500}
        want = []
        for pk, i in zip(probe["pk"].to_pylist(), probe["id"].to_pylist()):
            hit = pk is not None and pk in groups
            if hit and how != "anti":
                want.append((i, groups[pk]))
            elif not hit and how == "anti":
                want.append((i, None))
        assert out["id"].to_pylist()[:n] == [i for i, _s in want]
        if how == "inner":
            assert out["s"].to_pylist()[:n] == [s for _i, s in want]
        else:
            assert "s" not in out.names


@pytest.mark.parametrize("node", ["exchange", "join_probe", "aggregate"])
def test_a_group_count_where_a_row_mask_is_needed_is_a_type_error(node):
    """A Join's probe side straight above an Aggregate would and a count
    with a mask: refused, by name.  An Exchange or an Aggregate above one
    (a group-by over a group-by, TPC-H Q13) takes the group count as the
    mask of the rows in front: the same rows as a Filter that keeps every
    group makes."""
    batch = _fact()
    sums = ir.Aggregate(ir.Scan("t"), ("k",), (ir.Agg("sum", "v", "s"),))
    make = {"exchange": lambda f: ir.Exchange(f, "k"),
            "join_probe": lambda f: ir.Join(f, ir.Scan("t"), "k", "k"),
            "aggregate": lambda f: ir.Aggregate(
                f, ("s",), (ir.Agg("count", None, "c"),))}[node]
    # a Filter above the Aggregate makes a mask that every node takes
    every = make(ir.Filter(sums, "k", ">=", 0))
    want = plan.execute(every, {"t": batch})
    if node == "join_probe":
        with pytest.raises(TypeError, match="output of an Aggregate"):
            plan.execute(make(sums), {"t": batch})
        return
    got = plan.execute(make(sums), {"t": batch})
    if node == "exchange":   # (batch, live): the same live rows
        def rows(out):
            b, live = out
            live = np.asarray(live)
            return sorted(zip(*(
                [v for v, ok in zip(b[c].to_pylist(), live) if ok]
                for c in b.names)), key=repr)

        assert rows(got) == rows(want)
    else:                    # (result, num_groups): the same groups
        def groups(out):
            res, n = out
            return sorted(zip(res["s"].to_pylist()[:int(n)],
                              res["c"].to_pylist()[:int(n)]), key=repr)

        assert groups(got) == groups(want)


# ---------------------------------------------------------------------------
# keys of many words: the sort takes the spans of the live values
# ---------------------------------------------------------------------------

def _wide_key_batch(seed, n, spans, dead):
    """Four keys (int64, int64, DATE, decimal(12,2): eight packed words by
    their types) with nulls; ``spans="narrow"`` as TPC-H's are (84 bits of
    span), ``"wide"`` past what three words hold."""
    r = np.random.default_rng(seed)
    far = (1 << 40) if spans == "wide" else 1

    def col(a, t):
        return Column(jnp.asarray(a.astype(t.jnp_dtype)),
                      jnp.asarray(r.random(n) > 0.1), t)

    batch = ColumnBatch({
        "a": col(r.integers(-3, 4, n) * far * 1000, T.INT64),
        "b": col(r.integers(0, 5, n) * far - 7, T.INT64),
        "c": col(r.integers(9000, 9003, n), T.DATE),
        "d": col(r.integers(-2, 3, n) * far * 10, D12),
        "v": col(r.integers(-50, 50, n), T.INT64)})
    return batch, (jnp.asarray(r.random(n) > 0.3) if dead else None)


@pytest.mark.parametrize("dead", [False, True], ids=["all_live", "dead_rows"])
@pytest.mark.parametrize("spans", ["narrow", "wide"])
def test_keys_of_many_words_sort_by_their_spans_or_word_by_word(monkeypatch,
                                                                spans, dead):
    """The span-packed sort (three words and the row id), and the
    word-by-word passes it falls back to where the spans do not fit, give
    the groups and the order of the sort over all eight type-wide words."""
    from spark_rapids_jni_tpu.relational import keys as K
    from spark_rapids_jni_tpu.relational.aggregate import AggSpec, group_by

    batch, live = _wide_key_batch(7, 600, spans, dead)
    names = ["a", "b", "c", "d"]
    packed, fits = K.span_packed_keys(
        [batch[c] for c in names],
        live=jnp.ones((600,), jnp.bool_) if live is None else live,
        words=3, equality=True)
    assert bool(fits) == (spans == "narrow") and len(packed) == 3
    assert len(K.packed_radix_keys([batch[c] for c in names],
                                   equality=True)) == 8

    def run():
        res, ng = jax.jit(lambda b, l: group_by(
            b, names, [AggSpec("sum", "v", "s"), AggSpec("count", None, "n")],
            row_valid=l, engine="sort"))(batch, live)
        return {c: res[c].to_pylist()[:int(ng)] for c in res.names}

    got = run()
    monkeypatch.setattr(agg, "_WIDE_KEY_WORDS", 100)   # all eight words
    want = run()
    assert got == want and len(got["a"]) > 100
    # ... and plain Python agrees on the groups and their sums
    rows = zip(*(batch[c].to_pylist() for c in names + ["v"]),
               [True] * 600 if live is None else np.asarray(live).tolist())
    sums = {}
    for *key, v, ok in rows:
        if ok:
            sums.setdefault(tuple(key), []).append(v)
    assert len(sums) == len(got["a"])
    for *key, s, cnt in zip(*(got[c] for c in names + ["s", "n"])):
        vals = sums[tuple(key)]
        live_vals = [v for v in vals if v is not None]
        assert cnt == len(vals)
        assert s == (sum(live_vals) if live_vals else None)


def test_span_packed_keys_keep_order_and_equality():
    from spark_rapids_jni_tpu.relational import keys as K

    batch, live = _wide_key_batch(3, 300, "narrow", True)
    cols = [batch[c] for c in ("a", "b", "c", "d")]
    packed, fits = K.span_packed_keys(cols, live=live, words=3,
                                      equality=True)
    assert bool(fits)
    full = K.batch_radix_keys(cols, equality=True, nulls_first=True)
    ok = np.asarray(live)
    p = np.stack([np.asarray(w) for w in packed], 1)[ok].tolist()
    f = np.stack([np.asarray(w) for w in full], 1)[ok].tolist()
    order_p = sorted(range(len(p)), key=lambda i: (p[i], i))
    order_f = sorted(range(len(f)), key=lambda i: (f[i], i))
    assert order_p == order_f
    assert [p[i] == p[j] for i, j in zip(order_p, order_p[1:])] \
        == [f[i] == f[j] for i, j in zip(order_f, order_f[1:])]
    # a string key has no span: the caller keeps its type-wide words
    from spark_rapids_jni_tpu.columnar.column import StringColumn
    s = StringColumn.from_pylist(["a", "bb"], max_len=12)
    assert K.span_packed_keys([s], live=jnp.ones((2,), jnp.bool_), words=3,
                              equality=True) is None


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["assume_grouped", "sorting_path"])
def test_a_decimal_sum_whose_lane_scans_pass_two_to_the_32(grouped):
    """The per-group sums are differences of 64-bit prefix scans of 32-bit
    lanes, taken as 32-bit halves with the borrow (on the chip the whole
    64-bit move lost a high half: PERF.md section 6, PR 37): exact where
    the scans run far past 2^32 and the groups' sums are of either sign."""
    from spark_rapids_jni_tpu.relational.aggregate import AggSpec, group_by

    r = np.random.default_rng(0)
    n = 20000
    k = np.sort(r.integers(0, 6000, n))
    v = r.integers(-2**40, 2**40, n)
    batch = ColumnBatch({
        "k": Column(jnp.asarray(k), jnp.ones((n,), jnp.bool_), T.INT64),
        "v": Column(jnp.asarray(v), jnp.asarray(r.random(n) > 0.05),
                    T.SparkType.decimal(18, 2))})
    res, ng = jax.jit(lambda b: group_by(
        b, ["k"], [AggSpec("sum", "v", "s")], engine="sort",
        assume_grouped=grouped))(batch)
    ng = int(ng)
    want = {}
    for kk, vv in zip(k.tolist(), batch["v"].to_pylist()):
        if vv is not None:
            want[kk] = (want.get(kk) or 0) + vv
        else:
            want.setdefault(kk, None)
    got = dict(zip(res["k"].to_pylist()[:ng],
                   res["s"].to_unscaled_pylist()[:ng]))
    assert ng > 4096 and got == want
    assert min(x for x in want.values() if x is not None) < -2**40
