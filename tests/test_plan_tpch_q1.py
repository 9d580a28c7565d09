"""TPC-H Q1 through the IR: Spark's decimal typing, the expressions' lowering,
the two-key domain group-by with its exact wide sums, and the whole plan
against the row-at-a-time reference (``tests/tpch_q1_reference.py``), in
process and across the data plane."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, plan, profiler
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import (Column, ColumnBatch,
                                                  Decimal128Column)
from spark_rapids_jni_tpu.plan import compile as pc
from spark_rapids_jni_tpu.plan import ir, queries
from spark_rapids_jni_tpu.relational import aggregate as agg
from spark_rapids_jni_tpu.relational.aggregate import (AggSpec, Derived,
                                                       group_by,
                                                       group_by_onehot)

from tpch_q1_reference import RESULT_TYPES, tpch_q1_reference

DEC = T.SparkType.decimal
D12 = DEC(12, 2)
MAX12 = 999_999_999_999          # 9,999,999,999.99


@pytest.fixture(autouse=True)
def _fresh():
    plan.reset_plan_cache()
    yield
    config.reset()
    plan.reset_plan_cache()


def _col(values, dtype, valid=None):
    a = np.asarray(values)
    v = np.ones(len(a), bool) if valid is None else np.asarray(valid)
    return Column(jnp.asarray(a.astype(np.dtype(dtype.jnp_dtype))),
                  jnp.asarray(v), dtype)


def _values(c, n=None):
    out = c.to_unscaled_pylist() if isinstance(c, Decimal128Column) \
        else c.to_pylist()
    return out if n is None else out[:n]


def lineitem(n, seed, nulls=0.0):
    """A seeded LINEITEM by dbgen's rules (the benchmark's recipe, in numpy);
    returns the batch and its columns as Python lists."""
    r = np.random.default_rng(seed)
    qty = r.integers(1, 51, n)
    pk = r.integers(1, 2_000_001, n)
    retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
    ship = r.integers(8035, 10441, n) + r.integers(1, 122, n)
    receipt = ship + r.integers(1, 31, n)
    cols = {"l_returnflag": (np.where(receipt <= 9298,
                                      2 * r.integers(0, 2, n), 1), T.INT32),
            "l_linestatus": ((ship > 9298).astype(np.int64), T.INT32),
            "l_quantity": (qty * 100, D12),
            "l_extendedprice": (qty * retail, D12),
            "l_discount": (r.integers(0, 11, n), D12),
            "l_tax": (r.integers(0, 9, n), D12),
            "l_shipdate": (ship, T.DATE)}
    batch, host = {}, {}
    for name, (a, t) in cols.items():
        v = r.random(n) >= nulls
        batch[name] = _col(a, t, v)
        host[name] = [int(x) if ok else None for x, ok in zip(a, v)]
    return ColumnBatch(batch), host


# ---------------------------------------------------------------------------
# typing: Spark's rules, against results checked by hand
# ---------------------------------------------------------------------------

Q1_SCHEMA = {c: D12 for c in ("l_quantity", "l_extendedprice", "l_discount",
                              "l_tax")}
ONE_MINUS = 1 - ir.Col("l_discount")
ONE_PLUS = 1 + ir.Col("l_tax")
DISC_PRICE = ir.Col("l_extendedprice") * ONE_MINUS
CHARGE = DISC_PRICE * ONE_PLUS
WIDE = {"w": DEC(38, 10), "c": DEC(10, 4), "a": DEC(20, 2), "b": DEC(18, 6),
        "i": T.INT32}


@pytest.mark.parametrize("expr,schema,want,route", [
    (ir.Lit(1), {}, DEC(1, 0), None),
    (ONE_MINUS, Q1_SCHEMA, DEC(13, 2), "add:int64"),
    (ONE_PLUS, Q1_SCHEMA, DEC(13, 2), "add:int64"),
    (DISC_PRICE, Q1_SCHEMA, DEC(26, 4), "mul_exact:limbs"),
    # raw decimal(40,6): adjusted to 38 digits, the scale (the minimum, 6) kept
    (CHARGE, Q1_SCHEMA, DEC(38, 6), "mul_exact:limbs"),
    # raw decimal(49,14): 35 integral digits are kept, the scale falls to 6
    (ir.Col("w") * ir.Col("c"), WIDE, DEC(38, 6), "mul_rounded:dec256"),
    (ir.Col("a") + ir.Col("b"), WIDE, DEC(25, 6), "add:dec256"),
    # an int32 column beside a decimal is decimal(10,0)
    (ir.Col("i") * ir.Col("c"), WIDE, DEC(21, 4), "mul_exact:limbs"),
    (ir.Col("c") * ir.Lit(5, 1), WIDE, DEC(12, 5), "mul_exact:int64"),
], ids=["lit", "one_minus", "one_plus", "disc_price", "charge",
        "loses_scale", "wide_add", "int_operand", "narrow_mul"])
def test_spark_result_types(expr, schema, want, route):
    assert pc.expr_type(expr, schema) == want
    if route is not None:
        assert pc.expr_routes(expr, schema)[-1] == f"{route}:{want!r}"


@pytest.mark.parametrize("raw,want", [
    ((40, 6), (38, 6)), ((49, 14), (38, 6)), ((38, 6), (38, 6)),
    ((45, 3), (38, 3)), ((60, 30), (38, 8))])
def test_adjust_precision_scale(raw, want):
    assert pc.adjust_precision_scale(*raw) == want


def test_expressions_are_part_of_the_signature():
    a = ir.Project(ir.Scan("t"), ["x", ("y", ir.Col("x") * (1 - ir.Col("d")))])
    b = ir.Project(ir.Scan("t"), ["x", ("y", ir.Col("x") * (1 + ir.Col("d")))])
    assert a.signature() != b.signature()
    assert hash(a) != hash(b) and a == ir.Project(
        ir.Scan("t"), ("x", ("y", ir.Col("x") * (1 - ir.Col("d")))))
    assert ir.DateLit("1998-12-01", -90).days == 10471   # 1998-09-02
    assert queries.tpch_q1_plan().signature() \
        != queries.tpch_q1_plan(60).signature()


# the plans the benchmark's cells run, as the parent commit signed them
PARENT_SIGNATURES = {
    "q6_plan": ('Aggregate', ('Filter', ('Scan', 'batch', None), 'price', '<', 50.0), ('k',), (('Agg', 'sum', 'v', 'sum_v'), ('Agg', 'count', None, 'cnt'), ('Agg', 'mean', 'price', 'avg_price')), 100, True),
    "q95_plan": ('Aggregate', ('Exchange', ('Join', ('Exchange', ('Join', ('Exchange', ('Scan', 'fact', None), 'k', 8), ('Scan', 'dim1', None), 'k', 'k', 'inner', 'build', 'shuffled'), 'wh', 8), ('Scan', 'dim2', None), 'wh', 'wh', 'inner', 'build', 'shuffled'), 'seg', 8), ('seg',), (('Agg', 'count', None, 'orders'), ('Agg', 'sum', 'v', 'net')), 10, False),
    "q9_plan": ('Aggregate', ('Filter', ('Join', ('Join', ('Scan', 'fact', None), ('Scan', 'dim1', None), 'k', 'k', 'inner', 'build', 'auto'), ('Scan', 'dim2', None), 'wh', 'wh', 'inner', 'build', 'auto'), 'v', '>=', 250), ('seg',), (('Agg', 'sum', 'v', 'net_hi'), ('Agg', 'count', None, 'orders_hi'), ('Agg', 'mean', 'v', 'avg_hi')), 10, False),
}
PARENT_DECISIONS = {
    "q6_plan": {'adaptive': True},
    # a join's decision says its output form too (PR 32): q95's joins each
    # feed an exchange and hand on a row mask, a broadcast join compacts.
    # An aggregate the sort engine may run says the group slots at which it
    # fetches its result (PR 34): all 2^10 rows here, 4096 from there up,
    # and (PR 36) the wider fetches short of every row, none here;
    # q6's takes the one-hot engine and says nothing
    "q95_plan": {'adaptive': True, 'join0:k': {'strategy': 'shuffled', 'build_rows': 128, 'output': 'mask'}, 'join1:wh': {'strategy': 'shuffled', 'build_rows': 25, 'output': 'mask'}, 'aggregate0:seg': {'head': 1024, 'tiers': ()}},
    "q9_plan": {'adaptive': True, 'join0:k': {'strategy': 'broadcast', 'build_rows': 128, 'engine': 'hash', 'output': 'compact'}, 'join1:wh': {'strategy': 'broadcast', 'build_rows': 25, 'engine': 'hash', 'output': 'compact'}, 'aggregate0:seg': {'head': 1024, 'tiers': ()}},
}


@pytest.mark.parametrize("name", list(PARENT_SIGNATURES))
def test_the_older_plans_keep_their_cache_keys(name):
    import __graft_entry__ as ge

    the_plan = getattr(queries, name)()
    assert the_plan.signature() == PARENT_SIGNATURES[name]
    if name == "q6_plan":
        inputs = {"batch": ge._device_batch(0, 1 << 10)}
    else:
        fact, d1, d2 = ge._q95_batches(1 << 10, seed=1)
        inputs = {"fact": fact, "dim1": d1, "dim2": d2}
    cp = plan.compile_plan(the_plan, inputs)
    try:
        assert cp.decisions == PARENT_DECISIONS[name]
        assert cp.key == (PARENT_SIGNATURES[name],
                          pc._schema_fingerprint(inputs),
                          pc._config_fingerprint(),
                          pc._freeze(PARENT_DECISIONS[name]))
    finally:
        cp.close()


def test_no_knob_was_added_and_q6_keeps_its_27_slots():
    import __graft_entry__ as ge

    assert len(config._REGISTRY) == 71
    config.set("q6_float_mode", "f64")
    config.set("q6_onehot_engine", "xla")
    b = ge._device_batch(0, 1 << 10)
    plan.execute(queries.q6_plan(), {"batch": b})
    assert plan.plan_cache_metrics()["onehot_slots"] == 27 \
        == agg.onehot_slots()


# ---------------------------------------------------------------------------
# expressions against Python ints, the types' extremes included
# ---------------------------------------------------------------------------

def _project(outputs, batch):
    out = plan.execute(ir.Project(ir.Scan("t"), outputs), {"t": batch})
    return out[0] if isinstance(out, tuple) else out


def _fits(v, p):
    return v if abs(v) < 10**p else None


def test_q1_expressions_at_the_extremes_of_decimal_12_2():
    r = np.random.default_rng(5)
    n = 512
    ext = r.integers(-MAX12, MAX12 + 1, n)
    disc = r.integers(-MAX12, MAX12 + 1, n)
    tax = r.integers(-MAX12, MAX12 + 1, n)
    for a in (ext, disc, tax):
        a[:4] = [MAX12, -MAX12, MAX12, 0]
    disc[:2] = [-MAX12, -MAX12]
    tax[:3] = [MAX12, MAX12, -MAX12]
    valid = r.random(n) > 0.1
    b = ColumnBatch({"l_extendedprice": _col(ext, D12),
                     "l_discount": _col(disc, D12, valid),
                     "l_tax": _col(tax, D12)})
    got = _project([("disc_price", DISC_PRICE), ("charge", CHARGE)], b)
    assert got["disc_price"].dtype == DEC(26, 4)
    assert got["charge"].dtype == DEC(38, 6)
    want_dp = [int(e) * (100 - int(d)) if ok else None
               for e, d, ok in zip(ext, disc, valid)]
    want_ch = [None if dp is None else dp * (100 + int(t))
               for dp, t in zip(want_dp, tax)]
    assert _values(got["disc_price"]) == want_dp
    assert _values(got["charge"]) == want_ch
    assert max(abs(v) for v in want_ch if v is not None) > 10**35


def test_a_product_past_10_38_is_null_and_one_under_it_exact():
    r = np.random.default_rng(6)
    n = 256
    w = [int(x) * 10**8 + int(y) for x, y in zip(
        r.integers(-10**18 + 1, 10**18, n), r.integers(0, 10**8, n))]
    c = r.integers(-10**13 + 1, 10**13, n)
    w[:3] = [10**26 - 1, -(10**26 - 1), 10**25]
    c[:3] = [10**13 - 1, 10**13 - 1, 10**13 - 1]
    b = ColumnBatch({"w": Decimal128Column.from_unscaled(w, 26, 4),
                     "c": _col(c, DEC(13, 2))})
    got = _project([("p", ir.Col("w") * ir.Col("c"))], b)["p"]
    assert got.dtype == DEC(38, 6)      # raw (40,6): no digit rounds
    want = [_fits(x * int(y), 38) for x, y in zip(w, c)]
    assert want[:3] == [None, None, 10**25 * (10**13 - 1)]
    assert None in want[3:] and any(v is not None for v in want[3:])
    assert _values(got) == want


def test_a_product_that_loses_scale_takes_the_rounding_multiply():
    import decimal

    r = np.random.default_rng(7)
    n = 64
    w = [int(x) * 10**10 + int(y) for x, y in zip(
        r.integers(-10**17, 10**17, n), r.integers(0, 10**10, n))]
    c = r.integers(-10**10 + 1, 10**10, n)
    w[0], c[0] = 10**38 - 1, 10**10 - 1          # overflows: null
    w[1], c[1] = 15, 5 * 10**7                    # 7.5e-6: HALF_UP to 8
    b = ColumnBatch({"w": Decimal128Column.from_unscaled(w, 38, 10),
                     "c": _col(c, DEC(10, 4))})
    cp = plan.compile_plan(ir.Project(ir.Scan("t"), [("p", ir.Col("w")
                                                      * ir.Col("c"))]),
                           {"t": b})
    assert cp.decisions["project0:p"] == {
        "type": "decimal(38,6)", "routes": ("mul_rounded:dec256:decimal(38,6)",)}
    got = cp({"t": b})["p"]
    with decimal.localcontext() as ctx:
        ctx.prec = 90
        want = [_fits(int((decimal.Decimal(x * int(y)).scaleb(-14)).quantize(
            decimal.Decimal("1e-6"), rounding=decimal.ROUND_HALF_UP)
            .scaleb(6)), 38) for x, y in zip(w, c)]
    assert want[0] is None and want[1] == 8
    assert _values(got) == want
    assert plan.plan_cache_metrics()["mul_rounded"] == 1


def test_a_sum_past_18_digits_takes_the_256_bit_add():
    r = np.random.default_rng(9)
    n = 64
    a = [int(x) * 10**4 + int(y) for x, y in zip(
        r.integers(-10**16 + 1, 10**16, n), r.integers(0, 10**4, n))]
    b = r.integers(-10**18 + 1, 10**18, n)
    a[0], b[0] = 10**20 - 1, 10**18 - 1
    batch = ColumnBatch({"a": Decimal128Column.from_unscaled(a, 20, 2),
                         "b": _col(b, DEC(18, 6))})
    got = _project([("s", ir.Col("a") + ir.Col("b")),
                    ("d", ir.Col("b") - ir.Col("a"))], batch)
    assert got["s"].dtype == got["d"].dtype == DEC(25, 6)
    assert _values(got["s"]) == [x * 10**4 + int(y) for x, y in zip(a, b)]
    assert _values(got["d"]) == [int(y) - x * 10**4 for x, y in zip(a, b)]
    with pytest.raises(NotImplementedError, match="past 18 digits"):
        _project([("x", ir.Col("a") + ir.Lit(10**19))], batch)


# ---------------------------------------------------------------------------
# the two-key domain group-by and its exact wide sums
# ---------------------------------------------------------------------------

def _two_key_batch(n, seed, domains=(3, 2)):
    r = np.random.default_rng(seed)
    big = [int(x) * 10**9 + int(y) for x, y in zip(
        r.integers(-10**16, 10**16, n), r.integers(0, 10**9, n))]
    return ColumnBatch({
        "a": _col(r.integers(0, domains[0], n), T.INT32, r.random(n) > 0.1),
        "b": _col(r.integers(0, domains[1], n), T.INT64, r.random(n) > 0.1),
        "d12": _col(r.integers(-MAX12, MAX12 + 1, n), D12,
                    r.random(n) > 0.1),
        "d18": _col(r.integers(-10**18 + 1, 10**18, n), DEC(18, 4)),
        "d128": Decimal128Column.from_unscaled(big, 26, 4),
        "i": _col(r.integers(-2**62, 2**62, n), T.INT64, r.random(n) > 0.1),
    })


AGGS = [AggSpec("sum", "d12", "s12"), AggSpec("mean", "d12", "m12"),
        AggSpec("sum", "d18", "s18"), AggSpec("mean", "d18", "m18"),
        AggSpec("sum", "d128", "s128"), AggSpec("mean", "d128", "m128"),
        AggSpec("sum", "i", "si"), AggSpec("count", "i", "ci"),
        AggSpec("count", None, "n")]


_SORT_SCAN = {}


def _sort_scan_oracle():
    """The batch, its live mask and what the sort-scan ``group_by`` makes of
    them: compiled once for both engines' cases."""
    if not _SORT_SCAN:
        n = 3000
        b = _two_key_batch(n, 11)
        live = jnp.asarray(np.random.default_rng(12).random(n) > 0.2)
        want, nw = jax.jit(lambda x, rv: group_by(
            x, ["a", "b"], AGGS, row_valid=rv, engine="sort"))(b, live)
        _SORT_SCAN.update(b=b, live=live, want=want, nw=int(nw))
    return _SORT_SCAN


@pytest.mark.parametrize("engine", ["xla", "scatter"])
def test_two_key_domain_group_by_is_the_sort_scan_group_by(engine):
    o = _sort_scan_oracle()
    b, live, want = o["b"], o["live"], o["want"]
    got, ng, overflow = jax.jit(lambda x, rv: group_by_onehot(
        x, ("a", "b"), AGGS, (3, 2), row_valid=rv, engine=engine))(b, live)
    assert not bool(overflow)
    assert int(ng) == o["nw"] == 12      # each key's null is a group
    for name in want.names:
        g, w = got[name], want[name]
        assert g.dtype == w.dtype, name
        assert _values(g, 12) == _values(w, 12), name   # same order too
    assert got["s12"].dtype == DEC(22, 2) and got["m12"].dtype == DEC(16, 6)
    assert got["s18"].dtype == DEC(28, 4) and got["s128"].dtype == DEC(36, 4)


@pytest.mark.parametrize("engine", ["xla", "scatter"])
@pytest.mark.parametrize("bad", [("a", 3), ("b", -1)])
def test_a_key_outside_its_domain_raises_the_overflow_flag(engine, bad):
    b = _two_key_batch(256, 13)
    col, value = bad
    data = np.asarray(b[col].data).copy()
    valid = np.asarray(b[col].validity).copy()
    data[7], valid[7] = value, True
    cols = dict(zip(b.names, b.columns))
    cols[col] = Column(jnp.asarray(data), jnp.asarray(valid), b[col].dtype)
    _res, _ng, overflow = group_by_onehot(
        ColumnBatch(cols), ("a", "b"), AGGS[:1], (3, 2), engine=engine)
    assert bool(overflow)
    # a null or a dead row there does not
    valid[7] = False
    cols[col] = Column(jnp.asarray(data), jnp.asarray(valid), b[col].dtype)
    assert not bool(group_by_onehot(ColumnBatch(cols), ("a", "b"), AGGS[:1],
                                    (3, 2), engine=engine)[2])


@pytest.mark.parametrize("engine", ["xla", "scatter"])
def test_a_64_bit_decimal_sums_exactly_past_2_63(engine):
    """Spark types sum(decimal(18,2)) decimal(28,2): 64 rows of the type's
    largest value pass 2^63 and come out exact, not wrapped."""
    top = 10**18 - 1
    n = 64
    b = ColumnBatch({"k": _col([0] * 40 + [1] * 24, T.INT32),
                     "v": _col([top] * 40 + [-top] * 24, DEC(18, 2))})
    res, ng, _ = group_by_onehot(
        b, "k", [AggSpec("sum", "v", "s"), AggSpec("mean", "v", "m")], 2,
        engine=engine)
    assert int(ng) == 2 and res["s"].dtype == DEC(28, 2)
    assert _values(res["s"], 2) == [40 * top, -24 * top]
    assert 40 * top > 2**63
    assert _values(res["m"], 2) == [top * 10**4, -top * 10**4]
    assert n == 64


def test_decimal_12_2_at_its_largest_sums_past_2_63():
    """9.3 million rows of 9,999,999,999.99 (the scatter engine: the CPU's)."""
    n = 9_300_000
    b = ColumnBatch({"k": Column(jnp.zeros((n,), jnp.int32),
                                 jnp.ones((n,), jnp.bool_), T.INT32),
                     "v": Column(jnp.full((n,), MAX12, jnp.int64),
                                 jnp.ones((n,), jnp.bool_), D12)})
    res, ng, _ = jax.jit(lambda x: group_by_onehot(
        x, "k", [AggSpec("sum", "v", "s")], 1, engine="scatter"))(b)
    assert res["s"].dtype == DEC(22, 2)
    assert _values(res["s"], 1) == [n * MAX12] and n * MAX12 > 2**63


def test_a_sum_past_its_type_is_null():
    # decimal(38,0) sums stay decimal(38,0): two of the largest overflow
    b = ColumnBatch({"k": _col([0, 0, 1], T.INT32),
                     "v": Decimal128Column.from_unscaled(
                         [10**38 - 1, 10**38 - 1, 5], 38, 0)})
    for engine in ("xla", "scatter"):
        res, ng, _ = group_by_onehot(b, "k", [AggSpec("sum", "v", "s")], 2,
                                     engine=engine)
        assert _values(res["s"], 2) == [None, 5]


def test_computed_columns_are_made_slice_by_slice(monkeypatch):
    """The sliced schedule: five slices of 1000 rows over 4,321 (the last
    reaches back over rows the fourth took) give what whole columns give."""
    monkeypatch.setattr(agg, "_ONEHOT_SLICE", 1000)
    b, _host = lineitem(4321, 21, nulls=0.05)
    the_plan = queries.tpch_q1_plan().child      # the Aggregate
    project = the_plan.child
    base, live, _ = pc._lower(project.child, {"lineitem": b}, (),
                              pc._State([], []))
    pb, derive = pc._derived(project, base)
    assert isinstance(derive, Derived) and set(derive.dtypes) == {
        "disc_price", "charge"}
    aggs = [AggSpec(a.op, a.column, a.out_name) for a in the_plan.aggs]
    got, ng, _ = group_by_onehot(pb, the_plan.keys, aggs, the_plan.domain,
                                 row_valid=live, engine="xla", derive=derive)
    want, nw, _ = group_by_onehot(derive.over(pb), the_plan.keys, aggs,
                                  the_plan.domain, row_valid=live,
                                  engine="scatter")
    assert int(ng) == int(nw) > 4
    for name in want.names:
        assert _values(got[name], int(ng)) == _values(want[name], int(ng))
    assert agg.onehot_slots() == 56


# ---------------------------------------------------------------------------
# the whole plan
# ---------------------------------------------------------------------------

def _check_q1(res, ng, host):
    want = tpch_q1_reference(**host)
    n = int(ng)
    assert n == len(want["count_order"])
    assert list(res.names) == list(RESULT_TYPES)
    for name, t in RESULT_TYPES.items():
        assert repr(res[name].dtype) == t, name
        assert isinstance(res[name], Decimal128Column) == t.startswith("dec")
        assert _values(res[name], n) == want[name], name
    return n


@pytest.mark.parametrize("engine", ["auto", "xla"])
@pytest.mark.parametrize("nulls", [0.0, 0.05], ids=["dbgen", "with_nulls"])
def test_tpch_q1_plan_is_the_reference(engine, nulls):
    config.set("q6_onehot_engine", engine)
    b, host = lineitem(1 << 12, 3, nulls)
    the_plan = queries.tpch_q1_plan()
    t0 = plan.trace_count()
    cp = plan.compile_plan(the_plan, {"lineitem": b})
    assert cp.last_lookup == "miss"
    res, ng = cp({"lineitem": b})
    assert _check_q1(res, ng, host) == (4 if nulls == 0.0 else 10)
    assert plan.trace_count() == t0 + 1
    # the routes, from the types: recorded, and counted once a product
    d = cp.decisions
    assert d["project0:disc_price"] == {
        "type": "decimal(26,4)",
        "routes": ("add:int64:decimal(13,2)",
                   "mul_exact:limbs:decimal(26,4)")}
    assert d["project0:charge"] == {
        "type": "decimal(38,6)",
        "routes": ("add:int64:decimal(13,2)",
                   "mul_exact:limbs:decimal(38,6)")}
    assert "elided" in d["sort0:l_returnflag,l_linestatus"]
    m = plan.plan_cache_metrics()
    assert (m["mul_exact"], m["mul_rounded"]) == (2, 0)
    if engine == "xla":
        assert m["onehot_slots"] == 56
    # new data, same shape: a hit, and nothing traced
    b2, host2 = lineitem(1 << 12, 4, nulls)
    cp2 = plan.compile_plan(the_plan, {"lineitem": b2})
    assert cp2 is cp and cp2.last_lookup == "hit"
    _check_q1(*cp2({"lineitem": b2}), host2)
    assert plan.trace_count() == t0 + 1
    assert plan.plan_cache_metrics()["mul_exact"] == 2


def test_without_the_onehot_path_the_sort_is_not_elided():
    """``q6_group_path=sort`` sends the aggregate to the general engine:
    no elision is recorded and the Sort above it sorts the groups."""
    config.set("q6_group_path", "sort")
    b, host = lineitem(1 << 10, 8, 0.05)
    q1 = queries.tpch_q1_plan()
    small = ir.Sort(ir.Aggregate(
        q1.child.child, q1.keys,
        (ir.Agg("sum", "disc_price", "sum_disc_price"),
         ir.Agg("count", None, "count_order")),
        domain=(3, 2), onehot=True), q1.keys)
    cp = plan.compile_plan(small, {"lineitem": b})
    assert not [k for k in cp.decisions if k.startswith("sort")]
    assert cp.decisions["project0:charge"]["type"] == "decimal(38,6)"
    res, ng = cp({"lineitem": b})
    want = tpch_q1_reference(**host)
    for name in ("l_returnflag", "l_linestatus", "sum_disc_price",
                 "count_order"):
        assert _values(res[name], int(ng)) == want[name], name


def test_q1_scopes_start_at_their_own_plan_node():
    config.set("q6_onehot_engine", "xla")
    b, _ = lineitem(1 << 10, 9)
    inputs = {"lineitem": b}
    cp = plan.compile_plan(queries.tpch_q1_plan(), inputs)
    text = cp.fn.lower(inputs, ()).as_text(debug_info=True)
    paths = {profiler.scope_path(m) for m in re.findall(r'"(jit\(run\)[^"]*)"',
                                                        text)}
    heads = {p.split("/")[0] for p in paths if p}
    assert heads == {"plan.filter.l_shipdate", "plan.project.disc_price",
                     "plan.project.charge", "plan.aggregate.l_returnflag"}
    assert {"plan.project.disc_price/expr.add",
            "plan.project.disc_price/expr.mul_exact",
            "plan.project.charge/expr.add",
            "plan.project.charge/expr.mul_exact"} <= paths
    phases = {p.split("/")[1] for p in paths
              if p.startswith("plan.aggregate.l_returnflag/")}
    assert {"agg.onehot_slice", "agg.onehot_bucket", "agg.onehot_payload",
            "agg.onehot_build", "agg.onehot_contract_int8",
            "agg.onehot_rebuild", "agg.finalize"} <= phases


def test_q1_over_the_serving_runtime_and_the_data_plane():
    """The served hop a tenant's answer takes, in process: the plan runs as
    a session of a ``ServeRuntime`` (what a worker runs queries in), its
    result crosses as the worker ships it (one Arrow IPC stream, chunk CRCs
    in a descriptor) and is decoded as the supervisor does; the
    ``Decimal128Column``s arrive with limbs, nulls and types intact."""
    from spark_rapids_jni_tpu import mem
    from spark_rapids_jni_tpu.columnar.arrow import batch_to_ipc, ipc_to_batch
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.serve import ServeRuntime
    from spark_rapids_jni_tpu.serve import data_plane as dp

    b, host = lineitem(1 << 12, 17, 0.05)
    the_plan = queries.tpch_q1_plan()

    def query(ctx):
        res, ng = plan.execute(the_plan, {"lineitem": b}, ctx=ctx)
        n = int(ng)
        return jax.tree_util.tree_map(lambda a: a[:n], res)

    RmmSpark.set_event_handler(64 << 20, host_pool_bytes=8 << 20,
                               poll_ms=10.0)
    mem.install_spill_framework()
    try:
        rt = ServeRuntime(max_concurrent=2, task_id_base=61_000)
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
        desc = dp.build_descriptor("frames", "seg-q1", len(buf), fp,
                                   1 << 12, dp.chunk_crcs(buf, 1 << 12),
                                   epoch=3)
        dp.verify_epoch(desc, 3)
        dp.verify_chunks(buf, desc)
        back = ipc_to_batch(buf, expect_fingerprint=fp)
        assert dp.batch_digest(back) == dp.batch_digest(batch)
        n = back.num_rows
        _check_q1(back, n, host)
        for name in ("sum_qty", "sum_charge", "avg_disc"):
            assert isinstance(back[name], Decimal128Column)
            assert np.array_equal(np.asarray(back[name].limbs),
                                  np.asarray(batch[name].limbs))
