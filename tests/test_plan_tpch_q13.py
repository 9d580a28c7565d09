"""TPC-H Q13 through the IR: ``NOT LIKE`` over a padded string column
(``ops.strings.like``), the outer join that keeps every customer on the dense
path and on the general one, an Exchange and an Aggregate above an Aggregate,
and the whole plan against the row-at-a-time reference
(``tests/tpch_q13_reference.py``), eager and jitted."""

import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, plan
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import (Column, ColumnBatch,
                                                  StringColumn)
from spark_rapids_jni_tpu.ops.strings import like, like_segments
from spark_rapids_jni_tpu.plan import ir, queries
from spark_rapids_jni_tpu.relational.join import (hash_join,
                                                  join_dense_or_hash)

from tpch_q13_reference import like as py_like
from tpch_q13_reference import tpch_q13_reference, wrong_values

PATTERN = "%special%requests%"
# words of the grammar's text (clause 4.2.2.13) and a few that are not
WORDS = ("special", "requests", "pending", "packages", "ironic", "deposits",
         "sleep", "express", "spec", "request", "é", "日本")


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


def make_tables(n_orders, seed, nulls=0.0):
    """Seeded CUSTOMER and ORDERS by dbgen's key rules (ten orders a
    customer, none for a key that is a multiple of 3), comments of a few
    of ``WORDS``; returns name -> {column: list}, a null as ``None``."""
    r = np.random.default_rng(seed)
    nc = max(n_orders // 10, 3)
    rr = r.integers(0, nc - nc // 3, n_orders)
    comments = [" ".join(r.choice(WORDS, r.integers(0, 7)))
                for _ in range(n_orders)]
    tables = {"customer": {"c_custkey": list(range(1, nc + 1))},
              "orders": {"o_orderkey": [int(x) for x in
                                        r.permutation(n_orders) + 1],
                         "o_custkey": [int(x) for x in
                                       3 * (rr // 2) + 1 + rr % 2],
                         "o_comment": comments}}
    if nulls:
        for cols in tables.values():
            for c, vals in cols.items():
                cols[c] = [None if r.random() < nulls else v for v in vals]
    return tables


def to_batches(tables, width=64):
    out = {}
    for t, cols in tables.items():
        b = {}
        for c, vals in cols.items():
            if c == "o_comment":
                b[c] = StringColumn.from_pylist(vals, max_len=width)
                continue
            data = np.asarray([0 if v is None else v for v in vals], np.int64)
            b[c] = Column(jnp.asarray(data),
                          jnp.asarray([v is not None for v in vals]), T.INT64)
        out[t] = ColumnBatch(b)
    return out


def domain(tables):
    return max(k for k in tables["customer"]["c_custkey"]
               if k is not None) + 1


def run_plan(the_plan, inputs, mode="jit"):
    cp = plan.compile_plan(the_plan, inputs)
    if mode == "eager":
        with jax.disable_jit():
            res, n = cp(inputs)
    else:
        res, n = cp(inputs)
    n = int(n)
    for c in res.columns:   # nothing lives past the count
        assert not np.asarray(c.validity)[n:].any()
    return {c: res[c].to_pylist()[:n] for c in ("c_count", "custdist")}, \
        res, cp


def check(tables, mode="jit", custkey_domain=None):
    the_plan = queries.tpch_q13_plan(
        custkey_domain=custkey_domain or domain(tables))
    got, res, cp = run_plan(the_plan, to_batches(tables), mode)
    want = tpch_q13_reference(tables["customer"], tables["orders"])
    assert wrong_values(got, want) == 0, (got, want)
    assert list(res.names) == ["c_count", "custdist"]
    assert res["c_count"].dtype == res["custdist"].dtype == T.INT64
    return got, want, cp


# ---------------------------------------------------------------------------
# the whole plan against the plain reference
# ---------------------------------------------------------------------------

def test_the_plan_is_data_and_its_parameters_are_part_of_the_signature():
    q13 = queries.tpch_q13_plan()
    assert isinstance(q13, ir.Sort)
    assert [(o.name, o.ascending) for o in q13.order()] == [
        ("custdist", False), ("c_count", False)]
    assert q13.signature() != queries.tpch_q13_plan("pending").signature()
    assert q13.signature() != queries.tpch_q13_plan(
        custkey_domain=150_001).signature()
    assert ir.scan_names(q13) == ("orders", "customer")
    (join,) = [n for n in q13.walk() if isinstance(n, ir.Join)]
    assert (join.how, join.dense_domain) == ("right", 1_500_001)
    (flt,) = [n for n in q13.walk() if isinstance(n, ir.Filter)]
    assert (flt.column, flt.op, flt.value) == ("o_comment", "not_like",
                                               PATTERN)
    aggs = [n for n in q13.walk() if isinstance(n, ir.Aggregate)]
    assert [a.keys for a in aggs] == [("c_custkey",), ("c_count",)]
    assert len(config._REGISTRY) == 71   # no knob came with it


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_q13_plan_is_the_reference_on_dbgen_tables(engine, mode):
    """A third of the customers have no order and count zero, with those
    whose every order the pattern left out; every other customer counts
    its orders."""
    _engines(engine)
    n = 120 if mode == "eager" else 700
    tables = make_tables(n, 3)
    got, want, cp = check(tables, mode)
    assert 0 in got["c_count"]
    removed = sum(py_like(c, PATTERN) for c in tables["orders"]["o_comment"])
    assert 0 < removed < n
    d = cp.decisions
    assert d["join0:o_custkey"] == {"strategy": "shuffled",
                                    "build_rows": None, "output": "mask",
                                    "how": "right"}
    assert d["filter0:o_comment"] == {
        "op": "not_like", "pattern": PATTERN, "route": "strings.like"}
    m = plan.plan_cache_metrics()
    assert m["joins_masked"] == 1 and m["joins_compacted"] == 0
    assert m["like_char_slots"] == n * 64
    # each aggregate takes the join's slots: every order, then every
    # customer after them
    nc = len(tables["customer"]["c_custkey"])
    assert m["agg_input_slots"] == 2 * (n + nc)


@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_nulls_and_keys_outside_the_domain_take_the_general_branch(engine):
    """Null keys and comments on either side (a null comment leaves its
    order out; a customer with a null key is kept, alone in its count), and
    a domain too narrow for the customer keys, which sends the join through
    ``hash_join(..., 'right')`` inside the same program: the reference's
    answer either way."""
    _engines(engine)
    tables = make_tables(400, 11, nulls=0.05)
    check(tables)
    check(tables, custkey_domain=domain(tables) // 2)


def test_repeated_customer_keys_take_the_general_branch():
    """A customer key twice: the dense table holds one row a key, so the
    general branch answers, each order of the key joining both rows (the
    repeats keep within the branch's rows: probe rows and build rows)."""
    _engines("sort")
    tables = make_tables(300, 5)
    tables["customer"]["c_custkey"] += [1, 3, 6]
    check(tables)


# ---------------------------------------------------------------------------
# LIKE: the kernel against Python's semantics
# ---------------------------------------------------------------------------

STRINGS = ["", "a", "aa", "aaa", "aaaa", "ab", "ba", "abab", "special",
           "special requests", "requests special", "specialrequests",
           "a special, pending requests.", "specia lrequests",
           "xx special yy requests zz", "é", "éa", "aé", "日本語abc",
           "sépecial requests", "special 日本 requests"]
PATTERNS = ["%aa%aa%", "%", "%%", "", "a", "aa", "a%", "%a", "a%a", "%a%",
            "a%%a", "%ab%ab%", "b%a", PATTERN, "special%", "%requests",
            "special%requests", "%special requests%", "%é%", "é%", "%é",
            "%日本%abc", "%requests%special%"]


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_like_is_python_like_on_anchors_order_and_overlap(jit):
    """Anchored and unanchored segments, empty ones (``%%``), segments in
    order and without overlap (``%aa%aa%`` is false on ``aaa``, true on
    ``aaaa``), multibyte UTF-8 and empty strings."""
    col = StringColumn.from_pylist(STRINGS, max_len=40)
    for p in PATTERNS:
        fn = jax.jit(lambda c, p=p: like(c, p)) if jit else \
            (lambda c, p=p: like(c, p))
        got = [bool(x) for x in np.asarray(fn(col))]
        assert got == [py_like(s, p) for s in STRINGS], p
    got = np.asarray(like(col, "%aa%aa%"))
    assert not got[STRINGS.index("aaa")] and got[STRINGS.index("aaaa")]


def test_like_finds_a_match_that_ends_at_the_last_byte_of_the_width():
    """A string as long as the padded width: the segment at its very end is
    found, anchored or not, and a pattern longer than the width matches
    nothing."""
    s = ["x" * 12 + "requests", "special" + "y" * 13, "z" * 20]
    col = StringColumn.from_pylist(s, max_len=20)
    for p in ("%requests", "%requests%", "special%", "%special%", "%z",
              "z" * 20, "z" * 21, "%" + "z" * 21 + "%"):
        assert [bool(x) for x in np.asarray(like(col, p))] == \
            [py_like(x, p) for x in s], p


@pytest.mark.parametrize("pattern", ["a_c", "%special_requests%", "a\\%b",
                                     "%\\_%"])
def test_like_refuses_one_character_wildcards_and_escapes_by_pattern(pattern):
    col = StringColumn.from_pylist(["abc"])
    with pytest.raises(NotImplementedError, match=re.escape(repr(pattern))):
        like(col, pattern)
    with pytest.raises(NotImplementedError, match=re.escape(repr(pattern))):
        like_segments(pattern)
    # ... and a plan that holds one is refused when it is compiled
    the_plan = ir.Filter(ir.Scan("t"), "s", "like", pattern)
    with pytest.raises(NotImplementedError, match=re.escape(repr(pattern))):
        plan.compile_plan(the_plan, {"t": ColumnBatch({"s": col})})


@pytest.mark.parametrize("op", ["like", "not_like"])
def test_a_string_filter_drops_null_strings_under_like_and_not_like(op):
    vals = ["special requests", None, "nothing", "", None, "requests special"]
    batch = ColumnBatch({"s": StringColumn.from_pylist(vals, max_len=24),
                         "i": Column(jnp.arange(6, dtype=jnp.int64),
                                     jnp.ones(6, jnp.bool_), T.INT64)})
    the_plan = ir.Aggregate(ir.Filter(ir.Scan("t"), "s", op, PATTERN),
                            ("i",), (ir.Agg("count", None, "n"),))
    res, n = plan.execute(the_plan, {"t": batch})
    kept = sorted(res["i"].to_pylist()[:int(n)])
    want = [i for i, v in enumerate(vals) if v is not None
            and py_like(v, PATTERN) == (op == "like")]
    assert kept == want


def test_like_over_anything_but_a_padded_string_column_is_refused():
    """A dictionary-coded string column, or a number, is refused by name
    when the plan is compiled."""
    from spark_rapids_jni_tpu.columnar import encode_batch

    vals = ["special requests", "pending"] * 4
    batch = ColumnBatch({
        "s": StringColumn.from_pylist(vals, max_len=24),
        "i": Column(jnp.arange(8, dtype=jnp.int64), jnp.ones(8, jnp.bool_),
                    T.INT64)})
    for col, inputs in (("s", {"t": encode_batch(batch, dictionary=["s"])}),
                        ("i", {"t": batch})):
        the_plan = ir.Filter(ir.Scan("t"), col, "like", PATTERN)
        with pytest.raises(NotImplementedError, match="LIKE over"):
            plan.execute(the_plan, inputs)


def test_a_like_pattern_is_a_str_and_only_like_takes_one():
    with pytest.raises(ValueError, match="pattern"):
        ir.Filter(ir.Scan("t"), "s", "like", 3)
    with pytest.raises(ValueError, match="pattern"):
        ir.Filter(ir.Scan("t"), "s", "==", "special")


# ---------------------------------------------------------------------------
# the outer join that keeps every build row
# ---------------------------------------------------------------------------

def _outer_case(seed, case):
    """A probe side (orders: ``ok`` keys into ``ck``) and a build side
    (customers), each with dead rows; ``case`` adds null keys, keys outside
    the domain or a repeated build key."""
    r = np.random.default_rng(seed)
    nl, nr, dom = 300, 40, 41
    lk = r.integers(1, nr + 1, nl)
    rk = np.arange(1, nr + 1)
    lvalid = np.ones(nl, bool)
    rvalid = np.ones(nr, bool)
    if case == "nulls":
        lvalid[r.random(nl) < 0.1] = False
        rvalid[[3, 17]] = False
    elif case == "outside":
        lk[::25] = dom + 7      # probe keys past the domain match nothing
        lk[1] = -4
    elif case == "repeated":
        rk[5] = rk[6]           # the dense table holds one row a key
    elif case == "build_outside":
        rk[9] = dom + 3         # a build key the dense table cannot hold
    left = ColumnBatch({
        "ok": Column(jnp.asarray(lk, jnp.int64), jnp.asarray(lvalid),
                     T.INT64),
        "oid": Column(jnp.arange(nl, dtype=jnp.int64), jnp.ones(nl, bool),
                      T.INT64)})
    right = ColumnBatch({
        "ck": Column(jnp.asarray(rk, jnp.int64), jnp.asarray(rvalid),
                     T.INT64),
        "cid": Column(jnp.arange(nr, dtype=jnp.int64) + 1000,
                      jnp.ones(nr, bool), T.INT64)})
    live_l = jnp.asarray(r.random(nl) > 0.15)
    live_r = jnp.asarray(r.random(nr) > 0.1)
    return left, right, live_l, live_r, dom


def _live_rows(batch, live):
    live = np.asarray(live)
    cols = {c: (np.asarray(batch[c].data), np.asarray(batch[c].validity))
            for c in batch.names}
    return Counter(tuple((int(d[i]) if v[i] else None)
                         for d, v in cols.values())
                   for i in np.flatnonzero(live))


@pytest.mark.parametrize("case", ["plain", "nulls", "outside", "repeated",
                                  "build_outside"])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_the_dense_outer_join_gives_hash_joins_rows(case, engine):
    """Every build row kept, probe rows where they are: the same live rows
    as ``hash_join(..., 'right')``, with null keys, dead rows on either
    side, keys outside the domain and a repeated build key (which takes the
    general branch)."""
    _engines(engine)
    left, right, live_l, live_r, dom = _outer_case(7, case)
    want, cnt = hash_join(left, right, ["ok"], ["ck"], "right",
                          capacity=left.num_rows + right.num_rows,
                          left_valid=live_l, right_valid=live_r)
    want_live = jnp.arange(want.num_rows) < cnt

    def run(l, r, ll, lr):
        return join_dense_or_hash(l, r, "ok", "ck", dom, "right",
                                  left_valid=ll, right_valid=lr,
                                  compact=False)

    for fn in (run, jax.jit(run)):
        out, live = fn(left, right, live_l, live_r)
        assert list(out.names) == list(want.names) == ["ck", "cid", "oid"]
        assert out.num_rows == left.num_rows + right.num_rows
        assert _live_rows(out, live) == _live_rows(want, want_live)


def test_the_dense_outer_join_leaves_the_probe_rows_in_place():
    """The dense branch: row ``i`` of the output is probe row ``i`` (live
    where it matched), then every build row, live where no live probe row
    hit its key."""
    left, right, live_l, live_r, dom = _outer_case(3, "plain")
    out, live = join_dense_or_hash(left, right, "ok", "ck", dom, "right",
                                   left_valid=live_l, right_valid=live_r,
                                   compact=False)
    nl = left.num_rows
    live = np.asarray(live)
    assert np.array_equal(np.asarray(out["oid"].data)[:nl], np.arange(nl))
    lk = np.asarray(left["ok"].data)
    rk, lr = np.asarray(right["ck"].data), np.asarray(live_r)
    matched = np.asarray(live_l) & np.isin(lk, rk[lr])
    assert np.array_equal(live[:nl], matched)
    assert np.array_equal(live[nl:], lr & ~np.isin(rk, lk[matched]))
    assert not np.asarray(out["oid"].validity)[nl:].any()


def test_the_outer_join_names_its_unmatched_rows_scope():
    tables = make_tables(200, 2)
    inputs = to_batches(tables)
    cp = plan.compile_plan(queries.tpch_q13_plan(
        custkey_domain=domain(tables)), inputs)
    text = cp.fn.lower(inputs, ()).as_text(debug_info=True)
    outer = set(re.findall(r'"jit\(run\)/(plan\.join\.[^/"]*)/cond/'
                           r'[^/"]*/join\.dense_outer/', text))
    assert outer == {"plan.join.c_custkey"}
    assert '"jit(run)/plan.filter.o_comment/strings.like/' in text


# ---------------------------------------------------------------------------
# a group-by over a group-by
# ---------------------------------------------------------------------------

def _fact(n=500, seed=1):
    r = np.random.default_rng(seed)
    return ColumnBatch({
        "k": Column(jnp.asarray(r.integers(0, 60, n), jnp.int64),
                    jnp.asarray(r.random(n) > 0.05), T.INT64),
        "v": Column(jnp.asarray(r.integers(0, 5, n), jnp.int64),
                    jnp.ones(n, bool), T.INT64)})


@pytest.mark.parametrize("exchanged", [False, True])
@pytest.mark.parametrize("engine", ["auto", "sort"])
def test_an_aggregate_above_an_aggregate_takes_its_group_count(engine,
                                                               exchanged):
    """``count(*)`` of the groups of ``sum(v)`` by their sum: the first
    aggregate's group count is the second's row mask, through an Exchange
    on the second's key or straight."""
    _engines(engine)
    batch = _fact()
    sums = ir.Aggregate(ir.Scan("t"), ("k",), (ir.Agg("sum", "v", "s"),))
    child = ir.Exchange(sums, "s") if exchanged else sums
    the_plan = ir.Aggregate(child, ("s",), (ir.Agg("count", None, "n"),))
    res, n = plan.execute(the_plan, {"t": batch})
    n = int(n)
    got = dict(zip(res["s"].to_pylist()[:n], res["n"].to_pylist()[:n]))
    k, kv = np.asarray(batch["k"].data), np.asarray(batch["k"].validity)
    v = np.asarray(batch["v"].data)
    groups = Counter()
    for key, ok, val in zip(k, kv, v):
        groups[int(key) if ok else None] += int(val)
    assert got == dict(Counter(groups.values()))
