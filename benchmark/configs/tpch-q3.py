"""tpch-q3: the table recipe, the plan, the state and the comparison of TPC-H
Q3 over one scale-factor-1 database (see tpch-q3.json for the source, what is
assumed, the cut and the guarantees).

CUSTOMER, ORDERS and LINEITEM are made on the device from the seed by dbgen's
rules (specification clause 4.2.3), consistent with each other: every line
belongs to an order (1 to 7 lines an order, LINEITEM in order-key order as
dbgen writes it), every order to a customer whose key is no multiple of 3.
The state is ``planrun.PlanState`` over three tables a partition, with the
plan run once at the end of set-up so that its compile falls there, and a
result whose 128-bit ``revenue`` leaves as two 64-bit limbs:
``benchmark/kinds.py`` wraps every result column as a fixed-width ``Column``,
so ``query`` splits it into ``revenue.lo`` and ``revenue.hi`` (int64 bit
patterns) on the host, after the one transfer, and ``compare`` joins them as
Python ints."""

import numpy as np

from benchmark import lib, planrun
from benchmark.reference.tpch_q3 import (COLUMNS, days, tpch_q3_control,
                                         tpch_q3_reference, wrong_values)

RESULT_COLUMNS = ("l_orderkey", "revenue.lo", "revenue.hi", "o_orderdate",
                  "o_shippriority")
TABLES = {"customer": ("c_custkey", "c_mktsegment"),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_shippriority"),
          "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                       "l_shipdate")}


def table_rows(cfg):
    """Rows of each table.  ``--rows LOG2`` (a rehearsal) puts 2^LOG2 in the
    place of LINEITEM's count and keeps the proportions: four lines an
    order, ten orders a customer."""
    if not cfg.get("rehearsal"):
        return {t: int(n) for t, n in cfg["rows"].items()}
    lines = 1 << int(cfg["log2_rows"])
    orders = max(lines // 4, 8)
    return {"lineitem": lines, "orders": orders,
            "customer": max(orders // 10, 3)}


def rows_per_query(cfg):
    return table_rows(cfg)["lineitem"]


def query_bytes(cfg):
    """Bytes one query has to read: every column of the three tables with a
    validity byte each (``row_bytes``), whatever implements the plan."""
    return sum(n * int(cfg["row_bytes"][t])
               for t, n in table_rows(cfg).items())


def sparse_key(i):
    """dbgen's ``mk_sparse``: the order key of the ``i``-th order (from 1):
    the low three bits kept, the rest moved up two more, so that 8 of every
    32 values are used."""
    return ((i >> 3) << 5) | (i & 7)


def make_partition(cfg, key, rows):
    """One database: CUSTOMER, ORDERS and a LINEITEM of exactly ``rows``
    rows, by dbgen's rules."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    g = cfg["dbgen"]
    n = table_rows(cfg)
    n_cust, n_ord = n["customer"], n["orders"]
    kseg, kcust, kdate, klines, kq, kp, kd, ks = jax.random.split(key, 8)

    def draw(k, count, lo, hi):   # uniform over lo..hi, both ends in
        return jax.random.randint(k, (count,), lo, hi + 1, jnp.int32)

    def col(data, dtype):
        return Column(data.astype(dtype.jnp_dtype),
                      jnp.ones(data.shape, jnp.bool_), dtype)

    customer = ColumnBatch({
        "c_custkey": col(jnp.arange(1, n_cust + 1), T.INT64),
        # dictionary codes, alphabetical: AUTOMOBILE 0, BUILDING 1,
        # FURNITURE 2, HOUSEHOLD 3, MACHINERY 4
        "c_mktsegment": col(draw(kseg, n_cust, 0, int(g["segments"]) - 1),
                            T.INT32)})

    orderkey = sparse_key(jnp.arange(1, n_ord + 1, dtype=jnp.int32))
    # o_custkey: uniform over the customer keys that are no multiple of 3
    r = draw(kcust, n_ord, 0, n_cust - n_cust // 3 - 1)
    orderdate = draw(kdate, n_ord, days(g["orderdate"][0]),
                     days(g["orderdate"][1]))
    orders = ColumnBatch({
        "o_orderkey": col(orderkey, T.INT64),
        "o_custkey": col(3 * (r // 2) + 1 + r % 2, T.INT64),
        "o_orderdate": col(orderdate, T.DATE),
        "o_shippriority": col(jnp.zeros((n_ord,), jnp.int32), T.INT32)})

    # 1..7 lines an order, drawn; the drawn total is then made to meet the
    # table's row count: while it is short, the first orders of fewer than
    # 7 lines get one more, while it is over, the first of more than one
    # line lose one (tpch-q3.json, assumed)
    lines = draw(klines, n_ord, *g["lines_per_order"])
    short = rows - jnp.sum(lines)
    more, fewer = lines < g["lines_per_order"][1], \
        lines > g["lines_per_order"][0]
    lines = lines + (more & (jnp.cumsum(more) <= short)) \
        - (fewer & (jnp.cumsum(fewer) <= -short))
    starts = jnp.cumsum(lines) - lines
    # the order of each line: LINEITEM is written in order-key order
    of_order = jnp.cumsum(jnp.zeros((rows,), jnp.int32).at[starts[1:]].add(
        1, mode="drop"))
    qty = draw(kq, rows, *g["quantity"])
    part = draw(kp, rows, *g["partkey"])
    # P_RETAILPRICE in cents: 90000 + (partkey/10 mod 20001) + 100 (partkey mod 1000)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    dec = T.SparkType.decimal(12, 2)
    lineitem = ColumnBatch({
        "l_orderkey": col(orderkey[of_order], T.INT64),
        "l_extendedprice": col(qty.astype(jnp.int64) * retail, dec),
        "l_discount": col(draw(kd, rows, *g["discount_cents"]), dec),
        "l_shipdate": col(orderdate[of_order]
                          + draw(ks, rows, *g["ship_after_days"]), T.DATE)})
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    n = table_rows(cfg)
    return queries.tpch_q3_plan(
        int(cfg["segment_code"]), cfg["date"],
        custkey_domain=n["customer"] + 1,
        orderkey_domain=sparse_key(n["orders"]) + 1,
        limit=int(cfg["limit"]))


class State(planrun.PlanState):
    """``PlanState`` over three tables a partition, LINEITEM of
    ``rows_per_query`` rows (6,001,215: no power of two), the plan compiled
    inside set-up, answering with the plan's four columns as five of at
    most 64 bits."""

    def __init__(self, cfg, mod, seed, devs):
        import jax

        self.cfg, self.mod, self.devs = cfg, mod, devs
        self.rows = rows_per_query(cfg)
        self.partitions = int(cfg["partitions"])
        self.plan = plan(cfg)
        key = jax.random.PRNGKey(lib.seed_words(seed, 1)[0] & 0x7FFFFFFF)
        # one program makes every database: the index is an argument
        gen = jax.jit(lambda kk, part: make_partition(
            cfg, jax.random.fold_in(kk, part), self.rows))
        with jax.default_device(devs[0]):
            self.inputs = [gen(key, np.int32(p))
                           for p in range(self.partitions)]
            jax.block_until_ready(self.inputs)
            # the plan's cold compile (two dense/general join pairs, both
            # branches of the aggregate) belongs to set-up, not to the
            # first query a caller waits for
            self.query(0, -1, lib.Spans())

    def query(self, part, q, spans, inputs=None):
        import jax

        from spark_rapids_jni_tpu import plan as plan_mod
        from spark_rapids_jni_tpu.columnar import types as T

        inputs = self.inputs[part] if inputs is None else inputs
        with spans.span(q, "lookup"):
            cp = plan_mod.compile_plan(self.plan, inputs)
        with spans.span(q, "execute"):
            res, ng = jax.block_until_ready(cp(inputs))
        with spans.span(q, "result"):
            # ten row slots: the whole result and the row count in one
            # transfer, no second program
            small, n = jax.device_get((res, ng))
            n = int(n)
            if n > int(self.cfg["result_capacity"]):
                raise lib.BenchError(f"{n} rows, result_capacity "
                                     f"{self.cfg['result_capacity']}")
            out = {c: (np.asarray(small[c].data)[:n],
                       np.asarray(small[c].validity)[:n], small[c].dtype)
                   for c in ("l_orderkey", "o_orderdate", "o_shippriority")}
            limbs = np.asarray(small["revenue"].limbs)[:n].view(np.int64)
            valid = np.asarray(small["revenue"].validity)[:n]
            out["revenue.lo"] = (limbs[:, 0].copy(), valid, T.INT64)
            out["revenue.hi"] = (limbs[:, 1].copy(), valid, T.INT64)
            return {c: out[c] for c in RESULT_COLUMNS}


def build(cfg, mod, seed, devs):
    return State(cfg, mod, seed, devs)


def _columns(tables):
    return [tables[f"{t}.{c}"] for t, cols in TABLES.items() for c in cols]


def _params(cfg):
    return {"segment_code": int(cfg["segment_code"]),
            "date_iso": cfg["date"], "limit": int(cfg["limit"])}


def reference(cfg, tables):
    return tpch_q3_reference(*_columns(tables), **_params(cfg))


def control(cfg, tables):
    return tpch_q3_control(*_columns(tables), **_params(cfg))


def compare(cfg, got, want):
    """Values of the answer's four columns that the reference does not
    allow, row for row in ``ORDER BY`` order, any order among rows equal in
    both sort keys, any of the tied rows at the cut."""
    cols = {c: [int(x) for x in got[c]] for c in COLUMNS if c != "revenue"}
    # two's complement: the high limb signed, the low not
    cols["revenue"] = [(int(hi) << 64) | (int(lo) & (2**64 - 1))
                       for lo, hi in zip(got["revenue.lo"],
                                         got["revenue.hi"])]
    return {"wrong_exact_values": wrong_values(cols, want,
                                               int(cfg["limit"]))}
