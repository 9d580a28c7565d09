"""tpch-q18: the table recipe, the plan, the state and the comparison of TPC-H
Q18 over one scale-factor-1 database (see tpch-q18.json for the source, what
is assumed, the cut and the guarantees).

CUSTOMER, ORDERS and LINEITEM are made on the device from the seed by dbgen's
rules (specification clause 4.2.3), as ``tpch-q3`` makes them and consistent
with each other: every line belongs to an order (1 to 7 lines an order,
LINEITEM in order-key order as dbgen writes it), every order to a customer
whose key is no multiple of 3, and ``o_totalprice`` is the sum over the
order's lines that dbgen writes.  Of the columns only those Q18 reads are
kept.  The state is ``planrun.PlanState`` over three tables a partition, with
the plan run once at the end of set-up so that its compile falls there.

``benchmark/kinds.py`` wraps every result column as a fixed-width ``Column``,
so ``query`` hands the 128-bit ``sum_qty`` out as two 64-bit limbs
(``sum_qty.lo``/``.hi``) and writes ``c_name`` (``Customer#`` and the key in
nine digits: eighteen bytes, which the key determines and the scan prunes)
for the at most hundred rows on the host as three 64-bit words of its bytes
(``c_name.w0``-``.w2``); ``compare`` joins both again."""

import numpy as np

from benchmark import lib, planrun
from benchmark.reference.tpch_q18 import (COLUMNS, days, tpch_q18_control,
                                          tpch_q18_reference, wrong_values)

NAME_WORDS = ("c_name.w0", "c_name.w1", "c_name.w2")
RESULT_COLUMNS = NAME_WORDS + ("c_custkey", "o_orderkey", "o_orderdate",
                               "o_totalprice", "sum_qty.lo", "sum_qty.hi")
# what the two TPC-H configurations share: the tables' row counts (and a
# rehearsal's: 2^LOG2 LINEITEM rows, four lines an order, ten orders a
# customer), dbgen's sparse order keys, and what a query reads
_Q3 = lib.load_module("configs", "tpch-q3")
table_rows, rows_per_query = _Q3.table_rows, _Q3.rows_per_query
query_bytes, sparse_key = _Q3.query_bytes, _Q3.sparse_key

TABLES = {"customer": ("c_custkey",),
          "orders": ("o_orderkey", "o_custkey", "o_orderdate",
                     "o_totalprice"),
          "lineitem": ("l_orderkey", "l_quantity")}


def quantity(cfg):
    """QUANTITY; a rehearsal's few thousand orders hold none above 300, so
    it takes the lower value the configuration names."""
    return int(cfg["rehearsal_quantity" if cfg.get("rehearsal")
                   else "quantity"])


def make_partition(cfg, key, rows):
    """One database: CUSTOMER, ORDERS and a LINEITEM of exactly ``rows``
    rows, by dbgen's rules (``tpch-q3``'s recipe, with the quantity kept as
    a column and ``o_totalprice`` made from the lines)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    g = cfg["dbgen"]
    n = table_rows(cfg)
    n_cust, n_ord = n["customer"], n["orders"]
    kcust, kdate, klines, kq, kp, kd, kt = jax.random.split(key, 7)

    def draw(k, count, lo, hi):   # uniform over lo..hi, both ends in
        return jax.random.randint(k, (count,), lo, hi + 1, jnp.int32)

    def col(data, dtype):
        return Column(data.astype(dtype.jnp_dtype),
                      jnp.ones(data.shape, jnp.bool_), dtype)

    customer = ColumnBatch({
        "c_custkey": col(jnp.arange(1, n_cust + 1), T.INT64)})

    orderkey = sparse_key(jnp.arange(1, n_ord + 1, dtype=jnp.int32))
    # o_custkey: uniform over the customer keys that are no multiple of 3
    r = draw(kcust, n_ord, 0, n_cust - n_cust // 3 - 1)
    # 1..7 lines an order, drawn; the drawn total is then made to meet the
    # table's row count: while it is short, the first orders of fewer than
    # 7 lines get one more, while it is over, the first of more than one
    # line lose one (tpch-q18.json, assumed)
    lines = draw(klines, n_ord, *g["lines_per_order"])
    short = rows - jnp.sum(lines)
    more, fewer = lines < g["lines_per_order"][1], \
        lines > g["lines_per_order"][0]
    lines = lines + (more & (jnp.cumsum(more) <= short)) \
        - (fewer & (jnp.cumsum(fewer) <= -short))
    ends = jnp.cumsum(lines)
    starts = ends - lines
    # the order of each line: LINEITEM is written in order-key order
    of_order = jnp.cumsum(jnp.zeros((rows,), jnp.int32).at[starts[1:]].add(
        1, mode="drop"))
    qty = draw(kq, rows, *g["quantity"])
    part = draw(kp, rows, *g["partkey"])
    # P_RETAILPRICE in cents: 90000 + (partkey/10 mod 20001) + 100 (partkey mod 1000)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    ext = qty.astype(jnp.int64) * retail
    # O_TOTALPRICE as dbgen's mk_order adds it up, in whole cents a line:
    # extendedprice (1 - discount) cut to cents, then (1 + tax) cut to cents
    line_price = (ext * (100 - draw(kd, rows, *g["discount_cents"]))) // 100
    line_price = (line_price * (100 + draw(kt, rows, *g["tax_cents"]))) // 100
    upto = jnp.concatenate([jnp.zeros((1,), jnp.int64),
                            jnp.cumsum(line_price)])
    dec = T.SparkType.decimal(12, 2)
    orders = ColumnBatch({
        "o_orderkey": col(orderkey, T.INT64),
        "o_custkey": col(3 * (r // 2) + 1 + r % 2, T.INT64),
        "o_orderdate": col(draw(kdate, n_ord, days(g["orderdate"][0]),
                                days(g["orderdate"][1])), T.DATE),
        "o_totalprice": col(upto[ends] - upto[starts], dec)})
    lineitem = ColumnBatch({
        "l_orderkey": col(orderkey[of_order], T.INT64),
        "l_quantity": col(qty.astype(jnp.int64) * 100, dec)})
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    n = table_rows(cfg)
    return queries.tpch_q18_plan(
        quantity(cfg), custkey_domain=n["customer"] + 1,
        orderkey_domain=sparse_key(n["orders"]) + 1,
        limit=int(cfg["limit"]))


def name_words(custkeys):
    """``c_name`` of each key as three int64 words of its bytes (eighteen,
    NUL-padded to twenty-four), little-endian."""
    names = np.array(["Customer#%09d" % k for k in custkeys], dtype="S24")
    return names.view("<i8").reshape(len(names), 3)


def names_of(words):
    """The strings back from ``name_words``' columns."""
    raw = np.ascontiguousarray(np.stack(
        [np.asarray(w).astype("<i8") for w in words], axis=1)).tobytes()
    return [raw[i:i + 24].rstrip(b"\0").decode("ascii", "replace")
            for i in range(0, len(raw), 24)]


class State(planrun.PlanState):
    """``PlanState`` over three tables a partition, LINEITEM of
    ``rows_per_query`` rows (6,001,215: no power of two), the plan compiled
    inside set-up, answering with the query's six columns as nine of at
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
            # the plan's cold compile (three dense/general join pairs, the
            # fetch ladders of two aggregates) belongs to set-up, not to
            # the first query a caller waits for
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
            # a hundred row slots: the whole result and the row count in
            # one transfer, no second program
            small, n = jax.device_get((res, ng))
            n = int(n)
            if n > int(self.cfg["result_capacity"]):
                raise lib.BenchError(f"{n} rows, result_capacity "
                                     f"{self.cfg['result_capacity']}")
            out = {c: (np.asarray(small[c].data)[:n],
                       np.asarray(small[c].validity)[:n], small[c].dtype)
                   for c in ("c_custkey", "o_orderkey", "o_orderdate",
                             "o_totalprice")}
            # c_name: the key determines it and the scan prunes it
            keys, valid = out["c_custkey"][:2]
            words = name_words(np.where(valid, keys, 0))
            for i, c in enumerate(NAME_WORDS):
                out[c] = (words[:, i].copy(), valid, T.INT64)
            limbs = np.asarray(small["sum_qty"].limbs)[:n].view(np.int64)
            valid = np.asarray(small["sum_qty"].validity)[:n]
            out["sum_qty.lo"] = (limbs[:, 0].copy(), valid, T.INT64)
            out["sum_qty.hi"] = (limbs[:, 1].copy(), valid, T.INT64)
            return {c: out[c] for c in RESULT_COLUMNS}


def build(cfg, mod, seed, devs):
    return State(cfg, mod, seed, devs)


def _columns(tables):
    return [tables[f"{t}.{c}"] for t, cols in TABLES.items() for c in cols]


def _params(cfg):
    return {"quantity": quantity(cfg), "limit": int(cfg["limit"])}


def reference(cfg, tables):
    return tpch_q18_reference(*_columns(tables), **_params(cfg))


def control(cfg, tables):
    return tpch_q18_control(*_columns(tables), **_params(cfg))


def compare(cfg, got, want):
    """Values of the answer's six columns that the reference does not
    allow, row for row in ``ORDER BY`` order, any order among rows equal in
    both sort keys, any of the tied rows at the cut."""
    cols = {c: [int(x) for x in got[c]]
            for c in COLUMNS if c not in ("c_name", "sum_qty")}
    cols["c_name"] = names_of([got[c] for c in NAME_WORDS])
    # two's complement: the high limb signed, the low not
    cols["sum_qty"] = [(int(hi) << 64) | (int(lo) & (2**64 - 1))
                       for lo, hi in zip(got["sum_qty.lo"],
                                         got["sum_qty.hi"])]
    return {"wrong_exact_values": wrong_values(cols, want,
                                               int(cfg["limit"]))}
