"""tpch-q1: the table recipe, the plan, the state and the comparison of TPC-H
Q1 over one scale-factor-10 LINEITEM (see tpch-q1.json for the source, what
is assumed, the cut and the guarantees).

The seven columns Q1 reads are made on the device from the seed by dbgen's
rules (specification clause 4.2.3), rows drawn independently.  The state is
``planrun.PlanState`` with a row count that is no power of two and a result
whose 128-bit decimals leave as 64-bit limbs: ``benchmark/kinds.py`` wraps every
result column as a fixed-width ``Column``, so ``query`` splits each decimal
sum into ``<name>.lo`` and ``<name>.hi`` (int64 bit patterns) on the host,
after the one transfer, and ``compare`` joins them as Python ints."""

import datetime

import numpy as np

from benchmark import lib, planrun
from benchmark.reference.tpch_q1 import (COLUMNS, tpch_q1_control,
                                         tpch_q1_reference)

KEYS = ("l_returnflag", "l_linestatus")
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge")
AVGS = ("avg_qty", "avg_price", "avg_disc")
RESULT_COLUMNS = KEYS + tuple(f"{s}.{h}" for s in SUMS for h in ("lo", "hi")) \
    + AVGS + ("count_order",)
TABLE = ("l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
         "l_discount", "l_tax", "l_shipdate")


def _days(iso):
    return (datetime.date.fromisoformat(iso) - datetime.date(1970, 1, 1)).days


def rows_per_query(cfg):
    # --rows LOG2 (a rehearsal) puts log2_rows in the place of the row count
    return 1 << int(cfg["log2_rows"]) if cfg.get("rehearsal") \
        else int(cfg["rows"])


def query_bytes(cfg):
    """Bytes one query has to read: four decimal(12,2) in 64-bit storage, a
    DATE and two int32 codes, each with a validity byte: 51 a row, whatever
    implements the plan."""
    return rows_per_query(cfg) * int(cfg["row_bytes"])


def make_partition(cfg, key, rows):
    """One LINEITEM copy of ``rows`` rows, by dbgen's rules."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    g = cfg["dbgen"]
    kq, kp, kd, kt, ko, ks, kr, kf = jax.random.split(key, 8)

    def draw(k, lo, hi):   # uniform over lo..hi, both ends in
        return jax.random.randint(k, (rows,), lo, hi + 1, jnp.int32)

    qty = draw(kq, *g["quantity"])
    part = draw(kp, *g["partkey"])
    # P_RETAILPRICE in cents: 90000 + (partkey/10 mod 20001) + 100 (partkey mod 1000)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    ship = draw(ko, _days(g["orderdate"][0]), _days(g["orderdate"][1])) \
        + draw(ks, *g["ship_after_days"])
    receipt = ship + draw(kr, *g["receipt_after_days"])
    current = _days(g["currentdate"])
    # dictionary codes: l_returnflag A/N/R -> 0/1/2, l_linestatus F/O -> 0/1
    flag = jnp.where(receipt <= current, 2 * draw(kf, 0, 1), 1)
    status = (ship > current).astype(jnp.int32)
    ones = jnp.ones((rows,), jnp.bool_)
    dec = T.SparkType.decimal(12, 2)

    def cents(x):
        return Column(x.astype(jnp.int64), ones, dec)

    return {"lineitem": ColumnBatch({
        "l_returnflag": Column(flag, ones, T.INT32),
        "l_linestatus": Column(status, ones, T.INT32),
        "l_quantity": cents(qty * 100),
        "l_extendedprice": cents(qty.astype(jnp.int64) * retail),
        "l_discount": cents(draw(kd, *g["discount_cents"])),
        "l_tax": cents(draw(kt, *g["tax_cents"])),
        "l_shipdate": Column(ship, ones, T.DATE)})}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    return queries.tpch_q1_plan(int(cfg["delta_days"]))


class State(planrun.PlanState):
    """``PlanState`` over ``rows_per_query`` rows (59,986,052: no power of
    two), answering with the plan's ten columns as fourteen of 64 bits."""

    def __init__(self, cfg, mod, seed, devs):
        import jax

        self.cfg, self.mod, self.devs = cfg, mod, devs
        self.rows = rows_per_query(cfg)
        self.partitions = int(cfg["partitions"])
        self.plan = plan(cfg)
        key = jax.random.PRNGKey(lib.seed_words(seed, 1)[0] & 0x7FFFFFFF)
        # one program makes every copy: the index is an argument
        gen = jax.jit(lambda kk, part: make_partition(
            cfg, jax.random.fold_in(kk, part), self.rows))
        with jax.default_device(devs[0]):
            self.inputs = [gen(key, np.int32(p))
                           for p in range(self.partitions)]
            jax.block_until_ready(self.inputs)

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
            # twelve buckets at most: the whole result and the group count
            # in one transfer, no second program
            small, n = jax.device_get((res, ng))
            n = int(n)
            if n > int(self.cfg["result_capacity"]):
                raise lib.BenchError(f"{n} groups, result_capacity "
                                     f"{self.cfg['result_capacity']}")
            out = {}
            for c in KEYS + ("count_order",):
                out[c] = (np.asarray(small[c].data)[:n],
                          np.asarray(small[c].validity)[:n], small[c].dtype)
            for c in SUMS + AVGS:
                limbs = np.asarray(small[c].limbs)[:n].view(np.int64)
                valid = np.asarray(small[c].validity)[:n]
                if c in SUMS:
                    out[c + ".lo"] = (limbs[:, 0].copy(), valid, T.INT64)
                    out[c + ".hi"] = (limbs[:, 1].copy(), valid, T.INT64)
                    continue
                # decimal(16,6) fits its low limb: the high one is its sign
                if not np.array_equal(limbs[:, 1], limbs[:, 0] >> 63):
                    raise lib.BenchError(f"{c} does not fit 64 bits")
                out[c] = (limbs[:, 0].copy(), valid, T.INT64)
            return {c: out[c] for c in RESULT_COLUMNS}


def build(cfg, mod, seed, devs):
    return State(cfg, mod, seed, devs)


def _columns(tables):
    return [tables["lineitem." + c] for c in TABLE]


def reference(cfg, tables):
    return tpch_q1_reference(*_columns(tables),
                             delta_days=int(cfg["delta_days"]))


def control(cfg, tables):
    return tpch_q1_control(*_columns(tables),
                           delta_days=int(cfg["delta_days"]))


def compare(cfg, got, want):
    """Values of the answer's ten columns that differ from the reference's,
    row for row in ``ORDER BY`` order; a row too many or too few counts as
    ten."""
    cols = {c: [int(x) for x in got[c]] for c in KEYS + AVGS
            + ("count_order",)}
    for s in SUMS:   # two's complement: the high limb signed, the low not
        cols[s] = [(int(hi) << 64) | (int(lo) & (2**64 - 1))
                   for lo, hi in zip(got[s + ".lo"], got[s + ".hi"])]
    n_got, n_want = len(cols[KEYS[0]]), len(want[KEYS[0]])
    wrong = len(COLUMNS) * abs(n_got - n_want)
    for c in COLUMNS:
        wrong += sum(g != w for g, w in zip(cols[c], want[c]))
    return {"wrong_exact_values": int(wrong)}
