"""q6-scan-agg: the table recipe, the plan and the comparison of the program's
``q6_plan`` (see q6-scan-agg.json for sizes, source and guarantees).

The recipe is a copy of ``__graft_entry__._device_batch``: k ~ U[0,100) int32,
v ~ U[-1000,1000) int64, price ~ U[0,100) float64, all valid, made on the
device from the seed.  The control is the program's own lower-precision path
(``q6_float_mode=f32x3``, named in the JSON), so this module has none."""

import numpy as np

from benchmark import planrun
from benchmark.reference.q6 import q6_reference

RESULT_COLUMNS = ("k", "sum_v", "cnt", "avg_price")
build = planrun.build


def make_partition(cfg, key, rows):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    kk, kv, kp = jax.random.split(key, 3)
    ones = jnp.ones((rows,), jnp.bool_)
    return {"batch": ColumnBatch({
        "k": Column(jax.random.randint(kk, (rows,), 0, int(cfg["keys"]),
                                       jnp.int32), ones, T.INT32),
        "v": Column(jax.random.randint(kv, (rows,), -1000, 1000, jnp.int64),
                    ones, T.INT64),
        "price": Column(jax.random.uniform(kp, (rows,), jnp.float64) * 100.0,
                        ones, T.FLOAT64)})}


def make_shared(cfg, key, rows):
    return {}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    return queries.q6_plan()


def rows_per_query(cfg):
    return 1 << int(cfg["log2_rows"])


def query_bytes(cfg):
    """Bytes one query has to read: k 4 + v 8 + price 8 + three validity
    bytes a row, whatever implements the plan."""
    return rows_per_query(cfg) * (4 + 8 + 8 + 3)


def reference(cfg, tables):
    return q6_reference(tables["batch.k"], tables["batch.v"],
                        tables["batch.price"], int(cfg["keys"]))


def compare(cfg, got, want):
    wrong = planrun.compare_exact(got, want, "k", ("k", "sum_v", "cnt"))
    rel = None   # taken only where the groups line up
    if wrong == 0:
        order = np.argsort(got["k"], kind="stable")
        avg = np.asarray(got["avg_price"], np.float64)[order]
        rel = float(np.max(np.abs(avg - want["avg_price"])
                           / np.abs(want["avg_price"])))
    return {"wrong_exact_values": wrong, "avg_rel_err": rel}
