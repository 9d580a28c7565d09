"""q95-join-agg: the table recipe, the plan and the comparison of the
program's ``q95_plan`` (see q95-join-agg.json).

The recipe is a copy of ``__graft_entry__._device_q95``: a fact of uniform
keys over a dimension of rows/8 unique keys, 25 warehouses, 10 segments,
v in [1,500); dim1 above ``broadcast_threshold_rows`` so the join shuffles."""

import numpy as np

from benchmark import planrun
from benchmark.reference.q95 import q95_reference

RESULT_COLUMNS = ("seg", "orders", "net")
build = planrun.build


def _nd(cfg, rows):
    return max(rows // int(cfg["dim1_divisor"]), 1)


def make_partition(cfg, key, rows):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    kk, kw, ks, kv = jax.random.split(key, 4)
    ones = jnp.ones((rows,), jnp.bool_)

    def ints(k, hi):
        return Column(jax.random.randint(k, (rows,), 0, hi, jnp.int32), ones,
                      T.INT32)

    return {"fact": ColumnBatch({
        "k": ints(kk, _nd(cfg, rows)),
        "wh": ints(kw, int(cfg["warehouses"])),
        "seg": ints(ks, int(cfg["segments"])),
        "v": Column(jax.random.randint(kv, (rows,), 1, 500, jnp.int64), ones,
                    T.INT64)})}


def make_shared(cfg, key, rows):
    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    k1, k2 = jax.random.split(key, 2)
    nd, nw = _nd(cfg, rows), int(cfg["warehouses"])

    def dim(name, n, k, payload):
        ones = jnp.ones((n,), jnp.bool_)
        return ColumnBatch({
            name: Column(jnp.arange(n, dtype=jnp.int32), ones, T.INT32),
            payload: Column(jax.random.randint(k, (n,), 0, 9, jnp.int64),
                            ones, T.INT64)})

    return {"dim1": dim("k", nd, k1, "d1"), "dim2": dim("wh", nw, k2, "d2")}


def plan(cfg):
    from spark_rapids_jni_tpu.plan import queries

    return queries.q95_plan()


def rows_per_query(cfg):
    return 1 << int(cfg["log2_rows"])


def query_bytes(cfg):
    """Bytes one query has to read: the fact's k, wh, seg (4 each), v (8)
    and four validity bytes a row, and both dims (key 4, payload 8, two
    validity bytes a row)."""
    rows = rows_per_query(cfg)
    return rows * (4 * 3 + 8 + 4) \
        + (_nd(cfg, rows) + int(cfg["warehouses"])) * (4 + 8 + 2)


def reference(cfg, tables, dim1_rows=None):
    return q95_reference(tables["fact.k"], tables["fact.wh"],
                         tables["fact.seg"], tables["fact.v"],
                         tables["dim1.k"][:dim1_rows], tables["dim2.wh"])


def control(cfg, tables):
    """The reference with one stated guarantee broken: the join keeps only
    the build rows a broadcast would take (an eighth of dim1: at the cell's
    size ``broadcast_threshold_rows``, 65,536 of 2^19), so fact rows whose
    key lies beyond them are lost."""
    return reference(cfg, tables, int(
        float(cfg["control_build_share"]) * tables["dim1.k"].shape[0]))


def compare(cfg, got, want):
    return {"wrong_exact_values": planrun.compare_exact(
        got, want, "seg", ("seg", "orders", "net"))}
