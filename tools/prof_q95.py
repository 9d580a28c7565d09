"""Stage-by-stage cost breakdown of the q95-shaped pipeline.

The r5 default bench capture prices q95 (exchange -> join -> exchange ->
join -> group-by) alongside q6; on XLA-CPU it measured 0.71 Mrows/s vs a
47 Mrows/s numpy stand-in (vs_baseline 0.01).  Before optimizing, know
where the time goes: this times each stage in isolation with the same
no-repeat variant protocol as prof_q6.

Run on whatever backend resolves;
BENCH_FORCE_CPU=1 pins CPU via tools/_bootstrap.py.
"""
import _bootstrap  # noqa: F401  (repo root on sys.path)
import os
import time

import jax
import jax.numpy as jnp

import __graft_entry__ as ge
from spark_rapids_jni_tpu.columnar.column import ColumnBatch
from spark_rapids_jni_tpu.parallel.partition import (
    regroup_order,
    spark_partition_id,
)
from spark_rapids_jni_tpu.relational import AggSpec, group_by, hash_join
from spark_rapids_jni_tpu.relational.aggregate import group_by_domain_or_sort
from spark_rapids_jni_tpu.relational.gather import gather_column

N = int(os.environ.get("PROF_Q95_ROWS", 1 << 17))
REPS = int(os.environ.get("PROF_Q95_REPS", 4))
_seed = [300]


def bench(name, f, reps=REPS):
    jf = jax.jit(f)
    vs = [ge._q95_batches(N, seed=_seed[0] + i) for i in range(reps + 1)]
    _seed[0] += reps + 1
    jax.block_until_ready(jf(*vs[0]))
    outs = []
    t0 = time.perf_counter()
    for v in vs[1:]:
        outs.append(jf(*v))
    jax.block_until_ready(outs)
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:32s} {dt*1e3:8.2f} ms   {N/dt/1e6:8.2f} Mrows/s",
          flush=True)


P = 8


def exchange_local(b, key, live, engine="auto"):
    pid = spark_partition_id([b[key]], P, live)
    order = regroup_order(pid, P + 1, engine=engine)
    return ColumnBatch({name: gather_column(col, order)
                        for name, col in zip(b.names, b.columns)})


def stage_pid(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return spark_partition_id([fact["k"]], P, live)


def stage_exchange1(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return exchange_local(fact, "k", live)


def stage_exchange1_sort(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return exchange_local(fact, "k", live, engine="sort")


def stage_join1(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    staged = exchange_local(fact, "k", live)
    return hash_join(staged, dim1, ["k"], ["k"], "inner")


def stage_through_join2(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    staged = exchange_local(fact, "k", live)
    j1, c1 = hash_join(staged, dim1, ["k"], ["k"], "inner")
    j1_live = jnp.arange(j1.num_rows, dtype=jnp.int32) < c1
    staged2 = exchange_local(j1, "wh", j1_live)
    return hash_join(staged2, dim2, ["wh"], ["wh"], "inner",
                     left_valid=j1_live)


def stage_groupby_sortscan(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return group_by(
        fact, ["seg"],
        [AggSpec("count", None, "orders"), AggSpec("sum", "v", "net")],
        row_valid=live, engine="sort")


def stage_groupby_scatter(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return group_by(
        fact, ["seg"],
        [AggSpec("count", None, "orders"), AggSpec("sum", "v", "net")],
        row_valid=live, engine="scatter")


def stage_groupby_domain(fact, dim1, dim2):
    live = jnp.ones((fact.num_rows,), jnp.bool_)
    return group_by_domain_or_sort(
        fact, "seg",
        [AggSpec("count", None, "orders"), AggSpec("sum", "v", "net")],
        ge.Q95_SEG, row_valid=live)


def stage_join1_sortprobe(fact, dim1, dim2):
    return hash_join(fact, dim1, ["k"], ["k"], "inner", engine="sort")


def stage_join1_hashprobe(fact, dim1, dim2):
    return hash_join(fact, dim1, ["k"], ["k"], "inner", engine="hash")


def full_fused_sort(fact, dim1, dim2):
    """The sort-order-reuse plan: groupby_engine pinned to 'sort' routes
    the final aggregation through a seg-keyed exchange whose regroup
    sort carries the seg radix words, then assume_grouped group_by."""
    from spark_rapids_jni_tpu import config

    config.set("groupby_engine", "sort")
    try:
        return ge._q95_prefix(fact, dim1, dim2, "full")
    finally:
        config.reset("groupby_engine")


print("devices:", jax.devices(), "rows:", N, flush=True)
bench("partition_id_only", stage_pid)
bench("exchange1 (regroup auto)", stage_exchange1)
bench("exchange1 (regroup sort)", stage_exchange1_sort)
bench("exchange1 + join1", stage_join1)
bench("through join2 (2 exch, 2 join)", stage_through_join2)
bench("join1 only (sort probe)", stage_join1_sortprobe)
bench("join1 only (hash probe)", stage_join1_hashprobe)
bench("group_by(seg) sort-scan", stage_groupby_sortscan)
bench("group_by(seg) scatter", stage_groupby_scatter)
bench("group_by(seg) domain auto", stage_groupby_domain)
bench("full q95 step", ge._q95_step)
bench("full q95 (fused sort plan)", full_fused_sort)
