#!/usr/bin/env python3
"""The standing check that the query path starts and answers on the chip.

Run with no arguments on a machine with one TPU chip::

    python chip_smoke.py

Phase A serves, in a child process of this script: a ``FrontDoor`` with ONE
worker process, which takes the chip and says so in its hello; q6 digests
under two tenants and one arrow batch come back through it.  (A process that
builds a ``FrontDoor`` stays on the host CPU; its workers inherit
``JAX_PLATFORMS=tpu`` from the environment.)  Phase B runs after the
supervisor and its worker have exited: this process touches JAX for the
first time, takes the chip and runs what a worker runs inside (spill
framework + ``ServeRuntime``), then the q6 and q95 IR plans under the default
knobs against plain numpy references.  One process needs the chip at any
moment, and nothing falls back to the CPU.

``--chips 4`` runs only the exchange across four chips (``parallel/``
through the ``ShuffleService``) against the same operators on one of them.

Every phase prints one JSON line; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every number printed is a smoke reading, not a benchmark.
"""

import argparse
import glob
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

# before jax or the package is imported: every process of this run gets the
# chip or dies (FrontDoor passes os.environ to its workers)
WANT = os.environ.setdefault("JAX_PLATFORMS", "tpu")
# "tpu" or "tpu,cpu": the first comes first, and JAX fails if it cannot have it
PLATFORM = WANT.split(",")[0].strip().lower()
os.environ.setdefault("TPU_LOG_DIR", "disabled")

T0 = time.monotonic()


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields,
                      "t": round(time.monotonic() - T0, 1)}), flush=True)


class Failed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Failed(msg)


ARROW_ROWS = 1 << 13
ANSWER_TIMEOUT_S = 600.0  # longest wait for one answer
# A worker is lost after 3.5 silent heartbeats.  serve_heartbeat_ms (100) is
# for the CPU tests; 2000 lost the worker in 2 chip runs of 6, when its
# backend (about 10 s to start) still started after its hello.
HEARTBEAT_MS = 10000.0
# what every phase runs at: log2 of the q6 batch rows, of the q95 fact rows,
# and of the rows per device with --chips 4
REAL_ROWS = {"q6": 24, "q95": 24, "shard": 22, "tpch_q1": 22}


def q6_request(args):
    """The worker's q6_digest parameters and the bytes admission charges for
    one of its batches: phases A and B run the same recipe over the same
    seeds, so their digests must agree bit for bit."""
    rows = 1 << args.log2["q6"]
    batch_bytes = rows * 24  # k i32 + v i64 + price f64 + validity, rounded up
    return rows, batch_bytes, {"rows": rows, "stream": args.seed,
                               "query": 0, "steps": 2}


# ---------------------------------------------------------------------------
# phase A: served, the worker process holds the chip
# ---------------------------------------------------------------------------

class _LogTail(threading.Thread):
    """Keeps the latest text of every worker.log under the fleet dir: the
    supervisor removes a lost worker's directory, log included."""

    def __init__(self, fleet_dir):
        super().__init__(name="smoke-logtail", daemon=True)
        self.fleet_dir = fleet_dir
        self.logs = {}
        self._halt = threading.Event()

    def poll(self):
        for p in glob.glob(os.path.join(self.fleet_dir, "worker-*",
                                        "worker.log")):
            try:
                with open(p, errors="replace") as f:
                    self.logs[p] = f.read()[-8000:]
            except OSError:
                pass

    def run(self):
        while not self._halt.wait(0.25):
            self.poll()

    def stop(self):
        self._halt.set()
        self.join(2.0)
        self.poll()

    def dump(self):
        for p, text in sorted(self.logs.items()):
            sys.stderr.write(f"--- {p} ---\n{text}\n")
        sys.stderr.flush()


def _wait_all(door, sessions, timeout_s):
    """Results of ``sessions``; a lost worker fails the run at once instead
    of being respawned behind our back."""
    deadline = time.monotonic() + timeout_s
    while not all(s.done() for s in sessions):
        m = door.metrics.snapshot()
        lost = {k: m[k] for k in ("crashes", "stalls", "circuit_open",
                                  "respawns", "partitions_detected") if m[k]}
        check(not lost, f"phase A: the worker was lost: {lost} "
                        f"liveness={m['liveness']}")
        check(time.monotonic() < deadline,
              f"phase A: no answer after {timeout_s}s: "
              f"{[(s.kind, s.status) for s in sessions]}")
        time.sleep(0.05)
    return [s.result(timeout=1.0) for s in sessions]


def phase_a(args):
    """Runs in a child process (the supervisor); returns the served digests
    and the fields of the phase's line."""
    from spark_rapids_jni_tpu.serve import FrontDoor, data_plane

    rows, batch_bytes, params = q6_request(args)
    door = FrontDoor(workers=1, autoscale=False, respawn_max=0,
                     pool_bytes=3 * batch_bytes, max_concurrent=2,
                     heartbeat_ms=HEARTBEAT_MS)
    tail = _LogTail(door.fleet_dir)
    tail.start()
    pids, backends = [], []
    t0 = time.perf_counter()
    try:
        first = door.submit("q6_digest", params, tenant="tenant-a",
                            est_bytes=batch_bytes)
        (dig0, sec0), = _wait_all(door, [first], ANSWER_TIMEOUT_S)
        pids = [w.proc.pid for w in door._workers.values()]
        backends = [w.backend for w in door._workers.values()]
        rest = [door.submit("q6_digest", params, tenant=t,
                            est_bytes=batch_bytes)
                for t in ("tenant-b", "tenant-a")]
        rest.append(door.submit("arrow_batch",
                                {"rows": ARROW_ROWS, "seed": args.seed},
                                tenant="tenant-b"))
        (dig1, sec1), (dig2, sec2), arrow = _wait_all(
            door, rest, ANSWER_TIMEOUT_S)
        wall = time.perf_counter() - t0
        check(dig0 == dig1 == dig2,
              f"phase A: one query, three digests: {dig0} {dig1} {dig2}")
        arrow_digest = data_plane.batch_digest(arrow)
        metrics = door.metrics.snapshot()
    except BaseException:
        tail.stop()
        tail.dump()
        print(f"phase A fleet metrics: {door.metrics.snapshot()}",
              file=sys.stderr, flush=True)
        raise
    finally:
        tail.stop()
        report = door.shutdown()
    check(report["clean"], f"phase A: shutdown not clean: {report}")
    check(metrics["workers_spawned"] == 1,
          f"phase A: {metrics['workers_spawned']} workers were spawned")
    check(backends == [PLATFORM],
          f"phase A: the worker ran on {backends}, not on {PLATFORM!r}")
    for pid in pids:
        check(not os.path.exists(f"/proc/{pid}"),
              f"phase A: worker pid {pid} is still alive after shutdown")
    return dig0, arrow_digest, dict(
        rows=rows, queries=3, steps_per_query=2,
        first_query_s=round(sec0, 3),
        later_query_s=[round(sec1, 3), round(sec2, 3)],
        compile_s=round(sec0 - min(sec1, sec2), 3),
        wall_s=round(wall, 3), digest=dig0[:16],
        arrow_rows=ARROW_ROWS, data_batches=metrics["data_batches"],
        worker_pids=pids, worker_backend=backends[0])


# ---------------------------------------------------------------------------
# phase B: in process, this process holds the chip
# ---------------------------------------------------------------------------

def _take_devices(count):
    """This process's first touch of JAX."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == PLATFORM,
          f"found platform {devs[0].platform!r}, not {PLATFORM!r}")
    check(len(devs) == count,
          f"need {count} device(s), JAX reports {len(devs)}: {devs}")
    return devs


def _peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _q6_reference(k, v, price):
    m = price < 50.0
    ks = k[m].astype(np.int64)
    cnt = np.bincount(ks, minlength=100)
    sums = np.zeros(100, np.int64)
    np.add.at(sums, ks, v[m])
    avg = np.bincount(ks, weights=price[m], minlength=100) / np.maximum(cnt, 1)
    live = cnt > 0
    return np.flatnonzero(live), sums[live], cnt[live], avg[live]


def _q95_reference(fact, dim1, dim2):
    """Both dims carry unique keys, so each inner join keeps the fact rows
    whose key the dim holds; then group by seg: count(*), sum(v)."""
    k, wh, seg, v = (np.asarray(fact[c].data) for c in ("k", "wh", "seg", "v"))
    keep = np.isin(k, np.asarray(dim1["k"].data)) \
        & np.isin(wh, np.asarray(dim2["wh"].data))
    seg, v = seg[keep].astype(np.int64), v[keep]
    orders = np.bincount(seg)
    net = np.zeros(orders.shape[0], np.int64)
    np.add.at(net, seg, v)
    live = orders > 0
    return np.flatnonzero(live), orders[live], net[live]


def _q1_lineitem(rows, seed):
    """The seven LINEITEM columns TPC-H Q1 reads, by dbgen's rules (as
    ``benchmark/configs/tpch-q1.py`` makes them on the device), in numpy:
    name -> (values, spark type).  Decimals are unscaled at scale 2."""
    from spark_rapids_jni_tpu.columnar import types as T

    r = np.random.default_rng(seed)
    qty = r.integers(1, 51, rows)
    part = r.integers(1, 2_000_001, rows)
    retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    ship = r.integers(8035, 10441, rows) + r.integers(1, 122, rows)
    receipt = ship + r.integers(1, 31, rows)
    dec = T.SparkType.decimal(12, 2)
    return {"l_returnflag": (np.where(receipt <= 9298,
                                      2 * r.integers(0, 2, rows), 1),
                             T.INT32),
            "l_linestatus": ((ship > 9298).astype(np.int64), T.INT32),
            "l_quantity": (qty * 100, dec),
            "l_extendedprice": (qty * retail, dec),
            "l_discount": (r.integers(0, 11, rows), dec),
            "l_tax": (r.integers(0, 9, rows), dec),
            "l_shipdate": (ship, T.DATE)}


def _q1_reference(cols):
    """TPC-H Q1 in int64 numpy (a row's charge is under 1.14e11, so 2^22
    rows sum under 2^63), averages HALF_UP at scale 6 in Python ints: the
    ten columns as lists, in ORDER BY order."""
    c = {k: v for k, (v, _t) in cols.items()}
    keep = c["l_shipdate"] <= 10471            # 1998-12-01 less 90 days
    group = (c["l_returnflag"] * 2 + c["l_linestatus"])[keep]
    qty, ext, disc, tax = (c[k][keep] for k in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = ext * (100 - disc)
    charge = disc_price * (100 + tax)
    out = []
    for g in np.unique(group):
        m = group == g
        n = int(m.sum())
        check(int(charge[m].max()) * n < 2**63, "B_plan_tpch_q1: the "
              "reference's int64 sum would wrap")
        sums = [int(x[m].sum()) for x in (qty, ext, disc_price, charge)]
        avgs = [(2 * int(x[m].sum()) * 10**4 + n) // (2 * n)
                for x in (qty, ext, disc)]
        out.append([int(g) // 2, int(g) % 2] + sums + avgs + [n])
    return [list(col) for col in zip(*out)]


def _live_columns(res, ng, names):
    """Host copies of a plan result's live rows, ordered by the first name."""
    n = int(ng)
    cols = []
    for c in names:
        check(bool(np.asarray(res[c].validity)[:n].all()),
              f"null in result column {c}")
        cols.append(np.asarray(res[c].data)[:n])
    order = np.argsort(cols[0], kind="stable")
    return [c[order] for c in cols]


def _run_plan_twice(name, plan_obj, inputs):
    """compile_plan + execute, twice: the second lookup must hit the plan
    cache and trace nothing."""
    import jax

    from spark_rapids_jni_tpu import plan

    traces0 = plan.trace_count()
    t0 = time.perf_counter()
    cp = plan.compile_plan(plan_obj, inputs)
    lookup1 = cp.last_lookup
    out = jax.block_until_ready(cp(inputs))
    first_s = time.perf_counter() - t0
    traces1 = plan.trace_count()
    t0 = time.perf_counter()
    cp2 = plan.compile_plan(plan_obj, inputs)
    out2 = jax.block_until_ready(cp2(inputs))
    second_s = time.perf_counter() - t0
    check(lookup1 == "miss" and traces1 == traces0 + 1,
          f"{name}: first lookup {lookup1}, {traces1 - traces0} traces")
    check(cp2 is cp and cp2.last_lookup == "hit"
          and plan.trace_count() == traces1,
          f"{name}: second execution was not a plan-cache hit "
          f"({cp2.last_lookup}, {plan.trace_count() - traces1} retraces)")
    del out2
    return cp, out, first_s, second_s


def phase_b(args, digest_a, arrow_a):
    devs = _take_devices(1)
    dev = devs[0]

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import config, mem
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.plan import queries
    from spark_rapids_jni_tpu.relational.aggregate import (
        _resolve_groupby_engine, _resolve_onehot_engine)
    from spark_rapids_jni_tpu.relational.join import _resolve_join_engine
    from spark_rapids_jni_tpu.serve import ServeRuntime, data_plane
    from spark_rapids_jni_tpu.serve import worker as worker_mod

    engines = {
        "groupby_engine": f"{config.get('groupby_engine')}->"
                          f"{_resolve_groupby_engine(None)}",
        "join_engine": f"{config.get('join_engine')}->"
                       f"{_resolve_join_engine(None)}",
        "q6_group_path": config.get("q6_group_path"),
        "q6_onehot_engine":
            f"{config.get('q6_onehot_engine')}->"
            f"{_resolve_onehot_engine(config.get('q6_onehot_engine'))}",
    }

    # (i) what a worker runs inside: arena + spill framework + ServeRuntime,
    # the worker's own q6 digest kind over the same seeds as phase A
    rows, batch_bytes, params = q6_request(args)
    spill_dir = os.path.join(args.workdir, "spill")
    os.makedirs(spill_dir, exist_ok=True)
    adaptor = RmmSpark.set_event_handler(
        3 * batch_bytes, host_pool_bytes=16 << 20, poll_ms=10.0)
    mem.install_spill_framework(spill_dir=spill_dir)
    try:
        rt = ServeRuntime(max_concurrent=2, task_id_base=30_000)
        try:
            def query(ctx, sess):
                return worker_mod._qk_q6_digest(ctx, params, sess)

            first = rt.submit(query, est_bytes=batch_bytes,
                              tenant="tenant-a").result(
                                  timeout=ANSWER_TIMEOUT_S)
            later = [rt.submit(query, est_bytes=batch_bytes, tenant=t)
                     for t in ("tenant-b", "tenant-a")]
            later = [s.result(timeout=ANSWER_TIMEOUT_S) for s in later]
        finally:
            clean = rt.shutdown()
        check(clean, "phase B: ServeRuntime.shutdown() left wedged sessions")
        residue = (adaptor.total_allocated(), adaptor.host_total_allocated())
        check(not any(residue), f"phase B: arena not drained: {residue}")
    finally:
        mem.shutdown_spill_framework()
        RmmSpark.clear_event_handler()
    digs = [first[0]] + [d for d, _s in later]
    check(all(d == digest_a for d in digs),
          f"phase B: in-process digests {digs} differ from the served "
          f"digest {digest_a}")
    check(arrow_a == data_plane.batch_digest(
        worker_mod.make_result_batch(ARROW_ROWS, args.seed)),
        "phase B: the served arrow batch differs from the one built here")
    emit("B_runtime", rows=rows, queries=3, digest_equal_to_A=True,
         arrow_equal_to_A=True, first_query_s=round(first[1], 3),
         later_query_s=[round(s, 3) for _d, s in later],
         compile_s=round(first[1] - min(s for _d, s in later), 3),
         peak_bytes=_peak_bytes(dev), engines=engines)

    # (ii) the IR plans under the default knobs against numpy, inputs built
    # on the host from --seed
    k, v, price = ge._example_arrays(rows, seed=args.seed + 7)
    batch = ge._example_batch(rows, seed=args.seed + 7)
    q6_want = _q6_reference(k, v, price)
    del k, v, price

    def q6_plan_against_numpy(phase, avg_tol):
        cp, (res, ng), first_s, second_s = _run_plan_twice(
            phase, queries.q6_plan(), {"batch": batch})
        got = _live_columns(res, ng, ("k", "sum_v", "cnt", "avg_price"))
        check(all(np.array_equal(g, w) for g, w in zip(got[:3], q6_want[:3])),
              f"{phase}: keys, sums or counts differ from the numpy reference")
        rel = float(np.max(np.abs(got[3] - q6_want[3]) / np.abs(q6_want[3])))
        check(rel <= avg_tol, f"{phase}: avg(price) off by {rel} relative, "
                              f"more than {avg_tol}")
        emit(phase, rows=rows, groups=int(ng), first_s=round(first_s, 3),
             second_s=round(second_s, 3),
             compile_s=round(first_s - second_s, 3), second_lookup="hit",
             retraces=0, avg_max_rel_err=rel, avg_tol=avg_tol,
             q6_float_mode=config.get("q6_float_mode"),
             decisions=cp.decisions, peak_bytes=_peak_bytes(dev))
        cp.close()

    # The default q6_float_mode (f32x3) splits price into three f32 limbs
    # exactly and sums each in f32 on the MXU: avg(price) read 1.5804e-05
    # relative off numpy on the chip at 2^24 rows, seed 0 (chip run, PR 24).
    # Spark's own answer is the exact double sum: q6_float_mode=f64 (the
    # doubles as fixed-point digits on the int8 contraction), checked last.
    q6_plan_against_numpy("B_plan_q6", 1e-4)

    nq = 1 << args.log2["q95"]
    fact, dim1, dim2 = ge._q95_batches(nq, seed=args.seed + 19)
    cp, (res, ng), first_s, second_s = _run_plan_twice(
        "B_plan_q95", queries.q95_plan(),
        {"fact": fact, "dim1": dim1, "dim2": dim2})
    got = _live_columns(res, ng, ("seg", "orders", "net"))
    want = _q95_reference(fact, dim1, dim2)
    check(all(np.array_equal(g, w) for g, w in zip(got, want)),
          f"B_plan_q95: result differs from the numpy reference: "
          f"{[g.tolist() for g in got]} vs {[w.tolist() for w in want]}")
    emit("B_plan_q95", fact_rows=nq, dim1_rows=dim1.num_rows,
         groups=int(ng), first_s=round(first_s, 3),
         second_s=round(second_s, 3), compile_s=round(first_s - second_s, 3),
         second_lookup="hit", retraces=0,
         groupby_engine=engines["groupby_engine"],
         join_engine=engines["join_engine"], decisions=cp.decisions,
         peak_bytes=_peak_bytes(dev))
    cp.close()
    del fact, dim1, dim2, res, ng, cp

    # TPC-H Q1: expressions typed by Spark's rules, the two-key bucket, exact
    # decimal128 sums and averages, against int64 numpy
    n1 = 1 << args.log2["tpch_q1"]
    cols = _q1_lineitem(n1, args.seed + 31)
    import jax.numpy as jnp
    from spark_rapids_jni_tpu import plan as plan_mod
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    lineitem = ColumnBatch({
        name: Column(jnp.asarray(v.astype(np.dtype(t.jnp_dtype))),
                     jnp.ones((n1,), jnp.bool_), t)
        for name, (v, t) in cols.items()})
    cp, (res, ng), first_s, second_s = _run_plan_twice(
        "B_plan_tpch_q1", queries.tpch_q1_plan(), {"lineitem": lineitem})
    names = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
             "avg_disc", "count_order")
    types = {c: repr(res[c].dtype) for c in names}
    check(list(types.values()) == [
        "int32", "int32", "decimal(22,2)", "decimal(22,2)", "decimal(36,4)",
        "decimal(38,6)", "decimal(16,6)", "decimal(16,6)", "decimal(16,6)",
        "int64"], f"B_plan_tpch_q1: result types {types}")
    got = [(res[c].to_unscaled_pylist() if hasattr(res[c], "limbs")
            else res[c].to_pylist())[:int(ng)] for c in names]
    want = _q1_reference(cols)
    check(got == want, f"B_plan_tpch_q1: result differs from the numpy "
                       f"reference: {got} vs {want}")
    routes = {k: v for k, v in cp.decisions.items()
              if k.startswith(("project", "sort"))}
    check(len(routes) == 3 and "elided" in routes.get(
        "sort0:l_returnflag,l_linestatus", {}),
        f"B_plan_tpch_q1: decisions {cp.decisions}")
    emit("B_plan_tpch_q1", rows=n1, groups=int(ng),
         first_s=round(first_s, 3), second_s=round(second_s, 3),
         compile_s=round(first_s - second_s, 3), second_lookup="hit",
         retraces=0, result_types=types, decisions=cp.decisions,
         counters={k: plan_mod.plan_cache_metrics()[k] for k in (
             "mul_exact", "mul_rounded", "onehot_slots")},
         peak_bytes=_peak_bytes(dev))
    cp.close()
    del lineitem, res, ng, cp

    # after q95, so that the peak q95 reports is not this one's
    config.set("q6_float_mode", "f64")
    try:
        q6_plan_against_numpy("B_plan_q6_f64", 1e-9)
    finally:
        config.reset("q6_float_mode")
    return devs


# ---------------------------------------------------------------------------
# --chips 4: the exchange across chips against one chip of the four
# ---------------------------------------------------------------------------

def phase_four_chips(args):
    devs = _take_devices(4)

    import jax
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
    from spark_rapids_jni_tpu.parallel import (data_mesh,
                                               distributed_group_by,
                                               distributed_hash_join,
                                               shard_batch)
    from spark_rapids_jni_tpu.relational import AggSpec, group_by, hash_join

    P = 4
    per = 1 << args.log2["shard"]
    n = P * per
    rng = np.random.default_rng(args.seed)
    # the q95 skew: four rows in five carry one key
    k = np.where(rng.random(n) < 0.8, 7, rng.integers(0, 50, n)) \
        .astype(np.int32)
    v = rng.integers(-(10 ** 6), 10 ** 6, n)
    nd = 64
    dk = np.arange(nd, dtype=np.int32)
    ddv = dk.astype(np.int64) * 10

    def fact_on(put):
        ones = put(np.ones((n,), np.bool_))
        return ColumnBatch({"k": Column(put(k), ones, T.INT32),
                            "v": Column(put(v), ones, T.INT64)})

    def dim_on(put):
        ones = put(np.ones((nd,), np.bool_))
        return ColumnBatch({"k": Column(put(dk), ones, T.INT32),
                            "dv": Column(put(ddv), ones, T.INT64)})

    def shards_nonempty(what, tree):
        for leaf in jax.tree_util.tree_leaves(tree):
            sh = leaf.addressable_shards
            check(len(sh) == P and len({s.device for s in sh}) == P
                  and all(s.data.size > 0 for s in sh),
                  f"{what}: not spread over {P} devices: "
                  f"{[(str(s.device), s.data.shape) for s in sh]}")

    aggs = [AggSpec("sum", "v", "sum_v"), AggSpec("count", None, "cnt")]
    mesh = data_mesh(P)
    host = jnp.asarray
    with jax.default_device(jax.devices()[0]):
        fact = shard_batch(fact_on(host), mesh)
        dim = shard_batch(dim_on(host), mesh)
    shards_nonempty("sharded fact", fact)

    def live_mask(counts, total):
        i = jnp.arange(total, dtype=jnp.int32)
        return (i % (total // P)) < jnp.asarray(counts)[i // (total // P)]

    t0 = time.perf_counter()
    res, ng, dropped = distributed_group_by(fact, ["k"], aggs, mesh)
    jax.block_until_ready((res, ng, dropped))
    gb_s = time.perf_counter() - t0
    shards_nonempty("distributed group-by result", res)
    ng_h = np.asarray(ng)
    check(int(np.asarray(dropped).sum()) == 0, "group-by exchange dropped rows")
    check((ng_h > 0).all(), f"a device holds no group: {ng_h.tolist()}")
    gm = np.asarray(live_mask(ng, res.num_rows))
    dist_groups = sorted(zip(np.asarray(res["k"].data)[gm].tolist(),
                             np.asarray(res["sum_v"].data)[gm].tolist(),
                             np.asarray(res["cnt"].data)[gm].tolist()))

    t0 = time.perf_counter()
    jres, jcounts, jdrop = distributed_hash_join(
        fact, dim, ["k"], ["k"], "inner", mesh)
    jax.block_until_ready((jres, jcounts))
    join_s = time.perf_counter() - t0
    shards_nonempty("distributed join result", jres)
    jc = np.asarray(jcounts)
    check(int(np.asarray(jdrop).sum()) == 0, "join exchange dropped rows")
    check((jc > 0).all(), f"a device holds no join row: {jc.tolist()}")
    jm = live_mask(jcounts, jres.num_rows)
    dist_join = (int(jc.sum()),
                 int(jnp.sum(jnp.where(jm, jres["v"].data, 0))),
                 int(jnp.sum(jnp.where(jm, jres["dv"].data, 0))))

    # the same two operators on one device of the four
    one = jax.devices()[P - 1]
    put = lambda a: jax.device_put(a, one)  # noqa: E731
    fact1, dim1 = fact_on(put), dim_on(put)
    t0 = time.perf_counter()
    r1, ng1 = jax.jit(lambda b: group_by(b, ["k"], aggs))(fact1)
    j1, c1 = jax.jit(lambda a, b: hash_join(a, b, ["k"], ["k"], "inner"))(
        fact1, dim1)
    jax.block_until_ready((r1, ng1, j1, c1))
    one_s = time.perf_counter() - t0
    g = int(ng1)
    one_groups = sorted(zip(np.asarray(r1["k"].data)[:g].tolist(),
                            np.asarray(r1["sum_v"].data)[:g].tolist(),
                            np.asarray(r1["cnt"].data)[:g].tolist()))
    m1 = jnp.arange(j1.num_rows, dtype=jnp.int32) < c1
    one_join = (int(c1), int(jnp.sum(jnp.where(m1, j1["v"].data, 0))),
                int(jnp.sum(jnp.where(m1, j1["dv"].data, 0))))

    # and numpy, so that two equal wrong answers do not pass
    uk = np.unique(k)
    ref_groups = [(int(x), int(v[k == x].sum()), int((k == x).sum()))
                  for x in uk]
    check(dist_groups == one_groups == ref_groups,
          f"group-by differs: 4 chips {dist_groups[:3]}.. one chip "
          f"{one_groups[:3]}.. numpy {ref_groups[:3]}..")
    ref_join = (n, int(v.sum()), int(ddv[k].sum()))
    check(dist_join == one_join == ref_join,
          f"join differs: 4 chips {dist_join} one chip {one_join} "
          f"numpy {ref_join}")
    emit("four_chips", rows_per_device=per, rows=n, skew="80% one key",
         groups=len(dist_groups), groups_per_device=ng_h.tolist(),
         join_rows=dist_join[0], join_rows_per_device=jc.tolist(),
         group_by_s=round(gb_s, 3), join_s=round(join_s, 3),
         one_chip_both_s=round(one_s, 3),
         peak_bytes=[_peak_bytes(d) for d in devs])
    return devs


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the exchange across four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None, metavar="LOG2",
                    help="rehearsal: every phase at 2^LOG2 rows, on "
                         "whatever platform JAX_PLATFORMS names; prints no "
                         "result line and exits 3")
    args = ap.parse_args()
    args.log2 = REAL_ROWS if args.rows is None \
        else dict.fromkeys(REAL_ROWS, args.rows)

    if PLATFORM != "tpu" and args.rows is None:
        sys.exit(f"chip_smoke.py: JAX_PLATFORMS={WANT!r} names platform "
                 f"{PLATFORM!r}; this check is for the TPU chip and does "
                 "not run elsewhere")
    for tool in ("make", "g++"):
        # the two native libraries are built on first use from committed
        # sources (mem/rmm_spark.py, io/parquet_footer.py)
        if shutil.which(tool) is None:
            sys.exit(f"chip_smoke.py: {tool!r} is not on PATH; the native "
                     "libraries cannot be built")

    import spark_rapids_jni_tpu  # noqa: F401  (x64 + the compile cache)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        args.workdir = workdir
        try:
            if args.chips == 4:
                devs = phase_four_chips(args)
            else:
                # the supervisor is a process of its own: this one has not
                # touched JAX when phase B starts, and the worker is gone
                spawn = multiprocessing.get_context("spawn")
                with spawn.Pool(1) as supervisor:
                    digest, arrow, served = supervisor.apply(phase_a, (args,))
                emit("A_served", **served)
                devs = phase_b(args, digest, arrow)
        except Failed as e:
            sys.exit(f"chip_smoke.py: FAILED: {e}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rows is not None:
        print(f"chip_smoke.py: rehearsal at 2^{args.rows} rows passed on "
              f"{device}; no result line", file=sys.stderr, flush=True)
        sys.exit(3)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
