#!/usr/bin/env python3
"""The standing check that the query path starts and answers on the chip.

Run with no arguments on a machine with one TPU chip::

    python chip_smoke.py

Phase A serves: a ``FrontDoor`` with ONE worker process, which takes the
chip; q6 digests under two tenants and one arrow batch come back through it.
The supervisor (this process) decodes arrow results into JAX arrays, so it
needs a backend of its own: while the worker lives it is pinned to the host
CPU, and the worker inherits ``JAX_PLATFORMS=tpu`` from the environment.
Phase B runs after the worker has exited: this process takes the chip and
runs what a worker runs inside (spill framework + ``ServeRuntime``), then the
q6 and q95 IR plans against plain numpy references.  One process needs the
chip at any moment, and nothing falls back to the CPU.

``--chips 4`` runs only the exchange across four chips (``parallel/``
through the ``ShuffleService``) against the same operators on one of them.

Every phase prints one JSON line; the last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every number printed is a smoke reading, not a benchmark.
"""

import argparse
import glob
import json
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
# "tpu" or "tpu,cpu": the TPU comes first, and JAX fails if it cannot have it
FOR_CHIP = WANT.split(",")[0].strip().lower() == "tpu"
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
# A worker is lost after 3.5 silent heartbeats, and a worker on the chip is
# silent while its backend starts (about 10 s).  serve_heartbeat_ms (100) is
# for the CPU tests; 2000 lost the worker in 2 chip runs of 6.
HEARTBEAT_MS = 10000.0


def q6_request(args):
    """The worker's q6_digest parameters and the bytes admission charges for
    one of its batches: phases A and B run the same recipe over the same
    seeds, so their digests must agree bit for bit."""
    rows = 1 << args.rows
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
    import jax
    # the supervisor's own arrays (decoded arrow results) live on the host
    # while the worker holds the chip; this touches config, not a backend
    jax.config.update("jax_platforms", "cpu")

    from spark_rapids_jni_tpu.serve import FrontDoor, data_plane

    rows, batch_bytes, params = q6_request(args)
    door = FrontDoor(workers=1, autoscale=False, respawn_max=0,
                     pool_bytes=3 * batch_bytes, max_concurrent=2,
                     heartbeat_ms=HEARTBEAT_MS)
    tail = _LogTail(door.fleet_dir)
    tail.start()
    pids = []
    t0 = time.perf_counter()
    try:
        first = door.submit("q6_digest", params, tenant="tenant-a",
                            est_bytes=batch_bytes)
        (dig0, sec0), = _wait_all(door, [first], ANSWER_TIMEOUT_S)
        pids = [w.proc.pid for w in door._workers.values()]
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
    for pid in pids:
        check(not os.path.exists(f"/proc/{pid}"),
              f"phase A: worker pid {pid} is still alive after shutdown")
    emit("A_served", rows=rows, queries=3, steps_per_query=2,
         first_query_s=round(sec0, 3),
         later_query_s=[round(sec1, 3), round(sec2, 3)],
         compile_s=round(sec0 - min(sec1, sec2), 3),
         wall_s=round(wall, 3), digest=dig0[:16],
         arrow_rows=ARROW_ROWS, data_batches=metrics["data_batches"],
         worker_pids=pids)
    return dig0, arrow_digest


# ---------------------------------------------------------------------------
# phase B: in process, this process holds the chip
# ---------------------------------------------------------------------------

def _take_devices(count, rehearse):
    """First touch of the backend the environment asked for."""
    import jax
    import jax.extend.backend as jeb

    jax.config.update("jax_platforms", WANT)
    jeb.clear_backends()  # drops phase A's host backend, if it was started
    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and not rehearse:
        raise Failed(f"found platform {plat!r}, not 'tpu'")
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
    devs = _take_devices(1, args.rehearse)
    dev = devs[0]

    import jax

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import config, mem
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.plan import queries
    from spark_rapids_jni_tpu.relational.aggregate import \
        _resolve_groupby_engine
    from spark_rapids_jni_tpu.relational.join import _resolve_join_engine
    from spark_rapids_jni_tpu.serve import ServeRuntime, data_plane
    from spark_rapids_jni_tpu.serve import worker as worker_mod

    engines = {
        "groupby_engine": f"{config.get('groupby_engine')}->"
                          f"{_resolve_groupby_engine(None)}",
        "join_engine": f"{config.get('join_engine')}->"
                       f"{_resolve_join_engine(None)}",
        "q6_group_path": config.get("q6_group_path"),
        "q6_onehot_engine": f"{config.get('q6_onehot_engine')}->"
                            + ("scatter" if jax.default_backend() == "cpu"
                               else "xla"),
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

    # (ii) the IR plans against numpy, inputs built on the host from --seed
    k, v, price = ge._example_arrays(rows, seed=args.seed + 7)
    batch = ge._example_batch(rows, seed=args.seed + 7)
    cp, (res, ng), first_s, second_s = _run_plan_twice(
        "q6_plan", queries.q6_plan(), {"batch": batch})
    got = _live_columns(res, ng, ("k", "sum_v", "cnt", "avg_price"))
    want = _q6_reference(k, v, price)
    check(all(np.array_equal(g, w) for g, w in zip(got[:3], want[:3])),
          "q6_plan: keys, sums or counts differ from the numpy reference")
    rel = float(np.max(np.abs(got[3] - want[3]) / np.abs(want[3])))
    # off the CPU the one-hot engine sums the Dekker limbs of price in f32 on
    # the MXU (q6_float_mode=f32x3): the split is exact, the accumulator is
    # not, and the mean holds about 1e-5.  The f64 sums of the CPU hold 1e-9.
    f32_sums = (jax.default_backend() != "cpu"
                and config.get("q6_group_path") == "onehot"
                and config.get("q6_float_mode") == "f32x3")
    tol = 1e-4 if f32_sums else 1e-9
    check(rel <= tol, f"q6_plan: avg(price) off by {rel} relative, "
                      f"more than {tol}")
    emit("B_plan_q6", rows=rows, groups=int(ng), first_s=round(first_s, 3),
         second_s=round(second_s, 3), compile_s=round(first_s - second_s, 3),
         second_lookup="hit", retraces=0, avg_max_rel_err=rel, avg_tol=tol,
         q6_float_mode=config.get("q6_float_mode"),
         decisions=cp.decisions, peak_bytes=_peak_bytes(dev))
    cp.close()
    del batch, res, ng, cp, got, want, k, v, price

    nq = 1 << args.q95_rows
    fact, dim1, dim2 = ge._q95_batches(nq, seed=args.seed + 19)
    inputs = {"fact": fact, "dim1": dim1, "dim2": dim2}
    # groupby_engine is pinned for q95: under 'auto' the plan's last stage is
    # group_by_domain_or_sort, whose sort-scan branch (a 64-bit cumsum inside
    # lax.cond) the v5e compiler refuses for want of scoped vmem at 2^17,
    # 2^22 and 2^23 rows.  'sort' is the engine 'auto' names off the CPU.
    config.set("groupby_engine", "sort")
    try:
        cp, (res, ng), first_s, second_s = _run_plan_twice(
            "q95_plan", queries.q95_plan(), inputs)
    finally:
        config.reset("groupby_engine")
    got = _live_columns(res, ng, ("seg", "orders", "net"))
    want = _q95_reference(fact, dim1, dim2)
    check(all(np.array_equal(g, w) for g, w in zip(got, want)),
          f"q95_plan: result differs from the numpy reference: "
          f"{[g.tolist() for g in got]} vs {[w.tolist() for w in want]}")
    emit("B_plan_q95", fact_rows=nq, dim1_rows=dim1.num_rows,
         groups=int(ng), first_s=round(first_s, 3),
         second_s=round(second_s, 3), compile_s=round(first_s - second_s, 3),
         second_lookup="hit", retraces=0, groupby_engine="sort (pinned)",
         join_engine=engines["join_engine"], decisions=cp.decisions,
         peak_bytes=_peak_bytes(dev))
    cp.close()
    return devs


# ---------------------------------------------------------------------------
# --chips 4: the exchange across chips against one chip of the four
# ---------------------------------------------------------------------------

def phase_four_chips(args):
    devs = _take_devices(4, args.rehearse)

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
    per = 1 << args.shard_rows
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
    ap.add_argument("--rows", type=int, default=24,
                    help="log2 of the q6 batch rows")
    ap.add_argument("--q95-rows", type=int, default=24,
                    help="log2 of the q95 fact rows (2^24, bench_rows_tpu: "
                         "0.97 GB of temporaries with the sort engines)")
    ap.add_argument("--shard-rows", type=int, default=22,
                    help="log2 of the rows per device with --chips 4")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on a platform that is not the "
                         "chip; prints no result line and exits 3")
    args = ap.parse_args()

    if not FOR_CHIP:
        # not the chip's platform list, so looking takes no chip
        import jax

        found = jax.devices()[0].platform
        if found != "tpu" and not args.rehearse:
            sys.exit(f"chip_smoke.py: JAX_PLATFORMS={WANT!r} finds platform "
                     f"{found!r}; this check is for the TPU chip and does "
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
                digest, arrow = phase_a(args)
                devs = phase_b(args, digest, arrow)
        except Failed as e:
            sys.exit(f"chip_smoke.py: FAILED: {e}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke.py: rehearsal passed on {device}; not a chip "
              "run, so no result line", file=sys.stderr, flush=True)
        sys.exit(3)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
