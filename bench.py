"""Flagship benchmark: TPC-DS q6-shaped pipeline throughput on one chip.

Filter (selectivity ~0.5) → group-by(100 keys) with sum/count/avg over N
rows, the minimum end-to-end slice from SURVEY.md §7 Phase 1.  The reference
publishes no numbers, so ``vs_baseline`` is measured against a
numpy single-core implementation of the identical pipeline run in-process —
a stand-in for the CPU Spark executor this layer accelerates.

The parent process launches the measurement in a child (``--child``) on
whatever backend JAX finds, or on the CPU when ``BENCH_FORCE_CPU=1`` says
so.  There is no fallback: a child that fails or hangs is a failed run and
a non-zero exit.  A run that passes prints one JSON line per metric:

  {"metric": ..., "value": N, "unit": "Mrows/s", "vs_baseline": N,
   "platform": "tpu"|"cpu"}

The headline lines are always Mrows/s; micro entries below 0.1 Mrows/s
auto-scale to ``unit: "Krows/s"`` (a 2-decimal 0.0 reads as broken) —
consumers comparing ``value`` across runs must read ``unit``.

``python bench.py --micro`` additionally runs per-kernel microbenchmarks
mirroring the reference's five nvbench targets: row
conversion, string→float, bloom build+probe, murmur3/xxhash64, group-by.

``python bench.py --spill`` runs the q6 shape under an oversubscribed
device arena with the tiered spill framework installed; its JSON line adds
``spill_*_bytes`` counters so captures track spill overhead.

``python bench.py --shuffle`` runs one heavily skewed exchange through the
out-of-core ShuffleService under a capped device arena; its JSON line adds
``shuffle_*`` counters (rounds, skew ratio, spilled bytes).

``python bench.py --plan`` runs q6/q95 plus the IR-only q9 through the
whole-plan compiler (spark_rapids_jni_tpu/plan/); each row's ``note``
carries the plan-cache outcome and the adaptive decisions, and the q95 IR
row's ``vs_baseline`` rides its own only-shrinks floor (ci/q95_floor.json).

``python bench.py --multidevice`` runs the pallas engine tier over an
8-device mesh (virtual on the CPU): an ICI shuffle and a
streaming scan on the fused partition scatter, plus q95 with both
relational engine knobs pinned to pallas — every row parity-asserted
against its lax/default-engine twin before the rate is reported.

``python bench.py --compress`` runs the encoded q95-shape exchange twice
through the same ShuffleService — ``shuffle_compress=off`` then ``pack``
— asserting bit-identical delivered rows; its ``vs_baseline`` is the
wire-byte ratio bytes_moved_off / bytes_moved_pack (only-shrinks floor
``shuffle_compress_floor`` in ci/q95_floor.json), and a second
``spill_codec_roundtrip`` micro row round-trips representative spill
payloads through the mem/codec frames.

``python bench.py --cache`` replays a zipf-skewed q6/q95/q9-shaped
trace through a 2-worker FrontDoor with the fleet result cache on:
repeats must be served from sealed cached Arrow segments bit-identically
with zero compute, the hit rate must clear 0.5, and ``vs_baseline`` is
p99_miss / p99_hit (only-shrinks floor ``result_cache_floor`` in
ci/q95_floor.json).

``python bench.py --elastic`` runs the elastic-fleet scenario: a
skewed-tenant trace (one spill-heavy hog + a stream of one-shot light
tenants) replayed under ``placement=load`` vs ``placement=round_robin``
— ``vs_baseline`` is p99_rr / p99_load over the light latencies
(only-shrinks floor ``placement_p99_floor``) — plus a queue-driven
autoscale phase whose ``note`` carries ``scale_up_ms``/``scale_down_ms``
and must show >=1 scale-up and >=1 drained retirement.
"""

import json
import os
import subprocess
import sys
import time

REPS = int(os.environ.get("BENCH_REPS", 4))
# Total wall-clock budget for the WHOLE bench: the parent bounds its
# child against it (graceful-kill grace 15s included).
TOTAL_BUDGET_S = int(os.environ.get("BENCH_TOTAL_BUDGET_S", "240"))
N_SMALL = 1 << 18  # headline-first size: compile + measure in seconds
for _legacy in ("BENCH_TPU_TIMEOUT_S", "BENCH_CPU_TIMEOUT_S"):
    if os.environ.get(_legacy):
        sys.stderr.write(f"# note: {_legacy} is no longer used; set "
                         "BENCH_TOTAL_BUDGET_S (default 240)\n")


# --------------------------------------------------------------------------
# child: actual measurement (runs on whatever backend JAX resolves)
# --------------------------------------------------------------------------

def _numpy_pipeline(k, v, price):
    import numpy as np

    mask = price < 50.0
    ks, vs, ps = k[mask], v[mask], price[mask]
    uniq, inv = np.unique(ks, return_inverse=True)
    sums = np.bincount(inv, weights=vs.astype(np.float64))
    cnts = np.bincount(inv)
    avgs = np.bincount(inv, weights=ps) / cnts
    return uniq, sums, cnts, avgs


def _measure_devgen(step_fn, gen_fn, n_rows, seed_base, reps):
    """THE generation-subtraction protocol for device-generated inputs,
    shared by every devgen metric (q6, q95): time gen-only and gen+step
    on DISTINCT seed variants, then subtract the generation cost.

    Returns ``(net_mrows, note)``; ``note`` carries the gross rate and
    per-exec generation cost for the emitted JSON line.
    """
    import jax
    import jax.numpy as jnp

    step = jax.jit(step_fn)
    gen = jax.jit(gen_fn)
    seeds = [(jnp.int32(seed_base + i),) for i in range(2 * reps + 2)]
    gen_mrows = _bench_one(gen, seeds[0], n_rows, reps,
                           variants=seeds[:reps + 1])
    gross = _bench_one(step, seeds[reps + 1], n_rows, reps,
                       variants=seeds[reps + 1:])
    t_gen, t_full = n_rows / (gen_mrows * 1e6), n_rows / (gross * 1e6)
    note = {"gen_ms": round(t_gen * 1e3, 2),
            "gross_mrows": round(gross, 2)}
    net = t_full - t_gen
    if net <= t_full * 0.05:  # generation dominates; report gross
        return gross, note
    return n_rows / net / 1e6, note


def _numpy_q95_mrows(n_rows, seed=19):
    """Single-core numpy stand-in for the q95 shape: the unique-key joins
    reduce to payload gathers, the group-by to bincounts (the partition
    staging is a TPU-layout concern a CPU executor never pays).  The
    workload spec (domains, value ranges) is imported from
    __graft_entry__'s Q95_* constants so this baseline can never drift
    from the measured pipeline's data recipe."""
    import numpy as np

    import __graft_entry__ as ge

    rng = np.random.default_rng(seed)
    nd = max(n_rows // ge.Q95_ND_DIV, 1)
    k = rng.integers(0, nd, n_rows).astype(np.int32)
    wh = rng.integers(0, ge.Q95_WH, n_rows).astype(np.int32)
    seg = rng.integers(0, ge.Q95_SEG, n_rows).astype(np.int32)
    v = rng.integers(ge.Q95_V_LO, ge.Q95_V_HI, n_rows)
    d1 = rng.integers(0, ge.Q95_D_HI, nd)
    d2 = rng.integers(0, ge.Q95_D_HI, ge.Q95_WH)

    t0 = time.perf_counter()
    for _ in range(3):
        g1, g2 = d1[k], d2[wh]
        cnt = np.bincount(seg, minlength=ge.Q95_SEG)
        net = np.bincount(seg, weights=v.astype(np.float64),
                          minlength=ge.Q95_SEG)
        _ = (g1.sum(), g2.sum(), cnt, net)
    return n_rows / ((time.perf_counter() - t0) / 3) / 1e6


def _q95_note(ge, nq, qm, use_devgen, left_s):
    """The q95 line's ``note``: chosen engines + per-stage milliseconds
    (VERDICT's fallback done-bar — the emitted capture must defend any
    residual gap by showing where the time goes).  Stage times come from
    cumulative-prefix programs (``_q95_prefix``), differenced; the full
    step's time is derived from the already-measured ``qm`` so the
    breakdown costs three extra small compiles, not four.  Devgen
    (accelerator) runs skip the prefix timing — three more fresh-shape
    compiles don't fit the budget — and still document the engine plan."""
    import functools

    import jax

    from spark_rapids_jni_tpu.parallel import partition as _pt
    from spark_rapids_jni_tpu.relational.aggregate import (
        _resolve_groupby_engine,
    )
    from spark_rapids_jni_tpu.relational.join import _resolve_join_engine

    slots = 9  # P=8 partitions + 1 dead pseudo-partition (_q95_prefix)
    regroup = ("scatter" if jax.default_backend() == "cpu"
               and slots <= _pt._COUNTING_MAX_SLOTS
               and nq * slots <= _pt._COUNTING_MAX_CELLS else "sort")
    note = {"engines": {
        "groupby": _resolve_groupby_engine(None),
        "join": _resolve_join_engine(None),
        "regroup": regroup,
    }}
    if use_devgen or left_s < 60:
        return note
    reps = 2
    seed = [4000]

    def stage_ms(upto):
        jf = jax.jit(functools.partial(ge._q95_prefix, upto=upto))
        vs = [ge._q95_batches(nq, seed=seed[0] + i)
              for i in range(reps + 1)]
        seed[0] += reps + 1
        mrows = _bench_one(jf, vs[0], nq, reps, variants=vs)
        return nq / (mrows * 1e6) * 1e3

    try:
        t1 = stage_ms("exch1")
        t2 = stage_ms("join1")
        t3 = stage_ms("join2")
        t_full = nq / (qm * 1e6) * 1e3
        note["stages_ms"] = {
            "exchange1": round(t1, 2),
            "join1": round(max(t2 - t1, 0.0), 2),
            "exch2_join2": round(max(t3 - t2, 0.0), 2),
            "groupby": round(max(t_full - t3, 0.0), 2),
            "full": round(t_full, 2),
        }
    except Exception as e:  # the note must never sink the metric line
        note["stages_error"] = f"{type(e).__name__}: {e}"
    return note


def _bench_one(jfn, args, n_rows, reps, variants=None):
    """Compile+warm on ``variants[0]``, then time ``variants[1:]`` — each
    executed EXACTLY ONCE.

    A timed rep never repeats a (fn, buffers) pair.  ``reps`` is a cap on
    how many variants are timed; the dispatches are queued back-to-back
    and synced once, so the reported number is pipelined throughput.
    """
    import jax

    variants = list(variants) if variants else [args]
    if len(variants) < 2:
        # re-timing the just-warmed pair would measure the dedupe cache —
        # fail loudly rather than reproduce the invalid protocol
        raise ValueError("_bench_one needs >=2 variants (warm + timed)")
    jax.block_until_ready(jfn(*variants[0]))
    timed = variants[1:1 + reps]
    outs = []
    t0 = time.perf_counter()
    for v in timed:
        outs.append(jfn(*v))
    jax.block_until_ready(outs)
    dt = (time.perf_counter() - t0) / len(timed)
    return n_rows / dt / 1e6  # Mrows/s


def child_main():
    t_start = time.monotonic()
    deadline_s = float(os.environ.get("BENCH_CHILD_DEADLINE_S", "1e9"))

    import numpy as np

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")

    devs = jax.devices()
    platform = devs[0].platform
    print(f"# devices: {devs}", file=sys.stderr, flush=True)

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import config

    is_accel = platform != "cpu"
    n_full = int(os.environ.get("BENCH_N_ROWS", 0)) or config.get(
        "bench_rows_tpu" if is_accel else "bench_rows_cpu")
    if not is_accel:
        from spark_rapids_jni_tpu.relational.aggregate import (
            _resolve_groupby_engine,
        )

        # bench_rows_cpu=1M is sized for the scatter engines (~35ms/iter);
        # the sort/onehot/pallas engines are seconds per iteration on
        # XLA-CPU — an A/B override falling back to CPU must not blow the
        # driver window.  The general path
        # (q6_group_path != 'onehot') is only slow when the groupby_engine
        # knob resolves to 'sort' — since r6 it delegates to the shared
        # engine-selectable group_by, whose auto picks scatter on CPU.
        gp = config.get("q6_group_path")
        slow_general = (gp != "onehot"
                        and _resolve_groupby_engine(None) != "scatter")
        slow_onehot = (gp == "onehot"
                       and config.get("q6_onehot_engine")
                       not in ("auto", "scatter"))
        if slow_general or slow_onehot:
            n_full = min(n_full, 1 << 18)
    jfn = jax.jit(ge._q6_step)

    # Device-side generation (default on accelerators): host-built
    # variants pay their transfer per execution.  A seed scalar input is
    # ~4 bytes; generation cost is measured separately and subtracted.
    use_devgen = is_accel and os.environ.get("BENCH_DEVICE_GEN", "1") != "0"
    devgen_note = {}

    def measure(n_rows):
        if use_devgen:
            mrows, note = _measure_devgen(
                lambda s: ge._q6_step(ge._device_batch(s, n_rows)),
                lambda s: ge._consume_batch(ge._device_batch(s, n_rows)),
                n_rows, 1000, REPS)
            devgen_note[n_rows] = note
            return mrows
        # REPS+1 distinct batches: one to warm, REPS timed once each
        variants = [(ge._example_batch(n_rows, seed=7 + i),)
                    for i in range(REPS + 1)]
        return _bench_one(jfn, variants[0], n_rows, REPS, variants=variants)

    def numpy_mrows(n_rows):
        # the shared host-side recipe — pulling the device copies back
        # would cost hundreds of MB of transfer just to time a CPU baseline
        k, v, price = ge._example_arrays(n_rows, seed=7)
        t0 = time.perf_counter()
        for _ in range(3):
            _numpy_pipeline(k, v, price)
        return n_rows / ((time.perf_counter() - t0) / 3) / 1e6

    def emit(mrows, n_rows, cpu_mrows):
        line = {
            "metric": "q6_pipeline_throughput",
            "value": round(mrows, 2),
            "unit": "Mrows/s",
            "vs_baseline": round(mrows / cpu_mrows, 2),
            "platform": platform,
            "rows": n_rows,
        }
        if n_rows in devgen_note:
            line["devgen"] = devgen_note[n_rows]
        print(json.dumps(line), flush=True)

    # headline FIRST at a small size: a valid line exists within seconds
    # of backend init, no matter what happens to the full-size attempt
    n_small = min(N_SMALL, n_full)
    cpu_mrows = numpy_mrows(n_small)
    mrows = measure(n_small)
    emit(mrows, n_small, cpu_mrows)

    if n_full > n_small:
        # refine only if the scaled steady-state cost + a fresh-shape
        # compile (~40s) plausibly fits the remaining budget; the
        # steady-state per-iter cost extrapolates from the small run
        # accelerator steady-state + fresh-shape compile (~40s) + the
        # numpy re-baseline (host generation + 3 pipeline passes at a
        # conservative 5 Mrows/s)
        # extrapolate from the GROSS rate when devgen subtracted a
        # generation baseline (the net rate can be much higher than what
        # the wall clock pays per execution); devgen compiles TWO fresh
        # shapes (gen + step, ~40s each) and runs 2x(REPS+1) executions,
        # non-devgen one shape and REPS+1
        base_mrows = devgen_note.get(n_small, {}).get("gross_mrows", mrows)
        execs = (2 if use_devgen else 1) * (REPS + 1)
        compile_s = 100.0 if use_devgen else 60.0
        est = ((n_full / (base_mrows * 1e6)) * execs + compile_s
               + 3 * n_full / 5e6)
        left = deadline_s - (time.monotonic() - t_start)
        if est < left:
            # re-baseline numpy at the full size: its Mrows/s drops once
            # the working set leaves cache, and the ratio must compare
            # equal row counts
            emit(measure(n_full), n_full, numpy_mrows(n_full))
        else:
            print(f"# skipping full-size refine: est {est:.0f}s > "
                  f"remaining {left:.0f}s", file=sys.stderr, flush=True)

    # q95-shaped multi-stage entry in the SAME capture (VERDICT r4 item
    # 7): local exchange -> join -> exchange -> join -> group-by prices
    # the shuffle-shaped pipeline alongside the scan-shaped q6.  Runs
    # only if the q6 headline already landed and budget remains; the
    # emit-order in _emit_final keeps q6 as the LAST line either way.
    left = deadline_s - (time.monotonic() - t_start)
    nq = min(n_small, 1 << 17)
    if left < 100:
        print(f"# skipping q95 stage: {left:.0f}s left", file=sys.stderr,
              flush=True)
        return 0
    try:
        if use_devgen:
            qm, _ = _measure_devgen(
                lambda s: ge._q95_step(*ge._device_q95(s, nq)),
                lambda s: ge._consume_q95(*ge._device_q95(s, nq)),
                nq, 5000, REPS)
        else:
            qv = [ge._q95_batches(nq, seed=19 + i) for i in range(REPS + 1)]
            qm = _bench_one(jax.jit(ge._q95_step), qv[0], nq, REPS,
                            variants=qv)
        note = _q95_note(ge, nq, qm, use_devgen,
                         deadline_s - (time.monotonic() - t_start))
        print(json.dumps({
            "metric": "q95_shape_throughput", "value": round(qm, 2),
            "unit": "Mrows/s",
            "vs_baseline": round(qm / _numpy_q95_mrows(nq), 2),
            "platform": platform, "rows": nq, "note": note}), flush=True)
    except Exception as e:  # informative stage: never fail the capture
        print(f"# q95 stage failed: {e}", file=sys.stderr, flush=True)

    # encoded-execution rows (r7): the string-keyed q6 shape decoded vs
    # dictionary-encoded (the acceptance A/B — encoded must win on the
    # CPU smoke shape), and the q95 stage set on encoded wh/seg codes.
    # Encoding is a host-boundary op (np.unique over byte rows), so the
    # devgen path can't build these on device; the variants share one
    # dictionary per column (one dict_token → one compile, the per-file
    # reuse shape encoded execution is designed for).
    left = deadline_s - (time.monotonic() - t_start)
    if use_devgen or left < 60:
        print(f"# skipping encoded rows (devgen={use_devgen}, "
              f"{left:.0f}s left)", file=sys.stderr, flush=True)
        return 0
    ns = min(n_small, 1 << 16)
    try:
        jstr = jax.jit(ge._q6str_step)
        dec_v = [(ge._q6str_batch(ns, seed=37 + i),)
                 for i in range(REPS + 1)]
        dec = _bench_one(jstr, dec_v[0], ns, REPS, variants=dec_v)
        enc_v = ge._q6str_encoded_variants(ns, [37 + i
                                                for i in range(REPS + 1)])
        enc = _bench_one(jstr, enc_v[0], ns, REPS, variants=enc_v)
        print(json.dumps({
            "metric": "q6_strkey_throughput", "value": round(dec, 2),
            "unit": "Mrows/s", "platform": platform, "rows": ns}),
            flush=True)
        print(json.dumps({
            "metric": "q6_encoded_throughput", "value": round(enc, 2),
            "unit": "Mrows/s", "platform": platform, "rows": ns,
            "vs_decoded": round(enc / dec, 2)}), flush=True)
    except Exception as e:
        print(f"# encoded q6 rows failed: {e}", file=sys.stderr, flush=True)
    left = deadline_s - (time.monotonic() - t_start)
    if left < 45:
        print(f"# skipping encoded q95 row: {left:.0f}s left",
              file=sys.stderr, flush=True)
        return 0
    try:
        from spark_rapids_jni_tpu.relational.aggregate import (
            _resolve_groupby_engine,
        )
        from spark_rapids_jni_tpu.relational.join import _resolve_join_engine

        qv = ge._q95_encoded_variants(nq, [59 + i for i in range(REPS + 1)])
        qm_enc = _bench_one(jax.jit(ge._q95_encoded_step), qv[0], nq, REPS,
                            variants=qv)
        print(json.dumps({
            "metric": "q95_shape_encoded_throughput",
            "value": round(qm_enc, 2), "unit": "Mrows/s",
            "vs_baseline": round(qm_enc / _numpy_q95_mrows(nq), 2),
            "platform": platform, "rows": nq,
            "note": {"encoded": ["wh", "seg"],
                     "engines": {"groupby": _resolve_groupby_engine(None),
                                 "join": _resolve_join_engine(None)}}}),
            flush=True)
    except Exception as e:
        print(f"# encoded q95 row failed: {e}", file=sys.stderr, flush=True)
    return 0


# --------------------------------------------------------------------------
# spill scenario (--spill): q6 under an oversubscribed device arena
# --------------------------------------------------------------------------

def spill_main():
    """Two concurrent q6-shaped tasks under a device arena capped below
    their combined working set, with the spill framework installed and NO
    manual ``make_spillable`` — completion requires automatic cross-task
    device→host→disk eviction and read-back.  The emitted line carries the
    per-transition spill-bytes counters so BENCH_*.json tracks spill
    overhead round over round alongside throughput."""
    import tempfile
    import threading

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import mem
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark

    n_rows = int(os.environ.get("BENCH_SPILL_ROWS", str(1 << 16)))
    n_batches = int(os.environ.get("BENCH_SPILL_BATCHES", "4"))
    batch_bytes = mem.batch_nbytes(ge._example_batch(n_rows, seed=7))
    # device arena: 2.5 batches vs the 2x3 live batches the tasks hold at
    # peak; host tier below ONE batch so demotion cascades to disk
    pool = int(batch_bytes * 2.5)
    host_pool = max(batch_bytes // 2, 1 << 16)
    spill_dir = tempfile.mkdtemp(prefix="bench_spill_")
    jfn = jax.jit(ge._q6_step)
    jax.block_until_ready(jfn(ge._example_batch(n_rows, seed=7)))  # warm

    RmmSpark.set_event_handler(pool, host_pool_bytes=host_pool,
                               poll_ms=10.0)
    mem.install_spill_framework(spill_dir=spill_dir)
    fw = mem.get_spill_framework()
    failures = []
    t0 = time.perf_counter()

    def task(task_id, seed0):
        try:
            with mem.TaskContext(task_id) as ctx:
                held = []
                for i in range(n_batches):
                    def step(i=i):
                        b = ge._example_batch(n_rows, seed=seed0 + i)
                        h = mem.SpillableHandle(
                            b, ctx=ctx, name=f"bench-t{task_id}-{i}")
                        jax.block_until_ready(jfn(b))
                        return h
                    held.append(mem.run_with_retry(step, max_retries=50))
                    if len(held) > 3:
                        held.pop(0).close()
                # read back the survivors: disk→host→device + recompute
                for h in held:
                    def read(h=h):
                        jax.block_until_ready(jfn(h.get()))
                    mem.run_with_retry(read, max_retries=50)
                    h.close()
        except Exception as e:
            failures.append(f"task {task_id}: {e!r}")

    threads = [threading.Thread(target=task, args=(tid, 100 * tid),
                                name=f"bench-spill-{tid}")
               for tid in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    snap = fw.metrics.snapshot()
    mem.shutdown_spill_framework()
    RmmSpark.clear_event_handler()
    if failures:
        print(f"# spill scenario failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    total_rows = 2 * n_batches * n_rows
    print(json.dumps({
        "metric": "q6_spill_oversubscribed",
        "value": round(total_rows / dt / 1e6, 2),
        "unit": "Mrows/s",
        "platform": platform,
        "rows": total_rows,
        "device_pool_bytes": pool,
        "host_pool_bytes": host_pool,
        "spill_device_to_host_bytes": snap["device_to_host_bytes"],
        "spill_host_to_disk_bytes": snap["host_to_disk_bytes"],
        "spill_disk_read_bytes": snap["disk_to_host_bytes"],
        "spill_read_back_bytes": snap["host_to_device_bytes"],
        "spill_eviction_ms": round(snap["eviction_ns"] / 1e6, 2),
        "spill_disk_write_failures": snap["disk_write_failures"],
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# serving scenario (--serve): N concurrent tenant streams, solo-identical
# --------------------------------------------------------------------------

def serve_main():
    """N (>=4) concurrent q6-shaped tenant streams through the
    multi-tenant ``ServeRuntime`` sharing one capped arena.  The same
    query set first runs SOLO (``max_concurrent=1`` — same admission /
    ladder / unwind path, zero interleaving) to record per-query latency
    and the per-query result digests; the concurrent wave must be
    bit-identical to solo, and the emitted line carries solo vs
    concurrent p50/p99 so BENCH_*.json tracks the isolation tax.
    ``vs_baseline`` is solo_p99 / concurrent_p99 — the fairness ratio
    the ci/q95_floor.json ``serve_p99_floor`` ratchet guards."""
    import hashlib
    import tempfile

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import numpy as np

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import config, mem
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.serve import ServeRuntime

    n_streams = max(4, int(os.environ.get("BENCH_SERVE_STREAMS", "4")))
    n_queries = int(os.environ.get("BENCH_SERVE_QUERIES", "3"))
    n_rows = int(os.environ.get("BENCH_SERVE_ROWS", str(1 << 14)))
    steps = 2  # q6 steps per query
    batch_bytes = mem.batch_nbytes(ge._example_batch(n_rows, seed=7))
    # arena: one in-flight batch per stream plus headroom — enough
    # contention that admission and the LRU matter, not enough to stall
    pool = int(batch_bytes * (n_streams + 1))
    host_pool = max(batch_bytes, 1 << 16)
    spill_dir = tempfile.mkdtemp(prefix="bench_serve_")
    jfn = jax.jit(ge._q6_step)
    jax.block_until_ready(jfn(ge._example_batch(n_rows, seed=7)))  # warm

    def make_query(stream, k):
        def q(ctx):
            t0 = time.perf_counter()
            dig = hashlib.sha256()
            for s in range(steps):
                b = ge._example_batch(
                    n_rows, seed=1000 * stream + 10 * k + s)
                h = mem.SpillableHandle(
                    b, ctx=ctx, name=f"bench-serve-{stream}-{k}-{s}")
                out = jax.block_until_ready(jfn(b))
                for leaf in jax.tree_util.tree_leaves(out):
                    a = np.asarray(jax.device_get(leaf))
                    dig.update(str(a.dtype).encode())
                    dig.update(str(a.shape).encode())
                    dig.update(np.ascontiguousarray(a).tobytes())
                h.close()
            return dig.hexdigest(), time.perf_counter() - t0
        return q

    def run_wave(max_conc, base):
        rt = ServeRuntime(max_concurrent=max_conc, task_id_base=base)
        t0 = time.perf_counter()
        try:
            sessions = {}
            for i in range(n_streams):
                for k in range(n_queries):
                    sessions[(i, k)] = rt.submit(
                        make_query(i, k), est_bytes=batch_bytes,
                        tenant=f"stream-{i}")
            outs = {key: s.result(timeout=300.0)
                    for key, s in sessions.items()}
        finally:
            clean = rt.shutdown()
        wall = time.perf_counter() - t0
        if not clean:
            raise RuntimeError("ServeRuntime.shutdown() left wedged "
                               "sessions")
        return outs, wall

    def _pct(vals, q):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    adaptor = RmmSpark.set_event_handler(pool, host_pool_bytes=host_pool,
                                         poll_ms=10.0)
    mem.install_spill_framework(spill_dir=spill_dir)
    # solo may queue the whole wave behind one slot; don't let the
    # admission deadline turn a slow CPU box into a bogus QueryTimeout
    config.set("serve_admit_timeout_s", 300.0)
    try:
        solo, solo_wall = run_wave(1, 30_000)
        conc, wall = run_wave(n_streams, 40_000)
        # read residue BEFORE teardown: clear_event_handler frees the
        # native adaptor, so a later call would touch freed memory
        residue = (adaptor.total_allocated(),
                   adaptor.host_total_allocated())
    except Exception as e:
        print(f"# serve scenario failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        config.reset("serve_admit_timeout_s")
        mem.shutdown_spill_framework()
        RmmSpark.clear_event_handler()

    drift = [key for key in solo if solo[key][0] != conc[key][0]]
    if drift:
        print(f"# serve scenario: concurrent results DIFFER from solo "
              f"for {sorted(drift)}", file=sys.stderr, flush=True)
        return 1
    if any(residue):
        print(f"# serve scenario: arena not drained after shutdown "
              f"(device={residue[0]}B host={residue[1]}B)",
              file=sys.stderr, flush=True)
        return 1
    # multi-process wave: the SAME query set again, now through the
    # FrontDoor's supervised executor worker processes (each with its
    # own arena + spill store).  The worker-side ``q6_digest`` kind
    # replays the exact solo seeds, so the digests must match solo
    # bit-for-bit across the process boundary.  Runs after the
    # in-process teardown — the supervisor hosts no arena of its own.
    from spark_rapids_jni_tpu.serve import FrontDoor
    mp_workers = max(2, int(os.environ.get("BENCH_SERVE_MP_WORKERS", "2")))
    fd = FrontDoor(workers=mp_workers, pool_bytes=pool,
                   host_pool_bytes=host_pool, max_concurrent=n_streams)
    mp_t0 = time.perf_counter()
    try:
        mp_sessions = {
            (i, k): fd.submit(
                "q6_digest",
                {"rows": n_rows, "stream": i, "query": k, "steps": steps},
                tenant=f"stream-{i}", est_bytes=batch_bytes)
            for i in range(n_streams) for k in range(n_queries)}
        mp = {key: s.result(timeout=300.0)
              for key, s in mp_sessions.items()}
        mp_wall = time.perf_counter() - mp_t0
    except Exception as e:
        print(f"# serve MP wave failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        mp_report = fd.shutdown()
    mp_drift = [key for key in solo if solo[key][0] != mp[key][0]]
    if mp_drift:
        print(f"# serve scenario: MP results DIFFER from solo for "
              f"{sorted(mp_drift)}", file=sys.stderr, flush=True)
        return 1
    if not mp_report["clean"]:
        print(f"# serve scenario: MP fleet shutdown unclean: "
              f"{mp_report['workers']} orphans="
              f"{mp_report['orphan_spill_files']}",
              file=sys.stderr, flush=True)
        return 1

    # TCP sub-wave: the SAME query set a third time, now over the
    # multi-host transport — two workers placed on two named hosts
    # dialing the supervisor's TCP listener (both local here, but
    # crossing the same framed/CRC'd/deadlined wire a remote peer
    # would).  The digests must STILL match solo bit-for-bit: the
    # transport may add latency, never drift.
    tcp_workers = 2
    tfd = FrontDoor(workers=tcp_workers, pool_bytes=pool,
                    host_pool_bytes=host_pool, max_concurrent=n_streams,
                    transport="tcp", hosts="hostA,hostB")
    tcp_t0 = time.perf_counter()
    try:
        tcp_sessions = {
            (i, k): tfd.submit(
                "q6_digest",
                {"rows": n_rows, "stream": i, "query": k, "steps": steps},
                tenant=f"stream-{i}", est_bytes=batch_bytes)
            for i in range(n_streams) for k in range(n_queries)}
        tcp = {key: s.result(timeout=300.0)
               for key, s in tcp_sessions.items()}
        tcp_wall = time.perf_counter() - tcp_t0
    except Exception as e:
        print(f"# serve TCP wave failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        tcp_report = tfd.shutdown()
    tcp_drift = [key for key in solo if solo[key][0] != tcp[key][0]]
    if tcp_drift:
        print(f"# serve scenario: TCP results DIFFER from solo for "
              f"{sorted(tcp_drift)}", file=sys.stderr, flush=True)
        return 1
    if not tcp_report["clean"] or tcp_report["transport"] != "tcp":
        print(f"# serve scenario: TCP fleet shutdown unclean or not tcp: "
              f"transport={tcp_report['transport']} "
              f"workers={tcp_report['workers']}",
              file=sys.stderr, flush=True)
        return 1

    # data-plane sub-wave: columnar RESULT batches (the ``arrow_batch``
    # kind) instead of scalar digests.  The payload crosses the worker
    # boundary as one Arrow IPC stream on the zero-copy data plane —
    # memfd + SCM_RIGHTS on the unix fleet, binary chunk frames on tcp —
    # while only a small JSON descriptor rides the control wire.  The
    # solo arm builds the SAME batches in-process; both fleet arms must
    # produce byte-identical ``batch_digest`` values (NaN payloads,
    # -0.0, dictionary codes and RLE runs all survive the hop), and the
    # note's serve_wire fields ride the ci/q95_floor.json
    # serve_wire_floor ratchet: the descriptor JSON must stay >=10x
    # smaller than the payload bytes it keeps off the JSON wire.
    from spark_rapids_jni_tpu.serve import data_plane as dp_mod
    from spark_rapids_jni_tpu.serve.worker import make_result_batch
    dp_rows = int(os.environ.get("BENCH_SERVE_DP_ROWS", str(1 << 12)))
    n_dp = max(4, n_queries)
    dp_solo = {k: dp_mod.batch_digest(make_result_batch(dp_rows, k))
               for k in range(n_dp)}

    def dp_wave(transport, plane, hosts=None):
        door = FrontDoor(workers=2, pool_bytes=pool,
                         host_pool_bytes=host_pool, max_concurrent=n_dp,
                         transport=transport, hosts=hosts,
                         data_plane_mode=plane)
        t0 = time.perf_counter()
        lat = []
        try:
            sess = [(time.perf_counter(),
                     door.submit("arrow_batch",
                                 {"rows": dp_rows, "seed": k},
                                 tenant=f"dp-{k}"))
                    for k in range(n_dp)]
            digs = {}
            for k, (ts, s) in enumerate(sess):
                digs[k] = dp_mod.batch_digest(s.result(timeout=300.0))
                lat.append((time.perf_counter() - ts) * 1e3)
        finally:
            rep = door.shutdown()
        return digs, lat, rep, time.perf_counter() - t0
    try:
        shm_digs, shm_lat, shm_rep, shm_wall = dp_wave("unix", "shm")
        frm_digs, frm_lat, frm_rep, frm_wall = dp_wave(
            "tcp", "frames", hosts="hostA,hostB")
    except Exception as e:
        print(f"# serve data-plane wave failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    for tag, digs in (("shm", shm_digs), ("frames", frm_digs)):
        dp_drift = [k for k in dp_solo if digs.get(k) != dp_solo[k]]
        if dp_drift:
            print(f"# serve scenario: {tag} data-plane batches DIFFER "
                  f"from solo for {sorted(dp_drift)}",
                  file=sys.stderr, flush=True)
            return 1
    dpi = shm_rep["data_plane"]
    dpf = frm_rep["data_plane"]
    if (dpi["plane"] != "shm" or dpf["plane"] != "frames"
            or dpi["batches"] < n_dp or dpf["batches"] < n_dp
            or dpi["errors"] or dpf["errors"]):
        print(f"# serve scenario: data plane did not carry the batches: "
              f"shm={dpi} frames={dpf}", file=sys.stderr, flush=True)
        return 1

    # recovery sub-wave: the durable shuffle plane.  Wave A runs
    # ``shuffle_digest`` queries under FRESH store keys, so every map
    # shard executes and commits to the fleet-shared ShuffleStore
    # (replayed_shards counts those map runs); wave B re-issues the SAME
    # keys, so every exchange ADOPTS its committed map output instead of
    # re-running it (adopted_shards), and recovery_ms is wave B's wall —
    # what a replacement worker would pay to pick the work back up.
    # Both waves must be digest-identical; the note's recovery fields
    # ride the ci/q95_floor.json serve_recovery_floor ratchet.
    rfd = FrontDoor(workers=1, pool_bytes=pool,
                    host_pool_bytes=host_pool, max_concurrent=1)
    n_rec = max(2, n_queries)

    def rec_wave(tag):
        t0 = time.perf_counter()
        sess = {k: rfd.submit("shuffle_digest",
                              {"seed": k, "rows_per_shard": 64,
                               "store_key": f"bench-rec-{k}"},
                              tenant=f"recovery-{tag}")
                for k in range(n_rec)}
        outs = {k: s.result(timeout=300.0) for k, s in sess.items()}
        return outs, (time.perf_counter() - t0) * 1e3
    try:
        rec_a, replay_ms = rec_wave("a")
        rec_b, recovery_ms = rec_wave("b")
    except Exception as e:
        print(f"# serve recovery wave failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        rfd.shutdown()
    rec_drift = [k for k in rec_a
                 if rec_a[k]["digest"] != rec_b[k]["digest"]]
    if rec_drift:
        print(f"# serve scenario: adopted results DIFFER from the "
              f"original run for keys {sorted(rec_drift)}",
              file=sys.stderr, flush=True)
        return 1
    replayed_shards = sum(int(r["map_runs"]) for r in rec_a.values())
    adopted_shards = sum(int(r["adopted"]) for r in rec_b.values())
    if adopted_shards < 1:
        print("# serve scenario: recovery wave adopted no committed "
              "shards — the durable store path is dead",
              file=sys.stderr, flush=True)
        return 1

    # failover sub-wave: the SUPERVISOR itself killed mid-wave.  The
    # write-ahead session journal (serve/journal.py) makes the front
    # door recoverable: a journaled door takes the same ``q6_digest``
    # query set, is crash-simulated once a live session is RUNNING on a
    # worker, and a FRESH door adopts the same fleet dir — journal
    # replay, dead-generation fencing, resume-token re-dial of the
    # surviving workers, re-placement of every in-flight session.
    # ``failover_recovery_ms`` is the adoption wall (replacement
    # supervisor construction through a fully replayed state); every
    # recovered result must STILL match solo bit for bit, and the
    # note's failover fields ride the ci/q95_floor.json
    # ``failover_recovery_floor`` ratchet.
    ffd = FrontDoor(workers=2, pool_bytes=pool, host_pool_bytes=host_pool,
                    max_concurrent=2, partition_grace_ms=8000.0,
                    reconnect_max=60)
    fo_fleet = ffd.fleet_dir
    afd = None
    try:
        fo_sessions = {
            (i, k): ffd.submit(
                "q6_digest",
                {"rows": n_rows, "stream": i, "query": k, "steps": steps},
                tenant=f"stream-{i}", est_bytes=batch_bytes)
            for i in range(n_streams) for k in range(n_queries)}
        # kill only once the fleet is genuinely mid-wave — a live
        # session placed on a worker — so the recovery claim is never
        # vacuous; if the wave somehow outruns the poll, crash the
        # idle-but-journaled door (adoption must still re-dial workers)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with ffd._lock:
                placed_live = any(
                    s.worker_id is not None and not s.done()
                    for s in fo_sessions.values())
                all_done = all(s.done() for s in fo_sessions.values())
            if placed_live or all_done:
                break
            time.sleep(0.002)
        else:
            print("# serve failover wave: no session ever landed on a "
                  "worker", file=sys.stderr, flush=True)
            return 1
        ffd._simulate_crash()
        fo_t0 = time.perf_counter()
        afd = FrontDoor(workers=2, pool_bytes=pool,
                        host_pool_bytes=host_pool, max_concurrent=2,
                        partition_grace_ms=8000.0, reconnect_max=60,
                        adopt_dir=fo_fleet)
        failover_ms = (time.perf_counter() - fo_t0) * 1e3
        rec = afd.recovered()
        adopt_snap = afd.metrics.snapshot()
        fo = {}
        for key, old in fo_sessions.items():
            if old.sid in rec:
                fo[key] = rec[old.sid].result(timeout=300.0)
            else:  # finished (and delivered) before the crash landed
                fo[key] = old.result(timeout=30.0)
        # quiesce: every adopted worker must finish its resume-token
        # reattach before the drain, or the graceful shutdown op has no
        # link to ride and the worker is misreported wedged
        quiet_by = time.monotonic() + 20.0
        while time.monotonic() < quiet_by:
            with afd._lock:
                ws = list(afd._workers.values())
                quiet = bool(ws) and all(w.state == "healthy"
                                         for w in ws)
            if quiet:
                break
            time.sleep(0.01)
    except Exception as e:
        print(f"# serve failover wave failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        fo_report = afd.shutdown() if afd is not None else None
        ffd.shutdown()  # crashed-door no-op; real reap if crash never fired
    fo_drift = [key for key in solo if solo[key][0] != fo[key][0]]
    if fo_drift:
        print(f"# serve scenario: failover results DIFFER from solo for "
              f"{sorted(fo_drift)}", file=sys.stderr, flush=True)
        return 1
    if fo_report is None or not fo_report["clean"]:
        print(f"# serve scenario: adopted fleet shutdown unclean: "
              f"{(fo_report or {}).get('workers')}",
              file=sys.stderr, flush=True)
        return 1
    adopted_workers = int(adopt_snap.get("adopted_workers", 0))
    if adopted_workers < 1:
        print("# serve scenario: failover adopted no workers — the "
              "resume-token re-dial path is dead",
              file=sys.stderr, flush=True)
        return 1

    solo_lat = [dt * 1e3 for _, dt in solo.values()]
    conc_lat = [dt * 1e3 for _, dt in conc.values()]
    mp_lat = [dt * 1e3 for _, dt in mp.values()]
    total_rows = n_streams * n_queries * steps * n_rows
    conc_p99 = _pct(conc_lat, 0.99)
    print(json.dumps({
        "metric": "serve_concurrent_throughput",
        "value": round(total_rows / wall / 1e6, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(_pct(solo_lat, 0.99) / conc_p99, 3)
        if conc_p99 else 0.0,
        "platform": platform,
        "rows": total_rows,
        "note": {
            "streams": n_streams,
            "queries_per_stream": n_queries,
            "bit_identical": True,
            "solo_p50_ms": round(_pct(solo_lat, 0.5), 2),
            "solo_p99_ms": round(_pct(solo_lat, 0.99), 2),
            "concurrent_p50_ms": round(_pct(conc_lat, 0.5), 2),
            "concurrent_p99_ms": round(conc_p99, 2),
            "solo_wall_s": round(solo_wall, 3),
            "concurrent_wall_s": round(wall, 3),
            "mp_workers": mp_workers,
            "mp_bit_identical": True,
            "mp_p50_ms": round(_pct(mp_lat, 0.5), 2),
            "mp_p99_ms": round(_pct(mp_lat, 0.99), 2),
            "mp_wall_s": round(mp_wall, 3),
            "tcp_workers": tcp_workers,
            "tcp_bit_identical": True,
            "tcp_wall_s": round(tcp_wall, 3),
            "serve_wire": {
                "plane": dpi["plane"],
                "batches": int(dpi["batches"]),
                "shm_bytes": int(dpi["payload_bytes"]),
                "json_bytes": int(dpi["json_bytes"]),
                "reduction": round(
                    dpi["payload_bytes"] / max(1, dpi["json_bytes"]), 1),
                "frames_reduction": round(
                    dpf["payload_bytes"] / max(1, dpf["json_bytes"]), 1),
                "bit_identical": True,
                "p50_ms": round(_pct(shm_lat, 0.5), 2),
                "p99_ms": round(_pct(shm_lat, 0.99), 2),
                "shm_wall_s": round(shm_wall, 3),
                "frames_wall_s": round(frm_wall, 3),
            },
            "adopted_shards": adopted_shards,
            "replayed_shards": replayed_shards,
            "recovery_ms": round(recovery_ms, 2),
            "recovery_vs": round(replay_ms / recovery_ms, 3)
            if recovery_ms else 0.0,
            "failover_recovery_ms": round(failover_ms, 2),
            "adopted_workers": adopted_workers,
            "recovered_sessions": int(
                adopt_snap.get("recovered_sessions", 0)),
            "replayed_sessions": int(
                adopt_snap.get("replayed_sessions", 0)),
            "failover_bit_identical": True,
        },
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# result-cache scenario (--cache): replayed traffic served with zero compute
# --------------------------------------------------------------------------

def cache_main():
    """Replayed heavy-traffic trace through a 2-worker FrontDoor with the
    fleet result cache on: a zipf-skewed repeat stream over a small
    universe of q6/q95/q9-shaped ``arrow_batch`` queries, every submit
    declaring its input's content snapshot id.  The first occurrence of
    each distinct query computes live in a worker and its encoded Arrow
    IPC segment is inserted; every repeat must be served straight from
    the supervisor's sealed cache — before admission, with zero worker
    dispatch — and re-verified under a fresh descriptor (fence epoch,
    snapshot id, chunk CRCs) exactly like a live result.  Every result,
    hit or miss, must match the solo in-process ``batch_digest`` bit for
    bit, and the child fails outright when the replayed trace's hit rate
    drops to 0.5 or below.  ``vs_baseline`` is p99_miss / p99_hit — the
    latency a cache hit removes — riding the ci/q95_floor.json
    ``result_cache_floor`` ratchet."""
    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import random

    from spark_rapids_jni_tpu.serve import FrontDoor
    from spark_rapids_jni_tpu.serve import data_plane as dp_mod
    from spark_rapids_jni_tpu.serve import result_cache as rc_mod
    from spark_rapids_jni_tpu.serve.worker import make_result_batch

    n_submits = int(os.environ.get("BENCH_CACHE_SUBMITS", "96"))
    per_shape = int(os.environ.get("BENCH_CACHE_UNIVERSE", "4"))
    zipf_s = 1.2
    # the three trace shapes: q6-sized scans, the wide q95 join shape,
    # and the small adaptive q9 — distinct row counts so hits span
    # segment sizes, seeds disjoint per (shape, id)
    shapes = (("q6", int(os.environ.get("BENCH_CACHE_Q6_ROWS", "2048"))),
              ("q95", int(os.environ.get("BENCH_CACHE_Q95_ROWS", "4096"))),
              ("q9", int(os.environ.get("BENCH_CACHE_Q9_ROWS", "1024"))))
    universe = [(shape, rows, 100 * si + qi)
                for si, (shape, rows) in enumerate(shapes)
                for qi in range(per_shape)]
    # zipf-skewed replay: rank r drawn with weight 1/(r+1)^s — the
    # repeated-query head dominates, the tail keeps inserting
    rng = random.Random(int(os.environ.get("BENCH_CACHE_SEED", "7")))
    weights = [1.0 / (r + 1) ** zipf_s for r in range(len(universe))]
    trace = rng.choices(universe, weights=weights, k=n_submits)
    for q in universe:  # every distinct query appears at least once
        if q not in trace:
            trace[rng.randrange(len(trace))] = q

    solo = {q: dp_mod.batch_digest(make_result_batch(q[1], q[2]))
            for q in set(trace)}
    snaps = {q: rc_mod.snapshot_for_obj(
        {"shape": q[0], "rows": q[1], "seed": q[2], "gen": 0})
        for q in set(trace)}

    def _pct(vals, q):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    fd = FrontDoor(workers=2, max_concurrent=4)
    hit_lat, miss_lat, drift = [], [], []
    rows_served = 0
    t0 = time.perf_counter()
    try:
        for shape, rows, seed in trace:
            q = (shape, rows, seed)
            qt0 = time.perf_counter()
            sess = fd.submit("arrow_batch", {"rows": rows, "seed": seed},
                             tenant=f"trace-{shape}", snapshot=snaps[q])
            batch = sess.result(timeout=300.0)
            lat_ms = (time.perf_counter() - qt0) * 1e3
            (hit_lat if sess.served_from_cache else miss_lat).append(lat_ms)
            rows_served += rows
            if dp_mod.batch_digest(batch) != solo[q]:
                drift.append(q)
        wall = time.perf_counter() - t0
    except Exception as e:
        print(f"# cache scenario failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        report = fd.shutdown()
    if drift:
        print(f"# cache scenario: served results DIFFER from solo for "
              f"{sorted(set(drift))}", file=sys.stderr, flush=True)
        return 1
    if not report["clean"]:
        print(f"# cache scenario: fleet shutdown unclean: "
              f"{report['workers']}", file=sys.stderr, flush=True)
        return 1
    rc_info = report["result_cache"]
    hit_rate = len(hit_lat) / max(1, n_submits)
    if hit_rate <= 0.5:
        print(f"# cache scenario: hit rate {hit_rate:.2f} <= 0.5 over "
              f"{n_submits} replayed submits ({len(miss_lat)} misses) — "
              f"the cache is not serving the repeat traffic",
              file=sys.stderr, flush=True)
        return 1
    if rc_info["stale_rejected"] or rc_info["corrupt_quarantined"]:
        print(f"# cache scenario: fault-free replay rejected serves: "
              f"{rc_info}", file=sys.stderr, flush=True)
        return 1
    p99_hit = _pct(hit_lat, 0.99)
    p99_miss = _pct(miss_lat, 0.99)
    print(json.dumps({
        "metric": "result_cache_replay_throughput",
        "value": round(rows_served / wall / 1e6, 3),
        "unit": "Mrows/s",
        "vs_baseline": round(p99_miss / p99_hit, 3) if p99_hit else 0.0,
        "platform": platform,
        "rows": rows_served,
        "note": {
            "submits": n_submits,
            "universe": len(universe),
            "zipf_s": zipf_s,
            "shapes": [s for s, _ in shapes],
            "workers": 2,
            "hits": len(hit_lat),
            "misses": len(miss_lat),
            "hit_rate": round(hit_rate, 3),
            "bit_identical": True,
            "p50_hit_ms": round(_pct(hit_lat, 0.5), 2),
            "p99_hit_ms": round(p99_hit, 2),
            "p50_miss_ms": round(_pct(miss_lat, 0.5), 2),
            "p99_miss_ms": round(p99_miss, 2),
            "hit_bytes_served": int(rc_info["hit_bytes_served"]),
            "cache_inserts": int(rc_info["inserts"]),
        },
    }), flush=True)
    return 0


def elastic_main():
    """Elastic-fleet scenario (--elastic): skewed-tenant placement A/B
    plus autoscale reaction latency.

    Phase A replays the same skewed trace twice through a 2-worker
    FrontDoor: one "hog" tenant keeps a spill-heavy query permanently
    in flight on its pinned worker (below capacity, so that worker
    stays a placement candidate), while a stream of one-shot light
    tenants each place a fresh session.  Under ``placement=round_robin``
    the rotation colocates roughly half the light tenants with the hog,
    where they contend on the worker's arena/spill tiers; under
    ``placement=load`` the pong-fed load score steers them to the idle
    worker.  ``vs_baseline`` is p99_round_robin / p99_load over the
    light-tenant latencies — the tail latency load-aware placement
    removes — riding the only-shrinks ``placement_p99_floor`` in
    ci/q95_floor.json, and the child fails outright if load placement's
    p99 exceeds round-robin's.

    Phase B starts a 1-worker fleet with the queue-driven autoscaler on
    aggressive thresholds, bursts it with slow queries, and measures
    ``scale_up_ms`` (burst → first scale-up spawned) and
    ``scale_down_ms`` (backlog drained → first idle worker retired
    through the drain→fence→reap ladder).  At least one scale-up and
    one drained retirement (``fenced_commits == 0``) are mandatory."""
    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import threading

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.serve import FrontDoor

    n_lights = int(os.environ.get("BENCH_ELASTIC_LIGHTS", "14"))
    hog_rows = int(os.environ.get("BENCH_ELASTIC_HOG_ROWS", str(96 << 10)))
    light_rows = int(os.environ.get("BENCH_ELASTIC_LIGHT_ROWS",
                                    str(24 << 10)))

    def _pct(vals, q):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]

    def _placement_arm(mode):
        """One arm of the A/B: hog saturates its pinned worker's spill
        tiers while one-shot light tenants place fresh sessions; returns
        (light latencies ms, colocated count, hog worker id, wall s)."""
        fd = FrontDoor(workers=2, max_concurrent=3, placement=mode,
                       pool_bytes=1 << 20, host_pool_bytes=256 << 10,
                       heartbeat_ms=150.0)
        stop = threading.Event()
        hog_err = []

        def _hog():
            # double-buffered: two walks in flight at all times, so the
            # hog's worker never momentarily reads 0 sessions (a gap
            # would let load placement tie-break a light onto it) yet
            # stays below max_concurrent — a candidate in both modes
            seed = 0
            inflight = []
            try:
                while not stop.is_set():
                    while len(inflight) < 2:
                        seed += 1
                        inflight.append(fd.submit(
                            "spill_walk",
                            {"seed": seed, "rows": hog_rows},
                            tenant="hog-1"))
                    inflight.pop(0).result(timeout=120.0)
                for s in inflight:
                    s.result(timeout=120.0)
            except Exception as e:
                hog_err.append(e)

        t = threading.Thread(target=_hog, name="bench-elastic-hog",
                             daemon=True)
        lat_ms, colo = [], 0
        try:
            t.start()
            # wait for the hog's pin so light placements see its load
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                with fd._lock:
                    hog_wid = fd._pins.get("hog-1")
                if hog_wid is not None:
                    break
                time.sleep(0.01)
            else:
                raise RuntimeError("hog tenant never placed")
            # untimed warmups: fill every open slot on both workers so
            # each compiles the light shape before latencies count
            warm = [fd.submit("spill_walk",
                              {"seed": 900 + i, "rows": light_rows},
                              tenant=f"warm-{mode}-{i}")
                    for i in range(4)]
            for s in warm:
                s.result(timeout=120.0)
            wall0 = time.perf_counter()
            for i in range(n_lights):
                qt0 = time.perf_counter()
                s = fd.submit("spill_walk",
                              {"seed": 1000 + i, "rows": light_rows},
                              tenant=f"lt-{mode}-{i}")
                s.result(timeout=120.0)
                lat_ms.append((time.perf_counter() - qt0) * 1e3)
                if s.worker_id == hog_wid:
                    colo += 1
            wall = time.perf_counter() - wall0
        finally:
            stop.set()
            t.join(timeout=120.0)
            report = fd.shutdown()
        if hog_err:
            raise RuntimeError(f"hog tenant failed: {hog_err[0]!r}")
        if not report["clean"]:
            raise RuntimeError(
                f"placement arm {mode!r} shutdown unclean: "
                f"{report['workers']}")
        return lat_ms, colo, hog_wid, wall

    try:
        lat_load, colo_load, _, wall_load = _placement_arm("load")
        lat_rr, colo_rr, _, wall_rr = _placement_arm("round_robin")
    except Exception as e:
        print(f"# elastic placement A/B failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    p99_load = _pct(lat_load, 0.99)
    p99_rr = _pct(lat_rr, 0.99)
    if p99_load > p99_rr:
        print(f"# elastic scenario: load placement p99 {p99_load:.1f}ms "
              f"EXCEEDS round-robin p99 {p99_rr:.1f}ms — load-aware "
              f"placement is not avoiding the hog's worker "
              f"(colocated load={colo_load} rr={colo_rr})",
              file=sys.stderr, flush=True)
        return 1

    # --- phase B: autoscale reaction latency -----------------------------
    config.set("serve_autoscale_high_water", 1)
    config.set("serve_autoscale_low_water", 0)
    config.set("serve_autoscale_min", 1)
    config.set("serve_autoscale_max", 3)
    config.set("serve_autoscale_hold_ms", 100.0)
    config.set("serve_autoscale_idle_ms", 300.0)
    config.set("serve_autoscale_drain_ms", 4000.0)
    scale_up_ms = scale_down_ms = -1.0
    try:
        fd = FrontDoor(workers=1, max_concurrent=1, heartbeat_ms=60.0,
                       autoscale=True)
        try:
            burst0 = time.perf_counter()
            sessions = [fd.submit("sleep", {"seconds": 0.4},
                                  tenant=f"burst-{i}") for i in range(6)]
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if fd.metrics.snapshot()["scale_ups"] >= 1:
                    scale_up_ms = (time.perf_counter() - burst0) * 1e3
                    break
                time.sleep(0.01)
            for s in sessions:
                s.result(timeout=120.0)
            drain0 = time.perf_counter()
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if fd.metrics.snapshot()["scale_downs"] >= 1:
                    scale_down_ms = (time.perf_counter() - drain0) * 1e3
                    break
                time.sleep(0.01)
            snap = fd.metrics.snapshot()
        finally:
            report = fd.shutdown()
    except Exception as e:
        print(f"# elastic autoscale phase failed: {e!r}", file=sys.stderr,
              flush=True)
        return 1
    finally:
        config.reset("serve_autoscale_high_water")
        config.reset("serve_autoscale_low_water")
        config.reset("serve_autoscale_min")
        config.reset("serve_autoscale_max")
        config.reset("serve_autoscale_hold_ms")
        config.reset("serve_autoscale_idle_ms")
        config.reset("serve_autoscale_drain_ms")
    if snap["scale_ups"] < 1 or scale_up_ms < 0:
        print(f"# elastic scenario: burst never scaled the fleet up "
              f"(scale_ups={snap['scale_ups']})", file=sys.stderr,
              flush=True)
        return 1
    if snap["scale_downs"] < 1 or scale_down_ms < 0:
        print(f"# elastic scenario: idle fleet never scaled down "
              f"(scale_downs={snap['scale_downs']})", file=sys.stderr,
              flush=True)
        return 1
    bad_retired = [e for e in report["retired"]
                   if e["drained"] and e["fenced_commits"]]
    if bad_retired or not any(e["drained"] for e in report["retired"]):
        print(f"# elastic scenario: retirement ladder broken: "
              f"{report['retired']}", file=sys.stderr, flush=True)
        return 1

    print(json.dumps({
        "metric": "elastic_placement_throughput",
        "value": round(2 * n_lights / (wall_load + wall_rr), 3),
        "unit": "q/s",
        "vs_baseline": round(p99_rr / p99_load, 3) if p99_load else 0.0,
        "platform": platform,
        "rows": 2 * n_lights * light_rows,
        "note": {
            "lights": n_lights,
            "workers": 2,
            "hog_rows": hog_rows,
            "light_rows": light_rows,
            "p50_load_ms": round(_pct(lat_load, 0.5), 2),
            "p99_load_ms": round(p99_load, 2),
            "p50_rr_ms": round(_pct(lat_rr, 0.5), 2),
            "p99_rr_ms": round(p99_rr, 2),
            "colocated_load": colo_load,
            "colocated_rr": colo_rr,
            "scaled_up": int(snap["scale_ups"]),
            "scaled_down": int(snap["scale_downs"]),
            "scale_up_ms": round(scale_up_ms, 1),
            "scale_down_ms": round(scale_down_ms, 1),
            "retired_drained": sum(1 for e in report["retired"]
                                   if e["drained"]),
        },
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# shuffle scenario (--shuffle): skewed out-of-core exchange
# --------------------------------------------------------------------------

def shuffle_main():
    """A heavily skewed ``distributed_group_by`` (most rows share one hot
    key, so one partition receives most of the shuffle) through the
    ShuffleService under a device arena capped below the eager shuffle
    working set: completing it requires the skew planner's multi-round
    drain plus spill of idle round buffers.  The emitted line carries
    rounds/capacity/skew/spill counters so BENCH_*.json tracks
    out-of-core shuffle overhead alongside throughput."""
    if os.environ.get("BENCH_FORCE_CPU"):
        # the scenario needs a multi-device mesh; on the CPU carve 8
        # virtual devices (must land before jax initializes)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import tempfile

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu import config, mem
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
    from spark_rapids_jni_tpu.shuffle import ShuffleService, get_registry

    from spark_rapids_jni_tpu.parallel import distributed_group_by
    from spark_rapids_jni_tpu.relational import AggSpec

    P = len(jax.devices())
    mesh = data_mesh(P)
    per_dev = int(os.environ.get("BENCH_SHUFFLE_ROWS", str(1 << 14)))
    n_rows = P * per_dev
    rng = np.random.default_rng(11)
    # most rows share one hot key: its partition receives the bulk of the
    # shuffle, forcing the planner into a multi-round drain
    keys = np.where(rng.random(n_rows) < 0.7, 3,
                    rng.integers(0, 4 * P, n_rows)).astype(np.int64)
    vals = rng.integers(-1000, 1000, n_rows).astype(np.int64)
    batch = shard_batch(ColumnBatch({
        "k": Column(jnp.asarray(keys), jnp.ones((n_rows,), jnp.bool_),
                    T.INT64),
        "v": Column(jnp.asarray(vals), jnp.ones((n_rows,), jnp.bool_),
                    T.INT64)}), mesh)

    config.set("shuffle_capacity_bucket", 256)
    round_rows = int(os.environ.get("BENCH_SHUFFLE_ROUND_ROWS", "512"))
    config.set("shuffle_round_rows", round_rows)
    # arena below the eager working set (map buffer + all round chunks
    # live at once would need several x input size)
    pool = max(int(mem.batch_nbytes(batch) * 2), 1 << 21)
    spill_dir = tempfile.mkdtemp(prefix="bench_shuffle_")
    RmmSpark.set_event_handler(pool, poll_ms=10.0)
    mem.install_spill_framework(spill_dir=spill_dir)
    reg = get_registry()
    reg.reset()
    failures = []
    t0 = time.perf_counter()
    try:
        with mem.TaskContext(1) as ctx:
            res, ng, dropped = distributed_group_by(
                batch, ["k"], [AggSpec("sum", "v", "s")], mesh, ctx=ctx)
            jax.block_until_ready(res["s"].data)
        RmmSpark.task_done(1)
        if int(np.asarray(jax.device_get(dropped)).sum()) != 0:
            failures.append("dropped rows in skewed group-by")
    except Exception as e:
        failures.append(repr(e))
    dt = time.perf_counter() - t0
    snap = reg.metrics.snapshot()
    mem.shutdown_spill_framework()
    RmmSpark.clear_event_handler()
    if failures:
        print(f"# shuffle scenario failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    capacity = max((i.capacity for i in reg.shuffles().values()),
                   default=0)
    print(json.dumps({
        "metric": "shuffle_skew_outofcore",
        "value": round(n_rows / dt / 1e6, 2),
        "unit": "Mrows/s",
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "device_pool_bytes": pool,
        "shuffle_rounds": snap["rounds"],
        "shuffle_capacity": capacity,
        "shuffle_skew_ratio": round(snap["max_skew_ratio"], 2),
        "shuffle_bytes_moved": snap["bytes_moved"],
        "shuffle_spilled_bytes": snap["spilled_bytes"],
        "shuffle_dropped_rows": snap["dropped_rows"],
        "shuffle_io_failures": snap["io_failures"],
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# compress scenario (--compress): packed wire rounds + codec'd spill frames
# --------------------------------------------------------------------------

def compress_main():
    """Compressed-execution evidence, both seams in one child.

    The q95-shaped exchange batch (narrow-range int64 keys, int32
    quantities, bool flags, f32 prices — the shapes the pack planner is
    built for) runs twice through the same ShuffleService:
    ``shuffle_compress=off`` then ``pack``, delivered rows compared
    column for column.  ``vs_baseline`` is the wire-byte ratio
    bytes_moved_off / bytes_moved_pack (only-shrinks
    ``shuffle_compress_floor`` in ci/q95_floor.json) — an HONEST ratio,
    since ``bytes_moved`` already reflects the packed grid.  The second
    row round-trips representative spill payloads through the mem/codec
    frames (``pack`` on narrow ints/bools, ``block`` on repetitive
    bytes), asserting bit-exact decode before reporting the rate."""
    if os.environ.get("BENCH_FORCE_CPU"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
    from spark_rapids_jni_tpu.columnar.encoded import materialize_batch
    from spark_rapids_jni_tpu.mem import codec as spill_codec
    from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
    from spark_rapids_jni_tpu.shuffle import ShuffleService, get_registry

    P = len(jax.devices())
    mesh = data_mesh(P)
    n_rows = int(os.environ.get("BENCH_COMPRESS_ROWS", str(1 << 15)))
    n_rows -= n_rows % P
    rng = np.random.default_rng(23)

    def col(a, t):
        a = np.asarray(a)
        return Column(jnp.asarray(a), jnp.ones((len(a),), jnp.bool_), t)

    batch = shard_batch(ColumnBatch({
        "k": col(rng.integers(0, 1000, n_rows).astype(np.int64), T.INT64),
        "qty": col(rng.integers(-50, 50, n_rows).astype(np.int32),
                   T.INT32),
        "flag": col(rng.integers(0, 2, n_rows).astype(bool), T.BOOLEAN),
        "price": col(rng.standard_normal(n_rows).astype(np.float32),
                     T.FLOAT32)}), mesh)
    svc = ShuffleService(mesh)
    reg = get_registry()
    reg.reset()

    def digest(res):
        b = materialize_batch(res.batch)
        occ = np.asarray(jax.device_get(res.occupancy))
        return [np.asarray(jax.device_get(b[n].data))[occ]
                for n in b.names]

    def run_mode(mode):
        config.set("shuffle_compress", mode)
        try:
            svc.exchange(batch, key_names=("k",))  # warm the jit cache
            t0 = time.perf_counter()
            res = svc.exchange(batch, key_names=("k",))
            jax.block_until_ready(res.occupancy)
            return res, time.perf_counter() - t0
        finally:
            config.reset("shuffle_compress")

    failures = []
    try:
        r_off, _dt_off = run_mode("off")
        r_pack, dt_pack = run_mode("pack")
        bit_identical = all(
            a.dtype == b.dtype and a.shape == b.shape and bool((a == b).all())
            for a, b in zip(digest(r_off), digest(r_pack)))
        if not bit_identical:
            failures.append("packed exchange diverged from the raw wire")
        if r_pack.rows_moved != n_rows or r_off.rows_moved != n_rows:
            failures.append("rows_moved lost rows "
                            f"(off={r_off.rows_moved} "
                            f"pack={r_pack.rows_moved})")
        if r_pack.compressed_bytes_saved <= 0:
            failures.append("pack mode saved no wire bytes")
    except Exception as e:
        failures.append(repr(e))
    if failures:
        print(f"# compress scenario failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    ratio = r_off.bytes_moved / max(r_pack.bytes_moved, 1)
    print(json.dumps({
        "metric": "shuffle_compressed_throughput",
        "value": round(n_rows / dt_pack / 1e6, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(ratio, 2),
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "note": {
            "mode": "pack",
            "bytes_moved": int(r_pack.bytes_moved),
            "bytes_moved_off": int(r_off.bytes_moved),
            "bytes_saved": int(r_pack.compressed_bytes_saved),
            "ratio": round(ratio, 2),
            "bit_identical": bit_identical,
        },
    }), flush=True)

    # spill-codec micro: the two frame codecs on the payload shapes the
    # disk tier actually sees (narrow-range ints + bools → pack;
    # repetitive bytes → block), bit-exact decode asserted in-row
    payloads = [
        ("pack", rng.integers(0, 4096, 1 << 16).astype(np.int64)),
        ("pack", rng.integers(0, 2, 1 << 16).astype(bool)),
        ("block", np.repeat(
            rng.integers(0, 8, 1 << 10), 64).astype(np.int64)),
    ]
    orig_bytes = stored_bytes = 0
    roundtrip_ok = True
    t0 = time.perf_counter()
    for codec, arr in payloads:
        frame = spill_codec.encode_block(arr, codec)
        back = spill_codec.decode_block(frame)
        roundtrip_ok &= (back.dtype == arr.dtype
                         and bool(np.array_equal(back, arr)))
        orig_bytes += arr.nbytes
        stored_bytes += frame.nbytes
    dt_codec = time.perf_counter() - t0
    if not roundtrip_ok:
        print("# compress scenario failed: codec round-trip diverged",
              file=sys.stderr, flush=True)
        return 1
    codec_ratio = orig_bytes / max(stored_bytes, 1)
    print(json.dumps({
        "metric": "spill_codec_roundtrip",
        "value": round(orig_bytes / dt_codec / 1e6, 2),
        "unit": "MB/s",
        "vs_baseline": round(codec_ratio, 2),
        "platform": platform,
        "note": {
            "orig_bytes": int(orig_bytes),
            "compressed_bytes": int(stored_bytes),
            "codec_ratio": round(codec_ratio, 2),
            "bit_identical": roundtrip_ok,
        },
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# selectivity scenario (--selectivity): compressed-domain skip sweep
# --------------------------------------------------------------------------

def selectivity_main():
    """Skip-level evidence: one q6-style filter swept at ~1%/10%/90%
    selectivity over a SORTED FoR-packed column, reporting throughput
    plus blocks skipped at BOTH levels — zone-map morsel skipping
    (``MorselSource.from_batch`` + the encode-time sidecar) and footer
    row-group pruning (``MorselSource.from_parquet`` over the same data
    written as Parquet).  Every selectivity's pruned stream is asserted
    bit-identical to the filtered full stream in-child; the 1% point
    must skip at both levels (``blocks_skipped > 0`` AND
    ``row_groups_pruned > 0``) or the child fails.  ``vs_baseline`` is
    the 1% point's morsel-level skip fraction
    blocks_skipped / (skipped + scanned) — the only-shrinks
    ``blocks_skipped_floor`` in ci/q95_floor.json.  CPU-smoke caveat:
    the throughput column documents the 8-virtual-device CPU shape, not
    accelerator rates."""
    if os.environ.get("BENCH_FORCE_CPU"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
    from spark_rapids_jni_tpu.columnar.encoded import encode_for
    from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
    from spark_rapids_jni_tpu.shuffle import MorselSource, ShuffleService

    P = len(jax.devices())
    mesh = data_mesh(P)
    n_rows = int(os.environ.get("BENCH_SELECTIVITY_ROWS", str(1 << 15)))
    n_rows -= n_rows % P
    rng = np.random.default_rng(29)
    vals = np.sort(rng.integers(0, 1 << 20, n_rows)).astype(np.int64)
    keys = rng.integers(0, 256, n_rows).astype(np.int64)

    def col(a, t):
        a = np.asarray(a)
        return Column(jnp.asarray(a), jnp.ones((len(a),), jnp.bool_), t)

    # the sidecar comes from the encode step: sharding is a pytree
    # round-trip, which deliberately drops the column-attached copy
    zone = encode_for(col(vals, T.INT64), block=256).zone
    if zone is None:
        print("# selectivity scenario failed: encode_for attached no "
              "zone sidecar", file=sys.stderr, flush=True)
        return 1
    batch = shard_batch(ColumnBatch({
        "k": col(keys, T.INT64), "x": col(vals, T.INT64)}), mesh)
    svc = ShuffleService(mesh)
    morsel_rows = max(n_rows // P // 8, 1)

    # the same rows as Parquet for the footer level: sorted order gives
    # the row-group stats the same locality the zone blocks get
    tmpdir = tempfile.mkdtemp(prefix="bench_selectivity_")
    path = os.path.join(tmpdir, "sweep.parquet")
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq

        pq.write_table(pa.table({"k": pa.array(keys, pa.int64()),
                                 "x": pa.array(vals, pa.int64())}),
                       path, row_group_size=max(n_rows // 16, 1))
    except Exception as e:
        print(f"# selectivity scenario failed: parquet write: {e!r}",
              file=sys.stderr, flush=True)
        return 1

    def survivors(res, thresh):
        b = res.batch
        xs = np.asarray(jax.device_get(b["x"].data)).reshape(-1)
        vs = np.asarray(jax.device_get(b["x"].validity)).reshape(-1)
        ks = np.asarray(jax.device_get(b["k"].data)).reshape(-1)
        keep = vs & (xs < thresh)
        return sorted(zip(ks[keep].tolist(), xs[keep].tolist()))

    failures = []
    sweep = []
    try:
        full_src = MorselSource.from_batch(batch, mesh,
                                           morsel_rows=morsel_rows)
        full_res = svc.exchange_stream(full_src, key_names=["k"])
        jax.block_until_ready(full_res.occupancy)
        for sel in (0.01, 0.10, 0.90):
            thresh = int(np.quantile(vals, sel))
            pred = ("x", "<", thresh)
            src = MorselSource.from_batch(batch, mesh,
                                          morsel_rows=morsel_rows,
                                          predicate=pred, zone_map=zone)
            t0 = time.perf_counter()
            res = svc.exchange_stream(src, key_names=["k"])
            jax.block_until_ready(res.occupancy)
            dt = time.perf_counter() - t0
            if survivors(res, thresh) != survivors(full_res, thresh):
                failures.append(f"sel={sel}: pruned stream diverged "
                                "from the filtered full stream")
            counts = {}
            pruned_src = MorselSource.from_parquet(
                path, mesh, columns=["k", "x"],
                morsel_rows=morsel_rows, predicate=pred)
            counts["row_groups_pruned"] = pruned_src.row_groups_pruned
            counts["row_groups_scanned"] = pruned_src.row_groups_scanned
            sweep.append({
                "selectivity": sel,
                "throughput_mrows_s": round(n_rows / dt / 1e6, 2),
                "blocks_skipped": int(src.blocks_skipped),
                "blocks_scanned": int(src.blocks_scanned),
                **counts,
            })
        one_pct = sweep[0]
        if one_pct["blocks_skipped"] <= 0:
            failures.append("1% selectivity skipped no zone-map blocks")
        if one_pct["row_groups_pruned"] <= 0:
            failures.append("1% selectivity pruned no row groups")
    except Exception as e:
        failures.append(repr(e))
    if failures:
        print(f"# selectivity scenario failed: {failures}",
              file=sys.stderr, flush=True)
        return 1
    consulted = one_pct["blocks_skipped"] + one_pct["blocks_scanned"]
    skip_frac = one_pct["blocks_skipped"] / max(consulted, 1)
    print(json.dumps({
        "metric": "selectivity_skip_throughput",
        "value": one_pct["throughput_mrows_s"],
        "unit": "Mrows/s",
        "vs_baseline": round(skip_frac, 2),
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "note": {
            "sweep": sweep,
            "bit_identical": True,
            "blocks_skipped": one_pct["blocks_skipped"],
            "blocks_scanned": one_pct["blocks_scanned"],
            "row_groups_pruned": one_pct["row_groups_pruned"],
            "row_groups_scanned": one_pct["row_groups_scanned"],
            "skip_fraction": round(skip_frac, 2),
            "morsel_rows": morsel_rows,
            "zone_block": int(zone.block),
        },
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# scan scenario (--scan): streaming morsel-driven scan→shuffle pipeline
# --------------------------------------------------------------------------

def scan_main():
    """Out-of-core scan→shuffle: a Parquet input whose decoded size
    exceeds the device arena is streamed morsel-by-morsel through
    ``ShuffleService.exchange_stream`` — row-group decode of morsel k+1
    overlaps the drain of rounds fed by morsels <= k, and round chunks
    demote through the checksummed host→disk spill tiers.  The
    materialized path (read whole file, shard, ``exchange``) is timed as
    the baseline the streaming pipeline replaces (decode + shuffle,
    serialized), so ``vs_baseline`` is the streaming speedup and the
    note records the overlap evidence: decode ms vs drain ms, morsels,
    rounds, and how many rounds drained before end-of-stream
    (``rounds_overlapped`` — the scenario FAILS under 2, matching the
    acceptance bar).  ci/check_q95_line.py holds the row to its own
    only-shrinks floor and fails when the line goes missing."""
    if os.environ.get("BENCH_FORCE_CPU"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import tempfile

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu import config, mem
    from spark_rapids_jni_tpu.io.parquet import read_parquet
    from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
    from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
    from spark_rapids_jni_tpu.shuffle import (
        MorselSource,
        ShuffleService,
        get_registry,
    )

    P = len(jax.devices())
    mesh = data_mesh(P)
    n_rows = int(os.environ.get("BENCH_SCAN_ROWS", str(1 << 16)))
    n_rows -= n_rows % P
    rng = np.random.default_rng(23)
    keys = rng.integers(0, 1 << 20, n_rows).astype(np.int64)
    vals = rng.integers(-1000, 1000, n_rows).astype(np.int64)

    work_dir = tempfile.mkdtemp(prefix="bench_scan_")
    path = os.path.join(work_dir, "scan.parquet")
    # several row groups so the streaming path has real decode units to
    # overlap with the drains
    pq.write_table(pa.table({"k": keys, "v": vals}), path,
                   row_group_size=max(n_rows // 4, 1))
    input_bytes = n_rows * 2 * 8

    morsel_rows = int(os.environ.get("BENCH_SCAN_MORSEL_ROWS", "1024"))
    config.set("scan_morsel_rows", morsel_rows)
    config.set("shuffle_capacity_bucket", 64)
    config.set("shuffle_round_rows",
               int(os.environ.get("BENCH_SCAN_ROUND_ROWS", "128")))
    # device arena BELOW the decoded input: the materialized working set
    # cannot sit resident, so completing either path requires the spill
    # tiers; the streaming path additionally never holds more than the
    # open round chunks + one morsel
    pool = max(input_bytes // 2, 1 << 21)
    spill_dir = tempfile.mkdtemp(prefix="bench_scan_spill_")
    RmmSpark.set_event_handler(pool, poll_ms=10.0)
    mem.install_spill_framework(spill_dir=spill_dir)
    reg = get_registry()
    reg.reset()
    failures = []
    svc = ShuffleService(mesh, "data")

    def digest(res):
        occ = np.asarray(jax.device_get(res.occupancy))
        ks = np.asarray(jax.device_get(res.batch["k"].data))[occ]
        vs = np.asarray(jax.device_get(res.batch["v"].data))[occ]
        order = np.lexsort((vs, ks))
        return ks[order], vs[order]

    mat_dt = stream_dt = 0.0
    info = None
    try:
        with mem.TaskContext(1) as ctx:
            t0 = time.perf_counter()
            batch = shard_batch(read_parquet(path), mesh)
            mat = svc.exchange(batch, key_names=["k"], ctx=ctx)
            jax.block_until_ready(mat.batch["k"].data)
            mat_dt = time.perf_counter() - t0

            t0 = time.perf_counter()
            src = MorselSource.from_parquet(path, mesh)
            res = svc.exchange_stream(src, key_names=["k"], ctx=ctx)
            jax.block_until_ready(res.batch["k"].data)
            stream_dt = time.perf_counter() - t0

            # the two paths shard rows differently (morsels interleave
            # senders), so compare the delivered ROW SET; per-shard
            # bit-identity is tests/test_shuffle_service.py's job
            mk, mv = digest(mat)
            sk, sv = digest(res)
            if not (np.array_equal(mk, sk) and np.array_equal(mv, sv)):
                failures.append("streamed rows != materialized rows")
            if res.rows_moved != n_rows:
                failures.append(
                    f"accounting: {res.rows_moved} != {n_rows}")
            if res.rounds_overlapped < 2:
                failures.append(
                    f"only {res.rounds_overlapped} rounds overlapped "
                    "decode (acceptance needs >= 2)")
            info = res
        RmmSpark.task_done(1)
    except Exception as e:
        failures.append(repr(e))
    snap = reg.metrics.snapshot()
    mem.shutdown_spill_framework()
    RmmSpark.clear_event_handler()
    if failures:
        print(f"# scan scenario failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    mrows = n_rows / stream_dt / 1e6
    mat_mrows = n_rows / mat_dt / 1e6
    print(json.dumps({
        "metric": "scan_stream_throughput",
        "value": round(mrows, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(mrows / mat_mrows, 2),
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "device_pool_bytes": pool,
        "input_bytes": input_bytes,
        "note": {
            "morsels": info.morsels,
            "rounds": info.rounds,
            "rounds_overlapped": info.rounds_overlapped,
            "decode_ms": round(info.decode_ms, 1),
            "drain_ms": round(info.drain_ms, 1),
            "overlap_ratio": round(
                info.rounds_overlapped / max(info.rounds, 1), 2),
            "spilled_bytes": snap["spilled_bytes"],
        },
    }), flush=True)
    return 0


# --------------------------------------------------------------------------
# multidevice scenario (--multidevice): pallas engines across the mesh
# --------------------------------------------------------------------------

def multidevice_main():
    """The pallas engine tier across a real device mesh: 8 devices
    (virtual on the CPU, physical on hardware), the fused radix
    partition scatter driving a genuine ICI shuffle.  Three rows:

    * ``multidevice_shuffle_throughput`` — a multi-round
      ``exchange_stream`` over the mesh with ``shuffle_scatter_engine``
      pinned to pallas, bit-identical (k/v/occupancy, shard for shard)
      to the same stream on the lax engine, which is also the
      ``vs_baseline`` denominator;
    * ``multidevice_scan_stream_throughput`` — the morsel-driven
      Parquet scan→shuffle pipeline on the pallas scatter, delivered
      row set identical to the lax run;
    * ``multidevice_q95_throughput`` — the q95 shape executed with BOTH
      relational engine knobs (``groupby_engine``, ``join_engine``)
      pinned to the pallas tier, group-digest-identical to the
      scatter/hash engines.

    Every row asserts its parity BEFORE reporting a rate — drift fails
    the child outright, the parent gets no metric line, and
    ci/check_q95_line.py fails on the missing row.  Off-accelerator the
    pallas kernels run in interpret mode (same numerics, interpreter
    speed), so vs_baseline documents the interpreter tax on CPU and
    only means a win on hardware (PALLAS_MEMO.md decision rule)."""
    if os.environ.get("BENCH_FORCE_CPU"):
        # the scenario needs a multi-device mesh; on the CPU carve 8
        # virtual devices (must land before jax initializes)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import tempfile

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
    from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
    from spark_rapids_jni_tpu.shuffle import (
        MorselSource,
        ShuffleRegistry,
        ShuffleService,
    )

    P = len(jax.devices())
    if P < 2:
        print(f"# multidevice scenario needs >=2 devices, found {P}",
              file=sys.stderr, flush=True)
        return 1
    mesh = data_mesh(P)
    failures = []

    def emit(row):
        print(json.dumps(row), flush=True)

    # -- row 1: the ICI shuffle.  One in-memory stream, exchanged twice:
    # lax scatter (baseline) then the fused pallas scatter, asserted
    # bit-identical shard for shard before the rate is reported.
    per_dev = int(os.environ.get("BENCH_MD_ROWS", str(1 << 11)))
    n_rows = P * per_dev
    rng = np.random.default_rng(31)
    ones = jnp.ones((n_rows,), jnp.bool_)
    batch = shard_batch(ColumnBatch({
        "k": Column(jnp.asarray(rng.integers(0, 1 << 20, n_rows)), ones,
                    T.INT64),
        "v": Column(jnp.asarray(np.arange(n_rows, dtype=np.int64)), ones,
                    T.INT64)}), mesh)
    config.set("shuffle_capacity_bucket", 64)
    morsel_rows = int(os.environ.get("BENCH_MD_MORSEL_ROWS", "512"))
    round_rows = int(os.environ.get("BENCH_MD_ROUND_ROWS", "128"))

    def stream_once(engine):
        config.set("shuffle_scatter_engine", engine)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        src = MorselSource.from_batch(batch, mesh, morsel_rows=morsel_rows)
        t0 = time.perf_counter()
        res = svc.exchange_stream(list(src), key_names=["k"],
                                  round_rows=round_rows)
        jax.block_until_ready(res.batch["k"].data)
        dt = time.perf_counter() - t0
        arrs = tuple(np.asarray(jax.device_get(x))
                     for x in (res.batch["k"].data, res.batch["v"].data,
                               res.occupancy))
        return res, arrs, dt

    try:
        r_lax, a_lax, dt_lax = stream_once("lax")
        r_pls, a_pls, dt_pls = stream_once("pallas")
        if r_lax.rounds != r_pls.rounds or r_lax.capacity != r_pls.capacity:
            failures.append("shuffle: round/capacity plans diverged "
                            f"({r_lax.rounds}/{r_lax.capacity} vs "
                            f"{r_pls.rounds}/{r_pls.capacity})")
        if r_pls.rows_moved != n_rows:
            failures.append(f"shuffle accounting: {r_pls.rows_moved} "
                            f"!= {n_rows}")
        if r_pls.rounds < 1:
            failures.append("shuffle never went through an ICI round")
        for a, b, nm in zip(a_lax, a_pls, ("k", "v", "occupancy")):
            if not np.array_equal(a, b):
                failures.append(f"shuffle: pallas {nm} shard bytes != lax")
    except Exception as e:
        failures.append(repr(e))
    if failures:
        print(f"# multidevice shuffle failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    mrows = n_rows / dt_pls / 1e6
    emit({
        "metric": "multidevice_shuffle_throughput",
        "value": round(mrows, 3),
        "unit": "Mrows/s",
        "vs_baseline": round(dt_lax / dt_pls, 4),
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "shuffle_rounds": r_pls.rounds,
        "shuffle_capacity": r_pls.capacity,
        "note": {"scatter_engine": "pallas", "parity": "ok",
                 "lax_mrows": round(n_rows / dt_lax / 1e6, 3)},
    })

    # -- row 2: the streaming scan pipeline (Parquet decode overlapping
    # round drains) on the pallas scatter.  The two engines may
    # interleave morsels differently against the decoder, so the parity
    # check compares the delivered ROW SET (occupancy-masked, lexsorted)
    # — per-shard bit-identity on a fixed morsel list is row 1's job.
    work_dir = tempfile.mkdtemp(prefix="bench_md_")
    path = os.path.join(work_dir, "scan.parquet")
    pq.write_table(pa.table({"k": np.asarray(rng.integers(
        0, 1 << 20, n_rows)).astype(np.int64),
        "v": np.arange(n_rows, dtype=np.int64)}), path,
        row_group_size=max(n_rows // 4, 1))

    def rowset(res):
        occ = np.asarray(jax.device_get(res.occupancy))
        ks = np.asarray(jax.device_get(res.batch["k"].data))[occ]
        vs = np.asarray(jax.device_get(res.batch["v"].data))[occ]
        order = np.lexsort((vs, ks))
        return ks[order], vs[order]

    def scan_once(engine):
        config.set("shuffle_scatter_engine", engine)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        t0 = time.perf_counter()
        src = MorselSource.from_parquet(path, mesh)
        res = svc.exchange_stream(src, key_names=["k"],
                                  round_rows=round_rows)
        jax.block_until_ready(res.batch["k"].data)
        return res, time.perf_counter() - t0

    try:
        s_lax, sdt_lax = scan_once("lax")
        s_pls, sdt_pls = scan_once("pallas")
        lk, lv = rowset(s_lax)
        pk, pv = rowset(s_pls)
        if not (np.array_equal(lk, pk) and np.array_equal(lv, pv)):
            failures.append("scan: pallas delivered rows != lax")
        if s_pls.rows_moved != n_rows:
            failures.append(f"scan accounting: {s_pls.rows_moved} "
                            f"!= {n_rows}")
    except Exception as e:
        failures.append(repr(e))
    finally:
        import shutil

        shutil.rmtree(work_dir, ignore_errors=True)
    if failures:
        print(f"# multidevice scan failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    smrows = n_rows / sdt_pls / 1e6
    emit({
        "metric": "multidevice_scan_stream_throughput",
        "value": round(smrows, 3),
        "unit": "Mrows/s",
        "vs_baseline": round(sdt_lax / sdt_pls, 4),
        "platform": platform,
        "rows": n_rows,
        "devices": P,
        "shuffle_rounds": s_pls.rounds,
        "note": {"scatter_engine": "pallas", "parity": "ok",
                 "morsels": s_pls.morsels,
                 "lax_mrows": round(n_rows / sdt_lax / 1e6, 3)},
    })

    # -- row 3: the q95 shape with BOTH relational engine knobs pinned
    # to the pallas tier, against the default scatter/hash engines on
    # the same batches.  The group digest (seg → (orders, net)) must
    # match exactly — the acceptance bar the engine-parity tests hold
    # per kernel, here end to end through the full query.
    import __graft_entry__ as ge

    nq = int(os.environ.get("BENCH_MD_Q95_ROWS", str(1 << 13)))
    V = 3
    q95in = [ge._q95_batches(nq, seed=41 + k) for k in range(V)]

    def groups(res, ng):
        n_g = int(ng)
        k = np.asarray(jax.device_get(res["seg"].data))
        kv = np.asarray(jax.device_get(res["seg"].validity))
        o = np.asarray(jax.device_get(res["orders"].data))
        net = np.asarray(jax.device_get(res["net"].data))
        return {int(k[i]) if kv[i] else None: (int(o[i]), float(net[i]))
                for i in range(n_g)}

    def q95_once(gb_engine, join_engine):
        config.set("groupby_engine", gb_engine)
        config.set("join_engine", join_engine)
        step = jax.jit(lambda f, a, b: ge._q95_step(f, a, b))
        digests = [groups(*jax.device_get(step(*args))) for args in q95in]
        mr = _bench_one(step, q95in[0], nq, reps=2, variants=q95in)
        return digests, mr

    try:
        base_digests, base_mr = q95_once("scatter", "hash")
        pls_digests, pls_mr = q95_once("pallas", "pallas")
        if base_digests != pls_digests:
            failures.append("q95: pallas group digests != scatter/hash")
    except Exception as e:
        failures.append(repr(e))
    finally:
        config.reset()
    if failures:
        print(f"# multidevice q95 failed: {failures}", file=sys.stderr,
              flush=True)
        return 1
    emit({
        "metric": "multidevice_q95_throughput",
        "value": round(pls_mr, 3),
        "unit": "Mrows/s",
        "vs_baseline": round(pls_mr / base_mr, 4),
        "platform": platform,
        "rows": nq,
        "devices": P,
        "note": {"digest_match": True,
                 "engines": {"groupby": "pallas", "join": "pallas"},
                 "baseline_engines": {"groupby": "scatter", "join": "hash"},
                 "baseline_mrows": round(base_mr, 3)},
    })
    return 0


# --------------------------------------------------------------------------
# plan scenario (--plan): q6/q95/q9 through the whole-plan IR compiler
# --------------------------------------------------------------------------

def plan_main():
    """q6, q95 and the IR-only q9 lowered from logical IR into ONE
    jitted program each (spark_rapids_jni_tpu/plan/).  Every timed rep
    goes back through ``compile_plan`` — the first lookup is the miss
    that traces, every later one must be a plan-cache HIT replayed with
    zero retraces — and each emitted row's ``note`` records the cache
    outcome, the retrace count and the adaptive decisions, so
    BENCH_*.json defends the physical plan the compiler actually chose.
    ci/check_q95_line.py holds the q95 IR row to its own only-shrinks
    floor and fails when the q9 row goes missing."""
    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform

    import __graft_entry__ as ge
    from spark_rapids_jni_tpu import plan
    from spark_rapids_jni_tpu.plan import queries

    n_rows = int(os.environ.get("BENCH_PLAN_ROWS",
                                os.environ.get("BENCH_N_ROWS",
                                               str(1 << 16))))
    failures = 0

    def run_query(metric, plan_obj, make_inputs, rows, baseline_mrows=None):
        nonlocal failures
        try:
            variants = [make_inputs(i) for i in range(REPS + 1)]
            t_before = plan.trace_count()
            lookups = []

            def step(inputs):
                cp = plan.compile_plan(plan_obj, inputs)
                lookups.append(cp.last_lookup)
                return cp(inputs)

            mrows = _bench_one(step, (variants[0],), rows, REPS,
                               variants=[(v,) for v in variants])
            retraces = plan.trace_count() - t_before
            cp = plan.compile_plan(plan_obj, variants[0])
            note = {
                # 'hit' only when every post-warm lookup replayed the
                # cached program (the zero-retrace acceptance bar)
                "cache": ("hit" if lookups[0] == "miss"
                          and all(lk == "hit" for lk in lookups[1:])
                          and retraces == 1 else "miss"),
                "retraces": retraces,
                "decisions": cp.decisions,
            }
            cp.close()
            line = {"metric": metric, "value": round(mrows, 2),
                    "unit": "Mrows/s", "platform": platform, "rows": rows,
                    "note": note}
            if baseline_mrows:
                line["vs_baseline"] = round(mrows / baseline_mrows, 2)
            print(json.dumps(line), flush=True)
        except Exception as e:  # emit the other rows; fail the scenario
            failures += 1
            print(f"# {metric} failed: {e!r}", file=sys.stderr, flush=True)

    run_query("q6_ir_throughput", queries.q6_plan(),
              lambda i: {"batch": ge._example_batch(n_rows, seed=7 + i)},
              n_rows)

    nq = min(n_rows, 1 << 17)
    run_query("q95_ir_throughput", queries.q95_plan(),
              lambda i: dict(zip(("fact", "dim1", "dim2"),
                                 ge._q95_batches(nq, seed=19 + i))),
              nq, baseline_mrows=_numpy_q95_mrows(nq))

    # q9 exists ONLY as IR — its broadcast joins are the adaptive
    # layer's decision (the dims sit under broadcast_threshold_rows),
    # recorded in the row's note.decisions
    run_query("q9_ir_throughput", queries.q9_plan(),
              lambda i: dict(zip(("fact", "dim1", "dim2"),
                                 ge._q95_batches(nq, seed=101 + i))),
              nq, baseline_mrows=_numpy_q95_mrows(nq))
    return 1 if failures else 0


# --------------------------------------------------------------------------
# microbenchmarks (mirror the reference's nvbench targets; --micro)
# --------------------------------------------------------------------------

def micro_main():
    t_start = time.monotonic()
    deadline_s = float(os.environ.get("BENCH_CHILD_DEADLINE_S", "1e9"))

    import numpy as np

    import jax

    if os.environ.get("BENCH_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import (
        Column,
        ColumnBatch,
        StringColumn,
    )
    from spark_rapids_jni_tpu.ops import bloom_filter as bf
    from spark_rapids_jni_tpu.ops import cast_string, hashing, row_conversion

    rng = np.random.default_rng(42)
    results = []
    # input variants per kernel: variants[0] warms, the rest are timed
    # once each (the backend dedupes repeated calls — see _bench_one)
    V = 4

    skipped = []

    def over():
        # Self-enforced deadline: the child must EXIT before the parent's
        # graceful-kill window closes.  Reserve ~45s for one fresh-shape
        # TPU compile + measurement.  Checked both in run() AND between
        # the construction blocks below: building variants is itself host
        # generation + transfer work.
        # A BENCH_MICRO_ONLY child is done the moment its entry landed —
        # it must not keep executing micro_main's tail on the clock of
        # the parent that spawned it.
        if only and any(r.get("metric") == only for r in results):
            return True
        return time.monotonic() - t_start > deadline_s - 45

    def finish():
        if skipped:
            print(f"# deadline: skipped {len(skipped)} entries: "
                  f"{', '.join(skipped)}", file=sys.stderr, flush=True)
        # lines were emitted as they were measured; only signal
        # retry-on-CPU if NOTHING was measured
        return 18 if not results or all("error" in r for r in results) \
            else 0

    only = os.environ.get("BENCH_MICRO_ONLY")

    def want(*names):
        """Gate a heavy corpus-construction block in BENCH_MICRO_ONLY
        mode: build it only if one of its entries is the requested one."""
        return (not only) or (only in names)

    def want_isolated(name):
        """Gate construction for an isolate=True entry: its variants are
        only consumed in-process when this IS the isolated child (or the
        platform measures in-process, i.e. off-CPU) — the delegating
        parent must not pay the build just to discard it."""
        if only:
            return only == name
        return jax.default_backend() != "cpu"

    def run(name, jfn, variants, n, unit="Mrows/s", reps=10, isolate=False):
        if only and name != only:
            return
        if over():
            skipped.append(name)
            return
        if isolate and not only and jax.default_backend() == "cpu":
            # XLA-CPU's runtime caches compiled variadic-sort comparators
            # in a process-global registry keyed so that two programs
            # whose sorts differ in operand count collide: the SECOND
            # execution of a decimal group-by/multiply after any other
            # sort has been traced fails with "supplied N buffers but
            # compiled program expected M" (round 4; jax 0.9.0,
            # jax.clear_caches() does not reach it).  These entries
            # therefore measure in a fresh process.  TPU lowers sorts
            # natively (no comparator callback) AND a subprocess could not
            # have the chip its parent holds — so isolate only off
            # accelerator.
            budget = max(10, deadline_s - (time.monotonic() - t_start) - 30)
            env = dict(os.environ)
            env["BENCH_MICRO_ONLY"] = name
            env.setdefault("BENCH_FORCE_CPU", "1")
            print(f"# measuring {name} (isolated)", file=sys.stderr,
                  flush=True)
            def salvage(out, fallback):
                got = None
                for ln in (out or "").splitlines():
                    try:
                        obj = json.loads(ln)
                    except Exception:
                        continue
                    if obj.get("metric") == name:
                        got = obj
                return got if got is not None else \
                    {"metric": name, "error": fallback}

            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--child-micro"],
                    env=env, capture_output=True, text=True,
                    timeout=budget)
                got = salvage(proc.stdout,
                              f"isolated child rc={proc.returncode}")
            except subprocess.TimeoutExpired as e:
                # the child may have printed its metric BEFORE overrunning
                # (it keeps executing micro_main's tail after its entry)
                out = e.stdout
                if isinstance(out, bytes):
                    out = out.decode(errors="replace")
                got = salvage(out, "isolated child timeout")
            results.append(got)
            print(json.dumps(results[-1]), flush=True)
            return
        print(f"# measuring {name}", file=sys.stderr, flush=True)
        try:
            mrows = _bench_one(jfn, variants[0], n, reps, variants=variants)
            # auto-scale tiny rates: a 2-decimal "0.0 Mrows/s" reads as
            # broken when the entry is really 4 Krows/s (TPU-shaped
            # string codes on 1-core XLA-CPU)
            if unit == "Mrows/s" and mrows < 0.1:
                results.append({"metric": name,
                                "value": round(mrows * 1e3, 2),
                                "unit": "Krows/s"})
            else:
                results.append({"metric": name, "value": round(mrows, 2),
                                "unit": unit})
        except Exception as e:  # pragma: no cover - diagnostic path
            results.append({"metric": name, "error": f"{type(e).__name__}: {e}"})
            import traceback

            traceback.print_exc(file=sys.stderr)
        # emit incrementally: a slow-compiling kernel must not hold every
        # earlier measurement hostage (the parent keeps partial results)
        print(json.dumps(results[-1]), flush=True)

    n = 1 << 20
    ones = jnp.ones((n,), jnp.bool_)
    # hash: murmur3 + xxhash64 over int64 column
    vals = [] if not want("murmur3_int64", "xxhash64_int64") else [
        (Column(jnp.asarray(rng.integers(-(2**62), 2**62, n)), ones, T.INT64),)
        for _ in range(V)
    ]
    run("murmur3_int64", jax.jit(lambda c: hashing.murmur_hash3_32([c])), vals, n)
    run("xxhash64_int64", jax.jit(lambda c: hashing.xxhash64([c])), vals, n)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # string→float over padded numeric strings
    if want("string_to_float"):
        scs = [
            (StringColumn.from_pylist(
                ["%.6f" % x for x in rng.random(1 << 18) * 1e6], max_len=13),)
            for _ in range(V)
        ]
        run(
            "string_to_float",
            jax.jit(lambda c: cast_string.string_to_float(c, T.FLOAT64)),
            scs,
            1 << 18,
        )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # bloom build + probe (1M-bit filter)
    items = [] if not want("bloom_build", "bloom_probe") else [
        (Column(jnp.asarray(rng.integers(0, 1 << 40, n)), ones, T.INT64),)
        for _ in range(V)
    ]
    run(
        "bloom_build",
        jax.jit(lambda c: bf.bloom_filter_build(5, 1 << 14, c).bits),
        items,
        n,
    )
    if want("bloom_probe"):
        built = bf.bloom_filter_build(5, 1 << 14, items[0][0])
        run(
            "bloom_probe",
            jax.jit(lambda b, c: bf.bloom_filter_probe(b, c)),
            [(built, it[0]) for it in items],
            n,
        )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # row conversion (8 int64 cols → JCUDF rows)
    m = 1 << 16
    mones = jnp.ones((m,), jnp.bool_)
    cbs = [] if not want("columns_to_rows_8xi64") else [
        (ColumnBatch(
            {
                f"c{i}": Column(jnp.asarray(rng.integers(0, 1 << 30, m)), mones,
                                T.INT64)
                for i in range(8)
            }
        ),)
        for _ in range(V)
    ]
    run(
        "columns_to_rows_8xi64",
        jax.jit(lambda b: row_conversion.convert_to_rows(b)),
        cbs,
        m,
    )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # string hashes (the r5-deleted Pallas variants measured 10-130x
    # slower on v5e than these jnp paths — PALLAS_MEMO.md)
    strs = [] if not want("murmur3_string", "xxhash64_string") else [
        (StringColumn.from_pylist(
            [f"key-{rng.integers(0, 1 << 30)}" for _ in range(1 << 18)],
            pad_to_multiple=16),)
        for _ in range(V)
    ]
    run("murmur3_string", jax.jit(
        lambda c: __import__("spark_rapids_jni_tpu.ops.hashing",
                             fromlist=["x"]).murmur_hash3_32([c])),
        strs, 1 << 18)
    run("xxhash64_string", jax.jit(
        lambda c: __import__("spark_rapids_jni_tpu.ops.hashing",
                             fromlist=["x"]).xxhash64([c])),
        strs, 1 << 18)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # get_json_object (mirrors GET_JSON_OBJECT_BENCH)
    from spark_rapids_jni_tpu.ops.get_json_object import get_json_object

    m_json = 1 << 14
    json_entries = ("get_json_object_owner", "get_json_mixed_flat",
                    "get_json_mixed_bucketed", "get_json_dirty_1pct",
                    "get_json_dirty_10pct")
    jdocs = [] if not want(*json_entries) else [
        ('{"store":{"fruit":[{"weight":%d,"type":"apple"},'
         '{"weight":%d,"type":"pear"}],"basket":[1,2,3]},"email":"x@y.com",'
         '"owner":"amy%d"}') % (rng.integers(1, 99), rng.integers(1, 99), i)
        for i in range(m_json)
    ]
    jcols = [] if not want("get_json_object_owner") else [
        (StringColumn.from_pylist(
            [jdocs[(i + k) % m_json] for i in range(m_json)],
            pad_to_multiple=32),)
        for k in range(V)]
    run(
        "get_json_object_owner",
        jax.jit(lambda c: get_json_object(c, "$.owner")),
        jcols,
        m_json,
        reps=4,
    )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # mixed lengths with a 1% long tail: flat pads EVERY row to the
    # outlier width; bucketed scans each width bucket separately
    from spark_rapids_jni_tpu.columnar import BucketedStringColumn

    long_doc = ('{"store":{"basket":[1,2]},"owner":"big","pad":"%s"}'
                % ("x" * 1400))
    mdocs = [] if not want("get_json_mixed_flat", "get_json_mixed_bucketed") \
        else [long_doc if i % 100 == 0 else jdocs[i] for i in range(m_json)]
    mflat = [] if not want("get_json_mixed_flat") else [
        (StringColumn.from_pylist(
            [mdocs[(i + k) % m_json] for i in range(m_json)],
            pad_to_multiple=32),) for k in range(V)]
    run("get_json_mixed_flat",
        jax.jit(lambda c: get_json_object(c, "$.owner")), mflat, m_json,
        reps=2)
    mbuck = [] if not want("get_json_mixed_bucketed") else [
        (BucketedStringColumn.from_pylist(
            [mdocs[(i + k) % m_json] for i in range(m_json)]),)
        for k in range(V)]
    run("get_json_mixed_bucketed",
        jax.jit(lambda c: get_json_object(c, "$.owner")), mbuck, m_json,
        reps=2)

    # dirty-row-rate sweep (r5 per-row fallback compaction, VERDICT r4
    # weak #2): 1%/10% of rows carry a backslash escape, which flags the
    # fast engine's fallback; those rows must ride the compacted scan
    # sub-batch, keeping throughput within ~2x of the all-clean
    # get_json_object_owner rate instead of collapsing to the
    # whole-batch serial rate.
    dirty_doc = ('{"store":{"basket":[1,2]},"email":"x@y.com",'
                 '"owner":"a\\tb%d"}')
    for entry_name, period in (("get_json_dirty_1pct", 100),
                               ("get_json_dirty_10pct", 10)):
        dcols = [] if not want(entry_name) else [
            (StringColumn.from_pylist(
                [(dirty_doc % i) if i % period == 0
                 else jdocs[(i + k) % m_json] for i in range(m_json)],
                pad_to_multiple=32),)
            for k in range(V)]
        run(entry_name,
            jax.jit(lambda c: get_json_object(c, "$.owner")), dcols,
            m_json, reps=2)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # parse_uri (mirrors PARSE_URI_BENCH)
    from spark_rapids_jni_tpu.ops.parse_uri import parse_uri

    m_uri = 1 << 16
    uris = [] if not want("parse_uri_host") else [
        f"https://user{i}@www.example{i % 97}.com:8443/a/b/c{i}?k={i}&q=7#f"
        for i in range(m_uri)
    ]
    ucols = [] if not want("parse_uri_host") else [
        (StringColumn.from_pylist(
            [uris[(i + k) % m_uri] for i in range(m_uri)],
            pad_to_multiple=32),)
        for k in range(V)]
    run("parse_uri_host", jax.jit(lambda c: parse_uri(c, "HOST")), ucols,
        m_uri, reps=4)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # group-by (100 keys, sum+count) — mirrors the q6 aggregate stage
    from spark_rapids_jni_tpu.relational import AggSpec, group_by

    gbs = [] if not want("group_by_100keys", "group_by_100keys_scatter",
                         "group_by_100keys_domain") \
        else [
        (ColumnBatch(
            {
                "k": Column(jnp.asarray(rng.integers(0, 100, m)), mones, T.INT32),
                "v": Column(jnp.asarray(rng.integers(0, 1000, m)), mones, T.INT64),
            }
        ),)
        for _ in range(V)
    ]
    # engine pinned to 'sort': this row predates the engine knob and must
    # keep measuring the sort-scan path round over round
    run(
        "group_by_100keys",
        jax.jit(
            lambda b: group_by(
                b, ["k"], [AggSpec("sum", "v", "s"), AggSpec("count", None, "c")],
                engine="sort",
            )
        ),
        gbs,
        m,
    )

    # same shape on the r6 scatter engine (slot table + segment sums, no
    # row-sized sort) — the groupby_engine A/B row
    run(
        "group_by_100keys_scatter",
        jax.jit(
            lambda b: group_by(
                b, ["k"], [AggSpec("sum", "v", "s"), AggSpec("count", None, "c")],
                engine="scatter",
            )
        ),
        gbs,
        m,
    )

    # same shape on the domain-key engine (auto: scatter on CPU, MXU
    # one-hot on accelerators) — the q6 fast path vs the general engine
    from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

    run(
        "group_by_100keys_domain",
        jax.jit(
            lambda b: group_by_onehot(
                b, "k", [AggSpec("sum", "v", "s"),
                         AggSpec("count", None, "c")], 100,
                engine="auto",
            )
        ),
        gbs,
        m,
    )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # encoded-execution micro rows (r7): a join keyed on dictionary
    # CODES (both sides share one dictionary/token, so the probe
    # compares single canon words instead of padded-string radix words)
    # and a group-by over an RLE key.  Every variant shares the same
    # dictionary/run-count so the set compiles ONCE (fresh tokens or
    # run shapes would recompile per variant — the same per-file reuse
    # shape the q6/q95 encoded rows measure).
    import dataclasses as _dc

    from spark_rapids_jni_tpu.columnar.encoded import (
        RunLengthColumn,
        dictionary_from_arrays,
    )
    from spark_rapids_jni_tpu.relational import AggSpec as _ASpec
    from spark_rapids_jni_tpu.relational import group_by as _gb
    from spark_rapids_jni_tpu.relational import hash_join as _hjoin

    jds = []
    if want("dict_join_codes"):
        dim_strs = StringColumn.from_pylist(
            [f"sku-{i:04d}" for i in range(1000)], max_len=12)
        base = dictionary_from_arrays(
            rng.integers(0, 1000, m).astype(np.uint32), mones, dim_strs)
        dim_k = _dc.replace(base,
                            codes=jnp.arange(1000, dtype=jnp.uint32),
                            validity=jnp.ones((1000,), jnp.bool_))
        dim = ColumnBatch({
            "k": dim_k,
            "dv": Column(jnp.arange(1000, dtype=jnp.int64),
                         jnp.ones((1000,), jnp.bool_), T.INT64)})
        for i in range(V):
            f = base if i == 0 else _dc.replace(base, codes=jnp.asarray(
                rng.integers(0, 1000, m).astype(np.uint32)))
            jds.append((ColumnBatch({
                "k": f,
                "v": Column(jnp.asarray(rng.integers(0, 100, m)), mones,
                            T.INT64)}), dim))
    run("dict_join_codes",
        jax.jit(lambda f, d: _hjoin(f, d, ["k"], ["k"], "inner")),
        jds, m, reps=4)

    rbs = []
    if want("group_by_rle"):
        runs = 1 << 10
        for i in range(V):
            r = np.random.default_rng(90 + i)
            # cumsum of steps in [1, 50) mod 997: adjacent runs always
            # differ (the RLE invariant encode_rle guarantees)
            vals = (np.cumsum(r.integers(1, 50, runs)) % 997).astype(
                np.int32)
            k = RunLengthColumn(jnp.asarray(vals),
                                jnp.full((runs,), m // runs, jnp.int32),
                                mones, T.INT32)
            rbs.append((ColumnBatch({
                "k": k,
                "v": Column(jnp.asarray(r.integers(0, 1000, m)), mones,
                            T.INT64)}),))
    run("group_by_rle",
        jax.jit(lambda b: _gb(b, ["k"], [_ASpec("sum", "v", "s"),
                                         _ASpec("count", None, "c")])),
        rbs, m, reps=4)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # decimal128 group sum (exact 256-bit segmented sums — the TPC
    # revenue-aggregate shape; see relational/aggregate.py)
    from spark_rapids_jni_tpu.columnar.column import Decimal128Column as _D

    def _dec_gb(seed):
        r = np.random.default_rng(seed)
        limbs = np.zeros((m, 2), np.uint64)
        limbs[:, 0] = r.integers(0, 1 << 50, m, dtype=np.uint64)
        return ColumnBatch({
            "k": Column(jnp.asarray(r.integers(0, 100, m).astype(np.int32)),
                        mones, T.INT32),
            "d": _D(jnp.asarray(limbs), mones,
                    T.SparkType.decimal(38, 2)),
        })

    run(
        "group_by_decimal_sum",
        jax.jit(lambda b: group_by(b, ["k"],
                                   [AggSpec("sum", "d", "s")])[0]["s"].limbs),
        [(_dec_gb(70 + k),) for k in range(V)] if want_isolated(
            "group_by_decimal_sum") else [],
        m,
        isolate=True,
    )

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # the other BASELINE.md query shapes: q3 (join), q67 (window),
    # and the string/regex-heavy config (#4)
    import __graft_entry__ as ge

    nq = 1 << 18
    q3in = [] if not want("q3_join_agg") else [
        ge._q3_batches(nq, seed=11 + k) for k in range(V)]
    run("q3_join_agg", jax.jit(ge._q3_step), q3in, nq, reps=6)
    q67in = [] if not want("q67_window_topk") else [
        (ge._q67_batch(nq, seed=13 + k),) for k in range(V)]
    run("q67_window_topk", jax.jit(ge._q67_step), q67in, nq, reps=6)
    q95in = [] if not want("q95_shape_2exch_2join_agg") else [
        ge._q95_batches(nq, seed=19 + k) for k in range(V)]
    run("q95_shape_2exch_2join_agg", jax.jit(ge._q95_step), q95in, nq,
        reps=4)

    # dim-join engine A/B (r5/r6): general sort-probe vs slot-table
    # hash-probe vs the dense rowid-table path, same fact x dim1 data
    # and output contract.  join_dim_hash predates the join_engine knob
    # and stays pinned to the sorted-build binary-search engine so its
    # round-over-round meaning survives the 'auto' default.
    from spark_rapids_jni_tpu.relational import (
        hash_join as _hj,
        join_dense_or_hash as _jd,
    )

    jv = [] if not want("join_dim_hash", "join_dim_hashprobe",
                        "join_dim_dense") else [
        ge._q95_batches(nq, seed=29 + k) for k in range(V)]
    nd_j = max(nq // ge.Q95_ND_DIV, 1)
    run("join_dim_hash",
        jax.jit(lambda f, d1, d2: _hj(f, d1, ["k"], ["k"], "inner",
                                      engine="sort")),
        jv, nq, reps=4)
    run("join_dim_hashprobe",
        jax.jit(lambda f, d1, d2: _hj(f, d1, ["k"], ["k"], "inner",
                                      engine="hash")),
        jv, nq, reps=4)
    run("join_dim_dense",
        jax.jit(lambda f, d1, d2: _jd(f, d1, "k", "k", nd_j)),
        jv, nq, reps=4)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # pallas device-kernel A/B rows (r14): the fused slot-table build /
    # probe and the radix partition scatter against the lax formulations
    # they mirror, on IDENTICAL inputs.  Parity is asserted IN-ROW on
    # the warm variant (any drift turns the row into an error line), and
    # vs_baseline is pallas/lax throughput.  Off-accelerator the kernels
    # run in interpret mode, so the ratio documents the interpreter tax,
    # not a win — the PALLAS_MEMO.md decision rule keeps 'auto' on the
    # lax tier until a hardware round measures these rows faster.
    from spark_rapids_jni_tpu.ops import pallas_kernels as _PK
    from spark_rapids_jni_tpu.relational import hashtable as _HT

    def _tree_eq(a, b):
        la = jax.tree_util.tree_leaves(jax.device_get(a))
        lb = jax.tree_util.tree_leaves(jax.device_get(b))
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(la, lb))

    def run_pallas_ab(name, lax_fn, pallas_fn, variants, n_ab, reps=10):
        if only and name != only:
            return
        if over():
            skipped.append(name)
            return
        print(f"# measuring {name} (pallas A/B)", file=sys.stderr,
              flush=True)
        try:
            if not _tree_eq(lax_fn(*variants[0]), pallas_fn(*variants[0])):
                raise AssertionError("pallas output != lax output "
                                     "(bit-identity contract broken)")
            lax_m = _bench_one(lax_fn, variants[0], n_ab, reps,
                               variants=variants)
            pls_m = _bench_one(pallas_fn, variants[0], n_ab, reps,
                               variants=variants)
            row = {"metric": name,
                   "vs_baseline": round(pls_m / lax_m, 6),
                   "note": {"parity": "ok",
                            "lax_mrows": round(lax_m, 3),
                            "backend": jax.default_backend()}}
            if pls_m < 0.1:
                row.update(value=round(pls_m * 1e3, 3), unit="Krows/s")
            else:
                row.update(value=round(pls_m, 3), unit="Mrows/s")
            results.append(row)
        except Exception as e:  # pragma: no cover - diagnostic path
            results.append({"metric": name,
                            "error": f"{type(e).__name__}: {e}"})
            import traceback

            traceback.print_exc(file=sys.stderr)
        print(json.dumps(results[-1]), flush=True)

    pallas_rows = ("slot_build_pallas", "slot_probe_pallas",
                   "partition_scatter_pallas")
    n_sl, s_sl, rounds_sl = 1 << 11, 1 << 12, 24
    sl_vars = [] if not want(*pallas_rows) else [
        (jnp.asarray(rng.integers(0, 1 << 20, n_sl).astype(np.uint32)),
         jnp.ones((n_sl,), jnp.bool_))
        for _ in range(V)
    ]
    run_pallas_ab(
        "slot_build_pallas",
        jax.jit(lambda w, lv: _HT.build_slot_table(
            [w], lv, s_sl, max_rounds=rounds_sl, engine="lax")),
        jax.jit(lambda w, lv: _HT.build_slot_table(
            [w], lv, s_sl, max_rounds=rounds_sl, engine="pallas")),
        sl_vars, n_sl)

    pr_vars = []
    if want("slot_probe_pallas"):
        for bw, lv in sl_vars:
            owner, _, _ = jax.jit(lambda w, l: _HT.build_slot_table(
                [w], l, s_sl, max_rounds=rounds_sl))(bw, lv)
            # probe keys half hit, half miss (shifted domain)
            pw = jnp.asarray(rng.integers(0, 1 << 21,
                                          n_sl).astype(np.uint32))
            pr_vars.append((owner, bw, pw, lv))
    run_pallas_ab(
        "slot_probe_pallas",
        jax.jit(lambda ow, bw, pw, lv: _HT.probe_slot_table(
            ow, [bw], [pw], lv, max_rounds=64, engine="lax")),
        jax.jit(lambda ow, bw, pw, lv: _HT.probe_slot_table(
            ow, [bw], [pw], lv, max_rounds=64, engine="pallas")),
        pr_vars, n_sl)

    # the shuffle map step's fused scatter: one morsel routed into the
    # per-partition round window of the send chunks, null-partition rows
    # (pid == P) dropped, exactly as shuffle/service.py's lax body does
    p_sc, c_sc, m_sc, r_sc = 8, 256, 1 << 11, 1

    def _scatter_lax(ck, cv, occv, mk, mv, cnts, base):
        ends = jnp.cumsum(cnts)
        offs = ends - cnts
        i = jnp.arange(m_sc, dtype=jnp.int32)
        d = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        d_c = jnp.minimum(d, p_sc - 1)
        k = jnp.take(base, d_c) + (i - jnp.take(offs, d_c))
        in_round = (d < p_sc) & (k >= r_sc * c_sc) & (k < (r_sc + 1) * c_sc)
        t = jnp.where(in_round, d_c * c_sc + (k - r_sc * c_sc),
                      p_sc * c_sc)
        return (ck.at[t].set(mk, mode="drop"),
                cv.at[t].set(mv, mode="drop"),
                occv.at[t].set(True, mode="drop"))

    def _scatter_pallas(ck, cv, occv, mk, mv, cnts, base):
        (nk, nv), no = _PK.partition_scatter(
            [ck, cv], occv, [mk, mv], cnts, base, jnp.int32(r_sc),
            p_sc, c_sc)
        return nk, nv, no

    sc_vars = []
    if want("partition_scatter_pallas"):
        for _ in range(V):
            parts = rng.integers(0, p_sc + 1, m_sc)  # P == null partition
            cnts = jnp.asarray(np.bincount(np.minimum(parts, p_sc - 1),
                                           minlength=p_sc), jnp.int32)
            sc_vars.append((
                jnp.zeros((p_sc * c_sc,), jnp.int64),
                jnp.zeros((p_sc * c_sc,), jnp.float32),
                jnp.zeros((p_sc * c_sc,), jnp.bool_),
                jnp.asarray(rng.integers(0, 1 << 30, m_sc), jnp.int64),
                jnp.asarray(rng.random(m_sc), jnp.float32),
                cnts,
                jnp.asarray(rng.integers(0, 3 * c_sc, p_sc), jnp.int32)))
    run_pallas_ab("partition_scatter_pallas", jax.jit(_scatter_lax),
                  jax.jit(_scatter_pallas), sc_vars, m_sc)

    if over():
        skipped.append("<remaining suite>")
        return finish()

    # decimal128 multiply (the DecimalUtils hot op; 128-bit limb math)
    from spark_rapids_jni_tpu.columnar.column import Decimal128Column
    from spark_rapids_jni_tpu.ops import decimal as dec

    nd = 1 << 20
    dones = jnp.ones((nd,), jnp.bool_)
    dt = T.SparkType.decimal(38, 2)

    def dec_col(seed):
        r = np.random.default_rng(seed)
        limbs = np.zeros((nd, 2), np.uint64)
        limbs[:, 0] = r.integers(0, 1 << 40, nd, dtype=np.uint64)
        return Decimal128Column(jnp.asarray(limbs), dones, dt)

    decs = [(dec_col(60 + k), dec_col(80 + k)) for k in range(V)] \
        if want_isolated("decimal128_multiply") else []
    run("decimal128_multiply",
        jax.jit(lambda a, b: dec.multiply_decimal128(a, b, 4)[1].limbs),
        decs, nd, isolate=True)
    ns = 1 << 14
    qsin = [(ge._qstr_batch(ns, seed=17 + k),) for k in range(V)] \
        if want("qstr_string_heavy") else []
    run("qstr_string_heavy", jax.jit(ge._qstr_step), qsin, ns, reps=4)

    return finish()


# --------------------------------------------------------------------------
# parent: run one child, relay its metric lines
# --------------------------------------------------------------------------

def _communicate_graceful(proc, timeout_s, grace_s=15):
    """Wait for a child; on timeout SIGTERM → wait ``grace_s`` → SIGKILL.
    Returns (out, err, timed_out)."""
    try:
        out, err = proc.communicate(timeout=timeout_s)
        return out, err, False
    except subprocess.TimeoutExpired:
        proc.terminate()
        try:
            out, err = proc.communicate(timeout=grace_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
        return out, err, True


def _run_child(extra_env, timeout_s, mode):
    """Run a measurement child with a graceful timeout and salvage every
    metric line it managed to flush."""
    env = dict(os.environ)
    env.update(extra_env)
    # the child's own deadline leads the parent's TERM by enough to exit
    # voluntarily
    env.setdefault("BENCH_CHILD_DEADLINE_S", str(max(timeout_s - 10, 10)))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    out, err, timed_out = _communicate_graceful(proc, timeout_s)
    sys.stderr.write((err or "")[-4000:])
    lines = _valid_metric_lines(out or "")
    if lines:
        return lines, None
    return None, "timeout" if timed_out else f"rc={proc.returncode}"


def _valid_metric_lines(out):
    """Only lines that parse as JSON objects with a metric key — a child
    killed mid-write can leave a truncated line that would otherwise be
    'salvaged' here and then dropped by _emit_final, leaving no output."""
    lines = []
    for ln in out.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            if "metric" in json.loads(ln):
                lines.append(ln)
        except Exception:
            continue
    return lines


def _emit_final(lines):
    """Print one line per metric, keeping the LAST (most refined) value.

    The q6 headline always prints LAST: the driver parses the final JSON
    line of the tail as the round's headline metric, and auxiliary
    entries (q95) must not displace it."""
    best = {}
    order = []
    for ln in lines:
        try:
            metric = json.loads(ln).get("metric")
        except Exception:
            continue
        if metric not in best:
            order.append(metric)
        best[metric] = ln
    order.sort(key=lambda m: m == "q6_pipeline_throughput")  # stable
    for metric in order:
        print(best[metric], flush=True)


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode == "--child":
        sys.exit(child_main())
    if mode == "--child-micro":
        sys.exit(micro_main())
    if mode == "--child-spill":
        sys.exit(spill_main())
    if mode == "--child-serve":
        sys.exit(serve_main())
    if mode == "--child-shuffle":
        sys.exit(shuffle_main())
    if mode == "--child-plan":
        sys.exit(plan_main())
    if mode == "--child-scan":
        sys.exit(scan_main())
    if mode == "--child-compress":
        sys.exit(compress_main())
    if mode == "--child-selectivity":
        sys.exit(selectivity_main())
    if mode == "--child-multidevice":
        sys.exit(multidevice_main())
    if mode == "--child-cache":
        sys.exit(cache_main())
    if mode == "--child-elastic":
        sys.exit(elastic_main())

    run_micro = mode == "--micro"
    run_spill = mode == "--spill"
    run_serve = mode == "--serve"
    run_shuffle = mode == "--shuffle"
    run_plan = mode == "--plan"
    run_scan = mode == "--scan"
    run_compress = mode == "--compress"
    run_selectivity = mode == "--selectivity"
    run_multidevice = mode == "--multidevice"
    run_cache = mode == "--cache"
    run_elastic = mode == "--elastic"
    child_mode = ("--child-micro" if run_micro
                  else "--child-spill" if run_spill
                  else "--child-serve" if run_serve
                  else "--child-shuffle" if run_shuffle
                  else "--child-plan" if run_plan
                  else "--child-scan" if run_scan
                  else "--child-compress" if run_compress
                  else "--child-selectivity" if run_selectivity
                  else "--child-multidevice" if run_multidevice
                  else "--child-cache" if run_cache
                  else "--child-elastic" if run_elastic
                  else "--child")
    t0 = time.monotonic()

    def left():
        return TOTAL_BUDGET_S - (time.monotonic() - t0)

    # No probe and no fallback: without BENCH_FORCE_CPU the child runs on
    # whatever backend JAX finds, and a child that fails is a failed run.
    lines, err = _run_child({}, max(left() - 30, 20), child_mode)
    if lines is None:
        print(f"# bench child failed ({err})", file=sys.stderr, flush=True)
        sys.exit(1)
    _emit_final(lines)
    sys.exit(0)


if __name__ == "__main__":
    main()
