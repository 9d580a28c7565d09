"""Deterministic chaos campaign: every fault kind, every boundary, zero drift.

The premerge gate (ci/chaos.sh) that proves the fault-domain story
end-to-end: it sweeps every
registered ``faultinj.FAULT_KINDS`` entry across every instrumented
boundary of fourteen scenarios — a spill walk (device→host→disk→back), an
out-of-core skewed shuffle, the single-chip q95 pipeline, a global
distributed sort across the 8-device mesh, a JNI host-boundary
round-trip, a streaming morsel scan, a multi-tenant serving wave
(concurrent sessions through the ServeRuntime, killed and re-submitted
mid-flight), a multi-process front-door wave (supervised executor
workers SIGKILLed/wedged at every session lifecycle point, sessions
re-placed or loudly failed), and a durable-shuffle-plane wave
(store_recovery: map outputs committed to the fleet-shared
ShuffleStore, then torn mid-commit, corrupted post-commit, or orphaned
by a SIGKILLed worker — the replacement must ADOPT committed shards,
quarantine damage, and fence every revoked generation), and a
multi-host TCP fleet wave (multihost: network faults — dropped, stalled
and torn links — landed at the transport probes on both sides of both
directions, resolved by reconnect+reattach where a partition must end
in self-fencing with zero zombie commits), and a zero-copy data-plane
wave (dataplane: result batches crossing the worker boundary as Arrow
IPC segments, torn after their CRC stamps or announced under a dead
fence generation — the supervisor's epoch-then-CRC verify must detect
and re-place, bit-identically), and a fleet result-cache wave
(result_cache: replayed snapshot-pinned queries served from sealed
cached segments with zero compute — stale rewound snapshot ids
rejected by the descriptor verify, post-seal byte flips
quarantined-and-recomputed, and a mutated input NEVER served a stale
snapshot), and an elastic-fleet wave (elastic: a queue-pressured wave
through an autoscaling front door — a worker is SIGKILLed mid-wave
while the autoscaler is still adding capacity, launches are failed at
the launcher boundary (``scale_up_fail``), drains are wedged past the
deadline (``drain_stuck``), and the fleet must still converge: ≥1
scale-up, ≥1 retire, every drained generation fenced with zero zombie
commits, bit-identical digests), and a supervisor-failover wave
(supervisor_failover: the SUPERVISOR itself dies mid-wave — once
deliberately every run, and again wherever ``supervisor_crash`` /
``journal_torn`` rules land on the write-ahead journal's append seam or
``journal_replay`` kills an adopting generation mid-replay — and every
death resolves by a fresh FrontDoor adopting the same fleet dir:
journal replay, dead-generation fencing, resume-token re-dial of the
surviving workers, re-placement of everything still owed, a
double-restart leg that must resurrect nothing, and a journal-proven
zero-duplicate-run audit) — one fault per trial exhaustively,
plus ``chaos_trials`` seeded multi-fault trials per scenario.  The q95
and streaming_scan matrices additionally repeat their seam trials with
the engine knobs pinned to the pallas device-kernel tier (``+pallas``
labels — groupby/join slot-table kernels, fused shuffle scatter): the
digest check against the default-engine baseline makes each of those a
bit-identity proof for the fused kernels under fire.  Every trial must end with

* a result **bit-identical** to the scenario's fault-free baseline
  (sha256 over every output leaf's dtype/shape/bytes), and
* clean post-run invariants: device and host arena totals zero, spill
  store empty, spill directory empty, attempt counts within the
  replacement bound.

Fault schedules are deterministic by construction: rules pin their
firing to an exact boundary crossing via ``skip``/``count`` (the
injector's per-name occurrence clock), multi-fault trials derive from
``--seed``, and every injection lands in ``faultinj.fired_log()`` — a
failing trial prints the log, and replaying it needs nothing but the
(name, occurrence) pairs it contains.

Fault handling per kind mirrors production roles: ``spill_io`` /
``spill_corrupt`` / ``host_corrupt`` / ``shuffle_io`` / ``oom`` recover
INSIDE the run
(degradation, checksum+lineage rebuild, round re-drive, retry ladder);
``exception`` / ``fatal`` abort the attempt and the campaign re-runs the
scenario from scratch — the "replacement executor", whose teardown the
harness guarantees via the same close/shutdown path every attempt.

Usage::

    python -m tools.chaos [--fast] [--seed N] [--trials N] [--report F]
"""

import os
import sys

# the shuffle scenario needs an 8-device mesh; both flags must be set
# BEFORE jax initializes (same contract as tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

import argparse
import contextlib
import dataclasses
import hashlib
import json
import random
import shutil
import tempfile
import threading
import zlib
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
from spark_rapids_jni_tpu.mem import spill as spill_mod
from spark_rapids_jni_tpu.mem.executor import TaskContext, run_with_retry
from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark

KB = 1 << 10
MB = 1 << 20

# bounded replacement: an aborting fault (exception/fatal) costs one
# attempt; rules carry finite counts, so this bound only trips when a
# recovery path is genuinely broken
_MAX_ATTEMPTS = 8


class ChaosError(AssertionError):
    """A trial violated the campaign contract (drift, residue, or a
    boundary that never fired)."""


# the scenario-level probes: one per scenario, crossed at its step
# boundaries so exception/oom/fatal kinds have a deterministic seam
_spill_probe = faultinj.instrument(lambda: None, "chaos_spill_step")
_shuffle_probe = faultinj.instrument(lambda: None, "chaos_shuffle_step")
_q95_probe = faultinj.instrument(lambda: None, "chaos_q95_step")
_sort_probe = faultinj.instrument(lambda: None, "chaos_sort_step")
_jni_probe = faultinj.instrument(lambda: None, "chaos_jni_step")
# crossed at every morsel decode of the streaming scan — "mid-morsel"
# faults land between a round being half-received and its drain
_stream_probe = faultinj.instrument(lambda: None, "chaos_stream_morsel")


def _digest(tree) -> str:
    """sha256 over every leaf's dtype/shape/bytes — bit-identity, not
    approximate equality, is the campaign's bar."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(jax.device_get(leaf))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@contextlib.contextmanager
def _harness(device_bytes: int, host_bytes: int, tag: str):
    """Fresh framework + arenas per attempt; teardown is unconditional
    (the replacement-executor guarantee), invariants are checked only on
    the success path by the caller via :func:`_check_invariants`."""
    spill_dir = tempfile.mkdtemp(prefix=f"sptpu_chaos_{tag}_")
    fw = spill_mod.install(spill_dir=spill_dir)
    adaptor = RmmSpark.set_event_handler(device_bytes,
                                         host_pool_bytes=host_bytes,
                                         poll_ms=10.0)
    try:
        yield fw, adaptor
    finally:
        RmmSpark.clear_event_handler()
        spill_mod.shutdown()
        shutil.rmtree(spill_dir, ignore_errors=True)


def _check_invariants(fw, adaptor):
    """Post-run residue check: a recovered run must look like a run in
    which nothing ever went wrong."""
    problems = []
    if adaptor.total_allocated() != 0:
        problems.append(
            f"device arena not drained: {adaptor.total_allocated()}B")
    if adaptor.host_total_allocated() != 0:
        problems.append(
            f"host arena not drained: {adaptor.host_total_allocated()}B")
    if len(fw.store) != 0:
        problems.append(
            f"{len(fw.store)} orphaned handle(s) left in the spill store")
    leftovers = os.listdir(fw.spill_dir)
    if leftovers:
        problems.append(f"spill dir not empty: {sorted(leftovers)[:4]}")
    if problems:
        raise ChaosError("post-run invariants violated: "
                         + "; ".join(problems))


def _always_retry(fw):
    """Outer-body make_spillable for scenario steps: evict what can be
    evicted and report truthy so an injected RetryOOM retries
    immediately instead of parking (the chaos driver is single-threaded;
    there is no peer whose deallocation would wake a parked thread)."""
    return lambda: (fw.spill_to_fit() or 0) + 1


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

class SpillScenario:
    """Two lineage-backed handles walked device→host→disk and read back:
    crosses host_corrupt_probe then spill_io_write / spill_corrupt_file
    on the way down and spill_io_read (plus checksum verification, which
    inherits demotion-time CRCs so host damage survives the host→disk
    cascade) on the way up."""

    name = "spill"
    task_id = 201

    def run(self) -> Dict:
        srcs = [np.arange(16 * KB, dtype=np.int64) * (i + 3)
                for i in range(2)]  # 128 KB each
        with _harness(2 * MB, 512 * KB, self.name) as (fw, adaptor):
            with TaskContext(self.task_id) as ctx:
                def body():
                    _spill_probe()
                    handles = []
                    try:
                        for i, s in enumerate(srcs):
                            def mk(s=s):
                                return {"x": jnp.asarray(s)}
                            handles.append(spill_mod.SpillableHandle(
                                mk(), ctx=ctx, name=f"chaos-spill-{i}",
                                recompute=mk))
                        for h in handles:
                            h.spill()
                            h.spill_host()  # → disk: write + corrupt probes
                        _spill_probe()
                        out = [np.asarray(h.get()["x"]).copy()
                               for h in handles]  # read-back + verify
                        _spill_probe()
                        return _digest(out)
                    finally:
                        for h in handles:
                            h.close()
                digest = run_with_retry(body,
                                        make_spillable=_always_retry(fw))
            RmmSpark.task_done(self.task_id)
            _check_invariants(fw, adaptor)
        return {"digest": digest, "extra": {}}


class ShuffleScenario:
    """All-to-one skewed multi-round exchange under arenas tight enough
    that partition buffers demote all the way to disk: crosses
    shuffle_io_round every round and the whole spill boundary set for
    the buffers — a corrupted/lost buffer recovers via map lineage
    (ShuffleMetrics.recovered_partitions)."""

    name = "shuffle"
    task_id = 202

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
        from spark_rapids_jni_tpu.shuffle import (
            ShuffleRegistry,
            ShuffleService,
        )

        if len(jax.devices()) < 8:
            raise ChaosError(
                "shuffle scenario needs 8 devices; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 before jax init")
        P = 8
        n = P * 1024
        vals = (np.arange(n, dtype=np.int64) * 2654435761) % (1 << 40)
        mesh = data_mesh(P)
        batch = shard_batch(ColumnBatch({
            "v": Column(jnp.asarray(vals), jnp.ones((n,), jnp.bool_),
                        T.INT64)}), mesh)
        pid = jax.device_put(
            jnp.zeros((n,), jnp.int32),  # all-to-one: forces multi-round
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
        old_bucket = config.get("shuffle_capacity_bucket")
        config.set("shuffle_capacity_bucket", 256)
        try:
            with _harness(512 * KB, 128 * KB, self.name) as (fw, adaptor):
                reg = ShuffleRegistry()
                with TaskContext(self.task_id) as ctx:
                    def body():
                        _shuffle_probe()
                        res = ShuffleService(mesh, registry=reg).exchange(
                            batch, pid=pid, ctx=ctx, round_rows=128)
                        return _digest((res.batch, res.occupancy))
                    digest = run_with_retry(
                        body, make_spillable=_always_retry(fw))
                RmmSpark.task_done(self.task_id)
                _check_invariants(fw, adaptor)
        finally:
            config.set("shuffle_capacity_bucket", old_bucket)
        snap = reg.metrics.snapshot()
        return {"digest": digest,
                "extra": {"recovered_partitions":
                          snap["recovered_partitions"],
                          "io_failures": snap["io_failures"],
                          "rounds": snap["rounds"]}}


class Q95Scenario:
    """The single-chip q95 pipeline (exchange → join → exchange → join →
    group-by): the compute-shaped scenario, proving injected faults at a
    query step boundary replay to bit-identical aggregates."""

    name = "q95"

    def run(self) -> Dict:
        import __graft_entry__ as ge

        fact, dim1, dim2 = ge._q95_batches(4096, seed=19)
        with _harness(16 * MB, 4 * MB, self.name) as (fw, adaptor):
            def body():
                _q95_probe()
                res, ng = ge._q95_step(fact, dim1, dim2)
                _q95_probe()  # post-compute seam: skip=1 rules land here
                return _digest((res, ng))
            digest = run_with_retry(body, make_spillable=_always_retry(fw))
            _check_invariants(fw, adaptor)
        return {"digest": digest, "extra": {}}


class SortScenario:
    """Global sample-sort across the 8-device mesh (range partition by
    host-sampled splitters → shard_map exchange → local sort with dead
    slots last): the distributed-sort fault domain.  Crosses the
    chaos_sort_step seam before planning and after the sorted result
    lands, proving a faulted ``distributed_sort`` replays bit-identical
    (rows, occupancy, dropped) — the splitter sample, capacity plan and
    exchange are all re-derived from scratch by the replacement run."""

    name = "sort"

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.parallel import (
            data_mesh,
            distributed_sort,
            shard_batch,
        )

        if len(jax.devices()) < 8:
            raise ChaosError(
                "sort scenario needs 8 devices; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 before jax init")
        P = 8
        n = P * 1024
        keys = (np.arange(n, dtype=np.int64) * 2654435761) % (1 << 20)
        mesh = data_mesh(P)
        batch = shard_batch(ColumnBatch({
            "k": Column(jnp.asarray(keys), jnp.ones((n,), jnp.bool_),
                        T.INT64),
            "v": Column(jnp.asarray(np.arange(n, dtype=np.int64)),
                        jnp.ones((n,), jnp.bool_), T.INT64)}), mesh)
        with _harness(4 * MB, 1 * MB, self.name) as (fw, adaptor):
            def body():
                _sort_probe()
                out, occ, dropped = distributed_sort(batch, ["k"], mesh)
                _sort_probe()  # post-sort seam: skip=1 rules land here
                return _digest((out, occ, dropped))
            digest = run_with_retry(body, make_spillable=_always_retry(fw))
            _check_invariants(fw, adaptor)
        return {"digest": digest, "extra": {}}


class StreamingScanScenario:
    """The morsel-driven scan→shuffle pipeline under fire: a uniform
    stream goes multi-round with rounds draining while later morsels
    decode, under arenas tight enough that half-received round chunks
    demote through the host→disk spill tiers.  Every morsel decode
    crosses the ``chaos_stream_morsel`` seam (exception/oom/fatal land
    MID-STREAM, with open round chunks that the service must close on
    the way out); ``shuffle_io_round`` fires on the early drains; and
    spill/host corruption of a half-received chunk must recover by
    replaying its recorded morsel contributions
    (ShuffleMetrics.recovered_partitions) — never by holding a second
    copy resident."""

    name = "streaming_scan"
    task_id = 203

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
        from spark_rapids_jni_tpu.shuffle import (
            MorselSource,
            ShuffleRegistry,
            ShuffleService,
        )

        if len(jax.devices()) < 8:
            raise ChaosError(
                "streaming_scan scenario needs 8 devices; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 before jax init")
        P = 8
        n = P * 2048
        keys = (np.arange(n, dtype=np.int64) * 2654435761) % (1 << 20)
        mesh = data_mesh(P)
        ones = jnp.ones((n,), jnp.bool_)
        batch = shard_batch(ColumnBatch({
            "k": Column(jnp.asarray(keys), ones, T.INT64),
            "v": Column(jnp.asarray(np.arange(n, dtype=np.int64)), ones,
                        T.INT64)}), mesh)
        old_bucket = config.get("shuffle_capacity_bucket")
        config.set("shuffle_capacity_bucket", 16)
        try:
            with _harness(512 * KB, 128 * KB, self.name) as (fw, adaptor):
                reg = ShuffleRegistry()
                with TaskContext(self.task_id) as ctx:
                    def body():
                        src = MorselSource.from_batch(batch, mesh,
                                                      morsel_rows=512)
                        # the mid-morsel seam: every decode (including a
                        # lineage replay) crosses the probe first
                        morsels = [
                            (lambda r=r: (_stream_probe(), r())[1])
                            for r in src]
                        res = ShuffleService(
                            mesh, registry=reg).exchange_stream(
                                morsels, key_names=["k"], ctx=ctx,
                                round_rows=32)
                        return (_digest((res.batch, res.occupancy)),
                                res.rounds, res.rounds_overlapped)
                    digest, rounds, overlapped = run_with_retry(
                        body, make_spillable=_always_retry(fw))
                RmmSpark.task_done(self.task_id)
                _check_invariants(fw, adaptor)
        finally:
            config.set("shuffle_capacity_bucket", old_bucket)
        if rounds < 2 or overlapped < 1:
            raise ChaosError(
                f"streaming_scan degenerated: rounds={rounds} "
                f"overlapped={overlapped} — the stream no longer drains "
                "while morsels decode, so the trial proves nothing")
        snap = reg.metrics.snapshot()
        return {"digest": digest,
                "extra": {"recovered_partitions":
                          snap["recovered_partitions"],
                          "io_failures": snap["io_failures"],
                          "rounds": rounds,
                          "rounds_overlapped": overlapped}}


class JniScenario:
    """The Java/JNI host boundary: columns cross as Arrow-style host
    buffers, ops dispatch through ``jni_bridge.invoke`` (hash → bloom
    create/put/probe), results round-trip back through
    ``column_to_host`` — the embedded-host analogue of a Spark executor
    driving the bridge library.  A replacement attempt rebuilds every
    handle from the original host buffers, so an aborting fault
    mid-round-trip leaks nothing across attempts."""

    name = "jni"

    def run(self) -> Dict:
        from spark_rapids_jni_tpu import jni_bridge as jb

        n = 4096
        vals = (np.arange(n, dtype=np.int64) * 0x9E3779B9) % (1 << 31)
        data = vals.tobytes()
        with _harness(8 * MB, 2 * MB, self.name) as (fw, adaptor):
            def body():
                _jni_probe()
                col = jb.column_from_host("int64", n, data, b"")
                hashed, _meta = jb.invoke(
                    "Hash.murmurHash32", json.dumps({"seed": 42}), [col])
                _jni_probe()
                bf, _ = jb.invoke(
                    "BloomFilter.create",
                    json.dumps({"bits": 1 << 14, "num_hashes": 3}), [])
                put, _ = jb.invoke("BloomFilter.put", "", [bf[0], col])
                hits, _ = jb.invoke("BloomFilter.probe", "", [put[0], col])
                _jni_probe()
                out = [jb.column_to_host(hashed[0]),
                       jb.column_to_host(hits[0])]
                return _digest([np.frombuffer(c[2], dtype=np.uint8)
                                for c in out])
            digest = run_with_retry(body, make_spillable=_always_retry(fw))
            _check_invariants(fw, adaptor)
        return {"digest": digest, "extra": {}}


class ServingScenario:
    """A wave of concurrent tenants through the multi-tenant
    ``ServeRuntime``: each tenant's query builds a lineage-backed
    spillable handle inside its per-session ``TaskContext``, walks it
    device→host→disk and reads it back — crossing ``serve_admit`` /
    ``serve_step`` plus the whole spill boundary set from inside worker
    threads.  A killed tenant (``task_cancel`` anywhere on its path, or
    an aborting ``exception``) is re-submitted as a fresh session —
    the serving analogue of the replacement executor — while surviving
    tenants must stay bit-identical to the fault-free baseline.  The
    per-tenant results are position-stable, so the digest is
    deterministic even though WHICH concurrent tenant absorbs a given
    occurrence of a shared-clock fault is not.  After the wave the
    runtime must shut down cleanly: drained arenas, empty store, no
    orphan spill files, and no live ``serve-*`` worker threads."""

    name = "serving"
    n_tenants = 3

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import QueryCancelled, ServeRuntime

        srcs = [np.arange(8 * KB, dtype=np.int64) * (i + 5)
                for i in range(self.n_tenants)]  # 64 KB each
        results: List[Optional[np.ndarray]] = [None] * self.n_tenants
        kills = 0
        with _harness(2 * MB, 512 * KB, self.name) as (fw, adaptor):
            runtime = ServeRuntime(task_id_base=20_000)
            try:
                def make_query(i):
                    def q(ctx):
                        def mk(s=srcs[i]):
                            return {"x": jnp.asarray(s)}
                        h = spill_mod.SpillableHandle(
                            mk(), ctx=ctx, name=f"chaos-serve-{i}",
                            recompute=mk)
                        h.spill()
                        h.spill_host()  # → disk: write + corrupt probes
                        return np.asarray(h.get()["x"]).copy()
                    return q

                pending = list(range(self.n_tenants))
                attempts = {i: 0 for i in pending}
                while pending:
                    wave = [(i, runtime.submit(make_query(i),
                                               est_bytes=64 * KB,
                                               tenant=f"tenant-{i}"))
                            for i in pending]
                    pending = []
                    for i, sess in wave:
                        try:
                            results[i] = sess.result(timeout=30.0)
                        except faultinj.FatalInjectedFault:
                            raise  # whole-scenario replacement
                        except (faultinj.TaskCancelled,
                                faultinj.InjectedFault,
                                QueryCancelled, RetryOOM):
                            # a killed/aborted tenant resubmits as a
                            # FRESH session; its unwind must leave the
                            # shared arena consistent for the survivors.
                            # RetryOOM lands here only when injected at
                            # the ADMISSION probe — before the session's
                            # retry ladder exists to absorb it
                            kills += 1
                            attempts[i] += 1
                            if attempts[i] >= _MAX_ATTEMPTS:
                                raise ChaosError(
                                    f"serving: tenant {i} not done after "
                                    f"{_MAX_ATTEMPTS} re-submissions")
                            pending.append(i)
            finally:
                clean = runtime.shutdown()
            if not clean:
                raise ChaosError(
                    "serving: runtime.shutdown() left wedged sessions")
            _check_invariants(fw, adaptor)
            stragglers = [t.name for t in threading.enumerate()
                          if t.name.startswith("serve-")]
            if stragglers:
                raise ChaosError(
                    f"serving: live worker threads after shutdown: "
                    f"{stragglers}")
        return {"digest": _digest(results),
                "extra": {"tenant_kills": kills}}


class FrontdoorScenario:
    """A wave of tenants through the multi-process :class:`FrontDoor`:
    each tenant's ``spill_walk`` query runs inside an executor WORKER
    process (its own arena, spill store, and ServeRuntime), so the
    faults this scenario absorbs cross the process boundary — including
    ``worker_crash`` (the worker SIGKILLs itself mid-query) and
    ``worker_stall`` (it wedges and stops answering heartbeats).  The
    supervisor must detect the loss, reap the dead worker's spill files,
    re-place replayable sessions through the bounded backoff ladder, and
    respawn the slot; a loudly-failed victim (``WorkerLost`` — tenant 0
    is declared non-replayable) is re-submitted by the CLIENT, the
    multi-process analogue of the serving scenario's fresh session.
    Survivors must stay bit-identical (the ``spill_walk`` digest is a
    pure function of the seed), and shutdown must report every worker
    clean with zero orphan spill files fleet-wide."""

    name = "frontdoor"
    n_tenants = 3
    seeds = (11, 12, 13)

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)

        results: List[Optional[str]] = [None] * self.n_tenants
        kills = 0
        config.set("serve_backoff_ms", 30.0)
        fd = FrontDoor(workers=2, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=2,
                       heartbeat_ms=60.0, respawn_max=4)
        try:
            pending = list(range(self.n_tenants))
            attempts = {i: 0 for i in pending}
            while pending:
                wave = [(i, fd.submit(
                    "spill_walk", {"seed": self.seeds[i], "rows": 8 * KB},
                    tenant=f"tenant-{i}", priority=i,
                    replayable=(i != 0))) for i in pending]
                pending = []
                for i, sess in wave:
                    try:
                        results[i] = sess.result(timeout=60.0)
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except (WorkerLost, AdmissionShed,
                            faultinj.TaskCancelled, faultinj.InjectedFault,
                            QueryCancelled, RetryOOM):
                        # a victim the supervisor could NOT silently
                        # re-place (non-replayable mid-flight, budget
                        # out, shed) fails loudly; the client re-submits
                        kills += 1
                        attempts[i] += 1
                        if attempts[i] >= _MAX_ATTEMPTS:
                            raise ChaosError(
                                f"frontdoor: tenant {i} not done after "
                                f"{_MAX_ATTEMPTS} re-submissions")
                        pending.append(i)
        finally:
            report = fd.shutdown()
            config.reset("serve_backoff_ms")
        # the shutdown contract: every surviving worker drained its
        # arena and spill store (its bye says so), and no spill file
        # outlived its worker anywhere under the fleet dir
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(f"frontdoor: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"frontdoor: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError("frontdoor: fleet dir survived shutdown")
        for _ in range(40):  # reader threads exit async after close
            stragglers = [t.name for t in threading.enumerate()
                          if t.name.startswith("frontdoor-")]
            if not stragglers:
                break
            time.sleep(0.05)
        if stragglers:
            raise ChaosError(
                f"frontdoor: live supervisor threads after shutdown: "
                f"{stragglers}")
        h = hashlib.sha256()
        for r in results:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class StoreRecoveryScenario:
    """The durable shuffle plane under fire: ``shuffle_digest`` queries
    through a store-enabled :class:`FrontDoor` commit their map outputs
    to the fleet-shared :class:`ShuffleStore` in wave 0, then wave 1
    re-issues the SAME store keys — so a replacement worker (after
    ``worker_crash``), the same worker after a torn commit
    (``store_commit``), or adoption-time CRC verification after
    post-commit damage (``store_corrupt``) must all converge on the
    identical answer: adopt the committed shard, or quarantine it and
    lineage-rebuild — never a wrong result, never a hang.  Before
    shutdown the scenario also probes the fence: every generation the
    supervisor revoked at worker-loss time must be unable to commit
    (a zombie's late write can never become adoptable).  The digest
    hashes only the per-slot result digests (position-stable), not the
    adoption counters — WHICH recovery path served a slot may differ
    between the faulted run and the baseline; the answer may not."""

    name = "store_recovery"
    n_queries = 2
    seeds = (21, 22)

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)
        from spark_rapids_jni_tpu.shuffle import store as store_mod

        digests: List[Optional[str]] = [None] * (2 * self.n_queries)
        kills = adopted = rebuilt = 0
        config.set("serve_backoff_ms", 30.0)
        fd = FrontDoor(workers=1, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=1,
                       heartbeat_ms=60.0, respawn_max=4)
        try:
            for wave in (0, 1):
                pending = list(range(self.n_queries))
                attempts = {i: 0 for i in pending}
                while pending:
                    wv = [(i, fd.submit(
                        "shuffle_digest",
                        {"seed": self.seeds[i], "rows_per_shard": 64,
                         "store_key": f"chaos-store-{self.seeds[i]}"},
                        tenant=f"tenant-{i}")) for i in pending]
                    pending = []
                    for i, sess in wv:
                        try:
                            out = sess.result(timeout=60.0)
                            digests[wave * self.n_queries + i] = \
                                out["digest"]
                            adopted += int(out["adopted"])
                            rebuilt += int(out["rebuilt"])
                        except faultinj.FatalInjectedFault:
                            raise  # whole-scenario replacement
                        except (WorkerLost, AdmissionShed,
                                faultinj.TaskCancelled,
                                faultinj.InjectedFault, QueryCancelled,
                                RetryOOM):
                            kills += 1
                            attempts[i] += 1
                            if attempts[i] >= _MAX_ATTEMPTS:
                                raise ChaosError(
                                    f"store_recovery: tenant {i} not "
                                    f"done after {_MAX_ATTEMPTS} "
                                    f"re-submissions")
                            pending.append(i)
            # the fence probe, while the store dir still exists: every
            # generation the supervisor revoked must be commit-rejected.
            # The probe put runs in the SUPERVISOR process and crosses
            # the store probes like any commit, so the trial's own rules
            # may fire here too — any raise at a probe happens BEFORE
            # the rename, which prevents the commit just as surely as
            # the fence does, so it counts as rejected
            if fd.store_dir and os.path.isdir(fd.store_dir):
                reader = store_mod.ShuffleStore(fd.store_dir,
                                                max_attempts=0)
                for g in reader.revoked():
                    zombie = store_mod.ShuffleStore(fd.store_dir,
                                                    epoch=g,
                                                    max_attempts=0)
                    try:
                        committed = zombie.put("chaos-fence-probe",
                                               "zombie",
                                               {"x": jnp.arange(4)})
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except Exception:
                        committed = False  # aborted pre-rename
                    if committed:
                        raise ChaosError(
                            f"store_recovery: revoked gen {g} committed "
                            f"past its fence")
                    if reader.has_committed("chaos-fence-probe",
                                            "zombie"):
                        raise ChaosError(
                            f"store_recovery: revoked gen {g}'s entry "
                            f"became adoptable")
        finally:
            report = fd.shutdown()
            config.reset("serve_backoff_ms")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(
                f"store_recovery: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"store_recovery: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError(
                "store_recovery: fleet dir survived shutdown "
                "(shuffle_store_retain is off)")
        for i in range(self.n_queries):
            if digests[i] != digests[self.n_queries + i]:
                raise ChaosError(
                    f"store_recovery: tenant {i}'s adopted/rebuilt "
                    f"answer drifted from its wave-0 original")
        h = hashlib.sha256()
        for d in digests:
            h.update((d or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "adopted_shards": adopted,
                          "lineage_rebuilds": rebuilt,
                          "recovered_partitions": adopted + rebuilt,
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class MultihostScenario:
    """A two-host TCP fleet under network fire: two workers placed on
    named hosts (``hostA``/``hostB`` — both localhost processes, but
    dialing the supervisor's TCP listener exactly like a remote peer
    would) serve a store-backed tenant wave while ``net_drop`` /
    ``net_stall`` / ``net_torn`` faults land at the transport probes on
    either side of either direction.  A dropped or torn LINK must
    resolve through the reconnect ladder + idempotent-hello reattach
    (a connection loss is not a worker loss); a worker partitioned past
    the grace must SELF-FENCE — revoke its own store epoch, write the
    sentinel, exit — and the fence probe before shutdown proves that no
    revoked generation can ever commit an adoptable shard (zero zombie
    commits).  The digest hashes the per-slot result digests
    (position-stable); WHICH recovery path — reattach, re-placement, or
    self-fence + re-placement — served a slot may differ from the
    baseline, the answers may not."""

    name = "multihost"
    n_tenants = 3
    seeds = (31, 32, 33)

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)
        from spark_rapids_jni_tpu.shuffle import store as store_mod

        results: List[Optional[str]] = [None] * self.n_tenants
        kills = 0
        config.set("serve_backoff_ms", 30.0)
        fd = FrontDoor(workers=2, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=2,
                       heartbeat_ms=60.0, respawn_max=4,
                       transport="tcp", hosts="hostA,hostB",
                       partition_grace_ms=700.0, reconnect_max=3)
        try:
            pending = list(range(self.n_tenants))
            attempts = {i: 0 for i in pending}
            while pending:
                # tenants 0/1 exercise the durable store plane over the
                # TCP link; tenant 2 is the pure-compute control
                wave = [(i, fd.submit(
                    "shuffle_digest",
                    {"seed": self.seeds[i], "rows_per_shard": 64,
                     "store_key": f"chaos-mh-{self.seeds[i]}"},
                    tenant=f"tenant-{i}") if i < 2 else fd.submit(
                    "spill_walk",
                    {"seed": self.seeds[i], "rows": 8 * KB},
                    tenant=f"tenant-{i}")) for i in pending]
                pending = []
                for i, sess in wave:
                    try:
                        out = sess.result(timeout=90.0)
                        results[i] = (out["digest"] if isinstance(out, dict)
                                      else out)
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except (WorkerLost, AdmissionShed,
                            faultinj.TaskCancelled, faultinj.InjectedFault,
                            QueryCancelled, RetryOOM):
                        kills += 1
                        attempts[i] += 1
                        if attempts[i] >= _MAX_ATTEMPTS:
                            raise ChaosError(
                                f"multihost: tenant {i} not done after "
                                f"{_MAX_ATTEMPTS} re-submissions")
                        pending.append(i)
            # the split-brain fence probe, while the store still exists:
            # every generation revoked by EITHER side of a partition —
            # the supervisor at loss time or the worker self-fencing —
            # must be commit-rejected, and nothing it wrote adoptable
            if fd.store_dir and os.path.isdir(fd.store_dir):
                reader = store_mod.ShuffleStore(fd.store_dir,
                                                max_attempts=0)
                for g in reader.revoked():
                    zombie = store_mod.ShuffleStore(fd.store_dir,
                                                    epoch=g,
                                                    max_attempts=0)
                    try:
                        committed = zombie.put("chaos-mh-fence-probe",
                                               "zombie",
                                               {"x": jnp.arange(4)})
                    except faultinj.FatalInjectedFault:
                        raise
                    except Exception:
                        committed = False  # aborted pre-rename
                    if committed:
                        raise ChaosError(
                            f"multihost: revoked gen {g} committed past "
                            f"its fence (zombie shard)")
                    if reader.has_committed("chaos-mh-fence-probe",
                                            "zombie"):
                        raise ChaosError(
                            f"multihost: revoked gen {g}'s entry became "
                            f"adoptable")
        finally:
            report = fd.shutdown()
            config.reset("serve_backoff_ms")
        if report["transport"] != "tcp":
            raise ChaosError("multihost: fleet did not ride TCP")
        served = {e["host"] for e in report["workers"].values()}
        if served != {"hostA", "hostB"}:
            raise ChaosError(
                f"multihost: placement collapsed to {sorted(served)} — "
                f"both hosts must hold a slot")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(f"multihost: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"multihost: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError("multihost: fleet dir survived shutdown")
        for fenced in report["self_fenced"]:
            if fenced.get("fenced_commits"):
                raise ChaosError(
                    f"multihost: self-fenced worker {fenced['worker_id']} "
                    f"committed {fenced['fenced_commits']} shard(s) past "
                    f"its own revocation")
        h = hashlib.sha256()
        for r in results:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "self_fenced_workers":
                          report["fleet"]["self_fenced_workers"],
                          "reconnects": report["fleet"]["reconnects"],
                          "partitions_detected":
                          report["fleet"]["partitions_detected"],
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class DataPlaneScenario:
    """The zero-copy columnar data plane under fire: ``arrow_batch``
    tenants return RESULT BATCHES that cross the worker boundary as
    Arrow IPC payloads in memfd segments (SCM_RIGHTS fd-passing on the
    unix fleet) while the control wire carries only a JSON descriptor.
    ``shm_torn`` flips payload bytes in the mapped segment AFTER the
    descriptor's chunk CRCs were stamped; ``shm_stale`` rewrites the
    descriptor to a dead fence generation's segment name; and
    ``worker_crash`` at the result seam kills the worker with a segment
    in flight (descriptor undelivered, fd unreaped).  The supervisor
    must verify epoch-then-CRC before interpreting a single buffer,
    count the damage (``data_plane_errors``), re-place the session
    under a fresh sid, and converge on a batch whose canonical
    ``batch_digest`` — NaN payloads, -0.0, dictionary codes, RLE runs —
    is bit-identical to the fault-free baseline.  Damage detections are
    surfaced as ``recovered_partitions`` so torn/stale trials can
    assert the verify path actually fired, not merely that the wave
    survived."""

    name = "dataplane"
    n_tenants = 3
    seeds = (41, 42, 43)
    rows = 2048

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)
        from spark_rapids_jni_tpu.serve import data_plane as dp

        results: List[Optional[str]] = [None] * self.n_tenants
        kills = 0
        config.set("serve_backoff_ms", 30.0)
        fd = FrontDoor(workers=2, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=2,
                       heartbeat_ms=60.0, respawn_max=4,
                       data_plane_mode="shm")
        try:
            pending = list(range(self.n_tenants))
            attempts = {i: 0 for i in pending}
            while pending:
                wave = [(i, fd.submit(
                    "arrow_batch",
                    {"rows": self.rows, "seed": self.seeds[i]},
                    tenant=f"tenant-{i}")) for i in pending]
                pending = []
                for i, sess in wave:
                    try:
                        results[i] = dp.batch_digest(
                            sess.result(timeout=60.0))
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except (WorkerLost, AdmissionShed,
                            faultinj.TaskCancelled, faultinj.InjectedFault,
                            QueryCancelled, RetryOOM,
                            # a session whose damaged-transfer budget
                            # (serve_max_readmissions) ran out fails
                            # loudly with the data-plane error — absorb
                            # it into THIS loop's bounded re-submission,
                            # like any other killed session
                            dp.DataPlaneCorruption, dp.DataPlaneStale):
                        kills += 1
                        attempts[i] += 1
                        if attempts[i] >= _MAX_ATTEMPTS:
                            raise ChaosError(
                                f"dataplane: tenant {i} not done after "
                                f"{_MAX_ATTEMPTS} re-submissions")
                        pending.append(i)
        finally:
            report = fd.shutdown()
            config.reset("serve_backoff_ms")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(f"dataplane: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"dataplane: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError("dataplane: fleet dir survived shutdown")
        dp_info = report["data_plane"]
        if dp_info["plane"] != "shm":
            raise ChaosError(
                f"dataplane: fleet rode plane {dp_info['plane']!r}, "
                f"not shm")
        if dp_info["batches"] < self.n_tenants:
            raise ChaosError(
                f"dataplane: only {dp_info['batches']} batches crossed "
                f"the data plane for {self.n_tenants} tenants — results "
                f"leaked back onto the JSON wire")
        h = hashlib.sha256()
        for r in results:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "data_batches": dp_info["batches"],
                          "data_payload_bytes": dp_info["payload_bytes"],
                          "data_plane_errors": dp_info["errors"],
                          "recovered_partitions": dp_info["errors"],
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class ResultCacheScenario:
    """The fleet result cache under fire: three tenants replay the same
    ``arrow_batch`` queries with content snapshot ids declared, so the
    warm wave computes live and every replay wave should be served from
    the supervisor's sealed cache segments — BEFORE admission, with
    zero worker dispatch.  ``cache_stale`` rewinds the snapshot id a
    serve (or insert) records, and ``cache_corrupt`` flips a stored
    byte post-seal: the front door's live-grade verification (fence
    epoch, snapshot id, chunk CRCs, schema fingerprint) must reject the
    damaged serve, quarantine or stale-count it, and recompute —
    bit-identical to the fault-free baseline.  The final wave MUTATES
    every tenant's input (new snapshot ids): those submissions must all
    miss — a cache that serves even one stale snapshot to a mutated
    input fails the scenario outright, faults or no faults.  Stale
    rejections + quarantines surface as ``recovered_partitions`` so the
    cache trials can assert the verify path actually fired."""

    name = "result_cache"
    n_tenants = 3
    seeds = (61, 62, 63)
    rows = 1024
    replays = 3

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)
        from spark_rapids_jni_tpu.serve import data_plane as dp
        from spark_rapids_jni_tpu.serve import result_cache as rcache

        kills = 0
        config.set("serve_backoff_ms", 30.0)
        fd = FrontDoor(workers=2, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=2,
                       heartbeat_ms=60.0, respawn_max=4,
                       data_plane_mode="shm")
        try:
            def snap(i: int, gen: int) -> str:
                return rcache.snapshot_for_obj(
                    {"scenario": self.name, "tenant": i,
                     "seed": self.seeds[i], "gen": gen})

            def wave(gen: int, forbid_hits: bool = False):
                nonlocal kills
                digests: List[Optional[str]] = [None] * self.n_tenants
                pending = list(range(self.n_tenants))
                attempts = {i: 0 for i in pending}
                while pending:
                    subs = [(i, fd.submit(
                        "arrow_batch",
                        {"rows": self.rows, "seed": self.seeds[i]},
                        tenant=f"tenant-{i}", snapshot=snap(i, gen)))
                        for i in pending]
                    pending = []
                    for i, sess in subs:
                        if forbid_hits and sess.served_from_cache:
                            raise ChaosError(
                                f"result_cache: tenant {i} was served a "
                                f"CACHED result for a MUTATED input "
                                f"(snapshot {snap(i, gen)!r}) — stale "
                                f"serve, the one unforgivable outcome")
                        try:
                            digests[i] = dp.batch_digest(
                                sess.result(timeout=60.0))
                        except faultinj.FatalInjectedFault:
                            raise  # whole-scenario replacement
                        except (WorkerLost, AdmissionShed,
                                faultinj.TaskCancelled,
                                faultinj.InjectedFault, QueryCancelled,
                                RetryOOM, dp.DataPlaneCorruption,
                                dp.DataPlaneStale):
                            kills += 1
                            attempts[i] += 1
                            if attempts[i] >= _MAX_ATTEMPTS:
                                raise ChaosError(
                                    f"result_cache: tenant {i} not done "
                                    f"after {_MAX_ATTEMPTS} re-submissions")
                            pending.append(i)
                return digests

            warm = wave(gen=0)
            for r in range(self.replays):
                replay = wave(gen=0)
                if replay != warm:
                    raise ChaosError(
                        f"result_cache: replay wave {r} digests differ "
                        f"from the warm wave — cached bytes are not "
                        f"bit-identical ({replay} != {warm})")
            # every tenant's input mutates: fresh snapshot ids, so the
            # gen-0 entries must be unreachable — zero hits, recompute
            mutated = wave(gen=1, forbid_hits=True)
            if mutated != warm:  # same params → same values, recomputed
                raise ChaosError(
                    f"result_cache: mutated-input recompute differs "
                    f"({mutated} != {warm})")
        finally:
            report = fd.shutdown()
            config.reset("serve_backoff_ms")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(f"result_cache: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"result_cache: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError("result_cache: fleet dir survived shutdown")
        rc_info = report["result_cache"]
        if rc_info["hits"] < 1:
            raise ChaosError(
                f"result_cache: {self.replays} replay waves produced "
                f"{rc_info['hits']} cache hits — the cache never served")
        detections = (rc_info["stale_rejected"]
                      + rc_info["corrupt_quarantined"])
        h = hashlib.sha256()
        for r in warm:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "cache_hits": rc_info["hits"],
                          "cache_inserts": rc_info["inserts"],
                          "hit_bytes_served": rc_info["hit_bytes_served"],
                          "stale_rejected": rc_info["stale_rejected"],
                          "corrupt_quarantined":
                              rc_info["corrupt_quarantined"],
                          "recovered_partitions": detections,
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class ElasticScenario:
    """The elastic control plane under fire: a queue-pressured wave of
    tenants through a ONE-worker front door with autoscaling on, so the
    fleet must GROW to drain the backlog and SHRINK (drain → self-fence
    → reap) once it empties.  Mid-wave, the scenario SIGKILLs the first
    worker that placed a session — the multi-process analogue of losing
    a host while the autoscaler is still adding capacity — so loss
    re-placement, the respawn ladder, and scale-up all run concurrently.
    ``scale_up_fail`` (launcher boundary) and ``drain_stuck`` (wedged
    retirement) fire ONLY here: these trials keep both kinds in the
    coverage check.  Every trial must end with bit-identical digests
    (``spill_walk`` is a pure function of the seed, wherever and on
    however many workers it runs), ≥1 scale-up, ≥1 retirement, zero
    ``fenced_commits`` on every DRAINED generation (a clean drain
    revokes its own epoch before any zombie commit can happen), zero
    orphan spill files, and a converged shutdown."""

    name = "elastic"
    n_tenants = 4
    seeds = (71, 72, 73, 74)

    def run(self) -> Dict:
        import signal as _signal

        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)

        results: List[Optional[str]] = [None] * self.n_tenants
        kills = 0
        config.set("serve_backoff_ms", 30.0)
        config.set("serve_autoscale_high_water", 1)
        config.set("serve_autoscale_hold_ms", 80.0)
        config.set("serve_autoscale_idle_ms", 250.0)
        config.set("serve_autoscale_drain_ms", 1200.0)
        config.set("serve_autoscale_max", 3)
        fd = FrontDoor(workers=1, pool_bytes=2 * MB,
                       host_pool_bytes=512 * KB, max_concurrent=1,
                       heartbeat_ms=60.0, respawn_max=4, autoscale=True)
        try:
            host_killed = False
            pending = list(range(self.n_tenants))
            attempts = {i: 0 for i in pending}
            while pending:
                wave = [(i, fd.submit(
                    "spill_walk", {"seed": self.seeds[i], "rows": 8 * KB},
                    tenant=f"tenant-{i}", priority=i,
                    replayable=True)) for i in pending]
                pending = []
                if not host_killed:
                    # the mid-wave host loss: SIGKILL the first worker
                    # that placed a session, while the backlog is still
                    # pressuring the autoscaler upward
                    deadline = time.monotonic() + 20.0
                    victim = None
                    while victim is None and time.monotonic() < deadline:
                        placed = [s for _, s in wave
                                  if s.worker_id is not None]
                        if placed:
                            with fd._lock:
                                w = fd._workers.get(placed[0].worker_id)
                                victim = w.proc.pid if w is not None \
                                    else None
                        if victim is None:
                            time.sleep(0.02)
                    if victim is not None:
                        with contextlib.suppress(OSError):
                            os.kill(victim, _signal.SIGKILL)
                        host_killed = True
                for i, sess in wave:
                    try:
                        results[i] = sess.result(timeout=90.0)
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except (WorkerLost, AdmissionShed,
                            faultinj.TaskCancelled, faultinj.InjectedFault,
                            QueryCancelled, RetryOOM):
                        kills += 1
                        attempts[i] += 1
                        if attempts[i] >= _MAX_ATTEMPTS:
                            raise ChaosError(
                                f"elastic: tenant {i} not done after "
                                f"{_MAX_ATTEMPTS} re-submissions")
                        pending.append(i)
            # convergence: the drained queue must retire capacity back
            # DOWN TO the base fleet before shutdown — and the fleet
            # must be quiescent (every survivor healthy, nothing mid-
            # hello, no respawn pending, no drain in flight), so the
            # shutdown bye accounting below is race-free
            deadline = time.monotonic() + 40.0
            while time.monotonic() < deadline:
                with fd._lock:
                    ws = list(fd._workers.values())
                    quiet = (not fd._pending and not fd._respawn_at
                             and all(w.state == "healthy"
                                     and not w.retiring for w in ws)
                             and len(ws) <= fd._autoscaler.min_workers)
                if quiet and fd.metrics.snapshot()["scale_downs"] >= 1:
                    break
                time.sleep(0.05)
        finally:
            report = fd.shutdown()
            for knob in ("serve_backoff_ms", "serve_autoscale_high_water",
                         "serve_autoscale_hold_ms",
                         "serve_autoscale_idle_ms",
                         "serve_autoscale_drain_ms",
                         "serve_autoscale_max"):
                config.reset(knob)
        fleet = report["fleet"]
        if fleet["scale_ups"] < 1:
            raise ChaosError(
                f"elastic: the backlog never scaled the fleet up "
                f"(scale_ups={fleet['scale_ups']})")
        if fleet["scale_downs"] < 1:
            raise ChaosError(
                f"elastic: the drained fleet never retired capacity "
                f"(scale_downs={fleet['scale_downs']})")
        # the no-zombie-commit invariant: a generation that completed
        # the drain ladder revoked its OWN epoch, so its store counted
        # zero fenced commit attempts
        for e in report["retired"]:
            if e["drained"] and e["fenced_commits"]:
                raise ChaosError(
                    f"elastic: drained generation attempted "
                    f"{e['fenced_commits']} fenced commits: {e}")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(f"elastic: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"elastic: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError("elastic: fleet dir survived shutdown")
        for _ in range(40):  # reader threads exit async after close
            stragglers = [t.name for t in threading.enumerate()
                          if t.name.startswith("frontdoor-")]
            if not stragglers:
                break
            time.sleep(0.05)
        if stragglers:
            raise ChaosError(
                f"elastic: live supervisor threads after shutdown: "
                f"{stragglers}")
        h = hashlib.sha256()
        for r in results:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "scale_ups": fleet["scale_ups"],
                          "scale_downs": fleet["scale_downs"],
                          "retired": report["retired"],
                          "fleet": {k: v for k, v in fleet.items()
                                    if k != "liveness"}}}


class SupervisorFailoverScenario:
    """Supervisor crash recovery under fire: a three-tenant wave through
    a journaled :class:`FrontDoor` whose SUPERVISOR dies mid-wave — the
    deliberate kill lands once every run (baseline included), and the
    fault rules land ``supervisor_crash`` / ``journal_torn`` at the
    ``journal_append`` seam so additional deaths hit distinct lifecycle
    points (sessions still queued, just placed, result in flight) plus
    ``journal_replay`` so an ADOPTING supervisor dies mid-replay.  Every
    death is resolved the same way: a fresh FrontDoor pointed at the
    SAME fleet dir replays the write-ahead journal, fences every dead
    generation, re-dials surviving workers over their resume tokens, and
    re-places whatever the journal proves was still owed.  After the
    wave completes, the scenario crashes the ADOPTING door too and
    adopts a third time — the double-restart leg: a journal whose every
    session is terminal must resurrect NOTHING and recompute nothing.
    The trial contract on top of the campaign's bit-identity check:
    zero duplicate runs PROVEN FROM THE JOURNAL (per logical
    (tenant, kind, params) key, at most one non-cached ``done`` result
    record), zero zombie commits from any revoked generation, zero
    orphan spill files, and no straggler supervisor threads."""

    name = "supervisor_failover"
    n_tenants = 3
    seeds = (91, 92, 93)

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.mem import RetryOOM
        from spark_rapids_jni_tpu.serve import (AdmissionShed, FrontDoor,
                                                QueryCancelled, WorkerLost)
        from spark_rapids_jni_tpu.serve import journal as journal_mod
        from spark_rapids_jni_tpu.shuffle import store as store_mod

        results: List[Optional[str]] = [None] * self.n_tenants
        kills = 0
        failovers = 0
        recovery = {"adopted_workers": 0, "recovered_sessions": 0,
                    "replayed_sessions": 0}
        config.set("serve_backoff_ms", 30.0)

        def construct(adopt_dir=None, cache=None):
            # the generous reconnect ladder keeps surviving workers
            # dialling while the adopting door rebinds the fleet address
            nonlocal failovers
            while True:
                try:
                    return FrontDoor(workers=2, pool_bytes=2 * MB,
                                     host_pool_bytes=512 * KB,
                                     max_concurrent=2, heartbeat_ms=60.0,
                                     respawn_max=4,
                                     partition_grace_ms=8000.0,
                                     reconnect_max=60,
                                     adopt_dir=adopt_dir,
                                     result_cache=cache)
                except (faultinj.SupervisorCrash,
                        faultinj.JournalTornError):
                    # died DURING construction/adoption (the
                    # journal_replay fault): the double-restart path —
                    # the next generation adopts the same journal again
                    failovers += 1
                    if failovers > _MAX_ATTEMPTS:
                        raise ChaosError(
                            f"{self.name}: supervisor died more than "
                            f"{_MAX_ATTEMPTS} times during adoption")

        fd = construct()
        fleet = fd.fleet_dir
        jpath = journal_mod.journal_path(fleet)
        sessions: Dict[int, object] = {}
        try:
            def failover():
                nonlocal fd, failovers
                failovers += 1
                if failovers > _MAX_ATTEMPTS:
                    raise ChaosError(
                        f"{self.name}: supervisor died more than "
                        f"{_MAX_ATTEMPTS} times")
                nd = construct(adopt_dir=fleet, cache=fd.result_cache)
                snap = nd.metrics.snapshot()
                for k in recovery:
                    recovery[k] += snap[k]
                rec = nd.recovered()
                # rebind: the dead door's session handles are inert —
                # adopt whatever the new door resurrected, keyed back to
                # tenants.  A tenant the journal knows but the CLIENT
                # does not (the crash unwound ``submit`` after its
                # record landed) is adopted here too — re-submitting it
                # would be the duplicate run the journal exists to
                # prevent.  Only a tenant absent from BOTH re-submits.
                for i in range(self.n_tenants):
                    s = sessions.get(i)
                    if s is not None and s.done():
                        continue
                    mine = [ns for ns in rec.values()
                            if ns.tenant == f"tenant-{i}"]
                    live = [ns for ns in mine if not ns.done()]
                    if mine:
                        sessions[i] = (live or mine)[0]
                    elif s is not None:
                        del sessions[i]
                fd = nd

            self_killed = False
            done = set()
            attempts = {i: 0 for i in range(self.n_tenants)}
            deadline = time.monotonic() + 150.0
            while len(done) < self.n_tenants:
                if time.monotonic() > deadline:
                    raise ChaosError(
                        f"{self.name}: wave not complete after 150s "
                        f"(done={sorted(done)}, failovers={failovers})")
                if fd.crashed:
                    failover()
                    continue
                try:
                    for i in range(self.n_tenants):
                        if i not in done and i not in sessions:
                            sessions[i] = fd.submit(
                                "spill_walk",
                                {"seed": self.seeds[i], "rows": 8 * KB},
                                tenant=f"tenant-{i}", priority=i,
                                replayable=True)
                except (faultinj.SupervisorCrash,
                        faultinj.JournalTornError):
                    continue  # crash picked up at the top of the loop
                if not self_killed and len(sessions) == self.n_tenants:
                    # the deliberate mid-wave kill: spin at millisecond
                    # grain for the moment a live session lands on a
                    # worker — the placed-but-unfinished window — so
                    # the first supervisor dies with real sessions owed
                    # and every run exercises adoption, faulted or not
                    spin_by = time.monotonic() + 20.0
                    while time.monotonic() < spin_by:
                        live = [s for s in sessions.values()
                                if not s.done()]
                        if not live or any(s.worker_id is not None
                                           for s in live):
                            break
                        time.sleep(0.002)
                    fd._simulate_crash()
                    self_killed = True
                    continue
                for i, sess in list(sessions.items()):
                    if i in done:
                        continue
                    try:
                        results[i] = sess.result(timeout=0.25)
                        done.add(i)
                    except TimeoutError:
                        continue  # in flight (or the supervisor died)
                    except faultinj.FatalInjectedFault:
                        raise  # whole-scenario replacement
                    except (WorkerLost, AdmissionShed,
                            faultinj.TaskCancelled,
                            faultinj.InjectedFault, QueryCancelled,
                            RetryOOM):
                        kills += 1
                        attempts[i] += 1
                        if attempts[i] >= _MAX_ATTEMPTS:
                            raise ChaosError(
                                f"{self.name}: tenant {i} not done "
                                f"after {_MAX_ATTEMPTS} re-submissions")
                        del sessions[i]  # fresh submit next pass

            # -- double restart: every session is terminal, so the next
            # generation must adopt the fleet and resurrect NOTHING
            state_a = journal_mod.replay(jpath)
            fd._simulate_crash()
            failover()
            if fd.recovered():
                raise ChaosError(
                    f"{self.name}: double restart resurrected terminal "
                    f"sessions: {sorted(fd.recovered())}")
            state_b = journal_mod.replay(jpath)
            folded = [{sid: s.get("status") for sid, s
                       in st.sessions.items()}
                      for st in (state_a, state_b)]
            if folded[0] != folded[1]:
                raise ChaosError(
                    f"{self.name}: double restart drifted the journal's "
                    f"folded session states ({folded[0]} != {folded[1]})")

            # -- the duplicate-run proof, straight from the journal: per
            # logical (tenant, kind, params) key at most ONE non-cached
            # ``done`` result record may exist, across every generation
            by_sid: Dict[int, tuple] = {}
            runs: Dict[tuple, int] = {}
            for e in journal_mod.scan(jpath):
                if e.get("rec") == "submit":
                    by_sid[int(e["sid"])] = (
                        str(e.get("tenant")), str(e.get("kind")),
                        json.dumps(e.get("params") or {}, sort_keys=True))
                elif e.get("rec") in ("requeued", "replayed") \
                        and e.get("new_sid") is not None \
                        and int(e["sid"]) in by_sid:
                    by_sid[int(e["new_sid"])] = by_sid[int(e["sid"])]
                elif e.get("rec") == "result" \
                        and e.get("status") == "done" \
                        and not e.get("from_cache"):
                    key = by_sid.get(int(e.get("sid", 0)))
                    runs[key] = runs.get(key, 0) + 1
            dups = {k: n for k, n in runs.items() if n > 1}
            if dups:
                raise ChaosError(
                    f"{self.name}: the journal proves duplicate runs — "
                    f"{dups}")

            # -- quiesce: the third generation's adopted workers must
            # finish their resume-token reattach before shutdown, or
            # the graceful bye has no link to ride (an unattached
            # worker would self-fence at the grace instead)
            quiet_by = time.monotonic() + 20.0
            while time.monotonic() < quiet_by:
                with fd._lock:
                    ws = list(fd._workers.values())
                    quiet = bool(ws) and all(w.state == "healthy"
                                             for w in ws)
                if quiet:
                    break
                time.sleep(0.05)

            # -- the fence probe, while the store still exists: every
            # generation ANY dead supervisor owned must be unable to
            # commit an adoptable shard
            if fd.store_dir and os.path.isdir(fd.store_dir):
                reader = store_mod.ShuffleStore(fd.store_dir,
                                                max_attempts=0)
                for g in reader.revoked():
                    zombie = store_mod.ShuffleStore(fd.store_dir,
                                                    epoch=g,
                                                    max_attempts=0)
                    try:
                        committed = zombie.put("chaos-failover-probe",
                                               "zombie",
                                               {"x": jnp.arange(4)})
                    except faultinj.FatalInjectedFault:
                        raise
                    except Exception:
                        committed = False  # aborted pre-rename
                    if committed:
                        raise ChaosError(
                            f"{self.name}: revoked gen {g} committed "
                            f"past its fence (zombie shard)")
                    if reader.has_committed("chaos-failover-probe",
                                            "zombie"):
                        raise ChaosError(
                            f"{self.name}: revoked gen {g}'s entry "
                            f"became adoptable")
        finally:
            try:
                if fd.crashed:
                    # an aborting attempt still must not leak the
                    # fleet: one more adoption purely so shutdown can
                    # reap the workers and remove the fleet dir
                    with contextlib.suppress(Exception):
                        fd = construct(adopt_dir=fleet,
                                       cache=fd.result_cache)
                report = fd.shutdown()
            finally:
                config.reset("serve_backoff_ms")
        if failovers < 2:
            raise ChaosError(
                f"{self.name}: only {failovers} failover(s) ran — the "
                f"deliberate kill plus the double-restart leg demand "
                f"at least two")
        if recovery["adopted_workers"] < 1:
            raise ChaosError(
                f"{self.name}: no surviving worker was ever adopted "
                f"({recovery})")
        unclean = {wid: e for wid, e in report["workers"].items()
                   if not e.get("clean")}
        if unclean:
            raise ChaosError(
                f"{self.name}: unclean workers: {unclean}")
        if report["orphan_spill_files"]:
            raise ChaosError(f"{self.name}: orphan spill files: "
                             f"{report['orphan_spill_files']}")
        if os.path.exists(fd.fleet_dir):
            raise ChaosError(
                f"{self.name}: fleet dir survived shutdown")
        for fenced in report["self_fenced"]:
            if fenced.get("fenced_commits"):
                raise ChaosError(
                    f"{self.name}: self-fenced worker "
                    f"{fenced['worker_id']} committed "
                    f"{fenced['fenced_commits']} shard(s) past its own "
                    f"revocation")
        for _ in range(40):  # reader threads exit async after close
            stragglers = [t.name for t in threading.enumerate()
                          if t.name.startswith("frontdoor-")]
            if not stragglers:
                break
            time.sleep(0.05)
        if stragglers:
            raise ChaosError(
                f"{self.name}: live supervisor threads after shutdown: "
                f"{stragglers}")
        h = hashlib.sha256()
        for r in results:  # position-stable: tenant i's digest at slot i
            h.update((r or "<none>").encode())
        return {"digest": h.hexdigest(),
                "extra": {"tenant_kills": kills,
                          "failovers": failovers,
                          "adopted_workers": recovery["adopted_workers"],
                          "recovered_sessions":
                          recovery["recovered_sessions"],
                          "replayed_sessions":
                          recovery["replayed_sessions"],
                          "fleet": {k: v for k, v in
                                    report["fleet"].items()
                                    if k != "liveness"}}}


class ZoneMapScenario:
    """Zone-map block skipping under fire: a 1%-selective predicate over
    a sorted FoR-encoded column prunes the morsel stream through its
    sidecar before the streaming exchange drains it.
    ``zone_map_corrupt`` fires ONLY here and in the compressed tests:
    this trial keeps the kind in the coverage check.  The injected fault
    at the ``zone_map_check`` probe becomes REAL damage (the sidecar's
    max stats flipped after the CRC stamp) and the mandatory verify
    raises ``ZoneMapCorruptionError`` LOUDLY at skip time — a lying
    sidecar may never silently return wrong rows.  The scenario then
    recovers the only sound way: re-encode from source (a fresh sidecar
    is the lineage) and re-run the pruned stream, proving the recovered
    result is bit-identical to the fault-free baseline AND still skipped
    (``blocks_skipped > 0``) — corruption can't scare the planner into
    permanent full scans."""

    name = "zone_map"
    task_id = 204

    def run(self) -> Dict:
        from spark_rapids_jni_tpu.columnar.encoded import encode_for
        from spark_rapids_jni_tpu.faultinj import ZoneMapCorruptionError
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
        from spark_rapids_jni_tpu.shuffle import (
            MorselSource,
            ShuffleRegistry,
            ShuffleService,
        )

        if len(jax.devices()) < 8:
            raise ChaosError(
                "zone_map scenario needs 8 devices; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8 before jax init")
        P = 8
        n = P * 1024
        # sorted values give the sidecar real locality: a 1%-selective
        # "<" predicate leaves whole zone blocks provably empty (the
        # 2^20 domain keeps per-block residuals inside FoR's u32 lanes)
        vals = np.sort(
            (np.arange(n, dtype=np.int64) * 2654435761) % (1 << 20))
        keys = (np.arange(n, dtype=np.int64) * 40503) % 64
        thresh = int(vals[n // 100])
        mesh = data_mesh(P)
        ones = jnp.ones((n,), jnp.bool_)
        xcol = Column(jnp.asarray(vals), ones, T.INT64)
        batch = shard_batch(ColumnBatch({
            "k": Column(jnp.asarray(keys), ones, T.INT64),
            "x": xcol}), mesh)
        # roomy arenas: this scenario stresses the skip-decision seam,
        # not the spill tiers (streaming_scan owns that fault domain)
        with _harness(64 * MB, 16 * MB, self.name) as (fw, adaptor):
            reg = ShuffleRegistry()
            with TaskContext(self.task_id) as ctx:
                def attempt():
                    # sharding is a pytree round-trip (it drops the
                    # column-attached sidecar), so the zone map rides
                    # in explicitly from the encode step
                    zone = encode_for(xcol, block=256).zone
                    src = MorselSource.from_batch(
                        batch, mesh, morsel_rows=128,
                        predicate=("x", "<", thresh), zone_map=zone)
                    res = ShuffleService(
                        mesh, registry=reg).exchange_stream(
                            src, key_names=["k"], ctx=ctx,
                            round_rows=256)
                    return (_digest((res.batch, res.occupancy)),
                            src.blocks_skipped)

                def body():
                    reencodes = 0
                    while True:
                        try:
                            d, skipped = attempt()
                            return d, skipped, reencodes
                        except ZoneMapCorruptionError:
                            # the loud failure just proved itself; the
                            # only recovery is a fresh encode — the
                            # source column is the sidecar's lineage
                            reencodes += 1
                            if reencodes > 3:
                                raise
                digest, skipped, reencodes = run_with_retry(
                    body, make_spillable=_always_retry(fw))
            RmmSpark.task_done(self.task_id)
            _check_invariants(fw, adaptor)
        if skipped <= 0:
            raise ChaosError(
                "zone_map degenerated: blocks_skipped=0 — the "
                "1%-selective stream no longer skips, the trial "
                "proves nothing")
        snap = reg.metrics.snapshot()
        return {"digest": digest,
                "extra": {"blocks_skipped": skipped,
                          "blocks_scanned": snap["blocks_scanned"],
                          "zone_reencodes": reencodes}}


SCENARIOS = {s.name: s for s in (SpillScenario(), ShuffleScenario(),
                                 Q95Scenario(), SortScenario(),
                                 StreamingScanScenario(), JniScenario(),
                                 ServingScenario(), FrontdoorScenario(),
                                 StoreRecoveryScenario(),
                                 MultihostScenario(),
                                 DataPlaneScenario(),
                                 ResultCacheScenario(),
                                 ElasticScenario(),
                                 SupervisorFailoverScenario(),
                                 ZoneMapScenario())}


# ---------------------------------------------------------------------------
# the trial matrix
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Trial:
    scenario: str
    rules: List[dict]
    label: str
    # shuffle trials that damage a spilled partition must prove the
    # partial re-map actually ran
    expect_recovered: bool = False
    # the multihost partition trial must prove a worker actually walked
    # the self-fence path (revoked its own epoch and exited), not merely
    # that the wave survived
    expect_self_fenced: bool = False
    # engine knobs pinned for the trial (r14: the pallas device-kernel
    # tier under fire).  The digest is still compared against the
    # scenario's DEFAULT-engine fault-free baseline, so a pinned trial
    # asserts engine bit-identity and fault recovery in one check.
    engines: Optional[Dict[str, str]] = None


# the pallas tier pins: both relational knobs for the compute-shaped
# q95, plus the fused shuffle scatter for the streaming pipeline
_PALLAS_Q95 = {"groupby_engine": "pallas", "join_engine": "pallas"}
_PALLAS_STREAM = {"groupby_engine": "pallas", "join_engine": "pallas",
                  "shuffle_scatter_engine": "pallas"}


def single_fault_trials(fast: bool = False) -> List[Trial]:
    """One fault per trial, exhaustive over (scenario boundary × kind):
    every FAULT_KINDS entry appears, recoverable kinds at every
    instrumented seam they can reach, with skip variants pinning later
    occurrences (second file written, second round drained)."""
    t: List[Trial] = []

    def one(scenario, match, kind, skip=0, count=1, expect_recovered=False,
            engines=None):
        rule = {"match": match, "fault": kind, "count": count}
        if skip:
            rule["skip"] = skip
        tag = kind + (f"+skip{skip}" if skip else "")
        if engines:
            vals = sorted(set(engines.values()))
            tag += "+" + ("pallas" if vals == ["pallas"]
                          else "+".join(vals))
        t.append(Trial(scenario, [rule], f"{scenario}:{match}[{tag}]",
                       expect_recovered=expect_recovered, engines=engines))

    # spill scenario: step seam + the full disk boundary set
    for kind in ("exception", "oom", "fatal"):
        one("spill", "chaos_spill_step", kind)
    one("spill", "chaos_spill_step", "exception", skip=1)
    one("spill", "spill_io_write", "spill_io")
    one("spill", "spill_io_write", "spill_io", skip=1)
    one("spill", "spill_io_read", "spill_io")
    one("spill", "spill_corrupt_file", "spill_corrupt")
    one("spill", "spill_corrupt_file", "spill_corrupt", skip=1)
    # host-tier damage: flips land in the host copy at demotion; the
    # read-back (or the inherited-meta disk verify after a host→disk
    # cascade) detects them and lineage rebuilds — recovery INSIDE run()
    one("spill", "host_corrupt_probe", "host_corrupt")
    one("spill", "host_corrupt_probe", "host_corrupt", skip=1)
    # r15: the codec'd spill tiers under fire — the same corruption
    # trials with the stored bytes riding the pack / block codecs.
    # Corruption now lands in a COMPRESSED frame (the probe flips the
    # frame header too), so the stored-CRC → decode → leaf-CRC verify
    # chain must catch it and lineage-rebuild; the digest check against
    # the DEFAULT-knob (codec off) baseline makes every trial a
    # bit-identity proof for the codec round trip as well.  host_corrupt
    # additionally proves damage laundering stays impossible: host-tier
    # flips encoded INTO a valid frame still fail the decoded-leaf CRC.
    for codec in ("pack", "block"):
        one("spill", "spill_corrupt_file", "spill_corrupt",
            engines={"spill_codec": codec})
        one("spill", "host_corrupt_probe", "host_corrupt",
            engines={"spill_codec": codec})

    # shuffle scenario: transport seam, step seam, and spilled-buffer
    # damage that must recover via map lineage
    one("shuffle", "shuffle_io_round", "shuffle_io")
    one("shuffle", "shuffle_io_round", "oom")
    one("shuffle", "spill_corrupt_file", "spill_corrupt",
        expect_recovered=True)
    # r15: the compressed wire under fire — the same spilled-buffer
    # damage with every round chunk crossing the all_to_all bit-packed
    # (shuffle_compress=pack).  The chunk spills AS lane words and the
    # lineage redrive re-packs; the digest check against the
    # DEFAULT-knob baseline proves the packed exchange is bit-identical
    # through corruption recovery.
    one("shuffle", "spill_corrupt_file", "spill_corrupt",
        expect_recovered=True, engines={"shuffle_compress": "pack"})
    if not fast:
        one("shuffle", "shuffle_io_round", "shuffle_io", skip=1)
        one("shuffle", "chaos_shuffle_step", "exception")
        one("shuffle", "chaos_shuffle_step", "fatal")
        one("shuffle", "spill_io_read", "spill_io", expect_recovered=True)
        one("shuffle", "spill_io_write", "spill_io")

    # q95 scenario: the compute seam — each kind once on the default
    # engines and once with both relational knobs pinned to the pallas
    # tier (the fused slot-table kernels must replay bit-identical to
    # the default-engine baseline through aborts and retries)
    if not fast:
        for kind in ("exception", "oom", "fatal"):
            one("q95", "chaos_q95_step", kind)
            one("q95", "chaos_q95_step", kind, engines=_PALLAS_Q95)

    # streaming scan: every fault kind lands mid-morsel (the decode
    # seam), on the early-drain transport, and on a half-received round
    # chunk's spill tiers (corruption must recover by replaying the
    # chunk's recorded morsel contributions).  The corruption trials pin
    # OCCURRENCES: the demotion order is deterministic (fixed data,
    # fixed arenas), and the first spill victim is the already-drained
    # round-0 send chunk, which is never read again — damage there is
    # harmless but proves nothing.  skip=8 demotions / skip=40 leaf
    # writes land on the HALF-RECEIVED send chunk for round 4 (demoted
    # mid-stream, promoted again for later scatters and its drain), so
    # detection MUST fire and the chunk MUST rebuild from its recorded
    # morsel contributions; the not-fast variants hit a received round
    # chunk instead, which rebuilds by re-draining from its send chunk.
    for kind in ("exception", "oom", "fatal"):
        one("streaming_scan", "chaos_stream_morsel", kind)
    one("streaming_scan", "shuffle_io_round", "shuffle_io")
    one("streaming_scan", "spill_corrupt_file", "spill_corrupt",
        skip=40, expect_recovered=True)
    one("streaming_scan", "host_corrupt_probe", "host_corrupt",
        skip=8, expect_recovered=True)
    # the pallas tier under fire: the fused scatter (plus both
    # relational knobs) pinned while faults land on the same seams.
    # The digest check runs against the default-engine baseline, so
    # every one of these doubles as a bit-identity assertion.  The
    # occurrence-pinned corruption variants stay on the default engines
    # (their skip counts encode the default demotion order); the pallas
    # ones fire on first crossings, which are engine-independent.
    one("streaming_scan", "chaos_stream_morsel", "exception",
        engines=_PALLAS_STREAM)
    if not fast:
        one("streaming_scan", "chaos_stream_morsel", "exception", skip=2)
        one("streaming_scan", "shuffle_io_round", "oom")
        one("streaming_scan", "spill_corrupt_file", "spill_corrupt",
            skip=5, expect_recovered=True)
        one("streaming_scan", "host_corrupt_probe", "host_corrupt",
            skip=1, expect_recovered=True)
        one("streaming_scan", "spill_io_write", "spill_io")
        one("streaming_scan", "spill_io_read", "spill_io",
            expect_recovered=True)
        for kind in ("oom", "fatal"):
            one("streaming_scan", "chaos_stream_morsel", kind,
                engines=_PALLAS_STREAM)
        one("streaming_scan", "shuffle_io_round", "shuffle_io",
            engines=_PALLAS_STREAM)
        one("streaming_scan", "spill_corrupt_file", "spill_corrupt",
            engines=_PALLAS_STREAM)
        one("streaming_scan", "host_corrupt_probe", "host_corrupt",
            engines=_PALLAS_STREAM)

    # zone_map scenario: the skip-decision seam.  zone_map_corrupt fires
    # ONLY here and in the compressed tests — this trial keeps the kind
    # in the coverage check.  The injected fault becomes real post-CRC
    # stat damage, the mandatory verify fails LOUD, and the scenario
    # recovers by re-encoding (fresh sidecar = lineage) to the
    # fault-free baseline's exact digest, still skipping blocks.
    one("zone_map", "zone_map_check", "zone_map_corrupt")
    if not fast:
        one("zone_map", "zone_map_check", "zone_map_corrupt", count=2)

    # sort scenario: the distributed-sort seam (pre-plan and post-sort)
    if not fast:
        for kind in ("exception", "oom", "fatal"):
            one("sort", "chaos_sort_step", kind)
        one("sort", "chaos_sort_step", "exception", skip=1)

    # jni scenario: the host-boundary seam (between bridge invocations)
    if not fast:
        for kind in ("exception", "oom", "fatal"):
            one("jni", "chaos_jni_step", kind)
        one("jni", "chaos_jni_step", "oom", skip=1)

    # serving scenario: tenant kills at every lifecycle boundary — still
    # queued (serve_admit), mid-query (serve_step), and mid-spill-write —
    # plus the abort/recover kinds at the step seam and the full disk
    # boundary set crossed from inside worker threads.  task_cancel
    # appears ONLY here and in the serve tests: this is the trial set
    # that keeps the kind in the campaign's coverage check.
    one("serving", "serve_step", "task_cancel")
    one("serving", "serve_admit", "task_cancel")
    one("serving", "spill_io_write", "task_cancel")
    for kind in ("exception", "oom", "fatal"):
        one("serving", "serve_step", kind)
    one("serving", "spill_io_write", "spill_io")
    one("serving", "spill_corrupt_file", "spill_corrupt")
    if not fast:
        one("serving", "serve_step", "task_cancel", skip=1)
        one("serving", "serve_admit", "oom")
        one("serving", "spill_io_read", "spill_io")
        one("serving", "host_corrupt_probe", "host_corrupt")
        one("serving", "spill_corrupt_file", "spill_corrupt", skip=1)

    # frontdoor scenario: worker kills at every lifecycle point of the
    # process boundary — submission received (worker_recv), queued
    # (serve_admit), mid-query (serve_step), mid-spill-write, and result
    # computed but undelivered (worker_result) — plus the wedge kind and
    # the in-worker abort/recover set.  worker_crash / worker_stall fire
    # ONLY here: these trials keep both kinds in the coverage check.
    # Each worker process runs its own occurrence clock, so a count=1
    # rule can fire once in EVERY initial worker; the supervisor
    # re-exports counts minus fleet-wide fires to respawned workers,
    # which is what makes crash trials converge instead of looping.
    if not fast:
        for match in ("worker_recv", "serve_admit", "serve_step",
                      "spill_io_write", "worker_result"):
            one("frontdoor", match, "worker_crash")
        one("frontdoor", "serve_step", "worker_stall")
        one("frontdoor", "serve_step", "task_cancel")
        one("frontdoor", "serve_step", "exception")
        one("frontdoor", "serve_step", "oom")
        one("frontdoor", "spill_io_write", "spill_io")
        one("frontdoor", "spill_corrupt_file", "spill_corrupt")

    # store_recovery scenario: the durable shuffle plane.  store_commit /
    # store_corrupt fire ONLY here and in the store tests — these trials
    # keep both kinds in the coverage check.  The torn write loses the
    # durable copy (lineage covers, soft failure); worker_crash at the
    # commit probe is the SIGKILL-mid-commit variant (the supervisor
    # reaps the tmp remnant and revokes the gen); the crash at the
    # serve seam (skip=2 → wave 1's first query, maps already
    # committed) proves the replacement ADOPTS instead of re-running;
    # the corruption trial proves adoption's CRC pass quarantines the
    # damaged entry and falls back to lineage — bit-identical all ways.
    if not fast:
        one("store_recovery", "store_commit", "store_commit")
        one("store_recovery", "store_commit", "worker_crash",
            expect_recovered=True)
        one("store_recovery", "serve_step", "worker_crash", skip=2,
            expect_recovered=True)
        one("store_recovery", "store_corrupt_file", "store_corrupt",
            expect_recovered=True)
        # r15: the codec'd durable plane — commits ride the pack codec
        # (spill_codec exported to the worker processes through the env
        # layer), post-commit damage lands in compressed frames, and
        # adoption's stored-CRC → decode → leaf-CRC chain must
        # quarantine and lineage-rebuild to the codec-off baseline's
        # exact digest
        one("store_recovery", "store_corrupt_file", "store_corrupt",
            expect_recovered=True, engines={"spill_codec": "pack"})
        one("store_recovery", "serve_step", "worker_crash", skip=2,
            expect_recovered=True, engines={"spill_codec": "pack"})

    # dataplane scenario: the zero-copy result path.  shm_torn /
    # shm_stale fire ONLY here and in the data-plane tests — these
    # trials keep both kinds in the coverage check.  The torn trial
    # flips segment bytes AFTER the CRC stamps (the supervisor's chunk
    # verify must catch it and re-place under a fresh sid); the stale
    # trial rewrites the descriptor to a dead generation (the epoch
    # verify must reject BEFORE any CRC work); worker_crash at the
    # result seam kills the worker with a segment in flight — the fd
    # must be reaped with the transport, never decoded.  Torn/stale
    # trials assert expect_recovered: the damage counter proves the
    # verify path fired, not merely that the wave survived.
    if not fast:
        one("dataplane", "data_write_wk", "shm_torn",
            expect_recovered=True)
        one("dataplane", "data_write_wk", "shm_torn", skip=1,
            expect_recovered=True)
        one("dataplane", "data_descriptor_wk", "shm_stale",
            expect_recovered=True)
        one("dataplane", "worker_result", "worker_crash")
        one("dataplane", "serve_step", "worker_crash")
        one("dataplane", "serve_step", "exception")

    # result_cache scenario: the fleet result cache's serve/insert
    # seams.  cache_stale / cache_corrupt fire ONLY here and in the
    # result-cache tests — these trials keep both kinds in the coverage
    # check.  A stale serve rewinds the descriptor's snapshot id (the
    # front door's snapshot verify must reject BEFORE decode and
    # recompute live); a stale insert stores the rewound id (the NEXT
    # replay's serve is rejected the same way); corruption flips a
    # stored byte post-seal at either seam (the served chunk CRCs can
    # never match — quarantine-and-recompute).  All four assert
    # expect_recovered: the stale/quarantine counters prove the verify
    # path fired, not merely that the replays survived.  The scenario's
    # own mutated-input wave asserts zero hits after mutation on EVERY
    # trial, faulted or not.
    if not fast:
        one("result_cache", "cache_serve", "cache_stale",
            expect_recovered=True)
        one("result_cache", "cache_serve", "cache_corrupt",
            expect_recovered=True)
        one("result_cache", "cache_insert", "cache_stale",
            expect_recovered=True)
        one("result_cache", "cache_insert", "cache_corrupt",
            expect_recovered=True)
        one("result_cache", "cache_serve", "cache_stale", skip=1,
            expect_recovered=True)
        one("result_cache", "serve_step", "worker_crash")
        one("result_cache", "worker_result", "worker_crash")
        one("result_cache", "serve_step", "oom")

    # elastic scenario: the launcher and retirement seams.
    # scale_up_fail / drain_stuck fire ONLY here and in the elastic
    # tests — these trials keep both kinds in the coverage check.  The
    # failed launch lands at the launcher boundary (construction OR an
    # autoscale spawn, whichever crossing comes first) and must resolve
    # through the respawn ladder; the wedged drain must escalate to the
    # drain-deadline kill with the retired generation fenced; the crash
    # trial overlaps a worker loss with in-flight autoscaling.
    if not fast:
        one("elastic", "launcher_spawn", "scale_up_fail")
        one("elastic", "launcher_spawn", "scale_up_fail", skip=1)
        one("elastic", "worker_drain", "drain_stuck")
        one("elastic", "serve_step", "worker_crash")
        one("elastic", "serve_step", "oom")

    # supervisor_failover scenario: the journal seams.  supervisor_crash
    # and journal_torn fire ONLY here and in the journal tests — these
    # trials keep both kinds in the coverage check.  Every run already
    # kills its first supervisor deliberately; the skip bands land the
    # INJECTED death at distinct lifecycle points of the occurrence
    # clock (both doors share it): skip=3 is the first submit append
    # (sessions still queued), the mid band lands among the placement
    # appends, the late band among running/result appends or the
    # adopting generation's own writes — and the journal_replay trial
    # kills the ADOPTING supervisor mid-replay, the double-restart path
    # under fire.  Torn variants convert the same appends into REAL
    # tail damage that replay must truncate cleanly.
    one("supervisor_failover", "journal_append", "supervisor_crash",
        skip=3)
    if not fast:
        one("supervisor_failover", "journal_append", "supervisor_crash",
            skip=6)
        one("supervisor_failover", "journal_append", "supervisor_crash",
            skip=9)
        one("supervisor_failover", "journal_replay", "supervisor_crash",
            skip=4)
        one("supervisor_failover", "journal_append", "journal_torn",
            skip=3)
        one("supervisor_failover", "journal_append", "journal_torn",
            skip=8)
        one("supervisor_failover", "serve_step", "worker_crash")

    # multihost scenario: the three network kinds fired at the worker
    # side of both directions, link drops at the supervisor side of
    # both, and the partition trial.  net_drop / net_stall / net_torn
    # fire ONLY here and in the wire tests: these trials keep all three
    # kinds in the coverage check.  Worker-side rules export to BOTH
    # initial workers (each process runs its own occurrence clock), so a
    # count=1 rule may fire twice fleet-wide — every firing must still
    # resolve through the reconnect ladder.  The partition trial's
    # skip=2 spares each worker's hello + first pong; count=5 covers the
    # 1 live send + 3 ladder hellos one incarnation consumes, and the
    # supervisor re-exports counts minus FLEET-WIDE fires, so the
    # respawned generation inherits a quiet network and converges.
    if not fast:
        for kind in ("net_drop", "net_stall", "net_torn"):
            one("multihost", "net_send_wk", kind)
            one("multihost", "net_recv_wk", kind)
        one("multihost", "net_send_sup", "net_drop")
        one("multihost", "net_recv_sup", "net_drop")
        t.append(Trial(
            "multihost",
            [{"match": "net_send_wk", "fault": "net_drop",
              "skip": 2, "count": 5}],
            "multihost:net_send_wk[net_drop+partition]",
            expect_self_fenced=True))
    return t


# multi-fault sampling pools: kinds that recover INSIDE a run (plus
# exception, whose replacement re-run is itself a recovery path)
_MULTI_POOL = {
    "spill": [("chaos_spill_step", "oom"), ("chaos_spill_step", "exception"),
              ("spill_io_write", "spill_io"), ("spill_io_read", "spill_io"),
              ("spill_corrupt_file", "spill_corrupt"),
              ("host_corrupt_probe", "host_corrupt")],
    "shuffle": [("shuffle_io_round", "shuffle_io"),
                ("shuffle_io_round", "oom"),
                ("spill_corrupt_file", "spill_corrupt"),
                ("spill_io_write", "spill_io")],
    "streaming_scan": [("chaos_stream_morsel", "oom"),
                       ("chaos_stream_morsel", "exception"),
                       ("shuffle_io_round", "shuffle_io"),
                       ("spill_corrupt_file", "spill_corrupt"),
                       ("host_corrupt_probe", "host_corrupt")],
    "q95": [("chaos_q95_step", "oom"), ("chaos_q95_step", "exception")],
    "sort": [("chaos_sort_step", "oom"), ("chaos_sort_step", "exception")],
    "jni": [("chaos_jni_step", "oom"), ("chaos_jni_step", "exception")],
    "serving": [("serve_step", "oom"), ("serve_step", "task_cancel"),
                ("serve_step", "exception"),
                ("spill_io_write", "spill_io"),
                ("spill_corrupt_file", "spill_corrupt")],
    "frontdoor": [("serve_step", "worker_crash"), ("serve_step", "oom"),
                  ("serve_step", "task_cancel"),
                  ("spill_io_write", "spill_io"),
                  ("spill_corrupt_file", "spill_corrupt")],
    "store_recovery": [("serve_step", "worker_crash"),
                       ("store_commit", "store_commit"),
                       ("store_corrupt_file", "store_corrupt"),
                       ("serve_step", "oom")],
    "multihost": [("net_send_wk", "net_drop"), ("net_recv_wk", "net_torn"),
                  ("net_send_sup", "net_drop"),
                  ("net_recv_sup", "net_stall"),
                  ("serve_step", "worker_crash")],
    "dataplane": [("data_write_wk", "shm_torn"),
                  ("data_descriptor_wk", "shm_stale"),
                  ("worker_result", "worker_crash"),
                  ("serve_step", "oom")],
    "result_cache": [("cache_serve", "cache_stale"),
                     ("cache_serve", "cache_corrupt"),
                     ("cache_insert", "cache_stale"),
                     ("cache_insert", "cache_corrupt"),
                     ("serve_step", "worker_crash"),
                     ("serve_step", "oom")],
    "elastic": [("launcher_spawn", "scale_up_fail"),
                ("worker_drain", "drain_stuck"),
                ("serve_step", "worker_crash"),
                ("serve_step", "oom")],
    # journal_append kinds stay OUT of the composite pool on purpose: a
    # derived skip of 0-2 would land the death on the FIRST door's
    # meta/spawn appends — a construction crash that orphans a fleet
    # dir instead of exercising adoption.  journal_replay is safe (the
    # probe is only crossed while adopting), and the worker kinds run
    # concurrently with the scenario's deliberate failover.
    "supervisor_failover": [("journal_replay", "supervisor_crash"),
                            ("serve_step", "worker_crash"),
                            ("serve_step", "oom"),
                            ("spill_io_write", "spill_io")],
}


def multi_fault_trials(seed: int, per_scenario: int) -> List[Trial]:
    """Seeded composite schedules: 2-3 rules per trial drawn from the
    scenario's recoverable pool with derived skip/count offsets.  Same
    seed → same schedules, bit for bit — the scenario name is mixed in
    via crc32, NOT ``hash()``, which PYTHONHASHSEED re-randomizes every
    interpreter (schedules must replay identically across processes)."""
    trials: List[Trial] = []
    for scenario, pool in _MULTI_POOL.items():
        mix = zlib.crc32(scenario.encode()) % 1009
        for i in range(per_scenario):
            rng = random.Random(seed * 7919 + mix + i)
            picks = rng.sample(pool, k=min(rng.randint(2, 3), len(pool)))
            rules = []
            for match, kind in picks:
                rule = {"match": match, "fault": kind,
                        "count": rng.randint(1, 2)}
                # q95/sort cross their probe only twice per attempt;
                # larger skips could out-run the occurrence clock
                # (vacuous trial)
                skip = rng.randint(
                    0, 1 if scenario in ("q95", "sort") else 2)
                if skip:
                    rule["skip"] = skip
                rules.append(rule)
            # a trial where EVERY rule skips can out-run every occurrence
            # clock (some probes cross only once or twice per attempt):
            # the lead rule always fires on its first crossing
            rules[0].pop("skip", None)
            trials.append(Trial(
                scenario, rules, f"{scenario}:multi[seed={seed} #{i}]"))
    return trials


# ---------------------------------------------------------------------------
# the campaign
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _pinned_engines(engines: Optional[Dict[str, str]]):
    """Pin engine knobs for one trial, restoring the previous values on
    the way out.  Pinned trials are still digest-compared against the
    scenario's DEFAULT-engine fault-free baseline, so the comparison
    doubles as the engine bit-identity assertion under fire.

    Each pin is ALSO exported as its ``SPARK_RAPIDS_TPU_<KEY>`` env var:
    the frontdoor-family scenarios (frontdoor / store_recovery /
    multihost / dataplane) spawn worker PROCESSES inside the trial, and
    those read knobs through the config env layer — without the export a
    codec pin would apply only to the supervisor."""
    if not engines:
        yield
        return
    saved = {k: config.get(k) for k in engines}
    env_names = {k: "SPARK_RAPIDS_TPU_" + k.upper() for k in engines}
    saved_env = {ev: os.environ.get(ev) for ev in env_names.values()}
    try:
        for k, v in engines.items():
            config.set(k, v)
            os.environ[env_names[k]] = str(v)
        yield
    finally:
        for k, v in saved.items():
            config.set(k, v)
        for ev, v in saved_env.items():
            if v is None:
                os.environ.pop(ev, None)
            else:
                os.environ[ev] = v


def _run_with_replacement(scenario) -> Dict:
    """Run a scenario to completion under the active fault schedule:
    recoverable kinds resolve inside run(); exception/fatal abort the
    attempt and a replacement run starts from scratch (the harness tore
    everything down).  The attempt bound is the campaign's 'retry counts
    bounded' invariant."""
    last: Optional[BaseException] = None
    for attempt in range(1, _MAX_ATTEMPTS + 1):
        try:
            out = scenario.run()
            out["attempts"] = attempt
            return out
        except (faultinj.InjectedFault, faultinj.FatalInjectedFault) as e:
            last = e
    raise ChaosError(
        f"{scenario.name}: not done after {_MAX_ATTEMPTS} replacement "
        f"attempts (last: {last!r})")


def run_campaign(fast: bool = False, seed: int = 0,
                 trials: Optional[int] = None,
                 log: Callable[[str], None] = lambda s: None) -> Dict:
    """Execute the full matrix; returns the report dict (``ok`` key).
    Raises nothing on trial failure — failures are collected so one bad
    trial does not hide the others' evidence."""
    faultinj.configure()  # clean slate: no inherited schedules
    per_scenario = (0 if fast else
                    (trials if trials is not None
                     else int(config.get("chaos_trials"))))
    matrix = single_fault_trials(fast) + multi_fault_trials(
        seed, per_scenario)
    used = {t.scenario for t in matrix}

    baselines: Dict[str, Dict] = {}
    for name in sorted(used):
        log(f"baseline: {name}")
        baselines[name] = SCENARIOS[name].run()

    report = {"fast": fast, "seed": seed, "trials": [],
              "kinds_fired": [], "failures": [], "ok": False}
    kinds_fired = set()
    for trial in matrix:
        sc = SCENARIOS[trial.scenario]
        rec = {"label": trial.label, "rules": trial.rules}
        if trial.engines:
            rec["engines"] = trial.engines
        try:
            with _pinned_engines(trial.engines), \
                    faultinj.scope({"seed": seed, "faults": trial.rules}):
                out = _run_with_replacement(sc)
                fired = faultinj.fired_log()
            rec["attempts"] = out["attempts"]
            rec["fired"] = fired
            rec.update(out["extra"])
            if not fired:
                raise ChaosError(
                    f"{trial.label}: vacuous trial — no rule fired, the "
                    f"boundary was never crossed")
            if out["digest"] != baselines[trial.scenario]["digest"]:
                raise ChaosError(
                    f"{trial.label}: faulted result DIFFERS from the "
                    f"fault-free baseline "
                    f"({out['digest'][:12]} != "
                    f"{baselines[trial.scenario]['digest'][:12]})")
            if (trial.expect_recovered
                    and not out["extra"].get("recovered_partitions")):
                raise ChaosError(
                    f"{trial.label}: expected a lineage recovery "
                    f"(recovered_partitions > 0) but none was recorded")
            if (trial.expect_self_fenced
                    and not out["extra"].get("self_fenced_workers")):
                raise ChaosError(
                    f"{trial.label}: expected a partitioned worker to "
                    f"self-fence (self_fenced_workers > 0) but none did")
            kinds_fired.update(f["fault"] for f in fired)
            rec["ok"] = True
            log(f"ok: {trial.label} (attempts={out['attempts']}, "
                f"fired={len(fired)})")
        except Exception as e:  # collect, don't abort the sweep
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            rec.setdefault("fired", faultinj.fired_log())
            report["failures"].append(rec)
            log(f"FAIL: {trial.label}: {rec['error']}")
        report["trials"].append(rec)

    report["kinds_fired"] = sorted(kinds_fired)
    missing = set(faultinj.FAULT_KINDS) - kinds_fired
    if missing and not fast:
        report["failures"].append({
            "label": "coverage",
            "error": f"FAULT_KINDS never fired: {sorted(missing)}"})
        log(f"FAIL: kinds never fired: {sorted(missing)}")
    report["ok"] = not report["failures"]
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 subset: fewer single-fault trials, no "
                         "multi-fault soak, no q95 scenario")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trials", type=int, default=None,
                    help="multi-fault trials per scenario "
                         "(default: the chaos_trials knob)")
    ap.add_argument("--report", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args(argv)

    report = run_campaign(fast=args.fast, seed=args.seed,
                          trials=args.trials,
                          log=lambda s: print(f"[chaos] {s}", flush=True))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2)
    n = len(report["trials"])
    n_ok = sum(1 for t in report["trials"] if t.get("ok"))
    print(f"[chaos] {n_ok}/{n} trials ok; kinds fired: "
          f"{report['kinds_fired']}")
    if not report["ok"]:
        print("[chaos] CAMPAIGN FAILED — fired_log() per failing trial:",
              file=sys.stderr)
        for f_rec in report["failures"]:
            print(f"  {f_rec.get('label')}: {f_rec.get('error')}",
                  file=sys.stderr)
            for entry in f_rec.get("fired", []):
                print(f"    {entry}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
