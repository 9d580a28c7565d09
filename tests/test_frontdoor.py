"""Multi-process serving front door tests.

The robustness core of the front-door PR: supervised executor worker
processes behind a Unix-socket protocol — crash detection via
heartbeats + waitpid, session re-placement through the bounded backoff
ladder, the loud :class:`WorkerLost` contract for non-replayable
victims, load shedding under lost capacity, and the fleet-wide
zero-orphan shutdown report.

Each test spawns real worker processes (each imports jax), so the
fixtures keep fleets small and heartbeats fast.
"""

import os
import signal
import threading
import time

import pytest

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.serve import (
    AdmissionShed,
    FrontDoor,
    ServeError,
    WorkerLost,
    fleet_metrics,
)


@pytest.fixture(autouse=True)
def _fast_ladder(short_tempdir):
    # deterministic per-test fleet dirs: every mkdtemp (the fleet dir,
    # its sockets, stores, worker dirs) lands under THIS test's own
    # short directory instead of a shared /tmp — two tests (or a retried
    # flake) can never contend on leftover directories
    config.set("serve_backoff_ms", 40.0)
    yield
    config.reset("serve_backoff_ms")
    faultinj.configure(None)
    # bounded straggler drain: frontdoor threads from THIS test must
    # wind down before the next test builds a fleet, or a slow reader
    # from a dead fleet aliases into the next test's thread checks
    _poll(lambda: not [t.name for t in threading.enumerate()
                       if t.name.startswith("frontdoor-")], timeout=5.0)


def _poll(pred, timeout=15.0, interval=0.02):
    """Bounded condition wait — the deflake primitive: every wait in
    this file polls a predicate with a deadline instead of sleeping a
    guessed duration, so a slow box waits longer, never flakes."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _no_stragglers():
    return _poll(lambda: not [t.name for t in threading.enumerate()
                              if t.name.startswith("frontdoor-")],
                 timeout=5.0)


class TestHappyPath:
    def test_echo_roundtrip_pinning_and_clean_shutdown(self):
        fd = FrontDoor(workers=2, heartbeat_ms=80.0)
        try:
            sessions = [fd.submit("echo", {"value": f"v{i}"},
                                  tenant=f"t{i % 2}") for i in range(6)]
            assert [s.result(timeout=60) for s in sessions] == \
                [f"v{i}" for i in range(6)]
            # sticky pinning: every session of a tenant on ONE worker
            for tenant in ("t0", "t1"):
                workers = {s.worker_id for i, s in enumerate(sessions)
                           if f"t{i % 2}" == tenant}
                assert len(workers) == 1, (tenant, workers)
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["orphan_spill_files"] == []
        assert all(e["clean"] for e in report["workers"].values())
        assert not os.path.exists(fd.fleet_dir)
        # idempotent: the second call returns the first report
        assert fd.shutdown() == report
        with pytest.raises(ServeError):
            fd.submit("echo", {"value": "late"}).result(timeout=1)
        assert _no_stragglers()

    @pytest.mark.parametrize("fleet", [
        {}, {"transport": "tcp", "hosts": "hostA,hostB"}], ids=["unix", "tcp"])
    def test_computed_result_crosses_bit_identical(self, fleet):
        """The workers' ``q6_digest`` over seeded batches equals the same
        steps run here, over either transport.  (A heartbeat with room:
        a worker that computes under load is not what is tested.)"""
        from spark_rapids_jni_tpu.serve.worker import _qk_q6_digest

        asks = [{"rows": 2048, "stream": i, "query": 0, "steps": 1}
                for i in range(2)]
        fd = FrontDoor(workers=2, heartbeat_ms=5000.0, **fleet)
        try:
            sess = [fd.submit("q6_digest", p, tenant=f"t{i}")
                    for i, p in enumerate(asks)]
            got = [s.result(timeout=120)[0] for s in sess]
        finally:
            fd.shutdown()
        assert got == [_qk_q6_digest(None, p, None)[0] for p in asks]

    def test_unknown_kind_fails_loudly(self):
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            with pytest.raises(ServeError, match="unknown query kind"):
                fd.submit("no_such_kind", {}).result(timeout=60)
        finally:
            assert fd.shutdown()["clean"]


class TestWorkerLoss:
    def test_crash_replaces_replayable_session(self):
        """A worker that SIGKILLs itself mid-query is detected, its
        spill dir reaped, the session re-placed onto the respawned
        worker, and the merged fired_log carries the worker's trace."""
        faultinj.configure({"faults": [
            {"match": "serve_step", "fault": "worker_crash", "count": 1},
        ]})
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            s = fd.submit("spill_walk", {"seed": 3}, tenant="t0",
                          replayable=True)
            digest = s.result(timeout=90)
            assert s.replacements >= 1
            assert s.status == "done"
            # determinism across the replacement: same seed, same digest
            s2 = fd.submit("spill_walk", {"seed": 3}, tenant="t0")
            assert s2.result(timeout=90) == digest
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["crashes"] == 1
        assert report["fleet"]["respawns"] == 1
        fired = faultinj.fired_log()
        assert any(e.get("fault") == "worker_crash"
                   and str(e.get("source", "")).startswith("worker-")
                   for e in fired)

    def test_crash_fails_nonreplayable_with_worker_lost(self):
        """A non-replayable session whose worker dies with the result
        undelivered fails loudly with WorkerLost carrying the dead
        worker's fired_log — never a silent re-run."""
        faultinj.configure({"faults": [
            {"match": "worker_result", "fault": "worker_crash",
             "count": 1},
        ]})
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            s = fd.submit("sleep", {"seconds": 0.2}, tenant="t0",
                          replayable=False)
            with pytest.raises(WorkerLost) as exc:
                s.result(timeout=90)
            assert exc.value.worker_id == 0
            assert any(e.get("fault") == "worker_crash"
                       for e in exc.value.fired_log)
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["worker_lost"] == 1

    def test_stall_detected_and_session_replaced(self):
        """A wedged worker (stops answering heartbeats) is SIGKILLed by
        the monitor and its session re-placed — the supervisor's
        detector, not any in-process cleanup, ends the wedge."""
        faultinj.configure({"faults": [
            {"match": "serve_step", "fault": "worker_stall", "count": 1},
        ]})
        fd = FrontDoor(workers=1, heartbeat_ms=60.0)
        try:
            s = fd.submit("spill_walk", {"seed": 9}, tenant="t0")
            assert s.result(timeout=90)
            assert s.replacements >= 1
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["stalls"] == 1


class TestDegradation:
    def test_shed_lowest_priority_when_capacity_lost(self):
        """With one of two single-slot workers dead and its respawn
        circuit open, pending admissions beyond the surviving capacity
        are shed lowest-priority-first."""
        fd = FrontDoor(workers=2, max_concurrent=1, respawn_max=0,
                       shed_threshold=0.6, heartbeat_ms=60.0)
        try:
            assert _poll(lambda: sum(
                1 for w in fd._workers.values()
                if w.state == "healthy") == 2)
            busy = [fd.submit("sleep", {"seconds": 3.0}, tenant=f"b{i}")
                    for i in range(2)]
            assert _poll(lambda: all(
                s.worker_id is not None for s in busy), timeout=10.0)
            hi = fd.submit("echo", {"value": "hi"}, tenant="b0",
                           priority=5)
            lo = fd.submit("echo", {"value": "lo"}, tenant="b1",
                           priority=0)
            with fd._lock:
                pid = fd._workers[1].proc.pid
            os.kill(pid, signal.SIGKILL)
            with pytest.raises(AdmissionShed):
                lo.result(timeout=30)
            assert lo.status == "shed"
            assert hi.result(timeout=30) == "hi"
        finally:
            report = fd.shutdown()
        assert report["fleet"]["sheds"] >= 1
        assert report["fleet"]["circuit_open"] == 1

    def test_fleet_exhausted_fails_pending_with_worker_lost(self):
        """All workers dead with the breaker open: pending sessions
        fail with WorkerLost instead of hanging forever."""
        fd = FrontDoor(workers=1, max_concurrent=1, respawn_max=0,
                       heartbeat_ms=60.0)
        try:
            assert _poll(lambda: any(
                w.state == "healthy" for w in fd._workers.values()))
            hold = fd.submit("sleep", {"seconds": 5.0}, tenant="t0")
            assert _poll(lambda: hold.worker_id is not None, timeout=10.0)
            queued = fd.submit("echo", {"value": "q"}, tenant="t1")
            with fd._lock:
                pid = fd._workers[0].proc.pid
            os.kill(pid, signal.SIGKILL)
            with pytest.raises(WorkerLost):
                queued.result(timeout=30)
        finally:
            fd.shutdown()


class TestStorePlane:
    def test_crash_recovery_adopts_committed_shards(self):
        """The tentpole invariant: a worker SIGKILLed after committing
        its map output is re-placed onto a respawn that ADOPTS the
        committed shard (map_runs == 0) with a bit-identical digest;
        the same crash with the store disabled re-runs the map."""
        # query 1 commits, query 2's first step crashes the worker
        schedule = {"faults": [
            {"match": "serve_step", "fault": "worker_crash",
             "skip": 1, "count": 1}]}
        faultinj.configure(schedule)
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            r1 = fd.submit("shuffle_digest",
                           {"seed": 3, "store_key": "sd-3"},
                           tenant="t0").result(timeout=120)
            assert r1["map_runs"] == 1 and r1["adopted"] == 0
            s2 = fd.submit("shuffle_digest",
                           {"seed": 3, "store_key": "sd-3"}, tenant="t0")
            r2 = s2.result(timeout=120)
            assert s2.replacements >= 1
            assert r2["digest"] == r1["digest"]  # bit-identical recovery
            assert r2["adopted"] >= 1 and r2["map_runs"] == 0
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["crashes"] == 1
        assert "store" in report
        assert not os.path.exists(fd.fleet_dir)

        # the comparison arm: store disabled, same crash — the map MUST
        # re-run (map_runs 1 > the store run's 0), same digest
        faultinj.configure(schedule)
        fd2 = FrontDoor(workers=1, heartbeat_ms=80.0, store=False)
        try:
            p1 = fd2.submit("shuffle_digest",
                            {"seed": 3, "store_key": "sd-3"},
                            tenant="t0").result(timeout=120)
            s2 = fd2.submit("shuffle_digest",
                            {"seed": 3, "store_key": "sd-3"}, tenant="t0")
            p2 = s2.result(timeout=120)
            assert s2.replacements >= 1
            assert p2["digest"] == r1["digest"]
            assert p2["map_runs"] == 1 and p2["adopted"] == 0
            assert p1["map_runs"] == 1
        finally:
            report2 = fd2.shutdown()
        assert report2["clean"], report2
        assert "store" not in report2

    def test_zombie_generation_is_fenced(self):
        """A dead generation's epoch is revoked at loss time: a zombie
        that outlives its SIGKILL verdict can write tmp entries but its
        commit is rejected at the rename — never adoptable."""
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.shuffle.store import ShuffleStore

        faultinj.configure({"faults": [
            {"match": "serve_step", "fault": "worker_crash", "count": 1}]})
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            s = fd.submit("spill_walk", {"seed": 5}, tenant="t0")
            assert s.result(timeout=90)
            assert s.replacements >= 1  # gen 1 died and was revoked
            zombie = ShuffleStore(fd.store_dir, epoch=1)
            assert zombie.fenced(1)
            assert not zombie.put("zq", "map", {"x": jnp.arange(4)})
            assert zombie.snapshot()["fenced_commits"] == 1
            # nothing committed, nothing adoptable, by any reader
            reader = ShuffleStore(fd.store_dir)
            assert not reader.has_committed("zq", "map")
            assert reader.adopt("zq", "map") is None
            # the respawned generation (gen 2) is NOT fenced
            assert not zombie.fenced(2)
        finally:
            report = fd.shutdown()
        assert report["clean"], report

    def test_retain_knob_keeps_store_past_shutdown(self):
        """shuffle_store_retain=True: shutdown reaps the fleet but
        leaves the committed store for the next fleet to adopt from."""
        import shutil

        from spark_rapids_jni_tpu.shuffle.store import ShuffleStore

        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        config.set("shuffle_store_retain", True)
        try:
            r = fd.submit("shuffle_digest",
                          {"seed": 7, "store_key": "keep-7"},
                          tenant="t0").result(timeout=120)
            assert r["map_runs"] == 1
        finally:
            report = fd.shutdown()
            config.reset("shuffle_store_retain")
        try:
            assert report["clean"], report
            assert os.path.isdir(fd.store_dir)
            assert ShuffleStore(fd.store_dir).has_committed("keep-7", "map")
            # everything else in the fleet dir was still reaped
            assert os.listdir(fd.fleet_dir) == ["shuffle-store"]
        finally:
            shutil.rmtree(fd.fleet_dir, ignore_errors=True)


class TestMultiHostTransport:
    def test_tcp_two_host_fleet_round_trip(self):
        """Two workers placed round-robin on two named hosts over TCP:
        the fleet behaves exactly like the single-box Unix default."""
        fd = FrontDoor(workers=2, heartbeat_ms=80.0, transport="tcp",
                       hosts="hostA,hostB")
        try:
            sessions = [fd.submit("echo", {"value": f"v{i}"},
                                  tenant=f"t{i % 2}") for i in range(4)]
            assert [s.result(timeout=60) for s in sessions] == \
                [f"v{i}" for i in range(4)]
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["transport"] == "tcp"
        assert report["hosts"] == ["hostA", "hostB"]
        hosts = {e["host"] for e in report["workers"].values()}
        assert hosts == {"hostA", "hostB"}  # both hosts got a slot
        assert report["fleet"]["self_fenced_workers"] == 0

    def test_multi_host_list_forces_tcp(self):
        """>1 host cannot ride a Unix socket; the front door promotes
        the transport instead of silently colocating everything."""
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       hosts=["h0", "h1"])
        try:
            assert fd._transport == "tcp"
            assert fd.submit("echo", {"value": "m"}).result(timeout=60) \
                == "m"
        finally:
            assert fd.shutdown()["clean"]

    def test_reconnect_reattaches_without_session_loss(self):
        """The connection-supervision contract: an injected link drop on
        the supervisor's send is NOT a worker loss.  The worker re-dials,
        the idempotent hello re-attaches the same incarnation, the
        in-flight session completes exactly once — zero replacements,
        zero crashes, one reconnect."""
        faultinj.configure({"faults": [
            {"match": "net_send_sup", "fault": "net_drop", "count": 1}]})
        # generous grace: on a starved box a slow re-dial must stay a
        # reconnect, not cross into the partition/self-fence path
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       partition_grace_ms=8000.0)
        try:
            s = fd.submit("sleep", {"seconds": 1.0}, tenant="t0",
                          replayable=True)
            assert s.result(timeout=90) == "slept"
            assert s.replacements == 0  # link loss != worker loss
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["reconnects"] >= 1
        assert report["fleet"]["crashes"] == 0
        assert report["fleet"]["respawns"] == 0
        assert report["fleet"]["partitions_detected"] == 0
        fired = faultinj.fired_log()
        assert any(e.get("fault") == "net_drop" for e in fired)

    def test_partitioned_worker_self_fences_and_is_quarantined(self):
        """Split-brain: a worker that cannot reach the supervisor past
        the partition grace revokes its OWN store epoch (self-fence),
        writes the sentinel, and exits; the supervisor counts it and
        re-places the session.  Post-revocation commits from that
        generation are rejected at the rename — zero zombie shards."""
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.shuffle.store import ShuffleStore

        # skip=2 spares hello+first pong; count=4 = 1 live send + 3
        # ladder hellos, so the rule is fully consumed by the first
        # incarnation and the respawn inherits a quiet network
        faultinj.configure({"faults": [
            {"match": "net_send_wk", "fault": "net_drop",
             "skip": 2, "count": 4}]})
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       partition_grace_ms=700.0, reconnect_max=3)
        try:
            s = fd.submit("sleep", {"seconds": 2.0}, tenant="t0",
                          replayable=True)
            assert s.result(timeout=120) == "slept"
            assert s.replacements >= 1
            revoked = fd._store.revoked()
            assert 1 in revoked  # the fenced generation's epoch
            zombie = ShuffleStore(fd.store_dir, epoch=1)
            assert not zombie.put("zp", "map", {"x": jnp.arange(4)})
            reader = ShuffleStore(fd.store_dir)
            assert not reader.has_committed("zp", "map")
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["fleet"]["self_fenced_workers"] >= 1
        assert report["fleet"]["partitions_detected"] >= 1
        assert report["self_fenced"], report
        entry = report["self_fenced"][0]
        assert entry["worker_id"] == 0 and entry["epoch"] == 1
        assert entry["fenced_commits"] == 0  # nothing slipped through


class TestFailover:
    """Supervisor crash → a fresh FrontDoor adopts the same fleet dir
    off the write-ahead journal (serve/journal.py)."""

    @staticmethod
    def _adopt(fleet_dir, **kw):
        kw.setdefault("workers", 1)
        kw.setdefault("heartbeat_ms", 80.0)
        kw.setdefault("partition_grace_ms", 8000.0)
        kw.setdefault("reconnect_max", 60)
        return FrontDoor(adopt_dir=fleet_dir, **kw)

    def test_adoption_recovers_a_live_session(self):
        from spark_rapids_jni_tpu.serve import journal
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       partition_grace_ms=8000.0, reconnect_max=60)
        fleet = fd.fleet_dir
        sess = fd.submit("sleep", {"seconds": 3.0}, tenant="t")
        assert _poll(lambda: sess.worker_id is not None)
        fd._simulate_crash()
        assert fd.crashed
        nd = self._adopt(fleet)
        try:
            rec = nd.recovered()
            assert sess.sid in rec
            assert rec[sess.sid].result(timeout=60.0) == "slept"
            snap = nd.metrics.snapshot()
            assert snap["adopted_workers"] >= 1
            assert snap["recovered_sessions"] + \
                snap["replayed_sessions"] >= 1
            # the journal proves the adoption AND that the logical
            # query ran exactly once — follow the sid through any
            # re-keying to its single terminal record
            entries = journal.scan(journal.journal_path(fleet))
            assert any(e["rec"] == "adopt" for e in entries)
            sid, done = sess.sid, 0
            for e in entries:
                if e["rec"] in ("requeued", "replayed") \
                        and e.get("sid") == sid \
                        and e.get("new_sid") is not None:
                    sid = int(e["new_sid"])
                elif e["rec"] == "result" and e.get("sid") == sid \
                        and e.get("status") == "done":
                    done += 1
            assert done == 1
        finally:
            report = nd.shutdown()
            fd.shutdown()
        assert report["clean"]
        assert report["recovery"]["adopted_workers"] >= 1
        assert _no_stragglers()

    def test_double_restart_resurrects_nothing(self, tmp_path):
        from spark_rapids_jni_tpu.serve import journal
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       partition_grace_ms=8000.0, reconnect_max=60)
        fleet = fd.fleet_dir
        jpath = journal.journal_path(fleet)
        try:
            for i in range(2):
                assert fd.submit("echo", {"value": i},
                                 tenant="t").result(timeout=60.0) == i
            fd._simulate_crash()
            nd = self._adopt(fleet)
            fd = nd
            # the wave was terminal before the crash: adoption must
            # resurrect NOTHING
            assert nd.recovered() == {}
            state_a = journal.replay(jpath)
            nd._simulate_crash()
            fd = self._adopt(fleet)
            assert fd.recovered() == {}
            state_b = journal.replay(jpath)
            # double restart is idempotent: same folded session states
            assert {s: v["status"] for s, v in state_a.sessions.items()} \
                == {s: v["status"] for s, v in state_b.sessions.items()}
            # and the twice-adopted door still serves
            assert fd.submit("echo", {"value": "z"},
                             tenant="t").result(timeout=60.0) == "z"
        finally:
            report = fd.shutdown()
        assert report["clean"]
        assert _no_stragglers()

    def test_adoption_replays_past_a_self_fenced_worker(self):
        # the worker ORPHANS itself (supervisor silent past the grace)
        # before any new door adopts: the journal-alive pid is gone, so
        # adoption must fence its generation and REPLAY the session on
        # a fresh worker instead of re-dialing a corpse
        config.set("serve_orphan_grace_ms", 200.0)
        try:
            fd = FrontDoor(workers=1, heartbeat_ms=40.0)
            fleet = fd.fleet_dir
            sess = fd.submit("sleep", {"seconds": 30.0}, tenant="t")
            assert _poll(lambda: sess.worker_id is not None)
            with fd._lock:
                proc = list(fd._workers.values())[0].proc
            fd._simulate_crash()
            # rc=3: the orphan drained and self-fenced its generation
            assert _poll(lambda: proc.poll() is not None, timeout=30.0)
            assert proc.poll() == 3
            nd = self._adopt(fleet)
            try:
                rec = nd.recovered()
                assert sess.sid in rec
                assert rec[sess.sid].result(timeout=120.0) == "slept"
                snap = nd.metrics.snapshot()
                assert snap["adopted_workers"] == 0
                assert snap["replayed_sessions"] >= 1
            finally:
                report = nd.shutdown()
                fd.shutdown()
            assert report["clean"]
            # the fenced generation's sentinel surfaced in the report
            assert any("orphaned" in s.get("reason", "")
                       for s in report["self_fenced"]) or \
                report["recovery"]["adopted_workers"] == 0
        finally:
            config.reset("serve_orphan_grace_ms")
        assert _no_stragglers()

    def test_cancel_during_adoption_unwinds_cleanly(self):
        from spark_rapids_jni_tpu.serve import QueryCancelled
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       partition_grace_ms=8000.0, reconnect_max=60)
        fleet = fd.fleet_dir
        sess = fd.submit("sleep", {"seconds": 60.0}, tenant="t")
        assert _poll(lambda: sess.worker_id is not None)
        fd._simulate_crash()
        nd = self._adopt(fleet)
        try:
            rec = nd.recovered()
            assert sess.sid in rec
            ns = rec[sess.sid]
            ns.cancel()
            with pytest.raises(QueryCancelled):
                ns.result(timeout=60.0)
            assert ns.status == "cancelled"
        finally:
            report = nd.shutdown()
            fd.shutdown()
        # the unwound session left nothing behind: clean fleet, no
        # orphan spill files, fleet dir gone
        assert report["clean"]
        assert not os.path.exists(fleet)
        assert _no_stragglers()


class TestFleetMetrics:
    def test_zeros_safe_surface(self):
        snap = fleet_metrics()
        for field in ("workers_spawned", "crashes", "stalls", "sheds",
                      "respawns", "worker_lost", "circuit_open",
                      "replacements", "reconnects", "partitions_detected",
                      "self_fenced_workers", "recovered_sessions",
                      "adopted_workers", "replayed_sessions"):
            assert field in snap and snap[field] >= 0
        from spark_rapids_jni_tpu.mem.rmm_spark import RmmSpark
        assert RmmSpark.fleet_metrics() == fleet_metrics()
        summary = RmmSpark.fleet_metrics()
        assert summary["workers_spawned"] >= 0
        assert "liveness" in summary

    def test_counters_track_a_fleet(self):
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            fd.submit("echo", {"value": "x"}).result(timeout=60)
        finally:
            fd.shutdown()
        snap = fleet_metrics()
        assert snap["workers_spawned"] == 1
        assert snap["liveness"] == {0: "shutdown"}
