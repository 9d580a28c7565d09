"""Elastic fleet tests: pluggable launchers, load-aware placement,
queue-driven autoscaling, tenant quotas, and warm plan-cache sharing.

The RemoteLauncher test drives a REAL worker through a command
template (an ``sh -c 'exec "$@"'`` agent standing in for ssh) and
asserts the worker completes the exact same hello/fence/bye contract
as a fork-launched one — the acceptance criterion for the launcher
abstraction.  Autoscale tests use aggressive knobs (high-water 1,
sub-second hold/idle windows) so scale-up and drain-retire both
happen within a bounded poll.
"""

import os
import signal
import threading
import time

import pytest

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.serve import (
    AutoScaler,
    FrontDoor,
    LaunchedWorker,
    LocalLauncher,
    Placement,
    QuotaExceeded,
    RemoteLauncher,
    fleet_metrics,
)
from spark_rapids_jni_tpu.serve.launcher import launcher_from_config

# an "agent" that is just exec — argv passes through unchanged, so the
# worker the supervisor talks to is byte-for-byte the worker it asked
# for, proving RemoteLauncher changes HOW the process exists, not WHAT
REMOTE_TEMPLATE = "sh -c 'exec \"$@\"' launcher-agent {argv}"


@pytest.fixture(autouse=True)
def _fast_ladder(short_tempdir):
    config.set("serve_backoff_ms", 40.0)
    yield
    for knob in ("serve_backoff_ms", "serve_launcher", "serve_placement",
                 "serve_autoscale", "serve_autoscale_high_water",
                 "serve_autoscale_low_water", "serve_autoscale_min",
                 "serve_autoscale_max", "serve_autoscale_hold_ms",
                 "serve_autoscale_idle_ms", "serve_autoscale_drain_ms",
                 "serve_tenant_quota_bytes", "serve_tenant_quota_s",
                 "serve_plan_warm"):
        config.reset(knob)
    faultinj.configure(None)
    _poll(lambda: not [t.name for t in threading.enumerate()
                       if t.name.startswith("frontdoor-")], timeout=5.0)


def _poll(pred, timeout=15.0, interval=0.02):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class _FakeWorker:
    """Stand-in WorkerHandle carrying just the fields Placement and
    AutoScaler score on — no processes, so these tests are instant."""

    def __init__(self, worker_id, host="local", state="healthy",
                 sessions=0, queue_depth=0, arena_bytes=0,
                 pool_bytes=1 << 20, stall_suspect=False,
                 retiring=False, gen=1):
        self.worker_id = worker_id
        self.host = host
        self.state = state
        self.sessions = {i: object() for i in range(sessions)}
        self.queue_depth = queue_depth
        self.arena_bytes = arena_bytes
        self.pool_bytes = pool_bytes
        self.stall_suspect = stall_suspect
        self.retiring = retiring
        self.gen = gen


class TestLauncherContract:
    def test_local_launcher_owns_exact_pid(self, tmp_path):
        lw = LocalLauncher().launch(
            ["sh", "-c", "exit 0"], cwd=str(tmp_path), env=dict(os.environ),
            log_path=str(tmp_path / "w.log"))
        try:
            assert lw.owns_pid(lw.pid)
            assert not lw.owns_pid(lw.pid + 1)
            assert lw.wait(10.0) == 0
        finally:
            lw.close()

    def test_remote_handle_adopts_first_hello_pid(self):
        class _P:
            pid = 12345
            returncode = None

        lw = LaunchedWorker(_P(), remote=True)
        # remote pids are unknowable until hello: adopt the first
        # claimant, then hold it — a second pid is an impostor
        assert lw.owns_pid(777)
        assert lw.pid == 777
        assert lw.owns_pid(777)
        assert not lw.owns_pid(778)

    def test_remote_template_requires_argv_or_appends(self):
        with RemoteLauncher("agent --host h {argv}") as r:
            assert r._command(["python", "-m", "w"]) == \
                ["agent", "--host", "h", "python", "-m", "w"]
        with RemoteLauncher(["agent", "run"]) as r2:
            assert r2._command(["python"]) == ["agent", "run", "python"]

    def test_launcher_from_config_dispatch(self):
        local = launcher_from_config("local")
        assert isinstance(local, LocalLauncher)
        local.close()
        remote = launcher_from_config(REMOTE_TEMPLATE)
        assert isinstance(remote, RemoteLauncher)
        remote.close()
        passthrough = LocalLauncher()
        assert launcher_from_config(passthrough) is passthrough
        passthrough.close()

    def test_remote_launcher_runs_real_worker_same_contract(self):
        """Acceptance: a RemoteLauncher-driven worker completes the
        identical argv / hello / fence-epoch / bye lifecycle."""
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       launcher=REMOTE_TEMPLATE)
        try:
            s = fd.submit("echo", {"value": "remote-ok"}, tenant="t0")
            assert s.result(timeout=60) == "remote-ok"
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["launcher"] == "remote"
        assert all(e["clean"] for e in report["workers"].values())
        assert report["orphan_spill_files"] == []


class TestPlacement:
    def test_load_mode_prefers_least_loaded(self):
        p = Placement(["local"])
        idle = _FakeWorker(0)
        busy = _FakeWorker(1, sessions=3, queue_depth=2)
        assert p.pick([busy, idle]) is idle
        # stalled workers lose to equally-loaded healthy ones
        stalled = _FakeWorker(2, stall_suspect=True)
        assert p.pick([stalled, idle]) is idle
        # arena pressure breaks depth ties
        hot = _FakeWorker(3, arena_bytes=900 << 10, pool_bytes=1 << 20)
        assert p.pick([hot, idle]) is idle

    def test_round_robin_mode_rotates(self):
        p = Placement(["local"], mode="round_robin")
        ws = [_FakeWorker(0), _FakeWorker(1, sessions=5, queue_depth=9)]
        picks = [p.pick(ws).worker_id for _ in range(4)]
        # pure rotation ignores load entirely — the comparison arm
        assert picks == [0, 1, 0, 1]

    def test_host_for_slot_spreads_then_balances(self):
        p = Placement(["hostA", "hostB"])
        assert p.host_for_slot(0, []) == "hostA"
        w0 = _FakeWorker(0, host="hostA")
        assert p.host_for_slot(1, [w0]) == "hostB"
        w1 = _FakeWorker(1, host="hostB", sessions=4)
        # equal worker counts: summed depth breaks the tie
        assert p.host_for_slot(2, [w0, w1]) == "hostA"

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            Placement(["local"], mode="psychic")


class TestAutoScaler:
    def test_scales_up_after_sustained_backlog(self):
        config.set("serve_autoscale_hold_ms", 50.0)
        config.set("serve_autoscale_high_water", 2)
        config.set("serve_autoscale_max", 3)
        a = AutoScaler(base_workers=1)
        try:
            ws = [_FakeWorker(0)]
            assert a.decide(0.0, 5, ws) is None  # not held long enough
            assert a.decide(0.2, 5, ws) == ("up", None)
            # cooldown: an immediate second tick stays quiet
            assert a.decide(0.21, 5, ws) is None
        finally:
            a.stop()

    def test_scales_down_idle_highest_id_and_respects_min(self):
        config.set("serve_autoscale_idle_ms", 50.0)
        a = AutoScaler(base_workers=1)
        try:
            ws = [_FakeWorker(0), _FakeWorker(1), _FakeWorker(2)]
            assert a.decide(0.0, 0, ws) is None  # idle clock just started
            action = a.decide(0.2, 0, ws)
            assert action is not None and action[0] == "down"
            assert action[1].worker_id == 2  # newest retires first
            # at the floor, never retire the last base worker
            a2 = AutoScaler(base_workers=1)
            assert a2.decide(0.2, 0, [_FakeWorker(0)]) is None
            a2.stop()
        finally:
            a.stop()

    def test_autoscale_end_to_end_up_then_drain_retire(self):
        """Acceptance: backlog adds >=1 worker; idle retires one through
        the drain ladder with zero fenced commits."""
        config.set("serve_autoscale_high_water", 1)
        config.set("serve_autoscale_hold_ms", 100.0)
        config.set("serve_autoscale_idle_ms", 300.0)
        config.set("serve_autoscale_max", 3)
        fd = FrontDoor(workers=1, heartbeat_ms=60.0, max_concurrent=1,
                       autoscale=True)
        try:
            sessions = [fd.submit("sleep", {"seconds": 0.8},
                                  tenant=f"t{i}") for i in range(6)]
            assert _poll(lambda: fleet_metrics()["scale_ups"] >= 1,
                         timeout=30.0), fleet_metrics()
            for s in sessions:
                assert s.result(timeout=90) == "slept"
            assert _poll(lambda: fleet_metrics()["scale_downs"] >= 1,
                         timeout=30.0), fleet_metrics()
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["autoscale"]["scale_ups"] >= 1
        assert report["autoscale"]["scale_downs"] >= 1
        retired = report["retired"]
        assert retired, report
        for e in retired:
            assert e["drained"] is True, retired
            assert e["clean"] is True, retired
            # retired generations left no zombie commit attempts
            assert e["fenced_commits"] == 0, retired
        assert report["orphan_spill_files"] == []


class TestElasticFaults:
    def test_scale_up_fail_hits_respawn_ladder_and_recovers(self):
        """A launch that dies at the launcher boundary is treated as
        capacity loss: counted, backed off, retried, and the fleet
        still answers queries."""
        faultinj.configure({"faults": [
            {"match": "launcher_spawn", "fault": "scale_up_fail",
             "count": 1},
        ]})
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            s = fd.submit("echo", {"value": "survived"})
            assert s.result(timeout=60) == "survived"
            assert fleet_metrics()["scale_up_failures"] >= 1
        finally:
            report = fd.shutdown()
        assert report["clean"], report

    def test_drain_stuck_escalates_past_deadline(self):
        """A retiring worker that wedges inside drain is killed at the
        drain deadline; its generation is fenced, nothing orphans."""
        faultinj.configure({"faults": [
            {"match": "worker_drain", "fault": "drain_stuck",
             "count": 1},
        ]})
        config.set("serve_autoscale_high_water", 1)
        config.set("serve_autoscale_hold_ms", 100.0)
        config.set("serve_autoscale_idle_ms", 200.0)
        config.set("serve_autoscale_drain_ms", 700.0)
        config.set("serve_autoscale_max", 2)
        fd = FrontDoor(workers=1, heartbeat_ms=60.0, max_concurrent=1,
                       autoscale=True)
        try:
            sessions = [fd.submit("sleep", {"seconds": 0.6},
                                  tenant=f"t{i}") for i in range(4)]
            for s in sessions:
                assert s.result(timeout=90) == "slept"
            # the wedged drain ends as an unclean retirement (deadline
            # kill), not a hung fleet
            assert _poll(lambda: fleet_metrics()["scale_downs"] >= 1,
                         timeout=30.0), fleet_metrics()
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert any(not e["drained"] for e in report["retired"]), report
        assert report["orphan_spill_files"] == []


class TestQuotas:
    def test_byte_quota_rejects_at_admission(self):
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       tenant_quota_bytes=1 << 20)
        try:
            ok = fd.submit("echo", {"value": "fits"}, tenant="acct-1",
                           est_bytes=512 << 10)
            assert ok.result(timeout=60) == "fits"
            with pytest.raises(QuotaExceeded, match="bytes"):
                # rejected AT admission: no session ever exists to leak
                fd.submit("echo", {"value": "too-big"},  # graftlint: disable=GL012
                          tenant="acct-1", est_bytes=900 << 10)
            # another tenant is unaffected
            other = fd.submit("echo", {"value": "mine"}, tenant="acct-2",
                              est_bytes=900 << 10)
            assert other.result(timeout=60) == "mine"
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["quota"]["rejections"].get("acct-1") == 1
        assert fleet_metrics()["quota_rejections"] >= 1

    def test_time_quota_charges_completions(self):
        fd = FrontDoor(workers=1, heartbeat_ms=80.0,
                       tenant_quota_s=0.05)
        try:
            first = fd.submit("sleep", {"seconds": 0.2}, tenant="acct-1")
            assert first.result(timeout=60) == "slept"
            # charged at completion: the next admission is over budget
            assert _poll(lambda: _rejects(fd), timeout=10.0)
        finally:
            report = fd.shutdown()
        assert report["clean"], report
        assert report["quota"]["tenant_seconds"]["acct-1"] > 0


def _rejects(fd):
    try:
        fd.submit("echo", {"value": "x"}, tenant="acct-1").result(timeout=30)
        return False
    except QuotaExceeded:
        return True


class TestWarmPlans:
    def test_respawned_worker_ships_warm_plans(self):
        """After a tenant class completes a query, a worker spawned
        later receives that plan shape for warm-up."""
        fd = FrontDoor(workers=1, heartbeat_ms=80.0)
        try:
            s = fd.submit("echo", {"value": "seed-plan"}, tenant="acct-1")
            assert s.result(timeout=60) == "seed-plan"
            # the NEXT incarnation (loss-protocol respawn) must be
            # handed acct's warm plan shape
            with fd._lock:
                pid = fd._workers[0].proc.pid
            os.kill(pid, signal.SIGKILL)
            s2 = fd.submit("echo", {"value": "after"}, tenant="acct-1",
                           replayable=True)
            assert s2.result(timeout=90) == "after"
            assert _poll(lambda: fleet_metrics()["plan_warm_shipped"] >= 1,
                         timeout=15.0), fleet_metrics()
        finally:
            report = fd.shutdown()
        assert report["clean"], report
