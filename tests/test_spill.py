"""Tiered spill framework tests (mem/spill.py).

Covers the subsystem end-to-end: tier walks with exact metric
accounting, the bounded host tier demoting to disk under CpuRetryOOM
pressure, task-aware LRU eviction priority, the spill()/get() race fix,
TaskContext auto-unregistration, injected spill-I/O faults degrading to
the higher tier, and the acceptance scenario — two concurrent tasks
oversubscribing the device arena and completing via automatic cross-task
device→host→disk spill and read-back with no manual ``make_spillable``
wiring (the reference proves the same story with
SpillableColumnarBatch + SpillFramework suites plugin-side).
"""

import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu import faultinj
from spark_rapids_jni_tpu.mem import (
    RmmSpark,
    Spillable,
    SpillableHandle,
    TaskContext,
    ThreadStateRegistry,
    batch_nbytes,
    run_with_retry,
)
from spark_rapids_jni_tpu.mem import spill as spill_mod

MB = 1 << 20
KB = 1 << 10


@pytest.fixture
def framework(tmp_path):
    fw = spill_mod.install(spill_dir=str(tmp_path / "spill"))
    yield fw
    spill_mod.shutdown()


@pytest.fixture
def adaptor():
    a = RmmSpark.set_event_handler(2 * MB, host_pool_bytes=512 * KB,
                                   poll_ms=10.0)
    yield a
    RmmSpark.clear_event_handler()


def _tree(n_words, seed=0):
    """A device tree of n_words int32 (4 * n_words bytes)."""
    return {"x": jnp.asarray(
        np.random.default_rng(seed).integers(0, 1 << 20, n_words,
                                             dtype=np.int32))}


def _spill_files(fw):
    return [f for f in os.listdir(fw.spill_dir)
            if os.path.isfile(os.path.join(fw.spill_dir, f))]


class TestTierWalk:
    def test_device_host_disk_roundtrip_exact_metrics(self, framework):
        h = SpillableHandle(_tree(256), name="walk")
        want = np.asarray(h.get()["x"])
        assert h.tier == "device"
        h.spill()
        assert h.tier == "host"
        h.spill_host()
        assert h.tier == "disk"
        assert len(_spill_files(framework)) == 1
        got = np.asarray(h.get()["x"])
        assert h.tier == "device"
        assert (got == want).all()
        assert _spill_files(framework) == []  # read-back deletes the file
        m = framework.metrics.snapshot()
        assert m["device_to_host_bytes"] == 1024
        assert m["host_to_disk_bytes"] == 1024
        assert m["disk_to_host_bytes"] == 1024
        assert m["host_to_device_bytes"] == 1024
        assert all(m[k] == 1 for k in (
            "device_to_host_count", "host_to_disk_count",
            "disk_to_host_count", "host_to_device_count"))
        assert m["eviction_ns"] > 0
        h.close()
        assert h.tier == "closed"
        assert len(framework.store) == 0

    def test_close_cleans_disk_files(self, framework):
        h = SpillableHandle(_tree(64), name="cleanup")
        h.spill()
        h.spill_host()
        assert len(_spill_files(framework)) == 1
        h.close()
        assert _spill_files(framework) == []
        with pytest.raises(ValueError):
            h.get()

    def test_spill_is_idempotent(self, framework):
        h = SpillableHandle(_tree(64))
        assert h.spill() == 0  # uncharged (no ctx): moved but freed 0
        assert h.tier == "host"
        assert h.spill() == 0  # already host: no-op
        assert framework.metrics.snapshot()["device_to_host_count"] == 1
        h.close()


class TestChargedTiers:
    def test_spill_releases_device_charge_get_recharges(self, framework,
                                                        adaptor):
        with TaskContext(1) as ctx:
            h = SpillableHandle(_tree(64 * KB // 4), ctx=ctx)
            nbytes = 64 * KB
            assert adaptor.total_allocated() == nbytes
            freed = h.spill()
            assert freed == nbytes
            assert adaptor.total_allocated() == 0
            # host tier is CHARGED against the unified host arena
            assert adaptor.host_total_allocated() == nbytes
            h.get()
            assert adaptor.total_allocated() == nbytes
            assert adaptor.host_total_allocated() == 0
            h.close()
            assert adaptor.total_allocated() == 0
        RmmSpark.task_done(1)

    def test_host_pressure_demotes_lru_to_disk(self, framework, adaptor):
        """Filling the 512K host arena pushes the COLDEST host batch to
        disk (the SpillableHostStore host→disk demotion)."""
        with TaskContext(1) as ctx:
            h1 = SpillableHandle(_tree(200 * KB // 4, seed=1), ctx=ctx,
                                 name="h1")
            h2 = SpillableHandle(_tree(200 * KB // 4, seed=2), ctx=ctx,
                                 name="h2")
            h3 = SpillableHandle(_tree(300 * KB // 4, seed=3), ctx=ctx,
                                 name="h3")
            h1.spill()   # host: 200K
            h2.spill()   # host: 400K
            h3.spill()   # 300K > 112K free -> h1 (LRU) demoted to disk
            assert h1.tier == "disk"
            assert h2.tier == "host"
            assert h3.tier == "host"
            m = framework.metrics.snapshot()
            assert m["host_to_disk_bytes"] == 200 * KB
            assert adaptor.host_total_allocated() == 500 * KB
            for h, words, seed in ((h1, 200 * KB // 4, 1),
                                   (h2, 200 * KB // 4, 2),
                                   (h3, 300 * KB // 4, 3)):
                assert (np.asarray(h.get()["x"])
                        == np.asarray(_tree(words, seed=seed)["x"])).all()
                h.close()
        RmmSpark.task_done(1)

    def test_batch_bigger_than_host_pool_goes_straight_to_disk(
            self, framework, adaptor):
        with TaskContext(1) as ctx:
            h = SpillableHandle(_tree(1 * MB // 4), ctx=ctx)  # 1M > 512K
            h.spill()
            assert h.tier == "disk"  # host tier can NEVER hold it
            assert adaptor.host_total_allocated() == 0
            m = framework.metrics.snapshot()
            assert m["device_to_host_bytes"] == 1 * MB
            assert m["host_to_disk_bytes"] == 1 * MB
            h.close()
        RmmSpark.task_done(1)


class TestStorePriority:
    def test_lru_order_and_task_awareness(self, framework):
        a = SpillableHandle(_tree(64), name="a")
        a.task_id = 1
        b = SpillableHandle(_tree(64), name="b")
        b.task_id = 2
        c = SpillableHandle(_tree(64), name="c")
        c.task_id = 2
        a.get()  # a is now the hottest AND owned by the requester
        freed = framework.spill_to_fit(requesting_task_id=1)
        assert freed == 0  # uncharged handles free no device bytes
        # nbytes=None (spill everything eligible): others AND own unpinned
        assert a.tier == "host" and b.tier == "host" and c.tier == "host"
        for h in (a, b, c):
            h.close()

    def test_eviction_order_other_tasks_lru_first(self, framework):
        order = []
        hs = []
        for name, task in (("own-cold", 1), ("other-new", 2),
                           ("other-old", 2)):
            h = SpillableHandle(_tree(16), name=name)
            h.task_id = task
            orig = h.spill
            h.spill = (lambda o=orig, n=name: (order.append(n), o())[1])
            hs.append(h)
        hs[0]._last_use = 1  # requester's own batch is the COLDEST
        hs[2]._last_use = 2
        hs[1]._last_use = 3
        framework.spill_to_fit(requesting_task_id=1)
        # other tasks' batches go first (LRU among them); the requester's
        # own — though colder than both — goes last
        assert order == ["other-old", "other-new", "own-cold"]
        for h in hs:
            h.close()

    def test_pinned_handles_are_skipped(self, framework):
        h = SpillableHandle(_tree(64), name="pinned")
        with h.pinned():
            framework.spill_to_fit()
            assert h.tier == "device"
        framework.spill_to_fit()
        assert h.tier == "host"
        h.close()

    def test_spill_to_fit_stops_at_nbytes(self, framework, adaptor):
        with TaskContext(1) as ctx:
            h1 = SpillableHandle(_tree(64 * KB // 4), ctx=ctx, name="old")
            h2 = SpillableHandle(_tree(64 * KB // 4), ctx=ctx, name="new")
            h2.get()  # h1 is LRU
            freed = framework.spill_to_fit(1)  # any positive amount
            assert freed == 64 * KB
            assert h1.tier != "device" and h2.tier == "device"
            h1.close()
            h2.close()
        RmmSpark.task_done(1)


class TestSpillGetRace:
    def test_spill_while_getting_keeps_data_intact(self, framework):
        """The satellite race fix: cross-thread spill() during the owner's
        get() must serialize (or skip), never corrupt."""
        h = SpillableHandle(_tree(4096, seed=9), name="race")
        want = np.asarray(h.get()["x"]).copy()
        stop = threading.Event()
        errors = []

        def evictor():
            while not stop.is_set():
                try:
                    h.spill()
                    h.spill_host()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

        t = threading.Thread(target=evictor, daemon=True)
        t.start()
        try:
            for _ in range(300):
                got = np.asarray(h.get()["x"])
                assert (got == want).all()
        finally:
            stop.set()
            t.join(timeout=10.0)
        assert not errors, errors
        h.close()

    def test_busy_handle_is_skipped_not_deadlocked(self, framework):
        """An evictor hitting a handle whose lock is held treats it like a
        pinned one (try-lock), so no lock-order deadlock is possible."""
        h = SpillableHandle(_tree(64), name="busy")
        held = threading.Event()
        release = threading.Event()

        def holder():  # RLock is reentrant: must be held by ANOTHER thread
            h._lock.acquire()
            held.set()
            release.wait(10.0)
            h._lock.release()

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert held.wait(10.0)
        try:
            assert h.spill() == 0
            assert h.tier == "device"
        finally:
            release.set()
            t.join(timeout=10.0)
        h.spill()
        assert h.tier == "host"
        h.close()


class TestTaskContextIntegration:
    def test_exit_auto_closes_and_unregisters(self, framework, adaptor):
        with TaskContext(5) as ctx:
            SpillableHandle(_tree(64 * KB // 4), ctx=ctx)
            h2 = SpillableHandle(_tree(64 * KB // 4), ctx=ctx)
            h2.spill()
            h2.spill_host()
            assert len(framework.store) == 2
            assert len(_spill_files(framework)) == 1
        # never close()d explicitly: the context exit did it all
        assert len(framework.store) == 0
        assert _spill_files(framework) == []
        assert adaptor.total_allocated() == 0
        assert adaptor.host_total_allocated() == 0
        RmmSpark.task_done(5)

    def test_columnbatch_spillable_helper(self, framework, adaptor):
        import __graft_entry__ as ge

        with TaskContext(6) as ctx:
            batch = ge._example_batch(256)
            assert batch.device_nbytes == batch_nbytes(batch)
            h = batch.spillable(ctx)
            assert adaptor.total_allocated() == batch.device_nbytes
            h.spill()
            assert adaptor.total_allocated() == 0
            assert h.get().num_rows == 256
        RmmSpark.task_done(6)


class TestBatchNbytesDedupe:
    def test_aliased_leaves_charge_once(self):
        a = jnp.arange(1024, dtype=jnp.int32)
        assert batch_nbytes({"x": a}) == 4096
        assert batch_nbytes({"x": a, "y": a}) == 4096  # same buffer
        b = jnp.arange(1024, dtype=jnp.int32) + 1
        assert batch_nbytes({"x": a, "y": b}) == 8192

    def test_numpy_leaves_dedupe_by_identity(self):
        a = np.arange(1024, dtype=np.int32)
        assert batch_nbytes([a, a]) == 4096
        assert batch_nbytes([a, a.copy()]) == 8192


class TestSpillIOFault:
    def test_disk_write_fault_keeps_host_tier(self, framework, adaptor):
        faultinj.configure({"faults": [
            {"match": "spill_io_write", "fault": "spill_io", "count": 1}]})
        try:
            with TaskContext(7) as ctx:
                h = SpillableHandle(_tree(64 * KB // 4, seed=4), ctx=ctx)
                want = np.asarray(h.get()["x"]).copy()
                h.spill()
                assert h.tier == "host"
                h.spill_host()  # injected SpillIOError
                # graceful degradation: still host-resident, still charged
                assert h.tier == "host"
                assert adaptor.host_total_allocated() == 64 * KB
                assert _spill_files(framework) == []  # no partial files
                m = framework.metrics.snapshot()
                assert m["disk_write_failures"] == 1
                assert m["host_to_disk_count"] == 0
                h.spill_host()  # injection exhausted: now it works
                assert h.tier == "disk"
                assert (np.asarray(h.get()["x"]) == want).all()
                h.close()
            RmmSpark.task_done(7)
        finally:
            faultinj.configure({})

    def test_spill_io_rule_validates(self):
        faultinj._Rule({"match": "spill_io_*", "fault": "spill_io"})
        with pytest.raises(ValueError):
            faultinj._Rule({"fault": "bogus"})  # graftlint: disable=GL006


class TestMetricsExport:
    def test_rmm_spark_surfaces_the_frameworks_counters(self, framework,
                                                        adaptor):
        with TaskContext(9) as ctx:
            h = SpillableHandle(_tree(64 * KB // 4), ctx=ctx)
            h.spill()
            h.get()
            h.close()
        RmmSpark.task_done(9)
        g = RmmSpark.spill_metrics()
        assert g["device_to_host_bytes"] == 64 * KB
        assert g == framework.metrics.snapshot()
        t = RmmSpark.get_and_reset_task_spill_metrics(9)
        assert t["device_to_host_bytes"] == 64 * KB
        assert t["host_to_device_bytes"] == 64 * KB
        # consume-once, like get_and_reset_num_retry
        t2 = RmmSpark.get_and_reset_task_spill_metrics(9)
        assert sum(t2.values()) == 0

    def test_zeros_without_framework(self):
        assert sum(RmmSpark.spill_metrics().values()) == 0
        assert set(RmmSpark.spill_metrics()) == set(
            spill_mod.SpillMetrics.FIELDS)


class TestLegacySpillableDelegates:
    def test_spillable_registers_with_store(self, framework, adaptor):
        with TaskContext(11) as ctx:
            s = Spillable(_tree(64), ctx)
            assert isinstance(s, SpillableHandle)
            assert len(framework.store) == 1
            # the central store can now evict a legacy Spillable
            framework.spill_to_fit(requesting_task_id=99)
            assert s.is_spilled
            s.close()
        RmmSpark.task_done(11)


class TestEndToEndOversubscription:
    """The acceptance scenario: device arena (2M) below the combined
    working set (2 x 1.2M), two concurrent dedicated tasks, NO manual
    make_spillable — task 2's RetryOOM automatically evicts task 1's idle
    batch device→host, the 512K host arena bounces it to disk, and task 1
    reads it back — all transitions metered exactly."""

    NWORDS = 307200  # 1,228,800 bytes of int32

    def test_two_tasks_complete_via_automatic_tiered_spill(
            self, framework, adaptor):
        nbytes = self.NWORDS * 4
        ev_a_ready = threading.Event()
        ev_b_done = threading.Event()
        results = {}
        failures = []

        def task_a():
            try:
                with TaskContext(1) as ctx:
                    h = SpillableHandle(_tree(self.NWORDS, seed=1), ctx=ctx,
                                        name="task1-batch")
                    want = np.asarray(h.get()["x"]).copy()
                    ev_a_ready.set()
                    # idle while task 2 runs; blocked_section tells the
                    # native deadlock scan this thread is parked host-side
                    with ThreadStateRegistry.blocked_section():
                        if not ev_b_done.wait(60.0):
                            raise TimeoutError("task 2 never finished")
                    assert h.tier == "disk", h.tier  # evicted down both tiers
                    got = run_with_retry(lambda: np.asarray(h.get()["x"]))
                    results["a"] = (got == want).all()
            except BaseException as e:  # noqa: BLE001
                failures.append(("a", e))

        def task_b():
            try:
                if not ev_a_ready.wait(60.0):
                    raise TimeoutError("task 1 never set up")
                with TaskContext(2) as ctx:
                    def step():
                        h = SpillableHandle(_tree(self.NWORDS, seed=2),
                                            ctx=ctx, name="task2-batch")
                        out = int(np.asarray(h.get()["x"]).sum())
                        h.close()
                        return out

                    # NO make_spillable: the framework default evicts
                    # task 1's idle batch cross-task
                    results["b"] = run_with_retry(step)
                ev_b_done.set()
            except BaseException as e:  # noqa: BLE001
                failures.append(("b", e))
                ev_b_done.set()

        ta = threading.Thread(target=task_a, daemon=True)
        tb = threading.Thread(target=task_b, daemon=True)
        ta.start()
        tb.start()
        ta.join(timeout=90.0)
        tb.join(timeout=90.0)
        assert not ta.is_alive() and not tb.is_alive(), "deadlock"
        assert not failures, failures
        assert results["a"], "task 1's batch corrupted by the round trip"
        want_b = int(np.asarray(_tree(self.NWORDS, seed=2)["x"]).sum())
        assert results["b"] == want_b

        # ---- exact metric accounting across every tier transition ----
        m = framework.metrics.snapshot()
        assert m["device_to_host_bytes"] == nbytes
        assert m["device_to_host_count"] == 1
        assert m["host_to_disk_bytes"] == nbytes  # 1.2M > 512K host arena
        assert m["host_to_disk_count"] == 1
        assert m["disk_to_host_bytes"] == nbytes
        assert m["disk_to_host_count"] == 1
        assert m["host_to_device_bytes"] == nbytes
        assert m["host_to_device_count"] == 1
        assert m["disk_write_failures"] == 0
        # the spilled batch belonged to TASK 1: per-task attribution
        t1 = RmmSpark.get_and_reset_task_spill_metrics(1)
        assert t1["device_to_host_bytes"] == nbytes
        # task 2 went through the native retry ladder to get there
        assert adaptor.get_and_reset_num_retry(2) >= 1
        # nothing left behind
        assert adaptor.total_allocated() == 0
        assert adaptor.host_total_allocated() == 0
        assert len(framework.store) == 0
        assert _spill_files(framework) == []
        RmmSpark.task_done(1)
        RmmSpark.task_done(2)
