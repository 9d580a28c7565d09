"""Out-of-core ShuffleService tests: skew planning, lossless multi-round
drain, spillable buffers under a capped arena, strict/counted OOB ids,
transport fault injection, and the spillable join build table."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
from spark_rapids_jni_tpu.shuffle import (
    ShuffleError,
    ShuffleRegistry,
    ShuffleService,
    get_registry,
    plan_rounds,
)

P8 = 8


def _int_batch(vals):
    a = np.asarray(vals, np.int64)
    return ColumnBatch({
        "v": Column(jnp.asarray(a), jnp.ones((len(a),), jnp.bool_), T.INT64)
    })


def _row_sharded(arr, mesh):
    return jax.device_put(
        jnp.asarray(arr),
        jax.sharding.NamedSharding(mesh,
                                   jax.sharding.PartitionSpec("data")))


def _delivered(res):
    occ = np.asarray(jax.device_get(res.occupancy))
    out = np.asarray(jax.device_get(res.batch["v"].data))
    return out, occ


@pytest.fixture
def small_buckets():
    """Capacity bucket small enough that modest tests go multi-round."""
    old = config.get("shuffle_capacity_bucket")
    config.set("shuffle_capacity_bucket", 16)
    yield
    config.set("shuffle_capacity_bucket", old)


# ---------------------------------------------------------------------------
# planner units
# ---------------------------------------------------------------------------

class TestPlanRounds:
    def test_single_round_when_it_fits(self):
        plan = plan_rounds([[10, 5], [3, 2]], round_rows=64, bucket=16,
                           max_rounds=8)
        assert plan.rounds == 1
        assert plan.capacity == 16  # bucket-rounded max, not round_rows
        assert plan.max_bucket == 10 and plan.total_rows == 20
        assert plan.lossless

    def test_multi_round_drains_the_max_bucket(self):
        c = np.zeros((4, 4), np.int64)
        c[2, 1] = 1000
        plan = plan_rounds(c, round_rows=100, bucket=16, max_rounds=64)
        assert plan.capacity == 112  # 100 rounded up to the bucket
        assert plan.rounds == 9  # ceil(1000 / 112)
        assert plan.rounds * plan.capacity >= 1000 and plan.lossless

    def test_max_rounds_caps_by_raising_capacity(self):
        c = [[1000]]
        plan = plan_rounds(c, round_rows=10, bucket=1, max_rounds=4)
        assert plan.rounds <= 4
        assert plan.lossless  # never by dropping rows

    def test_zero_counts(self):
        plan = plan_rounds(np.zeros((8, 8), np.int64))
        assert plan.rounds == 1 and plan.total_rows == 0
        assert plan.skew_ratio == 0.0

    def test_skew_ratio_reads_all_to_one_as_p(self):
        c = np.zeros((P8, P8), np.int64)
        c[:, 0] = 64  # every sender's full batch goes to destination 0
        plan = plan_rounds(c, round_rows=1 << 16)
        assert plan.skew_ratio == pytest.approx(float(P8))

    def test_invalid_args_raise(self):
        with pytest.raises(ValueError):
            plan_rounds([[1]], round_rows=0)
        with pytest.raises(ValueError):
            plan_rounds([[1]], bucket=-1)


# ---------------------------------------------------------------------------
# adversarial skew through the service (lossless or loud)
# ---------------------------------------------------------------------------

class TestServiceAdversarialSkew:
    def test_all_rows_to_one_destination(self, eight_devices, small_buckets):
        mesh = data_mesh(P8)
        n = P8 * 64
        vals = np.arange(n, dtype=np.int64)
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded(np.zeros(n, np.int32), mesh)

        reg = ShuffleRegistry()
        res = ShuffleService(mesh, registry=reg).exchange(
            batch, pid=pid, round_rows=16)
        assert res.rounds >= 2  # skew forced a multi-round drain
        assert res.rows_moved == n
        assert res.skew_ratio == pytest.approx(float(P8))
        out, occ = _delivered(res)
        assert sorted(out[occ].tolist()) == vals.tolist()
        # every live row sits on device 0's shard
        shard_rows = out.shape[0] // P8
        assert not occ[shard_rows:].any()
        assert reg.metrics.snapshot()["dropped_rows"] == 0

    def test_zipf_pids_with_empty_partitions(self, eight_devices,
                                             small_buckets):
        mesh = data_mesh(P8)
        n = P8 * 128
        rng = np.random.default_rng(3)
        vals = rng.integers(0, 1 << 40, n).astype(np.int64)
        # zipf mass on low partitions, folded into [0, 5): partitions
        # 5..7 receive NOTHING — empty destinations must stay lossless
        pid_np = (np.minimum(rng.zipf(1.5, n), 1 << 20) % 5).astype(np.int32)
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded(pid_np, mesh)

        res = ShuffleService(mesh, registry=ShuffleRegistry()).exchange(
            batch, pid=pid, round_rows=32)
        assert res.rows_moved == n
        out, occ = _delivered(res)
        assert sorted(out[occ].tolist()) == sorted(vals.tolist())
        shard_rows = out.shape[0] // P8
        for d in range(P8):
            sl = slice(d * shard_rows, (d + 1) * shard_rows)
            want = sorted(vals[pid_np == d].tolist())
            assert sorted(out[sl][occ[sl]].tolist()) == want
        assert not occ[5 * shard_rows:].any()  # empty destinations

    def test_oob_pids_counted_when_not_strict(self, eight_devices):
        mesh = data_mesh(P8)
        n = P8 * 16
        vals = np.arange(n, dtype=np.int64)
        pid_np = (vals % P8).astype(np.int32)
        pid_np[::8] = 99
        pid_np[1::8] = -3
        n_oob = int(((pid_np < 0) | (pid_np > P8)).sum())
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded(pid_np, mesh)

        reg = ShuffleRegistry()
        res = ShuffleService(mesh, registry=reg).exchange(
            batch, pid=pid, strict=False)
        assert res.oob_rows == n_oob
        assert res.rows_moved == n - n_oob
        out, occ = _delivered(res)
        in_range = (pid_np >= 0) & (pid_np < P8)
        assert sorted(out[occ].tolist()) == sorted(vals[in_range].tolist())
        snap = reg.metrics.snapshot()
        assert snap["oob_rows"] == n_oob and snap["dropped_rows"] == 0

    def test_oob_pids_raise_when_strict(self, eight_devices):
        mesh = data_mesh(P8)
        n = P8 * 8
        batch = shard_batch(_int_batch(np.arange(n)), mesh)
        pid = _row_sharded(np.full(n, 99, np.int32), mesh)
        with pytest.raises(ShuffleError, match="out-of-range"):
            ShuffleService(mesh, registry=ShuffleRegistry()).exchange(
                batch, pid=pid, strict=True)


# ---------------------------------------------------------------------------
# the legacy data plane under the same adversarial shapes
# ---------------------------------------------------------------------------

class TestLegacyPlaneAdversarial:
    def test_plan_capacity_sizes_all_to_one_losslessly(self, eight_devices):
        from spark_rapids_jni_tpu.parallel import exchange
        from spark_rapids_jni_tpu.parallel.shuffle import plan_capacity

        mesh = data_mesh(P8)
        spec = jax.sharding.PartitionSpec("data")
        n = P8 * 24
        vals = np.arange(n, dtype=np.int64)
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded(np.zeros(n, np.int32), mesh)

        @jax.jit
        @jax.shard_map(mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
        def plan(p):
            return plan_capacity(p, "data", P8)[None]

        cap = int(np.asarray(jax.device_get(plan(pid)))[0])
        assert cap == 24  # every sender's whole shard targets one bucket

        @jax.jit
        @jax.shard_map(mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec, spec), check_vma=False)
        def run(b, p):
            out, occ, dropped = exchange(b, p, "data", P8, capacity=cap)
            return out, occ, dropped[None]

        out, occ, dropped = run(batch, pid)
        assert int(np.asarray(jax.device_get(dropped)).sum()) == 0
        occ = np.asarray(jax.device_get(occ))
        got = np.asarray(jax.device_get(out["v"].data))
        assert sorted(got[occ].tolist()) == vals.tolist()

    def test_exchange_hierarchical_counts_oob_in_dropped(self,
                                                         eight_devices):
        from spark_rapids_jni_tpu.parallel import exchange_hierarchical
        from spark_rapids_jni_tpu.parallel.distributed import (
            hierarchical_mesh,
        )

        mesh = hierarchical_mesh(2, 4)
        spec = jax.sharding.PartitionSpec(("dcn", "ici"))
        n = P8 * 8
        vals = np.arange(n, dtype=np.int64)
        pid_np = (vals % P8).astype(np.int32)
        pid_np[::16] = 99
        pid_np[1::16] = -2
        n_oob = int(((pid_np < 0) | (pid_np > P8)).sum())
        batch = jax.tree_util.tree_map(
            lambda x: jax.device_put(
                x, jax.sharding.NamedSharding(mesh, spec)),
            _int_batch(vals))
        pid = jax.device_put(
            jnp.asarray(pid_np), jax.sharding.NamedSharding(mesh, spec))

        @jax.jit
        @jax.shard_map(mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec, spec), check_vma=False)
        def run(b, p):
            out, occ, dropped = exchange_hierarchical(
                b, p, "dcn", "ici", 2, 4)
            return out, occ, dropped[None]

        out, occ, dropped = run(batch, pid)
        # OOB ids surface as COUNTED drops, not as silent padding
        assert int(np.asarray(jax.device_get(dropped)).sum()) == n_oob
        occ = np.asarray(jax.device_get(occ))
        got = np.asarray(jax.device_get(out["v"].data))
        in_range = (pid_np >= 0) & (pid_np < P8)
        assert sorted(got[occ].tolist()) == sorted(vals[in_range].tolist())


# ---------------------------------------------------------------------------
# out-of-core acceptance: eager buffers exceed the arena, shuffle completes
# ---------------------------------------------------------------------------

class TestOutOfCore:
    def test_skewed_exchange_spills_and_stays_lossless(self, eight_devices,
                                                       tmp_path):
        from spark_rapids_jni_tpu.mem import RmmSpark, TaskContext
        from spark_rapids_jni_tpu.mem import spill as spill_mod

        old_bucket = config.get("shuffle_capacity_bucket")
        config.set("shuffle_capacity_bucket", 256)
        get_registry().reset()
        mesh = data_mesh(P8)
        n = P8 * 4096
        rng = np.random.default_rng(7)
        vals = rng.integers(0, 1 << 40, n).astype(np.int64)
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded(np.zeros(n, np.int32), mesh)

        spill_mod.install(spill_dir=str(tmp_path))
        RmmSpark.set_event_handler(1 << 20, poll_ms=10.0)  # 1 MB arena
        try:
            with TaskContext(77) as ctx:
                res = ShuffleService(mesh).exchange(
                    batch, pid=pid, ctx=ctx, round_rows=512)
                out, occ = _delivered(res)
            RmmSpark.task_done(77)
        finally:
            RmmSpark.clear_event_handler()
            spill_mod.shutdown()
            config.set("shuffle_capacity_bucket", old_bucket)

        # lossless: the received multiset equals the sent multiset
        assert res.rows_moved == n
        assert sorted(out[occ].tolist()) == sorted(vals.tolist())
        summary = RmmSpark.shuffle_metrics()
        assert summary["rounds"] >= 2
        assert summary["spilled_bytes"] > 0  # the arena forced eviction
        assert summary["dropped_rows"] == 0
        assert get_registry().metrics.snapshot() == summary


# ---------------------------------------------------------------------------
# streaming morsel-driven exchange (bit-identical to the materialized path)
# ---------------------------------------------------------------------------

class TestStreamingExchange:
    """``exchange_stream`` must deliver rows BIT-IDENTICALLY to
    ``exchange`` over the same rows — same content, same per-shard
    order — while draining earlier rounds before the stream ends and
    tracing its drain program exactly once."""

    def _kv_batch(self, keys, vals):
        k = np.asarray(keys, np.int64)
        v = np.asarray(vals, np.int64)
        ones = jnp.ones((len(k),), jnp.bool_)
        return ColumnBatch({
            "k": Column(jnp.asarray(k), ones, T.INT64),
            "v": Column(jnp.asarray(v), ones, T.INT64)})

    @staticmethod
    def _rows(res):
        occ = np.asarray(jax.device_get(res.occupancy))
        k = np.asarray(jax.device_get(res.batch["k"].data))
        v = np.asarray(jax.device_get(res.batch["v"].data))
        return k, v, occ

    def _assert_bit_identical(self, mat, stream):
        """Delivered (occupancy-masked) rows equal per destination
        shard, in order.  Shapes may differ only when both paths fit in
        one round (the materialized capacity shrinks to its bucket) —
        the masked sequences still line up row for row."""
        mk, mv, mo = self._rows(mat)
        sk, sv, so = self._rows(stream)
        ra, rb = mk.shape[0] // P8, sk.shape[0] // P8
        for d in range(P8):
            a = slice(d * ra, (d + 1) * ra)
            b = slice(d * rb, (d + 1) * rb)
            assert np.array_equal(mk[a][mo[a]], sk[b][so[b]])
            assert np.array_equal(mv[a][mo[a]], sv[b][so[b]])

    def _run_both(self, keys, vals, round_rows, morsel_rows,
                  extra_morsels=None):
        from spark_rapids_jni_tpu.shuffle import MorselSource

        mesh = data_mesh(P8)
        batch = shard_batch(self._kv_batch(keys, vals), mesh)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        mat = svc.exchange(batch, key_names=["k"], round_rows=round_rows)
        src = MorselSource.from_batch(batch, mesh, morsel_rows=morsel_rows)
        morsels = list(src)
        if extra_morsels:
            for at, m in extra_morsels:
                morsels.insert(at, m)
        res = svc.exchange_stream(morsels, key_names=["k"],
                                  round_rows=round_rows)
        self._assert_bit_identical(mat, res)
        return mat, res

    def test_uniform_multiround_overlaps_decode(self, eight_devices,
                                                small_buckets):
        n = P8 * 512
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 1 << 20, n)
        mat, res = self._run_both(keys, np.arange(n), round_rows=16,
                                  morsel_rows=64)
        assert res.streamed and res.morsels == 8
        assert res.rows_moved == n and mat.rows_moved == n
        assert res.rounds >= 2
        # >= 2 rounds were IN FLIGHT: drained while later morsels were
        # still decoding, not after end-of-stream
        assert res.rounds_overlapped >= 2
        assert res.rounds == mat.rounds and res.capacity == mat.capacity

    def test_all_to_one_skew(self, eight_devices, small_buckets):
        # one constant key: every row hashes to a single destination,
        # the worst skew the planner can see
        n = P8 * 256
        mat, res = self._run_both(np.full(n, 7), np.arange(n),
                                  round_rows=64, morsel_rows=64)
        assert res.rows_moved == n
        assert res.rounds >= 2
        assert res.skew_ratio == pytest.approx(mat.skew_ratio)

    def test_zipf_keys_empty_partitions_and_empty_morsel(
            self, eight_devices, small_buckets):
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = data_mesh(P8)
        n = P8 * 128
        M = 32
        rng = np.random.default_rng(11)
        # zipf mass folded onto 5 distinct keys: several destinations
        # receive nothing at all
        keys = (np.minimum(rng.zipf(1.5, n), 1 << 20) % 5).astype(np.int64)
        sh = NamedSharding(mesh, PartitionSpec("data"))
        zeros = jax.device_put(jnp.zeros((P8 * M,), jnp.int64), sh)
        ones = jax.device_put(jnp.ones((P8 * M,), jnp.bool_), sh)
        empty = (ColumnBatch({"k": Column(zeros, ones, T.INT64),
                              "v": Column(zeros, ones, T.INT64)}),
                 jax.device_put(jnp.zeros((P8 * M,), jnp.bool_), sh))
        _, res = self._run_both(
            keys, np.arange(n), round_rows=32, morsel_rows=M,
            # an all-invalid morsel mid-stream contributes zero rows
            # everywhere and must not disturb accounting or order
            extra_morsels=[(2, lambda: empty)])
        assert res.rows_moved == n
        assert res.morsels == 5  # the empty one still counts as mapped

    def test_drain_program_traces_once(self, eight_devices, small_buckets):
        from spark_rapids_jni_tpu.shuffle.service import \
            _STREAM_DRAIN_TRACES

        n = P8 * 256
        rng = np.random.default_rng(13)
        self._run_both(rng.integers(0, 99, n), np.arange(n),
                       round_rows=16, morsel_rows=64)
        before = _STREAM_DRAIN_TRACES[0]
        # a second stream at the same capacity (fresh data, many
        # morsels, several rounds) must reuse every compiled program
        self._run_both(rng.integers(0, 99, n), np.arange(n) * 3,
                       round_rows=16, morsel_rows=64)
        assert _STREAM_DRAIN_TRACES[0] == before

    def test_out_of_core_stream_spills_and_stays_lossless(
            self, eight_devices, tmp_path):
        from spark_rapids_jni_tpu.mem import RmmSpark, TaskContext
        from spark_rapids_jni_tpu.mem import spill as spill_mod
        from spark_rapids_jni_tpu.shuffle import MorselSource

        old_bucket = config.get("shuffle_capacity_bucket")
        config.set("shuffle_capacity_bucket", 256)
        get_registry().reset()
        mesh = data_mesh(P8)
        n = P8 * 4096
        rng = np.random.default_rng(17)
        batch = shard_batch(
            self._kv_batch(np.full(n, 3), rng.integers(0, 1 << 40, n)),
            mesh)
        spill_mod.install(spill_dir=str(tmp_path))
        RmmSpark.set_event_handler(1 << 20, poll_ms=10.0)  # 1 MB arena
        try:
            with TaskContext(78) as ctx:
                src = MorselSource.from_batch(batch, mesh,
                                              morsel_rows=1024)
                res = ShuffleService(mesh).exchange_stream(
                    src, key_names=["k"], ctx=ctx, round_rows=512)
                k, _, occ = self._rows(res)
            RmmSpark.task_done(78)
        finally:
            RmmSpark.clear_event_handler()
            spill_mod.shutdown()
            config.set("shuffle_capacity_bucket", old_bucket)

        assert res.rows_moved == n
        assert (k[occ] == 3).all() and int(occ.sum()) == n
        summary = RmmSpark.shuffle_metrics()
        assert summary["rounds"] >= 2
        assert summary["spilled_bytes"] > 0  # the arena forced demotion
        assert summary["dropped_rows"] == 0


# ---------------------------------------------------------------------------
# transport fault injection (kind "shuffle_io")
# ---------------------------------------------------------------------------

class TestShuffleIOFaults:
    def _exchange(self, reg):
        mesh = data_mesh(P8)
        n = P8 * 8
        vals = np.arange(n, dtype=np.int64)
        batch = shard_batch(_int_batch(vals), mesh)
        pid = _row_sharded((vals % P8).astype(np.int32), mesh)
        res = ShuffleService(mesh, registry=reg).exchange(batch, pid=pid)
        return vals, res

    def test_round_is_redriven_after_injected_fault(self, eight_devices):
        reg = ShuffleRegistry()
        faultinj.configure({"faults": [{"match": "shuffle_io_round",
                                        "count": 1,
                                        "fault": "shuffle_io"}]})
        try:
            vals, res = self._exchange(reg)
        finally:
            faultinj.configure({})
        assert res.rows_moved == len(vals)
        out, occ = _delivered(res)
        assert sorted(out[occ].tolist()) == vals.tolist()
        assert reg.metrics.snapshot()["io_failures"] == 1

    def test_persistent_fault_raises_after_bounded_retries(self,
                                                           eight_devices):
        from spark_rapids_jni_tpu.shuffle.service import _IO_RETRIES

        reg = ShuffleRegistry()
        faultinj.configure({"faults": [{"match": "shuffle_io_round",
                                        "fault": "shuffle_io"}]})
        try:
            with pytest.raises(faultinj.ShuffleIOError):
                self._exchange(reg)
        finally:
            faultinj.configure({})
        assert reg.metrics.snapshot()["io_failures"] == _IO_RETRIES + 1


# ---------------------------------------------------------------------------
# service-backed distributed operators
# ---------------------------------------------------------------------------

class TestServiceBackedOperators:
    def test_group_by_routes_through_the_service(self, eight_devices):
        from spark_rapids_jni_tpu.parallel import distributed_group_by
        from spark_rapids_jni_tpu.parallel.distributed import collect_groups
        from spark_rapids_jni_tpu.relational import AggSpec

        mesh = data_mesh(P8)
        n = P8 * 32
        rng = np.random.default_rng(9)
        k = rng.integers(0, 6, n).astype(np.int64)
        v = rng.integers(-100, 100, n).astype(np.int64)
        batch = shard_batch(ColumnBatch({
            "k": Column(jnp.asarray(k), jnp.ones((n,), jnp.bool_), T.INT64),
            "v": Column(jnp.asarray(v), jnp.ones((n,), jnp.bool_), T.INT64),
        }), mesh)
        before = get_registry().metrics.snapshot()["shuffles"]
        res, ng, dropped = distributed_group_by(
            batch, ["k"], [AggSpec("sum", "v", "s")], mesh)
        assert int(np.asarray(jax.device_get(dropped)).sum()) == 0
        assert get_registry().metrics.snapshot()["shuffles"] == before + 1
        got = collect_groups(res, ng)
        want = {key: int(v[k == key].sum()) for key in np.unique(k)}
        assert dict(zip(got["k"], got["s"])) == want


# ---------------------------------------------------------------------------
# spillable join build tables (drop on eviction, rebuild on read-back)
# ---------------------------------------------------------------------------

class TestSpillableBuildTable:
    def _sides(self):
        rng = np.random.default_rng(1)
        def mk(keys, vals):
            a = np.asarray(keys, np.int64)
            b = np.asarray(vals, np.int64)
            return ColumnBatch({
                "k": Column(jnp.asarray(a), jnp.ones((len(a),), jnp.bool_),
                            T.INT64),
                "v": Column(jnp.asarray(b), jnp.ones((len(b),), jnp.bool_),
                            T.INT64),
            })
        left = mk(rng.integers(0, 40, 160), np.arange(160))
        right = mk(rng.integers(0, 40, 64), np.arange(64) + 1000)
        return left, right

    @staticmethod
    def _rows(batch, count):
        m = int(count)
        return sorted(zip(
            np.asarray(batch["k"].data)[:m].tolist(),
            np.asarray(batch["v"].data)[:m].tolist(),
            np.asarray(batch["v_r"].data)[:m].tolist()))

    def test_eviction_drops_and_get_rebuilds(self, tmp_path):
        from spark_rapids_jni_tpu.mem import spill as spill_mod
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            spillable_build_table,
        )

        left, right = self._sides()
        ref, nref = hash_join(left, right, ["k"], ["k"], "inner",
                              capacity=1024)
        fw = spill_mod.install(spill_dir=str(tmp_path))
        try:
            bt = spillable_build_table(right, ["k"])
            got, ngot = hash_join(left, right, ["k"], ["k"], "inner",
                                  capacity=1024, prebuilt=bt)
            assert self._rows(got, ngot) == self._rows(ref, nref)
            assert bt.tier == "device" and bt.rebuilds == 0

            fw.spill_to_fit()  # arena pressure: the build table is dropped
            assert bt.tier == "dropped"

            got2, n2 = hash_join(left, right, ["k"], ["k"], "inner",
                                 capacity=1024, prebuilt=bt)
            assert self._rows(got2, n2) == self._rows(ref, nref)
            assert bt.rebuilds == 1
            bt.close()
            assert bt.tier == "closed"
        finally:
            spill_mod.shutdown()

    def test_prebuilt_full_join_matches(self):
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            spillable_build_table,
        )

        left, right = self._sides()
        ref, nref = hash_join(left, right, ["k"], ["k"], "full",
                              capacity=1024)
        bt = spillable_build_table(right, ["k"])
        got, ngot = hash_join(left, right, ["k"], ["k"], "full",
                              capacity=1024, prebuilt=bt)
        bt.close()
        assert int(nref) == int(ngot)

    def test_guard_rails(self):
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            spillable_build_table,
        )

        left, right = self._sides()
        empty = ColumnBatch({
            "k": Column(jnp.zeros((0,), jnp.int64),
                        jnp.zeros((0,), jnp.bool_), T.INT64)})
        with pytest.raises(ValueError, match="empty build side"):
            spillable_build_table(empty, ["k"])
        bt = spillable_build_table(right, ["k"])
        with pytest.raises(ValueError, match="right"):
            hash_join(left, right, ["k"], ["k"], "right", prebuilt=bt)
        bt.close()
