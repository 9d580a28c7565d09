"""Plan IR + whole-query compiler (spark_rapids_jni_tpu/plan/).

The acceptance bars from the PR 7 issue, as tests:

* q6 and q95 expressed as pure IR are BIT-identical to the hand-fused
  ``_q6_step``/``_q95_step`` paths — plain AND encoded inputs, under
  both engine knob settings (the compiler's lowering rules ARE the
  hand paths, factored);
* a q9-shaped query exists ONLY as IR (no hand-fused ``_q9_step``
  anywhere) and still runs correctly, with the adaptive layer deciding
  broadcast joins from the observed dim sizes;
* a repeated plan shape is a cache hit that replays the already-traced
  program with ZERO retraces (``trace_count``), and any knob flip or
  shape change misses by construction;
* the adaptive decisions are pure functions over stats snapshots;
* a broadcast build table pinned to a plan-time engine rebuilds after
  eviction under that SAME engine even when the ``join_engine`` knob
  changed in between.
"""

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, plan
from spark_rapids_jni_tpu.plan import queries


# ---------------------------------------------------------------------------
# helpers / fixtures
# ---------------------------------------------------------------------------

def assert_bit_identical(got, want):
    """Same pytree structure, same leaf dtypes/shapes, same BYTES."""
    g_leaves, g_def = jax.tree_util.tree_flatten(got)
    w_leaves, w_def = jax.tree_util.tree_flatten(want)
    assert g_def == w_def
    for g, w in zip(g_leaves, w_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.fixture(autouse=True)
def fresh_plan_cache():
    plan.reset_plan_cache()
    yield
    plan.reset_plan_cache()


@pytest.fixture
def knob():
    """Targeted knob setter: every touched key is reset (individually —
    never a blanket reset, which would undo conftest's session knobs)."""
    touched = []

    def set_knob(key, value):
        touched.append(key)
        config.set(key, value)

    yield set_knob
    for key in touched:
        config.reset(key)


# ---------------------------------------------------------------------------
# q6 as IR: bit-parity with the hand-fused step
# ---------------------------------------------------------------------------

class TestQ6Parity:
    @pytest.mark.parametrize("path,engine", [
        ("onehot", None),          # the domain/MXU path, default knobs
        ("sort", "sort"),          # general group_by, sort engine
        ("sort", "scatter"),       # general group_by, scatter engine
    ])
    def test_int_key_parity(self, knob, path, engine):
        import __graft_entry__ as ge

        knob("q6_group_path", path)
        if engine is not None:
            knob("groupby_engine", engine)
        batch = ge._device_batch(0, 4096)
        want = ge._q6_step(batch)
        got = plan.execute(queries.q6_plan(), {"batch": batch})
        assert_bit_identical(got, want)

    @pytest.mark.parametrize("engine", ["sort", "scatter"])
    def test_string_key_parity(self, knob, engine):
        # the domain/onehot hints only engage for a plain int key: on the
        # string-keyed batch the SAME plan runs the general engine path
        import __graft_entry__ as ge

        knob("groupby_engine", engine)
        batch = ge._q6str_batch(2048)
        want = ge._q6str_step(batch)
        got = plan.execute(queries.q6_plan(), {"batch": batch})
        assert_bit_identical(got, want)

    @pytest.mark.parametrize("engine", ["sort", "scatter"])
    def test_encoded_parity(self, knob, engine):
        # dictionary-encoded key: the filter pushes onto codes and the
        # group-by keys on codes — same plan object, encoded lowering
        import __graft_entry__ as ge

        knob("groupby_engine", engine)
        batch = ge._q6str_batch(2048, encoded=True)
        want = ge._q6str_step(batch)
        got = plan.execute(queries.q6_plan(), {"batch": batch})
        assert_bit_identical(got, want)


# ---------------------------------------------------------------------------
# q95 as IR: bit-parity with the hand-fused pipeline
# ---------------------------------------------------------------------------

class TestQ95Parity:
    @pytest.mark.parametrize("join_engine,groupby_engine", [
        ("hash", "sort"),     # exchange+agg FUSES (secondary sort operands)
        ("sort", "sort"),
        ("hash", "scatter"),  # exchange before the agg is ELIDED
        ("sort", "scatter"),
    ])
    def test_plain_parity(self, knob, join_engine, groupby_engine):
        import __graft_entry__ as ge

        knob("join_engine", join_engine)
        knob("groupby_engine", groupby_engine)
        fact, dim1, dim2 = ge._q95_batches(4096)
        want = ge._q95_step(fact, dim1, dim2)
        got = plan.execute(queries.q95_plan(),
                           {"fact": fact, "dim1": dim1, "dim2": dim2})
        assert_bit_identical(got, want)

    @pytest.mark.parametrize("join_engine", ["hash", "sort"])
    def test_encoded_parity(self, knob, join_engine):
        # encoded wh/seg: joins ride the general hash_join (no rowid
        # fast path on codes) and the final group-by keys on seg codes
        import __graft_entry__ as ge

        knob("join_engine", join_engine)
        fact, dim1, dim2 = ge._q95_encoded_batches(4096)
        want = ge._q95_encoded_step(fact, dim1, dim2)
        got = plan.execute(queries.q95_plan(),
                           {"fact": fact, "dim1": dim1, "dim2": dim2})
        assert_bit_identical(got, want)


# ---------------------------------------------------------------------------
# q9: a new query that exists ONLY as IR
# ---------------------------------------------------------------------------

class TestQ9:
    def test_no_hand_fused_step_exists(self):
        import __graft_entry__ as ge

        assert not hasattr(ge, "_q9_step")

    def test_adaptive_broadcast_and_correctness(self):
        import __graft_entry__ as ge

        fact, dim1, dim2 = ge._q95_batches(4096)
        inputs = {"fact": fact, "dim1": dim1, "dim2": dim2}
        cp = plan.compile_plan(queries.q9_plan(), inputs)
        try:
            # both dims sit far under broadcast_threshold_rows, so the
            # strategy='auto' joins resolve to broadcast with the CPU
            # ('hash') engine pinned into the prebuilt build tables
            d0 = cp.decisions["join0:k"]
            d1 = cp.decisions["join1:wh"]
            assert d0["strategy"] == "broadcast"
            assert d0["build_rows"] == dim1.num_rows
            assert d1["strategy"] == "broadcast"
            assert d1["build_rows"] == dim2.num_rows
            assert len(cp.build_handles) == 2

            res, ng = cp(inputs)
            ng = int(ng)

            # cross-check against a from-scratch numpy evaluation: the
            # dims' arange keys always match, so q9 reduces to a
            # conditional (v >= threshold) group-by over fact
            seg = np.asarray(fact["seg"].data)
            v = np.asarray(fact["v"].data)
            hi = v >= queries.Q9_V_THRESHOLD
            want = {s: (int(v[hi & (seg == s)].sum()),
                        int(np.count_nonzero(hi & (seg == s))))
                    for s in np.unique(seg[hi])}
            assert ng == len(want)

            out_seg = np.asarray(res["seg"].data)[:ng]
            out_net = np.asarray(res["net_hi"].data)[:ng]
            out_cnt = np.asarray(res["orders_hi"].data)[:ng]
            out_avg = np.asarray(res["avg_hi"].data)[:ng]
            got = {int(s): (int(n), int(c))
                   for s, n, c in zip(out_seg, out_net, out_cnt)}
            assert got == want
            for s, n, c in zip(out_seg, out_net, out_cnt):
                assert np.isclose(out_avg[list(out_seg).index(s)],
                                  n / c)
        finally:
            cp.close()


# ---------------------------------------------------------------------------
# plan cache lifecycle
# ---------------------------------------------------------------------------

class TestPlanCache:
    def test_repeated_shape_hits_with_zero_retraces(self):
        import __graft_entry__ as ge

        b1 = ge._device_batch(0, 1024)
        r1 = plan.execute(queries.q6_plan(), {"batch": b1})
        t0 = plan.trace_count()
        assert plan.plan_cache_metrics()["misses"] >= 1

        # a FRESH plan object with the same shape and a same-shape batch
        # with different data: hit, and the traced program replays
        b2 = ge._device_batch(1, 1024)
        cp = plan.compile_plan(queries.q6_plan(), {"batch": b2})
        assert cp.last_lookup == "hit"
        r2 = cp({"batch": b2})
        assert plan.trace_count() == t0  # ZERO retraces
        assert plan.plan_cache_metrics()["hits"] >= 1

        # and the replayed program computes the RIGHT thing for the new
        # data, not a stale replay of the first batch's answer
        assert int(r1[1]) == 100
        assert_bit_identical(r2, ge._q6_step(b2))

    def test_knob_flip_is_a_miss(self, knob):
        import __graft_entry__ as ge

        b = ge._device_batch(0, 1024)
        plan.execute(queries.q6_plan(), {"batch": b})
        knob("groupby_engine", "sort")
        cp = plan.compile_plan(queries.q6_plan(), {"batch": b})
        assert cp.last_lookup == "miss"

    def test_shape_change_is_a_miss(self):
        import __graft_entry__ as ge

        plan.execute(queries.q6_plan(), {"batch": ge._device_batch(0, 1024)})
        cp = plan.compile_plan(queries.q6_plan(),
                               {"batch": ge._device_batch(0, 2048)})
        assert cp.last_lookup == "miss"

    def test_lru_eviction_under_shrunk_capacity(self, knob):
        import __graft_entry__ as ge

        knob("plan_cache_size", 1)
        b1 = ge._device_batch(0, 1024)
        b2 = ge._device_batch(0, 2048)
        plan.execute(queries.q6_plan(), {"batch": b1})
        plan.execute(queries.q6_plan(), {"batch": b2})  # evicts the first
        m = plan.plan_cache_metrics()
        assert m["evictions"] >= 1 and m["size"] == 1 and m["capacity"] == 1
        cp = plan.compile_plan(queries.q6_plan(), {"batch": b1})
        assert cp.last_lookup == "miss"  # the evicted shape re-compiles


# ---------------------------------------------------------------------------
# adaptive decisions: pure functions over stats snapshots
# ---------------------------------------------------------------------------

class TestAdaptive:
    def test_join_strategy_threshold_boundary(self, knob):
        assert plan.choose_join_strategy(100, threshold=100) == "broadcast"
        assert plan.choose_join_strategy(101, threshold=100) == "shuffled"
        knob("broadcast_threshold_rows", 50)
        assert plan.choose_join_strategy(50) == "broadcast"
        assert plan.choose_join_strategy(51) == "shuffled"

    def test_adaptive_off_means_static_defaults(self, knob):
        knob("adaptive_execution", False)
        assert plan.choose_join_strategy(1) == "shuffled"
        assert plan.choose_groupby_engine(counts=[1000, 0, 0, 0]) is None
        assert plan.choose_exchange_capacity(counts=[1000, 0, 0, 0]) is None

    def test_groupby_engine_from_skewed_counts(self):
        # max/mean == 4.0 exactly: the SKEW_SORT_RATIO boundary fires
        assert plan.choose_groupby_engine(counts=[1000, 0, 0, 0]) == "sort"
        assert plan.choose_groupby_engine(counts=[10, 10, 10, 10]) is None

    def test_groupby_engine_from_agg_dominant_stages(self):
        # agg > half the total: the platform engine is resolved and
        # RECORDED (scatter on the CPU tests run under)
        hint = plan.choose_groupby_engine(
            stages_ms={"exch1": 1.0, "join1": 1.0, "agg": 6.0})
        assert hint == "scatter"
        assert plan.choose_groupby_engine(
            stages_ms={"exch1": 5.0, "join1": 5.0, "agg": 2.0}) is None

    def test_exchange_capacity_from_counts_and_metrics(self):
        rp = plan.choose_exchange_capacity(counts=[4096, 64, 64, 64])
        assert rp is not None and rp.capacity >= 1 and rp.rounds >= 1

        rp2 = plan.choose_exchange_capacity(
            metrics={"shuffles": 2, "rows_moved": 1 << 16, "max_skew": 4.0},
            partitions=8)
        assert rp2 is not None and rp2.capacity >= 1

        assert plan.choose_exchange_capacity() is None  # no signal

    def test_plan_decisions_walk_keys(self, knob):
        import __graft_entry__ as ge

        fact, dim1, dim2 = ge._q95_batches(1024)
        inputs = {"fact": fact, "dim1": dim1, "dim2": dim2}
        d = plan.plan_decisions(queries.q9_plan(), inputs)
        assert d["adaptive"] is True
        assert d["join0:k"]["strategy"] == "broadcast"
        assert d["join1:wh"]["strategy"] == "broadcast"

        knob("adaptive_execution", False)
        d_off = plan.plan_decisions(queries.q9_plan(), inputs)
        assert d_off["adaptive"] is False
        assert d_off["join0:k"]["strategy"] == "shuffled"
        assert d_off["join1:wh"]["strategy"] == "shuffled"

        # a decisions delta alone changes the cache key
        assert (plan.compile.plan_cache_key(queries.q9_plan(), inputs, d)
                != plan.compile.plan_cache_key(queries.q9_plan(), inputs,
                                               d_off))


# ---------------------------------------------------------------------------
# broadcast build tables: engine pinning across eviction-driven rebuilds
# ---------------------------------------------------------------------------

class TestBuildTablePinning:
    def _right(self):
        import __graft_entry__ as ge

        _fact, dim1, _dim2 = ge._q95_batches(512)
        return dim1

    def test_pinned_engine_survives_knob_flip(self, knob, tmp_path):
        from spark_rapids_jni_tpu.mem import spill as spill_mod
        from spark_rapids_jni_tpu.relational import spillable_build_table

        spill_mod.install(spill_dir=str(tmp_path))
        try:
            bt = spillable_build_table(self._right(), ["k"], engine="sort")
            assert bt.engine == "sort" and bt.tier == "device"
            knob("join_engine", "hash")
            bt.spill()  # drop the derived tree (no ctx: frees no charge)
            assert bt.tier == "dropped"
            bt.get()  # eviction-driven rebuild
            assert bt.rebuilds == 1
            assert bt.engine == "sort"  # PINNED: the knob flip is ignored
            bt.close()
        finally:
            spill_mod.shutdown()

    def test_unpinned_table_follows_the_knob(self, knob, tmp_path):
        from spark_rapids_jni_tpu.mem import spill as spill_mod
        from spark_rapids_jni_tpu.relational import spillable_build_table

        spill_mod.install(spill_dir=str(tmp_path))
        try:
            knob("join_engine", "sort")
            bt = spillable_build_table(self._right(), ["k"])
            assert bt.engine == "sort"
            knob("join_engine", "hash")
            bt.spill()
            bt.get()
            assert bt.engine == "hash"  # unpinned: re-read at rebuild
            bt.close()
        finally:
            spill_mod.shutdown()

    def test_broadcast_build_handle_registers_under_ctx(self, tmp_path):
        from spark_rapids_jni_tpu.mem import RmmSpark, TaskContext
        from spark_rapids_jni_tpu.mem import spill as spill_mod
        from spark_rapids_jni_tpu.parallel import broadcast_build_handle

        right = self._right()
        spill_mod.install(spill_dir=str(tmp_path))
        RmmSpark.set_event_handler(32 << 20, poll_ms=10.0)
        try:
            with TaskContext(31) as ctx:
                h = broadcast_build_handle(right, ctx=ctx)
                assert h.task_id == 31
                with h.pinned():
                    got = h.get()
                assert_bit_identical(got, right)
                h.close()
            RmmSpark.task_done(31)
        finally:
            RmmSpark.clear_event_handler()
            spill_mod.shutdown()

    def test_compiled_q9_probes_survive_eviction(self, tmp_path):
        """End to end: the q9 broadcast builds registered by the compiler
        are dropped under pressure and the NEXT execution still matches —
        the pinned-engine rebuild feeds the same traced program."""
        import __graft_entry__ as ge
        from spark_rapids_jni_tpu.mem import spill as spill_mod

        fact, dim1, dim2 = ge._q95_batches(2048)
        inputs = {"fact": fact, "dim1": dim1, "dim2": dim2}
        spill_mod.install(spill_dir=str(tmp_path))
        try:
            cp = plan.compile_plan(queries.q9_plan(), inputs)
            res1, ng1 = cp(inputs)
            for h in cp.build_handles:
                h.spill()
                assert h.tier == "dropped"
            res2, ng2 = cp(inputs)
            assert all(h.rebuilds == 1 for h in cp.build_handles)
            assert_bit_identical((res1, ng1), (res2, ng2))
            cp.close()
        finally:
            spill_mod.shutdown()


# ---------------------------------------------------------------------------
# a dense inner join hands on a row mask where its consumer takes one
# ---------------------------------------------------------------------------

_ND, _NW, _NSEG = 96, 25, 10


def _holey_star(n=512, seed=5):
    """A q95-shaped star whose joins leave dead rows behind: a third of the
    fact's ``k`` lies past ``dim1``, three of its 28 ``wh`` values past
    ``dim2``, and a few keys are null."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.columnar import types as T
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    rng = np.random.default_rng(seed)

    def col(a, t, valid=None):
        v = np.ones(len(a), bool) if valid is None else valid
        return Column(jnp.asarray(a), jnp.asarray(v), t)

    fact = ColumnBatch({
        "k": col(rng.integers(0, _ND * 3 // 2, n).astype(np.int32), T.INT32,
                 np.arange(n) % 17 != 3),
        "wh": col(rng.integers(0, _NW + 3, n).astype(np.int32), T.INT32,
                  np.arange(n) % 23 != 5),
        "seg": col(rng.integers(0, _NSEG, n).astype(np.int32), T.INT32),
        "v": col(rng.integers(1, 500, n), T.INT64)})
    dim1 = ColumnBatch({
        "k": col(np.arange(_ND, dtype=np.int32), T.INT32),
        "d1": col(rng.integers(0, 9, _ND), T.INT64)})
    dim2 = ColumnBatch({
        "wh": col(np.arange(_NW, dtype=np.int32), T.INT32),
        "d2": col(rng.integers(0, 9, _NW), T.INT64)})
    # half of dim1's keys, for a join on the build side of another
    dimx = ColumnBatch({
        "k": col(np.arange(_ND // 2, dtype=np.int32), T.INT32),
        "dx": col(rng.integers(0, 9, _ND // 2), T.INT64)})
    return {"fact": fact, "dim1": dim1, "dim2": dim2, "dimx": dimx}


def _np_fact(inputs):
    f = inputs["fact"]
    c = {n: np.asarray(f[n].data) for n in f.names}
    in1 = np.asarray(f["k"].validity) & (c["k"] < _ND)
    in2 = np.asarray(f["wh"].validity) & (c["wh"] < _NW)
    return c, in1, in2


def _np_groups(inputs, keep):
    """seg -> (count, sum(v)) over the fact rows under ``keep``."""
    c, _, _ = _np_fact(inputs)
    return {int(s): (int(np.count_nonzero(keep & (c["seg"] == s))),
                     int(c["v"][keep & (c["seg"] == s)].sum()))
            for s in np.unique(c["seg"][keep])}


def _got_groups(res, ng):
    ng = int(ng)
    return {int(s): (int(o), int(v)) for s, o, v in zip(
        np.asarray(res["seg"].data)[:ng], np.asarray(res["orders"].data)[:ng],
        np.asarray(res["net"].data)[:ng])}


def _masked_plan(name):
    """``(plan, each join's output form in walk order, which fact rows the
    numpy reference keeps)`` for one consumer of a dense inner join."""
    from spark_rapids_jni_tpu.plan.ir import (Agg, Aggregate, Exchange, Filter,
                                              Join, Project, Scan, Sort)

    def j1(child):
        return Join(child, Scan("dim1"), "k", "k", dense_domain="build")

    def j2(child):
        return Join(child, Scan("dim2"), "wh", "wh", dense_domain="build")

    def agg(child, **kw):
        return Aggregate(child, keys=("seg",),
                         aggs=(Agg("count", None, "orders"),
                               Agg("sum", "v", "net")), **kw)

    fact = Scan("fact")
    both = lambda c, in1, in2: in1 & in2   # noqa: E731
    if name == "exchanges_and_fused_aggregate":     # q95_plan itself
        return (agg(Exchange(j2(Exchange(j1(Exchange(fact, "k")), "wh")),
                             "seg"), domain=_NSEG),
                ["mask", "mask"], both)
    if name == "exchange_not_fused":    # regroups on another key
        return (agg(Exchange(j2(j1(fact)), "wh")), ["mask", "mask"], both)
    first = lambda c, in1, in2: in1        # noqa: E731
    if name == "aggregate_general":
        return agg(j1(fact)), ["mask"], first
    if name == "aggregate_domain":
        return agg(j1(fact), domain=_NSEG), ["mask"], first
    if name == "aggregate_onehot":
        return agg(j1(fact), domain=_NSEG, onehot=True), ["mask"], first
    if name == "filter":
        return (agg(Filter(j2(Filter(j1(fact), "d1", "<", 7)), "v", ">=",
                           250), domain=_NSEG),
                ["mask", "mask"], None)
    if name == "project_hands_the_question_down":
        return (agg(Project(j2(Exchange(Project(
            j1(fact), ("wh", "seg", "v")), "wh")), ("seg", "v"))),
            ["mask", "mask"], both)
    if name == "second_join":           # the left child of a Join
        return agg(j2(j1(fact)), domain=_NSEG), ["mask", "mask"], both
    if name == "root_join":
        return j1(Exchange(fact, "k")), ["compact"], None
    if name == "sort_over_join":
        return Sort(j1(fact), ("v", "seg")), ["compact"], None
    if name == "project_under_the_root":
        return Project(j1(fact), ("k", "v", "d1")), ["compact"], None
    if name == "build_side_join":
        # a Join's right child hands on a mask too (PR 35): the build
        # takes ``right_valid`` as the probe takes ``left_valid``
        right = Join(Scan("dim1"), Scan("dimx"), "k", "k",
                     dense_domain="build")
        return (agg(Join(fact, right, "k", "k", dense_domain=_ND)),
                ["mask", "mask"], lambda c, in1, in2: in1 & (
                    c["k"] < _ND // 2))
    raise KeyError(name)


class TestMaskedJoins:
    """An inner join over a dense domain leaves its left rows where they
    are and hands on ``match`` as the row mask when what consumes it takes
    a scattered mask; the answers are the numpy reference's over data on
    which a third of the rows do NOT match."""

    AGGREGATED = ["exchanges_and_fused_aggregate", "exchange_not_fused",
                  "aggregate_general", "aggregate_domain",
                  "aggregate_onehot", "project_hands_the_question_down",
                  "second_join", "build_side_join"]

    def _run(self, name, inputs):
        the_plan, forms, keep = _masked_plan(name)
        inputs = {n: inputs[n] for n in plan.ir.scan_names(the_plan)}
        cp = plan.compile_plan(the_plan, inputs)
        out = cp(inputs)
        got = [d["output"] for k, d in sorted(cp.decisions.items())
               if k.startswith("join")]
        assert got == forms, cp.decisions
        m = plan.plan_cache_metrics()
        assert (m["joins_masked"], m["joins_compacted"]) == (
            forms.count("mask"), forms.count("compact"))
        return out, keep

    @pytest.mark.parametrize("join_engine,groupby_engine", [
        ("sort", "sort"), ("hash", "scatter")])
    @pytest.mark.parametrize("name", AGGREGATED)
    def test_aggregates_equal_numpy(self, knob, name, join_engine,
                                    groupby_engine):
        knob("join_engine", join_engine)
        knob("groupby_engine", groupby_engine)
        inputs = _holey_star()
        (res, ng), keep = self._run(name, inputs)
        want = _np_groups(inputs, keep(*_np_fact(inputs)))
        assert len(want) == _NSEG and _got_groups(res, ng) == want

    def test_filters_over_masked_joins_equal_numpy(self):
        inputs = _holey_star()
        (res, ng), _ = self._run("filter", inputs)
        c, in1, in2 = _np_fact(inputs)
        d1 = np.asarray(inputs["dim1"]["d1"].data)
        keep = in1 & in2 & (c["v"] >= 250)
        keep &= d1[np.where(in1, c["k"], 0)] < 7
        assert _got_groups(res, ng) == _np_groups(inputs, keep)

    def test_the_chips_engine_choice_traced(self, monkeypatch):
        """``auto`` asks ``jax.default_backend()``: answered as the chip
        does, the q95 shape takes the sort engines and the fused regroup,
        the form the benchmark's cell runs."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        inputs = _holey_star()
        (res, ng), keep = self._run("exchanges_and_fused_aggregate", inputs)
        assert _got_groups(res, ng) == _np_groups(
            inputs, keep(*_np_fact(inputs)))

    @staticmethod
    def _rows(batch, live):
        cols = [batch[n].to_pylist() for n in batch.names]
        return sorted((tuple(c[i] for c in cols)
                       for i in np.flatnonzero(np.asarray(live))), key=repr)

    @pytest.mark.parametrize("name", ["root_join", "sort_over_join",
                                      "project_under_the_root"])
    def test_a_join_nobody_takes_a_mask_from_compacts(self, name):
        inputs = _holey_star()
        (out, live), _ = self._run(name, inputs)
        c, in1, _ = _np_fact(inputs)
        live = np.asarray(live)
        n_live = int(np.count_nonzero(in1))
        # the matches are in front
        assert np.array_equal(live, np.arange(live.size) < n_live)
        d1 = np.asarray(inputs["dim1"]["d1"].data)
        rows = np.flatnonzero(in1)
        whv = np.asarray(inputs["fact"]["wh"].validity)
        if name == "project_under_the_root":
            want = [(int(c["k"][i]), int(c["v"][i]), int(d1[c["k"][i]]))
                    for i in rows]
        else:
            want = [(int(c["k"][i]), int(c["wh"][i]) if whv[i] else None,
                     int(c["seg"][i]), int(c["v"][i]), int(d1[c["k"][i]]))
                    for i in rows]
        assert self._rows(out, live) == sorted(want, key=repr)
        if name == "sort_over_join":
            vs = np.asarray(out["v"].data)[:n_live]
            assert np.all(vs[:-1] <= vs[1:])

    def test_a_broadcast_join_compacts(self):
        inputs = _holey_star()
        inputs = {n: inputs[n] for n in ("fact", "dim1", "dim2")}
        cp = plan.compile_plan(queries.q9_plan(), inputs)
        try:
            res, ng = cp(inputs)
            forms = [cp.decisions[k]["output"]
                     for k in ("join0:k", "join1:wh")]
            assert forms == ["compact", "compact"]
            m = plan.plan_cache_metrics()
            assert (m["joins_masked"], m["joins_compacted"]) == (0, 2)
            c, in1, in2 = _np_fact(inputs)
            keep = in1 & in2 & (c["v"] >= queries.Q9_V_THRESHOLD)
            want = _np_groups(inputs, keep)
            ng = int(ng)
            got = {int(s): (int(o), int(v)) for s, o, v in zip(
                np.asarray(res["seg"].data)[:ng],
                np.asarray(res["orders_hi"].data)[:ng],
                np.asarray(res["net_hi"].data)[:ng])}
            assert got == want
        finally:
            cp.close()


# ---------------------------------------------------------------------------
# the sort engine's head in a plan: the decision and the counter
# ---------------------------------------------------------------------------

class TestAggregateHeads:
    """An aggregate that the sort engine may run says in its decision the
    group slots at which it fetches its result (the head and the widths
    past it, short of every row), and
    ``plan_cache_metrics()["agg_rowwide_gathers"]`` the gathers of one
    index a row that the newest plan traced makes whatever the data
    holds."""

    ROWS = 1 << 13   # more than the head holds, so a row-wide gather counts

    @pytest.mark.parametrize("name,engine,want", [
        # the benchmark's q95 as the chip runs it: grouped rows read in
        # place, ten groups fetched at the head
        ("q95", "auto", 0),
        # q9's aggregate has a domain and takes the segment sums; the
        # sort engine is its fallback and fetches at the head too, but has
        # to move the column that sum(v) and avg(v) read through its sort
        # (data and validity, once for both)
        ("q9", "auto", 2),
        # no domain, the sort engine pinned: the same move for one sum
        ("general", "sort", 2),
        ("general", "scatter", 2),   # ... as the scatter engine's fallback
    ])
    def test_counter_and_decision(self, knob, monkeypatch, name, engine,
                                  want):
        import __graft_entry__ as ge

        from spark_rapids_jni_tpu.plan.ir import Agg, Aggregate, Scan

        fact, dim1, dim2 = ge._q95_batches(self.ROWS, seed=3)
        inputs = {"fact": fact, "dim1": dim1, "dim2": dim2}
        if name == "q95":
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            the_plan = queries.q95_plan()
        elif name == "q9":
            the_plan = queries.q9_plan()
        else:
            knob("groupby_engine", engine)
            the_plan = Aggregate(Scan("fact"), keys=("seg",),
                                 aggs=(Agg("sum", "v", "net"),))
            inputs = {"fact": fact}
        cp = plan.compile_plan(the_plan, inputs)
        try:
            res, ng = cp(inputs)
            assert cp.decisions["aggregate0:seg"]["head"] == 4096
            assert cp.decisions["aggregate0:seg"]["tiers"] == (4096,)
            m = plan.plan_cache_metrics()
            assert m["agg_rowwide_gathers"] == want
            assert 0 < int(ng) <= 10
            # a second lookup hits, traces nothing and leaves the counter
            t0 = plan.trace_count()
            again = plan.compile_plan(the_plan, inputs)
            assert again is cp and again.last_lookup == "hit"
            again(inputs)
            assert plan.trace_count() == t0
            assert plan.plan_cache_metrics()["agg_rowwide_gathers"] == want
        finally:
            cp.close()

    def test_a_onehot_aggregate_says_nothing(self):
        import __graft_entry__ as ge

        b = ge._device_batch(0, self.ROWS)
        cp = plan.compile_plan(queries.q6_plan(), {"batch": b})
        cp({"batch": b})
        assert not [k for k in cp.decisions if k.startswith("aggregate")]
        assert plan.plan_cache_metrics()["agg_rowwide_gathers"] == 0

    def test_the_head_follows_the_rows(self):
        """Under 4096 rows the head is every row, and the decision says
        so; above, 4096 and each wider fetch that is short of every row."""
        import __graft_entry__ as ge

        for rows, head, tiers in ((1 << 9, 1 << 9, ()), (1 << 12, 4096, ()),
                                  (1 << 13, 4096, (4096,)),
                                  (1 << 17, 4096, (4096, 65536))):
            fact, dim1, dim2 = ge._q95_batches(rows, seed=3)
            cp = plan.compile_plan(
                queries.q95_plan(),
                {"fact": fact, "dim1": dim1, "dim2": dim2})
            assert cp.decisions["aggregate0:seg"] == {"head": head,
                                                      "tiers": tiers}
            cp.close()
