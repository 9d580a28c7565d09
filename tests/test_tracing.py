"""The one tracer inside the program (``profiler.span`` / ``note`` /
``scope``): host spans under a query id in a bounded ring, named scopes on
the lowered plan, the per-query timeline across the process boundary, and the
converter that reads all of it back."""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu import config, plan, profiler
from spark_rapids_jni_tpu.plan import queries


def _since():
    return time.perf_counter_ns()


# ---------------------------------------------------------------------------
# the span primitive
# ---------------------------------------------------------------------------

class TestSpans:
    def test_spans_nest_and_carry_sid_and_parent(self):
        t = _since()
        with profiler.span("test.outer", sid=41, rec="x") as outer:
            with profiler.span("test.inner") as inner:
                with profiler.span("test.leaf", sid=42):
                    pass
        got = {s.name: s for s in profiler.spans(t)}
        assert got["test.outer"].parent is None
        assert got["test.inner"].parent == "test.outer"
        assert got["test.leaf"].parent == "test.inner"
        # a span without a sid takes its parent's; one given its own keeps it
        assert got["test.outer"].sid == 41
        assert got["test.inner"].sid == 41
        assert got["test.leaf"].sid == 42
        # intervals nest on the one clock, and sp.ms is the duration
        o, i = got["test.outer"], got["test.inner"]
        assert o.t0_ns <= i.t0_ns <= i.t1_ns <= o.t1_ns
        assert outer.ms == pytest.approx((o.t1_ns - o.t0_ns) / 1e6)
        assert 0.0 <= inner.ms <= outer.ms

    def test_parent_stack_is_per_thread_and_survives_an_exception(self):
        import threading

        t = _since()
        seen = []

        def other():
            with profiler.span("test.other_thread"):
                pass
            seen.append(1)

        with pytest.raises(KeyError):
            with profiler.span("test.raises", sid=7):
                th = threading.Thread(target=other)
                th.start()
                th.join()
                raise KeyError("boom")
        with profiler.span("test.after"):
            pass
        got = {s.name: s for s in profiler.spans(t)}
        assert seen and got["test.other_thread"].parent is None
        assert got["test.other_thread"].sid is None
        assert got["test.raises"].sid == 7       # recorded all the same
        assert got["test.after"].parent is None  # the stack was unwound

    def test_ring_is_bounded(self):
        for i in range(profiler.RING_SPANS + 50):
            with profiler.span("test.flood", sid=i):
                pass
        ring = profiler.spans()
        assert len(ring) == profiler.RING_SPANS
        # oldest first, the newest kept
        assert ring[-1].name == "test.flood"
        assert ring[-1].sid == profiler.RING_SPANS + 49
        assert all(a.t0_ns <= b.t0_ns for a, b in zip(ring, ring[1:]))
        # since_ns cuts a window out of it
        t = _since()
        with profiler.span("test.last"):
            pass
        assert [s.name for s in profiler.spans(t)] == ["test.last"]

    def test_stage_totals_add_up(self):
        before = profiler.stage_totals().get("test.total", {
            "count": 0, "sum_ms": 0.0, "max_ms": 0.0})
        t = _since()
        for _ in range(3):
            with profiler.span("test.total"):
                time.sleep(0.002)
        ms = profiler.note("test.total", 9, 1_000, 9_001_000)  # 9 ms
        assert ms == pytest.approx(9.0)
        after = profiler.stage_totals()["test.total"]
        assert after["count"] == before["count"] + 4
        mine = [(s.t1_ns - s.t0_ns) / 1e6 for s in profiler.spans(t)
                if s.name == "test.total"]
        assert len(mine) == 3   # the note started long before `t`
        assert after["sum_ms"] - before["sum_ms"] == pytest.approx(
            sum(mine) + 9.0, rel=1e-6)
        assert after["max_ms"] >= max(mine + [9.0]) - 1e-9
        noted = [s for s in profiler.spans() if s.name == "test.total"
                 and s.sid == 9]
        assert noted and noted[-1].t1_ns - noted[-1].t0_ns == 9_000_000

    def test_span_columns_cross_a_json_wire_and_back(self):
        import json

        t = _since()
        with profiler.span("test.cols_outer", sid=17):
            with profiler.span("test.cols_inner"):
                pass
            with profiler.span("test.cols_inner"):
                pass
        profiler.note("test.cols_note", "s-3", t, t + 5)
        cols = json.loads(json.dumps(profiler.span_columns(t)))
        back = [s for s in profiler.spans_from_columns(cols)
                if s.name.startswith("test.cols")]
        assert back == [s for s in profiler.spans(t)
                        if s.name.startswith("test.cols")]
        assert [s.name for s in back] == ["test.cols_inner",
                                          "test.cols_inner",
                                          "test.cols_outer",
                                          "test.cols_note"]
        # a name is written once, a span refers to it
        assert cols["names"].count("test.cols_inner") == 1
        assert back[0].parent == "test.cols_outer" and back[0].sid == 17
        assert back[3].parent is None and back[3].sid == "s-3"

    @pytest.mark.parametrize("bad", ["join", "plan.join/dim", "a b.c",
                                     ".x", "x.", "plan..x"])
    def test_scope_names_are_layer_dot_what(self, bad):
        with pytest.raises(ValueError):
            profiler.scope(bad)

    def test_scope_path_keeps_the_programs_scopes_only(self):
        assert profiler.scope_path(
            "jit(run)/plan.join.dim1/cond/branch_1_fun/join.dense_probe/"
            "jit(_take)/gather:") == "plan.join.dim1/join.dense_probe"
        assert profiler.scope_path("jit(run)/dot_general:") == ""
        assert profiler.scope_path(None) == ""
        assert profiler.scope_name("ws_item-sk (x)") == "ws_item_sk__x_"


# ---------------------------------------------------------------------------
# the plan layer: host spans and device scopes
# ---------------------------------------------------------------------------

def _q6_inputs(seed=0, rows=1 << 10):
    import __graft_entry__ as ge

    return {"batch": ge._device_batch(seed, rows)}


def _q95_inputs(rows=1 << 10):
    import __graft_entry__ as ge

    fact, d1, d2 = ge._q95_batches(rows, seed=19)
    return {"fact": fact, "dim1": d1, "dim2": d2}


class TestPlanSpans:
    def test_lookup_and_dispatch_once_a_query_trace_only_on_a_miss(self):
        config.set("q6_float_mode", "f32x3")   # a key no other test made
        try:
            names = ("plan.lookup", "plan.decisions", "plan.key",
                     "plan.dispatch", "plan.trace")
            count0 = profiler.stage_totals().get(
                "plan.dispatch", {"count": 0})["count"]
            t = _since()
            cp = plan.compile_plan(queries.q6_plan(), _q6_inputs(0))
            jax.block_until_ready(cp(_q6_inputs(0)))
            miss = [s for s in profiler.spans(t) if s.name in names]
            assert cp.last_lookup == "miss"
            assert sorted(s.name for s in miss) == sorted(names)
            by = {s.name: s for s in miss}
            assert by["plan.decisions"].parent == "plan.lookup"
            assert by["plan.key"].parent == "plan.lookup"
            assert by["plan.trace"].parent == "plan.dispatch"

            t = _since()
            cp2 = plan.compile_plan(queries.q6_plan(), _q6_inputs(1))
            jax.block_until_ready(cp2(_q6_inputs(1)))
            hit = [s.name for s in profiler.spans(t) if s.name in names]
            assert cp2.last_lookup == "hit"
            assert sorted(hit) == ["plan.decisions", "plan.dispatch",
                                   "plan.key", "plan.lookup"]
            # the launch counter is the dispatch span's count
            assert profiler.stage_totals()["plan.dispatch"]["count"] \
                == count0 + 2
        finally:
            config.reset()

    def test_spans_of_a_query_nest_under_the_callers_sid(self):
        t = _since()
        with profiler.span("test.query", sid=77):
            cp = plan.compile_plan(queries.q6_plan(), _q6_inputs(0))
            cp(_q6_inputs(0))
        mine = [s for s in profiler.spans(t) if s.name.startswith("plan.")]
        assert mine and all(s.sid == 77 for s in mine)


def _lowered_scopes(the_plan, inputs):
    cp = plan.compile_plan(the_plan, inputs)
    text = cp.fn.lower(inputs, ()).as_text(debug_info=True)
    return set(re.findall(r"[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)+(?=[/\"])",
                          text))


def _unscoped_ops(text):
    """The names, without a ``plan.`` scope, of the operations of a lowered
    program (``as_text(debug_info=True)``), each named as the compiler
    names it once it has inlined the calls: the call's name, then the
    operation's own (a function called from several places is named once
    for each)."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def name(ref):
        d = locs.get(ref, "")
        m = re.match(r'^"([^"]*)"', d)
        if m:
            return m.group(1)
        m = re.match(r"^(#loc\d+)$", d)
        return name(m.group(1)) if m else ""

    funcs, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"\s*func\.func (?:public|private) @([\w.$-]+)\(", ln)
        if m:
            cur = funcs.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(ln)
    skip = ("stablehlo.constant", "stablehlo.return", "func.return",
            "func.call", "stablehlo.tuple", "stablehlo.get_tuple_element")
    bad, seen, todo = set(), set(), [("main", "")]
    while todo:
        f, prefix = todo.pop()
        if (f, prefix) in seen:
            continue
        seen.add((f, prefix))
        for ln in funcs[f]:
            ml = re.search(r"loc\((#loc\d+)\)\s*$", ln)
            if not ml:
                continue
            full = "/".join(x for x in (prefix, name(ml.group(1))) if x)
            call = re.search(r"[\s=]call @([\w.$-]+)\(", ln)
            if call:
                todo.append((call.group(1), full))
                continue
            m = re.match(r'\s*(?:%[^=]+ = )?"?([a-z_]+\.[a-z_]+)', ln)
            if m and m.group(1) not in skip and "plan." not in full:
                bad.add(full)
    return bad


class TestScopes:
    def test_q6_plan_names_every_node_and_the_onehot_phases(self):
        config.set("q6_float_mode", "f64")
        config.set("q6_onehot_engine", "xla")   # the chip's path
        try:
            got = _lowered_scopes(queries.q6_plan(), _q6_inputs())
        finally:
            config.reset()
        want = {"plan.filter.price", "plan.aggregate.k",
                "agg.onehot_bucket", "agg.onehot_payload",
                "agg.onehot_digits", "agg.onehot_build",
                "agg.onehot_contract_int8", "agg.onehot_rebuild",
                "agg.finalize"}
        assert want <= got, sorted(want - got)
        assert "agg.onehot_contract_f64" not in got

    def test_q6_plan_under_f64_contracts_no_f64_operand(self):
        """The double sum rides the int8 contraction as digits: the q6
        plan holds dot_generals, and none of them sees an f64."""
        config.set("q6_float_mode", "f64")
        config.set("q6_onehot_engine", "xla")
        try:
            inputs = _q6_inputs()
            cp = plan.compile_plan(queries.q6_plan(), inputs)
            text = cp.fn.lower(inputs, ()).as_text()
        finally:
            config.reset()
        dots = [ln for ln in text.splitlines() if "dot_general" in ln]
        assert dots
        assert not [ln for ln in dots if "f64" in ln], dots

    def test_q95_plan_names_every_node_and_the_join_phases(self):
        config.set("join_engine", "sort")       # what auto is on the chip
        config.set("groupby_engine", "sort")
        try:
            # rows enough for three fetch widths: 4096, 65,536, every row
            inputs = _q95_inputs(1 << 17)
            cp = plan.compile_plan(queries.q95_plan(), inputs)
            text = cp.fn.lower(inputs, ()).as_text(debug_info=True)
        finally:
            config.reset()
        got = set(re.findall(r"[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)+(?=[/\"])",
                             text))
        want = {
            # every node of the plan (the last Exchange is fused into the
            # aggregate and lowers under its scope)
            "plan.exchange.k", "plan.join.dim1", "plan.exchange.wh",
            "plan.join.dim2", "plan.aggregate.seg",
            "exchange.partition_id", "exchange.regroup",
            "exchange.scatter",
            # the dense branch, a lookup: rowid table and probe (nobody
            # reads d1 or d2, so join.dense_rowid and join.gather_right
            # are dropped before the program is lowered)
            "join.dense_check", "join.dense_build", "join.dense_probe",
            # the general branch: bisection, expansion, both sides gathered
            "join.general", "join.probe_keys", "join.build_sort",
            "join.bisect", "keys.bisect_gather", "keys.bisect_compare",
            "join.expand", "join.gather_left",
            # the sort-scan aggregation over the regrouped rows, and the
            # branches that fetch its scans at the group ends
            "agg.sortscan_keys", "agg.sortscan_boundary",
            "agg.sortscan_reduce", "agg.sortscan_head",
            "agg.sortscan_tier.65536", "agg.sortscan_full"}
        assert want <= got, sorted(want - got)
        # both joins feed an exchange, which takes a scattered mask: no
        # join puts its matches in front, and a left column is gathered
        # only where the general engine expands rows
        assert "join.dense_compact" not in got
        paths = set(re.findall(r'"([^"]*join\.gather_left[^"]*)"', text))
        assert paths and all("/join.general/" in p for p in paths), paths
        assert [cp.decisions[k]["output"] for k in ("join0:k", "join1:wh")] \
            == ["mask", "mask"]

    @pytest.mark.parametrize("config_name", [
        "q6-scan-agg", "q95-join-agg", "tpch-q1", "tpch-q3", "tpch-q18",
        "tpch-q13"])
    def test_every_operation_of_a_cells_plan_has_a_node_scope(
            self, config_name, monkeypatch):
        """A benchmark configuration's plan as its cell runs it (its knobs,
        the chip's engines) at 2^12 rows: every operation the lowered
        program holds is named under a ``plan.`` scope, with the name the
        compiler gives it once calls are inlined (the call's, then the
        operation's own).  Left out: the counter of the one-hot engine's
        sliced loop (``tpch_q1_plan``), which is built under no scope so
        that the expressions in its body keep their own node's path."""
        from benchmark import lib

        cfg, mod = lib.load_config(config_name, 12)
        rows = mod.rows_per_query(cfg)
        key = jax.random.PRNGKey(7)
        inputs = dict(mod.make_partition(cfg, key, rows))
        inputs.update(mod.make_shared(cfg, key, rows)
                      if hasattr(mod, "make_shared") else {})
        # every "auto" asks jax.default_backend(): answer as the chip does
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        for k, v in cfg["knobs"].items():
            config.set(k, v)
        try:
            cp = plan.compile_plan(mod.plan(cfg), inputs)
            text = cp.fn.lower(inputs, ()).as_text(debug_info=True)
        finally:
            config.reset()
            plan.reset_plan_cache()   # no later test may run the chip's path
        loop = ({"jit(run)/while/cond/lt", "jit(run)/while/body/add"}
                if config_name == "tpch-q1" else set())
        assert _unscoped_ops(text) == loop
        # a prefix sum lowered out of line (jnp.cumsum) is named
        # reduce_window_sum, outside every scope, in the compiled program
        assert "func.func private @cumsum" not in text

    def test_node_scopes_hold_letters_digits_underscore_dot_only(self):
        from spark_rapids_jni_tpu.plan import compile as pc
        from spark_rapids_jni_tpu.plan import ir

        node = ir.Join(ir.Exchange(ir.Scan("web sales"), "k-1"),
                       ir.Scan("dim/1"), "k-1", "k")
        assert pc.node_scope(node) == "plan.join.dim_1"
        assert pc.node_scope(node.child) == "plan.exchange.k_1"
        assert pc.node_scope(ir.Sort(node, ("k",))) == "plan.sort"
        assert pc.node_scope(ir.Project(node, ("k",))) == "plan.project"
        for n in (node, node.child):
            with profiler.scope(pc.node_scope(n)):
                pass


# ---------------------------------------------------------------------------
# the converter
# ---------------------------------------------------------------------------

class TestConverter:
    def test_traced_capture_yields_the_programs_spans_with_their_sid(
            self, tmp_path):
        cap = str(tmp_path / "cap.bin")
        w = profiler.FileWriter(cap)
        profiler.Profiler.init(w)
        try:
            profiler.Profiler.start()
            with profiler.span("test.traced_query", sid=123, rec="r"):
                with profiler.span("test.traced_child"):
                    jax.block_until_ready(
                        jax.jit(lambda x: x + 1)(jnp.arange(64)))
            profiler.note("test.noted", 123, 0, 1)   # ring only
            profiler.Profiler.stop()
        finally:
            profiler.Profiler.shutdown()
            w.close()
        events = [e for e in profiler.convert_profile(cap) if "plane" in e]
        by = {e["name"]: e for e in events
              if e["name"].startswith("test.traced")}
        assert set(by) == {"test.traced_query", "test.traced_child"}
        assert by["test.traced_query"]["sid"] == 123
        assert by["test.traced_child"]["sid"] == 123   # inherited
        assert by["test.traced_child"]["dur_us"] \
            <= by["test.traced_query"]["dur_us"]
        assert not any(e["name"] == "test.noted" for e in events)

    def test_device_time_by_scope_takes_children_out(self):
        def ev(name, ts, dur, scope, plane="/device:TPU:0"):
            return {"name": name, "ts_us": ts, "dur_us": dur,
                    "plane": plane, "line": "XLA Ops", "scope": scope}

        events = [
            ev("while", 0.0, 10e6, "plan.join.d/join.bisect"),
            ev("fusion.1", 1e6, 2e6,
               "plan.join.d/join.bisect/keys.bisect_gather"),
            ev("fusion.2", 4e6, 1e6, "plan.join.d/join.bisect"),
            ev("copy", 12e6, 2e6, ""),
            ev("fusion.3", 20e6, 3e6, "plan.aggregate.k"),
            # a second chip is a second stack
            ev("fusion.1", 1e6, 2e6, "plan.join.d/join.bisect",
               plane="/device:TPU:1"),
            {"name": "host thing", "ts_us": 0.0, "dur_us": 99e6,
             "plane": "/host:CPU", "line": "python"},
        ]
        got = profiler.device_time_by_scope(events, depth=2)
        assert got == pytest.approx({
            "plan.join.d/join.bisect": 12.0, "(none)": 2.0,
            "plan.aggregate.k": 3.0})
        deep = profiler.device_time_by_scope(events, depth=3)
        assert deep["plan.join.d/join.bisect"] == pytest.approx(10.0)
        assert deep["plan.join.d/join.bisect/keys.bisect_gather"] \
            == pytest.approx(2.0)
        assert profiler.device_time_by_scope(events, depth=1) \
            == pytest.approx({"plan.join.d": 12.0, "(none)": 2.0,
                              "plan.aggregate.k": 3.0})

    def test_recorded_tpu_trace_reads_op_names_from_event_metadata(self):
        """The benchmark's recorded one-second TPU trace (taken before the
        program had scopes): every device operation gets a ``scope``, all of
        it empty, and the seconds are the trace's busy time."""
        import gzip
        import json
        import os

        here = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "selfcheck")
        with open(os.path.join(here, "expected.json")) as f:
            want = json.load(f)
        with gzip.open(os.path.join(here, want["file"]), "rb") as f:
            payload = f.read()
        names = profiler._op_names(payload)["/device:TPU:0"]
        assert any(v.startswith("jit(run)/") for v in names.values())
        events = profiler.convert_xplane(payload)
        ops = [e for e in events if "scope" in e]
        assert ops and all(e["plane"] == "/device:TPU:0"
                           and e["line"] == "XLA Ops" for e in ops)
        by = profiler.device_time_by_scope(events)
        assert set(by) == {"(none)"}
        # self-times add up to the union of the intervals but for the few
        # operations that overlap on the line
        assert by["(none)"] == pytest.approx(want["numbers"]["busy_s"],
                                             rel=1e-2)
        assert any(e["name"].startswith("bench.") for e in events
                   if e["plane"].startswith("/host:"))


    def test_recorded_scoped_tpu_trace_is_named_by_scope(self):
        """One second of ``q6.inproc`` on a TPU v5 lite, recorded with this
        program's scopes (``tools/trace_report.py --keep-xplane``, PR 26):
        the device's time lands under the aggregate's contraction, under 5%
        of it outside every scope, and the program's host spans are there,
        once a query."""
        import gzip
        import os

        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "q6_inproc_1s_scoped.xplane.pb.gz")
        with gzip.open(path, "rb") as f:
            events = profiler.convert_xplane(f.read())
        by = profiler.device_time_by_scope(events, depth=2)
        busy = sum(by.values())
        assert by["(none)"] / busy < 0.05
        top = max(by, key=by.get)
        assert top == "plan.aggregate.k/agg.onehot_contract_f64"
        assert by[top] / busy > 0.9
        # the filter's compare is fused into the aggregate's operations
        assert set(profiler.device_time_by_scope(events, depth=1)) \
            == {"plan.aggregate.k", "(none)"}
        heavy = max((e for e in events if e.get("scope")),
                    key=lambda e: e["dur_us"])
        assert heavy["scope"].startswith("plan.aggregate.k/")
        host = [e["name"] for e in events
                if e["plane"].startswith("/host:")]
        assert host.count("plan.dispatch") == host.count("plan.lookup") == 4


# ---------------------------------------------------------------------------
# one clock: ring spans on the trace, idle gaps by the span over them
# ---------------------------------------------------------------------------

def _device_op(ts, dur, plane="/device:TPU:0"):
    return {"name": "fusion", "ts_us": ts * 1e6, "dur_us": dur * 1e6,
            "plane": plane, "line": "XLA Ops", "scope": "plan.x.y"}


def _host_span(name, ts, dur):
    return {"name": name, "ts_us": ts * 1e6, "dur_us": dur * 1e6,
            "plane": "/host:CPU", "line": "python"}


class TestOneClock:
    def test_an_anchor_puts_a_ring_span_on_the_trace(self, tmp_path):
        """Under a CPU profiler session a span is in the ring and in the
        trace: its ring start, moved by the anchor, lands within 100 us of
        its event."""
        import glob
        import os

        t = _since()
        jax.profiler.start_trace(str(tmp_path))
        try:
            anchor = profiler.clock_anchor()
            with profiler.span("test.anchored", sid=3):
                time.sleep(0.003)
            jax.block_until_ready(jax.jit(lambda x: x * 2)(jnp.arange(8)))
            with profiler.span("test.anchored", sid=4):
                time.sleep(0.001)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        with open(path, "rb") as f:
            payload = f.read()
        start = profiler.trace_start_ns(payload)
        assert start is not None
        traced = sorted((e for e in profiler.convert_xplane(payload)
                         if e["name"] == "test.anchored"),
                        key=lambda e: e["ts_us"])
        ring = [s for s in profiler.spans(t) if s.name == "test.anchored"]
        placed = profiler.on_trace_clock(ring, anchor, start)
        assert [p["sid"] for p in placed] == [e["sid"] for e in traced] \
            == [3, 4]
        for p, e in zip(placed, traced):
            assert p["plane"] == profiler.RING_PLANE
            assert abs(p["ts_us"] - e["ts_us"]) < 100
            assert abs(p["dur_us"] - e["dur_us"]) < 100

    def test_idle_gaps_go_to_the_innermost_span_over_most_of_them(self):
        events = [
            _device_op(0, 2), _device_op(5, 1), _device_op(9, 1),
            _device_op(12, 1), _device_op(14, 1),
            # a second, less busy device: its gaps are not counted
            _device_op(0, 0.5, plane="/device:TPU:1"),
            _host_span("bench.execute", 0, 10),
            _host_span("plan.dispatch", 2.5, 2),   # 2 of the 3 s gap
            _host_span("plan.lookup", 6, 0.5),     # a sixth of that gap
            _host_span("bench.result", 10, 2),
            _host_span("TpuExecutable::Execute", 13, 1),   # not a span
        ]
        got = profiler.idle_by_span(events)
        assert got == pytest.approx({
            "plan.dispatch": 3.0, "bench.execute": 3.0,
            "bench.result": 2.0, profiler.NO_SPAN: 1.0})
        # the supervisor's ring span, put on the trace's clock, names the
        # gap that nothing in the trace covers; it does not widen the
        # window
        anchor, start_ns = (1_000, 5_000_000_000), 4_000_000_000
        sup = [profiler.Span("serve.decode", 8, None, 11_500_001_000,
                             14_000_001_000),
               profiler.Span("serve.send", 9, None, 19_000_001_000,
                             21_000_001_000)]
        ring = profiler.on_trace_clock(sup, anchor, start_ns)
        assert ring[0]["ts_us"] == pytest.approx(12.5e6)
        got = profiler.idle_by_span(events + ring)
        assert got == pytest.approx({
            "plan.dispatch": 3.0, "bench.execute": 3.0,
            "bench.result": 2.0, "serve.decode": 1.0})
        assert profiler.idle_by_span([_host_span("plan.x", 0, 1)]) == {}

    @pytest.mark.parametrize("path", [
        ("tests", "data", "q6_inproc_1s_scoped.xplane.pb.gz"),
        ("benchmark", "selfcheck", "q6_inproc_1s.xplane.pb.gz")])
    def test_recorded_traces_idle_as_the_benchmark_reduces_them(self, path):
        """The two recorded TPU traces: the gaps add up to the idle time
        that ``benchmark/trace.py`` gives them, and the benchmark's own
        reduction still gives its recorded numbers.  With the program's
        spans in the trace (the first), some of ``bench.execute``'s idle
        goes to ``plan.dispatch``."""
        import gzip
        import os

        from benchmark import trace as bench_trace

        file = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), *path)
        with gzip.open(file, "rb") as f:
            got = profiler.idle_by_span(profiler.convert_xplane(f.read()))
        want = bench_trace.reduce_file(file, 1)
        assert sum(got.values()) == pytest.approx(
            want["window_s"] - want["busy_fullest_s"], rel=1e-9)
        assert sum(got.values()) == pytest.approx(
            sum(v for _k, v in want["idle_gaps"]), rel=1e-9)
        assert ("plan.dispatch" in got) == (path[0] == "tests")
        assert bench_trace.selfcheck() == 0


# ---------------------------------------------------------------------------
# the serving runtime: one timeline a query, across the process boundary
# ---------------------------------------------------------------------------

SUPERVISOR_STAGES = ("serve.submit", "serve.journal", "serve.pending",
                     "serve.send", "serve.decode", "serve.segment_read",
                     "serve.ipc_to_batch")
WORKER_STAGES = ("worker.admit_wait", "worker.reserve", "worker.run",
                 "worker.encode", "worker.send")


@pytest.fixture(scope="module")
def fleet(short_tempdir_module):
    """One front door with one CPU worker."""
    from spark_rapids_jni_tpu.serve import FrontDoor

    fd = FrontDoor(workers=1, heartbeat_ms=5000.0)
    try:
        yield fd
    finally:
        fd.shutdown()


class TestTimeline:
    def test_a_served_query_has_every_stage(self, fleet):
        t = _since()
        sess = fleet.submit("arrow_batch", {"rows": 2048, "seed": 1},
                            tenant="t0", est_bytes=1 << 16)
        sess.result(timeout=240)
        tl = sess.timeline
        for stage in SUPERVISOR_STAGES + WORKER_STAGES:
            assert stage in tl, (stage, tl)
            assert tl[stage] >= 0.0, (stage, tl)
        leaves = sum(ms for st, ms in tl.items()
                     if st not in sess.TIMELINE_PARENTS
                     + ("total_ms", "unaccounted_ms"))
        assert leaves <= tl["total_ms"]
        assert tl["unaccounted_ms"] == pytest.approx(
            tl["total_ms"] - leaves)
        assert tl["serve.decode"] >= tl["serve.segment_read"] \
            + tl["serve.ipc_to_batch"] - 1e-6
        assert tl["worker.run"] > 0.0 and tl["worker.encode"] > 0.0
        # the supervisor's spans of the session sit in its ring under the
        # front door's sid: one send, one decode, and the journal records
        # (submit, placed, result in the timeline; the worker's "running"
        # ack is journalled beside the query's run and is in no sum)
        mine = [s for s in profiler.spans(t) if s.sid == sess.sid]
        names = [s.name for s in mine]
        assert names.count("serve.journal") in (3, 4), names
        for one in ("serve.submit", "serve.pending", "serve.send",
                    "serve.decode", "serve.segment_read",
                    "serve.ipc_to_batch"):
            assert names.count(one) == 1, (one, names)
        journal = sum((s.t1_ns - s.t0_ns) / 1e6 for s in mine
                      if s.name == "serve.journal")
        assert 0.0 < tl["serve.journal"] <= journal + 1e-6

    def test_fleet_snapshot_counts_the_stages(self, fleet):
        before = fleet.metrics.snapshot()["stage_ms"]
        sess = fleet.submit("echo", {"value": "v"}, tenant="t1")
        assert sess.result(timeout=120) == "v"
        after = fleet.metrics.snapshot()["stage_ms"]
        for stage in ("serve.journal", "serve.pending", "serve.send",
                      "worker.admit_wait", "worker.reserve", "worker.run",
                      "total_ms", "unaccounted_ms"):
            was = before.get(stage, {"count": 0, "sum_ms": 0.0})
            assert after[stage]["count"] == was["count"] + 1, stage
            assert after[stage]["sum_ms"] == pytest.approx(
                was["sum_ms"] + sess.timeline[stage]), stage
            assert after[stage]["max_ms"] >= sess.timeline[stage] - 1e-9
        # a value result crosses no data plane: nothing to encode or decode
        assert "worker.encode" not in sess.timeline
        assert "serve.decode" not in sess.timeline
        # and it reaches operators the way every fleet counter does
        from spark_rapids_jni_tpu.mem import RmmSpark

        assert RmmSpark.fleet_metrics()["stage_ms"] == after

    def test_a_cache_hit_has_a_timeline_too(self, fleet):
        from spark_rapids_jni_tpu.serve import result_cache as rc

        snap = rc.snapshot_for_obj({"test": "tracing", "gen": 0})
        ask = {"rows": 256, "seed": 3}
        warm = fleet.submit("arrow_batch", ask, tenant="a", snapshot=snap)
        warm.result(timeout=240)
        assert not warm.served_from_cache
        assert warm.timeline["serve.cache_probe"] >= 0.0   # a miss
        hit = fleet.submit("arrow_batch", ask, tenant="b", snapshot=snap)
        hit.result(timeout=60)
        assert hit.served_from_cache
        stages = set(hit.timeline) - {"total_ms", "unaccounted_ms",
                                      "serve.submit"}
        assert stages == {"serve.cache_probe", "serve.journal"}
        assert hit.timeline["serve.cache_probe"] \
            + hit.timeline["serve.journal"] <= hit.timeline["total_ms"]

    def test_a_workers_backend_is_reported(self, fleet):
        fleet.submit("echo", {"value": 1}, tenant="t2").result(timeout=120)
        snap = fleet.metrics.snapshot()
        dev = jax.devices()[0]
        assert snap["backends"] == {0: f"{dev.platform} {dev.device_kind}"}
        assert snap["liveness"] == {0: "healthy"}
