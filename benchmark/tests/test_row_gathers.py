"""``row_gathers``: the reader against the program's counter after a q95
query as the configuration runs it on the chip, and against a program that
has no such counter, where it has to give nothing and not raise.

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import lib  # noqa: E402

read = lib.load_module("metrics", "row_gathers").read


def test_reads_the_programs_counter(monkeypatch):
    import jax

    from spark_rapids_jni_tpu import config, plan

    # 2^13 rows: more than a group fetch's 4096, so the row gathers count
    cfg, mod = lib.load_config("q95-join-agg", 13)
    try:
        for k, v in cfg["knobs"].items():
            config.set(k, v)
        state = mod.build(cfg, mod, 2147483659, jax.devices()[:1])
        # ``auto`` answered as the chip answers it: the sort engines
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        state.query(0, 0, lib.Spans())
        monkeypatch.undo()
        counters = {"plan_cache": plan.plan_cache_metrics()}
        got = read({"counters": counters})
        assert got == counters["plan_cache"]["row_gathers"] > 0
        # an exchange of a scan of four nullable fixed-width columns moves
        # their data one gather a buffer beside their validity word (2^13
        # rows: under the 2^19 of a matrix's source) ...
        scan = {"fact": state.inputs[0]["fact"]}
        plan.execute(plan.ir.Exchange(plan.ir.Scan("fact"), "k"), scan)
        assert read({"counters": {
            "plan_cache": plan.plan_cache_metrics()}}) == 5
        # ... and all of it as one matrix, the source's limit lowered to
        # the scan's 2^13 rows
        from spark_rapids_jni_tpu.relational import gather

        monkeypatch.setattr(gather, "_MATRIX_FROM_ROWS", 1 << 13)
        plan.reset_plan_cache()
        plan.execute(plan.ir.Exchange(plan.ir.Scan("fact"), "k"), scan)
        assert read({"counters": {
            "plan_cache": plan.plan_cache_metrics()}}) == 1
    finally:
        config.reset()
        plan.reset_plan_cache()


def test_gives_nothing_where_the_program_has_no_such_counter():
    assert read({"counters": {"plan_cache": {"validity_gathers": 12}}}) \
        is None
    assert read({"counters": {"plan_cache": None}}) is None
    assert read({"counters": {}}) is None


def test_the_metric_is_declared_for_the_join_cells():
    (m,) = [m for m in lib.benchmark_json()["per_layer"]
            if m["name"] == "row_gathers"]
    assert m == {"name": "row_gathers", "unit": "count",
                 "better": "lower", "source": "program_counter",
                 "layer": "relational operators", "moves": "rows_per_s",
                 "workloads": ["q95.served", "tpch-q3.served",
                               "tpch-q18.served"]}
