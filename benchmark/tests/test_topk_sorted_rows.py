"""``topk_sorted_rows``: the reader against the program's counter after a Q3
query as the configuration runs it, and against a program that has no such
counter, where it has to give nothing and not raise.

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

read = lib.load_module("metrics", "topk_sorted_rows").read


def test_reads_the_programs_counter():
    import jax

    from spark_rapids_jni_tpu import config, plan

    cfg, mod = lib.load_config("tpch-q3", 12)
    try:
        # set-up ends with one query: the plan is traced by now
        state = mod.build(cfg, mod, 2147483659, jax.devices()[:1])
        counters = {"plan_cache": plan.plan_cache_metrics()}
        assert read({"counters": counters}) == state.rows == 1 << 12
        assert counters["plan_cache"]["joins_compacted"] == 0
        # a plan with no ordered limit reads 0
        plan.execute(plan.ir.Filter(plan.ir.Scan("orders"), "o_orderdate",
                                    "<", 9000),
                     {"orders": state.inputs[0]["orders"]})
        assert read({"counters": {
            "plan_cache": plan.plan_cache_metrics()}}) == 0
    finally:
        config.reset()
        plan.reset_plan_cache()


def test_gives_nothing_where_the_program_has_no_such_counter():
    assert read({"counters": {"plan_cache": {"joins_compacted": 0}}}) is None
    assert read({"counters": {"plan_cache": None}}) is None
    assert read({"counters": {}}) is None


def test_the_metric_is_declared_for_the_q3_cell():
    (m,) = [m for m in lib.benchmark_json()["per_layer"]
            if m["name"] == "topk_sorted_rows"]
    assert m == {"name": "topk_sorted_rows", "unit": "count",
                 "better": "lower", "source": "program_counter",
                 "layer": "relational operators", "moves": "query_p50_ms",
                 "workloads": ["tpch-q3.served"]}
