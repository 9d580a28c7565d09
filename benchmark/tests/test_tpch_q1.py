"""The ``tpch-q1`` configuration at 2^12 rows on the CPU, in this process:
the cell's answer is correct by its own comparison, its control is not, each
fault of ``benchmark/faults.py`` fails it, the benchmark's numpy reference
equals the row-at-a-time one of the repo's tests, and ``agg_onehot_slots``
reads the program's counter.

``test_correct.py`` drives the whole runs (``run.py --rows 12``, with
``--control`` and ``--fault``) for every cell of BENCHMARK.json, this one
included; these cases hold the pieces the configuration brings.

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmark import faults, lib, planrun  # noqa: E402


@pytest.fixture(scope="module")
def q1():
    """The configuration's state at 2^12 rows, the chip's engine named (on
    the CPU ``auto`` is the scatter engine), and one partition's tables."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import config, plan

    cfg, mod = lib.load_config("tpch-q1", 12)
    config.set("q6_onehot_engine", "xla")
    plan.reset_plan_cache()
    state = mod.build(cfg, mod, 2147484011, jax.devices()[:1])
    tables = state.host_tables(0)
    yield cfg, mod, state, tables
    config.reset()
    plan.reset_plan_cache()


def _answer(state, query=None):
    got, nulls = planrun.plain((query or state.query)(0, 0, lib.Spans()))
    return got, nulls


def test_the_cell_is_correct_and_its_control_is_not(q1):
    cfg, mod, state, tables = q1
    got, nulls = _answer(state)
    assert list(got) == list(mod.RESULT_COLUMNS) and len(got) == 14
    assert nulls == 0 and len(got["count_order"]) == 4
    assert mod.compare(cfg, got, mod.reference(cfg, tables)) == {
        "wrong_exact_values": 0}
    assert cfg["control"]["reference"] is True
    wrong = mod.compare(cfg, got, mod.control(cfg, tables))
    assert wrong["wrong_exact_values"] > 0
    assert set(cfg["limits"]) == {"answers_missing", "null_values",
                                  "wrong_exact_values"}


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
def test_a_planted_fault_fails_it(q1, fault):
    cfg, mod, state, tables = q1
    got, _nulls = _answer(state, faults.wrap(state, fault))
    assert mod.compare(cfg, got, mod.reference(cfg, tables))[
        "wrong_exact_values"] > 0


def test_the_numpy_reference_is_the_row_at_a_time_one(q1):
    from tpch_q1_reference import tpch_q1_reference as by_rows

    cfg, mod, _state, tables = q1
    want = by_rows(**{c: [int(x) for x in tables["lineitem." + c]]
                      for c in mod.TABLE})
    assert mod.reference(cfg, tables) == want
    assert len(want["count_order"]) == 4   # A/F, N/F, N/O, R/F


def test_a_row_too_many_counts_ten(q1):
    cfg, mod, state, tables = q1
    got, _ = _answer(state)
    want = mod.reference(cfg, tables)
    short = {c: v[:-1] for c, v in want.items()}
    assert mod.compare(cfg, got, short) == {"wrong_exact_values": 10}


def test_query_bytes_is_what_the_tables_hold(q1):
    cfg, mod, state, _tables = q1
    assert state.table_bytes() == state.partitions * mod.query_bytes(cfg)
    assert mod.query_bytes(cfg) == (1 << 12) * 51
    full, _ = lib.load_config("tpch-q1")
    assert mod.rows_per_query(full) == 59_986_052
    assert 2 * mod.query_bytes(full) == 6_118_577_304   # 6.12 GB resident


def test_agg_onehot_slots_reads_the_programs_counter(q1):
    import jax

    from spark_rapids_jni_tpu import config, plan

    _cfg, _mod, state, _tables = q1
    read = lib.load_module("metrics", "agg_onehot_slots").read
    state.query(0, 0, lib.Spans())
    assert read({"counters": {"plan_cache": plan.plan_cache_metrics()}}) == 56
    # q6 as its configuration runs it: 27
    cfg6, mod6 = lib.load_config("q6-scan-agg", 12)
    for k, v in dict(cfg6["knobs"], q6_onehot_engine="xla").items():
        config.set(k, v)
    s6 = mod6.build(cfg6, mod6, 11, jax.devices()[:1])
    s6.query(0, 0, lib.Spans())
    assert read({"counters": {"plan_cache": plan.plan_cache_metrics()}}) == 27
    # a program without the counter (the parent's): the metric is left out
    assert read({"counters": {"plan_cache": {"hits": 1}}}) is None
    assert read({"counters": {}}) is None
    bj = lib.benchmark_json()
    (m,) = [m for m in bj["per_layer"] if m["name"] == "agg_onehot_slots"]
    assert m["workloads"] == ["tpch-q1.served", "q6.served", "q6.inproc"]
