"""What decides ``correct`` has to be able to fail.

Each case drives a whole run of a cell but the look for a chip (a rehearsal
under ``JAX_PLATFORMS=cpu`` at 2^12 rows a partition) and reads the exit
code: 3 says every number compared kept its limit, 4 says ``correct`` came
out false.

* the cell as it is: correct
* the control the configuration's JSON names (``--control``: the program's
  own lower-precision path, or the reference with one stated guarantee
  broken, put in the reference's place): not correct
* each fault the cell can have, planted under the timed path
  (``benchmark/faults.py``): not correct

The last two cases hold the benchmark's own result path
(``planrun.PlanState.query``: the result's head and the group count in one
transfer) to the three-step fetch it replaced, in this process.

Run with ``python -m pytest benchmark/tests -q`` (some two minutes; not part
of the repo's tier-1 tests).  The controls' readings at the cells' own sizes
on the chip are in PERF.md.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import lib  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = {w["name"]: w for w in json.load(f)["workloads"]}
FAULTS = [(c, f) for c in CELLS for f in ("half_batch", "altered_answer")]


def rehearse(cell, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{CELLS[cell]['chips']}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "11", "--seconds", "2", "--rows", "12",
         *extra], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    tail = "\n".join(ln for ln in p.stderr.splitlines()
                     if "cpu_aot_loader" not in ln)[-3000:]
    assert p.stdout.strip() == "", "a rehearsal prints no result line"
    return p.returncode, tail


@pytest.mark.parametrize("cell", list(CELLS))
def test_cell_is_correct(cell):
    rc, tail = rehearse(cell)
    assert rc == 3, tail


@pytest.mark.parametrize("cell", list(CELLS))
def test_control_is_not_correct(cell):
    rc, tail = rehearse(cell, "--control")
    assert rc == 4, tail


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_not_correct(cell, fault):
    rc, tail = rehearse(cell, "--fault", fault)
    assert rc == 4, tail


def test_trace_reduction_selfcheck():
    p = subprocess.run([sys.executable,
                        os.path.join(ROOT, "benchmark", "run.py"),
                        "--selfcheck"], cwd=ROOT, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]


def _three_step_fetch(state, res, ng):
    """The result's fetch as it was before the one transfer: the group count
    read, a second program for the head, then its leaves."""
    import jax

    n, cap = int(ng), int(state.cfg["result_capacity"])
    small = jax.device_get(jax.jit(lambda r: jax.tree_util.tree_map(
        lambda a: a[:cap], r))(res))
    return {c: (np.asarray(small[c].data)[:n],
                np.asarray(small[c].validity)[:n], small[c].dtype)
            for c in state.mod.RESULT_COLUMNS}


@pytest.fixture
def plan_state():
    """``state(config, **over)``: a configuration's tables and plan at 2^12
    rows on the CPU, its knobs set for the test's length."""
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import config

    def state(name, **over):
        cfg, mod = lib.load_config(name, 12)
        cfg.update(over)
        for k, v in cfg["knobs"].items():
            config.set(k, v)
        return mod.build(cfg, mod, 11, jax.devices()[:1])

    yield state
    config.reset()


@pytest.mark.parametrize("name", ["q6-scan-agg", "q95-join-agg"])
def test_one_transfer_fetch_gives_the_three_step_columns(plan_state, name):
    from spark_rapids_jni_tpu import plan

    state = plan_state(name)
    for part in range(state.partitions):
        inputs = state.inputs[part]
        got = state.query(part, part, lib.Spans())
        want = _three_step_fetch(
            state, *plan.compile_plan(state.plan, inputs)(inputs))
        assert list(got) == list(want) == list(state.mod.RESULT_COLUMNS)
        assert len(got[state.mod.RESULT_COLUMNS[0]][0]) > 1
        for c in want:
            for g, w in zip(got[c][:2], want[c][:2]):
                assert g.dtype == w.dtype and g.tobytes() == w.tobytes(), c
            assert got[c][2] == want[c][2], c


def test_a_result_over_result_capacity_raises(plan_state):
    state = plan_state("q6-scan-agg", result_capacity=4)
    with pytest.raises(lib.BenchError, match="result_capacity 4"):
        state.query(0, 0, lib.Spans())
