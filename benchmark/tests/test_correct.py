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

Run with ``python -m pytest benchmark/tests -q`` (some two minutes; not part
of the repo's tier-1 tests).  The controls' readings at the cells' own sizes
on the chip are in PERF.md.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
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
