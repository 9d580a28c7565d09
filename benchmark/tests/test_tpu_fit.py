"""Ask the v5e compiler, with no chip attached, whether the q6 cells fit at
the size ``benchmark/configs/q6-scan-agg.json`` states: the configuration's
plan under its knobs over one batch of ``log2_rows`` rows compiles, and two
queries in flight (``served-2callers``) beside the resident batches stay
under what the compiler calls the chip's memory.

The topology is described inside a fixture, never at import, and the compile
runs in the test's own process: one process at a time may load the TPU
library (``tests/test_tpu_compile.py`` says the same of its own).  A pass
says the compiler accepts the program, not that the chip ran it.

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests).
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402

HBM_BYTES = 15.75 * (1 << 30)  # what the v5e compiler calls its hbm


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_q6_plan_fits_at_the_configurations_size(one_chip, monkeypatch):
    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64)
    from spark_rapids_jni_tpu import config, plan

    cfg, mod = lib.load_config("q6-scan-agg")
    rows = 1 << int(cfg["log2_rows"])
    inputs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: mod.make_partition(
            cfg, jax.random.PRNGKey(0), rows)))
    # the engines' "auto" asks jax.default_backend(): answer as the chip
    # does, or this compiles the CPU's scatter branch for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k, v in cfg["knobs"].items():
        config.set(k, v)
    try:
        cp = plan.compile_plan(mod.plan(cfg), inputs)
        lowered = cp.fn.lower({n: inputs[n] for n in cp.input_names}, ())
    finally:
        config.reset()
        plan.reset_plan_cache()
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes == mod.query_bytes(cfg)
    resident = int(cfg["partitions"]) * mem.argument_size_in_bytes
    in_flight = 2 * (mem.temp_size_in_bytes + mem.output_size_in_bytes)
    assert resident + in_flight < HBM_BYTES, (resident, in_flight)
