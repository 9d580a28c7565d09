"""Test config: force an 8-virtual-device CPU platform for the whole suite.

Mirrors the driver's multi-chip dry-run: sharding/collective code paths are
exercised on a virtual CPU mesh, no TPU required (an improvement over the
reference, whose entire test suite needs a physical GPU — SURVEY.md §4).
"""

import os

# XLA_FLAGS must be set before jax initializes its CPU backend; the platform
# itself is pinned with config.update below.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The CPU suite keeps no persistent compile cache (the package would put one
# in <checkout>/.jax_cache): six xdist workers and the worker processes the
# serving tests spawn would all write it, and a test run must not depend on
# what an earlier one left behind.  Set in the environment so that child
# processes inherit it.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import shutil  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The JSON scan's production unroll factor multiplies XLA-CPU compile time
# by ~the factor across the suite's many (shape, path) variants; CI pins
# it to 1 (unroll is a lax.scan parameter — semantics are identical; one
# dedicated test covers an unrolled run).
from spark_rapids_jni_tpu import config as _srj_config  # noqa: E402

_srj_config.set("json_scan_unroll", 1)


@pytest.fixture(autouse=True, scope="module")
def _freeze_compiled_state():
    """Keep single-process suite runs linear (r5 item 6 root cause).

    Every compiled jax program leaves a large long-lived object graph
    (jaxpr + executable) in the cyclic collector's gen-2; the suite's
    allocation-heavy tracing then fires collections whose cost grows
    with everything compiled so far — quadratic total time, measured as
    the r4 collapse (>4h single-process vs 38min chunked; repro:
    tools/compile_cache_pathology.py, +24%/100 programs unfrozen vs
    flat with freeze).  After each module, collect the actual garbage,
    then freeze survivors (compiled programs, session fixtures) out of
    future GC scans.  Frozen objects are never collected — acceptable
    for a test process; ci/run_tests_chunked.sh stays the memory-safe
    CI path.
    """
    yield
    import gc

    import jax as _jax

    # Release the module's compiled executables BEFORE freezing: the
    # cyclic-GC cost is gone either way, and clearing also bounds the
    # native-side accumulation (XLA-CPU's process-global compile state
    # segfaulted at ~240 accumulated suite programs in the r5 validation
    # run — modules rarely share shapes, so cross-module recompiles are
    # negligible).
    _jax.clear_caches()
    gc.collect()
    gc.freeze()


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


def _short_tempdir():
    """A short directory of the test's own as ``tempfile.tempdir``.

    A ``FrontDoor`` binds Unix sockets under ``tempfile.mkdtemp()``, and
    an ``AF_UNIX`` path holds 107 bytes: under pytest's ``tmp_path``
    (user name, run number, xdist worker, test name) whether it fits
    depends on all four.  ``/tmp/spXXXXXXXX`` is 14.
    """
    short = tempfile.mkdtemp(prefix="sp", dir="/tmp")
    before, tempfile.tempdir = tempfile.tempdir, short
    try:
        yield short
    finally:
        tempfile.tempdir = before
        shutil.rmtree(short, ignore_errors=True)


short_tempdir = pytest.fixture(_short_tempdir)
short_tempdir_module = pytest.fixture(scope="module")(_short_tempdir)


@pytest.fixture
def rng():
    return np.random.default_rng(42)
