"""spark_rapids_jni_tpu — TPU-native Spark acceleration layer.

A from-scratch re-design of the capabilities of spark-rapids-jni (the native
acceleration layer of the RAPIDS Accelerator for Apache Spark) for TPU:

* Arrow-layout column batches pinned in TPU HBM (``columnar``).
* Spark-semantics-exact expression kernels as JAX/XLA/Pallas programs
  (``ops``): casts, decimal128 limb arithmetic, JSONPath, URI parsing,
  murmur3/xxhash64, bloom filters, histogram percentiles, z-ordering,
  timezone/calendar conversion, and the JCUDF row⇄columnar transpose.
* Relational operators (filter/project/hash-aggregate/join/sort) that the
  reference delegates to libcudf, built TPU-first (``ops.aggregate`` etc.).
* A per-task memory-pressure retry/split scheduler with deadlock breaking
  (``mem``), implemented as a native C++ state machine mirroring the
  reference's SparkResourceAdaptor semantics.
* Multi-chip shuffle as ICI all-to-all over a ``jax.sharding.Mesh``
  (``parallel``), with murmur3 partition parity so results are bit-identical
  to CPU Spark.

Design notes
------------
``jax_enable_x64`` is switched on at import: Spark semantics are 64-bit
(LongType, TimestampType micros, Decimal128 limbs) and the kernels rely on
wrapping uint64 arithmetic.  On TPU, XLA emulates 64-bit integer ops with
32-bit pairs; the hot compute paths (hashing, decimal limb math) are written
against 32-bit lanes wherever possible.
"""

import os as _os

import jax

if _os.environ.get("SRJ_FORCE_CPU"):
    # The one switch embedded hosts use (the C++ glue test driver and
    # jni_bridge.py): pin the CPU platform before any submodule import.
    jax.config.update("jax_platforms", "cpu")

jax.config.update("jax_enable_x64", True)

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself; without it the cache sits
    # at a fixed place in the checkout (the path is part of the cache key),
    # shared by every process that imports the package.
    jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache"))

# The program's named scopes (profiler.scope) are op metadata, which JAX
# leaves out of the cache key by default: an executable cached by a commit
# with other scopes (or none) would then be served, and its device trace
# would carry that commit's names.  With metadata in the key a checkout
# compiles once after any change to the lowering's names or line numbers
# (it shows in a first run's set-up only) and a trace always names what ran.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

from . import columnar  # noqa: E402
from . import ops  # noqa: E402
from . import relational  # noqa: E402

__version__ = "0.1.0"
