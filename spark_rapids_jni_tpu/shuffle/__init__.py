"""Out-of-core ShuffleService: lossless multi-round exchange.

The reference stack splits the shuffle story across three layers, and
each module here is the TPU analogue of one of them:

* **Partition + pack** — the reference computes Spark-exact partition
  ids (``murmur_hash.cu:187``) and packs rows into fixed-size contiguous
  batches with size-then-write two-pass kernels (``row_conversion.cu``):
  here the map step of :mod:`.service` routes by the same
  ``pmod(murmur3(keys, 42), P)`` id, regroups rows destination-major,
  and emits the exact ``[P, P]`` count matrix — one cheap counts-only
  pass before any data moves.
* **Spillable shuffle buffers** — spark-rapids registers every shuffle
  buffer with the spill catalog so memory pressure demotes them
  device→host→disk instead of OOMing: :mod:`.buffers` wraps the map
  output and every received round chunk in a
  :class:`~spark_rapids_jni_tpu.mem.spill.SpillableHandle` registered
  with the PR-1 :class:`~spark_rapids_jni_tpu.mem.spill.SpillableStore`,
  with creation charges and read-backs running under the
  ``run_with_retry`` rollback ladder (a shuffle round is a retryable
  unit; ``RetryOOM`` between rounds triggers cross-task eviction, not
  job failure).
* **Fixed-batch transport discipline** — the reference never sizes a
  buffer for the worst case; it streams fixed 2GB batches:
  :mod:`.planner` turns the count matrix into a static
  ``(rounds, capacity)`` plan (``rounds * capacity >= max bucket``, so
  lossless by construction, with the skew ratio recorded) and
  :mod:`.service` drains the buckets through the existing static
  ``lax.all_to_all`` one capacity-slice per round — skewed keys cost
  rounds, never rows and never quadratic slot memory.
* **Shuffle manager bookkeeping** — RapidsShuffleManager keys exchanges
  by shuffle id and meters them: :mod:`.registry` assigns ids, records a
  :class:`ShuffleInfo` per exchange, and aggregates
  :class:`ShuffleMetrics` (rounds, rows/bytes moved, spilled bytes, skew
  peak, out-of-range ids, the ``dropped == 0`` invariant), surfaced via
  ``RmmSpark.shuffle_metrics()``.

* **Persistent shuffle plane** — the external-shuffle-service role:
  :mod:`.store` persists committed map outputs and drained round chunks
  (crash-safe tmp→fsync→rename commits, CRC-per-chunk manifests, epoch
  fencing against zombie writers) to a fleet-shared dir that survives
  the worker, so a replacement ADOPTS a dead worker's finished shards
  instead of lineage re-running them — ``adopted_shards`` vs
  ``lineage_rebuilds`` in :class:`ShuffleMetrics` decompose the
  recovery cost.

Out-of-range partition ids raise under the ``shuffle_strict_pids`` config
knob and are routed to the null partition (and counted) otherwise;
``shuffle_round_rows`` bounds per-round slot memory and
``shuffle_max_rounds`` caps the round count by raising capacity.
"""

from .buffers import MorselBuffer, PartitionBuffer, RoundChunk, \
    store_recompute
from .morsel import MorselSource
from .planner import (
    HierarchicalPlan,
    RoundPlan,
    plan_hierarchical,
    plan_rounds,
    plan_stream_capacity,
)
from .registry import (
    ShuffleInfo,
    ShuffleMetrics,
    ShuffleRegistry,
    get_registry,
)
from .service import ShuffleError, ShuffleResult, ShuffleService
from .store import ShuffleStore, get_store, install, shutdown_store

__all__ = [
    "MorselBuffer",
    "MorselSource",
    "PartitionBuffer",
    "RoundChunk",
    "ShuffleStore",
    "get_store",
    "install",
    "shutdown_store",
    "store_recompute",
    "HierarchicalPlan",
    "RoundPlan",
    "plan_hierarchical",
    "plan_rounds",
    "plan_stream_capacity",
    "ShuffleInfo",
    "ShuffleMetrics",
    "ShuffleRegistry",
    "get_registry",
    "ShuffleError",
    "ShuffleResult",
    "ShuffleService",
]
