"""Static-shape all-to-all row exchange (the shuffle data plane).

Runs *inside* ``shard_map``: every device holds a local batch of R rows and
a partition id per row; after :func:`exchange` every device holds the rows
whose partition id names it.  The XLA-friendly formulation:

1. stable-sort local rows by destination (padding keys sort last),
2. gather rows into a ``[P, C]`` slot grid (destination-major; C slots per
   destination, unfilled slots are null rows),
3. one ``lax.all_to_all`` over the mesh axis transposes the grid globally —
   device d receives slot-row p = what device p bucketed for d,
4. the receiver keeps the ``[P*C]`` layout plus an occupancy mask; callers
   pass that mask to group_by/compact downstream.

C (``capacity``) is the static per-(sender,destination) slot count — the TPU
analogue of the reference's fixed 2GB batch discipline
(``row_conversion.cu:93-98``): shapes are decided before the data is seen.
Rows beyond C for one destination are dropped and counted in ``dropped``
(callers size C for their skew; C = R is always lossless).

Out-of-range partition ids (``pid < 0`` or ``pid > P``) are routed to the
null pseudo-partition P and counted in ``dropped`` — they used to be
clamped silently, which DELIVERED negative ids to partition 0 and lost
``pid > P`` rows without a trace.  The :mod:`~spark_rapids_jni_tpu.shuffle`
service raises on them under the ``shuffle_strict_pids`` flag and counts
them in its metrics otherwise.

For lossless exchanges of arbitrary skew without quadratic slot memory,
use :class:`spark_rapids_jni_tpu.shuffle.ShuffleService` — it runs this
exchange in multiple planned rounds with spillable buffers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.column import ColumnBatch
from ..relational.gather import gather_batch


def route_out_of_range(pid, num_partitions: int):
    """Route ids outside ``[0, P]`` to the null partition P; return
    ``(pid int32, n_oob int32)``.  A negative id must never be delivered
    (the old clip sent it to partition 0) and an id past P must be
    counted, not silently absorbed into the padding slot."""
    pid = pid.astype(jnp.int32)
    P = jnp.int32(num_partitions)
    oob = (pid < 0) | (pid > P)
    return jnp.where(oob, P, pid), oob.sum(dtype=jnp.int32)


def exchange(
    batch: ColumnBatch,
    pid,
    axis_name: str,
    num_partitions: int,
    capacity: int | None = None,
):
    """All-to-all rows by partition id. Must run inside ``shard_map``.

    ``pid`` is int32[R] in [0, P]; P routes nowhere (padding).  Returns
    ``(out_batch [P*C rows], occupancy bool[P*C], dropped int32)``.
    ``dropped`` counts rows lost to slot overflow PLUS out-of-range ids
    (< 0 or > P), which are routed to the null partition, never delivered.
    """
    R = batch.num_rows
    P = num_partitions
    C = R if capacity is None else capacity

    pid, n_oob = route_out_of_range(pid, P)
    # platform-aware stable regroup (counting sort on CPU, lax.sort on
    # accelerators) — this local leg dominated the exchange cost on
    # XLA-CPU (r5)
    from .partition import regroup_order

    perm = regroup_order(pid, P + 1)
    pid_sorted = jnp.take(pid, perm)
    counts = jax.ops.segment_sum(
        jnp.ones((R,), jnp.int32), pid_sorted, num_segments=P + 1,
        indices_are_sorted=True,
    )[:P]
    offsets = jnp.cumsum(counts) - counts  # exclusive

    # destination-major slot grid: slot (p, c) <- sorted row offsets[p] + c
    p_ids = jnp.repeat(jnp.arange(P, dtype=jnp.int32), C)
    c_ids = jnp.tile(jnp.arange(C, dtype=jnp.int32), P)
    slot_occ = c_ids < jnp.take(counts, p_ids)
    src = jnp.take(offsets, p_ids) + c_ids
    send_idx = jnp.take(perm, jnp.clip(src, 0, max(R - 1, 0)))
    send = gather_batch(batch, send_idx, valid=slot_occ)
    dropped = jnp.maximum(counts - C, 0).sum(dtype=jnp.int32) + n_oob

    def a2a(x):
        grid = x.reshape((P, C) + x.shape[1:])
        out = jax.lax.all_to_all(grid, axis_name, split_axis=0, concat_axis=0)
        return out.reshape((P * C,) + x.shape[1:])

    out_batch = jax.tree_util.tree_map(a2a, send)
    occupancy = a2a(slot_occ)
    return out_batch, occupancy, dropped


def plan_capacity(pid, axis_name: str, num_partitions: int):
    """Per-device max (sender,destination) bucket size, maxed over the mesh.

    The lossless-shuffle planning pass: run this (inside ``shard_map``)
    first, fetch the scalar, and size :func:`exchange`'s static ``capacity``
    with it — shapes stay static, no rows can drop.  The host round-trip is
    the TPU analogue of the reference's size-then-write two-pass kernels.
    """
    R = pid.shape[0]
    P = num_partitions
    pid, _ = route_out_of_range(pid, P)
    counts = jax.ops.segment_sum(
        jnp.ones((R,), jnp.int32), pid, num_segments=P + 1
    )[:P]
    local_max = counts.max()
    return jax.lax.pmax(local_max, axis_name)


def exchange_hierarchical(
    batch: ColumnBatch,
    pid,
    dcn_axis: str,
    ici_axis: str,
    n_hosts: int,
    n_chips: int,
    capacity_dcn: int | None = None,
    capacity_ici: int | None = None,
):
    """Two-hop all-to-all over a (dcn, ici) mesh: rows cross the slow DCN
    link exactly once (to the destination host, same chip index), then the
    fast ICI once (to the destination chip).  This is the multi-host form
    of the reference's single-exchange shuffle — the global partition id
    ``p = host * n_chips + chip`` is still the Spark-exact murmur3 pmod id,
    so results are bit-identical to the flat exchange.

    Must run inside ``shard_map`` over both axes.  Returns
    ``(out_batch, occupancy, dropped)`` like :func:`exchange`; ``dropped``
    sums both hops.
    """
    from ..columnar import types as T
    from ..columnar.column import Column

    if "__pid__" in batch.names:
        raise ValueError("'__pid__' is reserved by exchange_hierarchical")
    P = n_hosts * n_chips
    pid, n_oob = route_out_of_range(pid, P)
    carried = batch.with_column("__pid__", Column(pid, pid < P, T.INT32))

    host_dst = jnp.where(pid < P, pid // n_chips, n_hosts)
    out_a, occ_a, drop_a = exchange(
        carried, host_dst, dcn_axis, n_hosts, capacity_dcn)

    # the routing column has done its job after hop one — don't pay ICI
    # bandwidth shuffling it again
    pid_a = out_a["__pid__"].data
    chip_dst = jnp.where(occ_a, pid_a % n_chips, n_chips)
    out_b, occ_b, drop_b = exchange(
        out_a.select(list(batch.names)), chip_dst, ici_axis, n_chips,
        capacity_ici)
    # OOB ids were routed to the null partition before hop one, so they
    # surface as padding (never as hop drops) — count them explicitly
    return out_b, occ_b, drop_a + drop_b + n_oob
