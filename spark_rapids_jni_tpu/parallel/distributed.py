"""Distributed relational operators: shuffle + local op under one ``jit``.

The composition mirrors a Spark stage boundary: map-side partition → exchange
→ reduce-side operator, except the whole thing is one SPMD program — XLA
sees the collective and the surrounding compute together and overlaps them.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..columnar.column import ColumnBatch
from ..columnar.encoded import DictionaryColumn, PACKED_COLUMNS, RunLengthColumn
from ..relational.aggregate import AggSpec, group_by
from .partition import spark_partition_id
from .shuffle import exchange, plan_capacity


def data_mesh(num_devices: Optional[int] = None, axis_name: str = "data") -> Mesh:
    """1-D mesh over the first ``num_devices`` devices (default: all)."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis_name,))


def shard_batch(batch: ColumnBatch, mesh: Mesh, axis_name: str = "data") -> ColumnBatch:
    """Place a batch row-sharded over the mesh (rows % devices == 0).

    Encoded columns shard by their ROW-length leaves: dictionary + canon
    are [d]-sized lookup tables every device reads, so they replicate;
    RLE's [r]-sized run leaves have no row decomposition at all, so runs
    decode here (sharding is an output boundary for a local encoding).
    """
    sharding = NamedSharding(mesh, PartitionSpec(axis_name))
    replicated = NamedSharding(mesh, PartitionSpec())
    cols = {}
    for name, col in zip(batch.names, batch.columns):
        if isinstance(col, (RunLengthColumn,) + PACKED_COLUMNS):
            # run/lane leaves have no per-row decomposition (lane i mixes
            # rows across shard boundaries), so local encodings decode at
            # the sharding boundary, same as RLE
            col = col.decode()
        if isinstance(col, DictionaryColumn) and col.dictionary is not None:
            cols[name] = dataclasses.replace(
                col,
                codes=jax.device_put(col.codes, sharding),
                validity=jax.device_put(col.validity, sharding),
                canon=jax.device_put(col.canon, replicated),
                dictionary=jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, replicated),
                    col.dictionary))
        else:
            cols[name] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), col)
    return ColumnBatch(cols)


def distributed_group_by(
    batch: ColumnBatch,
    key_names: Sequence[str],
    aggs: Sequence[AggSpec],
    mesh: Mesh,
    axis_name: str = "data",
    row_valid=None,
    capacity: Optional[int] = None,
    ctx=None,
):
    """Shuffle rows by key hash, then group each partition locally.

    Spark semantics hold globally because the shuffle is *complete*: all rows
    of one key meet on one device (the Spark-exact partition id), so local
    group results are disjoint across devices — no merge pass needed.

    Returns ``(result, num_groups, dropped)``: ``result`` is row-sharded with
    each device's groups in front of its shard, ``num_groups`` int32[P] are
    per-device group counts, ``dropped`` int32[P] counts rows lost to slot
    overflow (always zero on the default path — with ``capacity`` unset the
    exchange runs through the lossless multi-round
    :class:`~spark_rapids_jni_tpu.shuffle.ShuffleService`, whose buffers
    spill under pressure instead of dropping; pass an explicit ``capacity``
    to force the legacy single-round fused exchange).
    """
    P = mesh.shape[axis_name]
    if capacity is None:
        from ..shuffle import ShuffleService

        res = ShuffleService(mesh, axis_name).exchange(
            batch, key_names=key_names, row_valid=row_valid, ctx=ctx)
        local = _local_group_by_step(mesh, axis_name, tuple(key_names),
                                     tuple(aggs))
        result, ng = local(res.batch, res.occupancy)
        return result, ng, jnp.zeros((P,), jnp.int32)
    step = _group_by_step(
        mesh, axis_name, tuple(key_names), tuple(aggs), capacity,
        row_valid is None,
    )
    return step(batch) if row_valid is None else step(batch, row_valid)


def plan_exchange_capacity(batch, key_names, mesh, axis_name="data",
                           row_valid=None, bucket: Optional[int] = None):
    """Host-side planning: the exact global max bucket size, rounded up to
    ``bucket`` (default: the shuffle_capacity_bucket config knob) so
    repeated batches reuse one compiled exchange."""
    if bucket is None:
        from .. import config

        bucket = config.get("shuffle_capacity_bucket")
    plan = _plan_step(mesh, axis_name, tuple(key_names), row_valid is None)
    cmax = int(np.asarray(jax.device_get(
        plan(batch) if row_valid is None else plan(batch, row_valid)))[0])
    return max(bucket, -(-cmax // bucket) * bucket)


@lru_cache(maxsize=None)
def _plan_step(mesh, axis_name, key_names, all_valid):
    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)
    n_in = 1 if all_valid else 2

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,) * n_in, out_specs=spec, check_vma=False,
    )
    def plan(b, *rv):
        rv = jnp.ones((b.num_rows,), jnp.bool_) if all_valid else rv[0]
        pid = spark_partition_id([b[k] for k in key_names], P, rv)
        return plan_capacity(pid, axis_name, P)[None]

    return jax.jit(plan)


@lru_cache(maxsize=None)
def _group_by_step(mesh, axis_name, key_names, aggs, capacity, all_valid):
    """Jitted shuffle+group step, cached so repeated batches don't retrace."""
    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)
    n_in = 1 if all_valid else 2

    # check_vma off: kernel fori_loops seed carries from replicated constants
    # (hash seeds, zero accumulators), which the varying-axis checker rejects
    # inside shard_map even though the program is correct SPMD.
    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,) * n_in, out_specs=(spec, spec, spec),
        check_vma=False,
    )
    def step(b: ColumnBatch, *rv):
        rv = jnp.ones((b.num_rows,), jnp.bool_) if all_valid else rv[0]
        pid = spark_partition_id([b[k] for k in key_names], P, rv)
        shuffled, occ, dropped = exchange(b, pid, axis_name, P, capacity)
        res, ng = group_by(shuffled, key_names, aggs, row_valid=occ)
        return res, ng[None], dropped[None]

    return jax.jit(step)


@lru_cache(maxsize=None)
def _local_group_by_step(mesh, axis_name, key_names, aggs):
    """Reduce-side-only step for ShuffleService exchanges: the rows are
    already on their destination device (occupancy marks slot padding)."""
    spec = PartitionSpec(axis_name)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec), check_vma=False,
    )
    def step(b: ColumnBatch, occ):
        res, ng = group_by(b, key_names, aggs, row_valid=occ)
        return res, ng[None]

    return jax.jit(step)


@lru_cache(maxsize=None)
def _local_join_step(mesh, axis_name, left_on, right_on, how, out_capacity):
    """Reduce-side-only join for ShuffleService exchanges (both sides
    already routed to their key's device)."""
    from ..relational.join import hash_join

    spec = PartitionSpec(axis_name)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,) * 4, out_specs=(spec, spec), check_vma=False,
    )
    def step(lb: ColumnBatch, locc, rb: ColumnBatch, rocc):
        out, count = hash_join(lb, rb, list(left_on), list(right_on), how,
                               capacity=out_capacity,
                               left_valid=locc, right_valid=rocc)
        return out, count[None]

    return jax.jit(step)


def collect_groups(result: ColumnBatch, num_groups) -> dict:
    """Host-side: concatenate each device-shard's live group rows.

    Slices the live rows out of each shard (device-side gathers on index
    arrays) before any host conversion, so cost scales with actual group
    count, not the padded P*rows_per_dev result shape.
    """
    from ..relational.gather import gather_column

    ng = np.asarray(jax.device_get(num_groups))
    P = ng.shape[0]
    rows_per_dev = result.num_rows // P
    idx = np.concatenate(
        [d * rows_per_dev + np.arange(int(ng[d])) for d in range(P)]
    ).astype(np.int32)
    idx_dev = jnp.asarray(idx)
    return {
        name: gather_column(col, idx_dev).to_pylist()
        for name, col in zip(result.names, result.columns)
    }


def distributed_hash_join(
    left: ColumnBatch,
    right: ColumnBatch,
    left_on: Sequence[str],
    right_on: Sequence[str],
    how: str,
    mesh: Mesh,
    axis_name: str = "data",
    capacity: Optional[int] = None,
    out_capacity: Optional[int] = None,
    ctx=None,
):
    """Shuffle both sides by key hash, then join each partition locally.

    Spark semantics hold globally because matching keys land on the same
    device (identical murmur3 partition ids on both sides).  Returns
    ``(result, counts int32[P], dropped int32[P, 2])`` — result rows are
    device-local with each shard's matches in front.  With ``capacity``
    unset both sides route through the lossless
    :class:`~spark_rapids_jni_tpu.shuffle.ShuffleService` (dropped is
    zeros by invariant); an explicit ``capacity`` forces the legacy fused
    single-round exchange.
    """
    P = mesh.shape[axis_name]
    if capacity is None:
        from ..shuffle import ShuffleService

        svc = ShuffleService(mesh, axis_name)
        lres = svc.exchange(left, key_names=left_on, ctx=ctx)
        rres = svc.exchange(right, key_names=right_on, ctx=ctx)
        step = _local_join_step(mesh, axis_name, tuple(left_on),
                                tuple(right_on), how, out_capacity)
        out, count = step(lres.batch, lres.occupancy,
                          rres.batch, rres.occupancy)
        return out, count, jnp.zeros((P, 2), jnp.int32)
    step = _join_step(mesh, axis_name, tuple(left_on), tuple(right_on), how,
                      capacity, out_capacity)
    return step(left, right)


@lru_cache(maxsize=None)
def _join_step(mesh, axis_name, left_on, right_on, how, capacity,
               out_capacity):
    from ..relational.join import hash_join

    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec, spec), check_vma=False,
    )
    def step(lb: ColumnBatch, rb: ColumnBatch):
        lv = jnp.ones((lb.num_rows,), jnp.bool_)
        rv = jnp.ones((rb.num_rows,), jnp.bool_)
        lpid = spark_partition_id([lb[k] for k in left_on], P, lv)
        rpid = spark_partition_id([rb[k] for k in right_on], P, rv)
        ls, locc, ldrop = exchange(lb, lpid, axis_name, P, capacity)
        rs, rocc, rdrop = exchange(rb, rpid, axis_name, P, capacity)
        # dead slots neither match nor emit: hash_join's left_valid zeroes
        # probe counts and right_valid nulls build keys
        out, count = hash_join(ls, rs, list(left_on), list(right_on), how,
                               capacity=out_capacity,
                               left_valid=locc, right_valid=rocc)
        return out, count[None], jnp.stack([ldrop, rdrop])[None]

    return jax.jit(step)




def broadcast_build_handle(right: ColumnBatch, ctx=None,
                           name: Optional[str] = None):
    """Register a broadcast-join build batch with the spill store under
    the owning query's ``ctx`` (TaskContext).

    Shuffled builds were already spillable
    (``relational.join.spillable_build_table``); this closes the gap the
    broadcast path left — a parked tenant's replicated build batch was
    unevictable device residency.  Pass the handle to
    :func:`distributed_broadcast_join` as ``build=``; it is fetched
    through the retry ladder per call, so between calls (the tenant
    parked) the central store may demote it device→host→disk and the
    next call promotes it back.
    """
    return right.spillable(ctx=ctx, name=name or "broadcast-build")


def distributed_broadcast_join(
    left: ColumnBatch,
    right: Optional[ColumnBatch],
    left_on: Sequence[str],
    right_on: Sequence[str],
    how: str,
    mesh: Mesh,
    axis_name: str = "data",
    dense_domain: Optional[int] = None,
    out_capacity: Optional[int] = None,
    build=None,
    ctx=None,
):
    """Broadcast-hash join: the build side is replicated to every device
    and the sharded probe side never moves — ZERO exchange, vs the
    two-sided shuffle :func:`distributed_hash_join` pays.  This is the
    plan Spark picks for every small dimension join
    (BroadcastHashJoinExec; the reference accelerates exactly those
    plans), and on a TPU mesh it removes the all-to-all entirely — the
    only collective cost is XLA replicating the (small) build batch.

    With ``dense_domain`` set and a single join key, each device's local
    join takes the dense rowid-table path
    (:func:`~spark_rapids_jni_tpu.relational.join.join_dense_or_hash`);
    otherwise the general sort-probe engine runs locally.

    Join types: inner / left / semi / anti — the ones whose output is a
    function of each (probe row, whole build side) pair, so per-shard
    results compose globally.  ``right``/``full`` emit unmatched BUILD
    rows, and a replicated build row unmatched on one shard may match on
    another — every device would append its own copy, inflating the
    global result — so those types raise here; use
    :func:`distributed_hash_join` for them.

    Returns ``(result, counts int32[P])`` — result rows are
    device-local with each shard's matches compacted in front (same
    layout contract as :func:`distributed_hash_join`, minus the
    ``dropped`` output: nothing is exchanged, so nothing can drop).

    The build side registers with the spill store under the owning
    query's TaskContext: pass ``build=`` (a handle from
    :func:`broadcast_build_handle`, reusable across calls — the parked-
    tenant eviction story) or ``ctx=`` (a per-call handle is created,
    fetched through the retry ladder, and closed after the step).  With
    neither, ``right`` is used directly (the pre-registration
    behavior).
    """
    if how in ("right", "full"):
        raise ValueError(
            f"broadcast join cannot run {how!r}: unmatched build rows "
            "are per-shard facts on a replicated build side (each device "
            "would emit its own copy) — use distributed_hash_join")
    if len(left_on) != len(right_on):
        raise ValueError("left_on/right_on length mismatch")
    owned = None
    if build is None and ctx is not None:
        if right is None:
            raise ValueError("ctx= registration needs the right batch")
        owned = build = broadcast_build_handle(right, ctx=ctx)
    try:
        if build is not None:
            from ..mem.executor import run_with_retry

            # pin across the fetch AND the step: the central store must
            # not demote the build tree while the collective that
            # replicates it is in flight
            with build.pinned():
                right = run_with_retry(build.get)
                step = _bcast_join_step(
                    mesh, axis_name, tuple(left_on), tuple(right_on), how,
                    None if dense_domain is None else int(dense_domain),
                    out_capacity)
                return step(left, right)
        if right is None:
            raise ValueError("need either right= or build=")
        step = _bcast_join_step(
            mesh, axis_name, tuple(left_on), tuple(right_on), how,
            None if dense_domain is None else int(dense_domain),
            out_capacity)
        return step(left, right)
    finally:
        if owned is not None:
            owned.close()


@lru_cache(maxsize=None)
def _bcast_join_step(mesh, axis_name, left_on, right_on, how, dense_domain,
                     out_capacity):
    from ..relational.join import hash_join, join_dense_or_hash

    spec = PartitionSpec(axis_name)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, PartitionSpec()),  # build side replicated
        out_specs=(spec, spec), check_vma=False,
    )
    def step(lb: ColumnBatch, rb: ColumnBatch):
        if (dense_domain is not None and len(left_on) == 1
                and len(right_on) == 1):
            out, count = join_dense_or_hash(
                lb, rb, left_on[0], right_on[0], dense_domain, how,
                capacity=out_capacity)
        else:
            out, count = hash_join(lb, rb, list(left_on), list(right_on),
                                   how, capacity=out_capacity)
        return out, count[None]

    return jax.jit(step)


def _sample_splitters(batch: ColumnBatch, key_names, P: int):
    """Host-side sample-sort splitter plan shared by the 1-D and 2-D
    sorts: strided sample of the radix key words, P-1 picks."""
    from ..relational import keys as K

    kcols = [batch[k] for k in key_names]
    karr = K.batch_radix_keys(kcols, equality=False, nulls_first=True)
    n = karr[0].shape[0]
    sample_n = min(n, max(P * 64, 1024))
    stride = max(n // sample_n, 1)
    words = np.stack(
        [np.asarray(jax.device_get(a[::stride])) for a in karr], axis=1)
    order = np.lexsort(words[:, ::-1].T)
    m = words.shape[0]
    picks = order[np.linspace(0, m - 1, P + 1).astype(np.int64)[1:-1]]
    return jnp.asarray(words[picks])  # [P-1, nw]


def _local_sort_with_occ(shuffled: ColumnBatch, occ, key_names):
    """Local sort with dead shuffle slots last (shared epilogue)."""
    from ..relational.sort import SortKey, sort_by

    out = sort_by(shuffled, [SortKey(k) for k in key_names], live=occ)
    occ_sorted = jnp.arange(out.num_rows, dtype=jnp.int32) < jnp.sum(
        occ.astype(jnp.int32))
    return out, occ_sorted


def distributed_sort(
    batch: ColumnBatch,
    key_names: Sequence[str],
    mesh: Mesh,
    axis_name: str = "data",
    capacity: Optional[int] = None,
    ctx=None,
):
    """Global sort: range-partition by sampled splitters, then sort locally.

    Returns ``(result, occupancy bool rows, dropped)`` — device d holds the
    d-th global key range in sorted order (with slot padding interleaved).
    Splitters are sampled on the host from the first key column's radix
    words, the classic sample-sort plan pass.

    With ``capacity`` unset the range exchange routes through the
    lossless multi-round :class:`~spark_rapids_jni_tpu.shuffle.ShuffleService`
    (spillable buffers, skew-aware rounds, exact accounting — ``dropped``
    is zero by construction, and ``ctx`` charges the round buffers to the
    task's arena); pass an explicit ``capacity`` to force the legacy
    single-round fused exchange.
    """
    P = mesh.shape[axis_name]
    splitters = _sample_splitters(batch, key_names, P)

    if capacity is None:
        from ..shuffle import ShuffleService

        # _range_pid is elementwise over rows against the replicated
        # splitters, so it runs straight on the row-sharded globals
        pid = _range_pid(batch, key_names, splitters, P)
        res = ShuffleService(mesh, axis_name).exchange(
            batch, pid=pid, ctx=ctx)
        local = _local_sort_step(mesh, axis_name, tuple(key_names))
        out, occ_sorted = local(res.batch, res.occupancy)
        return out, occ_sorted, jnp.zeros((P,), jnp.int32)
    step = _sort_step(mesh, axis_name, tuple(key_names), splitters.shape,
                      capacity)
    return step(batch, splitters)


def _range_pid(b, key_names, splitters, P):
    from ..relational import keys as K

    karr = K.batch_radix_keys([b[k] for k in key_names], equality=False,
                              nulls_first=True)
    R = karr[0].shape[0]
    pid = jnp.zeros((R,), jnp.int32)
    for s in range(P - 1):
        gt = jnp.zeros((R,), jnp.bool_)
        lt = jnp.zeros((R,), jnp.bool_)
        for w, a in enumerate(karr):
            sw = splitters[s, w]
            gt = gt | (~lt & (a > sw))
            lt = lt | (~gt & (a < sw))
        pid = pid + gt.astype(jnp.int32)
    return pid


@lru_cache(maxsize=None)
def _local_sort_step(mesh, axis_name, key_names):
    """Reduce-side local sort over service-exchanged rows (dead shuffle
    slots sort last via the shared occupancy epilogue)."""
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
             out_specs=(spec, spec), check_vma=False)
    def step(shuffled: ColumnBatch, occ):
        return _local_sort_with_occ(shuffled, occ, key_names)

    return jax.jit(step)


@lru_cache(maxsize=None)
def _sort_step(mesh, axis_name, key_names, splitter_shape, capacity):
    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, PartitionSpec()),
             out_specs=(spec, spec, spec), check_vma=False)
    def step(b, splitters):
        pid = _range_pid(b, key_names, splitters, P)
        shuffled, occ, dropped = exchange(b, pid, axis_name, P, capacity)
        out, occ_sorted = _local_sort_with_occ(shuffled, occ, key_names)
        return out, occ_sorted, dropped[None]

    return jax.jit(step)


# ---------------------------------------------------------------------------
# hierarchical (multi-host) mesh: DCN x ICI
# ---------------------------------------------------------------------------

def hierarchical_mesh(n_hosts: int, chips_per_host: int,
                      dcn_axis: str = "dcn", ici_axis: str = "ici") -> Mesh:
    """(hosts, chips) mesh: the outer axis maps across hosts (DCN), the
    inner across each host's chips (ICI).  On real multi-host TPU the
    device order from ``jax.devices()`` is already host-major, so the
    reshape lines the axes up with the physical links."""
    devs = jax.devices()[: n_hosts * chips_per_host]
    if len(devs) < n_hosts * chips_per_host:
        raise RuntimeError(
            f"need {n_hosts * chips_per_host} devices, have {len(devs)}")
    return Mesh(np.array(devs).reshape(n_hosts, chips_per_host),
                (dcn_axis, ici_axis))


def _hier_count_matrix(pid, P: int):
    """Host-side ``[P senders, P destinations]`` count matrix from a
    row-sharded pid array (rows are sender-major over the flattened
    mesh, so the sender index is just the row block)."""
    a = np.asarray(jax.device_get(pid)).reshape(P, -1)
    counts = np.zeros((P, P), np.int64)
    for s in range(P):
        row = a[s]
        counts[s] = np.bincount(row[(row >= 0) & (row < P)],
                                minlength=P)[:P]
    return counts


def _plan_2d_capacities(pid, H: int, D: int, capacity_dcn, capacity_ici):
    """Resolve per-hop capacities: keep explicit values, plan the rest
    from the observed count matrix (plan_hierarchical — per-hop buckets
    instead of the flat ``rows_per_device`` / ``H * C_dcn`` worst case)."""
    from ..shuffle import plan_hierarchical

    if capacity_dcn is not None and capacity_ici is not None:
        return capacity_dcn, capacity_ici
    hplan = plan_hierarchical(_hier_count_matrix(pid, H * D), H, D)
    if capacity_dcn is None:
        capacity_dcn = hplan.capacity_dcn
        if capacity_ici is None:
            capacity_ici = hplan.capacity_ici
    if capacity_ici is None:
        # explicit hop-one override without a hop-two one keeps the
        # legacy always-lossless coupling
        capacity_ici = H * capacity_dcn
    return capacity_dcn, capacity_ici


def distributed_group_by_2d(
    batch: ColumnBatch,
    key_names: Sequence[str],
    aggs: Sequence[AggSpec],
    mesh: Mesh,
    dcn_axis: str = "dcn",
    ici_axis: str = "ici",
    capacity_dcn: Optional[int] = None,
    capacity_ici: Optional[int] = None,
):
    """Group-by over a multi-host mesh via the two-hop hierarchical shuffle
    (rows cross DCN once, ICI once; see shuffle.exchange_hierarchical).

    Unset capacities are PLANNED: one elementwise pid pass feeds
    :func:`~spark_rapids_jni_tpu.shuffle.plan_hierarchical`, which sizes
    each hop's slot grid to its observed max bucket (bucket-rounded,
    overridable via ``shuffle_capacity_dcn`` / ``shuffle_capacity_ici``)
    instead of the flat worst case — multi-host meshes stop paying
    ``rows_per_device`` DCN slots and ``n_hosts * C_dcn`` ICI slots for
    uniformly hashed keys.  Pass explicit capacities to pin the grids.
    """
    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    if capacity_dcn is None or capacity_ici is None:
        pid = spark_partition_id([batch[k] for k in key_names], H * D)
        capacity_dcn, capacity_ici = _plan_2d_capacities(
            pid, H, D, capacity_dcn, capacity_ici)
    step = _group_by_2d_step(mesh, dcn_axis, ici_axis, tuple(key_names),
                             tuple(aggs), capacity_dcn, capacity_ici)
    return step(batch)


@lru_cache(maxsize=None)
def _group_by_2d_step(mesh, dcn_axis, ici_axis, key_names, aggs,
                      capacity_dcn, capacity_ici):
    from .shuffle import exchange_hierarchical

    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    P = H * D
    spec = PartitionSpec((dcn_axis, ici_axis))

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,), out_specs=(spec, spec, spec), check_vma=False,
    )
    def step(b: ColumnBatch):
        rv = jnp.ones((b.num_rows,), jnp.bool_)
        pid = spark_partition_id([b[k] for k in key_names], P, rv)
        shuffled, occ, dropped = exchange_hierarchical(
            b, pid, dcn_axis, ici_axis, H, D, capacity_dcn, capacity_ici)
        res, ng = group_by(shuffled, key_names, aggs, row_valid=occ)
        return res, ng[None], dropped[None]

    return jax.jit(step)


def distributed_hash_join_2d(
    left: ColumnBatch,
    right: ColumnBatch,
    left_on: Sequence[str],
    right_on: Sequence[str],
    how: str,
    mesh: Mesh,
    dcn_axis: str = "dcn",
    ici_axis: str = "ici",
    capacity_dcn: Optional[int] = None,
    out_capacity: Optional[int] = None,
):
    """Hash join over a multi-host mesh via the two-hop shuffle (both
    sides routed by the same Spark-exact partition ids, so matching keys
    still meet on one chip).  With ``capacity_dcn`` unset both sides'
    count matrices feed the hierarchical planner and each hop's grid is
    sized to the larger side's observed bucket (see
    :func:`distributed_group_by_2d`)."""
    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    P = H * D
    if capacity_dcn is None:
        lpid = spark_partition_id([left[k] for k in left_on], P)
        rpid = spark_partition_id([right[k] for k in right_on], P)
        lc_dcn, lc_ici = _plan_2d_capacities(lpid, H, D, None, None)
        rc_dcn, rc_ici = _plan_2d_capacities(rpid, H, D, None, None)
        capacity_dcn = max(lc_dcn, rc_dcn)
        capacity_ici = max(lc_ici, rc_ici)
    else:
        capacity_ici = H * capacity_dcn
    step = _join_2d_step(mesh, dcn_axis, ici_axis, tuple(left_on),
                         tuple(right_on), how, capacity_dcn, capacity_ici,
                         out_capacity)
    return step(left, right)


@lru_cache(maxsize=None)
def _join_2d_step(mesh, dcn_axis, ici_axis, left_on, right_on, how,
                  capacity_dcn, capacity_ici, out_capacity):
    from ..relational.join import hash_join
    from .shuffle import exchange_hierarchical

    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    P = H * D
    spec = PartitionSpec((dcn_axis, ici_axis))

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec), out_specs=(spec, spec, spec), check_vma=False,
    )
    def step(lb: ColumnBatch, rb: ColumnBatch):
        lv = jnp.ones((lb.num_rows,), jnp.bool_)
        rv = jnp.ones((rb.num_rows,), jnp.bool_)
        lpid = spark_partition_id([lb[k] for k in left_on], P, lv)
        rpid = spark_partition_id([rb[k] for k in right_on], P, rv)
        ls, locc, ldrop = exchange_hierarchical(
            lb, lpid, dcn_axis, ici_axis, H, D, capacity_dcn,
            capacity_ici)
        rs, rocc, rdrop = exchange_hierarchical(
            rb, rpid, dcn_axis, ici_axis, H, D, capacity_dcn,
            capacity_ici)
        out, count = hash_join(ls, rs, list(left_on), list(right_on), how,
                               capacity=out_capacity,
                               left_valid=locc, right_valid=rocc)
        return out, count[None], jnp.stack([ldrop, rdrop])[None]

    return jax.jit(step)


def distributed_sort_2d(
    batch: ColumnBatch,
    key_names: Sequence[str],
    mesh: Mesh,
    dcn_axis: str = "dcn",
    ici_axis: str = "ici",
    capacity_dcn: Optional[int] = None,
):
    """Global sample-sort over a multi-host mesh: same splitter plan as
    :func:`distributed_sort` with P = hosts * chips range partitions,
    routed through the two-hop exchange.  Device (h, d) holds global
    range ``h * chips + d`` in sorted order.  With ``capacity_dcn``
    unset the range pids feed the hierarchical planner so each hop's
    grid tracks its observed bucket (a well-split sort is near-uniform,
    so this beats the flat ``rows // P`` worst case on multi-host
    meshes)."""
    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    P = H * D
    splitters = _sample_splitters(batch, key_names, P)

    if capacity_dcn is None:
        # elementwise over rows against replicated splitters: runs
        # straight on the row-sharded globals, same as distributed_sort
        pid = _range_pid(batch, key_names, splitters, P)
        capacity_dcn, capacity_ici = _plan_2d_capacities(
            pid, H, D, None, None)
    else:
        capacity_ici = H * capacity_dcn
    step = _sort_2d_step(mesh, dcn_axis, ici_axis, tuple(key_names),
                         splitters.shape, capacity_dcn, capacity_ici)
    return step(batch, splitters)


@lru_cache(maxsize=None)
def _sort_2d_step(mesh, dcn_axis, ici_axis, key_names, splitter_shape,
                  capacity_dcn, capacity_ici):
    from .shuffle import exchange_hierarchical

    H, D = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    P = H * D
    spec = PartitionSpec((dcn_axis, ici_axis))

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, PartitionSpec()),
             out_specs=(spec, spec, spec), check_vma=False)
    def step(b, splitters):
        pid = _range_pid(b, key_names, splitters, P)
        shuffled, occ, dropped = exchange_hierarchical(
            b, pid, dcn_axis, ici_axis, H, D, capacity_dcn,
            capacity_ici)
        out, occ_sorted = _local_sort_with_occ(shuffled, occ, key_names)
        return out, occ_sorted, dropped[None]

    return jax.jit(step)


def distributed_group_by_onehot(
    batch: ColumnBatch,
    key_name: str,
    aggs: Sequence[AggSpec],
    domain: int,
    mesh: Mesh,
    axis_name: str = "data",
    capacity: Optional[int] = None,
):
    """Distributed MXU-path aggregation: shuffle by key hash, then the
    one-hot matmul aggregate locally (relational.aggregate.group_by_onehot).

    Returns ``(result, num_groups int32[P], dropped int32[P],
    overflow bool[P])`` — overflow means some non-null key fell outside
    ``[0, domain)`` on that device and the caller must fall back to the
    sort-scan path.
    """
    if capacity is None:
        capacity = plan_exchange_capacity(batch, [key_name], mesh, axis_name)
    step = _group_by_onehot_step(mesh, axis_name, key_name, tuple(aggs),
                                 int(domain), capacity)
    return step(batch)


def distributed_group_by_domain(
    batch: ColumnBatch,
    key_name: str,
    aggs: Sequence[AggSpec],
    domain: int,
    mesh: Mesh,
    axis_name: str = "data",
    row_valid=None,
    engine: str = "auto",
    float_mode: str = "f64",
):
    """Map-side combine: NO row shuffle at all for small-domain keys.

    Each device reduces its local rows into additive ``[K+1]``-bucket
    partials (:func:`relational.aggregate._domain_partials` — the MXU
    one-hot contraction on TPU, segment sums on CPU), then ONE ``psum``
    over the mesh merges them and every device finalizes the identical
    replicated result.  The collective payload is O(domain x aggs)
    scalars instead of the row set — for the q6 shape (2M rows/device,
    domain 100) that is ~5 KB over ICI versus ~40 MB of all-to-all row
    exchange, and there is no capacity planning, no skew sensitivity,
    and no dropped-row accounting.  This is Spark's map-side combine
    (partial aggregation before the exchange) taken to its limit: the
    exchange degenerates into an all-reduce.

    Supports sum/count/mean over int/float/decimal128 (the additive
    ops); min/max stay on :func:`distributed_group_by`.  Returns
    ``(result, num_groups, overflow)`` — all REPLICATED across the mesh
    (every device holds the full group table; ``overflow`` True means
    some key fell outside ``[0, domain)`` somewhere and the caller must
    fall back to the shuffling path).
    """
    step = _group_by_domain_step(
        mesh, axis_name, key_name, tuple(aggs), int(domain),
        row_valid is None, engine, float_mode)
    return step(batch) if row_valid is None else step(batch, row_valid)


@lru_cache(maxsize=None)
def _group_by_domain_step(mesh, axis_name, key_name, aggs, domain,
                          all_valid, engine, float_mode):
    from ..relational.aggregate import _domain_partials, _finalize_domain

    spec = PartitionSpec(axis_name)
    rep = PartitionSpec()
    n_in = 1 if all_valid else 2

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,) * n_in, out_specs=(rep, rep, rep),
        check_vma=False,
    )
    def step(b: ColumnBatch, *rv):
        rv = jnp.ones((b.num_rows,), jnp.bool_) if all_valid else rv[0]
        parts, ovf = _domain_partials(
            b, key_name, list(aggs), domain, row_valid=rv, engine=engine,
            float_mode=float_mode)
        parts = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, axis_name), parts)
        ovf = jax.lax.psum(ovf.astype(jnp.int32), axis_name) > 0
        res, ng = _finalize_domain(b, key_name, domain, list(aggs), parts)
        return res, ng, ovf

    return jax.jit(step)


@lru_cache(maxsize=None)
def _group_by_onehot_step(mesh, axis_name, key_name, aggs, domain, capacity):
    from ..relational.aggregate import group_by_onehot

    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec,), out_specs=(spec, spec, spec, spec),
        check_vma=False,
    )
    def step(b: ColumnBatch):
        rv = jnp.ones((b.num_rows,), jnp.bool_)
        pid = spark_partition_id([b[key_name]], P, rv)
        shuffled, occ, dropped = exchange(b, pid, axis_name, P, capacity)
        res, ng, overflow = group_by_onehot(
            shuffled, key_name, list(aggs), domain, row_valid=occ)
        return res, ng[None], dropped[None], overflow[None]

    return jax.jit(step)
