"""The out-of-core exchange: map → plan → drain rounds → reassemble.

One :meth:`ShuffleService.exchange` call is a Spark stage boundary made
lossless:

1. **map** (one jitted shard_map): route rows to Spark-exact partition
   ids (or caller-supplied raw ids — out-of-range ones go to the null
   partition, counted), regroup destination-major, and emit the
   ``[P, P]`` (sender, destination) count matrix.
2. **plan** (host): :func:`~spark_rapids_jni_tpu.shuffle.planner.plan_rounds`
   turns the counts into a static ``(rounds, capacity)`` shape.
3. **drain** (one compiled program for ALL rounds — the round index is a
   traced scalar): round ``r`` sends slots ``[r*C, (r+1)*C)`` of every
   bucket through the static ``lax.all_to_all``; the map output and every
   received chunk live in spillable
   :class:`~spark_rapids_jni_tpu.shuffle.buffers.PartitionBuffer`s, so
   arena pressure between rounds demotes idle chunks device→host→disk
   instead of failing — each round is a retryable unit under
   :func:`~spark_rapids_jni_tpu.mem.executor.run_with_retry`.
4. **reassemble** (per-device concat under shard_map — a global
   concatenate would interleave shards) + **account**: rows received must
   equal rows sent and the residual must hit zero, else the service
   raises — ``dropped == 0`` is an invariant, not a metric you hope for.

Fault injection: each round passes a ``shuffle_io`` probe
(name ``shuffle_io_round``); an injected
:class:`~spark_rapids_jni_tpu.faultinj.ShuffleIOError` is retried a
bounded number of times (the data is still in the buffers) and counted.

Lineage recovery: every :class:`PartitionBuffer` carries its map lineage
as the handle's ``recompute=`` hook — the map buffer re-runs the map
shards, a round chunk re-drives round ``r`` against the (recovered) map
buffer.  A buffer whose spilled copy is lost or fails its checksum is
therefore rebuilt by re-running ONLY the affected shards, not the whole
shuffle; each rebuild counts in ``ShuffleMetrics.recovered_partitions``
and draws on the per-exchange ``shuffle_max_recoveries`` budget
(exhaustion raises :class:`ShuffleError` so a flapping disk cannot loop
an exchange forever).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .. import config, faultinj, profiler
from ..columnar.column import ColumnBatch
from ..columnar.encoded import (
    PACKED_COLUMNS,
    DictionaryColumn,
    RunLengthColumn,
    choose_pack_width,
    detach_dictionaries,
    pack_bits_rows,
    reattach_dictionaries,
    unpack_bits_rows,
)
from ..mem.executor import run_with_retry
from ..parallel.partition import regroup_order, spark_partition_id
from ..parallel.shuffle import route_out_of_range
from ..relational.gather import gather_batch
from . import store as store_mod
from .buffers import MorselBuffer, PartitionBuffer, RoundChunk, \
    store_recompute
from .planner import RoundPlan, plan_rounds, plan_stream_capacity
from .registry import ShuffleInfo, ShuffleRegistry, get_registry


class ShuffleError(RuntimeError):
    """Lossless-invariant violation or strict-mode partition id abuse."""


# every drain round passes this probe; kind "shuffle_io" rules in the
# injector make it raise ShuffleIOError (the transport-fault analogue)
_io_probe = faultinj.instrument(lambda: None, "shuffle_io_round")

_IO_RETRIES = 3  # bounded re-drives of one round on transport faults

# Serving-mode shared drain lane (installed by serve/runtime.py): when
# present, exchange() pipelines round r's all_to_all on the lane thread
# while the calling thread wraps round r-1's chunk — and because ONE
# lane is shared by every tenant, tenant A's round-(k+1) map/chunk work
# overlaps tenant B's round-k all-to-all (the double-buffered
# cross-tenant drain).  The lane contract: ``submit(task_id, fn)``
# returns a Future whose ``result()`` re-raises; task_id attributes the
# lane thread's arena charges (and deadlock-scan membership) to the
# tenant that owns the round.
_drain_lane = [None]


def install_drain_lane(lane) -> None:
    _drain_lane[0] = lane


def clear_drain_lane() -> None:
    _drain_lane[0] = None


def get_drain_lane():
    return _drain_lane[0]


@dataclass
class ShuffleResult:
    """A completed exchange: row-sharded output + its exact accounting."""

    batch: ColumnBatch     # [P * rounds * P * capacity] rows, row-sharded
    occupancy: jnp.ndarray  # bool, same rows: True = live row
    shuffle_id: int
    rounds: int
    capacity: int
    rows_moved: int
    bytes_moved: int
    spilled_bytes: int
    skew_ratio: float
    oob_rows: int
    recovered_partitions: int = 0
    streamed: bool = False          # produced by exchange_stream
    morsels: int = 0                # morsels mapped (streamed only)
    rounds_overlapped: int = 0      # rounds drained before end-of-stream
    decode_ms: float = 0.0          # cumulative morsel decode+map time
    drain_ms: float = 0.0           # cumulative round drain time
    compressed_bytes_saved: int = 0  # wire bytes the pack plan saved
    blocks_skipped: int = 0         # zone blocks the source's check excluded
    blocks_scanned: int = 0         # zone blocks consulted and kept


def _map_local(b: ColumnBatch, pid, P: int):
    """Shared map-side body: route OOB → regroup dest-major → count."""
    with profiler.scope("shuffle.map_regroup"):
        pid, n_oob = route_out_of_range(pid, P)
        perm = regroup_order(pid, P + 1)
        pid_sorted = jnp.take(pid, perm)
        counts = jax.ops.segment_sum(
            jnp.ones(pid.shape, jnp.int32), pid_sorted,
            num_segments=P + 1, indices_are_sorted=True,
        )[:P]
        return gather_batch(b, perm), counts[None], n_oob[None]


@lru_cache(maxsize=None)
def _map_step_keys(mesh, axis_name, key_names, all_valid):
    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)
    n_in = 1 if all_valid else 2

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,) * n_in,
             out_specs=(spec, spec, spec), check_vma=False)
    def step(b: ColumnBatch, *rv):
        rv = jnp.ones((b.num_rows,), jnp.bool_) if all_valid else rv[0]
        with profiler.scope("shuffle.map_partition_id"):
            pid = spark_partition_id([b[k] for k in key_names], P, rv)
        return _map_local(b, pid, P)

    return jax.jit(step)


@lru_cache(maxsize=None)
def _map_step_pid(mesh, axis_name):
    P = mesh.shape[axis_name]
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
             out_specs=(spec, spec, spec), check_vma=False)
    def step(b: ColumnBatch, pid):
        return _map_local(b, pid, P)

    return jax.jit(step)


# -- compressed wire (shuffle_compress) --------------------------------------
#
# The pack plan is one spec per flattened leaf of the mapped batch:
# None (ship raw), ("bit", w, dtype, -1) for bool leaves, or
# ("for", w, dtype, ref_idx) for integer leaves — frame-of-reference
# subtract a TRACED int64 reference, then bit-pack the residual words at
# a bucketed trace-static width.  The plan tuple keys the compiled drain
# program; the references ride as operands, so two exchanges with the
# same shape but different key ranges share one program.  Packed chunks
# stay packed through the PartitionBuffer tier (bytes_moved, spill and
# the durable store all see lane words); :func:`_unpack_chunk_tree` is
# the single sanctioned decode seam at reassembly.

def _pack_plan(batch: ColumnBatch, dicts, mode: str):
    """(plan, refs) for ``batch``'s flattened leaves, or (None, None).

    ``mode='pack'`` packs every eligible 1-D leaf (bools at width 1,
    integer leaves at their observed bucketed range width); ``'auto'``
    packs only the always-wins leaves of a dictionary-carrying exchange
    (validity bools + detached code words) so plain exchanges keep their
    exact legacy wire shape."""
    if mode == "off":
        return None, None
    code_ids = set()
    if mode == "auto":
        if not dicts:
            return None, None
        for name, col in zip(batch.names, batch.columns):
            if name in dicts and isinstance(col, DictionaryColumn):
                code_ids.add(id(col.codes))
    plan = []
    refs = []
    for leaf in jax.tree_util.tree_leaves(batch):
        sp = None
        if getattr(leaf, "ndim", None) == 1 and leaf.size:
            if leaf.dtype == jnp.bool_:
                sp = ("bit", 1, "bool", -1)
            elif jnp.issubdtype(leaf.dtype, jnp.integer) and (
                    mode == "pack" or id(leaf) in code_ids):
                # range over ALL rows (null/padding slots gather real
                # in-range values, so the observed range bounds every
                # word a drain round can ever pack); widen to cover 0 so
                # zero-initialized dead slots stay representable
                lo = min(int(jax.device_get(leaf.min())), 0)
                hi = max(int(jax.device_get(leaf.max())), 0)
                w = choose_pack_width(lo, hi)
                if w is not None and w < 8 * leaf.dtype.itemsize:
                    sp = ("for", w, jnp.dtype(leaf.dtype).name, len(refs))
                    refs.append(lo)
        plan.append(sp)
    if not any(plan):
        return None, None
    return tuple(plan), refs


def _bool_plan(batch: ColumnBatch):
    """The streaming pack plan: validity bools only — a stream's value
    ranges are unknowable before its last morsel, but width-1 bool
    packing is data-independent and always wins."""
    plan = tuple(
        ("bit", 1, "bool", -1)
        if getattr(leaf, "ndim", None) == 1 and leaf.size
        and leaf.dtype == jnp.bool_ else None
        for leaf in jax.tree_util.tree_leaves(batch))
    return plan if any(plan) else None


def _plan_saved_bytes(plan, P: int, capacity: int) -> int:
    """Static wire bytes one packed round chunk saves vs the raw grid
    (the occupancy mask always packs at width 1 alongside the plan)."""
    if plan is None:
        return 0
    rows = P * P * capacity

    def lanes_nbytes(w):
        return P * P * ((capacity * w + 31) // 32) * 4

    saved = rows - lanes_nbytes(1)  # the bool occupancy mask
    for sp in plan:
        if sp is not None:
            _, w, dts, _ = sp
            saved += rows * jnp.dtype(dts).itemsize - lanes_nbytes(w)
    return max(int(saved), 0)


def _occ_rows(occ) -> int:
    """Received-row count of a round chunk's occupancy, packed or not."""
    a = np.asarray(jax.device_get(occ))
    if a.dtype == np.bool_:
        return int(a.sum())
    return int(np.unpackbits(np.ascontiguousarray(a).view(np.uint8)).sum())


def _unpack_chunk_tree(out, occ, plan, treedef, capacity: int, refs):
    """THE sanctioned wire-unpack seam (graftlint GL014): lane words that
    crossed the all_to_all (and sat packed in the chunk buffers) become
    the reassembled batch + occupancy here, immediately before the
    per-device concat — nowhere earlier."""
    if plan is None:
        return out, occ
    leaves = []
    with profiler.scope("shuffle.slot_unpack"):
        for leaf, sp in zip(out, plan):
            if sp is None:
                leaves.append(leaf)
                continue
            kind, w, dts, ref_idx = sp
            words = unpack_bits_rows(leaf, w, capacity).reshape(-1)
            if dts == "bool":
                leaves.append(words.astype(jnp.bool_))
            elif kind == "bit":
                leaves.append(words.astype(jnp.dtype(dts)))
            else:
                leaves.append(
                    (words.astype(jnp.int64)
                     + jnp.int64(refs[ref_idx])).astype(jnp.dtype(dts)))
        occv = unpack_bits_rows(occ, 1, capacity).reshape(-1).astype(
            jnp.bool_)
    return jax.tree_util.tree_unflatten(treedef, leaves), occv


@lru_cache(maxsize=None)
def _drain_step(mesh, axis_name, capacity, plan=None):
    """One compiled program serves every round: the round index is a
    traced replicated scalar, so round r selects slots [r*C, (r+1)*C) of
    each bucket without retracing.  With a pack ``plan`` the planned
    leaves cross the all_to_all as bit-packed u32 lanes (references are
    traced operands) and the chunk STAYS packed until
    :func:`_unpack_chunk_tree`."""
    P = mesh.shape[axis_name]
    C = capacity
    spec = PartitionSpec(axis_name)
    in_specs = (spec, spec, PartitionSpec())
    if plan is not None:
        in_specs = in_specs + (PartitionSpec(),)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=in_specs,
             out_specs=(spec, spec, spec, spec), check_vma=False)
    def step(b: ColumnBatch, counts2d, r, *refs_args):
        counts = counts2d.reshape(-1)[:P]
        R = b.num_rows
        with profiler.scope("shuffle.slot_pack"):
            offsets = jnp.cumsum(counts) - counts
            p_ids = jnp.repeat(jnp.arange(P, dtype=jnp.int32), C)
            c_ids = jnp.tile(jnp.arange(C, dtype=jnp.int32), P)
            k = r * C + c_ids
            slot_occ = k < jnp.take(counts, p_ids)
            src = jnp.take(offsets, p_ids) + k
            send_idx = jnp.clip(src, 0, max(R - 1, 0))
            send = gather_batch(b, send_idx, valid=slot_occ)

        def a2a(x):
            with profiler.scope("shuffle.all_to_all"):
                grid = x.reshape((P, C) + x.shape[1:])
                out = jax.lax.all_to_all(
                    grid, axis_name, split_axis=0, concat_axis=0)
                return out.reshape((P * C,) + x.shape[1:])

        residual = jnp.maximum(counts - (r + 1) * C, 0).sum(dtype=jnp.int32)
        if plan is None:
            out = jax.tree_util.tree_map(a2a, send)
            occ = a2a(slot_occ)
            got = occ.sum(dtype=jnp.int32)
            return out, occ, got[None], residual[None]
        refs = refs_args[0]
        out = tuple(
            _pack_leaf_a2a(leaf, sp, refs, axis_name, P, C)
            if sp is not None else a2a(leaf)
            for leaf, sp in zip(jax.tree_util.tree_flatten(send)[0], plan))
        occ = _pack_leaf_a2a(slot_occ, ("bit", 1, "bool", -1), refs,
                             axis_name, P, C)
        got = jax.lax.population_count(occ).sum(dtype=jnp.int32)
        return out, occ, got[None], residual[None]

    return jax.jit(step)


def _pack_leaf_a2a(leaf, sp, refs, axis_name, P, C):
    """Pack one planned leaf into per-partition lane rows and send them
    through the collective (each row's lanes stay with its destination,
    so ``all_to_all`` still splits axis 0)."""
    kind, w, _dts, ref_idx = sp
    with profiler.scope("shuffle.slot_pack"):
        if kind == "bit":
            words = leaf.astype(jnp.uint32)
        else:
            words = (leaf.astype(jnp.int64)
                     - refs[ref_idx]).astype(jnp.uint32)
        lanes = pack_bits_rows(words.reshape(P, C), w)
    with profiler.scope("shuffle.all_to_all"):
        return jax.lax.all_to_all(lanes, axis_name, split_axis=0,
                                  concat_axis=0)


# traces of the streaming drain program, bumped INSIDE the traced body
# (the plan-cache _TRACE_COUNT pattern): a thousand-morsel stream must
# compile the drain exactly once, and the parity tests assert it.
_STREAM_DRAIN_TRACES = [0]


@lru_cache(maxsize=None)
def _chunk_init_step(mesh, axis_name, capacity):
    """An empty round chunk shaped like the stream: ``P * capacity``
    destination-major slot rows (zeros) + an all-false occupancy mask,
    with dtypes/structure taken from a mapped morsel."""
    P = mesh.shape[axis_name]
    C = capacity
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
             out_specs=(spec, spec), check_vma=False)
    def step(b: ColumnBatch):
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros((P * C,) + x.shape[1:], x.dtype), b)
        return zeros, jnp.zeros((P * C,), jnp.bool_)

    return jax.jit(step)


def _resolve_scatter_engine(engine=None):
    """``engine=None`` reads the ``shuffle_scatter_engine`` knob.

    ``auto`` is ``lax`` on every platform for now: per PALLAS_MEMO's
    delete-or-measure rule the fused kernel stays opt-in until a real
    hardware round records it faster than the XLA formulation.
    """
    if engine is None:
        engine = config.get("shuffle_scatter_engine")
    if engine == "auto":
        return "lax"
    if engine not in ("lax", "pallas"):
        raise ValueError(f"unknown shuffle scatter engine {engine!r} "
                         "(use 'auto', 'lax', or 'pallas')")
    return engine


@lru_cache(maxsize=None)
def _scatter_step(mesh, axis_name, capacity, engine="lax"):
    """Scatter one mapped morsel into round ``r``'s send chunk.

    Bucket ``(s, d)``'s rows occupy GLOBAL slots ``base[s,d] ..
    base[s,d]+count-1`` (``base`` = the host's cumulative counts before
    this morsel), so slot ``k`` belongs to round ``k // C`` at position
    ``k % C`` of destination ``d``'s C-slot region.  Rows outside round
    ``r`` — and null-partition / padding rows — scatter to index ``P*C``
    and drop.  Scatter targets are disjoint per (morsel, round) and the
    values deterministic, so replaying a scatter is idempotent: the
    chunk's lineage rebuild can safely re-apply every recorded
    contribution.  The round index and base matrix are traced, so one
    compiled program serves the whole stream.

    ``engine='pallas'`` routes the per-device body through the fused
    radix partition scatter kernel (:func:`ops.pallas_kernels.
    partition_scatter`) — same ``t`` map, bit-identical chunks.
    """
    P = mesh.shape[axis_name]
    C = capacity
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(spec, spec, spec, spec, PartitionSpec(),
                       PartitionSpec()),
             out_specs=(spec, spec), check_vma=False)
    def step(chunk: ColumnBatch, occv, morsel: ColumnBatch, m_counts,
             base, r):
        s = jax.lax.axis_index(axis_name)
        cnts = m_counts.reshape(-1)[:P]
        my_base = base[s]
        if engine == "pallas":
            from ..ops.pallas_kernels import partition_scatter

            ch_leaves, treedef = jax.tree_util.tree_flatten(chunk)
            mo_leaves = jax.tree_util.tree_flatten(morsel)[0]
            new_leaves, new_occ = partition_scatter(
                ch_leaves, occv, mo_leaves, cnts.astype(jnp.int32),
                my_base.astype(jnp.int32), r, P, C)
            return jax.tree_util.tree_unflatten(treedef, new_leaves), new_occ
        M = morsel.num_rows
        with profiler.scope("shuffle.slot_pack"):
            ends = jnp.cumsum(cnts)
            offs = ends - cnts
            i = jnp.arange(M, dtype=jnp.int32)
            d = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
            d_c = jnp.minimum(d, P - 1)
            k = jnp.take(my_base, d_c) + (i - jnp.take(offs, d_c))
            in_round = (d < P) & (k >= r * C) & (k < (r + 1) * C)
            t = jnp.where(in_round, d_c * C + (k - r * C), P * C)
            new_chunk = jax.tree_util.tree_map(
                lambda acc, x: acc.at[t].set(x, mode="drop"), chunk,
                morsel)
            new_occ = occv.at[t].set(True, mode="drop")
        return new_chunk, new_occ

    return jax.jit(step)


@lru_cache(maxsize=None)
def _stream_drain_step(mesh, axis_name, capacity, plan=None):
    """Drain ONE streaming round: the chunk is already destination-major
    packed by the scatter, so this is just the static all_to_all plus
    the received-row count — and the single program every round of every
    stream at this capacity reuses (``_STREAM_DRAIN_TRACES`` proves it).
    With a pack ``plan`` (bool leaves only — see :func:`_bool_plan`) the
    planned leaves cross as width-1 lanes and stay packed until
    :func:`_unpack_chunk_tree`.
    """
    P = mesh.shape[axis_name]
    C = capacity
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
             out_specs=(spec, spec, spec), check_vma=False)
    def step(chunk: ColumnBatch, slot_occ):
        _STREAM_DRAIN_TRACES[0] += 1

        def a2a(x):
            with profiler.scope("shuffle.all_to_all"):
                grid = x.reshape((P, C) + x.shape[1:])
                out = jax.lax.all_to_all(
                    grid, axis_name, split_axis=0, concat_axis=0)
                return out.reshape((P * C,) + x.shape[1:])

        if plan is None:
            out = jax.tree_util.tree_map(a2a, chunk)
            occ = a2a(slot_occ)
            got = occ.sum(dtype=jnp.int32)
            return out, occ, got[None]
        out = tuple(
            _pack_leaf_a2a(leaf, sp, None, axis_name, P, C)
            if sp is not None else a2a(leaf)
            for leaf, sp in zip(jax.tree_util.tree_flatten(chunk)[0], plan))
        occ = _pack_leaf_a2a(slot_occ, ("bit", 1, "bool", -1), None,
                             axis_name, P, C)
        got = jax.lax.population_count(occ).sum(dtype=jnp.int32)
        return out, occ, got[None]

    return jax.jit(step)


@lru_cache(maxsize=None)
def _concat_step(mesh, axis_name, n_chunks):
    """Per-DEVICE row concatenation of the round chunks.  A global
    ``jnp.concatenate`` on row-sharded arrays would interleave other
    devices' shards between this device's rounds; under shard_map each
    device stitches only its own shards."""
    spec = PartitionSpec(axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,) * n_chunks,
             out_specs=spec, check_vma=False)
    def step(*chunks):
        return jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *chunks)

    return jax.jit(step)


def _spill_snapshot():
    from ..mem import spill as spill_mod

    fw = spill_mod.get_framework()
    if fw is None:
        return None
    m = fw.metrics.snapshot()
    return m["device_to_host_bytes"] + m["host_to_disk_bytes"]


class ShuffleService:
    """Lossless multi-round exchange over one mesh axis.

    Stateless apart from the shared :class:`ShuffleRegistry`; the
    compiled map/drain/concat programs are cached module-wide, so
    constructing a service per call is free.
    """

    def __init__(self, mesh, axis_name: str = "data",
                 registry: Optional[ShuffleRegistry] = None):
        self.mesh = mesh
        self.axis_name = axis_name
        self.registry = registry or get_registry()

    # -- public API -----------------------------------------------------
    def exchange(
        self,
        batch: ColumnBatch,
        key_names: Optional[Sequence[str]] = None,
        pid=None,
        row_valid=None,
        ctx=None,
        round_rows: Optional[int] = None,
        strict: Optional[bool] = None,
        store_key: Optional[str] = None,
    ) -> ShuffleResult:
        """Exchange ``batch`` rows so partition p's rows land on device p.

        Route either by ``key_names`` (Spark-exact
        ``pmod(murmur3(keys, 42), P)``) or by a caller-supplied ``pid``
        array (int32 per row; P = padding, routed nowhere).  Out-of-range
        ids raise :class:`ShuffleError` when ``strict`` (default: the
        ``shuffle_strict_pids`` knob), else they are routed to the null
        partition and counted in the metrics.

        ``ctx`` (a :class:`~spark_rapids_jni_tpu.mem.executor.TaskContext`)
        charges every partition buffer to the device arena, making the
        exchange a first-class out-of-core citizen; without it buffers are
        registered but uncharged.

        ``store_key`` is the exchange's DURABLE logical identity in the
        persistent shuffle plane (:mod:`.store`): a caller-stable string
        (per-process shuffle ids don't survive a crash) under which the
        committed map output and every drained round chunk are persisted
        best-effort, and from which a retry of the same exchange — in
        this process or a replacement worker — ADOPTS finished shards
        instead of recomputing them.  None (or no installed store)
        disables the durable tier for this exchange.
        """
        from .. import config

        if (key_names is None) == (pid is None):
            raise ValueError("pass exactly one of key_names / pid")
        if strict is None:
            strict = bool(config.get("shuffle_strict_pids"))
        mesh, axis = self.mesh, self.axis_name
        P = mesh.shape[axis]
        sid = self.registry.begin_shuffle()
        spill_base = _spill_snapshot()
        store = store_mod.get_store() if store_key is not None else None

        # 0. encoded columns: the exchange moves CODES; each dictionary is
        # broadcast ONCE per shuffle (host-side reattach after reassembly)
        # so plan_rounds capacity math and every all_to_all see the u32
        # code width, not the value width.  RLE decodes here: runs do not
        # survive the destination-major regroup, and their [r]-shaped
        # leaves cannot ride the row-sharded specs.  Bit-packed/FoR
        # columns decode too (lane leaves have no per-row sharding); the
        # wire packer below re-compresses them per round chunk.
        if any(isinstance(c, (RunLengthColumn,) + PACKED_COLUMNS)
               for c in batch.columns):
            batch = ColumnBatch({
                name: (c.decode()
                       if isinstance(c, (RunLengthColumn,) + PACKED_COLUMNS)
                       else c)
                for name, c in zip(batch.names, batch.columns)})
        dicts = {}
        if any(isinstance(c, DictionaryColumn) for c in batch.columns):
            if key_names is not None and any(
                    isinstance(batch[k], DictionaryColumn)
                    for k in key_names):
                # Spark-exact pids hash key VALUES; compute them before
                # stripping the dictionaries (elementwise, so it runs on
                # the row-sharded globals without a shard_map) and route
                # the map step by pid — bit-identical to the keyed path.
                pid = spark_partition_id(
                    [batch[k] for k in key_names], P, row_valid)
                key_names = None
            batch, dicts = detach_dictionaries(batch)

        # 1. map: regroup destination-major + the count matrix
        if key_names is not None:
            step = _map_step_keys(mesh, axis, tuple(key_names),
                                  row_valid is None)
            run_map = ((lambda: step(batch)) if row_valid is None
                       else (lambda: step(batch, row_valid)))
        else:
            step = _map_step_pid(mesh, axis)
            run_map = lambda: step(batch, pid)  # noqa: E731
        # durable tier first: a prior attempt's COMMITTED map output (this
        # process's earlier try, or a dead worker's — same key) is adopted
        # instead of re-running the map; a store whose every attempt fails
        # CRC verification has quarantined them all and falls through to
        # the fresh run below, counted as a lineage rebuild.
        adopted_map = None
        if store is not None and store.has_committed(store_key, "map"):
            adopted_map = store.adopt(store_key, "map")
            if adopted_map is not None:
                self.registry.metrics.record_adopted()
            else:
                self.registry.metrics.record_lineage_rebuild()
        if adopted_map is not None:
            regrouped, counts, oob = adopted_map
        else:
            regrouped, counts, oob = run_map()
            if store is not None:
                # best-effort durable commit: a torn/fenced/failed put
                # returns False and the exchange proceeds from memory
                store.put(store_key, "map", (regrouped, counts, oob))
        counts_np = np.asarray(jax.device_get(counts)).reshape(P, P)
        oob_total = int(np.asarray(jax.device_get(oob)).sum())
        if oob_total and strict:
            raise ShuffleError(
                f"shuffle {sid}: {oob_total} out-of-range partition ids "
                f"(strict mode; ids must lie in [0, {P}])")

        # 2. plan: static (rounds, capacity) from the exact counts
        with profiler.span("shuffle.plan_rounds"):
            plan = plan_rounds(counts_np, round_rows=round_rows)

        # 2b. wire plan: which leaves cross the collective bit-packed
        compress = str(config.get("shuffle_compress") or "auto").lower()
        if compress not in ("auto", "off", "pack"):
            raise ValueError(f"shuffle_compress must be auto/off/pack, "
                             f"got {compress!r}")
        wire_plan, wire_refs = _pack_plan(regrouped, dicts, compress)
        wire_treedef = jax.tree_util.tree_structure(regrouped)
        refs_arr = (jnp.asarray(wire_refs or [0], jnp.int64)
                    if wire_plan is not None else None)
        saved_per_chunk = _plan_saved_bytes(wire_plan, P, plan.capacity)
        # packed chunks commit under a distinct shard name so a raw run
        # never adopts lane words (and vice versa) — the mismatch is a
        # clean adoption miss, not a mis-shaped tree
        round_tag = "roundp" if wire_plan is not None else "round"

        # lineage: each buffer's recompute= re-runs only the shards that
        # produced it, metered against the per-exchange recovery budget
        recovered = [0]
        _lineage = self._lineage_factory(sid, recovered)

        # 3. drain: multi-round all_to_all over spillable buffers
        def _adopt_map2():
            # lineage-time adoption: the stored shard carries the oob
            # vector too; the buffer only holds (regrouped, counts)
            t = store.adopt(store_key, "map")
            return None if t is None else (t[0], t[1])

        map_buf = PartitionBuffer(
            (regrouped, counts), ctx=ctx, name=f"shuffle{sid}-map",
            recompute=_lineage(lambda: run_map()[:2], "map output",
                               adopt=_adopt_map2 if store is not None
                               else None))
        drain = _drain_step(mesh, axis, plan.capacity, wire_plan)

        def _redrive(rr):
            # round rr's partitions depend only on the map buffer and
            # the static plan: rebuilding them re-runs ONE drain round
            # (which may itself recover the map buffer first)
            def rebuild():
                tree, cnts = map_buf.get()
                args = (tree, cnts, jnp.int32(rr))
                if refs_arr is not None:
                    args = args + (refs_arr,)
                out_r, occ_r, _, _ = drain(*args)
                return out_r, occ_r
            return rebuild

        chunks = []
        received = 0
        bytes_moved = 0
        compressed_saved = 0
        residual = -1
        lane = get_drain_lane()
        overlapped = 0

        def _rounds():
            # double-buffer depth 1 on the shared lane: round r+1 is in
            # flight on the lane thread while round r's result is wrapped
            # here.  Without a lane (or a single round) run sequentially.
            nonlocal overlapped
            if lane is None or plan.rounds <= 1:
                for r in range(plan.rounds):
                    yield (r, *self._run_round(drain, map_buf, r,
                                               refs_arr))
                return
            owner = getattr(ctx, "task_id", None)
            pending = []
            try:
                for r in range(plan.rounds):
                    pending.append((r, lane.submit(
                        owner,
                        lambda rr=r: self._run_round(drain, map_buf, rr,
                                                     refs_arr))))
                    if len(pending) == 2:
                        rr, fut = pending.pop(0)
                        overlapped += 1
                        yield (rr, *fut.result())
                while pending:
                    rr, fut = pending.pop(0)
                    yield (rr, *fut.result())
            finally:
                for _, fut in pending:  # consumer bailed: drop queued rounds
                    fut.cancel()

        try:
            for r, out, occ, got_n, residual in _rounds():
                if store is not None:
                    store.put(store_key, f"{round_tag}-{r}", (out, occ))
                chunk = PartitionBuffer(
                    (out, occ), ctx=ctx, name=f"shuffle{sid}-round{r}",
                    recompute=_lineage(
                        _redrive(r), f"round {r} chunk",
                        adopt=(lambda rr=r: store.adopt(
                            store_key, f"{round_tag}-{rr}"))
                        if store is not None else None))
                chunks.append(chunk)
                received += got_n
                bytes_moved += chunk.nbytes
                compressed_saved += saved_per_chunk

            # 4. account + reassemble
            sent = int(counts_np.sum())
            if residual != 0 or received != sent:
                self.registry.metrics.record_dropped(
                    max(sent - received, 0) + max(residual, 0))
                raise ShuffleError(
                    f"shuffle {sid}: lossless invariant violated "
                    f"(sent={sent} received={received} residual={residual})")
            if plan.rounds == 1:
                final_batch, final_occ = _unpack_chunk_tree(
                    *chunks[0].get(), wire_plan, wire_treedef,
                    plan.capacity, wire_refs)
            else:
                parts = [
                    _unpack_chunk_tree(*c.get(), wire_plan, wire_treedef,
                                       plan.capacity, wire_refs)
                    for c in chunks]
                concat = _concat_step(mesh, axis, len(parts))
                final_batch, final_occ = concat(*parts)
        finally:
            map_buf.close()
            for c in chunks:
                c.close()

        if dicts:
            # the once-per-shuffle broadcast: rebind each dictionary to
            # the reassembled codes and charge its bytes ONCE (not once
            # per round) so bytes_moved stays an honest transfer count
            final_batch = reattach_dictionaries(final_batch, dicts)
            bytes_moved += sum(
                leaf.size * leaf.dtype.itemsize
                for _, (canon, dictionary, _, _) in sorted(dicts.items())
                for leaf in jax.tree_util.tree_leaves((canon, dictionary)))

        spilled = 0
        if spill_base is not None:
            after = _spill_snapshot()
            spilled = (after - spill_base) if after is not None else 0
        info = ShuffleInfo(
            shuffle_id=sid, rounds=plan.rounds, capacity=plan.capacity,
            rows_moved=received, bytes_moved=bytes_moved,
            spilled_bytes=spilled, skew_ratio=plan.skew_ratio,
            oob_rows=oob_total, recovered_partitions=recovered[0],
            compressed_bytes_saved=compressed_saved)
        self.registry.record(info)
        return ShuffleResult(
            batch=final_batch, occupancy=final_occ, shuffle_id=sid,
            rounds=plan.rounds, capacity=plan.capacity, rows_moved=received,
            bytes_moved=bytes_moved, spilled_bytes=spilled,
            skew_ratio=plan.skew_ratio, oob_rows=oob_total,
            recovered_partitions=recovered[0],
            rounds_overlapped=overlapped,
            compressed_bytes_saved=compressed_saved)

    def exchange_stream(
        self,
        morsels,
        key_names: Optional[Sequence[str]] = None,
        ctx=None,
        round_rows: Optional[int] = None,
        strict: Optional[bool] = None,
        store_key: Optional[str] = None,
    ) -> ShuffleResult:
        """Morsel-driven exchange: map and route ``morsels`` one at a
        time, draining earlier rounds while later morsels are still
        decoding — bit-identical on delivered rows to
        :meth:`exchange` over the same rows, without ever materializing
        the whole map output.

        ``morsels`` yields either a morsel directly or (preferably) a
        zero-arg REPLAY callable returning one (see
        :class:`~spark_rapids_jni_tpu.shuffle.morsel.MorselSource`); a
        morsel is a row-sharded ``ColumnBatch`` or a ``(batch, aux)``
        pair where ``aux`` is the per-row validity (key mode) or the
        partition id array (pid mode, ``key_names=None``).  Replay
        callables are the stream's lineage: a lost or corrupt buffer
        re-decodes and re-maps its source morsels instead of holding a
        second copy resident.

        The round-chunk capacity is fixed up front
        (:func:`~.planner.plan_stream_capacity` — the counts don't exist
        yet) and the ROUND SCHEDULE is re-planned as morsel counts
        arrive: chunks are created and charged the moment a morsel first
        touches their round (long before the round is fully received),
        round ``r`` drains EARLY once every bucket's cumulative count
        clears ``(r+1) * capacity`` (no later morsel can touch it), and
        the final round count is whatever the observed maximum bucket
        needs.  ``shuffle_max_rounds`` does not apply here — a stream
        cannot raise a capacity it has already scattered into; bound
        round count via ``round_rows`` instead.  Encoded columns decode
        per morsel (codes-only streaming would need cross-morsel
        dictionary identity).

        ``store_key`` persists every DRAINED round chunk to the
        persistent shuffle plane (the stream's map output is morsel-
        incremental, so the committed grain is the received round): a
        retry of the same stream adopts already-drained rounds instead
        of re-scattering and re-draining them.
        """
        from .. import config

        if strict is None:
            strict = bool(config.get("shuffle_strict_pids"))
        mesh, axis = self.mesh, self.axis_name
        P = mesh.shape[axis]
        sid = self.registry.begin_shuffle()
        spill_base = _spill_snapshot()
        store = store_mod.get_store() if store_key is not None else None
        C = plan_stream_capacity(round_rows=round_rows)
        scatter = _scatter_step(mesh, axis, C, _resolve_scatter_engine())
        init = _chunk_init_step(mesh, axis, C)
        # the wire plan needs the stream's leaf structure — the drain
        # program is built at the first morsel (always before any round
        # drains).  Streams pack bool leaves only: value ranges are
        # unknowable before the last morsel (see _bool_plan).
        compress = str(config.get("shuffle_compress") or "auto").lower()
        if compress not in ("auto", "off", "pack"):
            raise ValueError(f"shuffle_compress must be auto/off/pack, "
                             f"got {compress!r}")
        drain = None
        wire_plan = None
        wire_treedef = None
        saved_per_chunk = 0
        recv_tag = "recv"
        recovered = [0]
        _lineage = self._lineage_factory(sid, recovered)

        def _make_run_map(replay):
            def run():
                item = replay()
                b, aux = item if isinstance(item, tuple) else (item, None)
                enc = (RunLengthColumn, DictionaryColumn) + PACKED_COLUMNS
                if any(isinstance(c, enc) for c in b.columns):
                    b = ColumnBatch({
                        n: (c.decode() if isinstance(c, enc) else c)
                        for n, c in zip(b.names, b.columns)})
                if key_names is not None:
                    step = _map_step_keys(mesh, axis, tuple(key_names),
                                          aux is None)
                    return step(b) if aux is None else step(b, aux)
                if aux is None:
                    raise ValueError(
                        "pid-mode streaming morsels must be (batch, pid) "
                        "pairs")
                return _map_step_pid(mesh, axis)(b, aux)
            return run

        cum = np.zeros((P, P), np.int64)
        send_chunks = {}
        contribs = {}
        recv = []
        first_map = [None]
        oob_total = 0
        received = 0
        bytes_moved = 0
        compressed_saved = 0
        next_drain = 0
        n_morsels = 0
        rounds_overlapped = 0
        decode_ms = 0.0
        drain_ms = 0.0

        def _rebuild_chunk(rr):
            # re-scatter every contribution recorded for round rr (a
            # superset of the lost state is fine: scatters are
            # idempotent and disjoint per contribution)
            def rebuild():
                state = None
                for run_m, base_j in contribs.get(rr, ()):
                    m_tree, m_counts = run_m()[:2]
                    if state is None:
                        state = init(m_tree)
                    state = scatter(state[0], state[1], m_tree, m_counts,
                                    jnp.asarray(base_j, jnp.int32),
                                    jnp.int32(rr))
                if state is None:
                    m_tree, _ = first_map[0]()[:2]
                    state = init(m_tree)
                return state
            return rebuild

        def _open_chunk(rr, m_tree):
            send_chunks[rr] = RoundChunk(
                init(m_tree), ctx=ctx, name=f"shuffle{sid}-send{rr}",
                recompute=_lineage(_rebuild_chunk(rr),
                                   f"round {rr} send chunk"))
            contribs[rr] = []

        def _drain_round(rr):
            with profiler.span("shuffle.round", round=rr):
                _drain_round_body(rr)

        def _drain_round_body(rr):
            nonlocal received, bytes_moved, compressed_saved
            chunk = send_chunks[rr]

            # a prior attempt already drained (and committed) this round:
            # adopt the received chunk instead of re-running the a2a
            adopted = (store.adopt(store_key, f"{recv_tag}-{rr}")
                       if store is not None else None)
            if adopted is not None:
                out, occ2 = adopted
                got_n = _occ_rows(occ2)
                self.registry.metrics.record_adopted()
            else:
                def round_step():
                    _io_probe()
                    tree, occv = chunk.get()
                    out, occ2, got = drain(tree, occv)
                    got_n = int(np.asarray(jax.device_get(got)).sum())
                    return out, occ2, got_n

                for attempt in range(_IO_RETRIES + 1):
                    try:
                        out, occ2, got_n = run_with_retry(round_step)
                        break
                    except faultinj.ShuffleIOError:
                        self.registry.metrics.record_io_failure()
                        if attempt == _IO_RETRIES:
                            raise
                if store is not None:
                    store.put(store_key, f"{recv_tag}-{rr}", (out, occ2))

            def redrive():
                tree, occv = chunk.get()
                o, oc, _ = drain(tree, occv)
                return o, oc

            buf = PartitionBuffer(
                (out, occ2), ctx=ctx, name=f"shuffle{sid}-recv{rr}",
                recompute=_lineage(
                    redrive, f"round {rr} chunk",
                    adopt=(lambda: store.adopt(store_key,
                                               f"{recv_tag}-{rr}"))
                    if store is not None else None))
            recv.append(buf)
            received += got_n
            bytes_moved += buf.nbytes
            compressed_saved += saved_per_chunk

        try:
            for item in morsels:
                replay = item if callable(item) else (lambda it=item: it)
                run_map_m = _make_run_map(replay)
                t0 = time.perf_counter()
                regrouped, counts, oob = run_map_m()
                counts_np = np.asarray(
                    jax.device_get(counts), np.int64).reshape(P, P)
                decode_ms += (time.perf_counter() - t0) * 1e3
                oob_n = int(np.asarray(jax.device_get(oob)).sum())
                oob_total += oob_n
                if oob_n and strict:
                    raise ShuffleError(
                        f"shuffle {sid}: {oob_n} out-of-range partition "
                        f"ids (strict mode; ids must lie in [0, {P}])")
                if first_map[0] is None:
                    first_map[0] = run_map_m
                    if compress == "pack":
                        wire_plan = _bool_plan(regrouped)
                        wire_treedef = jax.tree_util.tree_structure(
                            regrouped)
                        saved_per_chunk = _plan_saved_bytes(wire_plan, P, C)
                        if wire_plan is not None:
                            recv_tag = "recvp"
                    drain = _stream_drain_step(mesh, axis, C, wire_plan)
                base = cum.copy()
                cum = cum + counts_np
                m_idx = n_morsels
                n_morsels += 1
                mbuf = MorselBuffer(
                    (regrouped, counts), ctx=ctx,
                    name=f"shuffle{sid}-morsel{m_idx}",
                    recompute=_lineage(lambda rm=run_map_m: rm()[:2],
                                       f"morsel {m_idx} map output"))
                try:
                    nz = counts_np > 0
                    if m_idx == 0:
                        # round 0 always exists: an all-empty stream
                        # still drains one schema-bearing empty round
                        _open_chunk(0, mbuf.get()[0])
                    if nz.any():
                        r_lo = int((base[nz] // C).min())
                        r_hi = int(((cum[nz] - 1) // C).max())
                        for rr in range(r_lo, r_hi + 1):
                            if rr not in send_chunks:
                                _open_chunk(rr, mbuf.get()[0])
                            contribs[rr].append((run_map_m, base))
                            chunk = send_chunks[rr]
                            tree, occv = chunk.get()
                            m_tree, m_counts = mbuf.get()
                            new = run_with_retry(
                                lambda: scatter(
                                    tree, occv, m_tree, m_counts,
                                    jnp.asarray(base, jnp.int32),
                                    jnp.int32(rr)))
                            chunk.update(
                                new,
                                recompute=_lineage(
                                    _rebuild_chunk(rr),
                                    f"round {rr} send chunk"))
                finally:
                    mbuf.close()
                # early drain: rounds no future morsel can touch
                t0 = time.perf_counter()
                while (int(cum.min()) >= (next_drain + 1) * C
                       and next_drain in send_chunks):
                    _drain_round(next_drain)
                    rounds_overlapped += 1
                    next_drain += 1
                drain_ms += (time.perf_counter() - t0) * 1e3

            if first_map[0] is None:
                raise ValueError(
                    "exchange_stream needs at least one morsel (the "
                    "stream defines the output schema)")
            cmax = int(cum.max())
            rounds = max(1, -(-cmax // C))
            t0 = time.perf_counter()
            for rr in range(next_drain, rounds):
                _drain_round(rr)
            drain_ms += (time.perf_counter() - t0) * 1e3

            sent = int(cum.sum())
            if received != sent:
                self.registry.metrics.record_dropped(abs(sent - received))
                raise ShuffleError(
                    f"shuffle {sid}: lossless invariant violated "
                    f"(sent={sent} received={received} "
                    f"rounds={rounds})")
            if len(recv) == 1:
                final_batch, final_occ = _unpack_chunk_tree(
                    *recv[0].get(), wire_plan, wire_treedef, C, None)
            else:
                parts = [
                    _unpack_chunk_tree(*b.get(), wire_plan, wire_treedef,
                                       C, None)
                    for b in recv]
                concat = _concat_step(mesh, axis, len(parts))
                final_batch, final_occ = concat(*parts)
        finally:
            for c in send_chunks.values():
                c.close()
            for b in recv:
                b.close()

        spilled = 0
        if spill_base is not None:
            after = _spill_snapshot()
            spilled = (after - spill_base) if after is not None else 0
        # the materialized planner over the FINAL counts supplies the
        # skew diagnostics; rounds/capacity record what actually ran
        plan = plan_rounds(cum, round_rows=round_rows)
        # zone-map skip accounting rides the source (MorselSource fills
        # it when a predicate pruned the stream; plain iterables read 0).
        # The counters describe the source's ONE skip decision at
        # construction time, so a reused source (replays are re-runnable)
        # attributes them to its FIRST exchange only — re-recording the
        # same counts would inflate the registry aggregate.
        blocks_skipped = int(getattr(morsels, "blocks_skipped", 0))
        blocks_scanned = int(getattr(morsels, "blocks_scanned", 0))
        if getattr(morsels, "_zone_counts_recorded", False):
            blocks_skipped = blocks_scanned = 0
        else:
            try:
                morsels._zone_counts_recorded = True
            except AttributeError:
                pass  # plain iterables carry no counters to double-count
        info = ShuffleInfo(
            shuffle_id=sid, rounds=rounds, capacity=C,
            rows_moved=received, bytes_moved=bytes_moved,
            spilled_bytes=spilled, skew_ratio=plan.skew_ratio,
            oob_rows=oob_total, recovered_partitions=recovered[0],
            streamed=True, morsels=n_morsels,
            rounds_overlapped=rounds_overlapped,
            decode_ms=decode_ms, drain_ms=drain_ms,
            compressed_bytes_saved=compressed_saved,
            blocks_skipped=blocks_skipped, blocks_scanned=blocks_scanned)
        self.registry.record(info)
        return ShuffleResult(
            batch=final_batch, occupancy=final_occ, shuffle_id=sid,
            rounds=rounds, capacity=C, rows_moved=received,
            bytes_moved=bytes_moved, spilled_bytes=spilled,
            skew_ratio=plan.skew_ratio, oob_rows=oob_total,
            recovered_partitions=recovered[0], streamed=True,
            morsels=n_morsels, rounds_overlapped=rounds_overlapped,
            decode_ms=decode_ms, drain_ms=drain_ms,
            compressed_bytes_saved=compressed_saved,
            blocks_skipped=blocks_skipped, blocks_scanned=blocks_scanned)

    def plan(self, counts, round_rows: Optional[int] = None) -> RoundPlan:
        """Expose the planner on the service for callers that fetched
        their own count matrix."""
        return plan_rounds(counts, round_rows=round_rows)

    # -- internals ------------------------------------------------------
    def _lineage_factory(self, sid: int, recovered):
        """The per-exchange lineage wrapper: every restore draws on the
        shared ``shuffle_max_recoveries`` budget and is counted live.

        ``adopt`` plugs the durable tier under the lineage closure via
        :func:`~.buffers.store_recompute`: a committed, CRC-verified
        store entry restores the buffer without re-running the closure;
        only a store miss (or a fully-quarantined shard) re-runs it —
        each outcome counted (``adopted_shards`` / ``lineage_rebuilds``)
        on top of the live ``recovered_partitions``."""
        from .. import config

        max_recoveries = int(config.get("shuffle_max_recoveries"))

        def _lineage(rebuild, what, adopt=None):
            inner = store_recompute(
                adopt, rebuild,
                on_adopt=self.registry.metrics.record_adopted,
                on_rebuild=self.registry.metrics.record_lineage_rebuild)

            def run():
                if recovered[0] >= max_recoveries:
                    raise ShuffleError(
                        f"shuffle {sid}: {what} lost or corrupt and the "
                        f"recovery budget is exhausted (max_recoveries="
                        f"{max_recoveries}; see shuffle_max_recoveries)")
                recovered[0] += 1
                self.registry.metrics.record_recovered()
                return inner()
            return run
        return _lineage

    def _run_round(self, drain, map_buf: PartitionBuffer, r: int,
                   refs=None):
        """One retryable round: arena pressure runs the spill ladder
        (RetryOOM → cross-task eviction → retry), transport faults are
        re-driven a bounded number of times from the intact buffers."""

        def round_step():
            _io_probe()
            tree, cnts = map_buf.get()
            args = (tree, cnts, jnp.int32(r))
            if refs is not None:
                args = args + (refs,)
            out, occ, got, residual = drain(*args)
            # fetching the scalars forces the round to execute HERE, so
            # real device OOMs surface inside the retry ladder
            got_n = int(np.asarray(jax.device_get(got)).sum())
            res_n = int(np.asarray(jax.device_get(residual)).sum())
            return out, occ, got_n, res_n

        # one host-driven drain round, retries and all
        with profiler.span("shuffle.round", round=r):
            for attempt in range(_IO_RETRIES + 1):
                try:
                    return run_with_retry(round_step)
                except faultinj.ShuffleIOError:
                    self.registry.metrics.record_io_failure()
                    if attempt == _IO_RETRIES:
                        raise
