"""Spark-exact shuffle partition assignment.

Spark's ``HashPartitioning`` computes ``Pmod(Murmur3Hash(keys, 42), P)``;
the reference repo's murmur3 kernel exists precisely to keep this assignment
bit-identical between CPU and accelerator (reference ``murmur_hash.cu:187``,
``Hash.java``).  We reuse :func:`ops.hashing.murmur_hash3_32` and apply
Spark's ``pmod`` (non-negative remainder) on the int32 hash.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from ..ops.hashing import murmur_hash3_32


def spark_partition_id(
    key_columns: Sequence,
    num_partitions: int,
    row_valid=None,
) -> jnp.ndarray:
    """int32[n] partition ids in [0, P); padding rows get P (routed nowhere).

    ``row_valid`` marks occupied rows (compaction/filter padding is sent to
    the out-of-range pseudo-partition so the exchange drops it).
    """
    h = murmur_hash3_32(key_columns, seed=42).data  # int32, Spark seed
    p = jnp.int32(num_partitions)
    # Spark's pmod(h, p): jnp % already yields a non-negative remainder for
    # p > 0 (sign of divisor), which equals pmod exactly
    pid = h % p
    if row_valid is not None:
        pid = jnp.where(row_valid, pid, p)
    return pid


# auto-engine bounds: the counting sort materializes an [n, num_slots]
# int32 one-hot + same-size cumsum transient; past these the memory/
# bandwidth cost outgrows the O(n) sort it replaces, so 'auto' falls
# back to lax.sort.  The cell cap bounds the transients to ~268MB
# (2 x 4B x 2^25 cells) regardless of row count — a 2M-row 8-partition
# exchange (18M cells) stays on the fast path, the reviewer's 2M x 64
# case (128M cells, ~1GB) does not.
_COUNTING_MAX_SLOTS = 64
_COUNTING_MAX_CELLS = 1 << 25


def regroup_order(pid, num_slots: int, engine: str = "auto",
                  secondary=None, lead_bits=None):
    """Stable permutation that orders rows by partition id — the local
    leg every shuffle pays before its all-to-all.

    Bit-identical to ``jnp.argsort(pid, stable=True)`` for ``pid`` values
    in ``[0, num_slots)`` (callers clip; ``num_slots`` includes any
    pseudo-partition used for dead rows).  Engine is a hardware fact,
    same pattern as the relational domain-aggregation engines (r4):

    * ``'sort'`` — one stable ``lax.sort``: the TPU path (a 2-operand
      sort measured ~6 ms per 2M rows on v5e, BASELINE.md r2).
    * ``'scatter'`` — counting sort: per-partition ranks from one
      ``[n, num_slots]`` one-hot cumsum, plus ONE int32 scatter to
      invert the destination map.  The CPU path: ``lax.sort`` is
      XLA-CPU's worst primitive (r4 q6 engine table), while linear
      passes and scatters are its best.  Measured r5 (64K rows,
      1-core CPU): exchange leg 17.7 ms -> counting sort ~2 ms.
    * ``'auto'`` — scatter on CPU when the one-hot stays small (few
      slots AND bounded n*num_slots cells), sort otherwise.

    ``secondary`` (optional): extra uint32 sort operands ordered AFTER
    ``pid`` — an exchange whose regroup also orders rows by their
    aggregation key words, so a downstream sort-engine ``group_by`` can
    run ``assume_grouped=True`` instead of re-sorting rows it just
    received in key order (Spark's exchange-before-HashAggregate shape,
    fused into ONE row-sized sort).  Secondary operands force the sort
    engine: a counting sort has no within-slot key order.
    ``lead_bits`` says how few bits the first of them holds (a null flag:
    1), so that it and ``pid`` make one operand: on the v5e a sort's
    compile time grows with every key operand (PERF.md section 6).
    """
    import jax

    n = pid.shape[0]
    pid = pid.astype(jnp.int32)
    if secondary is not None:
        engine = "sort"
    if engine == "auto":
        engine = ("scatter" if jax.default_backend() == "cpu"
                  and num_slots <= _COUNTING_MAX_SLOTS
                  and n * num_slots <= _COUNTING_MAX_CELLS else "sort")
    if engine == "sort":
        if secondary is not None:
            secondary = tuple(secondary)
            lead = (pid.astype(jnp.uint32),)
            if lead_bits is not None and secondary:
                # the partition id above the first word's few bits: one
                # sort operand where there were two
                lead = ((lead[0] << jnp.uint32(lead_bits))
                        | secondary[0].astype(jnp.uint32),)
                secondary = secondary[1:]
            # the row id as the last key makes the order total: the
            # stable sort's answer from the unstable sort, which the v5e
            # compiler builds in half the time (as the aggregate's sort)
            ops = lead + secondary + (jnp.arange(n, dtype=jnp.int32),)
            return jax.lax.sort(ops, num_keys=len(ops),
                                is_stable=False)[-1]
        return jnp.argsort(pid, stable=True).astype(jnp.int32)
    if engine != "scatter":
        raise ValueError(f"unknown regroup engine {engine!r}")
    slots = jnp.arange(num_slots, dtype=jnp.int32)
    oh = (pid[:, None] == slots[None, :]).astype(jnp.int32)
    within = jnp.cumsum(oh, axis=0) - oh          # rank inside partition
    counts = within[-1] + oh[-1] if n > 0 else jnp.zeros(
        (num_slots,), jnp.int32)
    offsets = jnp.cumsum(counts) - counts         # exclusive
    dest = jnp.take_along_axis(
        within + offsets[None, :],
        jnp.clip(pid, 0, num_slots - 1)[:, None], axis=1)[:, 0]
    # dest is a bijection [n] -> [n]; invert it with one scatter to get
    # the gather permutation argsort would have produced
    return jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32))
