"""Multi-key sort via ``lax.sort`` over order-preserving radix keys.

Spark semantics: per-key ascending/descending and nulls-first/last.  The key
lowering (:mod:`keys`) yields uint32 arrays whose unsigned lexicographic
order is Spark's; descending keys are bitwise-complemented.  ``lax.sort``
with ``num_keys=len(keys)+1`` co-sorts an iota operand that becomes the row
permutation — XLA lowers this to its vectorized bitonic sorter on TPU.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from ..columnar.column import ColumnBatch
from . import keys as K
from .gather import gather_batch


@dataclasses.dataclass(frozen=True)
class SortKey:
    name: str
    ascending: bool = True
    nulls_first: bool = True


def order_words(batch: ColumnBatch, sort_keys: Sequence[SortKey]) -> list:
    """uint32 arrays whose unsigned lexicographic ascending order is the
    order of ``sort_keys``."""
    ops = []
    for sk in sort_keys:
        col = batch[sk.name]
        # Spark default: nulls first when ascending, last when descending;
        # callers pass the explicit flag.  Descending complements key bits,
        # including the null flag, so compute the flag for ascending order.
        flag_first = sk.nulls_first if sk.ascending else not sk.nulls_first
        arrays = [K.null_flag(col, flag_first)]
        # zero null rows' data keys: deterministic (stable) order among nulls
        arrays += [
            jnp.where(col.validity, k, jnp.zeros((), k.dtype))
            for k in K.column_radix_keys(col, equality=False)
        ]
        if not sk.ascending:
            arrays = [~a for a in arrays]
        ops.extend(arrays)
    return ops


def sort_permutation(batch: ColumnBatch, sort_keys: Sequence[SortKey]):
    """int32[n] permutation ordering the batch by the given keys (stable)."""
    ops = order_words(batch, sort_keys)
    n = batch.num_rows
    iota = jnp.arange(n, dtype=jnp.int32)
    res = jax.lax.sort(tuple(ops) + (iota,), num_keys=len(ops), is_stable=True)
    return res[-1]


def sort_by(batch: ColumnBatch, sort_keys: Sequence[SortKey]) -> ColumnBatch:
    return gather_batch(batch, sort_permutation(batch, sort_keys))


def top_k_rows(batch: ColumnBatch, sort_keys: Sequence[SortKey], k: int,
               live=None):
    """The rows that ``sort_by`` would put first, without the sort:
    ``(int32[k] row ids, count)``, ``count = min(k, live rows)`` of them
    meaningful, in the order of ``sort_keys``; among rows equal in every
    key the earliest row comes first (what the stable sort gives).

    ``k`` rounds of selection, each a chain of whole-column reductions:
    among the rows still standing the least first key word, among those
    with it the least second, and so on, then the first row left.  A
    round reads each key word once and moves nothing, where the sort
    permutes every word of every row and a gather then moves every
    column: for a ``k`` of ten over millions of row slots it is a few
    hundred microseconds a word."""
    n = batch.num_rows
    if n == 0:
        raise ValueError("top_k_rows over a batch of no rows")
    words = order_words(batch, sort_keys)
    iota = jnp.arange(n, dtype=jnp.int32)
    alive = jnp.ones((n,), jnp.bool_) if live is None \
        else live.astype(jnp.bool_)
    last = jnp.uint32(0xFFFFFFFF)

    def pick(i, carry):
        taken, rows = carry
        m = alive & ~taken
        for w in words:
            m = m & (w == jnp.min(jnp.where(m, w, last)))
        first = jnp.min(jnp.where(m, iota, jnp.int32(n)))  # n: none left
        return taken | (iota == first), rows.at[i].set(first)

    _taken, rows = jax.lax.fori_loop(
        0, k, pick, (jnp.zeros((n,), jnp.bool_),
                     jnp.full((k,), n, jnp.int32)))
    count = jnp.minimum(jnp.sum(alive, dtype=jnp.int32), jnp.int32(k))
    return rows, count
