"""Multi-key sort via ``lax.sort`` over order-preserving radix keys.

Spark semantics: per-key ascending/descending and nulls-first/last.  The key
lowering (:mod:`keys`) yields uint32 arrays whose unsigned lexicographic
order is Spark's; descending keys are bitwise-complemented.  The words are
packed end to end and ``lax.sort`` sorts them with an iota as the last key,
which becomes the row permutation — XLA lowers this to its vectorized
bitonic sorter on TPU.
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


def order_fields(batch: ColumnBatch, sort_keys: Sequence[SortKey]) -> list:
    """``(uint32 array, bits)`` fields whose unsigned lexicographic
    ascending order is the order of ``sort_keys``: each key's null flag, a
    0/1 in one bit, then its radix words in 32 bits each.  A descending
    key's fields are complemented whole, so its flag is ``~flag``, whose
    lowest bit is the complemented bit."""
    fields = []
    for sk in sort_keys:
        col = batch[sk.name]
        # Spark default: nulls first when ascending, last when descending;
        # callers pass the explicit flag.  Descending complements key bits,
        # including the null flag, so compute the flag for ascending order.
        flag_first = sk.nulls_first if sk.ascending else not sk.nulls_first
        part = [(K.null_flag(col, flag_first), 1)]
        # zero null rows' data keys: deterministic order among nulls
        part += [
            (jnp.where(col.validity, k, jnp.zeros((), k.dtype)), 32)
            for k in K.column_radix_keys(col, equality=False)
        ]
        if not sk.ascending:
            part = [(~f, bits) for f, bits in part]
        fields.extend(part)
    return fields


def order_words(batch: ColumnBatch, sort_keys: Sequence[SortKey]) -> list:
    """uint32 arrays whose unsigned lexicographic ascending order is the
    order of ``sort_keys``: :func:`order_fields`, a word each."""
    return [f for f, _bits in order_fields(batch, sort_keys)]


def sort_permutation(batch: ColumnBatch, sort_keys: Sequence[SortKey],
                     live=None):
    """int32[n] permutation ordering the batch by ``sort_keys``, rows equal
    in every key in their own order (what a stable sort gives), and the
    rows that ``live`` (bool[n]) says are dead after every live one.

    One unstable sort: :func:`order_fields` laid end to end
    (:func:`keys.pack_fields`: a null flag one bit, a radix word its 32,
    a dead row's flag leading), with the row id as the last key, which
    makes the order total.  Two int64 keys are 5 words where they were 6,
    and the v5e compiler's time for a sort grows with its key operands
    (PERF.md section 6, PR 35)."""
    fields = [] if live is None else [
        ((~live.astype(jnp.bool_)).astype(jnp.uint32), 1)]
    fields += [(f & jnp.uint32(1) if bits == 1 else f, bits)
               for f, bits in order_fields(batch, sort_keys)]
    words = K.pack_fields(fields)
    iota = jnp.arange(batch.num_rows, dtype=jnp.int32)
    return jax.lax.sort(tuple(words) + (iota,), num_keys=len(words) + 1,
                        is_stable=False)[-1]


def sort_by(batch: ColumnBatch, sort_keys: Sequence[SortKey],
            live=None) -> ColumnBatch:
    """The batch's rows in :func:`sort_permutation`'s order."""
    return gather_batch(batch, sort_permutation(batch, sort_keys, live))


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
