"""Filter: boolean-mask row selection with static-shape compaction.

XLA demands static shapes, so ``compact`` keeps the input length and returns
``(batch, count)``: selected rows are moved (stably) to the front, ``count``
is a device scalar, and trailing rows are nulled out.  Downstream kernels
either honor ``count`` or operate harmlessly on null padding — the same
discipline the reference applies to its ≤2GB batch splits (SURVEY.md §5
"long-context analogues").
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..columnar.column import ColumnBatch
from ..columnar.encoded import predicate_mask  # noqa: F401  (encoded filter
# path: evaluate the predicate over the d-entry dictionary once, map to
# rows with one gather — re-exported here as part of the filter API)
from ..columnar.encoded import packed_filter_mask  # noqa: F401  (packed
# filter path: compare u32 residual lanes against the once-transformed
# literal, no decode — the compressed-domain half of the filter API)
from .gather import gather_batch
from .keys import scan_sum


def selection_indices(mask):
    """(idx int32[n], count int32): stable front-compaction of True rows.

    ``idx`` is a true permutation: ``idx[:count]`` are the positions of the
    True rows in order, ``idx[count:]`` the False rows' positions in order.
    """
    n = mask.shape[0]
    mask = mask.astype(jnp.bool_)
    count = mask.sum(dtype=jnp.int32)
    # destination of each row: selected rows pack to the front by prefix
    # count, unselected rows follow — one permutation scatter instead of an
    # argsort (TPU sorts are the pipeline bottleneck; cumsum+scatter is not)
    sel_pos = scan_sum(mask.astype(jnp.int32)) - 1
    unsel_pos = count + scan_sum((~mask).astype(jnp.int32)) - 1
    pos = jnp.where(mask, sel_pos, unsel_pos)
    iota = jnp.arange(n, dtype=jnp.int32)
    idx = jnp.zeros((n,), jnp.int32).at[pos].set(iota)
    return idx, count


def compact(batch: ColumnBatch, mask) -> tuple:
    """Move rows where ``mask`` is True to the front; null out the tail."""
    idx, count = selection_indices(mask)
    valid = jnp.arange(idx.shape[0], dtype=jnp.int32) < count
    return gather_batch(batch, idx, valid), count


def apply_mask(batch: ColumnBatch, mask) -> ColumnBatch:
    """Null out rows where ``mask`` is False (no movement).

    The cheap filter: keeps shapes and row positions, so it fuses into
    surrounding elementwise work; use ``compact`` only when downstream cost
    depends on live row count.
    """
    mask = mask.astype(jnp.bool_)
    return ColumnBatch(
        {
            name: dataclasses.replace(col, validity=col.validity & mask)
            for name, col in zip(batch.names, batch.columns)
        }
    )
