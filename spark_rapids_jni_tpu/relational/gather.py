"""Row gather for every column representation.

The workhorse behind sort / filter-compaction / join materialization and
the plan's exchanges: one permutation (or index) vector applied to each
buffer of each column.  On TPU this lowers to XLA gathers, which
vectorize on the VPU; the string char matrix gathers whole padded rows (a
2-D gather with a broadcast index).

A gather costs the chip by the index, whatever its source's width
(PERF.md section 6), so :func:`gather_batch` moves the validity of all
its columns as one packed ``uint32`` word a row (bit i: the i-th
column's validity; every 32 columns take one more word) and not one
``bool`` buffer each.  :func:`validity_gathers` counts the validity
buffers that row gathers of more than 4096 indices move
(``plan.plan_cache_metrics()["validity_gathers"]``).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from ..columnar.column import Column, ColumnBatch, Decimal128Column, StringColumn
from ..columnar.encoded import (
    BitPackedColumn,
    DictionaryColumn,
    FrameOfReferenceColumn,
    RunLengthColumn,
    gather_bitpacked,
)

# representations whose validity rides the packed word
_PACKED = (Column, Decimal128Column, StringColumn, DictionaryColumn)
_WORD_BITS = 32
# a gather of at most this many indices is a group fetch, not a row
# gather (the sort engine's head: relational.aggregate.sortscan_head)
_COUNTED_ABOVE = 4096

_VALIDITY_GATHERS = [0]


def validity_gathers() -> int:
    """Validity buffers (a column's ``bool`` or a packed word) that row
    gathers of more than 4096 indices have traced in this process.  The
    plan compiler notes a plan's share
    (``plan.plan_cache_metrics()["validity_gathers"]``)."""
    return _VALIDITY_GATHERS[0]


def count_validity_gather(indices: int) -> None:
    """Note one validity buffer moved by a gather of ``indices``."""
    if indices > _COUNTED_ABOVE:
        _VALIDITY_GATHERS[0] += 1


def _decoded(col):
    # runs / FoR blocks do not survive an arbitrary permutation: decode
    # here (a sanctioned materialization point) so neither flows deeper
    if isinstance(col, (RunLengthColumn, FrameOfReferenceColumn)):
        return col.decode()
    return col


def _with_rows(col, idx, v):
    """``col``'s data at rows ``idx`` beside the gathered validity ``v``."""
    if isinstance(col, DictionaryColumn):
        # gather CODES; the dictionary (and its token) ride along, so the
        # output stays encoded through compaction and join materialization
        return dataclasses.replace(col, codes=col.codes[idx], validity=v)
    if isinstance(col, StringColumn):
        return StringColumn(col.chars[idx], col.lengths[idx] * v, v, col.dtype)
    if isinstance(col, Decimal128Column):
        return Decimal128Column(col.limbs[idx], v, col.dtype)
    return Column(col.data[idx], v, col.dtype)


def gather_column(col, idx, valid=None):
    """Take rows ``idx`` (int32[m], clipped); rows where ``valid`` is False
    become nulls (used for padded filter/join outputs)."""
    col = _decoded(col)
    idx = jnp.clip(idx, 0, max(col.num_rows - 1, 0))
    count_validity_gather(idx.shape[0])
    if isinstance(col, BitPackedColumn):
        # the global reference DOES survive permutation: extract
        # residuals, take, repack — the output stays packed
        return gather_bitpacked(col, idx, valid)
    v = col.validity[idx]
    if valid is not None:
        v = v & valid
    return _with_rows(col, idx, v)


def _gathered_validity(validities, idx, valid):
    """Each of ``validities`` at rows ``idx`` (and ``valid``), moved as
    packed words: one gather for every 32 buffers."""
    out = []
    for start in range(0, len(validities), _WORD_BITS):
        part = validities[start:start + _WORD_BITS]
        word = part[0].astype(jnp.uint32)
        for bit, v in enumerate(part[1:], 1):
            word = word | (v.astype(jnp.uint32) << bit)
        count_validity_gather(idx.shape[0])
        word = word[idx]
        for bit in range(len(part)):
            v = ((word >> bit) & 1) != 0
            out.append(v if valid is None else v & valid)
    return out


def gather_batch(batch: ColumnBatch, idx, valid=None) -> ColumnBatch:
    """:func:`gather_column` of every column of ``batch``, the validity of
    its plain, decimal, string and dictionary columns moved together as
    packed words where there are two or more of them."""
    cols = [_decoded(c) for c in batch.columns]
    packed = [c for c in cols if isinstance(c, _PACKED)]
    if len(packed) < 2:
        return ColumnBatch({name: gather_column(col, idx, valid)
                            for name, col in zip(batch.names, cols)})
    idx = jnp.clip(idx, 0, max(batch.num_rows - 1, 0))
    validity = iter(_gathered_validity([c.validity for c in packed],
                                       idx, valid))
    return ColumnBatch({
        name: (_with_rows(col, idx, next(validity))
               if isinstance(col, _PACKED) else gather_column(col, idx, valid))
        for name, col in zip(batch.names, cols)})
