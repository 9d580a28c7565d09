"""Row gather for every column representation.

The workhorse behind sort / filter-compaction / join materialization and
the plan's exchanges: one permutation (or index) vector applied to each
buffer of each column.  On TPU this lowers to XLA gathers, which
vectorize on the VPU; the string char matrix gathers whole padded rows (a
2-D gather with a broadcast index).

A gather costs the chip by the index, hardly by its source's width
(PERF.md section 6).  So :func:`gather_batch` moves the validity of all
its columns as one packed ``uint32`` word a row (bit i: the i-th
column's validity; every 32 columns take one more word) and not one
``bool`` buffer each.  A row gather (more than 4096 indices) out of a
source of 2^19 rows or more moves the fixed-width data as ``uint32``
words too: a 32-bit buffer one word a row, a 64-bit integer buffer two
(its low and high halves), a decimal's two limbs four.  With the validity
words they are laid one above the other as a ``[words, rows]`` matrix of
at most ``_MATRIX_WORDS`` words (one more for each more), gathered once
and taken apart again.  Char matrices, ``float64`` data (the v5e
compiler refuses ``bitcast f64 -> u64``), data narrower than 32 bits and
bit-packed columns gather on their own.  :func:`gather_rows` does the
same for bare buffers (the sort engine's scans at its group ends and its
aggregated columns along its sort).

:func:`validity_gathers` counts the validity buffers that row gathers
move (``plan.plan_cache_metrics()["validity_gathers"]``), a packed word
one whether it rides alone or in a matrix; :func:`row_gathers` the
gather operations they make (``plan.plan_cache_metrics()["row_gathers"]``),
a matrix one and a buffer gathered on its own one.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from jax import lax

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
# the most words one gathered matrix holds: one tile (PERF.md section 6)
_MATRIX_WORDS = 8
# the fewest rows a matrix's source has: out of fewer the v5e compiler
# gathers words as selects, or pads the gathered matrix to 128 lanes
# (2 GiB for 3 words at 2^22 indices; PERF.md section 6)
_MATRIX_FROM_ROWS = 1 << 19
# the u32 words a row of each buffer dtype that a matrix carries
_WORDS_PER_ROW = {jnp.dtype(jnp.int32): 1, jnp.dtype(jnp.uint32): 1,
                  jnp.dtype(jnp.float32): 1, jnp.dtype(jnp.int64): 2,
                  jnp.dtype(jnp.uint64): 2}

_VALIDITY_GATHERS = [0]
_ROW_GATHERS = [0]
_GATHER_OPS = [0]


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


def row_gathers() -> int:
    """Gather operations that row gathers of more than 4096 indices have
    traced in this process: a matrix of words one, a buffer gathered on
    its own one.  The plan compiler notes a plan's share
    (``plan.plan_cache_metrics()["row_gathers"]``)."""
    return _ROW_GATHERS[0]


def gather_ops() -> int:
    """Gather operations of any number of indices that gathers through
    this module have traced in this process."""
    return _GATHER_OPS[0]


def count_row_gather(indices: int, gathers: int = 1) -> None:
    """Note ``gathers`` gather operations of ``indices`` each."""
    _GATHER_OPS[0] += gathers
    if indices > _COUNTED_ABOVE:
        _ROW_GATHERS[0] += gathers


def take_rows(buf, idx):
    """``buf`` at rows ``idx`` (clipped): one gather, counted."""
    count_row_gather(idx.shape[0])
    return buf[idx]


def _decoded(col):
    # runs / FoR blocks do not survive an arbitrary permutation: decode
    # here (a sanctioned materialization point) so neither flows deeper
    if isinstance(col, (RunLengthColumn, FrameOfReferenceColumn)):
        return col.decode()
    return col


def _buffers(col):
    """The row-indexed data buffers of a ``_PACKED`` column."""
    if isinstance(col, DictionaryColumn):
        return [col.codes]
    if isinstance(col, StringColumn):
        return [col.chars, col.lengths]
    if isinstance(col, Decimal128Column):
        return [col.limbs]
    return [col.data]


def _rebuilt(col, bufs, v):
    """``col`` with its data ``bufs`` gathered, beside the gathered
    validity ``v``."""
    if isinstance(col, DictionaryColumn):
        # gather CODES; the dictionary (and its token) ride along, so the
        # output stays encoded through compaction and join materialization
        return dataclasses.replace(col, codes=bufs[0], validity=v)
    if isinstance(col, StringColumn):
        return StringColumn(bufs[0], bufs[1] * v, v, col.dtype)
    if isinstance(col, Decimal128Column):
        return Decimal128Column(bufs[0], v, col.dtype)
    return Column(bufs[0], v, col.dtype)


def _with_rows(col, idx, v):
    """``col``'s data at rows ``idx`` beside the gathered validity ``v``."""
    return _rebuilt(col, [take_rows(b, idx) for b in _buffers(col)], v)


def gather_column(col, idx, valid=None):
    """Take rows ``idx`` (int32[m], clipped); rows where ``valid`` is False
    become nulls (used for padded filter/join outputs)."""
    col = _decoded(col)
    idx = jnp.clip(idx, 0, max(col.num_rows - 1, 0))
    count_validity_gather(idx.shape[0])
    if isinstance(col, BitPackedColumn):
        # the global reference DOES survive permutation: extract
        # residuals, take, repack — the output stays packed
        count_row_gather(idx.shape[0], 2)
        return gather_bitpacked(col, idx, valid)
    v = take_rows(col.validity, idx)
    if valid is not None:
        v = v & valid
    return _with_rows(col, idx, v)


def _validity_words(validities):
    """``validities`` packed, 32 to a ``uint32`` word (bit i: the i-th)."""
    words = []
    for start in range(0, len(validities), _WORD_BITS):
        part = validities[start:start + _WORD_BITS]
        word = part[0].astype(jnp.uint32)
        for bit, v in enumerate(part[1:], 1):
            word = word | (v.astype(jnp.uint32) << bit)
        words.append(word)
    return words


def _validity_bits(words, count, valid):
    """The first ``count`` validity bits of ``words`` (and ``valid``)."""
    out = []
    for i in range(count):
        v = ((words[i // _WORD_BITS] >> (i % _WORD_BITS)) & 1) != 0
        out.append(v if valid is None else v & valid)
    return out


def _gathered_validity(validities, idx, valid):
    """Each of ``validities`` at rows ``idx`` (and ``valid``), moved as
    packed words: one gather for every 32 buffers."""
    words = _validity_words(validities)
    for _ in words:
        count_validity_gather(idx.shape[0])
    return _validity_bits([take_rows(w, idx) for w in words],
                          len(validities), valid)


def _words_a_row(buf) -> int:
    """The u32 words a row of ``buf`` takes in a matrix; 0 where it
    gathers on its own."""
    return (_WORDS_PER_ROW.get(jnp.dtype(buf.dtype), 0)
            * math.prod(buf.shape[1:]))


def _to_words(buf):
    """``buf`` as ``u32[n]`` words: a 32-bit lane's bits, or a 64-bit
    lane's low and high halves."""
    flat = buf.reshape(buf.shape[0], -1)
    if jnp.dtype(buf.dtype).itemsize == 4:
        return [lax.bitcast_convert_type(flat[:, j], jnp.uint32)
                for j in range(flat.shape[1])]
    u = lax.bitcast_convert_type(flat, jnp.uint64)
    return [half for j in range(flat.shape[1])
            for half in (u[:, j].astype(jnp.uint32),
                         (u[:, j] >> 32).astype(jnp.uint32))]


def _from_words(words, like):
    """The inverse of :func:`_to_words`: a buffer of ``like``'s dtype and
    trailing shape, rows as many as the words have."""
    dtype = jnp.dtype(like.dtype)
    if dtype.itemsize == 4:
        lanes = [lax.bitcast_convert_type(w, dtype) for w in words]
    else:
        lanes = [lax.bitcast_convert_type(
            (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64), dtype)
            for lo, hi in zip(words[::2], words[1::2])]
    if like.ndim == 1:
        return lanes[0]
    return jnp.stack(lanes, axis=1).reshape(
        (lanes[0].shape[0],) + like.shape[1:])


def _gathered_words(words, idx):
    """``u32[n]`` ``words`` at rows ``idx``: one gather of a ``[k, n]``
    matrix, taken on its rows axis, for every ``_MATRIX_WORDS`` of them.
    Rows stay the minor axis, so the chip pads ``k`` to 8 sublanes; the
    v5e compiler lays a ``[n, k]`` matrix gathered in a ``cond``'s branch
    out rows-major, ``k`` padded to 128 lanes."""
    out = []
    for start in range(0, len(words), _MATRIX_WORDS):
        part = words[start:start + _MATRIX_WORDS]
        if len(part) == 1:
            out.append(take_rows(part[0], idx))
        else:
            count_row_gather(idx.shape[0])
            out.extend(jnp.stack(part)[:, idx])
    return out


def _taken_as_words(bufs, words, idx):
    """Each of ``bufs`` at rows ``idx``, then each of the ``u32[n]``
    ``words``: the fixed-width buffers' words and ``words`` through
    :func:`_gathered_words`, every other buffer on its own."""
    got = iter(_gathered_words([w for b in bufs if _words_a_row(b)
                                for w in _to_words(b)] + list(words), idx))
    return [_from_words([next(got) for _ in range(k)], b)
            if (k := _words_a_row(b)) else take_rows(b, idx)
            for b in bufs] + list(got)


def _row_gather(indices: int, rows: int) -> bool:
    """Whether a gather of ``indices`` out of ``rows`` rows moves its
    words as matrices: a row gather out of a source the v5e compiler
    lays out rows-minor."""
    return indices > _COUNTED_ABOVE and rows >= _MATRIX_FROM_ROWS


def gather_rows(bufs, validities, idx):
    """``bufs`` and the ``bool`` ``validities`` (arrays of one row count)
    at rows ``idx`` (in range): in a row gather out of a source of at
    least ``_MATRIX_FROM_ROWS`` rows, the fixed-width buffers' words and
    the validities, packed 32 to a word, as matrices of at most
    ``_MATRIX_WORDS`` words (:func:`_gathered_words`), the rest one
    gather each; otherwise one gather a buffer.  Every gather counted."""
    bufs, validities = list(bufs), list(validities)
    m = idx.shape[0]
    rows = (bufs + validities)[0].shape[0] if bufs or validities else 0
    if not _row_gather(m, rows):
        for _ in validities:
            count_validity_gather(m)
        return ([take_rows(b, idx) for b in bufs],
                [take_rows(v, idx) for v in validities])
    vwords = _validity_words(validities)
    for _ in vwords:
        count_validity_gather(m)
    got = _taken_as_words(bufs, vwords, idx)
    return (got[:len(bufs)],
            _validity_bits(got[len(bufs):], len(validities), None))


def _gather_matrix(cols, packed, idx, valid):
    """:func:`gather_batch` of a row gather: the fixed-width buffers and
    the validity words of ``packed`` through one matrix (or a few), every
    other buffer and column on its own."""
    bufs = [_buffers(c) for c in packed]
    vwords = _validity_words([c.validity for c in packed])
    for _ in vwords:
        count_validity_gather(idx.shape[0])
    got = iter(_taken_as_words([b for bs in bufs for b in bs], vwords, idx))
    taken = [[next(got) for _ in bs] for bs in bufs]
    validity = iter(_validity_bits(list(got), len(packed), valid))
    rebuilt = iter(_rebuilt(c, out, next(validity))
                   for c, out in zip(packed, taken))
    return [next(rebuilt) if isinstance(c, _PACKED)
            else gather_column(c, idx, valid) for c in cols]


def gather_batch(batch: ColumnBatch, idx, valid=None) -> ColumnBatch:
    """:func:`gather_column` of every column of ``batch``, the validity of
    its plain, decimal, string and dictionary columns moved together as
    packed words where there are two or more of them; in a row gather
    (more than 4096 indices) of two or more words a row, their
    fixed-width data and validity words moved as one matrix (from a
    source of at least ``_MATRIX_FROM_ROWS`` rows)."""
    cols = [_decoded(c) for c in batch.columns]
    packed = [c for c in cols if isinstance(c, _PACKED)]
    words = (sum(_words_a_row(b) for c in packed for b in _buffers(c))
             + math.ceil(len(packed) / _WORD_BITS))
    if _row_gather(idx.shape[0], batch.num_rows) and words >= 2:
        idx = jnp.clip(idx, 0, max(batch.num_rows - 1, 0))
        return ColumnBatch(dict(zip(batch.names,
                                    _gather_matrix(cols, packed, idx,
                                                   valid))))
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
