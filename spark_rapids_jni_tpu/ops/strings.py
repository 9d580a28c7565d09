"""String expression kernels for the string-heavy benchmark config.

The reference repo delegates plain string functions to libcudf (out of
tree); the driver's string/regex-heavy config (BASELINE.md #4) names
``substring`` alongside the in-tree ``regexp`` fast path and
``get_json_object``, so the Spark-exact substring lives here, and with it
``like``, the kernel of a plan's ``LIKE`` / ``NOT LIKE`` filter.

Semantics follow Spark's ``UTF8String.substringSQL`` (character-based,
1-based positions, negative position counts from the end, window clamped
to the string):

    substring('abc',  -5, 3) -> 'a'    (window [-2, 1) clamps to [0, 1))
    substring('abcd', -2, 3) -> 'cd'
    substring('abc',   0, 2) -> 'ab'   (pos 0 behaves like 1)
"""

from __future__ import annotations

import jax.numpy as jnp

from ..columnar.column import StringColumn
from .regex_rewrite import _decode_utf8


def left_compact_rows(mat, keep, engine: str = "auto"):
    """Stable left-compaction of kept cells per row; returns
    ``(compacted, counts)`` with the tail beyond each row's count
    zeroed.

    The engine is a hardware fact (same pattern as
    ``parallel.regroup_order``, r5): on CPU (``'scatter'``) a per-row
    counting compaction — rank kept cells with one masked cumsum,
    invert the destination map with ONE scatter — because a ``[n, L]``
    stable sort is XLA-CPU's worst primitive (the argsort formulation
    measured ~630 ms for 16K x 788 bytes in the qstr pipeline; the
    counting path is linear).  On accelerators (``'sort'``) the stable
    argsort stays: sorts lower natively on TPU while per-element
    scatters serialize (BASELINE.md r2 primitive costs).  ``'auto'``
    picks by backend; the explicit names exist for tests and A/Bs.
    """
    import jax

    if engine == "auto":
        engine = "scatter" if jax.default_backend() == "cpu" else "sort"
    if engine not in ("scatter", "sort"):
        raise ValueError(f"unknown compaction engine {engine!r}")
    n, L = mat.shape
    counts = jnp.sum(keep, axis=1).astype(jnp.int32)
    if engine == "scatter":
        ki = keep.astype(jnp.int32)
        within = jnp.cumsum(ki, axis=1) - ki       # rank among kept
        dest = jnp.where(keep, within, L)          # L = discard column
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        cols = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32)[None, :], (n, L))
        src = jnp.full((n, L + 1), L, jnp.int32).at[rows, dest].set(
            cols)[:, :L]
        padded = jnp.pad(mat, ((0, 0), (0, 1)))    # col L reads as 0
        out = jnp.take_along_axis(padded, src, axis=1)
    else:
        order = jnp.argsort(~keep, axis=1, stable=True)
        out = jnp.take_along_axis(mat, order, axis=1)
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    out = jnp.where(pos < counts[:, None], out,
                    jnp.zeros((), mat.dtype))
    return out, counts


def substring(col: StringColumn, pos: int, length: int = -1) -> StringColumn:
    """Character-based Spark substring; ``length < 0`` means "to the end".

    Works on the padded byte matrix: UTF-8 start bytes give each byte a
    character index (continuation bytes inherit their start byte's index),
    the [start, end) character window selects bytes, and
    :func:`left_compact_rows` left-compacts the survivors with the
    platform-appropriate engine.
    """
    from ..columnar.bucketed import BucketedStringColumn

    if isinstance(col, BucketedStringColumn):
        return col.apply(lambda b: substring(b, pos, length))
    chars, lengths, validity = col.chars, col.lengths, col.validity
    n, L = chars.shape
    posax = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_str = posax < lengths[:, None]

    _, _, is_start = _decode_utf8(chars)
    is_start = is_start & in_str
    # 0-based character index per byte (continuation bytes inherit)
    char_idx = jnp.cumsum(is_start.astype(jnp.int32), axis=1) - 1
    nchars = jnp.sum(is_start, axis=1).astype(jnp.int32)

    if pos > 0:
        s0 = jnp.full((n,), pos - 1, jnp.int32)
    elif pos < 0:
        s0 = nchars + pos
    else:
        s0 = jnp.zeros((n,), jnp.int32)
    if length < 0:
        e0 = jnp.full((n,), 2**31 - 1, jnp.int32)
    else:
        # window end BEFORE clamping the start (Spark: the negative-start
        # window loses the part hanging off the front of the string)
        e0 = s0 + length
    lo = jnp.maximum(s0, 0)

    keep = in_str & (char_idx >= lo[:, None]) & (char_idx < e0[:, None])
    out, out_len = left_compact_rows(chars, keep)
    return StringColumn(out, jnp.where(validity, out_len, 0), validity)


def like_segments(pattern: str) -> tuple:
    """``(segments, free_start, free_end)`` of a ``LIKE`` pattern: the
    literal texts between its ``%`` as UTF-8 bytes (empty ones, from
    ``%%``, dropped), and whether the pattern begins and ends with ``%``.
    ``_`` (one character) and the escape character ``\\`` are refused,
    naming the pattern."""
    for c, what in (("_", "'_' (one character)"), ("\\", "an escape")):
        if c in pattern:
            raise NotImplementedError(
                f"LIKE pattern {pattern!r}: {what} is not supported; only "
                "literal text between '%' is")
    segs = tuple(p.encode("utf-8") for p in pattern.split("%") if p)
    return segs, pattern.startswith("%"), pattern.endswith("%")


def like(col: StringColumn, pattern: str):
    """``bool[n]``: whether each row's string matches ``pattern`` under
    Spark's ``LIKE`` (``%`` any run of characters), the rows' validity not
    applied.

    Each literal segment must be found in order, without overlap, after
    the end of the one before; a pattern that does not begin (end) with
    ``%`` anchors its first (last) segment at the string's start (end).
    Found leftmost, a segment leaves the most room to the ones after it,
    so one pass a segment decides every row exactly.  A segment's places
    are ``m`` shifted byte comparisons over the padded ``[n, width]``
    matrix, kept where the segment ends within the row's length; its
    leftmost place is one lane reduction, in int16 where the width allows
    (half the bytes of int32).  Matching bytes of UTF-8 is matching
    characters: no segment can start inside a character, since a
    continuation byte starts no character."""
    segs, free_start, free_end = like_segments(pattern)
    chars, lengths = col.chars, col.lengths
    n, width = chars.shape
    if not segs:   # '%' alone matches every string, '' the empty one
        return jnp.ones((n,), jnp.bool_) if "%" in pattern else lengths == 0
    if sum(len(s) for s in segs) > width:
        return jnp.zeros((n,), jnp.bool_)
    # a place, and a place past it by a segment, fit int16 below 2^14
    place = jnp.int16 if width < 1 << 14 else jnp.int32
    length = lengths.astype(place)[:, None]
    found = jnp.ones((n,), jnp.bool_)
    nxt = jnp.zeros((n, 1), place)   # where the next segment may start
    for k, seg in enumerate(segs):
        m = len(seg)
        places = width - m + 1
        at = jnp.arange(places, dtype=place)[None, :]
        ok = (at >= nxt) & (at + m <= length)
        for j, byte in enumerate(seg):
            ok = ok & (chars[:, j:j + places] == byte)
        if k == 0 and not free_start:
            ok = ok & (at == 0)
        if k == len(segs) - 1 and not free_end:
            ok = ok & (at + m == length)
        first = jnp.min(jnp.where(ok, at, place(width)), axis=1,
                        keepdims=True)
        found = found & (first[:, 0] < width)
        nxt = first + m
    return found
