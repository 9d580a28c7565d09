"""Order-preserving radix keys for sort / group-by / join.

Each key column is lowered to a list of ``uint32`` arrays such that comparing
rows by the concatenated arrays in unsigned lexicographic order reproduces
Spark's SQL ordering:

* signed ints: XOR the sign bit (``x ^ 0x80000000`` reinterpreted unsigned).
* floats: IEEE-754 total-order transform — negative values flip all bits,
  non-negative flip only the sign bit.  For *equality domains* (group/join)
  Spark first normalizes ``-0.0`` to ``0.0`` and every NaN to the canonical
  quiet NaN (NormalizeFloatingNumbers); for ordering, NaN sorts greater than
  +Inf, which the total-order transform already gives.
* 64-bit values emit (hi, lo) uint32 pairs — native 32-bit lanes on the VPU.
* strings: big-endian 4-byte words of the padded char matrix.  Trailing
  padding is zero, and a shorter string is a prefix of nothing else on equal
  words, so unsigned word order == byte order (cudf strings compare bytewise
  the same way).
* decimal128: sign-flipped high limb then lower limbs (values of one Spark
  decimal column share a scale, so unscaled-value order == value order).
* validity: one leading flag array placing nulls first or last.

The same lowering feeds ``lax.sort`` operands (sort), segment-boundary
detection (group-by) and lexicographic binary search (join probe).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import types as T
from ..columnar.column import Column, Decimal128Column, StringColumn
from ..profiler import scope
from ..columnar.encoded import (
    BitPackedColumn,
    DictionaryColumn,
    FrameOfReferenceColumn,
    RunLengthColumn,
)

# numpy, not jnp: module scope must not mint device arrays (GL001)
_SIGN32 = np.uint32(0x80000000)
_F64_QNAN = np.uint64(0x7FF8000000000000)


def scan_sum(vals):
    """``jnp.cumsum(vals)`` of a 1-D array, as the one ``reduce_window`` it
    lowers to, lowered in line: ``jnp.cumsum`` lowers into a function of
    its own whose operations the compiler's rewrite of a long scan (a
    128-lane blocked scan, the 64-bit one as u32 halves) names
    ``reduce_window_sum``, outside every scope; in line they carry the
    caller's.  The compiled program is the same."""
    if vals.dtype == jnp.bool_:
        vals = vals.astype(jnp.int64)   # what jnp.cumsum sums a flag in
    n = vals.shape[0]
    if n == 0:
        return vals
    return jax.lax.reduce_window(vals, jnp.zeros((), vals.dtype),
                                 jax.lax.add, (n,), (1,), ((n - 1, 0),))


def _split64(u64):
    """uint64[n] -> (hi, lo) uint32 pair."""
    return (u64 >> jnp.uint64(32)).astype(jnp.uint32), (
        u64 & jnp.uint64(0xFFFFFFFF)
    ).astype(jnp.uint32)


_F32_QNAN = np.uint32(0x7FC00000)


def _f32_total_order(d, normalize_zero: bool):
    if normalize_zero:
        d = jnp.where(d == 0.0, jnp.float32(0.0), d)
    bits = jax.lax.bitcast_convert_type(d, jnp.uint32)
    # all NaNs canonicalize (Java Double.compare semantics: one NaN, greatest)
    bits = jnp.where(jnp.isnan(d), _F32_QNAN, bits)
    neg = (bits & _SIGN32) != 0
    return jnp.where(neg, ~bits, bits ^ _SIGN32)


def _f64_total_order(d, normalize_zero: bool):
    if normalize_zero:
        d = jnp.where(d == 0.0, jnp.float64(0.0), d)
    # bitcast via uint32 pair: TPU X64 rewrite can't bitcast 64-bit lanes
    pair = jax.lax.bitcast_convert_type(d, jnp.uint32)
    lo = pair[..., 0].astype(jnp.uint64)
    hi = pair[..., 1].astype(jnp.uint64)
    bits = lo | (hi << 32)
    bits = jnp.where(jnp.isnan(d), _F64_QNAN, bits)
    neg = (bits >> jnp.uint64(63)) != 0
    sign64 = jnp.uint64(1) << jnp.uint64(63)
    return jnp.where(neg, ~bits, bits ^ sign64)


def column_radix_keys(col, *, equality: bool = False) -> list:
    """Lower one column to its list of uint32 key arrays (nulls not encoded).

    ``equality=True`` applies Spark's equality-domain float normalization
    (NormalizeFloatingNumbers: -0.0 -> 0.0 for group-by / join / partition
    keys).  Ordering domains (sort) keep -0.0 < 0.0, matching Java
    ``Double.compare``.  NaNs canonicalize in both domains (Java has one NaN,
    greater than +Inf).
    """
    if isinstance(col, DictionaryColumn):
        # words computed once on the d-entry dictionary, then gathered by
        # code: cross-dictionary safe (both sides lower to VALUE words),
        # and the per-row cost is one gather instead of a padded compare.
        # The single-word canon fast path lives in encoded.py and is
        # substituted by callers only under a dict_token match.
        idx = col.codes.astype(jnp.int32)
        return [w[idx] for w in
                column_radix_keys(col.dictionary, equality=equality)]
    if isinstance(col, RunLengthColumn):
        run = col.row_to_run()
        values = Column(col.run_values,
                        jnp.ones((col.num_runs,), jnp.bool_), col.dtype)
        return [w[run] for w in column_radix_keys(values, equality=equality)]
    if isinstance(col, BitPackedColumn):
        # reference+residual arithmetic, not a decode: the packed column
        # lowers straight to VALUE words, so it groups/joins against
        # plain int columns (and differently-referenced packed ones)
        # bit-identically
        vals = col.residuals().astype(jnp.int64) + col.reference
        return _int_value_words(vals, col.dtype)
    if isinstance(col, FrameOfReferenceColumn):
        return _int_value_words(col.values64(), col.dtype)
    if isinstance(col, StringColumn):
        chars, L = col.chars, col.max_len
        nwords = max(1, -(-L // 4))
        pad = nwords * 4 - L
        if pad:
            chars = jnp.pad(chars, ((0, 0), (0, pad)))
        w = chars.astype(jnp.uint32).reshape(chars.shape[0], nwords, 4)
        words = (w[:, :, 0] << 24) | (w[:, :, 1] << 16) | (w[:, :, 2] << 8) | w[:, :, 3]
        # trailing length key: padding is zero bytes, so equal-word prefixes
        # fall through to the length — distinguishes 'a' from 'a\x00'
        return [words[:, i] for i in range(nwords)] + [
            col.lengths.astype(jnp.uint32)
        ]
    if isinstance(col, Decimal128Column):
        if col.dtype.decimal_storage_bits < 128:
            lo_limb = col.limbs[:, 0]
            hi, lo = _split64(lo_limb ^ (jnp.uint64(1) << jnp.uint64(63)))
            return [hi, lo]
        hi_limb = col.limbs[:, 1] ^ (jnp.uint64(1) << jnp.uint64(63))
        parts = _split64(hi_limb) + _split64(col.limbs[:, 0])
        return list(parts)

    kind = col.dtype.kind
    d = col.data
    if kind is T.Kind.DECIMAL:
        # a decimal in 32- or 64-bit storage: its unscaled integer (values
        # of one column share a scale, so their order is the values')
        kind = T.Kind.INT32 if d.dtype.itemsize <= 4 else T.Kind.INT64
    if kind is T.Kind.BOOLEAN:
        return [d.astype(jnp.uint32)]
    if kind in (T.Kind.INT8, T.Kind.INT16, T.Kind.INT32, T.Kind.DATE):
        return [d.astype(jnp.int32).astype(jnp.uint32) ^ _SIGN32]
    if kind in (T.Kind.INT64, T.Kind.TIMESTAMP):
        u = d.astype(jnp.int64).astype(jnp.uint64) ^ (jnp.uint64(1) << jnp.uint64(63))
        return list(_split64(u))
    if kind is T.Kind.FLOAT32:
        return [_f32_total_order(d, normalize_zero=equality)]
    if kind is T.Kind.FLOAT64:
        return list(_split64(_f64_total_order(d, normalize_zero=equality)))
    raise NotImplementedError(f"radix keys for {col.dtype!r}")


def _int_value_words(vals64, dtype) -> list:
    """int64[n] decoded values -> the kind's order-preserving words
    (shared by the packed-column lowerings)."""
    kind = dtype.kind
    if kind in (T.Kind.INT8, T.Kind.INT16, T.Kind.INT32, T.Kind.DATE):
        return [vals64.astype(jnp.int32).astype(jnp.uint32) ^ _SIGN32]
    if kind in (T.Kind.INT64, T.Kind.TIMESTAMP):
        u = vals64.astype(jnp.uint64) ^ (jnp.uint64(1) << jnp.uint64(63))
        return list(_split64(u))
    raise NotImplementedError(f"packed radix keys for {dtype!r}")


def null_flag(col, nulls_first: bool) -> jax.Array:
    """Leading key array encoding null placement (0 sorts before 1)."""
    v = col.validity
    return jnp.where(v, jnp.uint32(1), jnp.uint32(0)) if nulls_first else jnp.where(
        v, jnp.uint32(0), jnp.uint32(1)
    )


def batch_radix_keys(
    cols: Sequence, *, equality: bool, nulls_first: bool = True
) -> list:
    """Key arrays for a composite key across columns, nulls flag included.

    Data keys of null rows are zeroed so every null row carries identical
    keys: padded/filtered batches keep residual payload data under a False
    validity bit, and Spark groups all nulls as ONE group.
    """
    out = []
    for c in cols:
        out.append(null_flag(c, nulls_first))
        v = c.validity
        out.extend(
            jnp.where(v, k, jnp.zeros((), k.dtype))
            for k in column_radix_keys(c, equality=equality)
        )
    return out


def packed_radix_keys(cols: Sequence, *, lead_flags: Sequence = (),
                      equality: bool, nulls_first: bool = True) -> list:
    """:func:`batch_radix_keys` with its bits laid end to end: each of
    ``lead_flags`` (uint32 0/1 arrays, the most significant) and each
    column's null flag takes one bit, each radix word its 32, and the bit
    string is cut into uint32 words, the last padded with zeros.  Unsigned
    lexicographic order and equality of the packed words are those of the
    unpacked ones, exactly: a three-key (int64, DATE, int32) composite
    with a row flag is 5 words where it was 8.  What a sort pays for
    (on the v5e its compile time grows with every key operand: PERF.md
    section 6, PR 35) follows the word count."""
    fields = [(f.astype(jnp.uint32), 1) for f in lead_flags]
    for c in cols:
        fields.append((null_flag(c, nulls_first), 1))
        v = c.validity
        fields.extend(
            (jnp.where(v, k, jnp.zeros((), k.dtype)), 32)
            for k in column_radix_keys(c, equality=equality))
    return pack_fields(fields)


def pack_fields(fields: Sequence) -> list:
    """``(uint32 array, bits)`` fields laid end to end, the first the most
    significant, and cut into uint32 words, the last padded with zeros: the
    packed words' unsigned lexicographic order is the fields'."""
    total = sum(bits for _f, bits in fields)
    words = [None] * (-(-total // 32))

    def put(i, part):
        words[i] = part if words[i] is None else words[i] | part

    off = 0
    for f, bits in fields:
        i, r = divmod(off, 32)
        if r + bits <= 32:
            put(i, f << jnp.uint32(32 - r - bits) if r + bits < 32 else f)
        else:   # a 32-bit word across two: r > 0
            put(i, f >> jnp.uint32(r))
            put(i + 1, f << jnp.uint32(32 - r))
        off += bits
    return words


def _place_bits(acc_hi, acc_lo, field, shift):
    """``field`` (uint64 rows, a scalar ``shift`` in 0..127 that need not
    be static) or-ed into a 128-bit string held as two uint64 limbs."""
    s = jnp.clip(jnp.asarray(shift, jnp.int32), 0, 127).astype(jnp.uint64)
    low = s < jnp.uint64(64)
    m63 = jnp.uint64(63)
    zero = jnp.zeros((), jnp.uint64)
    # no shift by 64 or more is ever asked of the hardware
    over = jnp.where(s == zero, zero, field >> ((jnp.uint64(64) - s) & m63))
    return (acc_hi | jnp.where(low, over, field << ((s - jnp.uint64(64)) & m63)),
            acc_lo | jnp.where(low, field << (s & m63), zero))


def span_packed_keys(cols: Sequence, *, lead_flags: Sequence = (), live,
                     words: int, equality: bool, nulls_first: bool = True):
    """:func:`packed_radix_keys` with each column's value cut to the bits
    that the values of its ``live`` rows span: ``value - least value`` in as
    many bits as ``greatest - least`` has, found on the device, so that
    four keys of a few million distinct values each fill three words where
    their types fill eight.  Returns ``(packed, fits)``: ``words`` uint32
    arrays whose unsigned lexicographic order and equality among live rows
    are those of the unpacked keys, exactly, wherever the scalar ``fits``
    is true (the flags and spans together have at most ``32 * words``
    bits); where it is false they mean nothing and the caller sorts the
    type-wide words instead.  ``None`` where a column lowers to more than
    two radix words (a wide decimal, a string): no span is taken of
    those.  A sort's compile time on the v5e grows with every key operand
    (PERF.md section 6, PR 37); a dead row's words are whatever its
    buffers hold and the caller overwrites them."""
    if not 1 <= words <= 4:
        raise ValueError(f"{words} words: the string has 128 bits")
    lowered = [column_radix_keys(c, equality=equality) for c in cols]
    if any(len(ws) > 2 for ws in lowered):
        return None
    n = live.shape[0]
    u64 = jnp.uint64
    acc_hi = jnp.zeros((n,), u64)
    acc_lo = jnp.zeros((n,), u64)
    used = jnp.int32(0)    # bits taken so far, from the top of the string
    for f in lead_flags:
        acc_hi, acc_lo = _place_bits(acc_hi, acc_lo, f.astype(u64),
                                     127 - used)
        used = used + 1
    top = jnp.uint32(0xFFFFFFFF)
    for c, ws in zip(cols, lowered):
        acc_hi, acc_lo = _place_bits(
            acc_hi, acc_lo, null_flag(c, nulls_first).astype(u64),
            127 - used)
        used = used + 1
        ok = c.validity & live
        hi = ws[0] if len(ws) == 2 else jnp.zeros((n,), jnp.uint32)
        lo = ws[-1]
        # least and greatest value over the rows that count, a word at a
        # time (32-bit reductions only)
        mn_hi = jnp.min(jnp.where(ok, hi, top))
        mn_lo = jnp.min(jnp.where(ok & (hi == mn_hi), lo, top))
        mx_hi = jnp.max(jnp.where(ok, hi, jnp.uint32(0)))
        mx_lo = jnp.max(jnp.where(ok & (hi == mx_hi), lo, jnp.uint32(0)))
        least = (mn_hi.astype(u64) << u64(32)) | mn_lo.astype(u64)
        most = (mx_hi.astype(u64) << u64(32)) | mx_lo.astype(u64)
        span = jnp.where(most >= least, most - least, u64(0))  # none counts
        bits = jnp.sum((span >> jnp.arange(64, dtype=u64)) != u64(0),
                       dtype=jnp.int32)
        value = (hi.astype(u64) << u64(32)) | lo.astype(u64)
        field = jnp.where(ok, value - least, u64(0))
        acc_hi, acc_lo = _place_bits(acc_hi, acc_lo, field,
                                     128 - used - bits)
        used = used + bits
    packed = list(_split64(acc_hi) + _split64(acc_lo))[:words]
    return packed, used <= 32 * words


def rows_equal_adjacent(key_arrays: Sequence[jax.Array]) -> jax.Array:
    """bool[n]: row i has identical keys to row i-1 (row 0 -> False)."""
    n = key_arrays[0].shape[0]
    eq = jnp.ones((n,), jnp.bool_)
    for k in key_arrays:
        eq = eq & (k == jnp.roll(k, 1))
    return eq.at[0].set(False)


def _lex_less(a_keys, b_keys, or_equal: bool):
    """Vectorized lexicographic a < b (or a <= b) over parallel key lists."""
    res = jnp.full(a_keys[0].shape, or_equal)
    for a, b in zip(reversed(a_keys), reversed(b_keys)):
        res = jnp.where(a == b, res, a < b)
    return res


def _search(sorted_keys, query_keys, *, lower: bool):
    """Vectorized lexicographic binary search over sorted composite keys.

    Returns int32 positions in [0, n].  ``lower=True`` gives the first index
    whose key is >= query (lower bound); else first index > query.
    """
    if len(sorted_keys) != len(query_keys):
        raise ValueError(
            f"composite key arity mismatch: {len(sorted_keys)} sorted vs "
            f"{len(query_keys)} query arrays (string key columns must be "
            "width-aligned first — see align_string_key_columns)"
        )
    n = sorted_keys[0].shape[0]
    m = query_keys[0].shape[0]
    if n == 0:
        return jnp.zeros((m,), jnp.int32)
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), n, jnp.int32)
    steps = n.bit_length() + 1

    def body(_, lohi):
        lo, hi = lohi
        active = lo < hi
        mid = (lo + hi) >> 1
        with scope("keys.bisect_gather"):
            mid_keys = [jnp.take(k, mid, mode="clip") for k in sorted_keys]
        # advance when sorted[mid] < q (lower) / sorted[mid] <= q (upper)
        adv = _lex_less(mid_keys, query_keys, or_equal=not lower)
        lo = jnp.where(active & adv, mid + 1, lo)
        hi = jnp.where(active & ~adv, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def lower_bound(sorted_keys, query_keys):
    return _search(sorted_keys, query_keys, lower=True)


def upper_bound(sorted_keys, query_keys):
    return _search(sorted_keys, query_keys, lower=False)


def equal_range(sorted_keys, query_keys):
    """(lower, upper) bounds in one fused loop — both carried as state, so
    the probe pays one round of composite-key gathers per bisection step
    instead of two (the join's dominant cost)."""
    if len(sorted_keys) != len(query_keys):
        raise ValueError(
            f"composite key arity mismatch: {len(sorted_keys)} sorted vs "
            f"{len(query_keys)} query arrays (string key columns must be "
            "width-aligned first — see align_string_key_columns)"
        )
    n = sorted_keys[0].shape[0]
    m = query_keys[0].shape[0]
    if n == 0:
        z = jnp.zeros((m,), jnp.int32)
        return z, z
    init = (
        jnp.zeros((m,), jnp.int32),
        jnp.full((m,), n, jnp.int32),
        jnp.zeros((m,), jnp.int32),
        jnp.full((m,), n, jnp.int32),
    )
    steps = n.bit_length() + 1

    def body(_, st):
        llo, lhi, ulo, uhi = st
        # two bisections share each round's gather when their mids coincide
        # (XLA CSEs the duplicate takes); state stays a flat 4-tuple
        lmid = (llo + lhi) >> 1
        umid = (ulo + uhi) >> 1
        with scope("keys.bisect_gather"):
            lkeys = [jnp.take(k, lmid, mode="clip") for k in sorted_keys]
            ukeys = [jnp.take(k, umid, mode="clip") for k in sorted_keys]
        with scope("keys.bisect_compare"):
            ladv = _lex_less(lkeys, query_keys, or_equal=False)
            uadv = _lex_less(ukeys, query_keys, or_equal=True)
        lact = llo < lhi
        uact = ulo < uhi
        llo = jnp.where(lact & ladv, lmid + 1, llo)
        lhi = jnp.where(lact & ~ladv, lmid, lhi)
        ulo = jnp.where(uact & uadv, umid + 1, ulo)
        uhi = jnp.where(uact & ~uadv, umid, uhi)
        return llo, lhi, ulo, uhi

    llo, _, ulo, _ = jax.lax.fori_loop(0, steps, body, init)
    return llo, ulo


def align_string_key_columns(lcols: Sequence, rcols: Sequence):
    """Pad paired string key columns to a common char-matrix width.

    Radix-key arity is derived from ``max_len``; comparing keys across two
    batches (join probe) requires both sides to lower to the same number of
    word arrays, else words would misalign against the trailing length key.
    """
    from ..columnar.column import StringColumn as _S

    def str_width(c):
        """Char-matrix width if the column lowers to string words."""
        if isinstance(c, _S):
            return c.max_len
        if isinstance(c, DictionaryColumn) and isinstance(c.dictionary, _S):
            return c.dictionary.max_len
        return None

    def pad_to(c, width):
        if isinstance(c, DictionaryColumn):
            d = c.dictionary
            if d.max_len == width:
                return c
            chars = jnp.pad(d.chars, ((0, 0), (0, width - d.max_len)))
            return dataclasses.replace(
                c, dictionary=_S(chars, d.lengths, d.validity, d.dtype))
        if c.max_len == width:
            return c
        chars = jnp.pad(c.chars, ((0, 0), (0, width - c.max_len)))
        return _S(chars, c.lengths, c.validity, c.dtype)

    lout, rout = [], []
    for lc, rc in zip(lcols, rcols):
        lw, rw = str_width(lc), str_width(rc)
        if (lw is None) != (rw is None):
            raise TypeError(f"join key type mismatch: {lc.dtype!r} vs {rc.dtype!r}")
        if lw is not None and lw != rw:
            width = max(lw, rw)
            lc, rc = pad_to(lc, width), pad_to(rc, width)
        lout.append(lc)
        rout.append(rc)
    return lout, rout
