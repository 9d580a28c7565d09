"""Spark DECIMAL128 arithmetic with 256-bit intermediates, vectorized.

Semantics derived from the reference's ``decimal_utils.cu`` (spark-rapids-jni):
every operation computes in a 256-bit integer domain ("chunked256",
``decimal_utils.cu:32-119``), rescales with HALF_UP rounding, and reports
per-row overflow = |result| >= 10^38 (``is_greater_than_decimal_38``).
Scales here are **Spark scales** (digits right of the point, >= 0); the
reference uses cudf scales which are their negation.

Replicated quirks (each is a compatibility contract, SURVEY.md §7):

* ``multiply`` with ``cast_interim_result=True`` (the default, matching
  Spark < 3.4.2/3.5.1/4.0.0) first rounds the raw product to 38 digits of
  precision, then rounds to the target scale — a known Spark bug
  (``DecimalUtils.java:33-37``) that changes the last digit for some inputs.
* ``integer_divide`` overflow is judged on the 128-bit quotient *before* the
  int64 narrowing (``DecimalUtils.java integerDivide128`` doc).
* ``remainder`` follows Java's sign rule (result sign = dividend sign) and
  computes via ``a - (a // b) * b`` in the divisor's scale domain
  (``dec128_remainder``).
* divide-by-zero rows report overflow=True, result 0 (``dec128_divider``).

TPU mapping: a 256-bit value is ``uint32[n, 8]`` little-endian limbs (native
32-bit VPU lanes; 64-bit ops on TPU are emulated pairs).  Multiplication is
8x8 schoolbook with uint64 partial products; division is the reference's
bit-serial long division (``divide_unsigned``, decimal_utils.cu:149) turned
inside-out: instead of indexing bit i of the numerator (dynamic limb index),
the numerator shifts left one bit per step so the loop body is
shift/compare/subtract on whole vectors — 256 ``lax.fori_loop`` steps with
no data-dependent control flow.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import types as T
from ..columnar.column import Column, Decimal128Column

# numpy, not jnp: this module is imported lazily from inside jitted
# aggregation bodies, and a jnp scalar created under an active trace is a
# tracer that outlives it (UnexpectedTracerError on the next trace)
_MASK32 = np.uint64(0xFFFFFFFF)

# pow10 limb table: 10^0 .. 10^76 as uint32[77, 8] little-endian
_POW10_NP = np.zeros((77, 8), dtype=np.uint32)
for _e in range(77):
    _v = 10**_e
    for _i in range(8):
        _POW10_NP[_e, _i] = (_v >> (32 * _i)) & 0xFFFFFFFF


def _pow10(e: int):
    """Static-exponent 10^e as a [1, 8] broadcastable constant."""
    return jnp.asarray(_POW10_NP[e : e + 1])


def _pow10_rows(e_rows):
    """Per-row 10^e gather (e int32[n] in [0, 76]) -> uint32[n, 8]."""
    return jnp.take(jnp.asarray(_POW10_NP), jnp.clip(e_rows, 0, 76), axis=0)


# ---------------------------------------------------------------------------
# uint32[n, 8] limb primitives
# ---------------------------------------------------------------------------


def _from_i128(limbs64) -> jax.Array:
    """Decimal128Column limbs (uint64[n,2] LE) -> sign-extended uint32[n,8]."""
    lo, hi = limbs64[:, 0], limbs64[:, 1]
    neg = (hi >> jnp.uint64(63)) != 0
    ext = jnp.where(neg, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    lanes = [
        (lo & _MASK32).astype(jnp.uint32),
        (lo >> jnp.uint64(32)).astype(jnp.uint32),
        (hi & _MASK32).astype(jnp.uint32),
        (hi >> jnp.uint64(32)).astype(jnp.uint32),
        ext, ext, ext, ext,
    ]
    return jnp.stack(lanes, axis=1)


def _to_i128(u) -> jax.Array:
    """Truncate uint32[n,8] -> uint64[n,2] (chunked256::as_128_bits)."""
    lo = u[:, 0].astype(jnp.uint64) | (u[:, 1].astype(jnp.uint64) << 32)
    hi = u[:, 2].astype(jnp.uint64) | (u[:, 3].astype(jnp.uint64) << 32)
    return jnp.stack([lo, hi], axis=1)


def _sign_neg(u) -> jax.Array:
    """bool[n]: 256-bit two's-complement value is negative."""
    return (u[:, 7] >> 31) != 0


def _add(a, b) -> jax.Array:
    lanes = []
    carry = jnp.zeros(a.shape[:1], jnp.uint64)
    for i in range(8):
        s = a[:, i].astype(jnp.uint64) + b[:, i].astype(jnp.uint64) + carry
        lanes.append((s & _MASK32).astype(jnp.uint32))
        carry = s >> jnp.uint64(32)
    return jnp.stack(lanes, axis=1)


def _add_small(a, inc) -> jax.Array:
    """a + inc where inc is int32[n] in {-1, 0, 1} (sign-extended)."""
    ext = jnp.where(inc < 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    b = jnp.stack(
        [inc.astype(jnp.uint32)] + [ext] * 7, axis=1
    )
    return _add(a, b)


def _neg(a) -> jax.Array:
    ones = jnp.ones(a.shape[:1], jnp.int32)
    return _add_small(~a, ones)


def _abs(a) -> Tuple[jax.Array, jax.Array]:
    neg = _sign_neg(a)
    return jnp.where(neg[:, None], _neg(a), a), neg


def _lt_u(a, b) -> jax.Array:
    """unsigned a < b; LSB-first fold so the highest differing limb wins."""
    res = jnp.zeros(a.shape[:1], jnp.bool_)
    for i in range(8):
        res = jnp.where(a[:, i] == b[:, i], res, a[:, i] < b[:, i])
    return res


def _shl1(a) -> jax.Array:
    lanes = [(a[:, 0] << 1)]
    for i in range(1, 8):
        lanes.append((a[:, i] << 1) | (a[:, i - 1] >> 31))
    return jnp.stack(lanes, axis=1)


def _mul(a, b) -> jax.Array:
    """Low 256 bits of a*b (reference ``multiply``, decimal_utils.cu:127)."""
    n = a.shape[0]
    res = [jnp.zeros((n,), jnp.uint32) for _ in range(8)]
    a64 = [a[:, i].astype(jnp.uint64) for i in range(8)]
    b64 = [b[:, j].astype(jnp.uint64) for j in range(8)]
    for j in range(8):
        carry = jnp.zeros((n,), jnp.uint64)
        for i in range(8 - j):
            t = a64[i] * b64[j] + res[i + j].astype(jnp.uint64) + carry
            res[i + j] = (t & _MASK32).astype(jnp.uint32)
            carry = t >> jnp.uint64(32)
    return jnp.stack(res, axis=1)


def _divmod_u(num, den) -> Tuple[jax.Array, jax.Array]:
    """Unsigned 256-bit / 256-bit long division -> (quotient, remainder).

    Bit-serial (256 steps), all rows in lockstep; den must be nonzero
    (callers mask div-by-zero rows to 1 and overwrite the result).
    """

    def body(_, st):
        nn, q, r = st
        top = nn[:, 7] >> 31  # numerator MSB enters the remainder
        nn = _shl1(nn)
        r = _shl1(r)
        r = r.at[:, 0].set(r[:, 0] | top)
        ge = ~_lt_u(r, den)
        r = jnp.where(ge[:, None], _add(r, _neg(den)), r)
        q = _shl1(q)
        q = q.at[:, 0].set(q[:, 0] | ge.astype(jnp.uint32))
        return nn, q, r

    n = num.shape[0]
    zeros = jnp.zeros((n, 8), jnp.uint32)
    _, q, r = jax.lax.fori_loop(0, 256, body, (num, zeros, zeros))
    return q, r


def _divmod_u_small(u, den, limbs: int = 8) -> Tuple[jax.Array, jax.Array]:
    """Unsigned 256-bit / small divisor -> (quotient, remainder).

    ``den``: uint64[n], 0 < den <= 2^31 (group-average divides by a row
    count); ``limbs`` says how many low u32 limbs of ``u`` can be nonzero
    (the caller knows from the type).  Restoring division a bit at a time,
    written out: the running remainder stays under 2^32, so every step is a
    shift, a compare and a subtract on native u32 lanes, and the whole
    division is one elementwise fusion.  (A u64 ``//`` and ``%`` a limb, the
    schoolbook form, is eight software divisions that the TPU's compiler
    takes 40 seconds over; :func:`_divmod_u`'s loop is 256 launches.)
    """
    d = den.astype(jnp.uint32)
    rem = jnp.zeros(u.shape[:1], jnp.uint32)
    one = jnp.uint32(1)
    qs = [jnp.zeros(u.shape[:1], jnp.uint32)] * 8
    for i in range(limbs - 1, -1, -1):
        limb = u[:, i]
        q = jnp.zeros(u.shape[:1], jnp.uint32)
        for b in range(31, -1, -1):
            rem = (rem << one) | ((limb >> jnp.uint32(b)) & one)
            ge = rem >= d
            rem = jnp.where(ge, rem - d, rem)
            q = (q << one) | ge.astype(jnp.uint32)
        qs[i] = q
    return jnp.stack(qs, axis=1), rem.astype(jnp.uint64)


def _precision10(u_abs) -> jax.Array:
    """Smallest i with 10^i >= |value| (reference precision10)."""
    table = jnp.asarray(_POW10_NP)  # [77, 8]
    # ge[n, e] = table[e] >= u_abs[n]; LSB-first fold, highest limb wins
    res = jnp.ones(u_abs.shape[:1] + (77,), jnp.bool_)
    for i in range(8):
        t = table[None, :, i]
        v = u_abs[:, i, None]
        res = jnp.where(t == v, res, t > v)
    return jnp.argmax(res, axis=1).astype(jnp.int32)


def _overflow_38(u) -> jax.Array:
    a, _ = _abs(u)
    return ~_lt_u(a, _pow10(38))


# ---------------------------------------------------------------------------
# signed helpers mirroring the reference's divide / rounding machinery
# ---------------------------------------------------------------------------


def _divide_signed(n_limbs, d_limbs):
    """(quotient signed, |remainder|, n_neg, d_neg); divisor 0 handled by
    callers (rows masked)."""
    abs_n, n_neg = _abs(n_limbs)
    abs_d, d_neg = _abs(d_limbs)
    safe_d = jnp.where(
        _is_zero(abs_d)[:, None], _one_like(abs_d), abs_d
    )
    q, r = _divmod_u(abs_n, safe_d)
    q = jnp.where((n_neg ^ d_neg)[:, None], _neg(q), q)
    return q, r, n_neg, d_neg


def _is_zero(u) -> jax.Array:
    return (u == 0).all(axis=1)


def _one_like(u) -> jax.Array:
    one = jnp.zeros_like(u)
    return one.at[:, 0].set(1)


def _round_half_up(q_signed, r_abs, d_abs, round_down) -> jax.Array:
    """HALF_UP: bump |q| by 1 when 2|r| >= |d| (reference
    round_from_remainder; the 256-bit domain makes its double-remainder
    overflow check unnecessary)."""
    need_inc = ~_lt_u(_shl1(r_abs), d_abs)
    inc = jnp.where(
        need_inc, jnp.where(round_down, jnp.int32(-1), jnp.int32(1)), jnp.int32(0)
    )
    return _add_small(q_signed, inc)


def _divide_and_round(n_limbs, d_limbs) -> jax.Array:
    """Signed divide with HALF_UP rounding (reference divide_and_round)."""
    q, r, n_neg, d_neg = _divide_signed(n_limbs, d_limbs)
    abs_d, _ = _abs(d_limbs)
    return _round_half_up(q, r, abs_d, n_neg ^ d_neg)


def _integer_divide(n_limbs, d_limbs) -> jax.Array:
    q, _, _, _ = _divide_signed(n_limbs, d_limbs)
    return q


def _set_scale_and_round(u, from_scale: int, to_scale: int) -> jax.Array:
    """Rescale between static Spark scales with HALF_UP on scale decrease."""
    if to_scale == from_scale:
        return u
    if to_scale > from_scale:
        return _mul(u, jnp.broadcast_to(_pow10(to_scale - from_scale), u.shape))
    d = jnp.broadcast_to(_pow10(from_scale - to_scale), u.shape)
    return _divide_and_round(u, d)


# ---------------------------------------------------------------------------
# public ops — each returns (overflow Column<bool>, result)
# ---------------------------------------------------------------------------


def _both_valid(a: Decimal128Column, b: Decimal128Column) -> jax.Array:
    return a.validity & b.validity


def _result(limbs_u8, valid, scale: int) -> Decimal128Column:
    return Decimal128Column(
        _to_i128(limbs_u8), valid, T.SparkType.decimal(38, scale)
    )


def _add_sub(a, b, result_scale: int, is_sub: bool):
    sa, sb = a.scale, b.scale
    inter = max(sa, sb)
    ua = _set_scale_and_round(_from_i128(a.limbs), sa, inter)
    ub = _set_scale_and_round(_from_i128(b.limbs), sb, inter)
    if is_sub:
        ub = _neg(ub)
    s = _add(ua, ub)
    s = _set_scale_and_round(s, inter, result_scale)
    valid = _both_valid(a, b)
    overflow = _overflow_38(s)
    return Column(overflow, valid, T.BOOLEAN), _result(s, valid, result_scale)


def add_decimal128(a: Decimal128Column, b: Decimal128Column, result_scale: int):
    """a + b at result_scale (reference add_decimal128, decimal_utils.cu:1110)."""
    return _add_sub(a, b, result_scale, is_sub=False)


def sub_decimal128(a: Decimal128Column, b: Decimal128Column, result_scale: int):
    """a - b at result_scale (reference sub_decimal128, decimal_utils.cu:1143)."""
    return _add_sub(a, b, result_scale, is_sub=True)


def multiply_decimal128(
    a: Decimal128Column,
    b: Decimal128Column,
    product_scale: int,
    cast_interim_result: bool = True,
):
    """a * b at product_scale (reference dec128_multiplier, decimal_utils.cu:657).

    ``cast_interim_result`` replicates the Spark < 3.4.2 double-rounding bug
    (round to precision 38 first, then to the target scale).
    """
    ua = _from_i128(a.limbs)
    ub = _from_i128(b.limbs)
    product = _mul(ua, ub)
    n = product.shape[0]
    mult_scale = jnp.full((n,), a.scale + b.scale, jnp.int32)

    if cast_interim_result:
        abs_p, _ = _abs(product)
        fdp = _precision10(abs_p) - 38
        do = fdp > 0
        divisor = _pow10_rows(jnp.where(do, fdp, 0))
        rounded = _divide_and_round(product, divisor)
        product = jnp.where(do[:, None], rounded, product)
        mult_scale = mult_scale - jnp.where(do, fdp, 0)

    # exponent > 0: divide down to the target scale; < 0: scale up
    exponent = mult_scale - product_scale
    abs_p, _ = _abs(product)
    new_precision = _precision10(abs_p)
    up_overflow = (exponent < 0) & (new_precision - exponent > 38)

    scale_div = _pow10_rows(jnp.where(exponent > 0, exponent, 0))
    scaled_down = _divide_and_round(product, scale_div)
    scale_mul = _pow10_rows(jnp.where(exponent < 0, -exponent, 0))
    scaled_up = _mul(product, scale_mul)
    product = jnp.where(
        (exponent > 0)[:, None],
        scaled_down,
        jnp.where((exponent < 0)[:, None], scaled_up, product),
    )

    valid = _both_valid(a, b)
    overflow = up_overflow | _overflow_38(product)
    return Column(overflow, valid, T.BOOLEAN), _result(product, valid, product_scale)


def limbs_for_precision(precision: int) -> int:
    """u32 limbs that hold every magnitude under ``10^precision``."""
    return -(-(10**precision - 1).bit_length() // 32)


def _magnitude_limbs(col, nlimbs: int):
    """``|unscaled|`` as ``nlimbs`` little-endian u32 arrays, and the sign:
    of a :class:`Decimal128Column`, or of a :class:`Column` whose decimal
    (or integer) lives in 32- or 64-bit storage."""
    if isinstance(col, Decimal128Column):
        lo, hi = col.limbs[:, 0], col.limbs[:, 1]
        neg = (hi >> jnp.uint64(63)) != 0
        nlo = ~lo + jnp.uint64(1)
        nhi = ~hi + (nlo == 0).astype(jnp.uint64)
        lo, hi = jnp.where(neg, nlo, lo), jnp.where(neg, nhi, hi)
        words = [lo & _MASK32, lo >> jnp.uint64(32),
                 hi & _MASK32, hi >> jnp.uint64(32)]
    else:
        v = col.data.astype(jnp.int64)
        neg = v < 0
        mag = jax.lax.bitcast_convert_type(jnp.where(neg, -v, v), jnp.uint64)
        words = [mag & _MASK32, mag >> jnp.uint64(32)]
    return [w.astype(jnp.uint32) for w in words[:nlimbs]], neg


def _limbs_to_column(limbs, neg, valid, out_dtype: T.SparkType):
    """Sign and magnitude (little-endian u32 limbs) -> the column that
    stores ``out_dtype``: int64 up to 18 digits, two 64-bit limbs past."""
    w = [l.astype(jnp.uint64) for l in limbs[:4]]
    w += [jnp.zeros_like(w[0])] * (4 - len(w))
    lo = w[0] | (w[1] << jnp.uint64(32))
    hi = w[2] | (w[3] << jnp.uint64(32))
    nlo = ~lo + jnp.uint64(1)
    nhi = ~hi + (nlo == 0).astype(jnp.uint64)
    lo, hi = jnp.where(neg, nlo, lo), jnp.where(neg, nhi, hi)
    if out_dtype.decimal_storage_bits < 128:
        return Column(jax.lax.bitcast_convert_type(lo, jnp.int64), valid,
                      out_dtype)
    return Decimal128Column(jnp.stack([lo, hi], axis=1), valid, out_dtype)


def multiply_exact(a, b, a_precision: int, b_precision: int,
                   out_dtype: T.SparkType):
    """``a * b`` where the typed product keeps every digit: its scale is the
    sum of the operands' scales, so nothing rounds and nothing divides.

    Sign and magnitude; the magnitudes are as many u32 limbs as the
    operands' precisions need (a ``decimal(12,2)`` takes two, a
    ``decimal(26,4)`` three), multiplied schoolbook with uint64 partial
    products: four of them for ``decimal(12,2) x decimal(13,2)``, six for
    ``decimal(26,4) x decimal(13,2)``, where the 256-bit route of
    :func:`multiply_decimal128` takes 36 and two long divisions.  Returns
    ``(overflow Column<bool>, result)`` like the other operations here;
    overflow is ``|product| >= 10^precision`` of ``out_dtype`` (Spark's
    ``CheckOverflow``), the result a :class:`Column` of int64 up to 18
    digits and a :class:`Decimal128Column` past them.
    """
    la = limbs_for_precision(a_precision)
    lb = limbs_for_precision(b_precision)
    am, aneg = _magnitude_limbs(a, la)
    bm, bneg = _magnitude_limbs(b, lb)
    la, lb = len(am), len(bm)
    n = am[0].shape[0]
    a64 = [x.astype(jnp.uint64) for x in am]
    b64 = [x.astype(jnp.uint64) for x in bm]
    res = [jnp.zeros((n,), jnp.uint32) for _ in range(la + lb)]
    for j in range(lb):
        carry = jnp.zeros((n,), jnp.uint64)
        for i in range(la):
            t = a64[i] * b64[j] + res[i + j].astype(jnp.uint64) + carry
            res[i + j] = (t & _MASK32).astype(jnp.uint32)
            carry = t >> jnp.uint64(32)
        res[j + la] = carry.astype(jnp.uint32)
    bound = 10**out_dtype.precision
    overflow = jnp.zeros((n,), jnp.bool_)
    if bound < 1 << (32 * (la + lb)):
        lt = jnp.zeros((n,), jnp.bool_)   # magnitude < bound, LSB first
        for i in range(la + lb):
            c = jnp.uint32((bound >> (32 * i)) & 0xFFFFFFFF)
            lt = jnp.where(res[i] == c, lt, res[i] < c)
        overflow = ~lt
    valid = a.validity & b.validity
    return (Column(overflow, valid, T.BOOLEAN),
            _limbs_to_column(res, aneg ^ bneg, valid, out_dtype))


def _div_prepare(a: Decimal128Column, b: Decimal128Column, quotient_scale: int):
    """Shared scaling logic of dec128_divider (reference decimal_utils.cu:744).

    Returns (n, d, n_shift_exp, div_by_zero) with Spark scales:
    n_shift_exp = quotient_scale - (a.scale - b.scale), the power of ten the
    numerator must gain (positive) or the quotient must lose (negative).
    """
    n_limbs = _from_i128(a.limbs)
    d_limbs = _from_i128(b.limbs)
    div0 = _is_zero(_abs(d_limbs)[0])
    shift = quotient_scale - (a.scale - b.scale)
    return n_limbs, d_limbs, shift, div0


def divide_decimal128(
    a: Decimal128Column, b: Decimal128Column, quotient_scale: int
):
    """a / b at quotient_scale, HALF_UP (reference dec128_divider<__int128_t>)."""
    n_limbs, d_limbs, shift, div0 = _div_prepare(a, b, quotient_scale)

    if shift < 0:
        # quotient has too many digits: divide, then shed 10^-shift with rounding
        q1 = _integer_divide(n_limbs, d_limbs)
        res = _divide_and_round(q1, jnp.broadcast_to(_pow10(-shift), q1.shape))
    elif shift > 38:
        # two-stage scale-up (reference n_shift_exp < -38 branch): multiply by
        # 10^38, divide, then scale quotient+remainder by the rest and divide
        # the remainder again so no intermediate exceeds 256 bits
        n1 = _mul(n_limbs, jnp.broadcast_to(_pow10(38), n_limbs.shape))
        q1, r1, n_neg, d_neg = _divide_signed(n1, d_limbs)
        r1_signed = jnp.where(n_neg[:, None], _neg(r1), r1)
        rest = shift - 38
        pow_rest = jnp.broadcast_to(_pow10(rest), q1.shape)
        res = _mul(q1, pow_rest)
        scaled_r = _mul(r1_signed, pow_rest)
        q2, r2, _, _ = _divide_signed(scaled_r, d_limbs)
        res = _add(res, q2)
        abs_d, _ = _abs(d_limbs)
        res = _round_half_up(res, r2, abs_d, n_neg ^ d_neg)
    else:
        n1 = _mul(n_limbs, jnp.broadcast_to(_pow10(shift), n_limbs.shape))
        res = _divide_and_round(n1, d_limbs)

    res = jnp.where(div0[:, None], jnp.zeros_like(res), res)
    valid = _both_valid(a, b)
    overflow = div0 | _overflow_38(res)
    return Column(overflow, valid, T.BOOLEAN), _result(res, valid, quotient_scale)


def integer_divide_decimal128(a: Decimal128Column, b: Decimal128Column):
    """a div b -> int64 (reference dec128_divider<uint64_t, true>; overflow is
    judged on the wide quotient, not the int64 narrowing)."""
    n_limbs, d_limbs, shift, div0 = _div_prepare(a, b, 0)

    if shift < 0:
        q1 = _integer_divide(n_limbs, d_limbs)
        res = _integer_divide(q1, jnp.broadcast_to(_pow10(-shift), q1.shape))
    elif shift > 38:
        n1 = _mul(n_limbs, jnp.broadcast_to(_pow10(38), n_limbs.shape))
        q1, r1, n_neg, _ = _divide_signed(n1, d_limbs)
        r1_signed = jnp.where(n_neg[:, None], _neg(r1), r1)
        rest = shift - 38
        pow_rest = jnp.broadcast_to(_pow10(rest), q1.shape)
        res = _mul(q1, pow_rest)
        scaled_r = _mul(r1_signed, pow_rest)
        q2, _, _, _ = _divide_signed(scaled_r, d_limbs)
        res = _add(res, q2)
    else:
        n1 = _mul(n_limbs, jnp.broadcast_to(_pow10(shift), n_limbs.shape))
        res = _integer_divide(n1, d_limbs)

    res = jnp.where(div0[:, None], jnp.zeros_like(res), res)
    valid = _both_valid(a, b)
    overflow = div0 | _overflow_38(res)
    limbs = _to_i128(res)
    # as_64_bits: low limb reinterpreted as int64
    lo = limbs[:, 0]
    hi32 = (lo >> jnp.uint64(32)).astype(jnp.uint32)
    lo32 = (lo & _MASK32).astype(jnp.uint32)
    i64 = (
        jax.lax.bitcast_convert_type(hi32, jnp.int32).astype(jnp.int64) << 32
    ) | lo32.astype(jnp.int64)
    return Column(overflow, valid, T.BOOLEAN), Column(i64, valid, T.INT64)


def remainder_decimal128(
    a: Decimal128Column, b: Decimal128Column, remainder_scale: int
):
    """a % b at remainder_scale, Java sign rule (reference dec128_remainder)."""
    n_limbs = _from_i128(a.limbs)
    d_limbs = _from_i128(b.limbs)
    div0 = _is_zero(_abs(d_limbs)[0])

    abs_n, n_neg = _abs(n_limbs)
    abs_d, _ = _abs(d_limbs)

    # shift the divisor into the remainder's scale domain
    d_shift = remainder_scale - b.scale  # >0: scale divisor up exactly
    n_shift = remainder_scale - a.scale
    if d_shift < 0:
        # rounding drop on the divisor (set_scale_and_round path)
        abs_d = _divide_and_round(
            abs_d, jnp.broadcast_to(_pow10(-d_shift), abs_d.shape)
        )
    else:
        n_shift -= d_shift

    safe_d = jnp.where(_is_zero(abs_d)[:, None], _one_like(abs_d), abs_d)

    if n_shift < 0:
        q1, _ = _divmod_u(abs_n, safe_d)
        int_div = _integer_divide(
            q1, jnp.broadcast_to(_pow10(-n_shift), q1.shape)
        )
    else:
        abs_n2 = (
            _mul(abs_n, jnp.broadcast_to(_pow10(n_shift), abs_n.shape))
            if n_shift > 0
            else abs_n
        )
        abs_n = abs_n2
        int_div, _ = _divmod_u(abs_n, safe_d)

    less_n = _mul(int_div, abs_d)
    if d_shift > 0:
        # the divisor was left unscaled (we shifted n instead), so the
        # subtrahend must gain the divisor's scale shift
        less_n = _mul(less_n, jnp.broadcast_to(_pow10(d_shift), less_n.shape))
    res = _add(abs_n, _neg(less_n))
    res = jnp.where(n_neg[:, None], _neg(res), res)
    res = jnp.where(div0[:, None], jnp.zeros_like(res), res)

    valid = _both_valid(a, b)
    overflow = div0 | _overflow_38(res)
    return Column(overflow, valid, T.BOOLEAN), _result(res, valid, remainder_scale)
