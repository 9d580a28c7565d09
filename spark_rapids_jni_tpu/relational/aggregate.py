"""Engine-selectable group-by aggregation (Spark hash-aggregate semantics).

Two general-key engines live here, selected by the ``groupby_engine``
config knob (``auto | sort | scatter``) or the ``engine=`` argument:

* **sort** — one multi-operand ``lax.sort``, then only scans and
  gathers.  Three designs were measured on the real chip in round 1:
  radix-sort + argsort + segment ops hit 3.2 Mrows/s (each sort/scatter
  95-630ms at 2M rows on this TPU); scatter-min bucket election was no
  better (XLA scatters are the slowest primitive on that chip, ~150ms
  per 2M-row scatter); the surviving design has **no scatter anywhere**
  and optionally lets agg values ride the sort as payload operands.
* **scatter** — no sort anywhere: rows map to key groups through the
  open-addressing slot table (:mod:`hashtable`), every aggregate is one
  ``segment_*`` pass, and only the small ``num_slots``-sized table is
  sorted to emit groups in the same key order as the sort engine.  The
  inversion is again a hardware fact: on XLA-CPU ``lax.sort`` is the
  worst primitive and scatters the best (round-4 A/B: segment_sum 80x
  faster than the one-hot matmul), so ``auto`` resolves to scatter on
  CPU and sort on accelerators.  If the slot table overflows (more
  distinct keys than slots) the jitted program falls back to the sort
  engine via ``lax.cond`` — both engines trace, the data picks one.

Sort-engine pipeline: lower keys to uint32 radix words (:mod:`keys`,
equality domain)
-> one ``lax.sort`` carrying [keys..., row-id] (agg values are gathered
along the permutation afterwards by default; config
``group_sort_payload='ride'`` makes them ride the sort as extra payload
operands instead; input that ``assume_grouped`` says is in order already
is neither sorted nor moved) ->
adjacent-compare boundaries on the sorted key words -> per-agg prefix
``cumsum`` (or segmented min/max ``associative_scan``) -> group result =
scan value at each group's last row minus the previous group's, fetched
at the compacted group-end positions: at the first ``w`` of them, padded
back to the input's rows, for the narrowest ``w`` of a short ladder
(:func:`sortscan_tiers`: 4096, 65,536, 1,048,576 while they are under the
row count, then every row) that holds the data's groups
(``lax.switch`` on how many widths ``num_groups`` exceeds; a gather
costs by the index, 33-47 ms a buffer of 2^22 on a v5e, PERF.md section
5, and a result of ten groups needs ten, one of 11,000 no 6.0 M).  A
width's branch reads every value it needs at the group ends in one
fetch, and the keys at the groups' first rows in another: past 4096
indices out of 2^19 rows or more, each as ``[words, w]`` matrices of up
to eight u32 words (:func:`relational.gather.gather_rows`), since a
gather costs by its indices and hardly by its width.

Output is padded to the input row count with a device ``num_groups``
scalar (same discipline as :mod:`filter`); groups appear in key-sorted
order, nulls first (Spark does not define a group order; this one is
deterministic).

Spark null/type semantics implemented here (mirrors what the plugin gets
from cudf groupby + Spark's type promotion):

* group keys: nulls form their own group; floats normalize -0.0/NaN first
  (equality domain, :mod:`keys`).
* sum/min/max ignore null inputs; all-null group -> null result.
* count(col) counts non-nulls, count(*) counts rows; never null.
* sum(int*) -> int64 (non-ANSI wraparound), sum(float*) -> float64,
  avg(*) -> float64.  Float sums are computed as prefix-sum differences;
  they are not bit-identical to a per-group left-fold (Spark itself is
  order-nondeterministic under shuffles).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar import types as T
from ..columnar.column import Column, ColumnBatch, Decimal128Column, StringColumn
from ..columnar.encoded import (
    DictionaryColumn,
    canon_key_column,
    is_encoded,
    materialize_batch,
    materialize_column,
)
from .. import profiler
from ..profiler import scope
from . import keys as K
from .gather import (gather_batch, gather_column, gather_ops, gather_rows,
                     take_rows)

_OPS = ("sum", "count", "min", "max", "mean")


def _canon_keys(key_cols):
    """Key-column substitution for the encoded fast path: within ONE
    batch every dictionary column's ``canon[codes]`` single word is both
    equality- and order-equivalent to its full gathered radix words, so
    both engines key on one u32 word and still emit bit-identical group
    order.  Output key columns gather from the ORIGINAL (still encoded)
    batch columns — only the key lowering is substituted."""
    return [canon_key_column(c) if isinstance(c, DictionaryColumn) else c
            for c in key_cols]


def _materialize_agg_values(batch, aggs):
    """Late-materialize encoded agg VALUE columns at the point of need
    (aggregation arithmetic runs on values, not codes); key columns stay
    encoded all the way to the output gather."""
    repl = {}
    for spec in aggs:
        c = spec.column
        if c is not None and c not in repl and is_encoded(batch[c]):
            repl[c] = materialize_column(batch[c])
    if not repl:
        return batch
    return ColumnBatch({n: repl.get(n, col)
                        for n, col in zip(batch.names, batch.columns)})


@dataclasses.dataclass(frozen=True)
class AggSpec:
    op: str           # sum | count | min | max | mean
    column: Optional[str]  # None only for count(*)
    out_name: str

    def __post_init__(self):
        if self.op not in _OPS:
            raise ValueError(f"unknown agg op {self.op!r}")
        if self.column is None and self.op != "count":
            raise ValueError("only count supports column=None (count(*))")


@dataclasses.dataclass(frozen=True)
class Derived:
    """Columns computed from a batch's own (a plan's ``Project``), handed to
    a domain engine unevaluated: ``dtypes`` names each and its Spark type,
    ``fn(batch)`` gives the columns over whatever rows ``batch`` holds.
    The one-hot engine calls it on one row block at a time, so a wide
    product never exists for the whole table; the engines that read whole
    columns call it once."""

    dtypes: dict
    fn: Callable

    def over(self, batch: ColumnBatch) -> ColumnBatch:
        """``batch`` with the computed columns beside its own."""
        cols = dict(zip(batch.names, batch.columns))
        cols.update(self.fn(batch))
        return ColumnBatch(cols)


def _is_decimal(dtype: T.SparkType) -> bool:
    return dtype.kind is T.Kind.DECIMAL


def _dtype_lookup(batch, dtypes):
    """name -> SparkType: of ``dtypes`` (a :class:`Derived`'s columns, which
    the batch does not hold) first, of the batch's own columns else."""
    dtypes = dtypes or {}
    return lambda c: dtypes[c] if c in dtypes else batch[c].dtype


def _widen_decimal(col):
    """A decimal in 32- or 64-bit storage as a :class:`Decimal128Column`
    (sign-extended), for the engines whose exact sums run on its limbs."""
    if isinstance(col, Decimal128Column):
        return col
    lo = jax.lax.bitcast_convert_type(col.data.astype(jnp.int64), jnp.uint64)
    hi = jnp.where(col.data < 0, jnp.uint64(0xFFFFFFFFFFFFFFFF),
                   jnp.uint64(0))
    return Decimal128Column(jnp.stack([lo, hi], axis=1), col.validity,
                            col.dtype)


def _sum_dtype(dtype: T.SparkType) -> T.SparkType:
    if dtype.kind in (T.Kind.BOOLEAN, T.Kind.INT8, T.Kind.INT16, T.Kind.INT32,
                      T.Kind.INT64):
        return T.INT64
    if dtype.kind in (T.Kind.FLOAT32, T.Kind.FLOAT64):
        return T.FLOAT64
    raise NotImplementedError(f"sum of {dtype!r}")


def _seg_scan_minmax(vals, boundary, op):
    """Segmented running min/max: resets at rows where boundary is True."""
    def comb(a, b):
        av, ab = a
        bv, bb = b
        m = jnp.minimum(av, bv) if op == "min" else jnp.maximum(av, bv)
        return jnp.where(bb, bv, m), ab | bb

    out, _ = jax.lax.associative_scan(comb, (vals, boundary))
    return out


def _seg_scan_sum(vals, boundary):
    """Segmented running sum (resets at boundaries).

    Used for FLOAT sums: a global prefix-sum difference cancels
    catastrophically when a small group sorts after a large one (1e18
    prefixes have ~128 ulp); the segmented scan keeps each group's sum a
    tree-reduction of only its own elements.
    """
    def comb(a, b):
        av, ab = a
        bv, bb = b
        return jnp.where(bb, bv, av + bv), ab | bb

    out, _ = jax.lax.associative_scan(comb, (vals, boundary))
    return out


def _dec128_lt(alo, ahi, blo, bhi):
    """Signed 128-bit a < b over (lo u64, hi u64) pairs."""
    ah = jax.lax.bitcast_convert_type(ahi, jnp.int64)
    bh = jax.lax.bitcast_convert_type(bhi, jnp.int64)
    return (ah < bh) | ((ah == bh) & (alo < blo))


def _seg_scan_minmax128(lo, hi, boundary, op):
    """Segmented running signed-128 min/max over (lo, hi) u64 limb pairs."""
    def comb(a, b):
        alo, ahi, ab = a
        blo, bhi, bb = b
        if op == "min":
            pick_b = _dec128_lt(blo, bhi, alo, ahi)
        else:
            pick_b = _dec128_lt(alo, ahi, blo, bhi)
        pick_b = pick_b | bb
        return (jnp.where(pick_b, blo, alo), jnp.where(pick_b, bhi, ahi),
                ab | bb)

    olo, ohi, _ = jax.lax.associative_scan(comb, (lo, hi, boundary))
    return olo, ohi


def _average_decimal_type(p: int, s: int):
    """Spark ``Average`` over DecimalType(p, s): ``DecimalType.bounded(
    p+4, s+4)`` — a plain clamp of precision AND scale to 38 (bounded
    does NOT apply adjustPrecisionScale's integral-digit trade; avg of
    decimal(38, 10) is decimal(38, 14) in Spark)."""
    return min(p + 4, 38), min(s + 4, 38)


def _decimal_avg(s256, cnt, in_dtype):
    """Group average from exact 256-bit sums: rescale to the result scale,
    divide by the count with HALF_UP, overflow -> invalid.

    Returns (limbs128, ok_mask, result_dtype); rows with cnt == 0 divide
    by a masked 1 — callers AND ``ok`` with their has-any mask.
    """
    from ..ops import decimal as D

    p_res, s_res = _average_decimal_type(in_dtype.precision, in_dtype.scale)
    d = s_res - in_dtype.scale  # >= 0 by the bounded rules
    scaled = D._mul(s256, jnp.broadcast_to(D._pow10(d), s256.shape)) \
        if d else s256
    mag, neg = D._abs(scaled)
    den = jnp.maximum(cnt, 1).astype(jnp.uint64)
    # a quotient that fits the result type has a numerator under
    # 10^p_res x 2^31 rows: only that many limbs are divided, and a
    # numerator past them is an overflow whatever its quotient
    limbs = min(8, -(-((10**p_res) << 31).bit_length() // 32))
    q, rem = D._divmod_u_small(mag, den, limbs)
    q = D._add_small(q, ((rem * 2) >= den).astype(jnp.int32))  # HALF_UP
    ok = D._lt_u(q, jnp.broadcast_to(D._pow10(p_res), q.shape)) \
        & (mag[:, limbs:] == 0).all(axis=1)
    signed = jnp.where(neg[:, None], D._neg(q), q)
    return (D._to_i128(signed), ok,
            T.SparkType.decimal(p_res, s_res))


def _resolve_groupby_engine(engine):
    """``engine=None`` reads the ``groupby_engine`` knob; ``auto`` is a
    platform call (scatter on CPU, sort on accelerators — see the module
    docstring for the measurements behind it)."""
    from .. import config as _config

    if engine is None:
        engine = _config.get("groupby_engine")
    if engine == "auto":
        return "scatter" if jax.default_backend() == "cpu" else "sort"
    if engine not in ("sort", "scatter", "pallas"):
        raise ValueError(f"unknown groupby engine {engine!r} "
                         "(use 'auto', 'sort', 'scatter', or 'pallas')")
    return engine


def _resolve_onehot_engine(engine):
    """The domain engines' ``auto`` (the ``q6_onehot_engine`` knob's
    default): segment-sum scatter on the CPU, the XLA one-hot contraction
    on accelerators."""
    if engine == "auto":
        return "scatter" if jax.default_backend() == "cpu" else "xla"
    return engine


def group_by(
    batch: ColumnBatch,
    key_names: Sequence[str],
    aggs: Sequence[AggSpec],
    row_valid=None,
    *,
    engine=None,
    num_slots=None,
    assume_grouped: bool = False,
) -> tuple:
    """Group ``batch`` by ``key_names``; returns (result_batch, num_groups).

    The result batch has the key columns (group order = key sort order,
    nulls first, deterministic — both engines emit the same order)
    followed by one column per AggSpec, padded to the input row count
    with null rows past ``num_groups``.

    ``row_valid`` (bool[n], optional) marks rows that exist: padding rows
    of an upstream filter/shuffle are excluded from every group.  They
    sort to the back as one trailing pseudo-run that the group count and
    end positions simply never reach.

    ``engine``: ``'sort' | 'scatter' | 'pallas' | 'auto'`` (default: the
    ``groupby_engine`` knob; ``'pallas'`` is the scatter engine with the
    slot table built by the fused VMEM kernel, bit-identical and
    interpret-mode-safe off-accelerator).  The scatter engine's slot
    table holds
    ``num_slots`` distinct keys (power of two, default 4096, clamped to
    2n); data with more distinct keys falls back to the sort engine at
    runtime inside the same jitted program, so the hint only costs
    speed, never correctness.  Size it at ~2x the expected key
    cardinality to keep probe chains short.

    ``assume_grouped``: the caller guarantees rows with equal keys are
    already adjacent and (when ``row_valid`` is given) dead rows form
    one trailing run — e.g. the batch came out of an exchange whose sort
    carried the group key as a secondary operand.  The main sort is
    skipped entirely (the boundary scan runs on input order) and groups
    are emitted in first-appearance instead of key order — Spark defines
    no group order.  Implies the sort engine: with no sort left to skip,
    the scatter engine has nothing to offer.
    """
    eng = _resolve_groupby_engine(engine)
    if not assume_grouped and eng in ("scatter", "pallas"):
        # 'pallas' is the scatter engine with the slot table built by the
        # fused VMEM kernel (ops.pallas_kernels) — bit-identical product,
        # so everything downstream of the table is shared
        return _group_by_hash(batch, key_names, aggs, row_valid, num_slots,
                              "pallas" if eng == "pallas" else "lax")
    return _group_by_sortscan(batch, key_names, aggs, row_valid,
                              assume_grouped)


# Group slots a general engine provides for before it has seen the data:
# the scatter engine's default table, and the head of the result at which
# the sort engine reads its scans.
_DEFAULT_GROUP_SLOTS = 4096

# Past the head the sort engine's fetch widens by this factor a step, so
# that it makes at most 16 times the indices the groups need; a finer
# ladder is a branch more to compile in every fetch of every aggregate.
# Three widths short of every row: at most four branches.
_TIER_FACTOR = 16
_TIER_STEPS = 3

# The sorting path's keys are the key columns' bits laid end to end
# (``packed_radix_keys``).  Past this many words the sort takes the spans
# of the live values instead (``span_packed_keys``), in this many words:
# the v5e compiler takes 40-60 s for every key operand of a sort (nine:
# 350-530 s; four: 120 s; PERF.md section 6, PR 37).
_WIDE_KEY_WORDS = 5
_SPAN_KEY_WORDS = 3

# key column representations whose gathered rows _pad_rows can pad
_HEAD_KEY_TYPES = (Column, Decimal128Column, StringColumn, DictionaryColumn)

_ROWWIDE_GATHERS = [0]


def sortscan_tiers(num_rows: Optional[int] = None) -> tuple:
    """The ascending widths, in group slots, at which the sort engine may
    fetch its result over ``num_rows`` input rows: the head, the head
    times 16 and times 256 while they are under the row count, then every
    row (None: more rows than any width, so the ladder without its end).
    Data takes the narrowest width that holds its groups."""
    ladder = tuple(_DEFAULT_GROUP_SLOTS * _TIER_FACTOR ** i
                   for i in range(_TIER_STEPS))
    if num_rows is None:
        return ladder
    n = int(num_rows)
    return tuple(w for w in ladder if w < n) + (n,)


def sortscan_head(num_rows: Optional[int] = None) -> int:
    """The first of :func:`sortscan_tiers`: ``min(num_rows, 4096)``."""
    return sortscan_tiers(num_rows)[0]


def rowwide_gathers() -> int:
    """Gather operations of one index a row (a matrix of words one), over
    inputs of more rows than the head, that the sort engine has traced in
    this process outside the branches that fetch its scans.  The plan
    compiler notes a plan's share
    (``plan.plan_cache_metrics()["agg_rowwide_gathers"]``)."""
    return _ROWWIDE_GATHERS[0]


def _rowwide(n: int, gather):
    """``gather()`` of one index a row of ``n``, its gather operations
    counted as the sort engine's row-wide ones where ``n`` is more than
    the head."""
    before = gather_ops()
    got = gather()
    if n > sortscan_head(n):
        _ROWWIDE_GATHERS[0] += gather_ops() - before
    return got


def _agg_data(col):
    """The data buffer of an aggregated column: a decimal's limbs, or the
    values."""
    return col.limbs if isinstance(col, Decimal128Column) else col.data


def _sort_wide_keys(karr, narrow, occ):
    """The sorting path's sort for keys of many words: ``(sorted words,
    permutation)`` with the order of ``lax.sort(karr + [row id])``, rows of
    equal keys adjacent and equal in every sorted word, a dead row's first
    bit set.  ``narrow`` is :func:`keys.span_packed_keys`' answer: where
    the live keys' spans fit its few words (TPC-H Q18's four keys, 229
    bits by their types, span 84) those and the row id are the sort's only
    operands.  Where they do not, the type-wide words ``karr`` are sorted
    least significant first by one stable two-operand sort inside a loop,
    which costs a gather and a sort a word at run time and next to nothing
    to compile; the answer is the same."""
    packed, fits = narrow
    n = packed[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    if occ is not None:   # a dead row: the flag's bit and nothing else
        packed = [jnp.where(occ, k, jnp.uint32(1 << 31 if i == 0 else 0))
                  for i, k in enumerate(packed)]

    def by_spans(_):
        with scope("agg.sortscan_sort"):
            return tuple(jax.lax.sort(tuple(packed) + (iota,),
                                      num_keys=len(packed) + 1,
                                      is_stable=False))

    def by_words(_):
        with scope("agg.sortscan_sort_passes"):
            stack = jnp.stack(karr)

            def one_pass(i, perm):
                word = jax.lax.dynamic_index_in_dim(
                    stack, len(karr) - 1 - i, axis=0, keepdims=False)
                return jax.lax.sort((word[perm], perm), num_keys=1,
                                    is_stable=True)[1]

            perm = jax.lax.fori_loop(0, len(karr), one_pass, iota)
            in_order = [k[perm] for k in karr]
            run = K.scan_sum((~K.rows_equal_adjacent(in_order))
                             .astype(jnp.uint32))
            # the row flag, then each run of equal keys under its ordinal
            return ((in_order[0] & jnp.uint32(1 << 31), run)
                    + (jnp.zeros((n,), jnp.uint32),) * (len(packed) - 2)
                    + (perm,))

    res = jax.lax.cond(fits, by_spans, by_words, None)
    return res[:-1], res[-1]


def _group_by_sortscan(batch, key_names, aggs, row_valid, assume_grouped):
    """The sort engine: one stable multi-operand sort, then scans."""
    n = batch.num_rows
    batch = _materialize_agg_values(batch, aggs)
    key_cols = _canon_keys([batch[k] for k in key_names])
    have_rv = row_valid is not None
    with scope("agg.sortscan_keys"):
        if assume_grouped:
            karr = K.batch_radix_keys(key_cols, equality=True,
                                      nulls_first=True)
            if have_rv:
                occ = row_valid.astype(jnp.bool_)
                karr = [jnp.where(occ, jnp.uint32(0), jnp.uint32(1))] + [
                    jnp.where(occ, k, jnp.zeros((), k.dtype)) for k in karr
                ]
        else:
            # the sorting path: the same bits laid end to end (the row
            # flag, each key's null flag and words), so that the sort
            # compares as few operands as they fill
            lead = []
            if have_rv:
                occ = row_valid.astype(jnp.bool_)
                lead = [jnp.where(occ, jnp.uint32(0), jnp.uint32(1))]
            karr = K.packed_radix_keys(key_cols, lead_flags=lead,
                                       equality=True, nulls_first=True)
            if have_rv:   # a dead row: the flag's bit and nothing else
                karr = [jnp.where(occ, k, jnp.uint32(1 << 31 if i == 0
                                                     else 0))
                        for i, k in enumerate(karr)]
    iota = jnp.arange(n, dtype=jnp.int32)

    agg_cols = []
    for spec in aggs:
        if spec.column is not None:
            col = batch[spec.column]
            if isinstance(col, StringColumn):
                raise NotImplementedError(
                    f"{spec.op} over {col.dtype!r} groups not implemented yet"
                )
            if spec.column not in agg_cols:
                agg_cols.append(spec.column)
    # Two ways to move agg values into sorted order, on the sorting path
    # only (config ``group_sort_payload``; grouped input is read where it
    # lies).  'ride': values ride the sort as payload operands — no
    # post-sort gathers, but every 64-bit operand is an emulated u32 pair
    # inside the TPU sort network.  'gather' (the default): the sort
    # carries only [keys..., row-id] and each agg column is fetched
    # afterwards along the permutation, every column's data and
    # validity together (relational.gather.gather_rows).
    from .. import config as _config

    ride = (not assume_grouped
            and _config.get("group_sort_payload") == "ride")
    payload = [iota]
    spans = {}
    if ride:
        # agg data rides the sort in its native dtype (the TPU X64-rewrite
        # pass legalizes 64-bit sort payloads but not u32-pair bitcasts).
        # Decimal128 limbs are [n, 2] and cannot be sort operands — those
        # columns always gather along the permutation instead.
        for name in agg_cols:
            col = batch[name]
            if isinstance(col, Decimal128Column):
                continue
            spans[name] = len(payload)
            payload.extend([col.data, col.validity])

    nk = len(karr)
    narrow = None
    if not assume_grouped and not ride and n > 0 and nk > _WIDE_KEY_WORDS:
        with scope("agg.sortscan_keys"):
            narrow = K.span_packed_keys(
                key_cols, lead_flags=lead,
                live=occ if have_rv else jnp.ones((n,), jnp.bool_),
                words=_SPAN_KEY_WORDS, equality=True, nulls_first=True)
    if narrow is not None:
        skeys, sperm = _sort_wide_keys(karr, narrow,
                                       occ if have_rv else None)
        nk, spay = len(skeys), ()
    elif assume_grouped:
        # sort-order reuse: an upstream stage already laid equal keys out
        # adjacently (dead rows in one trailing run), so the boundary
        # scan below works on input order directly and the whole sort —
        # the engine's dominant cost — disappears.
        skeys = tuple(karr)
        sperm = spay = None
    else:
        with scope("agg.sortscan_sort"):
            # the row id as the last key makes the order total: one
            # answer, the stable sort's, from the unstable sort (which
            # the v5e compiler builds in half the time)
            res = jax.lax.sort(tuple(karr) + tuple(payload),
                               num_keys=nk + 1, is_stable=False)
        skeys = res[:nk]
        sperm = res[nk]
        spay = res[nk + 1:]

    with scope("agg.sortscan_boundary"):
        boundary = ~K.rows_equal_adjacent(skeys)
        if not have_rv:
            sorted_occ = jnp.ones((n,), jnp.bool_)
        elif assume_grouped:
            sorted_occ = skeys[0] == 0
        else:   # the row flag is the packed words' first bit
            sorted_occ = (skeys[0] >> jnp.uint32(31)) == 0
        num_groups = (boundary & sorted_occ).sum(dtype=jnp.int32)

        # last row of each live group: next row starts a new group / is
        # dead / doesn't exist
        nxt_boundary = jnp.concatenate(
            [boundary[1:], jnp.ones((1,), jnp.bool_)])
        nxt_occ = jnp.concatenate(
            [sorted_occ[1:], jnp.zeros((1,), jnp.bool_)])
        is_end = sorted_occ & (nxt_boundary | ~nxt_occ)
        # compact end positions to the front (2-operand flag sort, no
        # scatter)
        ends = jax.lax.sort(
            ((~is_end).astype(jnp.uint32), iota), num_keys=1,
            is_stable=True
        )[1]
        prev_ends = jnp.roll(ends, 1)
        out_valid = iota < num_groups

    # A result of num_groups rows is read at the head of the group slots:
    # the scans stay whole, but what fetches them at the group ends takes
    # ``w`` indices and pads back to n rows, for the narrowest width of
    # the ladder that holds the data's groups (one scalar, the widths
    # that num_groups exceeds, picks the branch at run time).
    tiers = sortscan_tiers(n)
    tier = sum((num_groups > w).astype(jnp.int32) for w in tiers[:-1])
    tier_scopes = (["agg.sortscan_head"]
                   + [f"agg.sortscan_tier.{w}" for w in tiers[1:-1]]
                   + ["agg.sortscan_full"])

    def per_group(read, pad):
        """``read(w)``: per-group values at the first ``w`` group slots;
        padded by ``pad`` to the n rows of the result where ``w`` is under
        them."""
        if len(tiers) == 1:
            return read(n)

        def at(w, name):
            def fetch(_):
                with scope(name):
                    return read(n) if w == n else pad(read(w))

            return fetch

        return jax.lax.switch(
            tier, [at(w, name) for w, name in zip(tiers, tier_scopes)], None)

    first = iota == 0

    def ends_diff(ce):
        """Per-group totals from a prefix scan read at the group ends
        (``ce``): the scan at the group before is ``ce`` moved one slot.
        A 64-bit scan is moved and subtracted as its 32-bit halves, with
        the borrow: moved whole, the v5e compiler's program lost the high
        half of the scan at the group before at one slot in every 79,872
        (sums off by multiples of 2^32 once the scan had passed 2^32:
        PERF.md section 6, PR 37; the ``tpch-q18.served`` cell holds it)."""
        at_first = first[:ce.shape[0]]

        def before(x):
            return jnp.where(at_first, jnp.zeros((), x.dtype), jnp.roll(x, 1))

        if ce.dtype.itemsize < 8:
            return ce - before(ce)
        hi, lo = K._split64(ce.astype(jnp.uint64))
        hi_b, lo_b = before(hi), before(lo)
        d_hi = hi - hi_b - (lo < lo_b).astype(jnp.uint32)
        return ((d_hi.astype(jnp.uint64) << jnp.uint64(32))
                | (lo - lo_b).astype(jnp.uint64)).astype(ce.dtype)

    def itself(ce):
        return ce

    with scope("agg.sortscan_reduce"):
        # the aggregated columns in sorted row order: where they lie under
        # assume_grouped, or riding the sort; else every column's data (a
        # decimal in 64-bit storage narrow, widened after) and validity
        # moved along the permutation together
        sdata, svalidity = {}, {}
        for name in agg_cols:
            if assume_grouped:
                sdata[name] = _agg_data(batch[name])
                svalidity[name] = batch[name].validity
            elif name in spans:   # payload[0] is iota (== sperm)
                sdata[name] = spay[spans[name] - 1]
                svalidity[name] = spay[spans[name]]
        moved = [name for name in agg_cols if name not in svalidity]
        with_data = [name for name in moved
                     if any(spec.column == name and spec.op != "count"
                            for spec in aggs)]
        if moved:
            data, valid = _rowwide(n, lambda: gather_rows(
                [_agg_data(batch[name]) for name in with_data],
                [batch[name].validity for name in moved], sperm))
            sdata.update(zip(with_data, data))
            svalidity.update(zip(moved, valid))

        def sorted_valid(name):
            return svalidity[name] & sorted_occ

        # Every value read at the group ends comes from one fetch: the
        # arrays ``reads`` at ``ends[:w]`` (relational.gather.gather_rows:
        # their words as matrices in a row gather), and each value a
        # function of its reads, computed inside the fetch's branch.
        reads, values = [], []

        def at_ends(fn, *arrays):
            """The index, among the fetched values, of ``fn`` of
            ``arrays`` at the group ends."""
            values.append((fn, len(reads), len(arrays)))
            reads.extend(arrays)
            return len(values) - 1

        made = {}

        def once(key, make):
            """``make()``'s index, made once for every aggregate that
            reads the same value (``key``)."""
            if key not in made:
                made[key] = make()
            return made[key]

        def counts(name):
            """Each group's live rows (``None``) or non-null values of
            ``name``, int32: a count never passes the int32 row ids."""
            def make():
                flags = sorted_occ if name is None else sorted_valid(name)
                return at_ends(ends_diff, K.scan_sum(flags.astype(jnp.int32)))

            return once(("count", name), make)

        def planned(spec):
            """What ``spec`` reads at the group ends; returns what makes
            its column of the fetched values."""
            if spec.op == "count":
                h = counts(spec.column)
                return lambda got: Column(got[h].astype(jnp.int64),
                                          out_valid, T.INT64)

            dcol = batch[spec.column]
            # Spark types sum(decimal(p,s)) decimal(p+10,s) whatever stores
            # the column: a 64-bit one sums on the same limbs
            if isinstance(dcol, Decimal128Column) or (
                    _is_decimal(dcol.dtype) and spec.op in ("sum", "mean")):
                # Decimal128 aggregation over sorted runs.  sum/mean: exact
                # 256-bit segmented sums (values sign-extend to uint32[n,8]; a
                # 2^31-row group of |v|<2^127 stays < 2^158, never wraps) —
                # sum gets Spark's decimal(min(38, p+10), s) with overflow ->
                # null, mean divides by the count per Average's bounded(p+4,
                # s+4) HALF_UP.  min/max: signed-128 segmented scans on the
                # raw limb pairs.  (Non-ANSI nullOnOverflow; reference
                # DecimalUtils ops are per-element — group aggregation lives
                # above cudf in the plugin, so semantics follow Spark's
                # aggregate expressions.)
                from ..ops import decimal as D

                svalid = sorted_valid(spec.column)
                slimbs = sdata[spec.column]
                if slimbs.ndim == 1:   # in 32- or 64-bit storage
                    slimbs = _widen_decimal(
                        Column(slimbs, svalid, dcol.dtype)).limbs
                h_nn = counts(spec.column)

                def has_any(got):
                    return out_valid & (got[h_nn] > 0)

                if spec.op in ("min", "max"):
                    if spec.op == "min":  # fill nulls with +max signed 128
                        flo = jnp.uint64(0xFFFFFFFFFFFFFFFF)
                        fhi = jnp.uint64(0x7FFFFFFFFFFFFFFF)
                    else:                 # fill with -min signed 128
                        flo = jnp.uint64(0)
                        fhi = jnp.uint64(0x8000000000000000)
                    lo = jnp.where(svalid, slimbs[:, 0], flo)
                    hi = jnp.where(svalid, slimbs[:, 1], fhi)
                    rlo, rhi = _seg_scan_minmax128(lo, hi, boundary, spec.op)
                    h = at_ends(lambda a, b: jnp.stack([a, b], axis=1),
                                rlo, rhi)
                    return lambda got: Decimal128Column(got[h], has_any(got),
                                                        dcol.dtype)
                # Each 32-bit lane of the two's-complement value is summed
                # on its own as a 64-bit prefix scan (2^31 rows of < 2^32
                # stay under 2^63) and read at the group ends; a negative
                # row's four sign-extension lanes are 2^32 - 1 each, so
                # the count of negatives stands for them.  The carries are
                # folded once, on the per-group sums.  (A segmented
                # 256-bit associative scan gave the same sums; the v5e
                # compiler had not built it for 2^20 rows after 50
                # minutes of CPU time and 11 GB: PERF.md section 6, PR 35.)
                m32 = jnp.uint64(0xFFFFFFFF)

                def lane_sums(*ce):
                    low4 = [ends_diff(c) for c in ce[:4]]
                    ext = ends_diff(ce[4]).astype(jnp.uint64) * m32
                    return _carry_fold_u64_lanes(
                        jnp.stack(low4 + [ext] * 4, axis=1))

                def sums():
                    live = jnp.where(svalid[:, None], slimbs,
                                     jnp.zeros((), jnp.uint64))
                    scans = [K.scan_sum(x) for x in (
                        live[:, 0] & m32, live[:, 0] >> jnp.uint64(32),
                        live[:, 1] & m32, live[:, 1] >> jnp.uint64(32))]
                    negatives = K.scan_sum(
                        (live[:, 1] >> jnp.uint64(63)).astype(jnp.int32))
                    return at_ends(lane_sums, *scans, negatives)

                h = once(("lanes", spec.column), sums)
                if spec.op == "mean":
                    def mean(got):
                        limbs128, ok, out_t = _decimal_avg(got[h], got[h_nn],
                                                           dcol.dtype)
                        return Decimal128Column(limbs128, has_any(got) & ok,
                                                out_t)

                    return mean
                out_p = min(38, dcol.dtype.precision + 10)

                def total(got):
                    mag, _ = D._abs(got[h])
                    overflow = ~D._lt_u(mag, jnp.broadcast_to(
                        D._pow10(out_p), mag.shape))
                    return Decimal128Column(
                        D._to_i128(got[h]), has_any(got) & ~overflow,
                        T.SparkType.decimal(out_p, dcol.dtype.scale))

                return total

            data, valid = sdata[spec.column], sorted_valid(spec.column)
            col_dtype = dcol.dtype
            h_nn = counts(spec.column)

            if spec.op in ("sum", "mean"):
                out_t = (T.FLOAT64 if spec.op == "mean"
                         else _sum_dtype(col_dtype))
                acc = data.astype(out_t.jnp_dtype if spec.op == "sum"
                                  else jnp.float64)
                acc = jnp.where(valid, acc, jnp.zeros((), acc.dtype))
                if jnp.issubdtype(acc.dtype, jnp.floating):
                    h = once(("sum", spec.column, acc.dtype), lambda: at_ends(
                        itself, _seg_scan_sum(acc, boundary)))
                else:   # exact mod-2^64
                    h = once(("sum", spec.column, acc.dtype), lambda: at_ends(
                        ends_diff, K.scan_sum(acc)))

                def total(got):
                    s = got[h]
                    if spec.op == "mean":
                        s = s / jnp.maximum(got[h_nn], 1).astype(jnp.float64)
                    return Column(s, out_valid & (got[h_nn] > 0), out_t)

                return total
            # min / max — Spark float semantics: NaN greatest, one NaN
            is_float = jnp.issubdtype(data.dtype, jnp.floating)
            was_bool = data.dtype == jnp.bool_
            if is_float:
                fill = jnp.array(jnp.inf if spec.op == "min" else -jnp.inf,
                                 data.dtype)
                nan_in = valid & jnp.isnan(data)
                valid_num = valid & ~jnp.isnan(data)
            elif was_bool:   # a word, so that a matrix carries its run
                data = data.astype(jnp.uint32)
                fill = jnp.uint32(1 if spec.op == "min" else 0)
                valid_num = valid
            else:
                info = jnp.iinfo(data.dtype)
                fill = jnp.array(
                    info.max if spec.op == "min" else info.min,
                    data.dtype)
                valid_num = valid
            masked = jnp.where(valid_num, data, fill)
            h = at_ends(itself, _seg_scan_minmax(masked, boundary, spec.op))
            if is_float:
                h_nan = once(("nan", spec.column), lambda: at_ends(
                    ends_diff, K.scan_sum(nan_in.astype(jnp.int32))))
                h_num = once(("num", spec.column), lambda: at_ends(
                    ends_diff, K.scan_sum(valid_num.astype(jnp.int32))))

            def extreme(got):
                r = got[h]
                if is_float:
                    seg_nan = got[h_nan] > 0
                    nan = jnp.array(jnp.nan, r.dtype)
                    if spec.op == "max":
                        r = jnp.where(seg_nan, nan, r)
                    else:
                        r = jnp.where(seg_nan & ~(got[h_num] > 0), nan, r)
                if was_bool:
                    r = r.astype(jnp.bool_)
                return Column(r, out_valid & (got[h_nn] > 0), col_dtype)

            return extreme

        finish = [(spec.out_name, planned(spec)) for spec in aggs]

        head_keys = all(isinstance(batch[name], _HEAD_KEY_TYPES)
                        for name in key_names)
        key_batch = ColumnBatch({name: batch[name] for name in key_names})
        starts = jnp.clip(jnp.where(first, 0, prev_ends + 1), 0, n - 1)
        if head_keys and not assume_grouped:
            # a group's first sorted row is the row after the group
            # before's end: the permutation one slot on, read at the ends
            h_rows0 = at_ends(
                lambda after: jnp.where(first[:after.shape[0]], sperm[:1],
                                        jnp.roll(after, 1)),
                jnp.concatenate([sperm[1:], sperm[-1:]]))

        def fetch(w):
            got = gather_rows(reads, (), ends[:w])[0]
            return [fn(*got[at:at + k]) for fn, at, k in values]

        vals = per_group(fetch, pad=lambda got: [
            _pad_leading(v, n - v.shape[0]) for v in got])

        def first_rows(w):
            """Each key column at its group's first row."""
            rows0 = starts[:w] if assume_grouped else vals[h_rows0][:w]
            return list(gather_batch(key_batch, rows0, out_valid[:w]).columns)

        if head_keys:
            # a switch of their own: with the values' fetch in the same
            # branch the v5e compiler crashed (a segmentation fault in its
            # layout passes) on TPC-H Q18's aggregate below its exchange
            firsts = per_group(first_rows, pad=lambda cols: [
                _pad_rows(c, n) for c in cols])
        else:   # a representation with no null-row padding
            def whole():
                rows0 = starts if assume_grouped else take_rows(sperm, starts)
                return [gather_column(batch[name], rows0, out_valid)
                        for name in key_batch.names]

            firsts = _rowwide(n, whole)
        out = dict(zip(key_batch.names, firsts))
        for name, make in finish:
            out[name] = make(vals)

    return ColumnBatch(out), num_groups


def _group_by_hash(batch, key_names, aggs, row_valid, num_slots,
                   table_engine: str = "lax"):
    """The scatter engine: slot-table key mapping + segment reductions.

    Same contract, semantics, and group order as the sort engine — the
    only rounding difference is float sums/means (scatter-add order vs
    segmented-scan order; Spark itself is order-nondeterministic there).
    Slot-table overflow falls back to the sort engine via ``lax.cond``.
    ``table_engine`` picks the slot-table implementation (``'lax'`` or
    the fused ``'pallas'`` kernel — bit-identical either way).
    """
    from . import hashtable as H
    from ..plan import adaptive as _adaptive

    n = batch.num_rows
    batch = _materialize_agg_values(batch, aggs)
    key_cols = _canon_keys([batch[k] for k in key_names])
    karr = K.batch_radix_keys(key_cols, equality=True, nulls_first=True)
    row_live = jnp.ones((n,), jnp.bool_) if row_valid is None else \
        row_valid.astype(jnp.bool_)
    S = H.next_pow2(_DEFAULT_GROUP_SLOTS if num_slots is None
                    else int(num_slots))
    S = min(S, H.next_pow2(2 * n))
    # a spuriously long probe chain only costs a fallback to the sort
    # engine, so the round bound stays far below the table size — the
    # adaptive layer tightens it further from the observed load factor
    owner, slot, overflow = H.build_slot_table(
        karr, row_live, S, max_rounds=_adaptive.bound_build_rounds(n, S),
        engine=table_engine)

    def scat(_):
        return _scatter_groups(batch, key_names, aggs, karr, row_live,
                               owner, slot, S)

    def srt(_):
        return _group_by_sortscan(batch, key_names, aggs, row_valid, False)

    return jax.lax.cond(overflow, srt, scat, None)


def _scatter_groups(batch, key_names, aggs, karr, row_live, owner, slot, S):
    """Segment-reduction group-by over a resolved slot table.

    ``slot`` (int32[n], dead rows -> S) is the segment id; every
    aggregate is one ``segment_*`` over ``S + 1`` segments (segment S
    discards dead rows).  The S slots then sort by their owner's key
    words — a table-sized sort, not a row-sized one — so groups come out
    in exactly the sort engine's order (key order, nulls first), with
    the same representative row per group (the slot owner is the
    minimum row id of its key, which is also what the stable sort
    exposes as the group's first row).
    """
    from jax.ops import segment_max, segment_min, segment_sum

    n = batch.num_rows
    iota = jnp.arange(n, dtype=jnp.int32)
    dead_slot = owner == n
    oc = jnp.clip(owner, 0, n - 1)

    ops = [dead_slot.astype(jnp.uint32)] + [
        jnp.where(dead_slot, jnp.zeros((), k.dtype), jnp.take(k, oc))
        for k in karr] + [jnp.arange(S, dtype=jnp.int32)]
    rank2slot = jax.lax.sort(tuple(ops), num_keys=len(ops) - 1,
                             is_stable=True)[-1]
    num_groups = (~dead_slot).sum(dtype=jnp.int32)
    out_valid = iota < num_groups

    def per_group(per_slot):
        """[S+1] (or [S+1, ...]) segment result -> [n] in group-rank
        order (pad with zeros when the table is smaller than the batch;
        live groups always fit — there are at most n of them)."""
        a = jnp.take(per_slot[:S], rank2slot, axis=0)
        if a.shape[0] >= n:
            return a[:n]
        pad = jnp.zeros((n - a.shape[0],) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, pad], axis=0)

    def seg_sum(vals):
        return per_group(segment_sum(vals, slot, num_segments=S + 1))

    rows0 = per_group(oc)
    out = {}
    for name in key_names:
        out[name] = gather_column(batch[name], rows0, out_valid)

    for spec in aggs:
        if spec.column is not None and \
                isinstance(batch[spec.column], StringColumn):
            raise NotImplementedError(
                f"{spec.op} over {batch[spec.column].dtype!r} groups "
                "not implemented yet")
        if spec.op == "count":
            if spec.column is None:
                ones = row_live.astype(jnp.int64)
            else:
                ones = (batch[spec.column].validity
                        & row_live).astype(jnp.int64)
            out[spec.out_name] = Column(seg_sum(ones), out_valid, T.INT64)
            continue

        col = batch[spec.column]
        if _is_decimal(col.dtype) and spec.op in ("sum", "mean"):
            col = _widen_decimal(col)   # typed by Spark whatever stores it
        valid = col.validity & row_live
        nn = seg_sum(valid.astype(jnp.int32))
        has_any = nn > 0

        if isinstance(col, Decimal128Column):
            from ..ops import decimal as D

            has_any_d = out_valid & has_any
            if spec.op in ("min", "max"):
                # signed-128 min/max in two passes: elect the extreme hi
                # limb (signed), then the extreme unsigned lo limb among
                # rows holding it.  Fills match the sort engine's and the
                # segment identities (so empty/all-null groups agree).
                if spec.op == "min":
                    flo = jnp.uint64(0xFFFFFFFFFFFFFFFF)
                    fhi = jnp.uint64(0x7FFFFFFFFFFFFFFF)
                    seg_mm = segment_min
                else:
                    flo = jnp.uint64(0)
                    fhi = jnp.uint64(0x8000000000000000)
                    seg_mm = segment_max
                lo = jnp.where(valid, col.limbs[:, 0], flo)
                hi_i = jax.lax.bitcast_convert_type(
                    jnp.where(valid, col.limbs[:, 1], fhi), jnp.int64)
                m_hi = seg_mm(hi_i, slot, num_segments=S + 1)
                at_best = valid & (hi_i == jnp.take(m_hi, slot))
                m_lo = seg_mm(jnp.where(at_best, lo, flo), slot,
                              num_segments=S + 1)
                out[spec.out_name] = Decimal128Column(
                    jnp.stack([per_group(m_lo),
                               jax.lax.bitcast_convert_type(
                                   per_group(m_hi), jnp.uint64)], axis=1),
                    has_any_d, col.dtype)
                continue
            # sum / mean: exact 256-bit sums, u32 lanes summed in u64
            # (n <= 2^31 rows of < 2^32 stays under 2^63), carry-folded
            # once — the same argument as _domain_partials_scatter
            u = D._from_i128(jnp.where(valid[:, None], col.limbs,
                                       jnp.zeros((), jnp.uint64)))
            lanes = segment_sum(u.astype(jnp.uint64), slot,
                                num_segments=S + 1)
            s256 = per_group(_carry_fold_u64_lanes(lanes[:S]))
            if spec.op == "mean":
                limbs128, ok, out_t = _decimal_avg(s256, nn, col.dtype)
                out[spec.out_name] = Decimal128Column(
                    limbs128, has_any_d & ok, out_t)
                continue
            out_p = min(38, col.dtype.precision + 10)
            mag, _ = D._abs(s256)
            dovf = ~D._lt_u(mag, jnp.broadcast_to(D._pow10(out_p),
                                                  mag.shape))
            out[spec.out_name] = Decimal128Column(
                D._to_i128(s256), has_any_d & ~dovf,
                T.SparkType.decimal(out_p, col.dtype.scale))
            continue

        data = col.data
        if spec.op in ("sum", "mean"):
            out_t = T.FLOAT64 if spec.op == "mean" else _sum_dtype(col.dtype)
            acc = data.astype(out_t.jnp_dtype if spec.op == "sum"
                              else jnp.float64)
            acc = jnp.where(valid, acc, jnp.zeros((), acc.dtype))
            s = seg_sum(acc)
            if spec.op == "mean":
                s = s / jnp.maximum(nn, 1).astype(jnp.float64)
            out[spec.out_name] = Column(s, out_valid & has_any, out_t)
        else:  # min / max — same fills and NaN rules as the sort engine
            is_float = jnp.issubdtype(data.dtype, jnp.floating)
            was_bool = data.dtype == jnp.bool_
            if is_float:
                fill = jnp.array(jnp.inf if spec.op == "min" else -jnp.inf,
                                 data.dtype)
                nan_in = valid & jnp.isnan(data)
                valid_num = valid & ~jnp.isnan(data)
            elif was_bool:
                data = data.astype(jnp.uint8)
                fill = jnp.uint8(1 if spec.op == "min" else 0)
                valid_num = valid
            else:
                info = jnp.iinfo(data.dtype)
                fill = jnp.array(info.max if spec.op == "min" else info.min,
                                 data.dtype)
                valid_num = valid
            masked = jnp.where(valid_num, data, fill)
            seg_mm = segment_min if spec.op == "min" else segment_max
            r = per_group(seg_mm(masked, slot, num_segments=S + 1))
            if is_float:
                seg_nan = seg_sum(nan_in.astype(jnp.int32)) > 0
                seg_num = seg_sum(valid_num.astype(jnp.int32)) > 0
                nan = jnp.array(jnp.nan, r.dtype)
                if spec.op == "max":
                    r = jnp.where(seg_nan, nan, r)
                else:
                    r = jnp.where(seg_nan & ~seg_num, nan, r)
            if was_bool:
                r = r.astype(jnp.bool_)
            out[spec.out_name] = Column(r, out_valid & has_any, col.dtype)

    return ColumnBatch(out), num_groups


# ---------------------------------------------------------------------------
# MXU path: one-hot int8 matmul aggregation for small static key domains
# ---------------------------------------------------------------------------

def group_by_onehot(
    batch: ColumnBatch,
    key_name: str,
    aggs: Sequence[AggSpec],
    domain,
    row_valid=None,
    float_mode: str = "f64",
    engine: str = "xla",
    derive: Optional[Derived] = None,
):
    """Hash-aggregate as matmuls: the TPU-first alternative to the
    sort-scan path when one integer key column has a small static domain
    ``[0, domain)`` (dimension ids, date ordinals, bucketed keys — the q6
    shape).  The per-key FLOPs land on the MXU instead of the VPU sort
    network:

    * one-hot ``[n, K+1]`` int8 (bucket K holds null keys);
    * ALL integer payloads ride ONE chunked int8 x int8 -> int32
      contraction: column 0 is the count(*) ones, then per referenced
      column a validity flag, then for each integer sum the eight byte
      limbs ``b_l - 128`` (exact: true limb sums are rebuilt with
      ``+128*count`` and recombined in uint64 with Spark's non-ANSI
      wraparound).  One HBM pass over the one-hot instead of one per agg;
    * float sums in ``f64`` mode ride the SAME int8 contraction as exact
      fixed-point digits: per column the grid is ``2^(emax - 1075 - 38)``
      (``emax`` the largest live exponent), a row is 13 signed digits of
      7 bits plus three NaN/+inf/-inf flags (16 more slots), and the
      double is put together from the digit sums in
      ``agg.onehot_rebuild``.  Integer sums are exact on the MXU in any
      order, so the bucket's sum is the exact sum of its rows, each
      truncated toward zero on the grid (less than one grid unit a row
      and limb: over n <= 2^24 rows under 2^-13 ulp of the column's
      largest magnitude), rounded once — tighter than any order of f64
      additions and bit-identical under any permutation of the rows.
      Where nothing is truncated (a column within 2^38 of its largest
      value, q6's prices) it is ``math.fsum``.  Unlike IEEE addition a
      bucket of nothing but ``-0.0`` sums to ``+0.0``; one NaN or
      infinite row touches its own bucket only;
    * float sums in ``f32x3`` mode ride ONE f32 contraction (exact 3-way
      Dekker split of the f64 mantissa — MXU-native, but accumulated in
      f32: about 5e-5 relative off at q6's size);
    * decimal sums (any storage width) are exact: the two's-complement
      bytes a ``decimal(p, s)`` needs (6 for p=12, 11 for p=26, 16 for
      p=38) as ``b_l - 128`` limbs plus one negative-flag slot, rebuilt in
      256 bits, typed ``decimal(min(38, p+10), s)`` and judged against
      ``10^precision`` (past it the group is null, Spark's non-ANSI
      ``Sum``); the average is ``decimal(p+4, s+4)``, HALF_UP;
    * mean: sum / count in f64.

    ``key_name`` and ``domain`` may be tuples, one domain per key: the
    keys become one composite bucket (each key's null a bucket of its
    own, first), and the groups come out in key order, nulls first.
    ``derive`` (:class:`Derived`) names aggregated columns that are
    computed from the batch's own: the XLA engine then builds payload and
    one-hot one slice of ``_ONEHOT_SLICE`` rows at a time inside a loop
    (``agg.onehot_slice``) and evaluates them there.

    min/max stay on the sort-scan path.  Returns
    ``(result, num_groups, overflow)`` — ``overflow`` is a device bool
    that is True if any non-null key fell outside ``[0, domain)`` (result
    is then invalid; callers assert or fall back).

    ``engine="pallas"`` routes the contraction through the fused
    :func:`ops.pallas_kernels.onehot_groupby_parts` kernel, which never
    materializes the one-hot in HBM (the XLA engine does, twice at the
    widest dtype); the pallas engine always uses the f32x3 float split.
    ``engine="scatter"`` delegates to :func:`group_by_scatter` (linear
    segment sums — the CPU-fast engine); ``engine="auto"`` resolves per
    platform: scatter on CPU, xla one-hot on accelerators (measured both
    ways round 4: segment_sum 80x faster on XLA-CPU, scatters 2 orders
    slow on v5e).

    Internally this is :func:`_domain_partials` (additive per-bucket
    partials — the map-side-combine unit that
    :func:`parallel.distributed.distributed_group_by_domain` psum-merges
    across a mesh) followed by :func:`_finalize_domain`.
    """
    parts, overflow = _domain_partials(batch, key_name, aggs, domain,
                                       row_valid, engine, float_mode,
                                       derive)
    with scope("agg.finalize"):
        res, ng = _finalize_domain(
            batch, key_name, domain, aggs, parts,
            dtypes=derive.dtypes if derive is not None else None)
    return res, ng, overflow


def _domain_partials(batch, key_name, aggs, domain, row_valid=None,
                     engine="auto", float_mode="f64", derive=None):
    """Additive per-bucket partial aggregates over a static key domain.

    Returns ``(parts, overflow)`` where ``parts`` is a pytree of
    psum-mergeable arrays over buckets ``[0, K]`` (bucket K = null keys):

    * ``star``  int64[K+1] — count(*) rows
    * ``cnt``   {col: int64[K+1]} — non-null counts
    * ``isum``  {col: int64[K+1]} — integer sums (wrap mod 2^64 under
      merging, exactly Spark's non-ANSI overflow)
    * ``fsum``  {col: float64[K+1]} — float sums (merge-order rounding
      sits inside Spark's shuffle nondeterminism)
    * ``d64``   {col: uint64[K+1, 8]} — decimal sums as 256-bit
      two's-complement u32 limbs widened to u64, so a psum over P
      devices cannot carry out of a lane (P·2^32 < 2^64); the merged
      lanes re-fold in :func:`_finalize_domain`

    Every leaf is additive: element-wise sum of two devices' parts is
    the parts of their concatenated rows.  min/max are not expressible
    this way under psum and stay on the sort-scan path.
    """
    # the domain engines run arithmetic on raw key/value buffers: encoded
    # columns materialize here (their late point of need)
    batch = materialize_batch(batch)
    engine = _resolve_onehot_engine(engine)
    if derive is not None and engine != "xla":
        # these engines read whole columns: so are the computed ones
        batch, derive = derive.over(batch), None
    if engine == "scatter":
        return _domain_partials_scatter(batch, key_name, aggs, domain,
                                        row_valid)
    return _domain_partials_onehot(batch, key_name, aggs, domain,
                                   row_valid, float_mode, engine, derive)


# Rows to a block of the one-hot contraction.  int32 partials hold
# |x| <= 128 a row, so a block stays under 2^31/128 = 2^24 rows.  A payload
# built whole is contracted in _ONEHOT_BLOCK rows at a time; one built slice
# by slice (computed columns) takes _ONEHOT_SLICE rows, payload and all.
_ONEHOT_BLOCK = 1 << 23
_ONEHOT_SLICE = 1 << 19
_ONEHOT_SLOTS = [0]


def onehot_slots() -> int:
    """The int8 slots of the newest one-hot contraction this process
    traced (q6 under ``f64``: 27): the width its payload's assembly and the
    contraction scale with.  ``plan.plan_cache_metrics()`` carries it."""
    return _ONEHOT_SLOTS[0]


def _keys_domains(key_name, domain):
    """One key and an int domain is the single-key layout (null keys in
    bucket K, last); tuples are one domain per key of a composite bucket
    (each key's null first)."""
    if isinstance(key_name, str):
        return (key_name,), int(domain)
    keys = tuple(key_name)
    domains = tuple(int(d) for d in domain) \
        if isinstance(domain, (list, tuple)) else (int(domain),)
    if len(keys) != len(domains):
        raise ValueError(f"{len(domains)} domains for {len(keys)} keys")
    return keys, domains


def _bucket_count(domains) -> int:
    if isinstance(domains, int):
        return domains + 1
    g = 1
    for K_ in domains:
        g *= K_ + 1
    return g


def _domain_buckets(batch, keys, domains, row_live):
    """Bucket id per row and the out-of-domain flag, for either layout.
    Composite: key i contributes ``0`` for a null and ``k + 1`` for ``k``,
    most significant key first, so bucket order is key order with nulls
    first.  Dead rows get some bucket; callers mask them by ``row_live``."""
    for k in keys:
        if batch[k].dtype.kind not in (T.Kind.INT8, T.Kind.INT16,
                                       T.Kind.INT32, T.Kind.INT64):
            raise TypeError("the domain engines need integer key columns")
    if isinstance(domains, int):
        col = batch[keys[0]]
        return _domain_bucket_overflow(col, col.validity & row_live, domains)
    bucket = jnp.zeros(row_live.shape, jnp.int32)
    overflow = jnp.zeros((), jnp.bool_)
    for k, K_ in zip(keys, domains):
        col = batch[k]
        # the bounds check at the key's own width (int64 only for an
        # int64 key: a narrower one cannot hold what would wrap)
        wide = jnp.int64 if col.data.dtype == jnp.int64 else jnp.int32
        kv = col.data.astype(wide)
        overflow = overflow | jnp.any(col.validity & row_live
                                      & ((kv < 0) | (kv >= K_)))
        idx = jnp.where(col.validity,
                        jnp.clip(kv, 0, K_ - 1).astype(jnp.int32) + 1, 0)
        bucket = bucket * jnp.int32(K_ + 1) + idx
    return bucket, overflow


def _decimal_sum_bytes(precision: int) -> int:
    """Two's-complement bytes that hold every ``|v| < 10^precision``."""
    return -(-((10**precision - 1).bit_length() + 1) // 8)


@dataclasses.dataclass
class _SlotPlan:
    """The int8 slots of the stacked payload: ``[0]`` the count(*) ones,
    one valid flag per referenced column, eight byte limbs per integer
    sum column, then per decimal sum column the bytes its precision needs
    and a negative flag (``m8`` slots; float digits, where the mode has
    them, follow)."""

    valid_slot: dict
    int_cols: list
    float_cols: list
    dec_cols: list
    limb_slot: dict
    dec_slot: dict   # column -> (first slot, bytes)
    m8: int


def _plan_onehot_slots(aggs, dtype_of) -> _SlotPlan:
    valid_slot = {}
    int_cols, float_cols, dec_cols = [], [], []
    for spec in aggs:
        if spec.op not in ("sum", "mean", "count"):
            raise NotImplementedError(
                f"group_by_onehot: {spec.op} stays on the sort-scan path")
        if spec.column is None:
            continue
        c = spec.column
        valid_slot.setdefault(c, 0)  # slot index assigned below
        if spec.op in ("sum", "mean"):
            dt = dtype_of(c)
            if _is_decimal(dt):
                target = dec_cols
            elif dt.kind in (T.Kind.FLOAT32, T.Kind.FLOAT64):
                target = float_cols
            else:
                target = int_cols
            if c not in target:
                target.append(c)
    m = 1  # slot 0: count(*)
    for c in valid_slot:
        valid_slot[c] = m
        m += 1
    limb_slot = {}
    for c in int_cols:
        limb_slot[c] = m
        m += 8
    dec_slot = {}
    for c in dec_cols:
        nb = _decimal_sum_bytes(dtype_of(c).precision)
        dec_slot[c] = (m, nb)
        m += nb + 1
    return _SlotPlan(valid_slot, int_cols, float_cols, dec_cols, limb_slot,
                     dec_slot, m)


def _onehot_payload8(cols, row_live, lay: _SlotPlan):
    """The int8 columns of ``lay``'s count, valid-flag and integer-limb
    slots over the rows ``cols`` holds."""
    n = row_live.shape[0]
    cols8 = [jnp.ones((n,), jnp.int8)]  # slot 0: count(*)
    for c in lay.valid_slot:
        cols8.append((cols[c].validity & row_live).astype(jnp.int8))
    for c in lay.int_cols:
        vcol = cols[c]
        vvalid = vcol.validity & row_live
        u = jax.lax.bitcast_convert_type(
            jnp.where(vvalid, vcol.data.astype(jnp.int64), jnp.int64(0)),
            jnp.uint64)
        bytes8 = jax.lax.bitcast_convert_type(u, jnp.uint8)  # [n, 8]
        x = jnp.where(vvalid[:, None],
                      bytes8.astype(jnp.int16) - jnp.int16(128),
                      jnp.int16(0)).astype(jnp.int8)
        cols8.extend(x[:, j] for j in range(8))
    return cols8


def _decimal_pieces(vcol, vvalid, nb):
    """The int8 slots of one decimal sum column as 2-D pieces ``[n, k]`` in
    slot order: the low ``nb`` bytes of the two's-complement unscaled value
    as ``b - 128``, four to a u32 word of the value, then one negative-flag
    slot (the signed sum is the unsigned-representation sum minus
    2^(8 nb) x #negatives — unlike the int64 path that correction does NOT
    wrap away, since decimal overflow is judged exactly against
    10^precision).  A value past its type's precision is not a value of the
    type.  ``b - 128`` in int8 is ``b`` with its top bit flipped,
    reinterpreted: a word's four bytes are one xor and one bitcast."""
    m32 = jnp.uint64(0xFFFFFFFF)
    if isinstance(vcol, Decimal128Column):
        halves = [vcol.limbs[:, 0], vcol.limbs[:, 1]]
    else:
        halves = [jax.lax.bitcast_convert_type(
            vcol.data.astype(jnp.int64), jnp.uint64)]
    words = [w.astype(jnp.uint32) for h in halves
             for w in (h & m32, h >> jnp.uint64(32))]
    pieces = [jnp.where(vvalid[:, None], jax.lax.bitcast_convert_type(
        w ^ jnp.uint32(0x80808080), jnp.int8)[:, :min(4, nb - 4 * i)],
        jnp.int8(0)) for i, w in enumerate(words[:-(-nb // 4)])]
    neg = vvalid & ((halves[-1] >> jnp.uint64(63)) != 0)
    return pieces + [neg.astype(jnp.int8)[:, None]]


def _onehot_payload(cols, row_live, lay: _SlotPlan):
    """``X8``, the stacked payload: int8 ``[n, lay.m8]`` over the rows
    ``cols`` holds (a whole batch, or one slice of it with its computed
    columns)."""
    head = _onehot_payload8(cols, row_live, lay)
    if not lay.dec_cols:
        return jnp.stack(head, axis=1)
    # with decimal columns every slot group is a 2-D piece, and the pieces
    # stand side by side as zero-padded terms of one sum, which the TPU
    # compiler folds into the contraction's operand.  A stack of 1-D slot
    # vectors is a relayout and a concatenate there: 84 of the 102 ms a
    # TPC-H Q1 query took on the chip, where this takes 49 of 62
    pieces = [h[:, None] for h in head]
    for c in lay.dec_cols:
        pieces += _decimal_pieces(cols[c], cols[c].validity & row_live,
                                  lay.dec_slot[c][1])
    X8, at = None, 0
    for piece in pieces:
        term = jnp.pad(piece, ((0, 0),
                               (at, lay.m8 - at - piece.shape[1])))
        X8 = term if X8 is None else X8 + term
        at += piece.shape[1]
    return X8


def _onehot_contract_int8(bucket, live, X8, kids):
    """One block: the one-hot of ``bucket`` over ``kids`` contracted with
    the payload's rows, in int32 on the MXU."""
    with scope("agg.onehot_build"):
        ohc = (bucket[:, None] == kids) & live[:, None]
    with scope("agg.onehot_contract_int8"):
        return jax.lax.dot_general(
            ohc.astype(jnp.int8).T, X8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.int64)


def _onehot_sliced(batch, derive, lay, keys, domains, row_live):
    """The contraction with payload, bucket and one-hot built one slice of
    ``_ONEHOT_SLICE`` rows at a time, inside one loop: where aggregated
    columns are computed (``derive``) they are computed for the slice and
    consumed there, so a wide product never exists for the whole table.
    The last slice starts where a whole one still fits and leaves out the
    rows the slice before it took, so every slice has one shape."""
    n = batch.num_rows
    rows = min(_ONEHOT_SLICE, n)
    kids = jnp.arange(_bucket_count(domains), dtype=jnp.int32)[None, :]

    with profiler.detached() as rejoin:
        # the loop itself is lowered under no scope, and its body re-enters
        # the caller's path for the aggregate's phases: the computed
        # columns' operations then keep the paths of their own plan node
        def body(i, carry):
            part, overflow = carry
            with rejoin(), scope("agg.onehot_slice"):
                start = jnp.minimum(i * rows, n - rows)

                def cut(a):
                    return jax.lax.dynamic_slice_in_dim(a, start, rows, 0)

                blk = jax.tree_util.tree_map(cut, batch)
                live = cut(row_live) & (
                    start + jnp.arange(rows, dtype=jnp.int32) >= i * rows)
            blk = derive.over(blk)
            with rejoin():
                with scope("agg.onehot_bucket"):
                    bucket, ovf = _domain_buckets(blk, keys, domains, live)
                with scope("agg.onehot_payload"):
                    X8 = _onehot_payload(blk, live, lay)
                part = part + _onehot_contract_int8(bucket, live, X8, kids)
                return part, overflow | ovf

        with rejoin():
            init = (jnp.zeros((kids.shape[1], lay.m8), jnp.int64),
                    jnp.zeros((), jnp.bool_))
        return jax.lax.fori_loop(jnp.int32(0), jnp.int32(-(-n // rows)),
                                 body, init)


def _domain_partials_onehot(batch, key_name, aggs, domain, row_valid,
                            float_mode, engine, derive=None):
    keys, domains = _keys_domains(key_name, domain)
    G = _bucket_count(domains)
    n = batch.num_rows
    row_live = jnp.ones((n,), jnp.bool_) if row_valid is None else row_valid

    # ---- plan the stacked payload ------------------------------------
    lay = _plan_onehot_slots(aggs, _dtype_lookup(
        batch, derive.dtypes if derive is not None else None))
    int_cols, float_cols, dec_cols = (lay.int_cols, lay.float_cols,
                                      lay.dec_cols)
    valid_slot, limb_slot = lay.valid_slot, lay.limb_slot

    if engine not in ("xla", "pallas"):
        raise ValueError(f"unknown engine {engine!r} "
                         "(use 'auto', 'xla', 'pallas', or 'scatter')")
    if engine == "pallas" and float_cols and float_mode != "f32x3":
        raise ValueError(
            "engine='pallas' computes float sums with the f32x3 Dekker "
            "split only (no f64 contraction in the kernel); pass "
            "float_mode='f32x3' to acknowledge the non-bit-stable rounding")
    use_f32x3 = float_mode == "f32x3" or engine == "pallas"

    fpart, digits_of, digit_slot = None, {}, {}
    if derive is not None:
        if float_cols:
            raise NotImplementedError(
                "group_by_onehot: a float sum beside computed columns (its "
                "digit grid is set from the whole column)")
        part, overflow = _onehot_sliced(batch, derive, lay, keys, domains,
                                        row_live)
        _ONEHOT_SLOTS[0] = lay.m8
    else:
        part, fpart, overflow, digits_of, digit_slot = _onehot_whole(
            batch, lay, keys, domains, row_live, use_f32x3, engine)

    with scope("agg.onehot_rebuild"):
        fsum_of = {}
        for i, c in enumerate(float_cols):
            if use_f32x3:
                fsum_of[c] = (fpart[:, 3 * i] + fpart[:, 3 * i + 1]
                              + fpart[:, 3 * i + 2])
            else:
                s = digit_slot[c]
                fsum_of[c] = _float_sums_from_digits(
                    part[:, s:s + _FX_SLOTS], digits_of[c][1])

        counts_star = part[:, 0]
        cnt_of = {c: part[:, s] for c, s in valid_slot.items()}

        # ---- exact integer sums: rebuild from offset byte limbs ----------
        isum_of = {}
        shifts = (jnp.uint64(8) * jnp.arange(8, dtype=jnp.uint64))[None, :]
        for c in int_cols:
            s = limb_slot[c]
            true_limb = part[:, s:s + 8] + jnp.int64(128) * cnt_of[c][:, None]
            total_u = jnp.sum(
                jax.lax.bitcast_convert_type(true_limb, jnp.uint64)
                << shifts, axis=1)
            isum_of[c] = jax.lax.bitcast_convert_type(total_u, jnp.int64)

        # ---- exact decimal sums: 256-bit rebuild with sign correction -----
        # sum = (Σ_j true_limb_j · 256^j) − 2^(8·bytes) · #negatives, carried
        # out in uint32[G, 8] limbs (≤ 2^158 for 2^31 rows — never wraps);
        # overflow vs 10^min(38, p+10) nulls the group (Spark non-ANSI Sum)
        d64_of = {}
        if dec_cols:
            from ..ops import decimal as D

            m32 = jnp.uint64(0xFFFFFFFF)
            for c in dec_cols:
                s, nb = lay.dec_slot[c]
                true_limb = jax.lax.bitcast_convert_type(
                    part[:, s:s + nb]
                    + jnp.int64(128) * cnt_of[c][:, None], jnp.uint64)
                # lane accumulators stay uint64 (each < 2^41 + carries);
                # every byte sum j lands at bit 8j = 32·(j//4) + 8·(j%4)
                lanes = [jnp.zeros((G,), jnp.uint64) for _ in range(9)]
                for j in range(nb):
                    q, r = divmod(8 * j, 32)
                    slo = true_limb[:, j] & m32  # < 2^33; slo<<r fits u64
                    shi = true_limb[:, j] >> jnp.uint64(32)
                    a = slo << jnp.uint64(r)
                    b = shi << jnp.uint64(r)
                    lanes[q] = lanes[q] + (a & m32)
                    lanes[q + 1] = lanes[q + 1] + (a >> jnp.uint64(32)) \
                        + (b & m32)
                    lanes[q + 2] = lanes[q + 2] + (b >> jnp.uint64(32))
                usum = _carry_fold_u64_lanes(jnp.stack(lanes[:8], axis=1))
                # #negatives >= 0, < 2^31, at bit 8·bytes: two u32 limbs
                q, r = divmod(8 * nb, 32)
                negs = part[:, s + nb].astype(jnp.uint64) << jnp.uint64(r)
                sub = jnp.zeros((G, 8), jnp.uint32) \
                    .at[:, q].set((negs & m32).astype(jnp.uint32)) \
                    .at[:, q + 1].set(
                        (negs >> jnp.uint64(32)).astype(jnp.uint32))
                d64_of[c] = D._add(usum, D._neg(sub)).astype(jnp.uint64)

    parts = {"star": counts_star, "cnt": cnt_of, "isum": isum_of,
             "fsum": fsum_of, "d64": d64_of}
    return parts, overflow


def _onehot_whole(batch, lay, keys, domains, row_live, use_f32x3, engine):
    """The payload built for the whole batch and contracted in
    ``_ONEHOT_BLOCK``-row blocks (every column is the batch's own).
    Returns ``(part, fpart, overflow, digits_of, digit_slot)``: the last
    two say where the float digits it laid out are (f64 mode: column ->
    (int8[n, _FX_SLOTS], emax), column -> first slot)."""
    n = batch.num_rows
    digits_of = {}
    G = _bucket_count(domains)
    float_cols = lay.float_cols

    # null keys form their own group (bucket K), like the sort-scan path;
    # dead padding rows are dropped from the onehot entirely (callers
    # rely on the overflow flag to fall back to sort-scan)
    with scope("agg.onehot_bucket"):
        bucket, overflow = _domain_buckets(batch, keys, domains, row_live)

    with scope("agg.onehot_payload"):
        X8 = _onehot_payload(batch, row_live, lay)  # [n, lay.m8]

    def dekker_limbs(c):
        vcol = batch[c]
        return _dekker_limbs(jnp.where(vcol.validity & row_live,
                                       vcol.data.astype(jnp.float64), 0.0))

    F = None
    with scope("agg.onehot_payload"):
        if float_cols and use_f32x3:
            F = jnp.stack(
                sum((dekker_limbs(c) for c in float_cols), []), axis=1)
        elif float_cols:
            # f64 mode: a double is a fixed-point number on a grid chosen
            # from the column, and its digits are more int8 slots
            with scope("agg.onehot_digits"):
                for c in float_cols:
                    digits_of[c] = _float_digit_slots(
                        batch[c].data.astype(jnp.float64),
                        batch[c].validity & row_live)
        digit_slot = {c: lay.m8 + _FX_SLOTS * i
                      for i, c in enumerate(digits_of)}
        if digits_of:
            # side by side as zero-padded terms of one sum, which the TPU
            # compiler folds into the contraction's operand; a concatenate
            # of int8 pieces is a relayout pass of its own there
            m8 = lay.m8 + _FX_SLOTS * len(digits_of)
            X8 = jnp.pad(X8, ((0, 0), (0, m8 - lay.m8)))
            for c, (digits, _) in digits_of.items():
                s = digit_slot[c]
                X8 = X8 + jnp.pad(digits,
                                  ((0, 0), (s, m8 - s - _FX_SLOTS)))
    _ONEHOT_SLOTS[0] = X8.shape[1]

    if engine == "pallas":
        from ..ops.pallas_kernels import onehot_groupby_parts

        bucket_pl = jnp.where(row_live, bucket, jnp.int32(-1))
        Fp = F if F is not None else jnp.zeros((n, 0), jnp.float32)
        part, fpart = onehot_groupby_parts(bucket_pl, X8, Fp, G)
        return part, fpart, overflow, digits_of, digit_slot

    # Chunked contractions with the one-hot built PER CHUNK: only one
    # [B, G] one-hot is ever live (a full-width [n, G] one-hot is multi-GB
    # at bench row counts).  Static n means static slices, combined in
    # int64/float64 across chunks.
    B = _ONEHOT_BLOCK
    kids = jnp.arange(G, dtype=jnp.int32)[None, :]
    part = jnp.zeros((G, X8.shape[1]), jnp.int64)
    fpart = (jnp.zeros((G, F.shape[1]), jnp.float64)
             if F is not None else None)
    for lo in range(0, n, B):
        with scope("agg.onehot_build"):
            ohc = ((bucket[lo:lo + B, None] == kids)
                   & row_live[lo:lo + B, None])
        with scope("agg.onehot_contract_int8"):
            part = part + jax.lax.dot_general(
                ohc.astype(jnp.int8).T, X8[lo:lo + B],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            ).astype(jnp.int64)
        if F is not None:
            with scope("agg.onehot_contract_f32x3"):
                fpart = fpart + jax.lax.dot_general(
                    ohc.astype(jnp.float32).T, F[lo:lo + B],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.float64)
    return part, fpart, overflow, digits_of, digit_slot


# Fixed-point layout of one float sum column on the int8 contraction: the
# largest live value's 53-bit significand sits at the top of a field of
# _FX_GUARD + 53 = 91 bits, cut into 13 digits of 7 bits; three more slots
# count the column's NaN, +inf and -inf rows.
_FX_GUARD = 38
_FX_DIGITS = 13
_FX_SLOTS = _FX_DIGITS + 3


def _dekker_limbs(v):
    """Exact 3-way split of float64 rows into f32 (hi, mid, lo)."""
    hi = v.astype(jnp.float32)
    r1 = v - hi.astype(jnp.float64)
    mid = r1.astype(jnp.float32)
    lo = (r1 - mid.astype(jnp.float64)).astype(jnp.float32)
    return [hi, mid, lo]


def _f64_fields(v):
    """float64 rows read from their IEEE bits: ``(neg, e, top, lo, special,
    nan)`` with ``e`` the biased exponent (a subnormal counts as 1) and
    ``top:lo`` the 53-bit significand as 21 + 32 bits."""
    u32 = jnp.uint32
    w = jax.lax.bitcast_convert_type(v, jnp.uint64)
    lo, hi = w.astype(u32), (w >> jnp.uint64(32)).astype(u32)
    e = ((hi >> u32(20)) & u32(0x7FF)).astype(jnp.int32)
    frac = hi & u32(0xFFFFF)
    special = e == 0x7FF
    top = frac | jnp.where(e != 0, u32(1 << 20), u32(0))
    return ((hi >> u32(31)) != 0, jnp.maximum(e, 1), top, lo, special,
            special & ((frac | lo) != 0))


def _f32_fields(x):
    """An f32 limb in the fields of the double it equals (:func:`_f64_fields`):
    its 24-bit significand at the top of the 53, its exponent rebased."""
    u32 = jnp.uint32
    b = jax.lax.bitcast_convert_type(x, u32)
    e = ((b >> u32(23)) & u32(0xFF)).astype(jnp.int32)
    frac = b & u32(0x7FFFFF)
    special = e == 0xFF
    m = frac | jnp.where(e != 0, u32(1 << 23), u32(0))
    return ((b >> u32(31)) != 0, jnp.maximum(e, 1) + (1023 - 127),
            m >> u32(3), m << u32(29), special, special & (frac != 0))


def _place_on_grid(top, lo, s):
    """Three u32 words (low first) of the 96-bit field that holds the
    significand ``top:lo`` with its hidden bit at bit 90, shifted right by
    ``s >= 0``; what falls off the bottom is dropped."""
    u32 = jnp.uint32
    a2 = (top << u32(6)) | (lo >> u32(26))
    a1 = lo << u32(6)
    q, r = s >> 5, (s & 31).astype(u32)
    b2 = jnp.where(q == 0, a2, u32(0))
    b1 = jnp.where(q == 0, a1, jnp.where(q == 1, a2, u32(0)))
    b0 = jnp.where(q == 1, a1, jnp.where(q == 2, a2, u32(0)))
    up = u32(31) - r                                    # x << (32 - r), r = 0 safe
    return ((b0 >> r) | ((b1 << up) << u32(1)),
            (b1 >> r) | ((b2 << up) << u32(1)), b2 >> r)


def _add_words(a, b, sub):
    """``a + b``, or ``a - b`` where ``sub`` (``a >= b`` there), over u32
    words, low first; a carry is a sum smaller than its operand."""
    u32 = jnp.uint32
    carry = sub.astype(u32)                             # a - b = a + ~b + 1
    out = []
    for x, y in zip(a, b):
        t = x + jnp.where(sub, ~y, y)
        r = t + carry
        carry = ((t < x) | (r < t)).astype(u32)
        out.append(r)
    return tuple(out)


def _float_digit_slots(v, ok):
    """float64[n] -> (int8[n, ``_FX_SLOTS``], ``emax``): the rows'
    fixed-point digits on the grid ``2^(emax - 1075 - _FX_GUARD)``.

    ``emax`` is the largest biased exponent among the ``ok`` finite rows
    (a subnormal counts as 1).  A row's significand, shifted right by
    ``emax - e`` below the top of the field (bits that fall off the
    bottom are dropped: truncation toward zero), is cut into 7-bit
    digits, each times the row's sign: int8 in [-127, 127].  Rows that
    are not ``ok`` or not finite give zeros; the last three slots flag
    the ``ok`` rows that are NaN, +inf, -inf.  All of it is 32-bit integer
    work on the halves of the double.

    Where the backend keeps IEEE doubles their bits are read as they are.
    The TPU's compiler keeps a double as f32 parts and refuses
    ``bitcast f64 -> u64``: there the exact Dekker limbs (native f32, so
    their bits can be read) are placed on the grid one by one and added
    as 96-bit integers; each limb truncates on its own.
    """
    u32 = jnp.uint32
    if jax.default_backend() == "tpu":
        limbs = [_f32_fields(x) for x in _dekker_limbs(v)]
    else:
        limbs = [_f64_fields(v)]
    neg, e, _, _, special, is_nan = limbs[0]
    fin = ok & ~special
    emax = jnp.max(jnp.where(fin, e, 1), initial=1)
    words = None
    for lneg, le, top, lo, _, _ in limbs:
        w = _place_on_grid(jnp.where(fin, top, u32(0)),
                           jnp.where(fin, lo, u32(0)), emax - le)
        words = w if words is None else _add_words(words, w, lneg != neg)
    # one [n, _FX_SLOTS] expression, no column is made on its own: slot j
    # < 13 is bits [7j, 7j + 7) of the field (word 7j // 32, which may
    # run into the next one), the last three are the flags
    j = jnp.arange(_FX_SLOTS, dtype=jnp.int32)[None, :]
    k, b = (7 * j) >> 5, ((7 * j) & 31).astype(u32)
    w0, w1, w2 = (w[:, None] for w in words)
    here = jnp.where(k == 0, w0, jnp.where(k == 1, w1, w2))
    above = jnp.where(k == 0, w1, jnp.where(k == 1, w2, u32(0)))
    d = ((here >> b) | ((above << (u32(31) - b)) << u32(1))) & u32(0x7F)
    d = d.astype(jnp.int32)
    d = jnp.where(neg[:, None], -d, d)
    inf = ok & special & ~is_nan
    flags = jnp.where(j == _FX_DIGITS, (ok & is_nan)[:, None],
                      jnp.where(j == _FX_DIGITS + 1, (inf & ~neg)[:, None],
                                (inf & neg)[:, None]))
    return jnp.where(j < _FX_DIGITS, d, flags.astype(jnp.int32)).astype(
        jnp.int8), emax


def _float_sums_from_digits(part, emax):
    """int64[K+1, ``_FX_SLOTS``] digit sums and counts -> float64[K+1]:
    the exact sum of the truncated rows, rounded to nearest even once.

    The digit sums are carry-normalised in int64 into ``hi * 2^63 + lo``
    and the double is put together from the magnitude's bits with integer
    operations alone (the top 62 bits, a sticky bit for what lies below
    them, the grid's exponent), so the result is the same on every
    backend whatever its float arithmetic does with subnormals.  NaN
    rows, or +inf with -inf, make the bucket NaN; +inf or -inf alone
    make it that.
    """
    i64, u64 = jnp.int64, jnp.uint64
    carry = jnp.zeros(part.shape[:1], i64)
    lo = jnp.zeros(part.shape[:1], i64)
    hi = jnp.zeros(part.shape[:1], i64)
    for j in range(_FX_DIGITS):
        t = part[:, j] + carry
        d, carry = t & i64(0x7F), t >> i64(7)           # floor: 0 <= d < 128
        if j < 9:
            lo = lo | (d << i64(7 * j))
        else:
            hi = hi | (d << i64(7 * (j - 9)))
    hi = hi + (carry << i64(7 * (_FX_DIGITS - 9)))      # signed, |hi| < 2^60
    neg = hi < 0                                        # total = hi * 2^63 + lo
    mlo = jnp.where(neg, (-lo) & i64((1 << 63) - 1), lo).astype(u64)
    mhi = jnp.where(neg, -hi - (lo != 0).astype(i64), hi).astype(u64)
    # magnitude = top * 2^k (+ sticky), top its leading 62 bits
    k = jnp.where(mhi != 0, i64(65) - jax.lax.clz(mhi).astype(i64),
                  (mlo >> u64(62)).astype(i64))
    ku = k.astype(u64)
    top = jnp.where(mhi != 0, mhi << (u64(63) - ku), u64(0)) | (mlo >> ku)
    sticky = (mlo & ((u64(1) << ku) - u64(1))) != 0
    # the leading bit's exponent, and how many of top's bits lie under
    # the double's last place (2^-1074 at the least): drop > 0 rounds
    ex = k + emax.astype(i64) - i64(1075 + _FX_GUARD)
    be = ex + (i64(64) - jax.lax.clz(top).astype(i64)) - i64(1) + i64(1023)
    drop = jnp.maximum(be - i64(1075), i64(-1074)) - ex
    dr = jnp.clip(drop, 0, 63).astype(u64)
    q = jnp.where(drop > 0, top >> dr,
                  top << jnp.clip(-drop, 0, 63).astype(u64))
    rem, half = top & ((u64(1) << dr) - u64(1)), (u64(1) << dr) >> u64(1)
    up = (drop > 0) & ((rem > half)
                       | ((rem == half) & (sticky | ((q & u64(1)) != 0))))
    # the hidden bit of q carries into the exponent field, and so does a
    # mantissa that rounds up to the next power of two (or to inf)
    bits = (((jnp.maximum(be, 1) - i64(1)).astype(u64) << u64(52))
            + q + up.astype(u64))
    bits = jnp.where(be >= 2047, u64(0x7FF << 52), bits)
    bits = jnp.where(top == 0, u64(0), bits | (neg.astype(u64) << u64(63)))
    f = jax.lax.bitcast_convert_type(bits, jnp.float64)
    nan, pinf, ninf = (part[:, _FX_DIGITS + i] > 0 for i in range(3))
    f = jnp.where(pinf, jnp.inf, jnp.where(ninf, -jnp.inf, f))
    return jnp.where(nan | (pinf & ninf), jnp.nan, f)


def _domain_bucket_overflow(col, live, K):
    """Shared key lowering for the domain engines: bucket id per row
    (null/dead keys -> K) and the full-width out-of-domain flag.

    The bounds check runs at int64 width: an INT64 key like 2**32 wraps
    to 0 under an int32 cast and would silently pass, and a domain beyond
    a narrow key dtype's range (INT8 key, domain=200) must compare
    instead of raising at trace time.
    """
    k_orig = col.data.astype(jnp.int64)
    overflow = jnp.any(live & ((k_orig < 0) | (k_orig >= K)))
    k = k_orig.astype(jnp.int32)
    bucket = jnp.where(live, jnp.clip(k, 0, K - 1), K)
    return bucket, overflow


def _carry_fold_u64_lanes(lanes):
    """[G, 8] uint64 per-lane sums -> uint32[G, 8] limbs mod 2^256
    (carry-propagate once; bits beyond limb 7 drop = mod-2^256 add)."""
    m32 = jnp.uint64(0xFFFFFFFF)
    carry = jnp.zeros(lanes.shape[:1], jnp.uint64)
    out32 = []
    for i in range(8):
        t = lanes[:, i] + carry
        out32.append((t & m32).astype(jnp.uint32))
        carry = t >> jnp.uint64(32)
    return jnp.stack(out32, axis=1)


def _finalize_domain(batch, key_name, K, aggs, parts, dtypes=None):
    """Turn (possibly psum-merged) :func:`_domain_partials` into the
    group-by result.  Decimal lanes re-fold their carries here — after
    merging — and the overflow-vs-10^p check runs on the GLOBAL sum, so
    a per-device overflow that cancels across devices does not null the
    group (matching what a single-chip aggregation of the union would
    produce).  ``dtypes`` types the aggregated columns the batch does
    not hold (a :class:`Derived`'s)."""
    from ..ops import decimal as D

    keys, domains = _keys_domains(key_name, K)
    dtype_of = _dtype_lookup(batch, dtypes)
    dsum_of, dover_of, draw_of = {}, {}, {}
    for c, d64 in parts["d64"].items():
        s256 = _carry_fold_u64_lanes(d64)
        out_p = min(38, dtype_of(c).precision + 10)
        mag, _ = D._abs(s256)
        dover_of[c] = ~D._lt_u(mag, jnp.broadcast_to(D._pow10(out_p),
                                                     mag.shape))
        dsum_of[c] = (D._to_i128(s256),
                      T.SparkType.decimal(out_p, dtype_of(c).scale))
        draw_of[c] = s256
    return _assemble_domain_result(
        batch, keys, domains, aggs, parts["star"], parts["cnt"],
        parts["isum"], parts["fsum"], dsum_of, dover_of, draw_of, dtype_of)


def _domain_key_columns(batch, keys, domains, counts_star):
    """The key columns of every bucket, in bucket order."""
    if isinstance(domains, int):
        col = batch[keys[0]]
        key_valid = jnp.arange(domains + 1) < domains
        return {keys[0]: Column(
            jnp.arange(domains + 1, dtype=col.dtype.jnp_dtype),
            key_valid & (counts_star > 0), col.dtype)}
    out = {}
    g = jnp.arange(_bucket_count(domains), dtype=jnp.int32)
    stride = _bucket_count(domains)
    for k, K_ in zip(keys, domains):
        stride //= K_ + 1
        idx = (g // stride) % (K_ + 1)   # 0: the key's null
        dt = batch[k].dtype
        out[k] = Column(jnp.maximum(idx - 1, 0).astype(dt.jnp_dtype),
                        (idx > 0) & (counts_star > 0), dt)
    return out


def _assemble_domain_result(batch, keys, domains, aggs, counts_star, cnt_of,
                            isum_of, fsum_of, dsum_of, dover_of, draw_of,
                            dtype_of):
    """Shared tail of the domain-key engines (onehot / scatter): turn the
    per-bucket reductions into a result batch with live groups compacted
    to the front in bucket order (one key: key order, the null-key bucket
    K last among live; a composite bucket: key order, nulls first)."""
    out_cols = _domain_key_columns(batch, keys, domains, counts_star)

    for spec in aggs:
        if spec.op == "count" and spec.column is None:
            out_cols[spec.out_name] = Column(
                counts_star.astype(jnp.int64), counts_star >= 0, T.INT64)
            continue
        cnt_v = cnt_of[spec.column]
        if spec.op == "count":
            out_cols[spec.out_name] = Column(
                cnt_v.astype(jnp.int64), cnt_v >= 0, T.INT64)
            continue
        if spec.column in dsum_of:
            if spec.op == "mean":
                limbs128, ok, out_t = _decimal_avg(
                    draw_of[spec.column], cnt_v, dtype_of(spec.column))
                out_cols[spec.out_name] = Decimal128Column(
                    limbs128, (cnt_v > 0) & ok, out_t)
            else:
                limbs128, out_t = dsum_of[spec.column]
                out_cols[spec.out_name] = Decimal128Column(
                    limbs128, (cnt_v > 0) & ~dover_of[spec.column], out_t)
            continue
        if spec.column in fsum_of:
            fsum = fsum_of[spec.column]
            if spec.op == "mean":
                res = fsum / jnp.maximum(cnt_v, 1).astype(jnp.float64)
            else:
                res = fsum
            out_cols[spec.out_name] = Column(res, cnt_v > 0, T.FLOAT64)
        elif spec.op == "mean":
            out_cols[spec.out_name] = Column(
                isum_of[spec.column].astype(jnp.float64)
                / jnp.maximum(cnt_v, 1).astype(jnp.float64),
                cnt_v > 0, T.FLOAT64)
        else:
            out_cols[spec.out_name] = Column(
                isum_of[spec.column], cnt_v > 0, T.INT64)

    # compact live groups to the front (stable) like the sort-scan path
    live_group = counts_star > 0
    order = jnp.argsort(~live_group, stable=True).astype(jnp.int32)
    from .gather import gather_column

    compacted = ColumnBatch({
        name: gather_column(c, order) for name, c in out_cols.items()})
    ng = jnp.sum(live_group.astype(jnp.int32))
    return compacted, ng


def group_by_scatter(
    batch: ColumnBatch,
    key_name: str,
    aggs: Sequence[AggSpec],
    domain: int,
    row_valid=None,
):
    """Hash-aggregate as segment sums — the linear-pass engine for
    platforms where scatter-add is cheap.

    Same contract and Spark semantics as :func:`group_by_onehot`
    (small static integer key domain, null keys in bucket K, returns
    ``(result, num_groups, overflow)``), but each aggregate is ONE
    ``segment_sum`` pass over the rows instead of a one-hot contraction.

    Distinct from the general ``engine="scatter"`` of :func:`group_by`
    (r6 delete-or-measure verdict: NOT redundant, both stay): here the
    keys ARE the segment ids — dense ints in a static domain — so there
    is no key normalization, no slot-table build, no probe walk, and no
    overflow fallback.  The general scatter engine pays all four to
    handle arbitrary multi-column keys; at q6's shape the domain engine
    stays measurably ahead (micro rows ``group_by_100keys_scatter`` vs
    ``group_by_100keys_domain``).

    Engine choice is a hardware fact, not a preference: XLA scatters
    measured 16-150ms per 2M rows on TPU v5e (BASELINE.md) — two orders
    off the MXU one-hot — while on XLA-CPU the relationship inverts
    (segment_sum 5ms vs one-hot matmul 416ms at 256K rows, round 4).
    ``group_by_onehot(engine="auto")`` picks per platform.

    Float sums are plain f64 adds (the sort-scan path's rounding class);
    int64 sums keep Spark's non-ANSI mod-2^64 wraparound; decimal128
    sums are exact 256-bit with overflow -> null.
    """
    parts, overflow = _domain_partials_scatter(batch, key_name, aggs,
                                               domain, row_valid)
    res, ng = _finalize_domain(batch, key_name, domain, aggs, parts)
    return res, ng, overflow


def _domain_partials_scatter(batch, key_name, aggs, domain, row_valid=None):
    """Scatter/segment-sum engine for :func:`_domain_partials`."""
    from jax.ops import segment_sum

    batch = materialize_batch(batch)  # direct group_by_scatter entry
    keys, domains = _keys_domains(key_name, domain)
    G = _bucket_count(domains)
    n = batch.num_rows
    row_live = jnp.ones((n,), jnp.bool_) if row_valid is None else \
        row_valid.astype(jnp.bool_)

    bucket, overflow = _domain_buckets(batch, keys, domains, row_live)
    # dead rows land in some bucket with all-zero contributions (their
    # count/valid/value weights below are masked by row_live)

    counts_star = segment_sum(
        row_live.astype(jnp.int64), bucket, num_segments=G)

    cnt_of, isum_of, fsum_of, d64_of = {}, {}, {}, {}
    for spec in aggs:
        if spec.column is None:
            continue
        if spec.op not in ("sum", "mean", "count"):
            raise NotImplementedError(
                f"group_by_scatter: {spec.op} stays on the sort-scan path")
        c = spec.column
        vcol = batch[c]
        vvalid = vcol.validity & row_live
        if c not in cnt_of:
            cnt_of[c] = segment_sum(
                vvalid.astype(jnp.int64), bucket, num_segments=G)
        if spec.op not in ("sum", "mean"):
            continue
        if _is_decimal(vcol.dtype):
            if c in d64_of:
                continue
            from ..ops import decimal as D

            vcol = _widen_decimal(vcol)

            # _from_i128 sign-extends to 256-bit two's complement, so the
            # per-lane sums are already correct mod 2^256 (same argument
            # as the sort path's lane prefix scans: <= 2^31 rows of
            # |v| < 2^127 never reach the wrap)
            u = D._from_i128(jnp.where(vvalid[:, None], vcol.limbs,
                                       jnp.zeros((), jnp.uint64)))
            # each u32 lane sums in uint64: n <= 2^31 rows of < 2^32
            # stays under 2^63; carry-propagate once at the end
            lanes = segment_sum(u.astype(jnp.uint64), bucket,
                                num_segments=G)  # [G, 8]
            d64_of[c] = _carry_fold_u64_lanes(lanes).astype(jnp.uint64)
        elif vcol.dtype.kind in (T.Kind.FLOAT32, T.Kind.FLOAT64):
            if c not in fsum_of:
                fsum_of[c] = segment_sum(
                    jnp.where(vvalid, vcol.data.astype(jnp.float64), 0.0),
                    bucket, num_segments=G)
        else:
            if c not in isum_of:
                isum_of[c] = segment_sum(
                    jnp.where(vvalid, vcol.data.astype(jnp.int64),
                              jnp.int64(0)),
                    bucket, num_segments=G)

    return {"star": counts_star, "cnt": cnt_of, "isum": isum_of,
            "fsum": fsum_of, "d64": d64_of}, overflow


def _pad_leading(a, extra: int):
    """``a`` with ``extra`` zero rows after its own."""
    return jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1))


def _pad_rows(col, pad_to: int):
    """Pad a result column with null rows up to ``pad_to`` rows."""
    n = col.num_rows
    if n == pad_to:
        return col

    def rows(a):
        return _pad_leading(a, pad_to - n)

    if isinstance(col, DictionaryColumn):
        return dataclasses.replace(col, codes=rows(col.codes),
                                   validity=rows(col.validity))
    if isinstance(col, StringColumn):
        return StringColumn(rows(col.chars), rows(col.lengths),
                            rows(col.validity), col.dtype)
    if isinstance(col, Decimal128Column):
        return Decimal128Column(rows(col.limbs), rows(col.validity),
                                col.dtype)
    return Column(rows(col.data), rows(col.validity), col.dtype)


def group_by_domain_or_sort(
    batch: ColumnBatch,
    key_name: str,
    aggs: Sequence[AggSpec],
    domain: int,
    row_valid=None,
    engine: str = "auto",
    float_mode: str = "f64",
):
    """Adaptive aggregation: the domain engine when every live key fits
    ``[0, domain)``, the general sort-scan otherwise — in ONE jitted
    program.  Both paths trace; the overflow flag picks which executes
    at runtime (``lax.cond``), so callers no longer hand-roll the
    "assert or fall back" dance the raw :func:`group_by_onehot` contract
    requires.  Only the O(n) bounds check runs outside the cond; the
    domain partials (the O(n*K) contraction / segment sums) trace inside
    the domain branch, so an overflowing batch pays the sort-scan alone.

    Output rows are padded to ``max(num_rows, domain + 1)`` so the two
    branches agree in shape; group ORDER differs by branch (domain: key
    order with the null group last; sort-scan: key order, nulls first) —
    Spark defines no group order.  sum/count/mean only (the domain
    engines' op set).  Returns ``(result, num_groups)``.
    """
    n = batch.num_rows
    K = int(domain)
    pad_to = max(n, K + 1)
    col = materialize_column(batch[key_name])
    row_live = jnp.ones((n,), jnp.bool_) if row_valid is None else \
        row_valid.astype(jnp.bool_)
    _, overflow = _domain_bucket_overflow(col, col.validity & row_live, K)

    def pad(res_ng):
        res, ng = res_ng
        return (ColumnBatch({name: _pad_rows(c, pad_to)
                             for name, c in zip(res.names, res.columns)}),
                ng.astype(jnp.int32))

    def dom(_):
        parts, _ovf = _domain_partials(batch, key_name, aggs, domain,
                                       row_valid, engine, float_mode)
        return pad(_finalize_domain(batch, key_name, K, list(aggs), parts))

    def srt(_):
        return pad(group_by(batch, [key_name], list(aggs),
                            row_valid=row_valid))

    return jax.lax.cond(overflow, srt, dom, None)
