"""Equality joins with static-shape outputs, engine-selectable probe.

libcudf joins use a GPU hash table; here two engines share one output
contract, picked by the ``join_engine`` knob (``auto | sort | hash``) or
the ``engine=`` argument:

* **sort** — sorted build side + fused lexicographic binary search
  (:func:`keys.equal_range`): log2(n) gather rounds, every probe row in
  flight at once, no scatter anywhere.  The accelerator engine — on TPU
  pointer-chasing scatters serialize on the VPU.
* **hash** — open-addressing slot table over the build side
  (:mod:`hashtable`) + a linear-probe walk per probe row: expected O(1)
  rounds against the sort engine's fixed ~log2(32n) bisection steps,
  and no build-side ``lax.sort``.  The CPU engine — XLA-CPU's sort is
  its slowest primitive.  Output is bit-identical to the sort engine
  (matches enumerate in original right-row order under both; the build
  groups rows by slot with ONE stable single-operand sort).

Both expand matches via the classic offsets/searchsorted expansion,
padded to a static ``capacity``.

Spark semantics: SQL equality join keys — ``null`` matches nothing (inner
drops null-keyed rows, left outer emits them with a null right side, left
anti *keeps* them); float keys normalize -0.0/NaN (equality domain of
:mod:`keys`).

Join types: inner / left / right / full / semi / anti.  ``right`` is the
swapped left join (output keeps the right side's columns first, probe-side
key columns dropped — document order, not semantics).  ``full`` keeps ALL
right columns (keys included) so unmatched right rows retain their key
values, and appends them after the left-join region; its output capacity
is ``capacity + right.num_rows``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar.column import Column, ColumnBatch, Decimal128Column, StringColumn
from ..columnar.encoded import (
    BitPackedColumn,
    DictionaryColumn,
    FrameOfReferenceColumn,
    RunLengthColumn,
    align_encoded_key_columns,
)
from ..profiler import scope
from . import keys as K
from .filter import compact
from .gather import gather_batch

_compact_rows = compact   # where a parameter is called ``compact``

_HOWS = ("inner", "left", "right", "full", "semi", "anti")


def _resolve_join_engine(engine):
    """``engine=None`` reads the ``join_engine`` knob; ``auto`` is the
    same platform call as ``groupby_engine`` (hash on CPU, sort on
    accelerators)."""
    from .. import config as _config

    if engine is None:
        engine = _config.get("join_engine")
    if engine == "auto":
        return "hash" if jax.default_backend() == "cpu" else "sort"
    if engine not in ("sort", "hash", "pallas"):
        raise ValueError(f"unknown join engine {engine!r} "
                         "(use 'auto', 'sort', 'hash', or 'pallas')")
    return engine


def _sort_build_keys(rkeys, nr) -> tuple:
    """The sort engine's build product: the build side's radix words in
    order, and last the permutation that orders them.  The row id as the
    last key makes the order total, so the unstable sort gives the stable
    sort's answer (rows of one key in original order), and the v5e
    compiler builds it in three quarters of the time (PERF.md section 6,
    PR 37)."""
    return tuple(jax.lax.sort(
        tuple(rkeys) + (jnp.arange(nr, dtype=jnp.int32),),
        num_keys=len(rkeys) + 1, is_stable=False))


def _hash_build(rkeys, nr, table_engine: str = "lax"):
    """Hash-engine build product over the build side's radix words.

    Returns the flat tuple ``(owner, rslot, rperm, counts_slot,
    off_slot, *rkeys)`` — the same shape :func:`hash_join` accepts as a
    ``prebuilt`` when ``engine='hash'`` (the ``'pallas'`` engine builds
    a bit-identical tuple through the fused kernel, so the two tags are
    interchangeable on the probe side):

    * ``owner`` int32[S] — slot table (S = 2x the build rows rounded up
      to a power of two: load factor <= 1/2, so insertion always
      terminates and overflow is impossible);
    * ``rslot`` int32[nr] — each build row's slot (== its key group);
    * ``rperm`` int32[nr] — build rows grouped by slot, original order
      within a slot (ONE stable single-operand sort; within one key
      group this is exactly the order the sort engine's stable key sort
      yields, which is what makes the engines bit-identical);
    * ``counts_slot`` int32[S+1] / ``off_slot`` int32[S+1] — per-slot
      row counts and exclusive offsets into ``rperm``.
    """
    from . import hashtable as H

    S = H.next_pow2(2 * nr)
    iota_r = jnp.arange(nr, dtype=jnp.int32)
    owner, rslot, _ = H.build_slot_table(
        rkeys, jnp.ones((nr,), jnp.bool_), S, engine=table_engine)
    counts_slot = jax.ops.segment_sum(
        jnp.ones((nr,), jnp.int32), rslot, num_segments=S + 1)
    off_slot = jnp.cumsum(counts_slot) - counts_slot
    rperm = jax.lax.sort((rslot, iota_r), num_keys=1, is_stable=True)[-1]
    return (owner, rslot, rperm,
            counts_slot.astype(jnp.int32), off_slot.astype(jnp.int32)) \
        + tuple(rkeys)


def _one_null_row_like(batch: ColumnBatch) -> ColumnBatch:
    """A 1-row all-null batch with the same schema (empty-build-side pad).

    The padding row can never match: its null flag differs from every valid
    probe key, and ``counts`` is forced to zero anyway.
    """
    import dataclasses as _dc

    out = {}
    for name, col in zip(batch.names, batch.columns):
        invalid = jnp.zeros((1,), jnp.bool_)
        if isinstance(col, DictionaryColumn):
            # keep the dictionary (and token): downstream concat/keys see
            # a same-dictionary column whose one row is null
            out[name] = _dc.replace(col, codes=jnp.zeros((1,), jnp.uint32),
                                    validity=invalid)
            continue
        if isinstance(col, (RunLengthColumn, FrameOfReferenceColumn)):
            out[name] = Column(
                jnp.zeros((1,), col.dtype.jnp_dtype), invalid, col.dtype)
            continue
        if isinstance(col, BitPackedColumn):
            # keep the packed form (reference/width are program-family
            # aux): one null row = one zero residual lane
            out[name] = _dc.replace(col, lanes=jnp.zeros((1,), jnp.uint32),
                                    validity=invalid)
            continue
        if isinstance(col, StringColumn):
            out[name] = StringColumn(
                jnp.zeros((1, col.max_len), jnp.uint8),
                jnp.zeros((1,), jnp.int32),
                invalid,
                col.dtype,
            )
        elif isinstance(col, Decimal128Column):
            out[name] = Decimal128Column(
                jnp.zeros((1, 2), jnp.uint64), invalid, col.dtype
            )
        else:
            out[name] = Column(
                jnp.zeros((1,), col.data.dtype), invalid, col.dtype
            )
    return ColumnBatch(out)


def hash_join(
    left: ColumnBatch,
    right: ColumnBatch,
    left_on: Sequence[str],
    right_on: Sequence[str],
    how: str = "inner",
    capacity: Optional[int] = None,
    suffixes: tuple = ("", "_r"),
    left_valid=None,
    right_valid=None,
    prebuilt=None,
    engine=None,
) -> tuple:
    """Equality join; returns ``(result_batch, count)``.

    ``capacity`` is the static output row budget for the inner/left-join
    region; when omitted it defaults to ``left.num_rows``, which is
    exact whenever the build side is key-unique (fact-to-dimension) and
    a best-effort budget otherwise (full joins always append up to
    ``right.num_rows`` more rows on top of it).  ``count`` is the true
    match total; ``count > capacity`` signals truncation and callers
    re-run with a bigger budget — the TPU analogue of the reference's
    split-and-retry contract on output-size overflow.

    semi/anti return filtered left rows (padded + count, like ``compact``).

    ``left_valid``/``right_valid`` (bool[n], optional) mark live rows when
    the inputs carry shuffle slot padding: dead right rows never match,
    dead left rows produce no output (not even for left/anti joins, where
    Spark WOULD keep a live null-keyed row).

    ``engine``: ``'sort' | 'hash' | 'pallas' | 'auto'`` (default: the
    ``join_engine`` knob; ``'pallas'`` is the hash engine with the slot
    table built and probed by the fused VMEM kernels in
    :mod:`ops.pallas_kernels` — interpret mode off-accelerator, same
    bits).  All engines produce bit-identical live
    rows; see the module docstring for when each wins.

    ``prebuilt`` skips the build: either a raw build product tuple —
    ``(*sorted_rkeys, rperm)`` for the sort engine, :func:`_hash_build`'s
    tuple for the hash engine; it must match the engine this call
    resolves to — or a :class:`SpillableBuildTable` from
    :func:`spillable_build_table` (pinned for the duration, fetched
    through the retry ladder, probed under whichever engine it was
    (re)built with).  It MUST have been built from the same
    ``right``/``right_on``/``right_valid`` — nothing re-validates that.
    """
    if how not in _HOWS:
        raise ValueError(f"unknown join type {how!r}")
    if len(left_on) != len(right_on):
        raise ValueError("left_on/right_on length mismatch")
    if how == "right":
        if prebuilt is not None:
            # the swap makes the LEFT input the build side; a prebuilt
            # table for the original right would silently probe wrong
            raise ValueError("prebuilt build tables are not supported for "
                             "how='right' (the swap changes the build side)")
        # swapped left join (reference cudf right joins are the same
        # reversal); right side's columns come first in the output
        return hash_join(right, left, right_on, left_on, "left",
                         capacity=capacity, suffixes=(suffixes[1],
                                                      suffixes[0]),
                         left_valid=right_valid, right_valid=left_valid,
                         engine=engine)
    if prebuilt is not None and hasattr(prebuilt, "get"):
        from ..mem.executor import run_with_retry

        # hold the pin across the recursive call so an evictor cannot
        # drop the table (releasing its charge) while the probe is in
        # flight; get() re-runs the build if it was already dropped —
        # under whatever engine the join_engine knob selects at THAT
        # moment, which is why the probe takes the engine from the
        # handle rather than from this call's arguments
        with prebuilt.pinned():
            built = run_with_retry(prebuilt.get)
            return hash_join(left, right, left_on, right_on, how,
                             capacity=capacity, suffixes=suffixes,
                             left_valid=left_valid, right_valid=right_valid,
                             prebuilt=tuple(built),
                             engine=getattr(prebuilt, "engine", "sort"))

    nl, nr = left.num_rows, right.num_rows
    padded_right = nr == 0
    if nr == 0:
        if prebuilt is not None:
            raise ValueError("prebuilt build table for an empty build side")
        # pad the build side with one unmatchable null row: downstream
        # gathers stay in-bounds and every probe misses (count semantics of
        # an empty build: inner/semi -> 0 rows, left -> all-null right, anti
        # -> all left rows)
        right = _one_null_row_like(right)
        nr = 1
    if nl == 0:
        # empty probe side (e.g. how='right' over an empty right input):
        # one DEAD pad row keeps every downstream gather in-bounds while
        # producing no output — count semantics of an empty probe are 0
        # rows for every join type except full, which still appends the
        # unmatched right rows
        left = _one_null_row_like(left)
        nl = 1
        left_valid = jnp.zeros((1,), jnp.bool_)
    lkcols = [left[k] for k in left_on]
    rkcols = [right[k] for k in right_on]
    if prebuilt is None:
        # canon fast path: key pairs over the SAME dictionary (static
        # dict_token match) collapse to one u32 word per column; pairs
        # from different dictionaries keep the gathered-value-words
        # lowering, which is correct across dictionaries — the decoded
        # fallback inside the same program.  A prebuilt table's keys are
        # always value words, so substitution is skipped for it.
        lkcols, rkcols = align_encoded_key_columns(lkcols, rkcols)
    lcols, rcols = K.align_string_key_columns(lkcols, rkcols)
    if right_valid is not None:
        import dataclasses as _dc

        rcols = [_dc.replace(c, validity=c.validity & right_valid)
                 for c in rcols]

    engine = _resolve_join_engine(engine)
    with scope("join.probe_keys"):
        lkeys = K.batch_radix_keys(lcols, equality=True, nulls_first=False)
    l_null = jnp.zeros((nl,), jnp.bool_)
    for c in lcols:
        l_null = l_null | ~c.validity
    l_live = (jnp.ones((nl,), jnp.bool_) if left_valid is None
              else left_valid.astype(jnp.bool_))

    # build + probe.  Null build keys can never match: under the sort
    # engine they sort last and their flag word mismatches every valid
    # probe; under the hash engine they sit in their own slot that no
    # valid probe's words equal.  Null/dead probe rows are masked either
    # way.  Both engines yield the same (counts, lo, rperm) semantics:
    # a probe row's matches are rperm[lo .. lo+counts), enumerated in
    # original right-row order.
    rkeys = None
    if engine in ("hash", "pallas"):
        from . import hashtable as H
        from ..plan import adaptive as _adaptive

        table_engine = "pallas" if engine == "pallas" else "lax"
        if prebuilt is not None:
            owner, rslot, rperm = prebuilt[0], prebuilt[1], prebuilt[2]
            counts_slot, off_slot = prebuilt[3], prebuilt[4]
            rkeys = tuple(prebuilt[5:])
        else:
            with scope("join.hash_build"):
                rkeys = K.batch_radix_keys(rcols, equality=True,
                                           nulls_first=False)
                built = _hash_build(rkeys, nr, table_engine)
            owner, rslot, rperm, counts_slot, off_slot = built[:5]
        with scope("join.hash_probe"):
            probe_live = ~l_null & l_live
            found, lslot = H.probe_slot_table(
                owner, rkeys, lkeys, probe_live,
                max_rounds=_adaptive.bound_probe_rounds(owner, nr),
                engine=table_engine)
            counts = jnp.where(found, jnp.take(counts_slot, lslot),
                               jnp.int32(0))
            lo = jnp.take(off_slot, lslot)
    else:
        if prebuilt is not None:
            sorted_rkeys, rperm = tuple(prebuilt[:-1]), prebuilt[-1]
        else:
            with scope("join.build_sort"):
                rkeys = K.batch_radix_keys(rcols, equality=True,
                                           nulls_first=False)
                sorted_ops = _sort_build_keys(rkeys, nr)
            sorted_rkeys, rperm = sorted_ops[:-1], sorted_ops[-1]
        with scope("join.bisect"):
            lo, hi = K.equal_range(sorted_rkeys, lkeys)
            counts = jnp.where(l_null, 0, hi - lo).astype(jnp.int32)
            counts = jnp.where(l_live, counts, 0)

    if how == "semi":
        return compact(left, (counts > 0) & l_live)
    if how == "anti":
        return compact(left, (counts == 0) & l_live)

    outer = how in ("left", "full")
    if capacity is None:
        capacity = nl
    with scope("join.expand"):
        counts_out = jnp.where(l_live, jnp.maximum(counts, 1), 0) \
            if outer else counts
        cum = K.scan_sum(counts_out)  # inclusive
        total = cum[-1] if nl else jnp.int32(0)
        offsets = cum - counts_out

        j = jnp.arange(capacity, dtype=jnp.int32)
        # source left row for each output slot
        li = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
        li = jnp.clip(li, 0, max(nl - 1, 0))
        k = j - offsets[li] if nl else jnp.zeros_like(j)
        pos = jnp.clip(lo[li] + k, 0, max(nr - 1, 0))
        ri = rperm[pos] if nr else jnp.zeros_like(j)

        out_valid = j < total
        matched = (counts[li] > 0) & out_valid if nl \
            else jnp.zeros_like(out_valid)

    with scope("join.gather_left"):
        lpart = gather_batch(left, li, out_valid)
    # full joins keep the right key columns so unmatched right rows
    # retain their key values in the appended region
    right_names = (list(right.names) if how == "full"
                   else [n for n in right.names if n not in right_on])
    with scope("join.gather_right"):
        rpart = gather_batch(
            right.select(right_names) if right_names else ColumnBatch({}),
            ri,
            matched if outer else out_valid,
        )

    if how == "full":
        r_live = (jnp.ones((nr,), jnp.bool_) if right_valid is None
                  else right_valid.astype(jnp.bool_))
        if engine in ("hash", "pallas"):
            # a right row is matched iff some live non-null probe row
            # FOUND its slot: scatter-OR the probe hits over the slot
            # table, then read each build row's slot back.  (Misses and
            # dead probes carry lslot == S, the absorbing extra slot.)
            S = owner.shape[0]
            hit = jnp.zeros((S + 1,), jnp.bool_).at[lslot].max(found)
            unmatched = ~jnp.take(hit, rslot) & r_live
        else:
            # unmatched right rows: probe the LEFT keys with the right
            # keys.  Dead (shuffle-padding) left rows must not count as
            # matches: re-key them as nulls, which sort last and match
            # nothing.
            if left_valid is not None:
                import dataclasses as _dc

                lcols_live = [_dc.replace(c, validity=c.validity & l_live)
                              for c in lcols]
                lkeys = K.batch_radix_keys(lcols_live, equality=True,
                                           nulls_first=False)
            lkeys_sorted_ops = jax.lax.sort(
                tuple(lkeys) + (jnp.arange(nl, dtype=jnp.int32),),
                num_keys=len(lkeys), is_stable=True)
            sorted_lkeys = lkeys_sorted_ops[:-1]
            if rkeys is None:
                # prebuilt path carries only the SORTED keys; the reverse
                # probe needs them in right-row order
                rkeys = K.batch_radix_keys(rcols, equality=True,
                                           nulls_first=False)
            rlo, rhi = K.equal_range(sorted_lkeys, rkeys)
            r_null = jnp.zeros((nr,), jnp.bool_)
            for c in rcols:
                r_null = r_null | ~c.validity
            rcounts = jnp.where(r_null | ~r_live, 0, rhi - rlo)
            unmatched = (rcounts == 0) & r_live
        if padded_right:
            # the synthetic 1-row pad (empty build side) is not a real
            # right row; it must not be appended
            unmatched = jnp.zeros_like(unmatched)
        n_un = jnp.sum(unmatched.astype(jnp.int32))
        order = jnp.argsort(~unmatched, stable=True).astype(jnp.int32)
        app_valid = jnp.arange(nr, dtype=jnp.int32) < n_un
        rpart_app = gather_batch(right.select(right_names), order, app_valid)
        lpart_app = gather_batch(left, jnp.zeros((nr,), jnp.int32),
                                 jnp.zeros((nr,), jnp.bool_))
        lpart = _concat_batches(lpart, lpart_app)
        rpart = _concat_batches(rpart, rpart_app)
        # the append region sits at offset `capacity`; pull it up so live
        # rows are contiguous.  If the left-join region overflowed its
        # budget (emitted_main < true total_main), surface an
        # unambiguous overflow count — capacity+nr+1 always exceeds any
        # representable output, so the caller's count>capacity check
        # fires instead of garbage rows being presented as live.
        total_main = total
        emitted_main = jnp.minimum(total_main, capacity)
        total = jnp.where(total_main > capacity,
                          jnp.int32(capacity + nr + 1),
                          total_main + n_un)
        idx = jnp.arange(capacity + nr, dtype=jnp.int32)
        srcrow = jnp.where(idx < emitted_main, idx,
                           capacity + idx - emitted_main)
        srcrow = jnp.clip(srcrow, 0, capacity + nr - 1)
        live = idx < emitted_main + n_un
        lpart = gather_batch(lpart, srcrow, live)
        rpart = gather_batch(rpart, srcrow, live)

    return _merge_parts(lpart, rpart, suffixes), total


def join_dense_or_hash(
    left: ColumnBatch,
    right: ColumnBatch,
    left_on: str,
    right_on: str,
    domain: int,
    how: str = "inner",
    capacity: Optional[int] = None,
    suffixes: tuple = ("", "_r"),
    left_valid=None,
    right_valid=None,
    compact: bool = True,
) -> tuple:
    """Adaptive inner join for the dimension-table shape: when the build
    side's keys are UNIQUE ints in ``[0, domain)`` (dense surrogate keys
    — every TPC-DS dim), the sort+binary-search engine reduces to one
    scatter (build a ``[domain]`` rowid table) plus gathers; otherwise
    one ``lax.cond`` runs the general :func:`hash_join`.  Same adaptive
    pattern as ``group_by_domain_or_sort``: both branches trace, the
    data picks at runtime, and the output contract (row order = matches
    compacted in left-row order, ``(result, count)``, ``count >
    capacity`` = truncation) is bit-identical between branches.

    Only single-int-key inner, semi and anti joins take the dense path;
    anything else delegates to :func:`hash_join` outright.  Measured r5
    on the q95 shape (64K fact x 8K dim, 1-core XLA-CPU): the general
    engine's per-join cost is dominated by the build sort that this path
    skips.

    ``semi`` / ``anti`` (an ``IN`` / ``NOT EXISTS`` subquery as Spark
    plans it) ask only whether a key is there: the dense branch is
    ``present[key]`` and nothing else (no rowid table, no gather of a
    right column, build keys may repeat), the output the left side's
    columns and rows (a ``capacity`` is not looked at, as in
    :func:`hash_join`); an anti join keeps a live left row whose key is
    null or outside the domain, since it matches nothing.

    ``compact=False`` (only without a ``capacity``, so the output has the
    left side's rows) returns ``(result, live)`` with ``live`` a
    ``bool[left.num_rows]`` row mask, for a consumer that takes one: the
    dense branch is then a lookup that leaves the left rows where they
    are (no sort, no gather of a left column, its validity untouched: a
    dead row is dead by ``live`` alone) and ``live`` is the scattered
    ``match``; the general branch's rows are compacted by construction,
    its ``live`` a prefix.  The live rows are the same multiset either way.

    ``how='right'`` with ``compact=False`` is the outer join that keeps
    every build row, taken from the build side, whose keys are the unique
    ones (SQL's ``customer LEFT OUTER JOIN orders`` with ORDERS probing
    CUSTOMER): the output has ``left.num_rows + right.num_rows`` rows and
    :func:`hash_join`'s ``'right'`` columns (the build side's, its key
    among them, then the probe side's but its key).  The dense branch
    leaves the probe rows where they are, each match live by ``live``
    (the build columns fetched by row id, the key the probe's own value),
    and puts every build row after them, its probe columns null, live
    where no live probe row hit its key (``join.dense_outer``: one scatter
    of the probe rows' hits over the domain); the general branch is
    ``hash_join(..., 'right')`` compacted into as many rows (repeated build
    keys whose matches pass them are cut there, as the inner form's are
    past the probe side's rows).
    """
    if not compact and capacity is not None:
        raise ValueError("compact=False keeps the left side's rows: it "
                         "takes no capacity")
    lcol, rcol = left[left_on], right[right_on]
    presence = how in ("semi", "anti")   # is the key there: nothing fetched
    outer = how == "right" and not compact   # every build row kept
    eligible = ((how == "inner" or presence or outer) and domain > 0
                and not isinstance(lcol, (StringColumn, Decimal128Column,
                                          DictionaryColumn, RunLengthColumn,
                                          BitPackedColumn,
                                          FrameOfReferenceColumn))
                and not isinstance(rcol, (StringColumn, Decimal128Column,
                                          DictionaryColumn, RunLengthColumn,
                                          BitPackedColumn,
                                          FrameOfReferenceColumn))
                and jnp.issubdtype(lcol.data.dtype, jnp.integer)
                and jnp.issubdtype(rcol.data.dtype, jnp.integer)
                and right.num_rows > 0 and (left.num_rows > 0 or not outer))
    if outer:   # every probe row, then every build row
        capacity = left.num_rows + right.num_rows
    if not eligible:
        out, total = hash_join(left, right, [left_on], [right_on], how,
                               capacity=capacity, suffixes=suffixes,
                               left_valid=left_valid,
                               right_valid=right_valid)
        return out, (total if compact else _prefix_live(out, total))

    nl, nr = left.num_rows, right.num_rows
    K1 = int(domain)
    cap = nl if capacity is None else int(capacity)

    with scope("join.dense_check"):
        rv = (jnp.ones((nr,), jnp.bool_) if right_valid is None
              else right_valid.astype(jnp.bool_))
        r_live = rcol.validity & rv
        rk = rcol.data.astype(jnp.int32)
        in_dom = r_live & (rk >= 0) & (rk < K1)
        slot = jnp.where(in_dom, rk, K1)          # K1 = discard slot
        cnt = jnp.zeros((K1 + 1,), jnp.int32).at[slot].add(1)
        # wider-than-32-bit keys must round-trip the int32 cast exactly
        # on BOTH sides, else a key >= 2^32 could wrap into [0, domain)
        # and fabricate matches the general engine would never produce
        lv_pre = (jnp.ones((nl,), jnp.bool_) if left_valid is None
                  else left_valid.astype(jnp.bool_))
        lk32 = lcol.data.astype(jnp.int32)
        no_wrap = (
            jnp.all((rk.astype(rcol.data.dtype) == rcol.data) | ~r_live)
            & jnp.all((lk32.astype(lcol.data.dtype) == lcol.data)
                      | ~(lcol.validity & lv_pre)))
        # a rowid table holds one row a key; presence lets keys repeat
        unique = True if presence else jnp.all(cnt[:K1] <= 1)
        dense_ok = jnp.all(in_dom | ~r_live) & unique & no_wrap

    right_sel = ColumnBatch({}) if presence else right.select(
        [n for n in right.names if n != right_on])

    def dense_presence():
        """The semi/anti lookup: the left rows kept, where they are."""
        with scope("join.dense_semi"):
            lk = lcol.data.astype(jnp.int32)
            lk_ok = lcol.validity & lv_pre & (lk >= 0) & (lk < K1)
            found = lk_ok & (cnt[:K1] > 0)[jnp.where(lk_ok, lk, 0)]
            keep = found if how == "semi" else lv_pre & ~found
        if not compact:
            # the left columns are taken after the cond: zeros stand in
            return jax.tree_util.tree_map(jnp.zeros_like, left), keep
        with scope("join.dense_compact"):
            return _compact_rows(left, keep)

    def dense_outer(lk_safe, match, fetched):
        """The probe rows where they are, each match with its build row's
        columns (``fetched``), then every build row."""
        with scope("join.dense_outer"):
            # the build rows no live probe row hit: the probe's matches
            # scattered over the domain, read back at each build key
            hits = jnp.zeros((K1 + 1,), jnp.int32).at[
                jnp.where(match, lk_safe, K1)].add(1)
            hit = in_dom & (hits[jnp.where(in_dom, rk, 0)] > 0)
            unmatched = rv & ~hit
        key = Column(lcol.data.astype(rcol.data.dtype), match, rcol.dtype)
        probe_side = dict(zip(fetched.names, fetched.columns),
                          **{right_on: key})
        rpart = ColumnBatch({n: _concat_col(probe_side[n], right[n])
                             for n in right.names})
        lsel = left.select([n for n in left.names if n != left_on])
        lpart = _concat_batches(lsel, gather_batch(
            lsel, jnp.zeros((nr,), jnp.int32), jnp.zeros((nr,), jnp.bool_)))
        return (_merge_parts(rpart, lpart, (suffixes[1], suffixes[0])),
                jnp.concatenate([match, unmatched]))

    def dense(_):
        if presence:
            return dense_presence()
        with scope("join.dense_build"):
            rowid = jnp.zeros((K1 + 1,), jnp.int32).at[slot].set(
                jnp.arange(nr, dtype=jnp.int32))
            present = cnt[:K1] > 0
        with scope("join.dense_probe"):
            lv = (jnp.ones((nl,), jnp.bool_) if left_valid is None
                  else left_valid.astype(jnp.bool_))
            lk = lcol.data.astype(jnp.int32)
            lk_ok = lcol.validity & lv & (lk >= 0) & (lk < K1)
            lk_safe = jnp.where(lk_ok, lk, 0)
            match = lk_ok & present[lk_safe]
            total = jnp.sum(match, dtype=jnp.int32)
        if not compact:
            with scope("join.dense_rowid"):
                ri = rowid[lk_safe]
            with scope("join.gather_right"):
                rpart = gather_batch(right_sel, ri, match)
            if outer:
                return dense_outer(lk_safe, match, rpart)
            # the left columns are taken after the cond: zeros stand in
            lpart = jax.tree_util.tree_map(jnp.zeros_like, left)
            return _merge_parts(lpart, rpart, suffixes), match
        from ..parallel.partition import regroup_order

        with scope("join.dense_compact"):
            # matches first
            order = regroup_order(jnp.where(match, 0, 1), 2)
            li = order[:cap] if cap <= nl else jnp.pad(
                order, (0, cap - nl), constant_values=0)
            out_valid = jnp.arange(cap, dtype=jnp.int32) < total
        with scope("join.dense_rowid"):
            ri = rowid[jnp.clip(jnp.take(lk_safe, li), 0, K1)]
        with scope("join.gather_left"):
            lpart = gather_batch(left, li, out_valid)
        with scope("join.gather_right"):
            rpart = gather_batch(right_sel, ri, out_valid)
        return _merge_parts(lpart, rpart, suffixes), total

    def general(_):
        with scope("join.general"):
            out, total = hash_join(left, right, [left_on], [right_on],
                                   how, capacity=cap, suffixes=suffixes,
                                   left_valid=left_valid,
                                   right_valid=right_valid)
            return out, (total if compact else _prefix_live(out, total))

    out, live = jax.lax.cond(dense_ok, dense, general, None)
    if compact or outer:
        return out, live
    # The dense branch's left columns are the caller's, selected here and
    # not handed out of the cond: what a cond hands out the compiler keeps
    # in HBM, and an exchange gathers every column out of that at half the
    # speed it reads a fusion's output at (PERF.md section 5, PR 32).
    collide = set(left.names) & set(right_sel.names)   # as _merge_parts
    cols = dict(zip(out.names, out.columns))
    for name, col in zip(left.names, left.columns):
        to = name + suffixes[0] if name in collide else name
        cols[to] = jax.tree_util.tree_map(
            lambda a, b: jnp.where(dense_ok, a, b), col, cols[to])
    return ColumnBatch(cols), live


def _prefix_live(out: ColumnBatch, total):
    """The row mask of a compacted join output: its first ``total`` rows."""
    return jnp.arange(out.num_rows, dtype=jnp.int32) < total


def _merge_parts(lpart: ColumnBatch, rpart: ColumnBatch,
                 suffixes: tuple) -> ColumnBatch:
    """Suffix-disambiguating column merge shared by the join engines."""
    collisions = set(lpart.names) & set(rpart.names)
    merged = {}
    for part, suffix in ((lpart, suffixes[0]), (rpart, suffixes[1])):
        for name, col in zip(part.names, part.columns):
            out = name + suffix if name in collisions else name
            if out in merged:
                raise ValueError(
                    f"join output name collision: {out!r} "
                    f"(suffixes={suffixes!r})")
            merged[out] = col
    return ColumnBatch(merged)


def _concat_col(a, b):
    if isinstance(a, (BitPackedColumn, FrameOfReferenceColumn)) or \
            isinstance(b, (BitPackedColumn, FrameOfReferenceColumn)):
        # packed lane streams are not concatenable unless the first ends
        # lane-aligned (n*width % 32 == 0) AND the static aux matches —
        # concat is an output boundary, so materialize like mixed dicts
        from ..columnar.encoded import materialize_column

        a, b = materialize_column(a), materialize_column(b)
    if isinstance(a, DictionaryColumn) or isinstance(b, DictionaryColumn):
        import dataclasses as _dc

        if (isinstance(a, DictionaryColumn) and isinstance(b, DictionaryColumn)
                and a.dict_token == b.dict_token and a.dict_token > 0):
            # same dictionary: codes concatenate directly, stays encoded
            return _dc.replace(a, codes=jnp.concatenate([a.codes, b.codes]),
                               validity=jnp.concatenate([a.validity,
                                                         b.validity]))
        from ..columnar.encoded import materialize_column

        a, b = materialize_column(a), materialize_column(b)
    if isinstance(a, StringColumn):
        W = max(a.max_len, b.max_len)

        def pad(c):
            return jnp.pad(c.chars, ((0, 0), (0, W - c.max_len)))

        return StringColumn(
            jnp.concatenate([pad(a), pad(b)]),
            jnp.concatenate([a.lengths, b.lengths]),
            jnp.concatenate([a.validity, b.validity]), a.dtype)
    if isinstance(a, Decimal128Column):
        return Decimal128Column(
            jnp.concatenate([a.limbs, b.limbs]),
            jnp.concatenate([a.validity, b.validity]), a.dtype)
    return Column(jnp.concatenate([a.data, b.data]),
                  jnp.concatenate([a.validity, b.validity]), a.dtype)


def _concat_batches(a: ColumnBatch, b: ColumnBatch) -> ColumnBatch:
    return ColumnBatch({n: _concat_col(a[n], b[n]) for n in a.names})


# ---------------------------------------------------------------------------
# spillable build tables: eviction drops, read-back rebuilds
# ---------------------------------------------------------------------------

def spillable_build_table(right: ColumnBatch, right_on: Sequence[str],
                          right_valid=None, ctx=None,
                          name: Optional[str] = None, engine=None):
    """Register a join build table (the build product over
    ``right[right_on]``) in the spill framework as a
    :class:`SpillableBuildTable`.

    The reference spills hash-join build-side GpuColumnarBatches like any
    other buffer; here the build product is *derived* state — the source
    columns stay with the caller — so eviction just DROPS it (releasing
    the device charge with no host copy) and ``get()`` re-runs the
    compiled build.  Recompute-over-copy is the right trade for a product
    the probe can deterministically regenerate.

    The build product's SHAPE follows ``engine`` (sorted keys +
    permutation for the sort engine, :func:`_hash_build`'s slot-table
    tuple for the hash engine).  With ``engine=None`` the
    ``join_engine`` knob is re-read at every rebuild: a table built
    under one engine and evicted rebuilds under whatever engine is
    active THEN, and the handle's ``engine`` attribute tells
    ``hash_join(prebuilt=...)`` how to probe what it got.  Pass an
    explicit engine to PIN it across rebuilds — what the plan
    compiler's adaptive broadcast decision does, so an eviction-driven
    rebuild can never disagree with the engine the compiled program was
    traced against.

    Pass the result as ``hash_join(..., prebuilt=table)`` to reuse one
    build across many probe batches.  Close it when done.

    Raises for string join keys (their radix width is aligned to the
    probe side's ``max_len``, so a probe-independent prebuild could
    disagree with what ``hash_join`` derives) and for an empty build side
    (which ``hash_join`` pads with a synthetic row).
    """
    if right.num_rows == 0:
        raise ValueError("cannot pre-build an empty build side")
    rcols = [right[k] for k in right_on]
    if any(isinstance(c, StringColumn)
           or (isinstance(c, DictionaryColumn)
               and isinstance(c.dictionary, StringColumn))
           for c in rcols):
        raise ValueError(
            "string join keys cannot be pre-built: their radix key width "
            "depends on the probe side (align_string_key_columns)")
    if right_valid is not None:
        import dataclasses as _dc

        rcols = [_dc.replace(c, validity=c.validity & right_valid)
                 for c in rcols]
    nr = right.num_rows

    def builder():
        # pinned engine, else the knob at (re)build time
        eng = _resolve_join_engine(engine)
        rkeys = K.batch_radix_keys(rcols, equality=True, nulls_first=False)
        if eng in ("hash", "pallas"):
            return eng, _hash_build(rkeys, nr,
                                    "pallas" if eng == "pallas" else "lax")
        return eng, _sort_build_keys(rkeys, nr)

    return SpillableBuildTable(builder, ctx=ctx, name=name)


from ..mem.spill import SpillableHandle as _SpillableHandle  # noqa: E402


class SpillableBuildTable(_SpillableHandle):
    """A :class:`~spark_rapids_jni_tpu.mem.spill.SpillableHandle` whose
    payload is recomputed rather than copied: ``spill()`` drops the device
    tree and releases the charge (no host/disk tiers); read-back goes
    through the base class's generalized ``recompute=`` lineage path,
    which re-charges and re-runs the stored builder.

    ``builder`` returns ``(engine, tree)``; the engine tag of the most
    recent (re)build is exposed as ``self.engine`` so the probe side
    interprets the tree correctly even when the ``join_engine`` knob
    changed between eviction and read-back."""

    def __init__(self, builder, ctx=None, name: Optional[str] = None):
        self._builder = builder
        super().__init__(self._build(), ctx=ctx,
                         name=name or f"build-table-{id(self):x}",
                         recompute=self._build)

    def _build(self):
        self.engine, tree = self._builder()
        return tree

    @property
    def rebuilds(self) -> int:
        return self.lineage_rebuilds

    def spill(self) -> int:
        if not self._lock.acquire(blocking=False):
            return 0  # busy in another thread's get(): treat as pinned
        try:
            if self._closed or self._tree is None or self._pins > 0:
                return 0
            self._tree = None
            freed = self._device_charged
            if self._ctx is not None and self._device_charged:
                self._ctx.release(self._device_charged)
                self._device_charged = 0
            if self._fw is not None:
                # dropping IS this handle's device->host transition for
                # accounting purposes: zero bytes moved, one eviction
                self._fw.metrics.record("device_to_host", 0, self.task_id)
            return freed
        finally:
            self._lock.release()

    spill_host = spill  # no host tier to demote; keep the interface
