"""The sort engine's head (``relational/aggregate.py:_group_by_sortscan``):
a result of ``num_groups`` rows is fetched at the first group slots and
padded back to the input's rows, at the narrowest width of a short ladder
that holds the groups; data with more groups than the last of them takes
the row-wide fetch.  Every width against plain Python and against the
row-wide fetch, on either side of each ``num_groups = width``."""

import math
import re

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.relational import AggSpec, group_by


_HEAD = 64          # the head these cases patch in, so that n stays small
_HEAD_ROWS = 256
_TIER_HEAD = 8      # ... and one that makes three widths of those rows
_TIER_WIDTHS = (8, 128, _HEAD_ROWS)
_HEAD_AGGS = [
    AggSpec("count", None, "n"), AggSpec("count", "v", "nv"),
    AggSpec("sum", "v", "sv"), AggSpec("sum", "f", "sf"),
    AggSpec("mean", "v", "mv"), AggSpec("mean", "f", "mf"),
    AggSpec("min", "v", "lov"), AggSpec("max", "v", "hiv"),
    AggSpec("min", "f", "lof"), AggSpec("max", "f", "hif"),
    AggSpec("min", "b", "lob"), AggSpec("max", "b", "hib"),
    AggSpec("sum", "d", "sd"), AggSpec("mean", "d", "md"),
    AggSpec("min", "d", "lod"),
]
_HEAD_JITS = {}
# ... and the columns past them: an int32 sum, a decimal in 64-bit storage
_FETCH_AGGS = _HEAD_AGGS + [
    AggSpec("sum", "i", "si"), AggSpec("sum", "e", "se"),
    AggSpec("mean", "e", "me"), AggSpec("count", "e", "ne"),
]


def _head_case(n, g, has_rv, grouped, seed, aggs=_HEAD_AGGS, key64=False):
    """``g`` groups over the live rows of an ``n``-row batch (dead rows
    trailing, one null key, one group whose values are all null) and what
    every aggregate of ``aggs`` must give, group by group in the engine's
    order, from plain Python.  Beside the columns ``_HEAD_AGGS`` reads, an
    int32 ``i`` and a decimal ``e`` in 64-bit storage, drawn apart so that
    the others stay as they were; ``key64``: keys past 32 bits."""
    from spark_rapids_jni_tpu.columnar.column import Decimal128Column

    rng = np.random.default_rng(seed)
    more = np.random.default_rng(seed + 1)
    live = n - n // 8 if has_rv else n
    if g == "all":
        g = live
    if g == 0:
        live = 0
    cuts = np.sort(rng.choice(np.arange(1, live), g - 1, replace=False)) \
        if g > 1 else np.zeros((0,), np.int64)
    sizes = np.diff(np.concatenate([[0], cuts, [live]])) if g else []
    keyvals = [int(x) * ((1 << 33) + 1 if key64 else 1) for x in rng.choice(
        np.arange(-5 * n, 5 * n), g, replace=False)]
    if g >= 2:
        keyvals[1] = None
    rows = []   # (group, key, v, f, b, d, i, e)
    for j, size in enumerate(sizes):
        for _ in range(int(size)):
            dull = g >= 3 and j == 2
            pick = rng.random(4)
            fval = float(rng.integers(-1000, 1000))
            if rng.random() < 0.05:
                fval = math.nan
            pick2 = more.random(2)
            rows.append((
                j, keyvals[j],
                None if dull or pick[0] < 0.15
                else int(rng.integers(-(1 << 40), 1 << 40)),
                None if dull or pick[1] < 0.15 else fval,
                None if dull or pick[2] < 0.15 else bool(rng.random() < 0.5),
                None if dull or pick[3] < 0.15
                else int(rng.integers(-(10**18), 10**18)) * 10,
                None if dull or pick2[0] < 0.15
                else int(more.integers(-(1 << 31), 1 << 31)),
                None if dull or pick2[1] < 0.15
                else int(more.integers(-(10**17), 10**17))))
    if not grouped:
        rows = [rows[i] for i in rng.permutation(len(rows))]
    dead = [(None, int(rng.integers(-5 * n, 5 * n)), 7, 7.0, True, 7, 7, 7)
            for _ in range(n - live)]
    allrows = rows + dead
    e = [r[7] for r in allrows]
    batch = ColumnBatch({
        "k": Column.from_pylist([r[1] for r in allrows],
                                T.INT64 if key64 else T.INT32),
        "v": Column.from_pylist([r[2] for r in allrows], T.INT64),
        "f": Column.from_pylist([r[3] for r in allrows], T.FLOAT64),
        "b": Column.from_pylist([r[4] for r in allrows], T.BOOLEAN),
        "d": Decimal128Column.from_unscaled([r[5] for r in allrows], 20, 2),
        "i": Column.from_pylist([r[6] for r in allrows], T.INT32),
        "e": Column(jnp.asarray([x or 0 for x in e], jnp.int64),
                    jnp.asarray([x is not None for x in e]),
                    T.SparkType.decimal(18, 2)),
    })
    rv = jnp.asarray(np.arange(n) < live) if has_rv else None

    order = list(range(g))
    if not grouped:   # key order, nulls first
        order.sort(key=lambda j: (keyvals[j] is not None, keyvals[j] or 0))

    def fmin(xs, op):   # Spark: NaN is the greatest float
        nums = [x for x in xs if not math.isnan(x)]
        if op == "max":
            return math.nan if len(nums) < len(xs) else max(nums)
        return min(nums) if nums else math.nan

    def half_up(num, den):
        q, r = divmod(abs(num), den)
        q += 2 * r >= den
        return -q if num < 0 else q

    want = {name: [] for name in ["k"] + [a.out_name for a in _FETCH_AGGS]}
    for j in order:
        mine = [r for r in rows if r[0] == j]
        v, f, b, d, i, e = ([r[c] for r in mine if r[c] is not None]
                            for c in (2, 3, 4, 5, 6, 7))
        want["k"].append(keyvals[j])
        want["n"].append(len(mine))
        want["nv"].append(len(v))
        want["sv"].append(sum(v) if v else None)
        want["sf"].append(math.fsum(f) if f else None)
        want["mv"].append(float(sum(v)) / len(v) if v else None)
        want["mf"].append(math.fsum(f) / len(f) if f else None)
        want["lov"].append(min(v) if v else None)
        want["hiv"].append(max(v) if v else None)
        want["lof"].append(fmin(f, "min") if f else None)
        want["hif"].append(fmin(f, "max") if f else None)
        want["lob"].append(min(b) if b else None)
        want["hib"].append(max(b) if b else None)
        want["sd"].append(sum(d) if d else None)
        want["md"].append(half_up(sum(d) * 10**4, len(d)) if d else None)
        want["lod"].append(min(d) if d else None)
        want["si"].append(sum(i) if i else None)
        want["se"].append(sum(e) if e else None)
        want["me"].append(half_up(sum(e) * 10**4, len(e)) if e else None)
        want["ne"].append(len(e))
    names = ["k"] + [a.out_name for a in aggs]
    return batch, rv, g, {name: want[name] for name in names}


def _head_run(monkeypatch, head, batch, rv, grouped, mode):
    """``group_by`` on the sort engine with the head patched to ``head``
    slots; a jitted program is traced once for each shape of case."""
    from spark_rapids_jni_tpu.relational import aggregate as A

    import jax

    monkeypatch.setattr(A, "_DEFAULT_GROUP_SLOTS", head)

    def run(b, r):
        return group_by(b, ["k"], _HEAD_AGGS, row_valid=r, engine="sort",
                        assume_grouped=grouped)

    if mode == "jit":
        key = (head, batch.num_rows, rv is not None, grouped)
        run = _HEAD_JITS.setdefault(key, jax.jit(run))
    out, ng = run(batch, rv)
    return out, int(ng)


def _grouped_ints(n, g):
    """``g`` runs of equal int32 keys over ``n`` live rows with an int64
    value each; the batch, the runs' first rows after the first, each row's
    run, the keys and the values."""
    rng = np.random.default_rng(g)
    cuts = np.sort(rng.choice(np.arange(1, n), g - 1, replace=False))
    gid = np.zeros((n,), np.int64)
    gid[cuts] = 1
    gid = np.cumsum(gid)
    keys = rng.permutation(g).astype(np.int32)[gid]
    v = rng.integers(-(1 << 40), 1 << 40, n)
    ones = jnp.ones((n,), jnp.bool_)
    batch = ColumnBatch({"k": Column(jnp.asarray(keys), ones, T.INT32),
                         "v": Column(jnp.asarray(v), ones, T.INT64)})
    return batch, cuts, gid, keys, v


def _canon(vals):
    return [repr(x) if isinstance(x, float) else x for x in vals]


def _raw(col):
    if hasattr(col, "limbs"):
        return np.asarray(col.limbs), np.asarray(col.validity)
    return np.asarray(col.data), np.asarray(col.validity)


class TestSortScanHead:
    """The sort engine fetches its scans at the first ``head`` group slots
    and pads back to the input's rows; data with more groups takes the
    row-wide fetch.  Both equal plain Python, column for column, and each
    other bit for bit, on either side of ``num_groups = head`` and of
    every wider fetch's width."""

    def _check(self, monkeypatch, n, g, has_rv, grouped, mode, seed,
               head=_HEAD):
        batch, rv, g, want = _head_case(n, g, has_rv, grouped, seed)
        out, ng = _head_run(monkeypatch, head, batch, rv, grouped, mode)
        assert ng == g
        for name, vals in want.items():
            assert _canon(out[name].to_pylist()[:g]) == _canon(vals), name
        if n <= head:
            return   # the head is every row: one program, by construction
        # what the engine did before it had a head: every fetch row-wide
        ref, ng_ref = _head_run(monkeypatch, n, batch, rv, grouped, mode)
        assert ng_ref == g
        assert out.names == ref.names
        for name in out.names:
            (data, valid), (rdata, rvalid) = _raw(out[name]), _raw(ref[name])
            assert out[name].dtype == ref[name].dtype
            assert data.shape == rdata.shape and data.dtype == rdata.dtype
            assert np.array_equal(valid, rvalid), name
            assert not valid[g:].any(), name
            assert data[:g].tobytes() == rdata[:g].tobytes(), name

    @pytest.mark.parametrize("mode", ["eager", "jit"])
    @pytest.mark.parametrize("grouped", [True, False],
                             ids=["grouped", "sorting"])
    @pytest.mark.parametrize("g,has_rv", [
        (0, True), (1, True), (_HEAD - 1, True), (_HEAD, True),
        (_HEAD + 1, True), ("all", True), (1, False), (_HEAD - 1, False),
        (_HEAD, False), (_HEAD + 1, False), ("all", False)])
    def test_either_side_of_the_head(self, monkeypatch, g, has_rv, grouped,
                                     mode):
        self._check(monkeypatch, _HEAD_ROWS, g, has_rv, grouped, mode,
                    seed=3 + (g if isinstance(g, int) else 1000))

    @pytest.mark.parametrize("mode", ["eager", "jit"])
    @pytest.mark.parametrize("grouped", [True, False],
                             ids=["grouped", "sorting"])
    @pytest.mark.parametrize("g,has_rv", [(5, True), ("all", False)])
    def test_fewer_rows_than_the_head(self, monkeypatch, g, has_rv, grouped,
                                      mode):
        self._check(monkeypatch, _HEAD - 16, g, has_rv, grouped, mode,
                    seed=17)

    # every combination jitted (four programs, traced once each); eagerly,
    # where every fetch compiles its branches anew (7 s a case), grouped
    # rows with dead ones and sorted rows without
    @pytest.mark.parametrize("g,has_rv,grouped,mode", [
        pytest.param(w + d, has_rv, grouped, mode, id="-".join([
            str(w + d), "row_valid" if has_rv else "all_live",
            "grouped" if grouped else "sorting", mode]))
        for mode in ("jit", "eager") for has_rv in (True, False)
        for grouped in (True, False) if mode == "jit" or has_rv == grouped
        for w in _TIER_WIDTHS[:-1] for d in (-1, 0, 1)])
    def test_either_side_of_each_width(self, monkeypatch, g, has_rv, grouped,
                                       mode):
        """A head of 8 makes three widths of 256 rows (8, 128, every row):
        a group under, at and over each edge."""
        from spark_rapids_jni_tpu.relational import aggregate as A

        monkeypatch.setattr(A, "_DEFAULT_GROUP_SLOTS", _TIER_HEAD)
        assert A.sortscan_tiers(_HEAD_ROWS) == _TIER_WIDTHS
        self._check(monkeypatch, _HEAD_ROWS, g, has_rv, grouped, mode,
                    seed=29 + g, head=_TIER_HEAD)

    @pytest.mark.parametrize("rows,want", [
        (10, (10,)), (4096, (4096,)), (8192, (4096, 8192)),
        (1 << 22, (4096, 65536, 1048576, 1 << 22)),
        (6001215, (4096, 65536, 1048576, 6001215)),
        (None, (4096, 65536, 1048576))])
    def test_the_widths_follow_the_rows(self, rows, want):
        from spark_rapids_jni_tpu.relational import aggregate as A

        assert A.sortscan_tiers(rows) == want
        assert A.sortscan_head(rows) == want[0]

    def test_the_ladder_the_module_ships(self, monkeypatch):
        """Unpatched, 4097 groups over 131,072 grouped rows: the branch
        that ran is the 65,536-slot one, and its gathers take 65,536
        indices."""
        import jax

        n, g = 1 << 17, 4097
        batch, cuts, gid, keys, v = _grouped_ints(n, g)
        taken = []
        switch = jax.lax.switch

        def noting(index, branches, *operands):
            taken.append((int(index), len(branches)))
            return switch(index, branches, *operands)

        def run(b):
            return group_by(b, ["k"], [AggSpec("sum", "v", "s")],
                            engine="sort", assume_grouped=True)

        monkeypatch.setattr(jax.lax, "switch", noting)
        out, ng = run(batch)
        monkeypatch.undo()
        # of the widths 4096, 65,536 and every row, the second
        assert taken and set(taken) == {(1, 3)}
        assert int(ng) == g
        assert np.array_equal(np.asarray(out["k"].data)[:g],
                              keys[np.concatenate([[0], cuts])])
        assert np.array_equal(
            np.asarray(out["s"].data)[:g],
            np.add.reduceat(v, np.concatenate([[0], cuts])))
        assert np.asarray(out["s"].validity).sum() == g
        text = jax.jit(run).lower(batch).as_text(debug_info=True)
        locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
        indices = {}
        for m in re.finditer(r'"stablehlo\.gather"\(.*-> tensor<(\d+)[x>]'
                             r'.*loc\((#loc\d+)\)', text):
            scope = re.search(r"agg\.sortscan_(head|full|tier\.\d+)",
                              locs.get(m.group(2), ""))
            if scope:
                indices.setdefault(scope.group(0), set()).add(
                    int(m.group(1)))
        assert indices == {"agg.sortscan_head": {4096},
                           "agg.sortscan_tier.65536": {65536},
                           "agg.sortscan_full": {n}}

    @pytest.mark.parametrize("g", [10, 4096, 4097])
    def test_the_head_the_module_ships(self, g):
        """4096 slots, unpatched, on 8192 grouped rows: the counts and
        integer sums on both sides of it; and no gather of a row's worth
        of indices outside the branch that many groups take."""
        import jax

        from spark_rapids_jni_tpu.relational import aggregate as A

        n = 8192
        batch, cuts, gid, keys, v = _grouped_ints(n, g)
        before = A.rowwide_gathers()
        out, ng = jax.jit(lambda b: group_by(
            b, ["k"], [AggSpec("count", None, "c"), AggSpec("sum", "v", "s")],
            engine="sort", assume_grouped=True))(batch)
        assert A.rowwide_gathers() == before
        assert int(ng) == g
        assert np.array_equal(np.asarray(out["k"].data)[:g],
                              keys[np.concatenate([[0], cuts])])
        assert np.array_equal(np.asarray(out["c"].data)[:g],
                              np.bincount(gid, minlength=g))
        assert np.array_equal(
            np.asarray(out["s"].data)[:g],
            np.add.reduceat(v, np.concatenate([[0], cuts])))
        assert np.asarray(out["s"].validity).sum() == g


# ---------------------------------------------------------------------------
# the fetch as matrices: a head of 8 over 256 rows makes the widths 8, 128
# and every row; with row gathers counted from more than 8 indices and
# matrices out of sources of 256 rows, both wider branches move the words
# they read as matrices, the head one gather a buffer
# ---------------------------------------------------------------------------

_FETCH_JITS = {}
_FETCH_WIDTHS = {"head": 5, "tier.128": 100, "full": 200}   # groups


def _fetch_run(monkeypatch, batch, rv, aggs, grouped, matrices):
    """``group_by`` of ``aggs`` on the sort engine under the patched ladder,
    with the words moved as matrices past the head (``matrices``) or one
    gather a buffer; the lowered program's text beside the result."""
    import jax

    from spark_rapids_jni_tpu.relational import aggregate as A
    from spark_rapids_jni_tpu.relational import gather as G

    monkeypatch.setattr(A, "_DEFAULT_GROUP_SLOTS", _TIER_HEAD)
    monkeypatch.setattr(G, "_COUNTED_ABOVE", _TIER_HEAD)
    monkeypatch.setattr(G, "_MATRIX_FROM_ROWS",
                        _HEAD_ROWS if matrices else 1 << 40)
    key = (tuple(a.out_name for a in aggs), batch["k"].dtype, grouped,
           matrices)
    if key not in _FETCH_JITS:
        def run(b, r):
            return group_by(b, ["k"], aggs, row_valid=r, engine="sort",
                            assume_grouped=grouped)

        fn = jax.jit(run)
        _FETCH_JITS[key] = fn, fn.lower(batch, rv).as_text(debug_info=True)
    fn, text = _FETCH_JITS[key]
    out, ng = fn(batch, rv)
    return out, int(ng), text


def _branch_ops(text, op=r'"stablehlo\.gather"\(.*-> tensor<([^>]*)>'):
    """The types ``op`` captures of each of its operations in each fetch
    branch of a lowered program's text (by default every gather's
    result)."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    found = {}
    for m in re.finditer(op + r'.*loc\((#loc\d+)\)', text):
        scope = re.search(r"agg\.sortscan_(head|full|tier\.\d+)",
                          locs.get(m.group(2), ""))
        if scope:
            found.setdefault(scope.group(1), []).append(m.group(1))
    return found


class TestTheFetchAsMatrices:
    """The values every aggregate reads at the group ends come from one
    fetch a width: past the head, one gather for every eight u32 words of
    them and one for the keys, the same answers bit for bit as one gather a
    buffer, and both plain Python's."""

    # The words read at the group ends, 41 of them: the int32 counts of
    # the live rows and of each column's non-null values (7), sum(v) and
    # sum(i) (2 each), min(v) and max(v) (2 each), the NaN and number
    # counts of f (1 each), the runs of b widened to a word (1 each), the
    # decimals' lane sums (four 64-bit scans and the negatives: 9 each),
    # min(d)'s limbs (4); the sorting path reads the permutation one slot
    # on too (42).  Six matrices of eight and fewer; the float64 sums and
    # runs on their own (f's sum and mean share one, mean(v), min(f),
    # max(f)); the key's words and validity one matrix.  At the head one
    # gather a buffer: 31 (32) and the key's data and validity.
    @pytest.mark.parametrize("key64", [False, True],
                             ids=["int32_key", "int64_key"])
    @pytest.mark.parametrize("grouped,past_head,at_head", [
        (True, 6 + 4 + 1, 31 + 2), (False, 6 + 4 + 1, 32 + 2)],
        ids=["grouped", "sorting"])
    @pytest.mark.parametrize("width", list(_FETCH_WIDTHS))
    def test_every_aggregate_at_each_width(self, monkeypatch, width,
                                           grouped, past_head, at_head,
                                           key64):
        g = _FETCH_WIDTHS[width]
        batch, rv, g, want = _head_case(_HEAD_ROWS, g, True, grouped,
                                        seed=41 + g, aggs=_FETCH_AGGS,
                                        key64=key64)
        out, ng, text = _fetch_run(monkeypatch, batch, rv, _FETCH_AGGS,
                                   grouped, matrices=True)
        ref, ng_ref, ref_text = _fetch_run(monkeypatch, batch, rv,
                                           _FETCH_AGGS, grouped,
                                           matrices=False)
        assert ng == ng_ref == g
        assert (128 if width == "tier.128" else g) >= g > (
            {"head": 0, "tier.128": 8, "full": 128}[width])
        for name, vals in want.items():
            assert _canon(out[name].to_pylist()[:g]) == _canon(vals), name
        assert out.names == ref.names
        for name in out.names:
            assert out[name].dtype == ref[name].dtype
            for a, b in zip(_raw(out[name]), _raw(ref[name])):
                assert a.dtype == b.dtype and a.shape == b.shape, name
                assert a.tobytes() == b.tobytes(), name
        gathers = {w: len(ts) for w, ts in _branch_ops(text).items()}
        assert gathers == {"head": at_head, "tier.128": past_head,
                           "full": past_head}
        # every matrix a [words, slots] one of at most eight words
        for w, ts in _branch_ops(text).items():
            for t in ts:
                dims = t.split("x")[:-1]
                assert len(dims) == 1 or (len(dims) == 2 and w != "head"
                                          and int(dims[0]) <= 8), (w, t)
        # one gather a buffer where no matrix may be made
        one_each = {w: len(ts)
                    for w, ts in _branch_ops(ref_text).items()}
        assert one_each == {"head": at_head, "tier.128": at_head,
                            "full": at_head}

    @pytest.mark.parametrize("matrices", [True, False],
                             ids=["matrices", "one_a_buffer"])
    @pytest.mark.parametrize("width", list(_FETCH_WIDTHS))
    def test_a_64_bit_difference_borrows_on_halves(self, monkeypatch, width,
                                                   matrices):
        """sum(v) of values between 2^31 and 2^33, either sign: the prefix
        scan passes multiples of 2^32 between nearly every two group ends,
        so the difference borrows from the high half; no 64-bit array is
        moved one slot (``jnp.roll``, which lowers to a function of its
        own), the u32 halves are."""
        n, g = _HEAD_ROWS, _FETCH_WIDTHS[width]
        rng = np.random.default_rng(g)
        cuts = np.sort(rng.choice(np.arange(1, n), g - 1, replace=False))
        gid = np.zeros((n,), np.int64)
        gid[cuts] = 1
        gid = np.cumsum(gid)
        v = (rng.integers(1 << 31, 1 << 33, n)
             * rng.choice([-1, 1], n, p=[0.3, 0.7]))
        assert len(set(np.cumsum(v)[np.append(cuts, n) - 1] >> 32)) > g // 2
        ones = jnp.ones((n,), jnp.bool_)
        batch = ColumnBatch({
            "k": Column(jnp.asarray(gid.astype(np.int32)), ones, T.INT32),
            "v": Column(jnp.asarray(v), ones, T.INT64)})
        aggs = [AggSpec("sum", "v", "s")]
        out, ng, text = _fetch_run(monkeypatch, batch, None, aggs, True,
                                   matrices)
        assert ng == g
        assert np.array_equal(np.asarray(out["s"].data)[:g],
                              np.add.reduceat(v, np.concatenate([[0], cuts])))
        rolls = r"call @_roll\w*\((?:[^()]*)\) : \(tensor<([^>]*)>"
        moved = _branch_ops(text, rolls)
        assert set(moved) == {"head", "tier.128", "full"}
        for w, ts in moved.items():
            assert "ui32" in {t.split("x")[-1] for t in ts}, w
        assert not [t for t in re.findall(rolls, text)
                    if re.fullmatch(r"\d+x(ui|i)64", t)]
