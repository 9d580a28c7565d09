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


def _head_case(n, g, has_rv, grouped, seed):
    """``g`` groups over the live rows of an ``n``-row batch (dead rows
    trailing, one null key, one group whose values are all null) and what
    every aggregate of ``_HEAD_AGGS`` must give, group by group in the
    engine's order, from plain Python."""
    from spark_rapids_jni_tpu.columnar.column import Decimal128Column

    rng = np.random.default_rng(seed)
    live = n - n // 8 if has_rv else n
    if g == "all":
        g = live
    if g == 0:
        live = 0
    cuts = np.sort(rng.choice(np.arange(1, live), g - 1, replace=False)) \
        if g > 1 else np.zeros((0,), np.int64)
    sizes = np.diff(np.concatenate([[0], cuts, [live]])) if g else []
    keyvals = [int(x) for x in rng.choice(
        np.arange(-5 * n, 5 * n), g, replace=False)]
    if g >= 2:
        keyvals[1] = None
    rows = []   # (group, key, v, f, b, d)
    for j, size in enumerate(sizes):
        for _ in range(int(size)):
            dull = g >= 3 and j == 2
            pick = rng.random(4)
            fval = float(rng.integers(-1000, 1000))
            if rng.random() < 0.05:
                fval = math.nan
            rows.append((
                j, keyvals[j],
                None if dull or pick[0] < 0.15
                else int(rng.integers(-(1 << 40), 1 << 40)),
                None if dull or pick[1] < 0.15 else fval,
                None if dull or pick[2] < 0.15 else bool(rng.random() < 0.5),
                None if dull or pick[3] < 0.15
                else int(rng.integers(-(10**18), 10**18)) * 10))
    if not grouped:
        rows = [rows[i] for i in rng.permutation(len(rows))]
    dead = [(None, int(rng.integers(-5 * n, 5 * n)), 7, 7.0, True, 7)
            for _ in range(n - live)]
    allrows = rows + dead
    batch = ColumnBatch({
        "k": Column.from_pylist([r[1] for r in allrows], T.INT32),
        "v": Column.from_pylist([r[2] for r in allrows], T.INT64),
        "f": Column.from_pylist([r[3] for r in allrows], T.FLOAT64),
        "b": Column.from_pylist([r[4] for r in allrows], T.BOOLEAN),
        "d": Decimal128Column.from_unscaled([r[5] for r in allrows], 20, 2),
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

    want = {name: [] for name in ["k"] + [a.out_name for a in _HEAD_AGGS]}
    for j in order:
        mine = [r for r in rows if r[0] == j]
        v, f, b, d = ([r[i] for r in mine if r[i] is not None]
                      for i in (2, 3, 4, 5))
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
    return batch, rv, g, want


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
