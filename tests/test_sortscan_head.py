"""The sort engine's head (``relational/aggregate.py:_group_by_sortscan``):
a result of ``num_groups`` rows is fetched at the first group slots and
padded back to the input's rows; data with more groups than the head holds
takes the row-wide fetch.  Both against plain Python and against each other,
on either side of ``num_groups = head``."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.relational import AggSpec, group_by


_HEAD = 64          # the head these cases patch in, so that n stays small
_HEAD_ROWS = 256
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
    other bit for bit, on either side of ``num_groups = head``."""

    def _check(self, monkeypatch, n, g, has_rv, grouped, mode, seed):
        batch, rv, g, want = _head_case(n, g, has_rv, grouped, seed)
        out, ng = _head_run(monkeypatch, _HEAD, batch, rv, grouped, mode)
        assert ng == g
        for name, vals in want.items():
            assert _canon(out[name].to_pylist()[:g]) == _canon(vals), name
        if n <= _HEAD:
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

    @pytest.mark.parametrize("g", [10, 4096, 4097])
    def test_the_head_the_module_ships(self, g):
        """4096 slots, unpatched, on 8192 grouped rows: the counts and
        integer sums on both sides of it; and no gather of a row's worth
        of indices outside the branch that many groups take."""
        import jax

        from spark_rapids_jni_tpu.relational import aggregate as A

        n = 8192
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
