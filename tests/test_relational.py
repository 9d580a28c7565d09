"""Relational operator tests against pure-Python oracles.

The reference delegates these operators to libcudf and tests them upstream;
here they are in-tree, so the tests are too.  Spark semantics under test:
null ordering, null-safe grouping (nulls form a group), join keys where
null matches nothing, and float normalization (-0.0 == 0.0, one NaN).
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch, StringColumn
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.relational import (
    AggSpec,
    SortKey,
    apply_mask,
    compact,
    group_by,
    hash_join,
    sort_by,
)


def ints(vals, dtype=T.INT32):
    return Column.from_pylist(vals, dtype)


def strs(vals, **kw):
    return StringColumn.from_pylist(vals, **kw)


def trimmed(batch, count):
    """Host-side: first `count` rows as dict of lists."""
    c = int(count)
    return {k: v[:c] for k, v in batch.to_pydict().items()}


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

class TestSort:
    def test_ints_asc_nulls_first(self):
        b = ColumnBatch({"a": ints([3, None, 1, 2, None, -5])})
        out = sort_by(b, [SortKey("a")])
        assert out.to_pydict()["a"] == [None, None, -5, 1, 2, 3]

    def test_ints_desc_nulls_last(self):
        b = ColumnBatch({"a": ints([3, None, 1, 2, None, -5])})
        out = sort_by(b, [SortKey("a", ascending=False, nulls_first=False)])
        assert out.to_pydict()["a"] == [3, 2, 1, -5, None, None]

    def test_ints_desc_nulls_first(self):
        b = ColumnBatch({"a": ints([3, None, 1])})
        out = sort_by(b, [SortKey("a", ascending=False, nulls_first=True)])
        assert out.to_pydict()["a"] == [None, 3, 1]

    def test_two_keys_stable(self):
        b = ColumnBatch(
            {
                "k": ints([2, 1, 2, 1, 2]),
                "v": ints([10, 20, 30, 40, 50]),
            }
        )
        out = sort_by(b, [SortKey("k")])
        assert out.to_pydict() == {
            "k": [1, 1, 2, 2, 2],
            "v": [20, 40, 10, 30, 50],
        }

    def test_strings(self):
        b = ColumnBatch({"s": strs(["pear", "", None, "apple", "app", "z"])})
        out = sort_by(b, [SortKey("s")])
        assert out.to_pydict()["s"] == [None, "", "app", "apple", "pear", "z"]

    def test_floats_total_order(self):
        vals = [1.5, float("nan"), -0.0, 0.0, float("-inf"), float("inf"), None]
        b = ColumnBatch({"f": Column.from_pylist(vals, T.FLOAT64)})
        out = sort_by(b, [SortKey("f")])
        got = out.to_pydict()["f"]
        assert got[0] is None
        assert got[1] == float("-inf")
        assert got[2] == 0.0 and got[3] == 0.0  # -0.0 normalized to equal 0.0
        assert got[4] == 1.5
        assert got[5] == float("inf")
        assert math.isnan(got[6])  # NaN sorts greater than +inf (Spark)

    def test_int64_wide_range(self):
        vals = [2**62, -(2**62), 0, None, 7, -7]
        b = ColumnBatch({"a": ints(vals, T.INT64)})
        out = sort_by(b, [SortKey("a", nulls_first=False)])
        assert out.to_pydict()["a"] == [-(2**62), -7, 0, 7, 2**62, None]

    @pytest.mark.parametrize("jitted", [False, True])
    @pytest.mark.parametrize("with_live", [False, True])
    @pytest.mark.parametrize("ascending,nulls_first", [
        (True, True), (True, False), (False, True), (False, False)])
    def test_two_keys_and_dead_rows_against_python(
            self, ascending, nulls_first, with_live, jitted):
        """An int64 key and a float64 key in the other direction, both with
        nulls and repeats, and the rows ``live`` says are dead: the rows in
        Python's stable order of the keys, the dead ones after every live
        one and in that order among themselves."""
        import jax

        rng = np.random.default_rng(11)
        n = 96
        a = [None if rng.random() < 0.2 else int(rng.integers(-3, 3)) << 40
             for _ in range(n)]
        f = [None if rng.random() < 0.2 else float(rng.integers(-2, 2))
             for _ in range(n)]
        live = rng.random(n) < 0.7 if with_live else np.ones(n, bool)
        b = ColumnBatch({"a": ints(a, T.INT64),
                         "f": Column.from_pylist(f, T.FLOAT64),
                         "i": ints(list(range(n)))})
        keys = [SortKey("a", ascending, nulls_first),
                SortKey("f", not ascending, nulls_first)]
        run = (lambda b, m: sort_by(b, keys, m))
        if jitted:
            run = jax.jit(run)
        out = run(b, jnp.asarray(live) if with_live else None)

        def part(v, asc):
            null_rank = (v is None) != nulls_first
            return (null_rank, 0 if v is None else (v if asc else -v))

        want = sorted(range(n), key=lambda i: (
            not live[i], part(a[i], ascending), part(f[i], not ascending)))
        assert out.to_pydict()["i"] == want


# ---------------------------------------------------------------------------
# filter
# ---------------------------------------------------------------------------

class TestFilter:
    def test_compact(self):
        b = ColumnBatch(
            {"a": ints([1, 2, 3, 4, 5]), "s": strs(["a", "b", "c", "d", "e"])}
        )
        mask = jnp.asarray([True, False, True, False, True])
        out, count = compact(b, mask)
        assert int(count) == 3
        assert trimmed(out, count) == {"a": [1, 3, 5], "s": ["a", "c", "e"]}
        # tail rows are nulled
        assert out.to_pydict()["a"][3:] == [None, None]

    def test_apply_mask(self):
        b = ColumnBatch({"a": ints([1, None, 3])})
        out = apply_mask(b, jnp.asarray([True, True, False]))
        assert out.to_pydict()["a"] == [1, None, None]


# ---------------------------------------------------------------------------
# group_by
# ---------------------------------------------------------------------------

class TestGroupBy:
    def test_sum_count_min_max_mean(self):
        b = ColumnBatch(
            {
                "k": ints([1, 2, 1, 2, 1, None]),
                "v": ints([10, 20, None, 40, 30, 99]),
            }
        )
        out, ng = group_by(
            b,
            ["k"],
            [
                AggSpec("sum", "v", "s"),
                AggSpec("count", "v", "c"),
                AggSpec("count", None, "cstar"),
                AggSpec("min", "v", "mn"),
                AggSpec("max", "v", "mx"),
                AggSpec("mean", "v", "avg"),
            ],
        )
        assert int(ng) == 3
        got = trimmed(out, ng)
        # group order: key-sorted, nulls first
        assert got["k"] == [None, 1, 2]
        assert got["s"] == [99, 40, 60]
        assert got["c"] == [1, 2, 2]
        assert got["cstar"] == [1, 3, 2]
        assert got["mn"] == [99, 10, 20]
        assert got["mx"] == [99, 30, 40]
        assert got["avg"] == [99.0, 20.0, 30.0]

    def test_all_null_group_sum_is_null(self):
        b = ColumnBatch(
            {"k": ints([7, 7]), "v": ints([None, None])}
        )
        out, ng = group_by(b, ["k"], [AggSpec("sum", "v", "s"),
                                      AggSpec("count", "v", "c")])
        assert int(ng) == 1
        got = trimmed(out, ng)
        assert got["s"] == [None]
        assert got["c"] == [0]

    def test_string_keys(self):
        b = ColumnBatch(
            {
                "k": strs(["b", "a", "b", None, "a", "a"]),
                "v": ints([1, 2, 3, 4, 5, 6], T.INT64),
            }
        )
        out, ng = group_by(b, ["k"], [AggSpec("sum", "v", "s")])
        assert int(ng) == 3
        got = trimmed(out, ng)
        assert got["k"] == [None, "a", "b"]
        assert got["s"] == [4, 13, 4]

    def test_multi_key(self):
        b = ColumnBatch(
            {
                "k1": ints([1, 1, 2, 1]),
                "k2": strs(["x", "y", "x", "x"]),
                "v": Column.from_pylist([1.0, 2.0, 3.0, 4.0], T.FLOAT64),
            }
        )
        out, ng = group_by(b, ["k1", "k2"], [AggSpec("sum", "v", "s")])
        assert int(ng) == 3
        got = trimmed(out, ng)
        assert got["k1"] == [1, 1, 2]
        assert got["k2"] == ["x", "y", "x"]
        assert got["s"] == [5.0, 2.0, 3.0]

    def test_float_key_normalization(self):
        vals = [0.0, -0.0, float("nan"), float("nan")]
        b = ColumnBatch(
            {
                "k": Column.from_pylist(vals, T.FLOAT64),
                "v": ints([1, 1, 1, 1], T.INT64),
            }
        )
        out, ng = group_by(b, ["k"], [AggSpec("count", None, "c")])
        assert int(ng) == 2  # {0.0} and {NaN}
        assert trimmed(out, ng)["c"] == [2, 2]

    def test_sum_int_is_long(self):
        b = ColumnBatch(
            {"k": ints([1, 1]), "v": ints([2**30, 2**30])}
        )
        out, _ = group_by(b, ["k"], [AggSpec("sum", "v", "s")])
        assert out["s"].dtype == T.INT64
        assert out.to_pydict()["s"][0] == 2**31


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

class TestJoin:
    def _l(self):
        return ColumnBatch(
            {
                "k": ints([1, 2, 3, None, 2]),
                "lv": ints([10, 20, 30, 40, 50]),
            }
        )

    def _r(self):
        return ColumnBatch(
            {
                "k": ints([2, 3, 4, None]),
                "rv": ints([200, 300, 400, 999]),
            }
        )

    def test_inner_unique(self):
        out, count = hash_join(self._l(), self._r(), ["k"], ["k"], "inner")
        assert int(count) == 3
        got = trimmed(out, count)
        assert got["k"] == [2, 3, 2]
        assert got["lv"] == [20, 30, 50]
        assert got["rv"] == [200, 300, 200]

    def test_left_outer(self):
        out, count = hash_join(self._l(), self._r(), ["k"], ["k"], "left")
        assert int(count) == 5
        got = trimmed(out, count)
        assert got["lv"] == [10, 20, 30, 40, 50]
        assert got["rv"] == [None, 200, 300, None, 200]

    def test_semi_anti(self):
        out, count = hash_join(self._l(), self._r(), ["k"], ["k"], "semi")
        assert trimmed(out, count) == {"k": [2, 3, 2], "lv": [20, 30, 50]}
        out, count = hash_join(self._l(), self._r(), ["k"], ["k"], "anti")
        # null-keyed left rows are KEPT by anti join (Spark semantics)
        assert trimmed(out, count) == {"k": [1, None], "lv": [10, 40]}

    def test_many_to_many(self):
        left = ColumnBatch({"k": ints([1, 2]), "lv": ints([10, 20])})
        right = ColumnBatch({"k": ints([1, 1, 1, 2]), "rv": ints([1, 2, 3, 4])})
        out, count = hash_join(left, right, ["k"], ["k"], "inner", capacity=8)
        assert int(count) == 4
        got = trimmed(out, count)
        assert got["lv"] == [10, 10, 10, 20]
        assert sorted(got["rv"][:3]) == [1, 2, 3]
        assert got["rv"][3] == 4

    def test_capacity_overflow_reported(self):
        left = ColumnBatch({"k": ints([1])})
        right = ColumnBatch({"k": ints([1, 1, 1])})
        out, count = hash_join(left, right, ["k"], ["k"], "inner", capacity=2)
        assert int(count) == 3  # true total; output truncated at capacity=2

    def test_multi_key_string(self):
        left = ColumnBatch(
            {
                "a": ints([1, 1, 2]),
                "b": strs(["x", "y", "x"]),
                "lv": ints([7, 8, 9]),
            }
        )
        right = ColumnBatch(
            {
                "a": ints([1, 2]),
                "b": strs(["y", "x"]),
                "rv": ints([100, 200]),
            }
        )
        out, count = hash_join(left, right, ["a", "b"], ["a", "b"], "inner")
        got = trimmed(out, count)
        assert got["lv"] == [8, 9]
        assert got["rv"] == [100, 200]

    def test_name_collision_suffix(self):
        left = ColumnBatch({"k": ints([1]), "v": ints([1])})
        right = ColumnBatch({"k": ints([1]), "v": ints([2])})
        out, _ = hash_join(left, right, ["k"], ["k"], "inner")
        assert set(out.names) == {"k", "v", "v_r"}

    def test_jit_composes(self):
        import jax

        left, right = self._l(), self._r()

        @jax.jit
        def f(l, r):
            out, count = hash_join(l, r, ["k"], ["k"], "inner")
            return out, count

        out, count = f(left, right)
        assert int(count) == 3


class TestReviewRegressions:
    """Regressions from the first relational-layer review pass."""

    def test_null_rows_one_group_after_mask(self):
        # padded/filtered rows keep payload under validity=False; they must
        # still land in ONE null group
        b = ColumnBatch({"k": ints([1, 2, 3]), "v": ints([1, 1, 1], T.INT64)})
        masked = apply_mask(b, jnp.asarray([True, False, False]))
        out, ng = group_by(masked, ["k"], [AggSpec("count", None, "c")])
        assert int(ng) == 2
        got = trimmed(out, ng)
        assert got["k"] == [None, 1]
        assert got["c"] == [2, 1]

    def test_empty_build_side(self):
        left = ColumnBatch({"k": ints([1, 2]), "lv": ints([10, 20])})
        right = ColumnBatch({"k": ints([]), "rv": ints([])})
        out, count = hash_join(left, right, ["k"], ["k"], "inner")
        assert int(count) == 0
        out, count = hash_join(left, right, ["k"], ["k"], "left")
        assert trimmed(out, count) == {"k": [1, 2], "lv": [10, 20], "rv": [None, None]}
        out, count = hash_join(left, right, ["k"], ["k"], "anti")
        assert trimmed(out, count)["lv"] == [10, 20]

    def test_float_min_skips_nan_max_takes_nan(self):
        b = ColumnBatch(
            {
                "k": ints([1, 1, 2]),
                "v": Column.from_pylist([float("nan"), 1.0, float("nan")], T.FLOAT64),
            }
        )
        out, ng = group_by(b, ["k"], [AggSpec("min", "v", "mn"),
                                      AggSpec("max", "v", "mx")])
        got = trimmed(out, ng)
        assert got["mn"][0] == 1.0          # NaN skipped for min
        assert math.isnan(got["mx"][0])     # NaN is the max (Spark ordering)
        assert math.isnan(got["mn"][1])     # all-NaN group -> NaN
        assert math.isnan(got["mx"][1])

    def test_bool_minmax(self):
        b = ColumnBatch(
            {
                "k": ints([1, 1, 2]),
                "v": Column.from_pylist([True, False, True], T.BOOLEAN),
            }
        )
        out, ng = group_by(b, ["k"], [AggSpec("min", "v", "mn"),
                                      AggSpec("max", "v", "mx")])
        got = trimmed(out, ng)
        assert got["mn"] == [False, True]
        assert got["mx"] == [True, True]

    def test_trailing_nul_strings_distinct(self):
        b = ColumnBatch(
            {
                "k": strs(["a", "a\x00"]),
                "v": ints([1, 1], T.INT64),
            }
        )
        out, ng = group_by(b, ["k"], [AggSpec("count", None, "c")])
        assert int(ng) == 2  # 'a' and 'a\x00' are different keys

    def test_sort_minus_zero_before_zero(self):
        # ordering domain: Java Double.compare puts -0.0 before 0.0
        b = ColumnBatch({"f": Column.from_pylist([0.0, -0.0], T.FLOAT64)})
        out = sort_by(b, [SortKey("f")])
        got = np.asarray([math.copysign(1.0, x) for x in out.to_pydict()["f"]])
        assert got.tolist() == [-1.0, 1.0]

    def test_string_key_width_mismatch(self):
        left = ColumnBatch({"k": strs(["apple", "x"]), "lv": ints([1, 2])})
        right = ColumnBatch({"k": strs(["x", "y"]), "rv": ints([10, 20])})
        out, count = hash_join(left, right, ["k"], ["k"], "inner")
        assert trimmed(out, count) == {"k": ["x"], "lv": [2], "rv": [10]}

    def test_left_suffix_applied(self):
        left = ColumnBatch({"k": ints([1]), "v": ints([1])})
        right = ColumnBatch({"k": ints([1]), "v": ints([2])})
        out, _ = hash_join(left, right, ["k"], ["k"], "inner", suffixes=("_l", "_r"))
        assert set(out.names) == {"k", "v_l", "v_r"}


# ---------------------------------------------------------------------------
# window functions
# ---------------------------------------------------------------------------

class TestWindow:
    def test_rank_row_number_dense(self):
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        b = ColumnBatch(
            {
                "p": ints([1, 1, 1, 2, 2, 1]),
                "o": ints([10, 20, 20, 5, 5, 30]),
                "v": ints([1, 2, 3, 4, 5, 6], T.INT64),
            }
        )
        out = window(
            b, ["p"], ["o"],
            [
                WindowSpec("row_number", None, "rn"),
                WindowSpec("rank", None, "rk"),
                WindowSpec("dense_rank", None, "dr"),
                WindowSpec("sum", "v", "rs"),
            ],
        )
        d = out.to_pydict()
        # sorted: p=1 o=10,20,20,30 then p=2 o=5,5
        assert d["p"] == [1, 1, 1, 1, 2, 2]
        assert d["o"] == [10, 20, 20, 30, 5, 5]
        assert d["rn"] == [1, 2, 3, 4, 1, 2]
        assert d["rk"] == [1, 2, 2, 4, 1, 1]
        assert d["dr"] == [1, 2, 2, 3, 1, 1]
        # running sums in sorted order: v sorted = [1,2,3,6,4,5]
        assert d["rs"] == [1, 3, 6, 12, 4, 9]

    def test_running_min_max_nulls(self):
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        b = ColumnBatch(
            {
                "p": ints([1, 1, 1]),
                "o": ints([1, 2, 3]),
                "v": ints([5, None, 2], T.INT64),
            }
        )
        out = window(b, ["p"], ["o"],
                     [WindowSpec("min", "v", "mn"),
                      WindowSpec("max", "v", "mx"),
                      WindowSpec("count", "v", "c")])
        d = out.to_pydict()
        assert d["mn"] == [5, 5, 2]
        assert d["mx"] == [5, 5, 5]
        assert d["c"] == [1, 1, 2]

    def test_q67_shape(self):
        """sort + window(rank over partition) + filter rank<=k — the q67
        pipeline skeleton."""
        import numpy as np

        from spark_rapids_jni_tpu.relational import WindowSpec, window

        rng = np.random.default_rng(0)
        n = 256
        cat = rng.integers(0, 8, n)
        sales = rng.integers(1, 1000, n)
        b = ColumnBatch(
            {
                "cat": ints(list(cat)),
                "sales": ints(list(sales), T.INT64),
            }
        )
        out = window(b, ["cat"], ["sales"],
                     [WindowSpec("rank", None, "rk")],
                     descending=[True])
        d = out.to_pydict()
        # verify against numpy: rank of each row within its category by
        # descending sales
        got_top = {
            c: [s for s, cc, r in zip(d["sales"], d["cat"], d["rk"])
                if cc == c and r <= 3]
            for c in range(8)
        }
        for c in range(8):
            want = sorted([int(s) for s, cc in zip(sales, cat) if cc == c],
                          reverse=True)[:3]
            assert sorted(got_top[c], reverse=True)[:len(want)] == want

    def test_desc_order_nulls_last(self):
        """Spark default: DESC ordering puts nulls LAST (review regression:
        the null-flag word must not be bit-inverted with the data words)."""
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        b = ColumnBatch(
            {
                "p": ints([1, 1, 1]),
                "o": ints([10, None, 30]),
            }
        )
        out = window(b, ["p"], ["o"], [WindowSpec("row_number", None, "rn")],
                     descending=[True])
        d = out.to_pydict()
        assert d["o"] == [30, 10, None]
        assert d["rn"] == [1, 2, 3]

    def test_descending_arity_mismatch_raises(self):
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        b = ColumnBatch({"p": ints([1]), "o1": ints([1]), "o2": ints([2])})
        with pytest.raises(ValueError):
            window(b, ["p"], ["o1", "o2"],
                   [WindowSpec("row_number", None, "rn")],
                   descending=[True])


class TestReviewRegressions2:
    def test_float_sum_no_catastrophic_cancellation(self):
        """A tiny group sorting after a huge one must still sum exactly
        (segmented scan, not global prefix-sum difference)."""
        n = 4096
        ks = [0] * (n - 2) + [1, 1]
        vs = [1e12] * (n - 2) + [0.5, 0.5]
        b = ColumnBatch({"k": ints(ks), "v": Column.from_pylist(vs, T.FLOAT64)})
        out, ng = group_by(b, ["k"], [AggSpec("sum", "v", "s")])
        got = trimmed(out, ng)["s"]
        assert got[1] == 1.0


class TestQueryShapes:
    """The BASELINE.md pipeline shapes compile and produce sane results."""

    def test_q3_shape(self):
        import __graft_entry__ as ge
        import jax

        fact, dim = ge._q3_batches(512)
        res, ng = jax.jit(ge._q3_step)(fact, dim)
        assert 1 <= int(ng) <= 5
        got = trimmed(res, ng)
        assert sum(got["cnt"]) == 512  # every fact row joins exactly once

    def test_q67_shape(self):
        import __graft_entry__ as ge
        import jax

        b = ge._q67_batch(512)
        out = jax.jit(ge._q67_step)(b)
        d = out.to_pydict()
        live = [r for r, v in zip(d["rk"], d["cat"]) if v is not None]
        assert live and max(live) <= 100


def test_q95_step_matches_numpy_oracle():
    """The bench's q95 pipeline (exchange -> join -> exchange -> join ->
    domain group-by) end-to-end against a numpy oracle: the dims have
    unique keys covering every fact row, so the joins are filters and
    the group sums are bincounts."""
    import __graft_entry__ as ge

    fact, dim1, dim2 = ge._q95_batches(2048, seed=23)
    res, ng = ge._q95_step(fact, dim1, dim2)
    m = int(np.asarray(ng))
    got_orders = dict(zip(res["seg"].to_pylist()[:m],
                          res["orders"].to_pylist()[:m]))
    got_net = dict(zip(res["seg"].to_pylist()[:m],
                       res["net"].to_pylist()[:m]))
    seg = np.asarray(fact["seg"].data)
    v = np.asarray(fact["v"].data)
    want_orders = {s: int(c) for s, c in enumerate(
        np.bincount(seg, minlength=ge.Q95_SEG)) if c}
    want_net = {s: int(t) for s, t in enumerate(
        np.bincount(seg, weights=v.astype(np.float64),
                    minlength=ge.Q95_SEG).astype(np.int64))
        if want_orders.get(s)}
    assert got_orders == want_orders
    assert got_net == want_net



def test_q3_step_matches_numpy_oracle():
    """q3 shape end-to-end (dense dim join + domain group-by): the dim
    covers every fact key, so group sums reduce to bincounts."""
    import __graft_entry__ as ge

    fact, dim = ge._q3_batches(1024, seed=23)
    res, ng = ge._q3_step(fact, dim)
    m = int(np.asarray(ng))
    got_rev = dict(zip(res["seg"].to_pylist()[:m],
                       res["rev"].to_pylist()[:m]))
    got_cnt = dict(zip(res["seg"].to_pylist()[:m],
                       res["cnt"].to_pylist()[:m]))
    seg = np.asarray(fact["seg"].data)
    v = np.asarray(fact["v"].data)
    want_cnt = {s: int(c) for s, c in enumerate(np.bincount(seg, minlength=5))
                if c}
    want_rev = {s: int(t) for s, t in enumerate(
        np.bincount(seg, weights=v.astype(np.float64),
                    minlength=5).astype(np.int64)) if want_cnt.get(s)}
    assert got_cnt == want_cnt
    assert got_rev == want_rev



class TestGroupByOnehot:
    """MXU one-hot path must agree with the sort-scan group_by exactly
    (int sums bit-exact incl. wraparound; float sums within order
    tolerance)."""

    @staticmethod
    def run_both(k, v, price, kvalid=None, vvalid=None, row_valid=None,
                 domain=64):
        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec, group_by
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        n = len(k)
        kv = jnp.asarray(kvalid if kvalid is not None else [True] * n)
        vv = jnp.asarray(vvalid if vvalid is not None else [True] * n)
        batch = ColumnBatch(
            {
                "k": Column(jnp.asarray(np.asarray(k, np.int32)), kv,
                            T.INT32),
                "v": Column(jnp.asarray(np.asarray(v, np.int64)), vv,
                            T.INT64),
                "p": Column(jnp.asarray(np.asarray(price, np.float64)),
                            jnp.ones((n,), jnp.bool_), T.FLOAT64),
            }
        )
        aggs = [AggSpec("sum", "v", "s"), AggSpec("count", None, "c"),
                AggSpec("mean", "p", "m")]
        rv = None if row_valid is None else jnp.asarray(row_valid)
        res_a, ng_a = group_by(batch, ["k"], aggs, row_valid=rv)

        def groups(res, ng):
            out = {}
            ks = res["k"].to_pylist()[: int(ng)]
            ss = res["s"].to_pylist()[: int(ng)]
            cs = res["c"].to_pylist()[: int(ng)]
            ms = res["m"].to_pylist()[: int(ng)]
            for i in range(int(ng)):
                out[ks[i]] = (ss[i], cs[i], ms[i])
            return out

        ga = groups(res_a, ng_a)
        for engine in ("xla", "scatter"):
            res_b, ng_b, ovf = group_by_onehot(batch, "k", aggs, domain,
                                               row_valid=rv, engine=engine)
            assert not bool(ovf)
            gb = groups(res_b, ng_b)
            assert set(ga) == set(gb), engine
            for key in ga:
                sa, ca, ma = ga[key]
                sb, cb, mb = gb[key]
                assert sa == sb, (engine, key, sa, sb)
                assert ca == cb
                if ma is None:
                    assert mb is None
                else:
                    import math

                    assert math.isclose(ma, mb, rel_tol=1e-12), \
                        (engine, key, ma, mb)

    def test_basic(self):
        import numpy as np

        rng = np.random.default_rng(3)
        n = 4096
        self.run_both(rng.integers(0, 60, n), rng.integers(-(10**9), 10**9, n),
                      rng.random(n) * 100)

    def test_null_keys_and_values(self):
        import numpy as np

        rng = np.random.default_rng(4)
        n = 1000
        self.run_both(
            rng.integers(0, 30, n),
            rng.integers(-(10**12), 10**12, n),
            rng.random(n),
            kvalid=list(rng.random(n) > 0.1),
            vvalid=list(rng.random(n) > 0.2),
        )

    def test_row_valid_and_wraparound(self):
        import numpy as np

        rng = np.random.default_rng(5)
        n = 512
        big = [2**62, 2**62, 2**62, 2**62] * (n // 4)  # sums wrap int64
        self.run_both(
            [i % 3 for i in range(n)], big, rng.random(n),
            row_valid=list(rng.random(n) > 0.3), domain=8)

    def test_overflow_flag(self):
        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        batch = ColumnBatch({"k": Column(
            jnp.asarray(np.asarray([1, 99], np.int32)),
            jnp.ones((2,), jnp.bool_), T.INT32)})
        _, _, ovf = group_by_onehot(
            batch, "k", [AggSpec("count", None, "c")], 8)
        assert bool(ovf)

    def test_overflow_flag_int64_wraparound(self):
        """An INT64 key like 2**32 wraps to 0 under int32 — the overflow
        flag must be computed on the original width (round-2 advisor)."""
        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        batch = ColumnBatch({"k": Column(
            jnp.asarray(np.asarray([1, 2**32], np.int64)),
            jnp.ones((2,), jnp.bool_), T.INT64)})
        _, _, ovf = group_by_onehot(
            batch, "k", [AggSpec("count", None, "c")], 8)
        assert bool(ovf)

    def test_pallas_engine_matches_xla(self):
        """The fused Pallas contraction must agree with the XLA engine:
        exact int sums/counts, float sums to f32x3 tolerance; nulls,
        dead rows, and a key domain wider than one 128-lane block."""
        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        rng = np.random.default_rng(5)
        n, K = 3000, 200  # two lane blocks
        k = rng.integers(0, K, n).astype(np.int32)
        kval = rng.random(n) > 0.1
        v = rng.integers(-(2**40), 2**40, n)
        vval = rng.random(n) > 0.2
        price = rng.random(n) * 1e6
        live = rng.random(n) > 0.15
        batch = ColumnBatch({
            "k": Column(jnp.asarray(k), jnp.asarray(kval), T.INT32),
            "v": Column(jnp.asarray(v), jnp.asarray(vval), T.INT64),
            "p": Column(jnp.asarray(price), jnp.ones((n,), jnp.bool_),
                        T.FLOAT64),
        })
        aggs = [AggSpec("sum", "v", "sv"), AggSpec("count", None, "c"),
                AggSpec("count", "v", "cv"), AggSpec("mean", "p", "mp")]
        ra, nga, _ = group_by_onehot(batch, "k", aggs, K,
                                     row_valid=jnp.asarray(live),
                                     float_mode="f32x3")
        rb, ngb, _ = group_by_onehot(batch, "k", aggs, K,
                                     row_valid=jnp.asarray(live),
                                     float_mode="f32x3", engine="pallas")
        assert int(nga) == int(ngb)
        g = int(nga)
        for name in ("k", "sv", "c", "cv"):
            np.testing.assert_array_equal(
                np.asarray(ra[name].data)[:g], np.asarray(rb[name].data)[:g],
                err_msg=name)
            np.testing.assert_array_equal(
                np.asarray(ra[name].validity)[:g],
                np.asarray(rb[name].validity)[:g], err_msg=name)
        np.testing.assert_allclose(
            np.asarray(ra["mp"].data)[:g], np.asarray(rb["mp"].data)[:g],
            rtol=1e-5)

    def test_pallas_engine_int_only_and_f64_rejected(self):
        """Int-only aggs take the no-float kernel (mf=0); float aggs with
        the default f64 mode must be rejected loudly, not silently
        downgraded to f32x3 rounding."""
        import numpy as np

        import jax.numpy as jnp
        import pytest

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        rng = np.random.default_rng(9)
        n = 1500
        batch = ColumnBatch({
            "k": Column(jnp.asarray(rng.integers(0, 10, n).astype(np.int32)),
                        jnp.ones((n,), jnp.bool_), T.INT32),
            "v": Column(jnp.asarray(rng.integers(-100, 100, n)),
                        jnp.ones((n,), jnp.bool_), T.INT64),
            "p": Column(jnp.asarray(rng.random(n)), jnp.ones((n,), jnp.bool_),
                        T.FLOAT64),
        })
        aggs = [AggSpec("sum", "v", "sv"), AggSpec("count", None, "c")]
        ra, nga, _ = group_by_onehot(batch, "k", aggs, 10)
        rb, ngb, _ = group_by_onehot(batch, "k", aggs, 10, engine="pallas")
        g = int(nga)
        assert g == int(ngb)
        np.testing.assert_array_equal(np.asarray(ra["sv"].data)[:g],
                                      np.asarray(rb["sv"].data)[:g])
        with pytest.raises(ValueError, match="f32x3"):
            group_by_onehot(batch, "k", [AggSpec("sum", "p", "sp")], 10,
                            engine="pallas")
        with pytest.raises(ValueError, match="engine"):
            group_by_onehot(batch, "k", aggs, 10, engine="Pallas")


    def test_f32x3_mode_close(self):
        import math

        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import AggSpec
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        rng = np.random.default_rng(6)
        n = 4096
        batch = ColumnBatch(
            {
                "k": Column(jnp.asarray(rng.integers(0, 10, n)
                                        .astype(np.int32)),
                            jnp.ones((n,), jnp.bool_), T.INT32),
                "p": Column(jnp.asarray(rng.random(n) * 100),
                            jnp.ones((n,), jnp.bool_), T.FLOAT64),
            }
        )
        exact, ng, _ = group_by_onehot(
            batch, "k", [AggSpec("sum", "p", "s")], 16)
        approx, _, _ = group_by_onehot(
            batch, "k", [AggSpec("sum", "p", "s")], 16, float_mode="f32x3")
        for a, b in zip(exact["s"].to_pylist()[: int(ng)],
                        approx["s"].to_pylist()[: int(ng)]):
            assert math.isclose(a, b, rel_tol=1e-5)


_EXACT_SUM_CASES = [
    "q6_shape", "spread_2e80_both_signs", "cancels_to_near_zero",
    "subnormals_only", "float32_column", "nulls_null_keys_row_valid",
    "nan_and_infs_in_their_own_buckets", "permuted_rows_same_bits",
    "all_null_and_all_dead"]


def _exact_sum_case(name, rng):
    """(keys, values, value validity, key validity, row_valid, dtype) of one
    case of :class:`TestOnehotExactFloatSums`; 10 keys, every bucket holds
    some of the column's largest magnitudes."""
    n = 3000
    k = rng.integers(0, 10, n)
    ones = np.ones(n, bool)
    if name == "q6_shape":
        x = rng.random(n) * 100
        return k, x, ones, ones, x < 50.0, T.FLOAT64
    if name == "spread_2e80_both_signs":
        x = (rng.uniform(1, 2, n) * np.exp2(rng.integers(-40, 41, n))
             * rng.choice([-1.0, 1.0], n))
        return k, x, ones, ones, None, T.FLOAT64
    if name == "cancels_to_near_zero":
        h = n // 2
        x = rng.uniform(-100, 100, n)
        x[h:] = -x[:h]
        k[h:] = k[:h]
        x[::7] += rng.uniform(-1e-9, 1e-9, len(x[::7]))
        return k, x, ones, ones, None, T.FLOAT64
    if name == "subnormals_only":
        x = (rng.integers(0, 1 << 52, n).astype(np.uint64).view(np.float64)
             * rng.choice([-1.0, 1.0], n))
        return k, x, ones, ones, None, T.FLOAT64
    if name == "float32_column":
        x = (rng.random(n) * 1e4).astype(np.float32)
        return k, x, ones, ones, None, T.FLOAT32
    if name == "nulls_null_keys_row_valid":
        x = rng.normal(0, 1e6, n)
        return (k, x, rng.random(n) > 0.2, rng.random(n) > 0.1,
                rng.random(n) > 0.3, T.FLOAT64)
    if name == "nan_and_infs_in_their_own_buckets":
        x = rng.random(n) * 100
        k = rng.integers(0, 6, n)
        x[:8] = [np.nan, 1.0, np.inf, 2.0, -np.inf, 3.0, np.inf, -np.inf]
        k[:8] = [6, 6, 7, 7, 8, 8, 9, 9]
        return k, x, ones, ones, None, T.FLOAT64
    raise KeyError(name)


def _onehot_float_sums(k, x, xvalid, kvalid, row_valid, dtype):
    """group_by_onehot(engine="xla", float_mode="f64") -> {key: (sum or
    None, count(x), count(*))}; a null key is ``None``."""
    from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

    batch = ColumnBatch({
        "k": Column(jnp.asarray(np.asarray(k, np.int32)),
                    jnp.asarray(kvalid), T.INT32),
        "x": Column(jnp.asarray(x), jnp.asarray(xvalid), dtype)})
    res, ng, ovf = group_by_onehot(
        batch, "k", [AggSpec("sum", "x", "s"), AggSpec("count", "x", "cx"),
                     AggSpec("count", None, "c")], 10,
        row_valid=None if row_valid is None else jnp.asarray(row_valid),
        float_mode="f64", engine="xla")
    assert not bool(ovf)
    g = int(ng)
    cols = {c: res[c].to_pylist()[:g] for c in ("k", "s", "cx", "c")}
    return {cols["k"][i]: (cols["s"][i], cols["cx"][i], cols["c"][i])
            for i in range(g)}


class TestOnehotExactFloatSums:
    """``float_mode="f64"`` on the one-hot engine: a double sum is fixed-point
    digits on the int8 contraction, so a bucket's sum is ``math.fsum`` of
    its rows (to the last place: rows far under the column's largest are
    truncated on the grid) whatever the order.  ``bits`` reads IEEE doubles
    as the CPU keeps them; ``limbs`` is the route the TPU takes, where a
    double is f32 parts (so nothing outside f32's exponent range)."""

    # a double of f32 parts has no f64 subnormals: not a case of "limbs"
    @pytest.mark.parametrize("route,case", [
        (r, c) for r in ("bits", "limbs") for c in _EXACT_SUM_CASES
        if (r, c) != ("limbs", "subnormals_only")])
    def test_bucket_sums_equal_fsum(self, route, case, monkeypatch):
        if route == "limbs":
            import jax

            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        rng = np.random.default_rng(27)
        if case == "permuted_rows_same_bits":
            k, x, xv, kv, rv, dt = _exact_sum_case(
                "spread_2e80_both_signs", rng)
            a = _onehot_float_sums(k, x, xv, kv, rv, dt)
            p = rng.permutation(len(k))
            b = _onehot_float_sums(k[p], x[p], xv[p], kv[p], rv, dt)
            assert a.keys() == b.keys()
            for key in a:
                assert np.float64(a[key][0]).tobytes() \
                    == np.float64(b[key][0]).tobytes(), key
            return
        if case == "all_null_and_all_dead":
            k, x, xv, kv, rv, dt = _exact_sum_case("q6_shape", rng)
            got = _onehot_float_sums(k, x, ~xv, kv, None, dt)
            assert len(got) == 10
            assert all(v[0] is None and v[1] == 0 and v[2] > 0
                       for v in got.values())
            from spark_rapids_jni_tpu.relational.aggregate import (
                _domain_partials)

            batch = ColumnBatch({
                "k": Column(jnp.asarray(k, jnp.int32), jnp.asarray(kv),
                            T.INT32),
                "x": Column(jnp.asarray(x), jnp.asarray(xv), dt)})
            parts, _ = _domain_partials(
                batch, "k", [AggSpec("sum", "x", "s")], 10,
                jnp.zeros((len(k),), jnp.bool_), "xla", "f64")
            assert np.asarray(parts["fsum"]["x"]).tobytes() \
                == np.zeros(11).tobytes()
            assert not np.asarray(parts["star"]).any()
            return
        k, x, xv, kv, rv, dt = _exact_sum_case(case, rng)
        got = _onehot_float_sums(k, x, xv, kv, rv, dt)
        live = np.ones(len(k), bool) if rv is None else rv
        keys = {None if not kv[i] else int(k[i])
                for i in range(len(k)) if live[i]}
        assert set(got) == keys
        for key in keys:
            rows = live & (~kv if key is None else kv & (k == key))
            vals = [float(v) for v in x[rows & xv]]
            s, cx, c = got[key]
            assert (cx, c) == (len(vals), int(rows.sum())), key
            if not vals:
                assert s is None
            elif any(math.isnan(v) for v in vals) or (
                    math.inf in vals and -math.inf in vals):
                assert math.isnan(s), (key, s)
            elif math.inf in vals or -math.inf in vals:
                assert s == (math.inf if math.inf in vals else -math.inf)
            else:
                want = math.fsum(vals)
                assert abs(s - want) <= math.ulp(want), (key, s, want)


class TestOuterJoins:
    """right/full outer joins vs a pandas-style python oracle."""

    @staticmethod
    def oracle(lk, lv, rk, rv, how):
        out = []
        for i, k in enumerate(lk):
            matches = [j for j, k2 in enumerate(rk)
                       if k is not None and k2 == k]
            if matches:
                for j in matches:
                    out.append((k, lv[i], rk[j], rv[j]))
            elif how in ("left", "full"):
                out.append((k, lv[i], None, None))
        if how == "full":
            for j, k2 in enumerate(rk):
                if k2 is None or k2 not in [k for k in lk if k is not None]:
                    out.append((None, None, rk[j], rv[j]))
        return sorted(out, key=lambda t: (t[0] is None, t[0] or 0,
                                          t[1] is None, t[1] or 0,
                                          t[3] is None, t[3] or 0))

    @staticmethod
    def batches(lk, lv, rk, rv):
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

        return (
            ColumnBatch({"k": Column.from_pylist(lk, T.INT32),
                         "lv": Column.from_pylist(lv, T.INT64)}),
            ColumnBatch({"k": Column.from_pylist(rk, T.INT32),
                         "rv": Column.from_pylist(rv, T.INT64)}),
        )

    def test_full_outer(self):
        from spark_rapids_jni_tpu.relational import hash_join

        lk = [1, 2, None, 4, 5]
        lv = [10, 20, 30, 40, 50]
        rk = [2, 2, 6, None]
        rv = [200, 201, 600, 700]
        left, right = self.batches(lk, lv, rk, rv)
        res, total = hash_join(left, right, ["k"], ["k"], "full",
                               capacity=16)
        t = int(total)
        ks = res["k"].to_pylist()[:t]
        lvs = res["lv"].to_pylist()[:t]
        rks = res["k_r"].to_pylist()[:t] if "k_r" in res.names else \
            res["k" + "_right"].to_pylist()[:t]
        rvs = res["rv"].to_pylist()[:t]
        got = sorted(zip(ks, lvs, rks, rvs),
                     key=lambda x: (x[0] is None, x[0] or 0,
                                    x[1] is None, x[1] or 0,
                                    x[3] is None, x[3] or 0))
        want = self.oracle(lk, lv, rk, rv, "full")
        assert got == want

    def test_right_outer(self):
        from spark_rapids_jni_tpu.relational import hash_join

        lk = [1, 2, 2]
        lv = [10, 20, 21]
        rk = [2, 3]
        rv = [200, 300]
        left, right = self.batches(lk, lv, rk, rv)
        res, total = hash_join(left, right, ["k"], ["k"], "right",
                               capacity=8)
        t = int(total)
        # right join == swapped left join: right columns first, keys kept
        ks = res["k"].to_pylist()[:t]
        rvs = res["rv"].to_pylist()[:t]
        lvs = res["lv"].to_pylist()[:t]
        got = sorted(zip(ks, rvs, lvs),
                     key=lambda x: (x[0], x[2] is None, x[2] or 0))
        assert got == [(2, 200, 20), (2, 200, 21), (3, 300, None)]


    def test_full_join_overflow_and_empty_right(self):
        from spark_rapids_jni_tpu.relational import hash_join

        # overflow: 3 left rows each matching 2 right rows, capacity 4
        left, right = self.batches([1, 1, 1], [10, 11, 12],
                                   [1, 1, 9], [100, 101, 900])
        res, count = hash_join(left, right, ["k"], ["k"], "full",
                               capacity=4)
        assert int(count) > 4 + 3  # unambiguous overflow signal
        # retry with a big-enough budget succeeds
        res, count = hash_join(left, right, ["k"], ["k"], "full",
                               capacity=16)
        assert int(count) == 7  # 6 matches + unmatched k=9

        # empty right side: no spurious all-null appended row
        left, right = self.batches([1, 2], [10, 20], [], [])
        res, count = hash_join(left, right, ["k"], ["k"], "full",
                               capacity=4)
        assert int(count) == 2
        assert res["lv"].to_pylist()[:2] == [10, 20]


class TestLagLead:
    def test_lag_lead_within_partitions(self):
        import numpy as np

        import jax.numpy as jnp

        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        part = [1, 1, 1, 2, 2, 3]
        order = [10, 20, 30, 5, 6, 9]
        vals = [100, 200, 300, 400, 500, 600]
        batch = ColumnBatch(
            {"p": Column.from_pylist(part, T.INT32),
             "o": Column.from_pylist(order, T.INT64),
             "v": Column.from_pylist(vals, T.INT64)})
        res = window(batch, ["p"], ["o"],
                     [WindowSpec("lag", "v", "lag1"),
                      WindowSpec("lead", "v", "lead1"),
                      WindowSpec("lag", "v", "lag2", offset=2)])
        rows = sorted(zip(res["p"].to_pylist(), res["o"].to_pylist(),
                          res["lag1"].to_pylist(), res["lead1"].to_pylist(),
                          res["lag2"].to_pylist()))
        assert rows == [
            (1, 10, None, 200, None),
            (1, 20, 100, 300, None),
            (1, 30, 200, None, 100),
            (2, 5, None, 500, None),
            (2, 6, 400, None, None),
            (3, 9, None, None, None),
        ]

    def test_lag_propagates_source_nulls(self):
        from spark_rapids_jni_tpu.columnar import types as T
        from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
        from spark_rapids_jni_tpu.relational import WindowSpec, window

        batch = ColumnBatch(
            {"p": Column.from_pylist([1, 1, 1], T.INT32),
             "o": Column.from_pylist([1, 2, 3], T.INT64),
             "v": Column.from_pylist([7, None, 9], T.INT64)})
        res = window(batch, ["p"], ["o"], [WindowSpec("lag", "v", "lg")])
        got = [x for _, x in sorted(zip(res["o"].to_pylist(),
                                        res["lg"].to_pylist()))]
        assert got == [None, 7, None]


class TestGroupSortPayloadModes:
    """The two agg-movement strategies (config ``group_sort_payload``)
    must be bit-identical; 'gather' is the v5e-measured default, 'ride'
    is kept for A/B (see aggregate.py docstring / round-3 notes)."""

    def test_ride_equals_gather(self):
        from spark_rapids_jni_tpu import config

        rng = np.random.default_rng(5)
        n = 4096
        b = ColumnBatch({
            "k": Column.from_pylist(
                [None if x == 0 else int(x) for x in
                 rng.integers(0, 37, n)], T.INT32),
            "v": Column.from_pylist(
                [None if x % 11 == 0 else int(x) for x in
                 rng.integers(-(10**12), 10**12, n)], T.INT64),
            "f": Column.from_pylist(
                [None if x % 7 == 0 else float(x) for x in
                 rng.integers(-1000, 1000, n)], T.FLOAT64),
        })
        aggs = [AggSpec("sum", "v", "s"), AggSpec("count", "v", "c"),
                AggSpec("min", "f", "lo"), AggSpec("max", "f", "hi"),
                AggSpec("mean", "f", "m")]
        rv = jnp.asarray(rng.random(n) < 0.9)
        results = {}
        for mode in ("gather", "ride"):
            config.set("group_sort_payload", mode)
            try:
                out, ng = group_by(b, ["k"], aggs, row_valid=rv)
            finally:
                config.reset("group_sort_payload")
            results[mode] = (int(ng), {
                name: out[name].to_pylist()[: int(ng)]
                for name in ("k", "s", "c", "lo", "hi", "m")})
        assert results["ride"] == results["gather"]


class TestGroupByDecimalSum:
    """sum(decimal128) group aggregation: exact 256-bit segmented sums,
    Spark result type decimal(min(38, p+10), s), overflow -> null
    (non-ANSI Sum semantics; per-element add parity lives in
    tests/test_decimal.py against reference DecimalUtils)."""

    def _run(self, keys, vals, precision, scale, aggs=None, **kw):
        from spark_rapids_jni_tpu.columnar.column import Decimal128Column

        b = ColumnBatch({
            "k": Column.from_pylist(keys, T.INT32),
            "d": Decimal128Column.from_unscaled(vals, precision, scale),
        })
        out, ng = group_by(b, ["k"], aggs or [
            AggSpec("sum", "d", "s"), AggSpec("count", "d", "c")], **kw)
        n = int(ng)
        return (out["k"].to_pylist()[:n], out["s"].to_pylist()[:n],
                out["c"].to_pylist()[:n] if "c" in out.names else None,
                out["s"].dtype)

    def test_golden_sums_nulls_negatives(self):
        keys = [1, 2, 1, None, 2, 1, 3]
        vals = [10**20, -5, None, 7, 10**20 + 5, -(10**20), 0]
        ks, sums, cnts, dt = self._run(keys, vals, 21, 2)
        got = dict(zip(ks, sums))
        assert got == {None: 7, 1: 0, 2: 10**20, 3: 0}
        assert dict(zip(ks, cnts)) == {None: 1, 1: 2, 2: 2, 3: 1}
        assert (dt.precision, dt.scale) == (31, 2)

    def test_all_null_group_is_null(self):
        ks, sums, _, _ = self._run([1, 1, 2], [None, None, 3], 10, 0)
        assert dict(zip(ks, sums)) == {1: None, 2: 3}

    def test_overflow_to_null_at_38(self):
        # p=38 -> result precision stays 38; two values summing past
        # 10^38 must null out, a group within bounds must not
        big = 6 * 10**37
        ks, sums, _, dt = self._run([1, 1, 2, 2], [big, big, big, -big],
                                    38, 0)
        assert dict(zip(ks, sums)) == {1: None, 2: 0}
        assert dt.precision == 38

    def test_row_valid_and_payload_modes(self):
        from spark_rapids_jni_tpu import config

        keys = [5, 5, 6, 6, 5]
        vals = [100, 200, None, 400, 800]
        rv = jnp.asarray([True, False, True, True, True])
        res = {}
        for mode in ("gather", "ride"):
            config.set("group_sort_payload", mode)
            try:
                ks, sums, cnts, _ = self._run(keys, vals, 12, 3,
                                              row_valid=rv)
            finally:
                config.reset("group_sort_payload")
            res[mode] = (ks, sums, cnts)
        assert res["gather"] == res["ride"]
        ks, sums, cnts = res["gather"]
        assert dict(zip(ks, sums)) == {5: 900, 6: 400}
        assert dict(zip(ks, cnts)) == {5: 2, 6: 1}

    def test_onehot_decimal_sum_matches_sort_path(self):
        from spark_rapids_jni_tpu.columnar.column import Decimal128Column
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        rng = np.random.default_rng(11)
        n = 1000
        keys = [int(x) for x in rng.integers(0, 7, n)]
        vals = [None if x % 13 == 0 else int(x) * 10**18 - 5 * 10**17
                for x in rng.integers(-50, 50, n)]
        b = ColumnBatch({
            "k": Column.from_pylist(keys, T.INT32),
            "d": Decimal128Column.from_unscaled(vals, 25, 4),
        })
        aggs = [AggSpec("sum", "d", "s"), AggSpec("count", "d", "c")]
        want, ngw = group_by(b, ["k"], aggs)
        nw = int(ngw)
        want_map = dict(zip(want["k"].to_pylist()[:nw],
                            want["s"].to_pylist()[:nw]))
        for engine in ("xla", "pallas", "scatter"):
            got, ng, overflow = group_by_onehot(b, "k", aggs, 7,
                                                engine=engine)
            assert not bool(overflow)
            m = int(ng)
            got_map = dict(zip(got["k"].to_pylist()[:m],
                               got["s"].to_pylist()[:m]))
            assert got_map == want_map, engine
            assert got["s"].dtype.precision == 35
            assert dict(zip(got["k"].to_pylist()[:m],
                            got["c"].to_pylist()[:m])) == dict(
                zip(want["k"].to_pylist()[:nw],
                    want["c"].to_pylist()[:nw]))

    def test_onehot_decimal_overflow_group_nulls(self):
        from spark_rapids_jni_tpu.columnar.column import Decimal128Column
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        big = 6 * 10**37
        b = ColumnBatch({
            "k": Column.from_pylist([0, 0, 1, 1], T.INT32),
            "d": Decimal128Column.from_unscaled([big, big, big, -big],
                                                38, 0),
        })
        got, ng, overflow = group_by_onehot(
            b, "k", [AggSpec("sum", "d", "s")], 2)
        assert not bool(overflow) and int(ng) == 2
        m = dict(zip(got["k"].to_pylist()[:2], got["s"].to_pylist()[:2]))
        assert m == {0: None, 1: 0}

    def test_mean_min_max_decimal(self):
        """avg: Spark Average bounded(p+4, s+4) with HALF_UP; min/max:
        signed-128 comparisons.  Goldens from python Decimal."""
        keys = [1, 1, 1, 2, 2, 3, 3]
        # scale 0, precision 5
        vals = [0, 1, 1, -7, None, 10**4, -(10**4)]
        ks, outs, _, dt = self._run(
            keys, vals, 5, 0,
            aggs=[AggSpec("mean", "d", "s")])
        got = dict(zip(ks, outs))
        # avg type decimal(9, 4): unscaled at scale 4
        assert (dt.precision, dt.scale) == (9, 4)
        assert got == {1: 6667,          # 2/3 = 0.6667 HALF_UP
                       2: -70000,        # -7.0000
                       3: 0}
        ks, mins, _, mdt = self._run(keys, vals, 5, 0,
                                     aggs=[AggSpec("min", "d", "s")])
        assert dict(zip(ks, mins)) == {1: 0, 2: -7, 3: -(10**4)}
        assert (mdt.precision, mdt.scale) == (5, 0)
        ks, maxs, _, _ = self._run(keys, vals, 5, 0,
                                   aggs=[AggSpec("max", "d", "s")])
        assert dict(zip(ks, maxs)) == {1: 1, 2: -7, 3: 10**4}

    def test_mean_decimal_p38_bounded_clamp(self):
        # p=38 -> Average type is DecimalType.bounded(p+4, s+4): a plain
        # clamp of BOTH fields to 38 (no adjustPrecisionScale trade);
        # s=2 gives decimal(38, 6), s=10 gives decimal(38, 14)
        ks, outs, _, dt = self._run(
            [9, 9], [123456, 100], 38, 2,
            aggs=[AggSpec("mean", "d", "s")])
        assert (dt.precision, dt.scale) == (38, 6)
        # (1234.56 + 1.00)/2 = 617.78 -> unscaled at scale 6
        assert outs == [617780000]
        ks, outs, _, dt = self._run(
            [9, 9, 9], [2, 0, 0], 38, 10,
            aggs=[AggSpec("mean", "d", "s")])
        assert (dt.precision, dt.scale) == (38, 14)
        # (2e-10 + 0 + 0)/3 at scale 14 = 0.666... e-10 -> 6667 HALF_UP
        assert outs == [6667]

    def test_onehot_decimal_mean_matches_sort_path(self):
        from spark_rapids_jni_tpu.columnar.column import Decimal128Column
        from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

        rng = np.random.default_rng(23)
        n = 500
        keys = [int(x) for x in rng.integers(0, 5, n)]
        vals = [None if x % 17 == 0 else int(x)
                for x in rng.integers(-(10**10), 10**10, n)]
        b = ColumnBatch({
            "k": Column.from_pylist(keys, T.INT32),
            "d": Decimal128Column.from_unscaled(vals, 20, 3),
        })
        aggs = [AggSpec("mean", "d", "m")]
        want, ngw = group_by(b, ["k"], aggs)
        nw = int(ngw)
        want_map = dict(zip(want["k"].to_pylist()[:nw],
                            want["m"].to_pylist()[:nw]))
        for engine in ("xla", "pallas", "scatter"):
            got, ng, overflow = group_by_onehot(b, "k", aggs, 5,
                                                engine=engine)
            assert not bool(overflow)
            m = int(ng)
            assert dict(zip(got["k"].to_pylist()[:m],
                            got["m"].to_pylist()[:m])) == want_map, engine
            assert got["m"].dtype.precision == 24
            assert got["m"].dtype.scale == 7


class TestGroupByDomainOrSort:
    """Adaptive domain-or-sort aggregation: one jitted program, runtime
    branch on the key-overflow flag; both branches padded to a common
    shape and Spark-equal to the general sort-scan result."""

    @staticmethod
    def _build(rng, keys):
        import jax.numpy as jnp

        n = len(keys)
        return ColumnBatch({
            "k": Column(jnp.asarray(np.asarray(keys, np.int32)),
                        jnp.asarray(rng.random(n) > 0.1), T.INT32),
            "v": Column(jnp.asarray(rng.integers(-(10**9), 10**9, n)),
                        jnp.asarray(rng.random(n) > 0.2), T.INT64),
            "p": Column(jnp.asarray(rng.random(n) * 50),
                        jnp.ones((n,), jnp.bool_), T.FLOAT64),
        })

    def test_matches_sort_scan_both_branches(self):
        import jax

        from spark_rapids_jni_tpu.relational import (
            group_by_domain_or_sort,
        )

        rng = np.random.default_rng(8)
        aggs = [AggSpec("sum", "v", "s"), AggSpec("count", None, "c"),
                AggSpec("mean", "p", "m")]
        jfn = jax.jit(
            lambda b: group_by_domain_or_sort(b, "k", aggs, 32))

        def gmap(res, ng):
            g = int(ng)
            out = {}
            for i in range(g):
                m = res["m"].to_pylist()[i]
                out[res["k"].to_pylist()[i]] = (
                    res["s"].to_pylist()[i], res["c"].to_pylist()[i],
                    None if m is None else round(m, 9))
            return out

        cases = {
            "in-domain": list(rng.integers(0, 30, 500)),
            # one key outside [0, 32): the cond's sort branch must run
            "overflow": list(rng.integers(0, 30, 499)) + [77],
        }
        for name, keys in cases.items():
            b = self._build(rng, keys)
            res, ng = jfn(b)
            want, ngw = group_by(b, ["k"], aggs)
            assert gmap(res, ng) == gmap(want, ngw), name

    def test_small_batch_pads_to_domain(self):
        """n < domain+1: the sort branch's rows get PADDED up to K+1 —
        the one geometry where _pad_rows actually extends live results,
        so values (not just shapes) must survive the padding."""
        from spark_rapids_jni_tpu.relational import (
            group_by_domain_or_sort,
        )

        rng = np.random.default_rng(9)
        aggs = [AggSpec("count", None, "c"), AggSpec("sum", "v", "s")]
        keys = list(rng.integers(0, 30, 8))
        b = self._build(rng, keys)
        res, ng = group_by_domain_or_sort(b, "k", aggs, 32)
        assert res.num_rows == 33  # max(n=8, domain+1)
        want, ngw = group_by(b, ["k"], aggs)
        assert int(ng) == int(ngw)

        def gmap(r, m):
            return {r["k"].to_pylist()[i]:
                    (r["c"].to_pylist()[i], r["s"].to_pylist()[i])
                    for i in range(int(m))}

        assert gmap(res, ng) == gmap(want, ngw)
        # padding rows past num_groups are null
        assert not bool(np.asarray(res["k"].validity)[int(ng):].any())


class TestJoinDenseOrHash:
    """r5 dimension-join fast path: when the build side has unique dense
    int keys the join is a scatter-table + gathers; the output must be
    BIT-identical to hash_join in every case, including the ones where
    the runtime check rejects the dense path."""

    def _batches(self, lk, rk, lpay=None, rpay=None):
        import jax.numpy as jnp

        left = ColumnBatch({
            "k": Column.from_pylist(lk, T.INT32),
            "lv": Column.from_pylist(
                lpay or [i * 10 for i in range(len(lk))], T.INT64),
        })
        right = ColumnBatch({
            "k": Column.from_pylist(rk, T.INT32),
            "rv": Column.from_pylist(
                rpay or [i * 100 for i in range(len(rk))], T.INT64),
        })
        return left, right

    def _both(self, left, right, domain, **kw):
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            join_dense_or_hash,
        )

        want, wn = hash_join(left, right, ["k"], ["k"], "inner", **kw)
        got, gn = join_dense_or_hash(left, right, "k", "k", domain, **kw)
        assert int(gn) == int(wn)
        m = int(wn)
        for name in want.names:
            assert got[name].to_pylist()[:m] == \
                want[name].to_pylist()[:m], name
        return int(wn)

    def test_dense_dim_matches_hash_join(self):
        left, right = self._batches([3, 0, 7, 3, None, 9, 1],
                                    list(range(8)))
        # matches: 3, 0, 7, 3, 1 (null key and out-of-dim 9 both drop)
        n = self._both(left, right, 8)
        assert n == 5

    def test_partial_dim_coverage(self):
        # dim covers only even keys; odd fact keys must drop
        left, right = self._batches([0, 1, 2, 3, 4, 5], [0, 2, 4])
        n = self._both(left, right, 6)
        assert n == 3

    def test_duplicate_right_keys_fall_back(self):
        # duplicate build keys -> dense check fails -> general engine
        left, right = self._batches([1, 2, 1], [1, 1, 2])
        n = self._both(left, right, 4)
        assert n == 5  # rows with k=1 match twice

    def test_out_of_domain_right_keys_fall_back(self):
        left, right = self._batches([1, 2, 50], [1, 2, 50])
        self._both(left, right, 4)  # 50 >= domain -> general engine

    def test_valid_masks(self):
        import jax.numpy as jnp

        left, right = self._batches([0, 1, 2, 3], [0, 1, 2, 3])
        lv = jnp.asarray([True, False, True, True])
        rv = jnp.asarray([True, True, False, True])
        self._both(left, right, 4, left_valid=lv, right_valid=rv)

    def test_capacity_truncation_signals(self):
        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left, right = self._batches([0, 1, 2, 3], [0, 1, 2, 3])
        got, gn = join_dense_or_hash(left, right, "k", "k", 4, capacity=2)
        assert int(gn) == 4 and got.num_rows == 2  # count>capacity

    def test_non_inner_delegates(self):
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            join_dense_or_hash,
        )

        left, right = self._batches([0, 5, 2], [0, 1, 2])
        want, wn = hash_join(left, right, ["k"], ["k"], "left")
        got, gn = join_dense_or_hash(left, right, "k", "k", 4, how="left")
        assert int(gn) == int(wn)
        m = int(wn)
        for name in want.names:
            assert got[name].to_pylist()[:m] == want[name].to_pylist()[:m]

    def test_int64_wrap_keys_fall_back(self):
        # an int64 key >= 2^32 wraps to a small int32; the runtime check
        # must reject the dense path so no fabricated match appears
        left = ColumnBatch({
            "k": Column.from_pylist([3, (1 << 32) + 3], T.INT64),
            "lv": Column.from_pylist([10, 20], T.INT64),
        })
        right = ColumnBatch({
            "k": Column.from_pylist([3], T.INT64),
            "rv": Column.from_pylist([100], T.INT64),
        })
        from spark_rapids_jni_tpu.relational import (
            hash_join,
            join_dense_or_hash,
        )

        want, wn = hash_join(left, right, ["k"], ["k"], "inner")
        got, gn = join_dense_or_hash(left, right, "k", "k", 8)
        assert int(gn) == int(wn) == 1
        m = int(wn)
        for name in want.names:
            assert got[name].to_pylist()[:m] == want[name].to_pylist()[:m]


def _masked_case(name):
    """(left keys, right keys, domain, left_valid, right_valid, dense) of
    one case of a join that hands on a row mask."""
    t, f = True, False
    return {
        # the dense branch: unique build keys inside the domain
        "dense_all_match": ([3, 0, 7, 3, 1], list(range(8)), 8, None, None,
                            True),
        "dense_out_of_domain_and_null":
            ([3, 0, 9, None, -1, 7, 3, 64, None, 1], list(range(8)), 8,
             None, None, True),
        "dense_partial_coverage": ([0, 1, 2, 3, 4, 5], [0, 2, 4], 6, None,
                                   None, True),
        "dense_left_valid_holes": ([0, 1, 2, 3, 2, 1], [0, 1, 2, 3], 4,
                                   [t, f, t, t, f, t], None, True),
        "dense_right_valid_holes": ([0, 1, 2, 3, 2, 1], [0, 1, 2, 3], 4,
                                    None, [t, t, f, t], True),
        "dense_both_valid_holes": ([0, 1, None, 3, 2, 1], [0, 1, 2, 3], 4,
                                   [f, t, t, t, t, f], [f, t, t, t], True),
        "dense_null_build_key": ([0, 1, 2, 1], [0, None, 2], 4, None, None,
                                 True),
        "dense_no_match": ([5, 6, None], [0, 1, 2], 8, None, None, True),
        "dense_one_left_row": ([2], [0, 1, 2], 4, None, None, True),
        "dense_one_left_row_dead": ([2], [0, 1, 2], 4, [f], None, True),
        "dense_no_left_rows": ([], [0, 1, 2], 4, None, None, True),
        # the general branch: the check refuses the rowid table
        "general_duplicate_build_key": ([1, 2, 9, 3], [1, 1, 2], 4, None,
                                        None, False),
        # more matches than left rows: both forms keep the first nl
        "general_duplicates_overflow": ([1, 2, 1, 3], [1, 1, 2], 4, None,
                                        None, False),
        "general_build_key_past_domain": ([1, 2, 50, None], [1, 2, 50], 4,
                                          None, None, False),
        "general_left_valid_holes": ([1, 2, 1, 2], [2, 2, 1], 4,
                                     [t, f, f, t], None, False),
        "general_one_left_row": ([50], [1, 2, 50], 4, None, None, False),
        "general_no_left_rows": ([], [1, 1], 4, None, None, False),
    }[name]


_MASKED_CASES = [
    "dense_all_match", "dense_out_of_domain_and_null",
    "dense_partial_coverage", "dense_left_valid_holes",
    "dense_right_valid_holes", "dense_both_valid_holes",
    "dense_null_build_key", "dense_no_match", "dense_one_left_row",
    "dense_one_left_row_dead", "dense_no_left_rows",
    "general_duplicate_build_key", "general_duplicates_overflow",
    "general_build_key_past_domain",
    "general_left_valid_holes", "general_one_left_row",
    "general_no_left_rows"]


def _live_rows(batch, live):
    """The rows of ``batch`` under ``live`` as a sorted multiset."""
    cols = [batch[n].to_pylist() for n in batch.names]
    keep = np.asarray(live)
    return sorted((tuple(c[i] for c in cols) for i in range(len(keep))
                   if keep[i]), key=repr)


class TestJoinDenseMasked:
    """``join_dense_or_hash(compact=False)``: the same live rows as the
    compacting join, through both branches of the ``cond``; the dense one
    leaves the left rows where they are."""

    @staticmethod
    def _inputs(name):
        lk, rk, domain, lv, rv, dense = _masked_case(name)
        left = ColumnBatch({
            "k": Column.from_pylist(lk, T.INT32),
            "lv": Column.from_pylist(
                [None if i % 5 == 4 else i * 10 for i in range(len(lk))],
                T.INT64)})
        right = ColumnBatch({
            "k": Column.from_pylist(rk, T.INT32),
            "rv": Column.from_pylist(
                [None if i % 3 == 2 else i * 100 for i in range(len(rk))],
                T.INT64)})
        kw = {}
        if lv is not None:
            kw["left_valid"] = jnp.asarray(lv, jnp.bool_)
        if rv is not None:
            kw["right_valid"] = jnp.asarray(rv, jnp.bool_)
        return left, right, domain, kw, dense

    @pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
    @pytest.mark.parametrize("name", _MASKED_CASES)
    def test_same_live_rows_as_the_compacting_join(self, name, jit):
        import jax

        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left, right, domain, kw, dense = self._inputs(name)

        def run(compact):
            def f(left, right, kw):
                return join_dense_or_hash(left, right, "k", "k", domain,
                                          compact=compact, **kw)
            return (jax.jit(f) if jit else f)(left, right, kw)

        want, total = run(True)
        got, live = run(False)
        nl = left.num_rows
        assert live.dtype == jnp.bool_ and live.shape == (nl,)
        assert got.names == want.names and got.num_rows == nl
        assert want.num_rows == nl
        assert int(np.asarray(live).sum()) == min(int(total), nl)
        assert _live_rows(got, live) == _live_rows(
            want, np.arange(nl) < int(total))
        if dense:
            # a lookup: every left column is the caller's, bit for bit,
            # dead rows and their validity included
            for n in left.names:
                assert np.array_equal(np.asarray(got[n].data),
                                      np.asarray(left[n].data)), n
                assert np.array_equal(np.asarray(got[n].validity),
                                      np.asarray(left[n].validity)), n
        else:
            # hash_join's rows are compacted by construction
            assert np.array_equal(np.asarray(live),
                                  np.arange(nl) < int(total))

    def test_colliding_names_take_their_suffixes(self):
        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left = ColumnBatch({"k": ints([2, 9, 0]), "v": ints([1, 2, 3])})
        right = ColumnBatch({"k": ints([0, 1, 2]), "v": ints([7, 8, 9])})
        got, live = join_dense_or_hash(left, right, "k", "k", 4,
                                       suffixes=("_l", "_r"), compact=False)
        assert list(got.names) == ["k", "v_l", "v_r"]
        assert np.asarray(live).tolist() == [True, False, True]
        assert got["v_l"].to_pylist() == [1, 2, 3]
        assert got["v_r"].to_pylist() == [9, None, 7]

    def test_a_capacity_is_refused(self):
        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left, right, domain, kw, _ = self._inputs("dense_all_match")
        with pytest.raises(ValueError, match="capacity"):
            join_dense_or_hash(left, right, "k", "k", domain, capacity=4,
                               compact=False, **kw)

    @pytest.mark.parametrize("how", ["left", "semi"])
    def test_a_join_that_is_not_inner_hands_back_a_prefix(self, how):
        """... unless it keeps the left rows (semi, anti: a lookup on the
        dense path since PR 37, the scattered ``match`` its mask)."""
        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left, right, domain, kw, _ = self._inputs(
            "dense_out_of_domain_and_null")
        want, total = join_dense_or_hash(left, right, "k", "k", domain,
                                         how=how, **kw)
        got, live = join_dense_or_hash(left, right, "k", "k", domain,
                                       how=how, compact=False, **kw)
        if how == "semi":
            assert int(np.asarray(live).sum()) == int(total)
            assert not np.array_equal(
                np.asarray(live), np.arange(got.num_rows) < int(total))
            for c in left.names:   # the left rows where they were
                assert np.array_equal(np.asarray(got[c].data),
                                      np.asarray(left[c].data))
        else:
            assert np.array_equal(np.asarray(live),
                                  np.arange(got.num_rows) < int(total))
        assert _live_rows(got, live) == _live_rows(
            want, np.arange(want.num_rows) < int(total))

    def test_compacting_form_is_the_default_and_unchanged(self):
        """``compact=True`` is what every direct caller gets: matches in
        front in left-row order, a count, the rows past it null."""
        from spark_rapids_jni_tpu.relational import join_dense_or_hash

        left, right, domain, kw, _ = self._inputs(
            "dense_out_of_domain_and_null")
        a, na = join_dense_or_hash(left, right, "k", "k", domain, **kw)
        b, nb = join_dense_or_hash(left, right, "k", "k", domain,
                                   compact=True, **kw)
        assert int(na) == int(nb) == 5
        assert a.to_pydict() == b.to_pydict()
        assert a["k"].to_pylist() == [3, 0, 7, 3, 1] + [None] * 5
