"""``gather_batch`` moves the validity of its columns as packed ``uint32``
words (bit i: the i-th packable column) and, in a row gather (more than
4096 indices), their fixed-width data as words of one ``[rows, words]``
matrix: bit for bit the per-column gather (``gather_column``) and a numpy
reference of the validity, for every column representation, with and
without a row mask, with indices out of range (clipped), over 0 to 65
packable columns, at 4096 and 4097 indices; and the ``validity_gathers``
and ``row_gathers`` counters, of one gather and of the benchmark's plans
at 2^14 rows (``PERF.md`` section 3 records the counts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, plan
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import (Column, ColumnBatch,
                                                  Decimal128Column,
                                                  StringColumn)
from spark_rapids_jni_tpu.columnar.encoded import (BitPackedColumn,
                                                   encode_batch)
from spark_rapids_jni_tpu.relational import gather

N = 300
KINDS = ("i32", "i64", "f64", "dec", "str", "dict", "rle", "for", "bits")
# the matrix's own: int64 past 32 bits and below 0, a decimal in 64-bit
# storage, float32 with NaN and -0.0
WORD_KINDS = ("i64w", "d64", "f32")


def _validity(rng, n, nulls):
    if nulls == "none":
        return np.ones(n, bool)
    if nulls == "all":
        return np.zeros(n, bool)
    return rng.random(n) < 0.7


def _column(rng, kind, n, nulls):
    v = jnp.asarray(_validity(rng, n, nulls))
    if kind == "i32":
        return Column(jnp.asarray(rng.integers(-99, 99, n, dtype=np.int32)),
                      v, T.INT32)
    if kind == "f64":
        return Column(jnp.asarray(rng.normal(size=n)), v, T.FLOAT64)
    if kind == "f32":
        x = rng.normal(size=n).astype(np.float32)
        x[::5], x[1::7], x[2::11] = np.nan, -0.0, np.inf
        return Column(jnp.asarray(x), v, T.FLOAT32)
    if kind in ("i64w", "d64"):
        x = rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)
        x[::3] = rng.integers(-(1 << 33), 1 << 33, (n + 2) // 3)
        x[:4] = [-1, 1 << 32, -(1 << 63), (1 << 63) - 1][:n]
        return Column(jnp.asarray(x), v, T.INT64 if kind == "i64w"
                      else T.SparkType.decimal(18, 2))
    if kind == "dec":
        limbs = rng.integers(0, 1 << 62, (n, 2)).astype(np.uint64)
        return Decimal128Column(jnp.asarray(limbs), v,
                                T.SparkType.decimal(30, 2))
    if kind == "str":
        words = [f"{i % 97:02d}" * int(rng.integers(0, 4)) for i in range(n)]
        s = StringColumn.from_pylist(words, max_len=6)
        return StringColumn(s.chars, s.lengths * v, v, s.dtype)
    # int64: as it is, or encoded by the batch (runs for rle)
    vals = (np.repeat(rng.integers(0, 50, n // 10 + 1), 10)[:n]
            if kind == "rle" else rng.integers(0, 1000, n))
    return Column(jnp.asarray(vals.astype(np.int64)), v, T.INT64)


def _batch(rng, kinds, n=N, nulls="mixed"):
    b = ColumnBatch({f"{k}{i}": _column(rng, k, n, nulls)
                     for i, k in enumerate(kinds)})

    def named(kind):
        return [name for name, k in zip(b.names, kinds) if k == kind]

    return encode_batch(b, dictionary=named("dict"), rle=named("rle"),
                        bitpack=named("bits"),
                        frame_of_reference=named("for"))


def _indices(rng, n, m, out_of_range):
    idx = rng.integers(0, n, m)
    if out_of_range:
        idx[::7] = n + 5
        idx[3::11] = -3
    return jnp.asarray(idx.astype(np.int32))


def _assert_per_column(batch, idx, valid):
    got = gather.gather_batch(batch, idx, valid)
    assert got.names == batch.names
    clipped = np.clip(np.asarray(idx), 0, batch.num_rows - 1)
    for name, col in zip(batch.names, batch.columns):
        want = gather.gather_column(col, idx, valid)
        g_leaves, g_tree = jax.tree_util.tree_flatten(got[name])
        w_leaves, w_tree = jax.tree_util.tree_flatten(want)
        assert g_tree == w_tree, name
        for g, w in zip(g_leaves, w_leaves):
            assert g.dtype == w.dtype and g.shape == w.shape, name
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), name
        # and against numpy, not the per-column gather alone
        ref = np.asarray(col.validity)[clipped]
        if valid is not None:
            ref = ref & np.asarray(valid)
        assert np.array_equal(np.asarray(got[name].validity), ref), name
    return got


@pytest.mark.parametrize("nulls", ["mixed", "none", "all"])
@pytest.mark.parametrize("out_of_range", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_every_representation_in_one_batch(nulls, out_of_range, masked):
    rng = np.random.default_rng(11)
    b = _batch(rng, KINDS, nulls=nulls)
    assert isinstance(b["bits8"], BitPackedColumn)
    idx = _indices(rng, N, 257, out_of_range)
    valid = jnp.asarray(rng.random(257) < 0.8) if masked else None
    _assert_per_column(b, idx, valid)


@pytest.mark.parametrize("packable", [0, 1, 2, 32, 33])
@pytest.mark.parametrize("masked", [False, True])
def test_column_counts(packable, masked):
    """32 columns fill one word, the 33rd starts a second; a bit-packed
    column keeps its own validity beside them."""
    rng = np.random.default_rng(packable)
    kinds = [KINDS[i % 6] for i in range(packable)] + ["bits"]
    b = _batch(rng, kinds)
    idx = _indices(rng, N, 400, True)
    valid = jnp.asarray(rng.random(400) < 0.5) if masked else None
    _assert_per_column(b, idx, valid)


@pytest.mark.parametrize("nulls", ["mixed", "none", "all"])
def test_jitted_gather_is_the_eager_one(nulls):
    rng = np.random.default_rng(5)
    b = _batch(rng, ("i32", "i64", "dec", "str", "dict"), nulls=nulls)
    idx = _indices(rng, N, 200, True)
    valid = jnp.asarray(rng.random(200) < 0.6)
    eager = _assert_per_column(b, idx, valid)
    jitted = jax.jit(gather.gather_batch)(b, idx, valid)
    for g, w in zip(jax.tree_util.tree_leaves(jitted),
                    jax.tree_util.tree_leaves(eager)):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


def test_an_empty_batch_and_an_empty_index():
    assert gather.gather_batch(ColumnBatch({}), jnp.zeros((3,), jnp.int32)
                               ).num_columns == 0
    rng = np.random.default_rng(2)
    got = _assert_per_column(_batch(rng, ("i32", "str", "dec")),
                             jnp.zeros((0,), jnp.int32), None)
    assert got.num_rows == 0


# ---------------------------------------------------------------------------
# a row gather: the words as one matrix
# ---------------------------------------------------------------------------

ROWS = 4097   # one more than a group fetch: a row gather counts


@pytest.fixture
def small_sources(monkeypatch):
    """Matrices out of sources of ``ROWS`` rows, not the 2^19 and more
    that the chip's compiler lays out rows-minor."""
    monkeypatch.setattr(gather, "_MATRIX_FROM_ROWS", ROWS)


@pytest.mark.parametrize("rows,want", [((1 << 19) - 1, 3), (1 << 19, 1)])
def test_a_matrix_takes_a_source_of_2_19_rows_or_more(rows, want):
    rng = np.random.default_rng(8)
    b = _batch(rng, ("i32", "i64w"), n=rows)
    idx = _indices(rng, rows, ROWS, True)
    before = gather.row_gathers()
    _assert_per_column(b, idx, None)
    # two gathers a column for the per-column reference beside
    assert gather.row_gathers() - before == want + 4


@pytest.mark.usefixtures("small_sources")
@pytest.mark.parametrize("m", [4096, 4097])
@pytest.mark.parametrize("nulls", ["mixed", "none", "all"])
@pytest.mark.parametrize("masked", [False, True])
def test_a_row_gather_of_every_representation(m, nulls, masked):
    rng = np.random.default_rng(m)
    b = _batch(rng, KINDS + WORD_KINDS, n=ROWS, nulls=nulls)
    idx = _indices(rng, ROWS, m, True)
    valid = jnp.asarray(rng.random(m) < 0.8) if masked else None
    _assert_per_column(b, idx, valid)


@pytest.mark.usefixtures("small_sources")
@pytest.mark.parametrize("kind", ["i32", "i64", "i64w", "d64", "f32", "dec",
                                  "dict", "str", "f64", "bits"])
@pytest.mark.parametrize("beside", [(), ("str", "f64", "bits")])
@pytest.mark.parametrize("masked", [False, True])
def test_a_row_gather_of_each_representation(kind, beside, masked):
    """Each alone (one column, then two) and beside the columns that
    gather on their own."""
    rng = np.random.default_rng(len(kind) + len(beside))
    idx = _indices(rng, ROWS, ROWS, True)
    valid = jnp.asarray(rng.random(ROWS) < 0.7) if masked else None
    for kinds in ((kind,) + beside, (kind, kind) + beside):
        _assert_per_column(_batch(rng, kinds, n=ROWS), idx, valid)


@pytest.mark.usefixtures("small_sources")
@pytest.mark.parametrize("m", [4096, 4097])
def test_a_jitted_row_gather_is_the_eager_one(m):
    rng = np.random.default_rng(6)
    b = _batch(rng, ("i32", "i64w", "d64", "f32", "dec", "str", "dict",
                     "f64", "bits"), n=ROWS)
    idx = _indices(rng, ROWS, m, True)
    valid = jnp.asarray(rng.random(m) < 0.6)
    eager = _assert_per_column(b, idx, valid)
    jitted = jax.jit(gather.gather_batch)(b, idx, valid)
    for g, w in zip(jax.tree_util.tree_leaves(jitted),
                    jax.tree_util.tree_leaves(eager)):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


@pytest.mark.usefixtures("small_sources")
@pytest.mark.parametrize("kinds,want,per_buffer", [
    (("i32",), 1, 2),                 # a word and the validity word
    (("i64",), 1, 2),                 # two halves and the validity word
    (("dec",), 1, 2),                 # four limb words and the validity
    (("f64",), 2, 2),                 # one word: the parent's gathers
    (("f64", "f64"), 3, 3),           # one word: the parent's gathers
    (("i32", "f64"), 2, 3),           # a matrix, the double on its own
    (("str",), 2, 3),                 # lengths and validity; the chars
    (("bits", "i32"), 3, 4),          # the bit-packed column on its own
    (("i32", "dict", "d64", "f32"), 1, 5),   # six words
    (("i64",) * 4, 2, 5),             # nine words: a matrix of eight, one
    (("dec",) * 2, 2, 3),             # nine words
    # 33 data and two validity words; with no data word the two
    # validity words still make a matrix
    (("i32",) * 33, 5, 34),
])
def test_gathers_made_by_one_row_gather(kinds, want, per_buffer):
    rng = np.random.default_rng(7)
    b = _batch(rng, kinds, n=ROWS)
    idx = _indices(rng, ROWS, ROWS, False)
    before = gather.row_gathers()
    gather.gather_batch(b, idx)
    assert gather.row_gathers() - before == want
    # a fetch of at most 4096 indices is not a row gather
    before = gather.row_gathers()
    gather.gather_batch(b, _indices(rng, ROWS, 4096, False))
    assert gather.row_gathers() == before
    # with no data buffer a matrix may carry: one gather a buffer
    words = gather._WORDS_PER_ROW
    try:
        gather._WORDS_PER_ROW = {}
        before = gather.row_gathers()
        gather.gather_batch(b, idx)
        assert gather.row_gathers() - before == per_buffer
    finally:
        gather._WORDS_PER_ROW = words


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("packable,bitpacked,want", [
    (0, 0, 0), (1, 0, 1), (2, 0, 1), (3, 0, 1), (32, 0, 1), (33, 0, 2),
    (64, 0, 2), (65, 0, 3),
    # a bit-packed column moves its own validity beside the words
    (0, 1, 1), (1, 1, 2), (2, 1, 2), (33, 2, 4),
])
def test_validity_buffers_moved_by_one_gather(packable, bitpacked, want):
    rng = np.random.default_rng(3)
    b = _batch(rng, ["i32"] * packable + ["bits"] * bitpacked, n=ROWS)
    before = gather.validity_gathers()
    gather.gather_batch(b, _indices(rng, ROWS, ROWS, False))
    assert gather.validity_gathers() - before == want
    # a fetch of at most 4096 indices is not a row gather
    before = gather.validity_gathers()
    gather.gather_batch(b, _indices(rng, ROWS, 4096, False))
    assert gather.validity_gathers() == before


def test_the_count_only_rises():
    rng = np.random.default_rng(4)
    b = _batch(rng, ("i32", "i64", "str"), n=ROWS)
    seen = [gather.validity_gathers()]
    for m in (ROWS, 10, ROWS + 1):
        gather.gather_batch(b, _indices(rng, ROWS, m, False))
        gather.gather_column(b["str2"], _indices(rng, ROWS, m, False))
        seen.append(gather.validity_gathers())
    assert seen == sorted(seen) and seen[-1] - seen[0] == 4


# the benchmark's plans at 2^14 rows, traced as the chip runs them (``auto``
# answered by the sort engines): the validity buffers and the count the
# per-column gathers give (packing off); the gathers, one a buffer (no
# source of 2^19 rows, so no matrix), and with matrices out of sources of
# more than 4096 rows; the sort engine's reads at the group ends among
# them: PERF.md section 3
@pytest.mark.parametrize("config_name,packed,per_column,per_buffer,gathers", [
    ("q95-join-agg", 10, 29, 42, 16),
    ("tpch-q3", 6, 14, 27, 12),
    ("tpch-q18", 8, 18, 39, 18),
    ("tpch-q13", 8, 10, 20, 11),
    ("q6-scan-agg", 0, 0, 0, 0),
])
def test_the_plans_count(monkeypatch, config_name, packed, per_column,
                         per_buffer, gathers):
    from benchmark import lib

    cfg, mod = lib.load_config(config_name, 14)
    rows = mod.rows_per_query(cfg)
    key = jax.random.PRNGKey(0)
    shared = getattr(mod, "make_shared", None)
    inputs = {**mod.make_partition(cfg, key, rows),
              **(shared(cfg, key, rows) if shared else {})}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def traced():
        plan.reset_plan_cache()
        cp = plan.compile_plan(mod.plan(cfg), inputs)
        try:
            prebuilts = tuple(tuple(h.get()) for h in cp.build_handles)
            cp.fn.trace({n: inputs[n] for n in cp.input_names}, prebuilts)
        finally:
            cp.close()
        m = plan.plan_cache_metrics()
        return m["validity_gathers"], m["row_gathers"]

    for k, v in cfg["knobs"].items():
        config.set(k, v)
    try:
        assert traced() == (packed, per_buffer)
        monkeypatch.setattr(gather, "_MATRIX_FROM_ROWS", ROWS)
        assert traced() == (packed, gathers)
        monkeypatch.setattr(gather, "_WORDS_PER_ROW", {})
        assert traced() == (packed, per_buffer)
        monkeypatch.setattr(gather, "_PACKED", ())
        assert traced()[0] == per_column
    finally:
        config.reset()
        plan.reset_plan_cache()
