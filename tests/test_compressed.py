"""Compressed execution parity suite (columnar/encoded.py packed
encodings, shuffle/service.py compressed rounds, mem/codec.py spill
frames).

The correctness contract is BIT-PARITY with the uncompressed path at
every seam:

* ``pack_bits``/``unpack_bits`` round-trip every width 1..32 including
  full-range u32, and the device layout is interchangeable with the
  host codec's ``np_pack_bits`` (same little-endian lane format);
* ``encode_bitpacked``/``encode_for`` decode bit-exactly over valid
  rows (negative ints, nulls, clustered wide-range keys), fall back to
  the plain column when the range needs more than 32 residual bits,
  and ``gather_bitpacked`` keeps gather outputs packed;
* joins and group-bys fed packed key columns match the decoded plan on
  both engines (keys.py lowers residual+reference in-trace);
* the ShuffleService exchange under ``shuffle_compress=pack`` delivers
  the same rows as the raw wire while moving fewer bytes (and ``auto``
  packs dictionary codes/bools but leaves the plain-int wire exactly
  as the legacy program), for both ``exchange`` and
  ``exchange_stream``;
* spill frames (``encode_block``/``decode_block``) round-trip
  bit-exactly, the stored-bytes CRC detects disk damage BEFORE the
  decoder runs (no damage laundering), and the three-tier spill walk
  under ``spill_codec=pack`` shrinks the disk bytes while reading back
  exactly.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.columnar import types as T
from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch
from spark_rapids_jni_tpu.columnar.encoded import (
    BitPackedColumn,
    FrameOfReferenceColumn,
    choose_pack_width,
    encode_bitpacked,
    encode_column,
    encode_for,
    gather_bitpacked,
    is_encoded,
    materialize_batch,
    packed_decode_count,
    packed_filter_mask,
    reset_packed_decode_count,
    pack_bits,
    pack_bits_rows,
    unpack_bits,
    unpack_bits_rows,
)
from spark_rapids_jni_tpu.mem import SpillableHandle
from spark_rapids_jni_tpu.mem import codec as codec_mod
from spark_rapids_jni_tpu.mem import spill as spill_mod
from spark_rapids_jni_tpu.relational import AggSpec, group_by, hash_join


@pytest.fixture(autouse=True)
def _reset():
    yield
    config.reset()
    faultinj.configure({})


def col(vals, t, valid=None):
    vals = np.asarray(vals)
    v = np.ones(len(vals), bool) if valid is None else np.asarray(valid, bool)
    return Column(jnp.asarray(vals), jnp.asarray(v), t)


def col_i64(vals, valid=None):
    return col(np.asarray(vals, np.int64), T.INT64, valid)


def col_i32(vals, valid=None):
    return col(np.asarray(vals, np.int32), T.INT32, valid)


# ---------------------------------------------------------------------------
# lane-level pack/unpack
# ---------------------------------------------------------------------------

class TestPackBits:
    @pytest.mark.parametrize("width", list(range(1, 33)))
    def test_round_trip_every_width(self, width):
        rng = np.random.default_rng(width)
        # 97 rows: the last lane is partial and words straddle lane
        # boundaries at every non-power-of-two width
        n = 97
        hi = (1 << width) - 1
        words = rng.integers(0, hi + 1 if width < 32 else 1 << 32, n,
                             dtype=np.uint64).astype(np.uint32)
        lanes = pack_bits(jnp.asarray(words), width)
        assert lanes.dtype == jnp.uint32
        assert lanes.shape[0] == max(1, (n * width + 31) // 32)
        got = np.asarray(unpack_bits(lanes, width, n))
        assert np.array_equal(got, words)

    def test_full_range_u32_values(self):
        words = np.array([0, 1, 0xFFFFFFFF, 0x80000000, 0x7FFFFFFF],
                         np.uint32)
        lanes = pack_bits(jnp.asarray(words), 32)
        assert np.array_equal(np.asarray(unpack_bits(lanes, 32, 5)), words)

    @pytest.mark.parametrize("width", (1, 7, 12, 20, 31))
    def test_host_device_layouts_interchange(self, width):
        """The device packer emits the exact lane format of the host
        codec's np_pack_bits — streams cross the boundary either way."""
        rng = np.random.default_rng(width + 100)
        n = 130
        words = rng.integers(0, 1 << width, n, dtype=np.uint64).astype(
            np.uint32)
        dev = np.asarray(pack_bits(jnp.asarray(words), width))
        host = codec_mod.np_pack_bits(words, width)
        assert np.array_equal(dev[:host.shape[0]], host)
        # device-packed -> host-unpacked and vice versa
        assert np.array_equal(codec_mod.np_unpack_bits(dev, width, n), words)
        got = np.asarray(unpack_bits(jnp.asarray(host), width, n))
        assert np.array_equal(got, words)

    def test_empty_and_bad_width(self):
        assert np.asarray(unpack_bits(
            pack_bits(jnp.zeros((0,), jnp.uint32), 5), 5, 0)).shape == (0,)
        with pytest.raises(ValueError, match="width"):
            pack_bits(jnp.zeros((4,), jnp.uint32), 0)
        with pytest.raises(ValueError, match="width"):
            unpack_bits(jnp.zeros((4,), jnp.uint32), 33, 4)

    def test_rows_variant_packs_per_partition(self):
        rng = np.random.default_rng(9)
        words = rng.integers(0, 1 << 11, (4, 50), dtype=np.uint64).astype(
            np.uint32)
        lanes = pack_bits_rows(jnp.asarray(words), 11)
        assert lanes.shape[0] == 4
        got = np.asarray(unpack_bits_rows(lanes, 11, 50))
        assert np.array_equal(got, words)
        # each row independently matches the 1-D packer
        for p in range(4):
            one = np.asarray(pack_bits(jnp.asarray(words[p]), 11))
            assert np.array_equal(np.asarray(lanes[p]), one)


# ---------------------------------------------------------------------------
# packed column encodings
# ---------------------------------------------------------------------------

class TestPackedEncodings:
    def test_bitpacked_negatives_and_nulls(self):
        rng = np.random.default_rng(3)
        vals = rng.integers(-500, 40, 257)
        valid = rng.random(257) > 0.2
        c = col_i64(vals, valid)
        enc = encode_bitpacked(c)
        assert isinstance(enc, BitPackedColumn) and is_encoded(enc)
        assert enc.reference == int(vals[valid].min())
        assert enc.width == choose_pack_width(
            vals[valid].min(), vals[valid].max()) or enc.width <= 32
        dec = enc.decode()
        gv = np.asarray(dec.validity)
        assert np.array_equal(gv, valid)
        assert np.array_equal(np.asarray(dec.data)[valid], vals[valid])
        assert enc.to_pylist() == c.to_pylist()

    def test_for_clustered_wide_range_packs_narrow(self):
        """Per-block minima absorb cluster drift: a key family whose
        GLOBAL range needs 31 bits packs in a few residual bits."""
        rng = np.random.default_rng(5)
        base = np.repeat(np.arange(8, dtype=np.int64) * (1 << 28), 128)
        vals = base + rng.integers(0, 1 << 6, base.shape[0])
        c = col_i64(vals)
        enc = encode_for(c, block=128)
        assert isinstance(enc, FrameOfReferenceColumn)
        assert enc.num_blocks == 8
        assert enc.width <= 6 + 1
        # the plain bitpack of the same column needs the global range
        flat = encode_bitpacked(c)
        assert flat.width > enc.width
        assert np.array_equal(np.asarray(enc.values64()), vals)
        assert enc.to_pylist() == c.to_pylist()

    def test_wide_range_falls_back_to_plain(self):
        c = col_i64([0, 1 << 40])
        assert encode_bitpacked(c) is c
        f = encode_for(col_i64([0, 1 << 40]), block=1024)
        assert isinstance(f, Column)  # both rows in one block: fallback
        assert choose_pack_width(0, 1 << 40) is None

    def test_gather_stays_packed_and_matches_take(self):
        rng = np.random.default_rng(7)
        vals = rng.integers(-10, 90, 200)
        c = col_i64(vals, rng.random(200) > 0.1)
        enc = encode_bitpacked(c)
        idx = jnp.asarray(rng.integers(0, 200, 64))
        out = gather_bitpacked(enc, idx)
        assert isinstance(out, BitPackedColumn)
        assert out.width == enc.width and out.reference == enc.reference
        want = np.asarray(c.data)[np.asarray(idx)]
        wantv = np.asarray(c.validity)[np.asarray(idx)]
        dec = out.decode()
        assert np.array_equal(np.asarray(dec.validity), wantv)
        assert np.array_equal(np.asarray(dec.data)[wantv], want[wantv])

    def test_choose_pack_width_buckets(self):
        assert choose_pack_width(0, 1) == 1
        assert choose_pack_width(0, 3) == 2
        assert choose_pack_width(-50, 50) == 8      # range 100 -> 7 -> 8
        assert choose_pack_width(0, 1000) == 12     # 10 bits -> 12 bucket
        assert choose_pack_width(0, (1 << 32) - 1) == 32
        assert choose_pack_width(0, 1 << 32) is None
        assert choose_pack_width(5, 4) is None      # inverted range


# ---------------------------------------------------------------------------
# relational operators on packed keys (late materialization in keys.py)
# ---------------------------------------------------------------------------

def _pl(batch, count):
    n = int(count)
    return {c: batch[c].to_pylist()[:n] for c in batch.names}


class TestRelationalPackedKeys:
    @pytest.mark.parametrize("how", ("inner", "left", "full", "anti"))
    def test_join_parity_bitpacked_keys(self, how):
        rng = np.random.default_rng(11)
        lk, rk = rng.integers(0, 40, 150), rng.integers(20, 60, 50)
        left = ColumnBatch({"k": col_i64(lk),
                            "lv": col_i32(rng.integers(0, 99, 150))})
        right = ColumnBatch({"k": col_i64(rk),
                             "rv": col_i32(rng.integers(0, 99, 50))})
        eleft = ColumnBatch({"k": encode_bitpacked(left["k"]),
                             "lv": left["lv"]})
        eright = ColumnBatch({"k": encode_for(right["k"], block=16),
                              "rv": right["rv"]})
        rd, cd = hash_join(left, right, ["k"], ["k"], how, capacity=2048)
        re_, ce = hash_join(eleft, eright, ["k"], ["k"], how, capacity=2048)
        assert _pl(materialize_batch(rd), cd) == _pl(
            materialize_batch(re_), ce)

    @pytest.mark.parametrize("engine", ("sort", "scatter"))
    def test_groupby_parity_packed_keys(self, engine):
        rng = np.random.default_rng(13)
        n = 300
        batch = ColumnBatch({
            "k": col_i64(rng.integers(-8, 8, n), rng.random(n) > 0.1),
            "v": col_i32(rng.integers(-100, 100, n))})
        aggs = [AggSpec("count", None, "c"), AggSpec("sum", "v", "s"),
                AggSpec("min", "v", "mn"), AggSpec("max", "v", "mx")]
        enc = ColumnBatch({"k": encode_bitpacked(batch["k"]),
                           "v": batch["v"]})
        rd, nd = group_by(batch, ["k"], aggs, engine=engine)
        re_, ne = group_by(enc, ["k"], aggs, engine=engine)
        assert _pl(materialize_batch(rd), nd) == _pl(
            materialize_batch(re_), ne)


# ---------------------------------------------------------------------------
# compressed shuffle rounds (8 virtual devices)
# ---------------------------------------------------------------------------

P8 = 8


def _digest(res):
    b = materialize_batch(res.batch)
    occ = np.asarray(jax.device_get(res.occupancy))
    return [np.asarray(jax.device_get(b[n].data))[occ] for n in b.names]


def _assert_same(a_cols, b_cols):
    for a, b in zip(a_cols, b_cols):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


class TestShuffleCompress:
    def _mixed_batch(self, mesh, n, seed=0):
        from spark_rapids_jni_tpu.parallel import shard_batch
        rng = np.random.default_rng(seed)
        return shard_batch(ColumnBatch({
            "k": col_i64(rng.integers(0, 1000, n)),
            "q": col_i32(rng.integers(-50, 50, n)),
            "flag": col(rng.integers(0, 2, n).astype(bool), T.BOOLEAN),
            "price": col(rng.standard_normal(n).astype(np.float32),
                         T.FLOAT32),
        }), mesh)

    def test_exchange_pack_bit_parity_fewer_bytes(self, eight_devices):
        from spark_rapids_jni_tpu.parallel import data_mesh
        from spark_rapids_jni_tpu.shuffle import (
            ShuffleRegistry, ShuffleService)
        mesh = data_mesh(P8)
        n = P8 * 256
        batch = self._mixed_batch(mesh, n)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        config.set("shuffle_compress", "off")
        r_off = svc.exchange(batch, key_names=("k",))
        config.set("shuffle_compress", "pack")
        r_pack = svc.exchange(batch, key_names=("k",))
        _assert_same(_digest(r_off), _digest(r_pack))
        assert r_pack.rows_moved == r_off.rows_moved == n
        # 12-bit keys + 8-bit quantities + 1-bit flags beat the 1.5x bar
        assert r_pack.bytes_moved * 1.5 <= r_off.bytes_moved
        assert r_pack.compressed_bytes_saved > 0
        assert r_off.compressed_bytes_saved == 0
        snap = svc.registry.metrics.snapshot()
        assert snap["compressed_bytes_saved"] >= \
            r_pack.compressed_bytes_saved

    def test_auto_packs_dict_codes_and_bools(self, eight_devices):
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
        from spark_rapids_jni_tpu.shuffle import (
            ShuffleRegistry, ShuffleService)
        mesh = data_mesh(P8)
        n = P8 * 256
        rng = np.random.default_rng(1)
        db = shard_batch(ColumnBatch({
            "k": col_i64(rng.integers(0, 500, n)),
            "s": encode_column(col_i64(rng.integers(0, 4, n))),
            "flag": col(rng.integers(0, 2, n).astype(bool), T.BOOLEAN),
        }), mesh)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        config.set("shuffle_compress", "off")
        a_off = svc.exchange(db, key_names=("k",))
        config.set("shuffle_compress", "auto")
        a_auto = svc.exchange(db, key_names=("k",))
        _assert_same(_digest(a_off), _digest(a_auto))
        assert a_auto.compressed_bytes_saved > 0
        assert a_auto.bytes_moved < a_off.bytes_moved

    def test_plain_auto_keeps_legacy_wire(self, eight_devices):
        """auto on a plain fixed-width batch is byte-for-byte the legacy
        program: no pack plan, no saved bytes, same wire size."""
        from spark_rapids_jni_tpu.parallel import data_mesh
        from spark_rapids_jni_tpu.shuffle import (
            ShuffleRegistry, ShuffleService)
        mesh = data_mesh(P8)
        n = P8 * 128
        batch = self._mixed_batch(mesh, n, seed=2)
        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        config.set("shuffle_compress", "off")
        r_off = svc.exchange(batch, key_names=("k",))
        config.set("shuffle_compress", "auto")
        r_auto = svc.exchange(batch, key_names=("k",))
        assert r_auto.compressed_bytes_saved == 0
        assert r_auto.bytes_moved == r_off.bytes_moved
        _assert_same(_digest(r_off), _digest(r_auto))

    def test_stream_pack_parity(self, eight_devices):
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch
        from spark_rapids_jni_tpu.shuffle import (
            ShuffleRegistry, ShuffleService)
        mesh = data_mesh(P8)
        n = P8 * 256
        rng = np.random.default_rng(3)
        k = rng.integers(0, 700, n)
        q = rng.integers(-30, 30, n)
        flag = rng.integers(0, 2, n).astype(bool)

        def morsels():
            for i in range(4):
                lo, hi = i * n // 4, (i + 1) * n // 4
                yield shard_batch(ColumnBatch({
                    "k": col_i64(k[lo:hi]),
                    "q": col_i32(q[lo:hi]),
                    "flag": col(flag[lo:hi], T.BOOLEAN),
                }), mesh)

        svc = ShuffleService(mesh, registry=ShuffleRegistry())
        config.set("shuffle_compress", "off")
        s_off = svc.exchange_stream(morsels(), key_names=("k",))
        config.set("shuffle_compress", "pack")
        s_pack = svc.exchange_stream(morsels(), key_names=("k",))
        _assert_same(_digest(s_off), _digest(s_pack))
        assert s_pack.rows_moved == n
        assert s_pack.compressed_bytes_saved > 0
        assert s_pack.bytes_moved < s_off.bytes_moved


# ---------------------------------------------------------------------------
# spill codec frames and the codec'd tier walk
# ---------------------------------------------------------------------------

@pytest.fixture
def framework(tmp_path):
    fw = spill_mod.install(spill_dir=str(tmp_path / "spill"))
    yield fw
    spill_mod.shutdown()


class TestSpillCodecFrames:
    def test_pack_frame_round_trip(self):
        rng = np.random.default_rng(17)
        arr = rng.integers(0, 4096, 10000).astype(np.int64)
        payload = codec_mod.encode_block(arr, "pack")
        assert codec_mod.codec_name(payload) == "pack"
        assert payload.nbytes < arr.nbytes
        got = codec_mod.decode_block(payload)
        assert got.dtype == arr.dtype and np.array_equal(got, arr)

    def test_block_frame_round_trip(self):
        arr = np.repeat(np.arange(8, dtype=np.int64), 512)
        payload = codec_mod.encode_block(arr, "block")
        assert codec_mod.codec_name(payload) == "block"
        assert payload.nbytes < arr.nbytes
        got = codec_mod.decode_block(payload)
        assert np.array_equal(got, arr)

    def test_incompressible_stays_lossless(self):
        """Full-entropy floats gain nothing — the frame still decodes
        bit-exactly (raw body fallback inside the codec)."""
        rng = np.random.default_rng(19)
        arr = rng.standard_normal(4096)
        for codec in ("raw", "pack", "block"):
            got = codec_mod.decode_block(codec_mod.encode_block(arr, codec))
            assert np.array_equal(got.view(np.uint8), arr.view(np.uint8))

    def test_garbage_rejected_loudly(self):
        junk = np.frombuffer(b"not a SRCK frame at all" * 4, np.uint8)
        with pytest.raises(codec_mod.CodecError):
            codec_mod.decode_block(junk.copy())

    def test_invalid_knob_rejected(self, framework):
        config.set("spill_codec", "bogus")
        h = SpillableHandle({"x": jnp.arange(64, dtype=jnp.int32)},
                            name="bad")
        h.spill()
        with pytest.raises(ValueError, match="spill_codec"):
            h.spill_host()
        h.close()


class TestSpillCodecTierWalk:
    @pytest.mark.parametrize("codec", ("pack", "block"))
    def test_three_tier_round_trip_shrinks_disk(self, framework, codec):
        config.set("spill_codec", codec)
        rng = np.random.default_rng(23)
        tree = {"k": jnp.asarray(
                    np.repeat(rng.integers(0, 16, 512), 16).astype(np.int64)),
                "v": jnp.asarray(rng.integers(0, 200, 4096).astype(np.int64))}
        want = {n: np.asarray(a) for n, a in tree.items()}
        h = SpillableHandle(tree, name=f"codec-{codec}")
        h.spill()
        h.spill_host()
        assert h.tier == "disk"
        got = h.get()
        for n, a in want.items():
            assert np.array_equal(np.asarray(got[n]), a)
        m = framework.metrics.snapshot()
        assert m["compressed_bytes"] > 0
        assert m["precompress_bytes"] > m["compressed_bytes"]
        assert m["codec_ratio"] > 1.0
        h.close()

    def test_disk_damage_detected_before_decode(self, framework):
        """The STORED-bytes CRC fires before decode_block ever runs: a
        flipped frame raises SpillCorruptionError, never a laundered
        decode or a CodecError."""
        config.set("spill_codec", "pack")
        faultinj.configure({"faults": [
            {"match": "spill_corrupt_file", "fault": "spill_corrupt",
             "count": 1}]})
        h = SpillableHandle(
            {"x": jnp.arange(4096, dtype=jnp.int64)}, name="dmg")
        h.spill()
        h.spill_host()
        with pytest.raises(faultinj.SpillCorruptionError):
            h.get()
        h.close()

    def test_damage_recovers_via_lineage(self, framework):
        config.set("spill_codec", "pack")
        make = lambda: {"x": jnp.asarray(
            np.random.default_rng(29).integers(0, 50, 4096))}
        want = np.asarray(make()["x"])
        faultinj.configure({"faults": [
            {"match": "spill_corrupt_file", "fault": "spill_corrupt",
             "count": 1}]})
        h = SpillableHandle(make(), name="heal", recompute=make)
        h.spill()
        h.spill_host()
        got = h.get()  # detect -> discard -> rebuild from lineage
        assert np.array_equal(np.asarray(got["x"]), want)
        h.close()

    def test_codec_off_keeps_raw_disk_bytes(self, framework):
        config.set("spill_codec", "off")
        h = SpillableHandle({"x": jnp.arange(1024, dtype=jnp.int64)},
                            name="raw")
        h.spill()
        h.spill_host()
        got = h.get()
        assert np.array_equal(np.asarray(got["x"]), np.arange(1024))
        m = framework.metrics.snapshot()
        assert m["compressed_bytes"] == m["precompress_bytes"]
        assert m["codec_ratio"] == 1.0
        h.close()


# ---------------------------------------------------------------------------
# packed predicates: comparisons in the compressed domain (zero decodes)
# ---------------------------------------------------------------------------

_CMP_OPS = ("<", "<=", "==", "!=", ">=", ">")


def _np_cmp(op, a, v):
    import operator as _o

    return {"<": _o.lt, "<=": _o.le, "==": _o.eq, "!=": _o.ne,
            ">=": _o.ge, ">": _o.gt}[op](a, v)


class TestPackedPredicates:
    """``packed_filter_mask`` vs decode-then-compare, bit for bit, with
    the decode counter proving the fast path NEVER materializes."""

    def _sweep(self, enc, literals):
        # the expected side is allowed to decode — once, up front
        dec = np.asarray(enc.decode().data)
        reset_packed_decode_count()
        for op in _CMP_OPS:
            for v in literals:
                got = np.asarray(packed_filter_mask(enc, op, int(v)))
                assert got.shape == dec.shape, (op, v)
                assert np.array_equal(got, _np_cmp(op, dec, int(v))), (op, v)
        assert packed_decode_count() == 0  # ZERO decodes on the fast path

    @pytest.mark.parametrize(
        "width", [1, 2, 3, 5, 8, 13, 16, 21, 27, 31, 32])
    def test_bitpacked_parity_all_widths(self, width):
        rng = np.random.default_rng(width)
        n = 257  # not lane-aligned
        hi = (1 << width) - 1
        vals = rng.integers(0, hi + 1, n).astype(np.int64) - 7
        vals[0], vals[1] = -7, hi - 7  # pin the range -> exact width
        enc = encode_bitpacked(col_i64(vals))
        assert isinstance(enc, BitPackedColumn) and enc.width == width
        # domain edges, out-of-domain on both sides, and a mid literal
        self._sweep(enc, sorted({-8, -7, 0, int(vals[n // 2]),
                                 hi - 7, hi - 6}))

    @pytest.mark.parametrize("block", [64, 100])
    def test_for_parity_block_boundary_literals(self, block):
        rng = np.random.default_rng(block)
        n = 1000  # n % 64 != 0: the tail block is partial
        nb = -(-n // block)
        base = np.repeat(np.arange(nb, dtype=np.int64) * 10_000, block)[:n]
        vals = base + rng.integers(0, 500, n)
        enc = encode_for(col_i64(vals), block=block)
        assert isinstance(enc, FrameOfReferenceColumn)
        lits = {int(vals.min()) - 1, int(vals.max()) + 1}
        for b in (0, 1, nb - 1):  # first, second, and partial-tail block
            seg = vals[b * block:(b + 1) * block]
            lits.update((int(seg.min()), int(seg.max())))
        self._sweep(enc, sorted(lits))

    def test_all_blocks_excluded_and_none_excluded(self):
        # literals past either end: every mask folds to a constant
        vals = np.arange(512, dtype=np.int64) + 100
        for enc in (encode_bitpacked(col_i64(vals)),
                    encode_for(col_i64(vals), block=64)):
            reset_packed_decode_count()
            assert not np.asarray(
                packed_filter_mask(enc, "<", 100)).any()
            assert np.asarray(
                packed_filter_mask(enc, "<=", 10_000)).all()
            assert not np.asarray(
                packed_filter_mask(enc, ">", 10_000)).any()
            assert np.asarray(
                packed_filter_mask(enc, ">=", -5)).all()
            assert packed_decode_count() == 0

    def test_for_int64_extreme_frames_no_wrap(self):
        # value - ref computed in int64 lanes wraps when the literal and
        # a block reference sit at opposite ends of the int64 domain; a
        # wrapped block must still classify as out-of-domain on the
        # literal's side, bit-identical to decode-then-compare (before
        # the sign-check fix, '<' over refs near -2**62 with a literal
        # near +2**62 returned all-False where the truth is all-True)
        big = 1 << 62
        vals = np.concatenate([
            -big + np.arange(128, dtype=np.int64),
            big + np.arange(128, dtype=np.int64)])
        enc = encode_for(col_i64(vals), block=64)
        assert isinstance(enc, FrameOfReferenceColumn)
        self._sweep(enc, [-big - 1, -big + 5, 0, big + 5, big + 200])

    def test_null_rows_compare_on_decoded_values(self):
        # decode() is validity-independent (invalid rows decode to the
        # reference) — the packed mask must match that, NOT re-AND
        # validity
        vals = np.arange(64, dtype=np.int64) + 5
        valid = np.ones(64, bool)
        valid[::7] = False
        for enc in (encode_bitpacked(col_i64(vals, valid)),
                    encode_for(col_i64(vals, valid), block=16)):
            self._sweep(enc, [4, 20, 69])

    def test_knob_off_decodes_and_matches(self):
        vals = np.arange(100, dtype=np.int64)
        enc = encode_bitpacked(col_i64(vals))
        config.set("packed_predicates", False)
        reset_packed_decode_count()
        got = np.asarray(packed_filter_mask(enc, "<", 50))
        assert packed_decode_count() == 1  # the exact-parity fallback
        assert np.array_equal(got, vals < 50)

    def test_non_int_literal_falls_back(self):
        vals = np.arange(100, dtype=np.int64)
        enc = encode_for(col_i64(vals), block=32)
        reset_packed_decode_count()
        got = np.asarray(packed_filter_mask(enc, "<", 49.5))
        assert packed_decode_count() == 1
        assert np.array_equal(got, vals < 49.5)

    def test_compile_routes_packed_filters(self):
        # the IR Filter lowering must take the packed path, no decode
        from spark_rapids_jni_tpu.plan.compile import _filter_mask

        vals = np.arange(2048, dtype=np.int64) * 3
        for enc in (encode_bitpacked(col_i64(vals)),
                    encode_for(col_i64(vals), block=256)):
            reset_packed_decode_count()
            got = np.asarray(_filter_mask(enc, ">=", 3000))
            assert packed_decode_count() == 0
            assert np.array_equal(got, vals >= 3000)

    def test_plan_filter_parity_on_packed_input(self):
        # a full q6-shaped plan over a bit-packed filter column equals
        # the same plan over the plain column
        from spark_rapids_jni_tpu import plan
        from spark_rapids_jni_tpu.plan.ir import Agg, Aggregate, Filter, Scan
        from tests.test_plan import assert_bit_identical

        rng = np.random.default_rng(5)
        n = 2048
        price = rng.integers(0, 100, n).astype(np.int64)
        batch = {
            "k": col(rng.integers(0, 10, n).astype(np.int32), T.INT32),
            "v": col_i64(rng.integers(0, 1000, n)),
            "price": col_i64(price),
        }
        p = Aggregate(Filter(Scan("batch"), "price", "<", 50),
                      keys=("k",),
                      aggs=(Agg("sum", "v", "sum_v"),
                            Agg("count", None, "cnt")),
                      domain=10, onehot=True)
        want = plan.execute(p, {"batch": ColumnBatch(dict(batch))})
        packed = dict(batch)
        packed["price"] = encode_bitpacked(batch["price"])
        got = plan.execute(p, {"batch": ColumnBatch(packed)})
        assert_bit_identical(got, want)


# ---------------------------------------------------------------------------
# zone maps: the sidecar and morsel-level block skipping
# ---------------------------------------------------------------------------

class TestZoneMaps:
    def test_sidecar_stats_exact_with_partial_tail(self):
        # n % block != 0: the tail block's stats come from its REAL rows
        # only — padding lanes must never widen (or narrow) the range
        rng = np.random.default_rng(11)
        n, block = 1000, 128
        vals = rng.integers(-500, 500, n).astype(np.int64)
        enc = encode_for(col_i64(vals), block=block)
        zm = enc.zone
        assert zm is not None and zm.rows == n and zm.block == block
        assert zm.num_blocks == -(-n // block)
        dec = np.asarray(enc.decode().data)
        for b in range(zm.num_blocks):
            seg = dec[b * block:(b + 1) * block]
            assert zm.mins[b] == seg.min(), b
            assert zm.maxs[b] == seg.max(), b
        zm.verify()  # and the stamp matches what build() wrote

    def test_bitpacked_sidecar_tail_and_skip_decision(self):
        n = 1100  # 1024-row zone blocks -> 76-row partial tail
        vals = np.arange(n, dtype=np.int64)
        enc = encode_bitpacked(col_i64(vals))
        zm = enc.zone
        assert zm.num_blocks == 2 and zm.rows == n
        assert zm.maxs[1] == n - 1  # real tail max, not padding
        # a literal beyond the tail's real max excludes the tail block
        assert not zm.block_may_match(">", n - 1)[1]
        assert zm.block_may_match(">=", n - 1)[1]

    def test_corrupt_sidecar_fails_loud(self):
        enc = encode_for(col_i64(np.arange(256, dtype=np.int64)), block=64)
        lying = dataclasses.replace(enc.zone,
                                    maxs=enc.zone.maxs ^ np.int64(1))
        with pytest.raises(faultinj.ZoneMapCorruptionError):
            lying.verify()

    def test_encode_batch_tags_sidecar_with_column_name(self):
        from spark_rapids_jni_tpu.columnar.encoded import encode_batch

        batch = ColumnBatch({"x": col_i64(np.arange(256)),
                             "y": col_i64(np.arange(256))})
        enc = encode_batch(batch, bitpack=["x"], frame_of_reference=["y"])
        assert enc["x"].zone.column == "x"
        assert enc["y"].zone.column == "y"
        enc["x"].zone.verify()  # the tag is part of the stamp
        enc["y"].zone.verify()

    def test_tampered_column_tag_fails_crc(self):
        enc = encode_for(col_i64(np.arange(256, dtype=np.int64)),
                         block=64, column="x")
        assert enc.zone.column == "x"
        with pytest.raises(faultinj.ZoneMapCorruptionError):
            dataclasses.replace(enc.zone, column="y").verify()

    def test_knob_off_encodes_without_sidecar(self):
        config.set("zone_maps", False)
        enc = encode_for(col_i64(np.arange(256, dtype=np.int64)), block=64)
        assert enc.zone is None

    def test_tree_round_trip_drops_sidecar(self):
        # the sidecar is host metadata, NOT a pytree child: any tree
        # round-trip (shard, jit, device_put) reconstructs without it
        enc = encode_for(col_i64(np.arange(256, dtype=np.int64)), block=64)
        leaves, treedef = jax.tree_util.tree_flatten(enc)
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert enc.zone is not None and back.zone is None


class TestZoneMapMorselSkip:
    def _setup(self, eight_devices, thresh_q=0.01):
        from spark_rapids_jni_tpu.parallel import data_mesh, shard_batch

        P, n = 8, 8192
        rng = np.random.default_rng(7)
        vals = np.sort(rng.integers(0, 1 << 20, n)).astype(np.int64)
        keys = rng.integers(0, 64, n).astype(np.int64)
        enc = encode_for(col_i64(vals), block=256)
        mesh = data_mesh(P)
        batch = shard_batch(ColumnBatch({
            "k": col_i64(keys), "x": col_i64(vals)}), mesh)
        thresh = int(np.quantile(vals, thresh_q))
        return mesh, batch, enc.zone, thresh, vals

    def test_skips_blocks_and_streams_bit_identical(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import (MorselSource,
                                                  ShuffleRegistry,
                                                  ShuffleService)

        mesh, batch, zone, thresh, _ = self._setup(eight_devices)
        reg = ShuffleRegistry()
        svc = ShuffleService(mesh, registry=reg)
        src = MorselSource.from_batch(batch, mesh, morsel_rows=128,
                                      predicate=("x", "<", thresh),
                                      zone_map=zone)
        assert src.blocks_skipped > 0  # 1% selectivity MUST skip
        # ... and most of what the sidecar was consulted for
        assert src.blocks_skipped >= src.blocks_scanned
        res = svc.exchange_stream(src, key_names=["k"])
        full = svc.exchange_stream(
            MorselSource.from_batch(batch, mesh, morsel_rows=128),
            key_names=["k"])

        def survivors(r):
            xs = np.asarray(r.batch["x"].data).reshape(-1)
            vs = np.asarray(r.batch["x"].validity).reshape(-1)
            ks = np.asarray(r.batch["k"].data).reshape(-1)
            return sorted((k, x) for k, x, v in zip(ks, xs, vs)
                          if v and x < thresh)

        assert survivors(res) == survivors(full)
        # counters ride result AND registry metrics
        assert res.blocks_skipped == src.blocks_skipped
        snap = reg.metrics.snapshot()
        assert snap["blocks_skipped"] >= src.blocks_skipped
        assert snap["blocks_scanned"] >= src.blocks_scanned > 0

    def test_all_excluded_keeps_schema_morsel(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import MorselSource

        mesh, batch, zone, _, vals = self._setup(eight_devices)
        src = MorselSource.from_batch(
            batch, mesh, morsel_rows=128,
            predicate=("x", "<", int(vals.min())), zone_map=zone)
        assert len(src) == 1  # the schema-bearing morsel survives
        assert src.blocks_skipped > 0

    def test_none_excluded_scans_everything(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import MorselSource

        mesh, batch, zone, _, vals = self._setup(eight_devices)
        src = MorselSource.from_batch(
            batch, mesh, morsel_rows=128,
            predicate=("x", "<=", int(vals.max())), zone_map=zone)
        assert src.blocks_skipped == 0 and src.blocks_scanned > 0

    def test_wrong_column_sidecar_never_skips(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import MorselSource

        mesh, batch, _, thresh, vals = self._setup(eight_devices)
        # same row count but tagged with a different column: refused —
        # a wrong-column sidecar would skip morsels the x filter keeps
        wrong = encode_for(col_i64(vals), block=256, column="k").zone
        src = MorselSource.from_batch(batch, mesh, morsel_rows=128,
                                      predicate=("x", "<", thresh),
                                      zone_map=wrong)
        assert src.blocks_skipped == 0 and src.blocks_scanned == 0
        # tagged with the filter column, the same stats skip again
        tagged = encode_for(col_i64(vals), block=256, column="x").zone
        src = MorselSource.from_batch(batch, mesh, morsel_rows=128,
                                      predicate=("x", "<", thresh),
                                      zone_map=tagged)
        assert src.blocks_skipped > 0

    def test_reused_source_records_counters_once(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import (MorselSource,
                                                  ShuffleRegistry,
                                                  ShuffleService)

        mesh, batch, zone, thresh, _ = self._setup(eight_devices)
        reg = ShuffleRegistry()
        svc = ShuffleService(mesh, registry=reg)
        src = MorselSource.from_batch(batch, mesh, morsel_rows=128,
                                      predicate=("x", "<", thresh),
                                      zone_map=zone)
        first = svc.exchange_stream(src, key_names=["k"])
        assert first.blocks_skipped == src.blocks_skipped > 0
        base = reg.metrics.snapshot()["blocks_skipped"]
        # replays are re-runnable: a second exchange over the SAME
        # source must not re-record its one-time skip decision
        second = svc.exchange_stream(src, key_names=["k"])
        assert second.blocks_skipped == 0
        assert reg.metrics.snapshot()["blocks_skipped"] == base
        assert src.blocks_skipped > 0  # the public counter survives

    def test_knob_off_never_skips(self, eight_devices):
        from spark_rapids_jni_tpu.shuffle import MorselSource

        config.set("zone_maps", False)
        mesh, batch, zone, thresh, _ = self._setup(eight_devices)
        src = MorselSource.from_batch(batch, mesh, morsel_rows=128,
                                      predicate=("x", "<", thresh),
                                      zone_map=zone)
        assert src.blocks_skipped == 0 and src.blocks_scanned == 0
