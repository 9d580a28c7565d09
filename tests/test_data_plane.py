"""Zero-copy columnar data plane tests (serve/data_plane.py +
columnar/arrow.py codec).

The contract under test: a result :class:`ColumnBatch` crosses the
supervisor/worker boundary as ONE Arrow IPC stream — dictionary columns
as u32 codes + dictionary, RLE columns as run values + lengths, never
materialized — through a memfd segment (shm plane), binary chunk frames,
or a capped base64 fallback, and comes back **bit-exact**: NaN payloads,
-0.0, dictionary codes and run boundaries included.  Before a single
buffer is interpreted the receiver verifies the descriptor's fence epoch
(stale-generation rejection) and every chunk CRC (torn-payload
rejection); the debug json plane refuses — loudly — anything the
control-frame cap cannot carry.
"""

import os

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import config, faultinj
from spark_rapids_jni_tpu.columnar import arrow as arrow_mod
from spark_rapids_jni_tpu.columnar.encoded import (DictionaryColumn,
                                                   RunLengthColumn)
from spark_rapids_jni_tpu.serve import data_plane as dp
from spark_rapids_jni_tpu.serve import wire
from spark_rapids_jni_tpu.serve.worker import make_result_batch


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faultinj.configure(None)


def _np(x):
    return np.asarray(jax.device_get(x))


def _seg_desc(payload, fp, chunk_bytes=4096, epoch=1, plane="shm",
              seg="seg-w0-g1-0"):
    crcs = dp.chunk_crcs(payload, chunk_bytes)
    return dp.build_descriptor(plane, seg, len(payload), fp,
                               chunk_bytes, crcs, epoch)


class TestCodecRoundTrip:
    def test_dict_rle_bit_exact_through_memfd(self):
        """The full shm path: batch -> IPC -> memfd -> mmap verify ->
        IPC -> batch, with every buffer compared by raw bytes."""
        batch = make_result_batch(257, seed=5)
        payload, fp = arrow_mod.batch_to_ipc(batch)
        desc = _seg_desc(payload, fp)
        fd = dp.make_segment(desc["seg"], payload)
        dp.seal_segment(fd)
        try:
            out = dp.read_segment(fd, desc)
        finally:
            os.close(fd)
        assert out == bytes(memoryview(payload))
        back = arrow_mod.ipc_to_batch(out, expect_fingerprint=fp)
        assert back.names == batch.names

        # encodings survive the hop — codes cross as codes, runs as runs
        assert isinstance(back["tag"], DictionaryColumn)
        assert isinstance(back["r"], RunLengthColumn)

        for name in batch.names:
            a, b = batch[name], back[name]
            assert _np(a.validity).tobytes() == _np(b.validity).tobytes()
        # "f" carries NaN payloads, -0.0, and data under null rows:
        # live slots must match by BIT PATTERN (tobytes, not ==)
        fa, fb = _np(batch["f"].data), _np(back["f"].data)
        va = _np(batch["f"].validity).astype(bool)
        assert fa[va].tobytes() == fb[va].tobytes()
        assert np.isnan(fa[va]).any() and (np.signbit(fa[va])
                                           & (fa[va] == 0)).any()
        assert _np(batch["v"].data).tobytes() == _np(back["v"].data).tobytes()
        ta, tb = batch["tag"], back["tag"]
        assert _np(ta.codes).tobytes() == _np(tb.codes).tobytes()
        # the chars matrix may re-pad to a different planned width; the
        # VALUE bytes (each row up to its length) are the contract
        la, lb = _np(ta.dictionary.lengths), _np(tb.dictionary.lengths)
        assert la.tolist() == lb.tolist()
        ca, cb = _np(ta.dictionary.chars), _np(tb.dictionary.chars)
        for i, n in enumerate(la):
            assert ca[i, :n].tobytes() == cb[i, :n].tobytes()
        ra, rb = batch["r"], back["r"]
        assert _np(ra.run_values).tobytes() == _np(rb.run_values).tobytes()
        assert _np(ra.run_lengths).astype(np.int64).tobytes() == \
            _np(rb.run_lengths).astype(np.int64).tobytes()
        # and the canonical transport digest agrees
        assert dp.batch_digest(batch) == dp.batch_digest(back)

    def test_empty_batch_round_trip(self):
        batch = make_result_batch(0, seed=1)
        payload, fp = arrow_mod.batch_to_ipc(batch)
        back = arrow_mod.ipc_to_batch(payload, expect_fingerprint=fp)
        assert back.names == batch.names
        assert dp.batch_digest(batch) == dp.batch_digest(back)

    def test_fingerprint_mismatch_rejected(self):
        payload, _fp = arrow_mod.batch_to_ipc(make_result_batch(8, seed=1))
        with pytest.raises(ValueError, match="fingerprint"):
            arrow_mod.ipc_to_batch(payload, expect_fingerprint="0" * 16)


class TestDescriptorVerify:
    def test_torn_chunk_rejected(self):
        """A byte flipped in the segment AFTER the CRC stamps must be
        caught by the chunk verify, naming the torn chunk."""
        batch = make_result_batch(64, seed=2)
        payload, fp = arrow_mod.batch_to_ipc(batch)
        desc = _seg_desc(payload, fp, chunk_bytes=512)
        fd = dp.make_segment(desc["seg"], payload)
        try:
            mid = len(memoryview(payload)) // 2
            b = os.pread(fd, 1, mid)
            os.pwrite(fd, bytes([b[0] ^ 0xFF]), mid)
            dp.seal_segment(fd)
            with pytest.raises(dp.DataPlaneCorruption, match="torn"):
                dp.read_segment(fd, desc)
        finally:
            os.close(fd)

    def test_size_mismatch_rejected(self):
        desc = _seg_desc(b"abcdef", "00")
        with pytest.raises(dp.DataPlaneCorruption, match="bytes"):
            dp.verify_chunks(b"abcde", desc)

    def test_chunk_count_mismatch_rejected(self):
        desc = _seg_desc(b"abcdef", "00", chunk_bytes=2)
        desc["crcs"] = desc["crcs"][:-1]
        with pytest.raises(dp.DataPlaneCorruption, match="stamps"):
            dp.verify_chunks(b"abcdef", desc)

    def test_stale_epoch_rejected(self):
        desc = _seg_desc(b"payload", "00", epoch=2)
        dp.verify_epoch(desc, 2)  # live generation passes
        with pytest.raises(dp.DataPlaneStale, match="stale"):
            dp.verify_epoch(desc, 3)

    def test_empty_payload_has_a_stamp(self):
        # zero-size payloads still carry (and verify) one CRC stamp —
        # an empty descriptor is never "trusted by default"
        desc = _seg_desc(b"", "00")
        assert len(desc["crcs"]) == 1
        dp.verify_chunks(b"", desc)
        desc["crcs"] = [desc["crcs"][0] ^ 1]
        with pytest.raises(dp.DataPlaneCorruption):
            dp.verify_chunks(b"", desc)


class TestPlaneResolution:
    def test_auto_picks_shm_on_unix_frames_on_tcp(self):
        assert dp.resolve_plane("auto", "unix") == "shm"
        assert dp.resolve_plane("auto", "tcp") == "frames"

    def test_shm_refused_on_tcp(self):
        with pytest.raises(ValueError, match="fd"):
            dp.resolve_plane("shm", "tcp")

    def test_unknown_setting_refused(self):
        with pytest.raises(ValueError, match="expected"):
            dp.resolve_plane("zerocopy", "unix")

    def test_knob_default_is_auto(self):
        assert config.get("serve_data_plane") == "auto"
        assert dp.resolve_plane(None, "unix") == "shm"

    def test_segment_names_are_epoch_stamped(self):
        # a replacement generation can never alias its predecessor
        assert dp.segment_name(1, 3, 0) != dp.segment_name(1, 4, 0)


class TestJsonPlane:
    def test_round_trip(self):
        raw = os.urandom(1024)
        assert dp.decode_json_payload(dp.encode_json_payload(raw)) == raw

    def test_overflow_raises_wiredesync(self):
        """A payload the control-frame cap cannot carry is refused with
        a WireDesync-class error — loud, never truncated."""
        with pytest.raises(dp.DataPlaneOverflow, match="cap|budget"):
            dp.encode_json_payload(b"x" * 120, cap=100)
        assert issubclass(dp.DataPlaneOverflow, wire.WireDesync)


class TestEndToEnd:
    """Real fleets: batches through spawned workers on each plane.

    Supervision is not what is tested: at the 80 ms heartbeat the rest
    of the serving tests use, a worker busy under six xdist workers is
    declared lost until the re-placement budget is spent."""

    @pytest.fixture(autouse=True)
    def _fast_ladder(self):
        config.set("serve_backoff_ms", 40.0)
        yield
        config.reset("serve_backoff_ms")

    def test_shm_batch_bit_identical_with_metrics(self):
        from spark_rapids_jni_tpu.serve import FrontDoor
        want = {k: dp.batch_digest(make_result_batch(512, k))
                for k in range(2)}
        fd = FrontDoor(workers=1, heartbeat_ms=5000.0,
                       data_plane_mode="shm")
        try:
            sess = {k: fd.submit("arrow_batch", {"rows": 512, "seed": k})
                    for k in range(2)}
            got = {k: dp.batch_digest(s.result(timeout=90))
                   for k, s in sess.items()}
        finally:
            report = fd.shutdown()
        assert got == want
        info = report["data_plane"]
        assert info["plane"] == "shm"
        assert info["batches"] == 2 and info["errors"] == 0
        # the whole point: payload bytes off the JSON wire
        assert info["payload_bytes"] > 10 * info["json_bytes"]

    def test_torn_segment_detected_and_replaced(self):
        """shm_torn flips real segment bytes after the CRC stamps; the
        supervisor must reject the transfer, re-place the session, and
        still deliver the bit-identical batch."""
        from spark_rapids_jni_tpu.serve import FrontDoor
        faultinj.configure({"faults": [
            {"match": "data_write_wk", "fault": "shm_torn", "count": 1},
        ]})
        want = dp.batch_digest(make_result_batch(512, 7))
        fd = FrontDoor(workers=1, heartbeat_ms=5000.0,
                       data_plane_mode="shm")
        try:
            s = fd.submit("arrow_batch", {"rows": 512, "seed": 7})
            assert dp.batch_digest(s.result(timeout=90)) == want
        finally:
            report = fd.shutdown()
        assert report["data_plane"]["errors"] >= 1
        assert any(e.get("name") == "data_write_wk"
                   for e in faultinj.fired_log())

    def test_stale_descriptor_detected_and_replaced(self):
        """shm_stale announces a dead fence generation's segment; the
        epoch check must reject it BEFORE any CRC work and re-place."""
        from spark_rapids_jni_tpu.serve import FrontDoor
        faultinj.configure({"faults": [
            {"match": "data_descriptor_wk", "fault": "shm_stale",
             "count": 1},
        ]})
        want = dp.batch_digest(make_result_batch(512, 9))
        fd = FrontDoor(workers=1, heartbeat_ms=5000.0,
                       data_plane_mode="shm")
        try:
            s = fd.submit("arrow_batch", {"rows": 512, "seed": 9})
            assert dp.batch_digest(s.result(timeout=90)) == want
        finally:
            report = fd.shutdown()
        assert report["data_plane"]["errors"] >= 1
        assert any(e.get("name") == "data_descriptor_wk"
                   for e in faultinj.fired_log())
