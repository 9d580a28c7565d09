"""Profiler lifecycle + offline conversion (reference Profiler.java API)."""

import os

import jax
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu.profiler import (
    MAGIC,
    FileWriter,
    Profiler,
    ProfilerError,
    convert_profile,
    list_capture_files,
)


@pytest.fixture(autouse=True)
def _clean_profiler():
    yield
    Profiler.shutdown()


def test_lifecycle_and_convert(tmp_path):
    cap = str(tmp_path / "capture.bin")
    w = FileWriter(cap)
    Profiler.init(w)
    Profiler.start()
    x = jnp.arange(1 << 16)
    y = jax.jit(lambda v: (v * 3 + 1).sum())(x)
    jax.block_until_ready(y)
    Profiler.stop()
    Profiler.shutdown()
    w.close()

    with open(cap, "rb") as f:
        head = f.read(8)
    assert head == MAGIC
    files = list_capture_files(cap)
    assert files, "capture contains no trace artifacts"
    events = convert_profile(cap)
    assert isinstance(events, list)
    # XLA's CPU trace should contain at least one named duration event
    assert any(e["dur_us"] >= 0 and e["name"] for e in events)


def test_double_init_raises(tmp_path):
    w = FileWriter(str(tmp_path / "c.bin"))
    Profiler.init(w)
    with pytest.raises(ProfilerError):
        Profiler.init(w)
    Profiler.shutdown()
    w.close()


def test_start_without_init_raises():
    with pytest.raises(ProfilerError):
        Profiler.start()


def test_stop_idempotent(tmp_path):
    w = FileWriter(str(tmp_path / "c.bin"))
    Profiler.init(w)
    Profiler.stop()  # never started: no-op
    Profiler.shutdown()
    w.close()


class TestXplaneDecode:
    def test_xplane_pb_events_decode(self, tmp_path):
        """The converter must decode the XLA profiler's xplane.pb (the
        format that carries per-kernel device activity on TPU), not just
        the Chrome-trace JSON (VERDICT r2 item 8)."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.profiler import (
            FileWriter,
            Profiler,
            convert_profile,
            list_capture_files,
        )

        cap = str(tmp_path / "cap.bin")
        w = FileWriter(cap)
        Profiler.init(w)
        Profiler.start()
        jax.block_until_ready(
            jax.jit(lambda x: (x * 2 + 1).sum())(jnp.arange(4096)))
        Profiler.stop()
        Profiler.shutdown()
        w.close()

        names = list_capture_files(cap)
        assert any(n.endswith(".xplane.pb") for n in names), names
        events = convert_profile(cap)
        xev = [e for e in events if "plane" in e]
        assert xev, "no xplane events decoded"
        # empirical schema check: plane/line names decoded as text and at
        # least one event has a real name and a positive duration
        assert any(e["plane"] for e in xev)
        assert any(e["dur_us"] > 0 and not e["name"].startswith("event:")
                   for e in xev), xev[:5]

    def test_span_names_appear(self, tmp_path):
        """with span(name, sid=...): ... must annotate the capture (the
        NVTX-range analogue, SURVEY §5 tracing) and carry the query id."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_jni_tpu.profiler import (
            FileWriter,
            Profiler,
            convert_profile,
            span,
        )

        cap = str(tmp_path / "cap2.bin")
        w = FileWriter(cap)
        Profiler.init(w)
        Profiler.start()
        with span("srj_stage_filter", sid=5):
            jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.arange(64)))
        Profiler.stop()
        Profiler.shutdown()
        w.close()
        events = convert_profile(cap)
        assert any("srj_stage_filter" in e["name"] for e in events)
        assert any(e["name"] == "srj_stage_filter" and e.get("sid") == 5
                   for e in events)
