"""The two per-layer metrics that read the program's own tracer
(``plan_dispatch_ms``, ``plan_dispatches_per_query``): each reader against a
window cut out of the live tracer, and against a program that has no tracer,
where it has to give nothing and not raise.

Run with ``python -m pytest benchmark/tests -q`` (not part of the repo's
tier-1 tests)."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import lib  # noqa: E402
from spark_rapids_jni_tpu import profiler  # noqa: E402

dispatch_ms = lib.load_module("metrics", "plan_dispatch_ms")
per_query = lib.load_module("metrics", "plan_dispatches_per_query")


def _query(ms):
    """One record of the window's, stamped as ``window.py`` stamps it, with
    one ``plan.dispatch`` of about ``ms`` inside it."""
    rec = {"ok": True, "t0": time.perf_counter()}
    with profiler.span("plan.lookup"):
        pass
    with profiler.span("plan.dispatch"):
        time.sleep(ms / 1e3)
    rec["t1"] = time.perf_counter()
    return rec


def test_readers_cut_the_window_out_of_the_live_tracer():
    with profiler.span("plan.dispatch"):      # the warm-up, before the window
        time.sleep(0.05)
    records = [_query(ms) for ms in (2.0, 4.0, 30.0)]
    time.sleep(0.002)
    with profiler.span("plan.dispatch"):      # after the last answer
        pass
    ctx = {"records": records}
    got = dispatch_ms.read(ctx)
    assert 4.0 <= got < 20.0                  # the median, not the mean
    assert per_query.read(ctx) == 1.0
    # a plan split into two programs shows as more than one launch a query:
    # the window now runs to the fourth query's end, so the stray launch
    # above lies inside it, and the fourth query launches twice
    records.append(_query(1.0))
    with profiler.span("plan.dispatch"):
        pass
    records[-1]["t1"] = time.perf_counter()
    assert per_query.read({"records": records}) == pytest.approx(6 / 4)


def test_readers_give_nothing_where_there_is_nothing_to_read(monkeypatch):
    assert dispatch_ms.read({"records": []}) is None
    assert per_query.read({"records": []}) is None
    # a window in which the program launched nothing
    now = time.perf_counter()
    idle = {"records": [{"ok": True, "t0": now + 100.0, "t1": now + 101.0}]}
    assert dispatch_ms.read(idle) is None and per_query.read(idle) is None
    # the parent commit's program: no tracer at all
    records = [_query(1.0)]
    monkeypatch.delattr(profiler, "spans")
    assert dispatch_ms.read({"records": records}) is None
    assert per_query.read({"records": records}) is None


def test_a_ring_that_turned_over_inside_the_window_gives_nothing():
    first = _query(1.0)
    for _ in range(profiler.RING_SPANS):
        with profiler.span("test.flood"):
            pass
    last = _query(1.0)
    assert dispatch_ms.read({"records": [first, last]}) is None
    assert per_query.read({"records": [last]}) == 1.0
