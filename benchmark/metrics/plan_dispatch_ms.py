"""Median duration of the program's own ``plan.dispatch`` spans (all of
``CompiledPlan.__call__``: the inputs picked, build handles fetched, the
compiled program launched) that started inside the window.  Read off the
program's tracer, ``spark_rapids_jni_tpu.profiler.spans``, in the process that
ran the queries: the window's records and the tracer's spans are stamped by
the same ``time.perf_counter`` clock.  A program without the tracer, or a
ring that no longer holds the window's start, gives nothing."""

from benchmark import lib


def window_spans(ctx, name="plan.dispatch"):
    """The tracer's spans called ``name`` that started between the first
    submit and the last answer of ``ctx["records"]``, or None."""
    from spark_rapids_jni_tpu import profiler

    read = getattr(profiler, "spans", None)
    recs = ctx["records"]
    if read is None or not recs:
        return None
    lo = int(min(r["t0"] for r in recs) * 1e9)
    hi = int(max(r["t1"] for r in recs) * 1e9)
    ring = read()
    if len(ring) >= profiler.RING_SPANS and ring[0].t0_ns > lo:
        return None   # the ring turned over inside the window
    return [s for s in ring if s.name == name and lo <= s.t0_ns <= hi]


def read(ctx):
    spans = window_spans(ctx)
    if not spans:
        return None
    return lib.median([(s.t1_ns - s.t0_ns) / 1e6 for s in spans])
