"""``plan.trace_count()`` after the window minus before: what the window still
traced.  Predicted 0."""


def read(ctx):
    return ctx["counters"].get("plan_retraces")
