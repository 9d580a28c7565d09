"""The int8 slots of the newest one-hot contraction the program compiled
(``plan.plan_cache_metrics()["onehot_slots"]``: 27 for ``q6_plan`` under
``f64``): the width that the payload's assembly and the contraction scale
with.  ``None`` where the program has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("onehot_slots")
