"""The joins of the newest plan the program traced that put their matches
in front (a sort and a gather of every left column) where a row mask handed
on would have done (``plan.plan_cache_metrics()["joins_compacted"]``: 0 for
``q95_plan``, whose joins each feed an exchange).  ``None`` where the
program has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("joins_compacted")
