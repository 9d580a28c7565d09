"""The gathers of one index a row that the sort-engine aggregates of the
newest plan the program traced make whatever the data holds, outside the
branch that more groups than the head's 4096 take
(``plan.plan_cache_metrics()["agg_rowwide_gathers"]``: 0 for ``q95_plan``,
whose fused aggregate reads its grouped rows in place and fetches ten
groups at the head).  ``None`` where the program has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get(
        "agg_rowwide_gathers")
