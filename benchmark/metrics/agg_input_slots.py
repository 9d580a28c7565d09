"""The row slots that the aggregates of the newest plan the program traced
take in, summed over them
(``plan.plan_cache_metrics()["agg_input_slots"]``): 12,002,430 for
``tpch_q18_plan``, whose two aggregates each take LINEITEM's 6,001,215 slots,
the second for a few hundred live rows; fewer the day a plan compacts before
an aggregate.  ``None`` where the program has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("agg_input_slots")
