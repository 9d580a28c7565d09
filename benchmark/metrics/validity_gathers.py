"""The validity buffers that the row gathers (of more than 4096 indices) of
the newest plan the program traced move, a packed word of up to 32 columns'
validity counting one (``plan.plan_cache_metrics()["validity_gathers"]``:
one per nullable column gathered before PR 38).  ``None`` where the program
has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("validity_gathers")
