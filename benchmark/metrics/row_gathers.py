"""The gather operations that the row gathers (of more than 4096 indices) of
the newest plan the program traced make, every traced branch of a ``cond``
counted: a matrix of a batch's fixed-width words and validity words counts
one, a buffer gathered on its own one
(``plan.plan_cache_metrics()["row_gathers"]``).  ``None`` where the program
has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("row_gathers")
