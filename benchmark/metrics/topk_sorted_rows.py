"""The row slots that the ordered limits (``TopK``) of the newest plan the
program traced put through a sort or a selection
(``plan.plan_cache_metrics()["topk_sorted_rows"]``): the input's row slots
where every one of them is sorted or selected among (``tpch_q3_plan`` today:
LINEITEM's 6,001,215, of which some eleven thousand hold a group), fewer
where the limit looks at the live groups alone.  ``None`` where the program
has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("topk_sorted_rows")
