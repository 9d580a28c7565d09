"""The char slots (rows times the stored width of the padded bytes) that the
string predicates of the newest plan the program traced scan, summed
(``plan.plan_cache_metrics()["like_char_slots"]``): 15,000,000 x 80 =
1,200,000,000 for ``tpch_q13_plan`` at scale factor 10, whose
``NOT LIKE`` reads every byte slot of ``o_comment``, 48.5 of 80 a row live on
average; fewer the day a scan reads only the live characters.  ``None``
where the program has no such counter."""


def read(ctx):
    return (ctx["counters"].get("plan_cache") or {}).get("like_char_slots")
