"""Launches of a compiled plan per query: the number of the program's
``plan.dispatch`` spans that started inside the window over the window's
queries.  1.0 while a query is one program; it moves the day a plan is split
into several.  Same source and same window as ``plan_dispatch_ms``."""

from benchmark import lib


def read(ctx):
    spans = lib.load_module("metrics", "plan_dispatch_ms").window_spans(ctx)
    if not spans:
        return None
    return len(spans) / len(ctx["records"])
