"""Median of the benchmark's span around ``plan.compile_plan`` (a plan-cache
hit in the window)."""

from benchmark import lib


def read(ctx):
    return lib.median([s["lookup"] for s in ctx["spans"].values()
                       if "lookup" in s])
