"""Median of the benchmark's span around the query's execution, from the call
to ``block_until_ready``."""

from benchmark import lib


def read(ctx):
    return lib.median([s["execute"] for s in ctx["spans"].values()
                       if "execute" in s])
