"""Median, over the window's queries, of the caller's latency minus the kind's
own wall time in the worker: what front door, wire, journal, admission and the
data plane add.  Both from the benchmark's spans; only a served cell has the
``kind`` span."""

from benchmark import lib


def read(ctx):
    over = [(r["t1"] - r["t0"]) * 1e3 - ctx["spans"][str(r["q"])]["kind"]
            for r in ctx["records"]
            if "kind" in ctx["spans"].get(str(r["q"]), {})]
    return lib.median(over)
