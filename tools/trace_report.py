#!/usr/bin/env python3
"""Where one benchmark cell's time goes, by the program's own names.

    python3 tools/trace_report.py --workload q95.served --seed 7 --seconds 12

Runs the cell once with the profiler on, through the benchmark's own entry
(``benchmark/entries``), keeps the trace that ``benchmark/run.py`` throws
away, and reads it with the program's converter:

* ``device_scopes``: device seconds by named scope
  (``profiler.device_time_by_scope``; ``--depth 3`` keeps a third scope of
  each path, which tells ``agg.sortscan_head``, ``agg.sortscan_tier.<width>``
  and ``agg.sortscan_full`` apart), and the share outside every scope;
* ``idle_by_span``: the busiest device's idle gaps by the host span over
  each (``profiler.idle_by_span``), in a served cell with the supervisor's
  spans put on the worker's trace by a ``profiler.clock_anchor``; and
  ``reduce_s``, what reading and reducing the trace took;
* ``device_ops``: the heaviest device operations (the benchmark's short
  names: opcode and result type) with the scope each runs in;
* in a served cell, the median ``FrontDoorSession.timeline`` of the window's
  queries, ``serve_stages_ms`` (the median of each query's ``total_ms`` less
  its ``worker.run``) and ``serve_queue_ms`` (of ``serve.pending`` plus
  ``worker.admit_wait``) beside the benchmark's ``frontdoor_overhead_ms``,
  and the fleet's ``stage_ms``;
* in an in-process cell, the tracer's ``stage_totals()``.

One JSON object on standard output, the same in ``chiprun_out/``.  Needs the
chip (``--rows LOG2`` rehearses on the CPU: every step runs, the device part
of the report stays empty).  A ``perf_opt`` PR runs it on its parent and on
its change to see which scope its gain came out of.
"""

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import gzip
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "tpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import lib  # noqa: E402
from benchmark import trace as bench_trace  # noqa: E402
from benchmark.entries import served  # noqa: E402

ROOT = lib.ROOT
TIMELINES = "timelines.json"


def supervise_with_timelines(spec):
    """``served._supervise`` in the supervisor's process, with every
    session the window submits kept, so that their timelines can be written
    beside the trace, and with the supervisor's own spans and a clock
    anchor, which put its ``serve.*`` stages on the worker's trace (the
    benchmark hands back fixed keys)."""
    from spark_rapids_jni_tpu import profiler
    from spark_rapids_jni_tpu.serve import FrontDoor

    kept = []
    submit = FrontDoor.submit

    def keeping(self, kind, params=None, **kw):
        sess = submit(self, kind, params, **kw)
        kept.append(sess)
        return sess

    since = time.perf_counter_ns()
    FrontDoor.submit = keeping
    try:
        out = served._supervise(spec)
    finally:
        FrontDoor.submit = submit
    rows = [{"kind": s.kind, "q": s.params.get("q"), "status": s.status,
             "timeline": dict(s.timeline)} for s in kept]
    with open(os.path.join(spec["trace_dir"], TIMELINES), "w") as f:
        json.dump({"sessions": rows, "fleet": out.get("fleet"),
                   "anchor": profiler.clock_anchor(),
                   "spans": profiler.span_columns(since)}, f)
    return out


def timeline_report(rows, qs):
    """Over the window's queries' timelines: stage by stage the median, and
    the medians of ``serve_stages_ms`` (each query's ``total_ms`` less its
    ``worker.run``: the serving stages the program times) and
    ``serve_queue_ms`` (its ``serve.pending`` plus ``worker.admit_wait``:
    the waits in the front door's queue and the worker's admission)."""
    tls = [r["timeline"] for r in rows
           if r["kind"] == "bench_plan" and r["q"] in qs
           and r["status"] == "done"]
    stages = sorted({st for t in tls for st in t})
    return {"timeline_median_ms": {st: lib.median([t.get(st, 0.0)
                                                    for t in tls])
                                   for st in stages},
            "timeline_sessions": len(tls),
            "serve_stages_ms": lib.median(
                [t["total_ms"] - t.get("worker.run", 0.0) for t in tls]),
            "serve_queue_ms": lib.median(
                [t.get("serve.pending", 0.0) + t.get("worker.admit_wait", 0.0)
                 for t in tls])}


def device_report(xplane, top, depth, ring=None):
    """The trace's tables; ``ring``: ``(anchor, span columns)`` of another
    process (the supervisor), whose spans then name idle gaps too."""
    from spark_rapids_jni_tpu import profiler

    opener = gzip.open if xplane.endswith(".gz") else open
    with opener(xplane, "rb") as f:
        payload = f.read()
    t0 = time.perf_counter()
    events = profiler.convert_xplane(payload)
    if ring:
        events += profiler.on_trace_clock(
            profiler.spans_from_columns(ring[1]), ring[0],
            profiler.trace_start_ns(payload))
    by_scope = profiler.device_time_by_scope(events, depth=depth)
    idle = profiler.idle_by_span(events)
    reduce_s = time.perf_counter() - t0
    busy = sum(by_scope.values())
    # the heaviest operations, under the benchmark's names, with their
    # scope: self-times, so a while does not count its body twice
    keyed = [dict(e, scope=bench_trace.short_name(e["name"]) + " @ "
                  + (e["scope"] or profiler.NO_SCOPE))
             for e in events if "scope" in e]
    ops = profiler.device_time_by_scope(keyed, depth=99)
    rank = lambda d: [[k, v] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])]
    spans = {}
    for e in events:
        if e["plane"] != profiler.RING_PLANE and (
                "sid" in e or e["name"].split(".")[0] in profiler.SPAN_LAYERS):
            t = spans.setdefault(e["name"], [0, 0.0])
            t[0] += 1
            t[1] += e["dur_us"] / 1e3
    return {"busy_s": busy,
            "unscoped_share": by_scope.get(profiler.NO_SCOPE, 0.0) / busy
            if busy else None,
            "device_scopes": rank(by_scope),
            "idle_by_span": rank(idle),
            "device_ops": rank(ops)[:top],
            "reduce_s": reduce_s,
            "host_spans_in_trace": {k: {"count": c, "sum_ms": ms}
                                    for k, (c, ms) in sorted(spans.items())}}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rows", type=int, default=None, metavar="LOG2")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--depth", type=int, default=2,
                    help="scopes of a path that device_scopes keeps (3 "
                    "tells the sort engine's fetch branches apart)")
    ap.add_argument("--keep-xplane", default=None, metavar="PATH",
                    help="copy the trace there, gzipped")
    args = ap.parse_args()
    platform = os.environ["JAX_PLATFORMS"].split(",")[0].strip().lower()

    import spark_rapids_jni_tpu  # noqa: F401  (x64 + the compile cache)
    from spark_rapids_jni_tpu import profiler

    _bj, cell = lib.find_cell(args.workload)
    cfg, mod = lib.load_config(cell["config"], args.rows)
    traffic = lib.load_json("traffic", cell["traffic"] + ".json")
    entry = importlib.import_module("benchmark.entries." + traffic["entry"])
    trace_dir = tempfile.mkdtemp(prefix="trace_report_")
    ctx = {"cfg": cfg, "mod": mod, "traffic": traffic, "seed": args.seed,
           "seconds": args.seconds, "chips": int(cell["chips"]),
           "platform": platform, "knobs": {}, "trace_dir": trace_dir,
           "fault": None}
    report = {"workload": args.workload, "seed": args.seed}
    try:
        if entry is served:
            served._supervise = supervise_with_timelines
        out = entry.run(ctx)
        done = [r for r in out["records"] if r["ok"]]
        lat = [(r["t1"] - r["t0"]) * 1e3 for r in done]
        report.update(queries=len(done), window_s=out["window_s"],
                      query_p50_ms=lib.median(lat), device=out["device"])
        mctx = {"records": done, "spans": out["spans"]}
        ring = None
        if entry is served:
            with open(os.path.join(trace_dir, TIMELINES)) as f:
                kept = json.load(f)
            ring = kept["anchor"], kept["spans"]
            report.update(timeline_report(kept["sessions"],
                                          {r["q"] for r in done}))
            report.update(
                frontdoor_overhead_ms=lib.load_module(
                    "metrics", "frontdoor_overhead_ms").read(mctx),
                stage_ms=(kept["fleet"] or {}).get("stage_ms"),
                backends=(kept["fleet"] or {}).get("backends"))
        else:
            report.update(
                stage_totals=profiler.stage_totals(),
                plan_dispatch_ms=lib.load_module(
                    "metrics", "plan_dispatch_ms").read(mctx),
                plan_dispatches_per_query=lib.load_module(
                    "metrics", "plan_dispatches_per_query").read(mctx))
        files = bench_trace.find(trace_dir)
        if files and args.keep_xplane:
            os.makedirs(os.path.dirname(os.path.abspath(args.keep_xplane)),
                        exist_ok=True)
            with open(files[-1], "rb") as src, \
                    gzip.open(args.keep_xplane, "wb") as dst:
                shutil.copyfileobj(src, dst)
        if files:
            report.update(device_report(files[-1], args.top, args.depth,
                                        ring))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    text = json.dumps(report, indent=1)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "trace_report_"
                           f"{args.workload}_{args.seed}.json"), "w") as f:
        f.write(text + "\n")
    print(text)
    sys.exit(0 if args.rows is None else 3)


if __name__ == "__main__":
    main()
