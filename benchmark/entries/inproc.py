"""The ``inproc`` entry: the callers are threads of the process that holds the
chip (or the chips the cell asks for), and call the configuration's query
directly: how an embedding executor reaches the plan compiler.  It bypasses
the front door, the worker, the journal and the data plane."""

import time

from benchmark import faults, lib, trace, window


def run(ctx):
    cfg, mod, traffic = ctx["cfg"], ctx["mod"], ctx["traffic"]
    devs = lib.take_devices(ctx["chips"], ctx["platform"])

    from spark_rapids_jni_tpu import plan

    lib.apply_knobs(cfg)
    state = mod.build(cfg, mod, ctx["seed"], devs)
    table_bytes = int(state.table_bytes())
    query = faults.wrap(state, ctx.get("fault"))
    spans = lib.Spans()
    order = window.partition_order(ctx["seed"], state.partitions)
    for i in range(int(traffic["callers"])):
        query(order[-1 - i], -1 - i, spans)            # warm-up
    trace_dir = ctx.get("trace_dir")
    if trace_dir:
        trace.start(trace_dir)
    spans = lib.Spans()
    traces0 = plan.trace_count()
    lib.settle_gc()
    t_setup_done = time.monotonic()
    records, t0, t1 = window.run_window(
        traffic, ctx["seconds"], order,
        lambda caller, q, part: query(part, q, spans))
    if trace_dir:
        trace.stop()
    counters = {"plan_retraces": plan.trace_count() - traces0,
                "plan_cache": plan.plan_cache_metrics()}
    dev = lib.device_report(devs, lib.peak_bytes(devs))
    tables = state.tables_in_turn(sorted({r["part"] for r in records}))
    return {"records": records, "window_s": t1 - t0, "t0": t0,
            "t_setup_done": t_setup_done, "device": dev,
            "spans": spans.export(), "counters": counters, "tables": tables,
            "table_bytes": table_bytes, "notes": {}}
