"""The served kinds of the benchmark.  They reach the worker through the hook
the program has: ``FrontDoor(setup="benchmark.kinds")`` -> ``worker.py
--setup`` -> ``register_query_kinds``.

``bench_setup`` builds the configuration's tables on the worker's device from
the seed, keeps them in this module and sets the configuration's knobs in the
worker; ``bench_plan`` answers ``{partition, q}`` with the query's live rows
as a ``ColumnBatch`` (which crosses back over the data plane);
``bench_trace_start`` / ``bench_trace_stop`` start and stop the profiler in
the process that holds the chip; ``bench_finish`` hands back the spans, the
counters and the device's peak memory."""

import time

import numpy as np

from benchmark import faults, lib, trace

_S = {}


def _setup(ctx, params, sess):
    cfg, mod = lib.load_config(params["config"], params.get("log2_rows"))
    cfg["knobs"] = dict(cfg.get("knobs") or {}, **(params.get("knobs") or {}))
    devs = lib.take_devices(int(params["chips"]), params["platform"])
    lib.apply_knobs(cfg)
    t0 = time.perf_counter()
    state = mod.build(cfg, mod, int(params["seed"]), devs)
    _S.update(cfg=cfg, mod=mod, state=state, devs=devs, spans=lib.Spans(),
              query=faults.wrap(state, params.get("fault")))
    return {"device": lib.device_report(devs),
            "table_bytes": int(state.table_bytes()),
            "partitions": int(state.partitions),
            "build_s": time.perf_counter() - t0}


def _plan(ctx, params, sess):
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnBatch

    q = int(params["q"])
    t0 = time.perf_counter()
    out = _S["query"](int(params["partition"]), q, _S["spans"])
    batch = ColumnBatch({c: Column(d, v, t) for c, (d, v, t) in out.items()})
    _S["spans"].rows[q]["kind"] = (time.perf_counter() - t0) * 1e3
    return batch


def _mark(ctx, params, sess):
    """The window opens: counters from here on are the window's."""
    from spark_rapids_jni_tpu import plan

    _S["spans"] = lib.Spans()
    _S["traces0"] = plan.trace_count()
    lib.settle_gc()
    return True


def _trace_start(ctx, params, sess):
    trace.start(params["dir"])
    return True


def _trace_stop(ctx, params, sess):
    trace.stop()
    return True


def _finish(ctx, params, sess):
    from spark_rapids_jni_tpu import plan

    peak = lib.peak_bytes(_S["devs"])
    out = {"spans": _S["spans"].export(),
           "counters": {"plan_retraces":
                        plan.trace_count() - _S.get("traces0", 0),
                        "plan_cache": plan.plan_cache_metrics()},
           "memory_peak_bytes": peak}
    _S["state"].free()
    return out


def register_query_kinds(register):
    register("bench_setup", _setup)
    register("bench_plan", _plan)
    register("bench_mark", _mark)
    register("bench_trace_start", _trace_start)
    register("bench_trace_stop", _trace_stop)
    register("bench_finish", _finish)
