"""The ``served`` entry: callers reach the program through a ``FrontDoor``
with one worker, the tenant's path end to end.

The process that builds the ``FrontDoor`` stays off the chip (the program pins
it to the host CPU), so it is a child of the benchmark's process; the worker it
spawns takes the chip.  After the window the supervisor and the worker exit,
and the benchmark's own process takes the chip to make the tables again for the
reference.  The recipe (one worker, no respawn, ``heartbeat_ms=10000`` for a
backend that takes 10 s to start) is chip_smoke.py's phase A."""

import glob
import multiprocessing
import os
import threading
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmark import lib, window


class _LogTail(threading.Thread):
    """Keeps the latest text of every worker.log under the fleet dir: the
    supervisor removes a lost worker's directory, log included (copied from
    chip_smoke.py)."""

    def __init__(self, fleet_dir):
        super().__init__(name="bench-logtail", daemon=True)
        self.fleet_dir = fleet_dir
        self.logs = {}
        self._halt = threading.Event()

    def poll(self):
        for p in glob.glob(os.path.join(self.fleet_dir, "worker-*",
                                        "worker.log")):
            try:
                with open(p, errors="replace") as f:
                    self.logs[p] = f.read()[-6000:]
            except OSError:
                pass

    def run(self):
        while not self._halt.wait(0.5):
            self.poll()

    def stop(self):
        self._halt.set()
        self.join(2.0)
        self.poll()
        return "\n".join(f"--- {p} ---\n{t}" for p, t in
                         sorted(self.logs.items()))


LOSS = ("crashes", "stalls", "circuit_open", "respawns",
        "partitions_detected", "scale_up_failures")


def _lost(fleet):
    return {k: fleet[k] for k in LOSS if fleet.get(k)}


def _supervise(spec):
    """Runs in the supervisor's process; returns what the window gave."""
    from spark_rapids_jni_tpu.serve import FrontDoor

    cfg, traffic = spec["cfg"], spec["traffic"]
    part_bytes = int(spec["partition_bytes"])
    timeout = float(traffic["answer_timeout_s"])
    tenants = list(traffic["tenants"])
    door = FrontDoor(workers=int(traffic["workers"]), autoscale=False,
                     respawn_max=0, pool_bytes=4 * part_bytes,
                     max_concurrent=int(traffic["max_concurrent"]),
                     heartbeat_ms=float(traffic["heartbeat_ms"]),
                     setup="benchmark.kinds")
    tail = _LogTail(door.fleet_dir)
    tail.start()
    out = {"worker_log": ""}

    def ask(kind, params, tenant="bench-control", est=0, wait=timeout):
        """The answer; a lost worker fails the run at once instead of being
        waited for."""
        sess = door.submit(kind, params, tenant=tenant, est_bytes=est,
                           snapshot=traffic.get("snapshot"))
        deadline = time.monotonic() + wait
        while True:
            try:   # wakes at once when the answer is in
                return sess.result(timeout=0.25)
            except TimeoutError:
                if sess.done():   # answered meanwhile, or a timeout answered
                    return sess.result(timeout=0)
            lost = _lost(door.metrics.snapshot())
            if lost:
                raise RuntimeError(f"the worker was lost: {lost}")
            if time.monotonic() > deadline:
                raise TimeoutError(f"{kind}: no answer after {wait} s")

    def one_query(caller, q, part):
        batch = ask("bench_plan", {"partition": part, "q": q},
                    tenant=tenants[caller % len(tenants)], est=part_bytes)
        # the decoded result in hand: host copies of every column
        return {c: (np.asarray(batch[c].data), np.asarray(batch[c].validity),
                    None) for c in batch.names}

    try:
        info = ask("bench_setup", {
            "config": cfg["name"], "seed": spec["seed"],
            "log2_rows": cfg["log2_rows"] if cfg.get("rehearsal") else None,
            "knobs": spec["knobs"], "chips": spec["chips"],
            "fault": spec["fault"],
            "platform": spec["platform"]}, wait=spec["setup_timeout_s"])
        out["setup"] = info
        order = window.partition_order(spec["seed"], info["partitions"])
        # warm-up: every caller's tenant once, through the whole path
        for i in range(int(traffic["callers"])):
            one_query(i, -1 - i, order[-1 - i])
        trace_dir = spec["trace_dir"]
        if trace_dir:
            ask("bench_trace_start", {"dir": trace_dir})
        ask("bench_mark", {})
        lib.settle_gc()
        out["t_setup_done"] = time.monotonic()
        records, t0, t1 = window.run_window(traffic, spec["seconds"], order,
                                            one_query)
        if trace_dir:
            ask("bench_trace_stop", {})
        out.update(records=records, window_s=t1 - t0, t0=t0, order=order)
        out["finish"] = ask("bench_finish", {})
        out["fleet"] = door.metrics.snapshot()
    except Exception as e:   # reported by run(), with the worker's log
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        out["worker_log"] = tail.stop()
        report = door.shutdown()
    out["shutdown_clean"] = bool(report.get("clean"))
    return out


def run(ctx):
    cfg, mod = ctx["cfg"], ctx["mod"]
    spec = {k: ctx[k] for k in ("cfg", "traffic", "seed", "seconds", "chips",
                                "platform", "knobs", "fault", "trace_dir")}
    spec.update(partition_bytes=mod.query_bytes(cfg), setup_timeout_s=1100.0)
    spawn = multiprocessing.get_context("spawn")
    try:
        # an executor, not a Pool: a supervisor that dies breaks it, where a
        # Pool would wait for ever
        with ProcessPoolExecutor(1, mp_context=spawn) as supervisor:
            out = supervisor.submit(_supervise, spec).result()
    except Exception as e:
        raise lib.BenchError(f"the served window failed: "
                             f"{type(e).__name__}: {e}") from e
    if "error" in out:
        raise lib.BenchError(f"the served window failed: {out['error']}\n"
                             f"{out['worker_log']}")
    fleet = out["fleet"]
    lost = _lost(fleet)
    if lost or fleet.get("workers_spawned") != int(ctx["traffic"]["workers"]):
        raise lib.BenchError(f"the worker was lost: {lost} {fleet}\n"
                             f"{out['worker_log']}")
    dev = out["setup"]["device"]
    dev["memory_peak_bytes"] = out["finish"]["memory_peak_bytes"]
    # the supervisor and its worker are gone: this process takes the chip
    # and makes the same tables from the seed, for the reference only
    devs = lib.take_devices(ctx["chips"], ctx["platform"])
    if (devs[0].platform, devs[0].device_kind) != (dev["platform"],
                                                   dev["kind"]):
        raise lib.BenchError(f"worker ran on {dev}, reference tables on "
                             f"{devs[0]}")
    lib.apply_knobs(cfg)
    state = mod.build(cfg, mod, ctx["seed"], devs)
    tables = state.tables_in_turn(sorted({r["part"] for r in out["records"]}))
    return {"records": out["records"], "window_s": out["window_s"],
            "t0": out["t0"],
            "t_setup_done": out["t_setup_done"], "device": dev,
            "spans": out["finish"]["spans"],
            "counters": out["finish"]["counters"], "tables": tables,
            "table_bytes": out["setup"]["table_bytes"],
            "notes": {"shutdown_clean": out["shutdown_clean"],
                      "fleet": {k: fleet.get(k) for k in
                                ("data_batches", "workers_spawned")}}}
