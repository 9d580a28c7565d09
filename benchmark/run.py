#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Fails where JAX finds no TPU (no CPU fallback).  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``) and, last,
``compared``: each number compared beside its limit.

``--rows LOG2`` is a rehearsal: under ``JAX_PLATFORMS=cpu`` and a small size
every step of the cell runs, counts are printed, no device metric and no
result line, and the exit code is 3.  ``--selfcheck`` reduces the recorded
trace beside the reduction to known numbers.
"""

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.monotonic()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# before jax or the package is imported: every process of this run gets the
# chip or dies (FrontDoor passes os.environ to its workers)
WANT = os.environ.setdefault("JAX_PLATFORMS", "tpu")
PLATFORM = WANT.split(",")[0].strip().lower()
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import lib, planrun  # noqa: E402


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def run_cell(args):
    """Everything of a run but the look for a chip; returns the result line's
    object (in a rehearsal without metrics)."""
    bj, cell = lib.find_cell(args.workload)
    cfg, mod = lib.load_config(cell["config"], args.rows)
    # the configuration's control: the program's own lower-precision path
    # (knobs set over the configuration's) or, where the program has none,
    # the reference with one stated guarantee broken in the reference's place
    control = dict(cfg["control"]) if args.control else {}
    knobs = dict(control.get("knobs") or {})
    cfg["knobs"] = dict(cfg.get("knobs") or {}, **knobs)
    traffic = lib.load_json("traffic", cell["traffic"] + ".json")
    entry = importlib.import_module("benchmark.entries." + traffic["entry"])
    chips = int(cell["chips"])
    seconds = float(args.seconds)
    trace_dir = None
    if args.trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    ctx = {"cfg": cfg, "mod": mod, "traffic": traffic, "seed": args.seed,
           "seconds": seconds, "chips": chips, "platform": args.platform,
           "knobs": knobs, "trace_dir": trace_dir, "fault": args.fault}
    try:
        out = entry.run(ctx)
        trace = None
        if trace_dir:
            from benchmark import trace as trace_mod

            files = trace_mod.find(trace_dir)
            if not files:
                raise lib.BenchError(f"no trace under {trace_dir}")
            try:
                trace = trace_mod.reduce_file(files[-1], chips)
            except ValueError as e:
                if args.rows is None:
                    raise lib.BenchError(str(e)) from e
                say(f"rehearsal: a trace was written; off the chip it has "
                    f"no device plane to reduce ({str(e)[:80]}...)")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- the window's numbers -------------------------------------------
    records = out["records"]
    done = [r for r in records if r["ok"]]
    lat_ms = [(r["t1"] - r["t0"]) * 1e3 for r in done]
    rows_q = mod.rows_per_query(cfg)
    values = {
        "query_p50_ms": lib.median(lat_ms),
        "query_p95_ms": lib.percentile(lat_ms, 95),
        "rows_per_s": (len(done) * rows_q / out["window_s"]) if done else None,
        "setup_s": out["t_setup_done"] - T0,
    }

    # -- correct: every answer of the window against the plain reference --
    limits = dict(cfg["limits"])
    compared = {"answers_missing": len(records) - len(done),
                "null_values": 0}
    t_ref = time.perf_counter()
    # each partition's reference once, its host copy dropped before the next
    reference = mod.control if control.get("reference") else mod.reference
    wants = {part: reference(cfg, tables) for part, tables in out["tables"]}
    for r in done:
        got, nulls = planrun.plain(r.pop("result"))
        compared["null_values"] += nulls
        for name, v in mod.compare(cfg, got, wants[r["part"]]).items():
            # None: the number could not be taken (its rows do not line up)
            worst = compared.get(name, 0)
            compared[name] = None if v is None or worst is None \
                else max(worst, v)
    ref_s = time.perf_counter() - t_ref
    for name in limits:
        compared.setdefault(name, None)   # nothing answered
    correct = bool(done) and all(
        compared[n] is not None and compared[n] <= limits[n] for n in limits)

    dev = out["device"]
    say(f"benchmark: {args.workload} seed {args.seed} on {dev['platform']} "
        f"{dev['kind']} x{dev['count']}: {len(records)} queries, "
        f"{len(records) - len(done)} failed, window {out['window_s']:.3f} s, "
        f"tables {out['table_bytes']} B, reference {ref_s:.1f} s "
        f"{out['notes']}")
    for r in records:
        if not r["ok"]:
            say(f"  failed q{r['q']}: {r.get('error')}")
    # where a window's time went that its median does not show
    last, gaps = {}, []
    for r in sorted(records, key=lambda r: r["t0"]):
        if r["caller"] in last:
            gaps.append((r["t0"] - last[r["caller"]]) * 1e3)
        last[r["caller"]] = r["t1"]
    say(f"  slowest queries ms {[round(x, 1) for x in sorted(lat_ms)[-3:]]}, "
        f"longest waits between a caller's queries ms "
        f"{[round(x, 1) for x in sorted(gaps)[-3:]]}")
    for r in sorted(done, key=lambda r: r["t1"] - r["t0"])[-3:]:
        split = {k: round(v, 1) for k, v in
                 out["spans"].get(str(r["q"]), {}).items()}
        say(f"    q{r['q']} at {r['t0'] - out['t0']:.2f} s of the window: "
            f"{(r['t1'] - r['t0']) * 1e3:.1f} ms, the benchmark's spans {split}")
    line = {"correct": correct, "attempted": len(records),
            "failed": len(records) - len(done), "metrics": {}, "device": dev}
    if args.rows is not None:
        # a rehearsal: counts, and no number under a device metric's name
        say(f"rehearsal counts: {json.dumps(compared)} limits "
            f"{json.dumps(limits)} correct={correct} counters "
            f"{json.dumps(out['counters'])} spans {len(out['spans'])}")
        return line

    if args.trace:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        mctx = {"cfg": cfg, "mod": mod, "records": done, "chips": chips,
                "spans": out["spans"], "counters": out["counters"],
                "trace": trace, "device": dev,
                "peaks": lib.load_json("peaks.json")}
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    for m in lib.metrics_of(bj, args.workload,
                            "per_layer" if args.trace else "end_to_end"):
        v = lib.load_module("metrics", m["name"]).read(mctx) if args.trace \
            else values.get(m["name"])
        if v is not None:   # a reader that finds nothing leaves it out
            line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    line["compared"] = {n: {"value": compared[n], "limit": limits[n]}
                        for n in limits}
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None, metavar="LOG2",
                    help="rehearsal at 2^LOG2 rows a partition on whatever "
                         "JAX_PLATFORMS names: no result line, exit code 3")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's control (its JSON says "
                         "which), for reading a limit; the driver never "
                         "passes it")
    ap.add_argument("--fault", default=None,
                    help="plant a fault of benchmark/faults.py under the "
                         "timed path (for the tests)")
    ap.add_argument("--selfcheck", action="store_true")
    args = ap.parse_args()
    if args.selfcheck:
        from benchmark import trace as trace_mod

        sys.exit(trace_mod.selfcheck())
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None:
        args.seconds = float(lib.benchmark_json()["run_seconds"])
    if PLATFORM != "tpu" and args.rows is None:
        sys.exit(f"benchmark/run.py: JAX_PLATFORMS={WANT!r} names "
                 f"{PLATFORM!r}; the benchmark measures on the TPU and has "
                 "no CPU fallback (--rows LOG2 rehearses)")
    args.platform = PLATFORM
    try:
        import spark_rapids_jni_tpu  # noqa: F401  (x64 + the compile cache)

        line = run_cell(args)
    except lib.BenchError as e:
        sys.exit(f"benchmark/run.py: no result: {e}")
    if args.rows is not None:
        say(f"benchmark/run.py: rehearsal of {args.workload} at 2^{args.rows} "
            f"rows {'passed' if line['correct'] else 'FAILED'}; no result "
            "line")
        sys.exit(3 if line["correct"] else 4)
    for name, c in line["compared"].items():
        say(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line, allow_nan=False), flush=True)


if __name__ == "__main__":
    main()
