"""What every part of the benchmark shares: where its files are, how a cell's
files are found by name, seeds, spans and percentiles.

A cell of ``BENCHMARK.json`` names a ``config`` and a ``traffic``.  The
harness finds, by those names and nothing else:

* ``benchmark/configs/<config>.json``  the deployment: sizes, knobs, limits
* ``benchmark/configs/<config>.py``    its table recipe, query, reference
* ``benchmark/traffic/<traffic>.json`` callers, tenants, loop, entry
* ``benchmark/entries/<entry>.py``     how the callers reach the program
* ``benchmark/metrics/<metric>.py``    one reader for each per-layer metric

so a later PR adds a cell, a mix or a metric by adding files and entries.
"""

import contextlib
import gc
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class BenchError(Exception):
    """The run cannot give a result line (no chip, a lost worker, ...)."""


def load_json(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` by path: names may hold ``-`` and ``.``"""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    mod_name = "benchmark_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(name):
    bj = benchmark_json()
    for w in bj["workloads"]:
        if w["name"] == name:
            return bj, w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[w['name'] for w in bj['workloads']]}")


def load_config(name, log2_rows=None):
    """The configuration's sizes (with the rehearsal's row count put in
    where ``--rows`` gave one) and its module."""
    cfg = load_json("configs", name + ".json")
    cfg = dict(cfg, name=name)
    if log2_rows is not None:
        cfg["log2_rows"] = int(log2_rows)
        cfg["partitions"] = min(int(cfg["partitions"]), 4)
        cfg["rehearsal"] = True
    return cfg, load_module("configs", name)


def metrics_of(bj, cell, group):
    """The cell's metrics of ``end_to_end`` or ``per_layer``."""
    return [m for m in bj[group]
            if "workloads" not in m or cell in m["workloads"]]


def seed_words(seed, n, salt=0):
    """``n`` uint32 words from ``--seed`` (any whole number) and a salt."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), int(salt)])
    return [int(w) for w in ss.generate_state(n)]


def percentile(values, q):
    """Nearest-rank percentile of all ``values``: no interpolation."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(1, int(np.ceil(q / 100.0 * len(xs))))
    return xs[rank - 1]


def median(values):
    xs = sorted(values)
    if not xs:
        return None
    m = len(xs) // 2
    return xs[m] if len(xs) % 2 else 0.5 * (xs[m - 1] + xs[m])


class Spans:
    """Spans of the benchmark's own code around its calls into the program:
    ``with spans.span(q, "execute")`` records milliseconds under query ``q``
    and writes a ``bench.execute`` annotation into the profiler's trace."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows = {}   # q -> {name: ms}

    @contextlib.contextmanager
    def span(self, q, name):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation("bench." + name):
            yield
        ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self.rows.setdefault(int(q), {})[name] = ms

    def export(self):
        with self._lock:
            return {str(q): dict(v) for q, v in self.rows.items()}


def settle_gc():
    """Before a window opens, in every process that takes part in it: one
    full collection, and what is left (all that the imports and the set-up
    made) taken out of the collector's sight.  A full collection walks every
    tracked object of the process and the process stands still for it: 38
    ms inside a rehearsal's window on a sandbox core, which showed as that
    window's slowest query and was gone with this.  After it a collection
    walks what the window itself made, as in an executor that has been up
    for hours; nothing is switched off.  (The pauses of 105-125 ms on the
    chip's host are not collections: every process of the machine stands
    still in them, PERF.md section 6, PR 30.)"""
    gc.collect()
    gc.freeze()


def device_report(devs, peak=None):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peak_bytes(devs):
    """Peak on the fullest chip, as the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def take_devices(chips, platform):
    """This process's first touch of JAX: the chips the cell asks for, on the
    platform the run is for, or no run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"JAX found platform {devs[0].platform!r}, the run "
                         f"is for {platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX reports "
                         f"{len(devs)}: {devs}")
    return devs[:chips]


def apply_knobs(cfg):
    """The configuration's knobs, set in the process that runs its queries;
    and every program, however small, kept in the persistent compile cache
    (the package places it in the checkout), so that a second run's set-up
    compiles nothing."""
    import jax

    from spark_rapids_jni_tpu import config

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    for k, v in (cfg.get("knobs") or {}).items():
        config.set(k, v)
