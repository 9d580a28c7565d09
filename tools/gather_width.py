#!/usr/bin/env python3
"""How a row gather's cost grows with the 32-bit words it moves a row.

    python3 tools/gather_width.py --cases 4194304,6001215,4194304:25 \
        --widths 1,2,4,6,8,16

For each case ``m`` or ``m:s`` (``m`` indices into a source of ``s`` rows,
``m`` unless given) and each width ``k`` it times three programs.  Each
takes ``k`` u32 words of ``s`` rows, makes each the output of a fresh fusion
(``x ^ c``), moves them through ``m`` indices (a random permutation where
``s`` is ``m``, random rows otherwise) and hands the ``k`` gathered words
back, one array each:

* ``words``: ``k`` gathers of one word each;
* ``rows``: one ``[s, k]`` matrix (``jnp.stack(axis=1)``), taken on axis 0,
  its columns sliced out again;
* ``cols``: one ``[k, s]`` matrix (``jnp.stack(axis=0)``), taken on axis
  1, as ``relational/gather.py:gather_batch`` takes it.

For each program: the median wall seconds of ``--reps`` blocked calls after
a warm one; the device seconds of one more call, alone in a profiler
session (the events of its module), with its heaviest operations; the bytes of
``compiled.memory_analysis()``; and ``vs_word``, the device seconds over
those of one word (``words`` at ``k`` = 1).  One JSON object on standard
output, a line a program on standard error.  Needs the chip;
``JAX_PLATFORMS=cpu ... --cases 4096 --widths 1,2`` rehearses it (exit 3,
no device seconds).
"""

import _bootstrap  # noqa: F401  (repo root on sys.path)

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "tpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import trace as bench_trace  # noqa: E402

FORMS = ("words", "rows", "cols")
MODULES_LINE = "XLA Modules"


def program(form, k, m, s):
    import jax
    import jax.numpy as jnp

    def fn(cols, idx):
        fresh = [c ^ jnp.uint32(0x9E3779B9) for c in cols]
        if form == "words":
            return tuple(c[idx] for c in fresh)
        if form == "rows":
            got = jnp.stack(fresh, axis=1)[idx]
            return tuple(got[:, j] for j in range(k))
        return tuple(jnp.stack(fresh)[:, idx])

    fn.__name__ = f"gw_{form}_{k}_{m}_{s}"
    return jax.jit(fn)


def device_seconds(xplane):
    """``(device seconds, {op: seconds})`` of one trace: its module events
    (``XLA Modules``) and operations (``XLA Ops``) on the device planes;
    ``None`` where it has no device plane."""
    planes = [lines for pl, lines in bench_trace.load(xplane)
              if bench_trace.DEVICE_PLANE.match(pl)]
    if not planes:
        return None
    total, ops = 0.0, {}
    for lines in planes:
        by_line = dict(lines)
        total += sum(d for _n, _s, d in by_line.get(MODULES_LINE, [])) / 1e9
        for name, _s, d in by_line.get(bench_trace.OPS_LINE, []):
            key = bench_trace.short_name(name)
            ops[key] = ops.get(key, 0.0) + d / 1e9
    return total, ops


def traced(fn, arg, idx):
    """:func:`device_seconds` of one call of ``fn``, alone in a profiler
    session: two forms that compile to one program share its module."""
    import jax

    log_dir = tempfile.mkdtemp(prefix="gather_width_")
    try:
        bench_trace.start(log_dir)
        jax.block_until_ready(fn(arg, idx))
        bench_trace.stop()
        files = bench_trace.find(log_dir)
        return device_seconds(files[-1]) if files else None
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default="4194304,6001215")
    ap.add_argument("--widths", default="1,2,4,6,8,16")
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    platform = os.environ["JAX_PLATFORMS"].split(",")[0].strip().lower()

    import jax
    import jax.numpy as jnp

    import spark_rapids_jni_tpu  # noqa: F401  (x64 + the compile cache)

    cases = []
    for case in args.cases.split(","):
        m, _, s = case.partition(":")
        cases.append((int(m), int(s or m)))
    widths = [int(w) for w in args.widths.split(",")]
    key = jax.random.PRNGKey(args.seed)
    results = []
    for m, s in cases:
        kidx, kcol = jax.random.split(jax.random.fold_in(key, m * 7 + s))
        idx = (jax.random.permutation(kidx, m) if s == m else
               jax.random.randint(kidx, (m,), 0, s)).astype(jnp.int32)
        words = jax.random.bits(kcol, (max(widths), s), jnp.uint32)
        cols = [words[j] for j in range(max(widths))]
        mine = []
        for k in widths:
            for form in FORMS:
                if form != "words" and k == 1:
                    continue
                fn = program(form, k, m, s)
                arg = tuple(cols[:k])
                mem = fn.lower(arg, idx).compile().memory_analysis()
                jax.block_until_ready(fn(arg, idx))
                wall = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(arg, idx))
                    wall.append(time.perf_counter() - t0)
                rec = {
                    "m": m, "s": s, "k": k, "form": form,
                    "program": fn.__name__,
                    "wall_s": statistics.median(wall),
                    "bytes": {"argument": mem.argument_size_in_bytes,
                              "output": mem.output_size_in_bytes,
                              "temp": mem.temp_size_in_bytes}
                    if mem is not None else None}
                results.append(rec)
                mine.append((rec, fn, arg))
        for r, fn, arg in mine:
            dev = traced(fn, arg, idx)
            if dev is not None:
                r["device_s"] = dev[0]
                r["ops"] = sorted(([k, v] for k, v in dev[1].items()),
                                  key=lambda kv: -kv[1])[:6]
        one = next((r.get("device_s") for r, _f, _a in mine if r["k"] == 1),
                   None)
        for r, _f, _a in mine:
            if one and r.get("device_s"):
                r["vs_word"] = r["device_s"] / one
        del words, cols, idx, mine

    report = {"device": jax.devices()[0].device_kind, "platform": platform,
              "reps": args.reps, "results": results}
    text = json.dumps(report, indent=1)
    for r in results:
        print(r["m"], r["s"], r["k"], r["form"], "wall %.5f" % r["wall_s"],
              "device", r.get("device_s"), "vs_word", r.get("vs_word"),
              file=sys.stderr)
    print(text)
    sys.exit(0 if platform == "tpu" else 3)


if __name__ == "__main__":
    main()
