"""Stage-by-stage cost breakdown of the q6 pipeline + a profiler capture.

Answers VERDICT r2 weakness 2 ("the measured primitive costs don't
explain the pipeline cost — nobody profiled the gap"): times each stage
of the one-hot engine, both engines end-to-end, and then points the
in-tree Profiler at the full step and prints the top device events from
the decoded capture (xplane on TPU).

Run on whatever backend resolves.
"""
import _bootstrap  # noqa: F401  (repo root on sys.path)
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import __graft_entry__ as ge
from spark_rapids_jni_tpu.relational import AggSpec, group_by
from spark_rapids_jni_tpu.relational.aggregate import group_by_onehot

N = int(os.environ.get("PROF_Q6_ROWS", 1 << 21))
REPS = int(os.environ.get("PROF_Q6_REPS", 6))
# one warm-up variant + REPS timed variants per bench() call; a fresh seed
# block per call so no (fn, buffers) pair is ever executed twice
_seed = [100]


def bench(name, f, reps=REPS):
    jf = jax.jit(f)
    vs = [ge._example_batch(N, seed=_seed[0] + i) for i in range(reps + 1)]
    _seed[0] += reps + 1
    jax.block_until_ready(jf(vs[0]))
    outs = []
    t0 = time.perf_counter()
    for v in vs[1:]:
        outs.append(jf(v))
    jax.block_until_ready(outs)
    dt = (time.perf_counter() - t0) / reps
    print(f"{name:32s} {dt*1e3:8.2f} ms   {N/dt/1e6:8.1f} Mrows/s",
          flush=True)


print("devices:", jax.devices(), "rows:", N, flush=True)

# ---- one-hot engine stages ------------------------------------------------
bench("mask_only", lambda b: b["price"].data < 50.0)


def bucket_only(b):
    k = b["k"].data.astype(jnp.int32)
    live = b["k"].validity & (b["price"].data < 50.0)
    return jnp.where(live, jnp.clip(k, 0, 99), 100)


bench("bucket_build", bucket_only)


def onehot_int_dot(b):
    bucket = bucket_only(b)
    oh = (bucket[:, None] == jnp.arange(101, dtype=jnp.int32)[None, :]
          ).astype(jnp.int8)
    ones = jnp.ones((N, 1), jnp.int8)
    return jax.lax.dot_general(oh.T, ones, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


bench("onehot_count_dot", onehot_int_dot)

AGGS = [AggSpec("sum", "v", "sum_v"), AggSpec("count", None, "cnt"),
        AggSpec("mean", "price", "avg_price")]

bench("onehot_xla_f32x3", lambda b: group_by_onehot(
    b, "k", AGGS, 100, row_valid=b["price"].data < 50.0,
    float_mode="f32x3"))
bench("onehot_xla_f64_digits", lambda b: group_by_onehot(
    b, "k", AGGS, 100, row_valid=b["price"].data < 50.0,
    float_mode="f64"))
bench("onehot_pallas", lambda b: group_by_onehot(
    b, "k", AGGS, 100, row_valid=b["price"].data < 50.0,
    float_mode="f32x3", engine="pallas"))
bench("sort_scan_group_by", lambda b: group_by(
    b, ["k"], AGGS, row_valid=b["price"].data < 50.0))
bench("full_q6_default", ge._q6_step)

# ---- capture a real trace of the full step --------------------------------
from spark_rapids_jni_tpu.profiler import (  # noqa: E402
    FileWriter,
    Profiler,
    convert_profile,
)

cap = os.path.join(tempfile.gettempdir(), "q6_capture.bin")
if os.path.exists(cap):
    os.remove(cap)
w = FileWriter(cap)
Profiler.init(w)
jf = jax.jit(ge._q6_step)
cvars = [ge._example_batch(N, seed=900 + i) for i in range(5)]
jax.block_until_ready(jf(cvars[0]))
Profiler.start()
outs = [jf(v) for v in cvars[1:]]
jax.block_until_ready(outs)
Profiler.stop()
Profiler.shutdown()
w.close()

events = convert_profile(cap)
dev = [e for e in events
       if e.get("plane", "").lower().find("device") >= 0
       or e.get("plane", "").lower().find("tpu") >= 0]
pool = dev if dev else [e for e in events if "plane" in e]
agg = {}
for e in pool:
    agg.setdefault(e["name"], [0.0, 0])
    agg[e["name"]][0] += e["dur_us"]
    agg[e["name"]][1] += 1
print(f"\ncapture: {cap} ({len(events)} events, {len(dev)} device-plane)",
      flush=True)
print("top events by total us:")
for name, (us, cnt) in sorted(agg.items(), key=lambda kv: -kv[1][0])[:20]:
    print(f"  {us:10.1f} us  x{cnt:<5d} {name[:80]}")
