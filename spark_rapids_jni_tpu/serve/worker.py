"""Executor worker process: one ``ServeRuntime`` behind a socket.

Spawned by :class:`~spark_rapids_jni_tpu.serve.frontdoor.FrontDoor` as
``python -m spark_rapids_jni_tpu.serve.worker --socket ... --dir ...``.
Each worker owns the full single-process stack — its own arena (device +
host pools), spill store rooted under its private directory, plan cache,
and ``ServeRuntime`` — so a crash or wedge takes down exactly one
process's tenants and nothing shared.

The worker serves BOTH fleet transports (``--transport unix|tcp``,
serve/wire.py): it dials the supervisor, opens with the idempotent
``hello`` carrying ``(worker_id, fence_epoch, resume_token)``, and
treats connection loss as recoverable — a bounded reconnect ladder
(``--reconnect-max`` attempts, exponential backoff, capped by the
partition grace) re-dials and re-hellos; the same resume token
re-attaches this incarnation to its live sessions supervisor-side, and
results that could not be delivered while the link was down are queued
and flushed after reattach, so a dropped link costs zero sessions.

Split-brain safety: a worker that cannot reach the supervisor past
``--partition-grace-ms`` must assume it has been declared dead on the
other side of the partition.  It SELF-FENCES — revokes its own store
epoch through the PR-11 ``revoke()`` path so none of its in-flight
commits can ever be adopted (zero zombie commits), writes a
``self-fenced.json`` sentinel the supervisor reads at loss time, then
drains and exits.  Independently the main loop re-validates its fence
epoch against the store every ~0.5s: if the supervisor revoked this
generation (it believes we are lost) the worker stops serving and
exits rather than compute results nobody will adopt.

Submissions arrive as ``{"kind": name, "params": {...}}`` and are looked
up in the worker-side query-kind registry (:func:`register_query_kind`)
— the wire carries only JSON, never code.  Built-in kinds:

* ``echo``   — returns ``params["value"]`` (protocol smoke test)
* ``sleep``  — cooperative busy-wait for ``params["seconds"]``
* ``spill_walk`` — builds a batch from ``params["seed"]``, walks it
  device→host→disk and back through the spill tiers, returns a sha256
  digest of the promoted bytes (the chaos scenario's workload: the
  digest is a pure function of the seed, so survivors are comparable
  bit-for-bit across worker kills)
* ``q6_digest`` — ``steps`` q6 steps over deterministic example
  batches, returns ``[digest, seconds]`` (``chip_smoke.py`` compares
  the digest with the same steps run in process)
* ``shuffle_digest`` — a deterministic shuffle exchange keyed by
  ``params["store_key"]`` through the persistent shuffle store
  (``--store-dir``): returns the delivered rows' sha256 plus whether
  the map ran or a prior attempt's committed shards were ADOPTED — the
  store_recovery chaos scenario's workload
* ``arrow_batch`` — returns an actual :class:`ColumnBatch`
  (:func:`make_result_batch`: dictionary strings, RLE ints, floats with
  NaN/-0.0 payloads — a pure function of ``(rows, seed)``), which is
  exactly the kind of result that rides the zero-copy DATA plane
  instead of the JSON wire (the bench/chaos data-plane workload)

Data plane: a query whose result is a ``ColumnBatch`` does not cross as
JSON.  The watcher serializes it once with ``arrow.batch_to_ipc``
(encoded columns stay encoded) and ships it per ``--data-plane``: a
sealed memfd segment fd-passed with the result descriptor (``shm``),
binary chunk frames ahead of the descriptor (``frames``), or an inline
base64 fallback (``json`` — refused loudly past the control-frame cap).
The descriptor stamps this incarnation's fence epoch and per-chunk
CRC32s; the ``data_write_wk`` / ``data_descriptor_wk`` probes let chaos
tear stamped payload bytes (``shm_torn``) or resurrect a prior
generation's segment name (``shm_stale``) so the supervisor's
verification paths are exercised against real damage.

Fault injection: the supervisor exports its live schedule into this
process via ``SPARK_RAPIDS_TPU_FAULT_CONFIG`` and points
``SPARK_RAPIDS_TPU_FAULT_MIRROR`` at a per-worker append-only trace, so
an injection survives even our own SIGKILL.  This module installs the
process-level hooks for the ``worker_crash`` (kill -9 self) and
``worker_stall`` (wedge: stop answering heartbeats, block the querying
thread forever) kinds via :func:`faultinj.set_worker_fault_hooks`; the
``net_drop``/``net_stall``/``net_torn`` kinds fire inside the transport
itself at the ``net_send_wk``/``net_recv_wk`` probes.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import importlib
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import profiler

_QUERY_KINDS: Dict[str, Callable] = {}

_WEDGED = threading.Event()


def register_query_kind(name: str, fn: Callable):
    """Register ``fn(ctx, params, sess)`` under ``name`` for submissions."""
    _QUERY_KINDS[name] = fn


def _qk_echo(ctx, params, sess):
    return params.get("value")


def _qk_sleep(ctx, params, sess):
    end = time.monotonic() + float(params.get("seconds", 0.1))
    while time.monotonic() < end:
        sess._check_cancelled()
        time.sleep(0.01)
    return "slept"


def _qk_spill_walk(ctx, params, sess):
    import numpy as np

    from ..mem import spill as spill_mod

    seed = int(params.get("seed", 0))
    rows = int(params.get("rows", 8192))
    src = (np.arange(rows, dtype=np.int64) * (seed + 5)) % 7919

    def make():
        import jax.numpy as jnp
        return {"x": jnp.asarray(src)}

    h = spill_mod.SpillableHandle(make(), ctx=ctx,
                                  name=f"worker-walk-{seed}",
                                  recompute=make)
    # full tier walk: device→host→disk, then promote back and hash
    h.spill()
    h.spill_host()
    out = np.asarray(h.get()["x"])
    h.close()
    dig = hashlib.sha256()
    dig.update(str(out.dtype).encode())
    dig.update(str(out.shape).encode())
    dig.update(np.ascontiguousarray(out).tobytes())
    return dig.hexdigest()


def _qk_shuffle_digest(ctx, params, sess):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..columnar import types as T
    from ..columnar.column import Column, ColumnBatch
    from ..parallel import data_mesh, shard_batch
    from ..shuffle import ShuffleService, get_registry
    from ..shuffle import store as store_mod

    seed = int(params.get("seed", 0))
    P = jax.device_count()
    n = P * int(params.get("rows_per_shard", 64))
    store_key = str(params.get("store_key") or f"shuffle-{seed}-{n}")
    # pure function of the seed, so digests are comparable bit-for-bit
    # across attempts, workers, and store-enabled vs store-disabled runs
    vals = (np.arange(n, dtype=np.int64) * (2 * seed + 3)) % 7919
    pid_np = ((np.arange(n, dtype=np.int64) + seed) % P).astype(np.int32)
    mesh = data_mesh(P)
    batch = shard_batch(ColumnBatch({
        "v": Column(jnp.asarray(vals), jnp.ones((n,), jnp.bool_),
                    T.INT64)}), mesh)
    pid = jax.device_put(
        jnp.asarray(pid_np),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")))

    store = store_mod.get_store()
    pre_committed = store is not None \
        and store.has_committed(store_key, "map")
    m0 = get_registry().metrics.snapshot()
    res = ShuffleService(mesh).exchange(
        batch, pid=pid, round_rows=16, ctx=ctx, store_key=store_key)
    m1 = get_registry().metrics.snapshot()
    adopted = int(m1["adopted_shards"] - m0["adopted_shards"])

    dig = hashlib.sha256()
    for leaf in (res.batch["v"].data, res.occupancy):
        a = np.asarray(jax.device_get(leaf))
        dig.update(str(a.dtype).encode())
        dig.update(str(a.shape).encode())
        dig.update(np.ascontiguousarray(a).tobytes())
    return {
        "digest": dig.hexdigest(),
        "adopted": adopted,
        "rebuilt": int(m1["lineage_rebuilds"] - m0["lineage_rebuilds"]),
        # the acceptance metric: 0 when a prior attempt's committed map
        # output was adopted instead of re-running the map
        "map_runs": 0 if (pre_committed and adopted > 0) else 1,
    }


_Q6_JIT: list = []


def _qk_q6_digest(ctx, params, sess):
    import jax
    import numpy as np

    import __graft_entry__ as ge
    from .. import mem

    rows = int(params.get("rows", 1 << 14))
    stream = int(params.get("stream", 0))
    query = int(params.get("query", 0))
    steps = int(params.get("steps", 2))
    if not _Q6_JIT:
        _Q6_JIT.append(jax.jit(ge._q6_step))
    jfn = _Q6_JIT[0]
    t0 = time.perf_counter()
    dig = hashlib.sha256()
    for s in range(steps):
        b = ge._example_batch(rows, seed=1000 * stream + 10 * query + s)
        h = mem.SpillableHandle(
            b, ctx=ctx, name=f"worker-q6-{stream}-{query}-{s}")
        out = jax.block_until_ready(jfn(b))
        for leaf in jax.tree_util.tree_leaves(out):
            a = np.asarray(jax.device_get(leaf))
            dig.update(str(a.dtype).encode())
            dig.update(str(a.shape).encode())
            dig.update(np.ascontiguousarray(a).tobytes())
        h.close()
    return [dig.hexdigest(), time.perf_counter() - t0]


def make_result_batch(rows: int, seed: int):
    """Deterministic columnar result payload for the data-plane waves.

    A pure function of ``(rows, seed)`` so the solo / MP-shm / TCP-frames
    bench arms and every chaos retry are comparable bit-for-bit.  Exercises
    exactly what the zero-copy hop must preserve: dictionary-encoded
    strings (codes + dictionary, null rows borrowing a live code), an
    RLE-encoded int column, and float payload edge cases (NaN, -0.0)."""
    import jax.numpy as jnp
    import numpy as np

    from ..columnar import types as T
    from ..columnar.column import Column, ColumnBatch, StringColumn
    from ..columnar.encoded import encode_column, encode_rle

    n = int(rows)
    seed = int(seed)
    idx = np.arange(n, dtype=np.int64)
    v = (idx * (2 * seed + 3)) % 104729
    f = idx.astype(np.float64) * 0.5 - n / 4.0
    f[idx % 97 == 0] = np.nan
    f[idx % 89 == 0] = -0.0
    fv = (idx + seed) % 13 != 0
    tags = [t.encode() for t in
            ("alpha", "beta", "gamma", "delta-longer", "épsilon")]
    w = -(-max(len(t) for t in tags) // 8) * 8
    tmpl = np.zeros((len(tags), w), np.uint8)
    tlens = np.zeros((len(tags),), np.int32)
    for i, t in enumerate(tags):
        tmpl[i, : len(t)] = np.frombuffer(t, np.uint8)
        tlens[i] = len(t)
    tagidx = ((idx * (seed + 1)) % len(tags)).astype(np.int64)
    sv = (idx + 2 * seed) % 11 != 0
    chars = tmpl[tagidx] * sv[:, None].astype(np.uint8)
    lens = (tlens[tagidx] * sv).astype(np.int32)
    base = (np.arange(n // 8 + 1, dtype=np.int64) * (seed + 1)) % 5
    r = np.repeat(base, 8)[:n].astype(np.int32)
    rv = (idx + seed) % 17 != 0
    return ColumnBatch({
        "v": Column(jnp.asarray(v), jnp.ones((n,), jnp.bool_), T.INT64),
        "f": Column(jnp.asarray(f), jnp.asarray(fv), T.FLOAT64),
        "tag": encode_column(StringColumn(
            jnp.asarray(chars), jnp.asarray(lens), jnp.asarray(sv))),
        "r": encode_rle(Column(jnp.asarray(r), jnp.asarray(rv), T.INT32)),
    })


def _qk_arrow_batch(ctx, params, sess):
    return make_result_batch(int(params.get("rows", 1 << 13)),
                             int(params.get("seed", 0)))


register_query_kind("echo", _qk_echo)
register_query_kind("sleep", _qk_sleep)
register_query_kind("spill_walk", _qk_spill_walk)
register_query_kind("shuffle_digest", _qk_shuffle_digest)
register_query_kind("q6_digest", _qk_q6_digest)
register_query_kind("arrow_batch", _qk_arrow_batch)


def _crash_hook(name: str):
    # kill -9 semantics: no unwind, no atexit, no spill cleanup — the
    # supervisor's reaper is the only recovery path
    os.kill(os.getpid(), signal.SIGKILL)


def _stall_hook(name: str):
    # wedge: the main loop stops answering pings (so the supervisor's
    # heartbeat detector — not any in-process cleanup — must catch us),
    # and the querying thread blocks forever
    _WEDGED.set()
    while True:
        time.sleep(60.0)


class _SupervisorLink:
    """The worker's side of the supervised connection: one live
    transport, the idempotent hello, the bounded reconnect ladder, and
    the queue of frames that must survive a link outage (``running`` /
    ``result`` — the supervisor deduplicates by sid, so a flush after
    reattach is at-least-once delivery with exactly-once effect)."""

    def __init__(self, wire_mod, kind: str, address: str, worker_id: int,
                 epoch: int, token: str, grace_s: float,
                 reconnect_max: int):
        self._wire = wire_mod
        self.kind = kind
        self.address = address
        self.worker_id = int(worker_id)
        self.epoch = int(epoch)
        self.token = str(token)
        self.grace_s = float(grace_s)
        self.reconnect_max = int(reconnect_max)
        self._lock = threading.Lock()
        self._t = None
        # queued delivery jobs: (msg, fds, chunks) — plain control
        # messages queue as (msg, None, None); data-plane results keep
        # their segment fd / chunk list alive across the outage
        self._unsent: List[tuple] = []
        self.last_contact = time.monotonic()
        self.reconnects = 0
        # set by main once the session table exists: () -> live sids,
        # carried on every (re)hello so an ADOPTING supervisor can
        # reconcile journal placements against what we actually hold
        self.active_sids_fn = None

    def down(self) -> bool:
        with self._lock:
            return self._t is None

    def connect(self):
        """Dial + idempotent hello.  Raises on failure (the ladder in
        :meth:`reconnect` is the retry policy)."""
        import jax

        # the backend starts before the first dial: a worker that cannot
        # have the device its environment names dies here, in worker.log,
        # instead of after it was given a query
        dev = jax.devices()[0]
        extra = {"backend": jax.default_backend(),
                 "platform": dev.platform, "device_kind": dev.device_kind}
        t = self._wire.connect(self.kind, self.address, role="wk",
                               timeout_s=2.0)
        if self.active_sids_fn is not None:
            with contextlib.suppress(Exception):
                extra["active_sids"] = sorted(self.active_sids_fn())
        try:
            t.hello(self.worker_id, os.getpid(), self.epoch, self.token,
                    **extra)
        except (self._wire.WireError, OSError):
            t.close()
            raise
        t.settimeout(0.05)  # poll tick: lets the wedge flag win the loop
        with self._lock:
            old, self._t = self._t, t
        if old is not None:
            old.close()
        self.last_contact = time.monotonic()

    def reconnect(self) -> bool:
        """The bounded ladder: up to ``reconnect_max`` re-dials with
        exponential backoff, never outlasting the partition grace.
        True = reattached (queued frames flushed); False = partitioned."""
        start = time.monotonic()
        for attempt in range(self.reconnect_max):
            if time.monotonic() - start > self.grace_s:
                return False
            try:
                self.connect()
            except (self._wire.WireError, OSError):
                time.sleep(min(0.03 * (2 ** attempt),
                               max(0.05, self.grace_s / 4.0)))
                continue
            self.reconnects += 1
            self.flush_unsent()
            return True
        return False

    def _drop(self, t):
        with self._lock:
            if self._t is t:
                self._t = None
        t.close()

    def send(self, msg: dict, queue_on_fail: bool = False) -> bool:
        return self.send_payload(msg, None, None,
                                 queue_on_fail=queue_on_fail)

    def send_payload(self, msg: dict, fds: Optional[List[int]],
                     chunks: Optional[List[bytes]],
                     queue_on_fail: bool = False) -> bool:
        """Deliver one message plus its data-plane payload: chunk frames
        go FIRST (stream ordering means they are stashed supervisor-side
        before the descriptor arrives), an fd rides the descriptor frame
        itself via SCM_RIGHTS.  On success the worker's fd copy closes —
        the receiver holds the segment now.  A failed delivery requeues
        the whole job; the supervisor's sid dedup makes the eventual
        re-send at-least-once with exactly-once effect.

        A result's ``stages`` gain ``worker.send`` here: the time the
        payload's chunk frames took to go out, which is all of the send
        a frame can carry about itself (0 on the ``shm`` plane, where
        the descriptor carries an fd and nothing goes before it)."""
        with self._lock:
            t = self._t
            if t is None:
                if queue_on_fail:
                    self._unsent.append((msg, fds, chunks))
                return False
        try:
            t0 = time.perf_counter_ns()
            if chunks:
                sid = int(msg["sid"])
                for seq, c in enumerate(chunks):
                    t.send_data(sid, seq, c)
            if "stages" in msg:
                msg["stages"]["worker.send"] = profiler.note(
                    "worker.send", msg.get("sid"), t0,
                    time.perf_counter_ns())
            if fds:
                t.send_with_fds(msg, fds)
            else:
                t.send(msg)
        except (self._wire.WireError, OSError):
            self._drop(t)
            if queue_on_fail:
                with self._lock:
                    self._unsent.append((msg, fds, chunks))
            return False
        for fd in fds or ():
            with contextlib.suppress(OSError):
                os.close(fd)
        return True

    def flush_unsent(self):
        with self._lock:
            pending, self._unsent = self._unsent, []
        for i, job in enumerate(pending):
            if not self.send_payload(*job):
                with self._lock:
                    self._unsent = pending[i:] + self._unsent
                return

    def recv(self) -> dict:
        """One frame from the supervisor; ``socket.timeout`` at a frame
        boundary passes through for the poll loop, anything else drops
        the link (the main loop's ladder takes over)."""
        with self._lock:
            t = self._t
        if t is None:
            raise self._wire.WireError("link down")
        try:
            msg = t.recv()
        except socket.timeout:
            raise
        except (self._wire.WireError, OSError, ValueError):
            self._drop(t)
            raise self._wire.WireError("link lost")
        self.last_contact = time.monotonic()
        return msg

    def close(self):
        with self._lock:
            t, self._t = self._t, None
        if t is not None:
            t.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True,
                    help="supervisor address: Unix path, or host:port "
                         "for --transport tcp")
    ap.add_argument("--transport", default="unix",
                    choices=("unix", "tcp"))
    ap.add_argument("--worker-id", required=True, type=int)
    ap.add_argument("--dir", required=True)
    ap.add_argument("--host", default="",
                    help="logical placement host (informational: echoed "
                         "in hello and the self-fence sentinel)")
    ap.add_argument("--pool-bytes", type=int, default=64 << 20)
    ap.add_argument("--host-pool-bytes", type=int, default=16 << 20)
    ap.add_argument("--max-concurrent", type=int, default=0)
    ap.add_argument("--task-id-base", type=int, default=10_000)
    ap.add_argument("--store-dir", default=None,
                    help="fleet-shared persistent shuffle store root")
    ap.add_argument("--epoch", type=int, default=0,
                    help="this incarnation's store fencing epoch "
                         "(the supervisor passes the worker generation)")
    ap.add_argument("--resume-token", default="",
                    help="incarnation identity echoed in every hello so "
                         "a reconnect reattaches instead of replacing")
    ap.add_argument("--partition-grace-ms", type=float, default=1500.0)
    ap.add_argument("--orphan-grace-ms", type=float, default=0.0,
                    help="supervisor-silence bound (serve_orphan_grace_ms"
                         "): a link that LOOKS up but has carried nothing "
                         "— no pings, no frames — for this long means the "
                         "supervisor died without closing the socket; the "
                         "worker self-fences instead of serving a ghost. "
                         "0 disables (dead-socket orphans are still "
                         "covered by the reconnect ladder + partition "
                         "grace)")
    ap.add_argument("--reconnect-max", type=int, default=4)
    ap.add_argument("--data-plane", default="auto",
                    choices=("auto", "shm", "frames", "json"),
                    help="how ColumnBatch results cross back: memfd + "
                         "SCM_RIGHTS, binary chunk frames, or inline "
                         "base64 (resolved against --transport)")
    ap.add_argument("--segment-bytes", type=int, default=1 << 20,
                    help="data-plane chunk granularity (CRC stamp / "
                         "data-frame size; the serve_segment_bytes knob)")
    ap.add_argument("--setup", default=None,
                    help="module whose register_query_kinds(register) "
                         "adds custom kinds before serving")
    ap.add_argument("--warm", default=None,
                    help="JSON file of [{kind, params}] entries the "
                         "supervisor recorded per tenant class: "
                         "pre-traced off the critical path after "
                         "connect, so a fresh generation skips "
                         "first-query compile for warm classes")
    args = ap.parse_args(argv)

    from .. import faultinj
    faultinj.configure()  # env: the supervisor's exported schedule
    faultinj.set_worker_fault_hooks(crash=_crash_hook, stall=_stall_hook)

    from ..mem import spill as spill_mod
    from ..mem.rmm_spark import RmmSpark
    from . import data_plane as dp
    from . import wire
    from .runtime import ServeRuntime

    plane = dp.resolve_plane(args.data_plane, args.transport)

    if args.setup:
        importlib.import_module(args.setup).register_query_kinds(
            register_query_kind)

    spill_dir = os.path.join(args.dir, "spill")
    os.makedirs(spill_dir, exist_ok=True)
    adaptor = RmmSpark.set_event_handler(
        args.pool_bytes, host_pool_bytes=args.host_pool_bytes, poll_ms=20.0)
    fw = spill_mod.install(spill_dir=spill_dir)
    store = None
    if args.store_dir:
        from ..shuffle import store as shuffle_store
        store = shuffle_store.install(args.store_dir, epoch=args.epoch)
    runtime = ServeRuntime(
        max_concurrent=args.max_concurrent or None,
        task_id_base=args.task_id_base,
        store=store, epoch=args.epoch)

    link = _SupervisorLink(
        wire, args.transport, args.socket, args.worker_id, args.epoch,
        args.resume_token, grace_s=args.partition_grace_ms / 1000.0,
        reconnect_max=args.reconnect_max)

    def self_fence(reason: str):
        # safety first: revoke our OWN epoch so any commit still in
        # flight on a query thread is rejected at the store's rename —
        # a partitioned-but-alive worker must never zombie-commit
        if store is not None:
            with contextlib.suppress(OSError):
                store.revoke(args.epoch)
        info = {"worker_id": args.worker_id, "pid": os.getpid(),
                "epoch": args.epoch, "host": args.host,
                "reason": reason, "reconnects": link.reconnects}
        if store is not None:
            with contextlib.suppress(OSError):
                info["fenced_commits"] = \
                    store.snapshot().get("fenced_commits", 0)
        tmp = os.path.join(args.dir, "self-fenced.json.tmp")
        try:
            with open(tmp, "w") as f:
                json.dump(info, f)
            os.replace(tmp, os.path.join(args.dir, "self-fenced.json"))
        except OSError:
            pass

    partitioned = False
    revoked_out = False
    try:
        link.connect()
    except (wire.WireError, OSError):
        if not link.reconnect():
            self_fence("could not reach the supervisor at startup")
            partitioned = True

    sessions: Dict[int, object] = {}
    link.active_sids_fn = lambda: [
        sid for sid, s in sessions.items() if not s.done()]
    watchers: list = []
    warmed = [0]
    if args.warm and not partitioned:
        # warm plan-cache hand-off: run the supervisor-recorded (kind,
        # params) per tenant class through the runtime in a background
        # thread — jit traces land in this process's plan cache without
        # delaying the hello or blocking the serve loop
        try:
            with open(args.warm) as f:
                warm_entries = json.load(f)
        except (OSError, ValueError):
            warm_entries = []

        def run_warm():
            for e in warm_entries:
                kind = _QUERY_KINDS.get(e.get("kind"))
                if kind is None:
                    continue
                params = e.get("params") or {}

                def query(ctx, sess, k=kind, p=params):
                    return k(ctx, p, sess)

                try:
                    s = runtime.submit(query, est_bytes=0,
                                       tenant="__warm__", timeout_s=20.0)
                    s.result(timeout=30.0)
                    warmed[0] += 1
                except BaseException:
                    return  # warmth is best-effort, never load-bearing

        if warm_entries:
            threading.Thread(target=run_warm, name="worker-warm",
                             daemon=True).start()
    # lifecycle points unique to the process boundary: a submission was
    # received (session not yet created) and a result is about to be
    # sent (query done, result undelivered) — chaos lands worker_crash
    # on both to prove the supervisor's re-place / WorkerLost split at
    # each end of a session's life
    recv_probe = faultinj.instrument(lambda: None, "worker_recv")
    result_probe = faultinj.instrument(lambda: None, "worker_result")
    # data-plane fault points: after the CRC stamp (shm_torn tears real
    # payload bytes the stamps no longer cover) and at descriptor build
    # (shm_stale resurrects the previous generation's segment name)
    data_write_probe = faultinj.instrument(lambda: None, "data_write_wk")
    data_desc_probe = faultinj.instrument(lambda: None,
                                          "data_descriptor_wk")
    # the retirement ladder's fault point: drain_stuck fires here — the
    # order is acknowledged but never completed, and the supervisor's
    # drain deadline must escalate to the ordinary loss protocol
    drain_probe = faultinj.instrument(lambda: None, "worker_drain")
    seg_seq = iter(range(1 << 62))
    # sid -> input snapshot id declared by the submit (result-cache key
    # material, echoed back on the result descriptor)
    sid_snapshots: Dict[int, object] = {}

    def encode_batch_result(sid: int, batch):
        """ColumnBatch -> (descriptor fields, fds, chunk frames) on the
        resolved plane.  Payload bytes never enter the JSON message
        except on the loud-capped ``json`` fallback."""
        from ..columnar import arrow as arrow_mod

        with profiler.span("worker.batch_to_ipc"):
            payload, fp = arrow_mod.batch_to_ipc(batch)
        view = memoryview(payload)
        chunk_bytes = max(1, int(args.segment_bytes))
        with profiler.span("worker.crc"):
            crcs = dp.chunk_crcs(view, chunk_bytes)
        torn_at: Optional[int] = None
        try:
            data_write_probe()
        except faultinj.ShmTornError:
            # real damage, injected after the stamps: flip a byte in the
            # middle of the payload the CRCs claim to cover
            torn_at = len(view) // 2 if len(view) else None
        name = dp.segment_name(args.worker_id, args.epoch, next(seg_seq))
        # echo the submit's input snapshot id on the descriptor: the
        # supervisor's result cache inserts ONLY when the echo matches
        # what the client declared (provenance proven end to end)
        desc = dp.build_descriptor(plane, name, len(view), fp,
                                   chunk_bytes, crcs, args.epoch,
                                   snapshot=sid_snapshots.pop(sid, None))
        try:
            data_desc_probe()
        except faultinj.ShmStaleError:
            stale = max(0, args.epoch - 1)
            desc["epoch"] = stale
            desc["seg"] = dp.segment_name(args.worker_id, stale, 0)
        if plane == "shm":
            with profiler.span("worker.segment"):
                fd = dp.make_segment(name, view)
                if torn_at is not None:
                    b = os.pread(fd, 1, torn_at)
                    os.pwrite(fd, bytes([b[0] ^ 0xFF]), torn_at)
                dp.seal_segment(fd)
            desc["fds"] = 1
            return desc, [fd], None
        raw = bytearray(view)
        if torn_at is not None:
            raw[torn_at] ^= 0xFF
        if plane == "frames":
            chunks = [bytes(raw[o: o + chunk_bytes])
                      for o in range(0, len(raw), chunk_bytes)]
            return desc, None, chunks
        # raises DataPlaneOverflow past the control-frame cap: the json
        # fallback fails loudly, it never truncates
        desc["inline"] = dp.encode_json_payload(raw)
        return desc, None, None

    def watch(sid: int, sess):
        sess._done.wait()
        fds = chunks = None
        try:
            result_probe()  # chaos: crash with the result undelivered
            if sess.error is None:
                msg = {"op": "result", "sid": sid, "ok": True,
                       "status": sess.status}
                if dp.is_batch(sess.result_value):
                    with sess._span("worker.encode"):
                        msg["data"], fds, chunks = encode_batch_result(
                            sid, sess.result_value)
                else:
                    msg["value"] = sess.result_value
                # this session's stage milliseconds, for the front
                # door's timeline: admit_wait, reserve, run, encode (and
                # send, added as the frame goes out)
                msg["stages"] = {k: round(v, 4)
                                 for k, v in sess.stages.items()}
            else:
                msg = {"op": "result", "sid": sid, "ok": False,
                       "status": sess.status,
                       "error": type(sess.error).__name__,
                       "message": str(sess.error)}
        except BaseException as e:  # a non-crash kind fired at the probe
            msg = {"op": "result", "sid": sid, "ok": False,
                   "status": "failed", "error": type(e).__name__,
                   "message": str(e)}
        # queue on a downed link: the result is flushed after reattach
        # (the supervisor's sid dedup makes a re-send a no-op)
        link.send_payload(msg, fds, chunks, queue_on_fail=True)

    def handle_submit(msg: dict):
        sid = int(msg["sid"])
        if sid in sessions:
            # duplicate delivery: after a reattach the supervisor
            # re-sends every submit it never saw acked — either the
            # original submit or our "running" ack died with the old
            # link.  The session already exists; re-ack instead of
            # running the query twice (the result, if already computed,
            # sits in the pending queue and flushes on its own)
            if not sessions[sid].done():
                link.send({"op": "running", "sid": sid},
                          queue_on_fail=True)
            return
        kind = _QUERY_KINDS.get(msg.get("kind"))
        if kind is None:
            link.send({
                "op": "result", "sid": sid, "ok": False, "status": "failed",
                "error": "ServeError",
                "message": f"unknown query kind {msg.get('kind')!r}",
            }, queue_on_fail=True)
            return
        params = msg.get("params") or {}
        if msg.get("snapshot") is not None:
            sid_snapshots[sid] = msg["snapshot"]
        announced = threading.Event()

        def query(ctx, sess):
            if not announced.is_set():
                announced.set()
                link.send({"op": "running", "sid": sid},
                          queue_on_fail=True)
            return kind(ctx, params, sess)

        try:
            sess = runtime.submit(
                query, est_bytes=int(msg.get("est_bytes") or 0),
                tenant=msg.get("tenant"), timeout_s=msg.get("timeout_s"),
                priority=int(msg.get("priority") or 0), trace_sid=sid)
        except BaseException as e:
            link.send({
                "op": "result", "sid": sid, "ok": False, "status": "failed",
                "error": type(e).__name__, "message": str(e)},
                queue_on_fail=True)
            return
        sessions[sid] = sess
        t = threading.Thread(target=watch, args=(sid, sess),
                             name=f"worker-watch-{sid}", daemon=True)
        watchers.append(t)
        t.start()

    # -- main loop -------------------------------------------------------
    last_fence_check = time.monotonic()
    orphan_grace_s = max(0.0, args.orphan_grace_ms / 1000.0)
    draining = False
    retired = False
    while not partitioned:
        if draining and all(s.done() for s in sessions.values()):
            # drained: every placed session finished and no new work is
            # accepted — fall through to the retire exit (self-fence the
            # generation, bye, exit clean)
            retired = True
            break
        if _WEDGED.is_set():
            # simulated interpreter wedge: stop answering everything;
            # only the supervisor's SIGKILL ends this process
            while True:
                time.sleep(60.0)
        now = time.monotonic()
        # periodic fence re-validation: if the supervisor revoked this
        # generation it has declared us lost — stop serving rather than
        # compute results nobody will adopt
        if store is not None and now - last_fence_check >= 0.5:
            last_fence_check = now
            fenced = False
            with contextlib.suppress(OSError):
                fenced = store.fenced(args.epoch)
            if fenced:
                revoked_out = True
                break
        # orphan self-fence: the socket still LOOKS up, but the
        # supervisor has sent nothing — no pings, no frames — past the
        # orphan grace.  A live supervisor pings every heartbeat; total
        # silence this long means it died without the kernel ever
        # noticing (SIGKILL leaves established sockets half-open).  Run
        # the same self-fence ladder as a detected partition so a
        # never-restarted supervisor leaks neither this process nor an
        # unfenced generation.
        if orphan_grace_s > 0.0 and not link.down() \
                and now - link.last_contact > orphan_grace_s:
            self_fence("orphaned: supervisor silent past "
                       "serve_orphan_grace_ms")
            partitioned = True
            break
        if link.down():
            if link.reconnect():
                continue
            self_fence("supervisor unreachable past the partition grace")
            partitioned = True
            break
        try:
            msg = link.recv()
        except socket.timeout:
            continue
        except (wire.WireError, OSError):
            continue  # loop top runs the reconnect ladder
        op = msg.get("op")
        if op == "ping":
            link.send({
                "op": "pong", "t": msg.get("t"),
                "stall_breaks": RmmSpark.stall_break_count(),
                "live_sessions": sum(
                    1 for s in sessions.values() if not s.done()),
                # load signals for the supervisor's placement scorer:
                # admission-queue depth and arena residency ride every
                # pong (cheap decision channel, no payload bytes)
                "queue_depth": runtime.queue_depth(),
                "arena_bytes": int(adaptor.total_allocated()),
                "pool_bytes": int(args.pool_bytes),
                "warmed": warmed[0],
                "fence_epoch": args.epoch,
                "reconnects": link.reconnects,
                "fired": faultinj.fired_log(),
            })
        elif op == "drain":
            # retirement order from the autoscaler: finish placed
            # sessions, accept nothing new, self-fence, exit
            try:
                drain_probe()
                draining = True
            except faultinj.DrainStuckError:
                # acknowledged but never completed: the supervisor's
                # drain deadline is the recovery path
                pass
        elif op == "submit":
            try:
                recv_probe()  # chaos: crash before the session exists
            except BaseException as e:
                link.send({
                    "op": "result", "sid": int(msg["sid"]), "ok": False,
                    "status": "failed", "error": type(e).__name__,
                    "message": str(e)}, queue_on_fail=True)
                continue
            handle_submit(msg)
        elif op == "cancel":
            sess = sessions.get(int(msg.get("sid", -1)))
            if sess is not None and not sess.done():
                runtime.cancel(sess)
        elif op == "shutdown":
            break

    # -- graceful drain --------------------------------------------------
    clean = runtime.shutdown()
    for t in watchers:
        t.join(timeout=5.0)
    fenced_commits = 0
    if retired and store is not None:
        # the retired generation fences ITSELF before the bye: any
        # straggler commit from this incarnation is rejected at the
        # store's rename, so a retired worker can never zombie-commit —
        # the supervisor asserts fenced_commits == 0 (nothing was ever
        # rejected, because nothing was in flight after the drain)
        with contextlib.suppress(OSError):
            store.revoke(args.epoch)
            fenced_commits = store.snapshot().get("fenced_commits", 0)
    residue = [adaptor.total_allocated(), adaptor.host_total_allocated()]
    store_len = len(fw.store)
    leftovers = sorted(os.listdir(spill_dir)) if os.path.isdir(
        spill_dir) else []
    spill_mod.shutdown()
    RmmSpark.clear_event_handler()
    link.send({
        "op": "bye", "clean": bool(clean), "residue": residue,
        "store_len": store_len, "leftovers": leftovers,
        "retired": bool(retired), "fenced_commits": int(fenced_commits),
        "warmed": warmed[0],
        "fired": faultinj.fired_log(),
    })
    link.close()
    if partitioned:
        return 3  # self-fenced: the sentinel tells the supervisor why
    if revoked_out:
        return 4  # fenced by the supervisor: our gen is already revoked
    return 0 if clean else 1


if __name__ == "__main__":
    sys.exit(main())
