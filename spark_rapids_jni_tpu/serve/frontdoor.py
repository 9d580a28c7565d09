"""Multi-process serving front door: supervised executor workers.

PR 9's ``ServeRuntime`` kept the whole fleet in one interpreter — one
wedged or OOM-killed process took every tenant down.  The front door
splits that blast radius along the process boundary (the ROADMAP's
"tenants as clients over a socket, sessions pinned to executor
processes"; the same isolation argument "Accelerating Presto with GPUs"
makes for production query fleets):

* **Supervisor** (:class:`FrontDoor`) — listens on a Unix-domain socket
  under a private fleet directory (or a ``127.0.0.1`` TCP port with
  ``serve_transport=tcp`` — the multi-host placement path) and spawns
  ``serve_workers`` executor processes
  (``python -m spark_rapids_jni_tpu.serve.worker``), each hosting its
  OWN ``ServeRuntime``, arena, spill store, and plan cache.
* **Placement** — worker slots are distributed round-robin across the
  ``serve_hosts`` logical hosts (more than one host forces tcp); each
  worker's host rides its handle and the shutdown report, so chaos can
  prove both hosts served.
* **Connection supervision ≠ process supervision** — a lost
  *connection* (``net_drop``/``net_stall``/``net_torn``, or any real
  link failure) does NOT kill the worker: the slot enters
  ``reconnecting`` and the worker's bounded ladder
  (``serve_reconnect_max`` re-dials) re-attaches the same incarnation
  via its resume token — live sessions survive, queued results flush,
  nothing re-runs.  Only a lost *worker* (crash/wedge, or a connection
  silent past ``serve_partition_grace_ms``) triggers the loss protocol.
* **Partition-safe split-brain** — a worker that cannot reach the
  supervisor past ``serve_partition_grace_ms`` SELF-FENCES: it revokes
  its own store epoch (PR-11 ``revoke()``), writes a
  ``self-fenced.json`` sentinel, drains, and exits — so a
  partitioned-but-alive worker can never zombie-commit, whichever side
  notices the partition first.
* **Pinning** — a tenant's sessions stick to one worker (least-loaded on
  first sight, re-pinned only when the pinned worker is gone), so its
  spill-store residency and plan-cache pins stay process-local.
* **Heartbeats** — every ``serve_heartbeat_ms`` the supervisor pings
  each worker; pongs carry the native stall-breaker EPOCH
  (``RmmSpark.stall_break_count()``) and the worker's live-session
  count.  A worker silent past ~3.5 periods, or whose stall epoch keeps
  climbing across many pongs with no sessions completing, is declared
  wedged.
* **Loss protocol** — a crashed (waitpid), wedged, or never-connected
  worker is SIGKILLed, its spill directory reaped, and its durable
  injection trace (the ``SPARK_RAPIDS_TPU_FAULT_MIRROR`` file) merged
  into this process's :func:`faultinj.fired_log`.  Its sessions split
  two ways: queued-or-replayable sessions re-place onto healthy workers
  through the bounded ``serve_max_readmissions``/``serve_backoff_ms``
  ladder; in-flight non-replayable ones fail loudly with
  :class:`WorkerLost` carrying the worker's last fired_log.
* **Respawn** — lost workers are respawned with exponential backoff; a
  slot respawned more than ``serve_respawn_max`` times opens its
  circuit breaker and the fleet serves degraded on the survivors.
* **Degradation** — when the alive fraction of configured workers drops
  below ``serve_shed_threshold``, pending admissions beyond the
  surviving capacity are shed lowest-priority-first
  (:class:`AdmissionShed`) instead of queueing unboundedly; when NO
  worker can ever come back (all dead, circuits open) pending sessions
  fail with :class:`WorkerLost`.

* **Zero-copy data plane** — result BATCHES never cross as JSON: the
  worker ships one Arrow IPC stream per result (encoded columns stay
  encoded) over the ``serve_data_plane`` plane — a sealed memfd
  fd-passed with the result descriptor (``shm``, Unix transport),
  binary chunk frames ahead of it (``frames``, the TCP path), or a
  loud-capped inline fallback (``json``).  The supervisor verifies the
  descriptor's fence EPOCH against the worker's live generation (stale
  segment reuse is rejected) and every per-chunk CRC32 (a torn payload
  is rejected), then maps/decodes read-only.  A damaged transfer is not
  a failed query: the session re-queues under a FRESH sid (the worker
  dedups by sid) through the same bounded ladder.  Stashed fds and
  chunk stashes are reaped at worker loss exactly like spill dirs.

* **Durable shuffle plane** — unless disabled, a fleet-shared
  :mod:`~spark_rapids_jni_tpu.shuffle.store` root lives under the fleet
  dir; every worker generation commits its map outputs and drained
  round chunks there with its gen as the fencing epoch.  At loss time
  the supervisor REVOKES the dead gen (a zombie's late commit is
  rejected at the rename) and reaps only its UNcommitted tmp entries —
  committed shards survive for the replacement to ADOPT instead of
  lineage re-running (``adopted_shards`` vs ``lineage_rebuilds``).
  ``shuffle_store_retain`` keeps the store past ``shutdown()``.

* **Supervisor recovery** — the front door itself is no longer a
  single point of failure: every session lifecycle transition and
  fleet fact is journaled WRITE-AHEAD (O_APPEND + fsync + per-record
  CRC32, serve/journal.py) into the fleet dir before the in-memory
  state mutates.  A new FrontDoor pointed at a dead supervisor's fleet
  dir (``adopt_dir=``) replays the journal, fences the dead
  generations via the store's ``fence_handoff`` (revoke each, raise
  the floor to the OLDEST survivor), re-binds the recorded listener
  address so surviving workers' reconnect ladders re-attach over the
  resume-token hello (their live sessions and queued results adopt
  instead of dying), re-places journal-known queued/replayable
  sessions through the ordinary backoff ladder, and serves
  already-completed results straight from the handed-over result
  cache.  Double restart is idempotent — the adoption records append
  to the same journal, so a second replay folds to the same state.
  A worker whose supervisor goes silent without the socket ever dying
  self-fences past ``serve_orphan_grace_ms`` (serve/worker.py), so a
  never-restarted supervisor leaks no processes and no unfenced
  generations.

The chaos ``frontdoor`` scenario (tools/chaos.py) SIGKILLs workers at
every session lifecycle point and asserts survivors' digests are
bit-identical, victims re-placed or loudly failed, every worker arena
drained, and zero orphan spill files fleet-wide; the
``store_recovery`` scenario does the same around the store's commit
point and proves adoption, quarantine fallback, and the zombie fence.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from .. import config, faultinj, profiler
from ..shuffle import store as store_mod
from . import data_plane, wire
from . import elastic as elastic_mod
from . import journal as journal_mod
from . import result_cache as result_cache_mod
from .launcher import launcher_from_config
from .runtime import QueryCancelled, QueryTimeout, ServeError

_MISS_BUDGET = 3.5       # heartbeat periods of silence before SIGKILL
_STALL_EPOCH_LIMIT = 8   # consecutive no-progress epoch bumps before kill
_STARTUP_GRACE_S = 30.0  # max wait for a spawned worker's hello


class WorkerLost(ServeError):
    """The worker process hosting this session died (crash, SIGKILL, or
    missed heartbeats) and the session could not be re-placed: it was
    mid-flight and not replayable, its re-placement budget ran out, or
    no healthy worker can ever come back.  Carries the dead worker's
    last injection trace so the failure is diagnosable post-mortem."""

    def __init__(self, message: str, worker_id: Optional[int] = None,
                 fired_log: Optional[List[dict]] = None):
        super().__init__(message)
        self.worker_id = worker_id
        self.fired_log = list(fired_log or [])


class AdmissionShed(ServeError):
    """Degraded-mode load shedding: healthy capacity dropped below
    ``serve_shed_threshold`` and this pending admission was in the
    lowest priority class beyond the surviving capacity."""


class QuotaExceeded(ServeError):
    """Per-tenant admission quota exhausted (``serve_tenant_quota_bytes``
    / ``serve_tenant_quota_s``): the tenant's charged bytes or completed
    wall-seconds are over budget, and this submit is rejected LOUDLY at
    admission — never queued, never silently degraded.  Rejections are
    counted per tenant in the ``shutdown()`` report."""

    def __init__(self, message: str, tenant=None, resource: str = ""):
        super().__init__(message)
        self.tenant = tenant
        self.resource = resource


class FleetMetrics:
    """Fleet-level counters + per-worker liveness, scraped via
    :func:`fleet_metrics` → ``RmmSpark.fleet_metrics()``."""

    FIELDS = ("workers_spawned", "respawns", "crashes", "stalls",
              "replacements", "worker_lost", "sheds", "circuit_open",
              "reconnects", "partitions_detected", "self_fenced_workers",
              "data_batches", "data_payload_bytes", "data_json_bytes",
              "data_plane_errors", "cache_hits", "hit_bytes_served",
              "scale_ups", "scale_downs", "scale_up_failures",
              "quota_rejections", "plan_warm_shipped",
              "recovered_sessions", "adopted_workers",
              "replayed_sessions")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self.FIELDS, 0)
        self._liveness: Dict[int, str] = {}
        self._backends: Dict[int, str] = {}
        self._stage_ms: Dict[str, list] = {}  # stage -> [count, sum, max]

    def bump(self, field: str, n: int = 1):
        with self._lock:
            self._counts[field] += n

    def set_liveness(self, worker_id: int, state: str):
        with self._lock:
            self._liveness[int(worker_id)] = state

    def set_backend(self, worker_id: int, backend: str):
        with self._lock:
            self._backends[int(worker_id)] = backend

    def add_timeline(self, timeline: Dict[str, float]):
        """Fold one finished session's ``{stage: ms}`` in."""
        with self._lock:
            for stage, ms in timeline.items():
                t = self._stage_ms.setdefault(stage, [0, 0.0, 0.0])
                t[0] += 1
                t[1] += ms
                t[2] = max(t[2], ms)

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self._counts)
            out["liveness"] = dict(self._liveness)
            # what each worker's hello said it runs on: "tpu TPU v5 lite"
            out["backends"] = dict(self._backends)
            # per stage of FrontDoorSession.timeline, over finished
            # sessions: {count, sum_ms, max_ms}
            out["stage_ms"] = {
                st: {"count": c, "sum_ms": sm, "max_ms": mx}
                for st, (c, sm, mx) in self._stage_ms.items()}
            return out


# the last-constructed front door's metrics; zeros-safe before any ran
_last_metrics = FleetMetrics()


def fleet_metrics() -> dict:
    return _last_metrics.snapshot()


class FrontDoorSession:
    """Supervisor-side handle for one submitted query.

    Status walks ``pending → placed → running → done`` on the happy
    path, ending in ``failed`` / ``cancelled`` / ``shed`` otherwise;
    ``replacements`` counts how many worker losses it survived.
    ``replayable=False`` declares the query non-idempotent: once seen
    ``running`` it is never re-placed — a worker loss fails it with
    :class:`WorkerLost` instead of silently re-running side effects.

    ``timeline`` is where the session's time went, ``{stage: ms}``: the
    supervisor's own spans (``serve.*``) as they close, the worker's
    stage durations (``worker.*``) off its result frame — durations
    only, no clock of one process is ever compared with the other's.
    Finishing adds ``total_ms`` (submit to finish) and
    ``unaccounted_ms``: the total minus the leaf stages, which is wire
    transit and thread wake-ups.  A session served from the result
    cache has ``serve.cache_probe`` and the journal record of its
    finish, and no stage of dispatch, worker or decode."""

    # stages that enclose other stages: left out of the leaves' sum
    TIMELINE_PARENTS = ("serve.submit", "serve.decode")

    def __init__(self, door: "FrontDoor", sid: int, kind: str,
                 params: Optional[dict], tenant, priority: int,
                 est_bytes: int, timeout_s: Optional[float],
                 replayable: bool, snapshot=None):
        self._door = door
        self.sid = sid
        self.kind = kind
        self.params = dict(params or {})
        self.tenant = tenant
        self.priority = int(priority)
        self.est_bytes = int(est_bytes or 0)
        self.timeout_s = timeout_s
        self.replayable = bool(replayable)
        # input snapshot id the client declared (None = contents
        # unproven: the result cache never touches this session) plus
        # the submit-time three-component cache key
        self.snapshot = snapshot
        self.cache_key: Optional[tuple] = None
        self.served_from_cache = False
        self.status = "pending"
        self.worker_id: Optional[int] = None
        self.replacements = 0
        # data-plane transfer retries (torn/stale payloads) — separate
        # budget from worker-loss replacements, same bound
        self.data_retries = 0
        self.result_value = None
        self.error: Optional[BaseException] = None
        self._cancel_requested = False
        self._done = threading.Event()
        self.submitted_at = time.monotonic()
        self.timeline: Dict[str, float] = {}
        self._t0_ns = time.perf_counter_ns()
        self._queued_ns = self._t0_ns  # when it last joined _pending
        self._timeline_lock = threading.Lock()

    def _stage(self, name: str, ms: float):
        """Add ``ms`` under ``name``: a stage met twice (three journal
        records; a re-placement) adds up."""
        with self._timeline_lock:
            self.timeline[name] = self.timeline.get(name, 0.0) + ms

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"session {self.sid} still {self.status} after {timeout}s")
        if self.error is not None:
            raise self.error
        return self.result_value

    def cancel(self):
        self._door.cancel(self)

    def close(self, timeout: Optional[float] = 10.0):
        if not self._done.is_set():
            self._door.cancel(self)
        self._done.wait(timeout)

    def _finish(self, value=None, error: Optional[BaseException] = None,
                status: Optional[str] = None):
        if self._done.is_set():
            return
        if status is not None:
            final = status
        elif error is not None:
            final = "failed"
        else:
            final = "done"
        door = self._door
        if door is not None:
            # write-ahead: the terminal transition is durable before
            # any in-memory state observes it.  ``seconds`` is only
            # charged for completed compute — replay rebuilds tenant
            # wall-clock quotas from exactly these records.
            secs = 0.0
            if final == "done" and not self.served_from_cache:
                secs = max(0.0, time.monotonic() - self.submitted_at)
            self._stage("serve.journal", door._jrec(
                "result", sid=self.sid, status=final,
                from_cache=bool(self.served_from_cache),
                tenant=str(self.tenant), seconds=round(secs, 6)))
        with self._timeline_lock:
            tl = self.timeline
            total = (time.perf_counter_ns() - self._t0_ns) / 1e6
            tl["unaccounted_ms"] = total - sum(
                ms for st, ms in tl.items()
                if st not in self.TIMELINE_PARENTS)
            tl["total_ms"] = total
            stages = dict(tl)
        if door is not None:
            door.metrics.add_timeline(stages)
        self.result_value = value
        self.error = error
        self.status = final
        self._done.set()
        if door is not None:
            with contextlib.suppress(Exception):
                door._note_session_done(self)


class WorkerHandle:
    """Supervisor-side record of one executor worker process: the child
    handle, its socket, its private directory (spill files + fault
    mirror + log), heartbeat state, and the sessions placed on it.
    ``kill()``/``close()`` release the process and socket — graftlint
    GL012 flags constructions with no release on some exit path."""

    def __init__(self, worker_id: int, gen: int, wdir: str,
                 proc, host: str = "local", token: str = ""):
        self.worker_id = int(worker_id)
        self.gen = int(gen)
        self.dir = wdir
        # a launcher.LaunchedWorker (or any Popen-compatible handle):
        # pid/poll/wait/kill, plus owns_pid for the hello validation
        self.proc = proc
        self.host = host
        self.token = token  # incarnation identity for hello reattach
        self.backend: Optional[str] = None  # jax.default_backend(), by hello
        self.link: Optional[wire.Transport] = None
        self.state = "starting"  # starting | healthy | reconnecting | dead
        self.spawned_at = time.monotonic()
        self.last_pong = time.monotonic()
        self.conn_lost_at = 0.0
        self.ever_connected = False
        self.stall_breaks = 0
        self.stall_suspect = 0
        self.results_since_pong = 0
        # load signals from the last pong (placement scoring inputs)
        self.queue_depth = 0
        self.arena_bytes = 0
        self.pool_bytes = 0
        # autoscale retirement ladder state
        self.retiring = False
        self.drain_deadline = 0.0
        self.fired: List[dict] = []
        self.merged = False
        self.bye: Optional[dict] = None
        self.sessions: Dict[int, FrontDoorSession] = {}
        # frames-plane reassembly: sid -> [(seq, chunk bytes)] — chunks
        # arrive (in stream order) BEFORE their result descriptor;
        # reaped with the worker like everything else it owned
        self.data_stash: Dict[int, list] = {}

    def kill(self):
        with contextlib.suppress(OSError):
            self.proc.kill()

    def close(self):
        link, self.link = self.link, None
        if link is not None:
            link.close()


class _AdoptedProc:
    """Process handle for a worker this supervisor did NOT spawn: the
    journal recorded its pid, the dead supervisor was its parent-slash-
    launcher, and adoption needs the same pid/poll/wait/kill surface a
    :class:`~.launcher.LaunchedWorker` gives.  ``poll`` prefers
    ``waitpid(WNOHANG)`` (the worker IS our child when the crash was
    simulated in-process — this also reaps zombies the dead generation
    never collected) and falls back to ``kill(pid, 0)`` liveness."""

    def __init__(self, pid: int):
        self.pid = int(pid)
        self.returncode: Optional[int] = None

    def owns_pid(self, pid) -> bool:
        return pid is not None and int(pid) == self.pid

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        try:
            done, status = os.waitpid(self.pid, os.WNOHANG)
            if done == self.pid:
                self.returncode = os.waitstatus_to_exitcode(status)
        except ChildProcessError:
            try:
                os.kill(self.pid, 0)
            except ProcessLookupError:
                self.returncode = -9
            except OSError:
                pass
        except OSError:
            pass
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        while True:
            rc = self.poll()
            if rc is not None:
                return rc
            if deadline is not None and time.monotonic() >= deadline:
                raise subprocess.TimeoutExpired(
                    f"adopted pid {self.pid}", timeout)
            time.sleep(0.02)

    def kill(self):
        with contextlib.suppress(OSError):
            os.kill(self.pid, signal.SIGKILL)


def _keep_supervisor_on_host():
    """The supervisor decodes arrow results into JAX arrays
    (``ipc_to_batch``), so it starts a backend of its own — and an
    accelerator belongs to one process, which must be a worker.  If this
    process has not started a backend yet, hold it to the host CPU.
    Workers are spawned with ``os.environ``, which this leaves alone."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        jax.config.update("jax_platforms", "cpu")


class FrontDoor:
    """The supervisor: ``submit(kind, params)`` → session handle pinned
    to a worker process; ``shutdown()`` drains the fleet and returns a
    per-worker cleanliness report (idempotent).  The process that builds
    one stays on the host CPU (:func:`_keep_supervisor_on_host`); each
    worker reports the backend it started in its hello
    (``WorkerHandle.backend``)."""

    def __init__(self, workers: Optional[int] = None,
                 pool_bytes: int = 64 << 20,
                 host_pool_bytes: int = 16 << 20,
                 max_concurrent: Optional[int] = None,
                 heartbeat_ms: Optional[float] = None,
                 respawn_max: Optional[int] = None,
                 shed_threshold: Optional[float] = None,
                 setup: Optional[str] = None,
                 store: bool = True,
                 store_dir: Optional[str] = None,
                 transport: Optional[str] = None,
                 hosts=None,
                 partition_grace_ms: Optional[float] = None,
                 reconnect_max: Optional[int] = None,
                 data_plane_mode: Optional[str] = None,
                 segment_bytes: Optional[int] = None,
                 launcher=None,
                 placement: Optional[str] = None,
                 autoscale: Optional[bool] = None,
                 tenant_quota_bytes: Optional[int] = None,
                 tenant_quota_s: Optional[float] = None,
                 adopt_dir: Optional[str] = None,
                 result_cache=None):
        global _last_metrics
        _keep_supervisor_on_host()
        self._n_workers = int(workers if workers is not None
                              else config.get("serve_workers"))
        hosts_raw = hosts if hosts is not None else config.get("serve_hosts")
        if isinstance(hosts_raw, str):
            host_list = [h.strip() for h in hosts_raw.split(",")
                         if h.strip()]
        else:
            host_list = [str(h) for h in hosts_raw]
        self._hosts: List[str] = host_list or ["local"]
        self._transport = str(transport if transport is not None
                              else config.get("serve_transport"))
        if len(self._hosts) > 1 and self._transport == "unix":
            # a Unix socket can't span boxes: multi-host placement
            # implies the TCP transport
            self._transport = "tcp"
        if self._transport not in ("unix", "tcp"):
            raise ServeError(
                f"serve_transport must be 'unix' or 'tcp', "
                f"got {self._transport!r}")
        try:
            self._data_plane = data_plane.resolve_plane(
                data_plane_mode if data_plane_mode is not None
                else config.get("serve_data_plane"), self._transport)
        except ValueError as e:
            raise ServeError(str(e)) from None
        self._segment_bytes = max(1, int(
            segment_bytes if segment_bytes is not None
            else config.get("serve_segment_bytes")))
        self._grace_s = float(
            partition_grace_ms if partition_grace_ms is not None
            else config.get("serve_partition_grace_ms")) / 1000.0
        self._reconnect_max = int(
            reconnect_max if reconnect_max is not None
            else config.get("serve_reconnect_max"))
        self._pool_bytes = int(pool_bytes)
        self._host_pool_bytes = int(host_pool_bytes)
        self._max_concurrent = int(
            max_concurrent if max_concurrent is not None
            else config.get("serve_max_concurrent"))
        self._hb_s = float(heartbeat_ms if heartbeat_ms is not None
                           else config.get("serve_heartbeat_ms")) / 1000.0
        self._respawn_max = int(respawn_max if respawn_max is not None
                                else config.get("serve_respawn_max"))
        self._shed_threshold = float(
            shed_threshold if shed_threshold is not None
            else config.get("serve_shed_threshold"))
        self._replace_max = int(config.get("serve_max_readmissions"))
        self._backoff_s = float(config.get("serve_backoff_ms")) / 1000.0
        self._setup = setup
        # the elastic control plane: how workers come to exist
        # (serve/launcher.py), where they and their sessions go
        # (serve/elastic.py), and whether capacity follows the queue
        try:
            self._launcher = launcher_from_config(launcher)
            self._placement = elastic_mod.Placement(
                self._hosts, mode=placement)
        except ValueError as e:
            raise ServeError(str(e)) from None
        autoscale_on = bool(autoscale if autoscale is not None
                            else config.get("serve_autoscale"))
        self._autoscaler: Optional[elastic_mod.AutoScaler] = \
            elastic_mod.AutoScaler(self._n_workers) if autoscale_on else None
        self._drain_s = float(config.get("serve_autoscale_drain_ms")) \
            / 1000.0
        self._extra_slots = itertools.count(self._n_workers)
        self._retired: List[dict] = []
        # PR-9 policy remainder: per-tenant quotas charged at admission
        # + warm plan-cache sharing keyed per tenant class
        self._quota_bytes = int(
            tenant_quota_bytes if tenant_quota_bytes is not None
            else config.get("serve_tenant_quota_bytes"))
        self._quota_s = float(
            tenant_quota_s if tenant_quota_s is not None
            else config.get("serve_tenant_quota_s"))
        self._tenant_bytes: Dict[str, int] = {}
        self._tenant_seconds: Dict[str, float] = {}
        self._quota_rejected: Dict[str, int] = {}
        self._plan_warm_max = int(config.get("serve_plan_warm"))
        self._plan_warmth: Dict[str, dict] = {}
        # supervisor recovery: ``adopt_dir`` points at a DEAD
        # supervisor's fleet dir.  Replay its journal BEFORE any
        # resource opens — a crash mid-replay (the journal_replay fault
        # point) must leave nothing to leak, so the next adoption
        # attempt starts from exactly the same journal.
        self._adopt_state: Optional[journal_mod.JournalState] = None
        if adopt_dir is not None:
            if not bool(config.get("serve_adopt")):
                raise ServeError(
                    "adopt_dir given but serve_adopt is off — refusing "
                    "to silently start a fresh fleet over an existing "
                    "fleet dir")
            self.fleet_dir = os.path.abspath(adopt_dir)
            self._adopt_state = journal_mod.replay(
                journal_mod.journal_path(self.fleet_dir))
        else:
            self.fleet_dir = tempfile.mkdtemp(prefix="sptpu_frontdoor_")
        # the durable shuffle plane: fleet-shared, survives any worker.
        # store=False runs PR-10 style (pure lineage recovery) — the
        # comparison arm for the store_recovery chaos scenario.
        self.store_dir: Optional[str] = None
        self._store: Optional[store_mod.ShuffleStore] = None
        if store:
            jmeta = self._adopt_state.meta if self._adopt_state else {}
            self.store_dir = os.path.abspath(
                store_dir or jmeta.get("store_dir")
                or os.path.join(self.fleet_dir, "shuffle-store"))
            self._store = store_mod.ShuffleStore(self.store_dir)
        self.metrics = FleetMetrics()
        _last_metrics = self.metrics
        # the fleet-wide result cache: supervisor-resident, so an entry
        # one worker computed serves every worker's tenants and
        # survives any worker loss (serve/result_cache.py).  An
        # adoption may be handed the dead door's cache object (the
        # model for a cache tier that outlives the supervisor): its
        # completed entries then serve recovered sessions with zero
        # recompute.
        self.result_cache = result_cache if result_cache is not None \
            else result_cache_mod.ResultCache()
        self._cache_gen = 0  # supervisor epoch stamped on hit descriptors
        self._cache_seq = itertools.count(1)
        self._lock = threading.RLock()
        self._sids = itertools.count(1)
        self._gens = itertools.count(1)
        self._pending: List[list] = []   # [not_before, session]
        self._pins: Dict[object, int] = {}   # tenant -> worker slot
        self._workers: Dict[int, WorkerHandle] = {}
        self._respawn_count = dict.fromkeys(range(self._n_workers), 0)
        self._respawn_at: Dict[int, float] = {}
        self._broken: set = set()
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._shutdown_started = False
        self._shutdown_done = threading.Event()
        self._shutdown_result: Optional[dict] = None
        self._crashed = False
        # adoption bookkeeping: the dead supervisor's sid -> the
        # session this door resurrected for it
        self._recovered: Dict[int, FrontDoorSession] = {}
        self._adopt_stats = {"adopted_workers": 0,
                             "recovered_sessions": 0,
                             "replayed_sessions": 0}

        self._self_fenced: List[dict] = []
        where = os.path.join(self.fleet_dir, "frontdoor.sock") \
            if self._transport == "unix" else "127.0.0.1:0"
        if self._adopt_state is not None:
            if self._transport == "unix":
                # the dead supervisor's socket file survived it: unlink
                # so the rebind lands on the SAME path the surviving
                # workers' reconnect ladders keep re-dialling
                with contextlib.suppress(OSError):
                    os.unlink(where)
            elif self._adopt_state.meta.get("addr"):
                # rebind the journal-recorded port (free: its owner is
                # dead) so survivors re-dial straight back to us
                where = self._adopt_state.meta["addr"]
        try:
            self._listener, self._sock_addr = wire.listen(
                self._transport, where, backlog=self._n_workers * 2)
        except OSError:
            if self._adopt_state is None or self._transport != "tcp":
                raise
            # the recorded port got taken after all: bind fresh —
            # survivors can't find us and self-fence via their
            # partition grace; journal-known sessions still replay
            # onto freshly spawned workers
            self._listener, self._sock_addr = wire.listen(
                self._transport, "127.0.0.1:0",
                backlog=self._n_workers * 2)
        self._listener.settimeout(0.2)

        # the write-ahead journal opens AFTER the listener (the meta
        # record carries the live address) and appends to the adopted
        # fleet's existing file — one journal per fleet dir, across
        # supervisor generations
        self._journal: Optional[journal_mod.SessionJournal] = None
        if bool(config.get("serve_journal")):
            self._journal = journal_mod.SessionJournal(
                journal_mod.journal_path(self.fleet_dir))
        self._jrec("meta", addr=self._sock_addr,
                   transport=self._transport, store_dir=self.store_dir,
                   n_workers=self._n_workers, hosts=list(self._hosts),
                   data_plane=self._data_plane)

        with self._lock:
            if self._adopt_state is not None:
                self._adopt_locked()
            else:
                for slot in range(self._n_workers):
                    self._spawn_locked(slot)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="frontdoor-accept", daemon=True)
        self._monitor_thread = threading.Thread(
            target=self._monitor_loop, name="frontdoor-monitor", daemon=True)
        self._accept_thread.start()
        self._monitor_thread.start()

    # -- write-ahead journal + crash simulation -------------------------
    def _jrec(self, rec: str, **fields):
        """Append one write-ahead record BEFORE the matching in-memory
        mutation (graftlint GL021 enforces the ordering statically).
        The two supervisor-death faults surface here: ``supervisor_
        crash`` raises at the append probe and ``journal_torn``
        converts to real tail damage then raises — in both cases THIS
        process is the dead supervisor now, so the death is made real
        (:meth:`_simulate_crash`) and re-raised for the caller's test
        harness to observe.  A real journal I/O failure degrades to
        unjournaled operation rather than taking the fleet down.
        Returns the append's milliseconds (the ``O_APPEND`` write and
        its ``fsync``), for the session's timeline."""
        j = self._journal
        if j is None or j.closed:
            return 0.0
        with profiler.span("serve.journal", sid=fields.get("sid"),
                           rec=rec) as sp:
            try:
                j.append(rec, **fields)
            except (faultinj.SupervisorCrash, faultinj.JournalTornError):
                self._simulate_crash()
                raise
            except OSError:
                pass
        return sp.ms

    def _simulate_crash(self):
        """Become a dead supervisor, abruptly: stop the loops, drop the
        listener and every worker link mid-stream, abandon the journal
        fd with NO finalize record.  Nothing is fenced, reaped, or
        removed — exactly the mess a SIGKILL leaves behind, which is
        what an adopting FrontDoor on this fleet dir must clean up.
        Idempotent."""
        with self._lock:
            if self._crashed:
                return
            self._crashed = True
            self._shutdown_started = True
        self._stop.set()
        self._wake.set()
        with contextlib.suppress(OSError):
            self._listener.close()
        if self._journal is not None:
            self._journal.abandon()
        # closing the supervisor side leaves the worker with EOF — the
        # same thing the kernel delivers when a real supervisor dies —
        # so its reconnect ladder starts dialling the fleet address
        for w in list(self._workers.values()):
            w.close()

    @property
    def crashed(self) -> bool:
        # benign race: monotonic flag (False -> True once, never back)
        return self._crashed  # graftlint: guarded-by(_lock)

    def recovered(self) -> Dict[int, FrontDoorSession]:
        """Adoption map: the dead supervisor's sid -> the session this
        door resurrected for it (attached to a surviving worker,
        re-placed under a new sid, served from the result cache, or
        loudly failed if it was running and not replayable)."""
        with self._lock:
            return dict(self._recovered)

    def _adopt_locked(self):
        """Rebuild the fleet from the replayed journal: seed every
        counter past the dead generation's high-water marks, fence its
        dead generations (never past a survivor), pre-register
        surviving workers for resume-token reattach, and resurrect
        every journal-live session."""
        st = self._adopt_state
        now = time.monotonic()
        # a reused sid would collide with a surviving worker's dedup
        # table; a reused gen with the fence state of the generation
        # just revoked
        self._sids = itertools.count(st.max_sid + 1)
        self._gens = itertools.count(st.max_gen + 1)
        self._extra_slots = itertools.count(
            max(self._n_workers, st.max_slot + 1))
        # quota facts replay so a restart can't launder a tenant's
        # spent budget
        self._tenant_bytes = dict(st.tenant_bytes)
        self._tenant_seconds = dict(st.tenant_seconds)

        survivors: Dict[int, dict] = {}
        for slot, jw in st.workers.items():
            if jw["state"] != "alive" or jw["gen"] in st.revoked \
                    or jw["gen"] < st.stamped_floor:
                continue
            proc = _AdoptedProc(jw["pid"])
            if proc.poll() is None:
                survivors[slot] = dict(jw, proc=proc)
        # the generation handoff: revoke every non-surviving gen
        # surgically, raise the floor to the OLDEST survivor (or past
        # every known gen when nothing survived) — the dead
        # supervisor's generations can never zombie-commit, while the
        # survivors stay exactly as committable as before the crash
        alive_gens = {jw["gen"] for jw in survivors.values()}
        floor = min(alive_gens) if alive_gens else st.max_gen + 1
        dead_gens = sorted(set(st.all_gens) - alive_gens)
        # write-ahead, then fence, then rebuild: the adopt record marks
        # this journal as taken over, so a second restart replays both
        # generations to the same state (idempotence)
        self._jrec("adopt", floor=floor, dead_gens=dead_gens,
                   survivors=sorted(survivors),
                   truncated_tail=bool(st.truncated_tail))
        for g in dead_gens:
            self._jrec("revoke", gen=g)
        self._jrec("stamp", floor=floor)
        if self._store is not None:
            with contextlib.suppress(OSError):
                self._store.fence_handoff(dead_gens, floor)
        for slot, jw in sorted(survivors.items()):
            w = WorkerHandle(slot, jw["gen"], jw["wdir"], jw["proc"],
                             host=jw["host"], token=jw["token"])
            w.pool_bytes = self._pool_bytes
            w.ever_connected = True
            # an adopted worker is a live process behind a downed link:
            # its reconnect ladder re-dials the fleet address, our
            # partition grace bounds how long we wait for the hello
            w.state = "reconnecting"
            w.conn_lost_at = now
            self._workers[slot] = w
            self._respawn_count.setdefault(slot, 0)
            self.metrics.bump("adopted_workers")
            self.metrics.set_liveness(slot, "reconnecting")
            self._adopt_stats["adopted_workers"] += 1
        # base slots with no survivor get fresh incarnations
        for slot in range(self._n_workers):
            if slot not in self._workers:
                self._spawn_locked(slot)
        for sid, s in sorted(st.live_sessions().items()):
            self._resurrect_locked(sid, s, now)
        if self._autoscaler is not None:
            self._autoscaler.adopt_state(
                now, scale_downs=st.retired_count)

    def _resurrect_locked(self, old_sid: int, s: dict, now: float):
        """One journal-live session, three recovery paths: re-attach to
        its surviving worker (placed-but-unacked: the reattach hello's
        resend + the worker's sid dedup make delivery exactly-once in
        effect), serve from the handed-over result cache, or re-place
        through the ordinary backoff ladder under a FRESH sid."""
        kind = s.get("kind")
        if kind is None:
            return  # terminal-only stub: a result for an unseen sid
        slot, gen = s.get("slot"), s.get("gen")
        w = self._workers.get(slot) if slot is not None else None
        if s["status"] in ("placed", "running") and w is not None \
                and w.state != "dead" and w.gen == gen:
            sess = FrontDoorSession(
                self, old_sid, kind, s.get("params"), s.get("tenant"),
                int(s.get("priority") or 0),
                int(s.get("est_bytes") or 0), s.get("timeout_s"),
                bool(s.get("replayable", True)),
                snapshot=s.get("snapshot"))
            self._jrec("placed", sid=old_sid, slot=slot, gen=gen)
            sess.status = "placed"
            sess.worker_id = slot
            w.sessions[old_sid] = sess
            self._pins.setdefault(sess.tenant, slot)
            self.metrics.bump("recovered_sessions")
            self._adopt_stats["recovered_sessions"] += 1
            self._recovered[old_sid] = sess
            return
        # its worker died with the old supervisor
        sess = FrontDoorSession(
            self, next(self._sids), kind, s.get("params"),
            s.get("tenant"), int(s.get("priority") or 0),
            int(s.get("est_bytes") or 0), s.get("timeout_s"),
            bool(s.get("replayable", True)), snapshot=s.get("snapshot"))
        self._recovered[old_sid] = sess
        if s["status"] == "running" and not sess.replayable:
            self.metrics.bump("worker_lost")
            sess._finish(error=WorkerLost(
                f"session {old_sid} was running (not replayable) when "
                f"the supervisor died"))
            return
        if sess.snapshot is not None and self.result_cache.enabled():
            # completed work whose terminal record died with the crash:
            # the handed-over cache still holds the bytes — serve them,
            # never recompute
            if self._cache_probe(sess):
                self.metrics.bump("recovered_sessions")
                self._adopt_stats["recovered_sessions"] += 1
                return
        self._jrec("replayed", sid=old_sid, new_sid=sess.sid)
        self.metrics.bump("replayed_sessions")
        self._adopt_stats["replayed_sessions"] += 1
        self._enqueue_locked(now, sess)

    # -- public API -----------------------------------------------------
    def submit(self, kind: str, params: Optional[dict] = None, tenant=None,
               priority: int = 0, est_bytes: int = 0,
               timeout_s: Optional[float] = None,
               replayable: bool = True, snapshot=None) -> FrontDoorSession:
        """Queue a query of registered worker-side ``kind`` and return
        its session.  ``params`` must be JSON-serializable; everything
        else matches ``ServeRuntime.submit`` plus ``replayable`` (see
        :class:`FrontDoorSession`) and ``snapshot`` — the input's
        content snapshot id (see serve/result_cache.py).  With a
        snapshot declared, a repeat of the same ``(kind, params)``
        under the same knobs is served straight from the fleet result
        cache: the session finishes here, BEFORE admission — no shed
        check, no worker dispatch, no ticket, no compute."""
        # benign race: monotonic flag, re-checked under the lock by the
        # drain — a submit that slips past here is cancelled by shutdown
        if self._shutdown_started:  # graftlint: guarded-by(_lock)
            raise ServeError("front door is shut down")
        sid = next(self._sids)
        with profiler.span("serve.submit", sid=sid) as sp:
            sess = FrontDoorSession(
                self, sid, kind, params,
                tenant if tenant is not None else f"tenant-{sid}",
                priority, est_bytes, timeout_s, replayable,
                snapshot=snapshot)
            if snapshot is not None and self.result_cache.enabled() \
                    and self._cache_probe(sess):
                return sess
            now = time.monotonic()
            with self._lock:
                self._charge_admission_locked(sess)
                # write-ahead: the admission is durable before the
                # session is queued — a quota rejection above never
                # journals (the session was never admitted, replay must
                # not re-charge it)
                sess._stage("serve.journal", self._jrec(
                    "submit", sid=sid, kind=kind, params=sess.params,
                    tenant=str(sess.tenant), priority=sess.priority,
                    est_bytes=sess.est_bytes, timeout_s=sess.timeout_s,
                    replayable=sess.replayable, snapshot=sess.snapshot))
                self._enqueue_locked(now, sess)
                self._maybe_shed_locked()
                self._dispatch_locked(now)
        # a parent stage: it may close after a fast answer finished the
        # session, and is in no sum
        sess._stage("serve.submit", sp.ms)
        self._wake.set()
        return sess

    def _charge_admission_locked(self, sess: FrontDoorSession):
        """PR-9 policy remainder: per-tenant quotas, charged at
        admission.  Bytes are charged UP FRONT from the declared
        ``est_bytes``; wall-seconds accrue as sessions complete.  A
        tenant over either budget is rejected loudly — the shed ladder
        never sees the submit, the counters land in the report."""
        if self._quota_bytes <= 0 and self._quota_s <= 0:
            return
        t = str(sess.tenant)
        used_b = self._tenant_bytes.get(t, 0)
        used_s = self._tenant_seconds.get(t, 0.0)
        if self._quota_bytes > 0 \
                and used_b + sess.est_bytes > self._quota_bytes:
            self.metrics.bump("quota_rejections")
            self._quota_rejected[t] = self._quota_rejected.get(t, 0) + 1
            raise QuotaExceeded(
                f"tenant {t} over byte quota: {used_b} charged + "
                f"{sess.est_bytes} requested > serve_tenant_quota_bytes="
                f"{self._quota_bytes}", tenant=t, resource="bytes")
        if self._quota_s > 0 and used_s >= self._quota_s:
            self.metrics.bump("quota_rejections")
            self._quota_rejected[t] = self._quota_rejected.get(t, 0) + 1
            raise QuotaExceeded(
                f"tenant {t} over time quota: {used_s:.3f}s used >= "
                f"serve_tenant_quota_s={self._quota_s:g}", tenant=t,
                resource="seconds")
        self._tenant_bytes[t] = used_b + sess.est_bytes

    def _note_session_done(self, sess: FrontDoorSession):
        """Completion bookkeeping: charge the tenant's wall-clock and
        record the (kind, params) as the tenant class's warm plan-cache
        entry for future spawns.  Cache hits charge nothing — they cost
        no compute and ran no plan."""
        if sess.served_from_cache or sess.status != "done":
            return
        t = str(sess.tenant)
        dt = max(0.0, time.monotonic() - sess.submitted_at)
        with self._lock:
            self._tenant_seconds[t] = \
                self._tenant_seconds.get(t, 0.0) + dt
            if self._plan_warm_max > 0:
                cls = self._tenant_class(t)
                # re-insert to keep newest-class-last ordering
                self._plan_warmth.pop(cls, None)
                self._plan_warmth[cls] = {
                    "kind": sess.kind, "params": sess.params}

    def cancel(self, sess: FrontDoorSession):
        """Cancel wherever the session is: pending (finished here),
        placed/running (forwarded to its worker, which unwinds it
        kill-safe and reports ``cancelled``)."""
        link = None
        with self._lock:
            if sess._done.is_set():
                return
            sess._cancel_requested = True
            if sess.worker_id is None:
                self._pending = [e for e in self._pending if e[1] is not sess]
                sess._finish(error=QueryCancelled(
                    f"session {sess.sid} cancelled while pending"),
                    status="cancelled")
                return
            w = self._workers.get(sess.worker_id)
            if w is not None and w.link is not None and w.state == "healthy":
                link = w.link
        # the forward crosses a process boundary — never under the fleet
        # lock (a wedged worker pipe would stall every submit/monitor
        # tick behind this cancel)
        if link is not None:
            with contextlib.suppress(OSError):
                link.send({"op": "cancel", "sid": sess.sid})

    def sessions(self) -> List[FrontDoorSession]:
        with self._lock:
            out = [e[1] for e in self._pending]
            for w in self._workers.values():
                out.extend(w.sessions.values())
            return out

    def shutdown(self, timeout_s: float = 30.0) -> dict:
        """Drain the fleet: graceful ``shutdown`` to every live worker
        (its runtime cancels in-flight sessions kill-safe and reports a
        ``bye`` with residue), SIGKILL for stragglers, reap every worker
        directory, remove the fleet dir.  Returns a report with
        per-worker cleanliness, fleet counters, and any orphan spill
        files found before the reap.  Idempotent: later (or racing)
        calls wait for the first and return its report."""
        # benign race: monotonic flag, a crash racing this check still
        # reaps nothing (the drain below only touches workers it owns)
        if self._crashed:  # graftlint: guarded-by(_lock)
            # a dead supervisor owns NOTHING any more: the fleet dir,
            # journal, store, and workers belong to whichever door
            # adopts them — reaping here would destroy the very state
            # the recovery contract preserves
            return {"clean": False, "crashed": True, "workers": {}}
        with self._lock:
            first = not self._shutdown_started
            self._shutdown_started = True
        if not first:
            self._shutdown_done.wait(timeout_s + 10.0)
            return self._shutdown_result or {"clean": False, "workers": {}}
        self._stop.set()
        self._wake.set()
        self._monitor_thread.join(timeout=10.0)
        with contextlib.suppress(OSError):
            self._listener.close()
        self._accept_thread.join(timeout=10.0)

        report: dict = {"clean": True, "workers": {}, "orphan_spill_files": []}
        with self._lock:
            pending = [e[1] for e in self._pending]
            self._pending = []
            workers = list(self._workers.values())
        for sess in pending:
            sess._finish(error=QueryCancelled(
                f"session {sess.sid} cancelled: front door shutdown",
                reason="shutdown"), status="cancelled")
        for w in workers:
            if w.state != "dead" and w.link is not None:
                with contextlib.suppress(OSError):
                    w.link.send({"op": "shutdown"})
        deadline = time.monotonic() + timeout_s
        for w in workers:
            entry: dict
            if w.state == "dead":
                entry = {"state": "dead", "clean": True}
            else:
                try:
                    w.proc.wait(max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    w.kill()
                    with contextlib.suppress(Exception):
                        w.proc.wait(5.0)
                    entry = {"state": "wedged", "clean": False}
                else:
                    # the bye races the exit: the worker writes it and
                    # dies, and the frame can still sit in the socket
                    # buffer when waitpid returns — give the reader a
                    # bounded beat to drain it before classifying
                    grace = time.monotonic() + 2.0
                    while w.bye is None and time.monotonic() < grace:
                        time.sleep(0.01)
                    bye = w.bye or {}
                    residue = bye.get("residue") or [0, 0]
                    entry = {
                        "state": "ok" if bye else "no-bye",
                        "clean": bool(bye.get("clean")) and not any(residue)
                        and not bye.get("leftovers")
                        and not bye.get("store_len"),
                        "residue": residue,
                        "leftovers": bye.get("leftovers", []),
                    }
                self._merge_fired(w)
                w.state = "dead"
                self.metrics.set_liveness(w.worker_id, "shutdown")
            w.close()
            for sess in list(w.sessions.values()):
                sess._finish(error=QueryCancelled(
                    f"session {sess.sid} cancelled: front door shutdown",
                    reason="shutdown"), status="cancelled")
            w.sessions = {}
            entry["host"] = w.host
            report["workers"][w.worker_id] = entry
            report["clean"] = report["clean"] and entry["clean"]
        # zero-orphan-spill-files invariant, checked BEFORE the reap:
        # a gracefully drained worker leaves an empty spill dir, a
        # killed one had its dir reaped at loss time.  The durable
        # store's subtree is EXCLUDED — its files are supposed to
        # survive the workers, they are not spill residue.
        for root, dirs, files in os.walk(self.fleet_dir):
            if self.store_dir is not None:
                dirs[:] = [d for d in dirs
                           if os.path.join(root, d) != self.store_dir]
            for f in files:
                if "spill" in root.split(os.sep)[-1:] or f.endswith(".spill"):
                    report["orphan_spill_files"].append(
                        os.path.join(root, f))
        report["clean"] = report["clean"] and not report["orphan_spill_files"]
        report["fleet"] = self.metrics.snapshot()
        report["transport"] = self._transport
        fleet = report["fleet"]
        report["data_plane"] = {
            "plane": self._data_plane,
            "segment_bytes": self._segment_bytes,
            "batches": fleet["data_batches"],
            "payload_bytes": fleet["data_payload_bytes"],
            "json_bytes": fleet["data_json_bytes"],
            "errors": fleet["data_plane_errors"],
        }
        report["hosts"] = list(self._hosts)
        report["self_fenced"] = list(self._self_fenced)
        report["retired"] = list(self._retired)
        if self._autoscaler is not None:
            self._autoscaler.stop()
            report["autoscale"] = self._autoscaler.snapshot()
        report["launcher"] = getattr(self._launcher, "name", "local")
        self._launcher.close()
        report["placement"] = self._placement.mode
        # quota counters are mutated under the fleet lock by completion
        # bookkeeping; snapshot them the same way (a straggler
        # _note_session_done may still be finishing a cancelled session)
        with self._lock:
            report["quota"] = {
                "quota_bytes": self._quota_bytes,
                "quota_s": self._quota_s,
                "tenant_bytes": dict(self._tenant_bytes),
                "tenant_seconds": {t: round(s, 6) for t, s
                                   in self._tenant_seconds.items()},
                "rejections": dict(self._quota_rejected),
            }
        report["result_cache"] = self.result_cache.metrics()
        # entries ride spill handles: close them so arena charges and
        # demoted disk files release before the fleet dir reap
        self.result_cache.clear()
        if self._store is not None:
            report["store"] = self._store.snapshot()
        report["recovery"] = dict(self._adopt_stats)
        report["recovery"]["adopted_fleet"] = self._adopt_state is not None
        if self._journal is not None:
            report["recovery"]["journal_appends"] = self._journal.appended
            self._journal.close()
        retain = self.store_dir is not None \
            and bool(config.get("shuffle_store_retain"))
        if retain and self.store_dir.startswith(self.fleet_dir + os.sep):
            # retain ONLY the store: reap every other fleet entry (the
            # fleet dir itself must survive to hold the store)
            for entry in os.listdir(self.fleet_dir):
                p = os.path.join(self.fleet_dir, entry)
                if p == self.store_dir:
                    continue
                if os.path.isdir(p):
                    shutil.rmtree(p, ignore_errors=True)
                else:
                    with contextlib.suppress(OSError):
                        os.unlink(p)
        else:
            # default: the store dies with the fleet dir.  An external
            # ``store_dir=`` is outside the fleet dir and never reaped —
            # the front door doesn't own it.
            shutil.rmtree(self.fleet_dir, ignore_errors=True)
        self._shutdown_result = report
        self._shutdown_done.set()
        return report

    # -- spawning -------------------------------------------------------
    def _child_fault_config(self) -> Optional[dict]:
        """The supervisor's live fault schedule, with each rule's count
        decremented by the firings already merged from the fleet — so a
        respawned replacement doesn't re-arm a fault the fleet already
        absorbed (the fleet-wide occurrence clock)."""
        cfg = faultinj.current_config()
        if not cfg.get("faults"):
            return None
        fired = faultinj.fired_log()
        out = []
        for spec in cfg["faults"]:
            spec = dict(spec)
            cnt = spec.get("count")
            if cnt is not None:
                used = sum(
                    1 for e in fired
                    if e.get("match") == spec.get("match", "*")
                    and e.get("fault") == spec.get("fault", "exception"))
                left = int(cnt) - used
                if left <= 0:
                    continue
                spec["count"] = left
            out.append(spec)
        if not out:
            return None
        return {"seed": cfg.get("seed", 0), "faults": out}

    def _spawn_locked(self, slot: int) -> Optional[WorkerHandle]:
        gen = next(self._gens)
        wdir = os.path.join(self.fleet_dir, f"worker-{slot}-{gen}")
        os.makedirs(wdir, exist_ok=True)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        fault_cfg = self._child_fault_config()
        if fault_cfg is not None:
            cfg_path = os.path.join(wdir, "fault.json")
            with open(cfg_path, "w") as f:
                json.dump(fault_cfg, f)
            env[faultinj.ENV_CONFIG] = cfg_path
        else:
            # the supervisor's live schedule is authoritative — don't
            # let a stale inherited env re-arm faults in the child
            env.pop(faultinj.ENV_CONFIG, None)
        env[faultinj.ENV_MIRROR] = os.path.join(wdir, "fired.jsonl")
        host = self._placement.host_for_slot(slot, self._workers.values())
        token = f"{slot}-{gen}-{os.urandom(8).hex()}"
        cmd = [sys.executable, "-m", "spark_rapids_jni_tpu.serve.worker",
               "--socket", self._sock_addr,
               "--transport", self._transport,
               "--worker-id", str(slot),
               "--dir", wdir,
               "--host", host,
               "--resume-token", token,
               "--partition-grace-ms", str(self._grace_s * 1000.0),
               "--orphan-grace-ms",
               str(float(config.get("serve_orphan_grace_ms"))),
               "--reconnect-max", str(self._reconnect_max),
               "--pool-bytes", str(self._pool_bytes),
               "--host-pool-bytes", str(self._host_pool_bytes),
               "--max-concurrent", str(self._max_concurrent),
               "--task-id-base", str(10_000 + slot * 1_000),
               "--data-plane", self._data_plane,
               "--segment-bytes", str(self._segment_bytes)]
        # the gen doubles as the store's fencing epoch AND the hello's
        # fence_epoch: commits from this incarnation are keyed
        # attempt-<gen> and revocable the moment the supervisor declares
        # it lost, and an attach claiming any other epoch is refused
        cmd += ["--epoch", str(gen)]
        if self.store_dir is not None:
            cmd += ["--store-dir", self.store_dir]
        if self._setup:
            cmd += ["--setup", self._setup]
        warm = self._warm_entries()
        if warm:
            warm_path = os.path.join(wdir, "warm.json")
            with open(warm_path, "w") as f:
                json.dump(warm, f)
            cmd += ["--warm", warm_path]
            self.metrics.bump("plan_warm_shipped", len(warm))
        # the launcher owns HOW the argv becomes a process (local fork
        # or an agent/ssh template); a launch that dies at the boundary
        # (real, or the scale_up_fail kind at launcher_spawn) is a
        # capacity loss, not a crash: count it and keep the slot on the
        # respawn ladder instead of stranding queued sessions
        try:
            proc = self._launcher.launch(
                cmd, cwd=pkg_root, env=env,
                log_path=os.path.join(wdir, "worker.log"))
        except (faultinj.ScaleUpFailError, OSError):
            self.metrics.bump("scale_up_failures")
            self.metrics.set_liveness(slot, "spawn-failed")
            shutil.rmtree(wdir, ignore_errors=True)
            self._respawn_count[slot] = \
                self._respawn_count.get(slot, 0) + 1
            if self._respawn_count[slot] > self._respawn_max:
                self._broken.add(slot)
                self.metrics.bump("circuit_open")
                self.metrics.set_liveness(slot, "broken")
            else:
                delay = max(self._backoff_s, 0.05) * (
                    2 ** (self._respawn_count[slot] - 1))
                self._respawn_at[slot] = time.monotonic() + delay
            return None
        w = WorkerHandle(slot, gen, wdir, proc, host=host, token=token)
        w.pool_bytes = self._pool_bytes
        # write-ahead fleet fact: the incarnation exists (pid + resume
        # token + fencing epoch) before the fleet table says so — an
        # adopting supervisor can only re-attach workers it can prove
        self._jrec("spawn", slot=slot, gen=gen,
                   pid=int(getattr(proc, "pid", 0) or 0), token=token,
                   host=host, wdir=wdir)
        self._workers[slot] = w
        self.metrics.bump("workers_spawned")
        self.metrics.set_liveness(slot, "starting")
        return w

    def _tenant_class(self, tenant) -> str:
        text = str(tenant)
        head, sep, _tail = text.rpartition("-")
        return head if sep else text

    def _warm_entries(self) -> List[dict]:
        """The warm plan-cache hand-off for a new worker: the last
        completed (kind, params) per tenant class, newest classes
        first, capped at ``serve_plan_warm`` entries."""
        if self._plan_warm_max <= 0:
            return []
        out = list(self._plan_warmth.values())
        return out[-self._plan_warm_max:]

    # -- accept/reader threads ------------------------------------------
    def _accept_loop(self):
        while not self._stop.is_set():
            try:
                conn, _addr = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            link = wire.wrap(conn, self._transport, role="sup")
            try:
                link.settimeout(5.0)
                hello = link.recv()
                slot = int(hello.get("worker_id", -1))
                pid = hello.get("pid")
                token = hello.get("resume_token", "")
                epoch = int(hello.get("fence_epoch", -1))
            except (wire.WireError, socket.timeout, OSError, ValueError):
                link.close()
                continue
            with self._lock:
                w = self._workers.get(slot)
                # pid identity routes through the launch handle: local
                # workers must present the forked child's pid; remote
                # ones have their first hello's pid adopted (the token +
                # epoch prove the incarnation) and held ever after
                owns = getattr(w.proc, "owns_pid", None) \
                    if w is not None else None
                pid_ok = owns(pid) if owns is not None \
                    else (w is not None and w.proc.pid == pid)
                if w is None or w.state == "dead" or not pid_ok \
                        or w.token != token or w.gen != epoch:
                    # a stale incarnation raced its own SIGKILL, or the
                    # resume token / fence epoch doesn't match the slot's
                    # live generation: drop it — only the incarnation we
                    # spawned may attach to these sessions
                    link.close()
                    continue
                if w.ever_connected:
                    # the same incarnation re-dialled after a link loss:
                    # resume-token reattach, sessions stay live
                    self.metrics.bump("reconnects")
                w.ever_connected = True
                w.backend = hello.get("backend")
                self.metrics.set_backend(slot, " ".join(
                    str(hello[k]) for k in ("platform", "device_kind")
                    if hello.get(k)))
                link.settimeout(0.2)  # reader poll tick (supersession)
                old, w.link = w.link, link
                if old is not None:
                    old.close()
                w.state = "healthy"
                w.last_pong = time.monotonic()
                self.metrics.set_liveness(slot, "healthy")
                # at-least-once re-delivery: a submit in flight when the
                # old link died (or whose "running" ack died) was lost
                # with it — re-send every placed-but-unacked session; the
                # worker dedups by sid, so a duplicate is a re-ack, never
                # a second run.  Payloads are captured under the lock,
                # sent after release: the sends cross a process boundary
                # and must not wedge the fleet lock behind a slow pipe.
                resend = [
                    {"op": "submit", "sid": sess.sid,
                     "kind": sess.kind, "params": sess.params,
                     "tenant": str(sess.tenant),
                     "priority": sess.priority,
                     "est_bytes": sess.est_bytes,
                     "timeout_s": sess.timeout_s,
                     "snapshot": sess.snapshot}
                    for sess in list(w.sessions.values())
                    if sess.status == "placed" and not sess._done.is_set()]
                # a cancel issued while this link was down had no pipe
                # to ride (FrontDoor.cancel only forwards to a healthy
                # link) — re-forward it now; the worker's unwind is
                # idempotent, so a duplicate cancel is a no-op
                recancel = [sess.sid for sess in list(w.sessions.values())
                            if sess._cancel_requested
                            and not sess._done.is_set()]
                # adoption reconciliation: the hello's active_sids are
                # what the worker ACTUALLY holds — any sid we no longer
                # track (the journal never committed its placement, or
                # a data-retry moved the session to a fresh sid) is
                # cancelled worker-side rather than left computing for
                # a supervisor that will drop its result
                stale_sids = [int(s) for s in
                              (hello.get("active_sids") or [])
                              if int(s) not in w.sessions]
                reader_name = f"frontdoor-reader-{slot}-{w.gen}"
            for payload in resend:
                try:
                    link.send(payload)
                except OSError:
                    break  # link died again: next reattach retries
            for sid in stale_sids + recancel:
                with contextlib.suppress(OSError):
                    link.send({"op": "cancel", "sid": sid})
            threading.Thread(
                target=self._reader, args=(w, link),
                name=reader_name, daemon=True).start()
            self._wake.set()

    def _reader(self, w: WorkerHandle, link: wire.Transport):
        try:
            self._reader_loop(w, link)
        except (faultinj.SupervisorCrash, faultinj.JournalTornError):
            return  # this process just became a dead supervisor

    def _reader_loop(self, w: WorkerHandle, link: wire.Transport):
        while True:
            if w.link is not link:
                return  # superseded by a reattached connection
            try:
                msg = link.recv()
            except socket.timeout:
                continue
            except (wire.WireError, OSError, ValueError):
                # the CONNECTION died — not necessarily the worker: hand
                # the slot to reconnect supervision, not the loss protocol
                self._on_conn_lost(w, link)
                return
            if isinstance(msg, wire.DataChunk):
                # frames plane: stash the chunk for its descriptor —
                # stream ordering guarantees it lands before the result
                with self._lock:
                    w.data_stash.setdefault(msg.sid, []).append(
                        (msg.seq, msg.payload))
                continue
            op = msg.get("op")
            if op == "pong":
                self._on_pong(w, msg)
            elif op == "running":
                with self._lock:
                    sess = w.sessions.get(int(msg.get("sid", -1)))
                    if sess is not None and not sess._done.is_set():
                        self._jrec("running", sid=sess.sid)
                        sess.status = "running"
            elif op == "result":
                self._on_result(w, msg)
            elif op == "bye":
                w.bye = msg
                w.fired = list(msg.get("fired") or [])
                w.last_pong = time.monotonic()

    def _on_conn_lost(self, w: WorkerHandle, link: wire.Transport):
        """Connection supervision: the link died but the process may be
        fine.  Park the slot in ``reconnecting`` — sessions stay placed,
        the worker's ladder re-dials, and only the monitor's partition
        window (``serve_partition_grace_ms``) escalates to the loss
        protocol."""
        link.close()
        with self._lock:
            if w.link is link:
                w.link = None
                if w.state == "healthy":
                    w.state = "reconnecting"
                    w.conn_lost_at = time.monotonic()
                    self.metrics.set_liveness(w.worker_id, "reconnecting")
        self._wake.set()

    def _on_pong(self, w: WorkerHandle, msg: dict):
        with self._lock:
            w.last_pong = time.monotonic()
            w.fired = list(msg.get("fired") or [])
            # load signals for the placement scorer: the worker's own
            # admission-queue depth and arena residency ride every pong
            w.queue_depth = int(msg.get("queue_depth") or 0)
            w.arena_bytes = int(msg.get("arena_bytes") or 0)
            w.pool_bytes = int(msg.get("pool_bytes") or w.pool_bytes or 0)
            epoch = int(msg.get("stall_breaks") or 0)
            live = int(msg.get("live_sessions") or 0)
            # the native stall-breaker epoch backs the wedge detector: an
            # epoch that keeps climbing while nothing completes means the
            # breaker is firing but the worker isn't recovering
            if epoch > w.stall_breaks and live > 0 \
                    and w.results_since_pong == 0:
                w.stall_suspect += 1
            else:
                w.stall_suspect = 0
            w.stall_breaks = epoch
            w.results_since_pong = 0

    def _rebuild_error(self, msg: dict) -> BaseException:
        err = msg.get("error") or "ServeError"
        text = msg.get("message") or ""
        if err == "QueryCancelled":
            return QueryCancelled(text)
        if err == "QueryTimeout":
            return QueryTimeout(text)
        for cls in (faultinj.TaskCancelled, faultinj.InjectedFault,
                    faultinj.FatalInjectedFault, faultinj.WorkerCrash,
                    faultinj.WorkerStalled):
            if err == cls.__name__:
                return cls(text)
        if err in ("RetryOOM", "CpuRetryOOM", "SplitAndRetryOOM"):
            from ..mem import RetryOOM
            return RetryOOM(text)
        if err == "DataPlaneOverflow":
            return data_plane.DataPlaneOverflow(text)
        return ServeError(f"{err}: {text}")

    def _decode_data_result(self, sess: FrontDoorSession, w: WorkerHandle,
                            desc: dict, chunks: Optional[list],
                            fds: List[int]):
        """Verify (epoch, then per-chunk CRCs) and decode one data-plane
        payload into ``(ColumnBatch, verified payload bytes)`` — the
        bytes feed the result cache in their already-encoded form.
        Raises :class:`~.data_plane.DataPlaneStale` /
        :class:`~.data_plane.DataPlaneCorruption` — the TRANSFER failed,
        not the query; the caller re-queues under a fresh sid."""
        from ..columnar import arrow as arrow_mod

        # epoch before bytes: a stale generation's segment must be
        # rejected before anything in it is interpreted
        data_plane.verify_epoch(desc, w.gen)
        plane = desc.get("plane")
        if plane == "shm":
            if not fds:
                raise wire.WireError(
                    f"shm descriptor for segment {desc.get('seg')} "
                    f"arrived without its fd")
            # the copy out of the mapping, then its chunk CRCs
            with profiler.span("serve.segment_read", sink=sess._stage):
                payload = data_plane.read_segment(fds[0], desc)
        elif plane in ("frames", "json"):
            with profiler.span("serve.verify", sink=sess._stage):
                if plane == "frames":
                    parts = sorted(chunks or [], key=lambda e: e[0])
                    payload = b"".join(p for _seq, p in parts)
                else:
                    payload = data_plane.decode_json_payload(
                        desc.get("inline") or "")
                data_plane.verify_chunks(payload, desc)
        else:
            raise wire.WireError(f"unknown data plane {plane!r} in "
                                 f"result descriptor")
        with profiler.span("serve.ipc_to_batch", sink=sess._stage):
            batch = arrow_mod.ipc_to_batch(
                payload, expect_fingerprint=desc.get("schema_fp"))
        return batch, payload

    def _cache_probe(self, sess: FrontDoorSession) -> bool:
        """Look ``sess`` up in the result cache and, on a verified hit,
        finish it: True says it was served here.  Lookup, re-seal,
        verification and decode are the ``serve.cache_probe`` stage."""
        sig = result_cache_mod.query_signature(sess.kind, sess.params)
        fp = result_cache_mod.knob_fingerprint()
        sess.cache_key = (sig, sess.snapshot, fp)
        with profiler.span("serve.cache_probe", sid=sess.sid,
                           sink=sess._stage):
            view = self.result_cache.serve(sig, sess.snapshot, fp)
            value = None if view is None \
                else self._read_cache_hit(sess, view)
        if value is None:
            return False
        sess.served_from_cache = True
        sess._finish(value=value, status="done")
        return True

    def _read_cache_hit(self, sess: FrontDoorSession, view):
        """Read a cached result under a FRESH descriptor, verified
        exactly like a live result: the stored bytes go into a new
        sealed memfd, the descriptor carries the insert-time chunk CRCs
        and the entry's snapshot id, and epoch → snapshot → CRC →
        schema-fingerprint checks all run before the session finishes.
        Returns None on any rejection (stale snapshot, damage) — the
        caller falls through to a live dispatch, so a bad entry costs a
        recompute, never a wrong answer."""
        from ..columnar import arrow as arrow_mod

        name = data_plane.segment_name(
            "cache", self._cache_gen, next(self._cache_seq))
        desc = data_plane.build_descriptor(
            "shm", name, view.size, view.schema_fp, view.chunk_bytes,
            view.crcs, self._cache_gen, snapshot=view.snapshot)
        fd = data_plane.make_segment(name, view.payload)
        try:
            data_plane.seal_segment(fd)
            data_plane.verify_epoch(desc, self._cache_gen)
            # the exactness fence: the descriptor's snapshot must equal
            # the snapshot THIS submit declared — a rewound entry is
            # rejected here, a stale snapshot is never served
            data_plane.verify_snapshot(desc, sess.snapshot)
            payload = data_plane.read_segment(fd, desc)
            value = arrow_mod.ipc_to_batch(
                payload, expect_fingerprint=desc.get("schema_fp"))
        except data_plane.DataPlaneStale:
            self.result_cache.record_stale(view.key)
            return None
        except (data_plane.DataPlaneCorruption, wire.WireError,
                ValueError, OSError):
            self.result_cache.quarantine(view.key)
            return None
        finally:
            with contextlib.suppress(OSError):
                os.close(fd)
        self.metrics.bump("cache_hits")
        self.metrics.bump("hit_bytes_served", view.size)
        self.result_cache.record_hit(view.size)
        return value

    def _requeue_data_damaged(self, sess: FrontDoorSession, w: WorkerHandle,
                              exc: BaseException):
        """A data-plane transfer was damaged (torn payload, stale
        segment, fd gone missing): the query succeeded worker-side, only
        the hop failed.  Re-run it under a FRESH sid — the worker dedups
        by sid, so re-submitting the old one would be swallowed — within
        the same bounded budget; non-replayable queries fail loudly."""
        self.metrics.bump("data_plane_errors")
        with self._lock:
            sess.data_retries += 1
            if not sess.replayable or sess.data_retries > self._replace_max:
                sess._finish(error=exc, status="failed")
                return
            new_sid = next(self._sids)
            self._jrec("requeued", sid=sess.sid, new_sid=new_sid)
            sess.sid = new_sid
            sess.status = "pending"
            sess.worker_id = None
            self._enqueue_locked(
                time.monotonic() + self._backoff_s
                * (2 ** (sess.data_retries - 1)), sess)
            self._dispatch_locked(time.monotonic())
        self._wake.set()

    def _on_result(self, w: WorkerHandle, msg: dict):
        sid = int(msg.get("sid", -1))
        desc = msg.get("data")
        with self._lock:
            sess = w.sessions.pop(sid, None)
            w.results_since_pong += 1
            w.stall_suspect = 0
            chunks = w.data_stash.pop(sid, None)
        # the fd rides the descriptor frame: claim it even for a
        # deduplicated re-delivery, or the stash misaligns for the next
        # descriptor on this connection
        fds: List[int] = []
        if desc is not None and desc.get("plane") == "shm":
            link = w.link
            if link is not None:
                with contextlib.suppress(wire.WireError):
                    fds = link.take_fds(int(desc.get("fds", 1)))
        try:
            if sess is None:
                return
            # the worker's stage durations ride the result frame
            for stage, ms in (msg.get("stages") or {}).items():
                sess._stage(str(stage), float(ms))
            if msg.get("ok"):
                if desc is not None:
                    try:
                        with profiler.span("serve.decode", sid=sess.sid,
                                           sink=sess._stage):
                            value, payload = self._decode_data_result(
                                sess, w, desc, chunks, fds)
                    except (data_plane.DataPlaneStale,
                            data_plane.DataPlaneCorruption,
                            wire.WireError, ValueError, OSError) as e:
                        self._requeue_data_damaged(sess, w, e)
                        return
                    self.metrics.bump("data_batches")
                    self.metrics.bump("data_payload_bytes",
                                      int(desc.get("size") or 0))
                    self.metrics.bump("data_json_bytes", len(json.dumps(
                        msg, separators=(",", ":"))))
                    # result-cache insert: only with the submit-time key
                    # AND a worker echo matching the declared snapshot —
                    # provenance proven, never a guess
                    if (sess.cache_key is not None
                            and desc.get("snapshot") == sess.snapshot):
                        sig, snap, fp = sess.cache_key
                        self.result_cache.insert(
                            sig, snap, fp, payload,
                            desc.get("schema_fp"), tenant=sess.tenant,
                            chunk_bytes=self._segment_bytes)
                    sess._finish(value=value, status="done")
                else:
                    sess._finish(value=msg.get("value"), status="done")
            else:
                status = msg.get("status") or "failed"
                sess._finish(error=self._rebuild_error(msg),
                             status=status if status in
                             ("cancelled", "timeout", "failed") else "failed")
        finally:
            for fd in fds:
                with contextlib.suppress(OSError):
                    os.close(fd)
        self._wake.set()

    # -- monitor loop ---------------------------------------------------
    def _monitor_loop(self):
        try:
            self._monitor_ticks()
        except (faultinj.SupervisorCrash, faultinj.JournalTornError):
            return  # this process just became a dead supervisor

    def _monitor_ticks(self):
        while not self._stop.is_set():
            self._wake.wait(self._hb_s)
            self._wake.clear()
            if self._stop.is_set():
                return
            now = time.monotonic()
            to_ping = []
            with self._lock:
                for w in list(self._workers.values()):
                    if w.state == "dead":
                        continue
                    if w.proc.poll() is not None:
                        if w.retiring and w.bye is not None:
                            # the drain ladder completed: the worker
                            # drained, self-fenced its generation, said
                            # bye, and exited — reap, don't respawn
                            self._on_worker_retired_locked(w)
                        else:
                            self._on_worker_lost_locked(
                                w, f"exited rc={w.proc.returncode}",
                                "crashes", now)
                        continue
                    if w.retiring and now > w.drain_deadline:
                        # drain stuck (the drain_stuck kind, or a real
                        # wedge): escalate to the ordinary loss protocol
                        w.kill()
                        self._on_worker_lost_locked(
                            w, "drain stuck past serve_autoscale_drain_ms",
                            "stalls", now)
                        continue
                    if w.state == "healthy":
                        if w.link is not None:
                            to_ping.append(w.link)
                        if now - w.last_pong > self._hb_s * _MISS_BUDGET:
                            w.kill()
                            self._on_worker_lost_locked(
                                w, "missed heartbeats", "stalls", now)
                            continue
                        if w.stall_suspect >= _STALL_EPOCH_LIMIT:
                            w.kill()
                            self._on_worker_lost_locked(
                                w, "stall epoch climbing without progress",
                                "stalls", now)
                            continue
                    elif w.state == "reconnecting":
                        # connection supervision: wait out the worker's
                        # reconnect ladder; a link silent past the
                        # partition grace IS a partition — the worker
                        # self-fences on its side, we re-place on ours
                        if now - w.conn_lost_at > \
                                self._grace_s + self._hb_s * _MISS_BUDGET:
                            w.kill()
                            self._on_worker_lost_locked(
                                w, "connection lost past the partition "
                                "grace", "partitions_detected", now)
                    elif now - w.spawned_at > _STARTUP_GRACE_S:
                        w.kill()
                        self._on_worker_lost_locked(
                            w, "never connected", "crashes", now)
                self._maybe_respawn_locked(now)
                self._autoscale_tick_locked(now)
                self._maybe_shed_locked()
                self._dispatch_locked(now)
            # pings cross process boundaries: sent after the fleet lock
            # drops so one wedged pipe can't stall dispatch/admission
            # for the whole tick (a link killed above just raises into
            # the suppress)
            for link in to_ping:
                with contextlib.suppress(OSError):
                    link.send({"op": "ping", "t": now})

    def _merge_fired(self, w: WorkerHandle):
        """Merge the worker's injection trace into this process's log —
        the durable mirror file is authoritative (it survives SIGKILL);
        the last pong's copy is the fallback."""
        if w.merged:
            return
        w.merged = True
        entries: List[dict] = []
        mirror = os.path.join(w.dir, "fired.jsonl")
        try:
            with open(mirror) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        with contextlib.suppress(ValueError):
                            entries.append(json.loads(line))
        except OSError:
            entries = list(w.fired)
        if entries:
            faultinj.record_external(
                entries, source=f"worker-{w.worker_id}-{w.gen}")
            w.fired = entries

    def _on_worker_lost_locked(self, w: WorkerHandle, why: str,
                               kind: str, now: float):
        self._jrec("loss", slot=w.worker_id, gen=w.gen, why=why)
        w.state = "dead"
        self.metrics.bump(kind)
        self.metrics.set_liveness(w.worker_id, "dead")
        w.close()
        self._merge_fired(w)
        fired = list(w.fired)
        # a self-fence sentinel means the worker saw the partition from
        # its side and already revoked its own epoch before exiting —
        # count it (the supervisor's revoke below is then a no-op)
        sentinel = None
        with contextlib.suppress(OSError, ValueError):
            with open(os.path.join(w.dir, "self-fenced.json")) as f:
                sentinel = json.load(f)
        if sentinel is not None:
            self.metrics.bump("self_fenced_workers")
            self._self_fenced.append(sentinel)
            if kind != "partitions_detected":
                self.metrics.bump("partitions_detected")
        # fence the dead generation FIRST — a zombie can outlive its
        # SIGKILL verdict and must never commit late — then reap only
        # its UNcommitted tmp remnants: the committed shards are exactly
        # what the replacement adopts instead of re-running
        if self._store is not None:
            self._jrec("revoke", gen=w.gen)
            with contextlib.suppress(OSError):
                self._store.revoke(w.gen)
                self._store.reap_uncommitted(epoch=w.gen)
        # reap the dead worker's spill files (and its whole directory)
        shutil.rmtree(w.dir, ignore_errors=True)
        # triage its sessions: re-place what never ran (or is declared
        # replayable) through the bounded backoff ladder; fail the rest
        for sess in list(w.sessions.values()):
            if sess._done.is_set():
                continue
            if sess._cancel_requested:
                sess._finish(error=QueryCancelled(
                    f"session {sess.sid} cancelled (worker "
                    f"{w.worker_id} lost mid-cancel)"), status="cancelled")
            elif (sess.status != "running" or sess.replayable) \
                    and sess.replacements < self._replace_max:
                sess.replacements += 1
                self.metrics.bump("replacements")
                self._jrec("requeued", sid=sess.sid)
                sess.status = "pending"
                sess.worker_id = None
                not_before = now + self._backoff_s * (
                    2 ** (sess.replacements - 1))
                self._enqueue_locked(not_before, sess)
            else:
                self.metrics.bump("worker_lost")
                budget = "" if sess.status != "running" or sess.replayable \
                    else " (in flight, not replayable)"
                sess._finish(error=WorkerLost(
                    f"session {sess.sid} lost with worker {w.worker_id} "
                    f"({why}){budget or ' (re-placement budget exhausted)'}",
                    worker_id=w.worker_id, fired_log=fired))
        w.sessions = {}
        # reap the data plane with the worker: partial chunk stashes die
        # here, and any unclaimed segment fds were closed with the
        # transport in w.close() above — a crash with a segment
        # outstanding leaks nothing
        w.data_stash = {}
        # a retiring worker that died (stuck drain escalated, or a crash
        # mid-drain) still retires: the generation is fenced above, its
        # sessions were re-placed above — record it and DON'T respawn,
        # the autoscaler shrank the fleet on purpose
        if w.retiring:
            self.metrics.bump("scale_downs")
            self._retired.append({
                "worker_id": w.worker_id, "gen": w.gen, "host": w.host,
                "clean": False, "fenced_commits": 0, "drained": False,
            })
            self._workers.pop(w.worker_id, None)
            self._respawn_at.pop(w.worker_id, None)
            return
        # schedule the replacement, unless this slot's breaker is open
        if w.worker_id in self._broken:
            return
        self._respawn_count[w.worker_id] = \
            self._respawn_count.get(w.worker_id, 0) + 1
        if self._respawn_count[w.worker_id] > self._respawn_max:
            self._broken.add(w.worker_id)
            self.metrics.bump("circuit_open")
            self.metrics.set_liveness(w.worker_id, "broken")
        else:
            delay = max(self._backoff_s, 0.05) * (
                2 ** (self._respawn_count[w.worker_id] - 1))
            self._respawn_at[w.worker_id] = now + delay

    def _maybe_respawn_locked(self, now: float):
        for slot, due in list(self._respawn_at.items()):
            if now < due or self._shutdown_started:
                continue
            del self._respawn_at[slot]
            w = self._workers.get(slot)
            if w is not None and w.state != "dead":
                continue
            self.metrics.bump("respawns")
            self._spawn_locked(slot)

    # -- elastic control loop -------------------------------------------
    def _autoscale_tick_locked(self, now: float):
        if self._autoscaler is None or self._shutdown_started:
            return
        decision = self._autoscaler.decide(
            now, len(self._pending), list(self._workers.values()))
        if decision is None:
            return
        action, target = decision
        if action == "up":
            slot = next(self._extra_slots)
            self._respawn_count.setdefault(slot, 0)
            self.metrics.bump("scale_ups")
            self._spawn_locked(slot)
        elif action == "down" and target is not None:
            self._retire_locked(target, now)

    def _retire_locked(self, w: WorkerHandle, now: float):
        """Start the retirement ladder: drain order now, the worker
        drains and self-fences its generation, the monitor reaps its
        bye — or the drain deadline escalates to the loss protocol."""
        if w.retiring or w.state != "healthy" or w.link is None:
            return
        w.retiring = True
        w.drain_deadline = now + self._drain_s
        self.metrics.set_liveness(w.worker_id, "draining")
        # un-pin its tenants: new submits re-pin onto surviving workers
        # through the ordinary placement path (queued-session migration)
        self._pins = {t: s for t, s in self._pins.items()
                      if s != w.worker_id}
        with contextlib.suppress(OSError):
            w.link.send({"op": "drain"})

    def _on_worker_retired_locked(self, w: WorkerHandle):
        """A retiring worker completed its drain -> self-fence -> exit
        ladder: reap it, shrink the fleet, never respawn it."""
        self._jrec("retired", slot=w.worker_id, gen=w.gen)
        w.state = "dead"
        self.metrics.set_liveness(w.worker_id, "retired")
        self._merge_fired(w)
        bye = w.bye or {}
        # the worker already revoked its OWN epoch before the bye; the
        # supervisor-side revoke + tmp reap is the idempotent backstop
        if self._store is not None:
            self._jrec("revoke", gen=w.gen)
            with contextlib.suppress(OSError):
                self._store.revoke(w.gen)
                self._store.reap_uncommitted(epoch=w.gen)
        shutil.rmtree(w.dir, ignore_errors=True)
        # a drained worker has no sessions; any straggler that raced the
        # bye migrates through the ordinary re-placement ladder
        now = time.monotonic()
        for sess in list(w.sessions.values()):
            if sess._done.is_set():
                continue
            sess.replacements += 1
            self.metrics.bump("replacements")
            self._jrec("requeued", sid=sess.sid)
            sess.status = "pending"
            sess.worker_id = None
            self._enqueue_locked(now, sess)
        w.sessions = {}
        w.data_stash = {}
        w.close()
        w.kill()
        with contextlib.suppress(Exception):
            w.proc.wait(2.0)
        self.metrics.bump("scale_downs")
        self._retired.append({
            "worker_id": w.worker_id, "gen": w.gen, "host": w.host,
            "clean": bool(bye.get("clean")),
            "fenced_commits": int(bye.get("fenced_commits") or 0),
            "drained": True,
        })
        self._workers.pop(w.worker_id, None)
        self._respawn_at.pop(w.worker_id, None)
        self._wake.set()

    def _alive_workers(self) -> List[WorkerHandle]:
        return [w for w in self._workers.values()
                if w.state in ("starting", "healthy")]

    def _maybe_shed_locked(self):
        alive = self._alive_workers()
        if self._n_workers <= 0 \
                or len(alive) / self._n_workers >= self._shed_threshold:
            return
        if not alive and not self._respawn_at:
            return  # fleet exhausted: dispatch fails pending WorkerLost
        cap = max(1, len(alive)) * self._max_concurrent
        if self._autoscaler is not None:
            # elastic fleets prefer GROWING over shedding: while the
            # autoscaler has headroom, hold the backlog up to what a
            # max-size fleet could absorb — shed is the valve of last
            # resort once even that capacity is oversubscribed
            cap = max(cap,
                      self._autoscaler.max_workers * self._max_concurrent)
        while len(self._pending) > cap:
            # lowest priority class first; latest arrival within a class
            victim = min(self._pending,
                         key=lambda e: (e[1].priority, -e[1].sid))
            self._pending.remove(victim)
            sess = victim[1]
            self.metrics.bump("sheds")
            sess._finish(error=AdmissionShed(
                f"session {sess.sid} shed: {len(alive)}/{self._n_workers} "
                f"workers alive (< serve_shed_threshold="
                f"{self._shed_threshold:g})"), status="shed")

    def _pick_worker_locked(self, sess: FrontDoorSession
                            ) -> Optional[WorkerHandle]:
        healthy = [w for w in self._workers.values()
                   if w.state == "healthy" and w.link is not None
                   and not w.retiring
                   and len(w.sessions) < self._max_concurrent]
        if not healthy:
            return None
        pin = self._pins.get(sess.tenant)
        if pin is not None:
            for w in healthy:
                if w.worker_id == pin:
                    return w
            pinned = self._workers.get(pin)
            if pinned is not None and pinned.state != "dead" \
                    and not pinned.retiring and pin not in self._broken:
                return None  # pinned worker alive but full/starting: wait
        w = self._placement.pick(healthy)
        if w is None:
            return None
        self._pins[sess.tenant] = w.worker_id
        return w

    def _enqueue_locked(self, not_before: float, sess: FrontDoorSession):
        """Queue ``sess`` for placement at or after ``not_before``; its
        ``serve.pending`` stage runs from here to its placement."""
        sess._queued_ns = time.perf_counter_ns()
        self._pending.append([not_before, sess])

    def _dispatch_locked(self, now: float):
        if self._shutdown_started:
            return
        # fleet exhausted?  No alive worker and none ever coming back —
        # a slot in "reconnecting" is a live worker behind a downed
        # LINK (its ladder or the partition grace decides its fate),
        # never grounds for failing pending sessions
        if not self._alive_workers() and not self._respawn_at \
                and not any(w.state == "reconnecting"
                            for w in self._workers.values()):
            for _nb, sess in self._pending:
                self.metrics.bump("worker_lost")
                sess._finish(error=WorkerLost(
                    f"session {sess.sid}: no healthy workers and the "
                    f"respawn circuit breaker is open"))
            self._pending = []
            return
        still: List[list] = []
        for entry in sorted(self._pending,
                            key=lambda e: (-e[1].priority, e[1].sid)):
            not_before, sess = entry
            if sess._done.is_set():
                continue
            if now < not_before:
                still.append(entry)
                continue
            w = self._pick_worker_locked(sess)
            if w is None:
                still.append(entry)
                continue
            # write-ahead: placement is durable before the send and
            # the in-memory transition.  If the send then fails, the
            # journal over-claims a placement that never landed — safe
            # direction: adoption re-sends placed-but-unacked sessions
            # and the worker's sid dedup absorbs the duplicate.
            sess._stage("serve.pending", profiler.note(
                "serve.pending", sess.sid, sess._queued_ns,
                time.perf_counter_ns()))
            sess._stage("serve.journal", self._jrec(
                "placed", sid=sess.sid, slot=w.worker_id, gen=w.gen))
            try:
                with profiler.span("serve.send", sid=sess.sid,
                                   sink=sess._stage):
                    w.link.send({
                        "op": "submit", "sid": sess.sid,
                        "kind": sess.kind, "params": sess.params,
                        "tenant": str(sess.tenant),
                        "priority": sess.priority,
                        "est_bytes": sess.est_bytes,
                        "timeout_s": sess.timeout_s,
                        "snapshot": sess.snapshot,
                    })
            except OSError:
                # worker dying under us: leave it pending, the monitor's
                # loss protocol will re-route it
                sess._queued_ns = time.perf_counter_ns()
                still.append(entry)
                continue
            w.sessions[sess.sid] = sess
            sess.worker_id = w.worker_id
            sess.status = "placed"
        self._pending = still
