"""Always-attachable profiler with the reference's lifecycle + writer API.

Reference: the CUPTI-based profiler (``Profiler.java:37-124``: init/start/
stop/shutdown with a ``DataWriter`` sink; ``profiler_serializer.cpp`` emits
size-prefixed flatbuffer records; ``spark_rapids_profile_converter`` turns
captures into JSON offline).  The TPU equivalent wraps the XLA profiler
(xplane/trace collection via ``jax.profiler``):

* :class:`Profiler` — ``init(writer)`` / ``start()`` / ``stop()`` /
  ``shutdown()``.  Each start/stop cycle collects a trace and streams it to
  the writer as size-prefixed framed chunks, so a Spark executor can route
  profiles to distributed storage exactly like the reference's
  ``DataWriter`` path.
* :func:`convert_profile` — the offline converter: reads a captured
  stream back into per-event records (kernel/op name, start, duration,
  the program's ``scope`` on device operations, the query's ``sid`` on
  host spans); :func:`device_time_by_scope` adds those up, and
  :func:`idle_by_span` names each idle gap of the device after the host
  span over it, ring spans of any process put on the trace's clock by a
  :func:`clock_anchor`.
* :func:`span` / :func:`note` / :func:`scope` — the one tracer inside the
  program.  A span is a host interval under a query id: always recorded in
  a bounded per-process ring (:func:`spans`, :func:`stage_totals`) and,
  while a profiler session is on, written into the trace with its ``sid``
  as a stat.  A scope names the device operations lowered inside it
  (``jax.named_scope``): op metadata, free at run time.

Frame format: ``b"SPTPUPRF" u32(version) [u32(len) bytes]*`` — the same
size-prefixed-records idea as ``profiler.fbs`` (``ProfileHeader`` magic +
``ActivityRecords``), carrying trace files instead of CUPTI activities.
When a fault-injection schedule fired during the window, one synthetic
``faultinj.fired.json`` frame carries :func:`faultinj.fired_log` so the
capture explains its own anomalies.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import gzip
import io
import json
import os
import re
import shutil
import struct
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

MAGIC = b"SPTPUPRF"
VERSION = 1


class ProfilerError(RuntimeError):
    pass


class Profiler:
    """Process-wide profiler facade (mirrors Profiler.java's static API)."""

    _lock = threading.Lock()
    _writer: Optional[Callable[[bytes], None]] = None
    _dir: Optional[str] = None
    _running = False
    _initialized = False
    _wrote_header = False

    @classmethod
    def init(cls, data_writer: Callable[[bytes], None]):
        """Install the sink; profiling stays off until :meth:`start`."""
        with cls._lock:
            if cls._initialized:
                raise ProfilerError("profiler already initialized")
            cls._writer = data_writer
            cls._dir = tempfile.mkdtemp(prefix="sptpu_prof_")
            cls._initialized = True
            cls._wrote_header = False

    @classmethod
    def start(cls):
        """Begin collecting (cuProfilerStart equivalent)."""
        with cls._lock:
            if not cls._initialized:
                raise ProfilerError("profiler not initialized")
            if cls._running:
                return
            jax.profiler.start_trace(cls._dir)
            cls._running = True

    @classmethod
    def stop(cls):
        """Stop collecting and flush the capture to the writer."""
        with cls._lock:
            if not cls._initialized or not cls._running:
                return
            jax.profiler.stop_trace()
            cls._running = False
            cls._flush_locked()

    @classmethod
    def shutdown(cls):
        """Stop if needed, flush, and release the sink."""
        with cls._lock:
            if not cls._initialized:
                return
            if cls._running:
                jax.profiler.stop_trace()
                cls._running = False
                cls._flush_locked()
            shutil.rmtree(cls._dir, ignore_errors=True)
            cls._writer = None
            cls._dir = None
            cls._initialized = False

    # -- internals -------------------------------------------------------
    @classmethod
    def _flush_locked(cls):
        buf = io.BytesIO()
        if not cls._wrote_header:
            buf.write(MAGIC)
            buf.write(struct.pack("<I", VERSION))
            cls._wrote_header = True
        for path in sorted(
            glob.glob(os.path.join(cls._dir, "**", "*"), recursive=True)
        ):
            if not os.path.isfile(path):
                continue
            name = os.path.relpath(path, cls._dir).encode()
            with open(path, "rb") as f:
                payload = f.read()
            rec = struct.pack("<I", len(name)) + name + payload
            buf.write(struct.pack("<I", len(rec)))
            buf.write(rec)
            os.remove(path)
        # fault-injection trace rides the capture: when a schedule fired
        # inside this collection window the (name, fault, occurrence)
        # log lands as a synthetic frame, so a profile of a chaos run is
        # self-describing about which faults shaped its timeline
        from . import faultinj

        fired = faultinj.fired_log()
        if fired:
            name = b"faultinj.fired.json"
            payload = json.dumps(fired).encode()
            rec = struct.pack("<I", len(name)) + name + payload
            buf.write(struct.pack("<I", len(rec)))
            buf.write(rec)
        data = buf.getvalue()
        if data:
            cls._writer(data)


# ---------------------------------------------------------------------------
# the tracer: host spans under a query id, device scopes
# ---------------------------------------------------------------------------

RING_SPANS = 131072

Span = collections.namedtuple("Span", "name sid parent t0_ns t1_ns")

_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_totals: Dict[str, list] = {}   # name -> [count, sum_ns, max_ns]
_ring_lock = threading.Lock()
_tls = threading.local()


def _record(name, sid, parent, t0_ns, t1_ns):
    dur = t1_ns - t0_ns
    with _ring_lock:
        _ring.append((name, sid, parent, t0_ns, t1_ns))
        t = _totals.get(name)
        if t is None:
            _totals[name] = [1, dur, dur]
        else:
            t[0] += 1
            t[1] += dur
            if dur > t[2]:
                t[2] = dur


class span:
    """``with span("plan.dispatch", sid=7, rec="x") as sp:`` — one host
    interval of the program, the NVTX-range analogue (reference compiles
    nvtx3 ranges into kernels for nsys, SURVEY §5).

    The interval is read off ``time.perf_counter_ns()`` and appended to
    the process's ring as ``(name, sid, parent, t0_ns, t1_ns)``; ``parent``
    is the name of the span open around it on this thread, and a span
    without a ``sid`` takes its parent's, so everything a query runs nests
    under the id its outermost span was given.  The same interval is a
    ``TraceAnnotation`` carrying ``sid`` and ``attrs`` as stats, which the
    XLA profiler records only while a session is on.  After exit ``sp.ms``
    is the duration, and ``sink(name, ms)`` has been called with it if one
    was given (a session adding the stage to its timeline)."""

    __slots__ = ("name", "sid", "parent", "t0_ns", "t1_ns", "_attrs",
                 "_ann", "_sink")

    def __init__(self, name: str, sid=None, sink=None, **attrs):
        self.name = name
        self.sid = sid
        self.parent = None
        self._attrs = attrs
        self._sink = sink

    def __enter__(self):
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
        if stack:
            top = stack[-1]
            self.parent = top.name
            if self.sid is None:
                self.sid = top.sid
        stack.append(self)
        attrs = self._attrs
        if self.sid is not None:
            attrs = dict(attrs, sid=self.sid)
        self._ann = TraceAnnotation(self.name, **attrs)
        self._ann.__enter__()
        self.t0_ns = self.t1_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _tls.stack.pop()
        _record(self.name, self.sid, self.parent, self.t0_ns, self.t1_ns)
        if self._sink is not None:
            self._sink(self.name, self.ms)
        return False

    @property
    def ms(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e6


def note(name: str, sid, t0_ns: int, t1_ns: int) -> float:
    """Record an interval no ``with`` can bracket (a queue wait that starts
    in one thread and ends in another), stamped by the caller with
    ``time.perf_counter_ns()``.  Ring and totals only: a finished interval
    cannot be written into a running trace.  Returns its milliseconds."""
    _record(name, sid, None, int(t0_ns), int(t1_ns))
    return (t1_ns - t0_ns) / 1e6


def spans(since_ns: int = 0) -> List[Span]:
    """Copies of the ring's spans that started at or after ``since_ns``
    (``time.perf_counter_ns()``), oldest first.  The ring keeps the last
    ``RING_SPANS``."""
    with _ring_lock:
        rows = list(_ring)
    return [Span(*r) for r in rows if r[3] >= since_ns]


def span_columns(since_ns: int = 0) -> dict:
    """:func:`spans` as columns, for a process that hands its spans to
    another over a JSON wire: ``names`` once each, then a span a place in
    ``name`` and ``parent`` (indices into ``names``; -1 for no parent),
    ``sid``, ``t0_ns`` and ``t1_ns``.  :func:`spans_from_columns` reads them
    back."""
    index: Dict[str, int] = {}
    cols: dict = {"names": [], "name": [], "parent": [], "sid": [],
                  "t0_ns": [], "t1_ns": []}

    def at(name):
        if name is None:
            return -1
        if name not in index:
            index[name] = len(cols["names"])
            cols["names"].append(name)
        return index[name]

    for s in spans(since_ns):
        cols["name"].append(at(s.name))
        cols["parent"].append(at(s.parent))
        cols["sid"].append(s.sid)
        cols["t0_ns"].append(s.t0_ns)
        cols["t1_ns"].append(s.t1_ns)
    return cols


def spans_from_columns(cols: dict) -> List[Span]:
    """The :class:`Span` records of :func:`span_columns`' columns."""
    names = cols["names"]
    return [Span(names[n], sid, names[p] if p >= 0 else None, t0, t1)
            for n, p, sid, t0, t1 in zip(cols["name"], cols["parent"],
                                         cols["sid"], cols["t0_ns"],
                                         cols["t1_ns"])]


def clock_anchor():
    """``(perf_counter_ns, time_ns)`` read back to back: the ring's clock
    against the trace's.

    The XLA profiler stamps host events with CLOCK_REALTIME (tsl's
    ``EnvTime::NowNanos``, which is ``time.time_ns()``) and writes them
    relative to the session's start, the ``profile_start_time`` stat of
    the trace's ``Task Environment`` plane (:func:`trace_start_ns`); the
    runtime puts the device's events on the same clock.  A CPU trace
    bears it out: a span's event lies 2-3 us before ``time.time_ns()``
    read inside it.  The ring's ``time.perf_counter_ns()`` is
    CLOCK_MONOTONIC, one clock for every process of the machine, so one
    anchor places every process's ring spans on a trace
    (:func:`on_trace_clock`)."""
    return time.perf_counter_ns(), time.time_ns()


def stage_totals() -> Dict[str, dict]:
    """``{name: {count, sum_ms, max_ms}}`` over every span and note since
    the process started (not only those still in the ring).
    ``stage_totals()["plan.dispatch"]["count"]`` is the number of compiled
    plans launched."""
    with _ring_lock:
        return {n: {"count": c, "sum_ms": s / 1e6, "max_ms": m / 1e6}
                for n, (c, s, m) in _totals.items()}


_SCOPE_NAME = re.compile(r"^[A-Za-z0-9_]+(\.[A-Za-z0-9_]+)+$")


def scope(name: str):
    """``with scope("join.dense_probe"):`` names the device operations
    lowered inside it.  Names are ``<layer>.<what>`` (letters, digits,
    ``_``, ``.``): the dot is how :func:`device_time_by_scope` tells the
    program's scopes from JAX's own path components."""
    if not _SCOPE_NAME.match(name):
        raise ValueError(f"scope name {name!r}: want <layer>.<what> of "
                         "letters, digits, '_' and '.'")
    return jax.named_scope(name)


@contextlib.contextmanager
def detached():
    """Lower what is inside under an empty scope path, and yield ``rejoin``,
    a context that re-enters the caller's path.  A loop whose body holds
    operations of more than one plan node is built under this (the loop's
    own path is put before every operation of its body), so each
    operation's path still starts at the one node it belongs to."""
    from jax._src import source_info_util as siu

    outer = siu.current_name_stack()
    with siu.reset_name_stack():
        yield lambda: siu.set_name_stack(outer)


def scope_name(text) -> str:
    """A string made fit for a scope name's component (a column or table
    name from a plan): anything but letters, digits and ``_`` becomes
    ``_``."""
    return re.sub(r"[^A-Za-z0-9_]", "_", str(text)) or "_"


class FileWriter:
    """A DataWriter that appends frames to one capture file."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "ab")

    def __call__(self, data: bytes):
        self._f.write(data)
        self._f.flush()

    def close(self):
        self._f.close()


def _iter_frames(data: bytes):
    off = 0
    if data[:8] == MAGIC:
        off = 12
    while off + 4 <= len(data):
        (ln,) = struct.unpack_from("<I", data, off)
        off += 4
        rec = data[off: off + ln]
        off += ln
        (nlen,) = struct.unpack_from("<I", rec, 0)
        name = rec[4: 4 + nlen].decode()
        payload = rec[4 + nlen:]
        yield name, payload


# ---------------------------------------------------------------------------
# xplane.pb decoding
# ---------------------------------------------------------------------------
# Events (name, start, duration, their own stats: a span's ``sid``) are read
# with ``jax.profiler.ProfileData``.  What it does not expose is the stats of
# an event's METADATA, and that is where the TPU runtime puts what the
# compiler knew of a device operation: ``tf_op`` (the JAX op name with its
# named-scope path, ``jit(run)/plan.join.dim1/join.dense_probe/gather:``).
# So one minimal protobuf wire reader stays, for the metadata tables alone.
# Field numbers from tsl/profiler/protobuf/xplane.proto:
#   XSpace   { repeated XPlane planes = 1; }
#   XPlane   { string name=2; repeated XLine lines=3;
#              map<int64, XEventMetadata> event_metadata=4;
#              map<int64, XStatMetadata> stat_metadata=5; }
#   XEventMetadata { int64 id=1; string name=2; repeated XStat stats=5; }
#   XStatMetadata  { int64 id=1; string name=2; }
#   XStat    { int64 metadata_id=1; string str_value=5; uint64 ref_value=7; }
# The device planes ("/device:TPU:0") carry per-kernel events — the role of
# the reference's CUPTI activity records (profiler_serializer.cpp:222-280).

OPS_LINE = "XLA Ops"
NO_SCOPE = "(none)"


def _pb_fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes."""
    off = 0
    n = len(buf)
    while off < n:
        key = 0
        shift = 0
        while True:
            b = buf[off]
            off += 1
            key |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        field, wt = key >> 3, key & 7
        if wt == 0:  # varint
            v = 0
            shift = 0
            while True:
                b = buf[off]
                off += 1
                v |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            yield field, wt, v
        elif wt == 2:  # length-delimited
            ln = 0
            shift = 0
            while True:
                b = buf[off]
                off += 1
                ln |= (b & 0x7F) << shift
                if not b & 0x80:
                    break
                shift += 7
            yield field, wt, buf[off: off + ln]
            off += ln
        elif wt == 5:  # fixed32
            yield field, wt, buf[off: off + 4]
            off += 4
        elif wt == 1:  # fixed64
            yield field, wt, buf[off: off + 8]
            off += 8
        else:
            raise ProfilerError(f"unsupported protobuf wire type {wt}")


def _op_names(payload: bytes) -> Dict[str, Dict[str, str]]:
    """XSpace bytes -> ``{plane: {event name: tf_op}}`` from the planes'
    event-metadata stats."""
    out: Dict[str, Dict[str, str]] = {}
    for f, wt, plane in _pb_fields(payload):
        if f != 1 or wt != 2:
            continue
        pname, stat_names, metas = "", {}, []
        for pf, pwt, pv in _pb_fields(plane):
            if pwt != 2:
                continue
            if pf == 2:
                pname = pv.decode("utf-8", "replace")
            elif pf == 4:
                metas.append(pv)
            elif pf == 5:
                # map entry { int64 key=1; XStatMetadata value=2; }
                for mf, mwt, mv in _pb_fields(pv):
                    if mf == 2 and mwt == 2:
                        fields = {sf: sv for sf, _w, sv in _pb_fields(mv)}
                        stat_names[fields.get(1, 0)] = \
                            fields.get(2, b"").decode("utf-8", "replace")
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        if not tf_op:
            continue
        names = {}
        for entry in metas:
            for mf, mwt, mv in _pb_fields(entry):
                if mf != 2 or mwt != 2:
                    continue
                ename, op = "", None
                for ef, ewt, ev in _pb_fields(mv):
                    if ef == 2 and ewt == 2:
                        ename = ev.decode("utf-8", "replace")
                    elif ef == 5 and ewt == 2:
                        st = {xf: xv for xf, _w, xv in _pb_fields(ev)}
                        if st.get(1) in tf_op:
                            op = (st[5].decode("utf-8", "replace")
                                  if 5 in st else stat_names.get(st.get(7)))
                if op is not None:
                    names[ename] = op
        if names:
            out[pname] = names
    return out


def scope_path(op_name: str) -> str:
    """The program's scopes in a JAX op name, outermost first, joined by
    ``/``: ``jit(run)/plan.join.dim1/join.dense_probe/gather:`` ->
    ``plan.join.dim1/join.dense_probe``; ``""`` when it has none."""
    return "/".join(c for c in re.split(r"[/:]", op_name or "")
                    if _SCOPE_NAME.match(c))


def convert_xplane(payload: bytes) -> List[dict]:
    """One ``.xplane.pb``'s bytes -> flat event records ``{name, ts_us,
    dur_us, plane, line}``; a host span also carries its ``sid``, and every
    operation of a device plane's ``XLA Ops`` line its ``scope``
    (:func:`scope_path` of the op name; ``""`` for an operation lowered
    outside every scope)."""
    from jax.profiler import ProfileData

    op_names = _op_names(payload)
    events: List[dict] = []
    for plane in ProfileData.from_serialized_xspace(payload).planes:
        ops = op_names.get(plane.name, {})
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            scoped = device and line.name == OPS_LINE
            for ev in line.events:
                rec = {"name": ev.name, "ts_us": ev.start_ns / 1e3,
                       "dur_us": ev.duration_ns / 1e3,
                       "plane": plane.name, "line": line.name}
                if scoped:
                    rec["scope"] = scope_path(ops.get(ev.name, ""))
                elif not device:
                    for k, v in ev.stats:
                        if k == "sid":
                            rec["sid"] = int(v) if str(v).isdigit() else v
                events.append(rec)
    return events


def device_time_by_scope(events: List[dict], depth: int = 2
                         ) -> Dict[str, float]:
    """Device seconds by scope prefix (the first ``depth`` scopes of each
    operation's path), over the records of :func:`convert_profile` that
    carry a ``scope``.  Self-time: an operation that encloses others on
    its plane (a ``while`` and its body) counts without them.  Operations
    outside every scope add up under ``"(none)"``."""
    out: Dict[str, float] = {}
    by_plane: Dict[str, list] = {}
    for e in events:
        if "scope" in e:
            by_plane.setdefault(e["plane"], []).append(e)
    for evs in by_plane.values():
        stack: list = []   # [key, end_us, child_us, dur_us]

        def close(upto):
            while stack and stack[-1][1] <= upto:
                key, _end, child, dur = stack.pop()
                out[key] = out.get(key, 0.0) + max(dur - child, 0.0) / 1e6
                if stack:
                    stack[-1][2] += dur

        for e in sorted(evs, key=lambda e: (e["ts_us"], -e["dur_us"])):
            close(e["ts_us"])
            key = "/".join(e["scope"].split("/")[:depth]) or NO_SCOPE
            stack.append([key, e["ts_us"] + e["dur_us"], 0.0, e["dur_us"]])
        close(float("inf"))
    return out


def trace_start_ns(payload: bytes) -> Optional[int]:
    """The ``time.time_ns()`` at which the session of one ``.xplane.pb``
    started: its events' times are counted from it (:func:`clock_anchor`).
    ``None`` where the trace does not say."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_serialized_xspace(payload).planes:
        if plane.name == "Task Environment":
            for k, v in plane.stats:
                if k == "profile_start_time":
                    return int(v)
    return None


RING_PLANE = "ring"


def on_trace_clock(ring: List[Span], anchor, start_ns: int) -> List[dict]:
    """Ring spans (:func:`spans`, of any process of the machine) as records
    of :func:`convert_xplane` on a trace's clock: ``anchor`` from
    :func:`clock_anchor`, ``start_ns`` the trace's :func:`trace_start_ns`.
    Their plane is ``RING_PLANE``."""
    off = anchor[1] - anchor[0] - start_ns
    return [{"name": s.name, "ts_us": (s.t0_ns + off) / 1e3,
             "dur_us": (s.t1_ns - s.t0_ns) / 1e3, "plane": RING_PLANE,
             "line": "", "sid": s.sid} for s in ring]


# the layers whose host spans name the device's idle gaps: the program's own
# (``span``) and the benchmark's annotations around its calls into it
SPAN_LAYERS = ("bench", "plan", "serve", "worker", "shuffle")
NO_SPAN = "(no span)"


def _union(intervals):
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_by_span(events: List[dict]) -> Dict[str, float]:
    """Idle seconds of the busiest device by what the host was doing: each
    gap between its operations (the records of :func:`convert_xplane` that
    carry a ``scope``), from the first operation or span of the window to
    the last, goes to the innermost span of ``SPAN_LAYERS`` that covers
    more than half of it, else to the one that covers most of it, else to
    ``NO_SPAN``.  Host spans in the trace bound the window; ring spans put
    on its clock (:func:`on_trace_clock`: the supervisor's ``serve.*``)
    only name gaps.  ``{}`` where no device operation was traced."""
    busy: Dict[str, list] = {}
    for e in events:
        if "scope" in e:
            busy.setdefault(e["plane"], []).append(
                (e["ts_us"], e["ts_us"] + e["dur_us"]))
    if not busy:
        return {}
    spans_ = [(e["ts_us"], e["ts_us"] + e["dur_us"], e["name"], e["plane"])
              for e in events if not e["plane"].startswith("/device:")
              and "." in e["name"] and e["name"].split(".")[0] in SPAN_LAYERS]
    bounds = [iv for ivs in busy.values() for iv in ivs] + [
        (s, e) for s, e, _n, plane in spans_ if plane != RING_PLANE]
    w0, w1 = min(s for s, _e in bounds), max(e for _s, e in bounds)
    merged = {p: _union(ivs) for p, ivs in busy.items()}
    full = max(merged, key=lambda p: sum(e - s for s, e in merged[p]))
    edges = [w0] + [x for iv in merged[full] for x in iv] + [w1]
    out: Dict[str, float] = {}
    spans_.sort()
    active: list = []
    i = 0
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        while i < len(spans_) and spans_[i][0] < g1:
            active.append(spans_[i])
            i += 1
        # gaps come in order: a span that ended before this one ends
        # before every later one too
        active = [sp for sp in active if sp[1] > g0]
        best, cover, inner = NO_SPAN, 0.0, None
        for s, e, name, _plane in active:
            ov = min(e, g1) - max(s, g0)
            if ov <= 0:
                continue
            if 2 * ov > g1 - g0 and (inner is None or e - s < inner[0]):
                inner = (e - s, name)
            if ov > cover:
                best, cover = name, ov
        name = inner[1] if inner else best
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e6
    return out


def convert_profile(capture_path: str) -> List[dict]:
    """Offline converter: capture stream -> flat event records.

    Equivalent role to ``spark_rapids_profile_converter`` (flatbuffer ->
    JSON).  Decodes BOTH artifact formats the XLA profiler produces:

    * ``*.trace.json.gz`` Chrome-trace -> {"name", "ts_us", "dur_us",
      "tid", "pid"} records;
    * ``*.xplane.pb`` XSpace protos -> :func:`convert_xplane` records
      ({"name", "ts_us", "dur_us", "plane", "line"}, ``sid`` on the
      program's host spans, ``scope`` on device operations), where device
      planes carry the per-kernel activity (the reference's CUPTI record
      role);
    * the synthetic ``faultinj.fired.json`` frame -> one
      ``faultinj:<kind>@<boundary>`` record per injection that fired in
      the window, carrying the injector's (seq, occurrence) clock.
    """
    with open(capture_path, "rb") as f:
        data = f.read()
    if data[:8] != MAGIC:
        raise ProfilerError(f"{capture_path}: not a SPTPUPRF capture")
    events: List[dict] = []
    for name, payload in _iter_frames(data):
        if name.endswith(".trace.json.gz"):
            doc = json.loads(gzip.decompress(payload))
            for ev in doc.get("traceEvents", []):
                if ev.get("ph") == "X" and "name" in ev:
                    events.append(
                        {
                            "name": ev["name"],
                            "ts_us": ev.get("ts", 0),
                            "dur_us": ev.get("dur", 0),
                            "pid": ev.get("pid"),
                            "tid": ev.get("tid"),
                        }
                    )
        elif name.endswith(".xplane.pb"):
            events.extend(convert_xplane(payload))
        elif name == "faultinj.fired.json":
            for e in json.loads(payload):
                events.append({
                    "name": (f"faultinj:{e.get('fault')}"
                             f"@{e.get('name')}"),
                    "ts_us": 0.0,
                    "dur_us": 0.0,
                    "fault": e.get("fault"),
                    "boundary": e.get("name"),
                    "occurrence": e.get("occurrence"),
                    "seq": e.get("seq"),
                })
    return events


def list_capture_files(capture_path: str) -> List[str]:
    """Names of the raw trace artifacts inside a capture (xplane etc.)."""
    with open(capture_path, "rb") as f:
        data = f.read()
    return [name for name, _ in _iter_frames(data)]
