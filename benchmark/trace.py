"""The reduction from a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, time by operation, time in
collectives, and each idle gap by what the host was doing in it.

``start``/``stop`` run in the process that holds the chip.  The reduction
reads the file with ``jax.profiler.ProfileData`` and nothing else, so the
process that reduces a trace needs no chip.  ``python benchmark/trace.py FILE``
prints a trace's planes, lines and heaviest events, for a look by hand;
``selfcheck()`` reduces the small recorded trace beside this file to the
numbers recorded with it.
"""

import glob
import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# matched against an operation's own name, with "_" read as "-": XLA names an
# operation after its opcode (all-to-all.3) or after JAX's (all_to_all.41)
COLLECTIVE = re.compile(
    r"all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|collective-broadcast|ppermute|psum", re.I)


def is_collective(name):
    head = name.partition(" = ")[0]
    return bool(COLLECTIVE.search(head.replace("_", "-")))
NO_SPAN = "no bench span (between queries)"


def start(log_dir):
    """Starts the profiler in this process, which has to hold the chip."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the benchmark's own annotations are enough
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop():
    import jax

    jax.profiler.stop_trace()


def find(log_dir):
    """The ``.xplane.pb`` files a traced window left under ``log_dir``."""
    return sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))


def load(path):
    """[(plane, [(line, [(name, start_ns, duration_ns), ...]), ...]), ...]"""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [(pl.name, [(ln.name, [(e.name, float(e.start_ns),
                                   float(e.duration_ns)) for e in ln.events])
                       for ln in pl.lines]) for pl in pd.planes]


def union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def short_name(name):
    """An HLO event's name without its number and operands, with its result
    type: ``%fusion.449 = f32[8,101]{...} fusion(...)`` -> ``fusion
    f32[8,101]``, so that the unrolled copies of one operation add up and a
    name survives a renumbering."""
    head, sep, rest = name.partition(" = ")
    stem = re.sub(r"\.\d+", "", head.lstrip("%"))
    if not sep:
        return stem[:80]
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return (stem + (" " + m.group(1) if m else ""))[:80]


def self_times(events):
    """Seconds by event name with each event's children taken out of it:
    on one line an event that lies inside another is its child (a ``while``
    encloses the operations of its body)."""
    out = {}
    stack = []   # [name, end, child_ns, dur]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, child, dur = stack.pop()
            out[name] = out.get(name, 0.0) + max(dur - child, 0.0) / 1e9
            if stack:
                stack[-1][2] += dur

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        stack.append([name, s + d, 0.0, d])
    close(float("inf"))
    return out


def reduce_planes(planes, chips):
    """The numbers of one traced window.  Times are seconds."""
    devices, other = {}, {}
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        ops = [evs for lname, evs in lines if lname == OPS_LINE]
        if ops:
            devices[int(m.group(1))] = [e for evs in ops for e in evs]
            other[int(m.group(1))] = [e for lname, evs in lines
                                      if lname != OPS_LINE for e in evs]
    devices = {d: evs for d, evs in devices.items() if evs}
    if not devices:
        raise ValueError(f"no '{OPS_LINE}' line with events on a "
                         f"/device:TPU:<n> plane; planes: "
                         f"{[p for p, _ in planes]}")
    spans = [e for pname, lines in planes if pname.startswith("/host:")
             for _l, evs in lines for e in evs if e[0].startswith("bench.")]
    starts = [s for evs in devices.values() for _n, s, _d in evs] \
        + [s for _n, s, _d in spans]
    ends = [s + d for evs in devices.values() for _n, s, d in evs] \
        + [s + d for _n, s, d in spans]
    w0, w1 = min(starts), max(ends)
    busy, merged = {}, {}
    for d, evs in devices.items():
        merged[d] = union([(s, s + dur) for _n, s, dur in evs])
        busy[d] = sum(e - s for s, e in merged[d]) / 1e9
    used = sorted(busy, key=busy.get, reverse=True)[:chips]
    ops, coll = {}, 0.0
    for d in used:
        for name, sec in self_times(devices[d]).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + sec / len(used)
            if is_collective(name):
                coll += sec / len(used)
    if not coll:
        # collectives that run beside the operations (a line of their own):
        # the time during which one was in flight
        for d in used:
            coll += sum(e - s for s, e in union(
                [(s, s + dur) for n, s, dur in other[d]
                 if is_collective(n)])) / 1e9 / len(used)
    # idle gaps of the busiest device, each by the bench span that covers
    # most of it
    full = used[0]
    edges = [w0] + [x for iv in merged[full] for x in iv] + [w1]
    gaps = {}
    span_iv = sorted((s, s + d, n) for n, s, d in spans)
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, cover = NO_SPAN, 0.0
        for s, e, n in span_iv:
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov > cover:
                best, cover = n, ov
        gaps[best] = gaps.get(best, 0.0) + (g1 - g0) / 1e9
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy[d] for d in used) / len(used),
            "busy_by_device": {str(d): busy[d] for d in sorted(busy)},
            "busy_fullest_s": busy[full],
            "collective_s": coll,
            "device_ops": top(ops), "idle_gaps": top(gaps),
            "bench_spans": len(spans)}


def reduce_file(path, chips):
    return reduce_planes(load(path), chips)


def selfcheck():
    """Reduces the recorded trace to the numbers recorded beside it."""
    d = os.path.join(HERE, "selfcheck")
    with open(os.path.join(d, "expected.json")) as f:
        want = json.load(f)
    got = reduce_file(os.path.join(d, want["file"]), int(want["chips"]))
    bad = []
    for key, w in want["numbers"].items():
        g = got[key]
        if isinstance(w, list):
            g = g[:len(w)]
            ok = [a[0] for a in g] == [a[0] for a in w] and all(
                abs(a[1] - b[1]) <= 1e-9 + 1e-6 * abs(b[1])
                for a, b in zip(g, w))
        else:
            ok = abs(g - w) <= 1e-9 + 1e-6 * abs(w)
        if not ok:
            bad.append((key, g, w))
    # and the arithmetic itself, on a trace written out by hand
    toy = [("/device:TPU:0", [(OPS_LINE, [
        ("while", 0.0, 10e9), ("fusion.1", 1e9, 2e9), ("all-to-all.2", 4e9,
                                                       1e9),
        ("copy.3", 12e9, 2e9)])]),
        ("/host:CPU", [("python", [("bench.execute", 0.0, 10.5e9),
                                   ("bench.result", 10.5e9, 1e9),
                                   ("bench.lookup", 15e9, 1e9)])])]
    t = reduce_planes(toy, 1)
    toy_want = {"window_s": 16.0, "busy_s": 12.0, "collective_s": 1.0}
    for k, w in toy_want.items():
        if abs(t[k] - w) > 1e-9:
            bad.append(("toy." + k, t[k], w))
    if dict(map(tuple, t["device_ops"])) != {
            "while": 7.0, "fusion": 2.0, "all-to-all": 1.0, "copy": 2.0}:
        bad.append(("toy.device_ops", t["device_ops"], None))
    if dict(map(tuple, t["idle_gaps"])) != {
            "bench.result": 2.0, "bench.lookup": 2.0}:
        bad.append(("toy.idle_gaps", t["idle_gaps"], None))
    for b in bad:
        print("selfcheck: differs:", *b, file=sys.stderr)
    print(f"selfcheck: {'FAILED' if bad else 'ok'}: {want['file']} reduces "
          f"to window_s={got['window_s']!r} busy_s={got['busy_s']!r}",
          file=sys.stderr)
    return 1 if bad else 0


def dump(path, top=12):
    for pname, lines in load(path):
        print("PLANE", pname)
        for lname, evs in lines:
            by = {}
            for n, _s, d in evs:
                by[n] = by.get(n, 0.0) + d / 1e9
            tops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
            span = (min(e[1] for e in evs), max(e[1] + e[2] for e in evs)) \
                if evs else None
            print(f"  LINE {lname!r}: {len(evs)} events, span {span}")
            for n, s in tops:
                print(f"      {s:12.6f} s  {n[:100]}")


if __name__ == "__main__":
    dump(sys.argv[1])
    if len(sys.argv) > 2:
        print(json.dumps(reduce_file(sys.argv[1], int(sys.argv[2])),
                         indent=1)[:6000])
