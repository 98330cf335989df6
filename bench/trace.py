"""The profiler trace of a run, reduced to the device's busy and idle time,
the collectives' exposed time, and a breakdown.

``reduce`` works on plain events, so a test can hand it a trace built by
hand; ``load`` reads the events of a trace that ``capture`` recorded."""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re

Event = collections.namedtuple("Event", "plane line name start dur")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv|collective-broadcast)")
TOP = 10


@contextlib.contextmanager
def capture(logdir: str):
    """Record a profiler trace of the body into ``logdir``; host spans are
    kept, Python function calls are not."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts):
        yield


def load(logdir: str) -> list:
    """Every event of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace under {logdir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def gaps(busy, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_label(name: str) -> str:
    """An XLA op's trace name is its HLO instruction; keep the instruction's
    name and its result type without layouts."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"\{[^}]*\}", "", rest.split(" ")[0])
    return f"{head.lstrip('%')} {kind[:48]}".strip()


def leaves(evs) -> list:
    """The ops that contain no other op: a loop or call on the ops line
    encloses the ops of its body."""
    evs = sorted(evs, key=lambda e: (e.start, -e.dur))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start + nxt.dur > e.start + e.dur]


def reduce(events) -> dict:
    """Busy and idle time of each device within the host's window span, the
    time in which a collective runs on a device and no other op does, the
    device ops that took most time (mean per device, innermost ops only),
    and the longest idle gaps, each named by the harness span open on the
    host during it."""
    spans = [e for e in events
             if e.plane == HOST_PLANE and e.name.startswith(SPAN_PREFIX)]
    devices = collections.defaultdict(list)
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE:
            devices[int(m.group(1))].append(e)
    if not devices:
        raise ValueError("the trace holds no device op")
    win = [e for e in spans if e.name == WINDOW_SPAN]
    if win:
        lo, hi = win[0].start, win[0].start + win[0].dur
    else:
        lo = min(e.start for evs in devices.values() for e in evs)
        hi = max(e.start + e.dur for evs in devices.values() for e in evs)
    window = hi - lo
    per_device, op_time = {}, collections.Counter()
    all_gaps = []
    named = [(e.start, e.start + e.dur, e.name) for e in spans
             if e.name != WINDOW_SPAN]
    for dev, evs in sorted(devices.items()):
        busy = union(clip([(e.start, e.start + e.dur) for e in evs], lo, hi))
        evs = leaves(evs)
        coll = union(clip([(e.start, e.start + e.dur) for e in evs
                           if COLLECTIVE.match(e.name)], lo, hi))
        comp = union(clip([(e.start, e.start + e.dur) for e in evs
                           if not COLLECTIVE.match(e.name)], lo, hi))
        for e in evs:
            d = min(e.start + e.dur, hi) - max(e.start, lo)
            if d > 0:
                op_time[op_label(e.name)] += d / len(devices)
        per_device[dev] = {
            "busy_ns": length(busy),
            "collective_exposed_ns": length(coll) - overlap(coll, comp)}
        if dev == min(devices):
            for s, e in gaps(busy, lo, hi):
                best, name = 0.0, "no span"
                for a, b, n in named:
                    o = min(b, e) - max(a, s)
                    if o > best:
                        best, name = o, n
                all_gaps.append((name, (e - s) / 1e9))
    all_gaps.sort(key=lambda g: -g[1])
    busy_s = [d["busy_ns"] / 1e9 for d in per_device.values()]
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "idle_share": {dev: 1.0 - d["busy_ns"] / window
                       for dev, d in per_device.items()},
        "collective_exposed_share": {
            dev: d["collective_exposed_ns"] / window
            for dev, d in per_device.items()},
        "device_ops": [[n, t / 1e9] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [list(g) for g in all_gaps[:TOP]],
    }
